#!/bin/bash
# usage (through the chip tool, from the checkout's root): measure_sets.sh <cell> <seconds> "<6 set seeds>" "<extra seeds>" <trace seed> "<control seeds>"
# one call, one cell: a cold run that fills the compile cache, the two sets of
# six (same seeds in both), extra seeds with a short window, a traced run,
# and the lower-precision control
W=$1; SECS=$2; SEEDS=$3; EXTRA=$4; TSEED=$5; CSEEDS=$6
OUT=chiprun_out/sets; mkdir -p $OUT
J=$OUT/$W.jsonl
run() { lim=$1; label=$2; set=$3; seed=$4; secs=$5; shift 5
  timeout $lim python3 benchmark/run.py --workload $W --seed $seed --seconds $secs "$@" > $OUT/$W.$label.out 2> $OUT/$W.$label.err
  rc=$?
  line=$(tail -n 1 $OUT/$W.$label.out); [ -z "$line" ] && line=null
  echo "{\"label\": \"$label\", \"set\": $set, \"seed\": $seed, \"rc\": $rc, \"result\": $line}" >> $J
  echo "$label rc=$rc $(echo $line | cut -c1-420)"; grep -v "^$" $OUT/$W.$label.err | tail -n 1 | cut -c1-300; }
run 1100 cold 0 4000000007 8 --trace 0
n=0; for s in $SEEDS; do n=$((n+1)); run 340 a$n 1 $s $SECS --trace 0; done
n=0; for s in $SEEDS; do n=$((n+1)); run 340 b$n 2 $s $SECS --trace 0; done
n=0; for s in $EXTRA; do n=$((n+1)); run 340 x$n 0 $s 8 --trace 0; done
run 340 trace 3 $TSEED $SECS --trace 1
grep "^spans\|^counters" $OUT/$W.trace.err | cut -c1-1500
n=0; for s in $CSEEDS; do n=$((n+1)); run 1100 c$n 4 $s 8 --trace 0 --control 1; done
