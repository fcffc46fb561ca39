"""Records the small trace the reduction's test reads (run on the chip once):
three bursts of a jitted step under a TraceAnnotation, with sleeps between
them, so busy time, idle gaps and their host names are known by design."""
import glob
import json
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp

out = sys.argv[1]
os.makedirs(out, exist_ok=True)


@jax.jit
def small_step(x):
    for _ in range(4):
        x = jnp.tanh(x @ x) * 0.5
    return x


x = jnp.ones((1024, 1024), jnp.float32)
small_step(x).block_until_ready()
tmp = os.path.join(out, "raw")
jax.profiler.start_trace(tmp)
t0 = time.perf_counter()
for burst in range(3):
    with jax.profiler.TraceAnnotation(f"bench.burst{burst}"):
        for _ in range(5):
            x = small_step(x)
        x.block_until_ready()
    with jax.profiler.TraceAnnotation("bench.sleep"):
        time.sleep(0.05)
window = time.perf_counter() - t0
jax.profiler.stop_trace()
pb = glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb"))[0]
shutil.copy(pb, os.path.join(out, "small.xplane.pb"))
shutil.rmtree(tmp)
pd = jax.profiler.ProfileData.from_file(os.path.join(out, "small.xplane.pb"))
summary = {"window_s": window, "device": jax.devices()[0].device_kind,
           "planes": []}
for plane in pd.planes:
    lines = []
    for line in plane.lines:
        evs = list(line.events)
        names = {}
        for e in evs:
            names[e.name] = names.get(e.name, 0) + 1
        lines.append({"line": line.name, "events": len(evs),
                      "first": [[e.name, e.start_ns, e.duration_ns]
                                for e in evs[:6]],
                      "top_names": sorted(names.items(),
                                          key=lambda kv: -kv[1])[:12]})
    summary["planes"].append({"plane": plane.name, "lines": lines})
with open(os.path.join(out, "summary.json"), "w") as f:
    json.dump(summary, f, indent=1)
print(json.dumps(summary)[:20000])
print("SIZE", os.path.getsize(os.path.join(out, "small.xplane.pb")))
