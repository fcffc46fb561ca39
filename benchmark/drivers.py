"""The window drivers a traffic mix can name, over the system under test.

``pass_loop``            whole passes over the mix's statements, serially
                         through ``Session.sql`` (the NDS power path), each
                         result fetched to the host.
``served_closed_loop``   ``clients`` JAX-free child processes, each a
                         ``FlightClient`` on its own connection walking its
                         cycle through the statements, against a
                         ``FrontDoorServer`` -> ``QueryService`` -> ``Session``
                         that lives in this process (which holds the chip).

From the program they take the system and its spans, counters and ticket
times; the clocks, the load and the arithmetic are the benchmark's own.
"""
from __future__ import annotations

import base64
import contextlib
import glob
import json
import math
import os
import shutil
import subprocess
import sys
import time

from benchmark import trace_reduce
from benchmark.compare import ipc_bytes, ipc_table

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

def percentile(values: list, q: float) -> float:
    """Nearest-rank percentile of all the values (q in (0, 100])."""
    ordered = sorted(values)
    return ordered[max(0, math.ceil(q / 100.0 * len(ordered)) - 1)]


def served_values(latencies: list, good: int, seconds: float) -> dict:
    """The served cell's end-to-end arithmetic: requests answered correctly
    over the window's seconds, and the median and 95th percentile of ALL
    the window's requests (a failed one counts as infinitely late)."""
    out = {"served_qps": good / seconds}
    if latencies:
        out["served_ms_p50"] = percentile(latencies, 50)
        out["served_ms_p95"] = percentile(latencies, 95)
    return out


class Window:
    """What a window measured. ``work`` counts passes or requests,
    ``answers`` holds each distinct answer once as (statement index, table,
    times returned)."""

    def __init__(self):
        self.attempted = 0
        self.failed = 0
        self.work = 0
        self.answers: list = []
        self.notes: list = []
        self._seen: dict = {}

    def keep(self, ui: int, table, payload: bytes | None = None) -> None:
        key = (ui, payload if payload is not None else ipc_bytes(table))
        n = self._seen.get(key)
        if n is None:
            self._seen[key] = len(self.answers)
            self.answers.append([ui, table, 1])
        else:
            self.answers[n][2] += 1


@contextlib.contextmanager
def annotate(name: str, on: bool):
    """A ``jax.profiler.TraceAnnotation`` in the traced run, nothing in the
    untraced one."""
    if not on:
        yield
        return
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


class TraceSlice:
    """The profiler over a short slice at the head of the window (the
    traced run only; an untraced run never starts it)."""

    def __init__(self, obs, slice_s: float):
        self.obs = obs
        self.slice_s = slice_s
        self.dir = os.path.join(ROOT, ".benchmark_data", "trace",
                                str(os.getpid()))
        self.t0 = None
        self.note = None

    def start(self) -> None:
        if not self.obs.trace:
            return
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.t0 = time.perf_counter()
        self.note = jax.profiler.TraceAnnotation(
            trace_reduce.SLICE_ANNOTATION)
        self.note.__enter__()

    def due(self) -> bool:
        return self.t0 is not None and \
            time.perf_counter() - self.t0 >= self.slice_s

    def stop(self, work_done: int) -> None:
        """End the slice after ``work_done`` passes or requests."""
        if self.t0 is None:
            return
        import jax
        self.note.__exit__(None, None, None)
        window_s = time.perf_counter() - self.t0
        self.t0 = None
        jax.profiler.stop_trace()
        self.obs.slice_work = work_done
        found = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if found:
            self.obs.trace_summary = trace_reduce.reduce_file(found[0],
                                                              window_s)
        shutil.rmtree(self.dir, ignore_errors=True)


def _compiles() -> int:
    """Whole-plan XLA compilations so far (the program's own counter)."""
    from nds_tpu.obs.metrics import METRICS
    return METRICS.snapshot().get("compiles", 0)


def _tracer_now_us() -> float:
    from nds_tpu.obs.trace import TRACER
    return (time.perf_counter() - TRACER._epoch) * 1e6


class _Driver:
    def __init__(self, config, mix, stmts, obs, control=False):
        self.config, self.mix, self.stmts, self.obs = config, mix, stmts, obs
        self.control = control
        self.session = None

    def _engine_config(self):
        from nds_tpu.config import (EngineConfig, apply_decimal,
                                    maybe_enable_compile_cache)
        from nds_tpu.obs.trace import TRACER
        maybe_enable_compile_cache()
        if self.obs.trace:
            TRACER.configure(enabled=True)
        engine = dict(self.config["engine"])
        if self.control and self.config["control"]["kind"] == "engine":
            engine.update(self.config["control"]["engine"])
        cfg = EngineConfig(**engine)
        if "decimal_physical" in engine:
            apply_decimal(cfg, None)
        return cfg

    def _open_window(self):
        from nds_tpu.obs.metrics import METRICS
        self._metrics0 = METRICS.snapshot()
        self._ts0 = _tracer_now_us()

    def _close_window(self):
        from nds_tpu.obs.metrics import METRICS
        from nds_tpu.obs.trace import TRACER
        self.obs.counters = METRICS.delta(self._metrics0)
        self.obs.window_ts = (self._ts0, _tracer_now_us())
        self.obs.spans = TRACER.events() if self.obs.trace else []


class PassLoop(_Driver):
    def load(self) -> None:
        from nds_tpu.engine import Session
        from nds_tpu.power import setup_tables
        self.session = Session(self._engine_config())
        setup_tables(self.session, self.config["_warehouse"], "parquet")

    def _run(self, st, window: Window | None, ui: int = 0):
        """One statement as the power runner runs it; its result on the
        host as an Arrow table."""
        from nds_tpu.engine.arrow_bridge import to_arrow
        with annotate(f"bench.sql:{st.unit}", self.obs.trace):
            result = self.session.sql(st.sql, label=st.unit)
        stats = dict(self.session.last_exec_stats)
        with annotate(f"bench.fetch:{st.unit}", self.obs.trace):
            table = to_arrow(result)
        problem = None
        if stats.get("mode") not in self.config["want_modes"]:
            problem = f"mode {stats.get('mode')!r}"
        elif stats.get("nojit_reason") or stats.get("fallback_reasons") \
                or self.session.last_fallbacks:
            problem = "left the device: " + str(
                stats.get("nojit_reason") or stats.get("fallback_reasons")
                or self.session.last_fallbacks)
        elif "streaming" in self.config["want_modes"] and \
                not stats.get("bytes_uploaded"):
            problem = "streamed no bytes"
        if window is not None:
            window.attempted += 1
            if problem:
                window.failed += 1
                window.notes.append(f"{st.unit}: {problem}")
            else:
                self._returned.append((ui, table))
        return problem

    def warm(self) -> None:
        """Record every statement, compile all recorded programs together
        (the power runner's cold start), then whole passes until one runs
        every statement in the wanted mode with no compilation."""
        t0 = time.monotonic()
        for st in self.stmts:
            self._run(st, None)
        self.obs.clocks["first_pass_s"] = time.monotonic() - t0
        if "compiled" in self.config["want_modes"]:
            self.session._jax_executor().precompile_parallel()
        for _ in range(4):
            before = _compiles()
            problems = [p for p in (self._run(st, None)
                                    for st in self.stmts) if p]
            if not problems and _compiles() == before:
                return
        raise SystemExit(f"benchmark: warm-up never reached the steady "
                         f"state: {problems}")

    def window(self, seconds: float) -> Window:
        w = Window()
        self._returned: list = []
        tr = TraceSlice(self.obs, min(self.mix.get("trace_slice_s", 4.0),
                                      seconds / 2))
        self._open_window()
        tr.start()
        t0 = time.perf_counter()
        paused = 0.0            # writing the trace out is no pass's time
        while True:
            began = time.perf_counter()
            with annotate("bench.pass", self.obs.trace):
                for ui, st in enumerate(self.stmts):
                    self._run(st, w, ui)
            ended = time.perf_counter()
            w.work += 1
            if tr.due():
                tr.stop(w.work)
                paused += time.perf_counter() - ended
            # no pass starts that would end after the window does
            if ended - t0 - paused + (ended - began) > seconds:
                break
        tr.stop(w.work)             # a window shorter than the slice
        self._close_window()
        self._window_s = ended - t0 - paused
        for ui, table in self._returned:
            w.keep(ui, table)
        return w

    def end_to_end(self, w: Window, wrong: int) -> dict:
        """The whole window over all its passes: from its start to the end
        of the last whole pass."""
        return {"pass_s": self._window_s / w.work}

    def close(self) -> None:
        self.session = None


class ServedClosedLoop(_Driver):
    server = service = None
    children: list = ()

    def load(self) -> None:
        from nds_tpu.engine import Session
        from nds_tpu.service import (FrontDoorServer, QueryService,
                                     ServiceConfig)
        self.session = Session(self._engine_config())
        wh = self.config["_warehouse"]
        for t in sorted(os.listdir(wh)):
            data = os.path.join(wh, t, "data")
            if os.path.isdir(data):
                self.session.register_parquet(t, data)
        self.service = QueryService(
            self.session, ServiceConfig(**self.config.get("service", {})))
        self.service.start()
        self.server = FrontDoorServer(self.service, host="127.0.0.1", port=0)
        self.server.start()
        self.children = []

    def warm(self) -> None:
        """Each statement through the front door until it twice answers
        from a compiled program; then the clients, and with them what only
        concurrency compiles: a volley of every statement from all clients
        at once (tickets of one template that wait together ride one
        batched dispatch, a program of its own), then short closed loops
        until one compiles nothing."""
        from nds_tpu.service.frontdoor import FlightClient
        self._start_clients()       # they come up while the statements warm
        with FlightClient("127.0.0.1", self.server.port,
                          timeout_s=1100.0) as client:
            for st in self.stmts:
                good = 0
                for _ in range(6):
                    _t, resp = client.query(st.sql, label=st.unit)
                    good = good + 1 if resp["stats"]["mode"] \
                        in self.config["want_modes"] else 0
                    if good == 2:
                        break
                else:
                    raise SystemExit(f"benchmark: {st.unit} never ran as a "
                                     "compiled program behind the front door")
        for child in self.children:
            line = child.stdout.readline()
            if line.strip() != "READY":
                raise SystemExit(f"benchmark: a client said {line!r}")
        for ui in range(len(self.stmts)):
            for _ in range(2):
                self._order({"volley": ui, "at": time.monotonic() + 0.1})
        for _ in range(4):
            before = _compiles()
            t0 = time.monotonic() + 0.1
            reports = self._order({"start": t0,
                                   "end": t0 + self.mix["warm_loop_s"]})
            bad = [r for rep in reports for r in rep["requests"]
                   if r[5] or r[4]["mode"] not in self.config["want_modes"]]
            if not bad and _compiles() == before:
                return
        raise SystemExit("benchmark: the served warm-up never reached the "
                         f"steady state: {bad[:3]}")

    def _start_clients(self) -> None:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        spec = {"host": "127.0.0.1", "port": self.server.port,
                "timeout_s": 1100.0,
                "statements": [[st.unit, st.sql] for st in self.stmts]}
        for _ in range(self.mix["clients"]):
            child = subprocess.Popen(
                [sys.executable, os.path.join(ROOT, "benchmark",
                                              "served_client.py")],
                cwd=ROOT, env=env, text=True, stdin=subprocess.PIPE,
                stdout=subprocess.PIPE)
            child.stdin.write(json.dumps(spec) + "\n")
            child.stdin.flush()
            self.children.append(child)

    def _send(self, order: dict) -> None:
        """The same order to every client (each walks the cycle from its own offset)."""
        from benchmark import traffic
        for c, child in enumerate(self.children):
            mine = dict(order)
            if "end" in order:
                mine["walk"] = traffic.client_walk(len(self.stmts), c)
            child.stdin.write(json.dumps(mine) + "\n")
            child.stdin.flush()

    def _collect(self) -> list:
        """Every client's reply to the last order, once all have answered."""
        return [json.loads(child.stdout.readline())
                for child in self.children]

    def _order(self, order: dict) -> list:
        self._send(order)
        return self._collect()

    def window(self, seconds: float) -> Window:
        w = Window()
        tr = TraceSlice(self.obs, min(self.mix.get("trace_slice_s", 6.0),
                                      seconds / 2))
        self._open_window()
        tr.start()
        t0 = time.monotonic() + 0.2
        t1 = t0 + seconds
        slice_end = []
        self._send({"start": t0, "end": t1})
        with annotate("bench.serve_wait", self.obs.trace):
            while time.monotonic() < t1:
                if tr.due():
                    slice_end.append(time.monotonic())
                    tr.stop(0)
                time.sleep(0.02)
        if tr.t0 is not None:       # a window shorter than the slice
            slice_end.append(time.monotonic())
            tr.stop(0)
        reports = self._collect()
        self._close_window()
        latencies = []
        for report in reports:
            tables = [base64.b64decode(b) for _ui, b in report["answers"]]
            for ui, sent, done, aid, stats, err in report["requests"]:
                if done > t1:
                    continue       # answered after the window had closed
                w.attempted += 1
                if slice_end and done <= slice_end[0]:
                    self.obs.slice_work += 1
                if err or stats["mode"] not in self.config["want_modes"]:
                    w.failed += 1
                    w.notes.append(f"{self.stmts[ui].unit}: {err or stats}")
                    latencies.append(math.inf)
                    continue
                latencies.append((done - sent) * 1e3)
                self.obs.tickets.append(stats)
                w.keep(ui, ipc_table(tables[aid]), tables[aid])
        w.work = w.attempted
        self._latencies, self._seconds = latencies, seconds
        return w

    def end_to_end(self, w: Window, wrong: int) -> dict:
        return served_values(self._latencies,
                             w.attempted - w.failed - wrong, self._seconds)

    def close(self) -> None:
        for child in self.children:
            child.stdin.close()         # end of orders: the client exits
        for child in self.children:
            try:
                child.wait(timeout=30)
            except subprocess.TimeoutExpired:
                child.kill()
                child.wait()
        self.children = []
        if self.server is not None:
            self.server.stop()
        if self.service is not None:
            self.service.close()
        self.session = None


DRIVERS = {"pass_loop": PassLoop, "served_closed_loop": ServedClosedLoop}
