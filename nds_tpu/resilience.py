"""Resilience layer: retry policies, deadlines, and fault injection.

The NDS lifecycle runs for hours at real scale factors, and the reference
harness's only answer to failure is detection (record ``Failed`` in the
JSON summary and keep the stream going). Production SQL engines treat
query-level fault tolerance and bounded execution as table stakes; this
module supplies the primitives the runners build on:

- :class:`RetryPolicy` — deterministic exponential backoff with a
  transient/fatal exception classification, used by ``report.BenchReport``
  for per-query attempts and by ``bench`` for phase-level retry.
- :class:`Deadline` / :func:`run_with_deadline` — wall-clock budgets for a
  query or a stream; a budget overrun raises :class:`DeadlineExceeded`
  (the worker thread is abandoned, not killed — the caller records the
  failure and moves on).
- :class:`AdmissionRejected` — typed overload rejection raised by bounded
  admission points (the query service's bounded queue, ``nds_tpu/service``)
  so overload surfaces as an immediate, classifiable error instead of an
  unbounded pile-up behind the accelerator.
- :class:`CircuitOpen` / :class:`CircuitBreaker` — a per-error-class
  failure-rate breaker for admission points: a class of failures crossing
  its windowed rate trips the breaker open, new work is refused with the
  typed :class:`CircuitOpen`, and after a cooldown a bounded number of
  half-open PROBES test recovery (success closes, failure re-opens).
- :class:`FaultRegistry` — named engine-level fault points
  (``arrow.read``, ``device.put``, ``jax.compile``, ``jax.execute``,
  ``stream.spawn``, ``query.run``) threaded through the engine and
  harness, armable to raise, delay, or hang at a given point/probability.
  This generalizes the ad-hoc ``--fault_inject`` query list the power
  runner grew (now sugar over ``query.run`` specs) and lets the retry /
  deadline / restart machinery be tested without a flaky device.

**RetryPolicy classification table** (how each typed failure class is
handled by default — fatal wins when a type matches both lists):

==================  =========  ==============================================
exception           class      why
==================  =========  ==============================================
TransientError      transient  declared retryable by its raiser
FaultError          transient  injected faults model transient infra failures
JaxRuntimeError     transient  device runtime failure (OOM, failed program);
                               the caller's attempt bound decides, the engine
                               itself never answers it from the host
ConnectionError     transient  network blips
TimeoutError        transient  slow dependency, not a broken one
BrokenPipeError     transient  peer restarted; a retry reconnects
AdmissionRejected   transient  overload: back off and resubmit is the
                               intended client response (depth/limit carried)
DeadlineExceeded    fatal      the budget is spent; retrying double-spends it
ChipPlacementError  fatal      a property of the launch environment: every
                               retry would refuse the same way
CircuitOpen         fatal      permanent-until-probe: the breaker re-opens on
                               every submit until a half-open probe succeeds,
                               so client-side retry is wasted work — wait for
                               ``retry_after_s`` or route elsewhere
KeyboardInterrupt   fatal      interrupts must propagate
SystemExit          fatal      interpreter is leaving
<anything else>     transient  a mid-stream failure is worth one more try;
                               the attempt bound caps the cost
==================  =========  ==============================================

Everything here is deterministic: backoff schedules (jitter included) are
pure functions of the attempt number, and probabilistic fault draws come
from PER-SPEC seeded RNGs in that spec's firing order — so a spec's
firing-index set is a pure function of the registry seed and arming
order, independent of which service thread happens to hit the point.
"""
from __future__ import annotations

import atexit
import os
import random
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Iterable, Optional


class FaultError(RuntimeError):
    """Raised by an armed fault point (a deliberately injected failure)."""


class TransientError(RuntimeError):
    """Base class for errors a RetryPolicy treats as retryable."""


class DeadlineExceeded(RuntimeError):
    """A per-query or per-stream wall-clock budget expired."""


class ChipPlacementError(RuntimeError):
    """A parent was asked to start engine child processes that would each
    need an accelerator. A chip belongs to one process at a time and nothing
    here assigns chips to children, so the request is refused up front
    instead of letting the children fail or hang at backend start-up."""


def check_child_placement(what: str) -> None:
    """Raise ChipPlacementError unless engine child processes of this one
    run on the host: an environment that pins JAX to the CPU
    (``JAX_PLATFORMS=cpu``, which children inherit). Even a numpy-backend
    run asks JAX for its backend (the report's device capture), so the
    platform variable is the one thing that keeps a child off the chip.
    Decided from the environment alone — asking JAX for its devices would
    itself take the chip."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms.strip().lower() == "cpu":
        return
    raise ChipPlacementError(
        f"{what} starts one engine process per stream/server, and with "
        f"JAX_PLATFORMS={platforms!r} each of them would need an "
        "accelerator of its own: a chip belongs to one process at a time. "
        "Use an in-process mode (thread/service) on the chip, or set "
        "JAX_PLATFORMS=cpu to run the processes on the host")


class AdmissionRejected(RuntimeError):
    """A query was refused at a bounded admission point (service queue full,
    service closed) INSTEAD of piling up behind the accelerator. Carries the
    observed depth/limit so clients can back off proportionally; classified
    transient by RetryPolicy (retry-after-backoff is the intended client
    response to overload)."""

    def __init__(self, message: str, depth: int | None = None,
                 limit: int | None = None):
        super().__init__(message)
        self.depth = depth
        self.limit = limit


class CircuitOpen(AdmissionRejected):
    """A per-error-class circuit breaker is refusing admissions.

    Subclasses AdmissionRejected (it IS a typed admission refusal), but
    classifies FATAL under RetryPolicy — fatal wins over the inherited
    transient name — because the breaker stays open until a half-open
    probe succeeds: immediate client retry cannot help, only waiting
    ``retry_after_s`` (or routing elsewhere) can."""

    def __init__(self, message: str, error_class: str | None = None,
                 retry_after_s: float | None = None):
        super().__init__(message)
        self.error_class = error_class
        self.retry_after_s = retry_after_s


# -- retry --------------------------------------------------------------------

#: exception type names (searched over the whole MRO) retried by default.
#: JaxRuntimeError (a device runtime failure: OOM, a failed program) is named
#: here so jax need not be imported; FaultError is transient by design (injected faults
#: simulate transient infrastructure failures unless armed to repeat);
#: AdmissionRejected is the overload signal whose intended client response
#: IS retry-after-backoff. Full rationale: module-docstring table.
_TRANSIENT_NAMES = ("TransientError", "FaultError", "JaxRuntimeError",
                    "ConnectionError", "TimeoutError", "BrokenPipeError",
                    "AdmissionRejected")
#: never retried: a blown deadline already consumed its budget, interrupts
#: must propagate, and an open circuit re-rejects until a probe succeeds
#: (CircuitOpen's MRO also carries AdmissionRejected — fatal wins).
_FATAL_NAMES = ("DeadlineExceeded", "CircuitOpen", "ChipPlacementError",
                "KeyboardInterrupt", "SystemExit")


@dataclass
class RetryPolicy:
    """Deterministic bounded retry: ``max_attempts`` tries, exponential
    backoff ``backoff_s * factor**(attempt-1)`` capped at ``max_backoff_s``.

    ``jitter`` (0..1) spreads synchronized retriers: attempt k's backoff
    stretches by up to ``jitter`` of itself using a DETERMINISTIC
    pseudo-random fraction of the attempt number (a Weyl sequence — no
    RNG state, so a failing run still replays identically), and the
    jittered value stays capped at ``max_backoff_s``.

    Classification ("transient" retries, "fatal" re-raises) follows the
    module-docstring table; fatal wins when a type's MRO matches both.
    """
    max_attempts: int = 3
    backoff_s: float = 0.1
    backoff_factor: float = 2.0
    max_backoff_s: float = 30.0
    jitter: float = 0.0
    transient_names: tuple = _TRANSIENT_NAMES
    fatal_names: tuple = _FATAL_NAMES

    def classify(self, exc: BaseException) -> str:
        """"transient" (retryable) or "fatal". Fatal wins on conflict;
        unknown exception types default to transient — a mid-stream query
        failure is worth one more try, and the attempt bound caps the cost.
        """
        names = {c.__name__ for c in type(exc).__mro__}
        if names & set(self.fatal_names):
            return "fatal"
        if names & set(self.transient_names):
            return "transient"
        return "transient"

    def backoff(self, attempt: int) -> float:
        """Seconds to wait after failed attempt `attempt` (1-based)."""
        base = self.backoff_s * self.backoff_factor ** (attempt - 1)
        if self.jitter > 0:
            # golden-ratio Weyl fraction of the attempt number: well
            # spread across attempts, zero state, replays identically
            frac = (attempt * 0.6180339887498949) % 1.0
            base *= 1.0 + self.jitter * frac
        return min(self.max_backoff_s, base)

    def call(self, fn: Callable, *args, label: str = "",
             sleep: Callable[[float], None] = time.sleep,
             on_attempt: Optional[Callable] = None, **kwargs):
        """Run ``fn`` under this policy; re-raises the last error when
        attempts are exhausted or the error classifies fatal. ``on_attempt``
        (attempt#, exception|None) observes every try."""
        from .obs.metrics import RETRIES
        for attempt in range(1, self.max_attempts + 1):
            try:
                out = fn(*args, **kwargs)
                if on_attempt is not None:
                    on_attempt(attempt, None)
                return out
            except Exception as e:
                if on_attempt is not None:
                    on_attempt(attempt, e)
                if attempt >= self.max_attempts or \
                        self.classify(e) == "fatal":
                    raise
                RETRIES.inc()
                sleep(self.backoff(attempt))


# -- deadlines ----------------------------------------------------------------

class Deadline:
    """A wall-clock budget. ``seconds=None`` (or <= 0) never expires."""

    def __init__(self, seconds: Optional[float],
                 clock: Callable[[], float] = time.monotonic):
        self.seconds = seconds if seconds and seconds > 0 else None
        self._clock = clock
        self._t0 = clock()

    def remaining(self) -> Optional[float]:
        if self.seconds is None:
            return None
        return self.seconds - (self._clock() - self._t0)

    def expired(self) -> bool:
        rem = self.remaining()
        return rem is not None and rem <= 0

    def check(self, label: str = "") -> None:
        if self.expired():
            raise DeadlineExceeded(
                f"{label or 'deadline'} exceeded {self.seconds}s budget")


#: deadline workers abandoned mid-flight, drained (bounded) at exit: a
#: daemon thread killed while inside XLA compute aborts interpreter
#: teardown (std::terminate from the C++ runtime), turning an otherwise
#: clean run into a spurious nonzero exit the stream supervisor would
#: retry. Truly hung workers still abandon after the grace.
_ABANDONED: list[threading.Thread] = []
_ABANDONED_LOCK = threading.Lock()


def _drain_abandoned(grace_s: Optional[float] = None) -> None:
    grace = float(os.environ.get("NDS_TPU_DEADLINE_DRAIN_S", "10")) \
        if grace_s is None else grace_s
    until = time.monotonic() + grace
    with _ABANDONED_LOCK:
        workers = list(_ABANDONED)
        _ABANDONED.clear()
    for t in workers:
        t.join(max(0.0, until - time.monotonic()))


atexit.register(_drain_abandoned)


def run_with_deadline(fn: Callable, timeout_s: Optional[float], *args,
                      label: str = "", **kwargs):
    """Run ``fn`` bounded by ``timeout_s`` wall seconds.

    The call runs in a daemon worker thread; on overrun the worker is
    ABANDONED (python threads cannot be killed) and DeadlineExceeded
    raises in the caller, which records the failure and continues — the
    same containment posture the reference gets from per-app process
    isolation. Abandoned workers get a bounded join at interpreter exit
    (NDS_TPU_DEADLINE_DRAIN_S, default 10) so a worker still inside XLA
    doesn't abort teardown. timeout_s None/<=0 calls ``fn`` inline.
    """
    if not timeout_s or timeout_s <= 0:
        return fn(*args, **kwargs)
    box: dict = {}

    def work():
        try:
            box["result"] = fn(*args, **kwargs)
        except BaseException as e:      # delivered to the caller below
            box["error"] = e

    t = threading.Thread(target=work, daemon=True,
                         name=f"deadline-worker:{label or fn.__name__}")
    t.start()
    t.join(timeout_s)
    if t.is_alive():
        with _ABANDONED_LOCK:
            _ABANDONED[:] = [w for w in _ABANDONED if w.is_alive()]
            _ABANDONED.append(t)
        raise DeadlineExceeded(
            f"{label or 'call'} exceeded {timeout_s}s budget "
            "(worker abandoned)")
    if "error" in box:
        raise box["error"]
    return box.get("result")


# -- circuit breaker ----------------------------------------------------------

@dataclass
class CircuitBreakerConfig:
    """Knobs of one :class:`CircuitBreaker` (per-error-class windows)."""
    #: outcomes tracked per error class (sliding window; successes count
    #: toward every tracked class so rates decay as the engine heals)
    window: int = 16
    #: failures of one class required inside its window before the rate
    #: can trip (a floor so one early failure at 1/1 = 100% never trips)
    min_failures: int = 4
    #: windowed failure fraction at/above which the class trips open
    failure_rate: float = 0.5
    #: seconds a tripped class stays open before half-open probes start
    open_s: float = 2.0
    #: concurrent probe admissions allowed while half-open
    probes: int = 1
    #: error-class names the breaker never counts (a ticket blowing its
    #: OWN deadline budget says nothing about engine health)
    exclude: tuple = ("DeadlineExceeded",)


class _BreakerClass:
    """One error class's window + state. Mutated only under the breaker
    lock."""
    __slots__ = ("state", "outcomes", "opened_at", "probes_out", "trips")

    def __init__(self, window: int):
        self.state = "closed"               # closed | open | half_open
        self.outcomes: deque = deque(maxlen=window)   # True = failure
        self.opened_at = 0.0
        self.probes_out = 0
        self.trips = 0


class CircuitBreaker:
    """Per-error-class circuit breaker for admission points.

    The service reports every ticket outcome through :meth:`record`; each
    FAILURE class (exception type name) keeps its own sliding window, so a
    storm of one class (say FaultError from a sick device path) trips
    without a healthy class's successes masking the rate. While a class is
    OPEN, :meth:`admit` raises the typed :class:`CircuitOpen` (fatal under
    RetryPolicy: permanent-until-probe). After ``open_s`` the class goes
    HALF-OPEN: up to ``probes`` admissions pass through as probes — a
    probe success closes the class (window cleared), a probe failure
    re-opens it for another cooldown.

    Trips and probes land in the flight recorder (``trip``/``probe``
    events; a trip also dumps the ring — the moments post-mortems exist
    for) and in the ``circuit_trips`` metric. ``clock`` is injectable for
    deterministic tests.
    """

    def __init__(self, config: Optional[CircuitBreakerConfig] = None,
                 clock: Callable[[], float] = time.monotonic):
        self.config = config or CircuitBreakerConfig()
        self._clock = clock
        self._lock = threading.Lock()
        self._classes: dict[str, _BreakerClass] = {}

    def admit(self, label: str = "") -> Optional[str]:  # lint: thread-entry (every service client thread submits through this)
        """Gate one admission. Raises :class:`CircuitOpen` when some error
        class is open (or half-open with its probe slots taken). Returns
        the error-class name this admission PROBES for (caller must pass
        it back to :meth:`record`), or None for a normal admission."""
        cfg = self.config
        now = self._clock()
        probe_for = None
        with self._lock:
            for cls, st in self._classes.items():
                if st.state == "open":
                    waited = now - st.opened_at
                    if waited < cfg.open_s:
                        raise CircuitOpen(
                            f"circuit open for {cls} "
                            f"({cfg.open_s - waited:.2f}s until probes)",
                            error_class=cls,
                            retry_after_s=cfg.open_s - waited)
                    st.state = "half_open"
                    st.probes_out = 0
                if st.state == "half_open":
                    if st.probes_out >= cfg.probes:
                        raise CircuitOpen(
                            f"circuit half-open for {cls}: probe slots "
                            f"full ({cfg.probes} in flight)",
                            error_class=cls, retry_after_s=0.0)
                    if probe_for is None:
                        st.probes_out += 1
                        probe_for = cls
        if probe_for is not None:
            from .obs.flight import FLIGHT
            FLIGHT.record("probe", error_class=probe_for, label=label)
        return probe_for

    def record(self, error_name: Optional[str] = None,
               probe: Optional[str] = None, label: str = "") -> None:  # lint: thread-entry (device lane + client threads report outcomes)
        """Report one outcome: ``error_name`` is the failure's type name
        (None = success); ``probe`` is the class name admit() returned."""
        cfg = self.config
        excluded = error_name is not None and error_name in cfg.exclude
        now = self._clock()
        tripped: list[tuple[str, int, int]] = []
        closed: Optional[str] = None
        with self._lock:
            if probe is not None:
                st = self._classes.get(probe)
                if st is not None and st.state == "half_open":
                    st.probes_out = max(0, st.probes_out - 1)
                    if excluded:
                        pass    # no health signal: slot freed, stay half-open
                    elif error_name is None:
                        st.state = "closed"
                        st.outcomes.clear()
                        closed = probe
                    else:
                        # ANY failure of a probe (even another class) says
                        # the engine is still sick: re-open for a cooldown
                        st.state = "open"
                        st.opened_at = now
                        st.trips += 1
                        tripped.append((probe, st.trips,
                                        sum(st.outcomes)))
            if excluded:
                pass            # an excluded class teaches the windows nothing
            elif error_name is None:
                for st in self._classes.values():
                    st.outcomes.append(False)
            else:
                st = self._classes.get(error_name)
                if st is None:
                    st = self._classes[error_name] = _BreakerClass(
                        cfg.window)
                st.outcomes.append(True)
                fails = sum(st.outcomes)
                if st.state == "closed" and fails >= cfg.min_failures \
                        and fails / len(st.outcomes) >= cfg.failure_rate:
                    st.state = "open"
                    st.opened_at = now
                    st.trips += 1
                    tripped.append((error_name, st.trips, fails))
        if closed is not None:
            from .obs.flight import FLIGHT
            FLIGHT.record("probe", error_class=closed, outcome="closed",
                          label=label)
        for cls, trips, fails in tripped:
            from .obs.flight import FLIGHT
            from .obs.metrics import CIRCUIT_TRIPS
            CIRCUIT_TRIPS.inc()
            # the onset of a failure storm is exactly the window the
            # flight ring should preserve: trip (and dump) per class
            FLIGHT.trip(f"circuit:{cls}", error_class=cls, trips=trips,
                        window_failures=fails, label=label)

    def release(self, probe: Optional[str]) -> None:
        """Free a granted probe slot without a health signal (the probe
        admission was refused downstream before it could run)."""
        if probe is None:
            return
        with self._lock:
            st = self._classes.get(probe)
            if st is not None and st.state == "half_open":
                st.probes_out = max(0, st.probes_out - 1)

    def state(self) -> dict[str, dict]:
        """{error_class: {state, trips, window_failures}} snapshot."""
        with self._lock:
            return {cls: {"state": st.state, "trips": st.trips,
                          "window_failures": sum(st.outcomes)}
                    for cls, st in self._classes.items()}


# -- fault injection ----------------------------------------------------------

#: engine/harness fault points. Each is fired exactly once per logical
#: event by the owning layer:
#:   arrow.read   - host-side Arrow -> engine table conversion (arrow_bridge)
#:   device.put   - host -> device upload of a padded table (device.to_device)
#:   jax.compile  - XLA trace/compile of a whole-plan program (CompiledQuery)
#:   jax.execute  - execution of a device program (compiled run / eager record)
#:   stream.spawn - throughput supervisor starting a stream attempt
#:   query.run    - power runner starting a timed query (detail = query name)
#:   manifest.write     - warehouse manifest publication, BEFORE any byte
#:                        lands (warehouse.WarehouseTable._store_doc)
#:   txn.commit         - warehouse transaction about to publish its
#:                        version record + CURRENT (the commit point)
#:   txn.between_tables - a SECOND distinct table joining an open
#:                        warehouse transaction (the mid-commit kill
#:                        window: table A committed, table B untouched)
#:   frontdoor.drop     - a front-door connection handler about to write
#:                        a response (service/frontdoor.py): a raise-spec
#:                        makes the server sever the socket instead —
#:                        the client sees an abrupt EOF mid-frame
#:   frontdoor.kill     - the engine process serving a front-door query
#:                        (fired before dispatch): a raise-spec makes the
#:                        server process exit hard (os._exit) — the
#:                        chaos topology campaign's mid-query kill
FAULT_POINTS = ("arrow.read", "device.put", "jax.compile", "jax.execute",
                "stream.spawn", "query.run",
                "manifest.write", "txn.commit", "txn.between_tables",
                "frontdoor.drop", "frontdoor.kill")

#: default sleep for a ``hang`` spec with no explicit duration: long enough
#: that only a deadline/supervisor kill ends the attempt.
HANG_SECONDS = 3600.0


@dataclass
class FaultSpec:
    """One armed fault. Spec-string grammar (property-file friendly):

        point:action[:seconds][@probability][#times][/match]

    e.g. ``jax.execute:hang:5#1`` (hang 5s, first firing only),
    ``arrow.read:raise``, ``device.put:delay:0.2@0.5``,
    ``query.run:raise/query1`` (only when the fired detail is query1).
    """
    point: str
    action: str = "raise"           # raise | delay | hang
    seconds: float = 0.0            # delay/hang duration (hang: 0 => HANG_SECONDS)
    probability: float = 1.0
    times: Optional[int] = None     # max firings; None = unlimited
    match: Optional[str] = None     # exact match on the fire() detail
    source: str = "manual"          # "config" specs replaced on reconfigure
    fired: int = field(default=0, compare=False)
    #: per-spec probability RNG, seeded at arm time from (registry seed,
    #: arm index, spec identity): the spec's firing-index set is a pure
    #: function of the seed + arming order even when service threads hit
    #: the point in nondeterministic interleavings (seeded chaos
    #: campaigns rely on this). None until armed; draws under the
    #: registry lock.
    rng: Optional[random.Random] = field(default=None, compare=False,
                                         repr=False)

    @classmethod
    def parse(cls, text: str, source: str = "manual") -> "FaultSpec":
        body, match = (text.split("/", 1) + [None])[:2] \
            if "/" in text else (text, None)
        body, times = body.split("#", 1) if "#" in body else (body, None)
        body, prob = body.split("@", 1) if "@" in body else (body, None)
        parts = body.split(":")
        point = parts[0].strip()
        if point not in FAULT_POINTS:
            raise ValueError(f"unknown fault point {point!r} "
                             f"(expected one of {FAULT_POINTS})")
        action = parts[1].strip() if len(parts) > 1 else "raise"
        if action not in ("raise", "delay", "hang"):
            raise ValueError(f"unknown fault action {action!r} in {text!r} "
                             "(expected raise, delay, or hang)")
        seconds = float(parts[2]) if len(parts) > 2 else 0.0
        return cls(point=point, action=action, seconds=seconds,
                   probability=float(prob) if prob is not None else 1.0,
                   times=int(times) if times is not None else None,
                   match=match, source=source)

    def applies(self, detail: str) -> bool:
        if self.times is not None and self.fired >= self.times:
            return False
        return self.match is None or self.match == detail


class FaultRegistry:
    """Process-global registry of armed fault points.

    Engine/harness code calls :meth:`fire` at each point; the fast path
    (nothing armed) is one attribute read, so the hooks cost nothing in
    production. Probability draws come from PER-SPEC RNGs seeded at arm
    time, so a spec's firing-index set is deterministic in that spec's
    own firing order — chaos campaigns replay their schedules even when
    concurrent service threads interleave the points nondeterministically.

    Thread contract (audited for armed-under-live-traffic chaos runs):
    every mutation of the spec list AND every iteration over it — firing,
    certainty queries, arming, disarming, reconfiguring — happens under
    ``_lock``; ``fire`` collects the triggered specs under the lock and
    acts (sleeps/raises) outside it. The only unlocked read is the
    nothing-armed fast path, a single attribute load of the list object
    (atomic in CPython; a spec armed concurrently with that read is
    simply not yet visible, same as arming one instruction later).
    """

    def __init__(self, seed: int = 0x5E51):
        self._specs: list[FaultSpec] = []
        self._lock = threading.Lock()
        self._rng = random.Random(seed)     # fallback for unarmed specs
        self._seed = seed
        self._armed_total = 0               # arm-order index for spec seeds

    def _seed_spec(self, spec: FaultSpec) -> None:
        """Give the spec its deterministic RNG (under ``_lock``)."""
        self._armed_total += 1
        spec.rng = random.Random(
            f"{self._seed}:{self._armed_total}:{spec.point}:"
            f"{spec.action}:{spec.probability}:{spec.match}")

    def arm(self, spec, **kwargs) -> FaultSpec:  # lint: thread-entry (campaign drivers arm while service threads fire)
        """Arm a FaultSpec (or parse a spec string). Returns the armed spec
        so callers can :meth:`disarm` it."""
        if isinstance(spec, str):
            spec = FaultSpec.parse(spec, **kwargs)
        elif spec.point not in FAULT_POINTS:
            # parse() validates spec strings; directly-constructed specs
            # must not arm a point no engine layer will ever fire (a
            # typo'd chaos campaign would otherwise "pass" as a no-op)
            raise ValueError(f"unknown fault point {spec.point!r} "
                             f"(expected one of {FAULT_POINTS})")
        with self._lock:
            self._seed_spec(spec)
            self._specs.append(spec)
        return spec

    def disarm(self, spec: FaultSpec) -> None:  # lint: thread-entry (campaign drivers disarm while service threads fire)
        with self._lock:
            if spec in self._specs:
                self._specs.remove(spec)

    def configure(self, texts: Iterable[str]) -> list[FaultSpec]:  # lint: thread-entry (sessions build on service/stream threads)
        """Install config-sourced specs, replacing any previous config batch
        (manually armed specs are untouched). Called by Session.__init__
        from ``EngineConfig.fault_points``."""
        parsed = [FaultSpec.parse(t, source="config") for t in texts if t]
        with self._lock:
            self._specs = [s for s in self._specs if s.source != "config"]
            for s in parsed:
                self._seed_spec(s)
            self._specs.extend(parsed)
        return parsed

    def clear(self, point: Optional[str] = None) -> None:  # lint: thread-entry (campaign teardown races in-flight queries)
        with self._lock:
            self._specs = [] if point is None else \
                [s for s in self._specs if s.point != point]
            self._rng = random.Random(self._seed)
            if point is None:
                self._armed_total = 0

    def specs(self) -> list[FaultSpec]:
        with self._lock:
            return list(self._specs)

    def would_raise(self, point: str, detail: str = "",
                    aliases: tuple = ()) -> bool:
        """Is a certain (p=1) raise-spec armed for this point/detail?
        Lets the power runner skip warmup for queries whose timed run is
        guaranteed to fail, without consuming the spec."""
        with self._lock:
            return any(s.point == point and s.action == "raise"
                       and s.probability >= 1.0
                       and any(s.applies(d) for d in (detail, *aliases))
                       for s in self._specs)

    def fire(self, point: str, detail: str = "", aliases: tuple = ()) -> None:  # lint: thread-entry (every engine layer fires from service/staging threads)
        """Trigger any armed specs for ``point``. Raise-specs raise
        FaultError; delay-specs sleep; hang-specs sleep (default
        HANG_SECONDS) and then raise, so an abandoned deadline worker dies
        cleanly when it wakes instead of touching shared state."""
        if not self._specs:         # fast path: nothing armed
            return
        triggered: list[FaultSpec] = []
        with self._lock:
            for s in self._specs:
                if s.point != point or \
                        not any(s.applies(d) for d in (detail, *aliases)):
                    continue
                if s.probability < 1.0 and \
                        (s.rng or self._rng).random() >= s.probability:
                    continue
                s.fired += 1
                triggered.append(s)
        if triggered:
            from .obs.flight import FLIGHT
            from .obs.metrics import FAULT_FIRINGS
            FAULT_FIRINGS.inc(len(triggered))
            # a firing fault point is exactly the post-mortem moment the
            # flight recorder exists for: record it and auto-dump the
            # surrounding lifecycle window (no-op while disabled)
            FLIGHT.record("fault", point=point, detail=detail,
                          actions=[s.action for s in triggered])
            FLIGHT.trip("fault", point=point)
        for s in triggered:         # act outside the lock (sleeps)
            where = f"{point} ({detail})" if detail else point
            if s.action == "delay":
                time.sleep(s.seconds)
            elif s.action == "hang":
                time.sleep(s.seconds if s.seconds > 0 else HANG_SECONDS)
                raise FaultError(f"hung fault point woke at {where}")
            else:
                raise FaultError(f"injected fault at {where}")


#: the process-global registry every engine/harness fault point fires into.
FAULTS = FaultRegistry()
