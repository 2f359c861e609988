"""Fault-tolerant training runtime primitives.

The reference Fluid stack survives real fleets with a spread of
mechanisms — gRPC deadline/retry semantics
(/root/reference/paddle/fluid/operators/distributed/grpc/grpc_client.cc,
FLAGS_rpc_deadline / FLAGS_rpc_retry_times), the HeartBeatMonitor
(operators/distributed/heart_beat_monitor.h), checkpoint-notify ops, and
FLAGS_check_nan_inf nan/inf interception (framework/details/
nan_inf_utils_detail.cc). This module centralizes the runtime-neutral
pieces of that story so io.py, distributed/wire.py, distributed/ps.py and
framework/executor.py share one vocabulary:

- typed errors: CheckpointCorruptError, RpcDeadlineError, CircuitOpenError,
  NonFiniteError, WatchdogTimeout
- retry_call(fn, deadline, base_backoff): exponential backoff + jitter
  under a wall-clock deadline
- CircuitBreaker: per-endpoint closed/open/half-open fail-fast gate so a
  dead pserver costs one deadline, not one deadline per call forever
- watchdog(budget)/run_with_watchdog: abort work exceeding a wall-clock
  budget (the host-side analog of a preempted-TPU step that never returns)
- fault_injection(point, ...): test hook arming named failure points that
  production code declares with maybe_fail(point)
- chaos(points, ...): seeded, probabilistic, schedulable fault injection
  across MANY points at once — the serving chaos harness ("The Tail at
  Scale" failure modes on demand: crashes, delays, lost replies)
"""
import random
import threading
import time
import weakref
from contextlib import contextmanager

from .observability.metrics import default_registry as _registry
from .observability.recorder import flight_recorder as _flightrec

_CHAOS_FIRED = _registry().counter(
    "chaos_faults_fired_total",
    "chaos-harness faults actually injected, by armed point",
    labels=("point",), max_series=64)
_BUDGET_EXHAUSTED = _registry().counter(
    "serving_retry_budget_exhausted_total",
    "retries/hedges/failovers refused by the process retry budget, by "
    "consumer",
    labels=("what",), max_series=16)

# every live CircuitBreaker, for the breaker-state metrics collector
_BREAKERS = weakref.WeakSet()
_BREAKER_STATES = {"closed": 0, "half-open": 1, "open": 2}
_BREAKER_SERIES_CAP = 64
# endpoints ever folded past the cap: dropped = len(set) is monotone
# and grows with actual cardinality, not with scrape frequency
_folded_endpoints = set()
_fold_lock = threading.Lock()


def _collect_breakers():
    by_endpoint = {}
    for b in list(_BREAKERS):
        ep = b.endpoint or "unknown"
        st = _BREAKER_STATES.get(b.state, 0)
        by_endpoint[ep] = max(by_endpoint.get(ep, 0), st)
    items = sorted(by_endpoint.items())
    if len(items) > _BREAKER_SERIES_CAP:
        # fold the overflow into one _other series (max state, so an
        # OPEN breaker past the cap still trips dashboards) and feed
        # the fold count to telemetry_series_dropped_total — silent
        # truncation would read as "all breakers closed" mid-outage
        kept = items[:_BREAKER_SERIES_CAP - 1]
        overflow = items[_BREAKER_SERIES_CAP - 1:]
        kept.append(("_other", max(st for _ep, st in overflow)))
        items = kept
        with _fold_lock:
            _folded_endpoints.update(ep for ep, _st in overflow)
    with _fold_lock:
        dropped = len(_folded_endpoints)
    return [{"name": "resilience_breaker_state", "kind": "gauge",
             "help": "circuit breaker state by endpoint "
                     "(0=closed, 1=half-open, 2=open; max across "
                     "same-endpoint breakers)",
             "labels": ("endpoint",),
             "samples": [((ep,), st) for ep, st in items],
             "dropped": dropped}]


_registry().register_collector(
    _collect_breakers,
    families=[{"name": "resilience_breaker_state", "kind": "gauge",
               "help": "circuit breaker state by endpoint "
                       "(0=closed, 1=half-open, 2=open)",
               "labels": ("endpoint",)}])


# --------------------------------------------------------------------------
# typed errors
# --------------------------------------------------------------------------

class CheckpointCorruptError(RuntimeError):
    """A checkpoint file failed its manifest integrity check (sha256
    mismatch, truncation, or unreadable payload). Carries ``path`` — the
    offending file — so operators know what to delete/re-replicate."""

    def __init__(self, message, path=None):
        super().__init__(message)
        self.path = path


class RpcDeadlineError(ConnectionError):
    """An RPC did not succeed within its wall-clock deadline (reference
    gRPC FLAGS_rpc_deadline semantics). Subclasses ConnectionError so
    existing transport-failure handlers keep working. Carries
    ``endpoint`` and ``elapsed`` (seconds spent retrying)."""

    def __init__(self, message, endpoint=None, elapsed=None):
        super().__init__(message)
        self.endpoint = endpoint
        self.elapsed = elapsed


class CircuitOpenError(RpcDeadlineError):
    """Fail-fast rejection: the endpoint's circuit breaker is open after
    repeated failures, so the call is refused without touching the wire."""


class EnforceNotMet(RuntimeError):
    """Runtime enforcement violation (reference platform/enforce.h
    PADDLE_ENFORCE / fluid.core.EnforceNotMet)."""


class NonFiniteError(EnforceNotMet):
    """FLAGS_check_nan_inf tripped: a fetched output or updated parameter
    contains nan/inf. Carries ``var_name`` (first offender) and ``count``
    (non-finite element count in that tensor)."""

    def __init__(self, message, var_name=None, count=None):
        super().__init__(message)
        self.var_name = var_name
        self.count = count


class WatchdogTimeout(RuntimeError):
    """Work under a watchdog exceeded its wall-clock budget."""


class CheckpointIncompleteError(CheckpointCorruptError):
    """A checkpoint loaded for training resume lacks part of the full
    training state (optimizer slabs, the RNG stream record, ...). Resuming
    from it would SILENTLY diverge from the uninterrupted run — reset
    moments, replayed RNG draws — so the load refuses instead. Carries
    ``missing`` (the absent variable/extra names). Subclasses
    CheckpointCorruptError so existing corrupt-checkpoint handlers treat
    it as an unusable checkpoint."""

    def __init__(self, message, path=None, missing=None):
        super().__init__(message, path=path)
        self.missing = list(missing or [])


class PreemptedError(RuntimeError):
    """The training loop was preempted (SIGTERM/SIGINT or an in-process
    ``train.request_preemption``) and exited at a slab boundary after its
    bounded-deadline fast checkpoint. Carries ``slab``/``step`` (progress
    at exit), ``checkpoint_no`` (the newest durable checkpoint — None
    when the fast save missed its deadline and the previous checkpoint
    stands) and ``reason`` (which trigger fired)."""

    def __init__(self, message, slab=None, step=None, checkpoint_no=None,
                 reason=None):
        super().__init__(message)
        self.slab = slab
        self.step = step
        self.checkpoint_no = checkpoint_no
        self.reason = reason


class RestartBudgetExceeded(RuntimeError):
    """The supervised training loop crashed more times than
    ``FLAGS_train_restart_budget`` allows; the last failure is chained as
    ``__cause__``. Carries ``restarts`` and ``errors`` (the typed error
    names of every restart cause, oldest first)."""

    def __init__(self, message, restarts=None, errors=None):
        super().__init__(message)
        self.restarts = restarts
        self.errors = list(errors or [])


class FaultInjected(RuntimeError):
    """Default exception raised by an armed chaos fault point. Distinct
    from real failure types so a soak can tell injected damage from a
    genuine bug in the recovery machinery."""


class HierarchicalCommsError(RuntimeError):
    """The compiled multi-slice executable FAILED the pre-burn comms
    gate (``observability/comms.assert_hier_decomposition``): either
    DCN-priced traffic appears on an axis that should stay on ICI, or
    the cross-slice wire bytes don't beat the flat all-reduce estimate,
    or the program carries no cross-slice collectives at all (the
    hier_grad_sync pass never ran). Raised BEFORE the first slab is
    dispatched, so a mis-decomposed program costs a compile, not a
    DCN-saturated training run. Carries ``violations`` (human-readable
    strings) and ``ledger`` (the offending CommLedger)."""

    def __init__(self, message, violations=None, ledger=None):
        super().__init__(message)
        self.violations = list(violations or [])
        self.ledger = ledger


class SliceWidthError(RuntimeError):
    """A checkpoint restored at a different ``dcn_dp`` width carries
    state incompatible with the rebuilt program (an optimizer slab or
    parameter whose shape disagrees with the program's declaration).
    Raised by ``train.slices.validate_restored_widths`` instead of
    letting GSPMD silently reshard — or jit fail with an opaque shape
    error — mid-recovery. Carries ``var``, ``found`` and ``expected``
    shapes."""

    def __init__(self, message, var=None, found=None, expected=None):
        super().__init__(message)
        self.var = var
        self.found = tuple(found) if found is not None else None
        self.expected = tuple(expected) if expected is not None else None


class RetryBudgetExhausted(RpcDeadlineError):
    """The process retry budget refused this retry/hedge/failover: the
    fleet is already saturated with first-try traffic, and another
    retry would amplify the overload instead of fixing anything (the
    metastable retry-storm mode "The Tail at Scale" warns about).
    Callers must treat it as a fast shed — back off or surface the
    underlying failure — never as one more thing to retry.
    Subclasses :class:`RpcDeadlineError` so transport-failure handlers
    see a connection-class error; ``retry_call`` propagates it without
    retrying (the CircuitOpenError discipline)."""


# --------------------------------------------------------------------------
# retry budget (token bucket bounding ALL tail-fighting machinery)
# --------------------------------------------------------------------------

class RetryBudget:
    """Token bucket bounding retries/hedges/failovers process-wide.

    Every INITIAL request deposits ``ratio`` tokens
    (:meth:`record_request`); every retry-shaped action withdraws one
    (:meth:`try_acquire`/:meth:`acquire`). Steady state therefore allows
    ~``ratio`` retries per request — under a sustained overload every
    layer's retry machinery (client reconnect, hedging, router
    failover, ``retry_call`` backoff loops) collectively drains the
    bucket and converts into fast typed sheds instead of multiplying
    the offered load. A small time-based reserve
    (``min_reserve`` tokens refilled over ``window_s``) keeps isolated
    failures retryable on an otherwise idle process.

    The bucket is shared process-wide by design (per-layer budgets
    would multiply the allowed amplification), but each distinct
    consumer (``what``) also holds a small EMERGENCY reserve
    (``what_reserve`` tokens, refilled over ``window_s``, consulted
    only when the shared pool is dry) — one subsystem's storm draining
    the pool must bound, not STARVE, another subsystem's isolated
    recovery retry (a serving shed storm must not abort a trainer's
    recoverable pserver bounce). ``window_s = 0`` disables both
    time-based refills and the per-consumer reserve.

    ``ratio < 0`` disables the budget entirely (every acquire granted)
    — the A/B lever for demonstrating the retry-storm failure mode.
    """

    def __init__(self, ratio=None, min_reserve=10.0, window_s=10.0,
                 cap=None, what_reserve=2.0):
        if ratio is None:
            from .flags import flag
            ratio = flag("retry_budget_ratio")
        self.ratio = float(ratio)
        self.min_reserve = float(min_reserve)
        self.window_s = float(window_s)
        self.what_reserve = float(what_reserve)
        # cap bounds token accumulation so a long quiet stretch cannot
        # bank an unbounded retry burst
        self.cap = float(cap) if cap is not None \
            else max(4.0 * self.min_reserve, 60.0)
        self._tokens = self.min_reserve
        self._last_refill = time.monotonic()
        self._what = {}        # consumer -> [tokens, last_refill]
        self._lock = threading.Lock()
        self._granted = 0
        self._denied = 0
        self._deposits = 0

    def _refill_locked(self, now):
        if self.window_s > 0:
            dt = now - self._last_refill
            if dt > 0:
                self._tokens = min(
                    self.cap,
                    self._tokens + dt * self.min_reserve / self.window_s)
        self._last_refill = now

    def _what_acquire_locked(self, what, now):
        """Per-consumer trickle reserve: each distinct ``what`` starts
        with ``what_reserve`` emergency tokens and refills at
        ``what_reserve / window_s`` tokens/s — only reached when the
        shared pool is dry, so a storm elsewhere bounds this consumer
        to a trickle instead of starving it outright."""
        if self.window_s <= 0 or self.what_reserve <= 0:
            return False
        cell = self._what.get(what)
        if cell is None:
            if len(self._what) >= 64:   # bounded like a label set
                return False
            cell = self._what[what] = [self.what_reserve, now]
        dt = now - cell[1]
        if dt > 0:
            cell[0] = min(self.what_reserve,
                          cell[0] + dt * self.what_reserve
                          / self.window_s)
        cell[1] = now
        if cell[0] >= 1.0:
            cell[0] -= 1.0
            return True
        return False

    def record_request(self):
        """Deposit ``ratio`` tokens for one initial (non-retry)
        request."""
        if self.ratio < 0:
            return
        now = time.monotonic()
        with self._lock:
            self._refill_locked(now)
            self._tokens = min(self.cap, self._tokens + self.ratio)
            self._deposits += 1

    def try_acquire(self, what="retry"):
        """Withdraw one token for a retry/hedge/failover; False (and a
        bump of ``serving_retry_budget_exhausted_total{what}``) when the
        budget is spent."""
        if self.ratio < 0:
            return True
        now = time.monotonic()
        with self._lock:
            self._refill_locked(now)
            if self._tokens >= 1.0:
                self._tokens -= 1.0
                self._granted += 1
                return True
            if self._what_acquire_locked(str(what), now):
                self._granted += 1
                return True
            self._denied += 1
        _BUDGET_EXHAUSTED.inc(labels=(str(what),))
        _flightrec().record("retry_budget_exhausted", what=str(what))
        return False

    def acquire(self, what="retry"):
        """:meth:`try_acquire` or raise :class:`RetryBudgetExhausted`."""
        if not self.try_acquire(what=what):
            raise RetryBudgetExhausted(
                f"retry budget exhausted for {what} (ratio "
                f"{self.ratio}): the process is already retrying at its "
                f"bound — shedding instead of amplifying the overload")

    def snapshot(self):
        with self._lock:
            return {"tokens": round(self._tokens, 3),
                    "ratio": self.ratio, "granted": self._granted,
                    "denied": self._denied, "deposits": self._deposits}


_default_budget = None
_budget_lock = threading.Lock()


def default_retry_budget():
    """THE process-global retry budget — consulted by ``retry_call``,
    the serving client's reconnect/hedging, and the fleet router's
    failover/hedging, so one bucket bounds every layer's amplification
    at once (per-layer budgets would multiply)."""
    global _default_budget
    with _budget_lock:
        if _default_budget is None:
            _default_budget = RetryBudget()
        return _default_budget


def reset_retry_budget():
    """Drop the process budget so the next use rebuilds it from the
    current ``FLAGS_retry_budget_ratio`` — tests and flag flips."""
    global _default_budget
    with _budget_lock:
        _default_budget = None


# --------------------------------------------------------------------------
# retry with exponential backoff + jitter
# --------------------------------------------------------------------------

def retry_call(fn, deadline=30.0, base_backoff=0.05, max_backoff=2.0,
               retries=None, retry_on=(ConnectionError, OSError),
               jitter=0.5, what="call", endpoint=None, on_retry=None,
               budget=None):
    """Run ``fn()`` until it succeeds, a non-retryable error escapes, the
    attempt budget is spent, or the wall-clock ``deadline`` passes.

    Backoff between attempts is ``base_backoff * 2**k`` capped at
    ``max_backoff``, with up to ``jitter`` fraction of random extra so a
    fleet of trainers retrying a recovered pserver doesn't stampede it.
    ``retries`` bounds ADDITIONAL attempts (None = unlimited within the
    deadline; 0 = single attempt). CircuitOpenError and
    RetryBudgetExhausted always propagate — retrying a breaker- or
    budget-rejected call would defeat the shed.

    Every retry (not the first attempt) withdraws one token from the
    process :func:`default_retry_budget` (``budget=`` overrides; the
    first attempt deposits): when the bucket is dry the call raises
    :class:`RetryBudgetExhausted` chained to the last failure instead
    of sleeping into another attempt — a process full of failing
    callers stops amplifying its own overload.

    Raises RpcDeadlineError (chained to the last failure) when the budget
    is exhausted.
    """
    start = time.monotonic()
    attempt = 0
    backoff = float(base_backoff)
    bud = budget if budget is not None else default_retry_budget()
    bud.record_request()
    while True:
        try:
            return fn()
        except (CircuitOpenError, RetryBudgetExhausted):
            raise
        except retry_on as exc:
            now = time.monotonic()
            elapsed = now - start
            out_of_attempts = retries is not None and attempt >= retries
            # next attempt would land past the deadline: give up now
            # instead of sleeping into guaranteed failure
            out_of_time = deadline is not None and \
                elapsed + backoff >= deadline
            if out_of_attempts or out_of_time:
                raise RpcDeadlineError(
                    f"{what} failed after {attempt + 1} attempt(s) over "
                    f"{elapsed:.2f}s"
                    + (f" (deadline {deadline}s)" if deadline else "")
                    + (f" to {endpoint}" if endpoint else "")
                    + f": {type(exc).__name__}: {exc}",
                    endpoint=endpoint, elapsed=elapsed) from exc
            if not bud.try_acquire(what=what):
                raise RetryBudgetExhausted(
                    f"{what} not retried after {attempt + 1} attempt(s) "
                    f"over {elapsed:.2f}s"
                    + (f" to {endpoint}" if endpoint else "")
                    + f": process retry budget exhausted (last failure "
                    f"{type(exc).__name__}: {exc})") from exc
            if on_retry is not None:
                on_retry(attempt, exc)
            time.sleep(backoff * (1.0 + jitter * random.random()))
            attempt += 1
            backoff = min(backoff * 2.0, float(max_backoff))


# --------------------------------------------------------------------------
# circuit breaker
# --------------------------------------------------------------------------

class CircuitBreaker:
    """Per-endpoint fail-fast gate (closed -> open -> half-open).

    ``failure_threshold`` consecutive failures open the circuit: calls
    raise CircuitOpenError immediately for ``reset_timeout`` seconds.
    After that one trial call is admitted (half-open); success closes the
    circuit, failure re-opens it for another ``reset_timeout``.
    """

    def __init__(self, endpoint=None, failure_threshold=3,
                 reset_timeout=5.0):
        self.endpoint = endpoint
        self.failure_threshold = int(failure_threshold)
        self.reset_timeout = float(reset_timeout)
        self._failures = 0
        self._opened_at = None
        self._half_open_inflight = False
        self._lock = threading.Lock()
        _BREAKERS.add(self)

    @property
    def state(self):
        with self._lock:
            if self._opened_at is None:
                return "closed"
            if time.monotonic() - self._opened_at >= self.reset_timeout:
                return "half-open"
            return "open"

    def before_call(self):
        """Admission check; raises CircuitOpenError when open."""
        with self._lock:
            if self._opened_at is None:
                return
            waited = time.monotonic() - self._opened_at
            if waited < self.reset_timeout:
                raise CircuitOpenError(
                    f"circuit breaker open for {self.endpoint or 'peer'} "
                    f"({self._failures} consecutive failures; retrying "
                    f"in {self.reset_timeout - waited:.1f}s)",
                    endpoint=self.endpoint)
            # half-open: admit exactly one probe at a time
            if self._half_open_inflight:
                raise CircuitOpenError(
                    f"circuit breaker half-open for "
                    f"{self.endpoint or 'peer'}: probe already in flight",
                    endpoint=self.endpoint)
            self._half_open_inflight = True

    def record_success(self):
        with self._lock:
            self._failures = 0
            self._opened_at = None
            self._half_open_inflight = False

    def release_probe(self):
        """Abandon an admitted call without judging the endpoint — for
        failures that are the caller's (encode TypeError, interrupt), not
        the peer's. Frees the half-open probe slot so an abandoned probe
        cannot wedge the breaker in fail-fast forever."""
        with self._lock:
            self._half_open_inflight = False

    def record_failure(self):
        with self._lock:
            self._failures += 1
            self._half_open_inflight = False
            if self._failures >= self.failure_threshold:
                self._opened_at = time.monotonic()


# --------------------------------------------------------------------------
# watchdog
# --------------------------------------------------------------------------

@contextmanager
def watchdog(budget_secs, what="operation"):
    """Abort the enclosed block when it exceeds ``budget_secs``.

    Main-thread only (uses interrupt_main, the same lever Ctrl-C pulls);
    from other threads use run_with_watchdog. The interrupt lands at the
    next Python bytecode boundary — a block stuck inside a single C call
    is aborted as soon as it re-enters Python.
    """
    import signal
    import _thread
    main = threading.main_thread()
    if threading.current_thread() is not main:
        raise RuntimeError("watchdog() only arms on the main thread; "
                           "use run_with_watchdog elsewhere")
    fired = [False]
    armed = [True]
    # _fire sends the signal while HOLDING this lock, and the exit path
    # disarms while holding it — so the interrupt can never land after
    # the with-block has moved on into unrelated code
    arm_lock = threading.Lock()

    def _fire():
        with arm_lock:
            if not armed[0]:
                return
            fired[0] = True
            try:
                # a real SIGINT interrupts blocking syscalls (sleep,
                # socket recv) with EINTR; interrupt_main() only sets a
                # flag the interpreter notices AFTER the syscall returns
                signal.pthread_kill(main.ident, signal.SIGINT)
            except (AttributeError, OSError, ValueError):
                _thread.interrupt_main()

    timer = threading.Timer(float(budget_secs), _fire)
    timer.daemon = True
    timer.start()
    try:
        yield
    except KeyboardInterrupt:
        if fired[0]:
            raise WatchdogTimeout(
                f"{what} exceeded its {budget_secs}s wall-clock budget")
        raise
    finally:
        try:
            with arm_lock:
                armed[0] = False
        except KeyboardInterrupt:
            armed[0] = False
            if not fired[0]:
                raise           # a genuine Ctrl-C, not our timer
            # the timer fired in the instant between the block completing
            # and the disarm: the work finished within budget, absorb the
            # late interrupt instead of letting it escape
        timer.cancel()


def run_with_watchdog(fn, budget_secs, *args, what=None, **kwargs):
    """Run ``fn(*args, **kwargs)`` on a worker thread; raise
    WatchdogTimeout if it does not finish within ``budget_secs``. Safe
    from any thread. The overrunning worker is left to die as a daemon —
    its result is discarded."""
    box = {}

    def _target():
        try:
            box["result"] = fn(*args, **kwargs)
        except BaseException as exc:  # noqa: BLE001 — relayed to caller
            box["error"] = exc

    t = threading.Thread(target=_target, daemon=True)
    t.start()
    t.join(float(budget_secs))
    if t.is_alive():
        what = what or getattr(fn, "__name__", "operation")
        _flightrec().record("watchdog", what=str(what),
                            budget_s=float(budget_secs))
        raise WatchdogTimeout(
            f"{what} exceeded its {budget_secs}s wall-clock budget")
    if "error" in box:
        raise box["error"]
    return box.get("result")


def await_ready(ready, deadline, budget_secs, what, poll_s=1e-4):
    """Wait on the calling thread until ``ready()`` is true, asking
    every ``poll_s`` seconds; raise WatchdogTimeout once
    ``time.perf_counter()`` passes ``deadline``. The watchdog of a wait
    for a device result (``jax.Array.is_ready``): no thread, and a
    stall that ate the budget before the wait began counts, because
    the deadline is asked first."""
    while True:
        if time.perf_counter() > deadline:
            _flightrec().record("watchdog", what=str(what),
                                budget_s=float(budget_secs))
            raise WatchdogTimeout(
                f"{what} exceeded its {budget_secs}s wall-clock budget")
        if ready():
            return
        time.sleep(poll_s)


# --------------------------------------------------------------------------
# fault injection (test hook)
# --------------------------------------------------------------------------

_faults = {}
_faults_lock = threading.Lock()


def maybe_fail(point, **context):
    """Production-side failure point: raises the armed exception when a
    test has armed ``point`` via fault_injection. No-op (one dict lookup)
    otherwise."""
    with _faults_lock:
        spec = _faults.get(point)
        if spec is None or spec["remaining"] == 0:
            return
        spec["remaining"] -= 1
        spec["fired"] += 1
        exc = spec["exc"]
    if callable(exc) and not isinstance(exc, type):
        exc = exc(point, context)
        if exc is None:
            return
    raise exc if not isinstance(exc, type) else exc(
        f"fault injected at {point}")


def clear_faults():
    with _faults_lock:
        _faults.clear()


@contextmanager
def fault_injection(point, exc=ConnectionError, times=1):
    """Arm ``point`` to raise ``exc`` for the next ``times`` hits
    (``times=-1`` = every hit while armed). ``exc`` may be an exception
    class, an instance, or a callable ``(point, context) -> exception or
    None``. Yields the spec dict; ``spec['fired']`` counts trips."""
    spec = {"exc": exc, "remaining": int(times), "fired": 0}
    with _faults_lock:
        prev = _faults.get(point)
        _faults[point] = spec
    try:
        yield spec
    finally:
        with _faults_lock:
            if prev is None:
                _faults.pop(point, None)
            else:
                _faults[point] = prev


# --------------------------------------------------------------------------
# chaos harness (seeded, probabilistic, schedulable fault points)
# --------------------------------------------------------------------------

class ChaosMonkey:
    """Handle yielded by :func:`chaos`: per-point hit and fire counters
    (``hits[point]`` = times the armed point was reached, ``fired[point]``
    = times it actually injected a fault/delay)."""

    def __init__(self, seed):
        self.seed = seed
        self.hits = {}
        self.fired = {}
        self._lock = threading.Lock()

    def _record(self, point, fire):
        with self._lock:
            self.hits[point] = self.hits.get(point, 0) + 1
            if fire:
                self.fired[point] = self.fired.get(point, 0) + 1
        if fire:
            # black-box the injection: a chaos-soak postmortem dump
            # names every fault point that actually fired
            _CHAOS_FIRED.inc(labels=(point,))
            _flightrec().record("chaos", point=point, seed=self.seed)

    def total_fired(self):
        with self._lock:
            return sum(self.fired.values())


def _chaos_spec(point, cfg, monkey):
    """Build one armed-point callable from a per-point config dict:
    ``p`` (fire probability per hit), ``after`` (skip the first N hits),
    ``every`` (deterministic: fire on every Nth hit, overriding p),
    ``times`` (stop after N fires; -1 unlimited), ``delay`` (inject a
    stall of that many seconds instead of raising), ``exc`` (exception
    class/instance to raise). Each point draws from its OWN seeded RNG
    stream so arming more points never perturbs another point's
    pattern."""
    p = float(cfg.get("p", 1.0))
    after = int(cfg.get("after", 0))
    every = cfg.get("every")
    times = int(cfg.get("times", -1))
    delay = cfg.get("delay")
    exc = cfg.get("exc", FaultInjected)
    rng = random.Random(f"{monkey.seed}/{point}")
    state = {"hits": 0, "fires": 0}
    lock = threading.Lock()

    def _fire(pt, context):
        with lock:
            state["hits"] += 1
            hit = state["hits"]
            draw = rng.random()       # always drawn: keeps the stream
            if hit <= after:          # aligned whether or not we fire
                fire = False
            elif times >= 0 and state["fires"] >= times:
                fire = False
            elif every is not None:
                fire = (hit - after) % int(every) == 0
            else:
                fire = draw < p
            if fire:
                state["fires"] += 1
        monkey._record(pt, fire)
        if not fire:
            return None
        if delay:
            time.sleep(float(delay))
            return None
        if isinstance(exc, type):
            return exc(f"fault injected at {pt}")
        return exc

    return {"exc": _fire, "remaining": -1, "fired": 0}


@contextmanager
def chaos(points, p=1.0, seed=None, exc=FaultInjected, times=-1,
          after=0, every=None, delay=None):
    """Arm MANY fault points at once with seeded, probabilistic,
    schedulable behavior — the serving chaos harness.

    ``points`` is a point name, an iterable of names, or a dict mapping
    name -> per-point overrides (any of ``p``/``after``/``every``/
    ``times``/``delay``/``exc``); the keyword arguments are the
    defaults every point inherits. ``seed`` None reads
    ``FLAGS_chaos_seed``. Determinism: each point owns an RNG seeded
    from ``(seed, point)``, so a single-threaded test replays the exact
    same fire pattern run after run, and adding a point never shifts
    another's stream (under concurrency the per-point pattern stays
    fixed; which REQUEST absorbs each fault depends on scheduling).

    Yields a :class:`ChaosMonkey` with per-point hit/fire counters.
    """
    if seed is None:
        from .flags import flag
        seed = flag("chaos_seed")
    if isinstance(points, str):
        points = {points: {}}
    elif not isinstance(points, dict):
        points = {pt: {} for pt in points}
    monkey = ChaosMonkey(seed)
    defaults = {"p": p, "after": after, "every": every, "times": times,
                "delay": delay, "exc": exc}
    prev = {}
    with _faults_lock:
        for pt, overrides in points.items():
            cfg = dict(defaults)
            cfg.update(overrides or {})
            prev[pt] = _faults.get(pt)
            _faults[pt] = _chaos_spec(pt, cfg, monkey)
    try:
        yield monkey
    finally:
        with _faults_lock:
            for pt, old in prev.items():
                if old is None:
                    _faults.pop(pt, None)
                else:
                    _faults[pt] = old
