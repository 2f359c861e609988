"""Request queue + dynamic micro-batcher.

Clipper/ORCA-style adaptive batching for the TPU serving runtime: single
requests (each carrying a small leading-dim batch of examples) are
coalesced into padded device batches under ``max_batch_size`` /
``batch_timeout_ms``. Requests only share a batch when their PER-EXAMPLE
signature (trailing dims + dtype per feed) matches; total rows are
padded up to the next power-of-two bucket so at most 2x padding waste
and a bounded set of compiled shapes.

Admission control lives in ``RequestQueue.put``: a hard queue-depth
limit (backpressure -> ``ServerOverloadedError``), per-request deadlines
(``DeadlineExceededError`` — checked at admission, again when the batch
is formed, and a third time right before execution), and load-shedding
through a ``resilience.CircuitBreaker``: sustained overload/engine
failures open the breaker, and while it is open requests are refused in
O(1) without touching the queue.

Priority admission: every request carries a priority CLASS —
``interactive`` (the default), ``batch``, ``best_effort`` — and the
queue serves higher classes first (FIFO within a class). Under
backpressure the LOWEST class sheds first: a full queue evicts its
youngest lowest-class entry (typed ``ServerOverloadedError``) to admit
a strictly-higher-class arrival, and entries whose deadline expired
WHILE QUEUED are failed typed immediately instead of dequeuing into a
doomed micro-batch (``serving_expired_in_queue_total``).
"""
import threading
import time
from collections import deque, namedtuple

import numpy as np

from .metrics import (record_class_shed, record_class_done,
                      record_expired_in_queue, record_spec_accept_ratio)
from ..observability import tracing as _trace
from ..observability.recorder import flight_recorder as _flightrec
from ..resilience import (CircuitBreaker, CircuitOpenError, WatchdogTimeout,
                          maybe_fail, run_with_watchdog)

# priority classes, highest first: under overload the server sheds
# best_effort, then batch, and protects interactive (the brownout
# ladder follows the same order)
PRIORITIES = ("interactive", "batch", "best_effort")
_PRIORITY_RANK = {p: i for i, p in enumerate(PRIORITIES)}


def priority_rank(priority):
    """Validated rank (0 = highest) for a priority-class name; None
    means the default class."""
    if priority is None:
        return 0
    try:
        return _PRIORITY_RANK[priority]
    except KeyError:
        raise ValueError(
            f"unknown priority class {priority!r} — one of "
            f"{PRIORITIES}") from None


def remaining_budget_ms(budget_ms, t0, now=None):
    """Deadline budget still unspent at ``now`` in ms (may be <= 0 =
    spent) — the ONE copy of the propagation arithmetic shared by the
    client's re-send/hedge rewrites and the router's hop forwarding,
    so the two tiers' accounting can never drift."""
    return float(budget_ms) \
        - ((time.monotonic() if now is None else now) - t0) * 1e3


class ServingError(RuntimeError):
    """Base class for serving-runtime request failures."""


class DeadlineExceededError(ServingError):
    """The request's deadline passed before it reached the chip. Carries
    ``deadline_ms`` (the budget) and ``waited_ms`` (time actually spent
    queued when the expiry was detected)."""

    def __init__(self, message, deadline_ms=None, waited_ms=None):
        super().__init__(message)
        self.deadline_ms = deadline_ms
        self.waited_ms = waited_ms


class ServerOverloadedError(ServingError):
    """Admission refused: queue at depth limit or load-shed breaker open.
    Clients should back off (the wire server maps this to an
    ``etype: "Overloaded"`` reply)."""


class ServerShutdownError(ServerOverloadedError):
    """The server is draining or stopping: admission is closed, and
    requests still queued at ``stop()`` are failed with this
    immediately rather than left to ride out their own timeouts.
    Subclasses :class:`ServerOverloadedError` so pre-existing overload
    handlers (back off, try another replica) keep working; the wire
    server maps it to ``etype: "Shutdown"``."""


class RequestCancelledError(ServingError):
    """The request was cancelled by its client (hedged-request loser:
    the twin that lost the race is cancelled by request id so a hedged
    pair never executes twice)."""


class InternalServerError(ServingError):
    """Client-side face of an ``etype: "Internal"`` (or unrecognized)
    error reply: the server deliberately answered with a failure the
    wire protocol does not map to a more specific class. Still a
    ServingError — a caller catching the typed serving surface sees
    every reply-borne failure."""


class BadRequestError(ServingError):
    """Client-side face of an ``etype: "BadRequest"`` reply: the server
    validated the request and refused it (missing feeds, malformed
    prompt). Distinguishable from server faults — retrying without
    fixing the input will not help."""


def _record_stage_span(req, name, now, stats, stage):
    """One copy of the request-stage arithmetic for both batchers: the
    stage began at enqueue and ends NOW (monotonic). Its length goes to
    the ``stage`` histogram of ``stats`` (where the batcher has any)
    and, re-based onto the profiler's perf_counter clock, to a span
    ``name``: for every generate request (under its ``span_root``),
    for a sampled infer request (under the client's context)."""
    lasted = now - req.t_enqueue
    if stats:
        stats.hist[stage].observe(lasted)
    root = getattr(req, "span_root", req.trace)
    if root is None:
        return
    pc = time.perf_counter()
    _trace.record_child(name, pc - lasted, pc, root,
                        getattr(req, "span_attrs", None))


class Request:
    """One in-flight prediction request.

    ``feeds``: {name: np.ndarray}, every array with a leading example
    dim (shape ``(rows, *example_shape)``); all feeds must agree on
    ``rows``. The response is delivered through ``wait()`` ->
    ``result`` (list of np arrays, one per fetch target) or raises the
    recorded error.
    """

    __slots__ = ("feeds", "rows", "example_sig", "deadline_at",
                 "deadline_ms", "t_enqueue", "t_flush", "result", "error",
                 "_done", "trace", "priority", "rank")

    def __init__(self, feeds, deadline_ms=None, priority=None):
        self.feeds = {n: np.ascontiguousarray(a) for n, a in feeds.items()}
        if not self.feeds:
            raise ValueError("request has no feeds")
        rows = {a.shape[0] if a.ndim else 1 for a in self.feeds.values()}
        if len(rows) != 1:
            raise ValueError(
                f"feeds disagree on the leading example dim: "
                f"{ {n: a.shape for n, a in self.feeds.items()} }")
        self.rows = rows.pop()
        if self.rows < 1:
            raise ValueError("request carries zero examples")
        self.example_sig = tuple(sorted(
            (n, tuple(a.shape[1:]), str(a.dtype))
            for n, a in self.feeds.items()))
        self._init_lifecycle(deadline_ms, priority)

    def _init_lifecycle(self, deadline_ms, priority=None):
        """Deadline/event/result bookkeeping shared with subclasses that
        don't carry an infer feeds dict (GenerationRequest)."""
        self.rank = priority_rank(priority)
        self.priority = PRIORITIES[self.rank]
        self.deadline_ms = deadline_ms
        now = time.monotonic()
        self.t_enqueue = now
        self.t_flush = None
        self.deadline_at = (now + deadline_ms / 1e3
                            if deadline_ms else None)
        self.result = None
        self.error = None
        self._done = threading.Event()
        # request-scoped trace context: the server's connection handler
        # (or any caller) installs one via tracing.ambient() before
        # admission; stage spans (queue/pad/execute/decode) parent here
        self.trace = _trace.current()

    # -- lifecycle --------------------------------------------------------
    def expired(self, now=None):
        return (self.deadline_at is not None
                and (now or time.monotonic()) > self.deadline_at)

    def expire(self, now=None, where="queue"):
        now = now or time.monotonic()
        waited = (now - self.t_enqueue) * 1e3
        self.set_error(DeadlineExceededError(
            f"request deadline of {self.deadline_ms:.1f}ms exceeded in "
            f"{where} after {waited:.1f}ms",
            deadline_ms=self.deadline_ms, waited_ms=waited))

    def set_result(self, result):
        self.result = result
        self._done.set()

    def set_error(self, exc):
        self.error = exc
        self._done.set()

    def done(self):
        return self._done.is_set()

    def wait(self, timeout=None):
        """Block until the reply is in; returns the fetch list or raises
        the recorded error. ``timeout`` None waits forever."""
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"no reply within {timeout}s (request still in flight)")
        if self.error is not None:
            raise self.error
        return self.result


class RequestQueue:
    """Bounded priority queue with admission control. ``put`` is the
    single gate every request passes: breaker check (load shed), depth
    check (backpressure, lowest priority class shed first),
    deadline-already-passed check. ``get`` is consumed by the batchers
    only; it serves the highest class first (FIFO within a class) and
    evicts entries whose deadline expired while queued — they fail
    typed immediately instead of riding into a doomed batch."""

    def __init__(self, max_depth=None, breaker=None, stats=None):
        if max_depth is None:
            from ..flags import flag
            max_depth = flag("serving_queue_depth")
        self.max_depth = int(max_depth)
        # one FIFO per priority rank; depth/backpressure span all three
        self._items = {r: [] for r in range(len(PRIORITIES))}
        self._cv = threading.Condition()
        self._closed = False
        self._draining = False
        # flight-recorder admission sampling: per-outcome counters
        self._adm_lock = threading.Lock()
        self._adm_counts = {}
        self.stats = stats
        self.expired_in_queue = 0
        self.priority_evictions = 0
        if breaker is None:
            from ..flags import flag
            breaker = CircuitBreaker(
                endpoint="serving-admission",
                failure_threshold=flag("serving_shed_failures"),
                reset_timeout=flag("serving_shed_reset_secs"))
        self.breaker = breaker

    def __len__(self):
        with self._cv:
            return sum(len(q) for q in self._items.values())

    def _depth_locked(self):
        return sum(len(q) for q in self._items.values())

    def _sweep_expired_locked(self, now):
        """Drop every queued entry whose deadline already passed;
        returns them (the caller fails them OUTSIDE the lock — a
        waiter's callback must not run under ``_cv``)."""
        dead = []
        for q in self._items.values():
            live = []
            for req in q:
                if req.done():
                    continue           # abandoned while queued
                if req.expired(now):
                    dead.append(req)
                else:
                    live.append(req)
            q[:] = live
        return dead

    def _fail_expired(self, dead):
        if not dead:
            return
        self.expired_in_queue += len(dead)
        record_expired_in_queue(len(dead))
        for req in dead:
            if self.stats:
                self.stats.bump("shed_deadline")
            req.expire(where="queue")

    def _record_admission(self, outcome, **fields):
        """Flight-record one admission outcome, SAMPLED per outcome
        (first, then every 64th): at production QPS — shed storms
        included — a per-request event would turn the ring over in
        under a second and evict exactly the rare events (restarts,
        chaos, non-finite) the black box exists to keep. The cumulative
        per-outcome count rides every sampled event, so the dump still
        quantifies a storm it didn't record request-by-request."""
        with self._adm_lock:
            n = self._adm_counts.get(outcome, 0) + 1
            self._adm_counts[outcome] = n
        if n == 1 or n % 64 == 0:
            _flightrec().record("admission", outcome=outcome, n=n,
                                **fields)

    def put(self, req, max_depth=None):
        """Admit ``req`` or raise ServerOverloadedError /
        DeadlineExceededError. Never blocks — backpressure is a fast
        refusal, not a slow accept (the client owns retry policy).

        Under backpressure the lowest class sheds first: expired
        entries are swept out, then — if the queue is still full — the
        youngest entry of a strictly LOWER class than ``req`` is
        evicted (typed) to make room; only when no lower-class victim
        exists is ``req`` itself refused. ``max_depth`` overrides the
        queue's depth limit for this one admission (the brownout ladder
        shrinks admission for degraded classes without touching
        interactive traffic)."""
        maybe_fail("serving.admit")
        depth_cap = self.max_depth if max_depth is None \
            else min(int(max_depth), self.max_depth)
        try:
            self.breaker.before_call()
        except CircuitOpenError as e:
            if self.stats:
                self.stats.bump("shed_overload")
            record_class_shed(req.priority)
            self._record_admission("shed_breaker")
            raise ServerOverloadedError(
                f"load shedding: {e}") from e
        if req.expired():
            self.breaker.release_probe()    # not the server's fault
            if self.stats:
                self.stats.bump("shed_deadline")
            self._record_admission("shed_deadline",
                                   deadline_ms=req.deadline_ms)
            req.expire(where="admission")
            raise req.error
        dead, victim = [], None
        genuinely_full = False
        with self._cv:
            if self._closed or self._draining:
                self.breaker.release_probe()
                self._record_admission("shutdown")
                raise ServerShutdownError(
                    "server is draining — admission closed"
                    if self._draining and not self._closed
                    else "server is shutting down")
            if self._depth_locked() >= depth_cap:
                # expired entries must not hold a slot against live
                # traffic: sweep before judging the depth
                dead = self._sweep_expired_locked(time.monotonic())
            if self._depth_locked() >= depth_cap:
                genuinely_full = self._depth_locked() >= self.max_depth
                # victim eviction only for UN-capped admissions at a
                # genuinely full queue: a request admitted under a
                # shrunken per-call cap (the brownout ladder halving a
                # degraded class's admission) is refused outright — a
                # degraded class must never evict lower-class work the
                # queue already admitted, full or not
                if max_depth is None and genuinely_full:
                    # shed the lowest class first: evict the YOUNGEST
                    # entry of the lowest populated class strictly
                    # below req's (the youngest has waited least —
                    # least sunk cost to throw away)
                    for r in range(len(PRIORITIES) - 1, req.rank, -1):
                        if self._items[r]:
                            victim = self._items[r].pop()
                            self.priority_evictions += 1
                            break
                overloaded = victim is None
            else:
                overloaded = False
            if not overloaded:
                self._items[req.rank].append(req)
                self._cv.notify()
        self._fail_expired(dead)
        if victim is not None:
            if self.stats:
                self.stats.bump("shed_overload")
            record_class_shed(victim.priority)
            self._record_admission("shed_evicted",
                                   victim=victim.priority)
            victim.set_error(ServerOverloadedError(
                f"queued {victim.priority} request shed to admit "
                f"{req.priority} traffic under backpressure — back off "
                f"and retry"))
        if overloaded:
            if genuinely_full:
                self.breaker.record_failure()
            else:
                # refused by an ARTIFICIAL per-call cap (brownout
                # shrinking a degraded class) with global capacity to
                # spare: not the server's fault — the load-shed
                # breaker must not open and start refusing the
                # interactive traffic the ladder exists to protect
                self.breaker.release_probe()
            if self.stats:
                self.stats.bump("shed_overload")
            record_class_shed(req.priority)
            self._record_admission("shed_overload", depth=depth_cap)
            raise ServerOverloadedError(
                f"request queue at depth limit ({depth_cap}); "
                f"retry with backoff")
        self.breaker.record_success()
        if self.stats:
            self.stats.bump("requests_admitted")
        self._record_admission("admitted", rows=req.rows)
        return req

    def get(self, timeout=None, accept=None):
        """Pop the oldest request of the HIGHEST populated class, or
        None on timeout/close. ``accept(req)``, where given, is asked
        about that request under the queue's lock: refused, it stays
        where it was, first of its class, and None is returned, so a
        request the caller has no room for yet is never outside the
        queue (drain, close and the deadline sweep go on seeing it).
        Entries whose deadline expired (or were
        abandoned) while queued are failed typed as they reach the
        front — a doomed request must not burn a micro-batch slot —
        and the pop continues to the next live entry. Cost is
        amortized O(1): only entries actually removed are examined
        (the full sweep runs on the put-when-full path, where the
        depth scan is already being paid)."""
        maybe_fail("serving.queue")
        dead, out, refused = [], None, False
        with self._cv:
            if not self._depth_locked():
                self._cv.wait(timeout)
            now = time.monotonic()
            for r in range(len(PRIORITIES)):
                q = self._items[r]
                while q:
                    req = q.pop(0)
                    if req.done():          # abandoned while queued
                        continue
                    if req.expired(now):
                        dead.append(req)
                        continue
                    if accept is not None and not accept(req):
                        q.insert(0, req)
                        refused = True
                    else:
                        out = req
                    break
                if out is not None or refused:
                    break
        self._fail_expired(dead)
        return out

    def quiesce(self):
        """Stop admitting (``put`` raises :class:`ServerShutdownError`)
        but keep everything already queued flowing to the batcher — the
        drain() half of shutdown. Idempotent."""
        with self._cv:
            self._draining = True

    def close(self):
        """Stop admitting; fail whatever is still queued IMMEDIATELY
        with the typed shutdown error (a queued request must never be
        left to ride out its own timeout against a dead server)."""
        with self._cv:
            self._closed = True
            drained = [req for r in range(len(PRIORITIES))
                       for req in self._items[r]]
            for q in self._items.values():
                q.clear()
            self._cv.notify_all()
        for req in drained:
            req.set_error(ServerShutdownError(
                "server shut down with the request still queued"))


class GenerationRequest(Request):
    """One in-flight autoregressive generation request: a 1-D int prompt
    plus sampling knobs. Admission control (queue depth, deadline,
    breaker) is inherited from :class:`Request` — ``deadline_ms`` is
    token-level: it is re-checked between decode steps, so a request
    whose budget runs out mid-generation fails fast instead of holding
    its slot for the full ``max_new_tokens``.

    Disaggregated prefill/decode split (serving/fleet): with
    ``export_kv=True`` the request is prefill-ONLY — the prompt is
    prefilled and its first token sampled as usual, then the slot's KV
    blocks are serialized and delivered as the result instead of the
    row joining the decode bank. With ``kv=`` (a
    ``kvpool.export_slot`` payload) plus ``first_token=``, the request
    is the other half: it skips prefill entirely, streaming the
    migrated blocks into its slot and decoding from ``first_token``."""

    __slots__ = ("prompt", "max_new_tokens", "temperature", "top_k",
                 "eos_id", "out_tokens", "slot", "export_kv", "kv",
                 "first_token", "span_root")

    def __init__(self, prompt, max_new_tokens=32, temperature=0.0,
                 top_k=0, eos_id=None, deadline_ms=None,
                 export_kv=False, kv=None, first_token=None,
                 priority=None):
        prompt = np.asarray(prompt, dtype=np.int32).ravel()
        if prompt.size < 1:
            raise ValueError("generation request has an empty prompt")
        if int(max_new_tokens) < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if kv is not None and export_kv:
            raise ValueError("a request cannot both import (kv=) and "
                             "export (export_kv=True) KV state")
        if (kv is None) != (first_token is None):
            raise ValueError("kv= and first_token= come together: the "
                             "migrated payload is decoded FROM the "
                             "prefill-side sampled token")
        # no infer feeds dict: the prompt is the payload (feeds/
        # example_sig are MicroBatcher concepts; the DecodeBatcher
        # groups by slot, not signature)
        self.feeds = None
        self.rows = 1
        self.example_sig = None
        self._init_lifecycle(deadline_ms, priority)
        # the decode loop records serving/queue, serving/first_token and
        # serving/generate for EVERY request, under the client's trace
        # where it sent one and else under an id minted here
        self.span_root = _trace.request_root(self.trace)
        self.prompt = prompt
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.eos_id = None if eos_id is None else int(eos_id)
        self.out_tokens = []
        self.slot = None
        self.export_kv = bool(export_kv)
        self.kv = kv
        self.first_token = None if first_token is None \
            else int(first_token)

    @property
    def span_attrs(self):
        return {"prompt_len": int(self.prompt.size),
                "new_tokens": len(self.out_tokens)}


class SwapHandle:
    """Future for a hot weight swap scheduled onto the decode loop
    (:meth:`DecodeBatcher.request_swap`): ``wait()`` blocks until the
    loop applied the swap between decode steps (or failed); carries the
    measured admission pause in ``pause_ms``."""

    def __init__(self, apply_fn):
        self.apply_fn = apply_fn
        self.requested_at = time.monotonic()
        self.pause_ms = None
        self.error = None
        self._done = threading.Event()

    def apply(self):
        try:
            self.apply_fn()
            self.pause_ms = (time.monotonic() - self.requested_at) * 1e3
        except Exception as exc:  # noqa: BLE001 — relayed to the waiter
            self.error = exc
        self._done.set()

    def fail(self, exc):
        self.error = exc
        self._done.set()

    def wait(self, timeout=None):
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"weight swap not applied within {timeout}s (decode "
                f"rows still draining)")
        if self.error is not None:
            raise self.error
        return self.pause_ms


# a decode step that was sent and whose tokens are unread: ``sent`` is
# what GenerationEngine.collect_step reads, ``rows`` the {slot: request}
# that took part, ``t_sent`` when its engine/step span began
_Flight = namedtuple("_Flight", "sent rows t_sent")


class DecodeBatcher:
    """Continuous batching over a fixed bank of decode slots
    (ORCA-style iteration-level scheduling): one thread pulls
    GenerationRequests off the queue, prefills them into free slots,
    then steps the WHOLE bank one token at a time — new requests join
    between steps, finished rows (EOS / max_new_tokens / deadline) free
    their slot immediately for the next admission. Per-row state
    (position counter, current token, sampling config, done) lives
    here; the device-side slot caches live in the GenerationEngine.

    The loop runs one step ahead of its own reading: a step picks its
    tokens on the device and the next step takes them from there, so a
    round sends step t + 1 and then reads and delivers step t while
    the device runs t + 1. A row that ends at t on its ``eos_id``, a
    deadline, a cancel or a shed is found one step late: its row of
    t + 1 is computed and dropped. Rounds that cannot run ahead read
    their step in place: a round with speculative drafts (the drafter
    reads the newest tokens on the host) and a round in which a
    chunked prefill advances."""

    def __init__(self, queue, engine, stats=None, watchdog_s=None,
                 spec_k=None, drafter=None, brownout=None):
        from ..flags import flag
        if watchdog_s is None:
            watchdog_s = flag("serving_loop_watchdog_s")
        self.queue = queue
        self.engine = engine
        self.slots = engine.slots
        self.stats = stats
        self.watchdog_s = float(watchdog_s)
        # speculative decoding (FLAGS_decode_spec_k > 0): between
        # steps each live row proposes up to spec_k draft tokens
        # (drafter; FLAGS_decode_spec_mode picks the default) verified
        # in ONE span pass through the pool — rejection sampling keeps
        # the output distribution exact. The
        # draft depth is a LOAD knob: a windowed acceptance rate adapts
        # it globally (low acceptance = wasted verify compute) and the
        # brownout ladder shrinks it per-row for degraded classes
        # before their admission degrades.
        if spec_k is None:
            spec_k = flag("decode_spec_k")
        self.spec_k = int(spec_k)
        if self.spec_k > 0 and hasattr(engine, "gen"):
            # an architecture with no verify step refuses here, by name
            engine.gen._ensure_prog(f"verify_paged_{engine.pool.dtype}")
        self._drafter = drafter         # lazy: make_drafter on first use
        self.brownout = brownout
        self._accept_window = deque(maxlen=64)   # (accepted, proposed)
        self._spec_scope = f"decode-{id(self) & 0xffffff:x}"
        self._stop = threading.Event()
        self._thread = None
        self._free = list(range(self.slots))
        self._active = {}                       # slot -> request
        self._tok = np.zeros((self.slots,), np.int32)
        # rows whose next token the host has and the device has not
        # (admitted since the last step): they enter a step by _tok
        self._from_host = np.ones((self.slots,), bool)
        # the position the next step SENT writes: a plain step moves it
        # on as it is sent, a speculative one as it is delivered
        self._pos = np.zeros((self.slots,), np.int32)
        self._flight = None             # the step sent and not read yet
        self._t_tokens = 0.0            # when the last step's were read
        self._temp = np.zeros((self.slots,), np.float32)
        self._topk = np.zeros((self.slots,), np.int32)
        # supervision handles: the loop stamps `heartbeat` every
        # iteration; `_epoch` deposes a hung thread on restart (the old
        # loop notices the bump and exits without touching shared state)
        self.heartbeat = time.monotonic()
        self._epoch = 0
        self.consecutive_failures = 0
        self._swap = None                       # pending SwapHandle
        self._swap_lock = threading.Lock()
        self._admitting = 0     # popped from the queue, not yet in a slot
        self._admitting_reqs = []
        self._steps_since_sweep = 0             # paged-pool leak sweep
        # loop spans (observability.tracing): the trace every round of
        # this batcher belongs to, and the step index its rounds carry
        self._span_root = _trace.loop_root(
            f"loop:{id(self) & 0xffffff:x}")
        self._steps = 0
        # chunked-prefill states (engine.start_prefill dicts): rows
        # whose prompt is being ingested one chunk per decode round —
        # they hold a slot but are not yet in _active
        self._prefilling = []

    # -- lifecycle --------------------------------------------------------
    def start(self):
        self.heartbeat = time.monotonic()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="serving-decode-batcher")
        self._thread.start()
        return self

    def alive(self):
        return self._thread is not None and self._thread.is_alive()

    def inflight(self):
        """Rows being decoded PLUS requests mid-admission (popped from
        the queue but not yet in a slot — prefill compile can hold them
        there for seconds; drain() polls this to zero) PLUS rows mid
        chunked-prefill (slot held, prompt still ingesting)."""
        return len(self._active) + self._admitting \
            + len(self._prefilling)

    def spec_snapshot(self):
        """Speculative-decoding state for health()/dashboards: the
        configured depth, the window-adapted effective depth, and the
        windowed acceptance rate (None until any drafting happened)."""
        win = list(self._accept_window)
        proposed = sum(p for _, p in win)
        return {
            "spec_k": self.spec_k,
            "spec_k_effective": (self._adaptive_spec_k(self.spec_k)
                                 if self.spec_k > 0 else 0),
            "spec_accept_ratio": (
                round(sum(a for a, _ in win) / proposed, 4)
                if proposed else None),
        }

    def stop(self, timeout=5):
        self._stop.set()
        with self.queue._cv:
            self.queue._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout)
            if self._thread.is_alive():
                # loop thread owns the row state and is still inside a
                # long step (e.g. a first-shape compile); it fails the
                # in-flight requests itself on exit (_loop's finally),
                # so no client hangs even though we stop waiting here
                return
        release = getattr(self.engine, "release_slot", None)
        for slot, req in list(self._active.items()):
            if not req.done():
                req.set_error(ServerShutdownError(
                    "server stopped while the request was decoding"))
            if release is not None:
                release(slot)
        self._active.clear()
        for st in self._prefilling:
            if not st["req"].done():
                st["req"].set_error(ServerShutdownError(
                    "server stopped while the request was prefilling"))
            if release is not None:
                release(st["slot"])
        self._prefilling = []

    def restart(self, reason="supervisor restart"):
        """Replace a dead/hung loop thread: depose the old thread (epoch
        bump), fail every in-flight row with a typed error, reset the
        slot bank (row caches died with the old loop's state), start a
        fresh loop. Called by the LoopSupervisor only."""
        self._epoch += 1
        err = ServingError(f"decode loop restarted ({reason}); the "
                           f"request's decode state was lost")
        for req in list(self._active.values()):
            if not req.done():
                req.set_error(err)
                if self.stats:
                    self.stats.bump("requests_failed")
        for st in self._prefilling:
            if not st["req"].done():
                st["req"].set_error(err)
                if self.stats:
                    self.stats.bump("requests_failed")
        self._active.clear()
        self._prefilling = []
        self._free = list(range(self.slots))
        self._admitting = 0
        self._flight = None     # its rows were failed above
        self.engine.reset()
        with self._swap_lock:
            sw, self._swap = self._swap, None
        if sw is not None:
            sw.fail(ServingError(f"weight swap abandoned: {reason}"))
        self.consecutive_failures = 0
        self.start()

    # -- row lifecycle ----------------------------------------------------
    def _finish(self, req, error=None):
        slot = req.slot
        if slot is not None and slot in self._active:
            del self._active[slot]
            self._free.append(slot)
            # reset the freed slot's sampling config: a stale
            # temperature > 0 would force the full sampler program on
            # an otherwise all-greedy bank (the engine picks the argmax
            # fast path only when every row's temperature is <= 0)
            self._temp[slot] = 0.0
            self._topk[slot] = 0
            # paged pool: EOS/deadline/cancel/error all land here — the
            # row's KV blocks go back to the free list immediately
            release = getattr(self.engine, "release_slot", None)
            if release is not None:
                release(slot)
        if req.done():
            # abandoned request (e.g. the wire handler's wait budget
            # expired and set an error): the slot is reclaimed above,
            # nothing to deliver
            return
        if error is not None:
            req.set_error(error)
            if self.stats:
                self.stats.bump("requests_failed")
            return
        req.set_result([np.asarray(req.out_tokens, np.int32)])
        now = time.monotonic()
        record_class_done(req.priority, now - req.t_enqueue)
        _record_stage_span(req, "serving/generate", now, self.stats,
                           "total")
        if self.stats:
            self.stats.bump("requests_completed")

    def _deliver_token(self, req, tok):
        """Record one sampled token; finish the row on EOS or budget.
        Returns True while the row stays live."""
        if req.eos_id is not None and tok == req.eos_id:
            self._finish(req)
            return False
        req.out_tokens.append(tok)
        if len(req.out_tokens) == 1:
            _record_stage_span(req, "serving/first_token",
                               time.monotonic(), self.stats,
                               "first_token")
        if self.stats:
            self.stats.bump("tokens_generated")
        if len(req.out_tokens) >= req.max_new_tokens:
            self._finish(req)
            return False
        return True

    # -- speculative decoding ---------------------------------------------
    def _get_drafter(self):
        if self._drafter is None:
            from ..models.generation import make_drafter
            self._drafter = make_drafter(generator=self.engine.gen)
        return self._drafter

    def _adaptive_spec_k(self, k):
        """Effective draft depth from the windowed acceptance rate —
        the speculative analogue of the client's observed-p99 hedge
        delay: a measured signal replaces the configured constant once
        there is enough of it. Low acceptance means most of the verify
        span is wasted compute, so the depth backs off (never below 1:
        the window must keep refilling to observe recovery)."""
        proposed = sum(p for _, p in self._accept_window)
        if proposed < 32:
            return k            # not enough signal yet: trust the flag
        rate = sum(a for a, _ in self._accept_window) / proposed
        if rate >= 0.5:
            return k
        if rate >= 0.25:
            return max(k // 2, 1)
        return 1

    def _propose_drafts(self, k):
        """Draft proposals for every live row: np int32
        ``(drafts [slots, k], num_draft [slots])``. Per-row depth =
        the window-adapted global depth, shrunk by the brownout ladder
        for degraded priority classes, capped to the row's remaining
        token budget minus one (the verify step always emits at least
        one real token)."""
        drafts = np.zeros((self.slots, k), np.int32)
        nd = np.zeros((self.slots,), np.int32)
        k_eff = self._adaptive_spec_k(k)
        for slot, req in self._active.items():
            kr = k_eff
            if self.brownout is not None:
                kr = self.brownout.draft_depth(
                    priority_rank(req.priority), kr)
            kr = min(int(kr),
                     int(req.max_new_tokens) - len(req.out_tokens) - 1)
            if kr <= 0:
                continue
            ctx = np.concatenate([
                np.asarray(req.prompt, np.int32).reshape(-1),
                np.asarray(req.out_tokens, np.int32)])
            d = np.asarray(self._get_drafter().draft(ctx, kr),
                           np.int32).reshape(-1)[:kr]
            if d.size:
                drafts[slot, :d.size] = d
                nd[slot] = d.size
        return drafts, nd

    def _deliver_spec(self, out, acc, nd):
        """Deliver one verify step's emitted runs: row ``slot`` takes
        ``acc[slot]`` accepted drafts plus the correction/bonus token,
        stopping early on EOS/budget (later tokens of the run are
        dropped — their KV is garbage past the row's new position and
        is overwritten before it is ever attended). Updates the
        acceptance window, gauge, counters and flight events."""
        accepted = proposed = rejected = 0
        for slot in list(self._active):
            req = self._active[slot]
            if req.done():      # abandoned by its waiter
                self._finish(req)
                continue
            a, n = int(acc[slot]), int(nd[slot])
            accepted += a
            proposed += n
            if a < n:
                rejected += 1
                _flightrec().record("spec_rejected", slot=slot,
                                    proposed=n, accepted=a)
            alive = True
            for j in range(a + 1):
                alive = self._deliver_token(req, int(out[slot, j]))
                if not alive:
                    break
            if alive:
                self._pos[slot] += a + 1
                self._tok[slot] = int(out[slot, a])
        if self.stats:
            self.stats.bump("spec_steps")
            if proposed:
                self.stats.bump("spec_drafted", proposed)
            if accepted:
                self.stats.bump("spec_accepted", accepted)
            if rejected:
                self.stats.bump("spec_rejected", rejected)
        self._accept_window.append((accepted, proposed))
        win_p = sum(p for _, p in self._accept_window)
        if win_p:
            record_spec_accept_ratio(
                self._spec_scope,
                sum(a for a, _ in self._accept_window) / win_p)

    def _fail_active_if_bank_lost(self, exc):
        """After an engine failure, a donated-call loss of the slot bank
        takes every ACTIVE row's caches with it — fail those rows too
        rather than letting them silently decode against a rebuilt zero
        bank."""
        if not getattr(self.engine, "bank_lost", False):
            return
        self._flight = None     # a step of a pool that is gone
        if self._active:
            for req in list(self._active.values()):
                self._finish(req, ServingError(
                    f"decode slot bank lost to an engine failure "
                    f"({type(exc).__name__}: {exc}); the row's cache "
                    f"is unrecoverable"))

    def _check_deadlines(self, now):
        for slot in list(self._active):
            req = self._active[slot]
            if req.expired(now):
                waited = (now - req.t_enqueue) * 1e3
                if self.stats:
                    self.stats.bump("shed_deadline")
                self._finish(req, DeadlineExceededError(
                    f"token-level deadline of {req.deadline_ms:.1f}ms "
                    f"exceeded after {waited:.1f}ms with "
                    f"{len(req.out_tokens)} tokens generated",
                    deadline_ms=req.deadline_ms, waited_ms=waited))
        still = []
        for st in self._prefilling:
            req = st["req"]
            if not (req.done() or req.expired(now)):
                still.append(st)
                continue
            if not req.done():
                waited = (now - req.t_enqueue) * 1e3
                if self.stats:
                    self.stats.bump("shed_deadline")
                req.set_error(DeadlineExceededError(
                    f"deadline of {req.deadline_ms:.1f}ms exceeded "
                    f"after {waited:.1f}ms mid chunked prefill",
                    deadline_ms=req.deadline_ms, waited_ms=waited))
            self.engine.release_slot(st["slot"])
            self._free.append(st["slot"])
        self._prefilling[:] = still

    # -- admission --------------------------------------------------------
    def _admit(self, epoch=None):
        """Take what the queue holds into free slots; returns how many
        requests it took."""
        try:
            return self._admit_inner(
                self._epoch if epoch is None else epoch)
        except BaseException:
            # a crash mid-collection (e.g. an injected queue fault on
            # the SECOND pop) must not silently drop the requests
            # already taken off the queue — _admit_inner parks them in
            # _admitting_reqs until they reach a slot
            for req in self._admitting_reqs:
                if not req.done():
                    req.set_error(ServingError(
                        "decode loop crashed during admission"))
                    if self.stats:
                        self.stats.bump("requests_failed")
            raise
        finally:
            self._admitting_reqs = []
            self._admitting = 0

    def _admit_inner(self, epoch):
        take = self._admitting_reqs
        # a round takes no more than its one prefill can hold beside the
        # weights and the pool, by the engine's count: the request that
        # would not fit stays in the queue and leads the next round
        fit = getattr(self.engine, "prefill_fit", None)

        def fits(req):
            sizes = [r.prompt.size for r in take]
            return fit(sizes + [req.prompt.size]) > len(sizes)

        while self._free and len(take) < len(self._free) \
                and not self._stop.is_set() and self._epoch == epoch:
            # block briefly only when the bank is idle and nothing was
            # taken yet; once rows are decoding, admission must not
            # stall the step loop
            timeout = 0.05 if not (self._active or take
                                   or self._flight) else 0
            req = self.queue.get(
                timeout=timeout, accept=fits if fit and take else None)
            if req is None:
                break
            now = time.monotonic()
            if req.done():              # abandoned while queued
                continue
            if req.expired(now):
                if self.stats:
                    self.stats.bump("shed_deadline")
                req.expire(now, where="decode-queue")
                continue
            try:
                check = getattr(self.engine, "admission_check", None)
                if check is not None:
                    # pending_tokens: prompts already accepted this
                    # round hold free blocks hostage — admission must
                    # not promise the same blocks twice
                    check(req.prompt.size, req.max_new_tokens,
                          pending_tokens=[r.prompt.size for r in take])
                elif req.prompt.size + req.max_new_tokens \
                        > self.engine.max_len:
                    raise BadRequestError(
                        f"prompt ({req.prompt.size} tokens) + "
                        f"max_new_tokens ({req.max_new_tokens}) exceeds "
                        f"the decode cache length {self.engine.max_len}")
            except ServerOverloadedError as exc:
                # paged pool exhausted: typed shed — the client backs
                # off and retries once finished rows return blocks
                req.set_error(exc)
                if self.stats:
                    self.stats.bump("shed_overload")
                continue
            except Exception as exc:  # noqa: BLE001 — BadRequest etc.
                req.set_error(exc)
                if self.stats:
                    self.stats.bump("requests_failed")
                continue
            _record_stage_span(req, "serving/queue", now, self.stats,
                               "queue")
            take.append(req)
            self._admitting = len(take)
        if take:
            with _trace.loop_span(
                    "serving/admit", rows=len(take),
                    prompt_max=int(max(r.prompt.size for r in take))):
                self._place(take, epoch)
        return len(take)

    def _place(self, take, epoch):
        """Prefill (or import) the requests ``_admit_inner`` took off
        the queue into free slots and deliver their first tokens."""
        if self._epoch != epoch:
            for req in take:
                if not req.done():
                    req.set_error(ServingError(
                        "decode loop restarted during admission"))
                    if self.stats:
                        self.stats.bump("requests_failed")
            return
        # migrated requests (kv=) admit through the KV-import path,
        # everything else prefills; failures are ISOLATED — the fresh
        # prefills admit as one batch, but each migrated payload admits
        # ALONE (validation is per-payload), so one poisoned migration
        # neither takes down the round's prefills nor its sibling
        # imports
        fresh = [r for r in take if getattr(r, "kv", None) is None]
        imported = [r for r in take if getattr(r, "kv", None) is not None]
        inc = getattr(self.engine, "incremental_prefill_enabled", None)
        if fresh and inc is not None and inc():
            # chunked-prefill admission (Orca/Sarathi): each prompt
            # claims a slot now but ingests one chunk per decode round,
            # interleaved with the bank's steps — a 2048-token prompt
            # no longer freezes every active row's token cadence for a
            # monolithic prefill
            for req in fresh:
                slot = self._free.pop()
                try:
                    st = self.engine.start_prefill(req, slot)
                except Exception as exc:  # noqa: BLE001 — typed
                    self._free.append(slot)
                    if not req.done():
                        req.set_error(exc)
                    if self.stats:
                        self.stats.bump("requests_failed")
                    continue
                req.slot = slot
                self._prefilling.append(st)
            fresh = []
        admit_imported = getattr(self.engine, "admit_imported", None)
        if imported and admit_imported is None:
            for req in imported:
                req.set_error(BadRequestError(
                    "this engine cannot admit migrated KV state"))
                if self.stats:
                    self.stats.bump("requests_failed")
            imported = []
        batches = ([(fresh, self.engine.admit)] if fresh else []) \
            + [([r], admit_imported) for r in imported]
        for group, admit in batches:
            slots = [self._free.pop() for _ in group]
            try:
                first = admit(group, slots)
            except Exception as exc:  # noqa: BLE001 — reach the clients
                for req in group:
                    req.set_error(exc)
                    if self.stats:
                        self.stats.bump("requests_failed")
                if self._epoch != epoch:
                    # deposed: _free/_active belong to the new loop
                    # thread — and the round's remaining taken requests
                    # will never be admitted; fail them all
                    self._fail_deposed(take)
                    return
                self._free.extend(slots)
                if isinstance(exc, BadRequestError):
                    # the request's own payload was refused (migrated
                    # KV geometry mismatch, ...) — a client error, not
                    # an engine fault: the loop breaker must not move
                    continue
                self.consecutive_failures += 1
                if self.stats:
                    self.stats.bump("engine_failures")
                self._fail_active_if_bank_lost(exc)
                continue
            if self._epoch != epoch:
                # deposed while blocked in the prefill (it eventually
                # returned): the restarted loop owns the slot bank —
                # fail EVERY taken request instead of registering any
                self._fail_deposed(take)
                return
            if group is not fresh and self.stats:
                self.stats.bump("kv_imports", len(group))
            for tok, req, slot in zip(first, group, slots):
                if self.stats:
                    self.stats.bump("generate_requests")
                if getattr(req, "export_kv", False):
                    self._finish_export(req, slot, int(tok))
                    continue
                self._join(req, slot, int(tok))

    def _join(self, req, slot, tok):
        """A prefilled (or imported) request joins the decode bank at
        ``slot`` with its first token, which the host holds."""
        req.slot = slot
        self._active[slot] = req
        self._pos[slot] = req.prompt.size
        self._temp[slot] = req.temperature
        self._topk[slot] = req.top_k
        self._tok[slot] = tok
        self._from_host[slot] = True
        self._deliver_token(req, tok)

    def _fail_deposed(self, take):
        """The loop was restarted while this (now deposed) thread held
        requests it had already popped from the queue: fail every one
        that hasn't finished — the restarted loop will never see them,
        and a silent drop would strand their clients until the wire
        wait budget."""
        for req in take:
            if not req.done():
                req.set_error(ServingError(
                    "decode loop restarted during admission; "
                    "the request's prefill was discarded"))
                if self.stats:
                    self.stats.bump("requests_failed")

    def _advance_prefill(self, epoch):
        """Advance the OLDEST chunked prefill by one chunk this decode
        round (round-robin via the list's pop/append) — prompt
        ingestion shares the loop with decode steps instead of stalling
        them. A finished prompt samples its first token and joins the
        decode bank exactly as a monolithic admit would (export_kv rows
        deliver their KV payload instead)."""
        if not self._prefilling:
            return
        st = self._prefilling.pop(0)
        req, slot = st["req"], st["slot"]
        if req.done():                  # abandoned mid-prefill
            self.engine.release_slot(slot)
            self._free.append(slot)
            return
        try:
            done = self.engine.prefill_chunk(st)
            tok = self.engine.finish_prefill(st) if done else None
        except Exception as exc:  # noqa: BLE001 — reach the client
            if self._epoch != epoch:
                return       # deposed: restart() owns the row state
            self.engine.release_slot(slot)
            self._free.append(slot)
            if not req.done():
                req.set_error(exc)
            if isinstance(exc, ServerOverloadedError):
                # pool pressure mid-prefill: typed shed, same
                # bookkeeping as the admission-time shed
                if self.stats:
                    self.stats.bump("shed_overload")
                return
            self.consecutive_failures += 1
            if self.stats:
                self.stats.bump("engine_failures")
                self.stats.bump("requests_failed")
            self._fail_active_if_bank_lost(exc)
            return
        if self._epoch != epoch:
            return
        if not done:
            self._prefilling.append(st)
            return
        if self.stats:
            self.stats.bump("generate_requests")
        if getattr(req, "export_kv", False):
            self._finish_export(req, slot, int(tok))
            return
        self._join(req, slot, int(tok))

    def _finish_export(self, req, slot, tok):
        """Deliver a prefill-only request (disaggregated split): the
        freshly prefilled slot's KV blocks are serialized as the result
        — ``first_token`` and the prompt length ride inside the payload
        — and the slot is freed immediately; the row never joins the
        decode bank (its decode runs on another replica)."""
        try:
            payload = self.engine.export_slot(slot)
        except Exception as exc:  # noqa: BLE001 — typed to the client
            self.engine.release_slot(slot)
            self._free.append(slot)
            if not req.done():
                req.set_error(exc)
                if self.stats:
                    self.stats.bump("requests_failed")
            return
        self.engine.release_slot(slot)
        self._free.append(slot)
        payload["first_token"] = tok
        payload["prompt_tokens"] = int(req.prompt.size)
        if req.done():          # abandoned while prefilling
            return
        req.set_result([payload])
        # NOT record_class_done: in a disaggregated fleet this is the
        # prefill HOP of one user generate — the decode half records
        # the class completion; counting both would double goodput and
        # dilute the gated per-class latency with half-request times
        if self.stats:
            self.stats.bump("kv_exports")
            self.stats.bump("requests_completed")
            self.stats.hist["total"].observe(
                time.monotonic() - req.t_enqueue)

    # -- hot weight swap ---------------------------------------------------
    def request_swap(self, apply_fn):
        """Schedule ``apply_fn`` (the weight swap) onto the decode loop:
        admission pauses (new requests stay QUEUED, not failed), the
        in-flight rows finish their generations on the OLD weights, and
        the swap applies atomically between decode steps once the bank
        is empty. Returns a :class:`SwapHandle`. If the loop is not
        running the swap applies inline (nothing is in flight). A swap
        requested while another is still pending is failed immediately
        (one reload at a time — the caller retries after the first)."""
        handle = SwapHandle(apply_fn)
        with self._swap_lock:
            if self._swap is not None:
                handle.fail(ServingError(
                    "another weight swap is already pending — one "
                    "reload at a time"))
                return handle
            parked = self.alive()
            if parked:
                self._swap = handle
        if not parked:
            handle.apply()
            return handle
        with self.queue._cv:
            self.queue._cv.notify_all()
        # the loop may have exited BETWEEN the liveness check and the
        # store (its exit path only fails a swap it could see): reclaim
        # the parked handle and apply inline — nothing is in flight
        with self._swap_lock:
            orphaned = not self.alive() and self._swap is handle
            if orphaned:
                self._swap = None
        if orphaned:
            handle.apply()
        return handle

    # -- core loop --------------------------------------------------------
    def _loop(self):
        epoch = self._epoch
        try:
            while not self._stop.is_set() and self._epoch == epoch:
                self.heartbeat = time.monotonic()
                with _trace.loop_span("serving/round",
                                      self._span_root) as rnd:
                    alive = self._round(epoch, rnd.attrs)
                    # an empty poll of the queue is no round: nobody
                    # wants 20 rows a second of an idle server
                    rnd.dropped = not rnd.attrs
                if not alive:
                    return
        finally:
            # rows still mid-generation when the loop exits (stop() or
            # a crash) must fail fast, not leave their clients waiting.
            # A DEPOSED thread (epoch moved on: restart() owns the row
            # state now) must not touch anything.
            if self._epoch == epoch:
                self._admitting = 0
                self._flight = None     # its rows fail here, unread
                release = getattr(self.engine, "release_slot", None)
                for slot, req in list(self._active.items()):
                    if not req.done():
                        req.set_error(ServerShutdownError(
                            "server stopped while the request was "
                            "decoding"))
                    if release is not None:
                        release(slot)
                self._active.clear()
                for st in self._prefilling:
                    if not st["req"].done():
                        st["req"].set_error(ServerShutdownError(
                            "server stopped while the request was "
                            "prefilling"))
                    if release is not None:
                        release(st["slot"])
                self._prefilling = []
                with self._swap_lock:
                    sw, self._swap = self._swap, None
                if sw is not None:
                    sw.fail(ServerShutdownError(
                        "decode loop exited with the weight swap "
                        "pending"))

    def _round(self, epoch, attrs):
        """One iteration of the loop: admit, advance a chunked prefill,
        send the bank's next step, read and deliver the one in flight.
        ``attrs`` are the ``serving/round`` span's: left empty, the
        iteration did nothing and is not recorded. Returns False once
        the loop is deposed."""
        sw = self._swap
        idle = not (self._active or self._prefilling or self._flight)
        if sw is not None:
            # a pending swap stops admission so the bank drains;
            # in-flight rows (decoding OR mid chunked-prefill)
            # keep running on the old weights, and a step still in
            # flight is read (below) before the swap applies
            if idle:
                sw.apply()
                with self._swap_lock:
                    if self._swap is sw:
                        self._swap = None
                return True
        else:
            admitted = self._admit(epoch)
            if admitted:
                attrs["admitted"] = admitted
            idle = not (self._active or self._prefilling or self._flight)
        if idle:
            return True
        self._check_deadlines(time.monotonic())
        # what the loop holds decides whether this round may run ahead:
        # a drafter reads the newest tokens on the host, and a chunked
        # prefill advances against a pool no step is writing
        in_place = self.spec_k > 0 or bool(self._prefilling)
        if in_place and self._flight is not None:
            if not self._step(epoch, attrs, {}, in_place):
                return self._epoch == epoch
        if self._prefilling:
            attrs["prefilling"] = len(self._prefilling)
            self._advance_prefill(epoch)
        if self._epoch != epoch:
            return False
        if not self._active and self._flight is None:
            return True
        if self.spec_k > 0:
            alive = self._spec_round(epoch, attrs)
        else:
            alive = self._step(epoch, attrs, self._prepare_rows(),
                               in_place)
        if not alive:
            return self._epoch == epoch
        # periodic paged-pool leak sweep: blocks held by slots
        # no longer active are a bug — reclaim + flight-record
        # them instead of bleeding capacity
        self._steps_since_sweep += 1
        if self._steps_since_sweep >= 256:
            self._steps_since_sweep = 0
            sweep = getattr(self.engine, "reclaim_leaks", None)
            if sweep is not None:
                sweep(list(self._active)
                      + [st["slot"] for st in self._prefilling])
        return True

    def _prepare_rows(self):
        """The rows of the next step, ``{slot: request}``, with blocks
        under the positions they write. A row whose last token is due
        from the step in flight by its own ``max_new_tokens`` takes no
        part. Allocation on append: a row the pool cannot grow is shed
        TYPED, before its step is sent, while the rest of the bank
        keeps decoding (its freed blocks unblock the next step's
        growth)."""
        flying = self._flight.rows if self._flight is not None else {}
        rows = {slot: req for slot, req in self._active.items()
                if len(req.out_tokens) + (flying.get(slot) is req)
                < req.max_new_tokens}
        if rows:
            with _trace.loop_span("serving/prepare_step") as prepared:
                shed = self.engine.prepare_step(
                    {slot: int(self._pos[slot]) for slot in rows})
                self._shed_rows(shed)
                prepared.attrs["shed"] = len(shed)
            for slot in shed:
                del rows[slot]
        return rows

    def _count_failure(self, exc):
        self.consecutive_failures += 1
        if self.stats:
            self.stats.bump("engine_failures")
            if isinstance(exc, WatchdogTimeout):
                self.stats.bump("watchdog_timeouts")

    def _round_attrs(self, attrs, live):
        """What a ``serving/round`` that sends a step says of it."""
        self._steps += 1
        attrs["step"] = self._steps
        attrs["live"] = live
        pool = self.engine.pool
        by_group = pool.blocks_in_use_by_group()
        attrs["blocks_in_use"] = sum(by_group.values())
        attrs["blocks_total"] = pool.capacity_blocks
        if "window" in by_group:
            attrs["blocks_in_use_full"] = by_group["full"]
            attrs["blocks_in_use_window"] = by_group["window"]

    def _step(self, epoch, attrs, rows, in_place):
        """Send ``rows`` as the bank's next step (none where empty),
        then read and deliver the step that was in flight: the device
        runs the one while the host reads the other. With nothing in
        flight the step just sent stays in flight, or is read at once
        where the round is ``in_place``. One ``engine/step`` span covers
        the send and the read. Returns False when the step failed (its
        rows were failed) or the loop was deposed meanwhile."""
        old, self._flight = self._flight, None
        if not rows and old is None:
            return True
        new = landed = None
        try:
            with _trace.loop_span(
                    "engine/step",
                    ahead=int(bool(rows) and old is not None)) as stepped:
                if rows:
                    slots = list(rows)
                    self._round_attrs(attrs, len(rows))
                    # one paged kernel call of this step: its grid,
                    # the steps its rows' walks take and the blocks
                    # they read (kernel_steps * blocks a step over
                    # live_blocks is the padding of the walks' tails)
                    stepped.attrs["grid_steps"] = self.engine.slots
                    stepped.attrs.update(self.engine.step_attrs(len(rows)))
                    stepped.attrs["kernel_steps"], \
                        stepped.attrs["live_blocks"] = \
                        self.engine.kernel_walk(self._pos[slots])
                    new = _Flight(self.engine.dispatch_step(
                        self._tok, self._pos, self._temp, self._topk,
                        from_host=self._from_host), rows, stepped.t0)
                    self._pos[slots] += 1
                    self._from_host[slots] = False
                    if old is not None and self.stats:
                        self.stats.bump("decode_steps_ahead")
                landed = old or (new if in_place else None)
                if landed is not None:
                    toks = self.engine.collect_step(
                        landed.sent, budget=self.watchdog_s or None)
                    stepped.attrs.update(self.engine.step_routing or {})
        except Exception as exc:  # noqa: BLE001
            if self._epoch != epoch:
                return False     # deposed mid-step: restart() owns
            self._count_failure(exc)            # the row state
            # the rows of the step that failed get its error; the pool
            # went with it (bank_lost), so a step sent after it is
            # dropped unread and whoever else is live fails typed
            failed = landed.rows if landed is not None else rows
            for slot, req in failed.items():
                if self._active.get(slot) is req:
                    self._finish(req, exc)
            self._fail_active_if_bank_lost(exc)
            return False
        if self._epoch != epoch:
            # deposed while blocked in the step (hung chip call
            # that eventually returned): the restarted loop owns
            # _active/_free now — do not touch them
            return False
        if landed is not new:
            self._flight = new
        if landed is None:
            return True
        attrs["collected"] = len(landed.rows)
        self.consecutive_failures = 0
        # from the last step's tokens to this step's (from its own send
        # where nothing was in flight before it): the inter-token
        # latency, any stall included, that the SLO monitor's default
        # p99 rule evaluates windowed
        t0 = max(landed.t_sent, self._t_tokens)
        self._t_tokens = stepped.t1
        for req in landed.rows.values():
            if req.trace is not None:
                # per-token spans for TRACED rows only (sampled at the
                # client edge)
                _trace.record_child("serving/decode", t0, stepped.t1,
                                    req.trace)
        if self.stats:
            self.stats.hist["token"].observe(stepped.t1 - t0)
            self.stats.observe_decode_step(len(landed.rows), self.slots)
        toks = toks.tolist()
        with _trace.loop_span("serving/deliver") as delivered:
            before = len(self._active)
            for slot, req in landed.rows.items():
                if self._active.get(slot) is not req:
                    continue    # ended since its step was sent
                if req.done():      # abandoned by its waiter
                    self._finish(req)
                    continue
                self._tok[slot] = toks[slot]
                self._deliver_token(req, toks[slot])
            delivered.attrs["finished"] = before - len(self._active)
        return True

    def _spec_round(self, epoch, attrs):
        """A round of speculative decoding, read in place: draft,
        cover the verify spans with blocks, verify, deliver the runs.
        Returns False when the step failed or the loop was deposed."""
        # speculative rows draft BEFORE the allocation pass so
        # the whole verify span [pos, pos + nd + 1) is covered
        # by blocks (and COW-duplicated when shared) up front
        drafts, nd = self._propose_drafts(self.spec_k)
        with _trace.loop_span("serving/prepare_step") as prepared:
            shed = self.engine.prepare_step(
                {slot: int(self._pos[slot]) for slot in self._active},
                widths={slot: int(nd[slot]) + 1
                        for slot in self._active})
            self._shed_rows(shed)
            prepared.attrs["shed"] = len(shed)
        if not self._active:
            return True
        traced = [r for r in self._active.values()
                  if r.trace is not None]
        self._round_attrs(attrs, len(self._active))
        try:
            with _trace.loop_span("engine/step", ahead=0) as stepped:
                live_mask = np.zeros((self.slots,), bool)
                live_mask[list(self._active)] = True
                out, acc = self.engine.spec_step(
                    self._tok, self._pos, self._temp,
                    self._topk, drafts, nd, live_mask,
                    budget=self.watchdog_s or None)
        except Exception as exc:  # noqa: BLE001
            if self._epoch != epoch:
                return False     # deposed mid-step: restart() owns
            self._count_failure(exc)            # the row state
            for req in list(self._active.values()):
                self._finish(req, exc)
            return False
        if self._epoch != epoch:
            return False        # deposed while blocked in the step
        self.consecutive_failures = 0
        for r in traced:
            _trace.record_child("serving/decode", stepped.t0,
                                stepped.t1, r.trace)
        if self.stats:
            self.stats.hist["token"].observe(stepped.t1 - stepped.t0)
            self.stats.observe_decode_step(attrs["live"], self.slots)
        with _trace.loop_span("serving/deliver") as delivered:
            before = len(self._active)
            self._deliver_spec(out, acc, nd)
            delivered.attrs["finished"] = before - len(self._active)
        return True

    def _shed_rows(self, shed):
        """Finish the rows the pool could not grow (``prepare_step``'s
        ``{slot: exception}``)."""
        for slot, exc in shed.items():
            req = self._active.get(slot)
            if req is None:
                continue
            if isinstance(exc, ServerOverloadedError):
                # overload shed, not a failure: same
                # bookkeeping as the admission-time shed
                # (shed_overload only, no requests_failed),
                # then reclaim the slot + its blocks
                if not req.done():
                    req.set_error(exc)
                if self.stats:
                    self.stats.bump("shed_overload")
                self._finish(req)
            else:
                self._finish(req, exc)


def next_bucket(rows, min_bucket=1):
    """Smallest power-of-two >= rows (>= min_bucket): bounded padding
    waste (< 2x) and a bounded universe of compiled shapes."""
    b = max(int(min_bucket), 1)
    rows = max(int(rows), 1)
    while b < rows:
        b <<= 1
    return b


class MicroBatcher:
    """Pulls requests off the queue, groups them by per-example
    signature, and flushes a group to ``execute_fn(requests)`` when it
    reaches ``max_batch_size`` rows or its oldest member has waited
    ``batch_timeout_ms``. Single execution thread: batches hit the chip
    serially, which is exactly what a single-TPU serving process wants
    (the chip is the bottleneck resource; concurrency lives in the
    connection threads)."""


    def __init__(self, queue, execute_fn, max_batch_size=None,
                 batch_timeout_ms=None, stats=None, watchdog_s=None):
        from ..flags import flag
        self.queue = queue
        self.execute_fn = execute_fn
        self.max_batch_size = int(max_batch_size
                                  if max_batch_size is not None
                                  else flag("serving_max_batch_size"))
        timeout_ms = (batch_timeout_ms if batch_timeout_ms is not None
                      else flag("serving_batch_timeout_ms"))
        self.batch_timeout_s = float(timeout_ms) / 1e3
        self.watchdog_s = float(watchdog_s if watchdog_s is not None
                                else flag("serving_loop_watchdog_s"))
        self.stats = stats
        self._stop = threading.Event()
        self._thread = None
        self._pending = {}   # sig -> {"reqs": [...], "rows": n, "flush_at": t}
        self.heartbeat = time.monotonic()
        self._epoch = 0
        self._executing = 0           # requests inside execute_fn right now
        self._ingesting = 0           # popped, not yet in _pending
        self.consecutive_failures = 0

    # -- lifecycle --------------------------------------------------------
    def start(self):
        self.heartbeat = time.monotonic()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="serving-microbatcher")
        self._thread.start()
        return self

    def alive(self):
        return self._thread is not None and self._thread.is_alive()

    def inflight(self):
        """Requests forming a batch, mid-ingest, or inside the engine
        right now (drain() polls this to zero)."""
        return (sum(len(ent["reqs"]) for ent in self._pending.values())
                + self._executing + self._ingesting)

    def stop(self, timeout=5):
        self._stop.set()
        with self.queue._cv:
            self.queue._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout)
            if self._thread.is_alive():
                # the loop thread owns _pending; it is still inside a
                # long execute (e.g. a first-request compile) — touching
                # the dict here would race it, and the in-flight requests
                # will still get their results when it finishes
                return
        # thread is down (or never started): fail anything still forming
        # so no client hangs
        for ent in self._pending.values():
            for req in ent["reqs"]:
                if not req.done():
                    req.set_error(ServerShutdownError(
                        "server stopped while the request was batching"))
        self._pending.clear()

    def restart(self, reason="supervisor restart"):
        """Replace a dead/hung loop thread: depose the old thread (epoch
        bump), fail the batches it was forming with a typed error, start
        a fresh loop. Called by the LoopSupervisor only."""
        self._epoch += 1
        err = ServingError(f"batcher loop restarted ({reason}); the "
                           f"request was failed mid-batch")
        for ent in self._pending.values():
            for req in ent["reqs"]:
                if not req.done():
                    req.set_error(err)
                    if self.stats:
                        self.stats.bump("requests_failed")
        self._pending = {}
        self.consecutive_failures = 0
        self.start()

    # -- core loop --------------------------------------------------------
    def _admit_to_batch(self, req, now):
        if req.expired(now):
            if self.stats:
                self.stats.bump("shed_deadline")
            req.expire(now, where="queue")
            return
        ent = self._pending.get(req.example_sig)
        if ent is None:
            ent = {"reqs": [], "rows": 0,
                   "flush_at": now + self.batch_timeout_s}
            self._pending[req.example_sig] = ent
        ent["reqs"].append(req)
        ent["rows"] += req.rows
        # a full group flushes IMMEDIATELY — never deferred to the drain
        # loop's end, so no signature's group can grow past
        # max_batch_size (+ the final request's own rows) no matter how
        # deep the queue backlog is
        if ent["rows"] >= self.max_batch_size:
            del self._pending[req.example_sig]
            self._flush(ent["reqs"], time.monotonic())

    def _flush_ready(self, now):
        for sig in list(self._pending):
            ent = self._pending[sig]
            if now >= ent["flush_at"]:
                del self._pending[sig]
                self._flush(ent["reqs"], now)

    def _flush(self, reqs, now):
        live = []
        for req in reqs:
            if req.expired(now):
                if self.stats:
                    self.stats.bump("shed_deadline")
                req.expire(now, where="batcher")
            else:
                req.t_flush = now
                _record_stage_span(req, "serving/queue", now,
                                   self.stats, "queue")
                live.append(req)
        if not live:
            return
        self._executing = len(live)
        try:
            # the watchdog bounds a hung chip call (or a wedged
            # first-shape compile): the batch's clients get a typed
            # WatchdogTimeout instead of hanging, and the loop survives
            # to serve the next batch
            if self.watchdog_s > 0:
                run_with_watchdog(self.execute_fn, self.watchdog_s, live,
                                  what="serving execute")
            else:
                self.execute_fn(live)
            self.consecutive_failures = 0
        except Exception as exc:  # noqa: BLE001 — must reach the clients
            self.consecutive_failures += 1
            if self.stats:
                self.stats.bump("engine_failures")
                if isinstance(exc, WatchdogTimeout):
                    self.stats.bump("watchdog_timeouts")
            for req in live:
                if not req.done():
                    req.set_error(exc)
            if self.stats:
                self.stats.bump("requests_failed", len(live))
        finally:
            self._executing = 0

    def _loop(self):
        epoch = self._epoch
        try:
            while not self._stop.is_set() and self._epoch == epoch:
                self.heartbeat = time.monotonic()
                now = time.monotonic()
                if self._pending:
                    wake = min(ent["flush_at"]
                               for ent in self._pending.values())
                    timeout = max(min(wake - now, 0.1), 0.0)
                else:
                    timeout = 0.1
                req = self.queue.get(timeout=timeout)
                if self._epoch != epoch:
                    # deposed while blocked (hung execute that finally
                    # returned, or a get that raced a restart): the new
                    # loop owns _pending — fail the popped request
                    # instead of batching it into someone else's state
                    if req is not None and not req.done():
                        req.set_error(ServingError(
                            "batcher loop restarted; the request was "
                            "failed mid-ingest"))
                        if self.stats:
                            self.stats.bump("requests_failed")
                    return
                if req is not None:
                    self._ingesting = 1
                    self._admit_to_batch(req, time.monotonic())
                    # drain whatever is already queued before sleeping
                    # again: a burst coalesces instead of going
                    # request-by-request (full groups flush inside
                    # _admit_to_batch as they fill). Timed-out groups are
                    # checked INSIDE the drain — sustained arrivals must
                    # not starve a rare signature's batch_timeout_ms
                    # while the hot signature churns. The heartbeat is
                    # stamped HERE too: sustained load keeps the thread
                    # in this inner loop, and a fresh heartbeat is what
                    # tells the supervisor busy != hung.
                    while not self._stop.is_set() \
                            and self._epoch == epoch:
                        self.heartbeat = time.monotonic()
                        nxt = self.queue.get(timeout=0)
                        if nxt is None:
                            break
                        now = time.monotonic()
                        self._admit_to_batch(nxt, now)
                        self._flush_ready(now)
                    self._ingesting = 0
                if self._epoch != epoch:
                    return
                self._flush_ready(time.monotonic())
        finally:
            self._ingesting = 0
            # batches still forming when the loop exits (stop() or a
            # crash) fail fast — mirrors the decode loop's exit fix. A
            # deposed thread (restart() bumped the epoch and owns
            # _pending now) must not touch anything.
            if self._epoch == epoch and (self._stop.is_set()
                                         or self._pending):
                for ent in self._pending.values():
                    for r in ent["reqs"]:
                        if not r.done():
                            r.set_error(ServerShutdownError(
                                "server stopped while the request was "
                                "batching"))
                self._pending = {}
