"""Thread-based prediction service over the PS wire framing.

``InferenceServer`` turns a saved inference model into a multi-client
service: connection threads speak the length-prefixed, HMAC-optional
frame protocol from ``distributed/wire.py`` (so ``WireTruncationError``
and the PR-1 retry semantics apply unchanged), admission happens on the
connection thread (backpressure is refused in O(1), never queued), and
one MicroBatcher thread feeds the chip padded batches.

Wire protocol (all values inside the typed wire universe):

    request  {"op": "infer", "feed": {name: ndarray},
              "deadline_ms": float|None}
    reply    {"ok": True, "fetch": (ndarray, ...), "batched": int}
           | {"ok": False, "etype": "DeadlineExceeded"|"Overloaded"
                                    |"Shutdown"|"Cancelled"|"Watchdog"
                                    |"BadRequest"|"Internal",
              "error": str}
    request  {"op": "stats"}   -> {"ok": True, "stats": {...}}
    request  {"op": "metrics"} -> {"ok": True, "metrics": str}
                                  (Prometheus text exposition of the
                                   process metrics registry)
    request  {"op": "debug_dump", "write": bool} -> {"ok": True,
                                  "events": [...], "path": str|None}
                                  (flight-recorder snapshot / dump)
    request  {"op": "ping"}    -> {"ok": True}
    request  {"op": "health"}  -> {"ok": True, "health": {state, queue
                                   depths, loop liveness, weights_version,
                                   kvpool_occupancy}}
    request  {"op": "cancel", "rid": str} -> {"ok": True, "cancelled": bool}
    request  {"op": "prefill", "tokens": ...} -> {"ok": True, "kv": {...}}
                                  (disaggregated split, prefill half:
                                   the prompt's KV blocks serialized out
                                   of the paged pool, first_token and
                                   prompt_tokens riding inside)
    request  {"op": "generate", ..., "kv": {...}, "first_token": int}
                                  (decode half: stream migrated blocks
                                   into this replica's pool and decode
                                   from first_token — no prefill runs)
    request  {"op": "reload_weights", "path": str} -> {"ok": True,
                                  "weights_version": int,
                                  "swap_pause_ms": float}

Deadline semantics: ``deadline_ms`` is a budget measured from ADMISSION
at the server (transit time is the client's problem; clocks never need
agreement). It is checked at admission, when the batch forms, and the
expiry reply carries how long the request actually waited. A request
that expires mid-execution still completes and returns its result — the
chip's work is never thrown away.

Tracing: ``infer``/``generate`` requests may carry a ``"trace"`` dict
(``{"tid", "sid"}``, minted client-side at ``FLAGS_trace_sample_rate``)
next to the existing ``rid``; the server threads a child context through
admission -> queue -> pad/compile/execute (and prefill/decode in the
slot bank), recording spans into the profiler's unified span table so
``tools/timeline.py`` renders one Chrome/Perfetto trace per request.

Resilience layer: the server walks a lifecycle state machine (warming ->
serving -> draining -> stopped, plus degraded while the loop supervisor's
breaker is open), ``drain()`` is the graceful half of shutdown (stop
admission, let in-flight work finish, then stop), ``reload_weights()``
swaps a manifest-verified checkpoint in without dropping traffic, and
``infer``/``generate`` requests may carry a client ``rid`` — a hedged
pair (Dean & Barroso, "The Tail at Scale") dedups onto ONE in-flight
execution and the loser is cancelled by rid.
"""
import contextlib
import socket
import threading
import time
import uuid
from collections import OrderedDict, deque

import numpy as np

from .batching import (BadRequestError, DeadlineExceededError,
                       DecodeBatcher, GenerationRequest,
                       InternalServerError, MicroBatcher, Request,
                       RequestCancelledError, RequestQueue,
                       ServerOverloadedError, ServerShutdownError,
                       priority_rank, remaining_budget_ms)
from .brownout import BrownoutController
from .engine import GenerationEngine, ServingEngine
from .metrics import ServingStats, record_class_shed
from .supervise import LoopSupervisor
from ..distributed.wire import (WireError, default_key, recv_frame,
                                send_frame)
from ..observability import tracing as _trace
from ..observability.metrics import render_metrics
from ..observability.recorder import flight_recorder as _flightrec
from ..resilience import (WatchdogTimeout, default_retry_budget,
                          retry_call)


class ServingConfig:
    """Knobs, defaulting from ``FLAGS_serving_*`` (env-overridable like
    every other flag): batching shape, queue depth, deadlines, cache
    caps, load-shed breaker tuning."""

    _FLAG_FIELDS = {
        "max_batch_size": "serving_max_batch_size",
        "batch_timeout_ms": "serving_batch_timeout_ms",
        "queue_depth": "serving_queue_depth",
        "default_deadline_ms": "serving_default_deadline_ms",
        "cache_entries": "serving_cache_entries",
        "cache_bytes": "serving_cache_bytes",
        "shed_failures": "serving_shed_failures",
        "shed_reset_secs": "serving_shed_reset_secs",
        "loop_watchdog_s": "serving_loop_watchdog_s",
    }

    def __init__(self, **overrides):
        from ..flags import flag
        for field, fname in self._FLAG_FIELDS.items():
            setattr(self, field, overrides.pop(field, None)
                    if field in overrides else flag(fname))
            if getattr(self, field) is None:
                setattr(self, field, flag(fname))
        if overrides:
            raise TypeError(f"unknown ServingConfig fields: "
                            f"{sorted(overrides)}")


class InferenceServer:
    """Multi-client serving front-end. In-process use:

        server = InferenceServer(model_dir).start()
        out = server.infer({"x": batch})          # or submit() for async

    Network use: ``start()`` also binds a socket (default loopback,
    OS-assigned port) and ``Client(server.endpoint)`` speaks the wire
    protocol. Authentication mirrors the PS transport: set
    ``PADDLE_PS_AUTH_KEY`` on both ends (required for non-loopback binds
    unless ``allow_insecure=True``)."""

    def __init__(self, model_dir=None, *, engine=None, generator=None,
                 decode_slots=None, config=None,
                 host="127.0.0.1", port=0, auth_key=None,
                 allow_insecure=False, kv_paged=None,
                 kv_pool_name="serving", slo_rules=None,
                 **config_overrides):
        if kv_paged not in (None, True):
            # the keyword is kept, inert, for the frozen benchmark
            # drivers that still pass kv_paged=True (ROADMAP Queue 3)
            raise ValueError(
                "kv_paged=False: PR 31 removed the dense KV bank — the "
                "paged pool is the one KV store behind serving; drop "
                "the keyword")
        self.config = config or ServingConfig(**config_overrides)
        self.stats_sink = ServingStats()
        if engine is None and (model_dir is not None
                               or generator is None):
            from .cache import ExecutableCache
            cache = ExecutableCache(max_entries=self.config.cache_entries,
                                    max_bytes=self.config.cache_bytes)
            engine = ServingEngine(model_dir, cache=cache,
                                   stats=self.stats_sink)
        elif engine is not None:
            engine.stats = engine.stats or self.stats_sink
        self.engine = engine          # None for a generation-only server
        self.queue = self.batcher = None
        if engine is not None:
            self.queue = RequestQueue(max_depth=self.config.queue_depth,
                                      stats=self.stats_sink)
            self.batcher = MicroBatcher(
                self.queue, self.engine.execute,
                max_batch_size=self.config.max_batch_size,
                batch_timeout_ms=self.config.batch_timeout_ms,
                stats=self.stats_sink,
                watchdog_s=self.config.loop_watchdog_s)
        # generation endpoint: a models.generation.GPTGenerator turns
        # the server into a token service — requests join a fixed bank
        # of decode slots (continuous batching, slot reuse on finish)
        self.gen_engine = self.gen_queue = self.decode_batcher = None
        if generator is not None:
            self.gen_engine = GenerationEngine(generator,
                                               slots=decode_slots,
                                               stats=self.stats_sink,
                                               pool_name=kv_pool_name)
            self.gen_queue = RequestQueue(
                max_depth=self.config.queue_depth, stats=self.stats_sink)
            self.decode_batcher = DecodeBatcher(
                self.gen_queue, self.gen_engine, stats=self.stats_sink,
                watchdog_s=self.config.loop_watchdog_s)
        # supervision: dead/hung loop threads are restarted with backoff;
        # repeated restarts open the breaker -> DEGRADED state (generate
        # sheds, ping/health/stats keep answering)
        self.supervisor = LoopSupervisor(
            stats=self.stats_sink,
            watchdog_s=self.config.loop_watchdog_s,
            on_degraded=lambda: self._set_state("degraded",
                                               only_from=("serving",)),
            on_recovered=lambda: self._set_state("serving",
                                                 only_from=("degraded",)))
        if self.batcher is not None:
            self.supervisor.add("microbatcher", self.batcher)
        if self.decode_batcher is not None:
            self.supervisor.add("decode", self.decode_batcher)
        # SLO guardrails: declarative rules (default: p99 inter-token
        # latency, queue-depth ratios, kvpool occupancy, optional MFU
        # floor) evaluated on a supervised loop; breach state rides
        # health() so the fleet Router penalizes a breached replica's
        # dispatch score. Built in start() (FLAGS_slo_monitor) so the
        # default rules bind the final queue/engine wiring.
        self._slo_rules = slo_rules
        self.slo_monitor = None
        # brownout ladder (FLAGS_serving_brownout): an SLO breach
        # degrades best_effort, then batch traffic (shed / capped
        # max_new_tokens / shrunken admission) BEFORE interactive; the
        # getter reads the live monitor so the ladder follows breaches
        # the moment start() wires the rules
        self.brownout = BrownoutController(
            lambda: (len(self.slo_monitor.breached())
                     if self.slo_monitor is not None else 0),
            scope=f"server-{id(self) & 0xffffff:x}")
        if self.decode_batcher is not None:
            # the ladder is also the speculative-decoding load knob:
            # the batcher shrinks degraded classes' draft depth per row
            self.decode_batcher.brownout = self.brownout
        self.host = host
        self.port = int(port)
        self._key = auth_key if auth_key is not None else default_key()
        self._allow_insecure = allow_insecure
        self._sock = None
        self._stop = threading.Event()
        self._threads = []
        self._conns = set()
        self._conns_lock = threading.Lock()
        self._started_at = time.monotonic()
        self._state_lock = threading.Lock()
        self._lifecycle = "created"
        self._weights_version = 1
        # request-id dedup (hedged pairs attach to ONE in-flight
        # execution); LRU-capped like the PS push-dedup table
        self._rids = OrderedDict()
        self._rids_lock = threading.Lock()
        self._rid_cap = 2048

    # -- lifecycle --------------------------------------------------------
    @property
    def endpoint(self):
        return f"{self.host}:{self.port}"

    @property
    def state(self):
        """Lifecycle state: created -> warming -> serving -> draining ->
        stopped, with serving <-> degraded while the supervisor breaker
        is open."""
        with self._state_lock:
            return self._lifecycle

    def _set_state(self, new, only_from=None):
        with self._state_lock:
            if self._lifecycle == "stopped":      # terminal
                return False
            if only_from is not None \
                    and self._lifecycle not in only_from:
                return False
            self._lifecycle = new
            return True

    def start(self, serve_network=True, warmup_batch_sizes=None,
              warmup_signature_file=None):
        """Start the batcher (always) and the socket front-end (unless
        ``serve_network=False`` for purely in-process serving). Optional
        warmup precompiles before the first byte of traffic."""
        self._set_state("warming")
        if (warmup_batch_sizes or warmup_signature_file) \
                and self.engine is not None:
            self.engine.warmup(batch_sizes=warmup_batch_sizes or (),
                               signature_file=warmup_signature_file)
        if self.batcher is not None:
            self.batcher.start()
        if self.decode_batcher is not None:
            self.decode_batcher.start()
        self.supervisor.start()
        if serve_network:
            loopback = (self.host.startswith("127.")
                        or self.host in ("localhost", "::1"))
            if not loopback and self._key is None \
                    and not self._allow_insecure:
                raise PermissionError(
                    f"refusing to bind the inference server on "
                    f"non-loopback {self.host}:{self.port} without "
                    f"authentication — set PADDLE_PS_AUTH_KEY (both "
                    f"ends) or pass allow_insecure=True")
            self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            self._sock.bind((self.host, self.port))
            self.port = self._sock.getsockname()[1]
            self._sock.listen(128)
            t = threading.Thread(target=self._accept_loop, daemon=True,
                                 name="serving-accept")
            t.start()
            self._threads.append(t)
        from ..flags import flag as _flag
        if _flag("slo_monitor") and self.slo_monitor is None:
            from ..observability import slo as _slo
            if callable(self._slo_rules):
                rules = self._slo_rules(self)   # rules need live wiring
            elif self._slo_rules is not None:
                rules = self._slo_rules         # [] = monitor off
            else:
                rules = _slo.default_server_rules(self)
            if rules:
                scope = self.endpoint if serve_network \
                    else f"server-{id(self) & 0xffffff:x}"
                self.slo_monitor = _slo.SloMonitor(rules,
                                                   scope=scope).start()
        self._set_state("serving", only_from=("warming", "created"))
        return self

    def drain(self, timeout=30.0):
        """Graceful shutdown: stop ADMISSION (new requests are refused
        with the typed ``ServerShutdownError``), let every in-flight
        micro-batch and decode row finish — token-level deadlines stay
        enforced, so the wait is bounded — then ``stop()``. ``ping``/
        ``stats``/``health`` keep answering throughout. Returns
        ``{"drained": bool, "remaining": n}`` (``remaining`` counts the
        requests abandoned to the hard stop when ``timeout`` ran out)."""
        self._set_state("draining")
        for q in (self.queue, self.gen_queue):
            if q is not None:
                q.quiesce()

        def _inflight():
            n = 0
            if self.queue is not None:
                n += len(self.queue)
            if self.batcher is not None:
                n += self.batcher.inflight()
            if self.gen_queue is not None:
                n += len(self.gen_queue)
            if self.decode_batcher is not None:
                n += self.decode_batcher.inflight()
            return n

        deadline = time.monotonic() + float(timeout)
        zero_streak = 0
        while time.monotonic() < deadline:
            if _inflight() == 0:
                # require consecutive zero reads: a request can sit
                # BETWEEN the queue and the batcher's pending dict for
                # an instant (popped, not yet admitted to a batch)
                zero_streak += 1
                if zero_streak >= 3:
                    break
            else:
                zero_streak = 0
            time.sleep(0.005)
        remaining = _inflight()
        self.stop()
        return {"drained": remaining == 0, "remaining": remaining}

    def stop(self):
        self._set_state("stopped")
        if self.slo_monitor is not None:
            self.slo_monitor.stop()
            self.slo_monitor = None
        self.supervisor.stop()
        self._stop.set()
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
        # close accepted connections too: a keep-alive client blocked in
        # recv_frame on the other end holds its handler thread forever
        # otherwise (the _stop flag is only re-checked between frames)
        with self._conns_lock:
            conns = list(self._conns)
        for c in conns:
            try:
                c.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                c.close()
            except OSError:
                pass
        if self.queue is not None:
            self.queue.close()
        if self.batcher is not None:
            self.batcher.stop()
        if self.gen_queue is not None:
            self.gen_queue.close()
        if self.decode_batcher is not None:
            self.decode_batcher.stop()
        for t in self._threads:
            t.join(timeout=2)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()

    # -- in-process client path -------------------------------------------
    def submit(self, feeds, deadline_ms=None, priority=None):
        """Admit a request (raises ServerOverloadedError /
        DeadlineExceededError at the door); returns the Request — call
        ``.wait()`` for the fetch list. ``priority`` is the admission
        class (interactive/batch/best_effort): lower classes shed first
        under backpressure and brownout."""
        if self.queue is None:
            raise ValueError("no inference model loaded — this server "
                             "only serves 'generate'")
        if deadline_ms is None and self.config.default_deadline_ms > 0:
            deadline_ms = self.config.default_deadline_ms
        _mnt, depth_cap = self._brownout_gate(priority)
        return self.queue.put(
            Request(feeds, deadline_ms=deadline_ms, priority=priority),
            max_depth=depth_cap)

    def _brownout_gate(self, priority, max_new_tokens=None):
        """The one copy of the brownout admission verdict for the
        infer and generate doors: raises the typed shed for degraded
        classes, else returns ``(max_new_tokens, depth_cap)`` with the
        class's cap/shrink applied."""
        shed, mnt, depth_cap = self.brownout.admission(
            priority_rank(priority), max_new_tokens=max_new_tokens,
            queue_depth=self.config.queue_depth)
        if shed:
            if self.stats_sink:
                self.stats_sink.bump("shed_overload")
            record_class_shed(priority)
            raise ServerOverloadedError(
                f"brownout level {self.brownout.level()}: "
                f"{priority} traffic is shed while the server works "
                f"off its SLO breach — retry later or upgrade the "
                f"request's class")
        return mnt, depth_cap

    def infer(self, feeds, deadline_ms=None, timeout=None,
              priority=None):
        return self.submit(feeds, deadline_ms=deadline_ms,
                           priority=priority).wait(timeout=timeout)

    def submit_generate(self, tokens, max_new_tokens=32, temperature=0.0,
                        top_k=0, eos_id=None, deadline_ms=None,
                        export_kv=False, kv=None, first_token=None,
                        priority=None):
        """Admit a generation request into the decode bank (admission
        control applies: queue depth, breaker, deadline). Returns the
        GenerationRequest — ``.wait()`` yields ``[np int32 tokens]``.

        ``FLAGS_serving_default_deadline_ms`` is NOT inherited here: it
        is a per-infer-batch budget, and a whole generation (prefill +
        up to max_new_tokens decode steps) lives on a different time
        scale — generation deadlines are per-request opt-in.

        Requests that could NEVER run are refused typed AT THE DOOR,
        before any queue wait or prefill compile: an overlong prompt
        (prompt + max_new_tokens > the decode cache length) and a
        request bigger than the whole KV pool both raise
        :class:`BadRequestError` (wire ``etype: "BadRequest"`` —
        retrying cannot help)."""
        if self.gen_queue is None:
            raise ValueError("no generator loaded — pass generator= to "
                             "InferenceServer to serve 'generate'")
        ntokens = np.asarray(tokens).size
        self.gen_engine.admission_check(
            ntokens, max_new_tokens, static_only=True)
        if kv is not None:
            # door check: the migrated payload must describe exactly
            # this prompt's prefill (position arithmetic depends on it)
            claimed = kv.get("tokens") if isinstance(kv, dict) else None
            if claimed != ntokens:
                raise BadRequestError(
                    f"migrated KV payload covers {claimed!r} tokens but "
                    f"the prompt has {ntokens} — prefill and decode "
                    f"halves disagree")
        if self.state == "degraded":
            if self.stats_sink:
                self.stats_sink.bump("shed_overload")
            raise ServerOverloadedError(
                "server is degraded (supervisor breaker open after "
                "repeated loop failures) — generation is shed; "
                "ping/health/stats still answer")
        # brownout ladder: a breached-SLO server sheds best_effort
        # (then batch) typed at the door, caps batch token budgets and
        # shrinks batch admission — interactive traffic degrades LAST
        max_new_tokens, depth_cap = self._brownout_gate(
            priority, max_new_tokens=int(max_new_tokens))
        return self.gen_queue.put(GenerationRequest(
            tokens, max_new_tokens=max_new_tokens,
            temperature=temperature, top_k=top_k, eos_id=eos_id,
            deadline_ms=deadline_ms, export_kv=export_kv, kv=kv,
            first_token=first_token, priority=priority),
            max_depth=depth_cap)

    def generate(self, tokens, max_new_tokens=32, temperature=0.0,
                 top_k=0, eos_id=None, deadline_ms=None, timeout=None,
                 priority=None):
        """Generate new tokens for one prompt; returns a 1-D np.int32
        array (EOS excluded)."""
        req = self.submit_generate(tokens, max_new_tokens=max_new_tokens,
                                   temperature=temperature, top_k=top_k,
                                   eos_id=eos_id, deadline_ms=deadline_ms,
                                   priority=priority)
        return req.wait(timeout=timeout)[0]

    def stats(self):
        """One snapshot across every stage: admission counters, stage
        latency histograms, batch occupancy, executable-cache hit/miss/
        evict, queue depth."""
        extra = {}
        if self.queue is not None:
            extra["queue_depth"] = len(self.queue)
            extra["breaker_state"] = self.queue.breaker.state
        if self.engine is not None:
            for k, v in self.engine.cache.stats().items():
                extra[f"cache_{k}"] = v
        if self.gen_queue is not None:
            extra["decode_queue_depth"] = len(self.gen_queue)
            extra["decode_free_slots"] = len(self.decode_batcher._free)
            for k, v in self.gen_engine.gen.cache.stats().items():
                extra[f"decode_cache_{k}"] = v
            for k, v in self.gen_engine.pool.stats().items():
                extra[f"kvpool_{k}"] = v
            # more than the model's weight layers where a stack is run
            # several times, a cache a (pass, layer) pair
            extra["kv_cache_layers"] = self.gen_engine.pool.num_layers
            # pool-sized copies XLA left in each executable that
            # takes the pool: the generator's by program kind, the
            # pool's own writers beside them (0 everywhere is the
            # stored layout doing its work)
            extra["pool_relayouts"] = dict(
                self.gen_engine.gen.pool_relayouts,
                **extra.pop("kvpool_relayouts"))
        extra["state"] = self.state
        extra["weights_version"] = self._weights_version
        # level() (not snapshot's cached value): the ladder is
        # evaluated lazily, and a server whose traffic stopped at
        # level 2 must report recovery once its breaches clear
        extra["brownout_level"] = self.brownout.level()
        extra["brownout_shed"] = self.brownout.snapshot()["shed"]
        for q, key in ((self.queue, "expired_in_queue"),
                       (self.gen_queue, "decode_expired_in_queue")):
            if q is not None:
                extra[key] = q.expired_in_queue
                extra[key.replace("expired_in_queue",
                                  "priority_evictions")] = \
                    q.priority_evictions
        return self.stats_sink.snapshot(extra=extra)

    def health(self):
        """Liveness/readiness snapshot, cheap enough for a poller: the
        lifecycle state, queue depths, per-loop thread liveness +
        heartbeat age + restart counts, the supervisor breaker, and the
        current weights version."""
        h = {
            "state": self.state,
            "weights_version": self._weights_version,
            "uptime_s": round(time.monotonic() - self._started_at, 3),
            "loops": self.supervisor.snapshot(),
            "breaker": self.supervisor.breaker.state,
            # the autoscaler's queue-ratio signal and the router's
            # hedge policy read these: degradation state + the depth
            # cap that turns probed queue depths into a ratio
            "brownout_level": self.brownout.level(),
            "queue_capacity": int(self.config.queue_depth),
        }
        if self.slo_monitor is not None:
            # the Router's dispatch-score penalty reads this: current
            # SLO breach state next to the load signals, one cheap probe
            breached = self.slo_monitor.breached()
            h["slo_breached"] = len(breached)
            if breached:
                h["slo_breached_rules"] = ",".join(sorted(breached))
        if self.queue is not None:
            h["queue_depth"] = len(self.queue)
        if self.gen_queue is not None:
            h["decode_queue_depth"] = len(self.gen_queue)
            h["decode_active_rows"] = self.decode_batcher.inflight()
            if self.decode_batcher.spec_k > 0:
                # the speculative load knob's observable state: depth +
                # windowed acceptance next to the load signals
                h.update(self.decode_batcher.spec_snapshot())
            pool = self.gen_engine.pool
            # the router's least-loaded dispatch reads this: live
            # kvpool occupancy next to the queue depths, one cheap
            # probe instead of a full stats()/metrics scrape
            cap = pool.capacity_blocks
            # blocks_in_use excludes cache-only blocks: a pool full
            # of EVICTABLE prefix blocks reads as empty to the
            # dispatch score (those blocks are reclaimable capacity
            # that doubles as cache value), with the evictable
            # count alongside for the affinity-aware observer
            h["kvpool_occupancy"] = round(
                pool.blocks_in_use() / cap, 4) if cap else 0.0
            h["kvpool_evictable_blocks"] = pool.cached_blocks()
        return h

    def reload_weights(self, path, timeout=120.0):
        """Hot weight reload (CheckFreq-style atomic swap, zero dropped
        traffic): verify + load a manifest-carrying checkpoint dir,
        build the new DEVICE snapshot off the serving loops, then swap —
        the infer engine swaps atomically between micro-batches, and the
        decode bank pauses ADMISSION (requests queue, nothing is failed)
        while in-flight generations FINISH ON THE OLD WEIGHTS, applying
        the swap between decode steps once the bank is empty.

        A corrupt/incomplete checkpoint raises
        ``CheckpointCorruptError`` (or ``ValueError`` on a shape/dtype
        mismatch) with the old snapshot untouched. Returns
        ``{"weights_version", "swap_pause_ms"}``."""
        if self.state == "stopped":
            raise ServerShutdownError("cannot reload weights on a "
                                      "stopped server")
        # load + verify EVERYTHING first: a failure in either engine's
        # checkpoint must leave both snapshots untouched
        new_state = staged = None
        if self.engine is not None:
            new_state = self.engine.load_state_snapshot(path)
        if self.gen_engine is not None:
            host = self.gen_engine.load_param_snapshot(path)
            staged = self.gen_engine.stage_params(host)
        pause_ms = 0.0
        if new_state is not None:
            self.engine.swap_state(new_state)
        if staged is not None:
            if self.decode_batcher is not None \
                    and self.decode_batcher.alive():
                handle = self.decode_batcher.request_swap(
                    lambda: self.gen_engine.apply_params(staged))
                pause_ms = handle.wait(timeout)
            else:
                self.gen_engine.apply_params(staged)
        with self._state_lock:
            self._weights_version += 1
            version = self._weights_version
        self.stats_sink.bump("weight_reloads")
        _flightrec().record("weight_reload", path=str(path),
                            weights_version=version,
                            swap_pause_ms=round(float(pause_ms or 0.0),
                                                3))
        return {"weights_version": version,
                "swap_pause_ms": round(float(pause_ms or 0.0), 3)}

    def record_signatures(self, path=None):
        if self.engine is None:
            raise ValueError("no inference model loaded — this server "
                             "only serves 'generate'")
        return self.engine.record_signatures(path)

    # -- network front-end ------------------------------------------------
    def _accept_loop(self):
        while not self._stop.is_set():
            try:
                self._sock.settimeout(0.2)
                conn, _ = self._sock.accept()
            except socket.timeout:
                continue
            except OSError:
                break
            t = threading.Thread(target=self._serve_conn, args=(conn,),
                                 daemon=True, name="serving-conn")
            t.start()
            # prune finished connection threads so a long-lived server
            # doesn't accumulate one dead handle per past client
            self._threads = [x for x in self._threads if x.is_alive()]
            self._threads.append(t)

    def _serve_conn(self, conn):
        with self._conns_lock:
            self._conns.add(conn)
        try:
            while not self._stop.is_set():
                try:
                    msg = recv_frame(conn, self._key)
                except (ConnectionError, EOFError, OSError):
                    return
                except WireError:
                    # unauthenticated/malformed frame: drop the
                    # connection (same policy as the PS server)
                    return
                try:
                    # chaos point: a stalled/killed connection handler
                    # (the hedged-client scenario — the request made it
                    # onto the wire but its reply never comes)
                    from ..resilience import maybe_fail
                    maybe_fail("serving.handle")
                except Exception as e:  # noqa: BLE001 — typed reply
                    reply = _error_reply(e)
                else:
                    reply = self._handle(msg)
                tr = msg.get("trace") if isinstance(msg, dict) else None
                t_r0 = time.perf_counter() if tr is not None else 0.0
                try:
                    send_frame(conn, reply, self._key)
                except (ConnectionError, OSError):
                    return
                if tr is not None:
                    _trace.record_child("serving/reply", t_r0,
                                        time.perf_counter(),
                                        _trace.from_wire(tr))
        finally:
            with self._conns_lock:
                self._conns.discard(conn)
            try:
                conn.close()
            except OSError:
                pass

    def _dedup(self, rid, admit):
        """Request-id dedup: the second half of a hedged pair ATTACHES
        to the first's in-flight request instead of admitting a second
        execution. ``admit`` runs under the table lock (it is the O(1)
        non-blocking queue put), so racing twins cannot double-admit.
        Returns ``(request, joined)``."""
        if not rid:
            return admit(), False
        with self._rids_lock:
            req = self._rids.get(rid)
            if req is not None:
                self._rids.move_to_end(rid)
                return req, True
            req = admit()
            self._rids[rid] = req
            while len(self._rids) > self._rid_cap:
                self._rids.popitem(last=False)
            return req, False

    def metrics(self):
        """Prometheus text exposition of the process metrics registry
        (serving counters/histograms, executor cache, pass pipeline,
        breaker states, training, utilization gauges — everything that
        reports into ``observability.default_registry()``)."""
        return render_metrics()

    def _handle(self, msg):
        if not isinstance(msg, dict) or "op" not in msg:
            return {"ok": False, "etype": "BadRequest",
                    "error": "expected a dict with an 'op' field"}
        op = msg["op"]
        if op == "ping":
            return {"ok": True}
        if op in ("stats", "metrics", "health", "cancel"):
            # probe/control ops carry the trace context too (a router's
            # health-probe latency belongs on the Perfetto timeline next
            # to the requests it gates); span() with a None parent is
            # free, so untraced probes pay nothing
            with _trace.span(f"serving/{op}",
                             parent=_trace.from_wire(msg.get("trace"))):
                if op == "stats":
                    return {"ok": True, "stats": self.stats()}
                if op == "metrics":
                    return {"ok": True, "metrics": self.metrics()}
                if op == "health":
                    return {"ok": True, "health": self.health()}
                return self._handle_cancel(msg)
        if op == "debug_dump":
            return self._handle_debug_dump(msg)
        if op == "generate":
            return self._handle_generate(msg)
        if op == "prefill":
            return self._handle_prefill(msg)
        if op == "reload_weights":
            return self._handle_reload(msg)
        if op != "infer":
            return {"ok": False, "etype": "BadRequest",
                    "error": f"unknown op {op!r}"}
        return self._handle_infer(msg)

    def _handle_debug_dump(self, msg):
        """Flight-recorder snapshot over the wire; ``"write": True``
        also dumps it to a JSON file server-side and returns the
        path."""
        rec = _flightrec()
        path = None
        if msg.get("write"):
            try:
                path = rec.dump(reason="debug_dump wire op")
            except OSError as e:
                return _error_reply(e)
        return {"ok": True, "events": rec.snapshot(), "path": path}

    def _handle_infer(self, msg):
        if self.engine is None:
            return {"ok": False, "etype": "BadRequest",
                    "error": "no inference model loaded — this server "
                             "only serves 'generate'"}
        # the handler span is ambient for the whole body, so the
        # Request minted inside parents its stage spans under it
        with _trace.span("serving/handle",
                         parent=_trace.from_wire(msg.get("trace"))):
            try:
                feed = msg.get("feed")
                if not isinstance(feed, dict) or not feed:
                    raise ValueError("'feed' must be a non-empty dict "
                                     "of arrays")
                missing = [n for n in self.engine.feed_names
                           if n not in feed]
                if missing:
                    raise ValueError(f"missing feeds: {missing}")
                feed = {n: np.asarray(feed[n])
                        for n in self.engine.feed_names}
                req, joined = self._dedup(
                    msg.get("rid"),
                    lambda: self.submit(
                        feed, deadline_ms=msg.get("deadline_ms"),
                        priority=msg.get("priority")))
                if joined and self.stats_sink:
                    self.stats_sink.bump("hedge_dedup_hits")
            except Exception as e:  # noqa: BLE001 — typed refusal reply
                return _error_reply(e)
            # bound the wait: the deadline (if any) plus compile/execute
            # headroom, else a hard server-side cap
            budget = msg.get("deadline_ms")
            wait_s = (budget / 1e3 + 60.0) if budget else 300.0
            try:
                outs = req.wait(timeout=wait_s)
                return {"ok": True, "fetch": tuple(outs),
                        "batched": int(req.rows)}
            except Exception as e:  # noqa: BLE001 — surface, don't die
                return _error_reply(e)

    def _handle_cancel(self, msg):
        """Cancel a request by client request id (the hedge loser): a
        still-in-flight request is failed with the typed cancellation
        error (the batchers skip done requests), a finished one is left
        alone."""
        rid = msg.get("rid")
        req = None
        if rid:
            with self._rids_lock:
                req = self._rids.get(rid)
        cancelled = False
        if req is not None and not req.done():
            req.set_error(RequestCancelledError(
                f"cancelled by the client (request id {rid})"))
            cancelled = True
            if self.stats_sink:
                self.stats_sink.bump("requests_cancelled")
        return {"ok": True, "cancelled": cancelled}

    def _handle_generate(self, msg):
        if self.gen_queue is None:
            return {"ok": False, "etype": "BadRequest",
                    "error": "this server has no generator — pass "
                             "generator= to InferenceServer"}
        with _trace.span("serving/handle",
                         parent=_trace.from_wire(msg.get("trace"))):
            return self._handle_generate_inner(msg)

    def _handle_generate_inner(self, msg):
        try:
            tokens = msg.get("tokens")
            if tokens is None:
                raise ValueError("'tokens' (1-D int prompt) is required")
            first_token = msg.get("first_token")
            req, joined = self._dedup(
                msg.get("rid"),
                lambda: self.submit_generate(
                    np.asarray(tokens),
                    max_new_tokens=int(msg.get("max_new_tokens", 32)),
                    temperature=float(msg.get("temperature", 0.0)),
                    top_k=int(msg.get("top_k", 0)),
                    eos_id=msg.get("eos_id"),
                    deadline_ms=msg.get("deadline_ms"),
                    kv=msg.get("kv"),
                    first_token=None if first_token is None
                    else int(first_token),
                    priority=msg.get("priority")))
            if joined and self.stats_sink:
                self.stats_sink.bump("hedge_dedup_hits")
        except Exception as e:  # noqa: BLE001 — typed refusal reply
            return _error_reply(e)
        # generation budget: prompt prefill + one step per token, plus
        # compile headroom on the first request of a shape
        budget = msg.get("deadline_ms")
        wait_s = (budget / 1e3 + 120.0) if budget else 600.0
        try:
            out, = req.wait(timeout=wait_s)
            return {"ok": True, "tokens": np.asarray(out, np.int32),
                    "generated": int(np.asarray(out).size)}
        except TimeoutError:
            # abandon the request properly: marking it done lets the
            # DecodeBatcher reclaim its slot instead of decoding tokens
            # nobody will read, and the client gets a typed, retryable
            # error instead of a generic Internal
            err = DeadlineExceededError(
                f"server-side wait budget of {wait_s:.0f}s exceeded; "
                f"the request was abandoned")
            req.set_error(err)
            return _error_reply(err)
        except Exception as e:  # noqa: BLE001 — surface, don't die
            return _error_reply(e)

    def _handle_prefill(self, msg):
        """The compute-bound half of the disaggregated split: prefill
        the prompt, sample its first token, then serialize the slot's
        KV blocks out of the paged pool instead of decoding. Reply
        ``{"ok": True, "kv": payload}`` where the payload carries
        ``first_token``/``prompt_tokens`` plus the block arrays —
        ready to stream into a decode replica via ``generate``'s
        ``kv=`` field."""
        if self.gen_queue is None:
            return {"ok": False, "etype": "BadRequest",
                    "error": "this server has no generator — pass "
                             "generator= to InferenceServer"}
        with _trace.span("serving/handle",
                         parent=_trace.from_wire(msg.get("trace"))):
            try:
                tokens = msg.get("tokens")
                if tokens is None:
                    raise ValueError(
                        "'tokens' (1-D int prompt) is required")
                req, joined = self._dedup(
                    msg.get("rid"),
                    lambda: self.submit_generate(
                        np.asarray(tokens),
                        max_new_tokens=int(msg.get("max_new_tokens",
                                                    32)),
                        temperature=float(msg.get("temperature", 0.0)),
                        top_k=int(msg.get("top_k", 0)),
                        deadline_ms=msg.get("deadline_ms"),
                        export_kv=True,
                        priority=msg.get("priority")))
                if joined and self.stats_sink:
                    self.stats_sink.bump("hedge_dedup_hits")
            except Exception as e:  # noqa: BLE001 — typed refusal
                return _error_reply(e)
            budget = msg.get("deadline_ms")
            wait_s = (budget / 1e3 + 120.0) if budget else 600.0
            try:
                payload, = req.wait(timeout=wait_s)
                return {"ok": True, "kv": payload}
            except TimeoutError:
                err = DeadlineExceededError(
                    f"server-side wait budget of {wait_s:.0f}s "
                    f"exceeded; the prefill was abandoned")
                req.set_error(err)
                return _error_reply(err)
            except Exception as e:  # noqa: BLE001 — surface, don't die
                return _error_reply(e)

    def _handle_reload(self, msg):
        """Hot weight reload over the wire (the router's rolling-reload
        building block): same contract as :meth:`reload_weights`."""
        path = msg.get("path")
        if not isinstance(path, str) or not path:
            return {"ok": False, "etype": "BadRequest",
                    "error": "'path' (checkpoint dir) is required"}
        try:
            out = self.reload_weights(
                path, timeout=float(msg.get("timeout", 120.0)))
        except Exception as e:  # noqa: BLE001 — typed reply
            return _error_reply(e)
        return {"ok": True, **out}


# reply etype <-> exception mapping. Order matters server-side:
# subclasses (Cancelled/Shutdown before their bases) must match first
_ETYPE_MAP = (
    ("Cancelled", RequestCancelledError),
    ("Shutdown", ServerShutdownError),
    ("DeadlineExceeded", DeadlineExceededError),
    ("Overloaded", ServerOverloadedError),
    ("Watchdog", WatchdogTimeout),
    ("BadRequest", (BadRequestError, ValueError, TypeError)),
)
# client-side reply mapping: server-side BadRequest detection matches
# (ValueError, TypeError), but the CLIENT raises the typed ServingError
# subclass so input refusals stay distinguishable from server faults
_ETYPES = {etype: cls for etype, cls in _ETYPE_MAP
           if isinstance(cls, type)}
_ETYPES["BadRequest"] = BadRequestError


_ierr_lock = threading.Lock()
_ierr_counts = {}       # exception type name -> cumulative count


def _record_internal_error(exc):
    """Flight-record an internal error crossing the server boundary,
    SAMPLED per exception type (first, then every 64th, cumulative
    count riding each sampled event — the RequestQueue admission
    discipline): a wedged engine failing every request at production
    QPS must not churn the ring and evict the restart/chaos/non-finite
    events that explain WHY it wedged."""
    key = type(exc).__name__
    with _ierr_lock:
        n = _ierr_counts.get(key, 0) + 1
        _ierr_counts[key] = n
    if n == 1 or n % 64 == 0:
        _flightrec().record("internal_error", etype=key, n=n,
                            error=str(exc)[:200])


def _error_reply(exc):
    """Map an exception to its typed wire reply. Internal/Watchdog
    faults crossing the server boundary trigger an automatic
    flight-recorder dump (rate-limited; only when
    ``FLAGS_flight_recorder_dir`` is set) — the chaos-soak postmortem
    artifact."""
    for etype, cls in _ETYPE_MAP:
        if isinstance(exc, cls):
            if etype == "Watchdog":
                _flightrec().auto_dump(
                    f"Watchdog error crossed the server boundary: {exc}")
            return {"ok": False, "etype": etype, "error": str(exc)}
    _record_internal_error(exc)
    _flightrec().auto_dump(
        f"Internal error crossed the server boundary: "
        f"{type(exc).__name__}: {exc}")
    return {"ok": False, "etype": "Internal",
            "error": f"{type(exc).__name__}: {exc}"}


# "argument not given" sentinel for per-call timeout overrides (None is
# a meaningful value: block forever). The stable repr keeps
# tools/api_signatures.txt reproducible across processes (a bare
# object()'s repr embeds its address).
class _Unset:
    def __repr__(self):
        return "<unset>"


_UNSET = _Unset()


class Client:
    """Wire-protocol client. One socket, serial request/reply (run one
    Client per concurrent caller — sockets are cheap; the server batches
    across them). Transport failures surface as ConnectionError
    subclasses (``WireTruncationError`` included).

    Resilience: a dead cached socket is detected on send/recv failure
    and reconnected ONCE transparently before any error surfaces (a
    bounced server does not strand old clients), ``ping``/``stats``/
    ``health`` retry with backoff via ``resilience.retry_call`` (they
    are idempotent), every ``infer``/``generate`` carries a request id
    (the server dedups, so a retried or hedged pair executes once), and
    ``infer`` can HEDGE: if no reply lands within a p99-derived delay
    (``hedge_ms``, default ``FLAGS_serving_hedge_ms``; the observed p99
    takes over once enough latencies are banked), a twin request races
    on a second connection, the first reply wins and the loser is
    cancelled by request id."""

    def __init__(self, endpoint, auth_key=None, timeout=None,
                 connect_retries=20, hedge_ms=None, retry_budget=None):
        from ..flags import flag
        host, port = endpoint.rsplit(":", 1)
        self.endpoint = endpoint
        self._addr = (host, int(port))
        self._key = auth_key if auth_key is not None else default_key()
        self._timeout = timeout
        self._connect_retries = connect_retries
        # None = the process-global retry budget. Infrastructure
        # callers (the router's health-probe clients) pass their own —
        # a dead replica probed every interval must not drain the
        # shared bucket and suppress hedges/failovers for healthy
        # user traffic
        self._retry_budget = retry_budget
        self._sock = None
        self._hedge_ms = float(hedge_ms if hedge_ms is not None
                               else flag("serving_hedge_ms"))
        self._lat_s = deque(maxlen=256)     # winning infer latencies
        self._hedges = 0
        self._hedge_wins = 0
        self._hedges_suppressed = 0     # refused by the retry budget

    def _budget(self):
        return (self._retry_budget if self._retry_budget is not None
                else default_retry_budget())

    @staticmethod
    def _remaining_ms(budget_ms, t0):
        """Deadline budget still unspent at THIS moment — what actually
        goes on the wire, so a hop (or a delayed retry/hedge) never
        grants itself the caller's full original budget again. Raises
        the typed expiry when nothing is left: no tier should burn
        compute on a request its caller has already abandoned."""
        if budget_ms is None:
            return None
        rem = remaining_budget_ms(budget_ms, t0)
        if rem <= 0:
            raise DeadlineExceededError(
                f"deadline budget of {float(budget_ms):.1f}ms spent "
                f"client-side before the request reached a server",
                deadline_ms=float(budget_ms),
                waited_ms=(time.monotonic() - t0) * 1e3)
        return rem

    def _ensure(self, timeout=_UNSET):
        if self._sock is None:
            t = self._timeout if timeout is _UNSET else timeout
            # an explicit per-call timeout also bounds the CONNECT
            # retries: a router probing a dead replica must fail fast,
            # not ride out the 10s reconnect discipline
            deadline = 10.0 if timeout is _UNSET or timeout is None \
                else max(float(timeout), 0.05)
            self._sock = retry_call(
                lambda: socket.create_connection(self._addr, timeout=t),
                deadline=deadline, retries=self._connect_retries,
                what="serving connect", endpoint=self.endpoint,
                budget=self._budget())
        return self._sock

    def _transact(self, sock, msg, timeout=_UNSET):
        """One request/reply exchange on ``sock``; maps error replies to
        their typed exceptions. No reconnect logic here. ANY failure
        inside the exchange (transport error, timeout, injected fault)
        poisons the socket — a half-done exchange can leave the reply in
        the buffer, and reusing the socket would pair the NEXT request
        with this one's stale reply — so the cached socket is dropped
        and the next call reconnects. ``timeout`` overrides the client
        default for THIS exchange (health probes against a hung replica
        fail fast instead of inheriting the long socket default)."""
        t = self._timeout if timeout is _UNSET else timeout
        try:
            send_frame(sock, msg, self._key, timeout=t)
            reply = recv_frame(sock, self._key, timeout=t)
        except BaseException:
            if sock is self._sock:
                self.close()
            raise
        # past here the exchange is COMPLETE — reply-decode errors are
        # typed results, not transport damage; the socket stays cached
        if not isinstance(reply, dict):
            raise WireError(f"malformed serving reply: {type(reply)}")
        if reply.get("ok"):
            return reply
        etype = _ETYPES.get(reply.get("etype"), InternalServerError)
        raise etype(reply.get("error", "serving request failed"))

    def _call(self, msg, timeout=_UNSET, budget_ms=None, t0=None):
        """Exchange with reconnect-once: a send/recv failure on the
        cached socket (typically a bounced server) closes it and retries
        the exchange on a fresh connection before surfacing anything.
        Safe because infer/generate carry a request id the server
        dedups, and the other ops are idempotent.

        ``budget_ms``/``t0`` arm deadline propagation: before every
        attempt the wire ``deadline_ms`` is rewritten to the REMAINING
        budget (raising typed expiry when none is left), and the
        reconnect retry itself withdraws from the process retry budget
        — a saturated fleet turns a reconnect storm into fast typed
        sheds instead of doubled offered load."""
        for attempt in (0, 1):
            if budget_ms is not None:
                msg["deadline_ms"] = self._remaining_ms(budget_ms, t0)
            sock = self._ensure(timeout=timeout)
            try:
                return self._transact(sock, msg, timeout=timeout)
            except (ConnectionError, OSError) as e:
                self.close()
                # an explicit per-call timeout expiring is the answer
                # (replica hung), not a stale-socket symptom — retrying
                # would double the caller's deadline
                if attempt or (timeout is not _UNSET
                               and isinstance(e, socket.timeout)):
                    raise
                self._budget().acquire(what="client-reconnect")
        raise AssertionError("unreachable")

    # -- hedging -----------------------------------------------------------
    def _hedge_delay_s(self, hedge_ms):
        """Effective hedge trigger: the observed p99 infer latency once
        >= 16 samples are banked (floored at 1 ms so a microsecond p99
        cannot hedge every call), else the configured cold-start
        delay."""
        base = self._hedge_ms if hedge_ms is None else float(hedge_ms)
        if base <= 0:
            return 0.0
        if len(self._lat_s) >= 16:
            p99 = float(np.percentile(np.asarray(self._lat_s), 99)) * 1e3
            return max(p99, 1.0) / 1e3
        return base / 1e3

    def hedge_stats(self):
        return {"hedges": self._hedges, "hedge_wins": self._hedge_wins,
                "budget_suppressed": self._hedges_suppressed,
                "observed": len(self._lat_s)}

    def _call_hedged(self, msg, delay_s, budget_ms=None, t0=None):
        """Race the primary exchange against a delayed twin on a fresh
        connection; first reply wins, the loser is cancelled by request
        id (the server's dedup table guarantees the pair executed at
        most once). The twin withdraws from the process retry budget
        first: when the bucket is dry the hedge is SUPPRESSED (counted
        in :meth:`hedge_stats`) and the call rides the primary alone —
        hedging is optional tail-fighting work, the first thing a
        saturated fleet must stop doing."""
        state = {"reply": None, "who": None, "errors": [], "done": 0}
        cv = threading.Condition()

        def attempt(tag, fn):
            try:
                r = fn()
            except Exception as e:  # noqa: BLE001 — judged by the racer
                r = None
                err = e
            with cv:
                if r is not None and state["reply"] is None:
                    state["reply"], state["who"] = r, tag
                elif r is None:
                    state["errors"].append(err)
                state["done"] += 1
                cv.notify_all()

        if budget_ms is not None:
            msg["deadline_ms"] = self._remaining_ms(budget_ms, t0)
        sock = self._ensure()
        threading.Thread(
            target=attempt, args=("primary",
                                  lambda: self._transact(sock, msg)),
            daemon=True, name="serving-client-primary").start()
        launched = 1
        with cv:
            cv.wait_for(lambda: state["reply"] is not None
                        or state["done"] >= launched, timeout=delay_s)
            fire_hedge = state["reply"] is None and state["done"] < 1

        # the twin owns its COPY of the message (the primary thread may
        # still be serializing the original) and fires LATER than the
        # primary: it carries the budget remaining NOW, not the
        # primary's stale copy — a spent budget means no twin (the
        # primary is still the caller's best hope), checked BEFORE the
        # budget withdrawal so a deadline-cancelled hedge doesn't leak
        # a token
        hmsg = dict(msg) if fire_hedge else None
        if fire_hedge and budget_ms is not None:
            try:
                hmsg["deadline_ms"] = self._remaining_ms(budget_ms, t0)
            except DeadlineExceededError:
                fire_hedge = False
        if fire_hedge and not self._budget().try_acquire(
                what="client-hedge"):
            self._hedges_suppressed += 1
            fire_hedge = False
        if fire_hedge:
            self._hedges += 1

            def hedge_fn():
                hs = socket.create_connection(self._addr,
                                              timeout=self._timeout)
                try:
                    return self._transact(hs, hmsg)
                finally:
                    try:
                        hs.close()
                    except OSError:
                        pass

            threading.Thread(target=attempt, args=("hedge", hedge_fn),
                             daemon=True,
                             name="serving-client-hedge").start()
            launched = 2
        with cv:
            cv.wait_for(lambda: state["reply"] is not None
                        or state["done"] >= launched)
            reply, who = state["reply"], state["who"]
            errors = list(state["errors"])
        if reply is None:
            if all(isinstance(e, (ConnectionError, OSError))
                   for e in errors):
                # both attempts died on transport: the reconnect-once
                # contract still applies — one fresh-socket retry (the
                # request id makes the replay exactly-once server-side)
                self.close()
                self._budget().acquire(what="client-reconnect")
                return self._call(msg, budget_ms=budget_ms, t0=t0)
            raise errors[0]
        if who == "hedge":
            self._hedge_wins += 1
            # the primary worker is still blocked on the cached socket:
            # drop it so the NEXT call gets a fresh connection instead
            # of interleaving frames with the abandoned exchange
            self.close()
        if launched == 2:
            try:
                self._call({"op": "cancel", "rid": msg["rid"]})
            except Exception:  # noqa: BLE001 — cancel is best-effort
                pass
        return reply

    @contextlib.contextmanager
    def _traced(self, msg):
        """Attach the sampled/ambient trace context to an outgoing
        request and record the client/send span around the call — the
        one copy of the trace-attach arithmetic for infer/generate."""
        ctx = _trace.maybe_trace()
        if ctx is not None:
            msg["trace"] = _trace.to_wire(ctx)
        t0p = time.perf_counter() if ctx is not None else 0.0
        try:
            yield
        finally:
            if ctx is not None:
                _trace.record_span("client/send", t0p,
                                   time.perf_counter(), ctx)

    # -- ops ---------------------------------------------------------------
    def infer(self, feeds, deadline_ms=None, hedge_ms=None,
              priority=None):
        """Returns the fetch list (numpy arrays). Raises
        DeadlineExceededError / ServerOverloadedError /
        ServerShutdownError mapped from the server's reply,
        ConnectionError on transport failure. ``hedge_ms`` overrides the
        client's hedging delay for this call (0 disables); ``priority``
        is the admission class (interactive/batch/best_effort).
        ``deadline_ms`` is a BUDGET: what goes on the wire is the part
        still unspent at send time, so a retried/hedged attempt never
        re-grants itself the full original allowance. At
        ``FLAGS_trace_sample_rate`` (or inside an ambient
        ``tracing.span``) the request carries a trace context the
        server's stages parent under."""
        msg = {"op": "infer", "feed": dict(feeds),
               "deadline_ms": deadline_ms, "rid": uuid.uuid4().hex}
        if priority is not None:
            msg["priority"] = str(priority)
        delay_s = self._hedge_delay_s(hedge_ms)
        t0 = time.monotonic()
        self._budget().record_request()
        with self._traced(msg):
            if delay_s <= 0:
                reply = self._call(msg, budget_ms=deadline_ms, t0=t0)
            else:
                reply = self._call_hedged(msg, delay_s,
                                          budget_ms=deadline_ms, t0=t0)
        self._lat_s.append(time.monotonic() - t0)
        return [np.asarray(a) for a in reply["fetch"]]

    def generate(self, tokens, max_new_tokens=32, temperature=0.0,
                 top_k=0, eos_id=None, deadline_ms=None, priority=None):
        """Autoregressive generation for one prompt (1-D int tokens).
        Returns the NEW tokens as a 1-D np.int32 array (EOS excluded).
        Same error mapping as ``infer``; ``deadline_ms`` is token-level
        (checked between decode steps server-side) and propagates as a
        REMAINING budget across retries; ``priority`` is the admission
        class (interactive/batch/best_effort — lower classes shed
        first under overload and brownout)."""
        msg = {
            "op": "generate",
            "tokens": np.asarray(tokens, dtype=np.int32).ravel(),
            "max_new_tokens": int(max_new_tokens),
            "temperature": float(temperature),
            "top_k": int(top_k),
            "eos_id": None if eos_id is None else int(eos_id),
            "deadline_ms": deadline_ms,
            "rid": uuid.uuid4().hex,
        }
        if priority is not None:
            msg["priority"] = str(priority)
        t0 = time.monotonic()
        self._budget().record_request()
        with self._traced(msg):
            reply = self._call(msg, budget_ms=deadline_ms, t0=t0)
        return np.asarray(reply["tokens"], dtype=np.int32)

    def prefill(self, tokens, max_new_tokens=32, temperature=0.0,
                top_k=0, deadline_ms=None):
        """The compute-bound half of the disaggregated split: prefill
        the prompt on this (prefill) replica and return the serialized
        KV payload — ``first_token``/``prompt_tokens`` plus the slot's
        block arrays — ready to pass to another replica's
        :meth:`generate` as ``kv=``."""
        msg = {
            "op": "prefill",
            "tokens": np.asarray(tokens, dtype=np.int32).ravel(),
            "max_new_tokens": int(max_new_tokens),
            "temperature": float(temperature),
            "top_k": int(top_k),
            "deadline_ms": deadline_ms,
            "rid": uuid.uuid4().hex,
        }
        with self._traced(msg):
            return self._call(msg)["kv"]

    def generate_from_kv(self, tokens, kv, max_new_tokens=32,
                         temperature=0.0, top_k=0, eos_id=None,
                         deadline_ms=None):
        """The bandwidth-bound half: stream a migrated ``kv`` payload
        (from :meth:`prefill`) into this (decode) replica's pool and
        continue decoding from its ``first_token``. Returns ALL new
        tokens (the prefill-side first token included) as np.int32."""
        msg = {
            "op": "generate",
            "tokens": np.asarray(tokens, dtype=np.int32).ravel(),
            "max_new_tokens": int(max_new_tokens),
            "temperature": float(temperature),
            "top_k": int(top_k),
            "eos_id": None if eos_id is None else int(eos_id),
            "deadline_ms": deadline_ms,
            "kv": dict(kv),
            "first_token": int(kv["first_token"]),
            "rid": uuid.uuid4().hex,
        }
        with self._traced(msg):
            reply = self._call(msg)
        return np.asarray(reply["tokens"], dtype=np.int32)

    def reload_weights(self, path, timeout=120.0):
        """Hot weight reload on the server (manifest-verified atomic
        swap; the router's rolling-reload building block). Returns
        ``{"weights_version", "swap_pause_ms"}``."""
        msg = {"op": "reload_weights", "path": str(path),
               "timeout": float(timeout)}
        reply = self._call(msg)
        return {"weights_version": reply["weights_version"],
                "swap_pause_ms": reply["swap_pause_ms"]}

    def cancel(self, rid):
        """Cancel an in-flight request by its id (hedge losers; also
        usable after abandoning a slow call). Returns True if the server
        actually cancelled something."""
        msg = {"op": "cancel", "rid": str(rid)}
        with self._traced(msg):
            return bool(self._call(msg).get("cancelled"))

    def _idempotent(self, msg, timeout=_UNSET):
        deadline = 10.0 if timeout is _UNSET or timeout is None \
            else max(float(timeout), 0.05)
        return retry_call(lambda: self._call(msg, timeout=timeout),
                          deadline=deadline,
                          retries=2, what=f"serving {msg['op']}",
                          endpoint=self.endpoint, budget=self._budget())

    def stats(self, timeout=_UNSET):
        """One server-stage stats snapshot. ``timeout`` (seconds)
        overrides the client's socket default for this call — probe
        loops against a hung replica fail fast."""
        msg = {"op": "stats"}
        with self._traced(msg):
            return self._idempotent(msg, timeout=timeout)["stats"]

    def metrics(self, timeout=_UNSET):
        """Prometheus text exposition of the server process's metrics
        registry (the scrape endpoint: pipe it to a pushgateway or the
        node-exporter textfile collector via
        ``tools/export_metrics.py``). ``timeout`` is per-call."""
        msg = {"op": "metrics"}
        with self._traced(msg):
            return self._idempotent(msg, timeout=timeout)["metrics"]

    def debug_dump(self, write=False):
        """The server's flight-recorder snapshot:
        ``{"ok", "events", "path"}`` with ``events`` the structured
        event dicts, oldest first. ``write=True`` also dumps them to a
        JSON file server-side; ``path`` is then its location (None
        otherwise)."""
        msg = {"op": "debug_dump", "write": bool(write)}
        if write:
            # the server-side file write is NOT idempotent: a retry
            # after a dropped reply would leave orphan dump files that
            # disagree about the incident window — one shot only
            return self._call(msg)
        return self._idempotent(msg)

    def health(self, timeout=_UNSET):
        """The server's lifecycle/liveness snapshot (state, queue
        depths, loop heartbeats + restarts, weights_version, kvpool
        occupancy). ``timeout`` (seconds) overrides the
        client's socket default for this one call — the router's
        health probes pass ``FLAGS_router_probe_timeout_s`` so a hung
        replica (stalled accept loop included) fails the probe fast
        instead of inheriting the long execute-path default."""
        msg = {"op": "health"}
        with self._traced(msg):
            return self._idempotent(msg, timeout=timeout)["health"]

    def ping(self, timeout=_UNSET):
        return bool(self._idempotent({"op": "ping"},
                                     timeout=timeout).get("ok"))

    def close(self):
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
            self._sock = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
