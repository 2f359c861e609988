"""ServingEngine: saved inference model -> padded-batch executor with an
AOT executable cache.

Reuses the framework's lowering exactly as ``inference.AnalysisPredictor``
does (one XLA module per program), but compiles through an explicit
``jit.lower(...).compile()`` pipeline so the compiled executables live in
the serving ``ExecutableCache`` — byte/entry-capped, counted, recordable
— instead of jax's invisible internal cache. Model state (params) is
device-resident and shared by every executable; feeds are the only
per-call traffic.
"""
import json
import os
import time

import numpy as np

from .batching import next_bucket
from .cache import ExecutableCache, feed_signature
from .kvpool import adopt_decode_fetches, decode_feed
from .metrics import record_class_done
from ..flags import flag
from ..observability import tracing as _trace
from ..observability import utilization as _util
from ..resilience import CheckpointCorruptError, maybe_fail
from ..utils.lru import LRUCache

SIGNATURE_FILE = "_serving_signatures.json"


def load_param_snapshot(dirname, current):
    """Load + integrity-check new values for ``current``'s parameters
    from a ``save_params``-layout checkpoint dir (per-var ``.npy`` files
    + ``_manifest.json``) — the hot-weight-reload loader.

    Every file is verified against the manifest BEFORE anything is
    returned (CheckFreq-style atomic swap discipline: a corrupt or
    incomplete checkpoint raises :class:`CheckpointCorruptError` and the
    serving snapshot is never touched), and each array must match the
    live parameter's shape and dtype. Returns {name: host ndarray}.
    """
    from .. import io as fluid_io
    manifest = fluid_io._read_manifest(dirname)
    if manifest is None:
        raise CheckpointCorruptError(
            f"checkpoint dir {dirname!r} has no _manifest.json — "
            f"reload_weights only trusts manifest-verified checkpoints "
            f"(save with io.save_params / save_persistables)",
            path=dirname)
    meta = {"vars": {}}
    meta_path = os.path.join(dirname, fluid_io._META_FILE)
    if os.path.exists(meta_path):
        fluid_io._verify_against_manifest(dirname, fluid_io._META_FILE,
                                          manifest)
        with open(meta_path) as f:
            meta = json.load(f)
    out, missing = {}, []
    for name, cur in current.items():
        rel = fluid_io._escape(name) + ".npy"
        path = os.path.join(dirname, rel)
        if not os.path.exists(path):
            missing.append(name)
            continue
        fluid_io._verify_against_manifest(dirname, rel, manifest)
        try:
            arr = np.load(path, allow_pickle=False)
        except (OSError, ValueError) as e:
            raise CheckpointCorruptError(
                f"checkpoint file {rel!r} in {dirname!r} is unreadable: "
                f"{type(e).__name__}: {e}", path=path)
        tag = meta["vars"].get(name, {}).get("dtype", str(arr.dtype))
        arr = fluid_io._restore(arr, tag)
        cur_np = cur if hasattr(cur, "shape") else np.asarray(cur)
        if tuple(arr.shape) != tuple(cur_np.shape) \
                or str(arr.dtype) != str(np.dtype(cur_np.dtype)):
            raise ValueError(
                f"checkpoint param {name!r} is {arr.shape}/{arr.dtype}, "
                f"the serving snapshot holds "
                f"{tuple(cur_np.shape)}/{np.dtype(cur_np.dtype)} — "
                f"reload_weights only swaps like-for-like weights")
        out[name] = arr
    if missing:
        raise CheckpointCorruptError(
            f"checkpoint at {dirname!r} is missing {len(missing)} "
            f"serving parameter(s): {', '.join(sorted(missing))} — "
            f"the old snapshot was left untouched", path=dirname)
    return out


class ServingEngine:
    """Loads a saved inference model once and executes padded batches.

    ``execute(requests)`` is the MicroBatcher flush target: concatenates
    request rows, pads to the power-of-two bucket, runs the cached
    executable for that signature (compiling on miss), splits the rows
    back per request and delivers results. Also usable stand-alone via
    ``run(feeds)`` for single-shot prediction.
    """

    def __init__(self, model_dir=None, *, program=None, scope=None,
                 feed_names=None, fetch_targets=None, model_filename=None,
                 params_filename=None, cache=None, stats=None):
        from ..framework.executor import Executor, Scope, scope_guard
        from ..framework.lowering import analyze_block_io, build_block_fn
        import jax

        if program is None:
            if model_dir is None:
                raise ValueError("ServingEngine needs model_dir= or a "
                                 "loaded program=")
            from .. import io as fluid_io
            scope = scope or Scope()
            with scope_guard(scope):
                program, feed_names, fetch_targets = \
                    fluid_io.load_inference_model(
                        model_dir, Executor(),
                        model_filename=model_filename,
                        params_filename=params_filename)
        self.model_dir = model_dir
        self.program = program
        self.feed_names = list(feed_names)
        self.fetch_names = [t.name if hasattr(t, "name") else str(t)
                            for t in fetch_targets]
        self.stats = stats

        state_in, _ = analyze_block_io(program, 0, list(self.feed_names))
        fn = build_block_fn(program, 0, list(self.feed_names),
                            list(self.fetch_names), state_in, [])
        key = jax.random.PRNGKey(0)

        def infer(state, feed):
            fetches, _, _ = fn({}, state, feed, key)
            return fetches

        self._infer = jax.jit(infer)
        self._state = {}
        for n in state_in:
            v = scope.find_var(n) if scope is not None else None
            if v is None:
                raise RuntimeError(
                    f"inference model state var {n!r} is not in the "
                    f"scope — load_inference_model must run first")
            self._state[n] = jax.device_put(np.asarray(v))
        self.cache = cache if cache is not None else ExecutableCache()
        # feed signature -> cost_analysis dict|False (LRU: misses for
        # still-cached executables recompute via _util.cost_for)
        self._costs = LRUCache(max_entries=256)
        gb = program.global_block()
        # batching across requests is only sound when every feed's
        # leading dim is dynamic (-1): a static-batch model is executed
        # request-by-request at its natural shape instead
        self.batchable = all(
            (gb.vars.get(n) is None
             or not getattr(gb.vars[n], "shape", None)
             or int(gb.vars[n].shape[0]) < 0)
            for n in self.feed_names)
        # which fetches are per-row, decided STATICALLY from the program
        # IR: a dynamic (-1) leading dim means the output scales with the
        # batch and is sliced back per request; anything else (scalar,
        # fixed-size table) is batch-global and replicated. None = shape
        # unknown in the IR, fall back to a runtime dim check.
        self._row_aligned = []
        for n in self.fetch_names:
            var = gb.vars.get(n)
            shape = getattr(var, "shape", None) if var is not None else None
            self._row_aligned.append(
                None if not shape else int(shape[0]) < 0)

    # -- compilation ------------------------------------------------------
    def _compile(self, feed):
        """AOT-compile the module for this feed signature and cache it."""
        from .. import profiler as _prof
        maybe_fail("serving.compile")
        t0 = time.perf_counter()
        compiled = self._infer.lower(self._state, feed).compile()
        dt = time.perf_counter() - t0
        nbytes = self._executable_bytes(compiled, feed)
        sig = feed_signature(feed)
        self.cache.put(sig, compiled, nbytes=nbytes)
        # cost_analysis read once per executable: the live MFU/HBM
        # gauges attach it to every later execute() timing
        cost = _util.cost_for(self._costs, sig, compiled)
        # sharding audit + collective ledger on newly compiled serving
        # executables (flag-gated shared front door, mesh runs only —
        # the tensor-parallel serving PR this instruments)
        from ..observability.sharding import maybe_observe
        from ..parallel.mesh import get_mesh
        maybe_observe("infer", compiled, get_mesh(),
                      program=self.program,
                      feed_names=self.feed_names, cost=cost,
                      tag="serving_infer")
        if self.stats:
            self.stats.bump("compiles")
            self.stats.hist["compile"].observe(dt)
        else:
            _prof.record_duration("serving/compile", dt)
        return compiled

    @staticmethod
    def _executable_bytes(compiled, feed):
        """Byte cost of a cache entry: XLA's own generated-code +
        temp-buffer sizes when the backend reports them, else the feed
        buffer size as a proportional lower bound."""
        try:
            ma = compiled.memory_analysis()
            n = int(getattr(ma, "generated_code_size_in_bytes", 0)
                    + getattr(ma, "temp_size_in_bytes", 0)
                    + getattr(ma, "output_size_in_bytes", 0))
            if n > 0:
                return n
        except Exception:  # noqa: BLE001 — backend-dependent surface
            pass
        return sum(a.nbytes for a in feed.values())

    def _executable_for(self, feed):
        """(signature, executable, compile_seconds) for ``feed`` —
        ``compile_seconds`` is None on a cache hit, so callers can
        attribute a compile span without re-implementing the miss
        path."""
        sig = feed_signature(feed)
        compiled = self.cache.get(sig)
        if compiled is None:
            t0 = time.perf_counter()
            compiled = self._compile(feed)
            return sig, compiled, time.perf_counter() - t0
        return sig, compiled, None

    # -- hot weight reload ------------------------------------------------
    def load_state_snapshot(self, dirname):
        """Verify + load a new device snapshot of every model state var
        from a manifest-carrying checkpoint dir. Raises
        CheckpointCorruptError / ValueError without touching the live
        snapshot; the result is ready for :meth:`swap_state`."""
        import jax
        host = load_param_snapshot(dirname, self._state)
        return {n: jax.device_put(a) for n, a in host.items()}

    def swap_state(self, new_state):
        """Atomically swap the device param snapshot between
        micro-batches: ``execute``/``run`` capture ``self._state`` once
        at entry, so an in-flight batch finishes on the old weights and
        every later batch reads the new ones."""
        missing = [n for n in self._state if n not in new_state]
        if missing:
            raise ValueError(f"swap_state snapshot is missing state "
                             f"vars: {sorted(missing)}")
        self._state = {n: new_state[n] for n in self._state}

    # -- single-shot ------------------------------------------------------
    def run(self, feeds):
        """Run one feed dict as-is (no cross-request batching, still
        cached): returns the fetch list as numpy arrays."""
        state = self._state          # one snapshot for the whole call
        feed = {n: np.ascontiguousarray(feeds[n]) for n in self.feed_names}
        _sig, compiled, _dt = self._executable_for(feed)
        outs = compiled(state, feed)
        return [np.asarray(o) for o in outs]

    # -- batched path (MicroBatcher flush target) -------------------------
    def execute(self, requests):
        """Execute a same-signature group of requests as one padded
        batch. Delivers per-request results/errors; never raises for a
        single bad request (the batch-level failure path is handled by
        the MicroBatcher)."""
        maybe_fail("serving.execute")
        state = self._state          # one snapshot for the whole batch:
        now = time.monotonic()       # a reload swaps BETWEEN batches
        live = [r for r in requests if not r.done()]
        if not live:
            return
        if not self.batchable:
            # static-batch model: request-by-request at natural shape
            for req in live:
                try:
                    outs = self.run(req.feeds)
                    if self.stats:
                        self.stats.observe_batch(req.rows, req.rows)
                        self.stats.bump("requests_completed")
                        self.stats.hist["total"].observe(
                            time.monotonic() - req.t_enqueue)
                    req.set_result(outs)
                    record_class_done(req.priority,
                                      time.monotonic() - req.t_enqueue)
                except Exception as exc:  # noqa: BLE001
                    req.set_error(exc)
                    if self.stats:
                        self.stats.bump("requests_failed")
            return

        t_pad0 = time.perf_counter()
        total = sum(r.rows for r in live)
        bucket = next_bucket(total)
        feed = {}
        for name in self.feed_names:
            parts = [r.feeds[name] for r in live]
            arr = parts[0] if len(parts) == 1 else np.concatenate(parts)
            if bucket > total:
                pad = np.zeros((bucket - total,) + arr.shape[1:],
                               dtype=arr.dtype)
                arr = np.concatenate([arr, pad])
            feed[name] = np.ascontiguousarray(arr)
        t_pad = time.perf_counter() - t_pad0
        if self.stats:
            self.stats.hist["pad"].observe(t_pad)
        traced = [r for r in live if r.trace is not None]
        for req in traced:
            _trace.record_child("serving/pad", t_pad0, t_pad0 + t_pad,
                                req.trace)

        sig, compiled, compile_s = self._executable_for(feed)
        if compile_s is not None:
            t_c1 = time.perf_counter()
            for req in traced:
                _trace.record_child("serving/compile", t_c1 - compile_s,
                                    t_c1, req.trace)
        t_exec0 = time.perf_counter()
        outs = compiled(state, feed)
        outs = [np.asarray(o) for o in outs]
        t_exec = time.perf_counter() - t_exec0
        for req in traced:
            _trace.record_child("serving/execute", t_exec0,
                                t_exec0 + t_exec, req.trace)
        cost = _util.cost_for(self._costs, sig, compiled)
        if cost:
            _util.observe_execution("infer", cost, t_exec)
        if self.stats:
            self.stats.hist["execute"].observe(t_exec)
            self.stats.observe_batch(total, bucket)

        off = 0
        done_t = time.monotonic()
        for req in live:
            res = []
            for o, aligned in zip(outs, self._row_aligned):
                if aligned is None:
                    aligned = bool(o.ndim) and o.shape[0] == bucket
                if aligned:
                    res.append(o[off:off + req.rows])
                else:
                    # batch-global output (scalar, fixed table): the
                    # full tensor is replicated to every request
                    res.append(o)
            off += req.rows
            req.set_result(res)
            record_class_done(req.priority, done_t - req.t_enqueue)
            if self.stats:
                self.stats.bump("requests_completed")
                self.stats.hist["total"].observe(done_t - req.t_enqueue)

    # -- warmup -----------------------------------------------------------
    def feed_specs(self, batch_size=None):
        """{name: (shape, dtype)} for warmup feeds; dynamic dims become
        ``batch_size`` (leading) / 1 (others). Prefers the save-time
        ``feed_specs`` record ``save_inference_model`` writes into
        ``__model__`` (attached as ``program._feed_specs`` on load);
        falls back to the program's feed vars for pre-upgrade saves."""
        from ..framework.dtype import np_dtype
        gb = self.program.global_block()
        recorded = getattr(self.program, "_feed_specs", None) or {}
        specs = {}
        for n in self.feed_names:
            rec = recorded.get(n)
            if rec and rec.get("shape"):
                shape = [int(d) for d in rec["shape"]]
                dt = np_dtype(rec.get("dtype") or "float32")
            else:
                var = gb.vars.get(n)
                shape = [int(d)
                         for d in getattr(var, "shape", None) or (1,)]
                dt = np_dtype(getattr(var, "dtype", "float32")
                              or "float32")
            for i, d in enumerate(shape):
                if d < 0:
                    shape[i] = int(batch_size or 1) if i == 0 else 1
            specs[n] = (tuple(shape), np.dtype(dt).name)
        return specs

    def warmup(self, batch_sizes=(1,), signature_file=None):
        """Precompile executables before taking traffic: one per bucket
        size in ``batch_sizes`` (from the model's feed specs), plus every
        signature in ``signature_file`` (a recorded-traffic file written
        by ``record_signatures``; missing file is not an error — warmup
        is best-effort by design). Returns the number of compiles."""
        sigs = []
        for b in batch_sizes or ():
            sigs.append(self.feed_specs(batch_size=next_bucket(b)))
        if signature_file:
            path = signature_file
            if path is True and self.model_dir:
                path = os.path.join(self.model_dir, SIGNATURE_FILE)
            if isinstance(path, str) and os.path.exists(path):
                sigs.extend(ExecutableCache.load_signatures(path))
        n = 0
        for spec in sigs:
            try:
                feed = {name: np.zeros(shape, dtype=dtype)
                        for name, (shape, dtype) in spec.items()}
                if feed_signature(feed) not in self.cache:
                    self._compile(feed)
                    n += 1
            except Exception as e:  # noqa: BLE001 — warmup is best-effort
                import warnings
                warnings.warn(f"serving warmup skipped signature {spec}: "
                              f"{type(e).__name__}: {e}", stacklevel=2)
        return n

    def record_signatures(self, path=None):
        """Persist the cache's observed signatures for next launch's
        warmup. Default path: ``<model_dir>/_serving_signatures.json``."""
        if path is None:
            if not self.model_dir:
                raise ValueError("record_signatures needs a path when the "
                                 "engine was not loaded from a model_dir")
            path = os.path.join(self.model_dir, SIGNATURE_FILE)
        self.cache.record(path)
        return path


def _free_device_bytes():
    """Bytes the first device has free by its own count, less a tenth of
    its memory kept for what an executable needs beside its results;
    None where the backend keeps no count."""
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    limit, used = stats.get("bytes_limit"), stats.get("bytes_in_use")
    if not limit or used is None:
        return None
    return max(int(0.9 * limit) - int(used), 0)


class GenerationEngine:
    """Slot-batched autoregressive decoding primitives for the serving
    runtime, over a ``models.generation.GPTGenerator``.

    The engine owns a fixed bank of ``slots`` generation rows
    (``FLAGS_decode_slots``) whose KV caches live on the device in one
    shared block pool (``kvpool.KVBlockPool``: per-slot block tables,
    blocks allocated on append — concurrency bounded by actual tokens,
    not ``slots * max_len``), stepped by a single compiled paged decode
    executable. The ``DecodeBatcher`` drives it:

    - ``admit(requests, slot_ids)``: bucketed prefill over the new
      prompts, per-row sampling of their first tokens, and a jitted
      scatter of the fresh row caches into the slots' blocks (a
      finished row's blocks went back to the pool at release).
    - ``dispatch_step(tokens, pos, temperature, top_k, from_host)``
      sends one decode step that picks its own tokens over the whole
      bank and returns at once; ``collect_step(sent)`` reads them. The
      tokens stay on the device from one step to the next, so the
      batcher sends step t + 1 before it has read step t.
      ``step(tokens, pos, temperature, top_k)`` is the two in place.
      Rows at different positions (and with different sampling
      configs) share the executable.

    All methods are single-caller by design — the DecodeBatcher thread
    is the only driver (the chip is the bottleneck resource; concurrency
    lives in the connection threads, exactly like the infer path).
    """

    def __init__(self, generator, *, slots=None, stats=None, seed=0,
                 kv_dtype=None, kv_block_size=None,
                 kv_pool_blocks=None, pool_name="serving",
                 prefix_cache=None):
        import jax
        self.gen = generator
        self.slots = int(slots or flag("decode_slots"))
        self.stats = stats if stats is not None else generator.stats
        # the architecture's own layout: its KV heads and head width,
        # its layer groups (tensor-parallel serving: block arrays
        # sharded on the head axis of the generator's tp mesh).
        # ``pool_name`` labels the pool's kvpool_* gauge series — fleet
        # replicas sharing one process must not clobber each other's
        # occupancy. ``prefix_cache`` (None -> FLAGS_kv_prefix_cache)
        # turns on block-granular prompt-prefix reuse across requests.
        self.pool = generator.new_pool(
            self.slots, block_size=kv_block_size,
            num_blocks=kv_pool_blocks, dtype=kv_dtype, name=pool_name,
            prefix_cache=prefix_cache)
        # a generator WITHOUT its own sink adopts the server's (stage
        # histograms land in server.stats()), and a sink a PREVIOUS
        # engine bound is rebound to the live server (else a reused
        # generator reports into a dead server's sink). A sink the USER
        # set stays put — rebinding it would make unrelated offline
        # generate() calls pollute the served-traffic counters.
        if generator.stats is None or getattr(generator,
                                              "_stats_adopted", False):
            generator.stats = self.stats
            generator._stats_adopted = True
        self.max_len = generator.max_len
        self._key = jax.random.PRNGKey(int(seed))
        self.bank_lost = False     # see _drop_bank
        self.step_routing = {}     # the last step's moe_* span attrs
        # what every executable of this architecture runs a token: passes
        # over its weights (more than 1 where a stack is run several
        # times), the pool's cache layers and, where it has a state group,
        # that group's layers; on the engine/step and generator/prefill
        # spans
        self.loop_attrs = dict(
            ut_steps=int(getattr(generator.arch, "ut_steps", 1)),
            cache_layers=self.pool.num_layers, **self.pool.state_attrs())
        # int32 [slots] on the device: what the last dispatched step
        # picked, the next step's tokens for the rows that were in it
        self._prev_tokens = None

    def _ensure_caches(self):
        self.bank_lost = False
        self.pool.arrays()           # lazy device-side pool build

    def _drop_bank(self):
        """A failed donated call may have invalidated the pool's
        buffers: drop its DEVICE arrays (the next admission rebuilds
        zeros) and flag the loss so the DecodeBatcher fails every
        active row instead of letting them silently decode against a
        fresh zero cache. The host block accounting survives, and the
        failed rows return their blocks through the batcher's release
        path."""
        self.pool.drop_device()
        self._prev_tokens = None
        self.bank_lost = True

    def reset(self):
        """Forget the slot bank without flagging a loss — the restart
        path: a replaced decode loop starts from an empty bank (its rows
        were already failed by the supervisor), so the stale caches are
        garbage, not state. Every block is freed too."""
        self.pool.reset()
        self._prev_tokens = None
        self.bank_lost = False

    # -- paged-pool admission / lifecycle hooks ---------------------------
    def admission_check(self, prompt_len, max_new_tokens,
                        pending_tokens=(), static_only=False):
        """Typed admission gate, callable BEFORE any queue wait or
        prefill compile: an overlong request raises
        :class:`batching.BadRequestError` (the wire maps it to
        ``etype: "BadRequest"`` — retrying without fixing the input
        cannot help), and so does a request the pool
        could NEVER hold even empty; a request whose prompt blocks are
        merely not free RIGHT NOW (unless ``static_only``) raises the
        retryable :class:`kvpool.KVPoolExhaustedError` instead,
        counting requests already accepted this admission round via
        ``pending_tokens`` (their prompt lengths)."""
        from .batching import BadRequestError
        prompt_len, max_new_tokens = int(prompt_len), int(max_new_tokens)
        if prompt_len + max_new_tokens > self.max_len:
            raise BadRequestError(
                f"prompt ({prompt_len} tokens) + max_new_tokens "
                f"({max_new_tokens}) exceeds the decode cache length "
                f"{self.max_len}")
        self.pool.check_fits(prompt_len + max_new_tokens)
        if not static_only:
            # +1: the first decode append may open a fresh block
            self.pool.admission_check(
                prompt_len + 1, [int(t) + 1 for t in pending_tokens])

    def release_slot(self, slot):
        """Return a finished slot's KV blocks to the pool (EOS /
        deadline / cancel / error — the continuous-batching reclaim)."""
        self.pool.free_slot(slot)

    def prepare_step(self, active_pos, widths=None):
        """Allocation-on-append before a decode step: grow each live
        row's blocks to cover the slot its next token writes
        (``active_pos`` maps slot -> position). ``widths`` (slot ->
        token count, default 1 everywhere) covers a speculative verify
        span instead: the row writes ``[pos, pos + width)`` in one
        step, so allocation AND the COW barrier extend over the whole
        span — a shared prefix block must be duplicated BEFORE the
        speculative write lands, even for draft positions that may be
        rejected. Returns ``{slot: exc}`` for rows the pool could not
        grow — the batcher sheds exactly those rows (typed) while the
        rest of the bank keeps decoding."""
        shed = {}
        for slot, p in active_pos.items():
            w = max(int(widths.get(slot, 1)) if widths else 1, 1)
            try:
                self.pool.ensure(slot, int(p) + w - 1)
                if self.pool.prefix_enabled:
                    # COW barrier: any block this span lands in may be
                    # co-owned by the prefix cache (or another slot
                    # that adopted it) — duplicate before writing
                    self.pool.prepare_write(slot, int(p), int(p) + w)
            except Exception as exc:  # noqa: BLE001 — per-row shed
                shed[slot] = exc
        return shed

    def reclaim_leaks(self, live_slots):
        """Leak sweep: free blocks held by slots not in ``live_slots``
        (flight-recorded per leaking slot)."""
        return self.pool.reclaim_leaks(live_slots)

    # -- hot weight reload ------------------------------------------------
    def load_param_snapshot(self, dirname):
        """Verify + load new HOST values for every generator parameter
        (building the parameter-bearing programs first if no traffic
        has). Raises without touching the live snapshot."""
        for kind in self.gen.arch.eager_builders(self.max_len):
            self.gen._ensure_fn(kind)
        return load_param_snapshot(dirname, self.gen._params)

    def stage_params(self, host_params):
        """Device-put the verified host arrays — run OFF the decode loop
        so the swap itself (apply_params) is a dict rebind, not a
        transfer."""
        import jax
        return {n: jax.device_put(a) for n, a in host_params.items()}

    def apply_params(self, device_params):
        """The atomic swap half: rebind the generator's parameter
        snapshot. Scheduled between decode steps via
        DecodeBatcher.request_swap so in-flight generations finish on
        the old weights."""
        self.gen.swap_params(device_params)

    def admit(self, requests, slot_ids):
        """Prefill the new requests' prompts (one bucketed batch), pick
        their first tokens and write their caches into ``slot_ids``, in
        ONE donated executable (``GPTGenerator``'s ``<prefill
        kind>+<pick kind>`` over the pool's layout, sent by its
        ``_run_sample`` on a ``PendingPrefill``). Returns the first
        tokens as np int32 [len(requests)]."""
        maybe_fail("serving.prefill")
        self._ensure_caches()
        n = len(requests)
        fit = self.prefill_fit([r.prompt.size for r in requests])
        if fit < n:
            # more than one prefill can hold: what fits now, then the
            # rest (DecodeBatcher asks prefill_fit before it takes a
            # request off the queue, so its rounds never come here)
            return np.concatenate([
                self.admit(requests[:fit], slot_ids[:fit]),
                self.admit(requests[fit:], slot_ids[fit:])])
        t0 = time.perf_counter()
        span = _trace.loop_span
        gen, pool = self.gen, self.pool
        with span("engine/pack", rows=n):
            tokens, pos_ids, last = gen._pack_prompts(
                [req.prompt for req in requests])
            bb, seq = tokens.shape
            temp = np.zeros((bb,), np.float32)
            topk = np.zeros((bb,), np.int32)
            for r, req in enumerate(requests):
                temp[r] = req.temperature
                topk[r] = req.top_k
            feed = dict(tokens=tokens, pos_ids=pos_ids, last_pos=last)

        # allocate each row's prompt blocks BEFORE the call (the scatter
        # routes through the tables); a mid-batch failure rolls this
        # batch's allocations back untouched
        allocated = []
        with span("pool/alloc", rows=n):
            try:
                for req, slot in zip(requests, slot_ids):
                    pool.free_slot(slot)   # stale holder (if any)
                    pool.alloc(slot, int(req.prompt.size))
                    allocated.append(slot)
            except Exception:
                for sl in allocated:
                    pool.free_slot(sl)
                raise
            feed.update(pool.scatter_indices(
                slot_ids, seq, [int(r.prompt.size) for r in requests],
                rows=bb))
        # the first tokens are picked from the prefill's logits inside
        # its own call: the pick is handed the prefill before it is sent
        from ..models.generation import PendingPrefill
        prefill = PendingPrefill(gen.arch.prefill_kind(pool.dtype), feed,
                                 pool)
        with span("generator/prefill", rows=n, fused=1,
                  **self.loop_attrs) as ran:
            try:
                maybe_fail("serving.slot_insert")
                toks, self._key = gen._run_sample(prefill, temp, topk,
                                                  self._key)
            except Exception:
                # the donated device pool is lost; this batch's blocks
                # go back, the batcher fails the other active rows via
                # bank_lost
                for sl in slot_ids:
                    pool.free_slot(sl)
                self._drop_bank()
                raise
        if self.stats:
            self.stats.bump("admissions")
            self.stats.bump("admissions_fused")
        if pool.prefix_enabled:
            # deposit the freshly prefilled prompt blocks into the
            # prefix index (refcounted co-ownership — they outlive the
            # slot's EOS until evicted LRU); later requests sharing the
            # prompt prefix adopt them instead of recomputing
            for req, slot in zip(requests, slot_ids):
                pool.prefix_insert(req.prompt, slot)
        with span("engine/fetch", rows=n):
            out = np.asarray(toks)[:n]
            sent = prefill.sent
            ran.attrs.update(self._admitted(
                gen.aux_of(sent.kind, sent.fetches), requests, bb, seq))
        t1 = time.perf_counter()
        for req in requests:
            if getattr(req, "trace", None) is not None:
                _trace.record_child("serving/prefill", t0, t1, req.trace)
        # freeing the call's device results hands the interpreter to
        # whatever thread waits for it (_step's engine/release)
        with span("engine/release"):
            prefill = sent = toks = None
        return out

    def _count_routing(self, aux, rows=None):
        """Count one executable that ran (a step read, a prefill of
        ``rows`` real rows): ``loop_passes`` by the passes it made over
        the weights, and its ``[layers, experts]`` assignment counts
        into the ``moe_*`` counters. Returns what the span that ran it
        gains: ``exit_pass_mean`` where the executable hands back each
        row's ``exit_pass`` (the mean over live rows, whose pass is not
        0); the assignments, the fullest expert's load summed over the
        layers and the experts that got any where it routes."""
        if self.stats:
            self.stats.bump("loop_passes", self.loop_attrs["ut_steps"])
        looped = {}
        if "exit_pass" in aux:
            left = np.asarray(aux["exit_pass"])[:rows]
            left = left[left > 0]
            if left.size:
                looped["exit_pass_mean"] = float(left.mean())
        counts = aux.get("moe_counts")
        if counts is None:
            return looped
        counts = np.asarray(counts)
        routed = {"moe_tokens": int(counts.sum()),
                  "moe_load_max": int(counts.max(axis=1).sum()),
                  "moe_experts_hit": int((counts > 0).sum())}
        if self.stats:
            self.stats.bump("moe_assignments", routed["moe_tokens"])
            self.stats.bump("moe_expert_load_max", routed["moe_load_max"])
            self.stats.bump("moe_experts_hit", routed["moe_experts_hit"])
        return routed

    # -- chunked (incremental) prefill ------------------------------------
    def incremental_prefill_enabled(self):
        """Chunked prompt ingestion (Orca/Sarathi-style): on when
        either ``FLAGS_prefill_chunk_tokens``
        bounds the per-round prompt slice (long prompts stop stalling
        the decode bank's token cadence) or the prefix cache is on (the
        incremental path is what turns a cached-prefix hit into skipped
        prefill compute)."""
        return (int(flag("prefill_chunk_tokens")) > 0
                or self.pool.prefix_enabled)

    def start_prefill(self, req, slot):
        """Begin incremental prefill of ``req`` into ``slot``: reclaim
        the stale holder, adopt the longest cached prompt prefix (block
        references only — no compute), and return the prefill state the
        batcher advances one :meth:`prefill_chunk` per decode round. A
        FULL exact-prompt hit still replays the final token as a
        1-token chunk (COWing the shared tail block): that chunk's
        logits ARE the first-token distribution, so a repeat prompt
        pays one token of prefill instead of the whole prompt."""
        self._ensure_caches()
        prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        L = int(prompt.size)
        self.pool.free_slot(slot)       # stale holder (if any)
        reused = 0
        if self.pool.prefix_enabled:
            m = self.pool.match_prefix(prompt)
            if m is not None:
                self.pool.adopt_prefix(slot, m)
                reused = int(m["tokens"])
        return {"req": req, "slot": int(slot), "prompt": prompt,
                "next": min(reused, L - 1), "reused": reused,
                "chunk": int(flag("prefill_chunk_tokens")),
                "first_logits": None, "t0": time.perf_counter()}

    def prefill_chunk(self, state):
        """Ingest ONE chunk of ``state``'s prompt into its slot's
        blocks (at most the chunk budget; everything left when only the
        prefix cache turned the incremental path on). Typed pool
        pressure (alloc/COW) raises BEFORE any device call — the slot's
        accounting is intact and the batcher sheds just this row; a
        failure of the chunk executable itself loses the donated pool
        arrays, so the slot is released and ``bank_lost`` set, exactly
        like a failed monolithic scatter. Returns True when the prompt
        is fully ingested (sample via :meth:`finish_prefill`)."""
        slot, prompt = state["slot"], state["prompt"]
        L = int(prompt.size)
        s = int(state["next"])
        take = min(state["chunk"] or (L - s), L - s)
        # fixed chunk width under a budget, bucketed width otherwise —
        # either way a bounded universe of compiled chunk shapes
        C = state["chunk"] or min(
            next_bucket(take, min_bucket=self.gen.bucket_min),
            self.max_len)
        toks = np.zeros((1, C), np.int32)
        toks[0, :take] = prompt[s:s + take]
        pos_ids = np.clip(np.arange(s, s + C, dtype=np.int32),
                          0, L - 1)[None, :]
        self.pool.alloc(slot, s + take)
        if self.pool.prefix_enabled:
            self.pool.prepare_write(slot, s, s + take)
        try:
            logits, self._key = self.gen._run_prefill_chunk(
                toks, pos_ids, np.array([s], np.int32),
                np.array([take], np.int32),
                np.array([take - 1], np.int32), self.pool, self._key,
                rows=[slot])
        except Exception:
            # the donated device pool is lost; this row's blocks go
            # back, the batcher fails the other active rows via
            # bank_lost
            self.pool.free_slot(slot)
            self.bank_lost = True
            raise
        state["next"] = s + take
        if state["next"] >= L:
            state["first_logits"] = np.asarray(logits)[:1]
            return True
        return False

    def finish_prefill(self, state):
        """Sample the first token from the final chunk's logits, deposit
        the now-complete prompt blocks into the prefix index, and return
        the token (int). The per-request analogue of :meth:`admit`'s
        tail."""
        req, slot = state["req"], state["slot"]
        temp = np.array([req.temperature], np.float32)
        topk = np.array([req.top_k], np.int32)
        toks, self._key = self.gen._run_sample(
            state["first_logits"], temp, topk, self._key)
        if self.pool.prefix_enabled:
            self.pool.prefix_insert(state["prompt"], slot)
        if getattr(req, "trace", None) is not None:
            _trace.record_child("serving/prefill_chunked", state["t0"],
                                time.perf_counter(), req.trace)
        return int(np.asarray(toks)[0])

    # -- disaggregated prefill/decode (KV-block migration) ----------------
    def export_slot(self, slot):
        """Serialize ``slot``'s KV blocks for cross-replica migration
        (the prefill half of the disaggregated split): the block table
        is what makes in-flight KV state a well-defined, movable unit."""
        return self.pool.export_slot(slot)

    def admit_imported(self, requests, slot_ids):
        """Admit requests whose prefill ran on ANOTHER replica: stream
        each request's ``kv`` payload into its slot's blocks instead of
        running a prefill. Mirrors :meth:`admit`'s contract — returns
        the first tokens (carried in the payloads, sampled prefill-side)
        as np int32 [len(requests)]; on failure nothing stays allocated
        and a donated-array loss flags ``bank_lost``."""
        self._ensure_caches()
        t0 = time.perf_counter()
        imported = []
        try:
            for req, slot in zip(requests, slot_ids):
                self.pool.free_slot(slot)     # stale holder (if any)
                self.pool.import_slot(slot, req.kv)
                imported.append(slot)
        except Exception:
            for sl in imported:
                self.pool.free_slot(sl)
            # a scatter failure dropped the donated device arrays
            # (import_slot already forgot them); the other active rows'
            # caches died with them
            if self.pool._arrays is None:
                self.bank_lost = True
            raise
        t1 = time.perf_counter()
        first = np.asarray([int(req.first_token) for req in requests],
                           np.int32)
        for req in requests:
            if getattr(req, "trace", None) is not None:
                _trace.record_child("serving/kv_import", t0, t1,
                                    req.trace)
            # the device pool owns the blocks now: drop the host-side
            # payload — the server's rid-dedup table retains completed
            # request objects, and a pinned multi-MB payload per entry
            # would accumulate into real host-memory growth
            req.kv = None
        return first

    def step(self, tokens, pos, temperature, top_k, budget=None):
        """One decode + pick over the whole slot bank, sent and read in
        place. ``tokens``/``pos``/``temperature``/``top_k`` are np
        arrays of length ``slots`` (free slots carry harmless stale
        values — their rows are never read). Returns sampled np int32
        tokens [slots]. The executable and its signature are the ones
        the decode loop runs through :meth:`dispatch_step`, so calling
        this warms them. ``budget`` (seconds) bounds the wait, see
        :meth:`collect_step`."""
        return self.collect_step(
            self.dispatch_step(tokens, pos, temperature, top_k), budget)

    def dispatch_step(self, tokens, pos, temperature, top_k,
                      from_host=None):
        """Send one decode step over the whole slot bank and return at
        once with what :meth:`collect_step` reads. The step picks its
        own tokens (the pick program that ``temperature`` and ``top_k``
        call for, inside the decode executable) and they stay on the
        device: the next step takes them from there, but for the rows
        whose ``from_host`` is set (bool [slots]; None: all), which
        take ``tokens`` (a row admitted since the last step, whose
        first token the prefill's pick made). Every host vector and
        both block tables are copied here, so the caller may change
        them while the step is in flight; the pool's arrays and the RNG
        key are the step's results from now on, and whatever is sent
        after it (the next step, an admission's scatter) runs behind it
        on the device."""
        gen, slots = self.gen, self.slots
        with _trace.loop_span("engine/feed") as fed:
            self._ensure_caches()
            pick, pick_feed = gen.pick_for(
                np.array(temperature, dtype=np.float32),
                np.array(top_k, dtype=np.int32))
            feed = decode_feed(self.pool, np.array(tokens, dtype=np.int32),
                               np.array(pos, dtype=np.int32))
            feed["token_prev"] = self._prev_tokens \
                if self._prev_tokens is not None \
                else np.zeros(slots, np.int32)
            feed["token_from_host"] = np.ones(slots, bool) \
                if from_host is None or self._prev_tokens is None \
                else np.array(from_host, dtype=bool)
            feed.update(pick_feed)
            fed.attrs["entries"] = len(feed)
        try:
            sent = gen._dispatch(f"decode_paged_{self.pool.dtype}+{pick}",
                                 "decode", feed, self._key)
        except Exception:
            self._drop_bank()  # pool arrays were donated in
            raise
        with _trace.loop_span("engine/adopt"):
            self._prev_tokens = adopt_decode_fetches(self.pool,
                                                     sent.fetches)
            self._key = sent.key
            # what collect_step reads, on its way to the host already
            self._prev_tokens.copy_to_host_async()
            for a in gen.aux_of(sent.kind, sent.fetches).values():
                a.copy_to_host_async()
        return sent

    def collect_step(self, sent, budget=None):
        """Read a dispatched step's tokens: np int32 [slots]. The one
        place the decode loop waits for the chip, so the watchdog
        stands here: with ``budget`` (seconds) a wait longer than that,
        a stall at the chaos point included, raises WatchdogTimeout
        instead of wedging the loop, from a clock asked between polls
        of the result and no thread. On any failure the pool arrays,
        donated into the step, are presumed lost."""
        deadline = time.perf_counter() + budget if budget else None
        try:
            maybe_fail("serving.decode_step")
            self.gen._await(sent, deadline, budget)
            with _trace.loop_span("engine/fetch"):
                out = np.asarray(sent.fetches[0])
                # the batcher puts them on its engine/step span
                self.step_routing = self._count_routing(
                    self.gen.aux_of(sent.kind, sent.fetches))
        except Exception:
            self._drop_bank()
            raise
        return out

    def prefill_bytes(self, prompt_sizes):
        """Device bytes a prefill of these prompts, admitted together,
        holds beyond the weights and the pool (the architecture's own
        count at the buckets the generator would pad them to)."""
        from ..models.generation import length_bucket
        rows = length_bucket(len(prompt_sizes))
        seq = min(length_bucket(max(prompt_sizes), self.gen.bucket_min),
                  self.max_len)
        elem = {"fp32": 4, "bf16": 2, "int8": 1}[self.pool.dtype]
        return self.gen.arch.prefill_bytes(rows, seq, self.max_len, elem)

    def prefill_fit(self, prompt_sizes):
        """How many of these prompts, from the first on, one prefill can
        take together in the device's free memory: at least one (alone a
        prompt is admitted whatever its size, and fails by itself if the
        chip cannot hold it), and all of them where the backend keeps no
        count, as on the CPU. The one owner of that decision: ``admit``
        splits by it and ``DecodeBatcher`` caps a round by it."""
        n = len(prompt_sizes)
        budget = _free_device_bytes() if n > 1 else None
        if budget is not None:
            while n > 1 and self.prefill_bytes(prompt_sizes[:n]) > budget:
                n -= 1
        return n

    def spec_step(self, tokens, pos, temperature, top_k, drafts,
                  num_draft, live, budget=None):
        """One speculative verify + accept step over the whole slot
        bank.

        ``drafts`` is np int32 [slots, K] (drafter proposals per row),
        ``num_draft`` np int32 [slots] counts the real drafts per row
        (0 = the row takes a plain 1-token step through the same
        verify executable), ``live`` marks occupied slots — free rows
        get ``limit`` 0 so every one of their span writes routes to the
        pool's trash block. Returns ``(out [slots, K+1], accepted
        [slots])``: row ``s`` emits ``out[s, :accepted[s] + 1]`` tokens
        (accepted drafts, then the correction/bonus token), all drawn
        from the target distribution by rejection sampling.

        Sent and read in place (the drafter needs the newest tokens
        on the host); ``budget`` bounds the wait for the verify pass as
        in :meth:`collect_step`."""
        deadline = time.perf_counter() + budget if budget else None
        maybe_fail("serving.decode_step")
        self._ensure_caches()
        tok = np.ascontiguousarray(tokens, dtype=np.int32)
        posc = np.ascontiguousarray(pos, dtype=np.int32)
        drafts = np.ascontiguousarray(drafts, dtype=np.int32)
        nd = np.ascontiguousarray(num_draft, dtype=np.int32)
        S = drafts.shape[1] + 1
        cfg = self.gen.cfg
        feed = dict(self.pool.arrays())
        feed["tokens"] = np.concatenate([tok[:, None], drafts], axis=1)
        feed["pos_ids"] = np.clip(
            posc[:, None] + np.arange(S, dtype=np.int32)[None, :],
            0, cfg.max_position - 1)
        feed["start_pos"] = posc
        feed["limit"] = np.where(np.asarray(live, bool), nd + 1,
                                 0).astype(np.int32)
        feed["block_tables"] = np.ascontiguousarray(self.pool.tables)
        kind = f"verify_paged_{self.pool.dtype}"
        try:
            sent = self.gen._dispatch(kind, "decode", feed, self._key)
            logits = adopt_decode_fetches(self.pool, sent.fetches)
            self._key = sent.key
            self.gen._await(sent, deadline, budget)
        except Exception:
            self._drop_bank()  # pool arrays were donated in
            raise
        out, acc, self._key = self.gen._run_spec_accept(
            logits, drafts,
            np.ascontiguousarray(temperature, dtype=np.float32),
            np.ascontiguousarray(top_k, dtype=np.int32), nd, self._key)
        with _trace.loop_span("engine/fetch"):
            return np.asarray(out), np.asarray(acc)

    def _admitted(self, aux, requests, rows, seq):
        """What an admission's ``generator/prefill`` span gains:
        ``cache_bytes``, the bytes its call wrote into the pool (every
        row of the ``rows`` x ``seq`` bucket, the padding's on the trash
        block: ``KVBlockPool.scatter_bytes``); :meth:`_count_routing`'s
        attrs; and, over a pool with a state group, ``prompt_tokens``
        (the real ones) and ``scan_tokens`` (admitted rows x the
        bucket's length: what the recurrence was given to walk), counted
        into ``scan_tokens`` and ``state_slot_writes`` (a row's state
        written into its slot)."""
        attrs = self._count_routing(aux, rows=len(requests))
        attrs["cache_bytes"] = self.pool.scatter_bytes(rows, seq)
        if self.pool.state_layers:
            attrs["prompt_tokens"] = int(sum(r.prompt.size
                                             for r in requests))
            attrs["scan_tokens"] = len(requests) * int(seq)
            if self.stats:
                self.stats.bump("scan_tokens", attrs["scan_tokens"])
                self.stats.bump("state_slot_writes", len(requests))
        return attrs

    def step_attrs(self, live_rows):
        """What the ``engine/step`` span of a step sent over
        ``live_rows`` rows gains: ``loop_attrs`` and, over a pool with a
        state group, ``state_rows``, the live rows whose state the step
        advances (it advances the free slots' too, which nobody reads)."""
        if not self.pool.state_layers:
            return self.loop_attrs
        return dict(self.loop_attrs, state_rows=int(live_rows))
