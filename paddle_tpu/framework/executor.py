"""Scope + Executor.

Capability parity with the reference's Scope
(/root/reference/paddle/fluid/framework/scope.h:46) and Executor
(/root/reference/paddle/fluid/framework/executor.cc:184,495;
 python/paddle/fluid/executor.py:882). TPU-first re-design: `Executor.run`
jit-compiles the whole program once per (program-version, feed-shape,
fetch-list) key and replays the compiled XLA executable — there is no per-op
dispatch loop, no per-run InferShape, and no feed/fetch op injection; feeds
bind directly into the traced env and fetches read out of it.
"""
import time

import numpy as np
import jax
import jax.numpy as jnp

from .core import Program, Variable, default_main_program
from .dtype import np_dtype
from .lowering import analyze_block_io, build_block_fn, build_multi_step_fn
from ..flags import flag as _flag
from ..observability import tracing as _trace
from ..observability import utilization as _util
from ..observability import metrics as _obs_metrics
from ..observability.metrics import default_registry as _registry
from ..observability.recorder import flight_recorder as _flightrec
from ..resilience import NonFiniteError
from ..resilience import maybe_fail as _maybe_fail

RNG_STATE_NAME = "@RNG_KEY@"

# cache_stats() key -> exported metric (name, kind)
_CACHE_METRICS = (
    ("hits", "executor_cache_hits_total", "counter"),
    ("misses", "executor_cache_misses_total", "counter"),
    ("evictions", "executor_cache_evictions_total", "counter"),
    ("inserts", "executor_cache_inserts_total", "counter"),
    ("entries", "executor_cache_entries_count", "gauge"),
    ("bytes", "executor_cache_bytes", "gauge"),
    ("pass_ms", "executor_compile_pass_ms_total", "counter"),
    ("trace_ms", "executor_compile_trace_ms_total", "counter"),
    ("compile_ms", "executor_compile_xla_ms_total", "counter"),
    ("verify_ms", "executor_compile_verify_ms_total", "counter"),
    ("compiles", "executor_compiles_total", "counter"),
)


# live-executor aggregation: counters bank on GC so exported *_total
# stays monotonic across executor churn (tests, rolling in-process
# restarts); gauges — entries/bytes — retire to zero with the cache
# they described (observability.metrics.InstanceAggregator)
_exec_agg = _obs_metrics.InstanceAggregator(
    [k for k, _n, kd in _CACHE_METRICS if kd == "counter"])


def _collect_executors():
    """Scrape-time collector: Executor.cache_stats() summed across
    every live executor plus the retired totals of collected ones (the
    Python payload stays per-instance)."""
    totals = _exec_agg.totals(
        lambda exe: exe.cache_stats(),
        live_only_keys=[k for k, _n, kd in _CACHE_METRICS
                        if kd == "gauge"])
    return [{"name": name, "kind": kind,
             "help": f"Executor cache_stats() {key!r} (summed across "
                     f"live executors)",
             "labels": (), "samples": [((), totals[key])]}
            for key, name, kind in _CACHE_METRICS]


_registry().register_collector(
    _collect_executors,
    families=[{"name": name, "kind": kind,
               "help": f"Executor cache_stats() {key!r}", "labels": ()}
              for key, name, kind in _CACHE_METRICS])


def _nonfinite_count(value):
    """Count nan/inf elements host-side. Integer/bool tensors are always
    finite; non-native floats (bfloat16 & friends) go through float32."""
    arr = np.asarray(value)
    kind = arr.dtype.kind
    if kind in "iub" or arr.size == 0:
        return 0
    if kind not in "fc":
        try:
            arr = arr.astype(np.float32)
        except (TypeError, ValueError):
            return 0
    return int((~np.isfinite(arr)).sum())


def _scan_nonfinite(fetch_names, fetches, new_state):
    """FLAGS_check_nan_inf scan (reference
    framework/details/nan_inf_utils_detail.cc checks every op output; one
    compiled XLA module has no per-op boundary, so the observable surface
    is fetched outputs + updated state). Returns (kind, name, count) for
    the first offender or None."""
    for name, val in zip(fetch_names, fetches):
        n = _nonfinite_count(val)
        if n:
            return "fetched output", name, n
    for name, val in new_state.items():
        if name == RNG_STATE_NAME:
            continue
        n = _nonfinite_count(val)
        if n:
            return "updated variable", name, n
    return None


class Scope:
    """name -> device array table (reference: framework/scope.h:46). Flat —
    the reference's scope tree existed to manage per-run temporaries, which
    XLA now owns inside the compiled executable."""

    def __init__(self):
        self._vars = {}

    def find_var(self, name):
        return self._vars.get(name)

    def var(self, name):
        return self._vars.setdefault(name, None)

    def set(self, name, value):
        self._vars[name] = value

    def erase(self, name):
        self._vars.pop(name, None)

    def keys(self):
        return self._vars.keys()

    def items(self):
        return self._vars.items()

    def __contains__(self, name):
        return name in self._vars


_global_scope = Scope()


def global_scope():
    return _global_scope


class _scope_guard:
    def __init__(self, scope):
        self.scope = scope

    def __enter__(self):
        global _global_scope
        self.old = _global_scope
        _global_scope = self.scope

    def __exit__(self, *a):
        global _global_scope
        _global_scope = self.old


def scope_guard(scope):
    return _scope_guard(scope)


_RAW_KEY_SHAPES = {"threefry2x32": (2,), "rbg": (4,), "unsafe_rbg": (4,)}


def _key_impl_mismatch(key):
    """True when a RAW uint32 key's shape doesn't match the current
    default PRNG impl (typed keys carry their impl in the dtype and
    never mismatch)."""
    if jnp.issubdtype(getattr(key, "dtype", None), jax.dtypes.prng_key):
        return False
    expect = _RAW_KEY_SHAPES.get(jax.config.jax_default_prng_impl)
    return expect is not None and tuple(key.shape) != expect


def _check_int64_feed(name, arr):
    """Int64 policy (PARITY.md): with jax_enable_x64 off (the default)
    int64 device tensors are stored int32. A fed value outside int32
    range would silently wrap on device (the reference's kernels are true
    int64, e.g. operators/lookup_table_op.h) — validate at the feed
    boundary and raise instead."""
    if arr.dtype == np.int64 and arr.size \
            and not jax.config.jax_enable_x64:
        lo, hi = arr.min(), arr.max()
        if lo < -2**31 or hi >= 2**31:
            raise ValueError(
                f"feed {name!r} holds int64 values outside int32 range "
                f"([{lo}, {hi}]); TPU tensors are 32-bit by default — "
                f"enable jax_enable_x64 for true int64 (PARITY.md "
                f"int64 policy)")


def _sanitize_np_feed(gblock, name, arr):
    """Host-feed sanitation shared by run/run_steps/_device_put_slab:
    cast to the program var's dtype and validate int64 range at the
    feed boundary (np-path only — device arrays are already placed)."""
    var = gblock.vars.get(name) if gblock is not None else None
    if var is not None and arr.dtype != np_dtype(var.dtype):
        arr = arr.astype(np_dtype(var.dtype))
    _check_int64_feed(name, arr)
    return arr


class Executor:
    """Compile-and-run executor with a program cache
    (the reference caches prepared contexts at executor.py:1169; we cache
    jitted callables keyed on program version + feed signature).

    The cache is an LRU capped at ``FLAGS_executor_cache_entries``
    (previously unbounded: every new feed-shape signature grew it
    forever — a shape-diverse inference caller leaked compiled
    executables). Eviction only drops the jitted callable; the next use
    of that signature recompiles. ``cache_stats()`` exposes
    hit/miss/evict counters."""

    def __init__(self, place=None):
        from ..utils.lru import LRUCache
        self.place = place
        self._cache = LRUCache(max_entries=_flag("executor_cache_entries"))
        # optimized-program memo: the pass pipeline's output depends on
        # (program, fetch set, pass config) but NOT on feed shapes — a
        # shape-diverse caller must not re-clone + re-optimize per shape
        # signature, and all shape entries share ONE optimized clone
        self._opt_cache = LRUCache(max_entries=32)
        # cumulative cache-miss cost split: program passes, python
        # trace+StableHLO lowering, XLA compilation (milliseconds)
        self._compile_stats = {"pass_ms": 0.0, "trace_ms": 0.0,
                               "compile_ms": 0.0, "compiles": 0,
                               "verify_ms": 0.0}
        # cost_analysis memo per executable (False = backend reports
        # nothing) + the previous dispatch mark, for the live MFU/HBM
        # gauges (steady-state dispatch-to-dispatch timing — no sync)
        self._exec_costs = LRUCache(max_entries=256)
        self._last_dispatch = None
        self._gap_streak = 0    # consecutive over-cadence deltas
        # FLAGS_profile_ops sampling counters, per cache key (bounded:
        # cleared when the key universe outgrows the compile cache)
        self._profile_seq = {}
        # loop spans (observability.tracing): the trace this executor's
        # runs belong to, and the step number each run carries
        self._span_root = _trace.loop_root(
            f"exe:{id(self) & 0xffffff:x}")
        self._runs = 0
        # closures bind the stat containers, never self; clearing the
        # cache on retire drops the compiled executables (device memory)
        _exec_agg.track(
            self,
            lambda cache=self._cache, cs=self._compile_stats:
                {**cache.stats(), **cs},
            extra_retire=self._cache.clear)

    def cache_stats(self):
        """Compile-cache occupancy, hit/miss/evict counters, and the
        cumulative cost split of every cache miss: ``pass_ms``
        (pre-lowering optimization pipeline), ``trace_ms`` (python
        trace + StableHLO lowering), ``compile_ms`` (XLA compile),
        ``compiles`` (miss count), ``verify_ms`` (FLAGS_verify_passes
        program verification + per-pass translation validation)."""
        return {**self._cache.stats(), **self._compile_stats}

    def _observe_utilization(self, where, cost_key, compiled):
        """Feed the live MFU / HBM-bandwidth gauges: the executable's
        cost_analysis() flops/bytes (memoized once per executable)
        attached to the dispatch-to-dispatch wall time. Only
        consecutive dispatches of the SAME executable are measured —
        the steady-state training/inference loop — so no device sync is
        ever forced for telemetry. A delta far above the loop's recent
        cadence is an idle pause, not a slow step: it is dropped so the
        gauge keeps the utilization-while-executing semantics the
        serving stages report (utilization.py module docstring)."""
        now = time.perf_counter()
        cost = _util.cost_for(self._exec_costs, cost_key, compiled)
        prev = self._last_dispatch
        delta = cadence = None
        if prev is not None and prev[0] == cost_key:
            delta = now - prev[1]
            cadence = prev[2]
            if cadence is None:
                # first delta only SEEDS the cadence baseline — it may
                # span an arbitrary idle gap after warmup, which must
                # not inflate device_compute_ms_total
                cadence, delta = delta, None
                self._gap_streak = 0
            elif delta > 10.0 * cadence:
                # one or two outliers are idle gaps; a RUN of them
                # means the loop is durably slower, and a frozen
                # baseline would classify every future delta as idle —
                # gauges stuck at the pre-slowdown reading forever.
                # Re-seed exactly like the first delta above.
                self._gap_streak += 1
                if self._gap_streak >= 3:
                    cadence, delta = delta, None
                    self._gap_streak = 0
                else:
                    delta = None
            else:
                cadence = delta
                self._gap_streak = 0
        self._last_dispatch = (cost_key, now, cadence)
        if delta is not None and cost:
            _util.observe_execution(where, cost, delta)

    def _maybe_shard_obs(self, where, cache_key, compiled, mesh,
                         program, feed_names, batch_dim=0):
        """FLAGS_shard_audit / FLAGS_comms_ledger hook: audit one NEWLY
        compiled mesh executable's actual shardings and parse its HLO
        for collective traffic (observability/sharding.py + comms.py).
        Sits on the compile-miss path only, so it runs once per
        executable by construction; with both flags off the shared
        front door costs two flag reads per compile and nothing on the
        hot path (the cost_for read lands in the same memo
        _observe_utilization fills on this step anyway). The audit
        only reads the compiled artifact — numerics are
        bitwise-unchanged either way."""
        if mesh is None:
            return
        from ..observability.sharding import maybe_observe
        maybe_observe(
            where, compiled, mesh, program=program,
            feed_names=feed_names, batch_dim=batch_dim,
            cost=_util.cost_for(self._exec_costs, cache_key, compiled),
            tag=f"program_{program._uid}")

    def _optimize(self, program, fetch_names, feed_names=(), scope=None):
        """Run the FLAGS_program_passes pipeline over a clone of
        `program` (framework/passes.py), charging the span to
        ``pass_ms`` and the ``pass/program_<uid>`` profiler event. With
        the pipeline off the original program is returned untouched —
        bitwise the unoptimized lowering.

        Under ``FLAGS_verify_passes`` every compile-cache miss also
        verifies the USER program (framework/analysis.verify_program,
        with the live scope's names so scope-state reads/fetches check
        exactly) and each pass's output — a malformed program fails with
        a typed ProgramVerifyError naming the op (and producing pass)
        instead of a deep lowering KeyError. Verification wall time
        accumulates in ``cache_stats()['verify_ms']``."""
        from .. import profiler as _prof
        from .passes import _last_stats as _pass_stats
        from .passes import optimize_program, pipeline_signature
        sig = pipeline_signature()
        verify = _flag("verify_passes")
        if not sig and not verify:
            return program
        if verify:
            # verify on EVERY executable-cache miss, before the
            # optimized-program memo: feeds/scope/flag state differ per
            # call, so a memoized clean verdict from one (feed, scope)
            # must not silence a later broken binding (~1 ms against a
            # compile measured in hundreds)
            from .analysis import verify_program
            t0 = time.perf_counter()
            verify_program(
                program, fetch_names=fetch_names, feed_names=feed_names,
                scope_names=(set(scope.keys())
                             if scope is not None else None))
            self._compile_stats["verify_ms"] += \
                (time.perf_counter() - t0) * 1e3
        if not sig:
            return program
        # verify is part of the key: an optimized clone memoized with
        # validation off must not be served as 'validated' after the
        # operator flips FLAGS_verify_passes on to debug that program
        key = (program._uid, program.version, tuple(fetch_names), sig,
               verify)
        opt = self._opt_cache.get(key)
        if opt is not None:
            return opt
        t0 = time.perf_counter()
        opt = optimize_program(program, fetch_names=fetch_names)
        if opt is not program:
            dt = time.perf_counter() - t0
            vms = _pass_stats.get("verify_ms", 0.0) if verify else 0.0
            # the optimize span includes the per-pass validation when
            # the flag is on; split it out so pass_ms + verify_ms sum
            # to the miss cost instead of double-counting validation
            self._compile_stats["pass_ms"] += max(dt * 1e3 - vms, 0.0)
            self._compile_stats["verify_ms"] += vms
            _prof.record_duration(f"pass/program_{program._uid}",
                                  max(dt - vms / 1e3, 0.0))
        self._opt_cache[key] = opt
        return opt

    def _lower_and_compile(self, jitted, event, args):
        """Explicit trace (``jitted.lower``) / XLA-compile split so the
        two are separately measurable (``trace/<event>`` and
        ``compile/<event>`` profiler rows, cache_stats() totals). The
        returned AOT executable is what the cache replays."""
        from .. import profiler as _prof
        t0 = time.perf_counter()
        lowered = jitted.lower(*args)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()
        self._compile_stats["trace_ms"] += (t1 - t0) * 1e3
        self._compile_stats["compile_ms"] += (t2 - t1) * 1e3
        self._compile_stats["compiles"] += 1
        _prof.record_duration(f"trace/{event}", t1 - t0)
        _prof.record_duration(f"compile/{event}", t2 - t1)
        return compiled

    @staticmethod
    def _state_fetches(program, fetch_names, feed_names, state_in, scope):
        """Fetch targets no op produces and no feed binds are reads of
        scope state (e.g. PTQ fetching calibrated weights): they must
        ride state_in into the env even when DCE pruned every op that
        read them. Only names the scope actually holds qualify — a
        typo'd fetch stays out of state_in and surfaces as the
        trace-time \"fetch target was never computed\" KeyError instead
        of a misleading not-initialized error. Returns
        (state_in + extras, extras): the extras are scope-DEPENDENT, so
        cache entries record them and a hit under a scope that lacks one
        recompiles instead of replaying a wrong binding."""
        produced = {n for blk in program.blocks for op in blk.ops
                    for n in op.output_arg_names}
        known = produced | set(feed_names) | set(state_in)
        extras = [n for n in fetch_names
                  if n not in known and scope.find_var(n) is not None]
        return state_in + extras, tuple(extras)

    @staticmethod
    def _entry_valid(entry, scope):
        """A cached entry is replayable under `scope` iff every
        scope-state fetch it was compiled with is still present."""
        return all(scope.find_var(n) is not None for n in entry[-1])

    def _invoke(self, compiled, jitted, args, event, cache_key=None):
        """Replay the AOT executable; if the call-time avals drifted from
        the lowered ones (e.g. scope state replaced with a different
        weak-type/sharding after a checkpoint load), RE-lower+compile
        under the new avals and refresh the cache entry, so later calls
        return to the AOT fast path instead of paying a raised-and-caught
        validation error per step. Only input-validation failures recover
        — the AOT call validates BEFORE executing (and before any buffer
        donation), so nothing runs twice and the args are intact for the
        recompile; the recompile shows up in cache_stats() ``compiles``
        and the ``trace/``/``compile/`` events. Any other error
        propagates."""
        try:
            return compiled(*args)
        except (TypeError, ValueError) as e:
            if "compiled" not in str(e).lower():
                raise
            new_compiled = self._lower_and_compile(jitted, event, args)
            if cache_key is not None:
                ent = self._cache.get(cache_key)
                if ent is not None:
                    self._cache[cache_key] = \
                        (new_compiled,) + tuple(ent[1:])
            return new_compiled(*args)

    # -- helpers ---------------------------------------------------------
    @staticmethod
    def _feed_dict(feed):
        out = {}
        for k, v in (feed or {}).items():
            name = k.name if isinstance(k, Variable) else k
            out[name] = v
        return out

    @staticmethod
    def _fetch_names(fetch_list):
        names = []
        for f in fetch_list or []:
            names.append(f.name if isinstance(f, Variable) else str(f))
        return names

    @staticmethod
    def _split_scope_state(scope, state_in, state_out_set):
        """Bind state_in vars from the scope into (mutable, read-only)
        dicts — shared by run() and run_steps()."""
        state_mut, state_ro = {}, {}
        for n in state_in:
            v = scope.find_var(n)
            if v is None:
                raise RuntimeError(
                    f"variable {n!r} is not initialized in the scope — "
                    f"run the startup program first (fluid semantics: "
                    f"exe.run(fluid.default_startup_program()))")
            (state_mut if n in state_out_set else state_ro)[n] = v
        return state_mut, state_ro

    @staticmethod
    def _reshard_state_to_scope(scope, program, mesh, state_mut, state_ro):
        """Place state per dist_attr and write resharded arrays back so
        later runs see them already placed — shared by run()/run_steps()."""
        for st in (state_mut, state_ro):
            if _shard_state(st, mesh, program):
                for n, a in st.items():
                    scope.set(n, a)

    def _ensure_rng(self, scope, program):
        key = scope.find_var(RNG_STATE_NAME)
        if key is None or _key_impl_mismatch(key):
            # (re-)seed under the CURRENT default PRNG impl: a raw key
            # minted under threefry (shape (2,)) is rejected by
            # split/fold_in once the app switches to rbg (shape (4,)) —
            # e.g. bench.py enables rbg after tests populated the scope
            seed = program.random_seed or 0
            key = jax.random.PRNGKey(seed)
            scope.set(RNG_STATE_NAME, key)
        return key

    # -- main entry ------------------------------------------------------
    def run(self, program=None, feed=None, fetch_list=None, scope=None,
            return_numpy=True, use_program_cache=True,
            check_nan_inf=None, skip_nonfinite_steps=False):
        """``check_nan_inf`` (default: FLAGS_check_nan_inf) scans fetched
        outputs and updated variables for nan/inf after the step and
        raises NonFiniteError (an EnforceNotMet) naming the first
        offender. ``skip_nonfinite_steps`` instead ROLLS BACK the step —
        scope state and RNG are restored to their pre-step values and the
        (non-finite) fetches are returned, so one bad batch cannot poison
        the parameters (the trainer loop moves on to the next batch)."""
        from ..parallel.compiler import CompiledProgram
        mesh = None
        if isinstance(program, CompiledProgram):
            mesh = program.mesh
            program = program.program
        if program is None:
            program = default_main_program()
        scope = scope or global_scope()
        ls_op = next((op for op in program.global_block().ops
                      if op.type == "listen_and_serv"), None)
        if ls_op is not None:
            return self._run_pserver(ls_op, scope)
        self._runs += 1
        with _trace.loop_span("executor/run", self._span_root,
                              step_num=self._runs,
                              program=program._uid) as span:
            return self._run(program, mesh, feed, fetch_list, scope,
                             return_numpy, use_program_cache,
                             check_nan_inf, skip_nonfinite_steps,
                             span.attrs)

    def _run(self, program, mesh, feed, fetch_list, scope, return_numpy,
             use_program_cache, check_nan_inf, skip_nonfinite_steps,
             attrs):
        """The body of :meth:`run`, inside its ``executor/run`` loop
        span (``attrs`` are the span's): ``executor/prepare`` (feed
        conversion, the cache key, the state gathered from the scope;
        on a miss the compile), ``executor/dispatch`` (the compiled
        call), ``executor/commit`` (utilization, the scope's new
        state) and, where the fetches come back as numpy,
        ``executor/fetch_wait``."""
        with _trace.loop_span("executor/prepare"):
            feed = self._feed_dict(feed)
            fetch_names = self._fetch_names(fetch_list)

            feed_arrays = {}
            feed_sig = []
            for name, val in feed.items():
                arr = val if isinstance(val, jax.Array) \
                    else np.asarray(val)
                if isinstance(arr, np.ndarray):
                    arr = _sanitize_np_feed(program.global_block(), name,
                                            arr)
                feed_arrays[name] = arr
                feed_sig.append((name, tuple(arr.shape), str(arr.dtype)))

            from .passes import pipeline_signature
            cache_key = (program._uid, program.version,
                         tuple(sorted(feed_sig)), tuple(fetch_names),
                         id(mesh), pipeline_signature())
            entry = self._cache.get(cache_key) if use_program_cache \
                else None
            if entry is not None and not self._entry_valid(entry, scope):
                entry = None           # scope-state fetch binding changed
            attrs["compiled"] = entry is None
            if entry is not None:
                compiled, jitted, state_in, state_out, state_fetches = entry
            else:
                opt_prog = self._optimize(program, fetch_names,
                                          feed_names=feed_arrays.keys(),
                                          scope=scope)
                state_in, state_out = analyze_block_io(
                    opt_prog, 0, list(feed_arrays.keys()))
                state_in, state_fetches = self._state_fetches(
                    opt_prog, fetch_names, feed_arrays, state_in, scope)

            base_key = self._ensure_rng(scope, program)
            state_out_set = set(state_out)
            state_mut, state_ro = self._split_scope_state(
                scope, state_in, state_out_set)

            if mesh is not None:
                feed_arrays = _shard_feed(feed_arrays, mesh, program)
                # esp. read-only params of inference programs
                self._reshard_state_to_scope(scope, program, mesh,
                                             state_mut, state_ro)

            if entry is None:
                fn = build_block_fn(opt_prog, 0, list(feed_arrays.keys()),
                                    fetch_names, state_in, state_out,
                                    mesh=mesh)
                if mesh is not None:
                    jitted = _jit_with_mesh(fn, mesh, opt_prog)
                else:
                    jitted = jax.jit(fn, donate_argnums=(0,))
                compiled = self._lower_and_compile(
                    jitted, f"program_{program._uid}",
                    (state_mut, state_ro, feed_arrays, base_key))
                if use_program_cache:
                    self._cache[cache_key] = (compiled, jitted, state_in,
                                              state_out, state_fetches)
                self._maybe_shard_obs("step", cache_key, compiled, mesh,
                                      program, tuple(feed_arrays))
                if mesh is not None and "dcn_dp" in mesh.axis_names \
                        and _flag("dcn_hierarchical") \
                        and any(op.type == "hier_allreduce"
                                for op in program.global_block().ops):
                    # the single-step run() path lowers through plain GSPMD:
                    # hier_allreduce collapses to identity (no bound axes) and
                    # the gradient sync comes back as ONE flat all-reduce over
                    # dcn_dp+dp — numerically right, but every byte of it
                    # crosses the DCN. Warn once per compiled executable; the
                    # decomposed path is run_steps.
                    _flightrec().record(
                        "hier_single_step_flat",
                        where=f"program_{program._uid}",
                        mesh_axes=",".join(mesh.axis_names),
                        hint="FLAGS_dcn_hierarchical is on and the program "
                             "carries hier_allreduce sync ops, but "
                             "Executor.run lowers flat-GSPMD; use "
                             "run_steps for the hierarchical DCN path")

            if check_nan_inf is None:
                check_nan_inf = _flag("check_nan_inf")
            backup = None
            if skip_nonfinite_steps:
                # the executable donates state_mut buffers, so rollback needs
                # host copies taken BEFORE the step (the price of the opt-in)
                backup = {n: np.asarray(v) for n, v in state_mut.items()}

            # sampled measured op profiling (FLAGS_profile_ops=N): every
            # N-th dispatch of a program replays the optimized clone
            # op-by-op BEFORE the fused invoke (its buffers are donated
            # after). The committed result below is still the fused
            # executable's — numerics are untouched; with the default N=0
            # this costs one flag read.
            prof_n = int(_flag("profile_ops"))
            if prof_n > 0 and mesh is None:
                self._maybe_profile_ops(prof_n, cache_key, program,
                                        fetch_names, feed_arrays, state_mut,
                                        state_ro, base_key, scope)

        from .. import profiler as _prof
        with _trace.loop_span("executor/dispatch"):
            invoke_args = (compiled, jitted,
                           (state_mut, state_ro, feed_arrays, base_key),
                           f"program_{program._uid}",
                           cache_key if use_program_cache else None)
            if _prof.is_profiling():
                with _prof.record_event(f"run/program_{program._uid}"):
                    fetches, new_state, new_key = self._invoke(*invoke_args)
                    jax.block_until_ready(fetches)
            else:
                fetches, new_state, new_key = self._invoke(*invoke_args)
        with _trace.loop_span("executor/commit"):
            self._observe_utilization("step", cache_key, compiled)

            bad = None
            if check_nan_inf or skip_nonfinite_steps:
                bad = _scan_nonfinite(fetch_names, fetches, new_state)
            if bad is not None and skip_nonfinite_steps:
                # roll the step back: pre-step params/accumulators and RNG go
                # back into the scope, nothing is committed
                kind, name, count = bad
                _flightrec().record("nonfinite", program=program._uid,
                                    var=name, count=count, where=kind,
                                    rolled_back=True)
                for n, a in backup.items():
                    scope.set(n, a)
                scope.set(RNG_STATE_NAME, base_key)
                print(f"[executor] skip_nonfinite_steps: {kind} {name!r} has "
                      f"{count} non-finite value(s) — step rolled back")
                if return_numpy:
                    return [np.asarray(f) for f in fetches]
                return fetches

            # commit even when about to raise: state_mut buffers were donated
            # to the jit, so the scope must reference the step's outputs (the
            # error is a diagnostic about the step, not a rollback)
            for n, v in new_state.items():
                scope.set(n, v)
            scope.set(RNG_STATE_NAME, new_key)
            if bad is not None:
                kind, name, count = bad
                _flightrec().record("nonfinite", program=program._uid,
                                    var=name, count=count, where=kind)
                raise NonFiniteError(
                    f"Operator output contains Inf/Nan (FLAGS_check_nan_inf): "
                    f"{kind} {name!r} has {count} non-finite value(s) in "
                    f"program_{program._uid}. Feed data, learning rate, or "
                    f"loss scaling are the usual suspects.",
                    var_name=name, count=count)

        if return_numpy:
            with _trace.loop_span("executor/fetch_wait"):
                return [np.asarray(f) for f in fetches]
        return fetches

    # -- fused multi-step entry -----------------------------------------
    def run_steps(self, program=None, feed=None, fetch_list=None,
                  scope=None, return_numpy=True, use_program_cache=True,
                  check_nan_inf=None, skip_nonfinite_steps=False,
                  steps_per_run=None, unroll=None):
        """Run K training steps as ONE compiled executable: a jitted
        ``lax.scan`` over feeds stacked on a leading K axis (a "slab").
        Bitwise-identical to K sequential :meth:`run` calls — state
        threads through the scan carry with buffer donation and the RNG
        chain advances per step exactly as the unfused path does — but
        pays Python dispatch, H2D binding, and (optionally) fetch
        materialization once per slab instead of once per step.

        `feed` is either a dict of arrays with a leading K axis or a list
        of K per-step feed dicts (stacked here). Fetches come back
        stacked on a leading K axis, transferred in ONE device->host copy
        when `return_numpy` (device arrays, sync-free, otherwise).

        ``check_nan_inf`` (default FLAGS_check_nan_inf) compiles an
        on-device guard into the scan: each step emits a non-finite
        violation count + first-offender slot index, and the host reads
        back one small int vector per slab — no parameter transfer.
        NOTE: the raised NonFiniteError names the FIRST bad step, but
        all K steps have executed and the scope holds end-of-slab state
        (stopping mid-slab would need a per-step host sync — the cost
        this path removes). To preserve usable state past a bad batch
        use ``skip_nonfinite_steps`` (in-graph rollback); for
        first-failure forensics run with steps_per_run=1.
        ``skip_nonfinite_steps`` compiles the rollback IN-GRAPH: a
        ``lax.cond`` selects the pre-step state (and pre-step RNG key)
        when the step produced non-finite values, so no host backup
        copies exist and mesh-sharded state rolls back without a gather.

        ``unroll`` (default FLAGS_scan_unroll) is the scan unroll
        factor. The loop form (1) is bitwise-identical to sequential
        run(); 0 = auto picks full unroll on the CPU backend (whose
        while-loop bodies lose intra-op threading) — unrolled steps may
        fuse across step boundaries, numerically equivalent but not
        bit-identical.
        """
        from ..parallel.compiler import CompiledProgram
        mesh = None
        if isinstance(program, CompiledProgram):
            mesh = program.mesh
            program = program.program
        if program is None:
            program = default_main_program()
        scope = scope or global_scope()
        self._runs += 1
        with _trace.loop_span("executor/run", self._span_root,
                              step_num=self._runs,
                              program=program._uid) as span:
            return self._run_steps(
                program, mesh, feed, fetch_list, scope, return_numpy,
                use_program_cache, check_nan_inf, skip_nonfinite_steps,
                steps_per_run, unroll, span.attrs)

    def _run_steps(self, program, mesh, feed, fetch_list, scope,
                   return_numpy, use_program_cache, check_nan_inf,
                   skip_nonfinite_steps, steps_per_run, unroll, attrs):
        """The body of :meth:`run_steps`, inside its ``executor/run``
        loop span: the four phases of :meth:`_run`, once a slab."""
        with _trace.loop_span("executor/prepare"):
            if isinstance(feed, (list, tuple)):
                feed = _stack_feed_slab([self._feed_dict(f) for f in feed])
            feed = self._feed_dict(feed)
            if not feed:
                raise ValueError(
                    "run_steps needs at least one fed variable: the slab's "
                    "leading axis defines the step count")
            fetch_names = self._fetch_names(fetch_list)

            feed_arrays = {}
            feed_sig = []
            k_steps = None
            for name, val in feed.items():
                arr = val if isinstance(val, jax.Array) \
                    else np.asarray(val)
                if arr.ndim == 0:
                    raise ValueError(
                        f"feed {name!r} is a scalar — run_steps feeds "
                        f"need a leading steps axis")
                if k_steps is None:
                    k_steps = int(arr.shape[0])
                elif int(arr.shape[0]) != k_steps:
                    raise ValueError(
                        f"feed {name!r} has {arr.shape[0]} steps on its "
                        f"leading axis, other feeds have {k_steps}")
                if isinstance(arr, np.ndarray):
                    arr = _sanitize_np_feed(program.global_block(), name, arr)
                feed_arrays[name] = arr
                feed_sig.append((name, tuple(arr.shape), str(arr.dtype)))
            if steps_per_run is not None and int(steps_per_run) != k_steps:
                raise ValueError(
                    f"steps_per_run={steps_per_run} but the fed slab carries "
                    f"{k_steps} steps on its leading axis")

            if check_nan_inf is None:
                check_nan_inf = _flag("check_nan_inf")
            guard = bool(check_nan_inf or skip_nonfinite_steps)
            if unroll is None:
                unroll = _flag("scan_unroll")
            unroll = int(unroll)
            if unroll <= 0:
                # auto: XLA CPU runs while-loop bodies without intra-op
                # threading — full unroll restores it; accelerators keep the
                # loop form so compile time stays K-independent
                unroll = k_steps if jax.default_backend() == "cpu" else 1

            # hierarchical multi-slice path: a dcn_dp mesh whose program went
            # through the hier_grad_sync pass runs under shard_map so the
            # gradient reduction decomposes per fabric (RS in-slice / AR
            # cross-slice / AG in-slice). Requires the explicit sync ops —
            # without them per-device state would silently diverge — and a
            # pure data-parallel mesh (tp/pp/sp compose via GSPMD only).
            # FLAGS_dcn_hierarchical=False is the flat-GSPMD A/B baseline:
            # same program, hier_allreduce collapses to identity.
            from .lowering import hier_dp_axes
            hier_axes = ()
            if mesh is not None and _flag("dcn_hierarchical") \
                    and set(mesh.axis_names) <= {"dcn_dp", "dp"} \
                    and any(op.type == "hier_allreduce"
                            for op in program.global_block().ops):
                hier_axes = hier_dp_axes(mesh)
            hier_on = bool(hier_axes)

            from .passes import pipeline_signature
            cache_key = (program._uid, program.version,
                         tuple(sorted(feed_sig)), tuple(fetch_names), id(mesh),
                         "steps", k_steps, guard, bool(skip_nonfinite_steps),
                         unroll, hier_on, pipeline_signature())
            entry = self._cache.get(cache_key) if use_program_cache else None
            if entry is not None and not self._entry_valid(entry, scope):
                entry = None               # scope-state fetch binding changed
            fresh_compile = entry is None
            attrs["compiled"], attrs["steps"] = fresh_compile, k_steps
            if entry is not None:
                (compiled, jitted, state_in, state_out, mut_names, slot_names,
                 wo_avals, state_fetches) = entry
            else:
                opt_prog = self._optimize(program, fetch_names,
                                          feed_names=feed_arrays.keys(),
                                          scope=scope)
                state_in, state_out = analyze_block_io(
                    opt_prog, 0, list(feed_arrays.keys()))
                state_in, state_fetches = self._state_fetches(
                    opt_prog, fetch_names, feed_arrays, state_in, scope)

            base_key = self._ensure_rng(scope, program)
            state_out_set = set(state_out)
            state_mut, state_ro = self._split_scope_state(scope, state_in,
                                                          state_out_set)

            if mesh is not None:
                feed_arrays = _shard_feed_slab(feed_arrays, mesh)
                self._reshard_state_to_scope(scope, program, mesh, state_mut,
                                             state_ro)

            from .. import profiler as _prof
            if fresh_compile:
                step_fn = build_block_fn(
                    opt_prog, 0, list(feed_arrays.keys()), fetch_names,
                    state_in, state_out, mesh=mesh)
                feed_row = {n: jax.ShapeDtypeStruct(a.shape[1:], a.dtype)
                            for n, a in feed_arrays.items()}
                _, new_state_s, _ = jax.eval_shape(
                    step_fn, state_mut, state_ro, feed_row, base_key)
                mut_names = [n for n in state_in if n in state_out_set]
                slot_names = (["fetched output " + repr(n)
                               for n in fetch_names]
                              + ["updated variable " + repr(n)
                                 for n in new_state_s])
                wo_avals = {n: jax.ShapeDtypeStruct(s.shape, s.dtype)
                            for n, s in new_state_s.items()
                            if n not in state_mut}

            # write-only persistable outputs ride the scan carry so a
            # rolled-back step restores what the scope held (sequential-skip
            # parity); vars the scope has never seen are seeded with zeros
            # and un-committed below if every step rolled back
            absent_wo = set()
            for n, aval in wo_avals.items():
                v = scope.find_var(n)
                if v is None:
                    v = np.zeros(aval.shape, aval.dtype)
                    absent_wo.add(n)
                state_mut[n] = v
            if mesh is not None and wo_avals:
                tmp = {n: state_mut[n] for n in wo_avals}
                _shard_state(tmp, mesh, program)
                state_mut.update(tmp)

            if fresh_compile:
                fn = build_multi_step_fn(
                    opt_prog, 0, list(feed_arrays.keys()), fetch_names,
                    state_in, state_out, mut_names, mesh=mesh,
                    guard=guard,
                    skip_nonfinite=bool(skip_nonfinite_steps),
                    unroll=unroll,
                    viol_axes=hier_axes)
                if hier_on:
                    from .lowering import wrap_hier_dp_steps
                    jitted = jax.jit(wrap_hier_dp_steps(fn, mesh, feed_arrays),
                                     donate_argnums=(0,))
                elif mesh is not None:
                    jitted = _jit_with_mesh_steps(fn, mesh)
                else:
                    jitted = jax.jit(fn, donate_argnums=(0,))
                compiled = self._lower_and_compile(
                    jitted, f"fused_program_{program._uid}_x{k_steps}",
                    (state_mut, state_ro, feed_arrays, base_key))
                if use_program_cache:
                    self._cache[cache_key] = (compiled, jitted, state_in,
                                              state_out, mut_names,
                                              slot_names, wo_avals,
                                              state_fetches)
                # batch_dim=1: the slab's leading K axis replicates by
                # design; the batch dim the dp axis should shard sits
                # under it
                self._maybe_shard_obs("train", cache_key, compiled, mesh,
                                      program, tuple(feed_arrays),
                                      batch_dim=1)
                if hier_on and _flag("dcn_assert_hier"):
                    # pre-burn gate: parse the compiled HLO and prove the
                    # hierarchical decomposition landed — DCN-priced traffic
                    # only on the designated axes, cross-slice wire bytes
                    # strictly below the flat all-reduce — BEFORE the first
                    # slab is dispatched to hardware
                    from ..observability.comms import assert_hier_decomposition
                    assert_hier_decomposition(
                        compiled, mesh,
                        where=f"fused_program_{program._uid}_x{k_steps}")

        with _trace.loop_span("executor/dispatch"):
            # chaos point for the training dispatch stage: fires BEFORE the
            # executable runs, so the scope still holds pre-slab state and a
            # supervised restart resumes bitwise from the last checkpoint
            _maybe_fail("train.dispatch")
            if hier_axes:
                # chaos point for the cross-slice reduction stage: raising
                # simulates a slice whose DCN collective fails; delay=
                # simulates a straggling slice stretching the step
                _maybe_fail("train.allreduce_dcn")
            profiling = _prof.is_profiling()
            t0 = time.perf_counter()
            fetches, final_state, final_key, viols, slots = self._invoke(
                compiled, jitted, (state_mut, state_ro, feed_arrays, base_key),
                f"fused_program_{program._uid}_x{k_steps}",
                cache_key if use_program_cache else None)
            if profiling:
                t1 = time.perf_counter()
                jax.block_until_ready(fetches if fetches else final_key)
                span = time.perf_counter() - t0
                _prof.record_duration(
                    f"dispatch/program_{program._uid}_x{k_steps}", t1 - t0)
                _prof.record_duration(
                    f"scan/program_{program._uid}_x{k_steps}", span)
                _prof.record_step_time(span / k_steps, k_steps)
        with _trace.loop_span("executor/commit"):
            self._observe_utilization("train", cache_key, compiled)

            v = np.asarray(viols) if guard else None  # ONE small readback
            # commit (buffers were donated); guard diagnostics after. If
            # EVERY step rolled back, scope-absent write-only vars stay
            # uncommitted — K sequential skipped run() calls never create
            # them either (their committed value would be the zeros seed).
            all_rolled = bool(skip_nonfinite_steps and v is not None
                              and v.size and (v > 0).all())
            for n, val in final_state.items():
                if all_rolled and n in absent_wo:
                    continue
                scope.set(n, val)
            scope.set(RNG_STATE_NAME, final_key)

            if guard and v.any():
                first = int(np.argmax(v > 0))
                name = self._slot_name(slots, first, slot_names)
                _flightrec().record(
                    "nonfinite", program=program._uid, var=name,
                    count=int(v[first]), where=f"fused step {first}",
                    rolled_back=bool(skip_nonfinite_steps))
                if skip_nonfinite_steps:
                    rolled = int((v > 0).sum())
                    print(f"[executor] skip_nonfinite_steps: {rolled} of "
                          f"{k_steps} fused step(s) rolled back in-graph "
                          f"(first at slab step {first}: {int(v[first])} "
                          f"non-finite value(s) across outputs/state, "
                          f"first offender {name})")
                else:
                    raise NonFiniteError(
                        f"Operator output contains Inf/Nan "
                        f"(FLAGS_check_nan_inf): fused step "
                        f"{first}/{k_steps} of program_{program._uid} "
                        f"produced {int(v[first])} non-finite value(s) "
                        f"across outputs/state; first offender {name}. "
                        f"Feed data, learning rate, or loss scaling are "
                        f"the usual suspects.",
                        var_name=name, count=int(v[first]))

        if return_numpy:
            with _trace.loop_span("executor/fetch_wait"):
                return [np.asarray(f) for f in fetches]
        return fetches

    @staticmethod
    def _slot_name(slots, step_idx, slot_names):
        i = int(np.asarray(slots)[step_idx])
        return slot_names[i] if 0 <= i < len(slot_names) else f"slot {i}"

    def _maybe_profile_ops(self, every_n, cache_key, program,
                           fetch_names, feed_arrays, state_mut,
                           state_ro, base_key, scope):
        """The FLAGS_profile_ops sampling gate + measured replay: every
        ``every_n``-th dispatch of ``cache_key``, interpret the pass
        pipeline's optimized CLONE eagerly with per-op timing
        (observability.profiling.measure_op_times — spans, the
        hbm_live_bytes counter track, and the last_op_profile() table).
        Failures are swallowed: profiling must never break a step."""
        if len(self._profile_seq) > 512:
            self._profile_seq.clear()
        seq = self._profile_seq.get(cache_key, 0) + 1
        self._profile_seq[cache_key] = seq
        if (seq - 1) % max(every_n, 1):
            return
        try:
            from ..observability import profiling as _opprof
            opt = self._optimize(program, fetch_names,
                                 feed_names=feed_arrays.keys(),
                                 scope=scope)
            env = dict(state_ro)
            env.update(state_mut)
            env.update(feed_arrays)
            env[RNG_STATE_NAME] = base_key
            _opprof.measure_op_times(opt, env,
                                     tag=f"program_{program._uid}")
        except Exception:  # noqa: BLE001 — telemetry never kills a step
            pass

    def _run_pserver(self, ls_op, scope):
        """Host parameter-server event loop (reference
        listen_and_serv_op.cc:333 RunImpl — the op IS the server). Blocks
        until every trainer sent `stop`; the final tables are written back
        to the scope."""
        import numpy as np
        from ..distributed.ps import ParameterServer

        attrs = ls_op.attrs
        server = ParameterServer(attrs["endpoint"],
                                 trainers=int(attrs.get("Fanin", 1)),
                                 sync_mode=bool(attrs.get("sync_mode",
                                                          True)),
                                 heartbeat_timeout=attrs.get(
                                     "heartbeat_timeout"))
        for name in attrs.get("hosted_vars", []):
            val = scope.find_var(name)
            if val is None:
                raise RuntimeError(
                    f"pserver var {name!r} not initialized — run the "
                    f"pserver startup program first (transpiler."
                    f"get_startup_program(endpoint))")
            server.tables[name] = np.asarray(val)
        server.optimize_blocks = dict(attrs.get("optimize_blocks", {}))
        for name, lr in attrs.get("sparse_tables", {}).items():
            server.sparse_lr[name] = float(lr)
        server.serve(block=True)
        for name, val in server.tables.items():
            scope.set(name, val)
        return []

    def close(self):
        self._cache.clear()
        self._opt_cache.clear()

    # ---- dataset ingestion (reference executor.py:1440 train_from_dataset
    # -> C++ trainer threads; here the host parses/batches and the compiled
    # step consumes, with XLA overlapping H2D against compute) ----
    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100,
                           fetch_handler=None, skip_nonfinite_steps=False,
                           steps_per_run=None, fetch_every_n=None):
        """``steps_per_run=K`` (default FLAGS_steps_per_run) drives the
        fused :meth:`run_steps` path: the dataset collates K-step slabs
        (``batch_iterator(slab=K)``), the next slab's H2D transfer is
        dispatched while the current slab executes, and ``print_period``
        reports from the slab's already-materialized stacked fetches —
        no mid-loop device sync. ``fetch_every_n=N`` (default
        FLAGS_fetch_every_n) > 1 skips fetch materialization entirely on
        slabs that contain neither a ``print_period`` step nor an N-th
        slab boundary (those slabs run a fetch-free executable; the
        final slab always fetches so the return value is fresh). Under
        the fused path the returned last-fetches are stacked per-step
        arrays with a leading slab axis, not single-step values."""
        assert dataset is not None, "train_from_dataset needs a dataset"
        k_steps = int(steps_per_run if steps_per_run is not None
                      else _flag("steps_per_run"))
        fetch_every = int(fetch_every_n if fetch_every_n is not None
                          else _flag("fetch_every_n"))
        fetch_names = self._fetch_names(fetch_list)
        fetch_info = fetch_info or fetch_names
        monitor = None
        if fetch_handler is not None:
            monitor = _FetchHandlerMonitor(scope or global_scope(),
                                           fetch_handler)
            monitor.start()
        try:
            if k_steps > 1:
                return self._train_fused(
                    program, dataset, scope, fetch_list, fetch_names,
                    fetch_info, print_period, skip_nonfinite_steps,
                    k_steps, fetch_every)
            return self._train_stepwise(
                program, dataset, scope, fetch_list, fetch_names,
                fetch_info, print_period, skip_nonfinite_steps)
        finally:
            if monitor is not None:
                monitor.stop()

    def _train_stepwise(self, program, dataset, scope, fetch_list,
                        fetch_names, fetch_info, print_period,
                        skip_nonfinite_steps):
        """One run() per batch. Steps dispatch asynchronously
        (return_numpy=False); fetches only materialize on a reporting
        step — a print_period hit no longer forces a device sync on every
        non-reporting step, and step 0 (untrained params) is not
        reported."""
        last = None
        for step, feed in enumerate(dataset.batch_iterator()):
            out = self.run(program, feed=feed,
                           fetch_list=fetch_list, scope=scope,
                           return_numpy=False,
                           skip_nonfinite_steps=skip_nonfinite_steps)
            last = out
            if fetch_names and print_period and step \
                    and step % print_period == 0:
                vals = [np.asarray(v) for v in out]
                msg = ", ".join(f"{i}={v.mean():.6f}"
                                for i, v in zip(fetch_info, vals))
                print(f"step {step}: {msg}")
            elif step % 64 == 63:
                # backpressure: async dispatch with no fetch sync would
                # otherwise let in-flight steps (and their feed buffers)
                # pile up without bound on the device queue
                _block_on_step(out, scope)
        if last is not None:
            last = [np.asarray(v) for v in last]
        return last

    def _train_fused(self, program, dataset, scope, fetch_list,
                     fetch_names, fetch_info, print_period,
                     skip_nonfinite_steps, k_steps, fetch_every):
        """Slab loop behind train_from_dataset(steps_per_run=K): full
        slabs go through run_steps (one compiled scan), the short tail
        slab (dataset length not divisible by K, or a partial final
        batch) falls back to sequential run() calls so no second
        executable is compiled for a shape seen once."""
        from ..parallel.compiler import CompiledProgram
        if program is None:
            # resolve here, not just in run_steps: _device_put_slab
            # needs the program for feed dtype casts + int64 validation
            program = default_main_program()
        # mesh feeds are placed by _shard_feed_slab at run time; plain
        # device_put here would pin them to device 0 first
        prefetch = not isinstance(program, CompiledProgram)
        try:
            it = dataset.batch_iterator(slab=k_steps)
        except TypeError:
            # duck-typed dataset without the slab kwarg: collate here
            from ..dataio.dataset import DatasetBase
            it = DatasetBase._slab_batches(dataset.batch_iterator(),
                                           k_steps)
        last = None
        step = 0
        slab_idx = 0
        cur = next(it, None)
        if cur is not None and prefetch:
            cur = _device_put_slab(cur, program)
        while cur is not None:
            # prefetch BEFORE dispatching: the next slab's H2D is in
            # flight while this slab executes even when the guard makes
            # run_steps block on its per-slab violation readback
            nxt = next(it, None)
            if nxt is not None and prefetch:
                nxt = _device_put_slab(nxt, program)
            k = int(next(iter(cur.values())).shape[0])
            hit = bool(print_period) and fetch_names and any(
                (step + j) and (step + j) % print_period == 0
                for j in range(k))
            want = bool(fetch_names) and (
                fetch_every <= 1 or hit or slab_idx % fetch_every == 0
                or nxt is None)  # final slab: the return value is fresh
            flist = fetch_list if want else []
            if k == k_steps:
                out = self.run_steps(
                    program, feed=cur, fetch_list=flist, scope=scope,
                    return_numpy=False,
                    skip_nonfinite_steps=skip_nonfinite_steps)
            else:
                outs = [self.run(program,
                                 feed={n: a[j] for n, a in cur.items()},
                                 fetch_list=flist, scope=scope,
                                 return_numpy=False,
                                 skip_nonfinite_steps=skip_nonfinite_steps)
                        for j in range(k)]
                out = [np.stack([np.asarray(o[i]) for o in outs])
                       for i in range(len(fetch_names))] if want else []
            if want and out:
                mats = [np.asarray(v) for v in out]  # one copy per slab
                last = mats
                if hit:
                    for j in range(k):
                        g = step + j
                        if g and g % print_period == 0:
                            msg = ", ".join(
                                f"{i}={np.asarray(v[j]).mean():.6f}"
                                for i, v in zip(fetch_info, mats))
                            print(f"step {g}: {msg}")
            if not want and slab_idx % 8 == 7:
                _block_on_step(out, scope)  # bound the dispatch queue
            step += k
            slab_idx += 1
            cur = nxt
        if last is None and not fetch_names and slab_idx:
            last = []  # match the stepwise path's no-fetch return
        return last

    def infer_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        prog = program.clone(for_test=True) if program is not None else None
        return self.train_from_dataset(prog, dataset, scope, thread, debug,
                                       fetch_list, fetch_info, print_period)


def _block_on_step(out, scope):
    """Periodic backpressure for the async training loops: wait for the
    newest dispatched step (its fetches, or the committed RNG key when
    nothing was fetched) so unmaterialized in-flight steps can't grow
    the device queue without bound."""
    ref = out if out else (scope or global_scope()).find_var(
        RNG_STATE_NAME)
    if ref is not None:
        jax.block_until_ready(ref)


def _stack_feed_slab(feeds):
    """Stack a list of per-step feed dicts on a new leading K axis.
    Key ORDER may differ between steps; the variable set may not."""
    if not feeds:
        raise ValueError("run_steps got an empty feed list")
    names = list(feeds[0].keys())
    for f in feeds[1:]:
        if set(f.keys()) != set(names):
            raise ValueError(
                "run_steps feed dicts must bind the same variables in "
                f"every step: {sorted(names)} vs {sorted(f.keys())}")
    return {n: np.stack([np.asarray(f[n]) for f in feeds]) for n in names}


def _device_put_slab(slab, program=None):
    """Async H2D of a host slab (dispatch-only timing: device_put
    returns before the copy lands, which is the point — the transfer
    overlaps the previous slab's compute). Applies the same var-dtype
    cast and int64 feed-boundary validation run() would, BEFORE the
    value becomes a device array and skips that np-path."""
    from .. import profiler as _prof
    _maybe_fail("train.h2d")    # chaos point: slab H2D transfer stage
    gblock = program.global_block() if program is not None else None
    t0 = time.perf_counter()
    out = {}
    for n, a in slab.items():
        if isinstance(a, np.ndarray):
            a = _sanitize_np_feed(gblock, n, a)
        out[n] = jax.device_put(a)
    _prof.record_duration("h2d/slab", time.perf_counter() - t0)
    return out


def _jit_with_mesh(fn, mesh, program):
    """Data-parallel / SPMD jit: params replicated (or sharded per their
    dist_attr), feed sharded on the leading batch dim. XLA GSPMD inserts the
    collectives the reference built by hand in its multi-device SSA graph
    (ir/multi_devices_graph_pass/multi_devices_graph_pass.cc:456)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def sharded_fn(state_mut, state_ro, feed, base_key):
        feed = {
            n: jax.lax.with_sharding_constraint(
                a, NamedSharding(mesh, _batch_pspec(mesh, a)))
            for n, a in feed.items()
        }
        return fn(state_mut, state_ro, feed, base_key)

    return jax.jit(sharded_fn, donate_argnums=(0,))


def _batch_pspec(mesh, arr):
    return _batch_pspec_shape(mesh, tuple(arr.shape))


def _batch_pspec_shape(mesh, shape):
    from jax.sharding import PartitionSpec as P
    from ..parallel.mesh import partition_spec
    if not shape:
        return P()
    if "dcn_dp" in mesh.axis_names and "dp" in mesh.axis_names:
        # multi-slice: the batch dim shards jointly over the cross-slice
        # and in-slice data axes (dcn_dp-major, so each slice holds a
        # contiguous block of the global batch)
        return partition_spec(mesh, (("dcn_dp", "dp"),), shape)
    axis = "dp" if "dp" in mesh.axis_names else mesh.axis_names[0]
    return partition_spec(mesh, (axis,), shape)


def _slab_pspec(mesh, arr):
    """Batch pspec shifted one axis right for a K-leading feed slab: the
    steps axis replicates (every step runs on the whole mesh), the batch
    dim under it shards exactly as the unfused feed would."""
    from jax.sharding import PartitionSpec as P
    if arr.ndim <= 1:
        return P()
    return P(None, *_batch_pspec_shape(mesh, tuple(arr.shape[1:])))


def _jit_with_mesh_steps(fn, mesh):
    """Fused-scan variant of _jit_with_mesh: the same GSPMD treatment,
    with the sharding constraint applied under the slab's leading K
    axis."""
    from jax.sharding import NamedSharding

    def sharded_fn(state_mut, state_ro, feed_slab, base_key):
        feed_slab = {
            n: jax.lax.with_sharding_constraint(
                a, NamedSharding(mesh, _slab_pspec(mesh, a)))
            for n, a in feed_slab.items()
        }
        return fn(state_mut, state_ro, feed_slab, base_key)

    return jax.jit(sharded_fn, donate_argnums=(0,))


def _shard_feed_slab(feed_arrays, mesh):
    """_shard_feed for K-leading slabs: single-process shards the batch
    dim under the steps axis; multi-host assembles each trainer's local
    slab into one global array along dp (reference semantics — every
    trainer feeds its own shard)."""
    from jax.sharding import NamedSharding
    out = {}
    multi = jax.process_count() > 1
    for n, a in feed_arrays.items():
        arr = np.asarray(a) if not isinstance(a, jax.Array) else a
        sharding = NamedSharding(mesh, _slab_pspec(mesh, arr))
        if multi:
            out[n] = jax.make_array_from_process_local_data(
                sharding, np.asarray(arr))
        else:
            out[n] = jax.device_put(arr, sharding)
    return out


def _shard_state(state, mesh, program):
    """Place scope state per its Variable dist_attr (params annotated for tp
    are split across the mesh; everything else replicates). The jitted step
    then respects these input shardings — the GSPMD replacement for the
    reference's BCastParamsToDevices (parallel_executor.cc:739). Multi-host:
    every process holds the full value, so each assembles its addressable
    shards via make_array_from_callback."""
    from ..parallel.mesh import sharding_for
    gblock = program.global_block()
    changed = False
    for n, a in state.items():
        var = gblock.vars.get(n)
        target = sharding_for(mesh, var)
        if isinstance(a, jax.Array) and a.sharding == target:
            continue
        if jax.process_count() > 1:
            if isinstance(a, jax.Array) and not a.is_fully_addressable:
                # already a distributed global array on a different
                # sharding: reshard with a compiled identity (collectives
                # do the cross-host movement; np.asarray would raise)
                state[n] = jax.jit(lambda v: v, out_shardings=target)(a)
            else:
                arr = np.asarray(a)
                state[n] = jax.make_array_from_callback(
                    arr.shape, target, lambda idx, _arr=arr: _arr[idx])
        else:
            state[n] = jax.device_put(a, target)
        changed = True
    return changed


def _shard_feed(feed_arrays, mesh, program):
    """Single-process: shard the full fed batch over the mesh. Multi-host
    (fleet): each trainer process feeds its OWN local batch (reference
    semantics — every trainer reads its own data shard), assembled into one
    global array along the dp axis."""
    from jax.sharding import NamedSharding
    out = {}
    multi = jax.process_count() > 1
    for n, a in feed_arrays.items():
        # a batch the DataLoader prefetched is a device array already:
        # reshard it chip to chip, never through the host
        arr = a if isinstance(a, jax.Array) and not multi else np.asarray(a)
        sharding = NamedSharding(mesh, _batch_pspec(mesh, arr))
        if multi:
            out[n] = jax.make_array_from_process_local_data(sharding, arr)
        else:
            out[n] = jax.device_put(arr, sharding)
    return out


class FetchHandler:
    """Periodic background metric reporter during dataset training
    (reference executor.py:429 FetchHandler + the FetchHandlerMonitor
    thread): `var_dict` maps display keys to scope var names; `handler`
    receives {key: numpy value} every `period_secs`."""

    def __init__(self, var_dict=None, period_secs=60):
        assert var_dict is not None
        self.var_dict = dict(var_dict)
        self.period_secs = float(period_secs)

    def handler(self, res_dict):
        import sys
        for key, val in res_dict.items():
            if isinstance(val, np.ndarray) and val.size:
                sys.stdout.write(f"{key}[0]: {val.reshape(-1)[0]} ")
        sys.stdout.write("\n")

    @staticmethod
    def help():
        print("FetchHandler(var_dict={key: var_or_name}, period_secs=60); "
              "override handler(res_dict) for custom reporting")


class _FetchHandlerMonitor:
    def __init__(self, scope, fetch_handler):
        import threading
        self._scope = scope
        self._fh = fetch_handler
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)

    def start(self):
        self._thread.start()

    def _loop(self):
        import traceback
        while not self._stop.wait(self._fh.period_secs):
            res = {}
            try:
                for key, var in self._fh.var_dict.items():
                    name = var if isinstance(var, str) else var.name
                    val = self._scope.find_var(name)
                    if val is not None:
                        res[key] = np.asarray(val)
            except Exception:
                # racing the training step (e.g. reading a buffer the jit
                # just donated) must not kill the monitor — skip the tick
                continue
            try:
                self._fh.handler(res)
            except Exception:
                # a buggy user handler must neither die silently nor kill
                # the monitor: report it, keep ticking
                traceback.print_exc()

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=5)
