"""Autoregressive generation driver: the prefill/decode split over the
KV-cached GPT graphs (models/gpt.py gpt_prefill / gpt_decode_step_paged).

Naive generation re-runs the full forward for every new token — N tokens
cost N O(S^2) recomputes. ``GPTGenerator.generate`` instead runs ONE
bucketed prefill over the prompt, scatters every layer's fresh keys and
values into a block-paged pool (``serving/kvpool``), then loops a single
compiled decode step whose per-token cost is a cache append + read. All
executables are
AOT-compiled (``jit.lower().compile()``) into a serving
``ExecutableCache`` — length-bucketed prefill shapes stay bounded
(power-of-two buckets and, for long prompts, their midpoints;
``FLAGS_decode_bucket_min`` floor) and the cache's
hit/miss/evict counters make compile traffic observable. Sampling
(greedy / temperature / top-k, per ROW) is the ``sample_tokens`` op
drawing from the framework RNG stream: a fixed seed reproduces the
token sequence bitwise.

``generate_naive`` is the full-recompute baseline (same bucketing, same
sampler, no cache ops) — the A/B half of ``bench.py --config decode``
and the greedy-parity reference in tests.
"""
import collections
import threading
import time

import numpy as np

from ..flags import flag
from ..observability import tracing as _trace
from ..observability import utilization as _util
from ..resilience import await_ready

# fluid program construction mutates process-global state (the default
# program pair swapped by ``program_guard`` plus the unique_name
# counters). Two generators lazily building a program from different
# threads — e.g. several in-process fleet replicas hitting their first
# paged decode at once — would interleave ops into each other's
# programs; every build in this module happens under this lock.
_PROG_BUILD_LOCK = threading.Lock()


class TPCompileGateError(RuntimeError):
    """A tensor-parallel generation executable failed its compile-time
    gate: the sharding audit found a replicated large parameter (GSPMD
    silently undid the tp annotation — every chip would hold and
    compute the whole tensor, so tokens/s would NOT scale), or the
    collective ledger priced the executable's per-step wire bytes past
    the analytic budget (an inserted reshard is moving cache-sized
    tensors every token). Failing the COMPILE is the point: a silently
    replicated serving fleet burns N chips for 1 chip's throughput."""


class UnsupportedPathError(NotImplementedError):
    """A serving path that is not built for the architecture being
    served (``path`` names it: speculative verify, chunked prefill,
    ``tp > 1``, a KV pool dtype). Raised when the path is
    asked for, before any compile; the architecture's serving object
    (``cfg.serving()``) decides what it has."""

    def __init__(self, arch, path):
        super().__init__(
            f"{arch} has no {path} path: it serves through prefill and "
            f"the paged decode step only")
        self.arch, self.path = arch, path


# from here up a prompt's padding is tens of milliseconds of prefill, so
# the ladder of lengths also has the steps between two powers of two
_HALF_STEPS_FROM = 2048


def length_bucket(n, lo=1):
    """Smallest bucket >= n (>= lo): the powers of two and, from
    ``_HALF_STEPS_FROM`` up, their midpoints (2048, 3072, 4096, 6144,
    ...), which stay multiples of 1024 for the flash kernel's blocks.
    Bounded padding waste (< 2x, < 1.5x for long prompts) and a bounded
    universe of compiled prefill shapes — the serving batcher's
    bucketing policy, shared so prefill and batch buckets can't drift."""
    from ..serving.batching import next_bucket
    b = next_bucket(n, min_bucket=lo)
    mid = b // 4 * 3
    return mid if mid >= _HALF_STEPS_FROM and mid >= max(n, lo) else b


def _sample_program_outs():
    from .. import layers
    from ..layers import tensor as T
    logits = T.data("logits", [-1, -1], dtype="float32")
    temperature = T.data("temperature", [-1], dtype="float32")
    top_k = T.data("top_k", [-1], dtype="int32")
    toks = layers.nn.sample_tokens(logits, temperature, top_k)
    return {"feed_names": ["logits", "temperature", "top_k"],
            "tokens": toks}


def _sample_temp_program_outs():
    """Temperature-only variant (no TopK input): the op skips the
    full-vocab top-k sort entirely, which is pure waste when no row
    restricts the vocabulary."""
    from .. import layers
    from ..layers import tensor as T
    logits = T.data("logits", [-1, -1], dtype="float32")
    temperature = T.data("temperature", [-1], dtype="float32")
    toks = layers.nn.sample_tokens(logits, temperature)
    return {"feed_names": ["logits", "temperature"], "tokens": toks}


def _greedy_program_outs():
    """Pure-argmax variant for all-greedy batches: skips the sampler's
    full-vocab sort + categorical draw, which at a realistic vocab would
    dominate the serial per-token loop (still advances the RNG key once
    per call like every compiled program, so switching between greedy
    and sampled runs keeps the key chain aligned)."""
    from ..layers import tensor as T
    logits = T.data("logits", [-1, -1], dtype="float32")
    toks = T.cast(T.argmax(logits, axis=-1), "int32")
    return {"feed_names": ["logits"], "tokens": toks}


def pick_for(temperature, top_k):
    """``(kind, feed beside the logits)`` of the cheapest pick program
    that covers a batch, from its host-side sampling vectors: argmax
    when every row is greedy, the sort-free sampler when no row
    restricts top-k, the full sampler otherwise. All three advance the
    RNG key once and draw a sampled row's token from the same stream,
    so which one runs changes no row's token."""
    if np.all(np.asarray(temperature) <= 0.0):
        return "sample_greedy", {}
    if np.all(np.asarray(top_k) <= 0):
        return "sample_temp", {"temperature": temperature}
    return "sample", {"temperature": temperature, "top_k": top_k}


# a compiled call that was sent and not waited for yet: its results
# (device arrays that become ready when the device has run it), the
# advanced RNG key, and what GPTGenerator._await accounts for it by
_Sent = collections.namedtuple(
    "_Sent", "kind stage fetches key sig compiled t0")


class PendingPrefill:
    """The logits of a prefill into a pool that has not been sent yet:
    the pool's prefill ``kind``, its ``feed`` (the prompts and the pool's
    scatter indices) and the ``pool`` its keys, values and states go
    into. Handed to :meth:`GPTGenerator._run_sample` as the logits, the
    pick of the first tokens runs inside the prefill's own donated call
    (``<prefill kind>+<pick kind>``), so neither the logits nor the row
    caches leave the device. ``sent`` is that call once it was sent."""

    def __init__(self, kind, feed, pool):
        self.kind, self.feed, self.pool = kind, feed, pool
        self.sent = None


def _spec_accept_program_outs():
    """Acceptance program for speculative decoding: one ``spec_accept``
    op over the verify step's span logits (see ops/decode_ops.py for
    the rejection-sampling semantics)."""
    from .. import layers
    from ..layers import tensor as T
    logits = T.data("logits", [-1, -1, -1], dtype="float32")
    draft = T.data("draft", [-1, -1], dtype="int32")
    temperature = T.data("temperature", [-1], dtype="float32")
    top_k = T.data("top_k", [-1], dtype="int32")
    num_draft = T.data("num_draft", [-1], dtype="int32")
    toks, acc = layers.nn.spec_accept(logits, draft, temperature,
                                      num_draft, top_k=top_k)
    return {"feed_names": ["logits", "draft", "temperature", "top_k",
                           "num_draft"],
            "tokens": toks, "accepted": acc}


# -- drafters ----------------------------------------------------------
#
# A drafter proposes up to k continuation tokens for one row's context;
# the verify step scores them all in one pass and rejection sampling
# keeps whatever prefix the model agrees with. The protocol is one
# method — draft(ctx_tokens, k) -> 1-D int array of <= k proposals —
# so anything from a table lookup to a full small LM plugs in.

class NgramDrafter:
    """Self-drafting n-gram / prompt-lookup drafter (the LLMA /
    prompt-lookup-decoding idiom): find the most recent PRIOR
    occurrence of the context's trailing n-gram and propose the tokens
    that followed it. Free — no model, no device work — and highly
    effective exactly when decode output echoes its context
    (summarization, code edits, retrieval), which is also when decode
    is most bandwidth-starved."""

    def __init__(self, max_ngram=3):
        self.max_ngram = int(max_ngram)

    def draft(self, ctx, k):
        ctx = np.asarray(ctx, np.int32).ravel()
        n = int(ctx.size)
        k = int(k)
        if k <= 0 or n < 2:
            return np.zeros((0,), np.int32)
        for ng in range(min(self.max_ngram, n - 1), 0, -1):
            pat = ctx[n - ng:]
            # windows strictly before the trailing n-gram itself
            wins = np.lib.stride_tricks.sliding_window_view(
                ctx[:n - 1], ng)[:n - ng]
            hits = np.flatnonzero(np.all(wins == pat, axis=1))
            if hits.size:
                # most recent occurrence with a FULL k-token
                # continuation, else most recent outright: a cycling
                # context's nearest hit sits one period back, which
                # would clip every draft to the cycle length
                full = hits[hits + ng + k <= n]
                i = int(full[-1]) if full.size else int(hits[-1])
                cont = ctx[i + ng:i + ng + k]
                if 0 < cont.size < k:
                    # the continuation ran off the end of the context
                    # (the hit sits inside the trailing cycle): extend
                    # it periodically — a wrong guess merely gets
                    # rejected, a right one doubles the run length
                    cont = np.resize(cont, k)
                if cont.size:
                    return cont.astype(np.int32)
        return np.zeros((0,), np.int32)


class ModelDrafter:
    """Draft-model drafter: greedy continuations from a (small) wrapped
    :class:`GPTGenerator`. :meth:`from_generator` builds the standard
    shared-snapshot configuration — a truncated-depth copy of the
    target config over the SAME parameter scope, so the draft model
    reuses the generator's embeddings and first decoder layers without
    a second checkpoint."""

    def __init__(self, draft_gen):
        self.gen = draft_gen

    @classmethod
    def from_generator(cls, gen, num_layers=1):
        import copy
        cfg = copy.copy(gen.cfg)
        cfg.num_layers = max(1, min(int(num_layers), gen.cfg.num_layers))
        return cls(GPTGenerator(cfg, gen.scope, max_len=gen.max_len,
                                bucket_min=gen.bucket_min))

    def draft(self, ctx, k):
        ctx = np.asarray(ctx, np.int32).ravel()
        k = int(k)
        lim = self.gen.max_len - k
        if k <= 0 or lim < 1:
            return np.zeros((0,), np.int32)
        out = self.gen.generate([ctx[-lim:]], max_new_tokens=k,
                                temperature=0.0)
        return np.asarray(out[0], np.int32)


def make_drafter(mode=None, generator=None):
    """Drafter for ``FLAGS_decode_spec_mode``: ``"ngram"`` (default) is
    the free prompt-lookup drafter; ``"model"`` wraps a 1-layer draft
    GPT sharing ``generator``'s parameter snapshot."""
    mode = mode or flag("decode_spec_mode") or "ngram"
    if mode == "ngram":
        return NgramDrafter()
    if mode == "model":
        if generator is None:
            raise ValueError(
                "decode_spec_mode='model' needs the target generator "
                "to share parameters with")
        return ModelDrafter.from_generator(generator)
    raise ValueError(
        f"unknown decode_spec_mode {mode!r} — 'ngram' or 'model'")


class GPTGenerator:
    """Compiled prefill + decode-step + sampler over a parameter scope.

    The scope must already hold the model's trained (or startup-
    initialized) parameters under the standard ``models/gpt.py`` names —
    the generator builds its OWN inference programs and snapshots the
    parameters onto the device at first use (``refresh_state()`` re-pulls
    after further training).

        gen = GPTGenerator(cfg, scope, max_len=512)
        outs = gen.generate([prompt_ids], max_new_tokens=64,
                            temperature=0.8, top_k=40, seed=7)

    ``stats`` (a ``serving.ServingStats``) routes per-stage latencies
    into the prefill/decode/sample histograms (``serving/<stage>`` rows
    of ``paddle_tpu.profiler``'s event table while profiling is
    active); every call leaves a ``generator/dispatch`` and a
    ``generator/wait`` loop span (``observability.tracing``).
    """

    def __init__(self, cfg, scope=None, *, max_len=None, bucket_min=None,
                 cache=None, stats=None, tp=None):
        from ..framework.core import Program, program_guard
        from ..framework.executor import global_scope

        self.cfg = cfg
        self.scope = scope if scope is not None else global_scope()
        self.max_len = int(max_len or flag("decode_max_len"))
        if self.max_len > cfg.max_position:
            self.max_len = int(cfg.max_position)
        self.bucket_min = int(bucket_min or flag("decode_bucket_min"))
        if cache is None:
            from ..serving.cache import ExecutableCache
            cache = ExecutableCache()
        self.cache = cache
        self.stats = stats
        self.tp = int(flag("serving_tp") if tp is None else tp)
        # the architecture's own programs and pool layout come from the
        # config (models/gpt.GPTServing, models/mellum.MellumServing)
        self.arch = cfg.serving()
        if self.tp > 1 and not self.arch.supports_tp:
            raise UnsupportedPathError(self.arch.name, "tp > 1")
        self.mesh = self._init_tp_mesh() if self.tp > 1 else None

        builders = dict(self.arch.eager_builders(self.max_len))
        builders.update({
            "sample": _sample_program_outs,
            "sample_temp": _sample_temp_program_outs,
            "sample_greedy": _greedy_program_outs,
        })
        self._progs = {}
        with _PROG_BUILD_LOCK:
            for kind, build in builders.items():
                main, startup = Program(), Program()
                with program_guard(main, startup):
                    outs = build()
                self._annotate_tp(kind, main)
                self._progs[kind] = (main, outs)
        self._fns = {}      # kind -> (jitted, device_state)
        self._unpack = {}   # kind -> its results back in fetch order
        self._params = {}   # param name -> device array, shared by kinds
        # (bucket_rows, kv_dtype, block_size) -> KVBlockPool reused
        # across generate() calls: keeps the pool's jitted
        # prefill-scatter closure and device arrays warm instead of
        # recompiling/reallocating per call (blocks are still freed on
        # the way out of every call)
        self._paged_pools = {}
        # program kind -> pool-sized copies in its optimised HLO (the
        # worst of the kind's compiles): kvpool.count_pool_relayouts
        self.pool_relayouts = {}
        # signature -> cost_analysis dict|False for the live MFU/HBM
        # gauges; LRU so an evicted entry recomputes instead of
        # freezing the gauges for a still-cached executable
        from ..utils.lru import LRUCache
        self._exec_costs = LRUCache(max_entries=256)

    # -- tensor-parallel generation ---------------------------------------
    def _init_tp_mesh(self):
        """Build (and install as ambient) the tp mesh every generation
        executable compiles under — the SAME Megatron column/row scheme
        training uses (the architecture's ``apply_tp_sharding``), so a
        trained tp checkpoint serves without resharding."""
        import jax
        from ..parallel.mesh import MeshConfig, make_mesh, set_mesh
        ndev = len(jax.devices())
        if self.tp > ndev:
            raise ValueError(
                f"FLAGS_serving_tp={self.tp} exceeds the {ndev} visible "
                f"device(s)")
        if self.arch.kv_heads % self.tp:
            raise ValueError(
                f"serving_tp={self.tp} must divide num_heads="
                f"{self.arch.kv_heads} (the KV pool shards on the head "
                f"axis)")
        mesh = make_mesh(MeshConfig(tp=self.tp))
        set_mesh(mesh)
        return mesh

    def _annotate_tp(self, kind, main):
        """Annotate a freshly built program's parameters with the tp
        PartitionSpecs (no-op single-chip, and for the parameterless
        sampler/acceptance programs)."""
        if self.mesh is not None and not kind.startswith("sample") \
                and kind != "spec_accept":
            self.arch.apply_tp_sharding(main)

    def apply_pool_sharding(self, pool):
        """Shard a :class:`serving.kvpool.KVBlockPool`'s device arrays
        on the head axis of the tp mesh (the stored block arrays
        ``[num_blocks, H * block_size // f, f * D]`` are head-major in
        dim 1, the int8 scales ``[num_blocks, f, H * block_size // f]``
        in dim 2 — the axis ``apply_tp_sharding`` already splits qkv
        over, so the decode step's cache append/read never crosses
        chips). No-op without a mesh."""
        if self.mesh is None:
            return pool
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        from ..serving.kvpool import pool_feed_names
        val = NamedSharding(self.mesh, P(None, "tp", None))
        sc = NamedSharding(self.mesh, P(None, None, "tp"))
        pool.array_sharding = {
            n: (sc if ("pks" in n or "pvs" in n) else val)
            for n in pool_feed_names(pool.num_arrays, pool.quantized)}
        return pool

    def _tp_wire_budget(self, feed):
        """Generous analytic per-invocation wire-byte ceiling for a tp
        generation executable: the Megatron scheme moves ~2 activation
        all-reduces per layer plus the embedding/logits pair — budget
        8x that. Cache-sized traffic (a GSPMD reshard gathering the
        block pool every step) overshoots this by orders of magnitude,
        which is exactly the regression the gate exists to catch."""
        cfg = self.cfg
        t = feed.get("tokens")
        if t is not None:
            ntok = int(np.prod(np.shape(t)))
            rows = int(np.shape(t)[0])
        elif feed.get("token") is not None:
            ntok = rows = int(np.shape(feed["token"])[0])
        else:
            ntok = rows = 1
        analytic = (2 * cfg.num_layers + 2) * ntok * cfg.hidden_size * 4 \
            + 2 * rows * cfg.vocab_size * 4
        return 8 * analytic

    def _tp_compile_gate(self, kind, compiled, feed):
        """The compile-time gate of tp generation (sampler programs are
        parameterless and skip it): the PR-14 sharding audit must find
        NO replicated large parameter, and the collective ledger's
        wire-byte total must stay under the analytic budget. Raises
        :class:`TPCompileGateError` — tokens/s that silently does not
        scale is a bug, not a degraded mode."""
        if self.mesh is None or kind.startswith("sample"):
            return
        from ..observability.comms import CommLedger
        from ..observability.sharding import audit_executable
        main = self._ensure_prog(kind)[0]
        report = audit_executable(
            compiled, self.mesh, program=main, feed_names=tuple(feed),
            threshold_mb=float(flag("shard_audit_replicated_mb")))
        bad = report.by_code("replicated-large-param")
        if bad:
            worst = max(bad, key=lambda f: f.nbytes)
            raise TPCompileGateError(
                f"tp={self.tp} generation executable {kind!r} has "
                f"{len(bad)} replicated large parameter(s) — worst "
                f"{worst.var} at {worst.nbytes / 2**20:.1f} MiB: "
                f"{worst.message}")
        ledger = CommLedger.from_compiled(compiled, self.mesh)
        wire = int(ledger.totals()["wire_bytes"])
        budget = self._tp_wire_budget(feed)
        if wire > budget:
            raise TPCompileGateError(
                f"tp={self.tp} generation executable {kind!r} moves "
                f"{wire} wire bytes per step, over the analytic budget "
                f"of {budget} — an inserted reshard is shipping "
                f"cache-scale tensors every token")

    # -- compilation ------------------------------------------------------
    def _fetch_names(self, outs):
        if "accepted" in outs:              # spec_accept: tokens + count
            return [outs["tokens"].name, outs["accepted"].name]
        if "tokens" in outs:
            return [outs["tokens"].name]
        # what an architecture returns beside logits and caches (the
        # expert layers' assignment counts) comes last, in its own order
        aux = [v.name for v in outs.get("aux", {}).values()]
        if "cache_vars" in outs:            # paged decode: pool arrays
            return ([outs["logits"].name]
                    + [v.name for v in outs["cache_vars"]] + aux)
        kv = [v for k in ("cache_k", "cache_v") for v in outs.get(k, ())]
        return [outs["logits"].name] + [v.name for v in kv + list(
            outs.get("cache_state", {}).values())] + aux

    def aux_of(self, kind, fetches):
        """``{name: array}`` of what the ``kind`` program fetched beside
        logits and caches (empty for GPT)."""
        names = list(self._ensure_prog(kind)[1].get("aux", {}))
        return dict(zip(names, fetches[len(fetches) - len(names):])) \
            if names else {}

    def _ensure_prog(self, kind):
        """Program for ``kind``, building the lazily-declared ones on
        first use (the paged decode step exists per KV-cache dtype —
        ``decode_paged_fp32|bf16|int8`` — and most processes never
        touch them). A decode step that picks its own token
        (``<decode kind>+<pick kind>``) is its decode program."""
        kind = kind.partition("+")[0]
        entry = self._progs.get(kind)
        if entry is not None:
            return entry
        from ..framework.core import Program, program_guard
        with _PROG_BUILD_LOCK:
            entry = self._progs.get(kind)
            if entry is not None:     # lost the build race to a peer
                return entry
            main, startup = Program(), Program()
            with program_guard(main, startup):
                if kind == "spec_accept":
                    outs = _spec_accept_program_outs()
                else:
                    # KeyError for a kind no architecture has, a typed
                    # UnsupportedPathError for one this one lacks
                    outs = self.arch.build(kind, self.max_len)
            self._annotate_tp(kind, main)
            self._progs[kind] = (main, outs)
        return self._progs[kind]

    @staticmethod
    def _cache_places(outs, feed_names, fetch_names):
        """``{feed name: position in the fetch list}`` of every cache
        the ``outs`` program is fed and hands back updated: the pool
        arrays of a paged program (a prefill is fed none)."""
        names = list(outs.get("cache_names", ()))
        first = 1               # logits lead every cache-bearing fetch
        return {n: first + i for i, n in enumerate(names)
                if n in feed_names and first + i < len(fetch_names)}

    def _ensure_fn(self, kind, scatter=None):
        """``(jitted, device state)`` of the ``kind`` executable; with
        ``scatter`` (a pool's ``kvpool.ScatterLayout``) the admission
        ``<prefill kind>+<pick kind>`` into a pool of that layout."""
        fkey = kind if scatter is None else (kind, scatter)
        entry = self._fns.get(fkey)
        if entry is not None:
            return entry
        import jax
        import jax.numpy as jnp
        from ..framework.lowering import analyze_block_io, build_block_fn

        main, outs = self._ensure_prog(kind)
        feed_names = list(outs["feed_names"])
        fetch_names = self._fetch_names(outs)
        state_in, _ = analyze_block_io(main, 0, feed_names)
        fn = build_block_fn(main, 0, feed_names, fetch_names, state_in, [],
                            mesh=self.mesh)
        # ``<decode kind>+<pick kind>``: the decode step and the pick
        # that followed it as a second call, in one executable. The two
        # programs run as they did apart (the pick on the key the step
        # advanced, the key advanced again), so a seeded request draws
        # the tokens it drew from two calls; what comes back first is
        # int32 [rows] tokens, not the logits. Its token input is the
        # last step's result where it lies, but for the rows the host
        # has a newer token for (``token_from_host``).
        pick = kind.partition("+")[2]
        if pick:
            pick_main, pick_outs = self._ensure_prog(pick)
            pick_feeds = list(pick_outs["feed_names"])
            pick_fn = build_block_fn(
                pick_main, 0, pick_feeds, [pick_outs["tokens"].name],
                [], [], mesh=self.mesh)
        if scatter is not None:
            run, unpack = self._admission(kind, fn, feed_names, pick_fn,
                                          pick_feeds, scatter)
            return self._jit_fn(fkey, run, unpack, main, state_in)

        # only the decode step's KV caches are worth donating (XLA
        # aliases the cache append in place — no 2x cache traffic);
        # everything else is a fresh host array every call. JAX pairs a
        # donated argument with a result by shape and ORDER, and the
        # caches arrive as a dict (sorted names: ..._1, ..._10, ..._2):
        # results listed layer by layer would pair array 2 with array
        # 10's buffer and XLA would copy every array across. So the
        # caches go back the way they came, as a dict under the names
        # they were fed by, and each is updated where it lies.
        place = self._cache_places(outs, feed_names, fetch_names)
        at = {i: n for n, i in place.items()}

        def run(state, caches, feed, base_key):
            env = dict(feed)
            env.update(caches)
            if pick:
                env["token"] = jnp.where(env.pop("token_from_host"),
                                         env["token"],
                                         env.pop("token_prev"))
            fetches, _, new_key = fn({}, state, env, base_key)
            if pick:
                picked, _, new_key = pick_fn(
                    {}, {}, dict({n: env[n] for n in pick_feeds[1:]},
                                 logits=fetches[0]), new_key)
                fetches = [picked[0]] + list(fetches[1:])
            return ([f for i, f in enumerate(fetches) if i not in at],
                    {n: fetches[i] for n, i in place.items()}, new_key)

        def unpack(rest, caches):
            """The fetch list in ``fetch_names`` order again."""
            rest = iter(rest)
            return [caches[at[i]] if i in at else next(rest)
                    for i in range(len(fetch_names))]

        return self._jit_fn(fkey, run, unpack, main, state_in)

    def _admission(self, kind, fn, feed_names, pick_fn, pick_feeds,
                   scatter):
        """``(run, unpack)`` of an admission, ``<prefill kind>+<pick
        kind>`` into a pool of layout ``scatter``: the prefill, the pick
        of the first tokens and the pool's scatter of the keys and
        values (and states) the prefill made, in one executable. The
        programs run as they did as three calls (the pick on the key the
        prefill advanced, the key advanced again), so a seeded request
        draws the token it drew from them; the logits and the row caches
        never leave the device. The pool's arrays are donated and come
        back updated where they lie; every shape is the bucket's (the
        padding rows' blocks are the trash block's, their slots past the
        bank's end), so a length bucket compiles once a row bucket. The
        fetch list: int32 [rows] tokens, the pool's arrays in
        ``scatter.names`` order (``kvpool.adopt_decode_fetches``), then
        the prefill's aux."""
        from ..serving.kvpool import SCATTER_FEEDS, prefill_scatter
        write = prefill_scatter(scatter)
        naux = len(self._ensure_prog(kind)[1].get("aux", {}))

        def run(state, caches, feed, base_key):
            fetches, _, new_key = fn(
                {}, state, {n: feed[n] for n in feed_names}, base_key)
            picked, _, new_key = pick_fn(
                {}, {}, dict({n: feed[n] for n in pick_feeds[1:]},
                             logits=fetches[0]), new_key)
            rows = self._unpack_caches(kind, fetches)[1]
            pool = write(caches, rows,
                         *(feed.get(n) for n in SCATTER_FEEDS))
            return ([picked[0]] + fetches[len(fetches) - naux:], pool,
                    new_key)

        def unpack(rest, pool):
            return [rest[0]] + [pool[n] for n in scatter.names] \
                + list(rest[1:])

        return run, unpack

    def _jit_fn(self, fkey, run, unpack, main, state_in):
        """Jit ``run`` (its second argument, the caches, donated), keep
        it with its ``unpack`` and a device snapshot of the ``state_in``
        parameters under ``fkey``."""
        import jax
        jitted = jax.jit(run, donate_argnums=(1,))
        self._unpack[fkey] = unpack
        # one device snapshot per PARAMETER, shared by every kind's
        # state dict (prefill/decode/logits read the same weights — a
        # per-kind device_put would hold N identical copies in HBM)
        state = {}
        gblock = main.global_block()
        for n in state_in:
            a = self._params.get(n)
            if a is None:
                v = self.scope.find_var(n)
                if v is None:
                    raise RuntimeError(
                        f"generation parameter {n!r} is not in the "
                        f"scope — run the startup program or load "
                        f"trained params first")
                if self.mesh is not None:
                    # placed per the program's tp annotation — each
                    # chip holds only its shard (qkv columns, ffn
                    # rows/cols, vocab rows), which is the whole HBM
                    # and tokens/s win of tp serving
                    from ..parallel.mesh import sharding_for
                    a = jax.device_put(np.asarray(v),
                                       sharding_for(self.mesh,
                                                    gblock.vars.get(n)))
                else:
                    a = jax.device_put(np.asarray(v))
                self._params[n] = a
            state[n] = a
        self._fns[fkey] = (jitted, state)
        return self._fns[fkey]

    def bind_params(self, device_params):
        """Adopt arrays that are on the device already as the snapshot
        of the parameters they name: no host round trip and no second
        copy (a model that fills most of the chip has room for neither).
        The caller keeps them out of any call that donates its
        arguments. Names not given are still pulled from the scope at
        first use."""
        self._params.update(device_params)
        for kind, (jitted, state) in list(self._fns.items()):
            self._fns[kind] = (jitted, {
                n: self._params[n] for n in state})

    def refresh_state(self):
        """Re-snapshot the scope's parameters onto the device (call after
        the params changed, e.g. more training steps)."""
        import jax
        for n in list(self._params):
            v = self.scope.find_var(n)
            if v is not None:
                self._params[n] = jax.device_put(np.asarray(v))
        for kind, (jitted, state) in self._fns.items():
            for n in list(state):
                state[n] = self._params[n]

    def swap_params(self, device_params):
        """Atomically rebind the parameter snapshot to already-device
        arrays (the hot-weight-reload swap: the expensive device_put
        happened off-thread; this is dict construction only). Each
        compiled kind gets a FRESH state dict — an in-flight call
        already holds a reference to the old one, so it finishes on the
        old weights while every later call reads the new ones."""
        missing = [n for n in self._params if n not in device_params]
        if missing:
            raise ValueError(f"swap_params snapshot is missing "
                             f"parameters: {sorted(missing)}")
        self._params = {n: device_params[n] for n in self._params}
        for kind, (jitted, state) in list(self._fns.items()):
            self._fns[kind] = (jitted,
                               {n: self._params[n] for n in state})

    @staticmethod
    def _signature(kind, feed):
        from ..serving.cache import feed_signature
        return tuple(sorted(
            ((f"__program__/{kind}", (), "meta"),)
            + feed_signature(feed)))

    def _invoke(self, kind, stage, feed, key):
        """Run the ``kind`` executable on ``feed`` and wait for it."""
        sent = self._dispatch(kind, stage, feed, key)
        self._await(sent)
        return sent.fetches, sent.key

    def _dispatch(self, kind, stage, feed, key, scatter=None):
        """Send the ``kind`` executable on ``feed`` and return at once
        (a fresh signature compiles first): a :class:`_Sent` whose
        results the device fills in. Whoever needs them ready, or
        wants the call accounted for, hands it to :meth:`_await`.
        ``scatter``: an admission into a pool of that layout
        (:meth:`_ensure_fn`)."""
        import jax
        jitted, state = self._ensure_fn(kind, scatter)
        with _trace.loop_span("generator/signature"):
            sig = self._signature(kind, feed)
            caches = {n: a for n, a in feed.items()
                      if n.startswith("cache_")}
            rest = {n: a for n, a in feed.items()
                    if not n.startswith("cache_")}
            if self.mesh is not None:
                # commit the host-side feeds (tokens, positions, tables)
                # and the RNG key replicated on the tp mesh so AOT
                # lowering sees ONE consistent device set next to the
                # sharded params/pool arrays
                from jax.sharding import NamedSharding
                from jax.sharding import PartitionSpec as P
                rep = NamedSharding(self.mesh, P())
                rest = {n: jax.device_put(a, rep) for n, a in rest.items()}
                key = jax.device_put(key, rep)
            compiled = self.cache.get(sig)
        fresh = compiled is None
        if fresh:
            t0 = time.perf_counter()
            compiled = jitted.lower(state, caches, rest, key).compile()
            dt = time.perf_counter() - t0
            from ..serving.engine import ServingEngine
            self.cache.put(sig, compiled,
                           nbytes=ServingEngine._executable_bytes(
                               compiled, feed))
            cost = _util.cost_for(self._exec_costs, sig, compiled)
            # sharding audit + collective ledger on newly compiled
            # generation executables (flag-gated shared front door;
            # program + feed names so fed tensors — tokens, cache
            # slabs, masks — audit as FEEDS, not as replicated params)
            from ..observability.sharding import maybe_observe
            from ..parallel.mesh import get_mesh
            maybe_observe(stage, compiled, get_mesh(),
                          program=self._ensure_prog(kind)[0],
                          feed_names=tuple(feed), cost=cost,
                          tag=f"generate_{kind}")
            self._tp_compile_gate(kind, compiled, feed)
            from ..serving.kvpool import (count_pool_relayouts,
                                          pool_element_counts)
            pool_elems = pool_element_counts(caches)
            if pool_elems:
                self.pool_relayouts[kind] = max(
                    self.pool_relayouts.get(kind, 0),
                    count_pool_relayouts(compiled.as_text(), pool_elems))
            if self.stats:
                self.stats.bump("compiles")
                self.stats.hist["compile"].observe(dt)
        # two spans, so that a trace tells the host's dispatch from the
        # wait for the device; the first's start and the second's end
        # are the one interval every consumer in _await takes
        with _trace.loop_span("generator/dispatch", kind=kind,
                              stage=stage, compiled=fresh) as sending:
            fetched, kept, new_key = compiled(state, caches, rest, key)
            fetches = self._unpack[kind if scatter is None
                                   else (kind, scatter)](fetched, kept)
        return _Sent(kind, stage, fetches, new_key, sig, compiled,
                     sending.t0)

    def _await(self, sent, deadline=None, budget=None):
        """Wait until the device has run ``sent`` and account for it
        (stage histogram, MFU gauge) from its dispatch to now: with a
        step dispatched ahead that is the latency a reader saw, not the
        device's time. ``deadline`` (a ``perf_counter`` instant,
        ``budget`` seconds after the caller began) bounds the wait:
        past it, WatchdogTimeout, and no thread is spent on it."""
        import jax
        with _trace.loop_span("generator/wait", kind=sent.kind,
                              stage=sent.stage) as waited:
            cpu = time.thread_time()
            if deadline is None:
                jax.block_until_ready(sent.fetches)
            else:
                # an executable's results become ready together
                await_ready(sent.fetches[0].is_ready, deadline, budget,
                            f"serving {sent.stage} step")
            waited.attrs["cpu_s"] = time.thread_time() - cpu
        dt = waited.t1 - sent.t0
        cost = _util.cost_for(self._exec_costs, sent.sig, sent.compiled)
        if cost:
            _util.observe_execution(sent.stage, cost, dt)
        if self.stats:
            self.stats.hist[sent.stage].observe(dt)

    # -- stage runners ----------------------------------------------------
    def _unpack_caches(self, kind, fetches):
        """Fetch layout of the cache-bearing programs (_fetch_names):
        logits at 0, then cache_k_0..n-1, cache_v_0..n-1 and what its
        ``cache_state`` names, as the ``kind`` program hands them back."""
        outs = self._ensure_prog(kind)[1]
        n = len(outs["cache_k"])
        caches = dict(zip(outs.get("cache_state", ()), fetches[1 + 2 * n:]))
        caches.update({f"cache_{c}_{i}": fetches[1 + j * n + i]
                       for j, c in enumerate("kv") for i in range(n)})
        return fetches[0], caches

    def _run_prefill(self, tokens, pos_ids, last_pos, key, kv_dtype=None,
                     want_aux=False):
        """One bucketed prefill. ``kv_dtype`` is the pool's, for an
        architecture whose prefill hands back keys and values in it;
        ``want_aux`` adds :meth:`aux_of` as a fourth result."""
        feed = {"tokens": tokens, "pos_ids": pos_ids, "last_pos": last_pos}
        kind = self.arch.prefill_kind(kv_dtype or flag("kv_cache_dtype"))
        fetches, key = self._invoke(kind, "prefill", feed, key)
        logits, caches = self._unpack_caches(kind, fetches)
        if want_aux:
            return logits, caches, key, self.aux_of(kind, fetches)
        return logits, caches, key

    def new_pool(self, slots, **kw):
        """A :class:`serving.kvpool.KVBlockPool` laid out for this
        architecture: its KV heads, head width and layer groups (cache
        layers, not weight layers; a state group's are not KV layers)."""
        from ..serving.kvpool import KVBlockPool
        arch = self.arch
        if kw.get("dtype") and kw["dtype"] not in arch.kv_dtypes:
            raise UnsupportedPathError(arch.name,
                                       f"{kw['dtype']} KV pool")
        groups = arch.kv_groups()
        pool = KVBlockPool(
            slots=slots, num_layers=sum(
                len(g["layers"]) for g in groups if not g.get("state")),
            num_heads=arch.kv_heads, d_head=arch.head_dim,
            max_seq_len=self.max_len, groups=groups, **kw)
        return self.apply_pool_sharding(pool)

    def _run_decode_paged(self, token, pos, pool, key):
        """One decode step over the block-paged KV pool: feeds the
        pool's device arrays (donated — XLA appends in place) plus the
        host block tables, adopts the updated pool arrays back into the
        pool. On ANY failure the donated arrays must be presumed lost —
        the pool's device side is dropped (host accounting survives)."""
        from ..serving.kvpool import adopt_decode_fetches, decode_feed
        feed = decode_feed(pool, token, pos)
        try:
            fetches, key = self._invoke(f"decode_paged_{pool.dtype}",
                                        "decode", feed, key)
        except Exception:
            pool.drop_device()
            raise
        return adopt_decode_fetches(pool, fetches), key

    def _run_prefill_chunk(self, tokens, pos_ids, start_pos, limit,
                           last_idx, pool, key, rows=None):
        """One chunk of incremental paged prefill: ingest up to C
        prompt tokens per row straight into the block pool (donated, in
        place), attending each query over everything its row already
        wrote. ``rows`` selects which pool slots' block tables line up
        with the token rows (None = every slot, in slot order). Logits
        are only meaningful for rows whose LAST real token is in this
        chunk (per ``last_idx``) — callers sample only then. On any
        failure the donated pool arrays are presumed lost, same as the
        decode step."""
        from ..serving.kvpool import adopt_decode_fetches
        feed = dict(pool.arrays())
        feed["tokens"] = np.asarray(tokens, np.int32)
        feed["pos_ids"] = np.asarray(pos_ids, np.int32)
        feed["start_pos"] = np.asarray(start_pos, np.int32)
        feed["limit"] = np.asarray(limit, np.int32)
        feed["last_idx"] = np.asarray(last_idx, np.int32)
        tables = pool.tables if rows is None else pool.tables[list(rows)]
        feed["block_tables"] = np.ascontiguousarray(tables)
        try:
            fetches, key = self._invoke(f"prefill_chunk_{pool.dtype}",
                                        "prefill", feed, key)
        except Exception:
            pool.drop_device()
            raise
        return adopt_decode_fetches(pool, fetches), key

    def _run_verify_paged(self, tokens, pos_ids, start_pos, limit, pool,
                          key, rows=None):
        """One speculative verify step over the block-paged pool:
        prefill-style attention through the same block-table gather,
        per-row ``limit`` = real span (k_b drafts + 1; past-limit
        writes route to the trash block). Returns span logits
        [B, S, V]; the updated pool arrays are adopted in place. On any
        failure the donated pool arrays are presumed lost."""
        from ..serving.kvpool import adopt_decode_fetches
        feed = dict(pool.arrays())
        feed["tokens"] = np.asarray(tokens, np.int32)
        feed["pos_ids"] = np.asarray(pos_ids, np.int32)
        feed["start_pos"] = np.asarray(start_pos, np.int32)
        feed["limit"] = np.asarray(limit, np.int32)
        tables = pool.tables if rows is None else pool.tables[list(rows)]
        feed["block_tables"] = np.ascontiguousarray(tables)
        try:
            fetches, key = self._invoke(f"verify_paged_{pool.dtype}",
                                        "decode", feed, key)
        except Exception:
            pool.drop_device()
            raise
        return adopt_decode_fetches(pool, fetches), key

    def _run_spec_accept(self, logits, draft, temperature, top_k,
                         num_draft, key):
        """Rejection-sampling acceptance over a verified span: returns
        ``(tokens [B, S], accepted [B], key)`` — row b emits
        ``tokens[b, :accepted[b] + 1]``."""
        feed = {"logits": logits,
                "draft": np.asarray(draft, np.int32),
                "temperature": np.asarray(temperature, np.float32),
                "top_k": np.asarray(top_k, np.int32),
                "num_draft": np.asarray(num_draft, np.int32)}
        fetches, key = self._invoke("spec_accept", "sample", feed, key)
        return fetches[0], fetches[1], key

    def _run_logits(self, tokens, pos_ids, last_pos, key):
        feed = {"tokens": tokens, "pos_ids": pos_ids, "last_pos": last_pos}
        fetches, key = self._invoke("logits", "prefill", feed, key)
        return fetches[0], key

    def _run_sample(self, logits, temperature, top_k, key):
        """``(tokens, key)``: the pick of one token a row from
        ``logits``, as a call of its own; from a :class:`PendingPrefill`
        inside that prefill's call (:meth:`_run_admission`)."""
        kind, feed = pick_for(temperature, top_k)
        if isinstance(logits, PendingPrefill):
            return self._run_admission(logits, kind, feed, key)
        fetches, key = self._invoke(kind, "sample",
                                    dict(feed, logits=logits), key)
        return fetches[0], key

    def _run_admission(self, pending, pick, feed, key):
        """Send ``pending``'s prefill with the ``pick`` program on its
        logits and the pool's scatter of its row caches as ONE donated
        call (:meth:`_ensure_fn` with the pool's layout), adopt the
        pool's arrays it hands back and wait for it. Returns ``(first
        tokens int32 [rows], key)``."""
        from ..serving.kvpool import adopt_decode_fetches
        pool = pending.pool
        feed.update(pending.feed)
        feed.update(pool.arrays())
        pending.sent = sent = self._dispatch(
            f"{pending.kind}+{pick}", "prefill", feed, key,
            scatter=pool.scatter_layout())
        toks = adopt_decode_fetches(pool, sent.fetches)
        self._await(sent)
        return toks, sent.key

    pick_for = staticmethod(pick_for)

    # -- public API -------------------------------------------------------
    def _prep(self, prompts, max_new_tokens, seed, key):
        import jax
        # a bare 1-D array / flat list of ints is ONE prompt (the shape
        # the serving Client takes), not a batch of one-token prompts
        if isinstance(prompts, np.ndarray):
            prompts = [prompts] if prompts.ndim <= 1 else list(prompts)
        elif isinstance(prompts, (list, tuple)) and prompts \
                and np.isscalar(prompts[0]):
            prompts = [np.asarray(prompts)]
        prompts = [np.asarray(p).ravel().astype(np.int32)
                   for p in prompts]
        if not prompts:
            raise ValueError("generate() needs at least one prompt")
        lens = [int(p.size) for p in prompts]
        if min(lens) < 1:
            raise ValueError("empty prompt")
        if max(lens) + int(max_new_tokens) > self.max_len:
            raise ValueError(
                f"prompt len {max(lens)} + max_new_tokens "
                f"{max_new_tokens} exceeds the generator's max_len "
                f"{self.max_len} (raise max_len= or "
                f"FLAGS_decode_max_len)")
        if key is None:
            key = jax.random.PRNGKey(0 if seed is None else int(seed))
        return prompts, lens, key

    def _pack_prompts(self, prompts):
        """Right-pad 1-D int32 prompts into the bucketed prefill feed:
        (tokens [bb, s], pos_ids [bb, s], last_pos [bb]) — the ONE
        packing used by generate(), generate_naive() and the serving
        GenerationEngine, so offline and served prefill cannot drift."""
        lens = [int(p.size) for p in prompts]
        bb = length_bucket(len(prompts))
        s = min(length_bucket(max(lens), self.bucket_min), self.max_len)
        tokens = np.zeros((bb, s), np.int32)
        for r, p in enumerate(prompts):
            tokens[r, :p.size] = p
        pos_ids = np.broadcast_to(np.arange(s, dtype=np.int32),
                                  (bb, s)).copy()
        last = np.zeros((bb,), np.int32)
        last[:len(prompts)] = np.asarray(lens, np.int32) - 1
        return tokens, pos_ids, last

    @staticmethod
    def _emit(tok_h, outs, done, eos_id, max_new_tokens):
        for r in range(len(outs)):
            if done[r]:
                continue
            t = int(tok_h[r])
            if eos_id is not None and t == int(eos_id):
                done[r] = True
                continue
            outs[r].append(t)
            if len(outs[r]) >= max_new_tokens:
                done[r] = True

    def generate(self, prompts, max_new_tokens=32, temperature=0.0,
                 top_k=0, eos_id=None, seed=None, key=None,
                 kv_dtype=None, spec_k=None, spec_mode=None,
                 drafter=None):
        """KV-cached generation: one bucketed prefill (compute-bound and
        flash-fused), a jitted scatter of the fresh row caches into a
        transient :class:`serving.kvpool.KVBlockPool`, then one compiled
        paged decode step per token with allocation-on-append; the
        pool's blocks are freed when generation ends. ``prompts`` is a
        list of 1-D int token arrays (ragged lengths fine — rows are
        right-padded to the bucket and tracked by per-row position
        counters). Returns a list of 1-D int32 arrays of NEW tokens
        (prompt excluded; generation stops at ``eos_id``, which is not
        included).

        ``kv_dtype`` (None -> ``FLAGS_kv_cache_dtype``) selects the
        pool's element type (fp32/bf16/int8); over an fp32 pool greedy
        output is token for token ``generate_naive``'s.

        ``spec_k`` (None -> ``FLAGS_decode_spec_k``; 0 disables) turns
        on speculative decoding: a drafter proposes up to K tokens per
        row per step, one verify pass scores all K+1 positions, and
        rejection sampling keeps the model-agreed prefix — greedy
        output is BITWISE identical to the non-speculative path, and
        stochastic output preserves the sampler's distribution exactly.
        ``spec_mode`` (None -> ``FLAGS_decode_spec_mode``) picks the
        default drafter ('ngram' prompt-lookup / 'model' shared-weight
        draft GPT); ``drafter`` overrides it with any object exposing
        ``draft(ctx_tokens, k)``."""
        if spec_k is None:
            spec_k = int(flag("decode_spec_k"))
        if int(spec_k) > 0:
            return self._generate_spec(
                prompts, max_new_tokens, temperature, top_k, eos_id,
                seed, key, kv_dtype, int(spec_k), spec_mode, drafter)
        prompts, lens, key = self._prep(prompts, max_new_tokens, seed,
                                        key)
        B = len(prompts)
        tokens, pos_ids, last = self._pack_prompts(prompts)
        bb, s = tokens.shape
        kv_dtype = kv_dtype or flag("kv_cache_dtype")
        pool = self._offline_pool(bb, kv_dtype)
        try:
            for r in range(B):
                pool.alloc(r, lens[r])
            logits, row_caches, key = self._run_prefill(
                tokens, pos_ids, last, key, kv_dtype=kv_dtype)
            pool.scatter_prefill(list(range(B)), row_caches, s,
                                 lengths=lens)

            temp = np.full((bb,), float(temperature), np.float32)
            topk = np.full((bb,), int(top_k), np.int32)
            tok, key = self._run_sample(logits, temp, topk, key)
            tok_h = np.asarray(tok)

            outs = [[] for _ in range(B)]
            done = np.zeros(B, bool)
            # pos[r] = cache slot the NEXT fed token lands in
            pos = np.zeros((bb,), np.int32)
            pos[:B] = np.asarray(lens, np.int32)
            self._emit(tok_h, outs, done, eos_id, max_new_tokens)

            while not done.all():
                for r in range(B):
                    if not done[r]:       # allocation-on-append
                        pool.ensure(r, int(pos[r]))
                logits, key = self._run_decode_paged(tok, pos, pool, key)
                tok, key = self._run_sample(logits, temp, topk, key)
                tok_h = np.asarray(tok)
                pos[:B] = np.where(done, pos[:B], pos[:B] + 1)
                self._emit(tok_h, outs, done, eos_id, max_new_tokens)
                if self.stats:
                    self.stats.bump("decode_steps")
            if self.stats:
                self.stats.bump("tokens_generated",
                                int(sum(len(o) for o in outs)))
            return [np.asarray(o, np.int32) for o in outs]
        finally:
            self._release_offline_pool(pool)

    def _offline_pool(self, rows, kv_dtype):
        """The transient pool of one ``generate()`` call, one cached per
        (bucket rows, dtype, block size)."""
        pool_key = (rows, kv_dtype, int(flag("kv_block_size")))
        pool = self._paged_pools.get(pool_key)
        if pool is None:
            pool = self.new_pool(rows, dtype=kv_dtype, name="offline")
            self._paged_pools[pool_key] = pool
        return pool

    @staticmethod
    def _release_offline_pool(pool):
        # free every block and the device arrays, but KEEP the pool
        # instance (its compiled prefill-scatter closure is the
        # expensive part — the next call rebuilds zero arrays without
        # retracing); a cached pool must not pin its HBM between calls
        for r in range(pool.slots):
            pool.free_slot(r)
        pool.drop_device()

    def _generate_spec(self, prompts, max_new_tokens, temperature,
                       top_k, eos_id, seed, key, kv_dtype, spec_k,
                       spec_mode, drafter):
        """The speculative decode loop behind ``generate(spec_k=K)``:
        draft up to K tokens per row host-side, verify all K+1
        positions in ONE model pass (the whole win — a verify pass
        costs about one decode step, both bandwidth-bound), keep the
        accepted prefix plus the correction/bonus token via rejection
        sampling. Per-row draft counts are capped to the row's
        remaining budget."""
        kv_dtype = kv_dtype or flag("kv_cache_dtype")
        # an architecture with no verify program refuses here, by name
        self._ensure_prog(f"verify_paged_{kv_dtype}")
        prompts, lens, key = self._prep(prompts, max_new_tokens, seed,
                                        key)
        if drafter is None:
            drafter = make_drafter(spec_mode, generator=self)
        B = len(prompts)
        tokens, pos_ids, last = self._pack_prompts(prompts)
        bb, s = tokens.shape
        cfg = self.cfg
        pool = self._offline_pool(bb, kv_dtype)
        try:
            for r in range(B):
                pool.alloc(r, lens[r])
            logits, row_caches, key = self._run_prefill(
                tokens, pos_ids, last, key, kv_dtype=kv_dtype)
            pool.scatter_prefill(list(range(B)), row_caches, s)

            temp = np.full((bb,), float(temperature), np.float32)
            topk = np.full((bb,), int(top_k), np.int32)
            tok, key = self._run_sample(logits, temp, topk, key)
            tok_h = np.asarray(tok).astype(np.int32)

            outs = [[] for _ in range(B)]
            done = np.zeros(B, bool)
            pos = np.zeros((bb,), np.int32)
            pos[:B] = np.asarray(lens, np.int32)
            self._emit(tok_h, outs, done, eos_id, max_new_tokens)

            S = spec_k + 1
            while not done.all():
                # host-side drafting, capped to each row's remaining
                # budget (drafting past it is pure wasted verify work)
                draft = np.zeros((bb, spec_k), np.int32)
                nd = np.zeros((bb,), np.int32)
                for r in range(B):
                    if done[r]:
                        continue
                    kr = min(spec_k, max_new_tokens - len(outs[r]) - 1)
                    if kr <= 0:
                        continue
                    ctx = np.concatenate(
                        [prompts[r], np.asarray(outs[r], np.int32)])
                    d = np.asarray(drafter.draft(ctx, kr),
                                   np.int32).ravel()[:kr]
                    nd[r] = d.size
                    draft[r, :d.size] = d
                feed_toks = np.zeros((bb, S), np.int32)
                feed_toks[:, 0] = tok_h
                feed_toks[:, 1:] = draft
                span_pos = np.clip(
                    pos[:, None] + np.arange(S, dtype=np.int32)[None, :],
                    0, cfg.max_position - 1)
                limit = np.zeros((bb,), np.int32)
                for r in range(B):
                    if not done[r]:
                        limit[r] = int(nd[r]) + 1
                        pool.alloc(r, int(pos[r]) + int(nd[r]) + 1)
                logits, key = self._run_verify_paged(
                    feed_toks, span_pos, pos, limit, pool, key)
                out_toks, acc, key = self._run_spec_accept(
                    logits, draft, temp, topk, nd, key)
                out_h = np.asarray(out_toks)
                acc_h = np.asarray(acc)
                for r in range(B):
                    if done[r]:
                        continue
                    a = int(acc_h[r])
                    for j in range(a + 1):
                        if done[r]:
                            break
                        t = int(out_h[r, j])
                        if eos_id is not None and t == int(eos_id):
                            done[r] = True
                            break
                        outs[r].append(t)
                        if len(outs[r]) >= max_new_tokens:
                            done[r] = True
                    pos[r] += a + 1
                    tok_h[r] = out_h[r, a]
                if self.stats:
                    self.stats.bump("decode_steps")
                    self.stats.bump("spec_steps")
                    self.stats.bump("spec_drafted", int(nd.sum()))
                    self.stats.bump("spec_accepted",
                                    int(acc_h[:B].sum()))
                    self.stats.bump(
                        "spec_rejected",
                        int(((acc_h[:B] < nd[:B]) & (nd[:B] > 0)).sum()))
            if self.stats:
                self.stats.bump("tokens_generated",
                                int(sum(len(o) for o in outs)))
            return [np.asarray(o, np.int32) for o in outs]
        finally:
            self._release_offline_pool(pool)

    def generate_naive(self, prompts, max_new_tokens=32, temperature=0.0,
                       top_k=0, eos_id=None, seed=None, key=None):
        """Full-recompute baseline: every new token re-runs the whole
        forward at the (bucketed) current length — O(S^2) attention per
        token, no KV cache. Same bucketing, same sampler, same RNG
        stream as ``generate`` (greedy output is token-for-token
        identical); exists for the bench A/B and parity tests."""
        prompts, lens, key = self._prep(prompts, max_new_tokens, seed,
                                        key)
        B = len(prompts)
        bb = length_bucket(B)
        cur = [list(map(int, p)) for p in prompts]
        outs = [[] for _ in range(B)]
        done = np.zeros(B, bool)
        temp = np.full((bb,), float(temperature), np.float32)
        topk = np.full((bb,), int(top_k), np.int32)
        while not done.all():
            tokens, pos_ids, last = self._pack_prompts(
                [np.asarray(c, np.int32) for c in cur])
            logits, key = self._run_logits(tokens, pos_ids, last, key)
            tok, key = self._run_sample(logits, temp, topk, key)
            tok_h = np.asarray(tok)
            for r in range(B):
                if not done[r]:
                    cur[r].append(int(tok_h[r]))
            self._emit(tok_h, outs, done, eos_id, max_new_tokens)
        return [np.asarray(o, np.int32) for o in outs]
