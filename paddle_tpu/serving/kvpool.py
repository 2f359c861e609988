"""Block-paged KV-cache pool: decode memory priced by ACTUAL tokens.

A dense decode bank (a ``[slots, H, max_len, D]`` buffer per layer)
charges every slot ``max_len`` HBM whatever its real length, and decode
is bandwidth-bound against exactly that buffer. This module is the
vLLM/PagedAttention store (Kwon et al. 2023) that ``GenerationEngine``
and ``GPTGenerator.generate`` keep keys and values in: one
device-resident pool of fixed-size blocks
(``[num_blocks, H, block_size, D]`` per layer, K and V) shared across
slots, a per-slot block table, blocks allocated on append and returned
on EOS/deadline/cancel, so concurrent generations are bounded by the
pool's token capacity — not ``slots * max_len``.

Host side (this file): a free-list allocator with occupancy /
internal-fragmentation accounting, typed
:class:`KVPoolExhaustedError` admission backpressure (a
``ServerOverloadedError`` subclass — the wire maps it to
``etype: "Overloaded"`` and clients back off), ``kvpool_*`` metrics in
the process registry, flight-recorder events for exhaustion and block
leaks, and the ``serving.kv_alloc`` chaos point through every
allocation. Block 0 is the reserved TRASH block: padded block-table
entries point at it, so bucket-padded prefill scatters and stale free
slots write garbage somewhere harmless that position masks never read.

Layer groups: an architecture whose layers keep different spans of a row
(full-attention layers every position, window layers the last
``window``) gets one GROUP a kind, each with its own block ids, free
list and per-slot table in the one pool. A window group's table is a
ring of ``window_blocks`` columns: logical block ``b`` of a row lives in
column ``b % ring``, so the block that fell wholly behind the window is
the one the next append reuses (``window_blocks_recycled``) and a slot
never holds more than the ring. Allocation, admission, the leak sweep
and the counts cover every group; the prefix cache, copy-on-write and
migration are the full group's alone and are off for a pool that has a
window group.

Cache layers need not be weight layers. A group may say ``passes``: its
``layers`` are then ``passes`` cache layers a weight layer (a stack run
several times over the same weights, a cache a (pass, layer) pair: cache
layer ``u * arrays + i`` is pass ``u`` of weight layer ``i``), all under
the one block table, and the pool keeps a weight layer's passes in ONE
array of ``passes * num_blocks`` blocks, pass ``u``'s block ``b`` at
``u * num_blocks + b`` (each pass has a trash block of its own there).
A block id then stands for that block in every cache layer, as it does
with one pass; the executables add the offset. The prefix cache and
migration are off for such a pool too.

Not everything a row keeps is keys and values. A ``state`` group's layers
(a state-space mixer's: a recurrent state and a convolution's tail, the
same size whatever the context's length) keep arrays a layer indexed by
SLOT: ``[slots, ...]`` beside the block arrays, no table and no blocks,
in the one ``arrays()`` dict under ``cache_s<tag>_<m>``
(:func:`state_array_specs`). They are donated into the decode step and
adopted back with the block arrays, written by the prefill scatter for
the admitted slots in the same donated call, dropped and reset with
them, and left alone by ``free_slot`` (the next admission overwrites
them). A row's state cannot be cut at a shared prefix or moved without a
snapshot nobody takes yet: the prefix cache and migration are off for
such a pool too.

Device side: lazily-built jnp pool arrays (float32 / bfloat16 / int8
with per-(block, head, slot) float32 scales — ``FLAGS_kv_cache_dtype``;
at bandwidth-bound decode, halving cache bytes is ~2x tokens/s), a
jitted bucketed prefill scatter (dense prefill row caches reshaped to
blocks and scattered through the table in one donated call), and the
paged decode programs' feed dict. The fused read path is
``kernels/paged_attention.py``.

The stored-shape contract. A pool array is logically ``[num_blocks, H,
block_size, D]``; the device array, every executable's parameter and
result, is the same bytes as ``[num_blocks, H * block_size // f,
f * D]`` with ``f = 128 // D`` slots of a head in one 128-lane row
(``kernels/paged_attention.pool_packing``; int8 scales ``[num_blocks,
f, H * block_size // f]``). That shape is tile-exact, so the runtime's
layout, the append's, the prefill scatter's and the decode kernel's are
one and no executable copies a pool array to relay it: every fresh
compile of the scatter, the COW copy and the import counts the
pool-sized ``copy`` instructions of its optimised HLO
(:func:`count_pool_relayouts`; ``stats()["relayouts"]``, 0 is the aim,
and the generator counts its programs' the same way). Blocks are the
major dimension, so the block-row writers here index dimension 0 and
are in place. The logical shape stays the contract of
``export_slot``/``import_slot`` (the wire payload is unchanged), of
:meth:`KVBlockPool.logical` and of the tests: host-side reshapes.
"""
import hashlib
import math
import re
import threading
from collections import OrderedDict, namedtuple

import numpy as np

from ..flags import flag
from ..observability.metrics import default_registry
from ..observability.recorder import flight_recorder as _flightrec
from ..resilience import maybe_fail
from .batching import BadRequestError, ServerOverloadedError, next_bucket

# -- typed backpressure ----------------------------------------------------


class KVPoolExhaustedError(ServerOverloadedError):
    """The pool has no free blocks for the allocation. Subclasses
    :class:`ServerOverloadedError`, so admission surfaces it as
    backpressure (wire ``etype: "Overloaded"``) — the client backs off
    and retries, by which time finished rows have returned blocks.
    Carries ``needed``/``free``/``capacity`` block counts."""

    def __init__(self, message, needed=None, free=None, capacity=None):
        super().__init__(message)
        self.needed = needed
        self.free = free
        self.capacity = capacity


# -- metrics (native families; ``pool`` label keeps a serving pool and
#    transient offline pools from clobbering each other's gauges) --------

_BLOCKS_IN_USE = default_registry().gauge(
    "kvpool_blocks_in_use_count",
    "KV-pool blocks currently allocated to live slots",
    labels=("pool",), max_series=64)
_CAPACITY = default_registry().gauge(
    "kvpool_capacity_blocks_count",
    "KV-pool allocatable block capacity (trash block excluded)",
    labels=("pool",), max_series=64)
_OCCUPANCY = default_registry().gauge(
    "kvpool_occupancy_ratio",
    "allocated / allocatable KV-pool blocks",
    labels=("pool",), max_series=64)
_SAVED = default_registry().gauge(
    "kvpool_saved_vs_dense_bytes",
    "device bytes a dense [slots, H, max_len, D] fp32 bank would hold "
    "minus the pool bytes actually allocated",
    labels=("pool",), max_series=64)
_ALLOC_FAIL = default_registry().counter(
    "kvpool_alloc_failures_total",
    "block allocations refused with KVPoolExhaustedError",
    labels=("pool",), max_series=64)
_ALLOCATED = default_registry().counter(
    "kvpool_blocks_allocated_total",
    "KV-pool blocks handed out by the free-list allocator",
    labels=("pool",), max_series=64)
_FREED = default_registry().counter(
    "kvpool_blocks_freed_total",
    "KV-pool blocks returned to the free list",
    labels=("pool",), max_series=64)
_LEAKED = default_registry().counter(
    "kvpool_leaked_blocks_total",
    "blocks found still held by finished slots and reclaimed by the "
    "leak sweep",
    labels=("pool",), max_series=64)
_EXPORTED = default_registry().counter(
    "kvpool_blocks_exported_total",
    "KV blocks serialized out of the pool for cross-replica migration",
    labels=("pool",), max_series=64)
_IMPORTED = default_registry().counter(
    "kvpool_blocks_imported_total",
    "migrated KV blocks deserialized into the pool",
    labels=("pool",), max_series=64)
_PREFIX_ENTRIES = default_registry().gauge(
    "kvpool_prefix_entries_count",
    "prompt-prefix cache entries currently indexed",
    labels=("pool",), max_series=64)
_PREFIX_BLOCKS = default_registry().gauge(
    "kvpool_prefix_cached_blocks_count",
    "KV blocks held ONLY by the prefix cache (evictable under "
    "pressure; not counted as slot load)",
    labels=("pool",), max_series=64)
_PREFIX_HITS = default_registry().counter(
    "kvpool_prefix_hits_total",
    "prompt admissions that adopted cached prefix blocks",
    labels=("pool",), max_series=64)
_PREFIX_MISSES = default_registry().counter(
    "kvpool_prefix_misses_total",
    "prompt admissions that found no cached prefix",
    labels=("pool",), max_series=64)
_PREFIX_TOKENS_REUSED = default_registry().counter(
    "kvpool_prefix_tokens_reused_total",
    "prompt tokens whose prefill was skipped by adopting cached "
    "prefix blocks",
    labels=("pool",), max_series=64)
_PREFIX_EVICTIONS = default_registry().counter(
    "kvpool_prefix_evictions_total",
    "prefix-cache entries evicted LRU under pool pressure",
    labels=("pool",), max_series=64)
_PREFIX_COW = default_registry().counter(
    "kvpool_prefix_cow_copies_total",
    "shared KV blocks copy-on-write duplicated before a divergent "
    "write",
    labels=("pool",), max_series=64)

_DTYPES = ("fp32", "bf16", "int8")
_ELEM_BYTES = {"fp32": 4, "bf16": 2, "int8": 1}

# migration payload format tag (bump on any layout change: an importer
# must never guess at a frame written by a different code revision)
KV_WIRE_FMT = "kvblocks1"


def _np_pool_dtype(kv_dtype):
    import jax.numpy as jnp
    return {"fp32": jnp.float32, "bf16": jnp.bfloat16,
            "int8": jnp.int8}[kv_dtype]


_COPY_RESULT = re.compile(r"= \w+\[([\d,]*)\]\S* copy\(")


def count_pool_relayouts(hlo_text, element_counts):
    """``copy`` instructions of an optimised HLO whose result has as
    many elements as a pool array (``element_counts``): each is one
    whole array relaid to suit an op's layout, where the stored shape
    is meant to leave none. One scan of the text a fresh compile."""
    counts = set(int(c) for c in element_counts)
    return sum(
        1 for dims in _COPY_RESULT.findall(hlo_text)
        if math.prod(int(d) for d in dims.split(",") if d) in counts)


def pool_element_counts(arrays):
    """The element counts :func:`count_pool_relayouts` looks for: those
    of the ``cache_p*`` block arrays and ``cache_s*`` per-slot state
    arrays of a feed or of a pool."""
    return {int(math.prod(a.shape)) for n, a in arrays.items()
            if n.startswith(("cache_p", "cache_s"))}


class _PoolJit:
    """A jitted writer of the pool (its first argument, donated) that
    compiles ahead of time for every new set of shapes and counts the
    pool-sized copies XLA left in each executable."""

    def __init__(self, fn):
        import jax
        self._jit = jax.jit(fn, donate_argnums=(0,))
        self._compiled = {}
        self.relayouts = 0      # the worst executable's

    def __call__(self, pool, *args):
        import jax
        sharding = getattr(next(iter(pool.values())), "sharding", None)
        if getattr(sharding, "mesh", None) is not None \
                and sharding.mesh.size > 1:
            # beside a pool split over a tp mesh an executable compiled
            # ahead of time wants every other argument on that mesh too
            from jax.sharding import NamedSharding, PartitionSpec
            args = jax.device_put(
                args, NamedSharding(sharding.mesh, PartitionSpec()))
        return self.compiled(pool, *args)(pool, *args)

    def compiled(self, pool, *args):
        """The executable for these shapes (arrays or
        ``ShapeDtypeStruct``s), compiled and counted at first sight."""
        import jax
        key = tuple((a.shape, str(a.dtype)) for a in
                    jax.tree_util.tree_leaves((pool, args)))
        compiled = self._compiled.get(key)
        if compiled is None:
            compiled = self._jit.lower(pool, *args).compile()
            self._compiled[key] = compiled
            self.relayouts = max(self.relayouts, count_pool_relayouts(
                compiled.as_text(), pool_element_counts(pool)))
        return compiled


def pool_feed_names(num_layers, quantized):
    """Feed/fetch names of the paged decode program's pool arrays, in
    the ONE canonical order the graph builder, the generator's unpack
    and this pool all share: k pools, v pools, then (int8 only) k/v
    scale pools. The ``cache_`` prefix keeps them in the generator's
    donated-argument group — XLA aliases the append in place."""
    names = [f"cache_pk_{i}" for i in range(num_layers)] \
        + [f"cache_pv_{i}" for i in range(num_layers)]
    if quantized:
        names += [f"cache_pks_{i}" for i in range(num_layers)] \
            + [f"cache_pvs_{i}" for i in range(num_layers)]
    return names


def state_array_specs(groups):
    """``{feed name: (shape a slot, dtype)}`` of the per-slot arrays of
    ``groups``' state group (an architecture's ``kv_groups()``): array
    ``tag`` of the group's layer ``m`` is ``cache_s<tag>_<m>``, every
    layer's first array, then every layer's second: the order in which
    they follow :func:`pool_feed_names`' in every feed and fetch list."""
    return {f"cache_s{tag}_{m}": spec
            for g in groups or () if g.get("state")
            for tag, spec in g["arrays"].items() for m in g["layers"]}


def prompt_prefix_key(tokens, length=None):
    """Content hash of the first ``length`` tokens of a prompt (the
    whole prompt when ``length`` is None) — the ONE prefix key the
    pool's block index and the router's affinity map share, so 'the
    replica that cached this prefix' is a well-defined address
    fleet-wide. int32 token bytes hashed, so the key is independent of
    list/array input type."""
    a = np.ascontiguousarray(np.asarray(tokens, np.int32).reshape(-1))
    if length is not None:
        a = a[:int(length)]
    return hashlib.blake2b(a.tobytes(), digest_size=16).hexdigest()


def decode_feed(pool, token, pos):
    """ONE paged decode step's feed dict: the pool's device arrays
    (donated into the call — XLA appends in place), this step's
    token/pos vectors, and a copy of the host block tables as they are
    now (the window group's ring as ``block_tables_window`` where the
    pool has one): the pool may change its tables while the step is in
    flight. The one builder both the offline generator loop and the
    serving engine use."""
    feed = dict(pool.arrays())
    feed["token"] = token
    feed["pos"] = pos
    feed["block_tables"] = pool.tables.copy()
    if pool.window is not None:
        feed["block_tables_window"] = pool.window.tables.copy()
    return feed


def adopt_decode_fetches(pool, fetches):
    """Adopt a paged decode step's fetched (donated-in-place) pool
    arrays back into ``pool`` and return the logits — the fetch-order
    contract (logits first, then :meth:`KVBlockPool.feed_names` order)
    lives HERE, next to the feed-order contract, so the two callers
    cannot drift."""
    names = pool.feed_names()
    pool.update_arrays({n: fetches[1 + i] for i, n in enumerate(names)})
    return fetches[0]


# what the prefill scatter's body is built from: a pool's layout
# (KVBlockPool.scatter_layout), and the feed names of the host arrays it
# routes a prefill through (KVBlockPool.scatter_indices), in its order
ScatterLayout = namedtuple(
    "ScatterLayout",
    "names block_size quantized d_head passes per_pass full windowed state")
SCATTER_FEEDS = ("scatter_tables", "scatter_ring_src", "scatter_ring_dst",
                 "scatter_slots")


def prefill_scatter(layout):
    """The prefill scatter's body for a pool of ``layout`` (a
    :class:`ScatterLayout`), a pure function any jit may trace:
    ``(pool, row_caches, tables [n, nblk], ring_src, ring_dst, slots) ->
    pool``, the pool's arrays with every row's keys and values (and
    states) written (:meth:`KVBlockPool.scatter_prefill` says what goes
    where). ``ring_src``/``ring_dst`` are None without a window group,
    ``slots`` None without a state group; a slot past the bank's end is
    dropped."""
    import jax.numpy as jnp
    from ..kernels.paged_attention import (
        pool_packing, quantize_kv, scales_to_stored, to_stored)
    bs, quant, d_head = layout.block_size, layout.quantized, layout.d_head
    passes, per_pass = layout.passes, layout.per_pass

    def blocks_of(src, n, nblk):
        """[bb, H, L, D] -> [n, nblk, H, bs, D]: the first ``nblk``
        blocks of the first ``n`` rows, logical (what the int8 pool
        quantizes), zero-padded past ``L``."""
        cover = nblk * bs
        take = min(cover, src.shape[2])
        vals = src[:n, :, :take]
        if take < cover:
            pad = jnp.zeros((n, src.shape[1], cover - take, src.shape[3]),
                            src.dtype)
            vals = jnp.concatenate([vals, pad], axis=2)
        vals = vals.reshape(n, vals.shape[1], nblk, bs, vals.shape[3])
        return vals.transpose(0, 2, 1, 3, 4)

    def stored_blocks(src, n, nblk):
        """[bb, H, L, D] -> [n, nblk, H * bs // f, f * D]: the same
        blocks as the pool stores them (``to_stored`` of
        :func:`blocks_of`), formed from the positions-major ``[n, L, H,
        D]`` the prefill computed them in (the transpose back cancels
        its own) with the ``f`` slots of a stored row joined on the lane
        axis, so the one transpose left writes whole 128-lane rows (a
        v5e admits a bucket of 8 x 1,024 GPT-2 medium tokens in 97.9 ms
        so, in 103.6 ms through ``blocks_of``). Sliced here,
        inside the jit, where the slice fuses with the gather; the
        covered length is shape-determined, zero-padded past ``L``."""
        H, D = src.shape[1], src.shape[3]
        f = pool_packing(D, bs)
        cover = nblk * bs
        take = min(cover, src.shape[2])
        vals = src[:n, :, :take].transpose(0, 2, 1, 3)
        if take < cover:
            pad = jnp.zeros((n, cover - take, H, D), src.dtype)
            vals = jnp.concatenate([vals, pad], axis=1)
        vals = vals.reshape(n, nblk, bs // f, f, H, D)
        vals = jnp.concatenate([vals[:, :, :, r] for r in range(f)],
                               axis=-1)
        return vals.transpose(0, 1, 3, 2, 4).reshape(
            n, nblk, H * bs // f, f * D)

    def scatter(pool, rows, tables, ring_src, ring_dst, slots=None):
        out = dict(pool)
        n, nblk = tables.shape
        m, tables_flat = n * nblk, tables.reshape(-1)
        # several passes in an array: [U,bb,H,L,D] row caches, pass u's
        # blocks at the table's ids + u * per_pass. One pass keeps the
        # path it had, with no leading axis: the scatters the other cells
        # warm lower to the text they lowered to (an add and a
        # concatenate of one part would move their compile-cache keys)
        at = tables_flat if passes == 1 else jnp.concatenate(
            [tables_flat + u * per_pass for u in range(passes)])
        for i in layout.full:
            for kind in ("k", "v"):
                src = rows[f"cache_{kind}_{i}"]    # [bb,H,L,D]
                dst = out[f"cache_p{kind}_{i}"]
                # whole blocks into rows of the stored array: dimension 0
                # alone is indexed, so in place
                if quant:
                    q, sc = quantize_kv(blocks_of(src, n, nblk).reshape(
                        (m, -1, bs, d_head)))
                    out[f"cache_p{kind}_{i}"] = \
                        dst.at[tables_flat].set(to_stored(q))
                    skey = f"cache_p{kind}s_{i}"
                    out[skey] = out[skey].at[tables_flat].set(
                        scales_to_stored(sc, d_head))
                    continue
                vals = stored_blocks(src, n, nblk) if passes == 1 \
                    else jnp.concatenate([stored_blocks(src[u], n, nblk)
                                          for u in range(passes)])
                out[f"cache_p{kind}_{i}"] = dst.at[at].set(
                    vals.reshape((passes * m,) + vals.shape[2:]).astype(
                        dst.dtype))
        for i in layout.windowed:
            for kind in ("k", "v"):
                vals = stored_blocks(rows[f"cache_{kind}_{i}"], n, nblk)
                vals = jnp.take_along_axis(
                    vals, ring_src[:, :, None, None], axis=1)
                dst = out[f"cache_p{kind}_{i}"]
                out[f"cache_p{kind}_{i}"] = dst.at[ring_dst].set(
                    vals.reshape((-1,) + vals.shape[2:]).astype(dst.dtype))
        for name in layout.state:
            # a row's state into its slot: dimension 0 alone is indexed,
            # so in place
            dst = out[name]
            out[name] = dst.at[slots].set(rows[name][:n].astype(dst.dtype),
                                          mode="drop")
        return out

    return scatter


class _WindowGroup:
    """The window layers' share of a pool: block ids of its own, a LIFO
    free list and a ring table a slot. Not thread-safe by itself: the
    pool calls it under its lock."""

    def __init__(self, slots, layers, window, block_size, num_blocks=None):
        self.layers = list(layers)
        self.window = int(window)
        self.ring = _ceil_div(self.window, block_size) + 1
        self.num_blocks = int(num_blocks or slots * self.ring + 1)
        self.block_size = int(block_size)
        self.tables = np.zeros((slots, self.ring), np.int32)
        self.recycled = 0
        self.reset()

    def reset(self):
        self.free = list(range(self.num_blocks - 1, 0, -1))
        self.held = {}          # slot -> ring columns filled
        self.logical = {}       # slot -> logical blocks covered so far
        self.tables[:] = 0

    @property
    def capacity(self):
        return self.num_blocks - 1

    def in_use(self):
        return self.capacity - len(self.free)

    def need(self, slot, ntokens):
        """Blocks ``slot`` lacks to cover ``ntokens``: never more than
        the ring holds."""
        want = min(_ceil_div(max(int(ntokens), 0), self.block_size),
                   self.ring)
        return max(want - self.held.get(slot, 0), 0)

    def grow(self, slot, ntokens):
        """Cover ``ntokens``: fill ring columns while any are empty,
        then reuse the column whose block fell behind the window. The
        caller has checked :meth:`need` against the free list."""
        logical = _ceil_div(max(int(ntokens), 0), self.block_size)
        have = self.held.get(slot, 0)
        for col in range(have, min(logical, self.ring)):
            self.tables[slot, col] = self.free.pop()
        self.held[slot] = max(have, min(logical, self.ring))
        before = self.logical.get(slot, 0)
        self.recycled += max(logical - max(before, self.ring), 0)
        self.logical[slot] = max(before, logical)

    def release(self, slot):
        n = self.held.pop(slot, 0)
        self.logical.pop(slot, None)
        self.free.extend(int(self.tables[slot, c]) for c in range(n))
        self.tables[slot, :] = 0
        return n


class KVBlockPool:
    """Device block pool + host free-list allocator + per-slot tables.

    Single-driver by design, like the ``GenerationEngine`` it backs: the
    decode loop is the only caller of alloc/free/scatter/update (a lock
    still guards the accounting so stats()/metrics scrapes from other
    threads read consistent state).

    ``num_blocks`` counts the trash block: the allocatable capacity is
    ``num_blocks - 1``. Default sizing is HBM-equivalent to the dense
    bank it replaces (``slots * ceil(max_seq_len/block_size) + 1``) —
    the paged win is that short generations leave most of it free for
    MORE concurrent slots, where dense burned it on padding.
    """

    def __init__(self, *, slots, num_layers, num_heads, d_head,
                 max_seq_len, block_size=None, num_blocks=None,
                 dtype=None, name="serving", prefix_cache=None,
                 groups=None):
        self.slots = int(slots)
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.d_head = int(d_head)
        self.max_seq_len = int(max_seq_len)
        self.block_size = int(block_size or flag("kv_block_size"))
        if self.block_size < 1:
            raise ValueError("kv_block_size must be >= 1")
        self.dtype = dtype or flag("kv_cache_dtype")
        if self.dtype not in _DTYPES:
            raise ValueError(
                f"kv_cache_dtype must be one of {_DTYPES}, "
                f"got {self.dtype!r}")
        self.blocks_per_row = _ceil_div(self.max_seq_len, self.block_size)
        if num_blocks is None:
            num_blocks = int(flag("kv_pool_blocks")) or \
                self.slots * self.blocks_per_row + 1
        self.num_blocks = int(num_blocks)
        if self.num_blocks < 2:
            raise ValueError("KVBlockPool needs >= 2 blocks (block 0 is "
                             "the reserved trash block)")
        self.name = str(name)
        self.quantized = self.dtype == "int8"
        # layer groups (an architecture's kv_groups()): the full layers
        # are this pool's own tables and free list; window layers, where
        # there are any, a _WindowGroup beside them
        groups = groups or [{"name": "full", "window": None,
                             "layers": list(range(self.num_layers))}]
        stateful = [g for g in groups if g.get("state")]
        groups = [g for g in groups if not g.get("state")]
        full = [g for g in groups if not g.get("window")]
        windowed = [g for g in groups if g.get("window")]
        if len(full) > 1 or len(windowed) > 1 or len(stateful) > 1:
            raise ValueError("KVBlockPool holds one full, one window and "
                             "one state group of layers at most")
        # feed name -> (shape a slot, dtype) of the state group's arrays
        # (module docstring); ``num_layers`` counts KV cache layers alone
        self.state_layers = len(stateful[0]["layers"]) if stateful else 0
        self.state_arrays = state_array_specs(stateful)
        self.full_layers = list(full[0]["layers"]) if full else []
        # cache layers a weight layer, kept in one array (module docstring)
        self.passes = int(full[0].get("passes", 1)) if full else 1
        if self.passes > 1 and (windowed or self.quantized
                                or self.num_layers % self.passes):
            raise ValueError("a group of several passes is the pool's "
                             "only group, whole passes, and not int8")
        self.num_arrays = self.num_layers // self.passes
        self.window = None
        if windowed:
            if self.quantized:
                raise ValueError("a window group has no int8 pool")
            self.window = _WindowGroup(
                self.slots, windowed[0]["layers"], windowed[0]["window"],
                self.block_size)

        # host accounting (block 0 = trash, never allocated). LIFO free
        # list: recently-freed blocks are re-used first, which keeps the
        # working set of hot blocks small.
        self._lock = threading.Lock()
        self._free = list(range(self.num_blocks - 1, 0, -1))
        self._slot_nblocks = {}        # slot -> blocks held
        self._slot_tokens = {}         # slot -> tokens accounted
        self.tables = np.zeros((self.slots, self.blocks_per_row),
                               np.int32)
        # refcounted sharing (prefix cache / COW): every handed-out
        # block carries a refcount; a block returns to the free list
        # only when its LAST owner (slot table entry or prefix-cache
        # entry) releases it
        self._refs = {}                # block -> total owners
        self._cache_ref = {}           # block -> prefix-entry owners
        # hash(prompt prefix) -> {"blocks", "tokens", "hits"}; insertion
        # order IS the LRU order (move_to_end on hit, popitem(False)
        # under pressure)
        self._prefix = OrderedDict()
        # a cached prefix's window layers hold the END of the prompt it
        # was cut from, not of the prefix: with a window group nothing
        # is matched or deposited until a PR builds that
        self.prefix_enabled = bool(flag("kv_prefix_cache")
                                   if prefix_cache is None
                                   else prefix_cache) \
            and self.window is None and self.passes == 1 \
            and not self.state_layers
        self.array_sharding = None     # NamedSharding under a tp mesh
        self._arrays = None            # lazy device pool
        self._scatter_fn = None
        self._layout = None            # scatter_layout(), made once
        self._import_fn = None         # migration scatter (import_slot)
        self._copy_fn = None           # COW block duplication
        self._update_gauges()

    # -- sizing helpers ---------------------------------------------------
    def blocks_for_tokens(self, ntokens):
        return _ceil_div(max(int(ntokens), 0), self.block_size)

    @property
    def capacity_blocks(self):
        """Allocatable blocks of every group (trash excluded)."""
        return self.num_blocks - 1 + (
            self.window.capacity if self.window else 0)

    def block_bytes(self, layers=None):
        """Device bytes of one block across a group's ``layers`` (the
        full group's by default), K+V, scales included."""
        elem = _ELEM_BYTES[self.dtype]
        nl = len(self.full_layers) if layers is None else len(layers)
        n = 2 * nl * self.num_heads * self.block_size * self.d_head * elem
        if self.quantized:
            n += 2 * nl * self.num_heads * self.block_size * 4
        return n

    def state_bytes_per_slot(self):
        """Device bytes of the state group's arrays one slot keeps."""
        return sum(math.prod(shape) * np.dtype(dt).itemsize
                   for shape, dt in self.state_arrays.values())

    def state_attrs(self):
        """``{"state_layers": n}`` for the spans of what runs over a
        pool with a state group; nothing for any other pool."""
        return {"state_layers": self.state_layers} \
            if self.state_layers else {}

    def feed_names(self):
        """Every array of :meth:`arrays` in the one order the decode
        program is fed and fetches them: :func:`pool_feed_names`, then
        :func:`state_array_specs`' names."""
        return pool_feed_names(self.num_arrays, self.quantized) \
            + list(self.state_arrays)

    def dense_slot_bytes(self):
        """Device bytes ONE dense bank slot costs (fp32, max_seq_len)."""
        return 2 * self.num_layers * self.num_heads * self.max_seq_len \
            * self.d_head * 4

    # -- allocator --------------------------------------------------------
    def check_fits(self, ntokens):
        """Raise :class:`~.batching.BadRequestError` when a request of
        ``ntokens`` could NEVER be satisfied by this pool — even empty.
        The submit-time door check: refusing it early costs nothing,
        and the error is TERMINAL (wire ``etype: "BadRequest"``), not
        the retryable ``Overloaded`` backpressure — backing off cannot
        make an impossible request fit."""
        need = self.blocks_for_tokens(ntokens)
        if need > self.num_blocks - 1 or (
                self.window and self.window.need(None, ntokens)
                > self.window.capacity):
            raise BadRequestError(
                f"request needs {need} KV blocks "
                f"({ntokens} tokens at block_size={self.block_size}) "
                f"but the pool's total capacity is "
                f"{self.num_blocks - 1} blocks — it can never be "
                f"admitted; raise FLAGS_kv_pool_blocks")

    def admission_check(self, ntokens, pending_tokens=()):
        """The admission-time capacity gate: blocks for ``ntokens``,
        PLUS blocks for every entry of ``pending_tokens`` (requests
        already accepted this admission round but not yet allocated),
        must be free right now — else a counted, flight-recorded
        :class:`KVPoolExhaustedError` (the typed shed half of
        backpressure: the client backs off; blocks return as rows
        finish)."""
        need = self.blocks_for_tokens(ntokens)
        pending = sum(self.blocks_for_tokens(t) for t in pending_tokens)
        with self._lock:
            if need + pending > len(self._free):
                self._evict_cold_locked(need + pending)
            free = len(self._free)
            if self.window and need + pending <= free:
                # the window group counts too: what it lacks is what
                # the refusal reports
                w = self.window
                wneed = w.need(None, ntokens) + sum(
                    w.need(None, t) for t in pending_tokens)
                if wneed > len(w.free):
                    need, pending, free = wneed, 0, len(w.free)
        if need + pending > free:
            _ALLOC_FAIL.inc(labels=(self.name,))
            _flightrec().record(
                "kv_pool_exhausted", pool=self.name, slot=None,
                needed_blocks=need + pending, free_blocks=free,
                capacity_blocks=self.capacity_blocks)
            raise KVPoolExhaustedError(
                f"KV pool {self.name!r} cannot admit a request of "
                f"{ntokens} tokens right now: {need} block(s) needed "
                f"(+{pending} pending this round), {free} free of "
                f"{self.capacity_blocks} — back off and retry",
                needed=need + pending, free=free,
                capacity=self.capacity_blocks)

    def alloc(self, slot, ntokens):
        """Grow ``slot``'s allocation to cover ``ntokens`` tokens
        (no-op when it already does). Raises
        :class:`KVPoolExhaustedError` with nothing changed when the
        free list cannot cover the growth."""
        maybe_fail("serving.kv_alloc")
        slot = int(slot)
        need = self.blocks_for_tokens(ntokens)
        with self._lock:
            have = self._slot_nblocks.get(slot, 0)
            add = need - have
            w = self.window
            if add <= 0:
                self._slot_tokens[slot] = max(
                    self._slot_tokens.get(slot, 0), int(ntokens))
                if w:           # within a block: the ring has it too
                    w.grow(slot, ntokens)
                return 0
            if add > len(self._free):
                self._evict_cold_locked(add)
            if add > len(self._free):
                free_now = len(self._free)
            elif w and w.need(slot, ntokens) > len(w.free):
                # every group or none: nothing is held half
                add, free_now = w.need(slot, ntokens), len(w.free)
            else:
                if w:
                    w.grow(slot, ntokens)
                for j in range(have, need):
                    b = self._free.pop()
                    self._refs[b] = 1
                    self.tables[slot, j] = b
                self._slot_nblocks[slot] = need
                self._slot_tokens[slot] = max(
                    self._slot_tokens.get(slot, 0), int(ntokens))
                self._update_gauges_locked()
                free_now = None
        if free_now is not None:
            _ALLOC_FAIL.inc(labels=(self.name,))
            _flightrec().record(
                "kv_pool_exhausted", pool=self.name, slot=slot,
                needed_blocks=add, free_blocks=free_now,
                capacity_blocks=self.capacity_blocks)
            raise KVPoolExhaustedError(
                f"KV pool {self.name!r} exhausted: slot {slot} needs "
                f"{add} more block(s) for {ntokens} tokens, "
                f"{free_now} free of {self.capacity_blocks}",
                needed=add, free=free_now, capacity=self.capacity_blocks)
        _ALLOCATED.inc(add, labels=(self.name,))
        return add

    def ensure(self, slot, pos):
        """Allocation-on-append: make sure the block holding cache slot
        ``pos`` exists before the decode step writes there."""
        return self.alloc(slot, int(pos) + 1)

    def free_slot(self, slot):
        """Release every block ``slot`` holds (EOS / deadline / cancel /
        error — the continuous-batching reclaim). A refcounted block
        (shared with the prefix cache or another slot) only returns to
        the free list when its LAST owner releases it. Idempotent;
        returns the number of blocks physically freed."""
        slot = int(slot)
        with self._lock:
            n = self._slot_nblocks.pop(slot, 0)
            self._slot_tokens.pop(slot, None)
            freed = self._release_blocks_locked(
                int(self.tables[slot, j]) for j in range(n))
            self.tables[slot, :] = 0
            if self.window:
                freed += self.window.release(slot)
            self._update_gauges_locked()
        if freed:
            _FREED.inc(freed, labels=(self.name,))
        return freed

    def _release_blocks_locked(self, block_ids):
        """Drop one reference per block; append to the free list at
        refcount 0. Returns blocks physically freed."""
        freed = 0
        for b in block_ids:
            left = self._refs.get(b, 1) - 1
            if left <= 0:
                self._refs.pop(b, None)
                self._free.append(b)
                freed += 1
            else:
                self._refs[b] = left
        return freed

    def blocks_in_use(self):
        """Blocks allocated to live slots. Blocks held ONLY by the
        prefix cache are working capital, not load — they report under
        :meth:`cached_blocks` / ``kvpool_prefix_cached_blocks_count``
        and evict LRU under pressure."""
        with self._lock:
            return sum(self._in_use_by_group_locked().values())

    def blocks_in_use_by_group(self):
        """``{"full": n[, "window": m]}``: :meth:`blocks_in_use` a
        group."""
        with self._lock:
            return self._in_use_by_group_locked()

    def _in_use_by_group_locked(self):
        out = {"full": self.num_blocks - 1 - len(self._free)
               - self._cached_only_locked()}
        if self.window:
            out["window"] = self.window.in_use()
        return out

    def cached_blocks(self):
        """Blocks held only by the prefix cache (evictable)."""
        with self._lock:
            return self._cached_only_locked()

    def _cached_only_locked(self):
        return sum(1 for b, c in self._cache_ref.items()
                   if c > 0 and self._refs.get(b, 0) == c)

    def holders(self):
        """{slot: blocks_held} for every slot holding blocks."""
        with self._lock:
            return dict(self._slot_nblocks)

    def reclaim_leaks(self, live_slots):
        """Free blocks held by slots NOT in ``live_slots`` — the leak
        sweep (a finished slot should have freed on its way out; blocks
        it still holds are a leak). Records a flight-recorder event per
        leaking slot so ``debug_dump`` explains shed admissions.
        Returns blocks reclaimed."""
        live = set(int(s) for s in live_slots)
        with self._lock:
            leaked = [(s, n) for s, n in self._slot_nblocks.items()
                      if s not in live and n > 0]
        total = 0
        for slot, held in leaked:
            n = self.free_slot(slot)
            total += n
            _LEAKED.inc(n, labels=(self.name,))
            # shared = table entries whose blocks stayed alive under a
            # remaining reference (prefix cache / another slot) — the
            # sweep released the leaking slot's claim either way
            _flightrec().record("kv_block_leak", pool=self.name,
                                slot=slot, blocks=n,
                                shared=held - n)
        return total

    # -- device arrays ----------------------------------------------------
    def arrays(self):
        """The paged decode program's pool feed dict (lazily built
        zeros): ``{cache_pk_i, cache_pv_i[, cache_pks_i, cache_pvs_i]}``
        and a state group's ``[slots, ...]`` arrays — see
        :meth:`feed_names` for the order contract."""
        if self._arrays is None:
            import jax.numpy as jnp
            from ..kernels.paged_attention import stored_shape
            shape = stored_shape(self.num_blocks, self.num_heads,
                                 self.block_size, self.d_head)
            dt = _np_pool_dtype(self.dtype)
            arrs = {}
            for i in range(self.num_arrays):
                # a window layer's arrays hold its group's blocks, an
                # array of several passes every pass's
                n = self.window.num_blocks if self.window \
                    and i in self.window.layers \
                    else self.passes * self.num_blocks
                arrs[f"cache_pk_{i}"] = jnp.zeros((n,) + shape[1:], dt)
                arrs[f"cache_pv_{i}"] = jnp.zeros((n,) + shape[1:], dt)
            if self.quantized:
                sshape = (shape[0], shape[2] // self.d_head, shape[1])
                for i in range(self.num_arrays):
                    # scale 1.0, not 0: a read of a never-written slot
                    # dequantizes 0 * 1.0 instead of hitting a 0-scale
                    arrs[f"cache_pks_{i}"] = jnp.ones(sshape, jnp.float32)
                    arrs[f"cache_pvs_{i}"] = jnp.ones(sshape, jnp.float32)
            for name, (shape, state_dt) in self.state_arrays.items():
                arrs[name] = jnp.zeros((self.slots,) + tuple(shape),
                                       state_dt)
            if self.array_sharding is not None:
                # tp-mesh placement: blocks sharded on the head axis
                # (the stored rows are head-major: dim 1), matching
                # gpt.apply_tp_sharding's qkv split — each chip holds
                # its own heads' cache bytes. Scale pools share the
                # same head split (their columns, dim 2).
                import jax
                arrs = {n: jax.device_put(a, self.array_sharding[n])
                        for n, a in arrs.items()}
            self._arrays = arrs
        return self._arrays

    def update_arrays(self, new_arrays):
        """Adopt the decode step's fetched (donated-in-place) pool
        arrays."""
        self._arrays = dict(new_arrays)

    def logical(self, name, blocks=None):
        """Pool array ``name`` (its rows ``blocks`` where given) in the
        LOGICAL shape, on the host: ``[n, H, block_size, D]`` values,
        ``[n, H, block_size]`` int8 scales. What :meth:`export_slot`
        sends and what a test or a debugger reads; the executables never
        see it."""
        from ..kernels.paged_attention import scales_to_logical, to_logical
        a = self.arrays()[name]
        a = np.asarray(a if blocks is None else a[np.asarray(blocks,
                                                             np.int32)])
        if name.startswith(("cache_pks_", "cache_pvs_")):
            return scales_to_logical(a, self.num_heads)
        return to_logical(a, self.num_heads, self.d_head)

    def drop_device(self):
        """Forget the device arrays (a failed donated call may have
        invalidated them); the next :meth:`arrays` rebuilds zeros. Host
        accounting is NOT touched — callers that also lost the logical
        contents call :meth:`reset`."""
        self._arrays = None

    def reset(self):
        """Free everything and drop the device pool — the engine
        restart / bank-lost path."""
        with self._lock:
            freed = self.num_blocks - 1 - len(self._free)
            if self.window:
                freed += self.window.in_use()
                self.window.reset()
            self._free = list(range(self.num_blocks - 1, 0, -1))
            self._slot_nblocks.clear()
            self._slot_tokens.clear()
            self._refs.clear()
            self._cache_ref.clear()
            self._prefix.clear()
            self.tables[:] = 0
            self._arrays = None
            self._update_gauges_locked()
        if freed:
            _FREED.inc(freed, labels=(self.name,))

    # -- block-granular prefix cache (refcounted sharing + COW) -----------
    # A completed prompt's blocks are deposited into a hash-keyed index
    # (exact length AND block-aligned length, so both a full repeat and
    # a longer prompt sharing whole blocks can hit). A hit adopts the
    # cached blocks by reference — the adopting slot only prefills the
    # tail. Any write into a block with >1 owner is preceded by a
    # copy-on-write duplication (prepare_write), so cached content is
    # immutable while shared and per-prompt outputs stay bitwise
    # correct after divergence.

    def match_prefix(self, prompt):
        """Longest cached prefix of ``prompt``: the exact prompt first
        (full-repeat fast path), then block-aligned lengths descending.
        Returns ``{"key", "tokens", "blocks"}`` or None. A hit
        refreshes the entry's LRU position."""
        if not self.prefix_enabled:
            return None
        toks = np.asarray(prompt, np.int32).reshape(-1)
        L = int(toks.size)
        if L < 1:
            return None
        bs = self.block_size
        lengths = [L] + [n for n in range((L // bs) * bs, 0, -bs)
                         if n != L]
        with self._lock:
            for n in lengths:
                key = prompt_prefix_key(toks, n)
                e = self._prefix.get(key)
                if e is None or e["tokens"] != n:
                    continue
                self._prefix.move_to_end(key)
                e["hits"] += 1
                _PREFIX_HITS.inc(labels=(self.name,))
                return {"key": key, "tokens": n,
                        "blocks": list(e["blocks"])}
        _PREFIX_MISSES.inc(labels=(self.name,))
        return None

    def adopt_prefix(self, slot, match):
        """Attach a :meth:`match_prefix` hit's blocks to ``slot`` by
        reference (refcount +1 per block; the slot must hold nothing).
        The adopter owes a :meth:`prepare_write` before any write into
        the adopted range — COW duplicates on first divergence."""
        slot = int(slot)
        blocks = [int(b) for b in match["blocks"]]
        tokens = int(match["tokens"])
        with self._lock:
            if self._slot_nblocks.get(slot, 0):
                raise ValueError(
                    f"KV pool {self.name!r} slot {slot} already holds "
                    f"blocks — free it before adopting a cached prefix")
            for j, b in enumerate(blocks):
                self.tables[slot, j] = b
                self._refs[b] = self._refs.get(b, 0) + 1
            self._slot_nblocks[slot] = len(blocks)
            self._slot_tokens[slot] = tokens
            self._update_gauges_locked()
        _PREFIX_TOKENS_REUSED.inc(tokens, labels=(self.name,))
        return len(blocks)

    def prefix_insert(self, prompt, slot):
        """Deposit ``slot``'s freshly prefilled prompt blocks into the
        prefix index (refcount +1 per block — the cache co-owns them,
        so they survive the slot's EOS until evicted LRU). Inserts the
        exact-length entry and, when distinct, the block-aligned one.
        No-op per entry already indexed. Returns entries inserted."""
        if not self.prefix_enabled:
            return 0
        toks = np.asarray(prompt, np.int32).reshape(-1)
        L = int(toks.size)
        slot = int(slot)
        if L < 1:
            return 0
        bs = self.block_size
        lengths = [L]
        aligned = (L // bs) * bs
        if aligned and aligned != L:
            lengths.append(aligned)
        inserted = 0
        with self._lock:
            held = self._slot_nblocks.get(slot, 0)
            for n in lengths:
                nb = _ceil_div(n, bs)
                if nb < 1 or nb > held:
                    continue
                key = prompt_prefix_key(toks, n)
                if key in self._prefix:
                    self._prefix.move_to_end(key)
                    continue
                blocks = [int(self.tables[slot, j]) for j in range(nb)]
                if 0 in blocks:
                    continue
                for b in blocks:
                    self._refs[b] = self._refs.get(b, 0) + 1
                    self._cache_ref[b] = self._cache_ref.get(b, 0) + 1
                self._prefix[key] = {"blocks": blocks, "tokens": n,
                                     "hits": 0}
                inserted += 1
            if inserted:
                self._update_gauges_locked()
        return inserted

    def prepare_write(self, slot, start_pos, end_pos):
        """Copy-on-write barrier: make every block covering cache
        positions ``[start_pos, end_pos)`` of ``slot`` exclusively
        owned before a write lands there. Shared blocks are duplicated
        into fresh ones (one donated jitted device copy for the batch
        of them) and the slot's table re-pointed; the cache/other-slot
        owners keep the originals. Raises :class:`KVPoolExhaustedError`
        (after LRU eviction of cold prefixes) when no block can be
        found for a copy — with the slot's table unchanged. Returns
        blocks duplicated."""
        slot = int(slot)
        start, end = int(start_pos), int(end_pos)
        if end <= start:
            return 0
        bs = self.block_size
        j0, j1 = start // bs, _ceil_div(end, bs)
        copies = []
        with self._lock:
            def shared():
                out = []
                for j in range(j0, j1):
                    b = int(self.tables[slot, j])
                    if b != 0 and self._refs.get(b, 1) > 1:
                        out.append(j)
                return out
            js = shared()
            if len(js) > len(self._free):
                # eviction can also UNSHARE a block (the cache drops
                # its reference), so re-scan after
                self._evict_cold_locked(len(js))
                js = shared()
            if len(js) > len(self._free):
                free_now = len(self._free)
            else:
                free_now = None
                for j in js:
                    b = int(self.tables[slot, j])
                    nb = self._free.pop()
                    self._refs[b] -= 1
                    self._refs[nb] = 1
                    self.tables[slot, j] = nb
                    copies.append((b, nb))
                if copies:
                    self._update_gauges_locked()
        if free_now is not None:
            _ALLOC_FAIL.inc(labels=(self.name,))
            _flightrec().record(
                "kv_pool_exhausted", pool=self.name, slot=slot,
                needed_blocks=len(js), free_blocks=free_now,
                capacity_blocks=self.capacity_blocks)
            raise KVPoolExhaustedError(
                f"KV pool {self.name!r} cannot copy-on-write {len(js)} "
                f"shared block(s) for slot {slot}: {free_now} free of "
                f"{self.capacity_blocks}",
                needed=len(js), free=free_now,
                capacity=self.capacity_blocks)
        if not copies:
            return 0
        _PREFIX_COW.inc(len(copies), labels=(self.name,))
        self._copy_blocks([s for s, _ in copies],
                          [d for _, d in copies])
        return len(copies)

    def _evict_cold_locked(self, need):
        """Evict LRU prefix entries until at least ``need`` blocks are
        free (or the index is empty). Cold cached prefixes are working
        capital, not load — LRU eviction here is what keeps affinity
        routing from pinning a replica's pool full of them."""
        evicted = 0
        while self._prefix and len(self._free) < need:
            key, e = self._prefix.popitem(last=False)
            for b in e["blocks"]:
                c = self._cache_ref.get(b, 0) - 1
                if c <= 0:
                    self._cache_ref.pop(b, None)
                else:
                    self._cache_ref[b] = c
            freed = self._release_blocks_locked(e["blocks"])
            if freed:
                _FREED.inc(freed, labels=(self.name,))
            _PREFIX_EVICTIONS.inc(labels=(self.name,))
            _flightrec().record(
                "kv_prefix_evicted", pool=self.name, tokens=e["tokens"],
                blocks=len(e["blocks"]), freed=freed, hits=e["hits"])
            evicted += 1
        return evicted

    def _copy_blocks(self, src_ids, dst_ids):
        """Device-side block duplication (COW): one donated jitted call
        copies every pool array's ``src`` rows into ``dst``. On failure
        the donated arrays must be presumed lost (drop_device
        semantics) — the caller's bank-lost path applies."""
        import jax.numpy as jnp
        if self._copy_fn is None:
            def cp(pool, src, dst):
                return {n: a.at[dst].set(a[src])
                        for n, a in pool.items()}
            self._copy_fn = _PoolJit(cp)
        try:
            self._arrays = self._copy_fn(
                self.arrays(), jnp.asarray(src_ids, jnp.int32),
                jnp.asarray(dst_ids, jnp.int32))
        except Exception:
            self._arrays = None
            raise

    # -- prefill scatter --------------------------------------------------
    def scatter_layout(self):
        """What :func:`prefill_scatter`'s body is built from: this
        pool's layout, never its contents (hashable: one body and one
        executable a layout and shape)."""
        if self._layout is None:
            self._layout = ScatterLayout(
                names=tuple(self.feed_names()), block_size=self.block_size,
                quantized=self.quantized, d_head=self.d_head,
                passes=self.passes, per_pass=self.num_blocks,
                full=tuple(i for i in self.full_layers
                           if i < self.num_arrays),
                windowed=tuple(self.window.layers) if self.window else (),
                state=tuple(self.state_arrays))
        return self._layout

    def _scatter(self):
        """The prefill scatter as a donated jit of its own (built once):
        what :meth:`scatter_prefill` runs."""
        if self._scatter_fn is None:
            self._scatter_fn = _PoolJit(prefill_scatter(
                self.scatter_layout()))
        return self._scatter_fn

    def scatter_indices(self, slot_ids, bucket_len, lengths=None,
                        rows=None):
        """The host arrays :func:`prefill_scatter` routes a prefill of
        ``bucket_len`` positions through, for a batch of ``rows`` rows
        (``len(slot_ids)`` by default) whose first rows go to
        ``slot_ids``: ``scatter_tables`` int32 [rows, nblk] (the slots'
        block tables; a row past ``slot_ids`` points every entry at the
        trash block), with a window group ``scatter_ring_src`` int32
        [rows, ring] and ``scatter_ring_dst`` int32 [rows * ring] (of a
        prompt of ``lengths[r]`` tokens the last ``ring`` logical
        blocks, each into the ring column its index names; a row past
        ``slot_ids`` writes the window's trash block), with a state group
        ``scatter_slots`` int32 [rows] (a row past ``slot_ids`` names
        the slot past the bank's end, which the scatter drops)."""
        n = len(slot_ids)
        rows = n if rows is None else int(rows)
        nblk = self.blocks_for_tokens(bucket_len)
        slots = np.asarray(slot_ids, np.int32)
        tables = np.zeros((rows, nblk), np.int32)
        tables[:n] = self.tables[slots, :nblk]
        out = {"scatter_tables": tables}
        if self.window is not None:
            if lengths is None:
                raise ValueError("a pool with a window group scatters a "
                                 "prefill by the prompts' lengths")
            w = self.window
            last = (np.asarray(lengths, np.int64)[:n] - 1) // self.block_size
            first = np.maximum(last - (w.ring - 1), 0)
            logical = first[:, None] + np.arange(w.ring)[None, :]
            ring_src = np.zeros((rows, w.ring), np.int32)
            ring_src[:n] = np.minimum(logical, nblk - 1)
            ring_dst = np.zeros((rows, w.ring), np.int32)
            ring_dst[:n] = np.where(logical <= last[:, None],
                                    w.tables[slots[:, None], logical % w.ring],
                                    0)
            out["scatter_ring_src"] = ring_src
            out["scatter_ring_dst"] = ring_dst.reshape(-1)
        if self.state_arrays:
            out["scatter_slots"] = np.full((rows,), self.slots, np.int32)
            out["scatter_slots"][:n] = slots
        return out

    def scatter_bytes(self, rows, bucket_len):
        """Device bytes :func:`prefill_scatter` writes into the pool for
        a batch of ``rows`` rows of ``bucket_len`` positions: every
        row's blocks in each cache layer (a window layer's ring of them)
        and its slot of the state arrays, padding rows' included (they
        land on the trash block)."""
        rows, nblk = int(rows), self.blocks_for_tokens(bucket_len)
        n = rows * nblk * self.block_bytes()
        if self.window:
            n += rows * self.window.ring * self.block_bytes(
                self.window.layers)
        return n + rows * self.state_bytes_per_slot()

    def scatter_prefill(self, slot_ids, row_caches, bucket_len,
                        lengths=None):
        """Move a prefill's keys and values into the pool: rows
        ``slot_ids`` of the tables receive the first ``bucket_len``
        positions of ``row_caches[cache_{k,v}_i][:len(slot_ids)]``
        (shape ``[bb, H, L, D]``, ``L`` the bucket's length, as every
        prefill program hands them back, in the pool's dtype or in
        float32; a longer ``L`` is sliced, a shorter one zero-padded;
        with several passes an array ``[U, bb, H, L, D]``,
        every cache layer of weight layer ``i``), reshaped into blocks
        and scattered through the block table in ONE donated jitted
        call. Table entries past a row's allocation point at the trash
        block, so bucket padding lands there. A state group's arrays
        (``row_caches[cache_s<tag>_<m>]``, ``[bb, ...]``) go to rows
        ``slot_ids`` of the slot bank in the same call.
        A window group's layers keep only what a row's ring
        holds (:meth:`scatter_indices`). Quantizes on the way in for an
        int8 pool. On ANY failure the donated pool arrays must be
        presumed lost — callers reset the pool. The serving engine's
        admission runs the same body inside its prefill's executable
        (``GPTGenerator``'s ``<prefill kind>+<pick kind>``)."""
        import jax.numpy as jnp

        idx = self.scatter_indices(slot_ids, bucket_len, lengths)
        try:
            self._arrays = self._scatter()(
                self.arrays(), dict(row_caches),
                *(None if idx.get(k) is None else jnp.asarray(idx[k])
                  for k in SCATTER_FEEDS))
        except Exception:
            self._arrays = None
            raise

    # -- cross-replica block migration ------------------------------------
    # A finished prefill's KV state is a well-defined unit: the slot's
    # allocated blocks (in table order) plus the geometry needed to
    # validate them on the far side. export_slot/import_slot are the two
    # halves of the disaggregated prefill/decode split: a compute-bound
    # prefill replica serializes the finished slot out of its pool and a
    # bandwidth-bound decode replica streams it into its own. Payloads
    # stay inside the typed wire universe (bf16 travels as its uint16
    # bit pattern — numpy's bfloat16 is a void-kind dtype the wire
    # refuses; the bitcast round-trips exactly).

    def export_slot(self, slot):
        """Serialize ``slot``'s allocated blocks into a wire-safe dict:
        geometry fields + per-layer ``k_i``/``v_i`` arrays of shape
        ``[nblocks, H, block_size, D]`` (plus ``ks_i``/``vs_i`` float32
        scales for an int8 pool). Raises ``ValueError`` when the slot
        holds nothing. Single-driver like alloc/free — the decode loop
        is the only caller."""
        maybe_fail("serving.kv_export")
        self._no_window("block migration")
        slot = int(slot)
        with self._lock:
            n = int(self._slot_nblocks.get(slot, 0))
            tokens = int(self._slot_tokens.get(slot, 0))
            ids = self.tables[slot, :n].copy()
        if n == 0:
            raise ValueError(
                f"KV pool {self.name!r} slot {slot} holds no blocks — "
                f"nothing to export")
        payload = {
            "fmt": KV_WIRE_FMT, "pool_dtype": self.dtype,
            "block_size": self.block_size, "num_layers": self.num_layers,
            "num_heads": self.num_heads, "d_head": self.d_head,
            "tokens": tokens, "nblocks": n,
        }
        for i in range(self.num_layers):
            for kind in ("k", "v"):
                a = self.logical(f"cache_p{kind}_{i}", ids)
                if self.dtype == "bf16":
                    a = a.view(np.uint16)
                payload[f"{kind}_{i}"] = a
                if self.quantized:
                    payload[f"{kind}s_{i}"] = self.logical(
                        f"cache_p{kind}s_{i}", ids)
        _EXPORTED.inc(n, labels=(self.name,))
        return payload

    def _no_window(self, what):
        if self.state_layers:
            raise BadRequestError(
                f"KV pool {self.name!r} has a state group of layers: "
                f"{what} is built for keys and values in blocks only")
        if self.window is not None:
            raise BadRequestError(
                f"KV pool {self.name!r} has a window group of layers: "
                f"{what} is built for full-attention layers only")
        if self.passes > 1:
            raise BadRequestError(
                f"KV pool {self.name!r} keeps {self.passes} cache layers "
                f"a weight layer: {what} is built for one")

    @staticmethod
    def payload_bytes(payload):
        """Total array bytes a migration payload carries (the wire-cost
        number the router's fleet_kv_migrated_bytes_total counts)."""
        return int(sum(a.nbytes for a in payload.values()
                       if isinstance(a, np.ndarray)))

    def import_slot(self, slot, payload):
        """Deserialize a migrated payload into ``slot``: validates the
        geometry against this pool (mismatch -> typed
        :class:`~.batching.BadRequestError` — retrying cannot help),
        allocates the blocks (typed :class:`KVPoolExhaustedError`
        backpressure with nothing changed), then scatters the arrays
        through the fresh table entries in one donated jitted call. On a
        scatter failure the blocks are returned and the device arrays
        presumed lost (the caller's bank-lost path applies)."""
        maybe_fail("serving.kv_import")
        self._no_window("block migration")
        slot = int(slot)
        geom = self._validate_payload(payload)
        tokens, n = geom["tokens"], geom["nblocks"]
        self.alloc(slot, tokens)        # typed exhaustion, nothing held
        # the scatter's operand shapes are [nblocks, ...]: pad the
        # block count up to a power of two (the prefill bucketing
        # policy) so the jitted import compiles per BUCKET, not per
        # distinct prompt length — padded rows scatter into the trash
        # block, which nothing ever reads
        n_pad = next_bucket(n)
        with self._lock:
            ids = np.zeros(n_pad, np.int32)        # trash-block padding
            ids[:n] = self.tables[slot, :n]
        import jax.numpy as jnp
        from ..kernels.paged_attention import scales_to_stored, to_stored
        vals = {}
        try:
            pool_np = _np_pool_dtype(self.dtype)

            def padded(a):
                if n_pad == n:
                    return a
                return np.concatenate(
                    [a, np.zeros((n_pad - n,) + a.shape[1:], a.dtype)])

            for i in range(self.num_layers):
                for kind in ("k", "v"):
                    a = np.ascontiguousarray(payload[f"{kind}_{i}"])
                    if self.dtype == "bf16":
                        a = a.view(pool_np)
                    vals[f"cache_p{kind}_{i}"] = jnp.asarray(
                        to_stored(padded(a)))
                    if self.quantized:
                        vals[f"cache_p{kind}s_{i}"] = jnp.asarray(
                            scales_to_stored(padded(np.ascontiguousarray(
                                payload[f"{kind}s_{i}"],
                                dtype=np.float32)), self.d_head))
            if self._import_fn is None:
                def imp(pool, new_vals, idx):
                    out = dict(pool)
                    for name, v in new_vals.items():
                        out[name] = out[name].at[idx].set(v)
                    return out
                self._import_fn = _PoolJit(imp)
            self._arrays = self._import_fn(self.arrays(), vals,
                                           jnp.asarray(ids, jnp.int32))
        except Exception:
            # the donated pool arrays must be presumed lost; the blocks
            # just allocated go straight back
            self._arrays = None
            self.free_slot(slot)
            raise
        _IMPORTED.inc(n, labels=(self.name,))
        return n

    def _validate_payload(self, payload):
        """Geometry/shape checks for a migration payload; returns
        ``{"tokens", "nblocks"}``. Every refusal is a
        :class:`~.batching.BadRequestError` (terminal, not retryable)."""
        if not isinstance(payload, dict) \
                or payload.get("fmt") != KV_WIRE_FMT:
            raise BadRequestError(
                f"KV payload format {payload.get('fmt') if isinstance(payload, dict) else type(payload).__name__!r} "
                f"is not {KV_WIRE_FMT!r}")
        for field, mine in (("pool_dtype", self.dtype),
                            ("block_size", self.block_size),
                            ("num_layers", self.num_layers),
                            ("num_heads", self.num_heads),
                            ("d_head", self.d_head)):
            got = payload.get(field)
            if got != mine:
                raise BadRequestError(
                    f"KV payload {field}={got!r} does not match the "
                    f"receiving pool's {mine!r} — prefill and decode "
                    f"replicas must share the cache geometry")
        try:
            tokens = int(payload["tokens"])
            n = int(payload["nblocks"])
        except (KeyError, TypeError, ValueError):
            raise BadRequestError("KV payload lacks integer "
                                  "tokens/nblocks fields")
        if tokens < 1 or n != self.blocks_for_tokens(tokens):
            raise BadRequestError(
                f"KV payload claims {tokens} tokens in {n} blocks; "
                f"{self.blocks_for_tokens(tokens)} blocks expected at "
                f"block_size={self.block_size}")
        if tokens > self.max_seq_len:
            raise BadRequestError(
                f"KV payload holds {tokens} tokens but the receiving "
                f"pool's rows cap at max_seq_len={self.max_seq_len}")
        shape = (n, self.num_heads, self.block_size, self.d_head)
        for i in range(self.num_layers):
            for kind in ("k", "v"):
                a = payload.get(f"{kind}_{i}")
                if not isinstance(a, np.ndarray) \
                        or tuple(a.shape) != shape:
                    raise BadRequestError(
                        f"KV payload array {kind}_{i} is "
                        f"{getattr(a, 'shape', None)}, expected {shape}")
                if self.quantized:
                    s = payload.get(f"{kind}s_{i}")
                    if not isinstance(s, np.ndarray) \
                            or tuple(s.shape) != shape[:3]:
                        raise BadRequestError(
                            f"int8 KV payload scale array {kind}s_{i} "
                            f"is {getattr(s, 'shape', None)}, expected "
                            f"{shape[:3]}")
        return {"tokens": tokens, "nblocks": n}

    # -- reporting --------------------------------------------------------
    def _update_gauges_locked(self):
        lab = (self.name,)
        cached = self._cached_only_locked()
        in_use = sum(self._in_use_by_group_locked().values())
        _BLOCKS_IN_USE.set(in_use, labels=lab)
        _CAPACITY.set(self.capacity_blocks, labels=lab)
        # occupancy counts SLOT load only: blocks held just by the
        # prefix cache are evictable working capital, and the router's
        # load score must not shun the replica that cached the most
        _OCCUPANCY.set(in_use / self.capacity_blocks
                       if self.capacity_blocks else 0.0, labels=lab)
        _SAVED.set(self.slots * self.dense_slot_bytes()
                   - self._bytes_of(self._in_use_by_group_locked(),
                                    cached), labels=lab)
        _PREFIX_ENTRIES.set(len(self._prefix), labels=lab)
        _PREFIX_BLOCKS.set(cached, labels=lab)

    def _update_gauges(self):
        with self._lock:
            self._update_gauges_locked()

    def stats(self):
        """Occupancy / fragmentation snapshot (plain ints/floats — wire
        safe, merged into ``server.stats()`` under ``kvpool_*``)."""
        with self._lock:
            cached = self._cached_only_locked()
            by_group = self._in_use_by_group_locked()
            in_use = sum(by_group.values())
            recycled = self.window.recycled if self.window else 0
            tokens = sum(self._slot_tokens.values())
            slots_held = sum(1 for n in self._slot_nblocks.values()
                             if n > 0)
            prefix_entries = len(self._prefix)
        cap_tokens = by_group["full"] * self.block_size
        return {
            "blocks": self.num_blocks,
            "block_size": self.block_size,
            "dtype": self.dtype,
            "capacity_blocks": self.capacity_blocks,
            "blocks_in_use": in_use,
            "blocks_in_use_full": by_group["full"],
            "blocks_in_use_window": by_group.get("window", 0),
            "window_blocks_recycled": recycled,
            "blocks_free": self.capacity_blocks - in_use,
            "occupancy": round(in_use / self.capacity_blocks, 4)
            if self.capacity_blocks else 0.0,
            # internal fragmentation: allocated capacity the held
            # tokens don't fill (last-block slack per slot)
            "fragmentation": round(1.0 - tokens / cap_tokens, 4)
            if cap_tokens else 0.0,
            "tokens_held": tokens,
            "slots_holding_blocks": slots_held,
            # prefix cache: entries indexed and blocks held ONLY by the
            # cache — evictable on demand, so the router's load scoring
            # discounts them (satellite: cold prefixes must not read as
            # load)
            "prefix_entries": prefix_entries,
            "evictable_blocks": cached,
            "bytes_in_use": self._bytes_of(by_group, cached),
            "bytes_capacity": self._bytes_of(
                {"full": self.num_blocks - 1,
                 "window": self.window.capacity if self.window else 0}),
            "saved_vs_dense_bytes": self.slots * self.dense_slot_bytes()
            - self._bytes_of(by_group, cached),
            "relayouts": self.relayouts(),
            # a state group's per-slot arrays, and every array the pool
            # holds on the device: blocks (trash included) and the bank
            "state_layers": self.state_layers,
            "state_bytes_per_slot": self.state_bytes_per_slot(),
            "pool_bytes": self._bytes_of(
                {"full": self.num_blocks,
                 "window": self.window.num_blocks if self.window else 0})
            + self.slots * self.state_bytes_per_slot(),
        }

    def relayouts(self):
        """``{"scatter": n, "copy_blocks": n, "import": n}``: pool-sized
        copies in the optimised HLO of this pool's own executables (the
        worst of each kind's compiles; a kind that never ran is left
        out). 0 says the stored layout served the writer as it lies."""
        fns = (("scatter", self._scatter_fn), ("copy_blocks", self._copy_fn),
               ("import", self._import_fn))
        return {k: fn.relayouts for k, fn in fns if fn is not None}

    def _bytes_of(self, by_group, cached=0):
        """Device bytes of ``by_group``'s blocks (and the prefix
        cache's, which are the full group's)."""
        n = (by_group["full"] + cached) * self.block_bytes()
        if self.window:
            n += by_group.get("window", 0) * self.block_bytes(
                self.window.layers)
        return n


def _ceil_div(a, b):
    return -(-int(a) // int(b))
