"""Paged decode-attention as a Pallas TPU kernel, with a pure-JAX oracle.

The decode half of the flash-attention story (kernels/flash_attention.py
fused prefill): one query token per row attends over that row's KV cache
stored as BLOCKS of a shared pool (vLLM/PagedAttention, Kwon et al.
2023) instead of a dense per-slot ``[B, H, max_len, D]`` bank. The
block-table gather IS the kernel's walk: the pool stays in HBM and each
row's live blocks come to VMEM by DMA as the row's table names them, so
the gather and the attention read are one fused pass and the
``[B, max_len]`` dense cache is never materialized (decode is
bandwidth-bound: bytes streamed per token IS the token rate).

Two implementations, same math:

- ``pallas``: grid ``(B,)``, a grid step a row, the block table and
  ``pos`` scalar-prefetched, the pool arrays left in HBM. The kernel
  reads the row's trip count from ``pos`` (:func:`row_steps`: a step
  for every G of the blocks up to ``pos[b] // block_size``, from the
  first block a window still reaches) and runs a loop of that many
  steps. A step waits for G table entries of the row as G whole
  ``[H, block_size, D]`` tiles of K and of V (256 KB and more at
  serving shapes) in one half of a double buffer, starts the next
  step's copies into the other half, and folds all H heads of its
  tiles at once into the row's online-softmax state (m, l, acc: the
  loop's carry). A row's last step starts the first copies of the next
  row that holds blocks, so the pipe does not drain between rows. G
  follows from H, block_size, D, the pool's dtype and the table's width
  (:func:`blocks_per_step`). What is not live is not visited: a row
  whose first live table entry is block 0 (the pool's trash block: a
  free slot) costs an empty grid step and reads zeros, and a table's
  tail past ``pos`` costs nothing, so a call's time follows the blocks
  it reads. int8 blocks are dequantized against their per-slot scales
  as they leave VMEM. ``interpret`` runs the SAME kernel through the
  Pallas interpreter on CPU. What it replaced (PERF.md, PR 27 to 35): a
  grid of ``(B, table width / G)`` steps of 2 + 2G BlockSpec operands,
  each 0.04 to 0.09 us a step whether it moved or not.
- ``xla``: a ``jnp.take``-based gather + masked softmax composite — the
  CPU-CI path and the parity oracle the kernel is tested against.

Quantized cache (KVQuant-style bandwidth multiplier): blocks may hold
``int8`` values with a float32 scale per (block, head, slot) stored in a
parallel ``[N, H, block_size]`` array — at bandwidth-bound decode,
quarter-size cache bytes are ~4x tokens/s headroom. ``quantize_kv``/
``dequantize_kv`` are the one symmetric-scale codec every writer/reader
shares (absmax / 127 per head-token, zero-scale guarded).

Layout: q ``[B, H, 1, D]`` (single decode step per row), block tables
``[B, blocks_per_row]`` int32 (entries past a row's allocation point at
the reserved trash block 0 — masked by ``pos``; a row whose first live
entry is block 0 holds nothing: the kernel gives it zeros, the
composite whatever the trash block holds, and nobody reads either), pos
``[B]`` int32 (index of the query's own slot: key slot j is visible iff
``j <= pos[b]``).

The pool's STORED shape (PERF.md 7.5, PR 29). Logically a pool array is
``[num_blocks, H, block_size, D]``; what the runtime holds, the append
writes, the prefill scatter writes and this kernel reads is the same
bytes as ``[num_blocks, H * block_size // f, f * D]`` with
``f = 128 // D`` key slots of a head side by side in one 128-lane row
(:func:`pool_packing`; f = 1 at D >= 128, where the fold is the plain
``[H * block_size, D]``). The minor dimension then fills the lanes, the
default layout is unpadded row-major, and no executable relays a pool
array to suit one of its ops. Rows stay head-major (row
``h * (block_size // f) + t // f``, lanes ``(t % f) * D ...`` hold slot
t of head h), so a tp split of the heads is a contiguous row range. int8
scales are stored ``[num_blocks, f, H * block_size // f]``: entry
``[p, h * (block_size // f) + r]`` scales slot ``f * r + p`` of head h,
which is the order of a step's score columns. :func:`to_stored`,
:func:`to_logical`, :func:`scales_to_stored` and
:func:`scales_to_logical` are the one pair of index helpers the pool,
the writer op and both readers share; the logical shape stays the
contract of the oracle, of the migration payload and of the tests.
:func:`paged_attention` takes either: a 4-D pool is logical, a 3-D one
stored.

``paged_kv_append`` (:func:`paged_kv_append`) is the pool's one-token
writer on a TPU: a Pallas call aliased onto the pool, grid ``(B,)``,
that fetches the one block a row writes, selects the new vector into
its slot under an iota mask and stores the tile back, so the append is
in place in the stored layout (XLA's scatter relaid the whole array for
an index on dimensions 0 and 2).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _dispatch

_NEG_INF = -1e30
_LANES = 128
_QMAX = 127.0        # symmetric int8 range


# ------------------------------------------------------------ quant codec

def quantize_kv(kv):
    """Symmetric per-head-token int8 quantization of ``kv`` [..., D]:
    returns (int8 values, float32 scale [...]) with
    ``scale = absmax(D) / 127`` (0 -> 1.0 so an all-zero vector round-
    trips exactly). The ONE codec shared by the pool writer ops, the
    prefill scatter and the attention readers."""
    kv = kv.astype(jnp.float32)
    scale = jnp.max(jnp.abs(kv), axis=-1) / _QMAX
    scale = jnp.where(scale == 0.0, 1.0, scale)
    q = jnp.round(kv / scale[..., None])
    q = jnp.clip(q, -_QMAX, _QMAX).astype(jnp.int8)
    return q, scale


def dequantize_kv(q, scale):
    """Inverse of :func:`quantize_kv`: int8 values [..., D] * scale
    [...] -> float32."""
    return q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)


# ------------------------------------------------------- the stored shape

def pool_packing(d_head, block_size):
    """f, the key slots of a head that share one 128-lane row of the
    stored pool: ``128 // D`` where D divides the lanes and f the block,
    else 1 (a row a slot, today's ``[H * block_size, D]`` fold)."""
    f = _LANES // d_head if d_head < _LANES and _LANES % d_head == 0 else 1
    return f if block_size % f == 0 else 1


def stored_shape(num_blocks, heads, block_size, d_head):
    """What a pool array of logical ``[num_blocks, heads, block_size,
    d_head]`` is stored as."""
    f = pool_packing(d_head, block_size)
    return (num_blocks, heads * block_size // f, f * d_head)


def to_stored(blocks):
    """Logical ``[..., H, bs, D]`` blocks as stored ``[..., H * bs // f,
    f * D]``: the same bytes in the same order (numpy or jax)."""
    *lead, H, bs, D = blocks.shape
    return blocks.reshape(*lead, *stored_shape(0, H, bs, D)[1:])


def to_logical(stored, heads, d_head):
    """Stored ``[..., R, C]`` blocks as logical ``[..., heads, bs,
    d_head]`` (inverse of :func:`to_stored`)."""
    *lead, R, C = stored.shape
    return stored.reshape(*lead, heads, R * C // (heads * d_head), d_head)


def scales_to_stored(scales, d_head):
    """Logical ``[..., H, bs]`` int8 scales as stored ``[..., f, H * bs
    // f]``: slot parity major, then the pool's row order."""
    *lead, H, bs = scales.shape
    f, n = pool_packing(d_head, bs), len(lead)
    x = scales.reshape(*lead, H, bs // f, f)
    return x.transpose(*range(n), n + 2, n, n + 1).reshape(
        *lead, f, H * bs // f)


def scales_to_logical(stored, heads):
    """Inverse of :func:`scales_to_stored`: ``[..., f, R]`` ->
    ``[..., heads, bs]``."""
    *lead, f, R = stored.shape
    n = len(lead)
    x = stored.reshape(*lead, f, heads, R // heads)
    return x.transpose(*range(n), n + 1, n + 2, n).reshape(
        *lead, heads, R // heads * f)


# -------------------------------------------------------------- reference

def window_blocks(window, bs):
    """Blocks a window layer's row can still read: the one that holds
    the query and ``ceil(window / bs)`` behind it."""
    return -(-int(window) // int(bs)) + 1


def _first_block(pos, window, bs):
    """The first logical block a row at ``pos`` still reads (plain
    integers, numpy and jax arrays and a kernel's scalars alike)."""
    first = (pos - (window - 1)) // bs
    return first * (first > 0)


def _xla_paged_attention(q, k_pool, v_pool, tables, pos, k_scale, v_scale,
                         scale, window=None):
    """Gather-then-attend composite: per-row ``jnp.take`` of the row's
    blocks, per-row position mask (key slot j visible to query i iff
    j <= pos[b] + i), fp32 softmax.
    Runs anywhere (CPU CI) and is the kernel's parity oracle. Grouped
    queries fold against the KV heads they share; with a ``window`` the
    table is a ring (logical block ``b`` at column ``b % width``) and a
    key is visible iff it is at most ``window - 1`` behind its query."""
    B, H, S, D = q.shape
    Hkv, bs = k_pool.shape[1], k_pool.shape[2]
    nblk = tables.shape[1]
    L = nblk * bs
    rep = H // Hkv
    pos = pos.astype(jnp.int32)

    def gather(pool, sc):
        # [B, nblk, Hkv, bs, D] -> [B, Hkv, L, D], dequantized
        g = jnp.take(pool, tables, axis=0)
        if sc is not None:
            gs = jnp.take(sc, tables, axis=0)        # [B, nblk, Hkv, bs]
            g = dequantize_kv(g, gs)
        g = g.astype(jnp.float32)
        return g.transpose(0, 2, 1, 3, 4).reshape(B, Hkv, L, D)

    k = gather(k_pool, k_scale)
    v = gather(v_pool, v_scale)
    qg = q.astype(jnp.float32).reshape(B, Hkv, rep, S, D)
    scores = jnp.einsum("bgrsd,bgld->bgrsl", qg, k) * scale
    qry_pos = pos[:, None, None] \
        + jnp.arange(S, dtype=jnp.int32)[None, :, None]          # [B,S,1]
    if window is None:
        key_pos = jnp.arange(L, dtype=jnp.int32)[None, None, :]  # [1,1,L]
        mask = key_pos <= qry_pos                                # [B,S,L]
    else:
        # column c of the ring holds the one logical block of
        # [lo, lo + nblk) that is c modulo nblk
        lo = _first_block(pos, window, bs)                       # [B]
        col = jnp.arange(nblk, dtype=jnp.int32)[None, :]
        blk = lo[:, None] + (col - lo[:, None]) % nblk           # [B,nblk]
        key_pos = (blk[:, :, None] * bs + jnp.arange(
            bs, dtype=jnp.int32)[None, None, :]).reshape(B, 1, L)
        mask = (key_pos <= qry_pos) & (key_pos > qry_pos - window)
    scores = jnp.where(mask[:, None, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgrsl,bgld->bgrsd", probs, v)
    return out.reshape(B, H, S, D).astype(q.dtype)


# ----------------------------------------------------------------- kernel

# VMEM the K and V tiles of a grid step may hold, double-buffered and
# padded to their dtype's tile, and the widest row of scores
# [H, G * H * bs] a step's fold keeps in registers
_VMEM_BUDGET = 4 * 1024 * 1024
_SCORE_LANES = 2048
_NO_SLOT = 2 ** 30     # _slot_of's entry for another head's key: never visible


def _tile_bytes(H, bs, D, dtype):
    """VMEM bytes of one block's stored ``[H * bs // f, f * D]`` tile:
    its two dims pad to the dtype's (sublanes, 128 lanes) tile (8 rows
    of fp32, 16 of bf16, 32 of int8)."""
    _, R, C = stored_shape(0, H, bs, D)
    itemsize = jnp.dtype(dtype).itemsize
    sublanes = 8 * (4 // itemsize)
    return pl.cdiv(R, sublanes) * sublanes * pl.cdiv(C, _LANES) \
        * _LANES * itemsize


def blocks_per_step(H, bs, D, dtype, nblk, window=None):
    """G, the consecutive table entries of a row that one step of the
    kernel's walk fetches and folds: the largest power of two no wider
    than the table (than the :func:`window_blocks` a ``window`` row can
    read of its ring) whose K and V tiles, double-buffered, sit in
    ``_VMEM_BUDGET`` and whose scores fit ``_SCORE_LANES`` (1 where a
    single block already exceeds either)."""
    tile = _tile_bytes(H, bs, D, dtype)
    if window is not None:
        nblk = min(nblk, window_blocks(window, bs))
    g = 1
    while (2 * g <= nblk and 8 * g * tile <= _VMEM_BUDGET
           and 2 * g * H * bs <= _SCORE_LANES):
        g *= 2
    return g


def row_steps(pos, bs, G, nblk, window=None):
    """``(first, live, steps)`` of a row at ``pos`` in one
    ``paged_attention_decode`` call: the first block it reads (0, or the
    first a ``window`` still reaches), the blocks it reads (up to
    ``pos // bs``, of a table of ``nblk`` columns) and the steps its
    walk takes, one for every G of them. The kernel's own trip count of
    a row that holds blocks, written for plain integers, numpy arrays
    and the kernel's scalars alike; the ``engine/step`` span sums it
    over a step's rows as ``kernel_steps``."""
    first = 0 if window is None else _first_block(pos, window, bs)
    live = pos // bs + 1 - first
    live = live - (live - nblk) * (live > nblk)        # min(live, nblk)
    return first, live, (live + G - 1) // G


def _slot_of(H, bs, G, rep=1, f=1, rows=None):
    """``[f * rows, G * H * bs // f]`` int32 (``rows`` = the query heads
    ``H * rep``, or more where the caller pads them): for slot parity p
    and query head h (row ``p * rows + h``) and a step's key column
    (g, h', r) of the stored tiles, the key's slot in the step
    ``g * bs + f * r + p`` where ``h'`` is the KV head that h reads
    (``h // rep``) and ``_NO_SLOT`` elsewhere. One compare against
    ``pos`` less the step's first slot masks the other heads' keys and
    the slots past the row's position together. With f = 1 a row is a
    head and a column a key."""
    rows = rows or H * rep
    g, h2, r = np.meshgrid(np.arange(G), np.arange(H), np.arange(bs // f),
                           indexing="ij")
    head = np.arange(rows) // rep        # a padded row reads no head
    own = h2.reshape(1, -1) == np.where(np.arange(rows) < H * rep, head,
                                        -1)[:, None]
    return np.concatenate(
        [np.where(own, (g * bs + f * r + p).reshape(1, -1), _NO_SLOT)
         for p in range(f)], axis=0).astype(np.int32)


def _paged_kernel(tables_ref, pos_ref, q_ref, slot_of_ref, *refs, scale, bs,
                  G, f, D, quant, window, count_folds):
    """Grid step b is row b: the kernel walks the row's live blocks
    itself. The pools (``refs``: K, V and with ``quant`` their scale
    arrays) stay in HBM; step j of the row waits for table entries
    ``[j * G, (j + 1) * G)`` counted from the row's first live block, G
    stored ``[H * bs // f, f * D]`` tiles of K and of V (and G + G scale
    tiles ``[f, H * bs // f]``) in one half of the ``[2, G, ...]`` VMEM
    buffers, starts the next step's copies into the other half and
    folds all H heads at once into the row's online-softmax state, the
    loop's carry. The row's last step starts the first copies of the
    next row that holds blocks, so the pipe stays full from row to row;
    ``flight_ref`` (SMEM: the half the next step reads, and whether its
    copies are under way) hands that over. Only live blocks are copied:
    the tail of a row's last step keeps what the buffer held (V and its
    scales are cleared once a call, so a masked probability of 0 never
    meets a NaN). A row whose first live table entry is block 0, the
    pool's trash block, holds nothing and costs a grid step of nothing.

    All heads fold in two matrix products. The query comes laid out f
    times (``[f * Hq, f * D]``: row ``p * Hq + h`` holds q[h] in the
    lanes of slot parity p and zeros elsewhere), so its product with the
    step's key rows ``[G * H * bs // f, f * D]`` scores every head
    against every head's keys of that parity; ``slot_of`` keeps a head's
    own and drops the slots past ``pos[b]``, and the probabilities,
    exactly zero off a head's own columns, times the value rows are the
    weighted sums, of which row ``p * Hq + h`` is read in parity p's
    lanes alone. Each (parity, head) row keeps an online softmax of its
    own and the f parities of a head merge when the row ends. int8
    tiles stay unscaled: a key's scale multiplies its score and a
    value's its probability, column by column."""
    n = 4 if quant else 2
    hbm, out_ref, refs = refs[:n], refs[n], refs[n + 1:]
    folds_ref = None
    if count_folds:
        folds_ref, refs = refs[0], refs[1:]
    bufs, (sems, flight_ref) = refs[:n], refs[n:]
    b, B = pl.program_id(0), pl.num_programs(0)
    nblk = tables_ref.shape[1]
    Hq = q_ref.shape[1] // f

    def span(r):
        """Row r's first live block, its live blocks (0 where it holds
        none) and its steps."""
        first, live, steps = row_steps(pos_ref[r], bs, G, nblk, window)
        held = tables_ref[r, first % nblk] != 0
        return first, jnp.where(held, live, 0), jnp.where(held, steps, 0)

    def copies(act, r, first, live, j, half):
        """``act`` ("start" or "wait") the copy of every live tile of
        row r's step j into (out of) ``half``."""
        for i in range(G):
            @pl.when(j * G + i < live)
            def _():
                col = first + j * G + i
                block = tables_ref[r, col if window is None else col % nblk]
                for n, (src, dst) in enumerate(zip(hbm, bufs)):
                    getattr(pltpu.make_async_copy(
                        src.at[block], dst.at[half, i],
                        sems.at[half, n]), act)()

    first, live, steps = span(b)

    @pl.when(b == 0)
    def _reset():
        flight_ref[0] = 0
        flight_ref[1] = 0
        for buf in bufs[1::2]:          # V, and the values' scales
            buf[...] = jnp.zeros_like(buf)

    @pl.when(steps == 0)
    def _free_slot():
        out_ref[0] = jnp.zeros_like(out_ref[0])
        if folds_ref is not None:
            folds_ref[b] = 0

    @pl.when(steps > 0)
    def _row():
        half0 = flight_ref[0]

        @pl.when(flight_ref[1] == 0)
        def _first_of_the_call():
            copies("start", b, first, live, 0, half0)

        # the next row that holds blocks (B: none)
        nxt = jax.lax.while_loop(
            lambda r: (r < B) & (span(jnp.minimum(r, B - 1))[2] == 0),
            lambda r: r + 1, b + 1)
        nxt_first, nxt_live, _ = span(jnp.minimum(nxt, B - 1))
        # the query's position counted from the row's first live slot
        p = pos_ref[b] - first * bs
        qw = q_ref[0].astype(jnp.float32)

        def rows_of(buf, half):
            return buf[half].astype(jnp.float32).reshape(
                G * buf.shape[2], buf.shape[3])           # [G*H*bs/f, f*D]

        def column_scales(buf, half):
            """[f * Hq, G * H * bs / f]: the scale of the key (or value)
            that each score column holds for each parity's rows."""
            tiles = buf[half]              # [G, f, R padded to the lanes]
            sc = jnp.concatenate([tiles[i, :, :bufs[0].shape[2]]
                                  for i in range(G)], axis=1)
            return jnp.concatenate(
                [jnp.broadcast_to(sc[i:i + 1], (Hq, sc.shape[1]))
                 for i in range(f)], axis=0)

        def fold(j, carry):
            m_prev, l_prev, acc, folds = carry
            half = (half0 + j) % 2

            @pl.when(j + 1 < steps)
            def _next_step():
                copies("start", b, first, live, j + 1, 1 - half)

            @pl.when((j + 1 == steps) & (nxt < B))
            def _next_row():
                copies("start", nxt, nxt_first, nxt_live, 0, 1 - half)

            copies("wait", b, first, live, j, half)
            s = jax.lax.dot_general(
                qw, rows_of(bufs[0], half), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if quant:                                   # [f*Hq, G*H*bs/f]
                s = s * column_scales(bufs[2], half)
            rel = p - j * (G * bs)          # the query's slot in this step
            keep = slot_of_ref[...] <= rel
            if window is not None:
                keep = keep & (slot_of_ref[...] > rel - window)
            s = jnp.where(keep, s, _NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            # a parity that has no visible key yet keeps m at _NEG_INF
            # and would read exp(0) off its masked columns
            pr = jnp.where(keep, jnp.exp(s - m_new), 0.0) if f > 1 \
                else jnp.exp(s - m_new)
            l_new = l_prev * corr + jnp.sum(pr, axis=-1, keepdims=True)
            if quant:
                pr = pr * column_scales(bufs[3], half)
            acc = acc * corr + jax.lax.dot_general(
                pr, rows_of(bufs[1], half), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)           # [f*Hq, f*D]
            return m_new, l_new, acc, folds + 1

        m, l, acc, folds = jax.lax.fori_loop(0, steps, fold, (
            jnp.full((f * Hq, 1), _NEG_INF, jnp.float32),
            jnp.zeros((f * Hq, 1), jnp.float32),
            jnp.zeros((f * Hq, f * D), jnp.float32), jnp.int32(0)))
        flight_ref[0] = (half0 + steps) % 2
        flight_ref[1] = (nxt < B).astype(jnp.int32)
        if folds_ref is not None:
            folds_ref[b] = folds
        if f > 1:
            # merge a head's f parities: each weighs exp(m_p - max m);
            # its sums stand in its own lanes, the caller adds the lane
            # groups
            ms = [m[i * Hq:(i + 1) * Hq] for i in range(f)]
            m_all = functools.reduce(jnp.maximum, ms)
            lane_group = jax.lax.broadcasted_iota(
                jnp.int32, (Hq, f * D), 1) // D
            l_all = jnp.zeros((Hq, 1), jnp.float32)
            acc_all = jnp.zeros((Hq, f * D), jnp.float32)
            for i in range(f):
                w = jnp.exp(ms[i] - m_all)
                l_all = l_all + l[i * Hq:(i + 1) * Hq] * w
                acc_all = acc_all + jnp.where(
                    lane_group == i, acc[i * Hq:(i + 1) * Hq] * w, 0.0)
            l, acc = l_all, acc_all
        out_ref[0] = acc / jnp.where(l == 0.0, 1.0, l)


# jitted, so that a program's layers share one trace and one Mosaic
# lowering of the call (24 reads and 48 appends in gpt2-medium's decode
# step: 5.7 s of lowering without it, 1.2 with; XLA inlines the calls)
@functools.partial(jax.jit, static_argnames=("scale", "interpret", "rep",
                                             "window", "count_folds"))
def _pallas_paged_attention(q, k_pool, v_pool, tables, pos, k_scale,
                            v_scale, scale, interpret, rep, window=None,
                            count_folds=False):
    """The kernel over STORED pools ``[N, H * bs // f, f * D]`` (scales
    ``[N, f, H * bs // f]``); ``rep`` query heads share a KV head. With
    ``count_folds`` (the tests' alone) it also returns the folds each
    row made, ``[B]`` int32."""
    B, Hq, S, D = q.shape
    if S != 1:
        raise ValueError(
            f"paged_attention kernel decodes ONE query per row (S=1), "
            f"got S={S}; prefill goes through flash_attention")
    H = Hq // rep
    R, C = k_pool.shape[1:]
    f = C // D
    bs = R * f // H
    tile = _tile_bytes(H, bs, D, k_pool.dtype)
    if 4 * tile > _VMEM_BUDGET:
        raise ValueError(
            f"paged_attention: one [H={H}, block_size={bs}, D={D}] "
            f"{k_pool.dtype.name} block is {tile} bytes of VMEM; K and V "
            f"double-buffered take {4 * tile} of the kernel's "
            f"{_VMEM_BUDGET}: lower kv_block_size")
    quant = k_scale is not None
    G = blocks_per_step(H, bs, D, k_pool.dtype, tables.shape[1], window)
    # f parities of Hp rows each; sublane-aligned where the merge slices
    Hp = Hq if f == 1 else pl.cdiv(Hq, 8) * 8
    slot_of = _slot_of(H, bs, G, rep, f, Hp)
    # q laid out f times: row p * Hp + h holds q[h] in lane group p
    qw = jnp.pad(q.reshape(B, Hq, D), ((0, 0), (0, Hp - Hq), (0, 0)))
    qw = (jnp.eye(f, dtype=q.dtype)[None, :, None, :, None]
          * qw[:, None, :, None, :]).reshape(B, f * Hp, C)

    def row(b, *scalars):
        return (b, 0, 0)

    pools = (k_pool, v_pool)
    if quant:
        # Mosaic copies whole lane tiles out of HBM: a scale tile whose
        # rows are no multiple of 128 wide (12 heads) goes in padded
        pad = ((0, 0), (0, 0), (0, -R % _LANES))
        pools += (jnp.pad(k_scale, pad), jnp.pad(v_scale, pad))
    out_shape = [jax.ShapeDtypeStruct((B, Hp, C), jnp.float32)]
    out_specs = [pl.BlockSpec((1, Hp, C), row)]
    if count_folds:
        out_shape.append(jax.ShapeDtypeStruct((B,), jnp.int32))
        out_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,           # the block table as it is, and pos
        grid=(B,),
        in_specs=[pl.BlockSpec((1, f * Hp, C), row),
                  pl.BlockSpec(slot_of.shape, lambda b, *scalars: (0, 0))]
        + [pl.BlockSpec(memory_space=pltpu.HBM)] * len(pools),
        out_specs=out_specs,
        # both halves of a step's tiles a pool array, a DMA semaphore a
        # (half, array), and what one row hands the next
        scratch_shapes=[pltpu.VMEM((2, G) + pool.shape[1:], pool.dtype)
                        for pool in pools]
        + [pltpu.SemaphoreType.DMA((2, len(pools))),
           pltpu.SMEM((2,), jnp.int32)],
    )
    out, *folds = pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, bs=bs, G=G, f=f, D=D,
                          quant=quant, window=window,
                          count_folds=count_folds),
        name="paged_attention_decode", grid_spec=grid_spec,
        out_shape=out_shape,
        # a row's first copies may be started by the row before it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(tables.astype(jnp.int32), pos.astype(jnp.int32), qw, slot_of, *pools)
    # a head's weighted sum: its f lane groups added
    out = out[:, :Hq].reshape(B, Hq, f, D).sum(axis=2)
    out = out.reshape(B, Hq, 1, D).astype(q.dtype)
    return (out, folds[0]) if count_folds else out


# ---------------------------------------------------------- the one writer

def _append_kernel(ids_ref, offs_ref, *refs, f, D, rows_per_head, quant):
    """Grid step b: the block row b writes arrives as its stored tile,
    the row's new vector (laid over every slot of its head outside) is
    selected into slot ``offs[b]`` and the tile goes back where it came
    from. A bf16 row is half a packed sublane, so nothing is stored
    narrower than the tile; the select runs in 32 bits."""
    del ids_ref                       # the index maps' alone
    if quant:
        new_ref, new_sc_ref, pool_ref, sc_ref, out_ref, out_sc_ref = refs
    else:
        new_ref, pool_ref, out_ref = refs
    off = offs_ref[pl.program_id(0)]
    r, p = off // f, off % f          # the slot's row in its head, its lanes
    shape = out_ref.shape[1:]
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    hit = (row % rows_per_head == r) & (lane // D == p)
    wide = jnp.int32 if out_ref.dtype == jnp.int8 else jnp.float32
    out_ref[0] = jnp.where(hit, new_ref[0].astype(wide),
                           pool_ref[0].astype(wide)).astype(out_ref.dtype)
    if quant:
        shape = out_sc_ref.shape[1:]
        row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        hit = (row == p) & (col % rows_per_head == r)
        out_sc_ref[0] = jnp.where(hit, new_sc_ref[0], sc_ref[0])


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_kv_append(pool, new, block_ids, offs, scale=None, new_scale=None,
                    interpret=False):
    """One token a row into a STORED pool, in place: ``new`` [B, H, D]
    (the pool's dtype; int8 already quantized, with ``new_scale``
    [B, H] and the stored ``scale`` array) lands in slot ``offs[b]`` of
    block ``block_ids[b]``. The caller computes both vectors from its
    table (plain or ring): the call takes no table, so a device trace
    tells it from the decode kernel by its operands as well as by its
    name. Rows own disjoint blocks but for the trash block, where the
    last writer wins. Returns the pool, or ``(pool, scale)``."""
    B, H, D = new.shape
    R, C = pool.shape[1:]
    f, rows_per_head = C // D, R // H
    quant = scale is not None
    # the vector over every slot of its head: [B, H, D] -> [B, R, C]
    wide = jnp.broadcast_to(new[:, :, None, None, :],
                            (B, H, rows_per_head, f, D)).reshape(B, R, C)

    def row(b, ids, offs):
        return (b, 0, 0)

    def block(b, ids, offs):
        return (ids[b], 0, 0)

    tile = pl.BlockSpec((1, R, C), block)
    args, in_specs = [wide.astype(pool.dtype)], [pl.BlockSpec((1, R, C), row)]
    out_shape = [jax.ShapeDtypeStruct(pool.shape, pool.dtype)]
    if quant:
        args.append(jnp.repeat(new_scale.astype(scale.dtype), rows_per_head,
                               axis=1)[:, None, :])              # [B, 1, R]
        in_specs.append(pl.BlockSpec((1, 1, R), row))
        out_shape.append(jax.ShapeDtypeStruct(scale.shape, scale.dtype))
    first = 2 + len(args)             # the pool's place among the inputs
    args.append(pool)
    in_specs.append(tile)
    out_specs = [tile]
    if quant:
        sc_tile = pl.BlockSpec((1,) + scale.shape[1:], block)
        args.append(scale)
        in_specs.append(sc_tile)
        out_specs.append(sc_tile)
    outs = pl.pallas_call(
        functools.partial(_append_kernel, f=f, D=D,
                          rows_per_head=rows_per_head, quant=quant),
        name="paged_kv_append",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B,), in_specs=in_specs,
            out_specs=out_specs),
        out_shape=out_shape,
        input_output_aliases={first + i: i for i in range(len(out_specs))},
        interpret=interpret,
    )(block_ids.astype(jnp.int32), offs.astype(jnp.int32), *args)
    return tuple(outs) if quant else outs[0]


# ----------------------------------------------------------- public entry

def paged_attention(q, k_pool, v_pool, block_tables, pos, k_scale=None,
                    v_scale=None, scale=None, impl=None, mesh=None,
                    window=None, kv_heads=None):
    """Decode attention of one query per row over a block-paged KV pool.

    q ``[B, H, 1, D]``; k_pool/v_pool either logical ``[num_blocks, Hkv,
    block_size, D]`` or STORED ``[num_blocks, Hkv * block_size // f,
    f * D]`` (the module docstring; ``kv_heads`` then says Hkv, H where
    it is not given) with ``H`` a multiple of ``Hkv`` (query head h
    reads KV head ``h // (H // Hkv)``; float32/bfloat16, or int8 with
    ``k_scale``/``v_scale`` ``[num_blocks, Hkv, block_size]`` beside a
    logical pool and ``[num_blocks, f, Hkv * block_size // f]`` beside a
    stored one); block_tables ``[B, blocks_per_row]`` int32; pos ``[B]``
    int32. With ``window`` a key is visible iff it is at most
    ``window - 1`` behind its query, and the table is a ring: logical
    block ``b`` of a row is column ``b % blocks_per_row``, which has to
    be at least :func:`window_blocks` wide. Returns ``[B, H, 1, D]`` in
    q's dtype. impl: None (auto — pallas on a TPU, xla elsewhere, and
    xla for more than one query per row), "pallas", "interpret" (Pallas
    interpreter, CPU-runnable), "xla" (the gather composite / parity
    oracle, on the logical shape). Every resolution is counted and
    scoped by kernels/_dispatch.py. Under ``mesh`` the kernel runs per
    shard (heads over tp, the pool's own split); the composite is left
    to GSPMD."""
    if scale is None or scale == 0.0:
        scale = float(q.shape[-1]) ** -0.5
    if (k_scale is None) != (v_scale is None):
        raise ValueError("paged_attention needs BOTH k_scale and "
                         "v_scale for a quantized pool (or neither)")
    if k_pool.dtype == jnp.int8 and k_scale is None:
        raise ValueError("int8 KV pool needs k_scale/v_scale arrays")
    D = q.shape[-1]
    stored = k_pool.ndim == 3
    Hkv = int(kv_heads or q.shape[1]) if stored else k_pool.shape[1]
    if q.shape[1] % Hkv:
        raise ValueError(
            f"paged_attention: {q.shape[1]} query heads do not divide "
            f"into the pool's {Hkv} KV heads")
    bs = k_pool.shape[1] * k_pool.shape[2] // (Hkv * D) if stored \
        else k_pool.shape[2]
    window = int(window) if window else None
    if window is not None and block_tables.shape[1] < window_blocks(
            window, bs):
        raise ValueError(
            f"paged_attention: a ring of {block_tables.shape[1]} blocks "
            f"cannot hold a window of {window} at block size {bs}")
    reason = "requested" if impl else "backend"
    if not impl:
        impl = _dispatch.auto_impl()
        if impl != "xla" and q.shape[2] != 1:
            # the Pallas kernel decodes one query per row; chunked
            # prefill and the K+1 speculative verify (S>1 queries over
            # the paged pool) read via the gather composite, which masks
            # key j against pos[b]+i per query i
            impl, reason = "xla", "multi_query"
    with _dispatch.resolved("paged_attention", impl, reason):
        if impl == "xla":
            if stored:
                k_pool, v_pool = (to_logical(a, Hkv, D)
                                  for a in (k_pool, v_pool))
                if k_scale is not None:
                    k_scale, v_scale = (scales_to_logical(a, Hkv)
                                        for a in (k_scale, v_scale))
            return _xla_paged_attention(q, k_pool, v_pool, block_tables,
                                        pos, k_scale, v_scale,
                                        float(scale), window)
        if not stored:
            k_pool, v_pool = to_stored(k_pool), to_stored(v_pool)
            if k_scale is not None:
                k_scale, v_scale = (scales_to_stored(a, D)
                                    for a in (k_scale, v_scale))
        rep = q.shape[1] // Hkv

        def kernel(q, k_pool, v_pool, tables, pos, k_scale, v_scale):
            return _pallas_paged_attention(
                q, k_pool, v_pool, tables, pos, k_scale, v_scale,
                float(scale), impl == "interpret", rep, window)

        # rows stay whole: every row's table may name any pool block;
        # the stored rows are head-major, and so are the scales' columns
        nhbd = (None, "heads", None, None)
        nrc = (None, "heads", None)
        nfr = (None, None, "heads")
        return _dispatch.per_shard(
            kernel, mesh,
            (q, k_pool, v_pool, block_tables, pos, k_scale, v_scale),
            (nhbd, nrc, nrc, (None, None), (None,), nfr, nfr), nhbd)
