"""Paged decode-attention as a Pallas TPU kernel, with a pure-JAX oracle.

The decode half of the flash-attention story (kernels/flash_attention.py
fused prefill): one query token per row attends over that row's KV cache
stored as BLOCKS of a shared pool (vLLM/PagedAttention, Kwon et al.
2023) instead of a dense per-slot ``[B, H, max_len, D]`` bank. The
block-table gather IS the kernel's index map — each grid step's
``BlockSpec``s resolve their blocks from a scalar-prefetched table, so
the gather and the attention read are one fused pass over VMEM-resident
blocks and the ``[B, max_len]`` dense cache is never materialized (decode
is bandwidth-bound: bytes streamed per token IS the token rate).

Two implementations, same math:

- ``pallas``: grid ``(B, cdiv(blocks_per_row, G))``. A step fetches G
  consecutive table entries of a row as G whole ``[H, block_size, D]``
  tiles of K and of V (G pool operands each, 256 KB and more a step at
  serving shapes) and folds all H heads of them at once into the row's
  online-softmax state (m, l, acc in VMEM scratch carried across the
  row's steps). G follows from H, block_size, D, the pool's dtype and
  the table's width (:func:`blocks_per_step`). Past a row's last live
  block ``pos[b] // block_size`` every operand's block index stands
  still (:func:`_live_tables`), so a dead step costs neither a DMA nor
  a fold — table padding rides the same skip. int8 blocks are
  dequantized against their per-slot scales as they leave VMEM.
  ``interpret`` runs the SAME kernel through the Pallas interpreter on
  CPU. What a step costs on a v5e (PERF.md, PR 27): 0.04 us for each
  operand whose index map reads the table, whether it moves or not;
  the tiles' bytes and the fold are a tenth of that at 12% live.
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
the reserved trash block — masked by ``pos``), pos ``[B]`` int32 (index
of the query's own slot: key slot j is visible iff ``j <= pos[b]``).

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
    """The first logical block a row at ``pos`` still reads."""
    return jnp.maximum(pos - (window - 1), 0) // bs


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


def blocks_per_step(H, bs, D, dtype, nblk):
    """G, the consecutive table entries of a row that one grid step
    fetches and folds: the largest power of two no wider than the table
    whose K and V tiles, double-buffered, sit in ``_VMEM_BUDGET`` and
    whose scores fit ``_SCORE_LANES`` (1 where a single block already
    exceeds either)."""
    tile = _tile_bytes(H, bs, D, dtype)
    g = 1
    while (2 * g <= nblk and 8 * g * tile <= _VMEM_BUDGET
           and 2 * g * H * bs <= _SCORE_LANES):
        g *= 2
    return g


def decode_grid(B, H, bs, D, dtype, nblk):
    """``(grid, G)`` of one ``paged_attention_decode`` call: a step for
    every G table entries of every row."""
    g = blocks_per_step(H, bs, D, dtype, nblk)
    return (B, pl.cdiv(nblk, g)), g


def _live_tables(tables, pos, bs, G, steps, window=None):
    """``[B, steps * G]``: the block each (row, step, operand) fetches.
    Entry (b, j * G + i) is ``tables[b, j * G + i]`` up to the row's last
    live block ``pos[b] // bs``; past it, a live step's tail repeats that
    last block (fetched again, masked in the fold) and a dead step
    repeats the last live step's entries, so every operand's block index
    stands still and Pallas elides the fetch. Computed here, once a
    decode step, because an index map pays for its arithmetic at every
    (operand, grid step): 0.09 us with the clamp inside it, 0.04 us as
    one table read. With a ``window`` entry 0 is the row's first block
    still in reach (:func:`_first_block`) and the table a ring."""
    if window is None:
        lo = 0
        last = jnp.clip(pos // bs, 0, tables.shape[1] - 1)           # [B]
    else:
        lo = _first_block(pos, window, bs)
        last = pos // bs - lo                      # live blocks less one
    step = jnp.minimum(jnp.arange(steps, dtype=jnp.int32)[None, :],
                       (last // G)[:, None])                   # [B, steps]
    col = step[:, :, None] * G + jnp.arange(G, dtype=jnp.int32)
    col = jnp.minimum(col, last[:, None, None]).reshape(-1, steps * G)
    if window is not None:
        col = (lo[:, None] + col) % tables.shape[1]
    return jnp.take_along_axis(tables, col, axis=1)


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


def _paged_kernel(*refs, scale, bs, G, f, D, quant, window=None):
    """Grid step (b, j) folds table entries ``[j * G, (j + 1) * G)`` of
    row b, all H heads at once, into the row's online-softmax state. The
    gather already happened in the index maps: ``refs`` hold G key
    tiles, G value tiles of the stored ``[H * bs // f, f * D]`` each
    (and with ``quant`` G + G scale tiles ``[f, H * bs // f]``), then
    the output and the (m, l, acc) scratch.

    All heads fold in two matrix products. The query comes laid out f
    times (``[f * Hq, f * D]``: row ``p * Hq + h`` holds q[h] in the
    lanes of slot parity p and zeros elsewhere), so its product with the
    step's key rows ``[G * H * bs // f, f * D]`` scores every head
    against every head's keys of that parity; ``slot_of`` keeps a head's
    own and drops the slots past ``pos[b]``, and the probabilities,
    exactly zero off a head's own columns, times the value rows are the
    weighted sums, of which row ``p * Hq + h`` is read in parity p's
    lanes alone. Each (parity, head) row keeps an online softmax of its
    own (running max and sum in lane 0 of ``(f * Hq, 128)`` VMEM tiles,
    the flash kernel's idiom: Mosaic stores vectors to VMEM, never
    scalars) and the f parities of a head merge when the row ends. int8
    tiles stay unscaled: a key's scale multiplies its score and a
    value's its probability, column by column."""
    # scalar prefetch: the live table (read by the index maps alone),
    # pos and, with a window, the position of the table's first slot
    pos_ref, first_ref = refs[1], (refs[2] if window is not None else None)
    refs = refs[3 if window is not None else 2:]
    q_ref, slot_of_ref, refs = refs[0], refs[1], refs[2:]
    k_refs, v_refs, refs = refs[:G], refs[G:2 * G], refs[2 * G:]
    ks_refs = vs_refs = None
    if quant:
        ks_refs, vs_refs, refs = refs[:G], refs[G:2 * G], refs[2 * G:]
    out_ref, m_sc, l_sc, acc_sc = refs
    b, j = pl.program_id(0), pl.program_id(1)
    Hq = q_ref.shape[1] // f

    @pl.when(j == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    # the query's position counted from the table's first slot
    p = pos_ref[b] if window is None else pos_ref[b] - first_ref[b]

    def tiles(refs):
        return jnp.concatenate([ref[0].astype(jnp.float32) for ref in refs],
                               axis=0)                   # [G*H*bs/f, f*D]

    def column_scales(refs):
        """[f * Hq, G * H * bs / f]: the scale of the key (or value)
        that each score column holds for each parity's rows."""
        sc = jnp.concatenate([ref[0] for ref in refs], axis=1)
        return jnp.concatenate(
            [jnp.broadcast_to(sc[i:i + 1], (Hq, sc.shape[1]))
             for i in range(f)], axis=0)

    # dead-step skip: step j covers key slots [j*G*bs, (j+1)*G*bs);
    # nothing there is visible once j*G*bs > pos[b], and nothing was
    # fetched for it (_live_tables). Block-table padding (trash block 0)
    # only ever appears PAST a row's allocation, so the same predicate
    # and slot_of's compare keep garbage out of the state.
    @pl.when(j * (G * bs) <= p)
    def _fold():
        s = jax.lax.dot_general(
            q_ref[0].astype(jnp.float32), tiles(k_refs),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [f*Hq, G*H*bs/f]
        if quant:
            s = s * column_scales(ks_refs)
        rel = p - j * (G * bs)          # the query's slot in this step
        keep = slot_of_ref[...] <= rel
        if window is not None:
            keep = keep & (slot_of_ref[...] > rel - window)
        s = jnp.where(keep, s, _NEG_INF)
        m_prev = m_sc[:, :1]                                  # [f*Hq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        # a parity that has no visible key yet keeps m at _NEG_INF and
        # would read exp(0) off its masked columns
        pr = jnp.where(keep, jnp.exp(s - m_new), 0.0) if f > 1 \
            else jnp.exp(s - m_new)
        l_sc[:, :1] = l_sc[:, :1] * corr + jnp.sum(pr, axis=-1,
                                                   keepdims=True)
        m_sc[:, :1] = m_new
        if quant:
            pr = pr * column_scales(vs_refs)
        acc_sc[:] = acc_sc[:] * corr + jax.lax.dot_general(
            pr, tiles(v_refs), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # [f*Hq, f*D]

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        if f == 1:
            l = l_sc[:, :1]
            out_ref[0] = acc_sc[:] / jnp.where(l == 0.0, 1.0, l)
            return
        # merge a head's f parities: each weighs exp(m_p - max m); its
        # sums stand in its own lanes, the caller adds the lane groups
        ms = [m_sc[i * Hq:(i + 1) * Hq, :1] for i in range(f)]
        m_all = functools.reduce(jnp.maximum, ms)
        lane_group = jax.lax.broadcasted_iota(
            jnp.int32, (Hq, acc_sc.shape[1]), 1) // D
        l = jnp.zeros((Hq, 1), jnp.float32)
        acc = jnp.zeros((Hq, acc_sc.shape[1]), jnp.float32)
        for i in range(f):
            w = jnp.exp(ms[i] - m_all)
            l = l + l_sc[i * Hq:(i + 1) * Hq, :1] * w
            acc = acc + jnp.where(lane_group == i,
                                  acc_sc[i * Hq:(i + 1) * Hq, :] * w, 0.0)
        out_ref[0] = acc / jnp.where(l == 0.0, 1.0, l)


# jitted, so that a program's layers share one trace and one Mosaic
# lowering of the call (24 reads and 48 appends in gpt2-medium's decode
# step: 5.7 s of lowering without it, 1.2 with; XLA inlines the calls)
@functools.partial(jax.jit, static_argnames=("scale", "interpret", "rep",
                                             "window"))
def _pallas_paged_attention(q, k_pool, v_pool, tables, pos, k_scale,
                            v_scale, scale, interpret, rep, window=None):
    """The kernel over STORED pools ``[N, H * bs // f, f * D]`` (scales
    ``[N, f, H * bs // f]``); ``rep`` query heads share a KV head."""
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
    # a window row reads at most window_blocks of its ring, whatever the
    # ring's width
    nblk = tables.shape[1] if window is None \
        else min(tables.shape[1], window_blocks(window, bs))
    grid, G = decode_grid(B, H, bs, D, k_pool.dtype, nblk)
    # f parities of Hp rows each; sublane-aligned where the merge slices
    Hp = Hq if f == 1 else pl.cdiv(Hq, 8) * 8
    slot_of = _slot_of(H, bs, G, rep, f, Hp)
    # q laid out f times: row p * Hp + h holds q[h] in lane group p
    qw = jnp.pad(q.reshape(B, Hq, D), ((0, 0), (0, Hp - Hq), (0, 0)))
    qw = (jnp.eye(f, dtype=q.dtype)[None, :, None, :, None]
          * qw[:, None, :, None, :]).reshape(B, f * Hp, C)

    # index maps see the grid indices THEN the scalar-prefetch refs: the
    # i-th pool operand's block for (b, j) is whatever the row's live
    # table names at j * G + i — the fused gather
    def row(b, j, *scalars):
        return (b, 0, 0)

    def block(i):
        return lambda b, j, live, *_: (live[b, j * G + i], 0, 0)

    def pool_specs(pool):
        return [pl.BlockSpec((1,) + pool.shape[1:], block(i))
                for i in range(G)]

    in_specs = [pl.BlockSpec((1, f * Hp, C), row),
                pl.BlockSpec(slot_of.shape, lambda b, j, *scalars: (0, 0))]
    args = [qw, slot_of]
    # whole stored tiles, and for int8 the block's whole [f, H*bs/f]
    # scale tile: a block's last two dims divide (8, 128) or equal the
    # array's
    for pool in (k_pool, v_pool) + ((k_scale, v_scale) if quant else ()):
        in_specs += pool_specs(pool)
        args += [pool] * G

    pos = pos.astype(jnp.int32)
    scalars = [_live_tables(tables.astype(jnp.int32), pos, bs, G, grid[1],
                            window), pos]
    if window is not None:
        scalars.append(_first_block(pos, window, bs) * bs)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hp, C), row),
        scratch_shapes=[pltpu.VMEM((f * Hp, _LANES), jnp.float32),
                        pltpu.VMEM((f * Hp, _LANES), jnp.float32),
                        pltpu.VMEM((f * Hp, C), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, bs=bs, G=G, f=f, D=D,
                          quant=quant, window=window),
        name="paged_attention_decode", grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hp, C), jnp.float32),
        interpret=interpret,
    )(*scalars, *args)
    # a head's weighted sum: its f lane groups added
    out = out[:, :Hq].reshape(B, Hq, f, D).sum(axis=2)
    return out.reshape(B, Hq, 1, D).astype(q.dtype)


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
