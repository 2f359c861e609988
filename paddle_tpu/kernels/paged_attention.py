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

Layout: q ``[B, H, 1, D]`` (single decode step per row), k/v pools
``[num_blocks, H, block_size, D]``, block tables ``[B, blocks_per_row]``
int32 (entries past a row's allocation point at the reserved trash
block — masked by ``pos``), pos ``[B]`` int32 (index of the query's own
slot: key slot j is visible iff ``j <= pos[b]``).
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
    blocks, per-row position mask, fp32 softmax — identical math to
    ``ops.decode_ops.kv_cached_attention`` over the gathered layout.
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
    """VMEM bytes of one block's ``[H, bs, D]`` tile: its last two dims
    pad to the dtype's (sublanes, 128 lanes) tile."""
    itemsize = jnp.dtype(dtype).itemsize
    sublanes = 8 * (4 // itemsize)
    return H * pl.cdiv(bs, sublanes) * sublanes * pl.cdiv(D, _LANES) \
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


def _slot_of(H, bs, G, rep=1):
    """``[H * rep, G * H * bs]`` int32: for query head h and a step's
    key column (g, h', t), the key's slot in the step ``g * bs + t``
    where ``h'`` is the KV head that h reads (``h // rep``) and
    ``_NO_SLOT`` elsewhere. One compare against ``pos`` less the step's
    first slot masks the other heads' keys and the slots past the row's
    position together."""
    g, h2, t = np.meshgrid(np.arange(G), np.arange(H), np.arange(bs),
                           indexing="ij")
    own = h2.reshape(1, -1) == (np.arange(H * rep) // rep)[:, None]
    return np.where(own, (g * bs + t).reshape(1, -1),
                    _NO_SLOT).astype(np.int32)


def _paged_kernel(*refs, scale, bs, G, quant, window=None):
    """Grid step (b, j) folds table entries ``[j * G, (j + 1) * G)`` of
    row b, all H heads at once, into the row's online-softmax state. The
    gather already happened in the index maps: ``refs`` hold G key
    tiles, G value tiles (and with ``quant`` G + G scale tiles) of
    ``[H, bs, D]`` each, then the output and the (m, l, acc) scratch.

    All heads fold in two matrix products: q ``[H, D]`` against the
    step's keys ``[G * H * bs, D]`` scores every head against every
    head's keys, ``slot_of`` keeps a head's own and drops the slots past
    ``pos[b]``, and the probabilities, exactly zero off a head's own
    columns, times the values ``[G * H * bs, D]`` are the H weighted
    sums. The running max and sum live in lane 0 of ``(H, 128)`` VMEM
    tiles (the flash kernel's idiom): Mosaic stores vectors to VMEM,
    never scalars. int8 tiles are dequantized against their ``[H, bs]``
    scale tiles as they leave VMEM."""
    # scalar prefetch: the live table (read by the index maps alone),
    # pos and, with a window, the position of the table's first slot
    pos_ref, first_ref = refs[1], (refs[2] if window is not None else None)
    refs = refs[3 if window is not None else 2:]
    q_ref, slot_of_ref, refs = refs[0], refs[1], refs[2:]
    k_refs, v_refs, refs = refs[:G], refs[G:2 * G], refs[2 * G:]
    ks_refs = vs_refs = (None,) * G
    if quant:
        ks_refs, vs_refs, refs = refs[:G], refs[G:2 * G], refs[2 * G:]
    out_ref, m_sc, l_sc, acc_sc = refs
    b, j = pl.program_id(0), pl.program_id(1)
    D = q_ref.shape[2]
    H = k_refs[0].shape[1]

    @pl.when(j == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    # the query's position counted from the table's first slot
    p = pos_ref[b] if window is None else pos_ref[b] - first_ref[b]

    def tiles(refs, scale_refs):
        rows = []
        for ref, scale_ref in zip(refs, scale_refs):
            x = ref[0].astype(jnp.float32)                    # [H, bs, D]
            if scale_ref is not None:
                x = x * scale_ref[0][:, :, None]
            rows.append(x.reshape(H * bs, D))
        return jnp.concatenate(rows, axis=0)                  # [G*H*bs, D]

    # dead-step skip: step j covers key slots [j*G*bs, (j+1)*G*bs);
    # nothing there is visible once j*G*bs > pos[b], and nothing was
    # fetched for it (_live_tables). Block-table padding (trash block 0)
    # only ever appears PAST a row's allocation, so the same predicate
    # and slot_of's compare keep garbage out of the state.
    @pl.when(j * (G * bs) <= p)
    def _fold():
        s = jax.lax.dot_general(
            q_ref[0].astype(jnp.float32), tiles(k_refs, ks_refs),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # [H, G*H*bs]
        rel = p - j * (G * bs)          # the query's slot in this step
        keep = slot_of_ref[...] <= rel
        if window is not None:
            keep = keep & (slot_of_ref[...] > rel - window)
        s = jnp.where(keep, s, _NEG_INF)
        m_prev = m_sc[:, :1]                                  # [H, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        pr = jnp.exp(s - m_new)
        l_sc[:, :1] = l_sc[:, :1] * corr + jnp.sum(pr, axis=-1,
                                                   keepdims=True)
        m_sc[:, :1] = m_new
        acc_sc[:] = acc_sc[:] * corr + jax.lax.dot_general(
            pr, tiles(v_refs, vs_refs), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # [H, D]

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        l = l_sc[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        out_ref[0] = (acc_sc[:] / l).astype(out_ref.dtype)


def _pallas_paged_attention(q, k_pool, v_pool, tables, pos, k_scale,
                            v_scale, scale, interpret, window=None):
    B, Hq, S, D = q.shape
    if S != 1:
        raise ValueError(
            f"paged_attention kernel decodes ONE query per row (S=1), "
            f"got S={S}; prefill goes through flash_attention")
    H, bs = k_pool.shape[1], k_pool.shape[2]
    rep = Hq // H
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
    slot_of = _slot_of(H, bs, G, rep)

    # index maps see the grid indices THEN the scalar-prefetch refs: the
    # i-th pool operand's block for (b, j) is whatever the row's live
    # table names at j * G + i — the fused gather
    def row(b, j, *scalars):
        return (b, 0, 0)

    def block(i, ndim):
        return lambda b, j, live, *_: (live[b, j * G + i],) + (0,) * ndim

    def pool_specs(pool):
        return [pl.BlockSpec((1,) + pool.shape[1:], block(i, pool.ndim - 1))
                for i in range(G)]

    in_specs = [pl.BlockSpec((1, Hq, D), row),
                pl.BlockSpec(slot_of.shape, lambda b, j, *scalars: (0, 0))]
    args = [q.reshape(B, Hq, D), slot_of]
    # whole [H, bs, D] tiles, and for int8 the block's whole [H, bs]
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
        out_specs=pl.BlockSpec((1, Hq, D), row),
        scratch_shapes=[pltpu.VMEM((Hq, _LANES), jnp.float32),
                        pltpu.VMEM((Hq, _LANES), jnp.float32),
                        pltpu.VMEM((Hq, D), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, bs=bs, G=G,
                          quant=quant, window=window),
        name="paged_attention_decode", grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        interpret=interpret,
    )(*scalars, *args)
    return out.reshape(B, Hq, 1, D)


# ----------------------------------------------------------- public entry

def paged_attention(q, k_pool, v_pool, block_tables, pos, k_scale=None,
                    v_scale=None, scale=None, impl=None, mesh=None,
                    window=None):
    """Decode attention of one query per row over a block-paged KV pool.

    q ``[B, H, 1, D]``; k_pool/v_pool ``[num_blocks, Hkv, block_size,
    D]`` with ``H`` a multiple of ``Hkv`` (query head h reads KV head
    ``h // (H // Hkv)``; float32/bfloat16, or int8 with
    ``k_scale``/``v_scale`` ``[num_blocks, Hkv, block_size]``);
    block_tables ``[B, blocks_per_row]`` int32; pos ``[B]`` int32.
    With ``window`` a key is visible iff it is at most ``window - 1``
    behind its query, and the table is a ring: logical block ``b`` of a
    row is column ``b % blocks_per_row``, which has to be at least
    :func:`window_blocks` wide. Returns ``[B, H, 1, D]`` in q's dtype.
    impl: None (auto — pallas on a TPU, xla elsewhere, and xla for more
    than one query per row), "pallas", "interpret" (Pallas interpreter,
    CPU-runnable), "xla" (the gather composite / parity oracle). Every
    resolution is counted and scoped by kernels/_dispatch.py. Under
    ``mesh`` the kernel runs per shard (heads over tp, the pool's own
    split); the composite is left to GSPMD."""
    if scale is None or scale == 0.0:
        scale = float(q.shape[-1]) ** -0.5
    if (k_scale is None) != (v_scale is None):
        raise ValueError("paged_attention needs BOTH k_scale and "
                         "v_scale for a quantized pool (or neither)")
    if k_pool.dtype == jnp.int8 and k_scale is None:
        raise ValueError("int8 KV pool needs k_scale/v_scale arrays")
    if q.shape[1] % k_pool.shape[1]:
        raise ValueError(
            f"paged_attention: {q.shape[1]} query heads do not divide "
            f"into the pool's {k_pool.shape[1]} KV heads")
    window = int(window) if window else None
    if window is not None and block_tables.shape[1] < window_blocks(
            window, k_pool.shape[2]):
        raise ValueError(
            f"paged_attention: a ring of {block_tables.shape[1]} blocks "
            f"cannot hold a window of {window} at block size "
            f"{k_pool.shape[2]}")
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
            return _xla_paged_attention(q, k_pool, v_pool, block_tables,
                                        pos, k_scale, v_scale,
                                        float(scale), window)

        def kernel(q, k_pool, v_pool, tables, pos, k_scale, v_scale):
            return _pallas_paged_attention(
                q, k_pool, v_pool, tables, pos, k_scale, v_scale,
                float(scale), impl == "interpret", window)

        # rows stay whole: every row's table may name any pool block
        nhbd = (None, "heads", None, None)
        nhb = (None, "heads", None)
        return _dispatch.per_shard(
            kernel, mesh,
            (q, k_pool, v_pool, block_tables, pos, k_scale, v_scale),
            (nhbd, nhbd, nhbd, (None, None), (None,), nhb, nhb), nhbd)
