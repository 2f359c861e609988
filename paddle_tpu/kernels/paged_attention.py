"""Paged decode-attention as a Pallas TPU kernel, with a pure-JAX oracle.

The decode half of the flash-attention story (kernels/flash_attention.py
fused prefill): one query token per row attends over that row's KV cache
stored as BLOCKS of a shared pool (vLLM/PagedAttention, Kwon et al.
2023) instead of a dense per-slot ``[B, H, max_len, D]`` bank. The
block-table gather IS the kernel's index map — each grid step's
``BlockSpec`` resolves ``(tables[b, j], h, 0, 0)`` from a
scalar-prefetched block table, so the gather and the attention read are
one fused pass over VMEM-resident blocks and the ``[B, max_len]`` dense
cache is never materialized (decode is bandwidth-bound: bytes streamed
per token IS the token rate).

Two implementations, same math:

- ``pallas``: grid ``(B, H, blocks_per_row)``, online-softmax running
  state (m, l, acc) in VMEM scratch carried across a row's blocks,
  dead-block skipping via the per-row position counter (a block past
  ``pos[b]`` is never fetched into the running state — table padding
  rides the same skip), int8 blocks dequantized in-register against
  their per-slot scales. ``interpret`` runs the SAME kernel through the
  Pallas interpreter on CPU.
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

def _xla_paged_attention(q, k_pool, v_pool, tables, pos, k_scale, v_scale,
                         scale):
    """Gather-then-attend composite: per-row ``jnp.take`` of the row's
    blocks, per-row position mask, fp32 softmax — identical math to
    ``ops.decode_ops.kv_cached_attention`` over the gathered layout.
    Runs anywhere (CPU CI) and is the kernel's parity oracle."""
    B, H, S, D = q.shape
    bs = k_pool.shape[2]
    nblk = tables.shape[1]
    L = nblk * bs

    def gather(pool, sc):
        # [B, nblk, H, bs, D] -> [B, H, L, D], dequantized
        g = jnp.take(pool, tables, axis=0)
        if sc is not None:
            gs = jnp.take(sc, tables, axis=0)        # [B, nblk, H, bs]
            g = dequantize_kv(g, gs)
        g = g.astype(jnp.float32)
        return g.transpose(0, 2, 1, 3, 4).reshape(B, H, L, D)

    k = gather(k_pool, k_scale)
    v = gather(v_pool, v_scale)
    scores = jnp.einsum("bhsd,bhld->bhsl", q.astype(jnp.float32),
                        k) * scale
    key_idx = jnp.arange(L, dtype=jnp.int32)[None, None, :]       # [1,1,L]
    qry_pos = pos.astype(jnp.int32)[:, None, None] \
        + jnp.arange(S, dtype=jnp.int32)[None, :, None]
    mask = key_idx <= qry_pos                                     # [B,S,L]
    scores = jnp.where(mask[:, None, :, :], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhsl,bhld->bhsd", probs, v)
    return out.astype(q.dtype)


# ----------------------------------------------------------------- kernel

def _paged_kernel(tables_ref, pos_ref, q_ref, k_ref, v_ref, ks_ref,
                  vs_ref, out_ref, m_sc, l_sc, acc_sc, *, scale, bs,
                  nblk):
    """One (b, h, j) grid step folds block j of row b into the running
    online-softmax state. The block-table gather already happened in the
    BlockSpec index map — k_ref/v_ref hold block ``tables[b, j]``.

    The running max and sum live in lane 0 of ``(1, 128)`` VMEM tiles
    (the flash kernel's idiom): Mosaic stores vectors to VMEM, never
    scalars. int8 scales arrive as the block's whole ``[H, bs]`` tile
    and stay slot-major ``[1, bs]`` rows: ``q . (k_int * ks)`` equals
    ``(q . k_int) * ks`` and ``sum_j p_j vs_j v_int_j`` equals
    ``(p * vs) @ v_int``, so dequantization rides the score and
    probability rows and no ``[bs, 1]`` relayout is needed."""
    b, h, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    p = pos_ref[b]

    # dead-block skip: block j covers key slots [j*bs, (j+1)*bs); nothing
    # there is visible once j*bs > pos[b]. Block-table padding (trash
    # block 0) only ever appears PAST a row's allocation, so the same
    # predicate keeps garbage out of the state.
    @pl.when(j * bs <= p)
    def _fold():
        qv = q_ref[0, 0].astype(jnp.float32)                  # [1, D]
        kb = k_ref[0, 0].astype(jnp.float32)                  # [bs, D]
        s = jax.lax.dot_general(
            qv, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # [1, bs]
        if ks_ref is not None:
            s = s * ks_ref[0, pl.ds(h, 1), :]
        idx = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(idx <= p, s, _NEG_INF)
        m_prev = m_sc[:, :1]                                  # [1, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        pr = jnp.exp(s - m_new)                               # [1, bs]
        l_sc[:, :1] = l_sc[:, :1] * corr + jnp.sum(pr, axis=-1,
                                                   keepdims=True)
        m_sc[:, :1] = m_new
        if vs_ref is not None:
            pr = pr * vs_ref[0, pl.ds(h, 1), :]
        acc_sc[:] = acc_sc[:] * corr + jax.lax.dot_general(
            pr, v_ref[0, 0].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # [1, D]

    @pl.when(j == nblk - 1)
    def _finalize():
        l = l_sc[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        out_ref[0, 0] = (acc_sc[:] / l).astype(out_ref.dtype)


def _pallas_paged_attention(q, k_pool, v_pool, tables, pos, k_scale,
                            v_scale, scale, interpret):
    B, H, S, D = q.shape
    if S != 1:
        raise ValueError(
            f"paged_attention kernel decodes ONE query per row (S=1), "
            f"got S={S}; prefill goes through flash_attention")
    bs = k_pool.shape[2]
    nblk = tables.shape[1]
    quant = k_scale is not None

    # index maps see the grid indices THEN the scalar-prefetch refs:
    # the pool block for (b, j) is whatever the row's table names — the
    # fused gather
    in_specs = [
        pl.BlockSpec((1, 1, 1, D), lambda b, h, j, t, p: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, bs, D),
                     lambda b, h, j, t, p: (t[b, j], h, 0, 0)),
        pl.BlockSpec((1, 1, bs, D),
                     lambda b, h, j, t, p: (t[b, j], h, 0, 0)),
    ]
    args = [q, k_pool, v_pool]
    if quant:
        # the block's whole [H, bs] scale tile: a (1, 1, bs) block
        # over [N, H, bs] breaks the TPU rule that a block's last two
        # dims divide (8, 128) or equal the array's; the kernel picks
        # row h
        in_specs += [
            pl.BlockSpec((1, H, bs),
                         lambda b, h, j, t, p: (t[b, j], 0, 0)),
            pl.BlockSpec((1, H, bs),
                         lambda b, h, j, t, p: (t[b, j], 0, 0)),
        ]
        args += [k_scale, v_scale]

    body = functools.partial(_paged_kernel, scale=scale, bs=bs, nblk=nblk)

    if quant:
        kern = body
    else:
        def kern(tables_ref, pos_ref, q_ref, k_ref, v_ref, out_ref,
                 m_sc, l_sc, acc_sc):
            body(tables_ref, pos_ref, q_ref, k_ref, v_ref, None, None,
                 out_ref, m_sc, l_sc, acc_sc)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H, nblk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, 1, D),
                               lambda b, h, j, t, p: (b, h, 0, 0)),
        scratch_shapes=[pltpu.VMEM((1, _LANES), jnp.float32),
                        pltpu.VMEM((1, _LANES), jnp.float32),
                        pltpu.VMEM((1, D), jnp.float32)],
    )
    return pl.pallas_call(
        kern, name="paged_attention_decode", grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, 1, D), q.dtype),
        interpret=interpret,
    )(tables.astype(jnp.int32), pos.astype(jnp.int32), *args)


# ----------------------------------------------------------- public entry

def paged_attention(q, k_pool, v_pool, block_tables, pos, k_scale=None,
                    v_scale=None, scale=None, impl=None, mesh=None):
    """Decode attention of one query per row over a block-paged KV pool.

    q ``[B, H, 1, D]``; k_pool/v_pool ``[num_blocks, H, block_size, D]``
    (float32/bfloat16, or int8 with ``k_scale``/``v_scale``
    ``[num_blocks, H, block_size]``); block_tables ``[B, blocks_per_row]``
    int32; pos ``[B]`` int32. Returns ``[B, H, 1, D]`` in q's dtype.
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
                                        float(scale))

        def kernel(q, k_pool, v_pool, tables, pos, k_scale, v_scale):
            return _pallas_paged_attention(
                q, k_pool, v_pool, tables, pos, k_scale, v_scale,
                float(scale), impl == "interpret")

        # rows stay whole: every row's table may name any pool block
        nhbd = (None, "heads", None, None)
        nhb = (None, "heads", None)
        return _dispatch.per_shard(
            kernel, mesh,
            (q, k_pool, v_pool, block_tables, pos, k_scale, v_scale),
            (nhbd, nhbd, nhbd, (None, None), (None,), nhb, nhb), nhbd)
