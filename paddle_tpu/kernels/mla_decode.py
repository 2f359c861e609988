"""Absorbed latent-attention decode over a latent paged pool, as a Pallas
TPU kernel with a pure-JAX oracle.

Multi-head latent attention (DeepSeek-V2, 2024) caches, a position a
layer, one normalised latent ``c_kv`` [C] (C = ``kv_lora_rank``, 512 at
the published sizes) and one rotary key ``k_pe`` [R] (R =
``qk_rope_head_dim``, 64) shared by every head; no key or value is kept
per head. A decode step absorbs the key's up-projection into the query
and the value's into the output, so that every head reads the one
latent::

    q_lat[h] = q_nope[h] W_UK[:, h]^T                  # [C], outside
    s[h, t]  = scale * (q_lat[h] . c_kv[t] + q_pe[h] . k_pe[t])
    o_lat[h] = sum_t softmax_t(s[h]) c_kv[t]           # [C], here
    o[h]     = o_lat[h] W_UV[:, h]                     # outside

The pool (``serving/kvpool.py``'s latent group) keeps a layer's latents
as ``[num_blocks, block_size, C]`` (C fills whole 128-lane rows) and its
rotary keys in the pool's packed stored shape ``[num_blocks, block_size
// f, f * R]`` (``kernels/paged_attention.pool_packing``: f = 128 // R
slots of a block side by side in one row), so neither is padded.

Two implementations, same math:

- ``pallas``: grid ``(B,)``, a grid step a row, the block table and
  ``pos`` scalar-prefetched, both pool arrays left in HBM. The kernel
  reads the row's trip count from ``pos`` (``paged_attention.row_steps``)
  and walks the row's live blocks G at a time with the double-buffered
  copies of ``paged_attention``'s kernel: a step waits for G blocks of
  latents and of rotary keys in one half of the buffers, starts the next
  step's copies into the other half (a row's last step starts the next
  live row's first), and folds all heads at once against the blocks it
  read once, in an online softmax kept in float32. The latent products
  take the pool's dtype (bfloat16 when served) and accumulate in
  float32. A rotary row holds f slots: each of its f lane groups is
  scored against a copy of the query placed in those lanes, and a 0/1
  matrix (``expansion``) puts those scores in the slots' own columns,
  block by block. A row whose first table entry is block 0 (the trash
  block: a free slot) costs an empty grid step and reads zeros.
  ``interpret`` runs the same kernel through the Pallas interpreter.
- ``xla``: a ``jnp.take`` gather of the row's blocks and a masked
  float32 softmax: the CPU path and the kernel's parity oracle.

Both resolve through ``kernels/_dispatch.py`` under the op name
``paged_attention``, so a fall-back to the composite on a TPU is counted
where the other decode kernel's is.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _dispatch
from .paged_attention import pool_packing, row_steps

_NEG_INF = -1e30
# positions a step of the walk covers at most
_STEP_POSITIONS = 256


def blocks_per_step(block_size, nblk):
    """G: the largest power of two no wider than the table whose blocks
    hold at most ``_STEP_POSITIONS`` positions (1 for wider blocks)."""
    g = 1
    while 2 * g <= nblk and 2 * g * block_size <= _STEP_POSITIONS:
        g *= 2
    return g


def expansion(block_size, f):
    """``[f, block_size // f, block_size]`` float32: ``E[p, r, f * r + p]
    = 1``. Scores against the rows of a packed block, lane group p (slot
    ``f * r + p`` in row r), times ``E[p]`` stand in the slots' order."""
    e = np.zeros((f, block_size // f, block_size), np.float32)
    for p in range(f):
        e[p, np.arange(block_size // f), f * np.arange(block_size // f)
          + p] = 1.0
    return e


def _mla_kernel(tables_ref, pos_ref, qlat_ref, qpe_ref, expand_ref, c_hbm,
                r_hbm, out_ref, c_buf, r_buf, sems, flight_ref, *, scale,
                bs, G, f):
    """Grid step b is row b. Step j of its walk waits for table entries
    ``[j * G, (j + 1) * G)`` of the row as G latent tiles ``[bs, C]`` and
    G packed rotary tiles ``[bs // f, f * R]`` in one half of the
    ``[2, G, ...]`` buffers, starts the next step's copies into the other
    half and folds every head against them into the row's online
    softmax, the loop's carry. ``flight_ref`` (SMEM: the half the next
    step reads, and whether its copies are under way) hands the pipe
    from a row to the next row that holds blocks."""
    b, B = pl.program_id(0), pl.num_programs(0)
    nblk = tables_ref.shape[1]
    H = qlat_ref.shape[1]
    hbm, bufs = (c_hbm, r_hbm), (c_buf, r_buf)

    def span(r):
        """Row r's live blocks (0 where it holds none) and its steps."""
        _, live, steps = row_steps(pos_ref[r], bs, G, nblk)
        held = tables_ref[r, 0] != 0
        return jnp.where(held, live, 0), jnp.where(held, steps, 0)

    def copies(act, r, live, j, half):
        for i in range(G):
            @pl.when(j * G + i < live)
            def _():
                block = tables_ref[r, j * G + i]
                for n, (src, dst) in enumerate(zip(hbm, bufs)):
                    getattr(pltpu.make_async_copy(
                        src.at[block], dst.at[half, i], sems.at[half, n]),
                        act)()

    live, steps = span(b)

    @pl.when(b == 0)
    def _reset():
        flight_ref[0] = 0
        flight_ref[1] = 0
        # a step's tail past the row's blocks keeps what the buffer held:
        # finite from the first call on, so a masked score stays masked
        for buf in bufs:
            buf[...] = jnp.zeros_like(buf)

    @pl.when(steps == 0)
    def _free_slot():
        out_ref[0] = jnp.zeros_like(out_ref[0])

    @pl.when(steps > 0)
    def _row():
        half0 = flight_ref[0]

        @pl.when(flight_ref[1] == 0)
        def _first_of_the_call():
            copies("start", b, live, 0, half0)

        nxt = jax.lax.while_loop(
            lambda r: (r < B) & (span(jnp.minimum(r, B - 1))[1] == 0),
            lambda r: r + 1, b + 1)
        nxt_live, _ = span(jnp.minimum(nxt, B - 1))
        p_row = pos_ref[b]
        qlat = qlat_ref[0]                               # [H, C]
        qpe = qpe_ref[0]                                 # [f * H, f * R]
        cols = jax.lax.broadcasted_iota(jnp.int32, (H, G * bs), 1)

        def fold(j, carry):
            m_prev, l_prev, acc = carry
            half = (half0 + j) % 2

            @pl.when(j + 1 < steps)
            def _next_step():
                copies("start", b, live, j + 1, 1 - half)

            @pl.when((j + 1 == steps) & (nxt < B))
            def _next_row():
                copies("start", nxt, nxt_live, 0, 1 - half)

            copies("wait", b, live, j, half)
            c_rows = c_buf[half].reshape(G * bs, c_buf.shape[3])
            s = jax.lax.dot_general(
                qlat, c_rows, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)      # [H, G * bs]
            pe = []
            for i in range(G):
                blk = jnp.zeros((H, bs), jnp.float32)
                for p in range(f):
                    sp = jax.lax.dot_general(
                        qpe[p * H:(p + 1) * H], r_buf[half, i],
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)  # [H, bs/f]
                    blk = blk + jax.lax.dot_general(
                        sp, expand_ref[p], (((1,), (0,)), ((), ())),
                        precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32)
                pe.append(blk)
            s = (s + (pe[0] if G == 1 else jnp.concatenate(pe, axis=1))) \
                * scale
            s = jnp.where(cols <= p_row - j * (G * bs), s, _NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            pr = jnp.exp(s - m_new)
            l_new = l_prev * corr + jnp.sum(pr, axis=-1, keepdims=True)
            acc = acc * corr + jax.lax.dot_general(
                pr.astype(c_rows.dtype), c_rows, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)      # [H, C]
            return m_new, l_new, acc

        _, l, acc = jax.lax.fori_loop(0, steps, fold, (
            jnp.full((H, 1), _NEG_INF, jnp.float32),
            jnp.zeros((H, 1), jnp.float32),
            jnp.zeros((H, c_buf.shape[3]), jnp.float32)))
        flight_ref[0] = (half0 + steps) % 2
        flight_ref[1] = (nxt < B).astype(jnp.int32)
        out_ref[0] = acc / jnp.where(l == 0.0, 1.0, l)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _pallas_mla_decode(q_lat, q_pe, c_pool, r_pool, tables, pos, scale,
                       interpret):
    B, H, C = q_lat.shape
    R = q_pe.shape[-1]
    bs = c_pool.shape[1]
    f = pool_packing(R, bs)
    G = blocks_per_step(bs, tables.shape[1])
    dt = c_pool.dtype
    # the rotary query laid out f times: row p * H + h holds q_pe[h] in
    # lane group p, zeros elsewhere
    qpe = (jnp.eye(f, dtype=dt)[None, :, None, :, None]
           * q_pe.astype(dt)[:, None, :, None, :]).reshape(B, f * H, f * R)
    expand = jnp.asarray(expansion(bs, f))

    def row(b, *scalars):
        return (b, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,           # the block table and pos
        grid=(B,),
        in_specs=[pl.BlockSpec((1, H, C), row),
                  pl.BlockSpec((1, f * H, f * R), row),
                  pl.BlockSpec(expand.shape, lambda b, *s: (0, 0, 0)),
                  pl.BlockSpec(memory_space=pltpu.HBM),
                  pl.BlockSpec(memory_space=pltpu.HBM)],
        out_specs=pl.BlockSpec((1, H, C), row),
        scratch_shapes=[pltpu.VMEM((2, G) + c_pool.shape[1:], dt),
                        pltpu.VMEM((2, G) + r_pool.shape[1:], r_pool.dtype),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.SMEM((2,), jnp.int32)],
    )
    return pl.pallas_call(
        functools.partial(_mla_kernel, scale=scale, bs=bs, G=G, f=f),
        name="mla_paged_decode", grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, C), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(tables.astype(jnp.int32), pos.astype(jnp.int32), q_lat.astype(dt),
      qpe, expand, c_pool, r_pool)


def _xla_mla_decode(q_lat, q_pe, c_pool, r_pool, tables, pos, scale):
    """Gather-then-attend composite in float32 over the logical blocks
    (``[N, bs, C]`` latents, ``[N, bs, R]`` rotary keys)."""
    B, H, C = q_lat.shape
    R = q_pe.shape[-1]
    N = c_pool.shape[0]
    bs = c_pool.shape[1] * c_pool.shape[2] // C
    nblk = tables.shape[1]
    c = jnp.take(c_pool.reshape(N, bs, C), tables, axis=0).astype(
        jnp.float32).reshape(B, nblk * bs, C)
    r = jnp.take(r_pool.reshape(r_pool.shape[0], bs, R), tables,
                 axis=0).astype(jnp.float32).reshape(B, nblk * bs, R)
    hi = jax.lax.Precision.HIGHEST
    s = (jnp.einsum("bhc,blc->bhl", q_lat.astype(jnp.float32), c,
                    precision=hi)
         + jnp.einsum("bhr,blr->bhl", q_pe.astype(jnp.float32), r,
                      precision=hi)) * scale
    keep = jnp.arange(nblk * bs)[None, None, :] <= pos[:, None, None]
    p = jax.nn.softmax(jnp.where(keep, s, _NEG_INF), axis=-1)
    return jnp.einsum("bhl,blc->bhc", p, c, precision=hi)


def mla_decode(q_lat, q_pe, c_pool, r_pool, block_tables, pos, scale,
               impl=None):
    """One query a row over a layer's latent pool: ``q_lat`` [B, H, C]
    (the absorbed query), ``q_pe`` [B, H, R], ``c_pool`` and ``r_pool``
    the latents and rotary keys in the pool's stored shapes (``[N, bs,
    C]`` for a latent of whole 128-lane rows, ``[N, bs // f, f * R]``),
    ``block_tables`` [B, nblk] int32, ``pos`` [B] int32 (key slot j is
    visible iff ``j <= pos[b]``). Returns ``o_lat`` [B, H, C] float32.
    impl: None (auto: pallas on a TPU, xla elsewhere), "pallas",
    "interpret", "xla"; the kernel takes a latent of whole rows
    only."""
    reason = "requested" if impl else "backend"
    impl = impl or _dispatch.auto_impl()
    if impl != "xla" and c_pool.shape[-1] != q_lat.shape[-1]:
        raise ValueError(
            f"mla_decode: the kernel reads latents of whole 128-lane rows; "
            f"a latent of {q_lat.shape[-1]} is stored {c_pool.shape}")
    with _dispatch.resolved("paged_attention", impl, reason):
        if impl == "xla":
            return _xla_mla_decode(q_lat, q_pe, c_pool, r_pool,
                                   block_tables, pos.astype(jnp.int32),
                                   float(scale))
        return _pallas_mla_decode(q_lat, q_pe, c_pool, r_pool, block_tables,
                                  pos, float(scale), impl == "interpret")
