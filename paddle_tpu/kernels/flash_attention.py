"""Flash attention as a Pallas TPU kernel (fwd + bwd), with XLA fallback.

The reference's attention story is hand-fused CUDA
(operators/fused/multihead_matmul_op.cu — QKV matmul + softmax fused for
V100); the TPU-native equivalent is a blockwise softmax kernel that never
materializes the [Sq, Sk] score matrix in HBM.

The kernel was VPU-bound in its first form (r4: ~25µs/tile of softmax VPU
passes vs ~5µs of MXU work — neither roofline binding). This version cuts
the VPU work per [bq, Sk] tile to two passes (max + a single fused
exp chain) via:

- base-2 softmax: `scale * log2(e)` is folded into the q tile (a [bq, D]
  multiply instead of a [bq, Sk] one) and `exp2` replaces `exp`; the saved
  log-sum-exp is base-2 as well.
- the additive key bias is fused into BOTH the max-reduction pass and the
  exp chain (Mosaic folds the broadcast add into each loop over s2) — no
  separate materialized biased-score tile, and the row max is exact, so a
  bias-masked key can never underflow the real keys' probabilities.
- the softmax normalizer rides the MXU for free: D=64 values occupy half
  of a 128-lane tile, so V is staged into a [bk, 128] VMEM scratch with
  ones in lane D, and `p @ v_aug` yields both `p @ v` and the row sums in
  one matmul — the cross-lane sum reduction pass disappears.
- `p` is cast to the value dtype inside the same fused chain (one store).

Two forward kernels share those tricks:
- single-block (Sk fits one VMEM tile, the common case up to ~4k): no
  online-softmax state at all — one max, one exp chain, one matmul.
- online (long Sk): running (m, acc_aug) state where acc_aug's lane D IS
  the normalizer, so the rescale correction covers acc and l in one
  [bq, 128] multiply.

Backward: when Sk fits one tile, a single combined kernel grids over
q-blocks, recomputes p once, and produces dq (streamed) plus dk/dv
(accumulated in VMEM scratch) — five matmuls, two VPU chains. For long
Sk the classic two-kernel (dq; dk/dv) decomposition remains, updated to
the same base-2/fused-chain scheme.

Layout: q [B, H, Sq, D], k/v [B, H, Sk, D] (the forward takes v
[B, H, Sk, Dv] of another width too), optional additive key-position
bias [B, 1, 1, Sk] (the BERT padding-mask layout), optional causal masking.
The bias is treated as a constant mask (zero cotangent) — masks are data,
not parameters, in every caller in this framework.

impl selection: "pallas" (TPU compiled), "interpret" (Pallas interpreter —
exercises the real kernel on CPU, used by tests), "xla" (composite fallback,
exact same math). Default: pallas on a TPU, xla elsewhere; a general
[B, H, Sq, Sk] bias takes the composite on a TPU too. Every resolution is
counted and scoped by kernels/_dispatch.py.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _dispatch

_NEG_INF = -1e30
_LANES = 128
_LOG2E = 1.4426950408889634   # log2(e); folded into q so exp2 == exp

# VMEM working-set budget for auto block sizing (the chip has ~16 MB;
# leave headroom for Pallas double-buffering of the streamed operands)
_VMEM_BUDGET = 10 * 1024 * 1024
_SINGLE_BLOCK_MAX_SK = 4096


def _auto_bq(sq, sk, per_elem_bytes):
    """Largest power-of-two q block that divides Sq and keeps the
    [bq, Sk]-class intermediates inside the VMEM budget."""
    for cand in (1024, 512, 256, 128):
        if sq % cand == 0 and cand * sk * per_elem_bytes <= _VMEM_BUDGET:
            return cand
    return sq if sq <= 128 else None


def _block_sizes(sq, sk, bq, bk, per_elem_bytes=6, causal=False):
    """Resolve (bq, bk). bk == sk selects the single-block kernels;
    causal sequences >= 2k that divide into 1024-blocks prefer the
    online path, whose dead-block skipping beats the single-block
    kernel's wasted upper triangle (measured r5: 7.26 vs 7.81 ms fwd at
    S=2048). Causal lengths NOT divisible by 1024 (e.g. 2560) stay
    single-block — correct, just without the skip."""
    if bk is None:
        single_ok = sk <= _SINGLE_BLOCK_MAX_SK and not (
            causal and sk >= 2048 and sk % 1024 == 0)
        bk = sk if single_ok else (
            1024 if sk % 1024 == 0 else 512 if sk % 512 == 0
            else 256 if sk % 256 == 0 else 128 if sk % 128 == 0 else sk)
    if bq is None:
        bq = _auto_bq(sq, bk, per_elem_bytes) or sq
    if sq % bq or sk % bk:
        raise ValueError(
            f"flash_attention: Sq={sq}/Sk={sk} must divide block sizes "
            f"({bq}, {bk}); pad the sequence")
    return bq, bk


def _causal_mask(s, qi, ki, bq, bk, window=None):
    """Key j is visible to query i iff ``j <= i`` and, with a window,
    ``i - window < j``."""
    rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    keep = rows >= cols
    if window is not None:
        keep = keep & (rows - cols < window)
    return jnp.where(keep, s, _NEG_INF)


def _block_live(causal, qi, ki, bq, bk, window=None):
    """Whether k-block ki intersects the causal lower triangle of q-block
    qi (always true without causal) and, with a window, the band under
    it: the block's last key is inside the first query's window."""
    if not causal:
        return True
    live = ki * bk <= qi * bq + bq - 1
    if window is not None:
        live = live & (ki * bk + bk - 1 > qi * bq - window)
    return live


def _live_k_block(window, bq, bk):
    """Index of the k-block that (q-block i, step j) fetches under a
    window: j held inside the band's first and last block, so a dead
    step names the block a live neighbour fetched and moves nothing."""
    def clamp(i, j):
        first = jnp.maximum(i * bq - window + 1, 0) // bk
        last = (i * bq + bq - 1) // bk
        return jnp.clip(j, first, last)
    return clamp


def _bias2(bias_ref):
    """Key bias as a base-2 row [1, bk] (constant-mask contract)."""
    return (bias_ref[0, 0, 0, :].astype(jnp.float32) * _LOG2E)[None, :]


# The augmented-V normalizer trick only pays when D < 128 (the ones
# column rides the tile padding the MXU computes anyway); for D >= 128
# heads the kernels fall back to an explicit cross-lane sum and use the
# V block directly — still O(S) memory, one extra VPU reduce pass.

# ---------------------------------------------------------------- forward

def _fwd_single_kernel(q_ref, k_ref, v_ref, bias_ref, out_ref, lse_ref,
                       v_sc, *, scale, bq, causal, window=None):
    """Whole Sk in one tile: no online state. Grid (B, H, nq)."""
    b, h, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    d = v_ref.shape[-1]
    aug = v_sc is not None

    if aug:
        @pl.when((b == 0) & (h == 0) & (i == 0))
        def _once():
            # zeros in lanes d+1.. and ones in lane d never change
            v_sc[:] = jnp.zeros_like(v_sc)
            v_sc[:, d:d + 1] = jnp.ones((v_sc.shape[0], 1), v_sc.dtype)

        @pl.when(i == 0)
        def _stage_v():
            # the V block is constant across i: staged once per (b, h)
            v_sc[:, :d] = v_ref[0, 0].astype(v_sc.dtype)

    q = (q_ref[0, 0].astype(jnp.float32) * (scale * _LOG2E)).astype(
        q_ref.dtype)                                        # [bq, D] tiny
    s2 = jax.lax.dot_general(
        q, k_ref[0, 0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                 # [bq, Sk]
    if causal:
        s2 = _causal_mask(s2, i, 0, bq, k_ref.shape[2], window)
    if bias_ref is not None:
        # the broadcast add fuses into both s2 passes (same VMEM
        # traffic); an unbiased max could underflow every real key when
        # a masked key's raw score dominates
        s2 = s2 + _bias2(bias_ref)
    m2 = jnp.max(s2, axis=-1, keepdims=True)                # [bq, 1]
    arg = s2 - m2
    if aug:
        p = jnp.exp2(arg).astype(v_sc.dtype)                # fused chain
        acc = jax.lax.dot_general(
            p, v_sc[:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [bq, 128]
        l = acc[:, d:d + 1]
    else:
        p = jnp.exp2(arg).astype(v_ref.dtype)
        acc = jax.lax.dot_general(
            p, v_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [bq, D]
        l = jnp.sum(p.astype(jnp.float32), axis=-1, keepdims=True)
    l = jnp.where(l == 0.0, 1.0, l)
    out_ref[0, 0] = (acc[:, :d] / l).astype(out_ref.dtype)
    # lse rows live on lanes ([B, H, 1, Sq] avoids the 128x lane padding
    # a trailing-1 dim would get); base-2: lse2 = m2 + log2(l)
    lse_ref[0, 0] = (m2 + jnp.log2(l)).reshape(1, -1)


def _fwd_online_kernel(q_ref, k_ref, v_ref, bias_ref, out_ref, lse_ref,
                       m_sc, acc_sc, l_sc, v_sc, *, scale, bq, bk, nk,
                       causal, window=None):
    """Running (m, acc_aug) state; acc_aug lane D is the normalizer, so
    the rescale correction covers acc and l in one [bq, 128] multiply.
    Grid (B, H, nq, nk)."""
    b, h = pl.program_id(0), pl.program_id(1)
    qi, ki = pl.program_id(2), pl.program_id(3)
    d = v_ref.shape[-1]
    aug = v_sc is not None

    if aug:
        @pl.when((b == 0) & (h == 0) & (qi == 0) & (ki == 0))
        def _once():
            v_sc[:] = jnp.zeros_like(v_sc)
            v_sc[:, d:d + 1] = jnp.ones((v_sc.shape[0], 1), v_sc.dtype)

    @pl.when(ki == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        acc_sc[:] = jnp.zeros_like(acc_sc)
        if not aug:
            l_sc[:] = jnp.zeros_like(l_sc)

    @pl.when(_block_live(causal, qi, ki, bq, bk, window))
    def _fold():
        q = (q_ref[0, 0].astype(jnp.float32) * (scale * _LOG2E)).astype(
            q_ref.dtype)
        s2 = jax.lax.dot_general(
            q, k_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # [bq, bk]
        if causal:
            s2 = _causal_mask(s2, qi, ki, bq, bk, window)
        if bias_ref is not None:
            s2 = s2 + _bias2(bias_ref)
        m_prev = m_sc[:, :1]                                # [bq, 1]
        m_cur = jnp.max(s2, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        corr = jnp.exp2(m_prev - m_new)
        arg = s2 - m_new
        m_sc[:, :1] = m_new
        if aug:
            v_sc[:, :d] = v_ref[0, 0].astype(v_sc.dtype)
            p = jnp.exp2(arg).astype(v_sc.dtype)
            acc_sc[:] = acc_sc[:] * corr + jax.lax.dot_general(
                p, v_sc[:], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        else:
            p = jnp.exp2(arg).astype(v_ref.dtype)
            acc_sc[:] = acc_sc[:] * corr + jax.lax.dot_general(
                p, v_ref[0, 0], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            l_sc[:, :1] = l_sc[:, :1] * corr + jnp.sum(
                p.astype(jnp.float32), axis=-1, keepdims=True)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = acc_sc[:, d:d + 1] if aug else l_sc[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        out_ref[0, 0] = (acc_sc[:, :d] / l).astype(out_ref.dtype)
        lse_ref[0, 0] = (m_sc[:, :1] + jnp.log2(l)).reshape(1, -1)


def _fwd_pallas(q, k, v, bias, scale, causal, bq, bk, interpret,
                window=None):
    B, H, Sq, D = q.shape
    Sk, Dv = k.shape[2], v.shape[3]
    # grouped queries: query head h reads KV head h // rep through the
    # index map, so the KV is never repeated in memory
    rep = H // k.shape[1]
    aug = Dv < _LANES
    bq, bk = _block_sizes(Sq, Sk, bq, bk, per_elem_bytes=6,
                          causal=causal)
    nq, nk = Sq // bq, Sk // bk
    single = nk == 1
    kblock = _live_k_block(window, bq, bk) if window is not None \
        else (lambda i, j: j)

    if rep == 1 and window is None:
        # one KV head a query head, every block: the plain map (an
        # integer division in an index map is paid at every grid step
        # and in every compile, so it is there only where heads group)
        def kv_map(b, h, i, *j):
            return (b, h, j[0], 0) if j else (b, h, 0, 0)
    else:
        def kv_map(b, h, i, *j):
            return (b, h // rep, kblock(i, j[0]), 0) if j \
                else (b, h // rep, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, bq, D), lambda b, h, i, *j: (b, h, i, 0)),
        pl.BlockSpec((1, 1, bk, D), kv_map),
        pl.BlockSpec((1, 1, bk, Dv), kv_map),
    ]
    args = [q, k, v]
    if bias is not None:
        in_specs.append(
            pl.BlockSpec((1, 1, 1, bk), lambda b, h, i, *j:
                         (b, 0, 0, j[0]) if j else (b, 0, 0, 0)))
        args.append(bias)

    if single:
        body = functools.partial(_fwd_single_kernel, scale=scale, bq=bq,
                                 causal=causal, window=window)
        grid = (B, H, nq)
        scratch = [pltpu.VMEM((bk, _LANES), v.dtype)] if aug else []
        n_sc = len(scratch)

        def kern(q_ref, k_ref, v_ref, *rest):
            bias_ref, t = (rest[0], rest[1:]) if bias is not None \
                else (None, rest)
            out_ref, lse_ref = t[0], t[1]
            v_sc = t[2] if n_sc else None
            body(q_ref, k_ref, v_ref, bias_ref, out_ref, lse_ref, v_sc)
    else:
        body = functools.partial(_fwd_online_kernel, scale=scale, bq=bq,
                                 bk=bk, nk=nk, causal=causal,
                                 window=window)
        grid = (B, H, nq, nk)
        scratch = [
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES if aug else Dv), jnp.float32),
        ]
        if aug:
            scratch.append(pltpu.VMEM((bk, _LANES), v.dtype))
        else:
            scratch.append(pltpu.VMEM((bq, _LANES), jnp.float32))

        def kern(q_ref, k_ref, v_ref, *rest):
            bias_ref, t = (rest[0], rest[1:]) if bias is not None \
                else (None, rest)
            out_ref, lse_ref, m_sc, acc_sc, third = t
            l_sc, v_sc = (None, third) if aug else (third, None)
            body(q_ref, k_ref, v_ref, bias_ref, out_ref, lse_ref,
                 m_sc, acc_sc, l_sc, v_sc)
    out, lse = pl.pallas_call(
        kern,
        name="flash_attention_fwd",
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, bq, Dv), lambda b, h, i, *j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, 1, bq), lambda b, h, i, *j: (b, h, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sq, Dv), q.dtype),
            jax.ShapeDtypeStruct((B, H, 1, Sq), jnp.float32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
    )(*args)
    return out, lse


# --------------------------------------------------------------- backward

def _bwd_single_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref,
                       delta_ref, dq_ref, dk_ref, dv_ref, dk_sc, dv_sc,
                       *, scale, bq, causal, nq):
    """Combined dq/dk/dv when Sk fits one tile: p recomputed once, dq
    streamed per q-block, dk/dv accumulated in VMEM. Grid (B, H, nq)."""
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    q_raw = q_ref[0, 0]                                     # [bq, D]
    k_blk = k_ref[0, 0]                                     # [Sk, D]
    v_blk = v_ref[0, 0]
    do = do_ref[0, 0]
    lse = lse_ref[0, 0].reshape(-1, 1)                      # [bq, 1]
    delta = delta_ref[0, 0].reshape(-1, 1)
    q2 = (q_raw.astype(jnp.float32) * (scale * _LOG2E)).astype(q_raw.dtype)
    s2 = jax.lax.dot_general(
        q2, k_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                 # [bq, Sk]
    if causal:
        s2 = _causal_mask(s2, i, 0, bq, k_ref.shape[2])
    arg = s2 - lse
    if bias_ref is not None:
        arg = arg + _bias2(bias_ref)
    p = jnp.exp2(arg)                                       # [bq, Sk] f32
    pb = p.astype(do.dtype)
    dv_sc[:] = dv_sc[:] + jax.lax.dot_general(
        pb, do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                 # [Sk, D]
    dp = jax.lax.dot_general(
        do, v_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                 # [bq, Sk]
    ds = (p * (dp - delta) * scale).astype(k_blk.dtype)     # fused chain
    dq_ref[0, 0] = jax.lax.dot_general(
        ds, k_blk, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dq_ref.dtype)
    dk_sc[:] = dk_sc[:] + jax.lax.dot_general(
        ds, q_raw, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                 # [Sk, D]

    @pl.when(i == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_sc[:].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_sc, *, scale, bq, bk, nk, causal):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    @pl.when(_block_live(causal, qi, ki, bq, bk))
    def _fold():
        q_raw = q_ref[0, 0]                                # [bq, D]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0].reshape(-1, 1)                 # [1,bq]->[bq,1]
        delta = delta_ref[0, 0].reshape(-1, 1)
        k_blk = k_ref[0, 0]                                # [bk, D]
        v_blk = v_ref[0, 0]
        q2 = (q_raw.astype(jnp.float32) * (scale * _LOG2E)).astype(
            q_raw.dtype)
        s2 = jax.lax.dot_general(
            q2, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if causal:
            s2 = _causal_mask(s2, qi, ki, bq, bk)
        arg = s2 - lse
        if bias_ref is not None:
            arg = arg + _bias2(bias_ref)
        p = jnp.exp2(arg)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(k_blk.dtype)
        dq_sc[:] = dq_sc[:] + jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_sc[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_sc, dv_sc, *, scale, bq, bk, nq, causal):
    ki, qi = pl.program_id(2), pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    @pl.when(_block_live(causal, qi, ki, bq, bk))
    def _fold():
        k_blk = k_ref[0, 0]                                # [bk, D]
        v_blk = v_ref[0, 0]
        q_raw = q_ref[0, 0]                                # [bq, D]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0].reshape(-1, 1)                 # [1,bq]->[bq,1]
        delta = delta_ref[0, 0].reshape(-1, 1)
        q2 = (q_raw.astype(jnp.float32) * (scale * _LOG2E)).astype(
            q_raw.dtype)
        s2 = jax.lax.dot_general(
            q2, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bq, bk]
        if causal:
            s2 = _causal_mask(s2, qi, ki, bq, bk)
        arg = s2 - lse
        if bias_ref is not None:
            arg = arg + _bias2(bias_ref)
        p = jnp.exp2(arg)
        dv_sc[:] = dv_sc[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(q_raw.dtype)
        dk_sc[:] = dk_sc[:] + jax.lax.dot_general(
            ds, q_raw, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_sc[:].astype(dv_ref.dtype)


def _bwd_pallas(q, k, v, bias, scale, causal, bq, bk, interpret,
                out, lse, do):
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    # the backward holds ~2x the [bq, Sk]-class intermediates of the
    # forward (s, p, dp, ds): budget with 12 bytes/elem
    bq, bk = _block_sizes(Sq, Sk, bq, bk, per_elem_bytes=12,
                          causal=causal)
    nq, nk = Sq // bq, Sk // bk
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, :, None, :]                # [B, H, 1, Sq]

    if nk == 1:
        qspec = pl.BlockSpec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0))
        kspec = pl.BlockSpec((1, 1, bk, D), lambda b, h, i: (b, h, 0, 0))
        rspec = pl.BlockSpec((1, 1, 1, bq), lambda b, h, i: (b, h, 0, i))
        body = functools.partial(_bwd_single_kernel, scale=scale, bq=bq,
                                 causal=causal, nq=nq)
        specs = [qspec, kspec, kspec]
        args = [q, k, v]
        if bias is not None:
            specs.append(
                pl.BlockSpec((1, 1, 1, bk), lambda b, h, i: (b, 0, 0, 0)))
            args.append(bias)
            kern = body
        else:
            def kern(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dq_ref, dk_ref, dv_ref, dk_sc, dv_sc):
                body(q_ref, k_ref, v_ref, None, do_ref, lse_ref,
                     delta_ref, dq_ref, dk_ref, dv_ref, dk_sc, dv_sc)
        dq, dk, dv = pl.pallas_call(
            kern,
            name="flash_attention_bwd",
            grid=(B, H, nq),
            in_specs=specs + [qspec, rspec, rspec],
            out_specs=[qspec, kspec, kspec],
            out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                       jax.ShapeDtypeStruct(k.shape, k.dtype),
                       jax.ShapeDtypeStruct(v.shape, v.dtype)],
            scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                            pltpu.VMEM((bk, D), jnp.float32)],
            interpret=interpret,
        )(*args, do, lse, delta)
        return dq, dk, dv

    qspec = pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0))
    kspec_i = pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0))
    rspec = pl.BlockSpec((1, 1, 1, bq), lambda b, h, i, j: (b, h, 0, i))

    dq_body = functools.partial(_dq_kernel, scale=scale, bq=bq, bk=bk,
                                nk=nk, causal=causal)
    dq_specs = [qspec, kspec_i, kspec_i]
    dq_args = [q, k, v]
    if bias is not None:
        dq_specs.append(
            pl.BlockSpec((1, 1, 1, bk), lambda b, h, i, j: (b, 0, 0, j)))
        dq_args.append(bias)
        dq_kern = dq_body
    else:
        def dq_kern(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dq_ref, dq_sc):
            dq_body(q_ref, k_ref, v_ref, None, do_ref, lse_ref, delta_ref,
                    dq_ref, dq_sc)
    dq = pl.pallas_call(
        dq_kern,
        name="flash_attention_bwd_dq",
        grid=(B, H, nq, nk),
        in_specs=dq_specs + [qspec, rspec, rspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
    )(*dq_args, do, lse, delta)

    # dkv: k-block is the outer (carried) dim, q-blocks stream innermost
    kspec_o = pl.BlockSpec((1, 1, bk, D), lambda b, h, j, i: (b, h, j, 0))
    qspec_i = pl.BlockSpec((1, 1, bq, D), lambda b, h, j, i: (b, h, i, 0))
    rspec_i = pl.BlockSpec((1, 1, 1, bq), lambda b, h, j, i: (b, h, 0, i))
    dkv_body = functools.partial(_dkv_kernel, scale=scale, bq=bq, bk=bk,
                                 nq=nq, causal=causal)
    dkv_specs = [qspec_i, kspec_o, kspec_o]
    dkv_args = [q, k, v]
    if bias is not None:
        dkv_specs.append(
            pl.BlockSpec((1, 1, 1, bk), lambda b, h, j, i: (b, 0, 0, j)))
        dkv_args.append(bias)
        dkv_kern = dkv_body
    else:
        def dkv_kern(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dk_ref, dv_ref, dk_sc, dv_sc):
            dkv_body(q_ref, k_ref, v_ref, None, do_ref, lse_ref, delta_ref,
                     dk_ref, dv_ref, dk_sc, dv_sc)
    dk, dv = pl.pallas_call(
        dkv_kern,
        name="flash_attention_bwd_dkv",
        grid=(B, H, nk, nq),
        in_specs=dkv_specs + [qspec_i, rspec_i, rspec_i],
        out_specs=[kspec_o, kspec_o],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        interpret=interpret,
    )(*dkv_args, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------- public entry

def _xla_attention(q, k, v, bias, scale, causal, window=None):
    """Composite fallback: identical math, materialized scores. Grouped
    queries fold into ``[B, Hkv, rep, Sq, D]`` against the KV heads they
    share."""
    B, H, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    rep = H // Hkv
    qg = q.reshape(B, Hkv, rep, Sq, D)
    s = jnp.einsum("bgrqd,bgkd->bgrqk", qg, k) * scale
    if bias is not None:
        # match the Pallas path's constant-mask contract (zero cotangent)
        s = s + jax.lax.stop_gradient(bias).astype(s.dtype)[:, :, None]
    if causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, (Sq, Sk), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (Sq, Sk), 1)
        keep = rows >= cols
        if window is not None:
            keep = keep & (rows - cols < window)
        s = jnp.where(keep, s, _NEG_INF)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bgrqk,bgkd->bgrqd", p, v).reshape(
        B, H, Sq, v.shape[-1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash(q, k, v, bias, scale, causal, bq, bk, interpret, window):
    out, _ = _fwd_pallas(q, k, v, bias, scale, causal, bq, bk, interpret,
                         window)
    return out


def _flash_fwd(q, k, v, bias, scale, causal, bq, bk, interpret, window):
    out, lse = _fwd_pallas(q, k, v, bias, scale, causal, bq, bk, interpret,
                           window)
    return out, (q, k, v, bias, out, lse)


def _flash_bwd(scale, causal, bq, bk, interpret, window, res, do):
    q, k, v, bias, out, lse = res
    if window is not None or q.shape[1] != k.shape[1] \
            or v.shape[-1] != q.shape[-1]:
        raise NotImplementedError(
            "flash_attention's Pallas backward has neither a window, "
            "grouped queries nor a value width of its own (forward only: "
            "serving prefill); train through impl='xla'")
    dq, dk, dv = _bwd_pallas(q, k, v, bias, scale, causal, bq, bk,
                             interpret, out, lse, do)
    dbias = None if bias is None else jnp.zeros_like(bias)
    return dq, dk, dv, dbias


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, bias=None, scale=None, causal=False,
                    impl=None, block_q=None, block_k=None, mesh=None,
                    window=None):
    """Blockwise fused attention. q [B,H,Sq,D], k [B,Hkv,Sk,D], v
    [B,Hkv,Sk,Dv] with ``H`` a multiple of ``Hkv`` (query head h reads KV
    head ``h // (H // Hkv)``; the KV is not repeated in memory; the
    values may be narrower than the keys, as latent attention's 128
    against 192), optional
    additive key bias [B,1,1,Sk] (constant — zero cotangent). ``window``
    (with ``causal``) keeps key j for query i iff ``i - window < j <=
    i``; blocks wholly outside the band are skipped as the causal
    triangle's are. Returns [B,H,Sq,Dv]. impl: None (auto), "pallas",
    "interpret", "xla". The Pallas backward raises for a window or
    grouped queries. Under ``mesh`` the kernel runs per shard (batch
    over the data axes, heads over tp); the composite is left to
    GSPMD."""
    if scale is None or scale == 0.0:
        scale = float(q.shape[-1]) ** -0.5
    if q.shape[1] % k.shape[1] or k.shape[1] != v.shape[1]:
        raise ValueError(
            f"flash_attention: {q.shape[1]} query heads do not divide "
            f"into {k.shape[1]} key / {v.shape[1]} value heads")
    window = int(window) if window else None
    if window is not None and not causal:
        raise ValueError("flash_attention: a window needs causal=True")
    reason = "requested" if impl else "backend"
    requested, impl = impl, impl or _dispatch.auto_impl()
    if bias is not None and (bias.ndim != 4 or bias.shape[1] != 1
                             or bias.shape[2] != 1):
        if requested in ("pallas", "interpret"):
            raise ValueError(
                f"flash_attention impl={requested!r} supports only a "
                f"[B, 1, 1, Sk] key bias, got {tuple(bias.shape)}; use a "
                f"key mask (+ causal=True for causality) or impl='xla'")
        if impl != "xla":
            # general [B,H,Sq,Sk] bias: the kernel has no operand for
            # it, so the composite runs even on a TPU
            impl, reason = "xla", "general_bias"
    with _dispatch.resolved("flash_attention", impl, reason):
        if impl == "xla":
            return _xla_attention(q, k, v, bias, scale, causal, window)

        def kernel(q, k, v, bias):
            return _flash(q, k, v, bias, float(scale), bool(causal),
                          block_q, block_k, impl == "interpret", window)

        bhsd = ("batch", "heads", None, None)
        return _dispatch.per_shard(
            kernel, mesh, (q, k, v, bias),
            (bhsd, bhsd, bhsd, ("batch", None, None, None)), bhsd)
