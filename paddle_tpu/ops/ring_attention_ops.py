"""Ring attention: sequence/context parallelism for long sequences.

North-star capability with no reference counterpart (the reference's
sequence story is LoD ops + recurrent_op, bounded by one device's memory —
SURVEY §5.7): attention over a sequence sharded across the "sp" mesh axis,
where no device ever materializes the full [S, S] score matrix OR the full
K/V. The canonical TPU formulation (Ring Attention / blockwise attention):

  - Q stays put, sharded over sp; K/V blocks ROTATE around the sp ring via
    lax.ppermute (neighbor ICI traffic, overlapped with compute by XLA).
  - Each step folds one K/V block into a numerically-stable ONLINE softmax
    accumulator (running max m, normalizer l, weighted value sum acc) —
    flash-attention numerics, so the result is exact, not approximate.
  - sp_steps hops close the ring; the final out = acc / l.

Reverse-mode AD flows through shard_map + scan + ppermute, so the backward
pass is automatically the reverse ring — no hand-written grad.

Without an "sp" axis the lowering computes the same blockwise math in one
pass (exact standard attention), so sp-sharded and single-device runs are
numerically comparable.
"""
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..framework.registry import register_op
from .common import named, x_of

_NEG_INF = -1e30


def _block_fold(q, k_blk, v_blk, bias_blk, scale, m, l, acc,
                row0=None, col0=None):
    """Fold one K/V block into the online-softmax accumulator. With
    (row0, col0) global offsets, a causal mask is synthesized from
    iota — no [S, S] mask tensor ever exists (the point of ring
    attention at long S)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k_blk) * scale
    if bias_blk is not None:
        s = s + bias_blk
    if row0 is not None:
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        cols = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 3)
        s = jnp.where(rows >= cols, s, _NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    corr = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_new = l * corr + jnp.sum(p, axis=-1)
    acc_new = acc * corr[..., None] + jnp.einsum("bhqk,bhkd->bhqd",
                                                 p, v_blk)
    return m_new, l_new, acc_new


@register_op("ring_attention", infer_shape=False)
def ring_attention(ctx, ins, attrs):
    """inputs: Q, K, V [B, H, S, D] (+ optional Bias [B, 1, 1, S] or
    [B, H, S, S] additive mask); attrs: scale (default 1/sqrt(D)).
    output: Out [B, H, S, D]."""
    q = x_of(ins, "Q")
    k = x_of(ins, "K")
    v = x_of(ins, "V")
    bias = ins.get("Bias")
    bias = bias[0] if bias else None
    scale = float(attrs.get("scale", 0.0)) or float(q.shape[-1]) ** -0.5
    causal = bool(attrs.get("causal", False))

    mesh = ctx.mesh
    sp = (mesh.shape["sp"]
          if mesh is not None and "sp" in mesh.axis_names else 1)
    B, H, S, D = q.shape
    if sp > 1 and not ctx.abstract and S % sp:
        raise ValueError(
            f"ring_attention: sequence length {S} is not divisible by the "
            f"sp axis size {sp} — pad the sequence or resize the mesh "
            f"(a silent dense fallback would defeat the memory scaling)")
    use_ring = sp > 1 and not ctx.abstract

    if not use_ring:
        m = jnp.full(q.shape[:3], _NEG_INF, q.dtype)
        l = jnp.zeros(q.shape[:3], q.dtype)
        acc = jnp.zeros(q.shape, q.dtype)
        bias_full = None
        if bias is not None:
            bias_full = jnp.broadcast_to(bias, (B, bias.shape[1],
                                                bias.shape[2], S))
        m, l, acc = _block_fold(q, k, v, bias_full, scale, m, l, acc,
                                row0=0 if causal else None,
                                col0=0 if causal else None)
        return {"Out": acc / l[..., None]}

    qspec = P(None, None, "sp", None)
    # two supported bias layouts under sharding:
    #   [B, 1, 1, S]  key-position mask -> sharded on keys, ROTATES with
    #                 the K/V blocks
    #   [B, H, S, S]  full additive mask -> sharded on the QUERY dim; the
    #                 key-block slice is selected per ring step
    key_bias = bias is None or (bias.shape[1] == 1 and bias.shape[2] == 1)
    if bias is None:
        bias = jnp.zeros((B, 1, 1, S), q.dtype)
    bspec = P(None, None, None, "sp") if key_bias else qspec
    blk = S // sp

    def per_device(q_l, k_l, v_l, bias_l):
        idx = jax.lax.axis_index("sp")
        m = jnp.full(q_l.shape[:3], _NEG_INF, q_l.dtype)
        l = jnp.zeros(q_l.shape[:3], q_l.dtype)
        acc = jnp.zeros(q_l.shape, q_l.dtype)
        ring = [(i, (i + 1) % sp) for i in range(sp)]

        def step(carry, t):
            k_blk, v_blk, b_rot, m, l, acc = carry
            j = (idx - t) % sp
            if key_bias:
                b_blk = b_rot
            else:
                # full bias: columns of this step's key block
                b_blk = jax.lax.dynamic_slice_in_dim(
                    bias_l, j * blk, blk, axis=3)
            if causal:
                # global offsets of this device's query rows and the
                # current key block's columns; step t=0 folds the
                # DIAGONAL block first, so every row is live from the
                # start (the online-softmax all-masked hazard never
                # arises). Blocks entirely ABOVE the diagonal (j > idx)
                # skip the fold — that halves total FLOPs/energy, but
                # NOT wall-clock: the ppermute synchronizes every step
                # and device sp-1 folds on all of them (balancing needs
                # striped block assignment, which would change the
                # user-visible contiguous-shard layout).
                m, l, acc = jax.lax.cond(
                    j <= idx,
                    lambda m, l, acc: _block_fold(
                        q_l, k_blk, v_blk, b_blk, scale, m, l, acc,
                        row0=idx * blk, col0=j * blk),
                    lambda m, l, acc: (m, l, acc),
                    m, l, acc)
            else:
                m, l, acc = _block_fold(q_l, k_blk, v_blk, b_blk, scale,
                                        m, l, acc)
            k_blk = jax.lax.ppermute(k_blk, "sp", ring)
            v_blk = jax.lax.ppermute(v_blk, "sp", ring)
            if key_bias:
                b_rot = jax.lax.ppermute(b_rot, "sp", ring)
            return (k_blk, v_blk, b_rot, m, l, acc), None

        b0 = bias_l if key_bias else bias_l[:, :, :, :blk]
        (k_l, v_l, _, m, l, acc), _ = jax.lax.scan(
            step, (k_l, v_l, b0, m, l, acc), jnp.arange(sp))
        return acc / l[..., None]

    mapped = jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(qspec, qspec, qspec, bspec),
        out_specs=qspec, check_vma=False)
    return {"Out": mapped(q, k, v, bias)}


@register_op("flash_attention", infer_shape=False)
def flash_attention_op(ctx, ins, attrs):
    """Fused attention via the Pallas flash kernel
    (kernels/flash_attention.py) — the TPU-native equivalent of the
    reference's fused CUDA attention
    (operators/fused/multihead_matmul_op.cu). inputs: Q, K, V
    [B, H, S, D] (+ optional additive key Bias [B, 1, 1, S], treated as a
    constant mask); attrs: scale (default 1/sqrt(D)), causal, impl
    ("" = auto: Pallas on TPU, XLA composite elsewhere), window (0 =
    none; with causal, key j is visible to query i iff i - window < j
    <= i). K and V may have fewer heads than Q (grouped queries). Under
    a mesh the kernel runs per shard: batch over the data axes, heads
    over tp."""
    from ..kernels.flash_attention import flash_attention as _fa

    q = x_of(ins, "Q")
    k = x_of(ins, "K")
    v = x_of(ins, "V")
    bias = ins.get("Bias")
    bias = bias[0] if bias else None
    scale = float(attrs.get("scale", 0.0)) or None
    with named(attrs.get("scope")):
        out = _fa(q, k, v, bias, scale=scale,
                  causal=bool(attrs.get("causal", False)),
                  impl=attrs.get("impl") or None,
                  block_q=int(attrs.get("block_q", 0)) or None,
                  block_k=int(attrs.get("block_k", 0)) or None,
                  mesh=None if ctx.abstract else ctx.mesh,
                  window=int(attrs.get("window", 0)) or None)
    return {"Out": out}



@register_op("ulysses_attention", infer_shape=False)
def ulysses_attention(ctx, ins, attrs):
    """Ulysses-style sequence parallelism (the all-to-all alternative to
    the ring): swap the sharded dim from sequence to heads with one
    lax.all_to_all, run FULL attention on H/sp heads per device, swap
    back. Cheaper than the ring when heads divide evenly and the ICI
    all-to-all is fast; same exact math. Same signature as
    ring_attention; requires H % sp == 0."""
    q = x_of(ins, "Q")
    k = x_of(ins, "K")
    v = x_of(ins, "V")
    bias = ins.get("Bias")
    bias = bias[0] if bias else None
    scale = float(attrs.get("scale", 0.0)) or float(q.shape[-1]) ** -0.5
    causal = bool(attrs.get("causal", False))

    mesh = ctx.mesh
    sp = (mesh.shape["sp"]
          if mesh is not None and "sp" in mesh.axis_names else 1)
    B, H, S, D = q.shape
    if sp > 1 and not ctx.abstract and (S % sp or H % sp):
        raise ValueError(
            f"ulysses_attention: S={S} and n_head={H} must both be "
            f"divisible by the sp axis size {sp} (the all-to-all swaps the "
            f"shard dim from sequence to heads); use mechanism='ring' for "
            f"head counts that don't divide")
    use = sp > 1 and not ctx.abstract

    def full_attn(q_, k_, v_, bias_):
        s = jnp.einsum("bhqd,bhkd->bhqk", q_, k_) * scale
        if bias_ is not None:
            s = s + bias_
        if causal:
            # after the all-to-all each device holds FULL sequences for
            # its heads, so plain iota masking applies
            rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 3)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v_)

    if not use:
        return {"Out": full_attn(q, k, v, bias)}

    qspec = P(None, None, "sp", None)
    # bias layouts: [B,1,1,S] key mask -> sharded on keys, gathered
    # locally; [B,H,S,S] per-head mask -> sharded on HEADS (after the
    # all-to-all each device holds exactly its H/sp heads' mask);
    # [B,1,S,S] head-broadcast mask (e.g. causal) -> replicated (its
    # size-1 head axis cannot shard)
    key_bias = bias is None or (bias.shape[1] == 1 and bias.shape[2] == 1)
    head_bcast = (bias is not None and bias.shape[1] == 1
                  and bias.shape[2] > 1)
    if bias is None:
        bias = jnp.zeros((B, 1, 1, S), q.dtype)
    if key_bias:
        bspec = P(None, None, None, "sp")
    elif head_bcast:
        bspec = P(None, None, None, None)
    else:
        bspec = P(None, "sp", None, None)

    def per_device(q_l, k_l, v_l, bias_l):
        def seq_to_heads(a):      # [B, H, S/sp, D] -> [B, H/sp, S, D]
            return jax.lax.all_to_all(a, "sp", split_axis=1,
                                      concat_axis=2, tiled=True)

        qh, kh, vh = seq_to_heads(q_l), seq_to_heads(k_l), seq_to_heads(v_l)
        if key_bias:
            bias_h = jax.lax.all_gather(bias_l, "sp", axis=3, tiled=True)
        else:
            bias_h = bias_l           # already this device's heads
        out_h = full_attn(qh, kh, vh, bias_h)     # [B, H/sp, S, D]
        # heads -> sequence: inverse all_to_all
        return jax.lax.all_to_all(out_h, "sp", split_axis=2,
                                  concat_axis=1, tiled=True)

    mapped = jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(qspec, qspec, qspec, bspec),
        out_specs=qspec, check_vma=False)
    return {"Out": mapped(q, k, v, bias)}
