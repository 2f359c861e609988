"""Ops of a state-space mixer (Mamba-1): the causal depthwise convolution
in front of the recurrence, and the selective scan itself
(``kernels/selective_scan.py``).

Both take and hand back what a served row keeps between calls: the
convolution the last ``K - 1`` inputs of every channel (its ``Tail``,
stored flat ``[rows, (K - 1) * C]`` with the oldest input first, so that
the lanes are full and a step's shift is a slice), the scan the state
``[rows, N, C]``. ``Length`` says how many tokens of each right-padded
row are real: what comes back is the tail and the state after the row's
last real token, not at the bucket's end.
"""
import jax
import jax.numpy as jnp

from ..framework.registry import register_op
from .common import x_of


def _opt(ins, name):
    got = ins.get(name)
    return got[0] if got else None


@register_op("causal_conv1d", grad=False, infer_shape=False)
def causal_conv1d(ctx, ins, attrs):
    """Out[b, t] = silu(sum_k W[k] * X[b, t - (K - 1) + k] + Bias) over
    each channel alone, the inputs before the row's first coming from
    ``Tail`` (zeros without one). inputs: X [B, L, C] float32, W [K, C],
    Bias [C], optional Tail [B, (K - 1) * C] and Length [B] int32.
    outputs: Out [B, L, C] float32; NewTail [B, (K - 1) * C], the inputs
    at positions ``Length - K + 1 .. Length - 1`` (``L`` for ``Length``
    where it is left out), in Tail's dtype (X's without one)."""
    x = x_of(ins).astype(jnp.float32)
    w = x_of(ins, "W").astype(jnp.float32)
    bias = x_of(ins, "Bias").astype(jnp.float32)
    tail, length = _opt(ins, "Tail"), _opt(ins, "Length")
    rows, seq, ch = x.shape
    k = w.shape[0]
    tail_dt = tail.dtype if tail is not None else x.dtype
    with jax.named_scope("ssm/conv"):
        if seq == 1 and tail is not None and length is None:
            # a decode step: the tail's K - 1 slices of lanes and x
            past = tail.astype(jnp.float32)
            acc = x[:, 0] * w[k - 1] + bias
            for j in range(k - 1):
                acc = acc + past[:, j * ch:(j + 1) * ch] * w[j]
            new_tail = jnp.concatenate(
                [tail[:, ch:], x[:, 0].astype(tail_dt)], axis=1)
            return {"Out": jax.nn.silu(acc)[:, None], "NewTail": new_tail}
        past = jnp.zeros((rows, k - 1, ch), jnp.float32) if tail is None \
            else tail.astype(jnp.float32).reshape(rows, k - 1, ch)
        xp = jnp.concatenate([past, x], axis=1)       # [B, L + K - 1, C]
        acc = bias
        for j in range(k):
            acc = acc + xp[:, j:j + seq] * w[j]
        if length is None:
            new_tail = xp[:, seq:]
        else:
            # position p of x is row p + K - 1 of xp
            new_tail = jax.vmap(
                lambda row, n: jax.lax.dynamic_slice_in_dim(row, n, k - 1, 0)
            )(xp, length.astype(jnp.int32))
        return {"Out": jax.nn.silu(acc),
                "NewTail": new_tail.reshape(rows, (k - 1) * ch).astype(
                    tail_dt)}


@register_op("selective_scan", grad=False, infer_shape=False)
def selective_scan(ctx, ins, attrs):
    """The selective state-space recurrence with its step's softplus,
    its skip and its gate (``kernels/selective_scan.selective_scan``).
    inputs: X, Delta, Z [B, L, C]; B, C [B, L, N]; ALog [C, N]; D,
    DtBias [C]; optional State [B, N, C] (zeros without) and Length [B]
    int32. outputs: Out [B, L, C] float32; NewState [B, N, C] float32,
    after each row's last real token."""
    from ..kernels.selective_scan import selective_scan as _scan
    with jax.named_scope("ssm/scan"):
        out, state = _scan(
            x_of(ins), x_of(ins, "Delta"), x_of(ins, "Z"), x_of(ins, "B"),
            x_of(ins, "C"), x_of(ins, "ALog"), x_of(ins, "D"),
            x_of(ins, "DtBias"), state0=_opt(ins, "State"),
            length=_opt(ins, "Length"))
    return {"Out": out, "NewState": state}


@register_op("dense_acc32_nt", grad=False, infer_shape=False)
def dense_acc32_nt(ctx, ins, attrs):
    """Out = X @ W^T over X's last axis, as ``dense_acc32`` with the
    matrix stored ``[n, d]``: a head tied to the embedding table. X is
    rounded to W's dtype, the product accumulates in float32."""
    x, w = x_of(ins), x_of(ins, "W")
    return {"Out": jax.lax.dot_general(
        x.astype(w.dtype), w, (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)}
