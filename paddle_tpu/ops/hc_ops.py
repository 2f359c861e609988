"""Ops of manifold-constrained hyper-connections (mHC; Xie et al.,
DeepSeek 2025): a residual stream widened to ``n`` streams, each sublayer
reading a learned mix of them and writing back through another.

The streams of a token are kept flat, ``[..., n * C]`` float32 (stream
``i`` at lanes ``i * C .. (i + 1) * C``), so that the norm over the
whole state is one reduction and a device trace tells the mechanism's
operations from the rest by that width alone. For the flattened state
``x`` and its RMS-normalised copy ``xbar`` (no gain)::

    H_pre  = sigmoid(a_pre * xbar phi_pre + b_pre)            [n]
    H_post = 2 sigmoid(a_post * xbar phi_post + b_post)       [n]
    H_res  = sinkhorn(clamp(a_res * mat(xbar phi_res) + b_res))  [n, n]

``sinkhorn`` takes the exponential, then alternates ``iters`` row and
column normalisations, each with ``hc_eps`` in its denominator, so that
``H_res`` is doubly stochastic to within ``hc_eps``. The sublayer ``F``
reads ``sum_i H_pre[i] x_i`` and the streams become ``x_i <- sum_j
H_res[i, j] x_j + H_post[i] F(.)``. A head mix ``sigmoid(a xbar phi +
b)`` [n] reduces the streams to one after the last layer. ``phi`` is
float32 and its product is taken at ``precision=highest``: the whole of
the mix is float32.
"""
import jax
import jax.numpy as jnp

from ..framework.registry import register_op
from .common import x_of

_HI = jax.lax.Precision.HIGHEST


def _projection(xf, phi, eps):
    """``xbar phi`` for the flat streams ``xf`` [..., n * C]."""
    xf = xf.astype(jnp.float32)
    xbar = xf * jax.lax.rsqrt(
        jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return jnp.dot(xbar, phi.astype(jnp.float32), precision=_HI)


def sinkhorn(logits, iters, hc_eps):
    """exp, then ``iters`` rounds of rows then columns normalised, over
    the last two axes."""
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + hc_eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + hc_eps)
    return m


def mix_weights(xf, phi, alpha, bias, n, eps, hc_eps, iters, lo, hi):
    """``(H_pre [..., n], H_post [..., n], H_res [..., n, n])`` of the
    flat streams ``xf``: ``phi`` [n * C, n * (n + 2)] (columns pre |
    post | res, res row-major), ``alpha`` [3], ``bias`` [n * (n + 2)]."""
    proj = _projection(xf, phi, eps)
    alpha, bias = alpha.astype(jnp.float32), bias.astype(jnp.float32)
    pre = jax.nn.sigmoid(alpha[0] * proj[..., :n] + bias[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * proj[..., n:2 * n]
                                + bias[n:2 * n])
    res = alpha[2] * proj[..., 2 * n:] + bias[2 * n:]
    res = jnp.clip(res.reshape(res.shape[:-1] + (n, n)), lo, hi)
    return pre, post, sinkhorn(res, iters, hc_eps)


def streams(xf, n):
    """``[..., n * C]`` -> ``[..., n, C]``."""
    return xf.reshape(xf.shape[:-1] + (n, xf.shape[-1] // n))


@register_op("hc_pre", grad=False, infer_shape=False)
def hc_pre(ctx, ins, attrs):
    """The mix weights of a sublayer and the mix it reads. inputs: X
    [..., n * C] float32 (the flat streams), Phi [n * C, n * (n + 2)],
    Alpha [3], Bias [n * (n + 2)]; attrs: n, epsilon (the RMS norm's),
    hc_eps, iters, clamp_min, clamp_max. outputs: H [..., C] float32
    (``sum_i H_pre[i] x_i``), Post [..., n], Res [..., n, n]."""
    n = int(attrs["n"])
    xf = x_of(ins).astype(jnp.float32)
    with jax.named_scope("hc/pre"):
        pre, post, res = mix_weights(
            xf, x_of(ins, "Phi"), x_of(ins, "Alpha"), x_of(ins, "Bias"), n,
            float(attrs["epsilon"]), float(attrs["hc_eps"]),
            int(attrs["iters"]), float(attrs["clamp_min"]),
            float(attrs["clamp_max"]))
        h = jnp.einsum("...n,...nc->...c", pre, streams(xf, n),
                       precision=_HI)
    return {"H": h, "Post": post, "Res": res}


@register_op("hc_post", grad=False, infer_shape=False)
def hc_post(ctx, ins, attrs):
    """The write-back: ``x_i <- sum_j Res[i, j] x_j + Post[i] Y``.
    inputs: X [..., n * C], Y [..., C], Post [..., n], Res [..., n, n];
    outputs: Out [..., n * C] float32."""
    post, res = x_of(ins, "Post"), x_of(ins, "Res")
    n = post.shape[-1]
    xf = x_of(ins).astype(jnp.float32)
    y = x_of(ins, "Y").astype(jnp.float32)
    with jax.named_scope("hc/post"):
        out = jnp.einsum("...ij,...jc->...ic", res, streams(xf, n),
                         precision=_HI) + post[..., :, None] * y[..., None, :]
    return {"Out": out.reshape(xf.shape)}


@register_op("hc_head", grad=False, infer_shape=False)
def hc_head(ctx, ins, attrs):
    """The head mix: ``sum_i sigmoid(a xbar phi + b)[i] x_i``. inputs: X
    [..., n * C], Phi [n * C, n], Alpha [1], Bias [n]; attrs: epsilon.
    outputs: Out [..., C] float32."""
    phi = x_of(ins, "Phi")
    n = phi.shape[-1]
    xf = x_of(ins).astype(jnp.float32)
    with jax.named_scope("hc/head"):
        proj = _projection(xf, phi, float(attrs["epsilon"]))
        w = jax.nn.sigmoid(x_of(ins, "Alpha").astype(jnp.float32)[0] * proj
                           + x_of(ins, "Bias").astype(jnp.float32))
        out = jnp.einsum("...n,...nc->...c", w, streams(xf, n),
                         precision=_HI)
    return {"Out": out}
