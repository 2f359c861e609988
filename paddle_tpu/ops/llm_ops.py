"""Ops of today's decoder blocks that the GPT-2 family never needed:
RMSNorm, rotary position embedding, and a product that takes operands
in the weights' (lower) precision and accumulates in float32.

The statistics of the norm and the rotary tables are float32 whatever
the weights are held in; the rotary table's inverse frequencies are an
attribute, computed once from the model's config by the builder
(``models/mellum.rope_inv_freq``), so that one op serves plain and YaRN
layers alike.
"""
import jax
import jax.numpy as jnp

from ..framework.registry import register_op
from .common import x_of


@register_op("rms_norm", grad=False, infer_shape=False)
def rms_norm(ctx, ins, attrs):
    """Y = X / sqrt(mean(X^2, last axis) + epsilon) * Scale, statistics
    in float32; Y float32."""
    x = x_of(ins).astype(jnp.float32)
    scale = x_of(ins, "Scale").astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return {"Y": x * jax.lax.rsqrt(var + float(attrs["epsilon"])) * scale}


@register_op("rotary_embedding", grad=False, infer_shape=False)
def rotary_embedding(ctx, ins, attrs):
    """Rotate X [B, H, S, D] by the angles ``Pos[b, s] * inv_freq``
    (half-split layout: lane i pairs with lane i + D/2); cos and sin are
    float32 and scaled by ``attention_factor`` (YaRN's; 1 for plain
    rotary). Pos [B, S] int32; attrs: inv_freq (D/2 floats),
    attention_factor."""
    x = x_of(ins)
    pos = x_of(ins, "Pos").astype(jnp.float32)
    inv_freq = jnp.asarray(attrs["inv_freq"], jnp.float32)
    factor = float(attrs.get("attention_factor", 1.0))
    angles = pos[:, None, :, None] * inv_freq              # [B, 1, S, D/2]
    cos = jnp.cos(angles) * factor
    sin = jnp.sin(angles) * factor
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return {"Out": out.astype(x.dtype)}


@register_op("dense_acc32", grad=False, infer_shape=False)
def dense_acc32(ctx, ins, attrs):
    """Out = X @ W over X's last axis: X is rounded to W's dtype (the
    precision the weights are held in), the product accumulates in
    float32 and Out is float32. X [..., d], W [d, n]."""
    x, w = x_of(ins), x_of(ins, "W")
    return {"Out": jax.lax.dot_general(
        x.astype(w.dtype), w, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)}
