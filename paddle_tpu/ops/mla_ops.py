"""Ops of multi-head latent attention (MLA; DeepSeek-V2, 2024) over the
latent a position keeps: ``c_kv`` [C], normalised, and one rotary key
``k_pe`` [R] that every head shares.

``mla_prefill`` expands the latent into every head's key and value (the
key up-projection's ``dn`` lanes beside the shared rotary key: ``dn + R``
wide against values ``dv`` wide) and runs the flash forward over the
fed sequence. ``mla_decode`` absorbs the up-projections into the query
and the output instead and reads the latent pool through the latent
decode kernel (``kernels/mla_decode.py``). The up-projection ``W_kvb``
[C, H * (dn + dv)] holds, for head h, ``dn`` key columns then ``dv``
value columns. Its products take the weights' dtype and accumulate in
float32.
"""
import jax
import jax.numpy as jnp

from ..framework.registry import register_op
from .common import named, x_of


def _up(w_kvb, heads, dn):
    """``(W_UK [C, H, dn], W_UV [C, H, dv])`` of the up-projection."""
    w = w_kvb.reshape(w_kvb.shape[0], heads, -1)
    return w[:, :, :dn], w[:, :, dn:]


@register_op("mla_prefill", grad=False, infer_shape=False)
def mla_prefill(ctx, ins, attrs):
    """Causal latent attention over a fed sequence, expanded. inputs:
    QNope [B, H, S, dn], QPe [B, H, S, R] (rotated), CKV [B, S, C]
    (normalised), KPe [B, 1, S, R] (rotated), WKvb [C, H * (dn + dv)];
    attrs: scale, scope. outputs: Out [B, S, H * dv] float32."""
    from ..kernels.flash_attention import flash_attention
    q_nope, q_pe = x_of(ins, "QNope"), x_of(ins, "QPe")
    B, H, S, dn = q_nope.shape
    w = x_of(ins, "WKvb")
    w_uk, w_uv = _up(w, H, dn)
    dt = w.dtype
    with named(attrs.get("scope")):
        c = x_of(ins, "CKV").astype(dt)
        k_nope = jnp.einsum("bsc,chd->bhsd", c, w_uk,
                            preferred_element_type=jnp.float32)
        v = jnp.einsum("bsc,chd->bhsd", c, w_uv,
                       preferred_element_type=jnp.float32)
        k_pe = jnp.broadcast_to(x_of(ins, "KPe"), (B, H, S, q_pe.shape[-1]))
        q = jnp.concatenate([q_nope, q_pe], axis=-1).astype(dt)
        k = jnp.concatenate([k_nope, k_pe], axis=-1).astype(dt)
        out = flash_attention(q, k, v.astype(dt), causal=True,
                              scale=float(attrs["scale"]),
                              mesh=None if ctx.abstract else ctx.mesh)
    return {"Out": out.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(
        B, S, -1)}


@register_op("mla_decode", grad=False, infer_shape=False)
def mla_decode(ctx, ins, attrs):
    """One query a row over a layer's latent pool, absorbed. inputs:
    QNope [B, H, 1, dn], QPe [B, H, 1, R] (rotated), WKvb
    [C, H * (dn + dv)], CPool [N, bs, C], RPool (the rotary keys in the
    pool's stored shape), Tables [B, nblk] int32, Pos [B] int32; attrs:
    scale, impl, scope. outputs: Out [B, 1, H * dv] float32."""
    from ..kernels.mla_decode import mla_decode as _kernel
    q_nope = x_of(ins, "QNope")
    B, H, _, dn = q_nope.shape
    w = x_of(ins, "WKvb")
    w_uk, w_uv = _up(w, H, dn)
    dt = w.dtype
    with named(attrs.get("scope")):
        q_lat = jnp.einsum("bhd,chd->bhc", q_nope[:, :, 0].astype(dt), w_uk,
                           preferred_element_type=jnp.float32)
        o_lat = _kernel(q_lat, x_of(ins, "QPe")[:, :, 0],
                        x_of(ins, "CPool"), x_of(ins, "RPool"),
                        x_of(ins, "Tables"), x_of(ins, "Pos"),
                        float(attrs["scale"]), impl=attrs.get("impl") or None)
        out = jnp.einsum("bhc,chd->bhd", o_lat.astype(dt), w_uv,
                         preferred_element_type=jnp.float32)
    return {"Out": out.reshape(B, 1, -1)}
