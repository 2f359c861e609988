"""Mixture-of-Experts op: GShard-style expert parallelism over "ep".

No reference counterpart (the reference predates MoE) — this is a
north-star extra alongside sequence parallelism: the "ep" mesh axis must
be a first-class scaling dimension. The formulation is the canonical
GShard/Switch einsum dance: top-1 gating, capacity-bounded one-hot
dispatch, per-expert batched matmuls on tensors whose leading expert dim
is sharded over "ep" (sharding_constraint), so GSPMD inserts the
all-to-alls on the dispatch/combine einsums — no hand-written collectives
and one XLA module.

Outputs the combined tokens plus the standard load-balance auxiliary loss
(mean_gate * mean_dispatch * E^2).
"""
import jax
import jax.numpy as jnp

from ..framework.registry import register_op
from .common import x_of


@register_op("switch_moe", infer_shape=False)
def switch_moe(ctx, ins, attrs):
    """inputs: X [N, d], GateW [d, E], W1 [E, d, h], B1 [E, h],
    W2 [E, h, d], B2 [E, d]; attrs: capacity_factor (default 1.25).
    outputs: Out [N, d], AuxLoss [] (load-balance loss)."""
    x = x_of(ins)
    gate_w = x_of(ins, "GateW")
    w1 = x_of(ins, "W1")
    b1 = x_of(ins, "B1")
    w2 = x_of(ins, "W2")
    b2 = x_of(ins, "B2")
    cap_factor = float(attrs.get("capacity_factor", 1.25))
    N, d = x.shape
    E = gate_w.shape[1]
    C = max(int(cap_factor * N / E), 1)

    logits = x @ gate_w                           # [N, E]
    gates = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(gates, axis=-1)           # [N] top-1
    gate_val = jnp.max(gates, axis=-1)            # [N]

    onehot = jax.nn.one_hot(expert, E, dtype=x.dtype)       # [N, E]
    # 0-based position of each token within its expert's queue: the
    # running count of same-expert tokens up to and including this one
    rank = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=-1)
    pos_in_expert = (rank - 1.0).astype(jnp.int32)          # [N]
    keep = pos_in_expert < C
    # dispatch tensor [N, E, C]
    dispatch = (onehot * keep[:, None].astype(x.dtype))[:, :, None] * \
        jax.nn.one_hot(jnp.clip(pos_in_expert, 0, C - 1), C,
                       dtype=x.dtype)[:, None, :]

    def shard_ep(a):
        if ctx.mesh is not None and "ep" in ctx.mesh.axis_names and \
                not ctx.abstract and a.shape[0] % ctx.mesh.shape["ep"] == 0:
            from jax.sharding import NamedSharding, PartitionSpec as P
            return jax.lax.with_sharding_constraint(
                a, NamedSharding(ctx.mesh,
                                 P(*(("ep",) + (None,) * (a.ndim - 1)))))
        return a

    expert_in = jnp.einsum("nec,nd->ecd", dispatch, x)      # [E, C, d]
    expert_in = shard_ep(expert_in)
    h = jax.nn.gelu(jnp.einsum("ecd,edh->ech", expert_in, w1) +
                    b1[:, None, :])
    expert_out = jnp.einsum("ech,ehd->ecd", h, w2) + b2[:, None, :]
    expert_out = shard_ep(expert_out)
    combine = dispatch * gate_val[:, None, None]
    out = jnp.einsum("nec,ecd->nd", combine, expert_out)    # [N, d]

    # GShard/Switch load-balance aux loss: E * sum_e f_e * P_e
    # (== mean(f*P) * E^2); 1.0 at perfectly uniform routing for any E
    density = jnp.mean(onehot, axis=0)            # fraction routed / expert
    density_proxy = jnp.mean(gates, axis=0)       # mean gate prob / expert
    aux = jnp.mean(density * density_proxy) * (E * E)
    return {"Out": out, "AuxLoss": aux.reshape(())}


@register_op("routed_experts", grad=False, infer_shape=False)
def routed_experts(ctx, ins, attrs):
    """Top-k routed SwiGLU experts with no capacity and no dropped token
    (``kernels/moe_experts.py``). inputs: X [..., d] float32, RouterW
    [d, E] float32, WGate, WUp [E, d, f] and WDown [E, f, d] in the dtype
    the expert products run in (bfloat16 when served), optional Valid
    [...] (bool or int: tokens that are padding route nowhere); attrs:
    top_k, norm_topk_prob, impl ("" = auto). The router is float32
    throughout: logits at ``precision=highest``, softmax over all E,
    then the k largest, renormalised to sum 1 with ``norm_topk_prob``.
    outputs: Out [..., d] float32; Counts [E] int32, the assignments
    each expert got."""
    from ..kernels.moe_experts import routed_experts as _experts

    x = x_of(ins)
    lead, d = x.shape[:-1], x.shape[-1]
    x2 = x.reshape(-1, d).astype(jnp.float32)
    k = int(attrs["top_k"])
    valid = ins.get("Valid")
    valid = valid[0].reshape(-1).astype(bool) if valid else None
    with jax.named_scope("moe/router"):
        logits = jnp.dot(x2, x_of(ins, "RouterW").astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_i = jax.lax.top_k(probs, k)
        if attrs.get("norm_topk_prob", True):
            top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    out, counts = _experts(x2, top_i, top_w, x_of(ins, "WGate"),
                           x_of(ins, "WUp"), x_of(ins, "WDown"),
                           valid=valid, impl=attrs.get("impl") or None)
    return {"Out": out.reshape(lead + (d,)), "Counts": counts}
