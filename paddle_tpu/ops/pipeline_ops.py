"""Pipeline-parallel op: GPipe over the "pp" mesh axis.

Capability parity with the reference's pipeline stack (PipelineOptimizer
/root/reference/python/paddle/fluid/optimizer.py:3554, PipelineTrainer +
SectionWorker /root/reference/paddle/fluid/framework/pipeline_trainer.cc:122,
device_worker.h:329): the reference cuts a program into sections placed on
different devices and streams microbatches through scope queues between
section-worker threads.

TPU-native design: stages are UNIFORM (same sub-block, per-stage weight
slices stacked on a leading [S] dim sharded over "pp"), and the schedule is
one shard_map over the mesh — each tick every device runs its stage on its
current microbatch and rotates activations to the next stage via
lax.ppermute (ICI neighbor traffic). A scan over M + S - 1 ticks fills and
drains the pipeline; reverse-mode AD through the scan gives the backward
pipeline (and per-microbatch gradient accumulation) for free. This is the
standard JAX/praxis pipelining recipe rather than a thread/queue port —
XLA sees one static program it can overlap.

Without a "pp" mesh axis the op lowers to a sequential microbatch loop with
identical math, so pipelined and non-pipelined runs are numerically equal
(the parity the reference asserts between pipelined and plain programs).
"""
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..framework.registry import register_op
from .common import x_of


@register_op("pipeline", grad=None, infer_shape=False)
def pipeline_op(ctx, ins, attrs):
    """inputs: X=[batch input [B, ...]], P=[stacked params [S, ...]],
    R=[replicated non-param outer reads]; attrs: sub_block, num_stages,
    num_microbatches, x_name, out_name, p_names, r_names.
    output: Out [B, ...] (stage chain output; in/out shapes must match)."""
    x = x_of(ins)
    stacked = list(ins.get("P", []))
    repl = list(ins.get("R", []))
    S = int(attrs["num_stages"])
    M = int(attrs["num_microbatches"])
    x_name = attrs["x_name"]
    out_name = attrs["out_name"]
    p_names = list(attrs.get("p_names", []))
    r_names = list(attrs.get("r_names", []))
    sub = attrs["sub_block"]

    B = x.shape[0]
    if B % M:
        raise ValueError(f"pipeline: batch {B} not divisible by "
                         f"num_microbatches {M}")
    xs = x.reshape((M, B // M) + x.shape[1:])

    def stage_fn(stage_params, repl_vals, x_mb):
        # strict env: every outer read must arrive via P (stacked params)
        # or R (replicated) — nothing may be closed over from outside the
        # shard_map region (a missing binding raises by name)
        env = {}
        env.update(zip(r_names, repl_vals))
        env.update(zip(p_names, stage_params))
        env[x_name] = x_mb
        ctx.lower_block_ops(sub, env)
        y = env[out_name]
        if y.shape != x_mb.shape or y.dtype != x_mb.dtype:
            raise ValueError(
                f"pipeline stage must be shape/dtype-preserving (uniform "
                f"chain): in {x_mb.shape}/{x_mb.dtype} vs out "
                f"{y.shape}/{y.dtype}")
        return y

    mesh = ctx.mesh
    use_pp = (mesh is not None and "pp" in mesh.axis_names
              and mesh.shape["pp"] == S and S > 1 and not ctx.abstract)

    if not use_pp:
        # sequential fallback: same per-microbatch math, no pp axis
        def chain(x_mb):
            y = x_mb
            for s in range(S):
                y = stage_fn([p[s] for p in stacked], repl, y)
            return y

        return {"Out": jax.lax.map(chain, xs).reshape(x.shape)}

    batch_axis = "dp" if "dp" in mesh.axis_names and \
        xs.shape[1] % mesh.shape["dp"] == 0 else None
    xspec = P(None, batch_axis) if batch_axis else P()

    def per_device(params_local, repl_local, xs_local):
        params_here = [p[0] for p in params_local]   # [1,...] slice -> stage
        idx = jax.lax.axis_index("pp")
        state0 = jnp.zeros(xs_local.shape[1:], xs_local.dtype)
        outbuf0 = jnp.zeros(xs_local.shape, xs_local.dtype)
        fwd_ring = [(i, (i + 1) % S) for i in range(S)]

        def tick(carry, t):
            state, outbuf = carry
            x_in = jax.lax.dynamic_index_in_dim(
                xs_local, jnp.clip(t, 0, M - 1), keepdims=False)
            inp = jnp.where(idx == 0, x_in, state)
            y = stage_fn(params_here, repl_local, inp)
            ot = t - (S - 1)
            write = jnp.logical_and(
                idx == S - 1, jnp.logical_and(ot >= 0, ot < M))
            slot = jnp.clip(ot, 0, M - 1)
            cur = jax.lax.dynamic_index_in_dim(outbuf, slot, keepdims=False)
            outbuf = jax.lax.dynamic_update_index_in_dim(
                outbuf, jnp.where(write, y, cur), slot, 0)
            nxt = jax.lax.ppermute(y, "pp", fwd_ring)
            return (nxt, outbuf), None

        (_, outbuf), _ = jax.lax.scan(
            tick, (state0, outbuf0), jnp.arange(M + S - 1))
        # only the last stage holds real outputs; psum replicates over pp
        outbuf = jax.lax.psum(
            jnp.where(idx == S - 1, outbuf, jnp.zeros_like(outbuf)), "pp")
        return outbuf

    mapped = jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(tuple(P("pp") for _ in stacked),
                  tuple(P() for _ in repl), xspec),
        out_specs=xspec, check_vma=False)
    out = mapped(tuple(stacked), tuple(repl), xs)
    return {"Out": out.reshape(x.shape)}
