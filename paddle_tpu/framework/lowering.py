"""Program -> JAX function lowering.

TPU-native replacement for the reference's executors: instead of an op-by-op
interpreter loop (/root/reference/paddle/fluid/framework/executor.cc:471) or an
SSA-graph thread pool (details/fast_threaded_ssa_graph_executor.cc:54), a Block
lowers to ONE pure function over an environment of named arrays, jit-compiled
by XLA. Sequential in-place semantics of the reference (optimizer writes, BN
running stats) are recovered by name rebinding in the env; persistable writes
flow back to the Scope.
"""
import math

import jax
import jax.numpy as jnp

from .registry import get_op_def, normalize_outs


class LowerCtx:
    """State threaded through op lowerings: the env, rng base key, mesh."""

    def __init__(self, program, block, env, base_key, mesh=None,
                 abstract=False):
        self.program = program
        self.block = block
        self.env = env
        self.base_key = base_key
        self.mesh = mesh
        self.abstract = abstract

    def op_key(self, attrs):
        """Deterministic per-op PRNG key: fold the op's build-time seed into
        the run key. Forward and vjp-recomputed forward fold the same seed, so
        stochastic ops (dropout) reuse identical masks in backward."""
        seed = attrs.get("__rng_seed__", 0)
        user_seed = attrs.get("seed", 0)
        if self.abstract or self.base_key is None:
            base = jax.random.PRNGKey(user_seed or 0)
        elif user_seed:
            base = jax.random.PRNGKey(user_seed)
        else:
            base = self.base_key
        return jax.random.fold_in(base, seed)

    def sub_ctx(self, block_idx, env):
        return LowerCtx(self.program, self.program.blocks[block_idx], env,
                        self.base_key, mesh=self.mesh, abstract=self.abstract)

    def lower_block_ops(self, block_idx, env):
        """Run a sub-block's ops over `env` (control-flow op support)."""
        ctx = self.sub_ctx(block_idx, env)
        run_ops(ctx)
        return env

    def lookup(self, name):
        return self.env.get(name)


def run_ops(ctx):
    """Execute (trace) every op of ctx.block over ctx.env."""
    for op in ctx.block.ops:
        run_op(ctx, op)


def run_op(ctx, op):
    opdef = get_op_def(op.type)
    ins = {}
    for slot, names in op.inputs.items():
        ins[slot] = [ctx.env[n] if n in ctx.env else _missing(ctx, n, op)
                     for n in names]
    raw = opdef.lower(ctx, ins, op.attrs)
    if raw is None:
        return
    outs = normalize_outs(op.outputs, raw)
    for slot, names in op.outputs.items():
        vals = outs.get(slot)
        if vals is None:
            continue
        for n, v in zip(names, vals):
            if v is not None:
                ctx.env[n] = v


def _missing(ctx, name, op):
    raise KeyError(
        f"var {name!r} (input of op {op.type!r}) has no value: it was neither "
        f"fed, produced by an earlier op, nor found in the scope")


def analyze_block_io(program, block_idx, feed_names):
    """Which vars a block reads from outside (scope state) and which
    persistable vars it writes (state to store back).

    Mirrors the reference's unused-var/GC analysis role
    (framework/executor_gc_helper.cc) but for functional state threading.
    """
    block = program.blocks[block_idx]
    defined = set(feed_names)
    reads = []
    reads_set = set()
    writes = []
    writes_set = set()

    def visit_block(bidx, local_defined):
        blk = program.blocks[bidx]
        for op in blk.ops:
            for n in op.input_arg_names:
                if n not in local_defined and n not in reads_set:
                    reads_set.add(n)
                    reads.append(n)
            for sub_attr in ("sub_block", "sub_block_true", "sub_block_false"):
                sb = op.attrs.get(sub_attr)
                if sb is not None:
                    # names the op itself binds inside the sub-block (scan
                    # slices, loop memories, branch operands) are defined
                    # there, not read from the scope
                    bound = set(op.attrs.get("step_input_vars", ()))
                    bound.update(m[0] for m in op.attrs.get("memories", ()))
                    bound.update(op.attrs.get("x_names", ()))
                    if "x_name" in op.attrs:        # pipeline stage input
                        bound.add(op.attrs["x_name"])
                    visit_block(sb, set(local_defined) | bound)
            for n in op.output_arg_names:
                local_defined.add(n)
                if n not in writes_set:
                    try:
                        var = blk.var(n)
                        persistable = var.persistable
                    except ValueError:
                        persistable = False
                    if persistable:
                        writes_set.add(n)
                        writes.append(n)

    visit_block(block_idx, defined)
    return reads, writes


def build_block_fn(program, block_idx, feed_names, fetch_names, state_in,
                   state_out, mesh=None):
    """Return fn(state_mut, state_ro, feed, base_key) ->
    (fetches, new_state, new_key).

    `state_mut` (read-and-updated vars: params, optimizer moments, BN stats)
    is safe to buffer-donate; `state_ro` is read-only scope state.
    """
    feed_names = list(feed_names)
    fetch_names = list(fetch_names)

    def fn(state_mut, state_ro, feed, base_key):
        env = dict(state_ro)
        env.update(state_mut)
        env.update(feed)
        ctx = LowerCtx(program, program.blocks[block_idx], env, base_key,
                       mesh=mesh)
        run_ops(ctx)
        fetches = []
        for n in fetch_names:
            if n not in env:
                raise KeyError(f"fetch target {n!r} was never computed")
            fetches.append(env[n])
        new_state = {n: env[n] for n in state_out if n in env}
        new_key = jax.random.split(base_key, 1)[0]
        return fetches, new_state, new_key

    return fn


# ---------------------------------------------------------------------------
# Flattened-concat machinery for the fused multi-tensor optimizer kernels
# (framework/passes.py FuseOptimizerPass -> ops/optimizer_ops.py fused_*).
# A bucket of N per-param updates lowers as ONE elementwise update over
# the concatenation of the flattened params; because every op involved is
# elementwise, each element sees exactly the arithmetic the per-param op
# would apply — the fused path is bitwise-identical, just 1 kernel
# instead of N.
# ---------------------------------------------------------------------------

def flatten_concat(arrs, mesh=None):
    """Concatenate arrays into one flat vector; returns
    (flat, shapes) where `shapes` undoes the concat via
    :func:`split_unflatten`. Under a mesh the result is pinned
    REPLICATED: the fusion pass only buckets unsharded params, but
    GSPMD's propagation through a concat of values derived from
    tp-sharded activations must not be left to choose a partitioning
    the split would mis-slice."""
    shapes = [tuple(a.shape) for a in arrs]
    flat = jnp.concatenate([jnp.reshape(a, (-1,)) for a in arrs])
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        flat = jax.lax.with_sharding_constraint(
            flat, NamedSharding(mesh, P()))
    return flat, shapes


def split_unflatten(flat, shapes):
    """Inverse of :func:`flatten_concat`: split `flat` back into arrays
    of the given shapes (static sizes — XLA lowers this to slices)."""
    sizes = [math.prod(s) for s in shapes]
    offsets = []
    acc = 0
    for n in sizes[:-1]:
        acc += n
        offsets.append(acc)
    parts = jnp.split(flat, offsets) if offsets else [flat]
    return [jnp.reshape(p, s) for p, s in zip(parts, shapes)]


def broadcast_segments(scalars, shapes, dtype):
    """Per-segment scalar broadcast over a flattened concat: segment i
    (of size prod(shapes[i])) is filled with scalars[i]. Used for
    per-param scalars (adam's bias-corrected step size) so each element
    is multiplied by exactly the scalar its per-param op would use."""
    return jnp.concatenate([
        jnp.full((math.prod(s),), jnp.reshape(sc, ()).astype(dtype))
        for sc, s in zip(scalars, shapes)])


def _nonfinite_leaf(x):
    """Per-array non-finite element count as an in-graph int32 scalar.
    Integer/bool arrays are always finite and contribute a constant 0 (they
    stay in the slot list so slot indices line up with slot names)."""
    if jnp.issubdtype(x.dtype, jnp.floating) or \
            jnp.issubdtype(x.dtype, jnp.complexfloating):
        return (~jnp.isfinite(x)).sum(dtype=jnp.int32)
    return jnp.int32(0)


def build_multi_step_fn(program, block_idx, feed_names, fetch_names,
                        state_in, state_out, mut_names,
                        mesh=None, guard=False, skip_nonfinite=False,
                        unroll=1, viol_axes=()):
    """Return fn(state_mut, state_ro, feed_slab, base_key) ->
    (stacked_fetches, final_state, final_key, viol_counts, viol_slots):
    K training steps fused into one ``lax.scan`` over feeds stacked on a
    leading K axis.

    Per-step semantics are bitwise those of K sequential
    ``build_block_fn`` calls: the scan body IS the single-step fn, state
    rebinds through the carry and the RNG key advances by the same
    ``split(key, 1)[0]`` chain, so per-op ``fold_in`` streams match the
    unfused executor exactly.

    `mut_names` is the read-and-updated subset of `state_in`; the passed
    `state_mut` dict must ALSO carry an initial value for every
    write-only persistable output (callers seed it from the scope, or
    zeros when absent) — those live in the scan carry so the LAST
    step's value survives and a rolled-back step restores what the
    scope held, matching the sequential executor's skip path.

    With `guard` (FLAGS_check_nan_inf) the body also emits a per-step
    int32 violation count plus the index of the first offending slot
    (ordered: fetches, then updated state) — the whole non-finite check
    stays on device and costs one tiny readback instead of a device->host
    transfer of every updated parameter. With `skip_nonfinite` the carry
    update becomes a ``lax.cond`` select between pre- and post-step state
    (and pre/post RNG key): a poisoned step rolls back IN-GRAPH, with no
    host backup copies — this also works for mesh-sharded state where a
    host-side ``np.asarray`` snapshot would gather.

    `unroll` feeds through to ``lax.scan``: the loop form (1) keeps
    compile time K-independent; full unroll (K) restores straight-line
    code on backends whose while-loop bodies pessimize (XLA CPU drops
    intra-op threading inside loops). Both forms run the identical
    per-step computation.

    `viol_axes` (hierarchical multi-slice path): mapped axis names the
    per-step violation count is psum'd over INSIDE the scan body, so the
    ``skip_nonfinite`` rollback ``cond`` takes the same branch on every
    device — a NaN seen by one slice's local batch must roll the step
    back everywhere, not fork the replicas. Per-axis psums, innermost
    first, so the cross-slice hop of this int32 rides only the
    designated DCN axis."""
    step_fn = build_block_fn(program, block_idx, feed_names, fetch_names,
                             state_in, state_out, mesh=mesh)
    mut_names = list(mut_names)

    def fn(state_mut, state_ro, feed_slab, base_key):
        carry_state = dict(state_mut)

        def body(carry, feed_k):
            cstate, key = carry
            smut = {n: cstate[n] for n in mut_names}
            fetches, new_state, new_key = step_fn(smut, state_ro, feed_k,
                                                  key)
            out_state = dict(cstate)
            out_state.update(new_state)
            viol = jnp.int32(0)
            slot = jnp.int32(0)
            if guard or skip_nonfinite:
                leaves = list(fetches) + list(new_state.values())
                counts = (jnp.stack([_nonfinite_leaf(v) for v in leaves])
                          if leaves else jnp.zeros((1,), jnp.int32))
                viol = counts.sum(dtype=jnp.int32)
                slot = jnp.argmax(counts > 0).astype(jnp.int32)
                for a in reversed(tuple(viol_axes)):
                    viol = jax.lax.psum(viol, a)
                    slot = jax.lax.pmax(slot, a)
            if skip_nonfinite:
                out_state, new_key = jax.lax.cond(
                    viol > 0,
                    lambda: (cstate, key),
                    lambda: (out_state, new_key))
            return (out_state, new_key), (tuple(fetches), viol, slot)

        (final_state, final_key), (ys, viols, slots) = jax.lax.scan(
            body, (carry_state, base_key), feed_slab,
            unroll=max(int(unroll), 1))
        return list(ys), final_state, final_key, viols, slots

    return fn


# ---------------------------------------------------------------------------
# Multi-slice hierarchical data parallelism (ROADMAP item 5, MegaScale
# NSDI'24 shape): a mesh whose outermost axis is ``dcn_dp`` spans TPU
# slices over DCN. Left to GSPMD, the gradient sync would be ONE flat
# all-reduce over (dcn_dp x dp) — the full gradient payload crossing the
# slow fabric. Instead the executor runs the fused step fn under
# shard_map over the whole mesh, which binds the axis names so the
# ``hier_allreduce`` ops the hier_grad_sync pass inserted decompose per
# fabric: reduce-scatter@dp (ICI), all-reduce@dcn_dp on the owned 1/dp
# shard (DCN), all-gather@dp (ICI).
# ---------------------------------------------------------------------------

def hier_dp_axes(mesh):
    """The batch-sharding axes of a multi-slice mesh, outermost first
    (``("dcn_dp", "dp")`` / ``("dcn_dp",)``), or ``()`` when the mesh
    has no cross-slice axis (the hierarchical path does not apply)."""
    if mesh is None or "dcn_dp" not in mesh.axis_names:
        return ()
    return tuple(a for a in ("dcn_dp", "dp") if a in mesh.axis_names)


def _hier_fetch_reduce(y, axes):
    """Cross-replica mean of a fetched value, one pmean per axis
    (inner/ICI first) so the cross-slice hop reduces an already
    slice-reduced value and DCN traffic stays on the designated axis.
    Non-float fetches pass through (per-device value)."""
    if not jnp.issubdtype(jnp.result_type(y), jnp.inexact):
        return y
    for a in reversed(axes):
        y = jax.lax.pmean(y, a)
    return y


def wrap_hier_dp_steps(fn, mesh, feed_slab):
    """shard_map a ``build_multi_step_fn`` product over a dcn_dp mesh.

    Per-device semantics: each device traces the SAME program over its
    local batch shard (feed slabs shard dim 1 jointly over
    (dcn_dp, dp); state and the RNG key replicate), and the
    hier_allreduce ops make the updated state identical everywhere —
    ``out_specs=P()`` with the replication check off, since the
    compiler cannot prove what the sync guarantees. Fetches are
    pmean'd hierarchically before leaving the region (losses/metrics
    become their global-batch means, matching the GSPMD path's
    mean-over-global-batch up to summation order).

    The global batch must divide by the total data-parallel degree;
    feed arrays whose dim 1 does not divide (per-step scalars,
    K-leading aux feeds) replicate instead.
    """
    from jax.sharding import PartitionSpec as P

    axes = hier_dp_axes(mesh)
    denom = 1
    for a in axes:
        denom *= int(mesh.shape[a])
    batch_spec = axes if len(axes) > 1 else axes[0]
    feed_specs = {}
    for n, a in feed_slab.items():
        shape = tuple(getattr(a, "shape", ()) or ())
        if len(shape) >= 2 and denom > 1 and shape[1] % denom == 0:
            feed_specs[n] = P(None, batch_spec)
        else:
            feed_specs[n] = P()

    def body(state_mut, state_ro, feed_slab, base_key):
        ys, final_state, final_key, viols, slots = fn(
            state_mut, state_ro, feed_slab, base_key)
        ys = [_hier_fetch_reduce(y, axes) for y in ys]
        return ys, final_state, final_key, viols, slots

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(P(), P(), feed_specs, P()),
                         out_specs=P(), check_vma=False)
