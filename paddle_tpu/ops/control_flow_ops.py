"""Control-flow ops over sub-blocks.

TPU-native replacement for the reference's control-flow operators
(/root/reference/paddle/fluid/operators/controlflow/while_op.cc,
conditional_block_op.cc, /root/reference/paddle/fluid/operators/recurrent_op.cc).
The reference runs sub-blocks through a nested Executor with step scopes; here
each sub-block lowers into the SAME traced function via jax.lax structured
control flow (while_loop / cond / scan) — no interpreter, no scope churn, and
XLA fuses across the loop boundary. Constraints inherited from XLA: carried
shapes/dtypes are fixed across iterations and bodies are traced once.

Differentiability contract: `cond` and `recurrent` declare every outer var
they read as a real op input (slots Cond/X/Boot/P), so program-level autodiff
(backward.py) emits generic vjp grad ops whose primals connect through the
lax control-flow primitives. `while` is differentiable only when built with
`max_trip_count` (the loop lowers to a bounded, predicate-masked lax.scan —
lax.while_loop itself has no reverse-mode rule); unbounded While in a grad
path raises at append_backward time (reference while_op.cc has a grad because
its executor re-runs blocks; XLA needs a static trip bound instead).
"""
import jax
import jax.numpy as jnp

from ..framework.registry import register_op
from .common import named, x_of


def block_writes(program, block_idx):
    """Var names written by a block's ops (incl. nested sub-blocks)."""
    names = []
    seen = set()
    blk = program.blocks[block_idx]
    for op in blk.ops:
        for n in op.output_arg_names:
            if n not in seen:
                seen.add(n)
                names.append(n)
        for key in ("sub_block", "sub_block_true", "sub_block_false"):
            sb = op.attrs.get(key)
            if sb is not None:
                for n in block_writes(program, sb):
                    if n not in seen:
                        seen.add(n)
                        names.append(n)
    return names


def _as_pred(x):
    return jnp.reshape(x, ()).astype(bool)


@register_op("while", grad=None, infer_shape=False)
def while_op(ctx, ins, attrs):
    """Carry = condition var + every var the body writes that pre-exists
    outside (loop state). Reference semantics: while_op.cc re-runs the block
    until Condition is false.

    Functional over ins (Condition + X) so the generic vjp grad works.
    Two lowerings:
      - unbounded: one lax.while_loop (forward-only);
      - attrs["max_trip_count"]: a lax.scan of that length where each step's
        writes are jnp.where-masked by the live predicate — semantically the
        same loop, but reverse-mode differentiable. Finished iterations still
        execute (masked), the price of a static trip bound on TPU.
    """
    sub = attrs["sub_block"]
    cond_name = attrs["cond_name"]
    out_names = list(attrs.get("out_names") or
                     [n for n in block_writes(ctx.program, sub)
                      if n in ctx.env])
    x_names = list(attrs.get("x_names", []))
    x_map = dict(zip(x_names, ins.get("X", [])))
    cond0 = ins["Condition"][0]
    x_map[cond_name] = cond0

    carried = list(out_names)
    if cond_name not in carried:
        carried.insert(0, cond_name)
    outer_env = dict(ctx.env)
    outer_env.update(x_map)
    carry0 = {}
    for n in carried:
        if n not in outer_env:
            raise KeyError(
                f"While loop state {n!r} has no value before the loop; "
                f"initialize it (e.g. fill_constant) before While.block()")
        carry0[n] = outer_env[n]

    def run_body(carry):
        env = dict(outer_env)
        env.update(carry)
        ctx.lower_block_ops(sub, env)
        return {n: env[n] for n in carried}

    max_trip = attrs.get("max_trip_count")
    if max_trip is not None and attrs.get("max_trip_count_auto"):
        # the bound was auto-derived at build time; re-derive against
        # the FINAL program (ops appended after the While block — e.g.
        # an outer loop mutating the bound constant — could invalidate
        # it, which must be an error, not silent truncation)
        from ..layers.control_flow import _infer_max_trip
        sub_blk = ctx.program.blocks[sub]
        parent_blk = sub_blk.parent_block
        # find the forward while op by its (unique) sub-block index —
        # attrs may be a copy here (grad lowering re-enters with the
        # fwd spec), so identity comparison would miss
        this_op = next((op for op in parent_blk.ops
                        if op.type == "while"
                        and op.attrs.get("sub_block") == sub), None)
        now = _infer_max_trip(ctx.program, parent_blk, sub_blk,
                              cond_name, stop_op=this_op)
        if now != int(max_trip):
            # the bound is consumed only by the differentiable (scan)
            # lowering: in a program with a backward pass an invalid
            # bound must be an ERROR (silent truncation corrupts
            # training, and a nested loop may be differentiated
            # implicitly through an enclosing while_grad); forward-only
            # programs just fall back to the unbounded while_loop
            has_grad = any(
                op.type.endswith("_grad")
                for blk in ctx.program.blocks for op in blk.ops)
            if has_grad:
                raise ValueError(
                    f"While: the auto-derived max_trip_count "
                    f"({max_trip}) is no longer valid in the final "
                    f"program (re-derivation gives {now}); the loop "
                    f"bound is mutated after the loop was built — pass "
                    f"max_trip_count explicitly")
            max_trip = None
    if max_trip is None:
        def cond_fn(carry):
            return _as_pred(carry[cond_name])

        def body_fn(carry):
            return run_body(carry)

        final = jax.lax.while_loop(cond_fn, body_fn, carry0)
    else:
        def step(carry, _):
            pred, state = carry
            new_state = run_body(state)
            state = {n: jnp.where(pred, new_state[n], state[n])
                     for n in carried}
            pred = jnp.logical_and(pred, _as_pred(state[cond_name]))
            return (pred, state), None

        (_, final), _ = jax.lax.scan(
            step, (_as_pred(cond0), carry0), None, length=int(max_trip))
    return {"Out": [final[n] for n in out_names]}


@register_op("cond", grad=None, infer_shape=False)
def cond_op(ctx, ins, attrs):
    """Two-branch conditional (fluid layers.cond; the reference builds two
    conditional_block ops + select_input — here it's one lax.cond).

    inputs: Cond=[pred], X=[outer vars read by either branch]
    attrs: sub_block_true/false, x_names (inner names of X), true_outs,
    false_outs (in-branch var names per output).
    """
    pred = _as_pred(x_of(ins, "Cond"))
    x_vals = list(ins.get("X", []))
    x_names = list(attrs.get("x_names", []))
    outer_env = dict(ctx.env)
    outer_env.update(zip(x_names, x_vals))

    def branch(block_idx, out_names):
        def fn(xs):
            env = dict(outer_env)
            env.update(zip(x_names, xs))
            ctx.lower_block_ops(block_idx, env)
            return tuple(env[n] for n in out_names)
        return fn

    res = jax.lax.cond(pred,
                       branch(attrs["sub_block_true"],
                              list(attrs["true_outs"])),
                       branch(attrs["sub_block_false"],
                              list(attrs["false_outs"])),
                       tuple(x_vals))
    return {"Out": list(res)}


@register_op("recurrent", grad=None, infer_shape=False)
def recurrent_op(ctx, ins, attrs):
    """StaticRNN / recurrent_op as ONE lax.scan over the time dim.

    inputs: X=[outer time-major sequences], Boot=[initial memory values],
    P=[outer vars read inside the step (weights etc.)]
    attrs: sub_block; step_input_vars (inner names for X slices); memories
    [(pre_name, post_name)] aligned with Boot; p_names (inner names for P);
    step_outputs (in-block names); is_reverse; scope (a
    ``jax.named_scope`` around the loop, where given).
    Outputs "Out": stacked step outputs, time-major.
    """
    sub = attrs["sub_block"]
    step_in_inner = list(attrs["step_input_vars"])
    memories = [tuple(m) for m in attrs["memories"]]
    p_names = list(attrs.get("p_names", []))
    step_outs = list(attrs["step_outputs"])
    reverse = bool(attrs.get("is_reverse", False))

    xs = tuple(ins.get("X", []))
    carry0 = tuple(ins.get("Boot", []))
    p_vals = tuple(ins.get("P", []))

    outer_env = dict(ctx.env)

    def body(carry, x_t):
        env = dict(outer_env)
        env.update(zip(p_names, p_vals))
        env.update(zip(step_in_inner, x_t))
        for (pre, _), c in zip(memories, carry):
            env[pre] = c
        ctx.lower_block_ops(sub, env)
        new_carry = tuple(env[post] for _, post in memories)
        ys = tuple(env[n] for n in step_outs)
        return new_carry, ys

    # lax.scan(reverse=True) already returns ys position-aligned with xs
    with named(attrs.get("scope")):
        final_carry, stacked = jax.lax.scan(body, carry0, xs,
                                            reverse=reverse)
    out = {"Out": list(stacked)}
    if memories:
        out["FinalStates"] = list(final_carry)
    return out


# ---- LoDTensorArray ops ----
# The reference's tensor-array ops (controlflow/tensor_array_read_write_op.cc)
# mutate a vector<LoDTensor> variable. Trace-time arrays here are Python
# lists living in the env (indices must be trace-time constants); inside
# scan/while use the recurrent op's stacked outputs instead.

@register_op("write_to_array", grad=False, infer_shape=False)
def write_to_array(ctx, ins, attrs):
    x = x_of(ins)
    i = int(attrs["index"])  # folded at build time (layers.array_write)
    name = attrs["array_name"]
    arr = ctx.env.get(name)
    arr = list(arr) if isinstance(arr, list) else []
    while len(arr) <= i:
        arr.append(None)
    arr[i] = x
    ctx.env[name] = arr
    return None


@register_op("read_from_array", grad=False, infer_shape=False)
def read_from_array(ctx, ins, attrs):
    arr = ctx.env[attrs["array_name"]]
    return {"Out": arr[int(attrs["index"])]}


@register_op("lod_array_length", grad=False, infer_shape=False)
def lod_array_length(ctx, ins, attrs):
    arr = ctx.env.get(attrs["array_name"], [])
    return {"Out": jnp.asarray([len(arr)], jnp.int32)}
