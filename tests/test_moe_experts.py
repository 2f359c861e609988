"""The routed expert op (``ops/moe_ops.routed_experts`` over
``kernels/moe_experts.py``): dropless top-k routing against a loop over
experts, through both implementations."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.kernels import moe_experts

N, D, F, E, K = 24, 32, 16, 8, 2


def _weights(seed, dtype):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return {"x": jax.random.normal(ks[0], (N, D)),
            "router": 0.5 * jax.random.normal(ks[1], (D, E)),
            "gate": (0.2 * jax.random.normal(ks[2], (E, D, F))).astype(dtype),
            "up": (0.2 * jax.random.normal(ks[3], (E, D, F))).astype(dtype),
            "down": (0.2 * jax.random.normal(ks[4], (E, F, D))).astype(dtype)}


def _loop(w, idx, weights, valid=None):
    """One expert at a time over the tokens that chose it, in float64 on
    the operands the op rounds (x and the hidden to the weights' dtype)."""
    dtype = w["gate"].dtype

    def rounded(a):
        return np.asarray(jnp.asarray(a, jnp.float32).astype(dtype).astype(
            jnp.float32), np.float64)

    x = rounded(w["x"])
    out = np.zeros((N, D))
    for e in range(E):
        g, u, d = (np.asarray(w[k][e].astype(jnp.float32), np.float64)
                   for k in ("gate", "up", "down"))
        for n in range(N):
            if valid is not None and not valid[n]:
                continue
            for j in range(idx.shape[1]):
                if int(idx[n, j]) == e:
                    a = x[n] @ g
                    h = rounded(a / (1 + np.exp(-a)) * (x[n] @ u))
                    out[n] += float(weights[n, j]) * (h @ d)
    return out


def _route(w, k=K):
    probs = jax.nn.softmax(w["x"] @ w["router"])
    top_w, top_i = jax.lax.top_k(probs, k)
    return top_i, top_w / top_w.sum(-1, keepdims=True)


CASES = {
    "routed": lambda w: _route(w) + (None,),
    # every token of the batch chooses the same experts: one group holds
    # all the rows, and six experts get no token
    "all_same": lambda w: (jnp.tile(jnp.array([[5, 1]]), (N, 1)),
                           _route(w)[1], None),
    "one_expert_empty": lambda w: (
        jnp.where(_route(w)[0] == 3, 4, _route(w)[0]), _route(w)[1], None),
    "padding_routes_nowhere": lambda w: _route(w) + (
        np.arange(N) % 3 != 0,),
    "nothing_valid": lambda w: _route(w) + (np.zeros(N, bool),),
}


@pytest.mark.parametrize("impl", ["xla", "interpret"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_a_loop_over_experts(case, dtype, impl):
    w = _weights(0, dtype)
    idx, weights, valid = CASES[case](w)
    out, counts = moe_experts.routed_experts(
        w["x"], idx, weights, w["gate"], w["up"], w["down"],
        valid=None if valid is None else jnp.asarray(valid), impl=impl)
    # float32 sums of at most 32 + 16 products against float64
    np.testing.assert_allclose(np.asarray(out), _loop(w, idx, weights, valid),
                               rtol=0, atol=2e-5)
    want = np.bincount(np.asarray(idx)[
        np.ones(N, bool) if valid is None else valid].ravel(), minlength=E)
    assert np.asarray(counts).tolist() == want.tolist()
    assert int(np.asarray(counts).sum()) == K * (
        N if valid is None else int(valid.sum()))    # nothing dropped


def test_layout_pads_groups_to_whole_tiles_and_repeats_the_last_expert():
    expert_of = jnp.asarray([2, 0, 2, 2, 5, E, 0, 2], jnp.int32)
    tm = 2
    rows = moe_experts.padded_rows(8, E, tm)
    counts, row_of, src_of, tile_expert, tiles = moe_experts.group_layout(
        expert_of, E, tm, rows)
    assert counts.tolist() == [2, 0, 4, 0, 0, 1, 0, 0]
    assert int(tiles[0]) == 1 + 2 + 1
    assert tile_expert.tolist()[:4] == [0, 2, 2, 5]
    assert set(tile_expert.tolist()[4:]) == {5}      # dead tiles: no move
    # every live assignment's row holds its own token, in its expert's tiles
    for a, e in enumerate(expert_of.tolist()):
        if e < E:
            assert int(src_of[row_of[a]]) == a
            assert tile_expert[int(row_of[a]) // tm] == e
    assert moe_experts.rows_per_tile(256, 64) == 16
    assert moe_experts.rows_per_tile(8192 * 8, 64) == 256


@pytest.mark.parametrize("norm", [True, False])
def test_op_routes_in_float32_and_renormalises(norm):
    """Through a program: the layer's own router (softmax over all
    experts, top-k, renormalised or not) against the same in numpy."""
    w = _weights(3, jnp.float32)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [N, D], "float32")
        out, counts = layers.nn.routed_experts(
            x, E, K, F, norm_topk_prob=norm,
            param_attr={k: fluid.ParamAttr(name=f"moe_{k}")
                        for k in ("router", "gate", "up", "down")})
    exe, scope = fluid.Executor(), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        for k in ("router", "gate", "up", "down"):
            scope.set(f"moe_{k}", np.asarray(w[k]))
        got, got_counts = exe.run(main, feed={"x": np.asarray(w["x"])},
                                  fetch_list=[out, counts])
    probs = np.asarray(jax.nn.softmax(jnp.dot(
        w["x"], w["router"], precision="highest")), np.float64)
    idx = np.argsort(-probs, axis=1)[:, :K]
    weights = np.take_along_axis(probs, idx, axis=1)
    if norm:
        weights = weights / weights.sum(1, keepdims=True)
    np.testing.assert_allclose(got, _loop(w, idx, weights), rtol=0,
                               atol=2e-5)
    assert got_counts.sum() == N * K
