"""The Xing block (``models/xing.py``: latent attention over a latent
paged cache, four hyper-connected residual streams mixed by Sinkhorn,
sigmoid-routed experts beside a shared expert) against the plain
reference (``benchmark/families/xing.py``, which imports nothing of
``paddle_tpu``) on seeded weights at the rehearsal size: the latent
decode kernel against expanded attention over the same cache, the flash
forward with values narrower than keys, the mHC mix, the routing and
the shared expert (and Mellum's softmax routing as it was), the pool's
latent group, prefill then decode through the pool and through
``InferenceServer``, a fault in one cached latent, and the typed
refusals."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import serving
from paddle_tpu.flags import set_flags
from paddle_tpu.kernels import _dispatch
from paddle_tpu.kernels import mla_decode as mla_kernel
from paddle_tpu.kernels.flash_attention import flash_attention
from paddle_tpu.kernels.paged_attention import to_logical
from paddle_tpu.models import generation, xing
from paddle_tpu.models.generation import GPTGenerator
from paddle_tpu.ops import hc_ops, mla_ops, moe_ops
from paddle_tpu.serving import kvpool
from paddle_tpu.serving.kvpool import (KVBlockPool, adopt_decode_fetches,
                                       decode_feed)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs",
                       "xing4.0-29b-a4b.json")) as fh:
    CONFIG = json.load(fh)

from benchmark.families import xing as fam  # noqa: E402

SZ = fam.Sizes(CONFIG, rehearsal=True)
SEED = 2**31 + 43          # past 32 signed bits, as seeds may be

# float32 weights: program and reference differ by summation order and by
# XLA:CPU's default float32 product against precision=highest, measured
# under 1e-6 of logits of magnitude 0.3 through prefill and 20 decode
# steps; 2e-5 holds that with room.
TOL_F32 = 2e-5


def _tokens(rows, seq, seed=0):
    return np.random.default_rng(seed).integers(
        1, SZ.vocab_size, (rows, seq)).astype(np.int32)


def _cast(params, dtype):
    """The family rounds the matrices to bfloat16 once; float32 holds the
    same numbers exactly."""
    return {n: (a if a.dtype == jnp.float32 else a.astype(dtype))
            for n, a in params.items()}


def _generator(dtype="float32", max_len=64):
    cfg = fam.program_config(SZ)
    cfg.dtype = dtype
    params = fam.init_params(SZ, SEED)
    gen = GPTGenerator(cfg, fluid.Scope(), max_len=max_len)
    gen.bind_params(_cast(params, dtype))
    return cfg, gen, params


@pytest.fixture
def interpreted(monkeypatch):
    """The kernels a TPU would run, through the Pallas interpreter."""
    monkeypatch.setattr(_dispatch, "auto_impl", lambda: "interpret")


# ------------------------------------------------------------- the kernel

def _latent_case(rows, nblk, bs, dtype, seed=0, H=4, C=128, R=8, dn=16,
                 dv=8):
    rng = np.random.default_rng(seed)
    nblocks = rows * nblk + 1
    c = rng.normal(size=(nblocks, bs, C)).astype(np.float32)
    r = rng.normal(size=(nblocks, bs, R)).astype(np.float32)
    c, r = (jnp.asarray(a, dtype).astype(jnp.float32) for a in (c, r))
    tables = np.zeros((rows, nblk), np.int32)
    ids = rng.permutation(np.arange(1, nblocks))
    for b in range(rows):
        tables[b] = ids[b * nblk:(b + 1) * nblk]
    return dict(
        c=c, r=r, tables=tables,
        q_nope=jnp.asarray(rng.normal(size=(rows, H, 1, dn)), jnp.float32),
        q_pe=jnp.asarray(rng.normal(size=(rows, H, 1, R)), jnp.float32),
        w=jnp.asarray(rng.normal(size=(C, H * (dn + dv))) / np.sqrt(C),
                      jnp.float32).astype(dtype).astype(jnp.float32))


def _expanded(case, pos, scale, dn=16):
    """Attention as the prefill computes it, in float64: every head's
    keys ``[c W_UK | k_pe]`` and values ``c W_UV`` over the row's cache."""
    c, r = np.asarray(case["c"], np.float64), np.asarray(case["r"],
                                                         np.float64)
    w = np.asarray(case["w"], np.float64)
    rows, H = case["q_nope"].shape[:2]
    w = w.reshape(w.shape[0], H, -1)
    out = []
    for b in range(rows):
        n = int(pos[b]) + 1
        cb = c[case["tables"][b]].reshape(-1, c.shape[-1])[:n]
        rb = r[case["tables"][b]].reshape(-1, r.shape[-1])[:n]
        heads = []
        for h in range(H):
            k = np.concatenate([cb @ w[:, h, :dn], rb], axis=1)
            q = np.concatenate([np.asarray(case["q_nope"][b, h, 0]),
                                np.asarray(case["q_pe"][b, h, 0])])
            s = k @ q * scale
            p = np.exp(s - s.max())
            heads.append((p / p.sum()) @ (cb @ w[:, h, dn:]))
        out.append(np.concatenate(heads))
    return np.stack(out)


def _op_decode(case, pos, scale, dtype, impl):
    """The ``mla_decode`` op over the case's cache stored as the pool
    stores it (rows of a free slot point at the trash block)."""
    from paddle_tpu.kernels.paged_attention import to_stored
    c = jnp.asarray(case["c"], dtype)
    r = to_stored(jnp.asarray(case["r"], dtype)[:, None])
    ins = {"QNope": [case["q_nope"]], "QPe": [case["q_pe"]],
           "WKvb": [jnp.asarray(case["w"], dtype)], "CPool": [c],
           "RPool": [r], "Tables": [jnp.asarray(case["tables"])],
           "Pos": [jnp.asarray(pos, jnp.int32)]}
    out = mla_ops.mla_decode(None, ins, {"scale": scale, "impl": impl})
    return np.asarray(out["Out"])[:, 0]


# float32 pools: the absorbed order of the products against the expanded
# one in float64, measured under 1e-6 of outputs of magnitude 0.5; 1e-5.
# bfloat16 pools and weights: the absorbed query, the probabilities and
# the latent output are rounded to bfloat16 before their products
# (relative 4e-3 each), measured 6e-3 of outputs of magnitude 0.5; 2e-2.
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("bs,nblk", [(16, 4), (16, 24)])
def test_latent_decode_kernel_is_expanded_attention(dtype, tol, bs, nblk):
    """Rows that end inside the first block, at a block's edge, past a
    step of the walk (24 blocks of 16: two steps), and a free slot."""
    case = _latent_case(4, nblk, bs, dtype)
    case["tables"][2] = 0                       # a free slot
    pos = np.array([5, bs * nblk - 1, 0, bs * 3], np.int32)
    scale = 0.3
    want = _expanded(case, pos, scale)
    for impl in ("interpret", "xla"):
        got = _op_decode(case, pos, scale, dtype, impl)
        live = [0, 1, 3]
        np.testing.assert_allclose(got[live], want[live], rtol=0, atol=tol)
        if impl == "interpret":
            assert not np.any(got[2])           # a free slot reads zeros


def test_latent_decode_kernel_lowers_for_the_tpu_at_the_served_widths():
    """32 heads against a 576-wide latent in blocks of 128 (the cell's
    shapes, 68 blocks a row): the Pallas-to-Mosaic lowering rules, with
    no chip."""
    SDS = jax.ShapeDtypeStruct
    B, H, C, R, bs, N, nblk = 64, 32, 512, 64, 128, 3401, 68

    def fn(ql, qp, c, r, t, p):
        return mla_kernel.mla_decode(ql, qp, c, r, t, p, 0.14,
                                     impl="pallas")

    text = jax.jit(fn).trace(
        SDS((B, H, C), jnp.bfloat16), SDS((B, H, R), jnp.bfloat16),
        SDS((N, bs, C), jnp.bfloat16), SDS((N, bs // 2, 128), jnp.bfloat16),
        SDS((B, nblk), jnp.int32), SDS((B,), jnp.int32)).lower(
            lowering_platforms=("tpu",)).as_text()
    assert "mla_paged_decode" in text and "tpu_custom_call" in text
    assert mla_kernel.blocks_per_step(bs, nblk) == 2


def test_expansion_puts_a_packed_rows_scores_in_slot_order():
    e = mla_kernel.expansion(16, 4)
    assert e.shape == (4, 4, 16) and e.sum() == 16
    scores = np.arange(16, dtype=np.float32).reshape(4, 4)   # row, parity
    got = sum(scores[:, p] @ e[p] for p in range(4))
    np.testing.assert_array_equal(got, np.arange(16))


# float32 operands: the kernel's blockwise online softmax against the
# composite's one softmax, measured 3e-7 of outputs of magnitude 1; 1e-5.
@pytest.mark.parametrize("seq", [64, 2048])
def test_flash_forward_takes_values_narrower_than_keys(seq):
    """Keys and queries 192 wide against values 128 (latent attention's
    expanded prefill), causal, one block and the online path."""
    rng = np.random.default_rng(seq)
    q, k = (jnp.asarray(rng.normal(size=(1, 2, seq, 192)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(1, 2, seq, 128)), jnp.float32)
    got = flash_attention(q, k, v, causal=True, scale=0.1, impl="interpret")
    want = flash_attention(q, k, v, causal=True, scale=0.1, impl="xla")
    assert got.shape == (1, 2, seq, 128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=1e-5)


# ---------------------------------------------------------- the mHC mix

def test_hyper_connection_mix_is_the_references_and_doubly_stochastic():
    params = fam.init_params(SZ, SEED)
    n, c = SZ.hc_mult, SZ.hidden_size
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(6, n * c)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(6, c)), jnp.float32)
    name = "layer_1_hc_ffn"
    # a larger alpha than the initial 0.1, so that the mix differs from
    # token to token by more than rounding
    alpha = jnp.asarray([1.5, 1.5, 1.5], jnp.float32)
    ins = {"X": [x], "Phi": [params[f"{name}_phi"]], "Alpha": [alpha],
           "Bias": [params[f"{name}_bias"]]}
    attrs = {"n": n, "epsilon": SZ.rms_norm_eps, "hc_eps": SZ.hc_eps,
             "iters": SZ.hc_sinkhorn_iters,
             "clamp_min": SZ.mhc_h_res_clamp_min,
             "clamp_max": SZ.mhc_h_res_clamp_max}
    got = hc_ops.hc_pre(None, ins, attrs)
    pre, post, res = fam.hc_weights(SZ, x.reshape(6, n, c),
                                    params[f"{name}_phi"], alpha,
                                    params[f"{name}_bias"])
    # the same float32 operations in the same order: 1e-6 of weights of
    # magnitude 1 covers XLA's reassociation of the 20 rounds
    for a, b in ((got["Post"], post), (got["Res"], res)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(got["H"]), np.einsum("sn,snc->sc", np.asarray(pre),
                                        np.asarray(x).reshape(6, n, c)),
        rtol=0, atol=1e-5)
    res = np.asarray(got["Res"])
    assert np.all(res > 0) and np.std(res) > 1e-2
    # columns are normalised last: they sum to 1 within hc_eps; rows come
    # within hc_eps after 20 rounds of a 4 x 4 positive matrix
    np.testing.assert_allclose(res.sum(-2), 1.0, atol=SZ.hc_eps)
    np.testing.assert_allclose(res.sum(-1), 1.0, atol=SZ.hc_eps)
    assert np.all((np.asarray(got["Post"]) > 0)
                  & (np.asarray(got["Post"]) < 2))
    out = hc_ops.hc_post(None, {"X": [x], "Y": [y], "Post": [got["Post"]],
                                "Res": [got["Res"]]}, {})["Out"]
    want = np.einsum("sij,sjc->sic", res, np.asarray(x).reshape(6, n, c)) \
        + np.asarray(got["Post"])[:, :, None] * np.asarray(y)[:, None]
    np.testing.assert_allclose(np.asarray(out).reshape(6, n, c), want,
                               rtol=0, atol=1e-5)


def test_sinkhorn_of_a_clamped_extreme_stays_doubly_stochastic():
    """Logits at the clamp's ends (+-30): 20 rounds still leave a matrix
    whose rows and columns sum to 1 within hc_eps times its size."""
    logits = jnp.clip(jnp.asarray(
        [[[60.0, -60.0, 0.0, 1.0], [0.0, 30.0, -5.0, 2.0],
          [-30.0, 0.0, 30.0, 0.0], [1.0, 2.0, 3.0, 4.0]]]), -30, 30)
    m = np.asarray(hc_ops.sinkhorn(logits, 20, 1e-6))
    np.testing.assert_allclose(m.sum(-2), 1.0, atol=1e-5)
    assert np.all(m >= 0) and np.all(np.isfinite(m))


# ------------------------------------------------------------- routing

def _routing_case(seed=3):
    rng = np.random.default_rng(seed)
    n, d, e, f = 12, 16, 8, 8
    return (jnp.asarray(rng.normal(size=(n, d)), jnp.float32),
            jnp.asarray(rng.normal(size=(d, e)) * 0.3, jnp.float32),
            jnp.asarray(rng.normal(size=(e,)) * 0.2, jnp.float32),
            *(jnp.asarray(rng.normal(size=s) * 0.2, jnp.float32)
              for s in ((e, d, f), (e, d, f), (e, f, d))))


def test_sigmoid_routing_with_a_selection_bias_and_a_scale_is_noaux_tc():
    x, router, bias, wg, wu, wd = _routing_case()
    out = moe_ops.routed_experts(
        None, {"X": [x], "RouterW": [router], "EBias": [bias],
               "WGate": [wg], "WUp": [wu], "WDown": [wd]},
        {"top_k": 2, "norm_topk_prob": True, "scoring_func": "sigmoid",
         "routed_scaling_factor": 2.0, "impl": "xla"})
    xs, w_r = np.asarray(x, np.float64), np.asarray(router, np.float64)
    scores = 1 / (1 + np.exp(-(xs @ w_r)))
    want = np.zeros_like(xs)
    counts = np.zeros(8, np.int64)
    for t in range(xs.shape[0]):
        top = np.argsort(-(scores[t] + np.asarray(bias)))[:2]
        w = scores[t, top] / scores[t, top].sum() * 2.0
        for j, e in enumerate(top):
            g = xs[t] @ np.asarray(wg[e], np.float64)
            u = xs[t] @ np.asarray(wu[e], np.float64)
            want[t] += w[j] * ((g / (1 + np.exp(-g)) * u)
                               @ np.asarray(wd[e], np.float64))
            counts[e] += 1
    # float32 products against float64: measured 3e-7; 1e-5
    np.testing.assert_allclose(np.asarray(out["Out"]), want, rtol=0,
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(out["Counts"]), counts)
    # the bias chooses and does not weigh: a bias that changes no choice
    # changes nothing
    same = moe_ops.routed_experts(
        None, {"X": [x], "RouterW": [router], "EBias": [bias * 1e-6],
               "WGate": [wg], "WUp": [wu], "WDown": [wd]},
        {"top_k": 2, "scoring_func": "sigmoid", "impl": "xla",
         "routed_scaling_factor": 2.0})
    unbiased = np.argsort(-scores, axis=1)[:, :2]
    biased = np.argsort(-(scores + np.asarray(bias)), axis=1)[:, :2]
    assert np.any(np.sort(unbiased, 1) != np.sort(biased, 1))
    assert not np.allclose(np.asarray(same["Out"]), want, atol=1e-3)


def test_softmax_routing_is_as_it_was():
    """Mellum's path, bit for bit: softmax over all experts, the k
    largest, renormalised, as the op computed them before sigmoid
    routing came (written out here)."""
    from paddle_tpu.kernels.moe_experts import routed_experts as experts
    x, router, _, wg, wu, wd = _routing_case(4)
    got = moe_ops.routed_experts(
        None, {"X": [x], "RouterW": [router], "WGate": [wg], "WUp": [wu],
               "WDown": [wd]}, {"top_k": 3, "norm_topk_prob": True,
                                "impl": "xla"})
    logits = jnp.dot(x, router, precision=jax.lax.Precision.HIGHEST)
    top_w, top_i = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), 3)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    out, counts = experts(x, top_i, top_w, wg, wu, wd, impl="xla")
    np.testing.assert_array_equal(np.asarray(got["Out"]), np.asarray(out))
    np.testing.assert_array_equal(np.asarray(got["Counts"]),
                                  np.asarray(counts))


def test_the_expert_layer_adds_the_shared_expert_to_every_token():
    """Through the program: an expert layer's output less its routed
    experts is the shared SwiGLU of the normed input, for every token
    (padding included)."""
    cfg = fam.program_config(SZ)
    cfg.dtype = "float32"
    params = _cast(fam.init_params(SZ, SEED), "float32")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        h = fluid.layers.data("h", [2, 5, SZ.hidden_size], "float32",
                              append_batch_size=False)
        out, counts = xing.ffn(cfg, h, 1)
    exe, scope = fluid.Executor(), fluid.Scope()
    hv = np.random.default_rng(5).normal(
        size=(2, 5, SZ.hidden_size)).astype(np.float32)
    with fluid.scope_guard(scope):
        exe.run(startup)
        for p in main.all_parameters():
            scope.set(p.name, np.asarray(params[p.name]))
        got, cnt = exe.run(main, feed={"h": hv}, fetch_list=[out, counts])
    ref = np.asarray(fam._ffn(SZ, params, 1, jnp.asarray(hv.reshape(10, -1)),
                              fam._matmul("highest")))
    # float32 both: 1e-5 of outputs of magnitude 0.05
    np.testing.assert_allclose(got.reshape(10, -1), ref, rtol=0, atol=1e-5)
    assert int(np.asarray(cnt).sum()) == 10 * SZ.num_experts_per_tok


# ------------------------------------------------------------ the pool

def _latent_pool(slots=3, max_len=64, block_size=16, dtype="fp32"):
    groups = xing.XingConfig.tiny().serving().kv_groups()
    return KVBlockPool(slots=slots, num_layers=3, num_heads=1, d_head=136,
                       max_seq_len=max_len, block_size=block_size,
                       dtype=dtype, groups=groups, name="latent-test",
                       prefix_cache=True)


def test_latent_group_allocates_scatters_frees_and_reuses():
    pool = _latent_pool()
    assert pool.latent == {"c": 128, "r": 8} and pool.latent_layers == 3
    assert not pool.prefix_enabled            # asked for, declined
    arrays = pool.arrays()
    assert list(arrays) == pool.feed_names() == [
        "cache_pc_0", "cache_pc_1", "cache_pc_2",
        "cache_pr_0", "cache_pr_1", "cache_pr_2"]
    assert arrays["cache_pc_0"].shape == (pool.num_blocks, 16, 128)
    assert arrays["cache_pr_0"].shape == (pool.num_blocks, 1, 128)
    # a position: 3 layers x (128 + 8) x 4 B; a block of 16 positions
    assert pool.block_bytes() == 3 * 16 * 136 * 4
    rng = np.random.default_rng(0)
    rows = {f"cache_c_{i}": rng.normal(size=(2, 1, 32, 128)).astype(
        np.float32) for i in range(3)}
    rows.update({f"cache_r_{i}": rng.normal(size=(2, 1, 32, 8)).astype(
        np.float32) for i in range(3)})
    pool.alloc(0, 20)
    pool.alloc(2, 31)
    held = {0: pool.tables[0, :2].copy(), 2: pool.tables[2, :2].copy()}
    pool.scatter_prefill([0, 2], rows, 32)
    for r, slot in enumerate((0, 2)):
        for i in range(3):
            for tag in "cr":
                got = pool.logical(f"cache_p{tag}_{i}", held[slot])
                want = rows[f"cache_{tag}_{i}"][r, 0].reshape(2, 16, -1)
                np.testing.assert_array_equal(got[:, 0], want)
    assert pool.stats()["relayouts"]["scatter"] == 0
    assert pool.tokens_held() == 51
    assert pool.free_slot(0) == 2 and pool.tokens_held() == 31
    # the freed blocks are the next ones handed out (LIFO)
    pool.alloc(1, 17)
    assert sorted(pool.tables[1, :2]) == sorted(held[0])
    pool.free_slot(1)
    pool.free_slot(2)
    assert pool.blocks_in_use() == 0
    with pytest.raises(serving.batching.BadRequestError,
                       match="latent group"):
        pool.export_slot(0)
    with pytest.raises(ValueError, match="latent"):
        _latent_pool(dtype="int8")


def test_the_decode_step_appends_one_latent_and_rotary_key_a_row():
    """One decode step over a pool whose slot 1 holds a prompt of 5: what
    the step appends at position 5 is the program's own latent and
    rotary key for that token, and every other slot is as it was."""
    cfg, gen, params = _generator()
    pool = gen.new_pool(2)
    toks = _tokens(1, 6)[0]
    tokens, pos_ids, last = gen._pack_prompts([toks[:5]])
    logits, rows, _ = gen._run_prefill(tokens, pos_ids, last,
                                       jax.random.PRNGKey(0),
                                       kv_dtype=pool.dtype)
    pool.alloc(1, 5)
    pool.scatter_prefill([1], rows, tokens.shape[1])
    before = {n: np.asarray(a) for n, a in pool.arrays().items()}
    pool.ensure(1, 5)
    key = jax.random.PRNGKey(0)
    step_logits, _ = gen._run_decode_paged(
        np.array([0, toks[5]], np.int32), np.array([0, 5], np.int32), pool,
        key)
    _, rows6, _ = gen._run_prefill(*gen._pack_prompts([toks[:6]]),
                                   key, kv_dtype=pool.dtype)
    blk = int(pool.tables[1, 0])
    for i in range(SZ.num_hidden_layers):
        for tag in "cr":
            got = pool.logical(f"cache_p{tag}_{i}", [blk])[0, 0]
            want = np.asarray(rows6[f"cache_{tag}_{i}"])[0, 0, :6]
            np.testing.assert_allclose(got[:6], want, rtol=0, atol=1e-5)
            after = np.asarray(pool.arrays()[f"cache_p{tag}_{i}"])
            changed = np.any(after != before[f"cache_p{tag}_{i}"],
                             axis=tuple(range(1, after.ndim)))
            assert set(np.flatnonzero(changed)) <= {0, blk}
    assert np.asarray(step_logits).shape == (2, SZ.vocab_size)


# -------------------------------------------------- program and reference

def test_full_sequence_logits_through_executor_match_reference():
    cfg = fam.program_config(SZ)
    cfg.dtype = "float32"
    params = fam.init_params(SZ, SEED)
    toks, last = _tokens(2, 40), np.array([39, 21], np.int32)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        out = xing.xing_logits(cfg)
    exe, scope = fluid.Executor(), fluid.Scope()
    pos = np.broadcast_to(np.arange(40, dtype=np.int32), (2, 40)).copy()
    with fluid.scope_guard(scope):
        exe.run(startup)
        for p in main.all_parameters():
            scope.set(p.name, np.asarray(_cast(params, "float32")[p.name]))
        logits, counts = exe.run(
            main, feed={"tokens": toks, "pos_ids": pos, "last_pos": last},
            fetch_list=[out["logits"], out["aux"]["moe_counts"]])
    ref = np.asarray(fam.reference_logits(SZ, params, jnp.asarray(toks)))
    assert np.std(ref) > 1e-2
    for r in range(2):
        np.testing.assert_allclose(logits[r], ref[r, last[r]], rtol=0,
                                   atol=TOL_F32)
    # padding routes nowhere: 40 + 22 real tokens, 2 experts each, a layer
    assert counts.shape == (SZ.moe_layers, SZ.n_routed_experts)
    assert np.all(counts.sum(1) == 62 * SZ.num_experts_per_tok)


def _decode_against_reference(gen, params, prompt, steps, atol):
    """Prefill ``prompt`` into slot 1 of a two-slot pool, then ``steps``
    greedy decode steps (slot 0 free); each step's logits against the
    reference's full forward at that position."""
    pool = gen.new_pool(2)
    tokens, pos_ids, last = gen._pack_prompts([prompt])
    logits, rows, _ = gen._run_prefill(tokens, pos_ids, last,
                                       jax.random.PRNGKey(0),
                                       kv_dtype=pool.dtype)
    pool.alloc(1, prompt.size)
    pool.scatter_prefill([1], rows, tokens.shape[1])
    seq = list(prompt)
    got = [np.asarray(logits)[0]]
    key = jax.random.PRNGKey(0)
    for _ in range(steps):
        seq.append(int(np.argmax(got[-1])))
        pool.ensure(1, len(seq) - 1)
        out, key = gen._run_decode_paged(
            np.array([0, seq[-1]], np.int32),
            np.array([0, len(seq) - 1], np.int32), pool, key)
        got.append(np.asarray(out)[1])
    ref = np.asarray(fam.reference_logits(
        SZ, params, jnp.asarray(np.asarray(seq, np.int32))[None]))[0]
    want = ref[prompt.size - 1:]
    np.testing.assert_allclose(np.stack(got), want, rtol=0, atol=atol)
    return seq


def test_prefill_then_paged_decode_match_the_reference(interpreted):
    """Prefill of 21 (the expanded flash forward) then 20 decode steps
    (the latent decode kernel) through the pool, float32: past a block's
    edge twice and past the YaRN table's original 16 positions."""
    cfg, gen, params = _generator()
    _decode_against_reference(gen, params, _tokens(1, 21)[0], 20, TOL_F32)


def test_inference_server_serves_what_the_reference_puts_first(interpreted):
    """Three requests through ``InferenceServer`` (admission, the decode
    loop, the pool) with the bfloat16 weights the cell serves: every
    served token is the reference's best at its position, within the
    logit error bfloat16 products leave (measured 0 at this size; 2e-2
    holds a near tie), and the spans carry the latent counts."""
    from paddle_tpu.observability import tracing
    cfg, gen, params = _generator(dtype="bfloat16")
    server = serving.InferenceServer(generator=gen, kv_paged=True,
                                     decode_slots=4)
    server.start()
    prompts = [_tokens(1, n, seed=n)[0] for n in (9, 23, 40)]
    since = tracing.loop_spans(0.0, float("inf"))
    t0 = since[-1][2] if since else 0.0
    try:
        with serving.Client(server.endpoint) as client:
            served = [client.generate(p, max_new_tokens=18)
                      for p in prompts]
        stats = server.stats()
    finally:
        server.stop()
    gaps = fam.reference_served_gaps(SZ, SEED, list(zip(prompts, served)),
                                     64)
    assert all(g.size == 18 for g in gaps)
    assert max(float(g.max()) for g in gaps) <= 2e-2
    spans = tracing.loop_spans(t0, float("inf"))
    steps = [r[7] for r in spans if r[0] == "engine/step"
             and "mla_positions" in r[7]]
    prefills = [r[7] for r in spans if r[0] == "generator/prefill"
                and "mla_positions" in r[7]]
    assert steps and prefills
    assert all(a["mla_positions"] % SZ.num_hidden_layers == 0
               and a["mla_rows"] >= 1 for a in steps)
    assert sum(a["mla_positions"] for a in prefills) \
        == SZ.num_hidden_layers * (9 + 23 + 40)
    assert stats["mla_positions"] >= sum(a["mla_positions"]
                                         for a in steps + prefills)


def test_a_flipped_latent_value_is_caught(interpreted, monkeypatch):
    """A fault in what the pool holds: one bit of one latent value, the
    top bit of the exponent of lane 0 of layer 0's latent at each
    admitted row's first position (a single-event upset of the cache), as
    the admission's scatter writes it. The decode steps read it and the
    cell's comparison reads not correct."""
    real = kvpool.prefill_scatter

    def faulty(layout):
        scatter = real(layout)

        def run(pool, rows, tables, *rest):
            out = scatter(pool, rows, tables, *rest)
            a = out["cache_pc_0"]
            uint, bit = {2: (jnp.uint16, 0x4000),
                         4: (jnp.uint32, 0x40000000)}[a.dtype.itemsize]
            at = (tables[:, 0], 0, 0)
            flipped = jax.lax.bitcast_convert_type(
                jax.lax.bitcast_convert_type(a[at], uint) ^ uint(bit),
                a.dtype)
            out["cache_pc_0"] = a.at[at].set(flipped)
            return out

        return run

    monkeypatch.setattr(kvpool, "prefill_scatter", faulty)
    import types
    from benchmark.harness import cell, compare
    run = types.SimpleNamespace(
        cell=cell.Cell("xing4.0-29b-a4b.serve_docqa_closed64"), seed=7,
        seconds=0.5, trace=False, rehearsal=True, t0=0.0, root=ROOT,
        device={}, peaks=None, keep_xplane=None)
    from paddle_tpu import flags
    before = flags.get_flags(["FLAGS_kv_cache_dtype", "FLAGS_kv_block_size",
                              "FLAGS_kv_pool_blocks"])
    try:
        outcome = run.cell.driver.run(run)
    finally:
        flags.set_flags(before)
    checks = {c["name"]: c for c in outcome["checks"]}
    assert not compare.verdict(outcome["checks"]), checks
    for name in ("served_logit_gap", "served_off_share"):
        assert checks[name]["value"] > checks[name]["limit"], checks


# ------------------------------------------------------------ refusals

def test_config_keeps_the_source_keys_and_refuses_what_it_does_not_build():
    cfg = xing.XingConfig()
    for key, value in CONFIG.items():
        if hasattr(cfg, key) and key not in ("name", "num_hidden_layers",
                                             "num_nextn_predict_layers",
                                             "initializer_range"):
            assert getattr(cfg, key) == value, key
    inv_freq, factor, scale = cfg.rope()
    assert inv_freq.shape == (32,) and factor == 1.0
    # mscale^2 / sqrt(192), mscale = 0.1 ln 64 + 1
    assert scale == pytest.approx((0.1 * np.log(64) + 1) ** 2 / np.sqrt(192))
    assert cfg.serving().head_dim == 576 and cfg.serving().kv_heads == 1
    for bad in ({"scoring_func": "softmax"}, {"n_shared_experts": 2},
                {"num_nextn_predict_layers": 1}, {"topk_group": 2},
                {"tie_word_embeddings": True}):
        with pytest.raises(ValueError):
            xing.XingConfig(**bad)


@pytest.fixture(scope="module")
def refusing():
    cfg, gen, _ = _generator()
    return cfg, gen


def test_refuses_the_speculative_verify_by_name(refusing):
    cfg, gen = refusing
    with pytest.raises(generation.UnsupportedPathError,
                       match="speculative verify"):
        gen.generate([_tokens(1, 6)[0]], max_new_tokens=2, spec_k=2)
    with pytest.raises(generation.UnsupportedPathError,
                       match="speculative verify"):
        gen.arch.build("verify_paged_fp32", 64)


def test_refuses_chunked_prefill_by_name(refusing):
    cfg, gen = refusing
    engine = serving.GenerationEngine(gen, slots=2)
    prompt = _tokens(1, 6)[0]
    with pytest.raises(generation.UnsupportedPathError,
                       match="chunked prefill"):
        set_flags({"prefill_chunk_tokens": 4})
        try:
            assert engine.incremental_prefill_enabled()
            state = engine.start_prefill(
                serving.batching.GenerationRequest(prompt,
                                                   max_new_tokens=2), 0)
            engine.prefill_chunk(state)
        finally:
            set_flags({"prefill_chunk_tokens": 0})
            engine.release_slot(0)


def test_refuses_tp_over_one_by_name(refusing):
    cfg, _ = refusing
    with pytest.raises(generation.UnsupportedPathError, match="tp > 1"):
        GPTGenerator(cfg, fluid.Scope(), max_len=32, tp=2)


def test_refuses_an_int8_pool_by_name(refusing):
    _, gen = refusing
    with pytest.raises(generation.UnsupportedPathError,
                       match="int8 KV pool"):
        gen.new_pool(2, dtype="int8")


def test_refuses_training_by_name(refusing):
    _, gen = refusing
    with pytest.raises(generation.UnsupportedPathError, match="training"):
        gen.arch.training_program()
    with pytest.raises(generation.UnsupportedPathError, match="training"):
        gen.arch.build("train_fp32", 64)
