"""CPU tests of what the Xing configuration and its cell bring to the
benchmark: the configuration file against the catalog's row and the cut,
the cell and its traffic file, the family's closed-form counts, its
initialisation, the reference's mHC and latent attention against
hand-written float64 loops and its lower-precision control, and every new
reader on hand-made facts, spans and events. The cell's ``--rehearse-cpu``
runs are cases of ``test_benchmark.py``'s own rehearsal test, which walks
``BENCHMARK.json``."""
import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.families import xing as fam  # noqa: E402
from benchmark.harness import cell, peaks, readers  # noqa: E402

XING = "xing4.0-29b-a4b.serve_docqa_closed64"
SPECS = {s["name"]: s for s in cell.layer_specs()}
with open(os.path.join(ROOT, "benchmark", "configs",
                       "xing4.0-29b-a4b.json")) as fh:
    CONFIG = json.load(fh)
FULL, SMALL = fam.Sizes(CONFIG), fam.Sizes(CONFIG, rehearsal=True)
V5E = peaks.peaks_for("TPU v5 lite")
# path of the model catalog (a JSON-lines file), when one is at hand
CATALOG = os.environ.get("MODEL_CATALOG", "")
# the catalog's row, for a machine that has no catalog
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "q_lora_rank": 768, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 131072}


def read(name, facts, events=()):
    spec = SPECS[name]
    return readers.resolve(spec["reader"])(facts, list(events), spec)


def test_configuration_is_the_catalogs_row_but_the_cut():
    published, source = PUBLISHED, CONFIG["source"]
    if CATALOG and os.path.exists(CATALOG):
        with open(CATALOG) as fh:
            row = next(json.loads(line) for line in fh
                       if '"Xing4.0-29B-A4B"' in line)
        published, source = row["config"], row["source_url"]
        assert published == PUBLISHED
    for key, value in published.items():
        if key in CONFIG["reduced"]:
            assert CONFIG["published"][key] == value, key
        else:
            assert CONFIG[key] == value, key
    assert CONFIG["source"] == source and CONFIG["family"] == "xing"
    assert CONFIG["reduced"] == ["num_hidden_layers",
                                 "num_nextn_predict_layers"]
    assert (CONFIG["num_hidden_layers"], CONFIG["num_nextn_predict_layers"]) \
        == (7, 0)
    assert CONFIG["published"] == {"num_hidden_layers": 40,
                                   "num_nextn_predict_layers": 1}
    assert CONFIG["deployment"] == (
        "layers 1-7 of 40 on one 16 GB v5e, every expert, head and the "
        "whole vocabulary here; the other 33 layers and the MTP module on "
        "further chips as pipeline stages; fewer layers make the host's "
        "share larger than in a deployment")
    for key in ("embedding_copy", "head_mix", "sinkhorn", "initial_values",
                "rope_layout"):
        assert CONFIG["assumed"][key], key
    assert set(CONFIG["changed_why"]) >= set(CONFIG["reduced"])
    assert "float32 residual streams" in CONFIG["precision"]["states"]
    assert "fp8" in CONFIG["precision"]["below"]
    assert CONFIG["serve"] == {"kv_paged": True, "kv_cache_dtype": "bf16",
                               "kv_block_size": 128, "kv_pool_blocks": 3401,
                               "decode_slots": 64, "max_len": 8704}
    entry = next(c for c in cell.benchmark_json()["configs"]
                 if c["name"] == "xing4.0-29b-a4b")
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["file"] == "benchmark/configs/xing4.0-29b-a4b.json"
    # no width is cut, and the floors hold: 4 and more expert layers after
    # the dense ones, 64 >= 8 routed experts, the whole vocabulary
    assert FULL.moe_layers == 5 and FULL.dense_layers == 2
    assert FULL.n_routed_experts >= 8 and FULL.vocab_size == 131072


def test_the_cell_and_its_traffic_file_are_as_specified():
    c = cell.Cell(XING)
    assert (c.chips, c.family, c.driver.__name__) == (
        1, fam, "benchmark.drivers.closed_loop_serve_off_share")
    # held to the tokens a second it completes, as the other expert cells;
    # the Jamba cell stays last in that list (test_jamba_bench.py)
    assert c.end_to_end == ["serve_tokens_per_s", "setup_s"]
    t = c.traffic
    keys = ("callers", "prompt_min", "prompt_max", "new_min", "new_max",
            "n_requests", "warm_rows_max")
    assert tuple(t[k] for k in keys) == (64, 2048, 8192, 128, 512, 2048, 4)
    assert t["driver"] == "closed_loop_serve_off_share"
    assert t["controls"] == ["fp8"]
    # both numbers that decide correct have a limit, here and rehearsed
    assert set(t["limits"]) == set(t["rehearsal"]["limits"]) == {
        "served_logit_gap", "served_off_share"}
    # about one mean single-row admission of the prompt mix
    assert 0.1 <= t["caller_start_gap_s"] <= 0.3
    # no reply runs past the positions a slot holds
    assert t["prompt_max"] + t["new_max"] == CONFIG["serve"]["max_len"]
    from paddle_tpu.models.generation import length_bucket
    assert sorted({length_bucket(n, 16) for n in range(2048, 8193)}) == \
        [2048, 3072, 4096, 6144, 8192]
    entry = next(w for w in cell.benchmark_json()["workloads"]
                 if w["name"] == XING)
    assert (entry["config"], entry["traffic"]) == ("xing4.0-29b-a4b",
                                                   "docqa_closed64")
    assert len(entry["why"]) <= 200


def test_parameter_byte_and_operation_counts():
    # the sizes by hand: MLA 28.4M a layer, mHC 0.7M, a dense layer 128.2M,
    # an expert layer 745M, embedding and head 940M
    mla = 3584 * 768 + 768 * 32 * 192 + 3584 * 576 + 512 * 32 * 256 \
        + 4096 * 3584 + 768 + 512 + 2 * 3584
    assert 28.3e6 < mla < 28.5e6
    hc = 2 * (14336 * 24 + 3 + 24)
    dense = mla + hc + 3 * 3584 * 9216
    moe = mla + hc + 3584 * 64 + 64 + 3 * 3584 * 1024 \
        + 64 * 3 * 3584 * 1024
    ends = 2 * 131072 * 3584 + 14336 * 4 + 1 + 4 + 3584
    assert fam.param_count(FULL) == 2 * dense + 5 * moe + ends
    assert round(dense / 1e6, 1) == 128.2 and round(moe / 1e6) == 745
    assert 9.84e9 < fam.weight_bytes(FULL) < 9.86e9
    # a position: 7 layers x (512 + 64) x 2 B
    assert fam.kv_bytes_per_position(FULL, 2) == 8064
    assert FULL.position_width == 576
    # expanded causal attention: keys of 192, values of 128 a head
    assert fam.causal_attention_flops(FULL, 1, 100) == \
        2 * 32 * 5050 * 7 * (192 + 128)
    with pytest.raises(ValueError):
        fam.causal_attention_flops(FULL, 1, 8, backward=True)
    active = fam.active_matmul_params(FULL)
    assert active == 7 * (mla - 768 - 512 - 2 * 3584 + 2 * 14336 * 24) \
        + 2 * 3 * 3584 * 9216 + 5 * (3584 * 64 + 5 * 3 * 3584 * 1024) \
        + 14336 * 4 + 3584 * 131072
    # one prompt of 100 and 3 new tokens: the two decoded tokens' queries
    # read 101 and 102 positions, absorbed (576 + 512 a head a layer)
    assert fam.serve_flops(FULL, 100, 3) == pytest.approx(
        2 * active * 102 + fam.causal_attention_flops(FULL, 1, 100)
        + 2 * 32 * 7 * (576 + 512) * (101 + 102))
    # the decode kernel's bytes: positions x 1,152, a row a layer's query
    # and output in float32
    assert fam.mla_decode_bytes(FULL, 10, 2) == \
        10 * 1152 + 2 * 4 * 32 * (512 + 64 + 512)
    flops, nbytes = fam.expert_layer_work(FULL, 256, 63)
    assert flops == 256 * 6 * 3584 * 1024
    assert nbytes == 63 * 3 * 3584 * 1024 * 2 + 256 * 3584 * 6


def test_initialisation_is_the_familys():
    params = fam.init_params(SMALL, 7)
    assert set(params) == set(fam.param_shapes(SMALL))
    bias = np.asarray(params["layer_0_hc_attn_bias"])
    # pre and post at 0, the residual mix 2 on its diagonal
    np.testing.assert_array_equal(bias[:8], 0.0)
    np.testing.assert_array_equal(bias[8:].reshape(4, 4),
                                  2.0 * np.eye(4))
    assert np.asarray(params["layer_0_hc_attn_alpha"]).tolist() == \
        pytest.approx([0.1] * 3)
    assert np.asarray(params["hc_head_bias"]).tolist() == [0.0] * 4
    assert params["layer_0_q_a_proj.w_0"].dtype == jnp.bfloat16
    for name in ("layer_1_router.w_0", "layer_1_router_e_bias",
                 "layer_0_hc_attn_phi"):
        assert params[name].dtype == jnp.float32
        assert 0 < np.std(np.asarray(params[name])) < 0.05
    assert "layer_0_gate_proj.w_0" in params \
        and "layer_1_shared_gate_proj.w_0" in params \
        and "layer_0_router.w_0" not in params
    other = fam.init_params(SMALL, 8)
    assert np.abs(np.asarray(other["layer_1_router_e_bias"])
                  - np.asarray(params["layer_1_router_e_bias"])).max() > 0


def _norm(v, gain=1.0, eps=1e-6):
    return v / np.sqrt((v * v).mean(-1, keepdims=True) + eps) * gain


def test_reference_mix_and_latent_attention_are_the_equations():
    """``hc_weights`` and ``_attention`` against hand-written float64
    loops, and the whole forward's causality."""
    params = fam.init_params(SMALL, 3)
    f64 = {n: np.asarray(a.astype(jnp.float32), np.float64)
           for n, a in params.items()}
    rng = np.random.default_rng(3)
    n, c = SMALL.hc_mult, SMALL.hidden_size
    x = rng.normal(size=(5, n, c))
    pre, post, res = (np.asarray(a) for a in fam.hc_weights(
        SMALL, jnp.asarray(x, jnp.float32), params["layer_0_hc_attn_phi"],
        params["layer_0_hc_attn_alpha"], params["layer_0_hc_attn_bias"]))
    a, b = f64["layer_0_hc_attn_alpha"], f64["layer_0_hc_attn_bias"]
    for t in range(5):
        proj = _norm(x[t].reshape(-1)) @ f64["layer_0_hc_attn_phi"]
        np.testing.assert_allclose(
            pre[t], 1 / (1 + np.exp(-(a[0] * proj[:4] + b[:4]))), atol=1e-5)
        np.testing.assert_allclose(
            post[t], 2 / (1 + np.exp(-(a[1] * proj[4:8] + b[4:8]))),
            atol=1e-5)
        m = np.exp(np.clip((a[2] * proj[8:] + b[8:]).reshape(4, 4), -30,
                           30))
        for _ in range(20):
            m = m / (m.sum(1, keepdims=True) + 1e-6)
            m = m / (m.sum(0, keepdims=True) + 1e-6)
        np.testing.assert_allclose(res[t], m, atol=1e-5)
    np.testing.assert_allclose(res.sum(-2), 1.0, atol=1e-6)
    # latent attention of layer 0 over 6 positions, expanded, by hand
    h = rng.normal(size=(6, c))
    got = np.asarray(fam._attention(SMALL, params, 0,
                                    jnp.asarray(h, jnp.float32),
                                    fam._matmul("highest"), 2))
    inv_freq, factor, scale = fam.yarn_table(SMALL)
    H, dn, R, dv = 4, 16, 8, 8
    pre_ = "layer_0_"
    aa = _norm(h, f64[pre_ + "input_norm_scale"])
    q = (_norm(aa @ f64[pre_ + "q_a_proj.w_0"],
               f64[pre_ + "q_a_norm_scale"]) @ f64[pre_ + "q_b_proj.w_0"]
         ).reshape(6, H, dn + R)
    kv = aa @ f64[pre_ + "kv_a_proj.w_0"]
    ckv = _norm(kv[:, :128], f64[pre_ + "kv_a_norm_scale"])
    up = (ckv @ f64[pre_ + "kv_b_proj.w_0"]).reshape(6, H, dn + dv)

    def rope(v, p):
        ang = p * inv_freq.astype(np.float64)
        v1, v2 = v[..., :R // 2], v[..., R // 2:]
        return np.concatenate([v1 * np.cos(ang) - v2 * np.sin(ang),
                               v2 * np.cos(ang) + v1 * np.sin(ang)], -1) \
            * factor

    out = np.zeros((6, H * dv))
    for t in range(6):
        for hh in range(H):
            qv = np.concatenate([q[t, hh, :dn], rope(q[t, hh, dn:], t)])
            s = np.array([qv @ np.concatenate([up[j, hh, :dn],
                                               rope(kv[j, 128:], j)])
                          for j in range(t + 1)]) * scale
            p = np.exp(s - s.max())
            out[t, hh * dv:(hh + 1) * dv] = (p / p.sum()) @ up[:t + 1, hh,
                                                               dn:]
    want = out @ f64[pre_ + "o_proj.w_0"]
    np.testing.assert_allclose(got, want, atol=2e-5)
    toks = jnp.asarray(rng.integers(1, 256, 20), jnp.int32)
    whole = fam.reference_hidden(SMALL, params, toks)
    head = fam.reference_hidden(SMALL, params, toks[:11])
    np.testing.assert_allclose(np.asarray(whole)[:11], np.asarray(head),
                               atol=1e-5)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_lower_precision_control_reads_wider_than_the_reference(seed):
    rng = np.random.default_rng(seed)
    params = fam.init_params(SMALL, seed)
    rows = []
    for n in (30, 17):
        p = rng.integers(1, 256, n).astype(np.int32)
        padded = jnp.asarray(np.concatenate([p, np.zeros(8, np.int32)]))
        first = int(jnp.argmax(fam.reference_logits(
            SMALL, params, padded[None])[0, n - 1]))
        rows.append((p, np.asarray([first], np.int32)))
    own = fam.reference_served_gaps(SMALL, seed, rows, 40)
    assert max(float(g.max()) for g in own) == 0.0   # its own first choice
    fp8 = fam.reference_served_gaps(SMALL, seed, rows, 40, mode="fp8")
    assert all(g.shape == (1,) and float(g.min()) >= 0.0 for g in fp8)
    # an altered served token reads as wide as the logits are apart
    wrong = [(p, (t + 1) % 256) for p, t in rows]
    assert min(float(g.max()) for g in fam.reference_served_gaps(
        SMALL, seed, wrong, 40)) > 0.0


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "families", "xing.py")) as fh:
        source = fh.read()
    body = source.split("# -------------------------------------------------"
                        "------------- reference")[1]
    assert "paddle_tpu" not in body and "pallas" not in body


# ------------------------------------------------------------- the readers

def span(name, start, end, **attrs):
    return (name, start, end, 7, "loop:1", name + str(start), "", attrs)


def op(name, start_us, dur_us):
    return ["/device:TPU:0", "XLA Ops", name, 1000 * start_us,
            1000 * dur_us]


MLA_CALL = ('%mla_paged_decode.3 = f32[64,32,512]{2,1,0} custom-call('
            'bf16[3401,128,512]{2,1,0} %p, bf16[3401,64,128]{2,1,0} %q), '
            'custom_call_target="tpu_custom_call"')
FLASH_CALL = ('%flash_attention_fwd.2 = (bf16[1,32,8192,128]{3,2,1,0}) '
              'custom-call(...), custom_call_target="tpu_custom_call"')
HC_MIX = ("%fusion.12 = f32[64,1,3584]{2,1,0} fusion(f32[64,1,14336]{2,1,0}"
          " %p, f32[64,1,4]{2,1,0} %w), kind=kLoop")
HC_PHI = ("%fusion.13 = f32[64,24]{1,0} fusion(f32[64,14336]{1,0} %x, "
          "f32[14336,24]{1,0} %phi), kind=kOutput")
MOE_COMBINE = ("%fusion.14 = f32[64,4,3584]{2,1,0} fusion(...), "
               "kind=kLoop")
MATMUL = "%fusion.7 = f32[64,9216]{1,0} fusion(...), kind=kOutput"


def facts(**over):
    base = {"family": fam, "sizes": FULL, "peaks": V5E, "kv_bytes": 2,
            "slice": (0.0, 2.0), "window_s": 2.0, "spans": [],
            "records": [], "slice_records": []}
    base.update(over)
    return base


def test_decode_roofline_reads_the_steps_positions_over_the_kernels_time():
    spans = [
        span("engine/step", 0.1, 0.2, mla_positions=7 * 300_000,
             mla_rows=64, ut_steps=1, cache_layers=7),
        span("engine/step", 0.3, 0.4, mla_positions=7 * 290_000,
             mla_rows=62),
        span("engine/step", 0.45, 0.5),                   # read only
        span("generator/prefill", 0.5, 0.6, rows=1, mla_positions=7 * 4000),
        span("engine/step", 2.5, 2.6, mla_positions=7, mla_rows=1)]
    events = [op(MATMUL, 0, 50_000), op(MLA_CALL, 50_000, 4_000),
              op(MLA_CALL, 60_000, 3_000)]
    nbytes = fam.mla_decode_bytes(FULL, 7 * 300_000, 64 * 7) \
        + fam.mla_decode_bytes(FULL, 7 * 290_000, 62 * 7)
    got = read("mla_decode_roofline.xing", facts(spans=spans), events)
    assert got == pytest.approx(100 * nbytes / 819e9 / 0.007)
    assert 0 < got < 100
    assert SPECS["mla_decode_roofline.xing"]["bound"] == "bandwidth"
    # no kernel event, steps with no mla attrs (a program before the
    # latent cache), no chip's peaks: nothing, never 0
    assert read("mla_decode_roofline.xing", facts(spans=spans),
                [op(MATMUL, 0, 10)]) is None
    bare = [span("engine/step", 0.1, 0.2, ut_steps=1, cache_layers=7)]
    assert read("mla_decode_roofline.xing", facts(spans=bare),
                events) is None
    assert read("mla_decode_roofline.xing", facts(spans=spans, peaks=None),
                events) is None


def test_device_shares_find_the_mechanisms_by_name_and_width():
    events = [op(MATMUL, 0, 40_000), op(MLA_CALL, 40_000, 5_000),
              op(FLASH_CALL, 50_000, 10_000), op(HC_MIX, 60_000, 2_000),
              op(HC_PHI, 62_000, 1_000), op(MOE_COMBINE, 70_000, 3_000)]
    busy = 61_000
    assert read("mla_device_share.xing", facts(), events) == \
        pytest.approx(100 * 15_000 / busy)
    # the expert layer's combine is [.., 4, 3584], not the streams' 14,336
    assert read("hc_device_share.xing", facts(), events) == \
        pytest.approx(100 * 3_000 / busy)
    assert read("hc_device_share.xing", facts(),
                [op(MATMUL, 0, 10), op(MOE_COMBINE, 20, 5)]) is None
    rec = {"ok": True, "prompt_len": 4000, "new_tokens": 3, "t_send": 0.5,
           "t_reply": 0.9}
    got = read("flash_attention_roofline.xing",
               facts(slice_records=[rec]), events)
    assert got == pytest.approx(100 * fam.causal_attention_flops(
        FULL, 1, 4000) / 197e12 / 0.010)


def test_routing_readers_read_the_expert_layers_counters():
    spans = [span("engine/step", 0.1, 0.2, moe_tokens=1280,
                  moe_load_max=5 * 40, moe_experts_hit=5 * 63),
             span("generator/prefill", 0.3, 0.5, moe_tokens=5 * 4 * 4096,
                  moe_load_max=5 * 400, moe_experts_hit=320)]
    events = [op(("%moe_experts_swiglu.1 = bf16[512,3584] custom-call(...),"
                  ' custom_call_target="tpu_custom_call"'), 0, 20_000)]
    assert read("moe_load_max_over_mean.xing", facts(spans=spans)) == \
        pytest.approx(200 * 64 / 1280)
    least = 0.0
    for tokens, hit in ((1280, 315), (81920, 320)):
        flops, nbytes = fam.expert_layer_work(FULL, tokens, hit)
        least += max(flops / 197e12, nbytes / 819e9)
    assert read("moe_experts_roofline.xing", facts(spans=spans), events) \
        == pytest.approx(100 * least / 0.02)


NEW_METRICS = sorted(n for n in SPECS if n.endswith(".xing"))


def test_the_new_metrics_are_as_specified():
    assert NEW_METRICS == sorted(
        [f"{m}.xing" for m in (
            "mla_decode_roofline", "mla_device_share", "hc_device_share",
            "flash_attention_roofline", "moe_experts_roofline",
            "moe_load_max_over_mean", "serve_mfu", "device_idle_share",
            "decode_step_ms", "admit_share", "kv_pool_used_share",
            "generator_recompiles")])
    bench = cell.benchmark_json()
    listed = {m["name"]: m for m in bench["per_layer"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for name in NEW_METRICS:
        entry = listed[name]
        assert entry == {k: SPECS[name][k] for k in entry}, name
        assert entry["moves"] == "serve_tokens_per_s", name
        assert XING in e2e[entry["moves"]]["workloads"], name
    assert SPECS["hc_device_share.xing"]["layer"] == "residual streams"
    assert SPECS["mla_device_share.xing"]["layer"] == "latent attention"
    # the twins read through the readers their twins read through
    for name in NEW_METRICS:
        base = name[:-len(".xing")]
        twin = SPECS.get(base + ".jamba") or SPECS.get(base + ".mellum")
        if twin is not None:
            assert SPECS[name]["reader"] == twin["reader"], name


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_metric_names_its_cell_and_is_silent_without_a_reading(name):
    assert SPECS[name]["workloads"] == [XING]
    empty = {"family": fam, "sizes": FULL, "peaks": V5E, "window_s": 1.0,
             "records": [], "spans": [], "slice": (0.0, 1.0),
             "slice_records": []}
    # a program with no such span, counter or event: nothing, no raise
    assert read(name, empty) in (None, 0)
    if "roofline" in name or "mfu" in name or "share" in name:
        assert read(name, empty) is None


def test_off_share_counts_the_served_tokens_off_the_references_choice():
    from benchmark.drivers.closed_loop_serve_off_share import off_share
    gaps = [np.array([0.0, 0.0, 0.3, 0.0]), np.array([0.0, 1.2])]
    assert off_share(gaps) == pytest.approx(2 / 6)
    assert off_share([np.zeros(5)]) == 0.0
    # nothing compared: no limit passes it
    assert off_share([]) == 1.0 and off_share([np.zeros(0)]) == 1.0


def test_the_cells_driver_adds_the_off_share_to_the_base_drivers_checks(
        monkeypatch):
    import types
    from benchmark.drivers import closed_loop_serve as base
    from benchmark.drivers import closed_loop_serve_off_share as driver
    from benchmark.harness import compare
    served = [np.array([0.0, 0.4, 0.0, 0.0]), np.array([0.0, 0.0])]

    def base_run(run):
        # the base driver reads the reference through the cell's family
        gaps = run.cell.family.reference_served_gaps(None, run.seed, [], 0)
        assert run.cell.family.Sizes is fam.Sizes
        widest = max(float(g.max()) for g in gaps)
        return {"facts": {"family": run.cell.family},
                "checks": [compare.check("served_logit_gap", widest, 3.0),
                           compare.check("malformed_replies", 0, 0)]}

    monkeypatch.setattr(base, "run", base_run)
    monkeypatch.setattr(fam, "reference_served_gaps",
                        lambda *args, **kwargs: served)
    c = cell.Cell(XING)
    run = types.SimpleNamespace(cell=c, seed=5, rehearsal=False)
    outcome = driver.run(run)
    assert c.family is fam and outcome["facts"]["family"] is fam
    checks = [(k["name"], k["value"], k["limit"]) for k in outcome["checks"]]
    limit = c.traffic["limits"]["served_off_share"]
    assert checks == [("served_logit_gap", 0.4, 3.0),
                      ("served_off_share", pytest.approx(1 / 6), limit),
                      ("malformed_replies", 0.0, 0.0)]
    assert compare.verdict(outcome["checks"])
    # calibrate.py's lines are the base driver's: their nonzero_share is
    # this share
    assert driver.calibrate is base.calibrate
