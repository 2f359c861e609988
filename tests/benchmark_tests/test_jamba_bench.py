"""CPU tests of what the Jamba configuration and its cell bring to the
benchmark: the configuration file against the catalog's row, the family's
closed-form counts, the reference's recurrence against a hand-written
loop and its lower-precision control, the traffic file, and every new
reader on hand-made facts, spans and events. The cell's
``--rehearse-cpu`` runs are cases of ``test_benchmark.py``'s own
rehearsal test, which walks ``BENCHMARK.json``."""
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

from benchmark.families import jamba as fam  # noqa: E402
from benchmark.harness import cell, peaks, readers  # noqa: E402

JAMBA = "jamba2-3b.serve_chat_closed64"
SPECS = {s["name"]: s for s in cell.layer_specs()}
with open(os.path.join(ROOT, "benchmark", "configs",
                       "jamba2-3b.json")) as fh:
    CONFIG = json.load(fh)
FULL, SMALL = fam.Sizes(CONFIG), fam.Sizes(CONFIG, rehearsal=True)
V5E = peaks.peaks_for("TPU v5 lite")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the catalog's row, for a machine that has no catalog
PUBLISHED = {
    "attn_layer_offset": 7, "attn_layer_period": 14,
    "expert_layer_offset": 1, "expert_layer_period": 2,
    "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 8192,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16,
    "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "model_type": "jamba",
    "num_attention_heads": 20, "num_experts": 1, "num_experts_per_tok": 1,
    "num_hidden_layers": 28, "num_key_value_heads": 1,
    "num_logits_to_keep": 1, "rms_norm_eps": 1e-06, "sliding_window": None,
    "tie_word_embeddings": True, "use_mamba_kernels": True,
    "vocab_size": 65536}


def read(name, facts, events=()):
    spec = SPECS[name]
    return readers.resolve(spec["reader"])(facts, list(events), spec)


def test_configuration_is_the_catalogs_row_whole():
    published, source = PUBLISHED, CONFIG["source"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as fh:
            row = next(json.loads(line) for line in fh
                       if '"AI21-Jamba2-3B"' in line)
        published, source = row["config"], row["source_url"]
        assert published == PUBLISHED
    for key, value in published.items():
        assert key in CONFIG and CONFIG[key] == value, key
    assert CONFIG["source"] == source
    assert CONFIG["reduced"] == [] and CONFIG["family"] == "jamba"
    assert CONFIG["published"]["num_hidden_layers"] == 28
    assert CONFIG["published"]["parameters"] == 3_029_337_472
    assert CONFIG["serve"] == {"kv_paged": True, "kv_cache_dtype": "bf16",
                               "decode_slots": 64, "slots": 64,
                               "max_len": 2560}
    for key in ("layer_order", "inner_norms", "pre_norm_residuals",
                "positional_encoding", "projection_bias", "weights",
                "state_dtype", "initializer_range"):
        assert CONFIG["assumed"][key], key
    assert "fp8" in CONFIG["precision"]["below"]
    assert "float32" in CONFIG["precision"]["states"]
    # the rehearsal: two periods of three layers, an attention layer each
    assert SMALL.layers_block_type == ["mamba", "attention", "mamba"] * 2
    assert (SMALL.hidden_size, SMALL.inner, SMALL.mamba_d_state,
            SMALL.vocab_size) == (64, 128, 8, 512)
    entry = next(c for c in cell.benchmark_json()["configs"]
                 if c["name"] == "jamba2-3b")
    assert entry["reduced"] == [] and entry["source"] == CONFIG["source"]
    assert entry["file"] == "benchmark/configs/jamba2-3b.json"


def test_the_cell_and_its_traffic_file_are_the_issues():
    c = cell.Cell(JAMBA)
    assert (c.chips, c.family, c.driver.__name__) == (
        1, fam, "benchmark.drivers.closed_loop_serve")
    assert c.end_to_end == ["serve_tokens_per_s", "setup_s"]
    chat = c.traffic
    keys = ("callers", "prompt_min", "prompt_max", "new_min", "new_max",
            "n_requests", "warm_rows_max", "traced_seconds",
            "sample_replies")
    assert tuple(chat[k] for k in keys) == \
        (64, 256, 2048, 128, 512, 2048, 4, 20, 8)     # 20: PERF.md 7.14
    assert chat["driver"] == "closed_loop_serve"
    assert chat["controls"] == ["fp8"]
    assert chat["why_warm_rows_max"] and chat["why_caller_start_gap_s"]
    # no reply runs past the positions a slot holds
    assert chat["prompt_max"] + chat["new_max"] == \
        CONFIG["serve"]["max_len"]
    # twelve prefill shapes: four length buckets by three row buckets
    from paddle_tpu.models.generation import length_bucket
    assert sorted({length_bucket(n, 16) for n in range(256, 2049)}) == \
        [256, 512, 1024, 2048]
    entry = next(w for w in cell.benchmark_json()["workloads"]
                 if w["name"] == JAMBA)
    assert (entry["config"], entry["traffic"]) == ("jamba2-3b",
                                                   "chat_closed64")


def test_parameter_byte_and_operation_counts():
    # ISSUE 36's arithmetic
    mixer = 2560 * 10240 + 5120 * 4 + 5120 + 5120 * 192 + 160 * 5120 \
        + 5120 + 5120 * 16 + 5120 + 5120 * 2560 + 192
    assert mixer == 41_241_792
    attention = 2 * 2560 ** 2 + 2 * 2560 * 128
    mlp = 3 * 2560 * 8192
    assert 26 * (mixer + mlp + 5120) + 2 * (attention + mlp + 5120) \
        + 65536 * 2560 + 2560 == fam.param_count(FULL) == 3_029_337_472
    assert FULL.layers_block_type.count("attention") == 2
    assert [i for i, k in enumerate(FULL.layers_block_type)
            if k == "attention"] == [7, 21]
    # what a row keeps: 389,120 B a Mamba layer whatever its length,
    # 1,024 B a position in the two attention layers
    assert fam.state_bytes_per_row(FULL) == 26 * 389_120 == 10_117_120
    assert fam.kv_bytes_per_position(FULL, 2) == 1024
    matrices = 26 * (2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
                     + mlp) + 2 * (attention + mlp) + 65536 * 2560
    assert fam.matmul_params_per_token(FULL) == matrices
    vectors = fam.param_count(FULL) - matrices
    assert fam.stack_weight_bytes(FULL) == \
        (matrices - 65536 * 2560) * 2 + vectors * 4
    assert fam.head_weight_bytes(FULL) == 65536 * 2560 * 2
    assert 6.05e9 < fam.stack_weight_bytes(FULL) \
        + fam.head_weight_bytes(FULL) < 6.08e9
    flops, nbytes = fam.selective_scan_work(FULL, 1000, 4)
    assert flops == 26 * 1000 * 6 * 5120 * 16
    assert nbytes == 26 * (1000 * 4 * (4 * 5120 + 32)
                           + 4 * 2 * 4 * 5120 * 16)
    assert fam.causal_attention_flops(FULL, 1, 100) == \
        2 * 2 * 20 * 128 * 5050 * 2
    with pytest.raises(ValueError):
        fam.causal_attention_flops(FULL, 1, 8, backward=True)
    assert fam.serve_flops(FULL, 100, 3) == 2 * matrices * 102 \
        + 26 * 102 * (6 * 5120 * 16 + 2 * 4 * 5120) \
        + fam.causal_attention_flops(FULL, 1, 100) \
        + 2 * 2 * 20 * 128 * 2 * (101 + 102)


def test_initialisation_is_the_familys():
    params = fam.init_params(SMALL, 7)
    assert set(params) == set(fam.param_shapes(SMALL))
    a_log = np.asarray(params["layer_0_a_log"])
    np.testing.assert_allclose(np.exp(a_log), np.tile(
        np.arange(1, 9, dtype=np.float32), (128, 1)), rtol=1e-6)
    step = np.logaddexp(0, np.asarray(params["layer_0_dt_bias"],
                                      np.float64))
    assert fam.DT_MIN * 0.999 <= step.min() and step.max() <= \
        fam.DT_MAX * 1.001
    assert np.asarray(params["layer_0_d"]).tolist() == [1.0] * 128
    assert np.abs(np.asarray(params["layer_0_conv.w_0"])).max() <= 0.5
    assert params["layer_0_in_proj.w_0"].dtype == jnp.bfloat16
    assert params["layer_0_conv.w_0"].dtype == jnp.float32
    assert "layer_1_qkv_proj.w_0" in params \
        and "layer_1_in_proj.w_0" not in params
    other = fam.init_params(SMALL, 8)
    assert np.abs(np.asarray(other["layer_0_dt_bias"])
                  - np.asarray(params["layer_0_dt_bias"])).max() > 0


def test_reference_recurrence_is_the_equations_token_by_token():
    """``reference_mamba`` against a hand-written loop in float64 over
    one Mamba layer, and the whole forward's causality: a later token
    does not reach an earlier position."""
    import jax
    params = fam.init_params(SMALL, 3)
    f32 = {n: np.asarray(a.astype(jnp.float32), np.float64)
           for n, a in params.items()}
    rng = np.random.default_rng(3)
    x = rng.normal(size=(12, 64))
    mm = fam._matmul("highest")
    got = np.asarray(fam.reference_mamba(SMALL, params, 0,
                                         jnp.asarray(x, jnp.float32), mm))

    def norm(v, gain):
        return v / np.sqrt((v * v).mean(-1, keepdims=True) + 1e-6) * gain

    pre = "layer_0_"
    a = norm(x, f32[pre + "in_norm_scale"])
    xi, z = np.split(a @ f32[pre + "in_proj.w_0"], 2, axis=-1)
    state, want = np.zeros((128, 8)), []
    for t in range(12):
        conv = f32[pre + "conv.b_0"] + sum(
            f32[pre + "conv.w_0"][j] * xi[t - 3 + j]
            for j in range(4) if t - 3 + j >= 0)
        xc = conv / (1 + np.exp(-conv))
        dbc = xc @ f32[pre + "x_proj.w_0"]
        dt = norm(dbc[:4], f32[pre + "dt_norm_scale"])
        b = norm(dbc[4:12], f32[pre + "b_norm_scale"])
        c = norm(dbc[12:], f32[pre + "c_norm_scale"])
        step = np.logaddexp(0, dt @ f32[pre + "dt_proj.w_0"]
                            + f32[pre + "dt_bias"])
        state = np.exp(step[:, None] * -np.exp(f32[pre + "a_log"])) \
            * state + (step * xc)[:, None] * b[None, :]
        y = state @ c + f32[pre + "d"] * xc
        want.append((y * z[t] / (1 + np.exp(-z[t])))
                    @ f32[pre + "out_proj.w_0"])
    np.testing.assert_allclose(got, np.asarray(want), atol=2e-5)
    toks = jnp.asarray(rng.integers(1, 512, 20), jnp.int32)
    whole = fam.reference_forward(SMALL, params, toks)
    head = fam.reference_forward(SMALL, params, toks[:11])
    np.testing.assert_allclose(np.asarray(whole)[:11], np.asarray(head),
                               atol=1e-5)
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda v: bool(jnp.isfinite(v.astype(jnp.float32)).all()), params))


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_lower_precision_control_reads_wider_than_the_reference(seed):
    rng = np.random.default_rng(seed)
    params = fam.init_params(SMALL, seed)
    rows = []
    for n in (30, 17):
        p = rng.integers(1, 512, n).astype(np.int32)
        padded = jnp.asarray(np.concatenate([p, np.zeros(8, np.int32)]))
        first = int(jnp.argmax(fam.reference_logits(
            SMALL, params, padded[None])[0, n - 1]))
        rows.append((p, np.asarray([first], np.int32)))
    own = fam.reference_served_gaps(SMALL, seed, rows, 40)
    assert max(float(g.max()) for g in own) == 0.0   # its own first choice
    fp8 = fam.reference_served_gaps(SMALL, seed, rows, 40, mode="fp8")
    assert all(g.shape == (1,) for g in fp8)
    assert all(float(g.min()) >= 0.0 for g in fp8)
    # an altered served token reads as wide as the logits are apart
    wrong = [(p, (t + 1) % 512) for p, t in rows]
    assert min(float(g.max()) for g in fam.reference_served_gaps(
        SMALL, seed, wrong, 40)) > 0.0


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "families", "jamba.py")) as fh:
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


SCAN_CALL = ('%selective_scan_fwd.3 = (f32[4,2048,5120]{2,1,0}, '
             'f32[4,16,5120]{2,1,0}) custom-call(...), '
             'custom_call_target="tpu_custom_call"')
STATE_STEP = ("%fusion.41 = f32[64,16,5120]{2,1,0} fusion(f32[64,16,5120]"
              "{2,1,0} %p, ...), kind=kLoop")
TAIL_STEP = "%fusion.42 = f32[64,15360]{1,0} fusion(...), kind=kLoop"
PAGED_CALL = ('%paged_attention_decode.9 = f32[64,20,128]{2,1,0} '
              'custom-call(...), custom_call_target="tpu_custom_call"')
MATMUL = "%fusion.7 = f32[64,8192]{1,0} fusion(...), kind=kOutput"


def facts(**over):
    base = {"family": fam, "sizes": FULL, "peaks": V5E, "kv_bytes": 2,
            "slice": (0.0, 2.0), "window_s": 2.0, "spans": [],
            "records": [], "slice_records": []}
    base.update(over)
    return base


def test_scan_roofline_counts_real_tokens_over_the_kernels_time():
    spans = [
        span("generator/prefill", 0.1, 0.3, rows=2, prompt_tokens=1500,
             scan_tokens=2048, state_layers=26, ut_steps=1, cache_layers=2),
        span("generator/prefill", 0.5, 0.6, rows=1, prompt_tokens=300,
             scan_tokens=512, state_layers=26),
        span("generator/prefill", 2.5, 2.6, rows=1, prompt_tokens=999,
             scan_tokens=1024, state_layers=26)]     # outside the slice
    events = [op(MATMUL, 0, 50_000), op(SCAN_CALL, 50_000, 4_000),
              op(SCAN_CALL, 60_000, 2_000)]
    least = 0.0
    for tokens, rows in ((1500, 2), (300, 1)):
        flops, nbytes = fam.selective_scan_work(FULL, tokens, rows)
        # float32 work of the vector unit against the matrix unit's
        # peak: the bytes take longer than the operations
        assert nbytes / 819e9 > flops / 197e12
        least += nbytes / 819e9
    got = read("selective_scan_roofline.jamba", facts(spans=spans), events)
    assert got == pytest.approx(100 * least / 0.006)
    assert 0 < got < 100
    assert SPECS["selective_scan_roofline.jamba"]["bound"] == "bandwidth"
    # no event of the kernel, a program whose spans carry no
    # prompt_tokens, a run with no chip's peaks: nothing, never 0
    assert read("selective_scan_roofline.jamba", facts(spans=spans),
                [op(MATMUL, 0, 10)]) is None
    bare = [span("generator/prefill", 0.1, 0.3, rows=2, ut_steps=1)]
    assert read("selective_scan_roofline.jamba", facts(spans=bare),
                events) is None
    assert read("selective_scan_roofline.jamba",
                facts(spans=spans, peaks=None), events) is None


def test_state_share_and_hbm_roofline_read_the_banks_events_and_spans():
    rec = {"ok": True, "prompt_len": 100, "new_tokens": 3, "t_send": 0.0,
           "t_reply": 0.9}
    spans = [
        span("engine/step", 0.1, 0.2, state_rows=60, state_layers=26,
             ut_steps=1, cache_layers=2, ahead=1),
        span("engine/step", 0.3, 0.4, state_rows=64, state_layers=26),
        span("engine/step", 0.45, 0.5),                   # read only
        span("generator/prefill", 0.5, 0.6, rows=2, prompt_tokens=900,
             scan_tokens=1024, state_layers=26),
        span("engine/step", 2.5, 2.6, state_rows=64)]     # outside
    events = [op(MATMUL, 0, 60_000), op(STATE_STEP, 60_000, 9_000),
              op(TAIL_STEP, 70_000, 1_000), op(SCAN_CALL, 80_000, 5_000),
              op(PAGED_CALL, 90_000, 5_000)]
    busy = 0.080
    assert read("ssm_device_share.jamba", facts(), events) == \
        pytest.approx(100 * 15_000 / 80_000)
    assert read("ssm_device_share.jamba", facts(),
                [op(MATMUL, 0, 10), op(PAGED_CALL, 20, 5)]) is None
    weights = fam.stack_weight_bytes(FULL) + fam.head_weight_bytes(FULL)
    moved = 3 * weights + 2 * 10_117_120 * (60 + 64) \
        + (101 + 102) * 1024
    f = facts(spans=spans, slice_records=[rec], records=[rec])
    got = read("state_hbm_roofline.jamba", f, events)
    assert got == pytest.approx(100 * moved / 819e9 / busy)
    assert 0 < got < 100
    assert SPECS["state_hbm_roofline.jamba"]["bound"] == "bandwidth"
    # a program whose steps carry no state_rows, a trace with no device
    # event, a run with no chip's peaks: nothing
    bare = [span("engine/step", 0.1, 0.2, ut_steps=1, cache_layers=2)]
    assert read("state_hbm_roofline.jamba", facts(spans=bare),
                events) is None
    assert read("state_hbm_roofline.jamba", f) is None
    assert read("state_hbm_roofline.jamba", dict(f, peaks=None),
                events) is None
    assert read("paged_attention_roofline.jamba", f, events) == \
        pytest.approx(100 * (101 + 102) * 1024 / 819e9 / 0.005)
    assert read("serve_mfu.jamba", f) == pytest.approx(
        100 * fam.serve_flops(FULL, 100, 3) / 2.0 / 197e12)


NEW_METRICS = sorted(n for n in SPECS if n.endswith(".jamba"))


def test_the_new_metrics_are_the_issues():
    assert NEW_METRICS == sorted(
        [f"{m}.jamba" for m in (
            "serve_mfu", "device_idle_share", "device_idle_decode_host",
            "device_idle_admit_host", "decode_step_ms",
            "decode_round_host_ms", "decode_live_rows", "admit_share",
            "first_token_ms_p75", "queue_wait_ms_p75",
            "generator_recompiles", "kv_pool_used_share",
            "reply_ms_per_token_p95", "paged_attention_roofline",
            "flash_attention_roofline", "selective_scan_roofline",
            "ssm_device_share", "state_hbm_roofline")])
    assert all(SPECS[n]["moves"] == "serve_tokens_per_s"
               for n in NEW_METRICS)
    listed = {m["name"]: m for m in cell.benchmark_json()["per_layer"]}
    for name in NEW_METRICS:
        entry = listed[name]
        assert entry == {k: SPECS[name][k] for k in entry}, name
    assert SPECS["ssm_device_share.jamba"]["layer"] == "recurrent state"
    # the twins read through the readers their twins read through
    for name in NEW_METRICS:
        base = name[:-len(".jamba")]
        twin = SPECS.get(base + ".ouro") or SPECS.get(base + ".mellum")
        if twin is not None:
            assert SPECS[name]["reader"] == twin["reader"], name
    # the serve cell joins the metric it reports, and no other list
    e2e = {m["name"]: m for m in cell.benchmark_json()["end_to_end"]}
    assert e2e["serve_tokens_per_s"]["workloads"][-1] == JAMBA
    assert JAMBA not in e2e["serve_ms_per_token_p95"]["workloads"]


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_metric_names_its_cell_and_is_silent_without_a_reading(name):
    assert SPECS[name]["workloads"] == [JAMBA]
    empty = {"family": fam, "sizes": FULL, "peaks": V5E, "window_s": 1.0,
             "records": [], "spans": [], "slice": (0.0, 1.0),
             "slice_records": []}
    # a program with no such span, counter or event: nothing, no raise
    assert read(name, empty) in (None, 0)
    if "roofline" in name or "mfu" in name or "share" in name:
        assert read(name, empty) is None
