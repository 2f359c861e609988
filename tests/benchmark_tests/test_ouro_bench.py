"""CPU tests of what the Ouro configuration and the cell added with it
bring to the benchmark: the configuration file against the catalog's
published sizes, the family's closed-form counts (four passes, 192 cache
layers), the reference's exit distribution and its lower-precision
control, and every new reader on hand-made facts, spans and events. The
cell's ``--rehearse-cpu`` runs are cases of ``test_benchmark.py``'s own
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

from benchmark.families import gpt  # noqa: E402
from benchmark.families import ouro as fam  # noqa: E402
from benchmark.harness import cell, peaks, readers  # noqa: E402

OURO = "ouro-2.6b.serve_reason_closed16"
CHAT = "gpt2-medium.serve_chat_closed32"
SPECS = {s["name"]: s for s in cell.layer_specs()}
with open(os.path.join(ROOT, "benchmark", "configs",
                       "ouro-2.6b.json")) as fh:
    CONFIG = json.load(fh)
FULL, SMALL = fam.Sizes(CONFIG), fam.Sizes(CONFIG, rehearsal=True)
V5E = peaks.peaks_for("TPU v5 lite")


def read(name, facts, events=()):
    spec = SPECS[name]
    return readers.resolve(spec["reader"])(facts, list(events), spec)


def test_configuration_is_the_published_one_whole():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") \
            if os.path.exists(
                "/opt/skills/guides/model-configs/architectures.jsonl") \
            else open(os.devnull) as fh:
        rows = [json.loads(line) for line in fh if '"Ouro-2.6B"' in line]
    published = rows[0]["config"] if rows else {
        "hidden_size": 2048, "num_hidden_layers": 48,
        "num_attention_heads": 16, "num_key_value_heads": 16,
        "head_dim": 128, "intermediate_size": 5632, "vocab_size": 49152,
        "total_ut_steps": 4, "early_exit_threshold": 1,
        "rms_norm_eps": 1e-06, "rope_theta": 1000000,
        "max_position_embeddings": 65536, "hidden_act": "silu",
        "tie_word_embeddings": False, "rope_scaling": None,
        "sliding_window": None}
    for key, value in published.items():
        assert CONFIG[key] == value, key
    assert CONFIG["reduced"] == [] and CONFIG["family"] == "ouro"
    assert CONFIG["layer_types"] == ["full_attention"] * 48
    assert CONFIG["published"]["num_hidden_layers"] == 48
    assert CONFIG["published"]["total_ut_steps"] == 4
    assert CONFIG["serve"] == {"kv_paged": True, "kv_cache_dtype": "bf16",
                               "decode_slots": 16, "max_len": 320}
    for key in ("norms_a_block", "final_norm_a_pass", "exit_gate",
                "cache_per_pass", "projection_bias", "initializer_range",
                "weights"):
        assert CONFIG["assumed"][key], key
    assert "fp8" in CONFIG["precision"]["below"]
    small = CONFIG["rehearsal"]
    assert (small["num_hidden_layers"], small["hidden_size"],
            small["num_attention_heads"], small["head_dim"],
            small["intermediate_size"], small["vocab_size"],
            small["total_ut_steps"], small["serve"]) == \
        (2, 32, 4, 8, 64, 128, 4, {"decode_slots": 4, "max_len": 64})
    entry = next(c for c in cell.benchmark_json()["configs"]
                 if c["name"] == "ouro-2.6b")
    assert entry["reduced"] == [] and entry["source"] == CONFIG["source"]


def test_traffic_file_is_the_issues():
    reason = cell.Cell(OURO).traffic
    keys = ("callers", "prompt_min", "prompt_max", "new_min", "new_max",
            "n_requests", "shape_seed", "caller_start_gap_s",
            "warm_rows_max", "traced_seconds", "sample_replies")
    assert tuple(reason[k] for k in keys) == \
        (16, 32, 128, 64, 192, 1024, 33, 0.25, 4, 10, 8)
    assert reason["driver"] == "closed_loop_serve"
    assert reason["controls"] == ["fp8"]
    # the limit stands between the program's readings on the chip (0.32
    # to 0.64 over 12 seeds) and the fp8 control's (4.8 to 5.4)
    assert 0.64 < reason["limits"]["served_logit_gap"] < 4.8
    # no reply runs past the positions a slot holds
    assert reason["prompt_max"] + reason["new_max"] == \
        CONFIG["serve"]["max_len"]
    assert cell.Cell(OURO).end_to_end == ["serve_tokens_per_s", "setup_s"]


def test_parameter_byte_and_operation_counts():
    # ISSUE 33's arithmetic
    layer = 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048
    assert layer == 51_388_416
    assert fam.param_count(FULL) == 48 * layer + 2 * 49152 * 2048 + 4097 \
        == 2_667_974_657
    assert fam.layer_matmul_params(FULL) == layer - 4 * 2048
    # a token: every block's matrices four times, the head once
    per_token = 4 * (48 * (layer - 4 * 2048) + 2048) + 2048 * 49152
    assert fam.matmul_params_per_token(FULL) == per_token
    # 192 cache layers: 1.5 MB a position, 16 times GPT-2 medium's
    assert fam.kv_bytes_per_position(FULL, 2) == 1_572_864
    gsz = gpt.Sizes(cell.Cell(CHAT).config)
    assert gpt.kv_bytes_per_position(gsz, 2) * 16 == 1_572_864
    assert fam.stack_weight_bytes(FULL) == \
        48 * ((layer - 4 * 2048) * 2 + 4 * 2048 * 4) + 4097 * 4
    assert 4.93e9 < fam.stack_weight_bytes(FULL) < 4.94e9
    assert fam.head_weight_bytes(FULL) == 2048 * 49152 * 2
    assert fam.causal_attention_flops(FULL, 1, 100) == \
        2 * 2 * 16 * 128 * 5050 * 192
    flops = fam.serve_flops(FULL, 100, 3)
    assert flops == 2 * per_token * 102 + fam.causal_attention_flops(
        FULL, 1, 100) + 2 * 2 * 16 * 128 * 192 * (101 + 102)
    with pytest.raises(ValueError):
        fam.causal_attention_flops(FULL, 1, 8, backward=True)
    # one pass is a quarter of the stack's share of four
    one = fam.Sizes(dict(CONFIG, total_ut_steps=1))
    assert fam.matmul_params_per_token(one) - 2048 * 49152 == \
        (per_token - 2048 * 49152) // 4
    assert fam.kv_bytes_per_position(one, 2) * 4 == 1_572_864


def test_reference_exit_distribution_is_the_published_one():
    gates = jnp.asarray([[0.5, 0.1], [0.5, 0.9], [0.2, 0.3], [0.7, 0.6]])
    probs = np.asarray(fam.exit_distribution(gates))
    np.testing.assert_allclose(probs[:, 0], [0.5, 0.25, 0.05, 0.2],
                               atol=1e-7)
    np.testing.assert_allclose(probs[:, 1],
                               [0.1, 0.81, 0.09 * 0.3, 0.09 * 0.7],
                               atol=1e-7)
    np.testing.assert_allclose(probs.sum(axis=0), 1.0, atol=1e-7)
    # one pass: it takes everything
    np.testing.assert_allclose(
        np.asarray(fam.exit_distribution(gates[:1])), 1.0)
    params = fam.init_params(SMALL, 5)
    toks = jnp.asarray(np.random.default_rng(5).integers(1, 128, 20),
                       jnp.int32)
    hidden, probs = fam.reference_forward(SMALL, params, toks)
    assert hidden.shape == (20, 32) and probs.shape == (20, 4)
    np.testing.assert_allclose(np.asarray(probs).sum(axis=1), 1.0,
                               atol=1e-6)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_lower_precision_control_reads_wider_than_the_reference(seed):
    rng = np.random.default_rng(seed)
    params = fam.init_params(SMALL, seed)
    rows = []
    for n in (30, 17):
        p = rng.integers(1, 128, n).astype(np.int32)
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
    wrong = [(p, (t + 1) % 128) for p, t in rows]
    assert min(float(g.max()) for g in fam.reference_served_gaps(
        SMALL, seed, wrong, 40)) > 0.0


# ------------------------------------------------------------- the readers

def span(name, start, end, **attrs):
    return (name, start, end, 7, "loop:1", name + str(start), "", attrs)


def op(name, start_us, dur_us):
    return ["/device:TPU:0", "XLA Ops", name, 1000 * start_us,
            1000 * dur_us]


PAGED_CALL = ('%paged_attention_decode.9 = f32[16,16,128]{2,1,0} '
              'custom-call(...), custom_call_target="tpu_custom_call"')
APPEND_CALL = ('%paged_kv_append.960 = bf16[1284,256,128]{2,1,0} '
               'custom-call(...), custom_call_target="tpu_custom_call"')
FUSION = "%fusion.7 = f32[16,2048]{1,0} fusion(...)"


def facts(**over):
    base = {"family": fam, "sizes": FULL, "peaks": V5E, "kv_bytes": 2,
            "slice": (0.0, 2.0), "window_s": 2.0, "spans": [],
            "records": [], "slice_records": []}
    base.update(over)
    return base


def test_loop_roofline_counts_passes_of_weights_and_every_cache_layer():
    rec = {"ok": True, "prompt_len": 100, "new_tokens": 3, "t_send": 0.0,
           "t_reply": 0.9}
    late = dict(rec, t_send=1.9, t_reply=2.8)      # its steps: after t1
    spans = [
        span("engine/step", 0.1, 0.2, ut_steps=4, cache_layers=192,
             ahead=1),
        span("engine/step", 0.3, 0.4, exit_pass_mean=1.5),  # read only
        span("generator/prefill", 0.5, 0.6, rows=2, ut_steps=4,
             cache_layers=192),
        span("engine/step", 2.5, 2.6, ut_steps=4)]     # outside the slice
    events = [op(FUSION, 0, 60_000), op(PAGED_CALL, 50_000, 20_000),
              op(APPEND_CALL, 100_000, 5_000)]
    busy = 0.070 + 0.005
    weights = 2 * (4 * fam.stack_weight_bytes(FULL)
                   + fam.head_weight_bytes(FULL))
    cache = (101 + 102) * 1_572_864
    f = facts(spans=spans, slice_records=[rec, late], records=[rec])
    got = read("loop_hbm_roofline.ouro", f, events)
    assert got == pytest.approx(100 * (weights + cache) / 819e9 / busy)
    assert 0 < got < 100
    # a count that is missing reads nothing, never 0: a program whose
    # spans carry no ut_steps, a trace with no device event, a run with
    # no chip's peaks
    bare = [span("engine/step", 0.1, 0.2, ahead=1, grid_steps=9)]
    assert read("loop_hbm_roofline.ouro", facts(spans=bare), events) is None
    assert read("loop_hbm_roofline.ouro", f) is None
    assert read("loop_hbm_roofline.ouro", dict(f, peaks=None),
                events) is None
    share = read("loop_cache_device_share.ouro", facts(), events)
    assert share == pytest.approx(100 * 25_000 / 75_000)
    assert read("loop_cache_device_share.ouro", facts(),
                [op(FUSION, 0, 10)]) is None
    got = read("paged_attention_roofline.ouro", f, events)
    assert got == pytest.approx(100 * cache / 819e9 / 0.020)
    assert read("serve_mfu.ouro", f) == pytest.approx(
        100 * fam.serve_flops(FULL, 100, 3) / 2.0 / 197e12)


NEW_METRICS = sorted(n for n in SPECS if n.endswith(".ouro"))


def test_the_new_metrics_are_the_issues():
    assert NEW_METRICS == sorted(
        [f"{m}.ouro" for m in (
            "serve_mfu", "device_idle_share", "decode_step_ms",
            "decode_round_host_ms", "admit_share", "generator_recompiles",
            "kv_pool_used_share", "reply_ms_per_token_p95",
            "paged_attention_roofline", "loop_hbm_roofline",
            "loop_cache_device_share",
            # the review's four: layers the cell runs and had no metric on
            "decode_live_rows", "flash_attention_roofline",
            "device_idle_decode_host", "device_idle_admit_host")])
    # the cell that reports tokens a second alone moves only that
    assert all(SPECS[n]["moves"] == "serve_tokens_per_s"
               for n in NEW_METRICS)
    listed = {m["name"]: m for m in cell.benchmark_json()["per_layer"]}
    for name in NEW_METRICS:
        entry = listed[name]
        assert entry == {k: SPECS[name][k] for k in entry}, name
    records = [{"ok": True, "t_send": 0.0, "t_reply": 0.064 * k,
                "new_tokens": 64} for k in range(1, 102)]
    assert read("reply_ms_per_token_p95.ouro",
                {"records": records, "window_s": 45.0}) == \
        pytest.approx(96.0)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_metric_names_its_cell_and_is_silent_without_a_reading(name):
    cells = SPECS[name]["workloads"]
    assert cells == [OURO]
    # a program with no such span, counter or event: nothing, no raise
    assert read(name, {"family": fam, "sizes": FULL, "peaks": V5E,
                       "window_s": 1.0, "records": [], "spans": [],
                       "slice": (0.0, 1.0), "slice_records": []}) \
        in (None, 0)
    if "roofline" in name or "mfu" in name or "share" in name:
        assert read(name, {"family": fam, "sizes": FULL, "peaks": V5E,
                           "window_s": 1.0, "records": [], "spans": [],
                           "slice": (0.0, 1.0), "slice_records": []}) is None


LONG = "gpt2-medium.serve_long_decode"
LONG_METRICS = sorted(n for n in SPECS if n.endswith(".long_decode"))


def test_the_control_cell_is_the_issues():
    """ISSUE 33's second cell: data files only, the accepted
    configuration under a new traffic file."""
    bench = cell.benchmark_json()
    entry = next(w for w in bench["workloads"] if w["name"] == LONG)
    assert (entry["config"], entry["traffic"], entry["chips"]) == \
        ("gpt2-medium", "long_decode_closed8", 1)
    held_to = {m["name"] for m in bench["end_to_end"]
               if LONG in m.get("workloads", [LONG])}
    assert held_to == {"serve_tokens_per_s", "serve_ms_per_token_p95",
                       "setup_s"}
    traffic = cell.Cell(LONG).traffic
    assert {k: traffic[k] for k in (
        "callers", "prompt_min", "prompt_max", "new_min", "new_max",
        "n_requests", "shape_seed", "caller_start_gap_s", "warm_rows_max",
        "traced_seconds", "sample_replies")} == {
        "callers": 8, "prompt_min": 768, "prompt_max": 960, "new_min": 32,
        "new_max": 64, "n_requests": 4096, "shape_seed": 33,
        "caller_start_gap_s": 0.1, "warm_rows_max": 8,
        "traced_seconds": 10, "sample_replies": 16}
    # a slot holds prompt + reply; the limit lies between calibrate.py's
    # two readings (0.0173 and 0.269, PERF.md section 6)
    assert traffic["prompt_max"] + traffic["new_max"] <= 1024
    assert 0.0173 < traffic["limits"]["served_logit_gap"] < 0.269
    assert LONG_METRICS == sorted(f"{m}.long_decode" for m in (
        "serve_mfu", "device_idle_share", "decode_step_ms", "admit_share",
        "generator_recompiles", "kv_pool_used_share",
        "paged_attention_roofline"))


@pytest.mark.parametrize("name", LONG_METRICS)
def test_a_control_metric_is_its_twins_reader_over_the_new_cell(name):
    spec = SPECS[name]
    twin = SPECS[name.replace(".long_decode", ".serve")
                 if name != "serve_mfu.long_decode" else "serve_mfu"]
    assert spec["workloads"] == [LONG]
    same = {k: v for k, v in spec.items()
            if k not in ("name", "workloads", "what", "match", "moves")}
    assert same == {k: twin[k] for k in same}, name
    listed = {m["name"]: m for m in cell.benchmark_json()["per_layer"]}
    assert listed[name] == {k: spec[k] for k in listed[name]}
    # the cell reports the end-to-end metric its metric moves
    assert spec["moves"] in ("serve_tokens_per_s", "serve_ms_per_token_p95")

