"""CPU tests of what the Mellum-2 configuration and the two cells added
with it bring to the benchmark: the configuration file against the
catalog's published sizes, the family's closed-form counts, the reference
(routing, window, YaRN) on hand-checkable cases, the lower-precision
control, and every new reader on hand-made facts, spans and events. The
two cells' ``--rehearse-cpu`` runs are cases of ``test_benchmark.py``'s
own rehearsal test, which walks ``BENCHMARK.json``."""
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

from benchmark.families import mellum as fam  # noqa: E402
from benchmark.harness import cell, peaks, readers  # noqa: E402

MELLUM = "mellum2-12b-a2.5b.serve_code_closed32"
ONE = "gpt2-medium.serve_one_token"
SPECS = {s["name"]: s for s in cell.layer_specs()}
with open(os.path.join(ROOT, "benchmark", "configs",
                       "mellum2-12b-a2.5b.json")) as fh:
    CONFIG = json.load(fh)
FULL, SMALL = fam.Sizes(CONFIG), fam.Sizes(CONFIG, rehearsal=True)


def read(name, facts, events=()):
    spec = SPECS[name]
    return readers.resolve(spec["reader"])(facts, list(events), spec)


def test_configuration_keeps_every_published_width():
    published = {"hidden_size": 2304, "num_attention_heads": 32,
                 "num_key_value_heads": 4, "head_dim": 128,
                 "num_experts": 64, "num_experts_per_tok": 8,
                 "moe_intermediate_size": 896, "sliding_window": 1024,
                 "vocab_size": 98304, "intermediate_size": 7168,
                 "max_position_embeddings": 131072, "rms_norm_eps": 1e-06}
    for key, value in published.items():
        assert CONFIG[key] == value, key
    assert CONFIG["reduced"] == ["num_hidden_layers", "layer_types"]
    assert CONFIG["num_hidden_layers"] == 8
    assert CONFIG["published"]["num_hidden_layers"] == 28
    assert CONFIG["layer_types"] == (
        ["sliding_attention"] * 3 + ["full_attention"]) * 2
    yarn = CONFIG["rope_parameters"]["full_attention"]
    assert (yarn["rope_type"], yarn["factor"], yarn["beta_fast"]) == \
        ("yarn", 16, 32)
    for key in ("source", "assumed", "precision", "deployment", "serve"):
        assert CONFIG[key]
    small = CONFIG["rehearsal"]
    assert small["sliding_window"] < 12 and small["num_experts"] == 8 \
        and small["num_key_value_heads"] < small["num_attention_heads"] \
        and set(small["layer_types"]) == set(CONFIG["layer_types"])


def test_traffic_files_are_the_issues():
    code, one = cell.Cell(MELLUM).traffic, cell.Cell(ONE).traffic
    assert (code["callers"], code["prompt_min"], code["prompt_max"],
            code["new_min"], code["new_max"], code["n_requests"],
            code["traced_seconds"], code["sample_replies"]) == \
        (32, 1024, 6144, 64, 256, 1024, 10, 8)
    assert (one["callers"], one["prompt_min"], one["prompt_max"],
            one["new_min"], one["new_max"]) == (8, 64, 1023, 1, 1)
    assert code["driver"] == one["driver"] == "closed_loop_serve"
    assert cell.Cell(ONE).config["name"] == "gpt2-medium"


def test_parameter_and_operation_counts():
    # ISSUE 28's count: 417.7 M a layer, 453 M embedding and head
    per_layer = (2304 * 4096 * 2 + 2304 * 512 * 2 + 2304 * 64
                 + 64 * 3 * 2304 * 896 + 2 * 2304)
    assert fam.param_count(FULL) == 8 * per_layer + 2 * 98304 * 2304 + 2304
    active = 8 * (2304 * 4096 * 2 + 2304 * 512 * 2 + 2304 * 64
                  + 8 * 3 * 2304 * 896) + 98304 * 2304
    assert fam.active_matmul_params(FULL) == active
    # a window layer scores 1024 keys a query once past the window
    assert fam._pairs(4096, 1024) == 1024 * 1025 / 2 + 3072 * 1024
    assert fam._pairs(512, 1024) == 512 * 513 / 2
    assert fam.decode_positions(FULL, 5000) == 2 * 5000 + 6 * 1024
    assert fam.decode_positions(FULL, 300) == 8 * 300
    assert fam.kv_bytes_per_layer_position(FULL, 2) == 2048
    flops = fam.serve_flops(FULL, 2000, 1)
    assert flops == 2 * active * 2000 + fam.causal_attention_flops(
        FULL, 1, 2000)
    f, b = fam.expert_layer_work(FULL, 256, 64)
    assert f == 256 * 6 * 2304 * 896
    assert b == 64 * 3 * 2304 * 896 * 2 + 256 * 2304 * 6


def test_reference_router_takes_k_of_all_and_renormalises():
    import jax
    params = fam.init_params(SMALL, 5)
    x = jax.random.normal(jax.random.PRNGKey(0), (6, SMALL.hidden_size))
    out, top_i, top_w = fam._experts(
        SMALL, x, params["layer_0_router.w_0"],
        params["layer_0_experts_gate.w_0"],
        params["layer_0_experts_up.w_0"],
        params["layer_0_experts_down.w_0"], fam._matmul("highest"))
    probs = np.asarray(jax.nn.softmax(
        np.asarray(x, np.float64) @ np.asarray(
            params["layer_0_router.w_0"], np.float64)))
    order = np.argsort(-probs, axis=1)[:, :2]
    assert np.array_equal(np.sort(np.asarray(top_i)), np.sort(order))
    np.testing.assert_allclose(np.asarray(top_w).sum(1), 1.0, atol=1e-6)
    want = np.zeros_like(np.asarray(out), np.float64)
    for n in range(6):
        w = probs[n, order[n]] / probs[n, order[n]].sum()
        for j, e in enumerate(order[n]):
            g, u, d = (np.asarray(params[f"layer_0_experts_{k}.w_0"][e]
                                  .astype(jnp.float32), np.float64)
                       for k in ("gate", "up", "down"))
            a = np.asarray(x[n], np.float64) @ g
            want[n] += w[j] * ((a / (1 + np.exp(-a)))
                               * (np.asarray(x[n], np.float64) @ u)) @ d
    np.testing.assert_allclose(np.asarray(out), want, rtol=0, atol=1e-6)


def test_reference_window_forgets_what_fell_behind_it():
    """Changing a token more than a window behind changes a window-only
    model's last logits not at all, and a model with a full layer's."""
    params = fam.init_params(SMALL, 5)
    toks = np.random.default_rng(1).integers(1, 128, (1, 32)).astype(
        np.int32)
    other = toks.copy()
    other[0, 3] = (other[0, 3] % 127) + 1
    full = fam.reference_logits(SMALL, params, jnp.asarray(
        np.concatenate([toks, other])))
    assert float(jnp.abs(full[0, -1] - full[1, -1]).max()) > 1e-6
    config = dict(CONFIG)
    config["rehearsal"] = dict(CONFIG["rehearsal"], layer_types=[
        "sliding_attention", "sliding_attention"])
    windowed = fam.Sizes(config, rehearsal=True)
    only = fam.reference_logits(windowed, params, jnp.asarray(
        np.concatenate([toks, other])))
    # two window layers reach 2 x 7 positions back: position 31 sees
    # nothing of position 3, position 10 does
    assert float(jnp.abs(only[0, -1] - only[1, -1]).max()) == 0.0
    assert float(jnp.abs(only[0, 10] - only[1, 10]).max()) > 1e-6


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_lower_precision_control_reads_wider_than_the_reference(seed):
    rng = np.random.default_rng(seed)
    rows = [(rng.integers(1, 128, n).astype(np.int32), None)
            for n in (30, 17)]
    params = fam.init_params(SMALL, seed)
    rows = [(p, np.asarray(jnp.argmax(fam.reference_logits(
        SMALL, params, jnp.asarray(np.concatenate(
            [p, np.zeros(8, np.int32)])[None]))[0, p.size - 1:p.size + 3],
        axis=-1), np.int32)[:1]) for p, _ in rows]
    own = fam.reference_served_gaps(SMALL, seed, rows, 40)
    assert max(float(g.max()) for g in own) == 0.0   # its own first choice
    fp8 = fam.reference_served_gaps(SMALL, seed, rows, 40, mode="fp8")
    assert all(g.shape == (1,) for g in fp8)
    assert all(float(g.min()) >= 0.0 for g in fp8)


# ------------------------------------------------------------- the readers

def span(name, start, end, **attrs):
    return (name, start, end, 7, "loop:1", name + str(start), "", attrs)


def op(name, start_us, dur_us):
    return ["/device:TPU:0", "XLA Ops", name, 1000 * start_us,
            1000 * dur_us]


MOE_CALL = ('%moe_experts_swiglu.3 = f32[1216,2304]{1,0} custom-call(...), '
            'custom_call_target="tpu_custom_call"')
PAGED_CALL = ('%paged_attention_decode.9 = f32[32,32,128]{2,1,0} '
              'custom-call(...), custom_call_target="tpu_custom_call"')
FLASH_CALL = ('%flash_attention_fwd.2 = (bf16[1,32,4096,128]{3,2,1,0}) '
              'custom-call(...), custom_call_target="tpu_custom_call"')
V5E = peaks.peaks_for("TPU v5 lite")


def facts(**over):
    base = {"family": fam, "sizes": FULL, "peaks": V5E, "kv_bytes": 2,
            "slice": (0.0, 2.0), "window_s": 2.0, "spans": [],
            "records": [], "slice_records": []}
    base.update(over)
    return base


def test_expert_roofline_takes_the_larger_bound_of_each_call():
    spans = [
        # a decode step: 8 layers x 32 rows x 8, every expert hit
        span("engine/step", 0.1, 0.2, moe_tokens=2048, moe_experts_hit=512,
             moe_load_max=80),
        # a prefill of 4096 tokens
        span("generator/prefill", 0.3, 0.5, moe_tokens=8 * 4096 * 8,
             moe_experts_hit=512, moe_load_max=5000),
        span("engine/step", 2.5, 2.6, moe_tokens=2048, moe_experts_hit=512,
             moe_load_max=80)]                        # outside the slice
    events = [op(MOE_CALL, 100, 16000), op(MOE_CALL, 300_000, 24000),
              op("%fusion.1 = f32[8]{0} fusion(...)", 500_000, 999)]
    step_f, step_b = fam.expert_layer_work(FULL, 2048, 512)
    fill_f, fill_b = fam.expert_layer_work(FULL, 8 * 4096 * 8, 512)
    assert step_b / 819e9 > step_f / 197e12          # decode: bandwidth
    assert fill_f / 197e12 > fill_b / 819e9          # prefill: compute
    want = 100 * (step_b / 819e9 + fill_f / 197e12) / 0.040
    got = read("moe_experts_roofline.mellum", facts(spans=spans), events)
    assert got == pytest.approx(want) and 0 < got < 100
    assert read("moe_experts_roofline.mellum", facts(spans=spans)) is None
    assert read("moe_experts_roofline.mellum", facts(), events) is None
    assert read("moe_experts_roofline.mellum",
                facts(spans=spans, peaks=None), events) is None
    share = read("moe_device_share.mellum", facts(), events)
    assert share == pytest.approx(100 * 40000 / 40999)


def test_load_reader_means_over_the_slices_steps():
    spans = [span("engine/step", 0.1, 0.2, moe_tokens=2048,
                  moe_experts_hit=500, moe_load_max=64),
             span("engine/step", 0.3, 0.4, moe_tokens=1024,
                  moe_experts_hit=400, moe_load_max=48),
             span("generator/prefill", 0.5, 0.6, moe_tokens=9999,
                  moe_experts_hit=512, moe_load_max=9999),
             span("engine/step", 0.7, 0.8, grid_steps=1)]   # another model
    got = read("moe_load_max_over_mean.mellum", facts(spans=spans))
    assert got == pytest.approx((64 * 64 / 2048 + 48 * 64 / 1024) / 2)
    assert read("moe_load_max_over_mean.mellum", facts()) is None
    rounds = [span("serving/round", 0.1, 0.3, step=1,
                   blocks_in_use_full=400, blocks_in_use_window=130),
              span("serving/round", 0.4, 0.5, step=2, blocks_in_use=7)]
    assert read("kv_window_blocks_share.mellum",
                facts(spans=rounds)) == pytest.approx(32.5)


def test_windowed_paged_bytes_and_prefill_flops_come_from_the_records():
    rec = {"ok": True, "prompt_len": 3000, "new_tokens": 3, "t_send": 0.0,
           "t_reply": 0.9}
    late = dict(rec, t_send=1.9, t_reply=2.8)      # its steps: after t1
    f = facts(slice_records=[rec, late], records=[rec])
    positions = fam.decode_positions(FULL, 3001) + fam.decode_positions(
        FULL, 3002)
    events = [op(PAGED_CALL, 10, 500), op(FLASH_CALL, 900, 4000)]
    got = read("paged_attention_roofline.mellum", f, events)
    assert got == pytest.approx(100 * positions * 2048 / 819e9 / 500e-6)
    flops = 2 * fam.causal_attention_flops(FULL, 1, 3000)   # both were sent
    got = read("flash_attention_roofline.prefill", f, events)
    assert got == pytest.approx(100 * flops / 197e12 / 4000e-6)
    assert read("flash_attention_roofline.prefill", f, events[:1]) is None
    assert read("serve_mfu.mellum", f) == pytest.approx(
        100 * fam.serve_flops(FULL, 3000, 3) / 2.0 / 197e12)
    # the control cell reads the same reader with GPT-2's own counts
    from benchmark.families import gpt
    gsz = gpt.Sizes(cell.Cell(ONE).config)
    g = facts(family=gpt, sizes=gsz, slice_records=[dict(rec, prompt_len=500)])
    got = read("flash_attention_roofline.prefill", g, events)
    assert got == pytest.approx(100 * gpt.causal_attention_flops(
        gsz, 1, 500, False) / 197e12 / 4000e-6)


NEW_METRICS = sorted(n for n in SPECS if n.endswith(
    (".mellum", ".one_token", ".prefill")))


def test_the_new_metrics_are_the_issues_and_the_reviews():
    assert len(NEW_METRICS) == 26


def test_the_mellum_cell_reports_tokens_a_second_and_its_tail_per_layer():
    """Every slot of the closed loop is taken all the time, so the tail of
    some 215 replies is the seed's draw (PERF.md 7.7): the cell is held to
    ``serve_tokens_per_s``, every metric it lists moves that, and the
    95th percentile is a per-layer reading of the same records."""
    assert cell.Cell(MELLUM).end_to_end == ["serve_tokens_per_s", "setup_s"]
    assert set(cell.Cell(ONE).end_to_end) == {
        "serve_tokens_per_s", "serve_ms_per_token_p95", "setup_s"}
    assert all(s["moves"] == "serve_tokens_per_s"
               for s in SPECS.values() if MELLUM in s["workloads"])
    records = [{"ok": True, "t_send": 0.0, "t_reply": 0.064 * k,
                "new_tokens": 64} for k in range(1, 102)]
    facts = {"records": records, "window_s": 45.0}
    assert read("reply_ms_per_token_p95.mellum", facts) == \
        pytest.approx(96.0)
    records[0]["ok"] = False        # counts as the whole window a token
    assert read("reply_ms_per_token_p95.mellum", facts) == \
        pytest.approx(97.0)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_metric_names_its_cells_and_is_silent_without_a_reading(name):
    cells = SPECS[name]["workloads"]
    assert set(cells) <= {MELLUM, ONE} and cells
    # a program with no such span, counter or event: nothing, no raise
    assert read(name, {"family": fam, "sizes": FULL, "peaks": V5E,
                       "window_s": 1.0, "records": [], "spans": [],
                       "slice": (0.0, 1.0), "slice_records": []}) \
        in (None, 0)
