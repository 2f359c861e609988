"""Fast, CPU-only tests of the benchmark's own code (``benchmark/``).

They check the contract's letter (names, units, files that resolve), the
yardstick's arithmetic (parameter and operation counts, the trace reducer on
an event list recorded on the chip), that the plain reference agrees with the
program at a small size, that the lower-precision control and the planted
faults come out as not correct, and that a CPU rehearsal of each cell prints
a last line of the contract's shape. No timing is asserted anywhere.
"""
import json
import os
import re
import subprocess
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.families import gpt as fam  # noqa: E402
from benchmark.harness import (cell, compare, peaks, readers,  # noqa: E402
                               trace_reduce)

BENCH = cell.benchmark_json()
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "train_slice_events.json")


def config_of(name):
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    with open(os.path.join(ROOT, entry["file"])) as fh:
        return json.load(fh)


# ------------------------------------------------------------ the contract

def test_names_units_and_lengths():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), entry["name"]))
    assert len(names) == len(set(names))
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["why"]) <= 200 and len(c["source"]) <= 200
        assert all(NAME.match(k) for k in c["reduced"])
    assert "setup_s" in [m["name"] for m in BENCH["end_to_end"]]
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_every_named_file_resolves():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for name in CELLS:
        c = cell.Cell(name)
        assert callable(c.driver.run) and callable(c.driver.calibrate)
        assert callable(c.family.reference_logits)
        assert c.per_layer, name
        assert "setup_s" in c.end_to_end and len(c.end_to_end) >= 2
    for c in BENCH["configs"]:
        conf = config_of(c["name"])
        assert c["file"].startswith(tuple(BENCH["paths"]))
        assert conf["source"] == c["source"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
        # no width is ever cut
        assert not any(k.endswith(("_dim", "_rank")) or k in (
            "n_embd", "n_inner", "n_head") for k in c["reduced"])
    # the per-layer metrics of BENCHMARK.json are the files, letter for letter
    files = {s["name"]: s for s in cell.layer_specs()}
    assert set(files) == {m["name"] for m in BENCH["per_layer"]}
    for m in BENCH["per_layer"]:
        spec = files[m["name"]]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert all(spec[k] == m[k] for k in m)
        assert callable(readers.resolve(spec["reader"]))
        # it moves an end-to-end metric that each of its cells reports
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", CELLS)
        if "roofline" in m["name"]:
            assert m["unit"] == "%" and spec["bound"] in ("compute",
                                                          "bandwidth")


def test_peaks_table_refuses_an_unknown_kind():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(peaks.UnknownDeviceKind):
        peaks.peaks_for("TPU v9 imaginary")


# --------------------------------------------------------------- the counts

@pytest.mark.parametrize("name,params,matmul", [
    # 50257*768 + 1024*768 + 12*(768*2304+2304 + 768*768+768 + 768*3072+3072
    #   + 3072*768+768 + 4*768) + 2*768
    ("gpt2", 124_439_808, 12 * (4 * 768 * 768 + 2 * 768 * 3072)
     + 50257 * 768),
    ("gpt2-medium", 354_823_168, 24 * (4 * 1024 * 1024 + 2 * 1024 * 4096)
     + 50257 * 1024),
])
def test_parameter_and_operation_counts(name, params, matmul):
    sz = fam.Sizes(config_of(name))
    assert fam.param_count(sz) == params
    assert fam.matmul_params(sz) == matmul
    # causal attention forward at [8, heads, 1024, 64]: two products of
    # 1024*1024/2 x 64 multiply-adds a head; the backward pass twice that
    fwd = 2 * 2 * 8 * sz.n_head * (1024 * 1024 // 2) * 64 * sz.n_layer
    assert fam.causal_attention_flops(sz, 8, 1024, backward=False) == fwd
    assert fam.causal_attention_flops(sz, 8, 1024, backward=True) == 3 * fwd
    assert fam.train_flops_per_token(sz, 1024) == pytest.approx(
        6 * matmul + 3 * fwd / 8 / 1024)
    assert fam.kv_bytes_per_position(sz, 2) == 2 * sz.n_layer * sz.n_embd * 2
    # one prompt token and two new ones: the second new token's query reads
    # the prompt and the first
    assert fam.serve_flops(sz, 1, 2) == pytest.approx(
        2 * matmul * 2 + 2 * 2 * sz.n_layer * sz.n_head * 0.5 * sz.d_head
        + 2 * 2 * sz.n_layer * sz.n_embd * 2)


def test_paged_bytes_come_from_request_records():
    # one reply: prompt 10, 4 new tokens, sent at 0 s, back at 4 s: three
    # decode steps at 1.5, 2.5, 3.5 s reading 11, 12 and 13 positions
    rec = [{"ok": True, "prompt_len": 10, "new_tokens": 4,
            "t_send": 0.0, "t_reply": 4.0}]
    assert readers.decode_positions_read(rec, 0.0, 4.0) == 36
    assert readers.decode_positions_read(rec, 2.0, 3.0) == 12
    assert readers.decode_positions_read(rec, 5.0, 6.0) == 0


# -------------------------------------------------------------- the reducer

def test_reducer_on_a_hand_made_list():
    dev, host = "/device:TPU:0", "/host:CPU"
    events = [
        [dev, "XLA Ops", "fusion.1", 100, 50],          # 100-150
        [dev, "XLA Ops", "flash_attention_fwd", 140, 60],   # 140-200 overlaps
        [dev, "XLA Ops", "fusion.1", 400, 100],         # 400-500
        [dev, "XLA Modules", "jit_step", 100, 400],     # not an operation
        [host, "python3", "bench/slice", 0, 1000],
        [host, "python3", "bench/feed", 200, 150],      # covers 200-350
        [host, "python3", "bench/dispatch", 350, 60],
        [host, "python3", "other", 0, 1000],
    ]
    assert trace_reduce.union_ns([(100, 150), (140, 200), (400, 500)]) == 200
    assert trace_reduce.busy_seconds(events) == pytest.approx(200e-9)
    assert trace_reduce.window_seconds(events) == pytest.approx(1000e-9)
    assert trace_reduce.time_by_name(events) == {
        "fusion.1": pytest.approx(150e-9),
        "flash_attention_fwd": pytest.approx(60e-9)}
    assert trace_reduce.seconds_matching(events, ["flash_attention"]) \
        == pytest.approx(60e-9)
    assert trace_reduce.seconds_matching(events, ["paged"]) is None
    # the one gap, 200-400, lies mostly under the feed span
    assert trace_reduce.idle_gaps(events) == [["feed", pytest.approx(200e-9)]]
    assert readers.device_idle_share({}, events, {}) == pytest.approx(80.0)
    assert readers.device_idle_share({}, [], {}) is None


def test_breakdown_sums_the_same_operation_of_every_layer():
    """Two layers' calls of one kernel differ in the numbers after their
    ``%names`` and in nothing else: the breakdown counts them as one kind,
    so that one kernel cannot fill all ten places."""
    dev = "/device:TPU:0"
    call = ('%pallas.{n} = f32[32,16,1,64]{{3,2,1,0:T(1,128)S(1)}} '
            'custom-call(s32[32,64]{{1,0:T(8,128)}} %copy-done.{m}), '
            'custom_call_target="tpu_custom_call", '
            'operand_layout_constraints={{s32[32,64]{{1,0}}}}')
    events = [[dev, "XLA Ops", call.format(n=34, m=12), 0, 40],
              [dev, "XLA Ops", call.format(n=35, m=13), 50, 40],
              [dev, "XLA Ops", "%copy.7 = bf16[8]{0} copy(%fusion.1)", 100, 30]]
    kind = ('%pallas = f32[32,16,1,64] custom-call(s32[32,64] %copy-done), '
            'custom_call_target="tpu_custom_call", operand_layout_constraints=')
    assert trace_reduce.top_device_ops(events) == [
        [kind, pytest.approx(80e-9)],
        ["%copy = bf16[8] copy(%fusion)", pytest.approx(30e-9)]]


def test_reducer_on_the_recorded_chip_slice():
    """Values worked out by hand (a sort and a running maximum, in a
    spreadsheet's manner) from the list recorded on the v5e by this PR's
    own traced train run."""
    with open(FIXTURE) as fh:
        fixture = json.load(fh)
    events, expect = fixture["events"], fixture["expect"]
    assert len(events) >= 200
    assert trace_reduce.busy_seconds(events) == pytest.approx(
        expect["busy_s"], rel=1e-9)
    assert trace_reduce.window_seconds(events) == pytest.approx(
        expect["window_s"], rel=1e-9)
    assert readers.device_idle_share({}, events, {}) == pytest.approx(
        expect["idle_share"], rel=1e-9)
    by_name = trace_reduce.time_by_name(events)
    for name, seconds in expect["time_by_name"].items():
        assert by_name[name] == pytest.approx(seconds, rel=1e-9)
    # the Pallas call's real name on the chip is what the metric's file
    # matches
    spec = next(s for s in cell.layer_specs()
                if s["name"] == "flash_attention_roofline.train")
    assert trace_reduce.seconds_matching(events, spec["match"]) \
        == pytest.approx(expect["flash_s"], rel=1e-9)


# ------------------------------------------------ reference against program

@pytest.fixture
def interpreted(monkeypatch):
    """What a TPU would resolve to, through the Pallas interpreter, and
    the flags as they were afterwards."""
    from paddle_tpu import flags
    from paddle_tpu.kernels import _dispatch
    monkeypatch.setattr(_dispatch, "auto_impl", lambda: "interpret")
    before = flags.get_flags(["FLAGS_kv_cache_dtype"])
    yield
    flags.set_flags(before)


def rehearsal_run(workload, seed=7, seconds=0.5, trace=False):
    c = cell.Cell(workload)
    return types.SimpleNamespace(
        cell=c, seed=seed, seconds=seconds, trace=trace, rehearsal=True,
        t0=0.0, root=ROOT, device={}, peaks=None, keep_xplane=None)


def test_reference_agrees_with_the_program_at_a_small_size(interpreted):
    import paddle_tpu as fluid
    from paddle_tpu.models import gpt
    sz = fam.Sizes(config_of("gpt2"), rehearsal=True)
    params = fam.init_params(sz, 2**31 + 5)      # a seed past 32 signed bits
    cfg = fam.program_config(sz)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        out = gpt.gpt_logits(cfg)
    scope = fluid.Scope()
    fam._place(scope, main, params)
    tokens = np.random.default_rng(0).integers(
        0, sz.vocab_size, (3, 24), dtype=np.int32)
    logits, = fluid.Executor().run(main, scope=scope, feed={
        "tokens": tokens,
        "pos_ids": np.broadcast_to(np.arange(24, dtype=np.int32), (3, 24)),
        "last_pos": np.full((3,), 23, np.int32)},
        fetch_list=[out["logits"]])
    ref = np.asarray(fam.reference_logits(sz, params, tokens))[:, -1]
    assert np.std(ref) > 1e-3
    np.testing.assert_allclose(logits, ref, rtol=0, atol=2e-5)
    # and the same seed gives the same weights
    again = fam.init_params(sz, 2**31 + 5)
    assert all(np.array_equal(params[k], again[k]) for k in params)
    other = fam.init_params(sz, 2**31 + 6)
    assert not np.array_equal(params["word_embedding"],
                              other["word_embedding"])


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_lower_precision_control_is_not_correct(seed):
    """The control of the training cell, at a size a test can hold: the
    reference with fp8 operands, put in the program's place, reads three
    times what the reference with bf16 operands (the configuration's own
    precision) reads, or more, and a limit set between fails it."""
    sz = fam.Sizes(config_of("gpt2"), rehearsal=True)
    recipe = config_of("gpt2")["train"]
    first = [np.random.default_rng(seed).integers(
        0, sz.vocab_size, (4, 65), dtype=np.int32) for _ in range(3)]
    ref = fam.reference_train(sz, recipe, seed, first)
    sound, _ = compare.train_gaps(
        fam.reference_train(sz, recipe, seed, first, mode="bf16"), ref)
    control, _ = compare.train_gaps(
        fam.reference_train(sz, recipe, seed, first, mode="fp8"), ref)
    assert control["grad_norm_gap"] >= 3 * sound["grad_norm_gap"]
    limit = 2 * sound["grad_norm_gap"]
    assert compare.verdict([compare.check("g", sound["grad_norm_gap"], limit)])
    assert not compare.verdict(
        [compare.check("g", control["grad_norm_gap"], limit)])


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_lower_precision_control_of_serving_is_not_correct(seed):
    """The control of the serving cell, at a size a test can hold: at each
    position of the same prompts and tokens, the token that the reference
    with fp8 operands puts first lies further below the reference's best
    than the one bf16 operands (the configuration's own products) put
    first, three times or more, and a limit set between fails it."""
    sz = fam.Sizes(config_of("gpt2-medium"), rehearsal=True)
    rng = np.random.default_rng(seed)
    rows = [(rng.integers(1, sz.vocab_size, 8, dtype=np.int32),
             rng.integers(1, sz.vocab_size, 48, dtype=np.int32))
            for _ in range(8)]

    def widest(mode):
        return max(float(g.max()) for g in fam.reference_served_gaps(
            sz, seed, rows, sz.n_positions - 1, mode=mode))

    sound, control = widest("bf16"), widest("fp8")
    assert control >= 3 * sound and control > 1e-3
    limit = (sound + control) / 2
    assert compare.verdict([compare.check("g", sound, limit)])
    assert not compare.verdict([compare.check("g", control, limit)])


# ----------------------------------------------- faults under the timed path

def unchanged_state(monkeypatch):
    """A step that returns its state unchanged."""
    build = fam.build_train

    def broken(*args, **kw):
        built = build(*args, **kw)
        exe, scope = built["exe"], built["scope"]
        run = exe.run

        def run_and_put_back(program, **kwargs):
            if program is not built["main"]:
                return run(program, **kwargs)
            # copies: the step donates its state
            kept = {n: np.array(scope.find_var(n))
                    for n in built["moment1"]}
            out = run(program, **kwargs)
            for n, v in kept.items():
                scope.set(n, v)
            return out

        monkeypatch.setattr(exe, "run", run_and_put_back)
        return built

    monkeypatch.setattr(fam, "build_train", broken)


def half_batch(monkeypatch):
    """Half of the batch left out, the mean taken over the rest."""
    feed = fam.train_feed

    def broken(tokens):
        out = feed(tokens)
        out["loss_mask"][tokens.shape[0] // 2:] = 0.0
        return out

    monkeypatch.setattr(fam, "train_feed", broken)


def altered_token(monkeypatch):
    """A token altered where it is produced."""
    from paddle_tpu.models.generation import GPTGenerator
    sample = GPTGenerator._run_sample

    def broken(self, logits, temperature, top_k, key):
        toks, key = sample(self, logits, temperature, top_k, key)
        return (np.asarray(toks) + 1) % self.cfg.vocab_size, key

    monkeypatch.setattr(GPTGenerator, "_run_sample", broken)


@pytest.mark.parametrize("workload,fault,failing", [
    ("gpt2.train_8x1024", None, None),
    ("gpt2.train_8x1024", unchanged_state, "change_norm_gap"),
    ("gpt2.train_8x1024", half_batch, "grad_norm_gap"),
    ("gpt2-medium.serve_chat_closed32", None, None),
    ("gpt2-medium.serve_chat_closed32", altered_token, "served_logit_gap"),
])
def test_a_broken_timed_path_is_not_correct(workload, fault, failing,
                                            interpreted, monkeypatch):
    """The rest of a run past the look for a chip, at the rehearsal's
    sizes: sound it is correct, and with the timed path broken underneath
    the number that is there to catch the fault goes over its limit."""
    if fault is not None:
        fault(monkeypatch)
    run = rehearsal_run(workload)
    outcome = run.cell.driver.run(run)
    checks = {c["name"]: c for c in outcome["checks"]}
    if fault is None:
        assert compare.verdict(outcome["checks"]), checks
        assert outcome["failed"] == 0 and outcome["attempted"] > 0
    else:
        assert not compare.verdict(outcome["checks"]), checks
        assert checks[failing]["value"] > checks[failing]["limit"], checks
    if fault is unchanged_state:
        # by the training bullet's measure a leaf that has not moved reads 1
        assert checks["change_norm_gap"]["value"] == pytest.approx(1.0)


# ------------------------------------------------- the command, from outside

def bench_command(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), *args],
        capture_output=True, text=True, timeout=600, cwd=ROOT, env=env)


def test_without_a_tpu_it_exits_nonzero_and_prints_no_result():
    done = bench_command("--workload", CELLS[0], "--seed", "1",
                         "--seconds", "1", "--trace", "0")
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "cpu" in done.stderr and "TPU" in done.stderr


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_cpu_rehearsal_prints_the_contracts_line(workload, trace):
    done = bench_command("--workload", workload, "--seed", str(2**31 + 11),
                         "--seconds", "1", "--trace", str(trace),
                         "--rehearse-cpu")
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    keys = ["correct", "attempted", "failed", "metrics", "device"]
    keys += ["breakdown"] if trace else []
    assert list(line) == keys + ["rehearsal_not_a_chip_run", "checks"]
    assert line["rehearsal_not_a_chip_run"] is True
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    c = cell.Cell(workload)
    if trace:
        assert set(line["metrics"]) <= {s["name"] for s in c.per_layer}
        # no share of a chip's peak is ever made from a CPU run
        assert not any("mfu" in m or "roofline" in m or "idle" in m
                       for m in line["metrics"])
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        assert {"busy_s", "window_s"} <= set(line["device"])
    else:
        assert list(line["metrics"]) == c.end_to_end
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
    # each number compared stands beside its limit, last on stderr too
    last = done.stderr.strip().splitlines()[-len(line["checks"]):]
    assert all(l.startswith("check ") and "limit" in l for l in last)
