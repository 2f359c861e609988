"""tools/-class CI gates (reference tools/print_signatures.py +
diff_api.py API freeze, check_op_desc.py op-schema gate,
timeline.py Chrome-trace conversion): the committed baselines must
match the live package, and each gate must catch regressions."""
import json
import os
import subprocess
import sys
import tempfile

import paddle_tpu as fluid
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO, "tools")
sys.path.insert(0, TOOLS)


def test_api_freeze_baseline_current():
    """print_signatures vs the committed baseline through diff_api:
    no deletions/changes (additions allowed)."""
    out = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "print_signatures.py"),
         "paddle_tpu"],
        capture_output=True, text=True, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    with tempfile.NamedTemporaryFile("w", suffix=".txt",
                                     delete=False) as f:
        f.write(out.stdout)
        newpath = f.name
    gate = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "diff_api.py"),
         os.path.join(TOOLS, "api_signatures.txt"), newpath],
        capture_output=True, text=True)
    assert gate.returncode == 0, gate.stdout[-3000:]


def test_diff_api_catches_deletion_and_change():
    import diff_api
    origin = ["a.f (x) doc:1", "a.g (y) doc:2"]
    assert diff_api.diff(origin, list(origin)) == []
    assert diff_api.diff(origin, ["a.f (x) doc:1"])          # deletion
    assert diff_api.diff(origin, ["a.f (x, z) doc:1",
                                  "a.g (y) doc:2"])          # change
    # pure addition passes
    assert diff_api.diff(origin, origin + ["a.h (q) doc:3"]) == []


def test_op_schema_gate():
    import check_op_desc
    with open(os.path.join(TOOLS, "op_schema_baseline.json")) as f:
        baseline = json.load(f)
    now = check_op_desc.current_schema()
    errors, _added = check_op_desc.check(baseline, now)
    assert errors == [], errors
    # the gate catches a deleted op and a lost grad
    poisoned = dict(now)
    poisoned["definitely_gone_op"] = {"grad": True}
    errors, _ = check_op_desc.check(poisoned, now)
    assert any("deleted" in e for e in errors)
    lost = {k: dict(v) for k, v in now.items()}
    some = next(k for k, v in now.items() if v["grad"])
    lost[some]["grad"] = True
    now2 = {k: dict(v) for k, v in now.items()}
    now2[some]["grad"] = False
    errors, _ = check_op_desc.check(lost, now2)
    assert any("gradient" in e for e in errors)


def test_op_schema_gate_cli():
    """The check_op_desc.py CLI itself gates in tier-1 (it previously
    only ran by hand): exit 0 against the committed baseline, exit 1
    against a poisoned one."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    ok = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "check_op_desc.py"),
         os.path.join(TOOLS, "op_schema_baseline.json")],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=300)
    assert ok.returncode == 0, ok.stdout + ok.stderr[-2000:]
    assert "compatible" in ok.stdout
    with open(os.path.join(TOOLS, "op_schema_baseline.json")) as f:
        baseline = json.load(f)
    baseline["definitely_gone_op"] = {"grad": True}
    with tempfile.NamedTemporaryFile("w", suffix=".json",
                                     delete=False) as f:
        json.dump(baseline, f)
        poisoned = f.name
    bad = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "check_op_desc.py"),
         poisoned],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=300)
    assert bad.returncode == 1, bad.stdout + bad.stderr[-2000:]
    assert "deleted" in bad.stdout


def test_op_schema_gate_catches_rng_contract_change():
    """Flipping an op's needs_rng breaks every saved program's
    __rng_seed__ layout — the schema gate must flag it."""
    import check_op_desc
    now = check_op_desc.current_schema()
    rng_op = next(k for k, v in now.items() if v["needs_rng"])
    flipped = {k: dict(v) for k, v in now.items()}
    flipped[rng_op]["needs_rng"] = False
    errors, _ = check_op_desc.check(now, flipped)
    assert any("RNG contract" in e for e in errors), errors


def test_lint_flags_gate():
    """tools/lint_flags.py: the live tree is clean, and the checker
    catches both rot modes (undeclared reference, unreferenced
    declaration)."""
    import lint_flags
    from paddle_tpu import flags as F
    declared = set(F._DEFS)
    compat = set(F._COMPAT_ONLY)
    refs = lint_flags.scan_references()
    assert lint_flags.check(declared, compat, refs) == []
    # the aliased hot-path getter idiom _flag("name") must count as a
    # reference (a \b-anchored regex silently missed it)
    assert "verify_passes" in refs and "program_passes" in refs
    # a reference to an undeclared flag is flagged
    poisoned = dict(refs)
    poisoned["totally_new_flag"] = ["paddle_tpu/somewhere.py"]
    errors = lint_flags.check(declared, compat, poisoned)
    assert any("totally_new_flag" in e and "not declared" in e
               for e in errors), errors
    # a declared-but-never-referenced flag is flagged
    errors = lint_flags.check(declared | {"dead_flag"}, compat, refs)
    assert any("dead_flag" in e and "nothing" in e
               for e in errors), errors
    # compat-listed flags that ARE referenced get called out
    some_ref = next(n for n in refs if n in declared)
    errors = lint_flags.check(declared, compat | {some_ref}, refs)
    assert any(some_ref in e and "compat" in e for e in errors), errors


def test_lint_metrics_gate():
    """tools/lint_metrics.py: every registered metric name is
    snake_case, unique, unit-suffixed and documented in the README
    catalog — and the CLI itself gates in tier-1."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    ok = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "lint_metrics.py")],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=300)
    assert ok.returncode == 0, ok.stdout + ok.stderr[-2000:]
    assert "metrics clean" in ok.stdout


def _save_tools_mlp(tmp):
    import numpy as np  # noqa: F401 — program build only
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [-1, 16], "float32")
        h = fluid.layers.fc(x, 32, act="relu")
        out = fluid.layers.fc(h, 8, act="softmax")
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        fluid.io.save_inference_model(tmp, ["x"], [out], exe,
                                      main_program=main)
    return tmp


@pytest.mark.slow
def test_profile_program_gate(tmp_path):
    """tools/profile_program.py gates in tier-1: exit 0 on a clean
    program (per-op + memory report), exit 1 with a NAMED finding when
    --assert-mfu-floor is violated."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    path = _save_tools_mlp(str(tmp_path / "mlp"))
    ok = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "profile_program.py"),
         path, "--ops", "--memory", "--json", "--batch", "4"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=300)
    assert ok.returncode == 0, ok.stdout + ok.stderr[-2000:]
    doc = json.loads(ok.stdout)
    assert doc["ops"] and doc["memory"]["peak_bytes"] > 0
    assert doc["totals"]["flops"] > 0
    # a generous floor passes...
    ok2 = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "profile_program.py"),
         path, "--assert-mfu-floor", "1e-9", "--batch", "4"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=300)
    assert ok2.returncode == 0, ok2.stdout + ok2.stderr[-2000:]
    assert "OK: est MFU" in ok2.stdout
    # ...a bandwidth-starved chip profile violates the floor, exit 1,
    # and the finding NAMES the top cost op
    bad = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "profile_program.py"),
         path, "--assert-mfu-floor", "0.5", "--batch", "4",
         "--peak-tflops", "1000", "--peak-hbm-gbs", "0.001"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=300)
    assert bad.returncode == 1, bad.stdout + bad.stderr[-2000:]
    assert "MFU-FLOOR VIOLATION" in bad.stderr
    assert "top cost op" in bad.stderr


def _save_tools_mlp_sharded(tmp):
    """The _save_tools_mlp program with every 2-D param tp-annotated —
    the audits-clean input for the shard_report gate (dist_attr
    survives save_inference_model serialization)."""
    from paddle_tpu.parallel.mesh import set_param_dist_attr
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [-1, 16], "float32")
        h = fluid.layers.fc(x, 32, act="relu")
        out = fluid.layers.fc(h, 8, act="softmax")
        gb = main.global_block()
        for n, v in gb.vars.items():
            if getattr(v, "persistable", False) and len(v.shape) == 2:
                set_param_dist_attr(main, n, (None, "tp"))
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        fluid.io.save_inference_model(tmp, ["x"], [out], exe,
                                      main_program=main)
    return tmp


@pytest.mark.slow
def test_shard_report_gate(tmp_path):
    """tools/shard_report.py gates in tier-1: exit 0 (audit clean) on a
    tp-sharded program, exit 1 NAMING the replicated param on the same
    program without annotations — the CI gate every mesh PR's sharded
    program runs through."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    good = _save_tools_mlp_sharded(str(tmp_path / "good"))
    bad = _save_tools_mlp(str(tmp_path / "bad"))
    # 0.001 MiB: the 128-byte biases (legitimately replicated) pass,
    # the 2 KiB fc_0 weight matrix does not
    mesh = ["--mesh", "dp=2,tp=2", "--threshold-mb", "0.001"]
    ok = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "shard_report.py"), good,
         "--audit", "--ledger", "--assert-no-replicated-params",
         *mesh],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=300)
    assert ok.returncode == 0, ok.stdout + ok.stderr[-2000:]
    assert "OK: no replicated-large-param findings" in ok.stdout
    # the tp psum shows up in the ledger table
    assert "all-reduce" in ok.stdout and "comm-bound fraction" \
        in ok.stdout, ok.stdout
    r = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "shard_report.py"), bad,
         "--assert-no-replicated-params", "--json", *mesh],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=300)
    assert r.returncode == 1, r.stdout + r.stderr[-2000:]
    assert "REPLICATED-PARAM VIOLATION" in r.stderr
    doc = json.loads(r.stdout)
    worst = doc["finding"]
    # exit 1 NAMES the worst (largest) replicated param
    assert "fc_" in worst and ".w_" in worst, worst
    assert doc["audit"]["counts"]["replicated-large-param"] >= 1


def test_bench_compare_gate(tmp_path):
    """tools/bench_compare.py: the bench trajectory is a checkable
    artifact — exit 0 within tolerance, exit 1 naming the regressed
    key; lower-is-better keys invert; the BENCH_rNN wrapper parses."""
    import bench_compare
    old = {"metric": "m", "value": 100.0,
           "configs": {"widedeep": {"value": 1000.0},
                       "chaos": {"value": 10.0}}}
    new_ok = {"metric": "m", "value": 95.0,
              "configs": {"widedeep": {"value": 980.0},
                          "chaos": {"value": 10.5}}}
    new_bad = {"metric": "m", "value": 50.0,
               "configs": {"widedeep": {"value": 500.0},
                           "chaos": {"value": 30.0}}}
    p_old = str(tmp_path / "old.json")
    p_ok = str(tmp_path / "ok.json")
    p_bad = str(tmp_path / "bad.json")
    with open(p_old, "w") as f:
        json.dump({"tail": json.dumps(old)}, f)    # BENCH_rNN wrapper
    with open(p_ok, "w") as f:
        f.write(json.dumps({"noise": 1}) + "\n" + json.dumps(new_ok))
    with open(p_bad, "w") as f:
        json.dump(new_bad, f)
    keys = ["--key", "value", "--key", "configs.widedeep.value",
            "--key=-configs.chaos.value"]   # leading '-' needs '='
    assert bench_compare.main(
        [p_old, p_ok, *keys, "--max-regress-pct", "10"]) == 0
    assert bench_compare.main(
        [p_old, p_bad, *keys, "--max-regress-pct", "10"]) == 1
    regs, _notes = bench_compare.compare(
        old, new_bad, ["value", "configs.widedeep.value",
                       "-configs.chaos.value"], 10.0)
    assert len(regs) == 3
    assert any("configs.widedeep.value" in r for r in regs)
    # missing keys only fail under --strict
    assert bench_compare.main(
        [p_old, p_ok, "--key", "configs.nope.value"]) == 0
    assert bench_compare.main(
        [p_old, p_ok, "--key", "configs.nope.value", "--strict"]) == 1


def test_train_report_gate(tmp_path):
    """tools/train_report.py gates in tier-1: exit 0 rendering a
    goodput dump, exit 1 with a NAMED worst category when
    --assert-goodput-floor is violated, exit 2 on a dump with no
    ledger samples."""
    prom = "\n".join([
        'train_time_seconds_total{category="compute"} 3.0',
        'train_time_seconds_total{category="data_stall"} 6.0',
        'train_time_seconds_total{category="checkpoint"} 1.0',
        'train_goodput_ratio 0.3',
    ])
    f = str(tmp_path / "train.prom")
    with open(f, "w") as fh:
        fh.write(prom)
    flight = str(tmp_path / "flight.json")
    with open(flight, "w") as fh:
        json.dump({"events": [
            {"kind": "data_stall", "queue": "buffered",
             "wait_ms": 812.0, "window_s": 1.0, "fraction": 0.81},
            {"kind": "checkpoint", "no": 1}]}, fh)
    ok = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "train_report.py"),
         "--from", f, "--flight", flight,
         "--assert-goodput-floor", "0.25"],
        capture_output=True, text=True, timeout=120)
    assert ok.returncode == 0, ok.stdout + ok.stderr[-2000:]
    assert "data_stall" in ok.stdout and "812.0ms" in ok.stdout
    assert "OK: goodput ratio" in ok.stdout
    bad = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "train_report.py"),
         "--from", f, "--assert-goodput-floor", "0.8"],
        capture_output=True, text=True, timeout=120)
    assert bad.returncode == 1, bad.stdout + bad.stderr[-2000:]
    assert "GOODPUT-FLOOR VIOLATION" in bad.stderr
    assert "data_stall" in bad.stderr     # names the worst category
    empty = str(tmp_path / "empty.prom")
    with open(empty, "w") as fh:
        fh.write("some_other_metric 1\n")
    none = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "train_report.py"),
         "--from", empty],
        capture_output=True, text=True, timeout=120)
    assert none.returncode == 2, none.stdout + none.stderr[-2000:]


def test_fleet_report_gate(tmp_path):
    """tools/fleet_report.py gates in tier-1: exit 0 rendering the
    autoscaler trail + per-class ledger from a prom dump, exit 1 with
    the interactive p99 NAMED when --assert-interactive-p99-ms is
    violated, exit 2 on a dump with no interactive latency samples."""
    prom = "\n".join([
        'fleet_replicas_count{state="serving"} 3',
        'fleet_replicas_count{state="draining"} 1',
        'fleet_scale_events_total{direction="up"} 2',
        'fleet_scale_events_total{direction="down"} 1',
        'serving_class_completed_total{class="interactive"} 90',
        'serving_class_completed_total{class="batch"} 40',
        'serving_admission_shed_total{class="best_effort"} 25',
        'serving_admission_shed_total{class="batch"} 10',
        'serving_retry_budget_exhausted_total{what="router-failover"} 7',
        'serving_expired_in_queue_total 4',
        # interactive latency histogram: 80 obs <= 100ms, 10 in
        # (100, 250] -> p99 lands inside the 250ms bucket
        'serving_class_latency_ms_bucket{class="interactive",'
        'le="100.0"} 80',
        'serving_class_latency_ms_bucket{class="interactive",'
        'le="250.0"} 90',
        'serving_class_latency_ms_bucket{class="interactive",'
        'le="+Inf"} 90',
    ])
    f = str(tmp_path / "fleet.prom")
    with open(f, "w") as fh:
        fh.write(prom)
    ok = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "fleet_report.py"),
         "--from", f, "--assert-interactive-p99-ms", "300"],
        capture_output=True, text=True, timeout=120)
    assert ok.returncode == 0, ok.stdout + ok.stderr[-2000:]
    assert "up=2" in ok.stdout and "down=1" in ok.stdout
    assert "interactive" in ok.stdout and "best_effort" in ok.stdout
    assert "OK: interactive p99" in ok.stdout
    bad = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "fleet_report.py"),
         "--from", f, "--assert-interactive-p99-ms", "50"],
        capture_output=True, text=True, timeout=120)
    assert bad.returncode == 1, bad.stdout + bad.stderr[-2000:]
    assert "INTERACTIVE-P99 VIOLATION" in bad.stderr
    # goodput arithmetic: batch completed 40 / offered 50
    import fleet_report
    with open(f) as fh:
        doc = fleet_report.summarize(
            fleet_report.parse_exposition(fh.read()))
    assert doc["classes"]["batch"]["goodput"] == 0.8
    assert doc["classes"]["best_effort"]["completed"] == 0
    assert doc["retry_budget_exhausted"] == 7
    empty = str(tmp_path / "empty.prom")
    with open(empty, "w") as fh:
        fh.write("some_other_metric 1\n")
    none = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "fleet_report.py"),
         "--from", empty, "--assert-interactive-p99-ms", "300"],
        capture_output=True, text=True, timeout=120)
    assert none.returncode == 2, none.stdout + none.stderr[-2000:]


def test_timeline_conversion_end_to_end():
    """profiler spans -> stop_profiler(profile_path) -> timeline.py ->
    valid Chrome trace JSON."""
    import numpy as np
    from paddle_tpu import profiler
    import timeline

    with tempfile.TemporaryDirectory() as d:
        prof_path = os.path.join(d, "profile")
        profiler.reset_profiler()
        profiler.start_profiler("All")
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.data("x", [4, 4], "float32")
            y = fluid.layers.mean(fluid.layers.relu(x))
        exe = fluid.Executor()
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            with profiler.record_event("user_scope"):
                exe.run(main, feed={"x": np.ones((4, 4), np.float32)},
                        fetch_list=[y])
        profiler.stop_profiler(profile_path=prof_path)
        assert os.path.exists(prof_path)

        tl_path = os.path.join(d, "timeline.json")
        r = subprocess.run(
            [sys.executable, os.path.join(TOOLS, "timeline.py"),
             "--profile_path", prof_path, "--timeline_path", tl_path],
            capture_output=True, text=True)
        assert r.returncode == 0, r.stderr[-1500:]
        with open(tl_path) as f:
            trace = json.load(f)
        events = [e for e in trace["traceEvents"] if e["ph"] == "X"]
        names = {e["name"] for e in events}
        assert "user_scope" in names, names
        assert any(n.startswith("run/program") for n in names), names
        for e in events:
            assert e["dur"] > 0 and e["ts"] >= 0


_RECOVERY_DRILL = r"""
import os, sys, time, tempfile
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.parallel.mesh import MeshConfig, make_mesh
from paddle_tpu.parallel.compiler import CompiledProgram
from paddle_tpu.train.slices import SliceSupervisor


def build(width):
    if width == 1:
        time.sleep(2.0)    # a slow slice rebuild: recovery-heavy run
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup):
        x = fluid.data(name="x", shape=[-1, 4], dtype="float32")
        y = fluid.data(name="y", shape=[-1, 1], dtype="float32")
        loss = layers.mean(layers.square_error_cost(layers.fc(x, 1), y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    mesh = make_mesh(MeshConfig(dcn_dp=width, dp=4))
    compiled = CompiledProgram(main).with_data_parallel(
        loss_name=loss.name, mesh=mesh)
    return {"executor": fluid.Executor(), "program": compiled,
            "startup_program": startup, "scope": fluid.Scope()}


t = [0.0]
box = []


def cb(i, step, fetches):
    t[0] += 1.0
    box[0].beat(0, now=t[0])
    if i < 2:
        box[0].beat(1, now=t[0])


rng = np.random.RandomState(0)
slabs = [{"x": rng.randn(2, 16, 4).astype(np.float32),
          "y": rng.randn(2, 16, 1).astype(np.float32)} for _ in range(8)]
sup = SliceSupervisor(build, tempfile.mkdtemp(), slices=2,
                      heartbeat_timeout_s=1.5, window=2, cooldown_s=0.0,
                      clock=lambda: t[0], steps_per_run=2,
                      checkpoint_every_n_slabs=1, on_slab_end=cb)
box.append(sup)
res = sup.run_slabs(slabs)
assert res["dcn_dp"] == 1 and res["slice_events"], res
from paddle_tpu.observability import render_metrics
with open(sys.argv[1], "w") as f:
    f.write(render_metrics())
"""


@pytest.mark.slow
def test_train_report_goodput_floor_on_recovery_heavy_run(tmp_path):
    """tools/train_report.py --assert-goodput-floor as the multi-slice
    CI gate: a REAL slice-loss drill (subprocess, 8 virtual devices,
    deliberately slow rebuild) dumps its registry metrics; the report
    renders the recovery category, passes a sane floor, and exits 1
    naming ``recovery`` as the worst non-compute category when the
    floor is set above what a shrink-burdened run can deliver."""
    script = str(tmp_path / "drill.py")
    dump = str(tmp_path / "slices.prom")
    with open(script, "w") as f:
        f.write(_RECOVERY_DRILL)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, script, dump],
                       capture_output=True, text=True, cwd=REPO, env=env,
                       timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    with open(dump) as f:
        text = f.read()
    recov = [ln for ln in text.splitlines()
             if ln.startswith("train_time_seconds_total")
             and 'category="recovery"' in ln]
    assert recov and float(recov[0].rsplit(" ", 1)[1]) >= 2.0
    assert 'train_slice_events_total{event="slice_lost"}' in text
    assert 'train_slices_count{state="lost"}' in text
    ok = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "train_report.py"),
         "--from", dump, "--assert-goodput-floor", "0.01"],
        capture_output=True, text=True, timeout=120)
    assert ok.returncode == 0, ok.stdout + ok.stderr[-2000:]
    assert "recovery" in ok.stdout
    bad = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "train_report.py"),
         "--from", dump, "--assert-goodput-floor", "0.999"],
        capture_output=True, text=True, timeout=120)
    assert bad.returncode == 1, bad.stdout + bad.stderr[-2000:]
    assert "GOODPUT-FLOOR VIOLATION" in bad.stderr
    assert "recovery" in bad.stderr   # names the worst category


def test_bench_compare_multislice_dcn_keys(tmp_path):
    """tools/bench_compare.py over the MULTICHIP record's new
    ``meshes.dcn_dp_dp`` keys: cross-slice (DCN) wire bytes are
    lower-is-better; a record whose dcn_dp traffic balloons back to
    flat-all-reduce volume fails the gate by name."""
    import bench_compare

    def record(dcn_wire, total):
        return {"ok": True, "n_devices": 8, "meshes": {"dcn_dp_dp": {
            "loss": 1.85,
            "ledger": {"totals": {"count": 14, "payload_bytes": total,
                                  "wire_bytes": total,
                                  "by_axis": {"dp": total - dcn_wire,
                                              "dcn_dp": dcn_wire}}}}}}

    p_old = str(tmp_path / "old.json")
    p_ok = str(tmp_path / "ok.json")
    p_bad = str(tmp_path / "bad.json")
    with open(p_old, "w") as f:
        json.dump(record(588, 4080), f)
    with open(p_ok, "w") as f:
        json.dump(record(590, 4100), f)
    with open(p_bad, "w") as f:
        # hier decomposition silently lost: DCN carries flat volume
        json.dump(record(4116, 4116), f)
    keys = ["--key=-meshes.dcn_dp_dp.ledger.totals.by_axis.dcn_dp",
            "--key", "meshes.dcn_dp_dp.loss"]
    assert bench_compare.main(
        [p_old, p_ok, *keys, "--max-regress-pct", "10"]) == 0
    assert bench_compare.main(
        [p_old, p_bad, *keys, "--max-regress-pct", "10"]) == 1
    regs, _ = bench_compare.compare(
        record(588, 4080), record(4116, 4116),
        ["-meshes.dcn_dp_dp.ledger.totals.by_axis.dcn_dp"], 10.0)
    assert regs and "dcn_dp" in regs[0]


def _save_tools_gpt_serving(tmp, kind, sharded):
    """Save a tiny-GPT serving executable (bucketed prefill or paged
    decode step) for the shard_report gate, with or without the
    generation stack's tp annotations (models.gpt.apply_tp_sharding —
    dist_attr survives save_inference_model serialization)."""
    from paddle_tpu.models import gpt
    cfg = gpt.GPTConfig.tiny()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        if kind == "prefill":
            d = gpt.gpt_prefill(cfg)
        else:
            d = gpt.gpt_decode_step_paged(cfg)
        if sharded:
            gpt.apply_tp_sharding(main, cfg)
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        fluid.io.save_inference_model(tmp, d["feed_names"],
                                      [d["logits"]], exe,
                                      main_program=main)
    return tmp


@pytest.mark.slow
def test_shard_report_gate_serving_executables(tmp_path):
    """The pod-serving executables run through the SAME replicated-
    param CI gate as training programs: tp-annotated gpt_prefill AND
    gpt_decode_step_paged audit clean under the GPT tp mesh; the same
    decode step without annotations exits 1 naming word_embedding (the
    largest replicated matrix) — so a serving PR cannot silently ship
    a replicated model."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # 0.01 MiB: LN scales / output biases (legitimately replicated,
    # <=128 B) pass; tiny word_embedding (16 KiB) does not
    mesh = ["--mesh", "tp=2", "--threshold-mb", "0.01", "--batch", "2",
            "--assert-no-replicated-params"]
    for kind in ("prefill", "decode"):
        path = _save_tools_gpt_serving(str(tmp_path / kind), kind, True)
        r = subprocess.run(
            [sys.executable, os.path.join(TOOLS, "shard_report.py"),
             path, *mesh],
            capture_output=True, text=True, cwd=REPO, env=env,
            timeout=300)
        assert r.returncode == 0, \
            kind + ": " + r.stdout + r.stderr[-2000:]
        assert "OK: no replicated-large-param findings" in r.stdout
    bad = _save_tools_gpt_serving(str(tmp_path / "bad"), "decode",
                                  False)
    r = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "shard_report.py"), bad,
         "--json", *mesh],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=300)
    assert r.returncode == 1, r.stdout + r.stderr[-2000:]
    assert "REPLICATED-PARAM VIOLATION" in r.stderr
    doc = json.loads(r.stdout)
    assert "word_embedding" in doc["finding"], doc["finding"]


def test_bench_compare_serving_podscale_keys(tmp_path):
    """tools/bench_compare.py over the pod-serving rows: tp tokens/s
    and the fleet cache-hit ratio are higher-is-better, cached-prefix
    warm latency is lower-is-better; a record that silently loses the
    prefix cache (warm == cold, hit ratio 0) fails the gate by name."""
    import bench_compare

    def record(tps2, warm_ms, ratio):
        return {"configs": {
            "serving": {"generation": {
                "tp_scaling": {"2": {"tokens_per_sec": tps2},
                               "greedy_parity": True},
                "prefix_prefill": {"cold_ms": 42.0, "warm_ms": warm_ms,
                                   "leaked_blocks": 0}}},
            "fleet": {"prefix_affinity": {"cache_hit_ratio": ratio,
                                          "leaked_kv_blocks": 0}}}}

    p_old = str(tmp_path / "old.json")
    p_ok = str(tmp_path / "ok.json")
    p_bad = str(tmp_path / "bad.json")
    with open(p_old, "w") as f:
        json.dump(record(310.0, 11.0, 0.5), f)
    with open(p_ok, "w") as f:
        json.dump(record(305.0, 10.5, 0.52), f)
    with open(p_bad, "w") as f:
        # cache silently lost: warm prefill pays the cold price again
        json.dump(record(300.0, 42.0, 0.0), f)
    keys = ["--key",
            "configs.serving.generation.tp_scaling.2.tokens_per_sec",
            "--key=-configs.serving.generation.prefix_prefill.warm_ms",
            "--key", "configs.fleet.prefix_affinity.cache_hit_ratio"]
    assert bench_compare.main(
        [p_old, p_ok, *keys, "--max-regress-pct", "10"]) == 0
    assert bench_compare.main(
        [p_old, p_bad, *keys, "--max-regress-pct", "10"]) == 1
    regs, _ = bench_compare.compare(
        record(310.0, 11.0, 0.5), record(300.0, 42.0, 0.0),
        ["-configs.serving.generation.prefix_prefill.warm_ms",
         "configs.fleet.prefix_affinity.cache_hit_ratio"], 10.0)
    assert len(regs) == 2
    assert any("warm_ms" in r for r in regs)


def test_bench_compare_speculative_keys(tmp_path):
    """tools/bench_compare.py over the speculative-decoding rows: the
    best-K tokens/s, the batch-1 speedup over the plain paged kernel
    and the draft acceptance rate are all higher-is-better; a record
    where drafting silently stopped paying (speedup ~1x, acceptance 0)
    fails the gate by name."""
    import bench_compare

    def record(tps8, speedup, accept):
        return {"speculative": {
            "0": {"tokens_per_sec": 900.0},
            "8": {"tokens_per_sec": tps8, "acceptance_rate": accept},
            "speedup_vs_paged_at_batch1": speedup}}

    p_old = str(tmp_path / "old.json")
    p_ok = str(tmp_path / "ok.json")
    p_bad = str(tmp_path / "bad.json")
    with open(p_old, "w") as f:
        json.dump(record(2400.0, 2.6, 0.97), f)
    with open(p_ok, "w") as f:
        json.dump(record(2300.0, 2.5, 0.95), f)
    with open(p_bad, "w") as f:
        # the drafter stopped proposing: every verify pass pays the
        # span cost for zero accepted tokens
        json.dump(record(880.0, 0.98, 0.0), f)
    keys = ["--key", "speculative.8.tokens_per_sec",
            "--key", "speculative.speedup_vs_paged_at_batch1",
            "--key", "speculative.8.acceptance_rate"]
    assert bench_compare.main(
        [p_old, p_ok, *keys, "--max-regress-pct", "10"]) == 0
    assert bench_compare.main(
        [p_old, p_bad, *keys, "--max-regress-pct", "10"]) == 1
    regs, _ = bench_compare.compare(
        record(2400.0, 2.6, 0.97), record(880.0, 0.98, 0.0),
        ["speculative.8.tokens_per_sec",
         "speculative.speedup_vs_paged_at_batch1",
         "speculative.8.acceptance_rate"], 10.0)
    assert len(regs) == 3
    assert any("speedup_vs_paged_at_batch1" in r for r in regs)
