"""Unified telemetry (paddle_tpu/observability): MetricsRegistry +
Prometheus exposition, wire-propagated request tracing, live MFU/HBM
gauges, the flight recorder, the profiler span-drop counter, the
timeline round trip, and the server.stats() payload-compat guard."""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, profiler, resilience, serving
from paddle_tpu.observability import (FlightRecorder, MetricsRegistry,
                                      flight_recorder, render_metrics,
                                      set_peaks, tracing)
from paddle_tpu.observability import utilization as util
from paddle_tpu.serving.metrics import LatencyHistogram, ServingStats

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RNG = np.random.default_rng(3)


# ------------------------------------------------------- MetricsRegistry

def test_registry_counter_gauge_render():
    reg = MetricsRegistry()
    c = reg.counter("x_requests_total", "reqs", labels=("kind",))
    g = reg.gauge("x_depth_count", "depth")
    c.inc(labels=("a",))
    c.inc(2, labels=("b",))
    g.set(7)
    txt = reg.render()
    assert "# TYPE x_requests_total counter" in txt
    assert 'x_requests_total{kind="a"} 1' in txt
    assert 'x_requests_total{kind="b"} 2' in txt
    assert "# TYPE x_depth_count gauge" in txt
    assert "x_depth_count 7" in txt


def test_registry_histogram_cumulative_buckets():
    reg = MetricsRegistry()
    h = reg.histogram("x_lat_ms", "lat", bounds=(1.0, 10.0))
    for v in (0.5, 0.6, 5.0, 50.0):
        h.observe(v)
    txt = reg.render()
    assert 'x_lat_ms_bucket{le="1"} 2' in txt
    assert 'x_lat_ms_bucket{le="10"} 3' in txt
    assert 'x_lat_ms_bucket{le="+Inf"} 4' in txt
    assert "x_lat_ms_count 4" in txt


def test_registry_name_validation_and_uniqueness():
    reg = MetricsRegistry()
    with pytest.raises(ValueError, match="snake_case"):
        reg.counter("BadName_total")
    with pytest.raises(ValueError, match="unit suffix"):
        reg.counter("x_requests")
    reg.counter("dup_total")
    reg.counter("dup_total")            # same kind: idempotent
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("dup_total")          # kind mismatch


def test_registry_label_cardinality_bounded():
    reg = MetricsRegistry()
    c = reg.counter("x_card_total", labels=("k",), max_series=4)
    for i in range(10):
        c.inc(labels=(f"v{i}",))
    txt = reg.render()
    # overflow folded into the reserved series, loss counted
    assert 'x_card_total{k="_other"} 6' in txt
    assert "telemetry_series_dropped_total 6" in txt


def test_registry_collector_and_catalog():
    reg = MetricsRegistry()
    reg.register_collector(
        lambda: [{"name": "y_things_total", "kind": "counter",
                  "help": "h", "labels": (), "samples": [((), 5)]}],
        families=[{"name": "y_things_total", "kind": "counter",
                   "help": "h", "labels": ()}])
    assert "y_things_total 5" in reg.render()
    assert "y_things_total" in reg.catalog()
    # a collector-declared name blocks native re-registration
    with pytest.raises(ValueError, match="already"):
        reg.counter("y_things_total")


def test_label_escaping():
    reg = MetricsRegistry()
    c = reg.counter("x_esc_total", labels=("p",))
    c.inc(labels=('a"b\\c\nd',))
    assert 'p="a\\"b\\\\c\\nd"' in reg.render()


# ------------------------------------- LatencyHistogram consistent reads

def test_latency_histogram_snapshot_consistent_under_writes():
    """snapshot() derives p50/p99 from ONE copy of the buckets: under a
    concurrent observe() hammer the invariant p50 <= p99 <= max always
    holds (the torn-read bug could interpolate a percentile above the
    snapshotted max)."""
    h = LatencyHistogram("t")
    stop = threading.Event()

    def hammer():
        i = 0
        while not stop.is_set():
            h.observe((i % 1000) / 1e4)     # 0..100ms spread
            i += 1

    t = threading.Thread(target=hammer, daemon=True)
    t.start()
    try:
        deadline = time.monotonic() + 0.3
        while time.monotonic() < deadline:
            s = h.snapshot()
            assert s["p50_ms"] <= s["p99_ms"] + 1e-9
            assert s["p99_ms"] <= s["max_ms"] + 1e-9
    finally:
        stop.set()
        t.join(1)
    assert h.count > 0


def test_serving_stats_snapshot_keys_unchanged():
    """The server.stats() payload contract: every pre-telemetry key is
    still present with the same spelling (the registry bridge must not
    change the Python payload)."""
    snap = ServingStats().snapshot(extra={"queue_depth": 0})
    expected_counters = {
        "requests_admitted", "requests_completed", "requests_failed",
        "shed_overload", "shed_deadline", "batches", "rows",
        "padded_rows", "compiles", "generate_requests",
        "tokens_generated", "decode_steps", "decode_rows",
        "decode_slot_rows", "engine_failures", "watchdog_timeouts",
        "loop_restarts", "weight_reloads", "hedge_dedup_hits",
        "requests_cancelled", "kv_exports", "kv_imports",
        "spec_steps", "spec_drafted", "spec_accepted", "spec_rejected",
        # PR 28: the routed expert layers' counts
        "moe_assignments", "moe_expert_load_max", "moe_experts_hit",
        # PR 32: decode steps sent before the last step's tokens were read
        "decode_steps_ahead",
        # PR 33: passes over the weights, steps and prefills alike
        "loop_passes",
        # PR 36: per-slot recurrent state beside the KV blocks
        "state_slot_writes", "scan_tokens",
        # admissions, and those sent as one executable
        "admissions", "admissions_fused"}
    derived = {"uptime_s", "throughput_rps", "mean_batch_size",
               "batch_occupancy", "tokens_per_s", "decode_occupancy",
               "queue_depth", "spec_accept_ratio"}
    stage_keys = {f"{s}_{k}" for s in ServingStats.STAGES
                  for k in ("count", "mean_ms", "p50_ms", "p99_ms",
                            "max_ms")}
    assert set(snap) == expected_counters | derived | stage_keys


def test_counters_monotonic_across_sink_gc():
    """Exported serving counters must never decrease: a garbage-
    collected ServingStats banks its final counts into the retired
    totals (Prometheus rate() treats a drop as a counter reset)."""
    import gc
    import re

    def admitted():
        m = re.search(r"^serving_requests_admitted_total (\S+)$",
                      render_metrics(), re.M)
        return float(m.group(1))

    base = admitted()
    s = ServingStats()
    s.bump("requests_admitted", 5)
    s.hist["queue"].observe(0.001)
    assert admitted() == base + 5
    del s
    gc.collect()
    assert admitted() == base + 5


def test_spans_dropped_total_monotonic_across_reset(monkeypatch):
    """The exported drop counter is the process-lifetime total:
    reset_profiler zeroes only the session count."""
    base = profiler.spans_dropped_total()
    monkeypatch.setattr(profiler, "_MAX_SPANS", 1)
    root = tracing.new_trace()
    tracing.record_child("a", 0.0, 1.0, root)
    tracing.record_child("b", 0.0, 1.0, root)
    monkeypatch.undo()
    profiler.reset_profiler()
    assert profiler.spans_dropped() == 0
    assert profiler.spans_dropped_total() >= base + 1


# ---------------------------------------------------- profiler span drops

def test_profiler_counts_dropped_spans(tmp_path, capsys,
                                       monkeypatch):
    profiler.reset_profiler()
    monkeypatch.setattr(profiler, "_MAX_SPANS", 3)
    profiler.start_profiler()
    for _ in range(5):
        with profiler.record_event("ev"):
            pass
    path = str(tmp_path / "prof.json")
    profiler.stop_profiler(profile_path=path)
    out = capsys.readouterr().out
    assert profiler.spans_dropped() == 2
    assert "2 spans dropped" in out
    with open(path) as f:
        doc = json.load(f)
    assert doc["dropped"] == 2 and len(doc["spans"]) == 3
    profiler.reset_profiler()
    assert profiler.spans_dropped() == 0


# -------------------------------------------------------- flight recorder

def test_flight_recorder_ring_and_dump(tmp_path):
    rec = FlightRecorder(capacity=3)
    for i in range(5):
        rec.record("ev", i=i, arr=np.int32(7))   # coerced wire-safe
    events = rec.snapshot()
    assert len(events) == 3                      # ring bound
    assert [e["i"] for e in events] == [2, 3, 4]
    assert isinstance(events[0]["arr"], str)     # non-wire value coerced
    assert rec.counts() == {"ev": 3}
    path = rec.dump(path=str(tmp_path / "d.json"), reason="test")
    with open(path) as f:
        doc = json.load(f)
    assert doc["reason"] == "test" and len(doc["events"]) == 3


def test_flight_recorder_auto_dump_gated_and_rate_limited(tmp_path):
    rec = FlightRecorder(capacity=8)
    rec.record("x")
    assert rec.auto_dump("r") is None            # flag empty: off
    fluid.set_flags({"flight_recorder_dir": str(tmp_path)})
    try:
        p1 = rec.auto_dump("r")
        assert p1 and os.path.exists(p1)
        assert rec.auto_dump("r") is None        # rate-limited
    finally:
        fluid.set_flags({"flight_recorder_dir": ""})


def test_flight_recorder_singleton_tracks_capacity_flag():
    """set_flags({"flight_recorder_events": N}) resizes the live
    singleton's ring (keeping the newest events) — a pre-soak resize
    silently ignored would shrink the postmortem window."""
    rec = flight_recorder()
    default_cap = rec._ring.maxlen
    try:
        fluid.set_flags({"flight_recorder_events": 4})
        rec.record("cap_probe", i=0)
        assert rec._ring.maxlen == 4
        for i in range(1, 7):
            rec.record("cap_probe", i=i)
        kept = [e["i"] for e in rec.snapshot() if e["kind"] == "cap_probe"]
        assert kept == [3, 4, 5, 6]
        # pinned-capacity recorders (tests, embedders) stay pinned
        pinned = FlightRecorder(capacity=2)
        pinned.record("x")
        assert pinned._ring.maxlen == 2
    finally:
        fluid.set_flags({"flight_recorder_events": default_cap})
        rec.record("cap_probe", i=99)            # restores the ring size
        assert rec._ring.maxlen == default_cap


def test_breaker_collector_folds_overflow_not_truncates():
    """>64 distinct breaker endpoints: the collector folds the overflow
    into one _other series carrying the MAX state (an OPEN breaker past
    the cap must still trip dashboards) and feeds the fold count to
    telemetry_series_dropped_total instead of silently truncating."""
    keep = []                    # WeakSet: keep the breakers alive
    try:
        for i in range(70):
            b = resilience.CircuitBreaker(endpoint=f"ep{i:03d}:1")
            keep.append(b)
        # zz sorts past the 64-series cap; force it open
        zz = resilience.CircuitBreaker(endpoint="zz-host:9000")
        keep.append(zz)
        for _ in range(100):
            zz.record_failure()
        assert zz.state == "open"
        fams = resilience._collect_breakers()
        (fam,) = fams
        samples = dict(fam["samples"])
        assert len(samples) <= 64
        assert samples[("_other",)] == 2         # the open breaker shows
        assert fam["dropped"] >= 1
        # and the registry folds it into the process-wide drop counter
        text = render_metrics()
        line = [ln for ln in text.splitlines()
                if ln.startswith("telemetry_series_dropped_total ")][0]
        assert float(line.split()[1]) >= fam["dropped"]
    finally:
        keep.clear()


def test_chaos_firings_land_in_flight_recorder():
    rec = flight_recorder()
    before = rec.counts().get("chaos", 0)
    with resilience.chaos("obs.test_point", p=1.0, times=2):
        for _ in range(3):
            try:
                resilience.maybe_fail("obs.test_point")
            except resilience.FaultInjected:
                pass
    points = [e["point"] for e in rec.snapshot()
              if e["kind"] == "chaos"]
    assert points.count("obs.test_point") == 2
    assert rec.counts().get("chaos", 0) == before + 2


# ----------------------------------------------------------- utilization

def test_utilization_gauges_match_bench_formula():
    util.reset_windows()
    set_peaks(flops_per_s=1e12, hbm_bytes_per_s=1e11)
    try:
        cost = {"flops": 2e9, "bytes": 1e8}
        for _ in range(4):
            util.observe_execution("testwhere", cost, 0.01)
        u = util.utilization("testwhere")
        # the bench roofline formula: flops/sec / peak
        assert u["mfu"] == pytest.approx(2e9 / 0.01 / 1e12, rel=1e-6)
        assert u["hbm_bw_util"] == pytest.approx(1e8 / 0.01 / 1e11,
                                                 rel=1e-6)
    finally:
        set_peaks()
        util.reset_windows()


def test_bench_peak_tables_are_the_live_tables():
    import bench
    assert bench._PEAK_TFLOPS is util.PEAK_TFLOPS
    assert bench._HBM_PEAK is util.HBM_PEAK


def test_executor_exports_cost_counters():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [-1, 8], dtype="float32")
        y = layers.data("y", [-1, 1], dtype="float32")
        loss = layers.mean(
            layers.square_error_cost(layers.fc(x, 1), y))
        fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor()
    scope = fluid.Scope()
    feed = {"x": np.zeros((4, 8), np.float32),
            "y": np.zeros((4, 1), np.float32)}
    with fluid.scope_guard(scope):
        exe.run(startup)
        for _ in range(3):
            exe.run(main, feed=feed, fetch_list=[loss])
    txt = render_metrics()
    flops = [ln for ln in txt.splitlines()
             if ln.startswith('device_flops_total{where="step"}')]
    assert flops and float(flops[0].split()[-1]) > 0


def test_utilization_cadence_reseeds_after_sustained_slowdown(monkeypatch):
    """A durable >10x slowdown must re-seed the dispatch-to-dispatch
    cadence baseline (after 3 consecutive over-cadence deltas) instead
    of classifying every future delta as an idle gap forever — which
    would freeze the live gauges at the pre-slowdown reading."""
    from paddle_tpu.framework import executor as executor_mod

    exe = fluid.Executor()
    observed = []
    monkeypatch.setattr(executor_mod._util, "cost_for",
                        lambda memo, key, compiled: {"flops": 1.0,
                                                     "bytes": 1.0})
    monkeypatch.setattr(executor_mod._util, "observe_execution",
                        lambda where, cost, s: observed.append(s))
    clock = [0.0]
    monkeypatch.setattr(executor_mod.time, "perf_counter",
                        lambda: clock[0])

    def step(dt):
        clock[0] += dt
        exe._observe_utilization("step", "k", compiled=None)

    step(0.0)                       # first dispatch: no delta
    step(0.001)                     # seeds cadence (dropped)
    for _ in range(5):
        step(0.001)                 # steady state: measured
    assert len(observed) == 5
    for _ in range(3):
        step(0.015)                 # durable 15x slowdown: 3 gaps
    assert len(observed) == 5       # gap run dropped, third re-seeds
    for _ in range(4):
        step(0.015)                 # new steady state: measured again
    assert len(observed) == 9, "gauges froze after sustained slowdown"


def test_admission_sheds_sampled_into_flight_recorder():
    """A shed storm must not churn the flight-recorder ring: refusals
    are sampled per outcome (first, then every 64th) with the
    cumulative count riding each sampled event."""
    from paddle_tpu.serving.batching import Request, RequestQueue

    rec = flight_recorder()
    before = [e for e in rec.snapshot()
              if e["kind"] == "admission"
              and e.get("outcome") == "shed_overload"]
    q = RequestQueue(max_depth=1,
                     breaker=resilience.CircuitBreaker(
                         endpoint="shed-test",
                         failure_threshold=10**9))
    q.put(Request({"x": np.zeros((1, 2), np.float32)}))
    for _ in range(130):
        with pytest.raises(Exception):
            q.put(Request({"x": np.zeros((1, 2), np.float32)}))
    evs = [e for e in rec.snapshot()
           if e["kind"] == "admission"
           and e.get("outcome") == "shed_overload"
           and e not in before]
    # 130 sheds -> sampled events only (n=1, 64, 128), each carrying
    # the cumulative count
    assert 1 <= len(evs) <= 4, len(evs)
    assert evs[-1]["n"] >= 128
    q.close()


# --------------------------------------------------------------- tracing

def test_maybe_trace_sampling(monkeypatch):
    fluid.set_flags({"trace_sample_rate": 0.0})
    try:
        assert tracing.maybe_trace() is None
        fluid.set_flags({"trace_sample_rate": 1.0})
        ctx = tracing.maybe_trace()
        assert ctx is not None and ctx.parent_id == ""
        with tracing.ambient(ctx):
            child = tracing.maybe_trace()
            assert child.trace_id == ctx.trace_id
            assert child.parent_id == ctx.span_id
    finally:
        fluid.set_flags({"trace_sample_rate": 0.01})


def test_from_wire_rejects_garbage():
    assert tracing.from_wire(None) is None
    assert tracing.from_wire("x") is None
    assert tracing.from_wire({"tid": 3, "sid": "a"}) is None
    ctx = tracing.from_wire({"tid": "t" * 100, "sid": "s"})
    assert ctx.trace_id == "t" * 64                # capped


def test_traced_spans_record_without_profiler():
    profiler.reset_profiler()
    assert not profiler.is_profiling()
    root = tracing.new_trace()
    tracing.record_child("unit/span", 0.0, 1.0, root)
    spans = [s for s in list(profiler._spans)
             if len(s) >= 7 and s[0] != "process/gc"]
    assert spans and spans[-1][0] == "unit/span"
    assert spans[-1][4] == root.trace_id
    assert spans[-1][6] == root.span_id
    profiler.reset_profiler()


# ----------------------------------------------- timeline.py round trip

def test_timeline_round_trip(tmp_path):
    """Satellite: record spans -> stop_profiler JSON -> timeline CLI ->
    valid Chrome trace JSON with matching event count."""
    profiler.reset_profiler()
    profiler.start_profiler()
    for name in ("a", "b", "c"):
        with profiler.record_event(name):
            time.sleep(0.001)
    root = tracing.new_trace()
    tracing.record_child("traced/child", 10.0, 10.5, root)
    prof_path = str(tmp_path / "prof.json")
    out_path = str(tmp_path / "timeline.json")
    profiler.stop_profiler(profile_path=prof_path)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "timeline.py"),
         "--profile_path", prof_path, "--timeline_path", out_path],
        capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stderr
    with open(out_path) as f:
        trace = json.load(f)
    events = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert len(events) == 4                       # 3 profiled + 1 traced
    traced = [e for e in events if e.get("args", {}).get("trace_id")]
    assert len(traced) == 1
    assert traced[0]["args"]["trace_id"] == root.trace_id
    profiler.reset_profiler()


def test_timeline_op_spans_and_memory_counter_round_trip(tmp_path):
    """Satellite: a FLAGS_profile_ops measured replay -> op-level child
    spans + the hbm_live_bytes counter track -> stop_profiler JSON ->
    timeline.py -> valid Perfetto/Chrome JSON: counter ("C") events
    with monotone timestamps at op boundaries, op spans parent-chained
    under one profile span."""
    profiler.reset_profiler()
    profiler.start_profiler()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [-1, 8], dtype="float32")
        y = layers.mean(layers.relu(layers.fc(x, 4)))
    exe = fluid.Executor()
    scope = fluid.Scope()
    fluid.set_flags({"FLAGS_profile_ops": 1})
    try:
        with fluid.scope_guard(scope):
            exe.run(startup)
            exe.run(main, feed={"x": np.ones((2, 8), np.float32)},
                    fetch_list=[y])
    finally:
        fluid.set_flags({"FLAGS_profile_ops": 0})
    prof_path = str(tmp_path / "prof.json")
    out_path = str(tmp_path / "timeline.json")
    profiler.stop_profiler(profile_path=prof_path)
    with open(prof_path) as f:
        doc = json.load(f)
    assert doc.get("counters"), "hbm_live_bytes track missing"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "timeline.py"),
         "--profile_path", prof_path, "--timeline_path", out_path],
        capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stderr
    with open(out_path) as f:
        trace = json.load(f)
    events = trace["traceEvents"]
    ops = [e for e in events if e["ph"] == "X"
           and e["name"].startswith("op/")]
    parents = [e for e in events if e["ph"] == "X"
               and e["name"].startswith("profile/ops_")]
    assert ops and parents
    parent_ids = {p["args"]["span_id"] for p in parents}
    assert all(e["args"]["parent_span_id"] in parent_ids
               for e in ops), "op spans must chain under profile/ops"
    counters = [e for e in events if e["ph"] == "C"
                and e["name"] == "hbm_live_bytes"]
    assert counters
    ts = [e["ts"] for e in counters]
    assert ts == sorted(ts), "counter samples must be time-monotone"
    assert all(e["args"]["value"] >= 0 for e in counters)
    profiler.reset_profiler()


# ------------------------------------------- wire integration (server)

def _save_mlp(tmp_path, in_dim=8, out_dim=4):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [-1, in_dim], dtype="float32")
        h = layers.fc(x, 16, act="relu")
        out = layers.fc(h, out_dim, act="softmax")
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        path = str(tmp_path / "mlp")
        fluid.io.save_inference_model(path, ["x"], [out], exe,
                                      main_program=main)
    return path


def test_metrics_wire_op_and_trace_propagation(tmp_path):
    """Acceptance: the "metrics" wire op returns Prometheus text
    covering serving / executor-cache / pass / resilience / training
    metrics, server.stats() keys are unchanged, and one traced request
    yields client-send, queue, pad, execute and reply spans under ONE
    trace id with an unbroken parent chain."""
    profiler.reset_profiler()
    path = _save_mlp(tmp_path)
    server = serving.InferenceServer(path, batch_timeout_ms=1.0).start()
    try:
        with serving.Client(server.endpoint) as c:
            root = tracing.new_trace()
            with tracing.ambient(root):
                c.infer({"x": RNG.standard_normal((2, 8))
                         .astype(np.float32)})
            txt = c.metrics()
            dump = c.debug_dump()
        # exposition covers every subsystem named in the acceptance
        for needle in ("serving_requests_admitted_total",
                       "serving_stage_latency_ms_bucket",
                       "executor_cache_hits_total",
                       "program_pass_runs_total",
                       "resilience_breaker_state",
                       "train_checkpoints_total",
                       "device_mfu_ratio"):
            assert needle in txt, needle
        # stats payload unchanged (superset check is in the dedicated
        # keys test; here the wire payload must still carry the core)
        stats = server.stats()
        for key in ("requests_admitted", "throughput_rps",
                    "mean_batch_size", "queue_p99_ms", "cache_hits",
                    "state", "weights_version"):
            assert key in stats, key
        # flight recorder saw the admission
        assert any(e["kind"] == "admission"
                   and e["outcome"] == "admitted"
                   for e in dump["events"])
    finally:
        server.stop()

    # (the always-on loop spans of the executor ride the same table,
    # eight fields each, under their own trace ids)
    spans = [s for s in list(profiler._spans) if len(s) == 7]
    assert {s[4] for s in spans} == {root.trace_id}
    names = {s[0] for s in spans}
    for required in ("client/send", "serving/handle", "serving/queue",
                     "serving/pad", "serving/execute", "serving/reply"):
        assert required in names, (required, names)
    # unbroken parent chain: every span walks up to the trace root
    by_id = {s[5]: s for s in spans}
    for s in spans:
        cur, hops = s, 0
        while cur[6] != root.span_id and cur[6] != "" and hops < 16:
            cur = by_id.get(cur[6])
            assert cur is not None, f"broken parent chain from {s[0]}"
            hops += 1
    profiler.reset_profiler()


@pytest.mark.slow
def test_generate_trace_covers_prefill_and_decode():
    """One traced generation yields prefill + per-token decode spans
    under the same trace id (the decode slot bank threads the
    context)."""
    from paddle_tpu.models import gpt as gpt_mod
    from paddle_tpu.models.generation import GPTGenerator
    profiler.reset_profiler()
    cfg = gpt_mod.GPTConfig.tiny()
    gmain, gstartup = fluid.Program(), fluid.Program()
    with fluid.program_guard(gmain, gstartup):
        gpt_mod.gpt_logits(cfg)
    exe = fluid.Executor()
    gscope = fluid.Scope()
    with fluid.scope_guard(gscope):
        exe.run(gstartup)
    gen = GPTGenerator(cfg, gscope, max_len=32, bucket_min=8)
    server = serving.InferenceServer(generator=gen, decode_slots=2)
    server.start(serve_network=False)
    try:
        root = tracing.new_trace()
        with tracing.ambient(root):
            req = server.submit_generate(
                np.arange(1, 5, dtype=np.int32), max_new_tokens=3)
        req.wait(timeout=300)
    finally:
        server.stop()
    spans = [s for s in list(profiler._spans)
             if len(s) >= 7 and s[4] == root.trace_id]
    names = [s[0] for s in spans]
    assert "serving/queue" in names
    assert "serving/prefill" in names
    assert names.count("serving/decode") >= 2     # per-token spans
    profiler.reset_profiler()


def test_serving_engine_feeds_infer_utilization(tmp_path):
    util.reset_windows()
    set_peaks(flops_per_s=1e12, hbm_bytes_per_s=1e11)
    try:
        path = _save_mlp(tmp_path)
        server = serving.InferenceServer(path,
                                         batch_timeout_ms=1.0).start(
            serve_network=False)
        try:
            for _ in range(3):
                server.infer({"x": np.zeros((2, 8), np.float32)},
                             timeout=60)
        finally:
            server.stop()
        u = util.utilization("infer")
        assert 0.0 < u["mfu"] <= 1.0
    finally:
        set_peaks()
        util.reset_windows()


# -------------------------------------------------- lint_metrics checks

def test_lint_metrics_check_function():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import lint_metrics
    readme = "catalog: `good_things_total` and `also_ok_ms`"
    assert lint_metrics.check(
        ["good_things_total", "also_ok_ms"], readme) == []
    errors = lint_metrics.check(
        ["BadCase_total", "no_suffix", "undocumented_total",
         "good_things_total", "good_things_total"], readme)
    assert any("snake_case" in e for e in errors)
    assert any("unit suffix" in e for e in errors)
    assert any("missing from the README" in e for e in errors)
    assert any("more than once" in e for e in errors)
