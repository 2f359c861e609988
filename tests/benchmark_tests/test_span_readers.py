"""CPU tests of the readers of the program's loop spans
(``benchmark/harness/readers_spans.py``) and of the eight metrics that use
them: each reader on a hand-made span list with a known answer, on spans and
device events cut from a traced serve run on the chip (the anchor between the
two clocks included), with a program that keeps no ring, and through the
command in a CPU rehearsal. No timing is asserted."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import cell, readers, readers_spans  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "serve_slice_spans_events.json")
SPECS = {s["name"]: s for s in cell.layer_specs()}
SERVE, TRAIN = "gpt2-medium.serve_chat_closed32", "gpt2.train_8x1024"
NEW = {
    "decode_round_host_ms.serve": SERVE, "admit_share.serve": SERVE,
    "queue_wait_ms_p75.serve": SERVE, "first_token_ms_p75.serve": SERVE,
    "kv_pool_used_share.serve": SERVE,
    "device_idle_decode_host.serve": SERVE,
    "device_idle_admit_host.serve": SERVE, "executor_host_ms.train": TRAIN}


def read(name, facts, events=()):
    spec = SPECS[name]
    return readers.resolve(spec["reader"])(facts, list(events), spec)


def row(name, start, end, sid, parent="", trace="loop:1", **attrs):
    return (name, start, end, 7, trace, sid, parent, attrs)


# one loop, two rounds that stepped and one that only admitted, in a slice
# of two seconds; a round that began before the slice
HAND = [
    row("serving/round", -0.3, -0.001, "r0", step=1, blocks_in_use=90,
        blocks_total=100),
    row("serving/round", 0.0, 1.0, "r1", step=2, live=2, admitted=1,
        blocks_in_use=10, blocks_total=100),
    row("serving/admit", 0.0, 0.1, "a1", "r1", rows=1),
    row("generator/prefill", 0.01, 0.07, "p1", "a1"),
    row("generator/wait", 0.02, 0.06, "w0", "p1", stage="prefill"),
    row("engine/step", 0.1, 0.9, "s1", "r1"),
    row("generator/wait", 0.2, 0.7, "w1", "s1", stage="decode"),
    row("engine/fetch", 0.8, 0.85, "f1", "s1"),
    row("serving/round", 1.0, 1.5, "r2", step=3, live=2,
        blocks_in_use=30, blocks_total=100),
    row("engine/step", 1.05, 1.45, "s2", "r2"),
    row("generator/wait", 1.1, 1.4, "w2", "s2", stage="decode"),
    row("serving/round", 1.5, 1.6, "r3", admitted=1),
    row("serving/admit", 1.9, 2.1, "a4", "r4", rows=2),
    # another loop's wait under a span id that collides: not r1's
    row("generator/wait", 0.3, 0.4, "w9", "s1", trace="loop:2"),
    row("serving/queue", -0.05, 0.05, "q1", trace="req:1"),
    row("serving/queue", 0.1, 0.3, "q2", trace="req:2"),
    row("serving/queue", 0.5, 0.8, "q3", trace="req:3"),
    row("serving/queue", 1.0, 1.4, "q4", trace="req:4"),
    row("serving/queue", 1.9, 2.4, "q5", trace="req:5"),    # ends outside
    row("serving/first_token", 0.1, 0.5, "t2", trace="req:2"),
    row("serving/first_token", 0.5, 1.3, "t3", trace="req:3"),
]
HAND_FACTS = {"slice": (0.0, 2.0), "spans": HAND}


@pytest.mark.parametrize("name,expect", [
    # r1: 1.0 - (0.04 + 0.5) - 0.05 = 0.41; r2: 0.5 - 0.3 = 0.2
    ("decode_round_host_ms.serve", 305.0),
    # 0.1 s of a1 and the 0.1 s of a4 that lie inside, of 2 s
    ("admit_share.serve", 10.0),
    # 0.1, 0.2, 0.3, 0.4 s ended inside: 0.3 + 0.25 * 0.1
    ("queue_wait_ms_p75.serve", 325.0),
    ("first_token_ms_p75.serve", 700.0),
    # 10 and 30 of 100 at the two rounds that stepped inside the slice
    ("kv_pool_used_share.serve", 20.0),
])
def test_each_reader_on_a_hand_made_list(name, expect):
    assert read(name, HAND_FACTS) == pytest.approx(expect, rel=1e-9)


def test_a_round_begun_before_the_slice_has_no_self_time():
    """Its children may lie before the slice, among rows the reader was
    not given; its counts at the step inside the slice still count."""
    late = dict(HAND_FACTS, slice=(0.5, 2.0))
    assert read("decode_round_host_ms.serve", late) == pytest.approx(200.0)
    assert read("kv_pool_used_share.serve", late) == pytest.approx(20.0)


def test_idle_under_span_on_a_hand_made_trace():
    """A slice of 1 ms that the trace's clock starts at 1,000 ns and the
    host's at 5.0 s. The chip is busy 100-500 and 600-900 us: idle 0-100,
    500-600 and 900-1000. Two rounds tile the slice; an admission covers
    520-580 us."""
    dev, host, ns = "/device:TPU:0", "/host:CPU", 1000
    events = [[host, "python", "bench/slice", ns, 1_000_000],
              [dev, "XLA Ops", "fusion", ns + 100_000, 250_000],
              [dev, "XLA Ops", "copy", ns + 300_000, 200_000],
              [dev, "XLA Ops", "fusion", ns + 600_000, 300_000],
              [dev, "XLA Modules", "jit_run", ns, 1_000_000],
              ["/device:TPU:1", "XLA Ops", "fusion", ns, 1_000_000]]
    spans = [row("serving/round", 5.0 - 1e-4, 5.00055, "r1", step=1),
             row("serving/admit", 5.00052, 5.00058, "a1", "r1"),
             row("serving/round", 5.00055, 5.002, "r2", step=2)]
    facts = {"slice": (5.0, 5.001), "spans": spans}
    # idle outside the admission: 100 + 20 + 20 + 100 of 1000 us
    assert read("device_idle_decode_host.serve", facts, events) \
        == pytest.approx(24.0, rel=1e-9)
    # and inside it: 520-580
    assert read("device_idle_admit_host.serve", facts, events) \
        == pytest.approx(6.0, rel=1e-9)
    # together, what device_idle_share reads of chip 0
    assert readers.device_idle_share({}, events[:-1], {}) \
        == pytest.approx(30.0, rel=1e-9)
    # no chip's plane (the CPU rehearsal), no slice mark: nothing to read
    assert read("device_idle_decode_host.serve", facts, events[:1]) is None
    assert read("device_idle_admit_host.serve", facts, events[1:]) is None


def test_interval_arithmetic():
    m = readers_spans.merged([(5, 9), (0, 3), (2, 4), (9, 10), (7, 7)])
    assert m == [(0, 4), (5, 10)]
    assert readers_spans.common(m, [(3, 6), (8, 20)]) == [
        (3, 4), (5, 6), (8, 10)]
    assert readers_spans.outside(m, [(1, 2), (6, 30)], 0, 12) == [
        (0, 1), (2, 4), (5, 6)]


def test_the_train_reader_takes_the_last_traced_steps():
    """The train driver's facts name no slice: the traced steps are the
    last ``traced_steps`` the executor ran."""
    spans = []
    for i, (length, wait) in enumerate([(0.5, 0.1), (0.010, 0.004),
                                        (0.008, 0.001)]):
        spans.append(row("executor/run", i, i + length, f"x{i}",
                         trace="exe:1", step_num=i))
        spans.append(row("executor/fetch_wait", i + length - wait,
                         i + length, f"w{i}", f"x{i}", trace="exe:1"))
    facts = {"spans": spans, "traced_steps": 2}
    assert read("executor_host_ms.train", facts) == pytest.approx(6.5)
    assert read("executor_host_ms.train",
                {"spans": spans, "traced_steps": None}) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_nothing_to_read_is_none(name):
    """No span of that name in the slice, or no span at all."""
    empty = {"slice": (0.0, 2.0), "spans": [], "traced_steps": 2}
    assert read(name, empty) is None
    other = dict(empty, spans=[row("unit/other", 0.1, 0.2, "o")])
    events = [["/host:CPU", "python", "bench/slice", 0, 10],
              ["/device:TPU:0", "XLA Ops", "fusion", 2, 3]]
    assert read(name, other, events) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_program_without_the_ring_reads_none(name, monkeypatch):
    """The parent commit has no ``loop_spans``: the reader returns None
    and does not raise, and the line leaves the metric out."""
    from paddle_tpu.observability import tracing
    monkeypatch.delattr(tracing, "loop_spans")
    facts = {"slice": (0.0, 2.0), "traced_steps": 2}
    events = [["/host:CPU", "python", "bench/slice", 0, 10],
              ["/device:TPU:0", "XLA Ops", "fusion", 2, 3]]
    assert read(name, facts, events) is None


def test_the_readers_read_the_programs_own_ring():
    """With no ``spans`` among the facts the rows come from
    ``tracing.loop_spans`` over the slice."""
    import time
    from paddle_tpu import profiler
    from paddle_tpu.observability import tracing
    profiler.reset_profiler()
    t0 = time.perf_counter()
    with tracing.loop_span("serving/round", tracing.loop_root("loop:t"),
                           step=1, blocks_in_use=1, blocks_total=4):
        pass
    facts = {"slice": (t0, time.perf_counter())}
    assert read("kv_pool_used_share.serve", facts) == pytest.approx(25.0)
    profiler.reset_profiler()


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_new_metrics_keep_the_contracts_shape(name):
    bench = cell.benchmark_json()
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    spec = SPECS[name]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert all(spec[k] == entry[k] for k in entry)
    assert entry["workloads"] == [NEW[name]]
    assert entry["source"] == ("program_counter" if "kv_pool" in name
                               else "program_span")
    assert spec["reader"].startswith("readers_spans:")
    e2e = next(m for m in bench["end_to_end"] if m["name"] == entry["moves"])
    assert NEW[name] in e2e["workloads"]
    # the new entries stand after the ten that were there
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index(name) >= 10


# --------------------------------------------- the slice cut on the chip

def recorded_slice():
    """The fixture's operations as the event rows ``read_events`` gave,
    the mark among them, and the facts a driver would hold."""
    with open(FIXTURE) as fh:
        fixture = json.load(fh)
    mark = fixture["mark"]
    events = [[fixture["plane"], fixture["line"], fixture["names"][name],
               mark["start_ns"] + start, dur]
              for start, dur, name in fixture["ops"]]
    events.append([mark["plane"], mark["line"], mark["name"],
                   mark["start_ns"], mark["dur_ns"]])
    facts = {"slice": tuple(fixture["slice"]),
             "spans": [tuple(r) for r in fixture["spans"]]}
    return facts, events, fixture["expect"]


@pytest.mark.parametrize("name", sorted(n for n in NEW if n.endswith("serve")))
def test_readers_on_the_recorded_chip_slice(name):
    """Spans and chip 0's operations of the first 0.45 s of this PR's own
    traced serve slice on the v5e, the ``bench/slice`` mark beside
    ``facts["slice"]`` as the harness recorded both. The expected values
    were worked out apart from the readers (the fixture says how)."""
    facts, events, expect = recorded_slice()
    assert len(events) >= 5000 and len(facts["spans"]) >= 50
    assert read(name, facts, events) == pytest.approx(
        expect["metrics"][name], rel=expect["rel"])


def test_the_rounds_account_for_the_chips_idle_time():
    facts, events, expect = recorded_slice()
    idle = readers.device_idle_share({}, events, {})
    assert idle == pytest.approx(expect["device_idle_share"], rel=1e-9)
    attributed = (read("device_idle_decode_host.serve", facts, events)
                  + read("device_idle_admit_host.serve", facts, events))
    # the loop is inside a round but for the line between two of them
    assert 0.99 * idle <= attributed <= idle
    # moved by 50 ms against the trace's clock the spans would claim
    # another part of it: the anchor matters
    moved = dict(facts, slice=(facts["slice"][0] + 0.05,
                               facts["slice"][1] + 0.05))
    assert read("device_idle_admit_host.serve", moved, events) \
        != pytest.approx(expect["metrics"]["device_idle_admit_host.serve"],
                         rel=0.05)


# ------------------------------------------------- the command, from outside

@pytest.mark.parametrize("workload,printed", [
    (SERVE, ["decode_round_host_ms.serve", "admit_share.serve",
             "queue_wait_ms_p75.serve", "first_token_ms_p75.serve",
             "kv_pool_used_share.serve"]),
    (TRAIN, ["executor_host_ms.train"]),
])
def test_cpu_rehearsal_prints_the_span_metrics(workload, printed):
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", workload, "--seed", str(2**31 + 29), "--seconds", "1",
         "--trace", "1", "--rehearse-cpu"],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    for name in printed:
        assert line["metrics"][name]["unit"] == SPECS[name]["unit"]
        assert line["metrics"][name]["value"] > 0
    # the two that need a chip's plane are left out, never made from a CPU
    assert not any("idle" in m for m in line["metrics"])
