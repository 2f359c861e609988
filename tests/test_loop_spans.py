"""The always-on loop spans (``observability.tracing.loop_span``): what the
decode loop, the generator and ``Executor.run`` leave in the profiler's span
table for every round, request and step, with no profiler session and no
sampled request. CPU, tiny GPT; no timing is asserted beyond ordering."""
import json
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import profiler, serving
from paddle_tpu.observability import tracing

NAME, START, END, TID, TRACE, SPAN, PARENT, ATTRS = range(8)
REQUEST_SPANS = ("serving/queue", "serving/first_token", "serving/generate")
N_REQUESTS = 3


def rows_named(rows, name):
    return [r for r in rows if r[NAME] == name]


def children_of(rows, parent):
    return [r for r in rows if r[PARENT] == parent[SPAN]
            and r[TRACE] == parent[TRACE]]


@pytest.fixture(scope="module")
def served():
    """A paged tiny GPT behind an InferenceServer, three unsampled
    generate requests and one sampled one through it: the rows they left,
    the stats, the sampled request's root context."""
    from paddle_tpu.models import gpt as gpt_mod
    from paddle_tpu.models.generation import GPTGenerator
    rate = fluid.get_flags(["FLAGS_trace_sample_rate"])
    fluid.set_flags({"trace_sample_rate": 0.0})
    profiler.reset_profiler()
    cfg = gpt_mod.GPTConfig.tiny()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        gpt_mod.gpt_logits(cfg)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor().run(startup)
    gen = GPTGenerator(cfg, scope, max_len=32, bucket_min=8)
    server = serving.InferenceServer(generator=gen, decode_slots=2)
    server.start(serve_network=False)
    t0 = time.perf_counter()
    try:
        time.sleep(0.15)        # a few empty polls of the queue
        idle_rows = tracing.loop_spans(t0, time.perf_counter())
        reqs = [server.submit_generate(
            np.arange(1, 5 + i, dtype=np.int32), max_new_tokens=3)
            for i in range(N_REQUESTS)]
        for r in reqs:
            r.wait(timeout=300)
        root = tracing.new_trace()
        with tracing.ambient(root):
            sampled = server.submit_generate(
                np.arange(1, 6, dtype=np.int32), max_new_tokens=2)
        sampled.wait(timeout=300)
        stats = server.stats()
    finally:
        server.stop()
        fluid.set_flags(rate)
    rows = tracing.loop_spans(t0, time.perf_counter())
    yield {"rows": rows, "idle_rows": idle_rows, "stats": stats,
           "root": root, "requests": reqs, "active": profiler.is_profiling()}
    profiler.reset_profiler()


def test_nothing_needed_a_profiler_session_or_a_sampled_request(served):
    assert served["active"] is False
    assert all(r.trace is None for r in served["requests"])
    assert rows_named(served["rows"], "serving/round")


def test_an_empty_poll_of_the_queue_records_no_round(served):
    assert not [r for r in served["idle_rows"]
                if r[NAME].startswith("serving/")]


@pytest.mark.parametrize("name", REQUEST_SPANS)
def test_every_request_leaves_one_of_each_request_span(served, name):
    unsampled = [r for r in rows_named(served["rows"], name)
                 if r[TRACE].startswith("req:")]
    assert len(unsampled) == N_REQUESTS
    assert len({r[TRACE] for r in unsampled}) == N_REQUESTS
    assert all(r[PARENT] == "" for r in unsampled)
    assert all(r[ATTRS]["prompt_len"] >= 4 for r in unsampled)


def test_a_requests_three_spans_share_its_trace_id(served):
    by_trace = {}
    for r in served["rows"]:
        if r[NAME] in REQUEST_SPANS:
            by_trace.setdefault(r[TRACE], {})[r[NAME]] = r
    assert len(by_trace) == N_REQUESTS + 1
    for spans in by_trace.values():
        assert set(spans) == set(REQUEST_SPANS)
        q, f, g = (spans[n] for n in REQUEST_SPANS)
        # all three begin at put; the queue wait ends first, the reply last
        assert q[START] == pytest.approx(f[START], abs=1e-3)
        assert q[END] <= f[END] <= g[END]
        assert f[ATTRS]["new_tokens"] == 1
        assert g[ATTRS]["new_tokens"] >= 2


def test_a_sampled_request_hangs_under_the_clients_context(served):
    root = served["root"]
    mine = [r for r in served["rows"] if r[TRACE] == root.trace_id]
    names = {r[NAME] for r in mine}
    assert set(REQUEST_SPANS) <= names
    assert {"serving/prefill", "serving/decode"} <= names
    assert all(r[PARENT] == root.span_id for r in mine)


def test_every_stepped_round_links_step_dispatch_and_wait(served):
    rows = served["rows"]
    rounds = [r for r in rows_named(rows, "serving/round")
              if "step" in r[ATTRS]]
    assert len(rounds) >= 2
    assert [r[ATTRS]["step"] for r in rounds] == sorted(
        r[ATTRS]["step"] for r in rounds)
    ahead = 0
    for rnd in rounds:
        assert rnd[TRACE].startswith("loop:") and rnd[PARENT] == ""
        assert {"live", "blocks_in_use", "blocks_total"} <= set(rnd[ATTRS])
        kids = {k[NAME]: k for k in children_of(rows, rnd)}
        assert {"serving/prepare_step", "engine/step"} <= set(kids)
        step = kids["engine/step"]
        under = children_of(rows, step)
        decode = [k for k in under if k[ATTRS].get("stage") == "decode"]
        # the round sends its step and, where one was in flight (the
        # step ran ahead), waits for that one, reads and delivers it
        want = ["generator/dispatch"]
        if step[ATTRS]["ahead"]:
            ahead += 1
            want.append("generator/wait")
            assert "engine/fetch" in {k[NAME] for k in under}
            assert "serving/deliver" in kids
            assert rnd[ATTRS]["collected"] >= 1
        assert sorted(k[NAME] for k in decode) == want
        # no thread a step: the send and the wait are the loop's own
        assert all(k[TID] == step[TID] for k in decode)
    assert ahead >= 1
    # every step sent was read: by the round that sent the next one, or
    # by a round that sent none
    steps = rows_named(rows, "engine/step")
    sent = [s for s in steps if "generator/dispatch" in {
        k[NAME] for k in children_of(rows, s)}]
    read = [s for s in steps if "engine/fetch" in {
        k[NAME] for k in children_of(rows, s)}]
    assert len(sent) == len(read) == len(rounds)


def test_a_decode_step_says_what_the_paged_kernel_walked(served):
    """``engine/step`` carries the grid of one ``paged_attention_decode``
    call (a grid step a slot), the steps its rows' walks take (the
    kernel's own function of their positions) and the blocks the live
    rows hold up to their positions: against slots x blocks a row, the
    share of the table the kernel does not walk."""
    from paddle_tpu.flags import flag
    from paddle_tpu.kernels.paged_attention import blocks_per_step
    from paddle_tpu.models import gpt as gpt_mod
    import jax.numpy as jnp
    cfg = gpt_mod.GPTConfig.tiny()
    bs = int(flag("kv_block_size"))
    G = blocks_per_step(cfg.num_heads, bs, cfg.hidden_size // cfg.num_heads,
                        jnp.float32, -(-32 // bs))
    # the spans that sent a step (one that only reads the last step in
    # flight launches nothing)
    stepped = [s for s in rows_named(served["rows"], "engine/step")
               if "grid_steps" in s[ATTRS]]
    assert len(stepped) >= 2
    for step in stepped:
        rnd, = [r for r in served["rows"] if r[SPAN] == step[PARENT]]
        assert step[ATTRS]["grid_steps"] == 2          # the slots
        # prompts of 4 to 6 and 3 new tokens at most: with the default
        # block of 16 every live row is in its first block, one step
        assert bs < 16 or step[ATTRS]["live_blocks"] == rnd[ATTRS]["live"] \
            == step[ATTRS]["kernel_steps"]
        assert 1 <= step[ATTRS]["live_blocks"] <= rnd[ATTRS]["blocks_in_use"]
        assert -(-step[ATTRS]["live_blocks"] // G) \
            <= step[ATTRS]["kernel_steps"] <= step[ATTRS]["live_blocks"]


def test_children_lie_inside_their_parents(served):
    rows = [r for r in served["rows"] if r[NAME] not in REQUEST_SPANS
            and r[TRACE].startswith("loop:")]
    by_id = {r[SPAN]: r for r in rows}
    linked = 0
    for r in rows:
        parent = by_id.get(r[PARENT])
        if parent is not None:
            linked += 1
            assert parent[START] <= r[START] and r[END] <= parent[END], (
                r[NAME], parent[NAME])
    assert linked >= 10


def test_admission_leaves_its_phases_with_their_counts(served):
    rows = served["rows"]
    admits = rows_named(rows, "serving/admit")
    assert admits and sum(a[ATTRS]["rows"] for a in admits) == N_REQUESTS + 1
    for admit in admits:
        kids = {k[NAME]: k for k in children_of(rows, admit)}
        assert {"engine/pack", "pool/alloc", "generator/prefill",
                "generator/sample", "pool/scatter"} <= set(kids)
        assert kids["pool/scatter"][ATTRS]["blocks"] >= 1
        assert kids["pool/scatter"][ATTRS]["rows"] == admit[ATTRS]["rows"]
        # the round it ran in counts what it admitted
        rnd = next(r for r in rows_named(rows, "serving/round")
                   if r[SPAN] == admit[PARENT])
        assert rnd[ATTRS]["admitted"] == admit[ATTRS]["rows"]


@pytest.mark.parametrize("stage", ["queue", "first_token", "token", "total"])
def test_stats_carry_the_stage_for_generate_traffic(served, stage):
    stats = served["stats"]
    assert stats[f"{stage}_count"] >= N_REQUESTS
    assert stats[f"{stage}_max_ms"] > 0


def test_the_token_histogram_takes_the_step_spans_interval(served):
    """One observation a step read: from the last step's tokens to this
    step's, or from the step's own send where nothing was in flight."""
    rows = served["rows"]
    steps = sorted(rows_named(rows, "engine/step"), key=lambda r: r[START])
    unread, last_read, intervals = [], 0.0, []
    for s in steps:
        kids = {k[NAME] for k in children_of(rows, s)}
        if "generator/dispatch" in kids:
            unread.append(s[START])
        if "engine/fetch" in kids:
            intervals.append(s[END] - max(unread.pop(0), last_read))
            last_read = s[END]
    stats = served["stats"]
    assert stats["token_count"] == len(intervals) == stats["decode_steps"]
    assert stats["token_max_ms"] == pytest.approx(
        1e3 * max(intervals), abs=1e-3)


# ------------------------------------------------------------- the executor

def _mlp():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [-1, 8], "float32")
        loss = fluid.layers.mean(fluid.layers.fc(x, 4))
        fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss


@pytest.mark.parametrize("return_numpy,children", [
    (True, ["executor/prepare", "executor/dispatch", "executor/commit",
            "executor/fetch_wait"]),
    (False, ["executor/prepare", "executor/dispatch", "executor/commit"]),
])
def test_three_runs_leave_three_runs_with_their_phases(return_numpy,
                                                       children):
    main, startup, loss = _mlp()
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    t0 = time.perf_counter()
    for _ in range(3):
        exe.run(main, feed={"x": np.ones((2, 8), np.float32)}, scope=scope,
                fetch_list=[loss], return_numpy=return_numpy)
    rows = tracing.loop_spans(t0, time.perf_counter())
    runs = rows_named(rows, "executor/run")
    assert len(runs) == 3
    assert [r[ATTRS]["compiled"] for r in runs] == [True, False, False]
    steps = [r[ATTRS]["step_num"] for r in runs]
    assert steps == [steps[0], steps[0] + 1, steps[0] + 2]
    for run in runs:
        assert run[TRACE].startswith("exe:") and run[PARENT] == ""
        assert run[ATTRS]["program"] == main._uid
        kids = children_of(rows, run)
        assert [k[NAME] for k in kids] == children
        assert all(run[START] <= k[START] and k[END] <= run[END]
                   for k in kids)


def test_a_slab_of_steps_is_one_run_with_the_same_phases():
    main, startup, loss = _mlp()
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    t0 = time.perf_counter()
    exe.run_steps(main, feed=[{"x": np.ones((2, 8), np.float32)}] * 4,
                  scope=scope, fetch_list=[loss])
    rows = tracing.loop_spans(t0, time.perf_counter())
    run, = rows_named(rows, "executor/run")
    assert run[ATTRS]["steps"] == 4 and run[ATTRS]["compiled"] is True
    assert [k[NAME] for k in children_of(rows, run)] == [
        "executor/prepare", "executor/dispatch", "executor/commit",
        "executor/fetch_wait"]


# ------------------------------------------------------------ the recorder

def test_the_ring_rotates_and_counts_drops_at_the_cap(monkeypatch):
    profiler.reset_profiler()
    base = profiler.spans_dropped_total()
    monkeypatch.setattr(profiler, "_MAX_SPANS", 4)
    root = tracing.loop_root("loop:test")
    for i in range(7):
        with tracing.loop_span("unit/loop", root, i=i):
            pass
    rows = tracing.loop_spans(0.0, float("inf"))
    assert [r[ATTRS]["i"] for r in rows] == [3, 4, 5, 6]   # the newest
    assert profiler.spans_dropped() == 3
    assert profiler.spans_dropped_total() == base + 3
    monkeypatch.undo()
    profiler.reset_profiler()


def test_no_row_is_lost_or_doubled_under_threads(monkeypatch):
    """More writers than cores, a short switch interval, a cap that
    rotates: kept rows and counted drops add up to what was recorded, and
    every span id is handed out once."""
    import sys
    profiler.reset_profiler()
    monkeypatch.setattr(profiler, "_MAX_SPANS", 500)
    writers, each = 16, 200
    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def write(w):
            root = tracing.loop_root(f"loop:{w}")
            for i in range(each):
                with tracing.loop_span("unit/loop", root, i=i):
                    pass
        threads = [threading.Thread(target=write, args=(w,))
                   for w in range(writers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(before)
    rows = tracing.loop_spans(0.0, float("inf"))
    assert len(rows) == 500
    assert len(rows) + profiler.spans_dropped() == writers * each
    assert len({r[SPAN] for r in rows}) == len(rows)
    monkeypatch.undo()
    profiler.reset_profiler()


def test_loop_spans_rotate_under_a_profiler_session_too(monkeypatch):
    """A session keeps the first spans of its own events; the loop's
    ring keeps the newest, session or not."""
    profiler.reset_profiler()
    monkeypatch.setattr(profiler, "_MAX_SPANS", 2)
    profiler.start_profiler()
    try:
        for i in range(4):
            with tracing.loop_span("unit/loop", i=i):
                pass
    finally:
        monkeypatch.undo()
        profiler.stop_profiler(profile_path=None)
    assert [r[ATTRS]["i"] for r in tracing.loop_spans(0.0, float("inf"))
            ] == [2, 3]
    profiler.reset_profiler()


def test_the_parent_is_the_enclosing_span_or_the_one_given():
    profiler.reset_profiler()
    root = tracing.loop_root("loop:test")
    seen = {}
    with tracing.loop_span("outer", root) as outer:
        assert tracing.current_loop() is outer.ctx
        with tracing.loop_span("inner") as inner:
            pass

        def elsewhere():
            seen["ambient"] = tracing.current_loop()
            with tracing.loop_span("hopped", outer, n=1):
                pass
            with tracing.loop_span("lost"):
                pass

        worker = threading.Thread(target=elsewhere)
        worker.start()
        worker.join()
    assert tracing.current_loop() is None
    assert seen["ambient"] is None      # a thread-local would be lost here
    rows = {r[NAME]: r for r in tracing.loop_spans(0.0, float("inf"))}
    assert rows["outer"][PARENT] == "" and rows["outer"][TRACE] == "loop:test"
    assert rows["inner"][PARENT] == outer.ctx.span_id
    assert rows["hopped"][PARENT] == outer.ctx.span_id
    assert rows["hopped"][TID] != rows["outer"][TID]
    assert rows["lost"][PARENT] == "" and rows["lost"][TRACE] == "loop:-"
    assert inner.t0 <= inner.t1 and rows["inner"][START] == inner.t0
    profiler.reset_profiler()


def test_a_dropped_span_records_nothing_and_attrs_fill_inside():
    profiler.reset_profiler()
    with tracing.loop_span("kept", rows=2) as kept:
        kept.attrs["finished"] = 1
    with tracing.loop_span("dropped") as gone:
        gone.dropped = True
    rows = tracing.loop_spans(0.0, float("inf"))
    assert [r[NAME] for r in rows] == ["kept"]
    assert rows[0][ATTRS] == {"rows": 2, "finished": 1}
    profiler.reset_profiler()


def test_loop_spans_returns_what_overlaps_the_interval():
    profiler.reset_profiler()
    root = tracing.new_trace()
    tracing.record_child("early", 1.0, 2.0, root)
    tracing.record_child("straddles", 1.5, 3.5, root, {"k": 1})
    tracing.record_child("late", 4.0, 5.0, root)
    got = tracing.loop_spans(3.0, 3.8)
    assert [r[NAME] for r in got] == ["straddles"]
    assert all(len(r) == 8 for r in tracing.loop_spans(0.0, 9.0))
    assert tracing.loop_spans(0.0, 9.0)[0][ATTRS] == {}
    profiler.reset_profiler()


def test_timeline_renders_the_ring_with_attrs(tmp_path):
    import sys
    import os
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    import timeline
    profiler.reset_profiler()
    root = tracing.loop_root("loop:test")
    with tracing.loop_span("serving/round", root, step=7, live=2):
        with tracing.loop_span("engine/step"):
            pass
    spans = json.loads(json.dumps(tracing.loop_spans(0.0, float("inf"))))
    events = timeline.to_chrome_trace(spans)["traceEvents"]
    rnd = next(e for e in events if e.get("name") == "serving/round")
    assert rnd["args"]["step"] == 7 and rnd["args"]["live"] == 2
    assert rnd["args"]["trace_id"] == "loop:test"
    flows = [e for e in events if e.get("ph") in ("s", "f")]
    assert len(flows) == 2          # one arrow, round -> step
    profiler.reset_profiler()


def test_the_spans_are_annotations_in_a_profiler_trace(tmp_path):
    """Any ``jax.profiler`` trace of the process shows the same spans as
    ``pt/<name>`` events on the host plane, on the trace's clock."""
    import glob
    import jax
    from jax.profiler import ProfileData
    main, startup, loss = _mlp()
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    feed = {"x": np.ones((2, 8), np.float32)}
    exe.run(main, feed=feed, scope=scope, fetch_list=[loss])
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            exe.run(main, feed=feed, scope=scope, fetch_list=[loss])
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    names = [ev.name for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for ev in line.events
             if ev.name.startswith("pt/")]
    for phase in ("run", "prepare", "dispatch", "commit", "fetch_wait"):
        assert names.count(f"pt/executor/{phase}") == 2, names
