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
    """An idle server records its polls of the queue, 50 ms each, and
    neither a round nor a take around them."""
    idle = [r for r in served["idle_rows"] if r[NAME] != "process/gc"]
    assert idle and {r[NAME] for r in idle} == {"serving/poll"}
    assert len(idle) <= 4                           # 0.15 s of them
    assert all(r[END] - r[START] >= 0.04 for r in idle[:-1])


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


LEAVES = {"serving/take": {"taken", "fit_calls"}, "serving/poll": set(),
          "generator/signature": set(), "engine/feed": {"entries"},
          "engine/adopt": set(), "serving/deliver": {"finished"},
          "engine/release": set()}


@pytest.mark.parametrize("name", sorted(LEAVES))
def test_each_leaf_appears_with_its_attrs_inside_its_parent(served, name):
    rows = served["rows"]
    by_id = {(r[TRACE], r[SPAN]): r for r in rows}
    mine = rows_named(rows, name)
    assert mine
    for r in mine:
        assert r[TRACE].startswith("loop:")
        assert set(r[ATTRS]) == LEAVES[name], r
        parent = by_id.get((r[TRACE], r[PARENT]))
        if name == "serving/poll" and parent is None:
            continue            # the take of an empty poll is dropped
        assert parent[START] <= r[START] and r[END] <= parent[END]
    parents = {by_id[(r[TRACE], r[PARENT])][NAME] for r in mine
               if (r[TRACE], r[PARENT]) in by_id}
    assert parents <= {"serving/take": {"serving/round"},
                       "serving/poll": {"serving/take"},
                       "generator/signature": {
                           "engine/step", "generator/prefill",
                           "generator/sample"},
                       "engine/feed": {"engine/step"},
                       "engine/adopt": {"engine/step"},
                       "serving/deliver": {"serving/round",
                                           "serving/admit"},
                       "engine/release": {"serving/round",
                                          "serving/admit"}}[name]
    if name == "engine/feed":
        # the bank's vectors, the tables and the pool's arrays: one
        # count for every step of one model
        assert len({r[ATTRS]["entries"] for r in mine}) == 1
        assert mine[0][ATTRS]["entries"] >= 6


def test_a_take_counts_what_it_took_and_asked_the_engine(served):
    rows = served["rows"]
    takes = rows_named(rows, "serving/take")
    assert sum(t[ATTRS]["taken"] for t in takes) == N_REQUESTS + 1
    # prefill_fit is asked about each request after a round's first
    assert all(t[ATTRS]["fit_calls"] <= t[ATTRS]["taken"] for t in takes)
    for admit in rows_named(rows, "serving/admit"):
        take, = [t for t in takes if t[PARENT] == admit[PARENT]
                 and t[TRACE] == admit[TRACE]]
        assert take[END] <= admit[START]
        assert take[ATTRS]["taken"] == admit[ATTRS]["rows"]


def test_a_poll_is_recorded_only_while_the_bank_is_idle(served):
    """No request was in the bank (taken off the queue and not yet
    finished) at any instant of a poll."""
    rows = served["rows"]
    polls = rows_named(rows, "serving/poll")
    assert len(polls) >= N_REQUESTS
    taken = {r[TRACE]: r[END] for r in rows_named(rows, "serving/queue")}
    in_bank = [(taken[g[TRACE]], g[END])
               for g in rows_named(rows, "serving/generate")]
    assert len(in_bank) == N_REQUESTS + 1
    for poll in polls:
        assert not [(t0, t1) for t0, t1 in in_bank
                    if t0 < poll[END] and poll[START] < t1]


def thread_clock_tick():
    """The smallest step of ``time.thread_time()`` on this host: about a
    microsecond on most, 10 ms on one that charges a thread's CPU by the
    scheduler's tick (the chip's host, ``PERF.md`` 7.16)."""
    steps, last = [], time.thread_time()
    while len(steps) < 3:
        now = time.thread_time()
        if now != last:
            steps.append(now - last)
            last = now
    return min(steps)


@pytest.mark.parametrize("name", ["serving/round", "generator/wait"])
def test_cpu_seconds_ride_on_rounds_and_waits(served, name):
    """A span's ``cpu_s`` is its two readings of the thread's clock: a
    clock that ticks may charge a short span one tick more than its
    wall time, never more."""
    mine = [r for r in rows_named(served["rows"], name)
            if r[TRACE].startswith("loop:")]
    assert mine
    tick = thread_clock_tick()
    for r in mine:
        assert 0.0 <= r[ATTRS]["cpu_s"] <= r[END] - r[START] + tick + 1e-3


def test_a_stepped_round_names_each_phase_in_order(served):
    """Structural, no timing: under a round that sent a step, the loop
    takes requests, builds the feed, hashes its signature, sends it,
    adopts what it returns and, where a step was in flight, delivers."""
    rows = served["rows"]
    want = ["serving/take", "engine/feed", "generator/signature",
            "generator/dispatch", "engine/adopt", "serving/deliver"]
    checked = 0
    for rnd in rows_named(rows, "serving/round"):
        if "step" not in rnd[ATTRS] or "collected" not in rnd[ATTRS]:
            continue
        under, todo = [], [rnd]
        while todo:
            kids = children_of(rows, todo.pop())
            under += kids
            todo += kids
        step, = [k for k in under if k[NAME] == "engine/step"]
        seq = [k[NAME] for k in sorted(under, key=lambda k: k[START])
               if k[NAME] in want and (
                   k[NAME] in ("serving/take", "serving/deliver")
                   or k[START] >= step[START])]
        # the round's own take first, the step's delivery last
        assert seq[0] == "serving/take" and seq[-1] == "serving/deliver"
        picked = [seq[0]] + [n for n in seq[1:-1]
                             if n != "serving/deliver"] + [seq[-1]]
        it = iter(picked)
        assert all(name in it for name in want), picked
        checked += 1
    assert checked >= 1


def test_admission_leaves_its_phases_with_their_counts(served):
    rows = served["rows"]
    admits = rows_named(rows, "serving/admit")
    assert admits and sum(a[ATTRS]["rows"] for a in admits) == N_REQUESTS + 1
    for admit in admits:
        kids = {k[NAME]: k for k in children_of(rows, admit)}
        assert {"engine/pack", "pool/alloc", "generator/prefill",
                "engine/fetch", "serving/deliver"} <= set(kids)
        # the prefill, its pick and its scatter are one call
        assert not {"generator/sample", "pool/scatter"} & set(kids)
        prefill = kids["generator/prefill"]
        assert prefill[ATTRS]["rows"] == admit[ATTRS]["rows"]
        assert prefill[ATTRS]["fused"] == 1
        assert prefill[ATTRS]["cache_bytes"] >= 1
        sent = [k for k in children_of(rows, prefill)
                if k[NAME] == "generator/dispatch"]
        assert len(sent) == 1 and sent[0][ATTRS]["kind"].startswith(
            "prefill_")
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

@pytest.fixture
def quiet_gc():
    """No collection of its own starts during the test: a ``process/gc``
    row would take a place in a ring of a few rows."""
    import gc
    gc.disable()
    try:
        yield
    finally:
        gc.enable()


@pytest.mark.usefixtures("quiet_gc")
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


@pytest.mark.usefixtures("quiet_gc")
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


@pytest.mark.usefixtures("quiet_gc")
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


@pytest.mark.usefixtures("quiet_gc")
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


@pytest.mark.usefixtures("quiet_gc")
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


def test_a_collection_is_a_span_of_the_thread_it_stopped(tmp_path):
    """``gc.collect()`` inside a round: a ``process/gc`` child of the
    round with the generation and what it found, and a
    ``pt/process/gc`` event in a profiler trace of the process."""
    import gc
    import glob
    import jax
    from jax.profiler import ProfileData
    profiler.reset_profiler()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.loop_span("serving/round",
                               tracing.loop_root("loop:t")) as rnd:
            gc.collect()
    finally:
        jax.profiler.stop_trace()
    rows = tracing.loop_spans(rnd.t0, rnd.t1)
    full = [r for r in rows if r[NAME] == "process/gc"
            and r[ATTRS]["generation"] == 2]
    assert len(full) == 1
    r, = full
    assert r[PARENT] == rnd.ctx.span_id and r[TRACE] == "loop:t"
    assert r[TID] == threading.get_ident()
    assert rnd.t0 <= r[START] <= r[END] <= rnd.t1
    assert set(r[ATTRS]) == {"generation", "collected", "uncollectable"}
    assert r[ATTRS]["collected"] >= 0 and r[ATTRS]["uncollectable"] >= 0
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    names = [ev.name for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for ev in line.events]
    assert "pt/process/gc" in names and "pt/serving/round" in names
    # installed once: one hook in the interpreter's list
    assert gc.callbacks.count(tracing._gc_span) == 1
    profiler.reset_profiler()


@pytest.mark.usefixtures("quiet_gc")
@pytest.mark.parametrize("generation", [0, 1])
def test_only_older_generations_are_recorded(generation):
    """A young collection (generation 0, some 0.1 ms, hundreds a second
    under bulk allocation) leaves no row: rows of them would rotate the
    spans a reader wants out of the ring. One of generation 1 does."""
    import gc
    profiler.reset_profiler()
    t0 = time.perf_counter()
    with tracing.loop_span("serving/round", tracing.loop_root("loop:t")):
        gc.collect(generation)
    gcs = [r for r in tracing.loop_spans(t0, time.perf_counter())
           if r[NAME] == "process/gc"]
    assert [r[ATTRS]["generation"] for r in gcs] == \
        ([] if generation == 0 else [generation])
    assert not tracing._gc_open
    profiler.reset_profiler()


def test_a_collection_on_another_thread_is_outside_any_loop():
    import gc
    profiler.reset_profiler()
    t0 = time.perf_counter()
    worker = threading.Thread(target=gc.collect)
    worker.start()
    worker.join(timeout=60)
    assert not worker.is_alive()
    full = [r for r in tracing.loop_spans(t0, time.perf_counter())
            if r[NAME] == "process/gc" and r[ATTRS]["generation"] == 2]
    assert len(full) == 1 and full[0][TRACE] == "loop:-"
    assert full[0][PARENT] == "" and full[0][TID] != threading.get_ident()
    profiler.reset_profiler()


def test_collections_on_many_threads_lose_and_block_nothing(monkeypatch):
    """Writers that collect as they record, a reader of the ring between
    them, more threads than cores and a short switch interval: no
    deadlock on the ring's lock (a collection may start while its own
    thread holds it), no reader torn by a row appended mid-copy, every
    writer's rows kept, every collection closed with its counts."""
    import gc
    import sys
    profiler.reset_profiler()
    writers, each = 12, 150
    errors = []
    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def write(w):
            root = tracing.loop_root(f"loop:{w}")
            try:
                for i in range(each):
                    with tracing.loop_span("unit/loop", root, i=i):
                        if i % 10 == 0:
                            gc.collect(i % 3)
                        [object() for _ in range(50)]
            except Exception as exc:  # noqa: BLE001 — asserted below
                errors.append(exc)

        def read():
            try:
                for _ in range(200):
                    tracing.loop_spans(0.0, float("inf"))
            except Exception as exc:  # noqa: BLE001 — asserted below
                errors.append(exc)

        threads = [threading.Thread(target=write, args=(w,))
                   for w in range(writers)]
        threads += [threading.Thread(target=read) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(before)
    assert not errors, errors
    rows = tracing.loop_spans(0.0, float("inf"))
    unit = [r for r in rows if r[NAME] == "unit/loop"]
    assert len(unit) == writers * each and profiler.spans_dropped() == 0
    # a collection asked for while another is under way does nothing
    gcs = [r for r in rows if r[NAME] == "process/gc"]
    assert gcs
    assert all(set(r[ATTRS]) == {"generation", "collected",
                                 "uncollectable"} and r[START] <= r[END]
               for r in gcs)
    assert not tracing._gc_open
    profiler.reset_profiler()


@pytest.mark.usefixtures("quiet_gc")
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


@pytest.mark.usefixtures("quiet_gc")
@pytest.mark.parametrize("attrs", [{}, {"step": 7, "kind": "decode",
                                        "cpu_s": 0.01, "dropped": False}])
def test_a_ring_row_leaves_the_collectors_lists(attrs):
    """Once ``_FLAT_LAG`` newer rows came, a row holds its attrs flat, so
    the collector stops tracking it (a full ring no longer lengthens every
    full collection); what was added to its attrs after the block ended,
    before that, is kept; ``loop_spans`` hands them back as a dict."""
    import gc
    profiler.reset_profiler()
    with tracing.loop_span("unit/untracked", **attrs) as sp:
        pass
    sp.attrs["late"] = 1            # as admit() fills generator/prefill's
    for _ in range(profiler._FLAT_LAG):
        with tracing.loop_span("unit/newer"):
            pass
    row = profiler._spans[-profiler._FLAT_LAG - 1]
    assert row[NAME] == "unit/untracked"
    gc.collect()
    assert not gc.is_tracked(row)
    got = [r for r in tracing.loop_spans(sp.t0, sp.t1)
           if r[NAME] == "unit/untracked"]
    assert [r[ATTRS] for r in got] == [dict(attrs, late=1)]
    profiler.reset_profiler()


@pytest.mark.usefixtures("quiet_gc")
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
