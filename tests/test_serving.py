"""Serving runtime (paddle_tpu/serving): micro-batching correctness vs
unbatched reference outputs, executable-cache LRU behavior, admission
control (deadlines, backpressure, breaker load-shed), the wire-framed
InferenceServer end to end under concurrency, and a slow-marked soak."""
import os
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu import serving
from paddle_tpu.inference import AnalysisConfig, AnalysisPredictor
from paddle_tpu.serving import (Client, DeadlineExceededError,
                                ExecutableCache, InferenceServer, LRUCache,
                                MicroBatcher, Request, RequestQueue,
                                ServerOverloadedError, ServingEngine,
                                ServingStats, next_bucket)

RNG = np.random.default_rng(7)


def _save_mlp(tmp_path, name="mlp", in_dim=8, out_dim=4):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [-1, in_dim], dtype="float32")
        h = layers.fc(x, 16, act="relu")
        out = layers.fc(h, out_dim, act="softmax")
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        path = str(tmp_path / name)
        fluid.io.save_inference_model(path, ["x"], [out], exe,
                                      main_program=main)
    return path


# ---------------------------------------------------------------- LRU cache

def test_lru_cache_entry_cap_and_counters():
    c = LRUCache(max_entries=2)
    c.put("a", 1)
    c.put("b", 2)
    assert c.get("a") == 1              # a is now most-recent
    c.put("c", 3)                       # evicts b (LRU)
    assert c.get("b") is None
    assert c.get("a") == 1 and c.get("c") == 3
    st = c.stats()
    assert st["entries"] == 2
    assert st["evictions"] == 1
    assert st["hits"] == 3 and st["misses"] == 1


def test_lru_cache_byte_cap():
    evicted = []
    c = LRUCache(max_bytes=100, on_evict=lambda k, v: evicted.append(k))
    c.put("a", "A", nbytes=40)
    c.put("b", "B", nbytes=40)
    c.put("c", "C", nbytes=40)          # 120 > 100: evict a
    assert evicted == ["a"]
    assert c.nbytes == 80
    # an oversized entry evicts everything else but is itself kept
    c.put("huge", "H", nbytes=500)
    assert "huge" in c and len(c) == 1


def test_executable_cache_signature_roundtrip(tmp_path):
    cache = ExecutableCache(max_entries=8)
    feed = {"x": np.zeros((4, 8), np.float32)}
    sig = ExecutableCache.signature(feed)
    cache.put(sig, "exe", nbytes=128)
    path = str(tmp_path / "sigs.json")
    assert cache.record(path) == 1
    loaded = ExecutableCache.load_signatures(path)
    assert loaded == [{"x": ((4, 8), "float32")}]


# ----------------------------------------------------------- request queue

def test_queue_backpressure_and_breaker_shed():
    from paddle_tpu.resilience import CircuitBreaker
    stats = ServingStats()
    breaker = CircuitBreaker(endpoint="test-shed", failure_threshold=3,
                             reset_timeout=60.0)
    q = RequestQueue(max_depth=2, breaker=breaker, stats=stats)
    feeds = {"x": np.zeros((1, 4), np.float32)}
    q.put(Request(feeds))
    q.put(Request(feeds))
    # depth limit: refused fast, each refusal counts against the breaker
    for _ in range(3):
        with pytest.raises(ServerOverloadedError):
            q.put(Request(feeds))
    # breaker now open: shedding without touching the queue
    assert q.breaker.state == "open"
    with pytest.raises(ServerOverloadedError, match="load shedding"):
        q.put(Request(feeds))
    assert stats.counter("shed_overload") >= 4
    assert len(q) == 2


def test_queue_rejects_already_expired():
    q = RequestQueue(max_depth=8)
    req = Request({"x": np.zeros((1, 4), np.float32)}, deadline_ms=0.01)
    time.sleep(0.01)
    with pytest.raises(DeadlineExceededError):
        q.put(req)
    assert isinstance(req.error, DeadlineExceededError)


def test_request_validates_feeds():
    with pytest.raises(ValueError, match="no feeds"):
        Request({})
    with pytest.raises(ValueError, match="disagree"):
        Request({"a": np.zeros((2, 3)), "b": np.zeros((4, 3))})


def test_next_bucket():
    assert [next_bucket(n) for n in (1, 2, 3, 5, 8, 9)] == \
        [1, 2, 4, 8, 8, 16]


# ------------------------------------------------------------ microbatcher

def test_microbatcher_coalesces_and_respects_signature():
    batches = []
    q = RequestQueue(max_depth=64)
    mb = MicroBatcher(q, lambda reqs: (batches.append(list(reqs)),
                                       [r.set_result([]) for r in reqs]),
                      max_batch_size=8, batch_timeout_ms=40.0)
    reqs_a = [Request({"x": np.zeros((1, 4), np.float32)})
              for _ in range(3)]
    reqs_b = [Request({"x": np.zeros((1, 6), np.float32)})
              for _ in range(2)]
    for r in reqs_a + reqs_b:
        q.put(r)
    mb.start()
    for r in reqs_a + reqs_b:
        r.wait(timeout=5)
    mb.stop()
    # one batch per signature, none mixed
    assert len(batches) == 2
    sizes = sorted(len(b) for b in batches)
    assert sizes == [2, 3]
    for b in batches:
        assert len({r.example_sig for r in b}) == 1


def test_microbatcher_bounds_batches_under_deep_backlog():
    """A deep queue backlog must flush as a SEQUENCE of max_batch_size
    groups, never one oversized device batch (one compiled-shape
    universe, no surprise compiles at serve time)."""
    sizes = []
    q = RequestQueue(max_depth=256)
    mb = MicroBatcher(q, lambda reqs: (sizes.append(
        sum(r.rows for r in reqs)),
        [r.set_result([]) for r in reqs]),
        max_batch_size=8, batch_timeout_ms=1000.0)
    reqs = [Request({"x": np.zeros((1, 4), np.float32)})
            for _ in range(40)]
    for r in reqs:
        q.put(r)
    mb.start()
    for r in reqs:
        r.wait(timeout=10)
    mb.stop()
    assert sum(sizes) == 40
    assert max(sizes) <= 8, sizes
    assert len(sizes) == 5          # 40 rows / 8 = five full batches


def test_microbatcher_flushes_at_max_batch_without_waiting():
    batches = []
    q = RequestQueue(max_depth=64)
    mb = MicroBatcher(q, lambda reqs: (batches.append(len(reqs)),
                                       [r.set_result([]) for r in reqs]),
                      max_batch_size=4, batch_timeout_ms=10000.0)
    reqs = [Request({"x": np.zeros((1, 4), np.float32)})
            for _ in range(4)]
    for r in reqs:
        q.put(r)
    mb.start()
    t0 = time.monotonic()
    for r in reqs:
        r.wait(timeout=5)
    # flushed on size, NOT after the 10s timeout
    assert time.monotonic() - t0 < 5
    mb.stop()
    assert batches == [4]


# ------------------------------------------------- engine + batching math

def _assert_equal_to_a_few_ulp(got, want):
    """Served rows against the single-request Predictor run at ANOTHER
    batch size: XLA:CPU picks its codegen by batch size (the README's
    serving section bounds the difference at about one ulp), so the two
    agree to a few ulp and not bitwise. The ulp is that of the largest
    output: a softmax rounds at the scale of its largest terms, so its
    small probabilities carry the same absolute error. Where both sides
    run one batch shape the tests assert exact equality."""
    want = np.asarray(want)
    np.testing.assert_allclose(
        np.asarray(got), want, rtol=0,
        atol=4 * np.spacing(np.max(np.abs(want))))


def test_batched_results_bitwise_match_unbatched(tmp_path):
    """The acceptance property: rows executed in a padded batch are
    bitwise-identical to the same rows through the single-caller
    Predictor path at the same batch shape (exact by construction: one
    program, one shape), and within a few ulp of it at the requests'
    own shapes."""
    path = _save_mlp(tmp_path)
    pred = AnalysisPredictor(AnalysisConfig(path))
    engine = ServingEngine(path)
    xs = [RNG.standard_normal((r, 8)).astype(np.float32)
          for r in (1, 2, 1, 3)]
    padded = np.concatenate(xs + [np.zeros((1, 8), np.float32)])
    same_shape = pred.run([padded])[0]
    refs = [pred.run([x])[0] for x in xs]

    reqs = [Request({"x": x}) for x in xs]
    engine.execute(reqs)                 # 7 rows -> one padded batch of 8
    off = 0
    for req, x, ref in zip(reqs, xs, refs):
        got, = req.wait(timeout=10)
        np.testing.assert_array_equal(got, same_shape[off:off + len(x)])
        _assert_equal_to_a_few_ulp(got, ref)
        off += len(x)


def test_engine_cache_hit_and_eviction(tmp_path):
    path = _save_mlp(tmp_path)
    cache = ExecutableCache(max_entries=2, max_bytes=0)
    engine = ServingEngine(path, cache=cache)
    x = RNG.standard_normal((1, 8)).astype(np.float32)
    engine.run({"x": x})                 # miss + compile
    engine.run({"x": x})                 # hit
    st = cache.stats()
    assert st["hits"] == 1 and st["misses"] >= 1
    # three distinct signatures under a 2-entry cap: eviction
    engine.run({"x": np.zeros((2, 8), np.float32)})
    engine.run({"x": np.zeros((4, 8), np.float32)})
    st = cache.stats()
    assert st["entries"] <= 2
    assert st["evictions"] >= 1
    # evicted signature recompiles and still works
    out, = engine.run({"x": x})
    assert out.shape == (1, 4)


def test_engine_warmup_from_recorded_signatures(tmp_path):
    path = _save_mlp(tmp_path)
    engine = ServingEngine(path)
    engine.run({"x": np.zeros((2, 8), np.float32)})
    sig_path = engine.record_signatures()
    assert os.path.exists(os.path.join(path,
                                       serving.SIGNATURE_FILE))
    fresh = ServingEngine(path)
    n = fresh.warmup(batch_sizes=(1,), signature_file=sig_path)
    assert n == 2                        # bucket-1 spec + recorded (2, 8)
    before = fresh.cache.stats()
    fresh.run({"x": np.zeros((2, 8), np.float32)})
    after = fresh.cache.stats()
    assert after["hits"] == before["hits"] + 1   # warm — no new compile
    assert after["misses"] == before["misses"]


def test_feed_specs_recorded_on_save(tmp_path):
    import json
    path = _save_mlp(tmp_path)
    with open(os.path.join(path, "__model__")) as f:
        model = json.load(f)
    assert model["feed_specs"]["x"]["shape"] == [-1, 8]
    assert "float32" in model["feed_specs"]["x"]["dtype"]


# ------------------------------------------------------- deadlines / shed

def test_deadline_expires_in_queue(tmp_path, fault_points):
    path = _save_mlp(tmp_path)
    server = InferenceServer(path, max_batch_size=4,
                             batch_timeout_ms=1.0, queue_depth=64)
    server.start(serve_network=False)
    try:
        # slow the engine so follow-up requests sit in the queue long
        # enough to expire (callable fault: delay, don't raise)
        def slow(point, ctx):
            time.sleep(0.3)
            return None
        with fault_points.fault_injection("serving.execute", exc=slow,
                                          times=-1):
            x = RNG.standard_normal((1, 8)).astype(np.float32)
            first = server.submit({"x": x})          # occupies the engine
            time.sleep(0.1)          # first's batch flushed; engine busy
            late = server.submit({"x": x}, deadline_ms=50.0)
            with pytest.raises(DeadlineExceededError) as ei:
                late.wait(timeout=10)
            assert ei.value.deadline_ms == 50.0
            assert ei.value.waited_ms >= 50.0
            first.wait(timeout=10)                   # undamaged
        assert server.stats()["shed_deadline"] >= 1
    finally:
        server.stop()


def test_server_backpressure_overload(tmp_path, fault_points):
    path = _save_mlp(tmp_path)
    server = InferenceServer(path, max_batch_size=2,
                             batch_timeout_ms=1.0, queue_depth=2)
    server.start(serve_network=False)
    try:
        def slow(point, ctx):
            time.sleep(0.4)
            return None
        with fault_points.fault_injection("serving.execute", exc=slow,
                                          times=-1):
            x = RNG.standard_normal((1, 8)).astype(np.float32)
            admitted, refused = [], 0
            for _ in range(12):
                try:
                    admitted.append(server.submit({"x": x}))
                except ServerOverloadedError:
                    refused += 1
            assert refused >= 1
            for r in admitted:
                r.wait(timeout=30)
        assert server.stats()["shed_overload"] >= 1
    finally:
        server.stop()


# -------------------------------------------------------- executor cache

def test_executor_compile_cache_is_bounded():
    from paddle_tpu.flags import set_flags, get_flags
    old = get_flags("executor_cache_entries")["executor_cache_entries"]
    set_flags({"executor_cache_entries": 3})
    try:
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = layers.data("x", [-1, 4], dtype="float32")
            y = layers.reduce_sum(x)
        exe = fluid.Executor()
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            for batch in (1, 2, 3, 4, 5):    # 5 signatures, cap 3
                exe.run(main,
                        feed={"x": np.ones((batch, 4), np.float32)},
                        fetch_list=[y])
        st = exe.cache_stats()
        assert st["entries"] <= 3
        assert st["evictions"] >= 2
        assert st["max_entries"] == 3
    finally:
        set_flags({"executor_cache_entries": old})


def test_predictor_exposes_cache_stats(tmp_path):
    path = _save_mlp(tmp_path)
    pred = AnalysisPredictor(AnalysisConfig(path))
    x = RNG.standard_normal((2, 8)).astype(np.float32)
    pred.run([x])
    pred.run([x])
    st = pred.cache_stats()
    assert st["entries"] == 1 and st["hits"] == 1


# ------------------------------------------------------------- wire e2e

def test_e2e_concurrent_clients_over_wire(tmp_path):
    """Acceptance: >= 32 concurrent requests through InferenceServer over
    the wire framing; (a) results match single-request Predictor.run to
    a few ulp (which requests share a batch, and so its size, is up to
    timing), (b) observed mean batch size > 1, (c) ExecutableCache
    reports >= 1 hit and respects capacity under eviction pressure."""
    path = _save_mlp(tmp_path)
    pred = AnalysisPredictor(AnalysisConfig(path))
    server = InferenceServer(path, max_batch_size=8,
                             batch_timeout_ms=60.0, queue_depth=256,
                             cache_entries=2)
    server.start()
    n = 36
    rows = [1] * 30 + [2] * 3 + [9] * 3
    xs = [RNG.standard_normal((r, 8)).astype(np.float32) for r in rows]
    refs = [pred.run([x])[0] for x in xs]
    results = [None] * n
    errors = []

    def worker(i):
        try:
            with Client(server.endpoint) as c:
                results[i] = c.infer({"x": xs[i]})[0]
        except Exception as e:  # noqa: BLE001
            errors.append((i, e))

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    try:
        assert not errors, errors[:3]
        for got, want in zip(results, refs):
            _assert_equal_to_a_few_ulp(got, want)        # (a)
        st = server.stats()
        assert st["requests_completed"] == n
        assert st["mean_batch_size"] > 1.0, st           # (b)

        # serial probes through the wire add deterministic eviction
        # pressure (buckets 1, 1 again, 4) on top of the storm's 8/16
        # buckets: the repeat is a guaranteed hit, the third signature
        # guarantees eviction under the 2-entry cap
        with Client(server.endpoint) as c:
            for r in (1, 1, 3):
                x = RNG.standard_normal((r, 8)).astype(np.float32)
                got, = c.infer({"x": x})
                _assert_equal_to_a_few_ulp(got, pred.run([x])[0])
        st = server.stats()
        assert st["cache_hits"] >= 1, st                 # (c) hits
        assert st["cache_entries"] <= 2, st              # (c) capacity
        assert st["cache_evictions"] >= 1, st
    finally:
        server.stop()


def test_wire_stats_and_ping(tmp_path):
    path = _save_mlp(tmp_path)
    server = InferenceServer(path, batch_timeout_ms=1.0).start()
    try:
        with Client(server.endpoint) as c:
            assert c.ping()
            c.infer({"x": np.zeros((1, 8), np.float32)})
            st = c.stats()
            assert st["requests_completed"] == 1
            assert st["batches"] == 1
    finally:
        server.stop()


def test_wire_bad_request_and_deadline_reply(tmp_path):
    path = _save_mlp(tmp_path)
    server = InferenceServer(path, batch_timeout_ms=1.0).start()
    try:
        with Client(server.endpoint) as c:
            with pytest.raises(RuntimeError, match="missing feeds"):
                c.infer({"wrong_name": np.zeros((1, 8), np.float32)})
            # an already-expired deadline comes back as the typed error
            with pytest.raises(DeadlineExceededError):
                c.infer({"x": np.zeros((1, 8), np.float32)},
                        deadline_ms=1e-9)
    finally:
        server.stop()


def test_profiler_sees_serving_stages(tmp_path):
    from paddle_tpu import profiler
    path = _save_mlp(tmp_path)
    server = InferenceServer(path, batch_timeout_ms=1.0)
    server.start(serve_network=False)
    try:
        profiler.reset_profiler()
        profiler.start_profiler("All")
        server.infer({"x": np.zeros((1, 8), np.float32)}, timeout=30)
        rows = {r[0] for r in profiler.summary()}
        profiler.stop_profiler(profile_path=None)
        assert "serving/queue" in rows and "serving/execute" in rows
    finally:
        server.stop()
        profiler.reset_profiler()


# ------------------------------------------------------------------ soak

@pytest.mark.slow
def test_soak_mixed_traffic(tmp_path):
    """Sustained mixed-shape traffic with deadlines and bursts: every
    request either completes correctly or fails with a TYPED serving
    error; counters reconcile; the cache stays within caps."""
    path = _save_mlp(tmp_path)
    pred = AnalysisPredictor(AnalysisConfig(path))
    server = InferenceServer(path, max_batch_size=8,
                             batch_timeout_ms=5.0, queue_depth=64,
                             cache_entries=4)
    server.start()
    stop_at = time.monotonic() + 8.0
    ok, typed_fail, wrong = [0], [0], []
    lock = threading.Lock()

    def worker(wid):
        lrng = np.random.default_rng(wid)
        my_pred = pred.clone()           # clone-per-thread reference
        with Client(server.endpoint) as c:
            while time.monotonic() < stop_at:
                r = int(lrng.choice([1, 1, 1, 2, 4]))
                x = lrng.standard_normal((r, 8)).astype(np.float32)
                try:
                    out, = c.infer({"x": x}, deadline_ms=2000.0)
                    want, = my_pred.run([x])
                    if not np.array_equal(out, want):
                        with lock:
                            wrong.append(wid)
                    with lock:
                        ok[0] += 1
                except (DeadlineExceededError, ServerOverloadedError):
                    with lock:
                        typed_fail[0] += 1

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(12)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    try:
        assert not wrong, f"mismatched results from workers {wrong[:5]}"
        assert ok[0] > 50, (ok[0], typed_fail[0])
        st = server.stats()
        assert st["requests_completed"] >= ok[0]
        assert st["cache_entries"] <= 4
        assert st["mean_batch_size"] >= 1.0
        # admission ledger: everything admitted is accounted for
        assert st["requests_admitted"] >= st["requests_completed"]
    finally:
        server.stop()
