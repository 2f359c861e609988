"""Overload-resilient fleet (this PR's tentpole): retry budgets
(process-global token bucket consulted by retry_call / client
reconnect+hedging / router failover+hedging), priority admission
(interactive/batch/best_effort classes, lowest sheds first,
deadline-expired queue entries evicted typed), deadline propagation
(remaining budget across client -> router -> replica hops), the
brownout degradation ladder, the telemetry-driven Autoscaler
(hysteresis + cooldown, drain-aware scale-down), and the 3x-overload
chaos acceptance scenario (bounded interactive p99, typed errors only,
no leaked KV blocks, autoscaler up-then-drained)."""
import socket
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import resilience, serving
from paddle_tpu.distributed.wire import recv_frame, send_frame
from paddle_tpu.models import gpt
from paddle_tpu.models.generation import GPTGenerator
from paddle_tpu.resilience import (RetryBudget, RetryBudgetExhausted,
                                   RpcDeadlineError, chaos, retry_call)
from paddle_tpu.serving import (BrownoutController, Client,
                                DeadlineExceededError, GenerationRequest,
                                InferenceServer, RequestQueue,
                                ServerOverloadedError, ServingError,
                                fleet)
from paddle_tpu.serving.fleet.registry import Replica

RNG = np.random.default_rng(29)

TYPED_ERRORS = (ServingError, RpcDeadlineError, ConnectionError,
                TimeoutError)


@pytest.fixture(scope="module")
def tiny_gpt():
    cfg = gpt.GPTConfig.tiny()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        gpt.gpt_logits(cfg)
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    return cfg, scope


def _mksrv(tiny_gpt, name, **kw):
    cfg, scope = tiny_gpt
    kw.setdefault("decode_slots", 2)
    gen = GPTGenerator(cfg, scope, max_len=48, bucket_min=8)
    return InferenceServer(generator=gen, kv_pool_name=name,
                           **kw).start()


def _prompt(cfg, n=4, seed=3):
    rng = np.random.default_rng(seed)
    return rng.integers(1, cfg.vocab_size, n).astype(np.int32)


def _wait_until(cond, timeout=10.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return cond()


def _use_budget(budget):
    """Install ``budget`` as THE process retry budget for this test
    (the autouse conftest fixture resets it afterwards)."""
    resilience._default_budget = budget
    return budget


# ---------------------------------------------------------- retry budget

def test_retry_budget_token_bucket():
    b = RetryBudget(ratio=0.5, min_reserve=2, window_s=1000,
                    what_reserve=0)
    assert b.try_acquire() and b.try_acquire()      # the reserve
    assert not b.try_acquire()                      # dry
    for _ in range(4):
        b.record_request()                          # 4 * 0.5 = 2 tokens
    assert b.try_acquire() and b.try_acquire()
    assert not b.try_acquire()
    snap = b.snapshot()
    assert snap["granted"] == 4 and snap["denied"] == 2
    with pytest.raises(RetryBudgetExhausted):
        b.acquire(what="unit")
    # time-based reserve refill keeps isolated failures retryable
    b2 = RetryBudget(ratio=0.1, min_reserve=10, window_s=0.1)
    for _ in range(12):
        b2.try_acquire()
    time.sleep(0.15)
    assert b2.try_acquire()
    # ratio < 0 disables the budget entirely
    b3 = RetryBudget(ratio=-1.0, min_reserve=0)
    assert all(b3.try_acquire() for _ in range(100))
    # per-consumer emergency reserve: one subsystem draining the
    # shared pool must not STARVE another's isolated recovery retry —
    # each distinct `what` holds its own small bounded reserve
    b4 = RetryBudget(ratio=0.0, min_reserve=0.0, window_s=10,
                     what_reserve=1.0)
    assert b4.try_acquire(what="serving-storm")      # own reserve
    assert not b4.try_acquire(what="serving-storm")  # then bounded
    assert b4.try_acquire(what="ps-recovery")        # not starved
    assert not b4.try_acquire(what="ps-recovery")


def test_retry_call_consults_budget():
    """A failing call under a dry budget raises the typed
    RetryBudgetExhausted (chained) instead of sleeping into another
    attempt — and an outer retry_call never retries it."""
    calls = [0]

    def boom():
        calls[0] += 1
        raise ConnectionError("down")

    dry = RetryBudget(ratio=0.0, min_reserve=0.0, window_s=0)
    with pytest.raises(RetryBudgetExhausted) as ei:
        retry_call(boom, deadline=5.0, base_backoff=0.001, budget=dry)
    assert calls[0] == 1                  # no second attempt
    assert isinstance(ei.value.__cause__, ConnectionError)
    # RetryBudgetExhausted is ConnectionError-shaped but must NOT be
    # retried by an enclosing retry_call (that would be amplification)
    outer_calls = [0]

    def outer():
        outer_calls[0] += 1
        retry_call(boom, deadline=5.0, base_backoff=0.001, budget=dry)

    with pytest.raises(RetryBudgetExhausted):
        retry_call(outer, deadline=5.0, base_backoff=0.001)
    assert outer_calls[0] == 1
    # with the budget healthy the retry discipline is unchanged
    ok = RetryBudget(ratio=1.0, min_reserve=10)
    calls[0] = 0
    with pytest.raises(RpcDeadlineError):
        retry_call(boom, deadline=0.05, base_backoff=0.001,
                   retries=3, budget=ok)
    assert calls[0] == 4


# ----------------------------------------------------- priority admission

def test_queue_serves_higher_class_first_and_sheds_lowest():
    q = RequestQueue(max_depth=3)
    be = GenerationRequest([1], priority="best_effort")
    ba = GenerationRequest([1], priority="batch")
    ia = GenerationRequest([1])                       # interactive
    q.put(be)
    q.put(ba)
    q.put(ia)
    # full queue + a new interactive arrival: the youngest lowest-class
    # entry sheds typed, the arrival is admitted
    ia2 = GenerationRequest([1], priority="interactive")
    q.put(ia2)
    assert be.done()
    assert isinstance(be.error, ServerOverloadedError)
    assert q.priority_evictions == 1
    # service order: interactive FIFO first, then batch
    assert q.get(timeout=0) is ia
    assert q.get(timeout=0) is ia2
    assert q.get(timeout=0) is ba
    # a full queue with no lower-class victim refuses the arrival
    q2 = RequestQueue(max_depth=1)
    q2.put(GenerationRequest([1]))
    with pytest.raises(ServerOverloadedError):
        q2.put(GenerationRequest([1], priority="batch"))
    with pytest.raises(ValueError):
        GenerationRequest([1], priority="urgent")


def test_shrunken_admission_cap_refuses_instead_of_evicting():
    """A per-call depth cap (the brownout ladder halving a degraded
    class's admission) must refuse THAT request — only a genuinely
    full queue may evict lower-class work it already admitted."""
    q = RequestQueue(max_depth=8)
    be = GenerationRequest([1], priority="best_effort")
    q.put(be)
    for _ in range(10):
        with pytest.raises(ServerOverloadedError):
            q.put(GenerationRequest([1], priority="batch"),
                  max_depth=1)
    assert not be.done()            # admitted work untouched
    assert q.priority_evictions == 0
    # cap-caused refusals are not the server's fault: the load-shed
    # breaker must stay closed, or a batch burst under brownout would
    # shed the interactive traffic the ladder protects
    assert q.breaker.state == "closed"


def test_prefill_export_hop_not_counted_as_class_completion(tiny_gpt):
    """A disaggregated generate is prefill-export + decode: only the
    decode half may count toward serving_class_completed_total /
    serving_class_latency_ms, or fleet goodput doubles and the gated
    per-class p99 dilutes with half-request latencies."""
    from paddle_tpu.serving.metrics import _CLASS_DONE
    cfg, _scope = tiny_gpt
    srv = _mksrv(tiny_gpt, "export_count")
    try:
        with Client(srv.endpoint) as c:
            before = _CLASS_DONE.value(labels=("interactive",))
            kv = c.prefill(_prompt(cfg), max_new_tokens=4)
            assert "first_token" in kv
            assert _CLASS_DONE.value(labels=("interactive",)) == before
    finally:
        srv.stop()


def test_queue_evicts_expired_entries_typed():
    q = RequestQueue(max_depth=8)
    doomed = GenerationRequest([1], deadline_ms=15.0)
    live = GenerationRequest([1])
    q.put(doomed)
    q.put(live)
    time.sleep(0.04)
    # the expired entry never reaches the batcher; it fails typed and
    # is counted; the live one is served
    assert q.get(timeout=0) is live
    assert doomed.done()
    assert isinstance(doomed.error, DeadlineExceededError)
    assert q.expired_in_queue == 1
    # an expired entry must not hold a slot against fresh admission
    q3 = RequestQueue(max_depth=1)
    q3.put(GenerationRequest([1], deadline_ms=5.0))
    time.sleep(0.02)
    fresh = GenerationRequest([1])
    q3.put(fresh)                 # sweep frees the slot, no eviction
    assert q3.expired_in_queue == 1
    assert q3.get(timeout=0) is fresh


def test_queue_get_leaves_a_refused_head_where_it_was():
    """``get(accept=)`` asks about the request it would pop: refused, the
    request stays first of its class (so drain, close and the deadline
    sweep go on seeing it) and nothing behind it is served in its
    place; an expired entry in front of it is still swept."""
    q = RequestQueue(max_depth=8)
    doomed = GenerationRequest([1], deadline_ms=15.0)
    big = GenerationRequest([1, 2, 3])
    small = GenerationRequest([1])
    later = GenerationRequest([1], priority="batch")
    for r in (doomed, big, small, later):
        q.put(r)
    time.sleep(0.04)
    asked = []
    assert q.get(timeout=0, accept=lambda r: asked.append(r)) is None
    assert asked == [big] and len(q) == 3 and doomed.done()
    assert q.get(timeout=0, accept=lambda r: True) is big
    assert q.get(timeout=0) is small and q.get(timeout=0) is later
    held = GenerationRequest([1])
    q.put(held)
    assert q.get(timeout=0, accept=lambda r: False) is None
    q.close()                       # a refused request is still queued
    assert isinstance(held.error, serving.ServerShutdownError)


# -------------------------------------------------- deadline propagation

def test_client_rejects_spent_budget_before_the_wire(tiny_gpt):
    srv = _mksrv(tiny_gpt, "ddl_door")
    cfg, _scope = tiny_gpt
    try:
        with Client(srv.endpoint) as c:
            with pytest.raises(DeadlineExceededError):
                c.generate(_prompt(cfg), max_new_tokens=2,
                           deadline_ms=-1.0)
        # the replica door: an arrived-expired request is rejected at
        # ADMISSION (typed, shed_deadline), never reaching prefill
        before = srv.stats_sink.counter("shed_deadline")
        with pytest.raises(DeadlineExceededError):
            srv.submit_generate(_prompt(cfg), max_new_tokens=2,
                                deadline_ms=-5.0)
        assert srv.stats_sink.counter("shed_deadline") == before + 1
        assert srv.stats_sink.counter("generate_requests") == 0
    finally:
        srv.stop()


def test_router_forwards_remaining_deadline_minus_queue_time():
    """The router's hop carries budget MINUS its own elapsed time, and
    a spent budget returns typed expiry without touching a replica."""
    captured = {}
    lst = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    lst.bind(("127.0.0.1", 0))
    lst.listen(1)
    ep = f"127.0.0.1:{lst.getsockname()[1]}"

    def fake_replica():
        conn, _ = lst.accept()
        msg = recv_frame(conn, None)
        captured.update(msg)
        send_frame(conn, {"ok": True,
                          "tokens": np.asarray([1], np.int32),
                          "generated": 1}, None)
        conn.close()

    t = threading.Thread(target=fake_replica, daemon=True)
    t.start()
    router = fleet.Router([])
    rep = Replica(ep)
    rep.state = "healthy"
    rep.last_health = {"state": "serving"}
    router.registry._reps[ep] = rep
    try:
        # 100ms budget of which ~60ms is already spent router-side
        msg = {"op": "generate", "tokens": [1, 2], "rid": "r1",
               "deadline_ms": 100.0}
        reply, got_ep = router._dispatch(
            msg, ("both",), 5.0,
            budget=(100.0, time.monotonic() - 0.06))
        assert reply.get("ok") and got_ep == ep
        assert 0 < captured["deadline_ms"] <= 45.0
        # spent budget: typed expiry, no dispatch
        reply2, ep2 = router._dispatch(
            {"op": "generate", "tokens": [1], "rid": "r2",
             "deadline_ms": 50.0},
            ("both",), 5.0, budget=(50.0, time.monotonic() - 1.0))
        assert ep2 is None
        assert reply2["etype"] == "DeadlineExceeded"
        assert router.stats()["router_deadline_expired_in_router"] == 1
    finally:
        router.stop()
        t.join(timeout=2)
        lst.close()


# ------------------------------------------------- failover/hedge budget

def test_router_failover_respects_retry_budget():
    """With the budget dry, a transport death does NOT walk the
    rotation: the dispatch returns a typed Overloaded shed (fast)
    instead of hammering the next replica."""
    _use_budget(RetryBudget(ratio=0.0, min_reserve=0.0, window_s=0))
    router = fleet.Router([])
    for i, port in enumerate((1, 2)):     # nothing listens there
        ep = f"127.0.0.1:{port}"
        rep = Replica(ep)
        rep.state = "healthy"
        rep.last_health = {"state": "serving"}
        router.registry._reps[ep] = rep
    try:
        reply, ep = router._dispatch(
            {"op": "generate", "tokens": [1], "rid": "r"},
            ("both",), 0.5)
        assert ep is None
        assert reply["etype"] == "Overloaded"
        assert "retry budget" in reply["error"]
        st = router.stats()
        assert st["router_failovers_suppressed"] == 1
        assert st["router_failovers"] == 1      # the observed death
    finally:
        router.stop()


@pytest.mark.slow
def test_hedge_volume_respects_budget_under_saturation(tiny_gpt,
                                                       fault_points):
    """Satellite regression for the retry-storm path: under sustained
    stalls a hedging client fires twins only while the budget grants
    them; once dry, hedges are SUPPRESSED and counted in
    hedge_stats() — hedge volume is bounded by the budget, not by the
    stall rate."""
    cfg, _scope = tiny_gpt
    srv = _mksrv(tiny_gpt, "hedge_budget")
    p = _prompt(cfg)
    try:
        with Client(srv.endpoint) as warmc:
            warmc.generate(p, max_new_tokens=2)     # compile off-path
        # 3 hedge tokens total, no refill: the 4th+ stalled exchange
        # cannot hedge
        _use_budget(RetryBudget(ratio=0.0, min_reserve=3.0, window_s=0))
        hedger = Client(srv.endpoint, hedge_ms=25.0)
        try:
            with fault_points.fault_injection(
                    "serving.handle",
                    exc=lambda pt, ctx: time.sleep(0.2), times=-1):
                for _ in range(6):
                    try:
                        hedger._call_hedged({"op": "ping"}, 0.025)
                    except TYPED_ERRORS:
                        pass
            hs = hedger.hedge_stats()
            assert hs["hedges"] <= 3, hs
            assert hs["budget_suppressed"] >= 2, hs
            assert hs["hedges"] + hs["budget_suppressed"] >= 5, hs
        finally:
            hedger.close()
    finally:
        srv.stop()


def test_router_hedging_policy_and_budget(tiny_gpt, fault_points):
    """Router hedging under saturation: non-interactive requests never
    hedge, a brownout-active fleet never hedges, and a dry budget
    suppresses hedge twins (counted) while sustained failover pressure
    stays bounded."""
    cfg, _scope = tiny_gpt
    srv = _mksrv(tiny_gpt, "router_hedge")
    p = _prompt(cfg)
    with Client(srv.endpoint) as c:
        c.generate(p, max_new_tokens=2)             # compile off-path
    router = fleet.Router([srv.endpoint], hedge_ms=100.0,
                          probe_interval_s=0.05).start()
    try:
        _use_budget(RetryBudget(ratio=0.0, min_reserve=0.0, window_s=0))
        with fault_points.fault_injection(
                "serving.handle",
                exc=lambda pt, ctx: time.sleep(0.4), times=-1):
            for prio in (None, "batch"):
                out = router.generate(p, max_new_tokens=2,
                                      priority=prio)
                assert out.size >= 1
        st = router.stats()
        assert st["router_hedges"] == 0
        # interactive wanted a hedge (stall > 100ms) but the budget was
        # dry; batch never consults the budget (policy: no hedge)
        assert st["router_hedges_suppressed"] == 1, st
    finally:
        router.stop()
        srv.stop()


# ------------------------------------------------------------- brownout

def test_brownout_ladder_and_symmetric_recovery():
    breached = [0]
    bo = BrownoutController(lambda: breached[0], scope="unit",
                            enabled=True, escalate_s=0.08,
                            recover_s=0.05, batch_token_cap=4)
    assert bo.level() == 0
    # one breached rule -> level 1: best_effort sheds, batch capped,
    # interactive untouched
    breached[0] = 1
    assert bo.level() == 1
    shed, mnt, cap = bo.admission(2, max_new_tokens=32, queue_depth=16)
    assert shed
    shed, mnt, cap = bo.admission(1, max_new_tokens=32, queue_depth=16)
    assert not shed and mnt == 4 and cap == 8
    shed, mnt, cap = bo.admission(0, max_new_tokens=32, queue_depth=16)
    assert not shed and mnt == 32 and cap is None
    # a breach level 1 didn't clear escalates -> level 2: batch sheds
    time.sleep(0.1)
    assert bo.level() == 2
    shed, _mnt, _cap = bo.admission(1, max_new_tokens=32)
    assert shed
    shed, _mnt, _cap = bo.admission(0, max_new_tokens=32)
    assert not shed                       # interactive degrades LAST
    # >= 2 rules jumps straight to 2
    bo2 = BrownoutController(lambda: 2, scope="unit2", enabled=True)
    assert bo2.level() == 2
    # symmetric recovery: one level per recover_s of sustained health
    breached[0] = 0
    assert bo.level() == 2
    time.sleep(0.06)
    assert bo.level() == 1
    time.sleep(0.06)
    assert bo.level() == 0
    assert bo.snapshot()["transitions"] >= 4
    # disabled controller never degrades
    bo3 = BrownoutController(lambda: 5, scope="unit3", enabled=False)
    assert bo3.level() == 0


def test_server_brownout_degrades_lowest_class_first(tiny_gpt):
    cfg, _scope = tiny_gpt
    srv = _mksrv(tiny_gpt, "brownout_srv")
    p = _prompt(cfg)
    try:
        # force the ladder: a fake monitor reporting one breached rule
        class _FakeMon:
            def breached(self):
                return ["intertoken_p99_ms"]

            def stop(self):
                pass

        real = srv.slo_monitor
        if real is not None:
            real.stop()
        srv.slo_monitor = _FakeMon()
        srv.brownout.recover_s = 0.05
        assert srv.brownout.level() == 1
        assert srv.health()["brownout_level"] == 1
        with pytest.raises(ServerOverloadedError) as ei:
            srv.submit_generate(p, max_new_tokens=4,
                                priority="best_effort")
        assert "brownout" in str(ei.value)
        # batch is served but its token budget is CAPPED
        out = srv.generate(p, max_new_tokens=32, priority="batch",
                           timeout=30)
        assert out.size <= srv.brownout.batch_token_cap
        # interactive is untouched
        out = srv.generate(p, max_new_tokens=6, timeout=30)
        assert out.size <= 6
        # recovery: breaches clear -> admission reopens
        srv.slo_monitor = None
        assert _wait_until(lambda: srv.brownout.level() == 0,
                           timeout=2.0)
        out = srv.generate(p, max_new_tokens=3,
                           priority="best_effort", timeout=30)
        assert out.size <= 3
    finally:
        srv.stop()


# ------------------------------------------------------------ autoscaler

class _FakeReplicaServer:
    _n = 0

    def __init__(self):
        _FakeReplicaServer._n += 1
        self.endpoint = f"127.0.0.1:{20000 + _FakeReplicaServer._n}"
        self.drained = False

    def drain(self, timeout=None):
        self.drained = True
        return {"drained": True, "remaining": 0}


def _mark(router, ep, queue_ratio=0.0, kv=0.0, breached=0, cap=16):
    rep = router.registry.get(ep)
    rep.state = "healthy"
    rep.probe_failures = 0
    rep.last_health = {
        "state": "serving", "queue_capacity": cap,
        "decode_queue_depth": int(queue_ratio * cap),
        "kvpool_occupancy": kv, "slo_breached": breached,
    }


def test_autoscaler_hysteresis_cooldown_and_drain():
    spawned = []

    def factory():
        srv = _FakeReplicaServer()
        spawned.append(srv)
        return srv

    router = fleet.Router([])
    scaler = fleet.Autoscaler(router, factory, min_replicas=1,
                              max_replicas=3, cooldown_s=0.05,
                              window=2, up_queue_ratio=0.5,
                              down_queue_ratio=0.1)
    try:
        # tick on an empty rotation grows to the min floor
        scaler.tick()
        assert len(spawned) == 1
        ep0 = spawned[0].endpoint
        _mark(router, ep0, queue_ratio=0.9)
        # hysteresis: ONE overloaded sample is not a decision
        scaler.tick()
        assert len(spawned) == 1
        time.sleep(0.06)                      # past cooldown
        scaler.tick()                         # window full + uniform
        assert len(spawned) == 2
        ep1 = spawned[1].endpoint
        # cooldown: an immediately-following overloaded window waits
        _mark(router, ep0, queue_ratio=0.9)
        _mark(router, ep1, queue_ratio=0.9)
        scaler.tick()
        scaler.tick()
        assert len(spawned) == 2
        # mixed window never scales (all samples must agree)
        _mark(router, ep0, queue_ratio=0.9)
        _mark(router, ep1, queue_ratio=0.0)
        time.sleep(0.06)
        scaler.tick()
        _mark(router, ep0, queue_ratio=0.0)
        _mark(router, ep1, queue_ratio=0.9)
        scaler.tick()
        # (mean 0.45 < up threshold both ticks — no event)
        assert len(spawned) == 2
        # SLO breach alone is a scale-up signal
        for e in (ep0, ep1):
            _mark(router, e, breached=1)
        time.sleep(0.06)
        scaler.tick()
        scaler.tick()
        assert len(spawned) == 3
        # never past max_replicas
        for s in spawned:
            _mark(router, s.endpoint, breached=1)
        time.sleep(0.06)
        scaler.tick()
        scaler.tick()
        assert len(spawned) == 3
        # idle window drains back — one replica per cooldown, victim
        # retired through the drain-aware path, never below min
        for s in spawned:
            _mark(router, s.endpoint, queue_ratio=0.0)
        down = 0
        for _ in range(12):
            time.sleep(0.06)
            for s in spawned:
                if router.registry.get(s.endpoint) is not None:
                    _mark(router, s.endpoint, queue_ratio=0.0)
            scaler.tick()
            down = sum(1 for s in spawned if s.drained)
            if down == 2:
                break
        assert down == 2
        assert scaler._pool_size() == 1
        st = scaler.stats()
        ups = [e for e in st["events"] if e["direction"] == "up"]
        downs = [e for e in st["events"] if e["direction"] == "down"]
        assert len(ups) == 3 and len(downs) == 2
        from paddle_tpu.observability.metrics import default_registry
        fam = default_registry().collect()["fleet_scale_events_total"]
        ev = {labels[0]: v for labels, v in fam["samples"]}
        assert ev.get("up", 0) >= 3 and ev.get("down", 0) >= 2
    finally:
        scaler.stop()
        router.stop()


# ------------------------------------- the 3x-overload chaos acceptance

def _drive_load(endpoint, cfg, clients, n_req, new_tokens, lats,
                errors, lock):
    """clients = [(priority, deadline_ms)]; appends (priority, secs)
    to lats for completions, typed errors to errors. Client-side retry
    rides retry_call (the layered-retry path the budget bounds)."""
    def work(prio, ddl, seed):
        p = np.random.default_rng(seed).integers(
            1, cfg.vocab_size, 4).astype(np.int32)
        with Client(endpoint) as c:
            for _ in range(n_req):
                t0 = time.perf_counter()
                try:
                    retry_call(
                        lambda: c.generate(p, max_new_tokens=new_tokens,
                                           deadline_ms=ddl,
                                           priority=prio),
                        deadline=3.0, base_backoff=0.01,
                        retries=4,
                        retry_on=(ServerOverloadedError,),
                        what="bench-client-retry")
                except TYPED_ERRORS as exc:
                    with lock:
                        errors.append(exc)
                    continue
                except Exception as exc:  # noqa: BLE001 — the contract
                    with lock:
                        errors.append(exc)
                    continue
                with lock:
                    lats.append((prio or "interactive",
                                 time.perf_counter() - t0))

    threads = [threading.Thread(target=work, args=(prio, ddl, i))
               for i, (prio, ddl) in enumerate(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


def _p99(lats, prio):
    xs = [s for p, s in lats if p == prio]
    return float(np.percentile(np.asarray(xs), 99)) if xs else None


@pytest.mark.slow
def test_overload_3x_budgets_brownout_acceptance(tiny_gpt):
    """The acceptance scenario: 3x offered load with chaos jitter,
    budgets + brownout + priority admission on. Gates: interactive p99
    <= 2x its 1x value, typed errors only, the autoscaler scales up
    under pressure and fully drains back, zero leaked KV blocks."""
    cfg, _scope = tiny_gpt
    new_tokens = 4
    # pre-warmed replica pool: the factory hands out started, compiled
    # servers so a scale-up adds capacity, not a compile stall
    pool = [_mksrv(tiny_gpt, f"ovl{i}", decode_slots=2,
                   queue_depth=8) for i in range(3)]
    p = _prompt(cfg)
    for srv in pool:
        with Client(srv.endpoint) as c:
            c.generate(p, max_new_tokens=new_tokens)
    remaining = list(pool)
    router = fleet.Router([], probe_interval_s=0.05).start()
    scaler = fleet.Autoscaler(
        router, factory=lambda: remaining.pop(0),
        retire=remaining.append,    # scale-down returns it to the pool
        min_replicas=1, max_replicas=3, cooldown_s=0.2, poll_s=0.05,
        window=2, up_queue_ratio=0.3, down_queue_ratio=0.05,
        drain_timeout_s=10.0).start()
    lats, errors = [], []
    lock = threading.Lock()
    try:
        interactive = [(None, 3000.0)] * 2
        # 1x: interactive only, at slot capacity
        _drive_load(router.endpoint, cfg, interactive, 8, new_tokens,
                    lats, errors, lock)
        p99_1x = _p99(lats, "interactive")
        assert p99_1x is not None
        lats.clear()
        # 3x offered load: 2 interactive + 4 lower-class clients, with
        # chaos jitter stalling a fraction of connection handlers
        mixed = interactive + [("batch", None)] * 2 \
            + [("best_effort", None)] * 2
        with chaos({"serving.handle": {"delay": 0.02, "p": 0.05}},
                   seed=7):
            _drive_load(router.endpoint, cfg, mixed, 8, new_tokens,
                        lats, errors, lock)
        for exc in errors:
            assert isinstance(exc, TYPED_ERRORS), \
                f"untyped error crossed the fleet: {type(exc)}: {exc}"
        p99_3x = _p99(lats, "interactive")
        assert p99_3x is not None
        assert p99_3x <= 2.0 * p99_1x + 0.05, \
            (p99_1x, p99_3x)        # +50ms scheduler-noise allowance
        # interactive goodput stays near 1 (its requests carried
        # deadlines + top priority); shed landed on the lower classes
        n_interactive = sum(1 for pr, _s in lats
                            if pr == "interactive")
        assert n_interactive >= 12      # of 16 offered
        st = scaler.stats()
        assert any(e["direction"] == "up" for e in st["events"]), st
        peak = max(e["replicas"] for e in st["events"])
        assert peak >= 2
        # load gone: the pool drains back to min, one per cooldown
        assert _wait_until(lambda: scaler._pool_size() == 1,
                           timeout=30.0), scaler.stats()
        assert any(e["direction"] == "down"
                   for e in scaler.stats()["events"])
        # zero leaked KV blocks/slots fleet-wide
        assert _wait_until(
            lambda: all(s.gen_engine.pool.blocks_in_use() == 0
                        for s in pool), timeout=15.0), \
            {s.gen_engine.pool.name: s.gen_engine.pool.holders()
             for s in pool}
    finally:
        scaler.stop()
        router.stop()
        for srv in pool:
            srv.stop()


@pytest.mark.slow
def test_overload_priority_protects_interactive_fast(tiny_gpt):
    """Tier-1-sized slice of the acceptance scenario: one replica at
    ~3x its slot capacity — interactive requests (deadline-carrying,
    top class) complete while lower classes absorb the shed, all
    errors typed, nothing leaked."""
    cfg, _scope = tiny_gpt
    srv = _mksrv(tiny_gpt, "ovl_fast", decode_slots=2, queue_depth=4)
    p = _prompt(cfg)
    with Client(srv.endpoint) as c:
        c.generate(p, max_new_tokens=3)
    lats, errors = [], []
    lock = threading.Lock()
    try:
        mixed = [(None, 5000.0)] * 2 + [("batch", None)] * 2 \
            + [("best_effort", None)] * 2
        _drive_load(srv.endpoint, cfg, mixed, 4, 3, lats, errors, lock)
        for exc in errors:
            assert isinstance(exc, TYPED_ERRORS), \
                f"untyped error: {type(exc)}: {exc}"
        n_interactive = sum(1 for pr, _s in lats
                            if pr == "interactive")
        assert n_interactive == 8       # every interactive completed
        assert _wait_until(
            lambda: srv.gen_engine.pool.blocks_in_use() == 0,
            timeout=10.0)
    finally:
        srv.stop()
