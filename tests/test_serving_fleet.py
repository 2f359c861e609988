"""Disaggregated serving fleet (serving/fleet): KV-block migration
roundtrips (bitwise at fp32/bf16/int8, over real wire frames), the
router tier's telemetry-driven dispatch, disaggregated prefill/decode
parity against a colocated server, probe-driven eviction/readmission,
rid dedup, rolling weight reloads, per-call probe timeouts, the
client -> router -> replica two-hop trace timeline, and the fleet chaos
kill (one of three replicas dies mid-generation: typed errors only, no
leaked KV blocks on either side)."""
import socket
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import profiler, serving  # noqa: F401
from paddle_tpu.distributed.wire import recv_frame, send_frame
from paddle_tpu.models import gpt
from paddle_tpu.models.generation import GPTGenerator
from paddle_tpu.observability import tracing
from paddle_tpu.observability.recorder import flight_recorder
from paddle_tpu.resilience import FaultInjected, WatchdogTimeout, chaos
from paddle_tpu.serving import (BadRequestError, Client, InferenceServer,
                                KVBlockPool, KVPoolExhaustedError,
                                ServerOverloadedError, ServingError,
                                fleet)

RNG = np.random.default_rng(23)

# the chaos contract: every failure a fleet client may see is typed
TYPED_ERRORS = (ServingError, FaultInjected, WatchdogTimeout,
                ConnectionError, TimeoutError)


@pytest.fixture(scope="module")
def tiny_gpt():
    """One initialized tiny-GPT scope per module; generators (and the
    checkpoint for reload tests) are built from it per test."""
    cfg = gpt.GPTConfig.tiny()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        gpt.gpt_logits(cfg)
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    return cfg, main, exe, scope


def _mkgen(tiny_gpt, max_len=48):
    cfg, _main, _exe, scope = tiny_gpt
    return GPTGenerator(cfg, scope, max_len=max_len, bucket_min=8)


def _mksrv(tiny_gpt, name, **kw):
    kw.setdefault("decode_slots", 2)
    return InferenceServer(generator=_mkgen(tiny_gpt),
                           kv_pool_name=name, **kw).start()


def _prompts(cfg, lens, seed=7):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
            for n in lens]


def _wait_until(cond, timeout=10.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return cond()


def _mkpool(dtype, name):
    return KVBlockPool(slots=4, num_layers=2, num_heads=2, d_head=8,
                       max_seq_len=64, block_size=8, dtype=dtype,
                       name=name)


def _fill_random(pool, seed=0):
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    arrs = dict(pool.arrays())
    for k in list(arrs):
        a = rng.standard_normal(arrs[k].shape) * 3.0
        arrs[k] = jnp.asarray(np.asarray(a), arrs[k].dtype)
    pool.update_arrays(arrs)


# ------------------------------------------------- KV block migration

@pytest.mark.parametrize("dtype", ["fp32", "bf16", "int8"])
def test_kv_export_wire_import_roundtrip_bitwise(dtype):
    """Satellite: serialize a slot -> REAL wire frame -> deserialize
    into a second pool -> re-export: every payload array (int8 scales
    included) is bit-identical, and both pools' accounting balances."""
    src, dst = _mkpool(dtype, f"mig_src_{dtype}"), _mkpool(
        dtype, f"mig_dst_{dtype}")
    src.alloc(1, 13)
    _fill_random(src)
    payload = src.export_slot(1)
    lst = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    lst.bind(("127.0.0.1", 0))
    lst.listen(1)
    a = socket.create_connection(lst.getsockname())
    b, _ = lst.accept()
    try:
        send_frame(a, payload, None)
        wired = recv_frame(b, None)
    finally:
        a.close()
        b.close()
        lst.close()
    n = dst.import_slot(2, wired)
    assert n == payload["nblocks"] == dst.blocks_in_use()
    back = dst.export_slot(2)
    for key, val in payload.items():
        if isinstance(val, np.ndarray):
            assert val.dtype == back[key].dtype
            assert np.array_equal(val, back[key]), (dtype, key)
        else:
            assert back[key] == val, (dtype, key)
    dst.free_slot(2)
    assert dst.blocks_in_use() == 0 and dst.holders() == {}


def test_kv_import_validates_geometry_and_capacity():
    """A payload from a differently-shaped pool is refused TERMINALLY
    (BadRequest — retrying can't help); an exhausted pool refuses
    RETRYABLY (KVPoolExhausted) with nothing allocated."""
    src = _mkpool("fp32", "val_src")
    src.alloc(0, 10)
    _fill_random(src)
    payload = src.export_slot(0)

    other = KVBlockPool(slots=4, num_layers=2, num_heads=2, d_head=8,
                        max_seq_len=64, block_size=16, dtype="fp32",
                        name="val_bs")
    with pytest.raises(BadRequestError):
        other.import_slot(0, payload)
    assert other.blocks_in_use() == 0

    with pytest.raises(BadRequestError):
        _mkpool("bf16", "val_dt").import_slot(0, payload)

    tampered = dict(payload)
    tampered["nblocks"] = 777
    with pytest.raises(BadRequestError):
        _mkpool("fp32", "val_nb").import_slot(0, tampered)

    tiny = KVBlockPool(slots=4, num_layers=2, num_heads=2, d_head=8,
                       max_seq_len=64, block_size=8, num_blocks=2,
                       dtype="fp32", name="val_cap")
    with pytest.raises(KVPoolExhaustedError):
        tiny.import_slot(0, payload)
    assert tiny.blocks_in_use() == 0 and tiny.holders() == {}


@pytest.mark.slow
def test_disaggregated_split_matches_colocated_bitwise(tiny_gpt):
    """Tentpole acceptance: prefill on replica A, KV blocks over the
    wire into replica B's pool, greedy decode there — token-for-token
    identical to one colocated paged server. Both pools drain to zero
    and the kv_exports/kv_imports counters move."""
    cfg = tiny_gpt[0]
    prompt = _prompts(cfg, (9,))[0]
    ref_srv = _mksrv(tiny_gpt, "colo")
    try:
        with Client(ref_srv.endpoint) as c:
            ref = c.generate(prompt, max_new_tokens=8)
    finally:
        ref_srv.stop()

    pre = _mksrv(tiny_gpt, "pre")
    dec = _mksrv(tiny_gpt, "dec")
    try:
        with Client(pre.endpoint) as cp, Client(dec.endpoint) as cd:
            kv = cp.prefill(prompt, max_new_tokens=8)
            assert kv["prompt_tokens"] == prompt.size
            out = cd.generate_from_kv(prompt, kv, max_new_tokens=8)
        np.testing.assert_array_equal(out, ref)
        sp, sd = pre.stats(), dec.stats()
        assert sp["kv_exports"] == 1 and sd["kv_imports"] == 1
        assert sp["kvpool_blocks_in_use"] == 0
        assert sd["kvpool_blocks_in_use"] == 0
        # door check: a payload lying about its prompt is refused typed
        with Client(dec.endpoint) as cd:
            with pytest.raises(BadRequestError):
                cd.generate_from_kv(prompt[:4], kv, max_new_tokens=4)
    finally:
        pre.stop()
        dec.stop()


def test_dense_bank_keyword_is_refused(tiny_gpt):
    """The paged pool is the one KV store: ``kv_paged=False`` is refused
    at construction, by name; the kept keyword's other values do
    nothing."""
    with pytest.raises(ValueError, match="kv_paged=False"):
        InferenceServer(generator=_mkgen(tiny_gpt), decode_slots=2,
                        kv_paged=False)
    for kept in (None, True):
        srv = InferenceServer(generator=_mkgen(tiny_gpt), decode_slots=2,
                              kv_paged=kept)
        assert srv.gen_engine.pool is not None


# ------------------------------------------------------- router tier

@pytest.mark.slow
def test_router_routes_generate_and_scrapes_telemetry(tiny_gpt):
    """A Client pointed at the router cannot tell it from a replica;
    dispatch telemetry (probed health incl. kvpool occupancy) shows up
    in Router.stats()."""
    cfg = tiny_gpt[0]
    prompts = _prompts(cfg, (5, 9, 12))
    reps = [_mksrv(tiny_gpt, f"rt{i}") for i in range(2)]
    router = fleet.Router([r.endpoint for r in reps],
                          probe_interval_s=0.05).start()
    try:
        with Client(router.endpoint) as c:
            assert c.ping()
            outs = [c.generate(p, max_new_tokens=5) for p in prompts]
            for o in outs:
                assert o.size == 5
            h = c.health()
            assert h["replicas_healthy"] == 2
            st = c.stats()
        assert st["router_dispatches"] >= 3
        assert len(st["replicas"]) == 2
        for snap in st["replicas"].values():
            assert snap["state"] == "healthy"
            assert "kvpool_occupancy" in snap
            assert "load_score" in snap
        # in-process parity: the same dispatch path without a socket
        out = router.generate(prompts[0], max_new_tokens=5)
        with Client(reps[0].endpoint) as c0:
            ref = c0.generate(prompts[0], max_new_tokens=5)
        np.testing.assert_array_equal(out, ref)
    finally:
        router.stop()
        for r in reps:
            r.stop()


@pytest.mark.slow
def test_router_disaggregated_two_hop_parity(tiny_gpt):
    """Routed two-hop generate (prefill replica -> KV migration ->
    decode replica) matches the colocated greedy output bitwise;
    migration counters and the kv_migration flight event fire."""
    cfg = tiny_gpt[0]
    prompt = _prompts(cfg, (11,))[0]
    colo = _mksrv(tiny_gpt, "hop_colo")
    try:
        with Client(colo.endpoint) as c:
            ref = c.generate(prompt, max_new_tokens=7)
    finally:
        colo.stop()
    pre = _mksrv(tiny_gpt, "hop_pre")
    dec = _mksrv(tiny_gpt, "hop_dec")
    router = fleet.Router([(pre.endpoint, "prefill"),
                           (dec.endpoint, "decode")],
                          probe_interval_s=0.05).start()
    try:
        assert router.disaggregated
        with Client(router.endpoint) as c:
            out = c.generate(prompt, max_new_tokens=7)
        np.testing.assert_array_equal(out, ref)
        st = router.stats()
        assert st["router_kv_migrations"] == 1
        assert st["router_kv_migrated_bytes"] > 0
        assert st["fleet_events"]["kv_migration"] >= 1
        assert pre.stats()["kvpool_blocks_in_use"] == 0
        assert dec.stats()["kvpool_blocks_in_use"] == 0
        # max_new_tokens=1 is answered by the prefill hop alone
        with Client(router.endpoint) as c:
            one = c.generate(prompt, max_new_tokens=1)
        np.testing.assert_array_equal(one, ref[:1])
    finally:
        router.stop()
        pre.stop()
        dec.stop()


def test_router_rid_dedup_single_dispatch(tiny_gpt):
    """A replayed routed generate (same rid — reconnecting client)
    ATTACHES to the in-flight dispatch instead of dispatching twice."""
    cfg = tiny_gpt[0]
    rep = _mksrv(tiny_gpt, "dedup")
    router = fleet.Router([rep.endpoint],
                          probe_interval_s=0.05).start()
    try:
        msg = {"op": "generate",
               "tokens": _prompts(cfg, (8,))[0],
               "max_new_tokens": 16, "temperature": 0.0, "top_k": 0,
               "eos_id": None, "deadline_ms": None, "rid": "twin-rid"}
        replies = [None, None]

        def call(i):
            replies[i] = router._route_generate(dict(msg))

        ts = [threading.Thread(target=call, args=(i,)) for i in (0, 1)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
        assert replies[0] is not None and replies[1] is not None
        assert replies[0].get("ok") and replies[1].get("ok")
        np.testing.assert_array_equal(replies[0]["tokens"],
                                      replies[1]["tokens"])
        st = router.stats()
        assert st["router_dedup_hits"] == 1
        # the pair generated ONCE on the replica
        assert rep.stats()["generate_requests"] == 1
    finally:
        router.stop()
        rep.stop()


def test_probe_eviction_and_readmission(tiny_gpt, fault_points):
    """FLAGS_router_evict_after consecutive failed probes evict the
    replica from rotation (flight-recorded); the next healthy probe
    readmits it. Driven synchronously through the chaos point for
    determinism."""
    rep = _mksrv(tiny_gpt, "evict")
    router = fleet.Router([rep.endpoint], probe_interval_s=30.0,
                          evict_after=3)
    try:
        r = router.registry.get(rep.endpoint)
        assert r.state == "healthy"          # add() probed it
        with chaos("fleet.probe", times=3):
            for _ in range(3):
                assert not router.registry.probe_once(r)
        assert r.state == "evicted"
        assert router.registry.pick(("both",)) is None
        assert router.registry.probe_once(r)     # replica is fine
        assert r.state == "healthy" and r.probe_failures == 0
        assert router.registry.pick(("both",)) is r
        kinds = [e["kind"] for e in flight_recorder().snapshot()]
        assert "replica_evicted" in kinds
        assert "replica_readmitted" in kinds
        snap = router.stats()["replicas"][rep.endpoint]
        assert snap["evictions"] == 1 and snap["readmissions"] == 1
    finally:
        router.stop()
        rep.stop()


def test_rolling_reload_one_replica_at_a_time(tiny_gpt, tmp_path):
    """Drain-aware rolling weight reload across the fleet: every
    replica reloads (weights_version bumps), driven one at a time via
    the PR-6 reload machinery over the new wire op."""
    cfg, main, exe, scope = tiny_gpt
    ckpt = str(tmp_path / "ckpt")
    with fluid.scope_guard(scope):
        fluid.io.save_params(exe, ckpt, main_program=main)
    reps = [_mksrv(tiny_gpt, f"roll{i}") for i in range(2)]
    router = fleet.Router([r.endpoint for r in reps],
                          probe_interval_s=0.05).start()
    try:
        out = router.rolling_reload(ckpt, drain_timeout=5.0)
        assert set(out) == {r.endpoint for r in reps}
        for _ep, res in out.items():
            assert res["ok"], res
            assert res["weights_version"] == 2
        for r in reps:
            with Client(r.endpoint) as c:
                assert c.health()["weights_version"] == 2
        st = router.stats()
        assert st["router_rolling_reloads"] == 2
        assert st["fleet_events"]["rolling_reload"] >= 4  # drain+done x2
        assert st["replicas_healthy"] == 2               # back in rotation
        # a bogus path fails typed per-replica and EVICTS (ambiguous
        # weights never rejoin silently); the prober readmits later
        bad = router.rolling_reload(str(tmp_path / "nope"))
        assert all(not res["ok"] for res in bad.values())
    finally:
        router.stop()
        for r in reps:
            r.stop()


# ---------------------------------------------- probe-timeout satellite

def test_client_probe_ops_per_call_timeout_fail_fast():
    """Satellite: health/stats/metrics accept a per-call timeout that
    bounds a probe against a replica whose ACCEPT LOOP is hung (the
    connection lands in the OS backlog, the reply never comes) —
    instead of inheriting the long socket default."""
    lst = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    lst.bind(("127.0.0.1", 0))
    lst.listen(8)              # backlog accepts; nobody ever answers
    port = lst.getsockname()[1]
    try:
        with Client(f"127.0.0.1:{port}", connect_retries=1) as c:
            for call in (lambda: c.health(timeout=0.3),
                         lambda: c.stats(timeout=0.3),
                         lambda: c.metrics(timeout=0.3),
                         lambda: c.ping(timeout=0.3)):
                t0 = time.monotonic()
                with pytest.raises((ConnectionError, OSError)):
                    call()
                assert time.monotonic() - t0 < 5.0
    finally:
        lst.close()


def test_hedged_dispatch_typed_refusal_before_delay():
    """Regression: with hedging armed, a primary leg that comes back
    with a typed refusal BEFORE the hedge delay must surface that
    typed reply — not strand the hedge bookkeeping and leak an
    untyped internal error."""
    router = fleet.Router([], hedge_ms=50.0)
    try:
        with pytest.raises(ServerOverloadedError):
            router.generate(np.asarray([1, 2, 3], np.int32),
                            max_new_tokens=2)
    finally:
        router.stop()


def test_bad_kv_import_is_client_error_not_engine_failure(tiny_gpt):
    """Regression: a migrated payload whose GEOMETRY mismatches the
    receiving pool (it passes the token-count door check) is refused
    typed — and counted as a client error, not an engine failure: a
    bad payload must not walk the decode-loop breaker toward degraded
    on an otherwise healthy replica."""
    cfg = tiny_gpt[0]
    prompt = _prompts(cfg, (10,))[0]
    srv = _mksrv(tiny_gpt, "badkv")
    try:
        src = KVBlockPool(slots=2, num_layers=1, num_heads=1, d_head=4,
                          max_seq_len=32, block_size=8, dtype="fp32",
                          name="badkv_src")
        src.alloc(0, 10)           # right token count, wrong geometry
        payload = src.export_slot(0)
        payload["first_token"] = 1
        payload["prompt_tokens"] = 10
        with Client(srv.endpoint) as c:
            with pytest.raises(BadRequestError):
                c.generate_from_kv(prompt, payload, max_new_tokens=4)
            st = srv.stats()
            assert st["engine_failures"] == 0
            assert st["loop_restarts"] == 0
            # the replica still serves ordinary traffic afterwards
            out = c.generate(prompt, max_new_tokens=3)
        assert out.size == 3
        assert srv.gen_engine.pool.blocks_in_use() == 0
    finally:
        srv.stop()


# ------------------------------------------------- two-hop trace test

def test_two_hop_trace_timeline(tiny_gpt):
    """Satellite: one traced request yields client -> router -> replica
    spans under ONE trace id with an unbroken parent chain, and the
    router's probe ops (health) land on the timeline too."""
    profiler.reset_profiler()
    cfg = tiny_gpt[0]
    rep = _mksrv(tiny_gpt, "trace")
    router = fleet.Router([rep.endpoint],
                          probe_interval_s=0.05).start()
    try:
        root = tracing.new_trace()
        with tracing.ambient(root):
            with Client(router.endpoint) as c:
                c.generate(_prompts(cfg, (6,))[0], max_new_tokens=3)
                c.health()
        spans = [s for s in profiler._spans
                 if len(s) >= 7 and s[4] == root.trace_id]
        by_name = {}
        for s in spans:
            by_name.setdefault(s[0], []).append(s)
        for needed in ("client/send", "router/generate",
                       "serving/handle", "router/health"):
            assert needed in by_name, (needed, sorted(by_name))
        # unbroken chain: client/send -> router/generate ->
        # serving/handle (the replica hop parents under the router's
        # span, which parents under the client's)
        ids = {s[5] for s in spans}
        rg = by_name["router/generate"][0]
        assert rg[6] in ids                     # parent = client span
        sh = [s for s in by_name["serving/handle"]
              if s[6] == rg[5]]
        assert sh, "replica handle span does not parent under the " \
                   "router's generate span"
    finally:
        router.stop()
        rep.stop()
        profiler.reset_profiler()


# ------------------------------------------------------- chaos kill

@pytest.mark.slow
def test_fleet_chaos_kill_replica_mid_generation(tiny_gpt):
    """Acceptance: kill one of three replicas while generations are in
    flight. Every request either completes or fails TYPED; the router
    records the death/failover and evicts the replica (healthy drops
    to 2); aggregate KV-pool occupancy returns to ZERO on every
    replica — the killed one included (its stop path releases)."""
    cfg = tiny_gpt[0]
    reps = [_mksrv(tiny_gpt, f"chaos{i}", decode_slots=2)
            for i in range(3)]
    router = fleet.Router([r.endpoint for r in reps],
                          probe_interval_s=0.05, probe_timeout_s=0.5,
                          evict_after=2).start()
    results, errors = [], []
    lock = threading.Lock()

    def worker(i):
        prompt = _prompts(cfg, (4 + (i % 5),), seed=100 + i)[0]
        try:
            with Client(router.endpoint) as c:
                out = c.generate(prompt, max_new_tokens=24,
                                 deadline_ms=60000.0)
            with lock:
                results.append(out)
        except Exception as exc:  # noqa: BLE001 — judged below
            with lock:
                errors.append(exc)

    try:
        # warm the compile caches so the kill lands mid-DECODE, not
        # mid-compile (one short generation per replica, direct)
        for r in reps:
            with Client(r.endpoint) as c:
                c.generate(_prompts(cfg, (6,))[0], max_new_tokens=2)
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(9)]
        for t in threads:
            t.start()
        time.sleep(0.25)
        reps[1].stop()                       # the chaos kill
        for t in threads:
            t.join(120)
        assert not any(t.is_alive() for t in threads)
        for exc in errors:
            assert isinstance(exc, TYPED_ERRORS), \
                f"untyped error crossed the fleet: {type(exc)}: {exc}"
        # most requests survive the kill (failover re-executes them)
        assert len(results) >= 6, (len(results), errors)
        # the fleet noticed: death (dispatch-observed) or eviction
        # (probe-observed), and the rotation shrank to the survivors
        assert _wait_until(
            lambda: router.registry.healthy_count() == 2, timeout=10)
        st = router.stats()
        assert (st["fleet_events"]["replica_death"]
                + st["fleet_events"]["replica_evicted"]) >= 1
        # zero leaked KV blocks on EVERY side once traffic drains
        for r in reps:
            pool = r.gen_engine.pool
            assert _wait_until(lambda p=pool: p.blocks_in_use() == 0,
                               timeout=10), \
                f"leaked blocks in {pool.name}: {pool.holders()}"
            assert pool.holders() == {}
        # the survivors still serve
        with Client(router.endpoint) as c:
            out = c.generate(_prompts(cfg, (5,))[0], max_new_tokens=4)
        assert out.size == 4
    finally:
        router.stop()
        for r in reps:
            r.stop()
