"""Serving resilience layer: chaos harness determinism, lifecycle +
health states, graceful drain, supervised loop restarts, hot weight
reload (manifest-verified atomic swap), hedged/reconnecting clients with
server-side request-id dedup, and a slow-marked chaos soak (concurrent
infer+generate under seeded faults: no hangs, no silent drops, typed
errors only)."""
import os
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, resilience, serving
from paddle_tpu.models import gpt
from paddle_tpu.models.generation import GPTGenerator
from paddle_tpu.resilience import (CheckpointCorruptError, FaultInjected,
                                   WatchdogTimeout, chaos)
from paddle_tpu.serving import (Client, DeadlineExceededError,
                                InferenceServer, ServerOverloadedError,
                                ServerShutdownError, ServingError)

RNG = np.random.default_rng(11)

# every fault that seeded chaos may inject, plus every typed refusal
# the serving layer is allowed to answer with — the soak's definition
# of "typed errors only"
TYPED_ERRORS = (ServingError, FaultInjected, WatchdogTimeout,
                ConnectionError, TimeoutError)


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
        fluid.io.save_params(exe, os.path.join(path, "ckpt_v1"),
                             main_program=main)
        # v2 weights: every output doubles (linear model, params * 2)
        from paddle_tpu.framework.core import Parameter
        for v in main.global_block().vars.values():
            if isinstance(v, Parameter):
                scope.set(v.name,
                          np.asarray(scope.find_var(v.name)) * 2.0)
        fluid.io.save_params(exe, os.path.join(path, "ckpt_v2"),
                             main_program=main)
    return path


def _tiny_gpt(max_len=64):
    """A fresh tiny-GPT scope + generator + the training program (for
    save_params). Fresh per use — reload tests mutate the weights."""
    cfg = gpt.GPTConfig.tiny()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        gpt.gpt_logits(cfg)
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    gen = GPTGenerator(cfg, scope, max_len=max_len, bucket_min=8)
    return cfg, main, exe, scope, gen


def _wait_until(cond, timeout=10.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return cond()


# ---------------------------------------------------------------- chaos

def test_chaos_seeded_probabilistic_replay(fault_points):
    def pattern(seed):
        out = []
        with chaos({"pt": {"p": 0.4}}, seed=seed):
            for _ in range(30):
                try:
                    resilience.maybe_fail("pt")
                    out.append(0)
                except FaultInjected:
                    out.append(1)
        return out
    a, b, c = pattern(5), pattern(5), pattern(6)
    assert a == b                       # same seed -> same fire pattern
    assert a != c                       # different seed -> different one
    assert 0 < sum(a) < 30              # actually probabilistic


def test_chaos_schedulable_every_after_times(fault_points):
    fires = []
    with chaos("pt", every=3, after=2, times=2) as monkey:
        for i in range(14):
            try:
                resilience.maybe_fail("pt")
            except FaultInjected:
                fires.append(i)
    # skip 2 hits, then every 3rd, capped at 2 fires
    assert fires == [4, 7]
    assert monkey.hits["pt"] == 14 and monkey.fired["pt"] == 2


def test_chaos_delay_injects_stall_not_error(fault_points):
    with chaos("pt", delay=0.15, times=1):
        t0 = time.monotonic()
        resilience.maybe_fail("pt")      # stalls, does not raise
        dt = time.monotonic() - t0
        resilience.maybe_fail("pt")      # budget spent: no stall
    assert dt >= 0.14


def test_chaos_multi_point_streams_independent(fault_points):
    """Arming more points must not shift another point's pattern."""
    def fires_of_a(points):
        out = []
        with chaos({pt: {"p": 0.5} for pt in points}, seed=9):
            for _ in range(20):
                try:
                    resilience.maybe_fail("a")
                    out.append(0)
                except FaultInjected:
                    out.append(1)
        return out
    assert fires_of_a(["a"]) == fires_of_a(["a", "b", "c"])


# ------------------------------------------------- client reconnect fix

def test_client_reconnects_after_server_bounce(tmp_path):
    """Regression (satellite): a server restart used to leave every
    existing Client permanently broken on its dead cached socket."""
    path = _save_mlp(tmp_path)
    server = InferenceServer(path, batch_timeout_ms=1.0).start()
    port = server.port
    c = Client(server.endpoint)
    x = RNG.standard_normal((1, 8)).astype(np.float32)
    want, = c.infer({"x": x})            # socket now cached
    server.stop()
    server2 = InferenceServer(path, batch_timeout_ms=1.0,
                              port=port).start()
    try:
        got, = c.infer({"x": x})         # transparently reconnects once
        np.testing.assert_array_equal(got, want)
        assert c.ping()
    finally:
        c.close()
        server2.stop()


def test_client_idempotent_ops_retry(tmp_path):
    path = _save_mlp(tmp_path)
    server = InferenceServer(path, batch_timeout_ms=1.0).start()
    c = Client(server.endpoint)
    try:
        assert c.ping()
        c._sock.close()                  # simulate a silently dead socket
        assert c.ping()                  # retry_call + reconnect
        assert "state" in c.health()
    finally:
        c.close()
        server.stop()


# ------------------------------------------------ typed shutdown errors

def test_stop_fails_queued_requests_immediately(tmp_path, fault_points):
    """Satellite: queued-but-unbatched requests must fail at stop() with
    the typed shutdown error, not ride out their own timeouts."""
    path = _save_mlp(tmp_path)
    server = InferenceServer(path, max_batch_size=1,
                             batch_timeout_ms=1.0, queue_depth=64)
    server.start(serve_network=False)

    def slow(point, ctx):
        time.sleep(0.4)
        return None
    with fault_points.fault_injection("serving.execute", exc=slow,
                                      times=-1):
        x = RNG.standard_normal((1, 8)).astype(np.float32)
        first = server.submit({"x": x})          # occupies the engine
        time.sleep(0.05)
        queued = [server.submit({"x": x}) for _ in range(4)]
        t0 = time.monotonic()
        server.stop()
        for req in queued:
            with pytest.raises(ServerShutdownError):
                req.wait(timeout=5)
        assert time.monotonic() - t0 < 3.0       # immediate, not timeout
    assert server.state == "stopped"
    # the in-flight request still completed or failed typed — never hangs
    try:
        first.wait(timeout=5)
    except ServingError:
        pass


def test_draining_admission_refused_typed_over_wire(tmp_path):
    path = _save_mlp(tmp_path)
    server = InferenceServer(path, batch_timeout_ms=1.0).start()
    try:
        with Client(server.endpoint) as c:
            c.infer({"x": np.zeros((1, 8), np.float32)})
            server.queue.quiesce()               # drain's admission gate
            with pytest.raises(ServerShutdownError):
                c.infer({"x": np.zeros((1, 8), np.float32)})
            assert c.ping()                      # control ops still served
            assert c.health()["state"] == "serving"
    finally:
        server.stop()


# ----------------------------------------------- lifecycle + health op

def test_lifecycle_states_and_health(tmp_path):
    path = _save_mlp(tmp_path)
    server = InferenceServer(path, batch_timeout_ms=1.0)
    assert server.state == "created"
    server.start()
    try:
        assert server.state == "serving"
        with Client(server.endpoint) as c:
            h = c.health()
            assert h["state"] == "serving"
            assert h["weights_version"] == 1
            assert h["breaker"] == "closed"
            assert h["loops"]["microbatcher"]["alive"] is True
            assert h["loops"]["microbatcher"]["restarts"] == 0
            assert h["queue_depth"] == 0
        st = server.stats()
        assert st["state"] == "serving" and st["loop_restarts"] == 0
    finally:
        server.stop()
    assert server.state == "stopped"


def test_drain_completes_inflight_and_stops(tmp_path):
    path = _save_mlp(tmp_path)
    server = InferenceServer(path, batch_timeout_ms=10.0)
    server.start(serve_network=False)
    x = RNG.standard_normal((1, 8)).astype(np.float32)
    ref, = server.infer({"x": x}, timeout=30)
    reqs = [server.submit({"x": x}) for _ in range(6)]
    report = server.drain(timeout=30)
    assert report["drained"] and report["remaining"] == 0
    assert server.state == "stopped"
    for req in reqs:                     # admitted-before-drain: completed
        got, = req.wait(timeout=1)
        # the six may share one padded batch while ref ran alone, and
        # XLA:CPU picks its codegen by batch size: a few ulp (of the
        # largest softmax output), not bitwise
        np.testing.assert_allclose(got, ref, rtol=0,
                                   atol=4 * np.spacing(np.max(ref)))


@pytest.mark.slow
def test_drain_generation_greedy_parity():
    """Acceptance: drain() returns with zero in-flight rows and greedy
    outputs bitwise-identical to an undisturbed run for requests
    admitted before the drain."""
    cfg, _, _, _, gen = _tiny_gpt()
    prompts = [RNG.integers(1, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 9, 7)]
    ref = [gen.generate([p], max_new_tokens=8, seed=0)[0]
           for p in prompts]
    server = InferenceServer(generator=gen, decode_slots=2)
    server.start(serve_network=False)
    reqs = [server.submit_generate(p, max_new_tokens=8) for p in prompts]
    report = server.drain(timeout=120)
    assert report["drained"] and report["remaining"] == 0
    assert server.decode_batcher.inflight() == 0
    for req, want in zip(reqs, ref):
        got, = req.wait(timeout=1)
        np.testing.assert_array_equal(got, want)


def test_drain_waits_for_what_a_capped_round_left_in_the_queue(monkeypatch):
    """Where the device's free memory caps a round at one prompt, the
    prompts that did not fit stay in the queue; rows that end at their
    first token leave nothing decoding, and drain() must still serve
    every accepted request before it reports ``drained``."""
    from paddle_tpu.serving import engine as engine_mod
    cfg, _, _, _, gen = _tiny_gpt()
    prompts = [RNG.integers(1, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 9, 7, 6)]
    ref = [gen.generate([p], max_new_tokens=1, seed=0)[0]
           for p in prompts]
    monkeypatch.setattr(engine_mod, "_free_device_bytes", lambda: 1)
    server = InferenceServer(generator=gen, decode_slots=4)
    server.start(serve_network=False)
    rows = []
    real = server.gen_engine.admit
    monkeypatch.setattr(server.gen_engine, "admit", lambda reqs, slots: (
        rows.append(len(reqs)), real(reqs, slots))[1])
    reqs = [server.submit_generate(p, max_new_tokens=1) for p in prompts]
    report = server.drain(timeout=120)
    assert report["drained"] and report["remaining"] == 0
    assert rows == [1, 1, 1, 1]
    for req, want in zip(reqs, ref):
        got, = req.wait(timeout=1)
        np.testing.assert_array_equal(got, want)


# ------------------------------------------------------ supervised loops

def test_supervisor_restarts_crashed_microbatcher(tmp_path,
                                                  fault_points):
    path = _save_mlp(tmp_path)
    server = InferenceServer(path, batch_timeout_ms=1.0)
    server.supervisor.poll_s = 0.02
    server.start(serve_network=False)
    try:
        x = RNG.standard_normal((1, 8)).astype(np.float32)
        server.infer({"x": x}, timeout=30)
        with fault_points.fault_injection("serving.queue",
                                          exc=RuntimeError, times=1):
            assert _wait_until(lambda: server.stats()["loop_restarts"]
                               >= 1, timeout=5)
        assert _wait_until(server.batcher.alive, timeout=5)
        server.infer({"x": x}, timeout=30)       # serving again
        h = server.health()
        assert h["loops"]["microbatcher"]["restarts"] == 1
        assert server.state == "serving"         # one crash != degraded
    finally:
        server.stop()


def test_supervisor_restarts_crashed_decode_loop(fault_points):
    _, _, _, _, gen = _tiny_gpt()
    server = InferenceServer(generator=gen, decode_slots=2)
    server.supervisor.poll_s = 0.02
    server.start(serve_network=False)
    try:
        prompt = RNG.integers(1, 100, 5).astype(np.int32)
        server.submit_generate(prompt, max_new_tokens=2).wait(timeout=120)
        with fault_points.fault_injection("serving.queue",
                                          exc=RuntimeError, times=1):
            assert _wait_until(lambda: server.stats()["loop_restarts"]
                               >= 1, timeout=5)
        assert _wait_until(server.decode_batcher.alive, timeout=5)
        out, = server.submit_generate(prompt,
                                      max_new_tokens=2).wait(timeout=120)
        assert out.size >= 0                     # serving again
    finally:
        server.stop()


def test_watchdog_fails_hung_execute_typed(tmp_path, fault_points):
    """A hung execute is bounded by FLAGS_serving_loop_watchdog_s: the
    batch's clients get the typed WatchdogTimeout (etype Watchdog over
    the wire) and the loop survives to serve the next batch."""
    path = _save_mlp(tmp_path)
    server = InferenceServer(path, batch_timeout_ms=1.0,
                             loop_watchdog_s=0.3).start()
    try:
        with Client(server.endpoint) as c:
            x = RNG.standard_normal((1, 8)).astype(np.float32)
            want, = c.infer({"x": x})            # warm compile
            def hang(point, ctx):
                time.sleep(1.5)
                return None
            with fault_points.fault_injection("serving.execute",
                                              exc=hang, times=1):
                t0 = time.monotonic()
                with pytest.raises(WatchdogTimeout):
                    c.infer({"x": x})
                assert time.monotonic() - t0 < 1.4   # not the full hang
            got, = c.infer({"x": x})             # loop survived
            np.testing.assert_array_equal(got, want)
        st = server.stats()
        assert st["watchdog_timeouts"] >= 1
        assert server.batcher.alive()
    finally:
        server.stop()


@pytest.mark.slow
def test_repeated_crashes_trip_degraded_then_recover(fault_points):
    """Crash-looping decode loop -> breaker opens -> DEGRADED (generate
    sheds, ping/health/stats answer); sustained health -> SERVING."""
    _, _, _, _, gen = _tiny_gpt()
    server = InferenceServer(generator=gen, decode_slots=2)
    sup = server.supervisor
    sup.poll_s = 0.02
    sup.reset_secs = 0.4
    sup.breaker.failure_threshold = 2
    sup.breaker.reset_timeout = 0.4
    sup.restart_backoff = 0.01
    server.start(serve_network=False)
    try:
        prompt = RNG.integers(1, 100, 4).astype(np.int32)
        server.submit_generate(prompt, max_new_tokens=2).wait(timeout=120)
        with fault_points.fault_injection("serving.queue",
                                          exc=RuntimeError, times=-1):
            assert _wait_until(lambda: server.state == "degraded",
                               timeout=10), server.health()
            with pytest.raises(ServerOverloadedError, match="degraded"):
                server.submit_generate(prompt, max_new_tokens=2)
            h = server.health()              # health still answers
            assert h["state"] == "degraded"
            assert h["breaker"] in ("open", "half-open")
        # faults cleared: the restarted loop stays healthy -> recovery
        assert _wait_until(lambda: server.state == "serving",
                           timeout=10), server.health()
        out, = server.submit_generate(prompt,
                                      max_new_tokens=2).wait(timeout=120)
        assert server.stats()["loop_restarts"] >= 2
    finally:
        server.stop()


# ---------------------------------------------------- hot weight reload

def test_reload_weights_infer_engine(tmp_path):
    path = _save_mlp(tmp_path)
    server = InferenceServer(path, batch_timeout_ms=1.0)
    server.start(serve_network=False)
    try:
        x = np.ones((1, 8), np.float32)
        r1, = server.infer({"x": x}, timeout=30)
        report = server.reload_weights(os.path.join(path, "ckpt_v2"))
        assert report["weights_version"] == 2
        r2, = server.infer({"x": x}, timeout=30)
        assert not np.array_equal(r1, r2)        # new weights serving
        assert server.stats()["weights_version"] == 2
        assert server.stats()["weight_reloads"] == 1
    finally:
        server.stop()


def test_reload_weights_corrupt_checkpoint_aborts(tmp_path):
    path = _save_mlp(tmp_path)
    ckpt = os.path.join(path, "ckpt_v2")
    # flip one byte in one param file
    victim = next(f for f in sorted(os.listdir(ckpt))
                  if f.endswith(".npy"))
    with open(os.path.join(ckpt, victim), "r+b") as f:
        f.seek(128)
        b = f.read(1)
        f.seek(128)
        f.write(bytes([b[0] ^ 0xFF]))
    server = InferenceServer(path, batch_timeout_ms=1.0)
    server.start(serve_network=False)
    try:
        x = np.ones((1, 8), np.float32)
        r1, = server.infer({"x": x}, timeout=30)
        with pytest.raises(CheckpointCorruptError):
            server.reload_weights(ckpt)
        r2, = server.infer({"x": x}, timeout=30)
        np.testing.assert_array_equal(r1, r2)    # old snapshot untouched
        assert server.stats()["weights_version"] == 1
        with pytest.raises(CheckpointCorruptError, match="manifest"):
            server.reload_weights(str(tmp_path / "no_such_dir"))
    finally:
        server.stop()


def test_reload_weights_generation_inflight_old_new(tmp_path):
    """The CheckFreq-style swap contract: a generation in flight when
    reload_weights() lands finishes on the OLD weights (greedy output
    identical to an undisturbed v1 run); the next admission uses the
    NEW weights; nothing is dropped."""
    cfg, main, exe, scope, gen = _tiny_gpt()
    ck2 = str(tmp_path / "gpt_v2")
    p1 = RNG.integers(1, cfg.vocab_size, 5).astype(np.int32)
    p2 = RNG.integers(1, cfg.vocab_size, 6).astype(np.int32)
    ref1_v1 = gen.generate([p1], max_new_tokens=40, seed=0)[0]
    # v2: steer the residual stream toward token 7's embedding row so
    # greedy argmax provably changes (uniform shifts are invisible —
    # the final LN zero-means them)
    w = np.asarray(scope.find_var("word_embedding"))
    bname = "decoder_layer_%d_ffn_1.b_0" % (cfg.num_layers - 1)
    b_old = np.asarray(scope.find_var(bname)).copy()
    scope.set(bname, b_old + 10.0 * w[7])
    with fluid.scope_guard(scope):
        fluid.io.save_params(exe, ck2, main_program=main)
    scope.set(bname, b_old)              # the generator still serves v1

    server = InferenceServer(generator=gen, decode_slots=2)
    server.start(serve_network=False)
    try:
        server.submit_generate(p1, max_new_tokens=2).wait(timeout=120)
        # the loop runs 40 steps of a tiny model in a few milliseconds:
        # a short stall at every step's chaos point keeps the generation
        # in flight until the reload has been asked for
        with chaos("serving.decode_step", p=1.0, delay=0.01):
            long_req = server.submit_generate(p1, max_new_tokens=40)
            assert _wait_until(
                lambda: server.decode_batcher.inflight() > 0
                or long_req.done(), timeout=10)
            assert not long_req.done(), "generation finished before " \
                "the reload could land mid-flight"
            report = server.reload_weights(ck2, timeout=120)
        assert report["weights_version"] == 2
        assert report["swap_pause_ms"] >= 0.0
        got_long, = long_req.wait(timeout=60)
        np.testing.assert_array_equal(got_long, ref1_v1)   # OLD weights
        got2, = server.submit_generate(p2,
                                       max_new_tokens=8).wait(timeout=60)
        ref2_v2 = gen.generate([p2], max_new_tokens=8, seed=0)[0]
        np.testing.assert_array_equal(got2, ref2_v2)       # NEW weights
        assert 7 in got2                 # the steering is visible
    finally:
        server.stop()


# ------------------------------------------------------- hedged clients

def test_hedged_infer_wins_and_dedups(tmp_path, fault_points):
    """A stalled reply triggers the hedge after the configured delay;
    the twin wins, the pair executes once (request-id dedup), and the
    loser is cancelled best-effort."""
    path = _save_mlp(tmp_path)
    server = InferenceServer(path, batch_timeout_ms=1.0).start()
    x = RNG.standard_normal((1, 8)).astype(np.float32)
    # warm (compile) BEFORE the hedging client exists: under full-suite
    # load the first reply's compile can exceed hedge_ms, which would
    # fire a spurious hedge and flake the hedges==0 assertion
    server.infer({"x": x})
    c = Client(server.endpoint, hedge_ms=150.0)
    try:
        want, = c.infer({"x": x})        # warm path; no hedge
        assert c.hedge_stats()["hedges"] == 0
        with fault_points.fault_injection(
                "serving.handle",
                exc=lambda pt, ctx: time.sleep(1.5), times=1):
            t0 = time.monotonic()
            got, = c.infer({"x": x})
            dt = time.monotonic() - t0
        np.testing.assert_array_equal(got, want)
        assert dt < 1.4                  # the hedge won, not the stall
        assert c.hedge_stats() == {"hedges": 1, "hedge_wins": 1,
                                   "budget_suppressed": 0,
                                   "observed": 2}
        # once the stalled primary resumes it ATTACHES to the hedged
        # twin's (completed) request: a dedup hit, not a 2nd execution
        assert _wait_until(
            lambda: server.stats()["hedge_dedup_hits"] >= 1, timeout=5)
        # warm + client pair-executed-once: one completion each, not 4
        assert server.stats()["requests_completed"] == 3
    finally:
        c.close()
        server.stop()


def test_cancel_op_reclaims_inflight_request(tmp_path, fault_points):
    path = _save_mlp(tmp_path)
    server = InferenceServer(path, max_batch_size=1,
                             batch_timeout_ms=1.0).start()
    try:
        def slow(point, ctx):
            time.sleep(0.3)
            return None
        with fault_points.fault_injection("serving.execute", exc=slow,
                                          times=-1):
            x = RNG.standard_normal((1, 8)).astype(np.float32)
            blocker = server.submit({"x": x})    # keeps the engine busy
            victim = server._dedup(
                "rid-x", lambda: server.submit({"x": x}))[0]
            with Client(server.endpoint) as c:
                assert c.cancel("rid-x") is True
                assert c.cancel("rid-x") is False     # already done
                assert c.cancel("never-seen") is False
            with pytest.raises(serving.RequestCancelledError):
                victim.wait(timeout=5)
            blocker.wait(timeout=10)
        assert server.stats()["requests_cancelled"] == 1
    finally:
        server.stop()


# ------------------------------------------------------------------ soak

@pytest.mark.slow
def test_soak_chaos_mixed_traffic(tmp_path, fault_points):
    """Acceptance soak: concurrent infer+generate clients under seeded
    fault injection on every serving stage — every call terminates with
    a result or a TYPED error (no hangs, no silent drops), correct
    results stay bitwise-correct, loop restarts are observed, a
    mid-soak reload_weights completes with zero failures attributable
    to the swap, and the final drain leaves zero in-flight rows."""
    from paddle_tpu.inference import AnalysisConfig, AnalysisPredictor
    path = _save_mlp(tmp_path)
    cfg, gmain, gexe, gscope, gen = _tiny_gpt()
    # the server serves BOTH engines, so the reload checkpoint must
    # carry both param sets — save_params into the shared dir preserves
    # the MLP's manifest entries (the PR-5 shared-dir fix)
    with fluid.scope_guard(gscope):
        fluid.io.save_params(gexe, os.path.join(path, "ckpt_v1"),
                             main_program=gmain)
    pred = AnalysisPredictor(AnalysisConfig(path))
    server = InferenceServer(path, generator=gen, decode_slots=4,
                             max_batch_size=8, batch_timeout_ms=5.0,
                             queue_depth=64, loop_watchdog_s=5.0)
    server.supervisor.poll_s = 0.05
    server.start()
    prompts = [RNG.integers(1, cfg.vocab_size, n).astype(np.int32)
               for n in (4, 6, 9)]
    gen_refs = [gen.generate([p], max_new_tokens=6, seed=0)[0]
                for p in prompts]

    stop_at = time.monotonic() + 8.0
    ok, typed, wrong, untyped = [0], [0], [], []
    lock = threading.Lock()

    def worker(wid):
        lrng = np.random.default_rng(wid)
        my_pred = pred.clone()
        with Client(server.endpoint) as c:
            while time.monotonic() < stop_at:
                try:
                    if wid % 3 == 0:     # generation traffic
                        k = int(lrng.integers(0, len(prompts)))
                        out = c.generate(prompts[k], max_new_tokens=6,
                                         deadline_ms=30000.0)
                        good = np.array_equal(out, gen_refs[k])
                    else:                # infer traffic
                        r = int(lrng.choice([1, 1, 2, 4]))
                        x = lrng.standard_normal((r, 8)) \
                            .astype(np.float32)
                        out, = c.infer({"x": x}, deadline_ms=20000.0)
                        good = np.array_equal(out, my_pred.run([x])[0])
                    with lock:
                        if good:
                            ok[0] += 1
                        else:
                            wrong.append(wid)
                except TYPED_ERRORS:
                    with lock:
                        typed[0] += 1
                except Exception as e:  # noqa: BLE001 — the soak's point
                    with lock:
                        untyped.append(repr(e))

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(9)]
    # seeded, low-probability chaos across EVERY serving stage. NOTE:
    # wire faults are excluded for infer workers' correctness check
    # simplicity — transport errors surface as ConnectionError (typed)
    points = {
        "serving.admit": {"p": 0.01},
        "serving.queue": {"p": 0.002},           # loop crashes+restarts
        "serving.execute": {"p": 0.02},
        "serving.compile": {"p": 0.01},
        "serving.decode_step": {"p": 0.01},
        "serving.slot_insert": {"p": 0.005},
        "serving.prefill": {"p": 0.01},
        "serving.handle": {"p": 0.01},
        "wire.send_frame": {"p": 0.002},
        "wire.recv_frame": {"p": 0.002},
    }
    with chaos(points, seed=1234) as monkey:
        for t in threads:
            t.start()
        # mid-soak hot reload: SAME weights (v1 bytes) so every
        # correctness reference stays valid — the swap machinery is
        # what's under test, and any request failure it caused would
        # show up in wrong/untyped
        time.sleep(2.5)
        report = server.reload_weights(os.path.join(path, "ckpt_v1"),
                                       timeout=60)
        assert report["weights_version"] == 2
        for t in threads:
            t.join(120)
        assert not any(t.is_alive() for t in threads), "worker hung"
    assert not wrong, f"silent wrong results from workers {wrong[:5]}"
    assert not untyped, f"untyped errors escaped: {untyped[:5]}"
    assert ok[0] > 50, (ok[0], typed[0])
    assert monkey.total_fired() > 0      # chaos actually bit
    st = server.stats()
    report = server.drain(timeout=60)
    assert report["drained"] and report["remaining"] == 0
    if server.decode_batcher is not None:
        assert server.decode_batcher.inflight() == 0
    # ledger: everything admitted is accounted for, and if a loop died
    # it was restarted (queue faults make that probable, not certain)
    assert st["requests_admitted"] >= st["requests_completed"]
    if monkey.fired.get("serving.queue"):
        assert st["loop_restarts"] >= 1


# --------------------------------------------- review-hardening guards

def test_concurrent_swap_requests_fail_fast():
    """One reload at a time: a swap requested while another is pending
    fails immediately instead of silently replacing it."""
    from paddle_tpu.serving import DecodeBatcher, RequestQueue

    class _Engine:
        slots = 2
        max_len = 64

        def reset(self):
            pass

    q = RequestQueue(max_depth=4)
    db = DecodeBatcher.__new__(DecodeBatcher)
    DecodeBatcher.__init__(db, q, _Engine(), watchdog_s=0)
    applied = []
    # loop not running: first swap applies inline
    h1 = db.request_swap(lambda: applied.append(1))
    assert h1.wait(timeout=1) is not None or applied == [1]
    # park a fake pending swap, then a second request must fail fast
    db._swap = serving.SwapHandle(lambda: None)
    h3 = db.request_swap(lambda: applied.append(3))
    with pytest.raises(ServingError, match="already pending"):
        h3.wait(timeout=1)
    assert applied == [1]
    db._swap = None


def test_bad_request_reply_maps_to_typed_client_error(tmp_path):
    """etype BadRequest raises the typed BadRequestError client-side —
    input refusals stay distinguishable from InternalServerError."""
    from paddle_tpu.serving import BadRequestError, InternalServerError
    path = _save_mlp(tmp_path)
    server = InferenceServer(path, batch_timeout_ms=1.0).start()
    try:
        with Client(server.endpoint) as c:
            with pytest.raises(BadRequestError, match="missing feeds"):
                c.infer({"wrong": np.zeros((1, 8), np.float32)})
            assert not isinstance(
                BadRequestError("x"), InternalServerError)
    finally:
        server.stop()
