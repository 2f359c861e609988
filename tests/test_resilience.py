"""Fault-tolerant runtime tests: checkpoint integrity (manifest + atomic
rename + CheckpointCorruptError), RPC retry/backoff/deadline + circuit
breaker, wire truncation diagnostics, the FLAGS_check_nan_inf non-finite
guard with skip_nonfinite_steps rollback, and the watchdog / fault
injection hooks (reference lineage: gRPC FLAGS_rpc_deadline semantics,
nan_inf_utils_detail.cc, TF atomic checkpoint rename)."""
import os
import socket
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.resilience import (
    CheckpointCorruptError, CircuitBreaker, CircuitOpenError,
    NonFiniteError, RpcDeadlineError, WatchdogTimeout, retry_call,
    run_with_watchdog, watchdog,
)

_RPC_FLAG_DEFAULTS = {
    "FLAGS_rpc_deadline": 150.0, "FLAGS_rpc_retry_times": 3,
    "FLAGS_rpc_retry_base_backoff": 0.05,
    "FLAGS_rpc_circuit_break_failures": 3,
    "FLAGS_rpc_circuit_reset_secs": 5.0,
}


@pytest.fixture
def fast_rpc_flags():
    fluid.set_flags({"FLAGS_rpc_deadline": 1.0,
                     "FLAGS_rpc_retry_times": 2,
                     "FLAGS_rpc_retry_base_backoff": 0.01,
                     "FLAGS_rpc_circuit_break_failures": 3,
                     "FLAGS_rpc_circuit_reset_secs": 5.0})
    yield
    fluid.set_flags(_RPC_FLAG_DEFAULTS)


def _free_ep():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    ep = f"127.0.0.1:{s.getsockname()[1]}"
    s.close()
    return ep


def _build_mlp(seed=7):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [-1, 8], "float32")
        y = fluid.data("y", [-1, 1], "float32")
        h = layers.fc(x, 16, act="relu")
        pred = layers.fc(h, 1)
        loss = layers.mean(layers.square(pred - y))
        fluid.optimizer.AdamOptimizer(learning_rate=0.05).minimize(loss)
    return main, startup, loss, pred


def _batch(i, nan=False):
    rng = np.random.RandomState(i)
    x = rng.randn(16, 8).astype(np.float32)
    y = x[:, :1] * 2.0 + 1.0
    if nan:
        x[3, 2] = np.nan
    return {"x": x, "y": y}


# ---------------------------------------------------------------------------
# checkpoint integrity
# ---------------------------------------------------------------------------

def test_corrupt_checkpoint_byte_rejected(tmp_path):
    """A flipped byte in a saved parameter file must raise
    CheckpointCorruptError naming that file, not silently load."""
    ckpt = str(tmp_path / "ckpt")
    main, startup, loss, _ = _build_mlp()
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=_batch(0), fetch_list=[loss])
        fluid.save_persistables(exe, ckpt, main_program=main)

    victim = next(f for f in sorted(os.listdir(ckpt))
                  if f.endswith(".npy"))
    path = os.path.join(ckpt, victim)
    blob = bytearray(open(path, "rb").read())
    blob[-1] ^= 0xFF
    with open(path, "wb") as f:
        f.write(bytes(blob))

    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        with pytest.raises(CheckpointCorruptError) as ei:
            fluid.load_persistables(exe, ckpt, main_program=main)
    assert victim in str(ei.value)
    assert ei.value.path == path


def test_truncated_checkpoint_rejected(tmp_path):
    """Truncation (crash mid-write made visible) is caught by the size
    check before hashing."""
    ckpt = str(tmp_path / "ckpt")
    main, startup, loss, _ = _build_mlp()
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.save_persistables(exe, ckpt, main_program=main)
    victim = next(f for f in sorted(os.listdir(ckpt))
                  if f.endswith(".npy"))
    path = os.path.join(ckpt, victim)
    blob = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(blob[:len(blob) // 2])
    with fluid.scope_guard(fluid.Scope()):
        with pytest.raises(CheckpointCorruptError, match="truncated"):
            fluid.load_persistables(exe, ckpt, main_program=main)


def test_load_vars_aggregates_all_missing(tmp_path):
    """Missing variable files are reported in ONE error listing every
    absent name, and the scope is left untouched (no partial restore)."""
    ckpt = str(tmp_path / "ckpt")
    main, startup, loss, _ = _build_mlp()
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.save_params(exe, ckpt, main_program=main)
        params = sorted(p.name for p in main.all_parameters())
        gone = params[:2]
        for name in gone:
            os.remove(os.path.join(ckpt, name.replace("/", "%2F") + ".npy"))
        # manifest knows the files are missing — remove it to exercise the
        # aggregation path rather than the integrity path
        os.remove(os.path.join(ckpt, "_manifest.json"))
        before = {n: np.asarray(scope.find_var(n)).copy() for n in params}
        with pytest.raises(RuntimeError) as ei:
            fluid.load_params(exe, ckpt, main_program=main)
        msg = str(ei.value)
        assert all(name in msg for name in gone), msg
        assert "2 variable(s)" in msg
        for n in params:   # nothing was clobbered
            np.testing.assert_array_equal(
                np.asarray(scope.find_var(n)), before[n])


def test_checkpoint_saver_retention_async_and_restore(tmp_path):
    d = str(tmp_path / "saver")
    main, startup, loss, _ = _build_mlp()
    exe = fluid.Executor()
    saver = fluid.CheckpointSaver(d, max_to_keep=2, prefix="ckpt-")
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        for i in range(3):
            exe.run(main, feed=_batch(i), fetch_list=[loss])
            assert saver.save(exe, main_program=main) == i
        no = saver.save_async(exe, main_program=main)
        saver.wait()
        assert no == 3
    # retention pruned 0 and 1
    assert saver.checkpoint_numbers() == [2, 3]
    params = [p.name for p in main.all_parameters()]
    want = {n: np.asarray(scope.find_var(n)) for n in params}
    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        assert saver.restore(exe, main_program=main) == 3
        for n in params:
            np.testing.assert_array_equal(
                np.asarray(scope2.find_var(n)), want[n])


def test_checkpoint_saver_async_error_surfaces(tmp_path, fault_points):
    """A background save that dies (disk full, injected here) must
    re-raise from wait(), not vanish."""
    main, startup, loss, _ = _build_mlp()
    exe = fluid.Executor()
    saver = fluid.CheckpointSaver(str(tmp_path / "s"), max_to_keep=None)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        with fault_points.fault_injection(
                "io.fsync_write", exc=OSError("disk full"), times=1):
            saver.save_async(exe, main_program=main)
            with pytest.raises(OSError, match="disk full"):
                saver.wait()


def test_checkpoint_saver_concurrent_async_distinct_numbers(tmp_path):
    """Back-to-back save_async without an intervening wait() must pick
    distinct checkpoint numbers (no staging-dir collision)."""
    main, startup, loss, _ = _build_mlp()
    exe = fluid.Executor()
    saver = fluid.CheckpointSaver(str(tmp_path / "s"), max_to_keep=None)
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        nos = [saver.save_async(exe, main_program=main) for _ in range(3)]
        saver.wait()
    assert nos == [0, 1, 2]
    assert saver.checkpoint_numbers() == [0, 1, 2]
    for n in nos:
        fluid.io.verify_checkpoint(str(tmp_path / "s" / f"{saver.prefix}{n}"))


def test_load_verifies_manifest(tmp_path):
    """fluid.load hash-checks .pdparams before touching the scope."""
    main, startup, _, _ = _build_mlp()
    exe = fluid.Executor()
    base = str(tmp_path / "m" / "model")
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        fluid.save(main, base)
    path = base + ".pdparams"
    blob = bytearray(open(path, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    open(path, "wb").write(bytes(blob))
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        with pytest.raises(CheckpointCorruptError, match="pdparams"):
            fluid.load(main, base)


def test_torn_inference_model_rejected(tmp_path):
    """A truncated __model__ surfaces as CheckpointCorruptError, not a
    JSONDecodeError after params already restored."""
    main, startup, _, pred = _build_mlp()
    exe = fluid.Executor()
    d = str(tmp_path / "inf")
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        fluid.io.save_inference_model(d, ["x"], [pred], exe,
                                      main_program=main)
        path = os.path.join(d, "__model__")
        blob = open(path, "rb").read()
        open(path, "wb").write(blob[:len(blob) // 2])
        with pytest.raises(CheckpointCorruptError, match="__model__"):
            fluid.io.load_inference_model(d, exe)


def test_fleet_checkpoint_corruption_detected(tmp_path):
    """fleet save_checkpoint -> corrupt a byte -> load_checkpoint raises
    CheckpointCorruptError (integration over CheckpointSaver)."""
    from paddle_tpu.incubate.fleet.base.role_maker import (
        Role, UserDefinedRoleMaker)
    from paddle_tpu.incubate.fleet.collective import (
        Collective, TrainStatus)

    fleet_obj = Collective()
    fleet_obj.init(UserDefinedRoleMaker(current_id=0, role=Role.WORKER,
                                        worker_num=1))
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 5
    with fluid.program_guard(main, startup):
        x = layers.data("x", [8, 4], dtype="float32")
        y = layers.data("y", [8, 1], dtype="float32")
        loss = layers.mean(layers.square_error_cost(
            layers.fc(x, 1), y))
        fleet_obj.distributed_optimizer(
            fluid.optimizer.SGD(0.1)).minimize(loss)
    exe = fluid.Executor()
    path = str(tmp_path / "fleet_ckpt")
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        no = fleet_obj.save_checkpoint(exe, path, TrainStatus(1),
                                       main_program=main)
    ckpt = os.path.join(path, f"__paddle_checkpoint__{no}")
    victim = next(f for f in sorted(os.listdir(ckpt))
                  if f.endswith(".npy"))
    with open(os.path.join(ckpt, victim), "r+b") as f:
        f.seek(-1, 2)
        b = f.read(1)
        f.seek(-1, 2)
        f.write(bytes([b[0] ^ 0xFF]))
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        with pytest.raises(CheckpointCorruptError):
            fleet_obj.load_checkpoint(exe, path, main_program=main)


# ---------------------------------------------------------------------------
# RPC retry / deadline / circuit breaker
# ---------------------------------------------------------------------------

def test_retry_call_recovers_from_transient_failures():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise ConnectionError("transient")
        return "ok"

    assert retry_call(flaky, deadline=5.0, base_backoff=0.01) == "ok"
    assert calls["n"] == 3


def test_retry_call_deadline_raises_typed_error():
    def dead():
        raise ConnectionError("nope")

    t0 = time.monotonic()
    with pytest.raises(RpcDeadlineError) as ei:
        retry_call(dead, deadline=0.3, base_backoff=0.05,
                   endpoint="1.2.3.4:5")
    assert time.monotonic() - t0 < 2.0
    assert ei.value.endpoint == "1.2.3.4:5"
    assert "1.2.3.4:5" in str(ei.value)


def test_circuit_breaker_state_machine():
    br = CircuitBreaker("ep", failure_threshold=2, reset_timeout=0.2)
    assert br.state == "closed"
    br.before_call(); br.record_failure()
    br.before_call(); br.record_failure()
    assert br.state == "open"
    with pytest.raises(CircuitOpenError):
        br.before_call()
    time.sleep(0.25)
    assert br.state == "half-open"
    br.before_call()            # the probe is admitted…
    br.record_success()
    assert br.state == "closed"


def test_dead_ps_deadline_then_breaker_fast_fail(fast_rpc_flags):
    """Kill a PS mid-push: the next push retries then raises
    RpcDeadlineError within the deadline; the breaker then opens so
    subsequent calls fail fast instead of re-paying the deadline."""
    from paddle_tpu.distributed import ParameterServer, PSClient

    ep = _free_ep()
    server = ParameterServer(ep, trainers=1, sync_mode=False)
    server.tables["w"] = np.zeros(4, np.float32)
    ready = threading.Event()
    server.serve(ready_event=ready, block=False)
    ready.wait(10)

    cli = PSClient()
    cli.push_dense(ep, "w", np.ones(4, np.float32))        # healthy push
    cli.stop_servers([ep])
    time.sleep(0.5)                                        # accept loop exits

    t0 = time.monotonic()
    with pytest.raises(RpcDeadlineError):
        cli.push_dense(ep, "w", np.ones(4, np.float32))
    elapsed = time.monotonic() - t0
    assert elapsed < 5.0, f"no indefinite hang, took {elapsed:.1f}s"

    assert cli.breaker_state(ep) == "open"
    t0 = time.monotonic()
    with pytest.raises(CircuitOpenError):
        cli.pull_dense(ep, "w")
    assert time.monotonic() - t0 < 0.2, "breaker must fail fast"


def test_unresponsive_ps_hits_deadline(fast_rpc_flags):
    """An endpoint that ACCEPTS but never replies (hung server) trips the
    io timeout and surfaces RpcDeadlineError within the deadline."""
    from paddle_tpu.distributed import PSClient

    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(4)
    ep = f"127.0.0.1:{srv.getsockname()[1]}"
    accepted = []
    t = threading.Thread(
        target=lambda: accepted.append(srv.accept()), daemon=True)
    t.start()
    try:
        cli = PSClient()
        t0 = time.monotonic()
        with pytest.raises(RpcDeadlineError):
            cli.pull_dense(ep, "w")
        assert time.monotonic() - t0 < 4.0   # rpc_deadline=1.0 + slack
    finally:
        srv.close()
        for conn, _ in accepted:
            conn.close()


def test_fault_injected_send_retries_transparently(fast_rpc_flags,
                                                  fault_points):
    """One injected transport failure on the wire: the client retries and
    the call still succeeds (the conftest fault-injection fixture)."""
    from paddle_tpu.distributed import ParameterServer, PSClient

    ep = _free_ep()
    server = ParameterServer(ep, trainers=1, sync_mode=False)
    server.tables["w"] = np.zeros(4, np.float32)
    ready = threading.Event()
    server.serve(ready_event=ready, block=False)
    ready.wait(10)
    try:
        cli = PSClient()
        with fault_points.fault_injection(
                "wire.send_frame", exc=ConnectionError, times=1) as spec:
            val = np.asarray(cli.pull_dense(ep, "w"))
        assert spec["fired"] == 1
        np.testing.assert_allclose(val, np.zeros(4))
    finally:
        cli.stop_servers([ep])


def test_push_dense_replay_not_double_applied(fast_rpc_flags,
                                              fault_points):
    """A push whose REPLY is lost gets retried (at-least-once on the
    wire) but the server dedups the (uid, seq) tag, so the gradient is
    applied exactly once — sync-mode accumulation must hold one grad."""
    from paddle_tpu.distributed import ParameterServer, PSClient

    ep = _free_ep()
    server = ParameterServer(ep, trainers=1, sync_mode=True)
    server.tables["w"] = np.zeros(4, np.float32)
    ready = threading.Event()
    server.serve(ready_event=ready, block=False)
    ready.wait(10)
    try:
        cli = PSClient()
        # the failure fires on the client's recv of the reply — AFTER the
        # server has already accumulated the grad
        with fault_points.fault_injection(
                "wire.recv_frame", exc=ConnectionResetError,
                times=1) as spec:
            cli.push_dense(ep, "w", np.ones(4, np.float32))
        assert spec["fired"] == 1
        assert len(server._grad_acc["w"]) == 1, \
            "retried push was double-accumulated"
    finally:
        cli.stop_servers([ep])


def test_stalled_endpoint_does_not_block_healthy_one(fast_rpc_flags):
    """Per-endpoint IO locks: a thread stuck waiting on a silent pserver
    must not serialize RPCs to a healthy one."""
    from paddle_tpu.distributed import ParameterServer, PSClient

    silent = socket.socket()
    silent.bind(("127.0.0.1", 0))
    silent.listen(4)
    dead_ep = f"127.0.0.1:{silent.getsockname()[1]}"

    ep = _free_ep()
    server = ParameterServer(ep, trainers=1, sync_mode=False)
    server.tables["w"] = np.arange(4, dtype=np.float32)
    ready = threading.Event()
    server.serve(ready_event=ready, block=False)
    ready.wait(10)
    try:
        cli = PSClient()
        started = threading.Event()

        def _stuck():
            started.set()
            with pytest.raises(RpcDeadlineError):
                cli.pull_dense(dead_ep, "w")

        t = threading.Thread(target=_stuck, daemon=True)
        t.start()
        started.wait(5)
        time.sleep(0.1)          # let the stuck thread enter its recv
        t0 = time.monotonic()
        val = np.asarray(cli.pull_dense(ep, "w"))
        assert time.monotonic() - t0 < 0.5, \
            "healthy-endpoint call waited on the dead endpoint's IO"
        np.testing.assert_allclose(val, np.arange(4, dtype=np.float32))
        t.join(10)
    finally:
        silent.close()
        cli.stop_servers([ep])


def test_load_vars_corrupt_rng_extra_raises(tmp_path):
    """A corrupt extra-state file (the RNG key) on a manifest-less
    checkpoint must raise, not silently skip the RNG restore."""
    main, startup, loss, _ = _build_mlp()
    exe = fluid.Executor()
    d = str(tmp_path / "ck")
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        fluid.io.save_persistables(exe, d, main)
    os.remove(os.path.join(d, "_manifest.json"))   # legacy checkpoint
    rng_file = os.path.join(d, "@RNG_KEY@.npy")
    assert os.path.exists(rng_file)
    open(rng_file, "wb").write(b"\x00" * 8)        # not a valid .npy
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        with pytest.raises(RuntimeError, match="unreadable"):
            fluid.io.load_persistables(exe, d, main)


def test_wire_truncation_error_names_peer_and_bytes():
    """A peer dying mid-frame yields a WireError carrying the endpoint
    and expected/received byte counts."""
    from paddle_tpu.distributed.wire import WireTruncationError, recv_frame

    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    port = srv.getsockname()[1]

    def half_frame():
        conn, _ = srv.accept()
        conn.sendall(b"PT01" + b"\x00" * 10)   # 14 of the 44 header bytes
        conn.close()

    t = threading.Thread(target=half_frame, daemon=True)
    t.start()
    try:
        sock = socket.create_connection(("127.0.0.1", port), timeout=5)
        with pytest.raises(WireTruncationError) as ei:
            recv_frame(sock, timeout=5)
        err = ei.value
        assert isinstance(err, ConnectionError)   # transport handlers see it
        assert err.expected == 44 and err.received == 14
        assert err.endpoint == f"127.0.0.1:{port}"
        sock.close()
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# non-finite guard
# ---------------------------------------------------------------------------

def test_check_nan_inf_names_fetched_var():
    main, startup, loss, pred = _build_mlp()
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        bad = _batch(0, nan=True)
        with pytest.raises(NonFiniteError) as ei:
            exe.run(main, feed=bad, fetch_list=[loss],
                    check_nan_inf=True)
        assert ei.value.var_name == loss.name
        assert loss.name in str(ei.value)
        assert isinstance(ei.value, fluid.EnforceNotMet)


def test_check_nan_inf_flag_and_updated_vars():
    """Via FLAGS_check_nan_inf (no per-call arg); with no fetch list the
    guard still catches the poisoned parameter UPDATE."""
    main, startup, loss, _ = _build_mlp()
    exe = fluid.Executor()
    scope = fluid.Scope()
    fluid.set_flags({"FLAGS_check_nan_inf": True})
    try:
        with fluid.scope_guard(scope):
            exe.run(startup)
            with pytest.raises(NonFiniteError) as ei:
                exe.run(main, feed=_batch(0, nan=True))
            assert "updated variable" in str(ei.value)
            assert ei.value.var_name
    finally:
        fluid.set_flags({"FLAGS_check_nan_inf": False})


def test_skip_nonfinite_steps_recovers_loss_curve():
    """A NaN batch under skip_nonfinite_steps is rolled back: params and
    RNG are exactly as before the bad step, so the rest of the run is
    bit-identical to a run that never saw the bad batch."""
    main, startup, loss, _ = _build_mlp()
    exe = fluid.Executor()

    clean = []
    scope_a = fluid.Scope()
    with fluid.scope_guard(scope_a):
        exe.run(startup)
        for i in range(5):
            l, = exe.run(main, feed=_batch(i), fetch_list=[loss])
            clean.append(float(l))

    scope_b = fluid.Scope()
    with fluid.scope_guard(scope_b):
        exe.run(startup)
        for i in range(3):
            exe.run(main, feed=_batch(i), fetch_list=[loss])
        bad, = exe.run(main, feed=_batch(99, nan=True), fetch_list=[loss],
                       skip_nonfinite_steps=True)
        assert not np.isfinite(bad).all()       # the loss WAS non-finite
        resumed = [float(exe.run(main, feed=_batch(i),
                                 fetch_list=[loss])[0])
                   for i in range(3, 5)]
        for p in main.all_parameters():          # nothing got poisoned
            assert np.isfinite(np.asarray(
                scope_b.find_var(p.name))).all()
    np.testing.assert_allclose(resumed, clean[3:], rtol=1e-6)


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------

def test_run_with_watchdog_times_out_and_passes_results():
    with pytest.raises(WatchdogTimeout):
        run_with_watchdog(time.sleep, 0.2, 5.0)
    assert run_with_watchdog(lambda a, b: a + b, 5.0, 2, 3) == 5
    with pytest.raises(ValueError, match="boom"):
        run_with_watchdog(lambda: (_ for _ in ()).throw(ValueError("boom")),
                          5.0)


@pytest.mark.parametrize("ready_after,deadline_in,times_out", [
    (0.0, 5.0, False),      # ready at the first asking
    (0.05, 5.0, False),     # ready after some polls
    (10.0, 0.1, True),      # never ready inside the budget
    (0.0, -0.01, True),     # the budget was spent before the wait began
])
def test_await_ready_polls_on_the_callers_thread(ready_after, deadline_in,
                                                 times_out):
    from paddle_tpu.resilience import await_ready
    t0 = time.perf_counter()
    threads = threading.active_count()

    def ready():
        assert threading.active_count() == threads      # no thread
        return time.perf_counter() - t0 >= ready_after

    if times_out:
        with pytest.raises(WatchdogTimeout, match="a wait"):
            await_ready(ready, t0 + deadline_in, 0.1, "a wait")
        assert time.perf_counter() - t0 < 2.0
    else:
        await_ready(ready, t0 + deadline_in, 5.0, "a wait")
        assert time.perf_counter() - t0 >= ready_after


def test_watchdog_context_aborts_overbudget_block():
    t0 = time.monotonic()
    with pytest.raises(WatchdogTimeout, match="budget"):
        with watchdog(0.3, what="stuck step"):
            time.sleep(10)
    assert time.monotonic() - t0 < 5.0
    with watchdog(5.0):           # under budget: no interference
        time.sleep(0.01)


# ---------------------------------------------------------------------------
# serving resilience primitives (chaos harness, supervised-loop breaker)
# ---------------------------------------------------------------------------

def test_watchdog_bounds_serving_execute(fault_points):
    """run_with_watchdog under the MicroBatcher execute path: a hung
    engine call fails the batch's clients with WatchdogTimeout while
    the loop thread survives (the serving half of the watchdog
    contract)."""
    from paddle_tpu.serving import MicroBatcher, Request, RequestQueue

    calls = []

    def engine(reqs):
        calls.append(len(reqs))
        if len(calls) == 1:
            time.sleep(2.0)          # first batch hangs
        for r in reqs:
            r.set_result([np.zeros(1)])

    q = RequestQueue(max_depth=16)
    mb = MicroBatcher(q, engine, max_batch_size=4, batch_timeout_ms=1.0,
                      watchdog_s=0.2)
    mb.start()
    try:
        hung = q.put(Request({"x": np.zeros((1, 2), np.float32)}))
        with pytest.raises(WatchdogTimeout):
            hung.wait(timeout=5)
        ok = q.put(Request({"x": np.zeros((1, 2), np.float32)}))
        ok.wait(timeout=5)           # the loop survived the hang
        assert mb.alive()
        # the success resets the failure streak, but set_result wakes
        # this thread BEFORE the loop thread performs the reset — poll
        # briefly instead of racing it
        deadline = time.monotonic() + 2.0
        while mb.consecutive_failures and time.monotonic() < deadline:
            time.sleep(0.01)
        assert mb.consecutive_failures == 0   # reset by the success
    finally:
        mb.stop()


class _FakeLoop:
    """Minimal supervised-loop duck type for LoopSupervisor unit tests."""

    def __init__(self):
        self.heartbeat = time.monotonic()
        self.consecutive_failures = 0
        self.restarts = 0
        self._alive = True

    def alive(self):
        return self._alive

    def restart(self, reason=""):
        self.restarts += 1
        self._alive = True
        self.heartbeat = time.monotonic()


def test_circuit_breaker_drives_degraded_state_and_recovery():
    """Repeated loop deaths trip the supervisor's CircuitBreaker into
    the degraded callback; sustained health closes it again."""
    from paddle_tpu.serving import LoopSupervisor

    events = []
    loop = _FakeLoop()
    sup = LoopSupervisor(watchdog_s=5.0, poll_s=0.01,
                         restart_threshold=2, reset_secs=0.2,
                         restart_backoff=0.0,
                         on_degraded=lambda: events.append("degraded"),
                         on_recovered=lambda: events.append("recovered"))
    sup.add("loop", loop)
    now = time.monotonic()
    # two consecutive deaths: threshold 2 -> breaker open -> degraded
    loop._alive = False
    sup._tick(now)
    assert loop.restarts == 1 and events == []
    loop._alive = False
    sup._tick(now + 0.1)
    assert loop.restarts == 2
    assert events == ["degraded"] and sup.degraded
    assert sup.breaker.state in ("open", "half-open")
    # healthy past reset_secs -> breaker closes -> recovered
    loop.heartbeat = now + 1.0
    sup._tick(now + 1.0)
    assert events == ["degraded", "recovered"]
    assert not sup.degraded and sup.breaker.state == "closed"
    assert sup.restarts() == 2


def test_supervisor_counts_engine_failure_streaks():
    """A loop that is alive but fails every batch must also feed the
    breaker (degraded on repeated execute failures, not just crashes)."""
    from paddle_tpu.serving import LoopSupervisor

    events = []
    loop = _FakeLoop()
    sup = LoopSupervisor(watchdog_s=5.0, poll_s=0.01,
                         restart_threshold=2, reset_secs=60.0,
                         on_degraded=lambda: events.append("degraded"))
    sup.add("loop", loop)
    now = time.monotonic()
    for i in range(2):
        loop.heartbeat = now + i
        loop.consecutive_failures = 2       # streak >= threshold
        sup._tick(now + i)
        assert loop.consecutive_failures == 0    # consumed by the tick
    assert events == ["degraded"]
    assert loop.restarts == 0               # no restart: the loop lives


def test_chaos_restores_previously_armed_points(fault_points):
    """chaos() nests over fault_injection without clobbering it."""
    from paddle_tpu.resilience import FaultInjected, chaos, maybe_fail
    with fault_points.fault_injection("pt", exc=ValueError, times=-1):
        with chaos("pt", exc=FaultInjected, times=1):
            with pytest.raises(FaultInjected):
                maybe_fail("pt")
        with pytest.raises(ValueError):      # outer arming restored
            maybe_fail("pt")
