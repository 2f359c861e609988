"""Fleet API: role makers, collective 2-process parity via the launcher,
PS-mode fleet lifecycle (reference pattern: test_dist_fleet_base.py)."""
import json
import os
import subprocess
import sys
import tempfile

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def test_role_makers():
    from paddle_tpu.incubate.fleet.base.role_maker import (
        PaddleCloudRoleMaker, Role, UserDefinedRoleMaker)

    env = {"TRAINING_ROLE": "TRAINER", "PADDLE_TRAINER_ID": "1",
           "PADDLE_TRAINERS_NUM": "2",
           "PADDLE_TRAINER_ENDPOINTS": "127.0.0.1:7000,127.0.0.1:7001"}
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        rm = PaddleCloudRoleMaker()
        assert rm.is_worker() and not rm.is_server()
        assert rm.worker_index() == 1 and rm.worker_num() == 2
        assert rm.get_current_endpoint() == "127.0.0.1:7001"
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    rm = UserDefinedRoleMaker(current_id=0, role=Role.SERVER,
                              server_endpoints=["127.0.0.1:7100"])
    assert rm.is_server() and rm.get_current_endpoint() == "127.0.0.1:7100"


def test_launcher_refuses_two_children_that_may_open_the_tpu(monkeypatch):
    """A chip belongs to one process at a time and the launcher does not
    narrow which chips a child opens: more than one child needs the
    children pinned off the TPU."""
    from paddle_tpu.distributed import launch
    two = ["--nproc_per_node=2", "train.py"]
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(launch.TPUProcessLimitError, match="--device=cpu"):
        launch.launch(launch.parse_args(two))
    with pytest.raises(launch.TPUProcessLimitError):
        launch.launch(launch.parse_args(["--device=tpu"] + two))
    with pytest.raises(launch.TPUProcessLimitError):
        launch.launch(launch.parse_args(
            ["--server_num=1", "--worker_num=1", "train.py"]))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    launch._check_tpu_process_limit(launch.parse_args(two), 2)
    monkeypatch.delenv("JAX_PLATFORMS")
    launch._check_tpu_process_limit(
        launch.parse_args(["--nproc_per_node=1", "train.py"]), 1)


@pytest.mark.skipif(
    os.environ.get("PADDLE_TPU_MULTIPROC_TESTS") != "1",
    reason="this jaxlib's CPU backend cannot execute cross-process "
           "computations (XlaRuntimeError: \"Multiprocess computations "
           "aren't implemented on the CPU backend\" from the jitted "
           "all-reduce step) — set PADDLE_TPU_MULTIPROC_TESTS=1 to run "
           "on a backend with multiprocess collectives (real TPU pod or "
           "a jaxlib built with CPU collectives)")
def test_fleet_collective_two_process_parity():
    """2 worker processes through the launcher: both ranks' losses are
    identical (dp all-reduce over jax.distributed) and match a local
    full-batch run (mean-loss over the global batch == local run)."""
    fd, outpat = tempfile.mkstemp(suffix=".json")
    os.close(fd)
    outpat = outpat.replace(".json", ".%r.json")
    fd, argpath = tempfile.mkstemp(suffix=".json")
    os.close(fd)
    steps = 4
    with open(argpath, "w") as f:
        json.dump({"steps": steps, "out": outpat}, f)
    pp = [REPO] + ([os.environ["PYTHONPATH"]]
                   if os.environ.get("PYTHONPATH") else [])
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(pp))
    env.pop("XLA_FLAGS", None)   # children provision their own 1-dev cpu
    # --device=cpu: the launcher owns platform hygiene, and refuses to
    # start two children that could both reach for a TPU
    rc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node=2", "--device=cpu",
         os.path.join(HERE, "dist_fleet_runner.py"), argpath],
        env=env, capture_output=True, timeout=420)
    assert rc.returncode == 0, rc.stderr.decode()[-3000:]
    res = []
    for r in range(2):
        with open(outpat.replace("%r", str(r))) as f:
            res.append(json.load(f))
    np.testing.assert_allclose(res[0]["losses"], res[1]["losses"],
                               rtol=1e-5)
    assert res[0]["losses"][-1] < res[0]["losses"][0]

    # local full-batch baseline with the same init and data
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.framework.initializer import NumpyArrayInitializer
    rng = np.random.default_rng(77)
    w1 = rng.standard_normal((8, 16)).astype(np.float32) * 0.3
    w2 = rng.standard_normal((16, 1)).astype(np.float32) * 0.3
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [-1, 8], dtype="float32")
        y = layers.data("y", [-1, 1], dtype="float32")
        h = layers.fc(x, 16, act="tanh",
                      param_attr=fluid.ParamAttr(
                          name="w1", initializer=NumpyArrayInitializer(w1)),
                      bias_attr=False)
        pred = layers.fc(h, 1,
                         param_attr=fluid.ParamAttr(
                             name="w2",
                             initializer=NumpyArrayInitializer(w2)),
                         bias_attr=False)
        loss = layers.mean(layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor()
    scope = fluid.Scope()
    local = []
    with fluid.scope_guard(scope):
        exe.run(startup)
        for step in range(steps):
            brng = np.random.default_rng(500 + step)
            xg = brng.standard_normal((8, 8)).astype(np.float32)
            yg = (xg[:, :1] * 0.7 - 0.2).astype(np.float32)
            l, = exe.run(main, feed={"x": xg, "y": yg}, fetch_list=[loss])
            local.append(float(l))
    # rank losses are per-local-half; the global mean loss equals the local
    # full-batch loss only when halves average — assert the first step's
    # mean matches and the curves track
    mean_dist = np.mean([res[0]["losses"], res[1]["losses"]], axis=0)
    np.testing.assert_allclose(mean_dist, local, rtol=2e-4, atol=1e-6)


def test_fleet_ps_mode_smoke():
    """PS fleet lifecycle in one process: server in a thread, worker
    trains through fleet.main_program."""
    import threading
    import socket

    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.incubate.fleet.base.role_maker import (
        Role, UserDefinedRoleMaker)
    from paddle_tpu.incubate.fleet.parameter_server import (
        ParameterServerFleet)

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    ep = f"127.0.0.1:{s.getsockname()[1]}"
    s.close()

    def build(fleet_obj, role):
        fleet_obj.init(role)
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 5
        with fluid.program_guard(main, startup):
            x = layers.data("x", [-1, 4], dtype="float32")
            y = layers.data("y", [-1, 1], dtype="float32")
            # explicit param names: server and worker build in ONE process
            # here, so auto unique_name counters would diverge
            pred = layers.fc(
                x, 1,
                param_attr=fluid.ParamAttr(
                    name="ps_smoke.w",
                    initializer=fluid.initializer.ConstantInitializer(0.1)),
                bias_attr=fluid.ParamAttr(
                    name="ps_smoke.b",
                    initializer=fluid.initializer.ConstantInitializer(0.0)))
            loss = layers.mean(layers.square_error_cost(pred, y))
            opt = fleet_obj.distributed_optimizer(fluid.optimizer.SGD(0.1))
            opt.minimize(loss, startup_program=startup)
        return main, startup, loss

    server_fleet = ParameterServerFleet()
    srole = UserDefinedRoleMaker(current_id=0, role=Role.SERVER,
                                 worker_num=1, server_endpoints=[ep])
    build(server_fleet, srole)
    server_fleet.init_server()
    th = threading.Thread(target=server_fleet.run_server, daemon=True)
    th.start()

    worker_fleet = ParameterServerFleet()
    wrole = UserDefinedRoleMaker(current_id=0, role=Role.WORKER,
                                 worker_num=1, server_endpoints=[ep])
    _, startup, loss = build(worker_fleet, wrole)
    worker_fleet.init_worker()
    exe = fluid.Executor()
    scope = fluid.Scope()
    rng = np.random.default_rng(0)
    xv = rng.standard_normal((16, 4)).astype(np.float32)
    yv = (xv[:, :1] * 0.5).astype(np.float32)
    with fluid.scope_guard(scope):
        exe.run(startup)
        losses = [float(exe.run(worker_fleet.main_program,
                                feed={"x": xv, "y": yv},
                                fetch_list=[loss])[0])
                  for _ in range(6)]
    worker_fleet.stop_worker()
    th.join(timeout=30)
    assert losses[-1] < losses[0], losses
