"""Multi-process launcher (reference:
python/paddle/distributed/launch.py:193 — spawns one process per device,
setting the PADDLE_* env contract; launch_ps.py for pserver clusters).

    python -m paddle_tpu.distributed.launch --nproc_per_node=2 train.py
    python -m paddle_tpu.distributed.launch --server_num=1 \
        --worker_num=2 train.py            # parameter-server cluster

Collective workers get PADDLE_TRAINER_ID / PADDLE_TRAINERS_NUM /
PADDLE_TRAINER_ENDPOINTS / PADDLE_CURRENT_ENDPOINT (trainer 0's endpoint
doubles as the jax.distributed coordinator — fleet.init dials it).
PS mode additionally launches PSERVER-role processes with
PADDLE_PSERVERS_IP_PORT_LIST, exactly the env PaddleCloudRoleMaker reads.

One process for each TPU host: a chip belongs to one process at a time,
one process drives every chip of its host through the mesh, and this
launcher does not narrow which chips a child may open. So it starts more
than one child only when the children are pinned off the TPU
(``--device=cpu``, or an inherited ``JAX_PLATFORMS`` that does not name
it); otherwise the second child would fail or hang at its first use of
JAX, and :class:`TPUProcessLimitError` is raised before any child starts.
"""
import argparse
import os
import signal
import socket
import subprocess
import sys


class TPUProcessLimitError(RuntimeError):
    """More than one child was asked for and nothing keeps the children
    off the TPU: all but the first would find the chips taken."""


def _check_tpu_process_limit(args, n_children):
    """Refuse a multi-child launch whose children may open the TPU.
    Judged from ``--device`` and the inherited ``JAX_PLATFORMS`` alone:
    the launcher itself must stay off JAX, or it would hold the chips
    its one child needs."""
    platforms = (args.device or os.environ.get("JAX_PLATFORMS", "")).lower()
    names = [p.strip() for p in platforms.split(",") if p.strip()]
    if n_children > 1 and (not names or "tpu" in names):
        raise TPUProcessLimitError(
            f"refusing to start {n_children} processes that may each open "
            f"the TPU (children's platform: {platforms or 'JAX default'!r}): "
            f"a chip belongs to one process at a time and one process "
            f"drives every chip of its host. Start one process per TPU "
            f"host, or pass --device=cpu for a multi-process CPU run.")


def _free_ports(n, ip="127.0.0.1"):
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind((ip, 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def parse_args(argv=None):
    p = argparse.ArgumentParser("paddle_tpu.distributed.launch")
    p.add_argument("--nproc_per_node", type=int, default=None,
                   help="collective worker processes on this node")
    p.add_argument("--node_ip", default="127.0.0.1")
    p.add_argument("--started_port", type=int, default=None)
    p.add_argument("--server_num", type=int, default=0,
                   help="parameter-server processes (PS mode)")
    p.add_argument("--worker_num", type=int, default=0,
                   help="trainer processes (PS mode)")
    p.add_argument("--log_dir", default=None)
    p.add_argument("--device", default=None,
                   help="pin the JAX platform for children (cpu/tpu/...). "
                        "The launcher owns platform hygiene: children must "
                        "not inherit a JAX_PLATFORMS that names a backend "
                        "their environment can't provide.")
    p.add_argument("training_script")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def _child_env(args, **overrides):
    """Child env = parent env + PADDLE_* contract, with the launcher owning
    platform hygiene: --device pins JAX_PLATFORMS so children never inherit
    a backend name their own environment can't provide (reference launcher
    env plumbing: python/paddle/distributed/launch.py:193)."""
    env = dict(os.environ, **{k: str(v) for k, v in overrides.items()})
    if args.device:
        env["JAX_PLATFORMS"] = args.device
    return env


def _spawn(cmd, env, log_dir, tag):
    if log_dir:
        os.makedirs(log_dir, exist_ok=True)
        out = open(os.path.join(log_dir, f"{tag}.log"), "wb")
    else:
        out = None
    return subprocess.Popen(cmd, env=env, stdout=out, stderr=out)


def launch(args):
    cmd_base = [sys.executable, "-u", args.training_script] + \
        args.training_script_args
    procs = []
    if args.server_num or args.worker_num:
        # ---- PS cluster ----
        n_servers = args.server_num or 1
        n_workers = args.worker_num or 1
        _check_tpu_process_limit(args, n_servers + n_workers)
        sports = _free_ports(n_servers, args.node_ip)
        server_eps = ",".join(f"{args.node_ip}:{p}" for p in sports)
        for i in range(n_servers):
            env = _child_env(
                args,
                TRAINING_ROLE="PSERVER",
                PADDLE_PSERVERS_IP_PORT_LIST=server_eps,
                PADDLE_CURRENT_ENDPOINT=f"{args.node_ip}:{sports[i]}",
                PADDLE_TRAINERS_NUM=n_workers)
            procs.append(_spawn(cmd_base, env, args.log_dir, f"server.{i}"))
        for i in range(n_workers):
            env = _child_env(
                args,
                TRAINING_ROLE="TRAINER",
                PADDLE_PSERVERS_IP_PORT_LIST=server_eps,
                PADDLE_TRAINER_ID=i,
                PADDLE_TRAINERS_NUM=n_workers)
            procs.append(_spawn(cmd_base, env, args.log_dir, f"worker.{i}"))
    else:
        # ---- collective ----
        n = args.nproc_per_node or 1
        _check_tpu_process_limit(args, n)
        ports = ([args.started_port + i for i in range(n)]
                 if args.started_port else _free_ports(n, args.node_ip))
        eps = ",".join(f"{args.node_ip}:{p}" for p in ports)
        for i in range(n):
            env = _child_env(
                args,
                TRAINING_ROLE="TRAINER",
                PADDLE_TRAINER_ID=i,
                PADDLE_TRAINERS_NUM=n,
                PADDLE_TRAINER_ENDPOINTS=eps,
                PADDLE_CURRENT_ENDPOINT=f"{args.node_ip}:{ports[i]}",
                FLAGS_selected_tpus=i)
            procs.append(_spawn(cmd_base, env, args.log_dir, f"trainer.{i}"))

    def _terminate(signum=None, frame=None):
        for p in procs:
            if p.poll() is None:
                p.terminate()

    signal.signal(signal.SIGINT, _terminate)
    signal.signal(signal.SIGTERM, _terminate)
    # poll ALL children: the first nonzero exit tears the cluster down
    # (a crashed trainer must not leave the launcher blocked on a pserver
    # whose stop message will never arrive)
    import time
    rc = 0
    live = list(procs)
    while live:
        still = []
        for p in live:
            code = p.poll()
            if code is None:
                still.append(p)
            elif code != 0:
                rc = rc or code
        if rc:
            _terminate()
            for p in procs:
                p.wait()
            return rc
        live = still
        if live:
            time.sleep(0.2)
    return rc


if __name__ == "__main__":
    sys.exit(launch(parse_args()))
