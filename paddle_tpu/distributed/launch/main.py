"""Launcher: spawn training processes with rendezvous env wiring.

Reference: ``python/paddle/distributed/launch/main.py:23`` + the collective
controller (``controllers/collective.py``) and HTTP/ETCD master
(``controllers/master.py``).

TPU-native model: single-controller SPMD — ONE process per HOST drives all
local chips (the reference spawns one per GPU). So:

- single-node: run the script once with the bootstrap env set (optionally
  N virtual processes for CPU-backend testing via
  ``--nproc_per_node`` > 1, each pinned to a subset via JAX flags).
- multi-node: per node, set ``PADDLE_MASTER`` (the jax.distributed
  coordination service address — the TCPStore/ETCD-master analog),
  ``PADDLE_NNODES``, ``PADDLE_TRAINER_ID``; ``init_parallel_env`` then wires
  ``jax.distributed.initialize`` from these.

Failure watching (reference ``watcher.py``): the launcher polls children and
tears the job down when any exits nonzero — the elastic manager's restart
hook point.
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional

__all__ = ["launch", "main"]


def _parse_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    p = argparse.ArgumentParser(
        prog="paddle_tpu.distributed.launch",
        description="Launch a (multi-host) SPMD training job",
    )
    p.add_argument("--master", default=None, help="coordinator host:port (multi-node)")
    p.add_argument("--nnodes", type=int, default=int(os.environ.get("PADDLE_NNODES", "1")))
    p.add_argument("--rank", "--node_rank", type=int, dest="rank",
                   default=int(os.environ.get("PADDLE_TRAINER_ID", "0")))
    p.add_argument("--nproc_per_node", type=int, default=1,
                   help="processes per host (1 for TPU SPMD; >1 for CPU testing)")
    p.add_argument("--devices", "--gpus", default=None, dest="devices",
                   help="visible device ids (comma separated)")
    p.add_argument("--log_dir", default=None)
    p.add_argument("--run_mode", default="collective", choices=["collective"])
    p.add_argument(
        "--max_restarts", type=int, default=int(os.environ.get("PADDLE_ELASTIC_MAX_RESTARTS", "0")),
        help="elastic fault tolerance: relaunch a failed worker up to N times "
        "(reference elastic manager relaunch, manager.py:251); the child sees "
        "PADDLE_RESTART_COUNT and should resume from its latest checkpoint",
    )
    p.add_argument("training_script", help="script to run")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def _child_env(args: argparse.Namespace, local_rank: int) -> Dict[str, str]:
    from paddle_tpu.core.compile_cache import compile_cache_dir

    env = dict(os.environ)
    # workers share one persistent compile cache (theirs to read: the
    # launcher itself never touches the jax backend — a chip belongs to one
    # process, and it must be the worker)
    env.setdefault("JAX_COMPILATION_CACHE_DIR", compile_cache_dir())
    world = args.nnodes * args.nproc_per_node
    global_rank = args.rank * args.nproc_per_node + local_rank
    env["PADDLE_TRAINER_ID"] = str(global_rank)
    env["PADDLE_TRAINERS_NUM"] = str(world)
    env["PADDLE_NNODES"] = str(args.nnodes)
    env["PADDLE_LOCAL_RANK"] = str(local_rank)
    if args.master:
        env["PADDLE_MASTER"] = args.master
        env["MASTER_ADDR"] = args.master.split(":")[0]
        env["MASTER_PORT"] = args.master.split(":")[-1]
    if args.devices:
        env["TPU_VISIBLE_DEVICES"] = args.devices
        env["CUDA_VISIBLE_DEVICES"] = args.devices  # harmless off-GPU
    return env


def launch(argv: Optional[List[str]] = None) -> int:
    args = _parse_args(argv)
    if args.log_dir:
        os.makedirs(args.log_dir, exist_ok=True)

    def spawn(local_rank: int, restart_count: int = 0) -> subprocess.Popen:
        cmd = [sys.executable, "-u", args.training_script] + args.training_script_args
        stdout = None
        if args.log_dir:
            log_path = os.path.join(args.log_dir, f"workerlog.{local_rank}")
            stdout = open(log_path, "a" if restart_count else "w")
        env = _child_env(args, local_rank)
        env["PADDLE_RESTART_COUNT"] = str(restart_count)
        proc = subprocess.Popen(
            cmd, env=env, stdout=stdout, stderr=subprocess.STDOUT if stdout else None
        )
        proc._local_rank = local_rank  # type: ignore[attr-defined]
        proc._log = stdout  # type: ignore[attr-defined]
        return proc

    def reap(p: subprocess.Popen) -> None:
        if getattr(p, "_log", None) is not None:
            p._log.close()  # type: ignore[attr-defined]

    def terminate_all(procs: List[subprocess.Popen]) -> None:
        for other in procs:
            other.send_signal(signal.SIGTERM)
        for other in procs:
            try:
                other.wait(timeout=10)
            except subprocess.TimeoutExpired:
                other.kill()
            reap(other)

    restart_count = 0
    procs: List[subprocess.Popen] = [spawn(r) for r in range(args.nproc_per_node)]

    # watcher (reference watcher.py): poll children; on failure either
    # relaunch (elastic fault tolerance, --max_restarts) or tear the job
    # down. A relaunch restarts the WHOLE local group — surviving ranks are
    # blocked inside collectives waiting on the dead one and a lone fresh
    # process could never rejoin the advanced coordination state (the
    # reference elastic manager also relaunches all local trainers).
    rc = 0
    try:
        while procs:
            for p in list(procs):
                ret = p.poll()
                if ret is None:
                    continue
                procs.remove(p)
                reap(p)
                if ret == 0:
                    continue
                if restart_count < args.max_restarts:
                    restart_count += 1
                    sys.stderr.write(
                        f"[launch] worker {p._local_rank} exited rc={ret}; "  # type: ignore[attr-defined]
                        f"restarting the local group "
                        f"(restart {restart_count}/{args.max_restarts})\n"
                    )
                    terminate_all(procs)
                    procs = [spawn(r, restart_count) for r in range(args.nproc_per_node)]
                    break
                rc = ret
                terminate_all(procs)
                procs = []
                break
            time.sleep(0.2)
    finally:
        for p in procs:
            reap(p)
    return rc


def main() -> None:
    sys.exit(launch())
