"""Multi-process / multi-host launcher.

Reference: python/paddle/distributed/launch.py + fleet/launch.py (spawn
one trainer process per device, export PADDLE_TRAINER_ID /
PADDLE_TRAINER_ENDPOINTS, restart on failure when elastic is on).

TPU-native redesign: on TPU one *process per host* drives all local chips
(JAX SPMD), so the launcher's unit is the host, not the device. It

- exports ``PBOX_*`` env (rank, world size, coordinator address) and, for
  multi-host, hands them to ``jax.distributed.initialize`` via
  ``init_runtime_env()`` called from the worker;
- can spawn N local worker processes to emulate a multi-host job on one
  machine (tests / CPU-mesh dev), each seeing a disjoint rank — ONLY as
  an explicit CPU emulation (``JAX_PLATFORMS=cpu`` in the environment
  the workers inherit): a chip belongs to one process at a time, so N
  local workers on a host with chips would fight over them and all but
  one fail or hang. ``nproc > 1`` without it is refused;
- integrates ElasticManager: on a worker death (or scale event) it stops
  the survivors and restarts everyone from the latest published
  checkpoint pointer.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import signal
import subprocess
import sys
import time
from typing import Dict, List, Optional, Sequence

from paddlebox_tpu.distributed.elastic import ElasticManager, FileKVStore
from paddlebox_tpu.utils.logging import get_logger

log = get_logger(__name__)

ENV_RANK = "PBOX_RANK"
ENV_WORLD = "PBOX_WORLD_SIZE"
ENV_COORD = "PBOX_COORDINATOR"
ENV_RESUME = "PBOX_RESUME_CKPT"


@dataclasses.dataclass
class LaunchConfig:
    nproc: int = 1                      # local worker processes
    coordinator: str = "127.0.0.1:8476"
    job_id: str = "default"
    elastic_root: Optional[str] = None  # KV dir; enables elastic restarts
    # network KV (host:port of a KVServer) — elastic restarts with NO
    # shared filesystem (TcpKVStore; overrides elastic_root)
    elastic_endpoint: Optional[str] = None
    max_restarts: int = 3
    stop_grace_sec: float = 5.0


def init_runtime_env() -> Dict[str, int]:
    """Worker-side bootstrap: read the env the launcher exported and, when
    the job is actually multi-process, initialize the JAX distributed
    runtime (coordinator rendezvous over DCN)."""
    rank = int(os.environ.get(ENV_RANK, "0"))
    world = int(os.environ.get(ENV_WORLD, "1"))
    if world > 1 and os.environ.get("PBOX_JAX_DISTRIBUTED", "0") == "1":
        import jax

        jax.distributed.initialize(
            coordinator_address=os.environ[ENV_COORD],
            num_processes=world, process_id=rank)
    return {"rank": rank, "world_size": world}


def _spawn(cmd: Sequence[str], rank: int, world: int, cfg: LaunchConfig,
           resume: Optional[str]) -> subprocess.Popen:
    env = dict(os.environ)
    env[ENV_RANK] = str(rank)
    env[ENV_WORLD] = str(world)
    env[ENV_COORD] = cfg.coordinator
    if resume:
        env[ENV_RESUME] = resume
    return subprocess.Popen(list(cmd), env=env)


def _stop_all(procs: List[subprocess.Popen], grace: float) -> None:
    for p in procs:
        if p.poll() is None:
            p.send_signal(signal.SIGTERM)
    deadline = time.time() + grace
    for p in procs:
        left = max(0.1, deadline - time.time())
        try:
            p.wait(timeout=left)
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()


def launch_local(cmd: Sequence[str], cfg: LaunchConfig) -> int:
    """Run ``cmd`` as cfg.nproc rank-stamped local processes; restart the
    gang (from the latest checkpoint pointer) on failure when elastic is
    enabled. Returns the final exit code (0 = all ranks clean)."""
    if cfg.nproc > 1 and os.environ.get("JAX_PLATFORMS", "") != "cpu":
        raise ValueError(
            f"nproc={cfg.nproc}: one process per host drives all local "
            "chips, and a chip belongs to one process at a time. Several "
            "local workers are a CPU emulation of a multi-host job — "
            "say so with JAX_PLATFORMS=cpu.")
    manager: Optional[ElasticManager] = None
    if cfg.elastic_endpoint or cfg.elastic_root:
        if cfg.elastic_endpoint:
            from paddlebox_tpu.distributed.kv_server import TcpKVStore
            kv = TcpKVStore(cfg.elastic_endpoint)
        else:
            kv = FileKVStore(cfg.elastic_root)
        manager = ElasticManager(
            kv, cfg.job_id,
            host=f"local-{os.getpid()}", np=1, ttl=10.0)
        manager.register()

    restarts = 0
    try:
        while True:
            resume = None
            if manager is not None:
                ckpt = manager.latest_checkpoint()
                if ckpt:
                    resume = ckpt["path"]
                    log.info("starting gang from checkpoint %s", resume)
            procs = [_spawn(cmd, r, cfg.nproc, cfg, resume)
                     for r in range(cfg.nproc)]
            # poll instead of wait: one crashed rank must not leave hung
            # survivors blocking the restart (peer-loss in a collective)
            failed = False
            while True:
                codes = [p.poll() for p in procs]
                if any(c is not None and c != 0 for c in codes):
                    failed = True
                    break
                if all(c == 0 for c in codes):
                    break
                if manager is not None and manager.scale_event() is not None:
                    log.warning("membership changed; restarting gang")
                    failed = True
                    break
                time.sleep(0.05)
            if not failed:
                return 0
            codes = [p.poll() for p in procs]
            log.warning("gang failed with codes %s", codes)
            _stop_all(procs, cfg.stop_grace_sec)
            codes = [p.returncode for p in procs]
            restarts += 1
            if manager is None or restarts > cfg.max_restarts:
                return max((c for c in codes if c), default=1)
            log.info("elastic restart %d/%d", restarts, cfg.max_restarts)
    finally:
        if manager is not None:
            manager.deregister()


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m paddlebox_tpu.distributed.launch",
        description="PaddleBox-TPU job launcher")
    ap.add_argument("--nproc", type=int, default=1,
                    help="local worker processes (emulated hosts; >1 "
                         "needs JAX_PLATFORMS=cpu)")
    ap.add_argument("--coordinator", default="127.0.0.1:8476")
    ap.add_argument("--job-id", default="default")
    ap.add_argument("--elastic-root", default=None,
                    help="shared KV dir; enables elastic restart")
    ap.add_argument("--elastic-endpoint", default=None,
                    help="KVServer host:port (network KV, no shared "
                         "filesystem); enables elastic restart and "
                         "overrides --elastic-root")
    ap.add_argument("--max-restarts", type=int, default=3)
    ap.add_argument("cmd", nargs=argparse.REMAINDER,
                    help="worker command (e.g. python train.py ...)")
    args = ap.parse_args(argv)
    cmd = args.cmd
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    if not cmd:
        ap.error("missing worker command")
    cfg = LaunchConfig(nproc=args.nproc, coordinator=args.coordinator,
                       job_id=args.job_id, elastic_root=args.elastic_root,
                       elastic_endpoint=args.elastic_endpoint,
                       max_restarts=args.max_restarts)
    return launch_local(cmd, cfg)


if __name__ == "__main__":
    sys.exit(main())
