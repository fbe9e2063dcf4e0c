#!/usr/bin/env python
"""Multi-host CTR training on one machine — launcher + TCP global shuffle.

Spawns N worker processes via the launcher (each sees PBOX_RANK /
PBOX_WORLD_SIZE, like the reference's paddle.distributed.launch ranks),
and each worker:

  1. reads its round-robin shard of the file list,
  2. exchanges records with its peers through the TcpShuffler
     (the PaddleShuffler/ShuffleData role — data_set.cc:2573),
  3. trains DeepFM on its post-shuffle partition and reports AUC.

This is a CPU EMULATION of a multi-host job: the spawned workers are
pinned to ``JAX_PLATFORMS=cpu``, because a chip belongs to one process at
a time and N local processes cannot share one host's chips. On a real
multi-host pod the same worker runs once per host (one process driving
all of the host's chips) with the env provided by your scheduler; only
the endpoints change.

    python examples/train_multihost.py [--workers 2] [--rows 4000]
"""

import argparse
import json
import os
import socket
import subprocess
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def worker(args) -> None:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np
    import optax

    from paddlebox_tpu.config import FLAGS
    from paddlebox_tpu.data import DataFeedDesc, DatasetFactory
    from paddlebox_tpu.distributed.collective import TcpCollective
    from paddlebox_tpu.distributed.shuffle import TcpShuffler
    from paddlebox_tpu.metrics import auc_compute_global
    from paddlebox_tpu.models import DeepFM
    from paddlebox_tpu.ps import EmbeddingTable, SparseSGDConfig
    from paddlebox_tpu.train import Trainer

    rank = int(os.environ["PBOX_RANK"])
    world = int(os.environ["PBOX_WORLD_SIZE"])
    endpoints = os.environ["SHUFFLE_ENDPOINTS"].split(",")

    desc = DataFeedDesc.criteo(batch_size=args.batch_size)
    FLAGS.native_parse = False   # the exchange moves record objects
    ds = DatasetFactory().create_dataset("InMemoryDataset", desc)
    files = sorted(os.path.join(args.data, f)
                   for f in os.listdir(args.data))
    ds.set_filelist(files, shard_by_rank=True)
    ds.load_into_memory()
    loaded = len(ds.records)

    sh = TcpShuffler(rank, world, endpoints, seed=7)
    ds.global_shuffle(sh)        # records route to hash(record) % world
    sh.close()

    table = EmbeddingTable(
        mf_dim=8, capacity=1 << 16,
        cfg=SparseSGDConfig(mf_create_thresholds=0.0))
    tr = Trainer(DeepFM(hidden=(64, 32)), table, desc,
                 tx=optax.adam(1e-2), seed=rank)
    for _ in range(args.passes):
        res = tr.train_pass(ds, log_prefix=f"[rank {rank}] ")
    # ONE global AUC across all workers (metrics.cc:288-304): allreduce
    # the bucket tables over the host collective plane
    coll_eps = os.environ.get("COLLECTIVE_ENDPOINTS")
    global_auc = None
    if coll_eps:
        coll = TcpCollective(rank, world, coll_eps.split(","))
        global_auc = round(float(
            auc_compute_global(tr.state.auc, coll).auc), 4)
        coll.close()
    print(json.dumps(dict(rank=rank, loaded=loaded,
                          after_shuffle=len(ds.records),
                          auc=round(float(res["auc"]), 4),
                          global_auc=global_auc,
                          features=int(table.feature_count))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--rows", type=int, default=4000)
    ap.add_argument("--passes", type=int, default=3)
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--data", default=None)
    ap.add_argument("--worker", action="store_true",
                    help=argparse.SUPPRESS)  # internal re-exec flag
    args = ap.parse_args()
    if args.worker:
        worker(args)
        return

    from paddlebox_tpu.data.criteo import generate_criteo_files
    data = args.data or os.path.join(tempfile.mkdtemp(prefix="pbox_mh_"),
                                     "data")
    if not os.path.isdir(data) or not os.listdir(data):
        generate_criteo_files(data, num_files=2 * args.workers,
                              rows_per_file=args.rows // (2 * args.workers),
                              vocab_per_slot=200, seed=1)

    socks = [socket.socket() for _ in range(2 * args.workers)]
    for s in socks:
        s.bind(("127.0.0.1", 0))
    ports = [s.getsockname()[1] for s in socks]
    endpoints = ",".join(f"127.0.0.1:{p}" for p in ports[:args.workers])
    coll_eps = ",".join(f"127.0.0.1:{p}" for p in ports[args.workers:])
    for s in socks:
        s.close()

    procs = []
    for r in range(args.workers):
        env = dict(os.environ, PBOX_RANK=str(r),
                   PBOX_WORLD_SIZE=str(args.workers),
                   SHUFFLE_ENDPOINTS=endpoints,
                   COLLECTIVE_ENDPOINTS=coll_eps, JAX_PLATFORMS="cpu")
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--worker",
             "--data", data, "--rows", str(args.rows),
             "--passes", str(args.passes),
             "--batch-size", str(args.batch_size)],
            env=env))
    rc = [p.wait() for p in procs]
    if any(rc):
        raise SystemExit(f"worker failures: {rc}")
    print("all workers done")


if __name__ == "__main__":
    main()
