#!/usr/bin/env python
"""Chunked-exchange parity gate (ISSUE 11), wired into tier-1 by
``tests/test_scaling_check.py``.

**Chunked parity end-to-end through train_pass**: on the in-process
CPU mesh, ``FLAGS.a2a_chunks=2`` reproduces the ``a2a_chunks=1``
model digest (params + packed table + AUC) BIT-FOR-BIT, and the
digest is deterministic across two seeded runs — the fused
computation-collective schedule (train/sharded) changes the
exchange's shape, never its math.

Graceful skip (exit 0 with a SKIP note): fewer than 2 visible devices.

Scaling itself is a chip measurement over all chips of a host, never
virtual CPU devices: the four-chip training cell that ROADMAP.md R1
asks of ``benchmarks/``.
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile
from typing import List, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _digest(trainer) -> str:
    from paddlebox_tpu.train.checkpoint import sharded_state_digest
    return sharded_state_digest(trainer)


def parity_check(rows_per_file: int = 500,
                 chunks: Tuple[int, ...] = (2,)) -> Optional[bool]:
    """a2a_chunks ∈ chunks reproduce the chunks=1 digest through
    train_pass (×2 seeded runs each). None = skipped (no mesh)."""
    import jax
    if len(jax.devices()) < 2:
        print("scaling_check: SKIP parity — fewer than 2 devices "
              "(needs a CPU mesh: XLA_FLAGS="
              "--xla_force_host_platform_device_count=N)")
        return None
    import optax

    from paddlebox_tpu.config import flags_scope
    from paddlebox_tpu.data import DataFeedDesc, DatasetFactory
    from paddlebox_tpu.data.criteo import generate_criteo_files
    from paddlebox_tpu.models import DeepFM
    from paddlebox_tpu.parallel import make_mesh
    from paddlebox_tpu.ps import SparseSGDConfig
    from paddlebox_tpu.ps.sharded import ShardedEmbeddingTable
    from paddlebox_tpu.train.sharded import ShardedTrainer

    n = min(8, len(jax.devices()))
    mesh = make_mesh(n)
    with tempfile.TemporaryDirectory(prefix="pbox_scaling_") as td:
        files = generate_criteo_files(td, num_files=1,
                                      rows_per_file=rows_per_file,
                                      vocab_per_slot=40, seed=17)
        desc = DataFeedDesc.criteo(batch_size=32)
        desc.key_bucket_min = 1024
        ds = DatasetFactory().create_dataset("InMemoryDataset", desc)
        ds.set_filelist(files)
        ds.load_into_memory()

        def run(c: int) -> str:
            cfg = SparseSGDConfig(mf_create_thresholds=0.0,
                                  mf_initial_range=0.0,
                                  learning_rate=0.1,
                                  mf_learning_rate=0.1)
            table = ShardedEmbeddingTable(
                n, mf_dim=4, capacity_per_shard=4096, cfg=cfg,
                req_bucket_min=256, serve_bucket_min=256)
            with flags_scope(log_period_steps=10 ** 6, a2a_chunks=c):
                tr = ShardedTrainer(DeepFM(hidden=(16, 16)), table,
                                    desc, mesh, tx=optax.adam(2e-3))
                tr.train_pass(ds)
            return _digest(tr)

        want = run(1)
        if run(1) != want:
            print("scaling_check: FAIL — chunks=1 digest is not "
                  "deterministic across seeded runs", file=sys.stderr)
            return False
        for c in chunks:
            got = run(c)
            if got != want:
                print(f"scaling_check: FAIL — a2a_chunks={c} digest "
                      f"{got[:16]} != monolithic {want[:16]}",
                      file=sys.stderr)
                return False
    print(f"scaling_check: parity OK — a2a_chunks {list(chunks)} "
          f"bit-identical to monolithic on the {n}-way mesh "
          f"(digest {want[:16]})")
    return True


def main(argv: Optional[List[str]] = None) -> int:
    argparse.ArgumentParser(
        description=__doc__.splitlines()[0]).parse_args(argv)
    return 1 if parity_check() is False else 0


if __name__ == "__main__":
    # a standalone run needs the virtual CPU mesh BEFORE jax imports
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8").strip()
    sys.exit(main())
