#!/usr/bin/env python
"""Beyond-HBM training: a model BIGGER than the device windows, trained
across day-passes on a mesh — the AIBox/BoxPS architecture end to end.

Each key%N HBM shard holds only one pass's working set; the full model
lives in per-shard host stores (RAM + optional disk spill). Per pass:
stage (BuildPull: host fetch) → begin_pass (BuildGPUTask: scatter to
HBM) → train → end_pass (EndPass: write-back). Reference:
ps_gpu_wrapper.cc:337,684,983; box_wrapper.cc:1415 (LoadSSD2Mem).

Run on real chips, or simulate a pod slice on CPU:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/train_tiered.py
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import optax

from paddlebox_tpu.data import DataFeedDesc, DatasetFactory
from paddlebox_tpu.data.criteo import generate_criteo_files
from paddlebox_tpu.models import DeepFM
from paddlebox_tpu.parallel import make_mesh
from paddlebox_tpu.ps import (BoxPSHelper, SparseSGDConfig,
                              TieredShardedEmbeddingTable)
from paddlebox_tpu.train.sharded import ShardedTrainer

VOCAB = 400


def write_day(work: str, day: int, rows: int = 3000) -> str:
    """Day-k criteo files in a SLIDING value range — consecutive days
    share half their feature space (the production CTR pattern: day k+1
    mostly re-touches day k's features while the multi-day union still
    exceeds any pass window), so the persistent window's delta staging
    has real reuse to exploit."""
    return generate_criteo_files(
        os.path.join(work, f"day{day}"), num_files=1, rows_per_file=rows,
        vocab_per_slot=VOCAB, seed=1000 + day,
        value_base=day * VOCAB // 2)[0]


def main() -> None:
    n = len(jax.devices())
    mesh = make_mesh(n)
    work = tempfile.mkdtemp(prefix="pbox_tiered_")
    desc = DataFeedDesc.criteo(batch_size=128)
    desc.key_bucket_min = 4096

    cfg = SparseSGDConfig(mf_create_thresholds=0.0, mf_initial_range=1e-3,
                          learning_rate=0.05, mf_learning_rate=0.05)
    # HBM window deliberately smaller than the multi-day union: each
    # pass's working set (~10.4k uniques) fits, the 4-day model does not
    cap = (12_000 + n - 1) // n
    table = TieredShardedEmbeddingTable(n, mf_dim=8,
                                        capacity_per_shard=cap, cfg=cfg)
    tr = ShardedTrainer(DeepFM(hidden=(128, 64)), table, desc, mesh,
                        tx=optax.adam(2e-3))
    helper = BoxPSHelper(table, trainer=tr)

    def make_day(day: int):
        """PaddleBoxDataset so day k+1's IO/parse can ALSO overlap day
        k's training (preload_into_memory / wait_feed_pass_done — the
        box_wrapper.h:1142 double-buffering)."""
        d = DatasetFactory().create_dataset("PaddleBoxDataset", desc)
        d.set_filelist([write_day(work, day)])
        return d

    ds = make_day(0)
    helper.read_data_to_memory(ds)
    for day in range(4):
        tr.reset_metrics()                          # per-day AUC
        helper.begin_pass(ds)                       # host → HBM window
        st = dict(table.last_pass_stats)            # delta accounting
        ds_next = make_day(day + 1) if day < 3 else None
        if ds_next is not None:
            helper.preload_into_memory(ds_next)     # IO overlaps epoch 1
        for e in range(3):                          # epochs in the window
            res = tr.train_pass(ds)                 # or train_pass_resident
            if e == 0 and ds_next is not None:
                # the FULL overlap pipeline: day k+1's IO/parse rode
                # epoch 1 in reader threads; its host-tier fetch of
                # MISSING keys (pre_build_thread, ps_gpu_wrapper.cc:913)
                # now rides epochs 2-3 — with the sliding feature
                # space, ~half of day k+1 is already resident and never
                # re-ships
                helper.wait_feed_pass_done(ds_next)
                helper.stage_pass(ds_next)
        helper.end_pass(ds, need_save_delta=True,
                        delta_path=os.path.join(work, f"delta_{day}.npz"))
        print(f"day {day}: auc={res['auc']:.4f} "
              f"staged={st['staged']} resident={st['resident']} "
              f"evicted={st['evicted']} "
              f"window_rows={sum(len(ix) for ix in table.indexes)} "
              f"host_tier_rows={table.feature_count()}")
        ds = ds_next

    hbm_window = n * table.capacity
    total = table.feature_count()
    print(f"\nhost tier holds {total} features vs {hbm_window} HBM window "
          f"rows ({total / hbm_window:.1f}x beyond device memory)")

    # full-model lifecycle runs on the host tier between passes; the
    # threshold ≈ 5 unclicked shows after decay
    # — features seen only a handful of times genuinely age out
    base = os.path.join(work, "base.npz")
    helper.save_base(base)
    freed = helper.shrink_table(delete_threshold=0.5)
    print(f"saved full base ({base}); shrink aged out {freed} rows")


if __name__ == "__main__":
    main()
