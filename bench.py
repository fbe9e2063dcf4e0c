#!/usr/bin/env python
"""Benchmark: DeepFM CTR training throughput on one chip.

Prints one JSON line: {"metric", "value", "unit", "vs_baseline"}.

Baseline derivation (BASELINE.md): north-star is 1M examples/sec on a
v5p-32 slice (16 chips) ⇒ 62,500 examples/sec/chip. vs_baseline is
measured chip throughput / 62,500.

The measured pass mirrors the reference's steady state (SURVEY.md §3.2):
data already resident in memory (loaded during the previous pass window),
per-batch host prep (dedup + row assign) overlapped with device compute via
the prefetch thread, one fused jit step per batch.
"""

import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np


def build_records(num_records: int, num_slots: int = 26,
                  vocab_per_slot: int = 100_000, seed: int = 0,
                  avg_keys_per_slot: float = 1.0,
                  key_dist: str = "uniform"):
    """Synthetic criteo-shaped records, built columnar-fast.

    ``avg_keys_per_slot > 1`` produces RAGGED slots: per-(record, slot)
    key counts ~ 1 + Poisson(avg-1) — variable-length multi-key slots,
    the real PaddleBox feed-log shape (data_feed.h:2066-2287) that
    stresses the segment stream and the non-trivial seqpool path.

    ``key_dist="zipf"`` draws per-slot key ids from a bounded Zipf
    (s=1.2) instead of uniform — the hot-key CTR shape: a few ids
    dominate every batch, so dedup, the persistent HBM window and the
    host/SSD tiers stop being flattered by uniform draws."""
    from paddlebox_tpu.data.record import SlotRecord
    rng = np.random.default_rng(seed)

    def draw_keys(size):
        if key_dist == "zipf":
            # bounded Zipf over [0, vocab): P(r) ∝ 1/(r+1)^1.2 — one
            # vectorized choice() call per pass build
            w = 1.0 / np.arange(1, vocab_per_slot + 1,
                                dtype=np.float64) ** 1.2
            return rng.choice(vocab_per_slot, size=size, p=w / w.sum())
        return rng.integers(0, vocab_per_slot, size=size)

    dense_all = rng.normal(size=(num_records, 13)).astype(np.float32)
    labels = (rng.random(num_records) < 0.25).astype(np.float32)
    slot_base = (np.arange(num_slots) * vocab_per_slot).astype(np.uint64)
    if avg_keys_per_slot <= 1.0:
        keys_all = draw_keys((num_records, num_slots))
        keys_all = (keys_all + slot_base).astype(np.uint64)
        offsets = np.arange(num_slots + 1, dtype=np.int32)
        return [
            SlotRecord(keys=keys_all[i], slot_offsets=offsets,
                       dense=dense_all[i], label=float(labels[i]),
                       show=1.0, clk=float(labels[i]))
            for i in range(num_records)
        ]
    counts = 1 + rng.poisson(avg_keys_per_slot - 1.0,
                             size=(num_records, num_slots))
    offs = np.zeros((num_records, num_slots + 1), np.int32)
    np.cumsum(counts, axis=1, out=offs[:, 1:])
    total = offs[:, -1]
    flat = draw_keys(int(total.sum()))
    flat_base = np.repeat(
        np.tile(slot_base, num_records),
        counts.reshape(-1))
    flat = (flat + flat_base).astype(np.uint64)
    starts = np.concatenate([[0], np.cumsum(total)[:-1]])
    return [
        SlotRecord(keys=flat[starts[i]:starts[i] + total[i]],
                   slot_offsets=offs[i],
                   dense=dense_all[i], label=float(labels[i]),
                   show=1.0, clk=float(labels[i]))
        for i in range(num_records)
    ]


def dense_flops_per_example(params) -> float:
    """Analytic train-step FLOPs/example of the DENSE net: 2·in·out per
    matmul kernel forward, ×3 for fwd+bwd (the embedding path is
    bandwidth-bound — gathers/scatters, ~0 FLOPs). Used for the MFU
    line; the denominator is the chip's matmul peak."""
    import jax
    f = 0.0
    for leaf in jax.tree.leaves(params):
        if getattr(leaf, "ndim", 0) >= 2:
            f += 2.0 * float(np.prod(leaf.shape))
    return 3.0 * f


#: Peak rates of one chip, keyed by ``jax.devices()[0].device_kind`` —
#: the ONLY source of a peak in this repo. A kind that is not listed is
#: an error, never a default.
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_sec": 197e12,
        "hbm_bytes_per_sec": 819e9,
        "source": 'Google Cloud documentation, "TPU v5e": 197 TFLOP/s '
                  "bf16, 819 GB/s HBM per chip",
    },
}


def device_peaks(device_kind: str) -> dict:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks recorded for device_kind "
            f"{device_kind!r}; add it to bench.DEVICE_PEAKS with its "
            f"source (known: {sorted(DEVICE_PEAKS)})") from None


def require_chip():
    """Every mode of this file measures the chip: refuse to run (and to
    shrink) without one, and refuse the ~50x slower python host index."""
    import jax
    from paddlebox_tpu.native import require_native
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench.py measures a TPU chip; jax found platform "
            f"{dev.platform!r} ({dev.device_kind}). CPU runs prove "
            f"correctness in tests/, never a rate.")
    require_native()
    return dev


SHAPES = {
    # BENCH_SHAPE → (num_slots, avg_keys_per_slot, default_bs,
    #                default_records, default_vocab_per_slot, key_dist)
    "uniform": (26, 1.0, 8192, 262_144, 100_000, "uniform"),
    "ragged": (26, 5.0, 4096, 131_072, 100_000, "uniform"),
    "thousand": (1000, 1.0, 512, 32_768, 4_000, "uniform"),
    # hot-key CTR shape: bounded Zipf key draws — same geometry as
    # "uniform" so the two rows isolate the skew effect on dedup /
    # window / tier hit rates
    "zipf": (26, 1.0, 8192, 262_144, 100_000, "zipf"),
}


def measure_tiered(num_passes: int = 4, shape: str = "uniform") -> dict:
    """Pass-window benchmark: the tiered sharded PS with PERSISTENT HBM
    windows (ps/tiered.py), driven through the UNIFIED pass pipeline
    (train/device_pass.PassPipeline — ISSUE 9): plan build, dedup/pack,
    the H2D wire and the host-tier feed-pass fetch all ride the depth-N
    preloader worker, begin_pass is reconcile-only, end_pass submits to
    the epilogue lane (which also evicts ahead for the next queued
    stage). Consecutive passes draw from the same key space (the CTR
    workload), so delta staging shrinks the begin boundary to ~the
    working-set delta; a drop_window control pass measures what full
    re-staging would cost on the same box state. Returns the JSON
    record (caller prints)."""
    import jax
    import optax

    from paddlebox_tpu.config import FLAGS
    from paddlebox_tpu.data import DataFeedDesc, InMemoryDataset, SlotDef
    from paddlebox_tpu.models import DeepFM
    from paddlebox_tpu.parallel import make_mesh
    from paddlebox_tpu.ps import BoxPSHelper, SparseSGDConfig
    from paddlebox_tpu.ps.tiered import TieredShardedEmbeddingTable
    from paddlebox_tpu.train.sharded import ShardedTrainer

    n_slots, avg_keys, bs_default, _, _, key_dist = SHAPES[shape]
    bs = int(os.environ.get("BENCH_BATCH_SIZE", bs_default))
    # smaller working set than the resident headline: the cold stage
    # ships the full working set host→device once
    num_records = int(os.environ.get("BENCH_RECORDS", 32768))
    vocab = int(os.environ.get("BENCH_VOCAB", 10_000))
    mf_dim = int(os.environ.get("BENCH_MF_DIM", 8))
    chips = len(jax.devices())
    slots = [SlotDef("label", "float", 1), SlotDef("dense", "float", 13)]
    slots += [SlotDef(f"C{i}", "uint64") for i in range(1, n_slots + 1)]
    desc = DataFeedDesc(slots=slots, batch_size=bs, label_slot="label",
                        key_bucket_min=(bs * n_slots
                                        if avg_keys <= 1.0 else 4096))
    cfg = SparseSGDConfig(mf_create_thresholds=0.0, mf_initial_range=1e-3)

    def make_ds(seed: int) -> InMemoryDataset:
        d = InMemoryDataset(desc)
        d.records = build_records(num_records, num_slots=n_slots,
                                  vocab_per_slot=vocab, seed=seed,
                                  avg_keys_per_slot=avg_keys,
                                  key_dist=key_dist)
        d.columnarize()
        return d

    mesh = make_mesh(chips)
    # SSD third tier attached (ps/ssd.py): idle during the headline
    # passes (occupancy 0 below the demote watermark), then exercised
    # by the promote-attribution section below
    ssd_root = tempfile.mkdtemp(prefix="pbox_bench_ssd_")
    table = TieredShardedEmbeddingTable(
        chips, mf_dim=mf_dim, capacity_per_shard=(1 << 22) // chips,
        cfg=cfg, req_bucket_min=1 << 12, serve_bucket_min=1 << 12,
        ssd_dir=ssd_root)
    tr = ShardedTrainer(DeepFM(hidden=(512, 256, 128)), table,
                        desc, mesh, tx=optax.adam(1e-3))
    helper = BoxPSHelper(table, trainer=tr)
    pool = [make_ds(s) for s in range(2)]

    # the pipeline: cold pass + measured passes, alternating datasets
    # (~96% key overlap). BENCH_NO_OVERLAP=1 = the sequential
    # kick-per-pass control (depth 0); BENCH_PRELOAD_DEPTH overrides.
    no_overlap = os.environ.get("BENCH_NO_OVERLAP", "0") == "1"
    depth = (0 if no_overlap else
             int(os.environ.get("BENCH_PRELOAD_DEPTH",
                                str(FLAGS.preload_depth))))
    seq = [pool[i % 2] for i in range(num_passes + 2)]
    pipe = tr.tiered_pass_pipeline(iter(seq), depth=depth)
    pipe.start_next()

    def one_pass():
        t0 = time.perf_counter()
        rp = pipe.wait()
        t_wait = time.perf_counter() - t0     # prologue stall
        t1 = time.perf_counter()
        pipe.begin_pass()                     # reconcile-only boundary
        t_begin = time.perf_counter() - t1
        if not no_overlap:
            pipe.start_next()
        t2 = time.perf_counter()
        tr.train_pass_resident(rp)
        t_train = time.perf_counter() - t2
        if no_overlap:
            pipe.start_next()
        t3 = time.perf_counter()
        pipe.end_pass()
        # with the async epilogue (FLAGS.async_end_pass, the default)
        # this is SUBMIT time — the HBM→host write-back drains in the
        # background; its true cost/overlap comes from endpass_stats()
        t_end = time.perf_counter() - t3
        return t_wait, t_begin, t_train, t_end, \
            dict(table.last_pass_stats)

    # warmup, the resident headline's discipline (its pass 0 pays
    # compile+upload and is excluded): TWO unmeasured passes — the cold
    # pass stages the full working set + compiles dataset A's shapes,
    # the warm pass stages the A→B key delta + compiles B's shapes (the
    # two datasets' routing buckets can differ, each costing a one-off
    # jit). Pass 1's build+stage already ride the worker during cold
    # training (the pre_build_thread shape, ps_gpu_wrapper.cc:913);
    # measured passes then show the steady-state boundary.
    w0, b0, _, e0, st0 = one_pass()
    w1, b1, _, _, st1 = one_pass()
    # scope the epilogue accounting to the MEASURED passes: drain the
    # warmup passes' write-backs and snapshot the cumulative stats; the
    # post-loop snapshot diffs against this (the warmups and the
    # device-only rerun below would otherwise pollute the headline
    # overlap fraction)
    table.fence()
    eps0 = table.endpass_stats()
    wait_l, begin_l, train_l, end_l = [], [], [], []
    staged_l, stall_l, ep_dispatch_l = [], [], []
    for i in range(num_passes):
        w, b, t, e, st = one_pass()
        wait_l.append(w)
        begin_l.append(w + b)   # critical-path boundary stall: preload
        train_l.append(t)       # wait + the reconcile-only begin
        end_l.append(e)
        staged_l.append(st["staged"])
        ep_dispatch_l.append(st.get("end_pass_dispatch_sec", 0.0))
        # per-pass begin_stall attribution (ps/tiered.begin_pass):
        # stage wait on the critical path, evict+scatter, the async-
        # lane vs emergency-inline eviction split, and the SSD promote
        # seconds the staging incurred (wait = main-thread share — ~0
        # when the promote rode the overlapped stage)
        stall_l.append({k: st.get(k, 0.0)
                        for k in ("stage_wait_sec", "evict_scatter_sec",
                                  "evict_async_sec", "evict_async_rows",
                                  "evict_emergency_sec",
                                  "ssd_promote_sec",
                                  "ssd_promote_wait_sec",
                                  "ssd_promoted_rows")})
    # drain the measured passes' epilogue, then diff the cumulative
    # accounting against the cold-pass snapshot — end_pass_overlap_sec
    # is the measured write-back time that never blocked the main
    # thread (the seconds the async epilogue bought). The fence here is
    # part of the accounting: the LAST measured pass's write-back has
    # no next pass to hide behind in this loop, so any residual wait
    # honestly lands in the critical fence-wait term.
    table.fence()
    eps1 = table.endpass_stats()
    eps = {k: eps1[k] - eps0[k] for k in
           ("jobs_run", "writeback_sec", "fence_wait_sec",
            "critical_fence_wait_sec")}
    eps["overlap_sec"] = max(
        0.0, eps["writeback_sec"] - eps["critical_fence_wait_sec"])
    pipe_stats = dict(
        preload_depth=depth,
        preload_builds=pipe.builds,
        preload_build_sec_total=round(pipe.build_sec_total, 4),
        preload_build_stage_sec={
            k: round(v, 4)
            for k, v in sorted(pipe.build_stage_sec.items())})
    # quiesce the pipeline before the reruns/controls: stop the worker
    # and discard queued stages that will never begin (their plan pins
    # release — ps/tiered.discard_queued_stages)
    pipe.drain()
    # device-only rerun (duty-cycle attribution): re-stage the last
    # pass classically, build once, and re-train the staged batches —
    # nothing crosses host→device, so this is the device's real compute
    # time per pass (same two-rerun discipline as the resident
    # headline; these extra passes perturb only model state, which the
    # tiered bench does not report, and run AFTER the epilogue
    # accounting snapshot so they cannot skew it)
    ds_dev = pool[(num_passes + 1) % 2]
    helper.begin_pass(ds_dev)
    rp_dev = tr.build_resident_pass(ds_dev)
    tr.train_pass_resident(rp_dev)          # warm rerun
    t0 = time.perf_counter()
    tr.train_pass_resident(rp_dev)
    dev_only = num_records / max(time.perf_counter() - t0, 1e-9)
    helper.end_pass(None)
    # control: drop residency, re-stage the SAME working set as the
    # last measured pass, fully (drop_window also discards the stage
    # the last pass overlapped)
    table.drop_window()
    t0 = time.perf_counter()
    helper.begin_pass(pool[(num_passes + 1) % 2])
    begin_full = time.perf_counter() - t0
    staged_full = table.last_pass_stats["staged"]
    helper.end_pass(None)
    # --- SSD third-tier attribution (ISSUE 7; docs/STORAGE.md) ---
    # Demote the WHOLE model to segments, then stage pass B's working
    # set back twice: once synchronously (begin_pass pays the segment
    # reads inline — the LoadSSD2Mem cost on the critical path) and
    # once ridden on the overlapped stage during pass A's training
    # (the production pre_build_thread shape). The acceptance claim is
    # overlap_promote_wait_sec << sync_promote_wait_sec for the same
    # working set (scripts/ssd_check.run_overlap_check gates it; the
    # bench reports the measured numbers).
    table.fence()
    table.drop_window()
    t0 = time.perf_counter()
    ssd_demoted = sum(h.demote_cold() for h in table.hosts)
    ssd_demote_sec = time.perf_counter() - t0
    t0 = time.perf_counter()
    helper.begin_pass(pool[1])            # sync: promote paid inline
    begin_ssd_sync = time.perf_counter() - t0
    sync_st = dict(table.last_pass_stats)
    helper.end_pass(None)
    table.fence()
    table.drop_window()
    sum(h.demote_cold() for h in table.hosts)
    helper.begin_pass(pool[0])            # A staged inline (unmeasured)
    helper.stage_pass(pool[1])            # B's promote rides A's train
    tr.train_pass_resident(pool[0])
    helper.end_pass(pool[0])
    t0 = time.perf_counter()
    helper.begin_pass(pool[1])
    begin_ssd_overlap = time.perf_counter() - t0
    ov_st = dict(table.last_pass_stats)
    helper.end_pass(None)
    table.fence()
    ssd = table.ssd_stats()
    shutil.rmtree(ssd_root, ignore_errors=True)
    walls = [b + t + e for b, t, e in zip(begin_l, train_l, end_l)]
    value = num_records * len(walls) / sum(walls) / chips
    dev_time_total = num_records * len(walls) / max(dev_only, 1e-9)
    # steady state = the median begin (the first delta pass pays any
    # residual compile; later passes show the true boundary)
    begin_steady = float(np.median(begin_l))
    metric = "deepfm_ctr_examples_per_sec_per_chip"
    if shape != "uniform":
        metric += f"_{shape}"
    return {
        "metric": metric + "_tiered",
        "value": round(value, 1),
        "unit": "examples/sec/chip",
        "vs_baseline": round(value / (1_000_000 / 16), 4),
        "mode": "tiered", "shape": shape, "batch_size": bs,
        "num_slots": n_slots, "avg_keys_per_slot": avg_keys,
        "records_per_pass": num_records,
        "passes": num_passes,
        "stage_cold_sec": round(w0 + b0, 3),
        "staged_rows_cold": st0["staged"],
        # begin_delta = the critical-path pass boundary: preload wait
        # (build+stage pipeline starvation) + the reconcile-only begin
        "begin_delta_sec": [round(b, 3) for b in begin_l],
        "preload_wait_sec": [round(w, 3) for w in wait_l],
        "staged_rows_delta": staged_l,
        "train_sec": [round(t, 3) for t in train_l],
        # unified pass pipeline (train/device_pass.PassPipeline):
        # depth + worker build accounting, the resident bench's fields
        **pipe_stats,
        # async epilogue: end_pass_sec is SUBMIT time (critical-path
        # cost of the boundary); the write-back itself runs overlapped.
        # dispatch = the bucketed D2H gather dispatch inside submit
        # (the rest is the touched-row snapshot) — the submit-parity
        # audit's split (ISSUE 9)
        "end_pass_sec": [round(e, 3) for e in end_l],
        "end_pass_dispatch_sec": [round(d, 4) for d in ep_dispatch_l],
        "end_pass_writeback_sec_total": round(eps["writeback_sec"], 4),
        "end_pass_fence_wait_sec_total": round(
            eps["critical_fence_wait_sec"], 4),
        # the headline of ISSUE 4: write-back seconds off the critical
        # path, and their fraction of total write-back time (>0.5 =
        # the epilogue is genuinely overlapped with next-pass train)
        "end_pass_overlap_sec": round(eps["overlap_sec"], 4),
        "end_pass_overlap_frac": round(
            eps["overlap_sec"] / max(eps["writeback_sec"], 1e-9), 4),
        "end_pass_jobs": eps["jobs_run"],
        # fraction of measured wall the device spent on real compute
        # (records/dev_only per pass, wire-free rerun — the resident
        # headline's device_busy_frac, now for tiered mode)
        "device_busy_frac": round(
            min(dev_time_total / max(sum(walls), 1e-9), 1.0), 4),
        "device_only_ex_per_sec": round(dev_only / chips, 1),
        "begin_delta_steady_sec": round(begin_steady, 4),
        # the first DELTA boundary is the warm (2nd unmeasured) pass:
        # it stages the A→B working-set delta + pays B's one-off compile
        "begin_first_delta_sec": round(w1 + b1, 3),
        "staged_rows_first_delta": st1["staged"],
        "begin_full_control_sec": round(begin_full, 3),
        "staged_rows_full_control": staged_full,
        # the headline ratio: steady-state boundary stall with delta
        # staging vs full re-staging of the same working set
        "begin_stall_shrink": round(
            begin_full / max(begin_steady, 1e-9), 1),
        # per-pass begin_stall attribution (stage wait / evict+scatter /
        # SSD promote seconds) — the tiered-mode gap finally has
        # per-stage numbers (ISSUE 7)
        "begin_stall_breakdown": [
            {k: (round(float(v), 4) if isinstance(v, float) else v)
             for k, v in st.items()} for st in stall_l],
        # SSD third tier (ps/ssd.py): cumulative tier accounting plus
        # the sync-vs-overlapped promote comparison for pass B's
        # working set — overlap wait must sit far below the sync
        # control where begin_pass pays the segment reads inline
        "ssd": {
            "demoted_rows": int(ssd.get("demoted_rows", 0)),
            "promoted_rows": int(ssd.get("promoted_rows", 0)),
            "compacted_rows": int(ssd.get("compacted_rows", 0)),
            "demote_sec_total": round(ssd.get("demote_sec", 0.0), 4),
            "promote_sec_total": round(ssd.get("promote_sec", 0.0), 4),
            "promote_wait_sec_total": round(
                ssd.get("promote_wait_sec", 0.0), 4),
            "live_rows": int(ssd.get("live_rows", 0)),
            "segments": int(ssd.get("segments", 0)),
            "bytes": int(ssd.get("bytes", 0)),
            "demote_all_rows": int(ssd_demoted),
            "demote_all_sec": round(ssd_demote_sec, 4),
            "begin_sync_sec": round(begin_ssd_sync, 4),
            "begin_overlap_sec": round(begin_ssd_overlap, 4),
            "sync_promote_wait_sec": round(
                sync_st.get("ssd_promote_wait_sec", 0.0), 4),
            "sync_promoted_rows": int(
                sync_st.get("ssd_promoted_rows", 0)),
            "overlap_promote_sec": round(
                ov_st.get("ssd_promote_sec", 0.0), 4),
            "overlap_promote_wait_sec": round(
                ov_st.get("ssd_promote_wait_sec", 0.0), 4),
            "overlap_promoted_rows": int(
                ov_st.get("ssd_promoted_rows", 0)),
        },
    }


def build_pv_records(n_pvs: int, num_slots: int, vocab_per_slot: int,
                     dense_dim: int, seed: int = 0):
    """Synthetic search pages for the PV rank-attention lane: 2-4 ads
    per PV with shuffled 1-based ranks and valid cmatch, so every batch
    carries a dense rank_offset matrix (data/pv.build_rank_offset)."""
    from paddlebox_tpu.data.record import SlotRecord
    rng = np.random.default_rng(seed)
    recs = []
    for sid in range(n_pvs):
        n_ads = int(rng.integers(2, 5))
        ranks = rng.permutation(n_ads) + 1
        for a in range(n_ads):
            keys = (rng.integers(0, vocab_per_slot, num_slots)
                    + np.arange(num_slots) * vocab_per_slot).astype(
                        np.uint64)
            label = float(rng.random() < 0.25)
            recs.append(SlotRecord(
                keys=keys,
                slot_offsets=np.arange(num_slots + 1, dtype=np.int32),
                dense=rng.normal(size=dense_dim).astype(np.float32),
                label=label, show=1.0, clk=label, search_id=sid,
                rank=int(ranks[a]), cmatch=222))
    return recs


def measure_pv(num_passes: int = 3) -> list:
    """BENCH_MODE=pv (ISSUE 13 / ROADMAP item 5): the PV-batch
    rank-attention scenario — PvBatchBuilder batches (PV merge +
    rank_offset) through an AdsRank net with ALL THREE device-side CTR
    ops on its path (rank_attention, the slot_fc batch_fc tower, the
    cross_norm hadamard block) over the sparse PS pull→train→push
    loop. Emits one row per implementation:

        adsrank_pv_examples_per_sec_per_chip           (XLA, default)
        adsrank_pv_examples_per_sec_per_chip_pallas    (fused kernels)

    keyed separately so perf_gate compares each impl against its OWN
    history. BENCH_PV_IMPLS=xla|pallas|both selects."""
    import jax
    import jax.numpy as jnp
    import optax

    from paddlebox_tpu.config import flags_scope
    from paddlebox_tpu.data import DataFeedDesc, SlotDef
    from paddlebox_tpu.data.pv import PvBatchBuilder
    from paddlebox_tpu.models import AdsRank
    from paddlebox_tpu.ops import (fused_seqpool_cvm,
                                   init_cross_norm_summary)
    from paddlebox_tpu.ps import EmbeddingTable, SparseSGDConfig

    n_pvs = int(os.environ.get("BENCH_PV_PVS", "8192"))
    bs = int(os.environ.get("BENCH_BATCH_SIZE", "4096"))
    s = int(os.environ.get("BENCH_PV_SLOTS", "8"))
    d_model = int(os.environ.get("BENCH_PV_DMODEL", "128"))
    max_rank = 3
    mf_dim = int(os.environ.get("BENCH_MF_DIM", 8))
    dense_dim = 4
    vocab = int(os.environ.get("BENCH_VOCAB", 10_000))
    impls = os.environ.get("BENCH_PV_IMPLS", "both")
    if impls not in ("xla", "pallas", "both"):
        # a typo'd knob must not produce a silent empty round
        raise SystemExit(
            f"BENCH_PV_IMPLS={impls!r}: must be xla, pallas or both")

    slots = [SlotDef("label", "float", 1),
             SlotDef("dense", "float", dense_dim)]
    slots += [SlotDef(f"C{i}", "uint64") for i in range(s)]
    desc = DataFeedDesc(slots=slots, batch_size=bs, label_slot="label",
                        pv_batch_size=max(1, bs // 8),
                        key_bucket_min=max(512, bs * s))
    recs = build_pv_records(n_pvs, s, vocab, dense_dim)
    pvb = PvBatchBuilder(desc, max_rank=max_rank)
    batches = pvb.batches(recs)
    instances = len(recs)
    d = 3 + mf_dim
    cfg = SparseSGDConfig(mf_create_thresholds=0.0, mf_initial_range=1e-3)
    model = AdsRank(d_model=d_model, max_rank=max_rank,
                    hidden=(128, 64), slot_fc=True, cross_norm=True)
    summary = init_cross_norm_summary(1, d_model)

    rows = []
    flag_sets = {"xla": dict(use_pallas_rank_attention=False,
                             use_pallas_batch_fc=False,
                             use_pallas_cross_norm=False),
                 "pallas": dict(use_pallas_rank_attention=True,
                                use_pallas_batch_fc=True,
                                use_pallas_cross_norm=True)}
    for impl in ("xla", "pallas"):
        if impls not in ("both", impl):
            continue
        table = EmbeddingTable(mf_dim=mf_dim, capacity=1 << 20, cfg=cfg,
                               unique_bucket_min=512)
        tx = optax.adam(5e-3)
        b0, ro0 = batches[0]
        with flags_scope(**flag_sets[impl]):
            params = model.init(jax.random.PRNGKey(0),
                                jnp.zeros((bs, s, d)),
                                jnp.zeros((bs, dense_dim)),
                                jnp.asarray(ro0), summary)
            opt = tx.init(params)

            @jax.jit
            def step(params, opt, values_k, segments, show_clk, dense,
                     label, ro, ins_w):
                def loss_fn(params, values_k):
                    pooled = fused_seqpool_cvm(values_k, segments,
                                               show_clk, bs, s)
                    logits = model.apply(params, pooled, dense, ro,
                                         summary)
                    ls = optax.sigmoid_binary_cross_entropy(logits, label)
                    return (jnp.sum(ls * ins_w)
                            / jnp.maximum(ins_w.sum(), 1.0))
                loss, (gp, gk) = jax.value_and_grad(
                    loss_fn, argnums=(0, 1))(params, values_k)
                upd, opt = tx.update(gp, opt, params)
                params = optax.apply_updates(params, upd)
                return params, opt, loss, gk

            def run_epoch(params, opt):
                for batch, ro in batches:
                    idx = table.prepare(batch)
                    values_k = table.pull(idx)
                    show_clk = jnp.stack([jnp.asarray(batch.show),
                                          jnp.asarray(batch.clk)], axis=1)
                    ins_w = jnp.asarray(
                        (batch.show > 0).astype(np.float32))
                    params, opt, loss, gk = step(
                        params, opt, values_k,
                        jnp.asarray(batch.segments), show_clk,
                        jnp.asarray(batch.dense),
                        jnp.asarray(batch.label), jnp.asarray(ro), ins_w)
                    gk = jnp.concatenate(
                        [gk[:, :2], gk[:, 2:] * (-1.0 * bs)], axis=1)
                    table.push(idx, gk)
                    jax.block_until_ready(loss)
                return params, opt

            params, opt = run_epoch(params, opt)     # warmup/compile
            t0 = time.perf_counter()
            for _ in range(num_passes):
                params, opt = run_epoch(params, opt)
            wall = time.perf_counter() - t0
        value = instances * num_passes / max(wall, 1e-9)
        metric = "adsrank_pv_examples_per_sec_per_chip"
        if impl == "pallas":
            metric += "_pallas"
        rows.append({
            "metric": metric, "value": round(value, 1),
            "unit": "examples/sec/chip",
            "vs_baseline": round(value / (1_000_000 / 16), 4),
            "mode": "pv", "shape": "pv", "impl": impl,
            "batch_size": bs, "pv_batch_size": desc.pv_batch_size,
            "instances_per_pass": instances, "n_pvs": n_pvs,
            "num_slots": s, "d_model": d_model, "max_rank": max_rank,
            "passes": num_passes, "wall_sec": round(wall, 3),
            "backend": jax.devices()[0].platform,
        })
    return rows


def measure_serve(shape: str = "uniform") -> list:
    """BENCH_MODE=serve (ISSUE 15 / ROADMAP item 3): the concurrent-
    serving lane. Trains a small DeepFM, publishes it through the
    artifact layer (``BoxPSHelper.publish_base`` → ``ArtifactStore``),
    adopts it into a snapshot-isolated ``ServingModel`` and then
    sustains batched inference (``predict_many`` micro-batches) over
    the training data, measuring:

        serving.{shape}.qps       queries (micro-batches)/sec — higher
                                  is better, the usual gate rule
        serving.{shape}.p99_ms    per-query p99 latency — gated
                                  LOWER-is-better (perf_gate ``*_ms``)

    The p99 comes from exact client-side timings; the same samples
    also land in the ``pbox_serving_latency_seconds`` histogram (the
    scrapeable p50/p99 lines — which additionally carry the cold-start
    compile sample the headline row excludes, so the two are close but
    not identical). BENCH_SERVE_QUERIES overrides the query count."""
    import tempfile

    import jax
    import optax

    from paddlebox_tpu.artifacts import ArtifactStore
    from paddlebox_tpu.data import DataFeedDesc, InMemoryDataset, SlotDef
    from paddlebox_tpu.models import DeepFM
    from paddlebox_tpu.ps import EmbeddingTable, SparseSGDConfig
    from paddlebox_tpu.ps.box_helper import BoxPSHelper
    from paddlebox_tpu.serving import ServingModel
    from paddlebox_tpu.train import Trainer

    (shape_slots, shape_avg, _bs, _recs, shape_vocab,
     shape_dist) = SHAPES[shape]
    bs = int(os.environ.get("BENCH_BATCH_SIZE", "4096"))
    num_records = int(os.environ.get("BENCH_RECORDS", str(bs * 32)))
    n_queries = int(os.environ.get("BENCH_SERVE_QUERIES", "256"))
    mf_dim = int(os.environ.get("BENCH_MF_DIM", 8))

    slots = [SlotDef("label", "float", 1), SlotDef("dense", "float", 13)]
    slots += [SlotDef(f"C{i}", "uint64")
              for i in range(1, shape_slots + 1)]
    desc = DataFeedDesc(slots=slots, batch_size=bs, label_slot="label",
                        key_bucket_min=(bs * shape_slots
                                        if shape_avg <= 1.0 else 4096))
    ds = InMemoryDataset(desc)
    ds.records = build_records(num_records, num_slots=shape_slots,
                               vocab_per_slot=shape_vocab, seed=11,
                               avg_keys_per_slot=shape_avg,
                               key_dist=shape_dist)
    ds.columnarize()

    cfg = SparseSGDConfig(mf_create_thresholds=0.0, mf_initial_range=1e-3)
    table = EmbeddingTable(mf_dim=mf_dim, capacity=1 << 21, cfg=cfg,
                           unique_bucket_min=desc.key_bucket_min)
    tr = Trainer(DeepFM(hidden=(64, 32)), table, desc,
                 tx=optax.adam(1e-3))
    tr.train_pass(ds)
    tr.sync_table()

    workdir = tempfile.mkdtemp(prefix="pbox_serve_bench_")
    store = ArtifactStore(os.path.join(workdir, "registry"))
    helper = BoxPSHelper(table)
    helper.publish_base(store)
    dense = os.path.join(workdir, "m")
    tr.save(dense)

    srv = ServingModel(DeepFM(hidden=(64, 32)), desc, mf_dim=mf_dim,
                       capacity=1 << 21)
    srv.adopt(store)
    srv.load_dense(dense + ".dense.pkl")
    srv.register_health()
    batches = list(ds.batches())

    # warmup: compile the serving forward + fault in the host mirror
    srv.predict(batches[0])
    lat: list = []
    examples = 0
    t0 = time.perf_counter()
    done = 0
    while done < n_queries:
        for batch in batches:
            if done >= n_queries:
                break
            q0 = time.perf_counter()
            pred, ins_w = srv.predict(batch, return_valid=True)
            lat.append(time.perf_counter() - q0)
            examples += int(ins_w.sum())
            done += 1
    wall = time.perf_counter() - t0
    lat.sort()
    p99_ms = lat[int(0.99 * (len(lat) - 1))] * 1e3
    p50_ms = lat[len(lat) // 2] * 1e3
    qps = done / max(wall, 1e-9)

    srv.release()
    if not os.environ.get("BENCH_SERVE_KEEP", ""):
        shutil.rmtree(workdir, ignore_errors=True)
    common = dict(mode="serve", shape=shape, batch=bs, queries=done,
                  backend=jax.devices()[0].platform,
                  examples_per_sec=round(examples / max(wall, 1e-9), 1))
    return [
        {"metric": f"serving.{shape}.qps", "value": round(qps, 2),
         "unit": "queries/sec", "p50_ms": round(p50_ms, 4),
         "p99_ms": round(p99_ms, 4), **common},
        {"metric": f"serving.{shape}.p99_ms",
         "value": round(p99_ms, 4), "unit": "ms/query",
         "qps": round(qps, 2), **common},
    ]


def xplane_device_busy_sec(trace_dir: str) -> float:
    """Parse the jax.profiler XPlane dump: summed UNION of XLA-module
    execution intervals on every /device: plane → measured device busy
    seconds (the round-5 answer to 'device_busy_frac is modeled, not
    measured')."""
    import glob as _glob

    import jax
    paths = _glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                       recursive=True)
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(sorted(paths)[-1])
    iv = []
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Modules":
                continue
            for ev in line.events:
                iv.append((float(ev.start_ns),
                           float(ev.start_ns) + float(ev.duration_ns)))
    iv.sort()
    busy = 0.0
    cur_s = cur_e = None
    for s, e in iv:
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                busy += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        busy += cur_e - cur_s
    return busy / 1e9


_PERF_GATE_MOD = None


def _perf_gate():
    """scripts/perf_gate loaded by path once (scripts/ is not a
    package; a bench run emits several rows)."""
    global _PERF_GATE_MOD
    if _PERF_GATE_MOD is None:
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "perf_gate", os.path.join(os.path.dirname(os.path.abspath(
                __file__)), "scripts", "perf_gate.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _PERF_GATE_MOD = mod
    return _PERF_GATE_MOD


def emit_result(row: dict) -> None:
    """Print one bench JSON line AND record it on the perf-regression
    trajectory (scripts/perf_gate.py): the recorded best per metric is
    what `perf_gate.py --check` gates future runs against, and a live
    row landing below the gate prints a loud REGRESSION banner here.
    BENCH_TRAJECTORY=0 disables recording; =path overrides."""
    print(json.dumps(row))
    if os.environ.get("BENCH_TRAJECTORY", "") == "0":
        return
    _perf_gate().record_result(row)


def setup_telemetry() -> None:
    """Write the run's telemetry JSONL next to the BENCH_*.json artifacts
    (repo root — same dir as this script), so every bench round carries
    per-pass stage/queue/HBM attribution for free
    (scripts/telemetry_report.py renders it). BENCH_TELEMETRY_JSONL
    overrides the path; =0 disables.

    BENCH_TRACE=1 (or =path) additionally records the causal pass
    trace (obs/trace): per-lane Chrome rows — main / preload.worker /
    epilogue.lane / ssd.compact — with build→consume flow arrows,
    saved at exit as BENCH_trace.json. Default OFF: the headline runs
    with tracing inert (the hub.active contract)."""
    import atexit

    from paddlebox_tpu.obs.hub import get_hub
    from paddlebox_tpu.obs.sinks import JsonlSink
    dest = os.environ.get("BENCH_TELEMETRY_JSONL", "")
    here = os.path.dirname(os.path.abspath(__file__))
    if dest != "0":
        path = dest or os.path.join(here, "BENCH_telemetry.jsonl")
        get_hub().add_sink(JsonlSink(path, truncate=True))
        print(f"telemetry jsonl: {path}", file=sys.stderr)
    tdest = os.environ.get("BENCH_TRACE", "")
    if tdest and tdest != "0":
        from paddlebox_tpu.obs.trace import ChromeLaneTraceSink
        from paddlebox_tpu.utils.profiler import ChromeTraceWriter
        tpath = (tdest if tdest != "1"
                 else os.path.join(here, "BENCH_trace.json"))
        writer = ChromeTraceWriter()
        get_hub().add_sink(ChromeLaneTraceSink(writer))
        atexit.register(writer.save, tpath)
        print(f"pass trace: {tpath}", file=sys.stderr)


def main() -> None:
    import optax
    from paddlebox_tpu.config import FLAGS
    from paddlebox_tpu.data import DataFeedDesc, InMemoryDataset, SlotDef
    from paddlebox_tpu.models import DeepFM
    from paddlebox_tpu.ps import EmbeddingTable, SparseSGDConfig
    from paddlebox_tpu.train import PassPreloader, Trainer

    dev = require_chip()
    setup_telemetry()

    # workload shape (BASELINE.json ladder): "uniform" = 26 slots, one
    # key each (rung 2 steady state); "ragged" = 26 slots, avg 5
    # variable keys/slot (the feed-log shape, data_feed.h:2066-2287);
    # "thousand" = 1000+ sparse slots, one key each (rung 4)
    shape = os.environ.get("BENCH_SHAPE", "uniform")
    # per-slot vocab: thousand-slot workloads share the key budget (1000
    # slots x 100k would overflow the 2^23-row table)
    (shape_slots, shape_avg, bs_default, rec_default,
     shape_vocab, shape_dist) = SHAPES[shape]
    bs = int(os.environ.get("BENCH_BATCH_SIZE", bs_default))
    num_records = int(os.environ.get("BENCH_RECORDS", rec_default))
    mf_dim = int(os.environ.get("BENCH_MF_DIM", 8))
    num_passes = int(os.environ.get("BENCH_PASSES", 5))
    mode = os.environ.get("BENCH_MODE", "resident")
    if mode == "pv":
        # PV-batch rank-attention lane (ISSUE 13): proves the CTR op
        # family in a real pull→train→push loop, one row per impl
        for row in measure_pv(int(os.environ.get("BENCH_PASSES", 3))):
            emit_result(row)
        return
    if mode == "serve":
        # concurrent-serving lane (ISSUE 15): snapshot-isolated
        # batched inference qps + p99 latency (p99 gates lower-is-
        # better — scripts/perf_gate.py *_ms rule)
        for row in measure_serve(shape):
            emit_result(row)
        return
    FLAGS.log_period_steps = 10 ** 9
    # the exact f64 host AUC finalize pulls the [2, 1e6] bucket tables
    # to the host per pass; the bench opts into the device reduce
    # (~1e-5 f32 drift)
    FLAGS.auc_device_reduce = True

    slots = [SlotDef("label", "float", 1), SlotDef("dense", "float", 13)]
    slots += [SlotDef(f"C{i}", "uint64") for i in range(1, shape_slots + 1)]
    # uniform: one key per slot → exact key bucket (bs*S), zero padding
    # waste and a single compile variant; ragged: bucket rides the max
    desc = DataFeedDesc(slots=slots, batch_size=bs, label_slot="label",
                        key_bucket_min=(bs * shape_slots
                                        if shape_avg <= 1.0 else 4096))

    def make_ds(seed: int) -> InMemoryDataset:
        d = InMemoryDataset(desc)
        d.records = build_records(num_records, num_slots=shape_slots,
                                  vocab_per_slot=shape_vocab, seed=seed,
                                  avg_keys_per_slot=shape_avg,
                                  key_dist=shape_dist)
        d.columnarize()
        return d

    cfg = SparseSGDConfig(mf_create_thresholds=0.0, mf_initial_range=1e-3)
    metric = "deepfm_ctr_examples_per_sec_per_chip"
    if shape != "uniform":
        metric += f"_{shape}"
    chips = 1

    if mode == "sharded":
        # mesh-mode benchmark: the SHARDED trainer (key%N all_to_all
        # embedding routing + psum dense + sharded AUC) over a mesh of
        # every chip of the host. Reported value stays PER-CHIP for a
        # comparable vs_baseline.
        import jax
        from paddlebox_tpu.parallel import make_mesh
        from paddlebox_tpu.ps.sharded import ShardedEmbeddingTable
        from paddlebox_tpu.train.sharded import ShardedTrainer
        chips = len(jax.devices())
        metric += "_sharded"
        mesh = make_mesh(chips)
        table = ShardedEmbeddingTable(
            chips, mf_dim=mf_dim, capacity_per_shard=(1 << 23) // chips,
            cfg=cfg, req_bucket_min=1 << 12, serve_bucket_min=1 << 12)
        swire = os.environ.get("BENCH_FLOAT_WIRE", "q8")
        if swire not in ("q8", "f32"):
            print(f"warning: BENCH_FLOAT_WIRE={swire} unsupported in "
                  "sharded mode, using f32", file=sys.stderr)
            swire = "f32"
        tr = ShardedTrainer(DeepFM(hidden=(512, 256, 128)), table,
                            desc, mesh, tx=optax.adam(1e-3),
                            float_wire=swire)
        build_fn = tr.build_resident_pass
        if "BENCH_ARENA" in os.environ:
            print("warning: BENCH_ARENA is ignored in sharded mode",
                  file=sys.stderr)
    elif mode in ("tiered", "stream"):
        pass  # table/trainer built inside the mode's measurement branch
    else:
        # slot-arena allocation → the resident path ships the COMPACT
        # wire (per-key ~17-bit slot-local rows, no dedup streams); set
        # BENCH_ARENA=0 to measure the host-dedup wire instead
        arena = int(os.environ.get("BENCH_ARENA", "1"))
        table = EmbeddingTable(mf_dim=mf_dim, capacity=1 << 23, cfg=cfg,
                               unique_bucket_min=1 << 12,
                               arena_slots=shape_slots if arena else None)
        tr = Trainer(DeepFM(hidden=(512, 256, 128)), table, desc,
                     tx=optax.adam(1e-3), prefetch=8)
        build_fn = None

    extras = {"mode": mode, "shape": shape, "batch_size": bs,
              "records_per_pass": num_records, "num_slots": shape_slots,
              "avg_keys_per_slot": shape_avg}
    if mode == "tiered":
        emit_result(measure_tiered(
            int(os.environ.get("BENCH_PASSES", 4)), shape=shape))
        return
    elif mode == "stream":
        # windowed streaming-ingest bench (docs/RESILIENCE.md
        # §Streaming): criteo-format text files through the windowed
        # QueueDataset + Trainer.train_stream — end-to-end ingest
        # (parse, window dispatch, train, stream-boundary checkpoints),
        # headline in windows/sec. The first window is the warmup
        # (compile + first upload); the measured call CONTINUES the same
        # stream in-process, which is exactly the resumable-window
        # contract the mode exists to exercise.
        import shutil
        import tempfile
        from paddlebox_tpu.data import DatasetFactory
        from paddlebox_tpu.data.criteo import generate_criteo_files
        from paddlebox_tpu.train.checkpoint import CheckpointManager
        n_files = int(os.environ.get("BENCH_STREAM_FILES", "12"))
        rows = int(os.environ.get("BENCH_STREAM_ROWS_PER_FILE", "2048"))
        FLAGS.stream_window_files = int(
            os.environ.get("BENCH_STREAM_WINDOW_FILES", "2"))
        FLAGS.stream_ckpt_every_windows = int(
            os.environ.get("BENCH_STREAM_CKPT_EVERY", "2"))
        sdesc = DataFeedDesc.criteo(batch_size=bs)
        sdesc.key_bucket_min = max(4096, bs * 26)
        stream_tr = Trainer(
            DeepFM(hidden=(512, 256, 128)),
            EmbeddingTable(mf_dim=mf_dim, capacity=1 << 23, cfg=cfg,
                           unique_bucket_min=1 << 12),
            sdesc, tx=optax.adam(1e-3))
        base = tempfile.mkdtemp(prefix="pbox_stream_bench_")
        try:
            files = generate_criteo_files(
                os.path.join(base, "data"), num_files=n_files,
                rows_per_file=rows, vocab_per_slot=100_000,
                seed=FLAGS.seed)
            ds = DatasetFactory().create_dataset("QueueDataset", sdesc)
            ds.set_filelist(files)
            cm = CheckpointManager(os.path.join(base, "ckpt"))
            stream_tr.train_stream(ds, cm, max_windows=1)  # warmup
            t0 = time.perf_counter()
            out = stream_tr.train_stream(ds, cm)
            wall = time.perf_counter() - t0
        finally:
            shutil.rmtree(base, ignore_errors=True)
        meas_files = int(out["files"])
        emit_result({
            "metric": "stream_windows_per_sec",
            "value": round(out["windows"] / wall, 3),
            "unit": "windows/sec",
            "vs_baseline": None,
            "mode": mode,
            "window_files": FLAGS.stream_window_files,
            "ckpt_every_windows": FLAGS.stream_ckpt_every_windows,
            "windows": int(out["windows"]),
            "files": meas_files,
            "rows_per_file": rows,
            "batches": int(out["batches"]),
            "replayed_files": int(out["replayed_files"]),
            "files_per_sec": round(meas_files / wall, 2),
            "examples_per_sec": round(meas_files * rows / wall, 1),
            "wall_sec": round(wall, 3),
        })
        return
    elif mode == "streaming":
        # distinct gate key: the per-batch streaming pass measures a
        # different pipeline than the resident headline, and the perf
        # trajectory (scripts/perf_gate.py) keys on the metric name —
        # sharing the resident name would gate streaming runs against
        # the resident recorded best
        metric += "_streaming"
        ds = make_ds(0)
        warm = InMemoryDataset(desc)
        warm.records = build_records(bs * 3, num_slots=shape_slots,
                                     vocab_per_slot=shape_vocab, seed=99,
                                     avg_keys_per_slot=shape_avg,
                                     key_dist=shape_dist)
        warm.columnarize()
        tr.train_pass(warm)
        res = tr.train_pass(ds)
        value = res["examples_per_sec"]
    else:
        # Device-resident passes with double-buffered preload — the
        # reference's steady state (preload_into_memory overlaps training,
        # BeginPass stages the pass in HBM; SURVEY.md §3.3). Pass 0 pays
        # compile+upload; measurement is ADAPTIVE: at least BENCH_PASSES
        # passes, extended until the trimmed estimate stabilizes within
        # 10% (a bimodal pass wall cannot fake a steady rate) or a
        # pass/wall budget is hit. Datasets come from a cycled pool:
        # synthetic data GENERATION is the data source, not the system
        # under test (the measured pipeline still includes batch build,
        # row assign and upload via the preloader).
        import itertools
        pool = [make_ds(s) for s in range(4)]
        datasets = itertools.cycle(pool)
        # q8 float wire (per-column affine int8 dense + exact-u8
        # label/show/clk) — CTR dense features fit 8-bit affine
        # (test_resident_q8_wire_learns covers AUC parity)
        import jax.numpy as jnp
        wire = os.environ.get("BENCH_FLOAT_WIRE", "q8")
        wire = {"bf16": jnp.bfloat16, "f32": np.float32}.get(wire, wire)
        blockp = os.environ.get("BENCH_BLOCK_PRELOAD", "0") == "1"
        debug = os.environ.get("BENCH_DEBUG", "0") == "1"
        no_overlap = os.environ.get("BENCH_NO_OVERLAP", "0") == "1"
        # pipeline depth: FLAGS.preload_depth unless overridden;
        # BENCH_NO_OVERLAP = the manual kick-per-pass control (depth 0)
        depth = (0 if no_overlap else
                 int(os.environ.get("BENCH_PRELOAD_DEPTH",
                                    str(FLAGS.preload_depth))))
        pre = (PassPreloader(datasets, build_fn=build_fn, depth=depth)
               if build_fn is not None else
               PassPreloader(datasets, table, floats_dtype=wire,
                             block_transfers=blockp, depth=depth))
        pre.start_next()
        rp = pre.wait()
        pre.start_next()
        tr.train_pass_resident(rp)          # warmup/compile pass
        # per-pass wall includes that pass's preload wait
        walls_l, waits_l, trains_l, rates_l, wire_l = [], [], [], [], []
        max_passes = int(os.environ.get("BENCH_MAX_PASSES",
                                        str(max(12, num_passes))))
        budget_s = float(os.environ.get("BENCH_WALL_BUDGET_SEC", "180"))

        def trimmed_kept(walls):
            """Indices of the kept passes after dropping the worst ~20%
            (≥1, but never the only pass): one-off host stalls are
            environment noise; the TOTAL-based rate over the kept passes
            resists the alternating-wall pattern a plain median
            overstates."""
            d = max(1, len(walls) // 5) if len(walls) > 1 else 0
            order = np.argsort(walls)
            return order[:len(walls) - d], d

        def trimmed_estimate(walls):
            kept, d = trimmed_kept(walls)
            return (num_records * len(kept)
                    / sum(walls[i] for i in kept) / chips), d

        est_hist = []
        stable = False
        bench_t0 = time.perf_counter()
        while True:
            t0 = time.perf_counter()
            rp = pre.wait()
            t_wait = time.perf_counter() - t0
            if not no_overlap:
                pre.start_next()
            t1 = time.perf_counter()
            tr.train_pass_resident(rp)
            t_train = time.perf_counter() - t1
            if no_overlap:
                pre.start_next()
            wall = time.perf_counter() - t0
            if debug:
                print(f"pass: wait={t_wait:.3f}s train={t_train:.3f}s",
                      file=sys.stderr)
            walls_l.append(wall)
            waits_l.append(t_wait)
            trains_l.append(t_train)
            rates_l.append(rp.num_records / wall)
            if hasattr(rp, "nbytes"):
                wire_l.append(rp.nbytes())
            if len(walls_l) >= 2:
                est_hist.append(trimmed_estimate(walls_l)[0])
            if len(walls_l) < num_passes:
                continue
            # stable = two consecutive estimate moves both within 10%
            stable = (len(est_hist) >= 3
                      and abs(est_hist[-1] - est_hist[-2])
                      <= 0.10 * est_hist[-2]
                      and abs(est_hist[-2] - est_hist[-3])
                      <= 0.10 * est_hist[-3])
            if stable or len(walls_l) >= max_passes \
                    or time.perf_counter() - bench_t0 > budget_s:
                break
        # one EXTRA traced pass (not in the headline estimate): XPlane
        # device-span measurement of the TRUE duty cycle — the modeled
        # device_busy_frac below divides a wire-free rerun rate into
        # wall and inherits that rerun's error; this one is measured
        import jax
        busy_meas = None
        if os.environ.get("BENCH_XPLANE", "1") == "1":
            import shutil
            import tempfile
            xdir = tempfile.mkdtemp(prefix="pbox_xplane_")
            try:
                rp = pre.wait()
                pre.start_next()
                t0 = time.perf_counter()
                with jax.profiler.trace(xdir):
                    tr.train_pass_resident(rp)
                wall_t = time.perf_counter() - t0
                busy_meas = xplane_device_busy_sec(xdir) / wall_t
            finally:
                shutil.rmtree(xdir, ignore_errors=True)
        # quiesce the pipeline before the wire-free rerun: the cycled
        # dataset source ALWAYS has passes building ahead, and their
        # background batch-build + H2D upload would contaminate
        # dev_only (deflating device_only_ex_per_sec /
        # device_busy_frac). stop() halts the worker (an in-flight
        # build aborts or completes), drain() joins it, and the
        # remaining staged passes' transfers are waited out.
        pre.drain()
        while True:
            rp_next = pre.wait()
            if rp_next is None:
                break
            if getattr(rp_next, "dev", None) is not None:
                jax.block_until_ready(jax.tree.leaves(rp_next.dev))
        # device-only rate: re-run the LAST staged pass (its wire is
        # already resident, so nothing crosses host→device) — the clean
        # numerator for MFU / duty-cycle attribution. TWO reruns, the
        # second measured: a single rerun underreads steady state ~15%
        # (first-rerun warmup effects — XPlane-verified on the sharded
        # pass, DESIGN_NOTES §4i addendum). NOTE: these are real
        # training passes (params/table/AUC see the last pass again);
        # they run after every measured number is taken and the bench
        # reports throughput only, so nothing downstream reads the
        # perturbed model state — keep them LAST if extending the bench.
        tr.train_pass_resident(rp)
        t0 = time.perf_counter()
        tr.train_pass_resident(rp)
        dev_only = rp.num_records / (time.perf_counter() - t0)
        value, n_dropped = trimmed_estimate(walls_l)
        # evidence block: per-pass arrays + duty cycle + wire + MFU
        # (PrintSyncTimer per-stage reporting, box_wrapper.cc:1182)
        params = (tr.state.params if hasattr(tr.state, "params")
                  else None)
        fpe = dense_flops_per_example(params) if params is not None else 0
        peaks = device_peaks(dev.device_kind)
        peak = peaks["bf16_flops_per_sec"]
        # honest duty cycle: the device's ACTUAL compute time per pass is
        # records/dev_only (wire-free rerun); jnp.asarray is lazy, so
        # sum(train)/sum(wall) counts in-step H2D waits as "busy" and
        # saturates exactly when the device is idlest (the round-3
        # reviewer finding) — report both, clearly named
        n_meas = len(walls_l)
        dev_time_total = num_records * n_meas / max(dev_only, 1e-9)
        extras.update(
            passes=n_meas,
            passes_dropped=n_dropped,
            estimate_stable=stable,
            # deep pass pipeline attribution (ISSUE 5 / BENCH_r06):
            # depth, total prologue stall over the measured passes, and
            # the per-stage build-seconds breakdown so a starved
            # pipeline names its slow stage (front/dedup/pack/h2d)
            preload_depth=pre.depth if not no_overlap else 0,
            preload_depth_clamped=pre.depth_clamped,
            prologue_wait_sec_total=round(sum(waits_l), 4),
            preload_builds=pre.builds,
            preload_build_sec_total=round(pre.build_sec_total, 4),
            preload_build_stage_sec={
                k: round(v, 4)
                for k, v in sorted(pre.build_stage_sec.items())},
            per_pass_wall_sec=[round(w, 3) for w in walls_l],
            per_pass_wait_sec=[round(w, 3) for w in waits_l],
            per_pass_train_sec=[round(w, 3) for w in trains_l],
            per_pass_ex_per_sec=[round(r, 1) for r in rates_l],
            # fraction of wall the device spent on real compute
            device_busy_frac=round(
                min(dev_time_total / max(sum(walls_l), 1e-9), 1.0), 4),
            # XPlane-measured duty over one traced (extra) pass: union
            # of XLA-module device spans / pass wall — measured, not
            # derived from the wire-free rerun model
            device_busy_frac_measured=(None if busy_meas is None
                                       else round(busy_meas, 4)),
            # fraction of wall spent inside the step CALL (includes
            # waiting on in-flight wire — NOT device busyness)
            wall_in_step_frac=round(sum(trains_l) / max(sum(walls_l),
                                                        1e-9), 4),
            flops_per_example_dense=round(fpe),
            # per-chip rate over one chip's peak (value is already /chips)
            mfu_dense=round(value * fpe / peak, 6),
            # wire-free rerun of the staged pass: pure device throughput
            device_only_ex_per_sec=round(dev_only / chips, 1),
            mfu_dense_device_only=round(dev_only / chips * fpe / peak, 6),
            device_kind=dev.device_kind,
            peak_bf16_tflops=peak / 1e12,
            peak_source=peaks["source"],
        )
        if wire_l:
            wire_rate = sum(wire_l) / 1e6 / max(sum(walls_l), 1e-9)
            extras.update(
                wire_mb_per_pass=round(np.mean(wire_l) / 1e6, 2),
                wire_bytes_per_record=round(
                    np.mean(wire_l) / num_records, 1),
                wire_mb_per_sec=round(wire_rate, 2))
        if (mode == "sharded"
                and os.environ.get("BENCH_A2A_PROBE", "1") == "1"):
            # measured exchange/compute attribution (ISSUE 11;
            # train/a2a_probe): per-chunk a2a vs pool seconds, plus the
            # fused-schedule A/B over the same wire. Runs AFTER every
            # headline number (its timed steps are real training steps,
            # same discipline as the wire-free rerun); emits
            # a2a.pull.*/a2a.push spans when BENCH_TRACE is on, and
            # exchange_wait rides the next pass event's critical_path.
            from paddlebox_tpu.train.a2a_probe import probe_exchange
            pr = probe_exchange(tr, dataset=pool[0])
            # one extra wire-free pass so the probe's exchange_wait
            # part rides a pass event's critical_path block (the
            # telemetry/report view of the attribution)
            tr.train_pass_resident(rp)
            extras.update(
                a2a_chunks=pr["a2a_chunks"],
                exchange_overlap_frac=pr["exchange_overlap_frac"],
                exchange_sec_total=pr["exchange_sec_total"],
                exchange_wait_sec=pr["exchange_wait_sec"],
                a2a_pull_sec=pr["a2a_pull_sec"],
                a2a_pool_sec=pr["pool_sec"],
                a2a_push_sec=pr["push_sec"],
                step_monolithic_sec=pr["step_monolithic_sec"],
                step_chunked_sec=pr["step_chunked_sec"])
    baseline_per_chip = 1_000_000 / 16  # v5p-32 north-star / chips
    if (mode == "resident" and shape == "uniform"
            and os.environ.get("BENCH_TIERED_ROW", "1") == "1"):
        # the driver runs plain `python bench.py`: emit the tiered
        # delta-staging architecture row in the same artifact. Headline
        # line stays LAST for parsers that take the final line.
        emit_result(measure_tiered(num_passes=3))
    emit_result({
        "metric": metric,
        "value": round(value, 1),
        "unit": "examples/sec/chip",
        "vs_baseline": round(value / baseline_per_chip, 4),
        **extras,
    })


if __name__ == "__main__":
    main()
