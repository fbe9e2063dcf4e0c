#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

ONE process that owns the chip drives the repo's main paths once, through
the entry points a user calls, at a full DeepFM width:
DeepFM(512,256,128), 26 sparse slots + 13 dense, mf_dim 8, batch 8192,
an 8M-row HBM table, adam(1e-3) (the benchmark's own widths are in
benchmarks/configs/). Depth is cut (a few batches per pass, a few
passes per phase); weights are random, data comes from a seed. Phases:

  resident   InMemoryDataset → PassPreloader → Trainer.train_pass_resident
  streaming  Trainer.train_pass
  serve      publish_base → ServingModel.adopt → predict, against the
             trainer's own eval forward
  sharded    ShardedEmbeddingTable + ShardedTrainer over every device
  tiered     TieredShardedEmbeddingTable + the tiered pass pipeline +
             BoxPSHelper: begin_pass delta scatter, end_pass write-back
  kernels    each use_pallas_* flag on in turn, against the flag-off
             program, with the dispatch counter naming what ran

Each phase checks finite loss-like metrics, AUC in (0,1), the expected
global_step, touched table rows, and that nothing compiled after its
warm-up; any failure raises and the process exits non-zero. There is no
CPU scale-down: without a TPU the script refuses to run. Its timings are
smoke output, not measurements — they go into no record.

The last line of stdout is the JSON verdict the driver reads.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.metadata
import itertools
import json
import os
import sys
import tempfile
import time
from typing import Dict, Iterator, List, Optional, Tuple

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np


@dataclasses.dataclass(frozen=True)
class Widths:
    """Full DeepFM width for the resident, streaming, serve, sharded and
    tiered phases, and the AdsRank-PV widths of the kernels phase. Only
    tests pass anything else."""

    hidden: Tuple[int, ...] = (512, 256, 128)
    mf_dim: int = 8
    batch_size: int = 8192
    capacity: int = 1 << 23
    batches_per_pass: int = 4
    vocab_per_slot: int = 10_000
    ragged_avg_keys: float = 2.0
    pv_batch_size: int = 4096
    pv_d_model: int = 128
    pv_slots: int = 8
    pv_pvs: int = 4096
    pv_max_rank: int = 3
    pv_capacity: int = 1 << 20
    bucket_min: int = 1 << 12


# ---------------------------------------------------------------------------
# compile accounting (jax.monitoring — what each pass asked the compiler for)
# ---------------------------------------------------------------------------

_COMPILE_STAGES = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class CompileWatch:
    """Counts, between ``mark()`` calls: programs that needed an
    executable (one backend_compile event per jit-cache miss, whether
    XLA compiled it or the persistent cache served it), persistent-cache
    hits among them, and the seconds spent tracing+lowering+compiling."""

    def __init__(self) -> None:
        import jax.monitoring
        self.programs = 0
        self.cache_hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, secs: float, **_) -> None:
        if event in _COMPILE_STAGES:
            self.seconds += secs
            if event == _COMPILE_STAGES[2]:
                self.programs += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self) -> Tuple[int, int, float]:
        return self.programs, self.cache_hits, self.seconds

    def since(self, mark) -> Dict[str, float]:
        programs = self.programs - mark[0]
        hits = self.cache_hits - mark[1]
        return {"programs": programs, "cache_hits": hits,
                "xla_compiled": programs - hits,
                "compile_sec": round(self.seconds - mark[2], 3)}


class Phase:
    """One phase's bookkeeping: named passes with wall + compile deltas,
    the checks every phase shares, and the summary line."""

    def __init__(self, name: str, watch: CompileWatch) -> None:
        self.name = name
        self.watch = watch
        self.passes: List[Dict] = []
        self._t0 = time.perf_counter()
        self._m0 = watch.mark()
        print(f"[{name}] start", flush=True)

    @contextlib.contextmanager
    def timed(self, label: str) -> Iterator[None]:
        m, t = self.watch.mark(), time.perf_counter()
        yield
        rec = {"label": label,
               "wall_sec": round(time.perf_counter() - t, 3),
               **self.watch.since(m)}
        self.passes.append(rec)
        print(f"[{self.name}] {label}: " + " ".join(
            f"{k}={v}" for k, v in rec.items() if k != "label"), flush=True)

    def check(self, cond: bool, what: str) -> None:
        if not cond:
            raise AssertionError(f"[{self.name}] {what}")

    def check_pass_result(self, res: Dict, what: str) -> None:
        for k in ("mae", "rmse", "predicted_ctr", "actual_ctr"):
            self.check(np.isfinite(res[k]), f"{what}: {k}={res[k]}")
        if "last_loss" in res:
            self.check(np.isfinite(res["last_loss"]),
                       f"{what}: last_loss={res['last_loss']}")
        self.check(0.0 < res["auc"] < 1.0, f"{what}: auc={res['auc']}")

    def check_no_compile(self, from_pass: int) -> None:
        """Nothing asked the compiler for a program after the warm-up
        passes (steady state must be compile-free)."""
        for rec in self.passes[from_pass:]:
            self.check(rec["programs"] == 0,
                       f"{rec['label']} needed {rec['programs']} new "
                       f"program(s) after warm-up")

    def done(self) -> Dict:
        """wall = the whole phase; compile = seconds in trace + lower +
        compile anywhere in it (background threads included, so it can
        exceed wall); steady = wall of the timed passes that needed no
        program."""
        tot = self.watch.since(self._m0)
        out = {"phase": self.name,
               "wall_sec": round(time.perf_counter() - self._t0, 2),
               "compile_sec": tot["compile_sec"],
               "steady_sec": round(sum(
                   r["wall_sec"] for r in self.passes
                   if r["programs"] == 0), 3),
               "programs": tot["programs"],
               "persistent_cache_hits": tot["cache_hits"],
               "xla_compiled": tot["xla_compiled"]}
        print(f"[{self.name}] PASS " + json.dumps(out), flush=True)
        return out


# ---------------------------------------------------------------------------
# data (seeded; the README's file → parser → dataset path for DeepFM)
# ---------------------------------------------------------------------------

def criteo_dataset(workdir: str, name: str, rows: int, w: Widths,
                   seed: int, value_base: int = 0):
    """Criteo-format text files through the dataset factory and the
    native parser into an InMemoryDataset (README quick start).
    ``value_base`` shifts the id range: a later "day" that shares only
    part of its feature space with the first."""
    from paddlebox_tpu.data import DataFeedDesc, DatasetFactory
    from paddlebox_tpu.data.criteo import generate_criteo_files
    desc = DataFeedDesc.criteo(batch_size=w.batch_size)
    # one key per slot → exact key bucket, one compile variant
    desc.key_bucket_min = w.batch_size * len(desc.sparse_slots)
    files = generate_criteo_files(
        os.path.join(workdir, name), num_files=1, rows_per_file=rows,
        vocab_per_slot=w.vocab_per_slot, seed=seed, value_base=value_base)
    ds = DatasetFactory().create_dataset("InMemoryDataset", desc)
    ds.set_filelist(files)
    ds.load_into_memory()
    return ds, desc


def build_records(num_records: int, num_slots: int = 26,
                  vocab_per_slot: int = 100_000, seed: int = 0,
                  avg_keys_per_slot: float = 1.0,
                  key_dist: str = "uniform"):
    """Seeded criteo-shaped records, built columnar-fast. Slot ``s``
    draws its ids from ``[s * vocab_per_slot, (s + 1) * vocab_per_slot)``.

    ``avg_keys_per_slot > 1`` gives RAGGED slots: per-(record, slot) key
    counts ~ 1 + Poisson(avg-1), the feed-log shape that takes the
    segment stream and the non-trivial seqpool path.

    ``key_dist="zipf"`` draws a slot's ids from a bounded Zipf (s=1.2)
    instead of uniformly: a few ids dominate every batch."""
    from paddlebox_tpu.data.record import SlotRecord
    rng = np.random.default_rng(seed)

    def draw_keys(size):
        if key_dist == "zipf":
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


def build_pv_records(n_pvs: int, num_slots: int, vocab_per_slot: int,
                     dense_dim: int, seed: int = 0):
    """Seeded search pages for the PV rank-attention lane: 2-4 ads per
    PV with shuffled 1-based ranks and valid cmatch, so every batch
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


def ragged_dataset(rows: int, w: Widths, seed: int):
    """Multi-key slots (the feed-log shape): the only layout whose
    pooling is not a reshape, i.e. where the seqpool kernel runs."""
    from paddlebox_tpu.data import DataFeedDesc, InMemoryDataset, SlotDef
    slots = [SlotDef("label", "float", 1), SlotDef("dense", "float", 13)]
    slots += [SlotDef(f"C{i}", "uint64") for i in range(1, 27)]
    desc = DataFeedDesc(slots=slots, batch_size=w.batch_size,
                        label_slot="label", key_bucket_min=w.bucket_min)
    ds = InMemoryDataset(desc)
    ds.records = build_records(rows, num_slots=26,
                               vocab_per_slot=w.vocab_per_slot, seed=seed,
                               avg_keys_per_slot=w.ragged_avg_keys)
    ds.columnarize()
    return ds, desc


def sparse_cfg():
    from paddlebox_tpu.ps import SparseSGDConfig
    return SparseSGDConfig(mf_create_thresholds=0.0, mf_initial_range=1e-3)


def free_device_memory() -> None:
    """Drop the finished phase's tables before the next phase allocates
    its own (each phase builds an HBM table; trainer ↔ step cycles only
    die in a collection)."""
    gc.collect()


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def resident_trainer(desc, w: Widths, arena: bool = True):
    import optax
    from paddlebox_tpu.models import DeepFM
    from paddlebox_tpu.ps import EmbeddingTable
    from paddlebox_tpu.train import Trainer
    table = EmbeddingTable(
        mf_dim=w.mf_dim, capacity=w.capacity, cfg=sparse_cfg(),
        unique_bucket_min=w.bucket_min,
        arena_slots=len(desc.sparse_slots) if arena else None)
    return Trainer(DeepFM(hidden=w.hidden), table, desc,
                   tx=optax.adam(1e-3))


def run_preloaded_passes(ph: Phase, tr, pre, n_passes: int,
                         after_first=None) -> None:
    """n resident passes handed out by a depth-N preloader (the
    single-chip and the mesh trainer share the protocol)."""
    try:
        pre.start_next()
        for i in range(n_passes):
            with ph.timed(f"pass{i + 1}"):
                rp = pre.wait()
                ph.check(rp is not None, f"preloader ended at pass {i + 1}")
                pre.start_next()
                step0 = tr.global_step
                res = tr.train_pass_resident(rp)
            ph.check_pass_result(res, f"pass{i + 1}")
            ph.check(tr.global_step - step0 == rp.num_batches > 0,
                     f"global_step advanced {tr.global_step - step0}, "
                     f"expected {rp.num_batches}")
            if i == 0 and after_first is not None:
                after_first()
    finally:
        pre.drain()


def check_table_trained(ph: Phase, table, packed) -> None:
    """Rows were assigned and pushes landed in the device state."""
    import jax.numpy as jnp
    ph.check(table.obs_stats()["used"] > 0, "no table rows assigned")
    ph.check(float(jnp.abs(packed).sum()) > 0, "device table still zero")


def phase_resident(watch: CompileWatch, ds, desc, w: Widths) -> Dict:
    from paddlebox_tpu.config import FLAGS
    from paddlebox_tpu.train import PassPreloader
    ph = Phase("resident", watch)
    tr = resident_trainer(desc, w)
    n_passes = 3
    run_preloaded_passes(
        ph, tr, PassPreloader(itertools.repeat(ds, n_passes), tr.table,
                              depth=FLAGS.preload_depth), n_passes)
    check_table_trained(ph, tr.table, tr.state.table.packed)
    ph.check_no_compile(from_pass=1)
    return ph.done()


def phase_streaming(watch: CompileWatch, ds, desc, w: Widths):
    """Returns (summary, trainer) — the serve phase publishes this
    trainer's model."""
    ph = Phase("streaming", watch)
    tr = resident_trainer(desc, w)
    for i in range(2):
        with ph.timed(f"pass{i + 1}"):
            step0 = tr.global_step
            res = tr.train_pass(ds)
        ph.check_pass_result(res, f"pass{i + 1}")
        ph.check(tr.global_step - step0 == res["batches"] > 0,
                 f"global_step advanced {tr.global_step - step0}, "
                 f"expected {res['batches']}")
    check_table_trained(ph, tr.table, tr.state.table.packed)
    ph.check_no_compile(from_pass=1)
    return ph.done(), tr


def phase_serve(watch: CompileWatch, tr, ds, desc, w: Widths,
                workdir: str) -> Dict:
    from paddlebox_tpu.artifacts import ArtifactStore
    from paddlebox_tpu.metrics import init_auc_state
    from paddlebox_tpu.models import DeepFM
    from paddlebox_tpu.ps.box_helper import BoxPSHelper
    from paddlebox_tpu.serving import ServingModel
    from paddlebox_tpu.train.step import make_device_batch
    ph = Phase("serve", watch)
    tr.sync_table()
    store = ArtifactStore(os.path.join(workdir, "registry"))
    with ph.timed("publish_base"):
        BoxPSHelper(tr.table).publish_base(store)
        dense = os.path.join(workdir, "model")
        tr.save(dense)
    srv = ServingModel(DeepFM(hidden=w.hidden), desc, mf_dim=w.mf_dim,
                       capacity=w.capacity)
    try:
        with ph.timed("adopt"):
            srv.adopt(store)
            srv.load_dense(dense + ".dense.pkl")
        batches = list(ds.batches())
        ph.check(len(batches) >= 2, "need at least two request batches")
        preds = []
        for i, batch in enumerate(batches):
            with ph.timed(f"predict{i + 1}"):
                pred, valid = srv.predict(batch, return_valid=True)
            ph.check(pred.shape == (desc.batch_size,), f"shape {pred.shape}")
            live = pred[valid > 0]
            ph.check(live.size > 0 and np.isfinite(live).all()
                     and (live > 0).all() and (live < 1).all(),
                     f"predict{i + 1}: predictions outside (0,1)")
            preds.append(pred)
        # reference: the trainer's own eval forward on the same batch
        # (tests/test_serving.py's oracle and tolerance)
        idx = tr.table.prepare_eval(batches[0])
        _, ref = tr.step_fn.eval(tr.state.table, tr.state.params,
                                 init_auc_state(),
                                 make_device_batch(batches[0], idx))
        diff = float(np.max(np.abs(preds[0] - np.asarray(ref))))
        print(f"[serve] max |serve - trainer eval| = {diff:.3e}", flush=True)
        np.testing.assert_allclose(preds[0], np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)
    finally:
        srv.release()
    # passes: publish, adopt, predict1 (compiles), predict2.. (must not)
    ph.check_no_compile(from_pass=3)
    return ph.done()


def device_memory_report(label: str, packed) -> Optional[List[int]]:
    """Per-device bytes_in_use (where the backend reports it) and where
    the table state lives."""
    import jax
    devs = jax.devices()
    stats = [d.memory_stats() for d in devs]
    in_use = (None if any(s is None for s in stats)
              else [int(s["bytes_in_use"]) for s in stats])
    holders = sorted(d.id for d in packed.sharding.device_set)
    shapes = sorted((s.device.id, tuple(s.data.shape))
                    for s in packed.addressable_shards)
    print(f"[memory] {label}: bytes_in_use={in_use} "
          f"table_device_set={holders} shards={shapes}", flush=True)
    return in_use


def check_born_sharded(ph: Phase, label: str, packed) -> None:
    """Every device holds exactly its shard of the stacked table state,
    and no device (device 0 included) holds more than its share plus
    the small replicated state."""
    import jax
    n = len(jax.devices())
    in_use = device_memory_report(label, packed)
    ph.check(len(packed.sharding.device_set) == n,
             f"{label}: table state on "
             f"{len(packed.sharding.device_set)} of {n} devices")
    for s in packed.addressable_shards:
        ph.check(s.data.shape[0] == 1,
                 f"{label}: device {s.device.id} holds {s.data.shape}")
    if in_use is None:
        return
    share = packed.size * packed.dtype.itemsize // n
    slack = share // 4 + (256 << 20)   # AUC tables, dense state, staged wire
    for d, b in enumerate(in_use):
        ph.check(b >= share, f"{label}: device {d} holds {b} B < its "
                 f"table share {share} B")
        ph.check(b <= share + slack, f"{label}: device {d} holds {b} B > "
                 f"share {share} B + slack {slack} B")


def check_every_shard_trained(ph: Phase, table) -> None:
    """Pushes reached every owner shard — the all_to_all crossed chips."""
    import jax.numpy as jnp
    mass = np.asarray(jnp.abs(table.state.packed).sum(axis=(1, 2)))
    print(f"[{ph.name}] per-shard |table| mass = {mass.tolist()}",
          flush=True)
    ph.check(bool((mass > 0).all()), f"untrained shard(s): {mass.tolist()}")
    ph.check(table.feature_count() > 0, "no table rows assigned")


def sharded_trainer(table, desc, w: Widths):
    import jax
    import optax
    from paddlebox_tpu.models import DeepFM
    from paddlebox_tpu.parallel import make_mesh
    from paddlebox_tpu.train.sharded import ShardedTrainer
    return ShardedTrainer(DeepFM(hidden=w.hidden), table, desc,
                          make_mesh(len(jax.devices())),
                          tx=optax.adam(1e-3))


def phase_sharded(watch: CompileWatch, ds, desc, w: Widths) -> Dict:
    import jax
    from paddlebox_tpu.config import FLAGS
    from paddlebox_tpu.ps.sharded import ShardedEmbeddingTable
    from paddlebox_tpu.train import PassPreloader
    ph = Phase("sharded", watch)
    n = len(jax.devices())
    table = ShardedEmbeddingTable(
        n, mf_dim=w.mf_dim, capacity_per_shard=w.capacity // n,
        cfg=sparse_cfg(), req_bucket_min=w.bucket_min,
        serve_bucket_min=w.bucket_min)
    check_born_sharded(ph, "sharded table constructed", table.state.packed)
    tr = sharded_trainer(table, desc, w)
    n_passes = 3
    run_preloaded_passes(
        ph, tr, PassPreloader(itertools.repeat(ds, n_passes),
                              build_fn=tr.build_resident_pass,
                              depth=FLAGS.preload_depth), n_passes,
        after_first=lambda: check_born_sharded(
            ph, "sharded after pass 1", table.state.packed))
    check_every_shard_trained(ph, table)
    ph.check_no_compile(from_pass=1)
    return ph.done()


def phase_tiered(watch: CompileWatch, ds_a, ds_b, desc, w: Widths) -> Dict:
    """Windows A, B, A through the tiered pass pipeline, then one
    classic BoxPSHelper window. Window 2 is the first DELTA window (B's
    new keys scatter into the persistent HBM window, its routing buckets
    may be new shapes), so steady state is judged on window 3."""
    import jax
    from paddlebox_tpu.config import FLAGS
    from paddlebox_tpu.ps import BoxPSHelper
    from paddlebox_tpu.ps.tiered import TieredShardedEmbeddingTable
    ph = Phase("tiered", watch)
    n = len(jax.devices())
    table = TieredShardedEmbeddingTable(
        n, mf_dim=w.mf_dim, capacity_per_shard=(w.capacity // 2) // n,
        cfg=sparse_cfg(), req_bucket_min=w.bucket_min,
        serve_bucket_min=w.bucket_min)
    check_born_sharded(ph, "tiered table constructed", table.state.packed)
    tr = sharded_trainer(table, desc, w)
    pipe = tr.tiered_pass_pipeline(iter([ds_a, ds_b, ds_a]),
                                   depth=FLAGS.preload_depth)
    staged, written = [], []
    try:
        pipe.start_next()
        for i in range(3):
            with ph.timed(f"window{i + 1}"):
                rp = pipe.wait()
                ph.check(rp is not None, f"pipeline ended at window {i + 1}")
                pipe.begin_pass()
                staged.append(int(table.last_pass_stats["staged"]))
                pipe.start_next()
                step0 = tr.global_step
                res = tr.train_pass_resident(rp)
                pipe.end_pass()
                written.append(int(table.last_pass_stats["written_back"]))
            ph.check_pass_result(res, f"window{i + 1}")
            ph.check(tr.global_step - step0 == rp.num_batches > 0,
                     f"global_step advanced {tr.global_step - step0}, "
                     f"expected {rp.num_batches}")
            if i == 0:
                check_born_sharded(ph, "tiered after window 1",
                                   table.state.packed)
    finally:
        pipe.drain()
    table.fence()
    eps = table.endpass_stats()
    print(f"[tiered] staged rows per window = {staged}, written back = "
          f"{written}, epilogue = {eps}", flush=True)
    ph.check(staged[0] > 0, "cold window staged nothing")
    ph.check(staged[1] > 0, "delta window staged nothing (begin_pass "
             "delta scatter never ran)")
    ph.check(all(x > 0 for x in written), f"end_pass wrote back {written}")
    ph.check(eps["jobs_run"] >= 3, f"epilogue ran {eps['jobs_run']} jobs")
    # the classic protocol through the helper: begin → train → end
    helper = BoxPSHelper(table, trainer=tr)
    with ph.timed("helper_window"):
        helper.begin_pass(ds_b)
        res = tr.train_pass_resident(tr.build_resident_pass(ds_b))
        helper.end_pass(ds_b)
        helper.fence()
    ph.check_pass_result(res, "helper_window")
    check_every_shard_trained(ph, table)
    ph.check(ph.passes[2]["programs"] == 0,
             f"window3 needed {ph.passes[2]['programs']} new program(s)")
    return ph.done()


# ---- kernels ---------------------------------------------------------------

def dispatch_value(kernel: str, impl: str) -> int:
    from paddlebox_tpu.obs.hub import get_hub
    return int(get_hub().counter("pbox_kernel_dispatch_total").value(
        kernel=kernel, impl=impl))


def logical_state(tr):
    """(sorted keys, host rows by field, dense param leaves) — the
    numeric form of state_digest (tests/test_pallas_train_gate.py)."""
    import jax
    tr.sync_table()
    with tr.table.host_lock:
        keys, rows = tr.table.index.items()
    order = np.argsort(keys)
    blob = tr.table._gather_host(rows[order])
    leaves = [np.asarray(x) for x in jax.tree.leaves(
        jax.device_get(tr.state.params))]
    return keys[order], blob, leaves


def resident_kernel_run(ph: Phase, flag: Optional[str],
                        expect: Dict[str, str], ds, desc, w: Widths,
                        exact: bool):
    """One seeded resident pass with ``flag`` on (None = all flags off).
    Flag on: the dispatch counter must name the expected impl and must
    NOT book pallas for a program that runs something else. Returns the
    trained state — a digest (``exact``) or its numeric form."""
    from paddlebox_tpu.config import flags_scope
    from paddlebox_tpu.train.checkpoint import state_digest
    label = f"{flag}=1" if flag else "flags-off"
    before = {k: (dispatch_value(k, "pallas"), dispatch_value(k, v))
              for k, v in expect.items()}
    with flags_scope(**({flag: True} if flag else {})):
        tr = resident_trainer(desc, w, arena=False)
        with ph.timed(label):
            res = tr.train_pass_resident(ds)
        ph.check_pass_result(res, label)
        out = state_digest(tr) if exact else logical_state(tr)
    for k, impl in expect.items():
        ph.check(dispatch_value(k, impl) > before[k][1],
                 f"{flag}: kernel {k!r} never booked impl={impl!r}")
        if impl != "pallas":
            ph.check(dispatch_value(k, "pallas") == before[k][0],
                     f"{flag}: kernel {k!r} booked pallas for a program "
                     f"that runs {impl!r}")
    if expect:
        print(f"[kernels] {flag}: dispatch booked {expect}", flush=True)
    del tr
    free_device_memory()
    return out


def check_state_close(flag: str, off, on) -> None:
    """tests/test_pallas_train_gate.py::test_zipf_ragged_state_close —
    same keys, table rows and dense params within the f32 tolerance of
    a different MXU summation order."""
    np.testing.assert_array_equal(off[0], on[0])
    worst = 0.0
    for f in sorted(off[1]):
        worst = max(worst, float(np.max(np.abs(on[1][f] - off[1][f]),
                                        initial=0.0)))
        np.testing.assert_allclose(
            on[1][f], off[1][f], rtol=2e-4, atol=2e-5,
            err_msg=f"{flag}: table field {f} beyond parity tolerance")
    for a, b in zip(off[2], on[2]):
        worst = max(worst, float(np.max(np.abs(b - a), initial=0.0)))
        np.testing.assert_allclose(b, a, rtol=2e-4, atol=2e-5,
                                   err_msg=f"{flag}: dense params")
    print(f"[kernels] {flag}: max |flag-on - flag-off| = {worst:.3e} "
          f"(rtol 2e-4, atol 2e-5)", flush=True)


class PvJob:
    """The AdsRank-PV job: PV-merged batches with a rank_offset matrix
    through rank_attention + the slot_fc batch_fc tower + the
    cross_norm block, over a pull→train→push loop."""

    def __init__(self, w: Widths) -> None:
        import jax
        import jax.numpy as jnp
        from paddlebox_tpu.data import DataFeedDesc, SlotDef
        from paddlebox_tpu.data.pv import PvBatchBuilder
        from paddlebox_tpu.models import AdsRank
        from paddlebox_tpu.ops import init_cross_norm_summary
        self.w = w
        self.bs, self.s, dense_dim = w.pv_batch_size, w.pv_slots, 4
        slots = [SlotDef("label", "float", 1),
                 SlotDef("dense", "float", dense_dim)]
        slots += [SlotDef(f"C{i}", "uint64") for i in range(self.s)]
        desc = DataFeedDesc(slots=slots, batch_size=self.bs,
                            label_slot="label",
                            pv_batch_size=max(1, self.bs // 8),
                            key_bucket_min=max(512, self.bs * self.s))
        recs = build_pv_records(w.pv_pvs, self.s, w.vocab_per_slot,
                                dense_dim)
        self.batches = PvBatchBuilder(
            desc, max_rank=w.pv_max_rank).batches(recs)
        self.summary = init_cross_norm_summary(1, w.pv_d_model)
        kw = dict(d_model=w.pv_d_model, max_rank=w.pv_max_rank,
                  hidden=(128, 64), slot_fc=True, cross_norm=True)
        self.model = AdsRank(**kw)
        # the same tower computing in f32: parity of the kernels is
        # judged without bf16 layers re-quantizing their outputs
        self.model_f32 = AdsRank(compute_dtype=jnp.float32, **kw)
        self.params0 = self.model.init(
            jax.random.PRNGKey(0),
            jnp.zeros((self.bs, self.s, 3 + w.mf_dim)),
            jnp.zeros((self.bs, dense_dim)),
            jnp.asarray(self.batches[0][1]), self.summary)

    def new_table(self):
        from paddlebox_tpu.ps import EmbeddingTable
        return EmbeddingTable(mf_dim=self.w.mf_dim,
                              capacity=self.w.pv_capacity,
                              cfg=sparse_cfg(), unique_bucket_min=512)

    def loss_fn(self, model):
        import jax.numpy as jnp
        import optax
        from paddlebox_tpu.ops import fused_seqpool_cvm
        bs, s, summary = self.bs, self.s, self.summary

        def loss(params, values_k, segments, show_clk, dense, label, ro,
                 ins_w):
            pooled = fused_seqpool_cvm(values_k, segments, show_clk, bs, s)
            logits = model.apply(params, pooled, dense, ro, summary)
            ls = optax.sigmoid_binary_cross_entropy(logits, label)
            return (jnp.sum(ls * ins_w) / jnp.maximum(ins_w.sum(), 1.0),
                    logits)
        return loss

    def inputs(self, table, batch, ro):
        """(idx, device inputs) of one PV batch: prepare + pull."""
        import jax.numpy as jnp
        idx = table.prepare(batch)
        return idx, (
            table.pull(idx), jnp.asarray(batch.segments),
            jnp.stack([jnp.asarray(batch.show), jnp.asarray(batch.clk)],
                      axis=1),
            jnp.asarray(batch.dense), jnp.asarray(batch.label),
            jnp.asarray(ro),
            jnp.asarray((batch.show > 0).astype(np.float32)))


def pv_train_pass(ph: Phase, label: str, flags: Dict[str, bool],
                  job: PvJob):
    """One pull→train→push pass under ``flags``; returns (per-batch
    losses, trained params, trained table)."""
    import jax
    import jax.numpy as jnp
    import optax
    from paddlebox_tpu.config import flags_scope
    with flags_scope(**flags):
        table = job.new_table()
        tx = optax.adam(5e-3)
        params, opt = job.params0, tx.init(job.params0)
        loss_fn = job.loss_fn(job.model)

        @jax.jit
        def step(params, opt, values_k, *rest):
            (loss, _), (gp, gk) = jax.value_and_grad(
                loss_fn, argnums=(0, 1), has_aux=True)(
                    params, values_k, *rest)
            upd, opt = tx.update(gp, opt, params)
            return optax.apply_updates(params, upd), opt, loss, gk

        losses = []
        with ph.timed(label):
            for batch, ro in job.batches:
                idx, args = job.inputs(table, batch, ro)
                params, opt, loss, gk = step(params, opt, *args)
                table.push(idx, jnp.concatenate(
                    [gk[:, :2], gk[:, 2:] * (-1.0 * job.bs)], axis=1))
                losses.append(float(loss))
    ph.check(len(losses) > 0 and bool(np.isfinite(losses).all()),
             f"{label}: losses {losses}")
    ph.check(len(table.index) > 0, f"{label}: no table rows")
    return np.asarray(losses), params, table


def pv_tower_outputs(ph: Phase, label: str, flags: Dict[str, bool],
                     job: PvJob, params, table) -> List:
    """Forward + backward of the f32 tower over every batch of the pass,
    from ONE fixed (params, table) — no optimizer between the kernel and
    the comparison. f32 matmul precision on both sides: a TPU runs the
    XLA composition's f32 einsums as one bf16 pass by default (~1e-3),
    the kernels compute at Precision.HIGHEST."""
    import jax
    import jax.numpy as jnp
    from paddlebox_tpu.config import flags_scope
    with flags_scope(**flags), jax.default_matmul_precision("highest"):
        loss_fn = job.loss_fn(job.model_f32)

        def summed(params, values_k, *rest):
            # the SUMMED loss: O(1) grads, so the absolute tolerance of
            # the grad comparison means something
            loss, logits = loss_fn(params, values_k, *rest)
            return loss * jnp.maximum(rest[-1].sum(), 1.0), logits

        @jax.jit
        def fwd_bwd(params, values_k, *rest):
            (_, logits), (gp, gk) = jax.value_and_grad(
                summed, argnums=(0, 1), has_aux=True)(
                    params, values_k, *rest)
            return logits, gp, gk

        outs = []
        with ph.timed(label):
            for batch, ro in job.batches:
                _, args = job.inputs(table, batch, ro)
                logits, gp, gk = fwd_bwd(params, *args)
                outs.append([np.asarray(logits), np.asarray(gk)] + [
                    np.asarray(x) for x in jax.tree.leaves(gp)])
    return outs


def phase_kernels(watch: CompileWatch, ds_uniform, desc_uniform,
                  w: Widths) -> Dict:
    from paddlebox_tpu.obs import MemorySink
    from paddlebox_tpu.obs.hub import get_hub
    from paddlebox_tpu.ops.pallas_kernels import _interpret
    ph = Phase("kernels", watch)
    print(f"[kernels] _interpret() = {_interpret()}", flush=True)
    # the dispatch counter only books under an active hub
    get_hub().add_sink(MemorySink())
    ds_ragged, desc_ragged = ragged_dataset(
        w.batch_size * w.batches_per_pass, w, seed=5)
    # seqpool: MXU one-hot pooling sums in another order → tolerance
    rag_off = resident_kernel_run(ph, None, {}, ds_ragged, desc_ragged, w,
                                  exact=False)
    rag_on = resident_kernel_run(
        ph, "use_pallas_seqpool",
        {"fused_embed_pool_cvm": "pallas", "seqpool_grad": "mxu"},
        ds_ragged, desc_ragged, w, exact=False)
    check_state_close("use_pallas_seqpool", rag_off, rag_on)
    del rag_off, rag_on
    # gather moves the same bits → the digest must not change
    uni_off = resident_kernel_run(ph, None, {}, ds_uniform, desc_uniform,
                                  w, exact=True)
    flag = "use_pallas_gather"
    got = resident_kernel_run(ph, flag, {"gather_rows": "pallas"},
                              ds_uniform, desc_uniform, w, exact=True)
    ph.check(got == uni_off, f"{flag}: state digest differs from "
             f"flag-off ({got[:12]} vs {uni_off[:12]})")
    print(f"[kernels] {flag}: state digest bit-identical to flag-off",
          flush=True)

    # the CTR family over one AdsRank-PV pass at the Widths' pv_* sizes
    job = PvJob(w)
    off = dict(use_pallas_rank_attention=False, use_pallas_batch_fc=False,
               use_pallas_cross_norm=False)
    ref_losses, params, table = pv_train_pass(ph, "pv train flags-off",
                                              off, job)
    ref = pv_tower_outputs(ph, "pv tower flags-off", off, job, params,
                           table)
    for flag, kernel in (("use_pallas_rank_attention", "rank_attention"),
                         ("use_pallas_batch_fc", "batch_fc"),
                         ("use_pallas_cross_norm", "cross_norm")):
        on = dict(off, **{flag: True})
        before = dispatch_value(kernel, "pallas")
        # (1) the kernel inside the real train loop: runs, stays finite,
        # and the loss trajectory tracks the flag-off pass
        losses, _, t_on = pv_train_pass(ph, f"pv train {flag}", on, job)
        del t_on
        np.testing.assert_allclose(losses, ref_losses, rtol=2e-3,
                                   atol=2e-4, err_msg=f"{flag}: losses")
        # (2) tests/test_pallas_ctr.py::test_ads_rank_full_tower_parity
        # on every batch of the pass: logits and every grad
        got = pv_tower_outputs(ph, f"pv tower {flag}", on, job, params,
                               table)
        ph.check(dispatch_value(kernel, "pallas") >= before + 2,
                 f"{flag}: kernel {kernel!r} never booked impl='pallas'")
        worst_logit = worst_grad = 0.0
        for i, (r, g) in enumerate(zip(ref, got)):
            np.testing.assert_allclose(
                g[0], r[0], rtol=1e-4, atol=1e-5,
                err_msg=f"{flag}: logits of batch {i}")
            worst_logit = max(worst_logit,
                              float(np.max(np.abs(g[0] - r[0]))))
            for a, b in zip(r[1:], g[1:]):
                ph.check(bool(np.isfinite(b).all()),
                         f"{flag}: non-finite grad in batch {i}")
                np.testing.assert_allclose(
                    b, a, rtol=5e-3, atol=1e-4,
                    err_msg=f"{flag}: grads of batch {i}")
                worst_grad = max(worst_grad,
                                 float(np.max(np.abs(b - a), initial=0.0)))
        print(f"[kernels] {flag}: booked {kernel}/pallas; loss trajectory "
              f"max diff {np.max(np.abs(losses - ref_losses)):.3e}; tower "
              f"max |logit diff| {worst_logit:.3e}, max |grad diff| "
              f"{worst_grad:.3e}", flush=True)
    del table
    return ph.done()


# ---------------------------------------------------------------------------

def main() -> int:
    t_start = time.perf_counter()
    import jax
    dev = jax.devices()[0]
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    print(f"jax {jax.__version__} jaxlib "
          f"{importlib.metadata.version('jaxlib')} libtpu {libtpu}")
    print(f"platform={dev.platform} device_kind={dev.device_kind!r} "
          f"device_count={len(jax.devices())}", flush=True)
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke.py needs a TPU: jax found platform "
            f"{dev.platform!r} ({dev.device_kind}). There is no CPU mode.")

    from paddlebox_tpu.native import native_status, require_native
    from paddlebox_tpu.utils.compile_cache import enable_compilation_cache
    require_native()
    print(f"native library: {native_status()} on this host "
          f"(libpbox_native.so from the tracked sources)")
    print(f"compile cache: {enable_compilation_cache()}", flush=True)

    w = Widths()
    n = len(jax.devices())
    watch = CompileWatch()
    summaries = []
    with tempfile.TemporaryDirectory(prefix="pbox_chip_smoke_") as work:
        t0 = time.perf_counter()
        rows = w.batch_size * w.batches_per_pass
        ds_a, desc = criteo_dataset(work, "a", rows, w, seed=1)
        ds_small, _ = criteo_dataset(work, "small", 2 * w.batch_size, w,
                                     seed=3)
        print(f"[data] {rows}+{2 * w.batch_size} criteo rows parsed in "
              f"{time.perf_counter() - t0:.1f}s", flush=True)

        summaries.append(phase_resident(watch, ds_a, desc, w))
        free_device_memory()
        s, tr = phase_streaming(watch, ds_small, desc, w)
        summaries.append(s)
        summaries.append(phase_serve(watch, tr, ds_small, desc, w, work))
        del tr
        free_device_memory()

        # mesh phases: one global batch = n device batches
        t0 = time.perf_counter()
        if n == 1:
            mesh_a = ds_a
        else:
            mesh_a, _ = criteo_dataset(work, "mesh_a", rows * n, w, seed=1)
        # day B shares half of day A's ids: its window is a real delta
        mesh_b, _ = criteo_dataset(work, "mesh_b", rows * n, w, seed=2,
                                   value_base=w.vocab_per_slot // 2)
        print(f"[data] mesh datasets ({rows * n} rows each) in "
              f"{time.perf_counter() - t0:.1f}s", flush=True)
        summaries.append(phase_sharded(watch, mesh_a, desc, w))
        free_device_memory()
        summaries.append(phase_tiered(watch, mesh_a, mesh_b, desc, w))
        del mesh_a, mesh_b
        free_device_memory()

        summaries.append(phase_kernels(watch, ds_a, desc, w))

    print("[summary] " + json.dumps(summaries))
    print(f"[summary] total wall {time.perf_counter() - t_start:.1f}s; "
          f"{watch.programs} programs needed an executable: "
          f"{watch.cache_hits} came from the persistent cache, "
          f"{watch.programs - watch.cache_hits} XLA compiled; "
          f"{watch.seconds:.1f}s in trace+lower+compile")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": n}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
