"""True multi-process training integration: N worker processes on
localhost, each reading its rank's file shard, exchanging records through
the TcpShuffler (global shuffle over "DCN"), training the same model, and
reporting metrics — the reference's ``test_dist_base`` strategy
(SURVEY.md §4: subprocess trainers on localhost endpoints, diff results).
"""

import json
import os
import socket
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from paddlebox_tpu.data.criteo import generate_criteo_files

WORKER = textwrap.dedent("""
    import json, os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
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
    coll_eps = os.environ["COLLECTIVE_ENDPOINTS"].split(",")
    data_dir, out_dir = sys.argv[1], sys.argv[2]

    desc = DataFeedDesc.criteo(batch_size=64)
    desc.key_bucket_min = 2048
    FLAGS.native_parse = False  # record objects needed for the exchange

    ds = DatasetFactory().create_dataset("InMemoryDataset", desc)
    files = sorted(os.path.join(data_dir, f)
                   for f in os.listdir(data_dir))
    ds.set_filelist(files, shard_by_rank=True)   # this rank's slice
    ds.load_into_memory()
    n_loaded = len(ds.records)

    sh = TcpShuffler(rank, world, endpoints, seed=11)
    ds.global_shuffle(sh)                        # cross-process exchange
    sh.close()
    n_after = len(ds.records)

    cfg = SparseSGDConfig(mf_create_thresholds=0.0, mf_initial_range=0.0,
                          learning_rate=0.05, mf_learning_rate=0.05)
    table = EmbeddingTable(mf_dim=4, capacity=1 << 13,
                           unique_bucket_min=2048, cfg=cfg)
    tr = Trainer(DeepFM(hidden=(16, 8)), table, desc,
                 tx=optax.adam(1e-2), seed=rank)
    for _ in range(3):
        res = tr.train_pass(ds)

    # ONE global AUC across workers (metrics.cc:288-304 role)
    coll = TcpCollective(rank, world, coll_eps)
    gres = auc_compute_global(tr.state.auc, coll)
    coll.close()

    out = dict(rank=rank, loaded=n_loaded, after_shuffle=n_after,
               auc=float(res["auc"]), global_auc=float(gres.auc),
               global_ins=float(gres.ins_num),
               features=int(table.feature_count))
    with open(os.path.join(out_dir, f"r{rank}.json"), "w") as fh:
        json.dump(out, fh)
    np.savez(os.path.join(out_dir, f"auc_r{rank}.npz"),
             **{f: np.asarray(x) for f, x in
                zip(tr.state.auc._fields, tr.state.auc)})
""")


def _free_ports(n):
    socks = [socket.socket() for _ in range(n)]
    for s in socks:
        s.bind(("127.0.0.1", 0))
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


@pytest.mark.slow
def test_two_process_shuffle_and_train(tmp_path):
    world = 2
    data_dir = tmp_path / "data"
    data_dir.mkdir()
    files = generate_criteo_files(str(data_dir), num_files=4,
                                  rows_per_file=300, vocab_per_slot=40,
                                  seed=3)
    out_dir = tmp_path / "out"
    out_dir.mkdir()
    worker = tmp_path / "worker.py"
    worker.write_text(WORKER)
    ports = _free_ports(2 * world)
    endpoints = ",".join(f"127.0.0.1:{p}" for p in ports[:world])
    coll_endpoints = ",".join(f"127.0.0.1:{p}" for p in ports[world:])

    procs = []
    for r in range(world):
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ, PBOX_RANK=str(r),
                   PBOX_WORLD_SIZE=str(world),
                   SHUFFLE_ENDPOINTS=endpoints,
                   COLLECTIVE_ENDPOINTS=coll_endpoints,
                   JAX_PLATFORMS="cpu",
                   PYTHONPATH=repo + os.pathsep
                   + os.environ.get("PYTHONPATH", ""))
        env.pop("XLA_FLAGS", None)  # single-device CPU is fine per worker
        procs.append(subprocess.Popen(
            [sys.executable, str(worker), str(data_dir), str(out_dir)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True))
    outs = [p.communicate(timeout=600)[0] for p in procs]
    if any(p.returncode != 0 for p in procs):
        raise AssertionError("\n\n".join(
            f"--- rank {r} rc={p.returncode} ---\n{o[-1500:]}"
            for r, (p, o) in enumerate(zip(procs, outs))))

    res = [json.load(open(out_dir / f"r{r}.json")) for r in range(world)]
    # every record loaded somewhere, every record landed somewhere
    assert sum(r["loaded"] for r in res) == 1200
    assert sum(r["after_shuffle"] for r in res) == 1200
    # the shuffle actually moved records (both ranks end non-empty and
    # differently sized than their raw shard with overwhelming odds)
    assert all(r["after_shuffle"] > 0 for r in res)
    # both workers trained to something sane on their shard
    for r in res:
        assert np.isfinite(r["auc"]) and r["auc"] > 0.55, res
        assert r["features"] > 0
    # the global AUC is identical on every rank and covers ALL instances
    assert res[0]["global_auc"] == pytest.approx(res[1]["global_auc"],
                                                 abs=1e-9)
    # 3 passes over 1200 records — the allreduced total, on EVERY rank
    for r in res:
        assert r["global_ins"] == 3 * 1200
    # and it equals a single-process AUC over the UNION of both ranks'
    # accumulated prediction histograms (the metrics.cc:288-304 merge)
    from paddlebox_tpu.metrics import AucState, auc_compute
    blobs = [np.load(out_dir / f"auc_r{r}.npz") for r in range(world)]
    merged = AucState(*[
        sum(np.asarray(b[f], np.float64) for b in blobs)
        for f in AucState._fields])
    union = auc_compute(merged)
    assert res[0]["global_auc"] == pytest.approx(union.auc, abs=1e-12)


MM_COMMON = textwrap.dedent("""
    import numpy as np
    from paddlebox_tpu.data import DataFeedDesc, SlotDef
    from paddlebox_tpu.data.dataset import InMemoryDataset
    from paddlebox_tpu.data.record import SlotRecord

    def build_dataset(n_dev, B=8, S=4, n_rec=96):
        slots = [SlotDef("label", "float", 1), SlotDef("dense", "float", 3)]
        slots += [SlotDef(f"C{i}", "uint64") for i in range(S)]
        desc = DataFeedDesc(slots=slots, batch_size=B, label_slot="label",
                            key_bucket_min=B * S)
        rng = np.random.default_rng(7)
        offsets = np.arange(S + 1, dtype=np.int32)
        recs = []
        for j in range(n_rec):
            label = float(rng.integers(0, 2))
            recs.append(SlotRecord(
                keys=rng.integers(0, 200, size=S).astype(np.uint64),
                slot_offsets=offsets,
                dense=rng.normal(size=3).astype(np.float32),
                label=label, show=1.0, clk=label,
                ins_id=f"ins_{j:05d}", uid=j % 7,
                rank=0, cmatch=401 if j % 3 == 0 else 402))
        ds = InMemoryDataset(desc)
        ds.records = recs
        return desc, ds

    def make_trainer(desc, mesh, n_dev):
        import optax
        from paddlebox_tpu.models import DeepFM
        from paddlebox_tpu.ps import SparseSGDConfig
        from paddlebox_tpu.ps.sharded import ShardedEmbeddingTable
        from paddlebox_tpu.train.sharded import ShardedTrainer
        cfg = SparseSGDConfig(mf_create_thresholds=0.0,
                              mf_initial_range=0.0)
        table = ShardedEmbeddingTable(n_dev, mf_dim=4,
                                      capacity_per_shard=512, cfg=cfg,
                                      req_bucket_min=16,
                                      serve_bucket_min=16)
        tr = ShardedTrainer(DeepFM(hidden=(16, 8)), table, desc, mesh,
                            tx=optax.adam(1e-2))
        tr.metrics.init_metric("q_auc", "auc")
        tr.metrics.init_metric("cm_auc", "cmatch_rank_auc",
                               cmatch_rank_group="401:0",
                               ignore_rank=True)
        tr.metrics.init_metric("wu", "wuauc")
        return tr
""")

DUMP_METRIC_WORKER = textwrap.dedent("""
    import json, os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    from paddlebox_tpu.distributed.launch import init_runtime_env
    info = init_runtime_env()
    rank = info["rank"]
    import numpy as np
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from mm_common import build_dataset, make_trainer
    from paddlebox_tpu.train.multihost import global_mesh, globalize_state
    from paddlebox_tpu.utils.dump import DumpConfig

    out_dir = sys.argv[1]
    n = jax.device_count()
    assert n == 4, n
    mesh = global_mesh()
    desc, ds = build_dataset(n)
    tr = make_trainer(desc, mesh, n)
    tr.state = globalize_state(mesh, tr.state, tr.step_fn.state_spec)
    tr.set_dump(DumpConfig(os.path.join(out_dir, "pod/preds"),
                           fields=("pred", "label", "show", "clk")))
    res = tr.train_pass(ds)
    # every process calls get_metric_msg in lockstep (collective gather)
    msgs = {nm: tr.metrics.get_metric_msg(nm)
            for nm in ("q_auc", "cm_auc", "wu")}
    with open(os.path.join(out_dir, f"pod_r{rank}.json"), "w") as fh:
        json.dump({"auc": res["auc"], "batches": res["batches"],
                   "last_loss": res["last_loss"], "msgs": msgs}, fh)
    print(f"rank={rank} dumpmetrics ok", flush=True)
""")


@pytest.mark.slow
def test_two_process_dump_and_metric_variants(tmp_path):
    """Per-worker dump + registry metric variants at pod scale: each
    process dumps its ADDRESSABLE device rows
    into its own part file and feeds its rows to its registry; the
    rank-dump concatenation equals the single-controller dump
    line-for-line, and every metric variant matches the
    single-controller value after the pod reduce."""
    import importlib.util

    import jax
    import optax  # noqa: F401  (mm_common imports it lazily)

    from tests.test_multihost_jax import _run_two_workers
    from paddlebox_tpu.parallel import make_mesh
    from paddlebox_tpu.utils.dump import DumpConfig

    common = tmp_path / "mm_common.py"
    common.write_text(MM_COMMON)
    spec = importlib.util.spec_from_file_location("mm_common", str(common))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    # oracle: single-controller, 4 local devices
    n = 4
    desc, ds = mod.build_dataset(n)
    tr = mod.make_trainer(desc, make_mesh(n), n)
    tr.set_dump(DumpConfig(str(tmp_path / "oracle/preds"),
                           fields=("pred", "label", "show", "clk")))
    res = tr.train_pass(ds)
    oracle_msgs = {nm: tr.metrics.get_metric_msg(nm)
                   for nm in ("q_auc", "cm_auc", "wu")}
    oracle_lines = [ln for d in range(n) for ln in open(
        tmp_path / f"oracle/preds.part-{d:05d}").read().splitlines()]
    assert len(oracle_lines) == 96

    outs = _run_two_workers(tmp_path, DUMP_METRIC_WORKER, "w_dm.py",
                            argv=[str(tmp_path)])
    for r, o in enumerate(outs):
        assert f"rank={r} dumpmetrics ok" in o, o

    # per-device part files are keyed by device row, so the pod run
    # (rank 0 writes rows 0-1, rank 1 rows 2-3) reproduces the
    # single-controller dump line-for-line when concatenated in device
    # order
    pod_lines = [ln for d in range(n) for ln in open(
        tmp_path / f"pod/preds.part-{d:05d}").read().splitlines()]
    assert pod_lines == oracle_lines

    # per-rank registry partials reduce to the single-controller values
    pod = [json.load(open(tmp_path / f"pod_r{r}.json")) for r in range(2)]
    for r in range(2):
        assert pod[r]["batches"] == res["batches"]
        assert pod[r]["auc"] == pytest.approx(res["auc"], abs=1e-6)
        assert pod[r]["last_loss"] == pytest.approx(res["last_loss"],
                                                    abs=1e-6)
        for nm, want in oracle_msgs.items():
            got = pod[r]["msgs"][nm]
            for k, v in want.items():
                assert got[k] == pytest.approx(v, abs=1e-6), (nm, k, got)
