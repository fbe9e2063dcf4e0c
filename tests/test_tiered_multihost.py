"""Tiered sharded PS on a multi-controller mesh (ps/tiered_multihost.py):
per-process host tiers behind a global table — the pod topology where
each AIBox node owns its PS slice (box_wrapper.h:446-450, SURVEY §2.6).

Single-process test proves the mechanics (owned = all shards must equal
the plain tiered table bit-for-bit); the 2-process test proves the pod
split (each process's host tiers hold exactly its shards, training
matches the single-process oracle)."""

import os
import textwrap

import numpy as np
import jax
import optax
import pytest

from paddlebox_tpu.config import flags_scope
from paddlebox_tpu.data import DataFeedDesc, DatasetFactory
from paddlebox_tpu.data.criteo import generate_criteo_files
from paddlebox_tpu.models import DeepFM
from paddlebox_tpu.parallel import make_mesh
from paddlebox_tpu.ps import (BoxPSHelper, SparseSGDConfig,
                              TieredShardedEmbeddingTable)
from paddlebox_tpu.ps.tiered_multihost import MultihostTieredShardedTable
from paddlebox_tpu.train.sharded import ShardedTrainer

N = 8


def _cfg():
    return SparseSGDConfig(mf_create_thresholds=0.0, mf_initial_range=0.0,
                           learning_rate=0.1, mf_learning_rate=0.1)


def _ds(tmp_path, seed=71):
    files = generate_criteo_files(str(tmp_path / f"mh{seed}"), num_files=1,
                                  rows_per_file=800, vocab_per_slot=40,
                                  seed=seed)
    desc = DataFeedDesc.criteo(batch_size=32)
    desc.key_bucket_min = 1024
    ds = DatasetFactory().create_dataset("InMemoryDataset", desc)
    ds.set_filelist(files)
    ds.load_into_memory()
    return ds, desc


def test_multihost_tiered_single_process_matches_plain(tmp_path):
    """With one process owning every shard, the multihost table's
    local-scatter/reassembly path must reproduce the plain tiered table
    exactly (same AUC, same dense params, same host-tier content)."""
    assert len(jax.devices()) >= N
    mesh = make_mesh(N)
    ds, desc = _ds(tmp_path)

    def run(table):
        with flags_scope(log_period_steps=10000):
            tr = ShardedTrainer(DeepFM(hidden=(16, 16)), table, desc,
                                mesh, tx=optax.adam(2e-3), seed=5)
        helper = BoxPSHelper(table, trainer=tr)
        r = None
        for _ in range(2):
            helper.begin_pass(ds)
            r = tr.train_pass(ds)
            helper.end_pass(ds)
        return tr, r

    ta = TieredShardedEmbeddingTable(N, mf_dim=4, capacity_per_shard=2048,
                                     cfg=_cfg(), req_bucket_min=256,
                                     serve_bucket_min=256)
    tb = MultihostTieredShardedTable(mesh, mf_dim=4,
                                     capacity_per_shard=2048, cfg=_cfg(),
                                     req_bucket_min=256,
                                     serve_bucket_min=256)
    assert tb.owned == set(range(N))
    tra, ra = run(ta)
    trb, rb = run(tb)
    assert rb["ins_num"] == ra["ins_num"]
    assert np.isclose(rb["auc"], ra["auc"], atol=1e-9), (ra["auc"],
                                                         rb["auc"])
    for x, y in zip(jax.tree.leaves(tra.state.params),
                    jax.tree.leaves(trb.state.params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for s in range(N):
        ka, _ = ta.hosts[s].index.items()
        kb, _ = tb.hosts[s].index.items()
        np.testing.assert_array_equal(np.sort(ka), np.sort(kb))
        a = ta.hosts[s].fetch(np.sort(ka))
        b = tb.hosts[s].fetch(np.sort(ka))
        np.testing.assert_array_equal(a["embed_w"], b["embed_w"])
        np.testing.assert_array_equal(a["show"], b["show"])
    # delta staging engaged on pass 2 identically
    assert tb.last_pass_stats["resident"] > 0
    assert tb.last_pass_stats["staged"] == ta.last_pass_stats["staged"]


MH_TIERED_WORKER = textwrap.dedent("""
    import os, sys, json
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    from paddlebox_tpu.distributed.launch import init_runtime_env
    info = init_runtime_env()
    rank = info["rank"]
    import numpy as np
    import optax
    from paddlebox_tpu.config import FLAGS
    FLAGS.log_period_steps = 10 ** 9
    from paddlebox_tpu.models import DeepFM
    from paddlebox_tpu.ps import SparseSGDConfig
    from paddlebox_tpu.ps.tiered_multihost import MultihostTieredShardedTable
    from paddlebox_tpu.train.multihost import (global_mesh, stage_global,
                                               stage_global_batch)
    from paddlebox_tpu.train.sharded import (ShardedTrainer,
                                             ShardedStepState,
                                             make_global_arrays)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from mh_common import build_case

    n = jax.device_count()
    assert n == 4, n
    mesh = global_mesh()
    desc, batches = build_case(n)
    cfg = SparseSGDConfig(mf_create_thresholds=0.0, mf_initial_range=0.0,
                          learning_rate=0.1, mf_learning_rate=0.1)
    table = MultihostTieredShardedTable(mesh, mf_dim=4,
                                        capacity_per_shard=512, cfg=cfg,
                                        req_bucket_min=16,
                                        serve_bucket_min=16)
    tr = ShardedTrainer(DeepFM(hidden=(16, 16)), table, desc, mesh,
                        tx=optax.adam(1e-3))

    # the pass working set: all batch keys (identical on every process)
    keys = np.unique(np.concatenate(
        [b.keys[:b.num_keys] for b in batches]))
    table.begin_pass(keys)
    host = make_global_arrays(batches, table.prepare_global(batches))
    gb = stage_global_batch(mesh, host)
    st0 = tr.state
    state = ShardedStepState(
        table=table.state,
        params=jax.tree.map(lambda l: stage_global(
            mesh, np.asarray(jax.device_get(l)), shard_dim0=False),
            st0.params),
        opt_state=jax.tree.map(lambda l: stage_global(
            mesh, np.asarray(jax.device_get(l)), shard_dim0=False),
            st0.opt_state),
        auc=type(st0.auc)(*[stage_global(
            mesh, np.asarray(jax.device_get(l)), shard_dim0=True)
            for l in st0.auc]),
        step=stage_global(mesh, np.asarray(jax.device_get(st0.step)),
                          shard_dim0=False))
    losses = []
    for i in range(2):
        state, stats = tr.step_fn(state, gb, jax.random.PRNGKey(i))
        l = stats["loss"]
        l = (np.asarray(jax.device_get(l.addressable_shards[0].data))
             if hasattr(l, "addressable_shards") else np.asarray(l))
        losses.append(float(np.ravel(l)[0]))
    table.state = state.table
    table.end_pass()

    want = [float(x) for x in os.environ["ORACLE_LOSSES"].split(",")]
    for got, w in zip(losses, want):
        assert abs(got - w) < 1e-6, (losses, want)
    # each process's host tiers hold exactly its owned shards
    fp = {}
    for s in sorted(table.owned):
        ks, _ = table.hosts[s].index.items()
        ks = np.sort(ks)
        vals = table.hosts[s].fetch(ks)
        fp[str(s)] = [ks.tolist(),
                      np.round(vals["embed_w"], 6).tolist()]
    assert all(table.hosts[s] is None
               for s in range(n) if s not in table.owned)
    with open(os.path.join(os.environ["OUT_DIR"],
                           f"host_r{rank}.json"), "w") as fh:
        json.dump(fp, fh)
    print(f"rank={rank} tiered-mh ok losses={losses} "
          f"owned={sorted(table.owned)}", flush=True)
""")


@pytest.mark.slow
def test_two_process_tiered_matches_single_process(tmp_path):
    """The pod split: 2 processes × 2 devices form one 4-shard global
    mesh; each process's host tiers carry exactly its 2 shards. Step
    losses and every shard's written-back host values must match a
    single-process 4-shard tiered run of the same batches."""
    from test_multihost_jax import MH_COMMON, _run_two_workers
    import importlib.util
    import json

    common = tmp_path / "mh_common.py"
    common.write_text(MH_COMMON)
    spec = importlib.util.spec_from_file_location("mh_common", str(common))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    n = 4
    desc, batches = mod.build_case(n)
    cfg = SparseSGDConfig(mf_create_thresholds=0.0, mf_initial_range=0.0,
                          learning_rate=0.1, mf_learning_rate=0.1)
    oracle_table = TieredShardedEmbeddingTable(
        n, mf_dim=4, capacity_per_shard=512, cfg=cfg,
        req_bucket_min=16, serve_bucket_min=16)
    with flags_scope(log_period_steps=10 ** 9):
        tr = ShardedTrainer(DeepFM(hidden=(16, 16)), oracle_table, desc,
                            make_mesh(n), tx=optax.adam(1e-3))
    keys = np.unique(np.concatenate(
        [b.keys[:b.num_keys] for b in batches]))
    oracle_table.begin_pass(keys)
    from paddlebox_tpu.train.sharded import make_global_batch
    gb = make_global_batch(batches, oracle_table.prepare_global(batches))
    state = tr.state
    oracle = []
    for i in range(2):
        state, stats = tr.step_fn(state, gb, jax.random.PRNGKey(i))
        oracle.append(float(stats["loss"]))
    oracle_table.state = state.table
    oracle_table.end_pass()

    out_dir = tmp_path / "out"
    out_dir.mkdir()
    outs = _run_two_workers(
        tmp_path, MH_TIERED_WORKER, "w_tiered.py",
        extra_env={"ORACLE_LOSSES": ",".join(f"{x:.9f}" for x in oracle),
                   "OUT_DIR": str(out_dir)})
    for r, o in enumerate(outs):
        assert f"rank={r} tiered-mh ok" in o, o

    # union of the two processes' host tiers == the oracle's, shard by
    # shard, value for value
    seen = set()
    for r in range(2):
        fp = json.load(open(out_dir / f"host_r{r}.json"))
        for s_str, (ks, ws) in fp.items():
            s = int(s_str)
            assert s not in seen  # each shard owned by exactly one rank
            seen.add(s)
            ka, _ = oracle_table.hosts[s].index.items()
            ka = np.sort(ka)
            np.testing.assert_array_equal(np.asarray(ks, np.uint64), ka)
            want = oracle_table.hosts[s].fetch(ka)["embed_w"]
            np.testing.assert_allclose(np.asarray(ws), want, atol=2e-6)
    assert seen == set(range(n))


MH_TIERED_ELASTIC_WORKER = textwrap.dedent("""
    import json, os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    from paddlebox_tpu.distributed.launch import init_runtime_env
    info = init_runtime_env()
    rank = info["rank"]
    import numpy as np
    import optax
    from paddlebox_tpu.config import FLAGS
    FLAGS.log_period_steps = 10 ** 9
    from paddlebox_tpu.data import DataFeedDesc, DatasetFactory
    from paddlebox_tpu.data.criteo import generate_criteo_files
    from paddlebox_tpu.models import DeepFM
    from paddlebox_tpu.ps import BoxPSHelper, SparseSGDConfig
    from paddlebox_tpu.ps.tiered_multihost import MultihostTieredShardedTable
    from paddlebox_tpu.train.checkpoint import CheckpointManager
    from paddlebox_tpu.train.multihost import global_mesh
    from paddlebox_tpu.train.sharded import ShardedTrainer

    out_dir = sys.argv[1]
    kill_after = os.environ.get("KILL_AFTER_PASS")
    resume = os.environ.get("RESUME") == "1"
    n_passes = int(os.environ["N_PASSES"])

    n = jax.device_count()
    assert n == 4, n
    mesh = global_mesh()

    # identical datasets on every process (the SPMD host contract);
    # two "days" with offset value ranges exercise the delta chain
    dss = []
    desc = DataFeedDesc.criteo(batch_size=32)
    desc.key_bucket_min = 1024
    for i, base in enumerate((0, 700)):
        files = generate_criteo_files(
            os.path.join(out_dir, f"data{i}"), num_files=1,
            rows_per_file=400, vocab_per_slot=25, seed=60 + i,
            value_base=base)
        ds = DatasetFactory().create_dataset("InMemoryDataset", desc)
        ds.set_filelist(files)
        ds.load_into_memory()
        dss.append(ds)

    cfg = SparseSGDConfig(mf_create_thresholds=0.0, mf_initial_range=0.0,
                          learning_rate=0.1, mf_learning_rate=0.1)
    table = MultihostTieredShardedTable(mesh, mf_dim=4,
                                        capacity_per_shard=2048, cfg=cfg,
                                        req_bucket_min=128,
                                        serve_bucket_min=128)
    tr = ShardedTrainer(DeepFM(hidden=(16, 16)), table, desc, mesh,
                        tx=optax.adam(2e-3))
    tr.globalize_dense_state()   # table leaf is already a global array
    helper = BoxPSHelper(table, trainer=tr)
    nb_per_pass = sum(1 for _ in tr._group_iter(dss[0].batches()))

    # PER-PROCESS checkpoint dir: each rank's base+delta chain carries
    # its OWNED shards' host tiers (the per-node SaveBase convention)
    cm = CheckpointManager(os.path.join(out_dir, f"ckpt_r{rank}"),
                           keep=10)
    start_pass = 0
    if resume:
        restored = cm.restore(tr)   # LoadSSD2Mem role: rebuilds owned
        assert restored is not None # host tiers + drop_window + dense
        start_pass = restored // nb_per_pass
        print(f"rank {rank}: resumed at pass {start_pass}", flush=True)

    res = None
    for p in range(start_pass, n_passes):
        ds = dss[p % 2]
        helper.begin_pass(ds)
        res = tr.train_pass(ds)
        helper.end_pass(ds)
        if kill_after is not None and not resume \\
                and p == int(kill_after):
            # the gang dies WITHOUT saving this pass (its work is lost;
            # the restarted gang replays it from the chain)
            os._exit(1)
        cm.save(tr, delta=(p > 0))

    params = np.concatenate([np.asarray(l).ravel()
                             for l in jax.tree.leaves(tr.state.params)])
    fp = {}
    for s in sorted(table.owned):
        ks, _ = table.hosts[s].index.items()
        ks = np.sort(ks)
        vals = table.hosts[s].fetch(ks)
        fp[str(s)] = [int(len(ks)),
                      float(np.abs(vals["embed_w"]).sum()),
                      float(np.abs(vals["embedx_w"]).sum())]
    out = dict(rank=rank, auc=float(res["auc"]),
               step=int(tr.global_step),
               param_sum=float(np.abs(params).sum()), hosts=fp)
    with open(os.path.join(out_dir, f"final_r{rank}.json"), "w") as fh:
        json.dump(out, fh)
    np.save(os.path.join(out_dir, f"params_r{rank}.npy"), params)
    print(f"rank={rank} elastic-mh ok step={tr.global_step}", flush=True)
""")


@pytest.mark.slow
def test_pod_topology_elastic_recovery(tmp_path):
    """Elastic recovery of the POD topology: a
    2-process global-mesh gang over MultihostTieredShardedTable dies
    mid-run WITHOUT saving its in-flight pass; the restarted gang's
    ranks rebuild their OWNED shards' host tiers from their per-process
    save_base + delta chains (LoadSSD2Mem on recovery,
    box_wrapper.cc:1415; load → drop_window is the recovery entry),
    resume at the last pass boundary, and the final params + per-shard
    host-tier content match an uninterrupted run."""
    import json

    from test_multihost_jax import _run_two_workers

    n_passes = 4

    def run(sub, kill, resume):
        out = tmp_path / sub
        out.mkdir(exist_ok=True)
        env = {"N_PASSES": str(n_passes)}
        if kill is not None:
            env["KILL_AFTER_PASS"] = str(kill)
        if resume:
            env["RESUME"] = "1"
        try:
            _run_two_workers(tmp_path, MH_TIERED_ELASTIC_WORKER,
                             f"w_el_{sub}_{resume}.py", extra_env=env,
                             argv=[str(out)])
            return True
        except AssertionError:
            return False

    # attempt 1 dies after pass 1 (unsaved); the "replacement" gang
    # resumes from the per-rank chains and completes
    assert not run("killed", kill=1, resume=False)
    assert run("killed", kill=None, resume=True)
    # uninterrupted oracle
    assert run("clean", kill=None, resume=False)

    for r in range(2):
        a = json.load(open(tmp_path / "killed" / f"final_r{r}.json"))
        b = json.load(open(tmp_path / "clean" / f"final_r{r}.json"))
        assert a["step"] == b["step"]
        assert np.isclose(a["auc"], b["auc"], atol=1e-6), (a, b)
        assert a["hosts"].keys() == b["hosts"].keys()
        for s in a["hosts"]:
            na, wa, xa = a["hosts"][s]
            nb_, wb, xb = b["hosts"][s]
            assert na == nb_, (s, a, b)
            assert np.isclose(wa, wb, rtol=1e-6), (s, a, b)
            assert np.isclose(xa, xb, rtol=1e-6), (s, a, b)
        pa = np.load(tmp_path / "killed" / f"params_r{r}.npy")
        pb = np.load(tmp_path / "clean" / f"params_r{r}.npy")
        np.testing.assert_allclose(pa, pb, rtol=1e-6, atol=1e-7)
