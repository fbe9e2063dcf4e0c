"""Tier-1 (CPU) gates for the chip bring-up (ISSUE 21): chip_smoke
refuses to run without a chip, its seeded record generators give the
traffic they name, the compile cache lands where the contract says,
peaks come from one table, the native library is never a foreign
binary, and the dispatch counter books what ran."""

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))


def _run(code_or_args, env=None, cwd=None, timeout=180):
    args = ([sys.executable, "-c", code_or_args]
            if isinstance(code_or_args, str) else code_or_args)
    e = dict(os.environ, PYTHONPATH=REPO)
    for k, v in (env or {}).items():
        if v is None:
            e.pop(k, None)       # None = unset in the child
        else:
            e[k] = v
    return subprocess.run(args, env=e, cwd=cwd or REPO, text=True,
                          capture_output=True, timeout=timeout)


# ---- (a) no chip, no run ------------------------------------------------

def test_chip_smoke_refuses_cpu():
    r = _run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
             env={"JAX_PLATFORMS": "cpu"}, timeout=120)
    assert r.returncode != 0
    assert "'cpu'" in r.stderr, r.stderr[-500:]
    # the platform it found is printed; the JSON verdict is not
    assert "platform=cpu" in r.stdout
    assert '"ok"' not in r.stdout


# ---- (b) compile-cache placement ---------------------------------------

_CACHE_PROBE = """
import json, types, jax
from paddlebox_tpu.utils import compile_cache as cc
{setup}
d = cc.enable_compilation_cache()
print(json.dumps([d, jax.config.jax_compilation_cache_dir]))
"""

_FAKE_TPU = ("jax.devices = lambda *a: "
             "[types.SimpleNamespace(platform='tpu')]")


def _cache_probe(setup, env=None, cwd=None):
    base = {"JAX_PLATFORMS": "cpu"}
    base.update(env or {})
    r = _run(_CACHE_PROBE.format(setup=setup), env=base, cwd=cwd)
    assert r.returncode == 0, r.stderr[-800:]
    return tuple(json.loads(r.stdout.strip().splitlines()[-1]))


def test_cache_env_dir_wins_and_code_sets_none(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: that directory is used and the
    code leaves jax.config.jax_compilation_cache_dir alone."""
    envdir = str(tmp_path / "from_env")
    used, cfg = _cache_probe(
        _FAKE_TPU + "\njax.config.update('jax_compilation_cache_dir', "
        "'/sentinel/untouched')",
        env={"JAX_COMPILATION_CACHE_DIR": envdir})
    assert used == envdir
    assert cfg == "/sentinel/untouched"


def test_cache_default_is_one_fixed_checkout_dir(tmp_path):
    """Unset: the fixed in-checkout path, the same from two cwd's and
    two processes — never tempfile, a pid or a timestamp."""
    want = os.path.join(REPO, ".jax_cache")
    env = {"JAX_COMPILATION_CACHE_DIR": None}
    a = _cache_probe(_FAKE_TPU, env=env, cwd=REPO)
    b = _cache_probe(_FAKE_TPU, env=env, cwd=str(tmp_path))
    assert a == b == (want, want)
    with open(os.path.join(REPO, ".gitignore")) as fh:
        assert ".jax_cache/" in fh.read().split()


def test_cache_stays_off_on_cpu():
    """A CPU run (tier-1) never fills the in-checkout directory: XLA:CPU
    executables must not travel to the chip machine with the tree."""
    used, cfg = _cache_probe("", env={"JAX_COMPILATION_CACHE_DIR": None})
    assert used is None and cfg is None
    cache = os.path.join(REPO, ".jax_cache")
    assert not os.path.isdir(cache) or not os.listdir(cache)


# ---- (c) peaks: one table, unknown device = error -----------------------

def test_peak_table_raises_on_unknown_device_kind():
    spec = importlib.util.spec_from_file_location(
        "peaks", os.path.join(REPO, "benchmarks", "peaks.py"))
    peaks = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(peaks)
    v5e = peaks.device_peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["source"]
    with pytest.raises(KeyError, match="TPU v9"):
        peaks.device_peaks("TPU v9")


# ---- (d) native library: strict on the chip path, never foreign ---------

def test_strict_native_loader_raises_when_build_fails():
    """CXX=false: require_native() (chip_smoke / benchmarks) raises, while
    make_kv's python index — which tests ask for by name — still works.
    The failed build leaves the real artifact untouched."""
    code = """
import numpy as np
from paddlebox_tpu.native import load_native, require_native
assert load_native() is None
try:
    require_native()
except RuntimeError as e:
    print("RAISED", e)
from paddlebox_tpu.ps.kv import PyKV, make_kv
kv = make_kv(16)
assert isinstance(kv, PyKV)
print("ROWS", kv.assign(np.array([7, 9, 7], np.uint64)).tolist())
"""
    r = _run(code, env={"CXX": "false", "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-800:]
    assert "RAISED native library required" in r.stdout
    assert "ROWS [0, 1, 0]" in r.stdout


def test_foreign_native_binary_is_rebuilt_not_loaded(tmp_path):
    """A .so that arrived with a directory copy (stamp from another
    host) must be rebuilt from the tracked sources, never CDLL'd."""
    code = f"""
import paddlebox_tpu.native as nat
nat._SO = {str(tmp_path / 'libpbox_native.so')!r}
nat._STAMP = nat._SO + ".stamp"
open(nat._SO, "wb").write(b"not an ELF: built on another machine")
open(nat._STAMP, "w").write("0" * 64)
assert nat.load_native() is not None
print("STATUS", nat.native_status())
nat._LIB, nat._TRIED = None, False
assert nat.load_native() is not None
print("STATUS", nat.native_status())
"""
    r = _run(code, env={"JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-800:]
    assert r.stdout.split("STATUS")[1].strip() == "built"
    assert r.stdout.split("STATUS")[2].strip() == "verified"


# ---- one process per chip ----------------------------------------------

def test_launcher_refuses_local_gang_unless_cpu_emulation(monkeypatch):
    from paddlebox_tpu.distributed.launch import LaunchConfig, launch_local
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(ValueError, match="JAX_PLATFORMS=cpu"):
        launch_local([sys.executable, "-c", "pass"], LaunchConfig(nproc=2))
    assert launch_local([sys.executable, "-c", "pass"],
                        LaunchConfig(nproc=1)) == 0


# ---- mesh state is born where it lives ---------------------------------

def test_mesh_state_born_sharded_and_second_pass_compiles_nothing(
        tmp_path):
    """Stacked table state and AUC tables are allocated shard-by-shard
    on their own devices, the trainer's initial state carries the step
    program's own shardings — so the second pass finds its executable
    (an uncommitted initial state recompiled the whole pass program)."""
    import jax
    import jax.monitoring
    import optax
    from paddlebox_tpu.data import DataFeedDesc, DatasetFactory
    from paddlebox_tpu.data.criteo import generate_criteo_files
    from paddlebox_tpu.models import DeepFM
    from paddlebox_tpu.parallel import make_mesh
    from paddlebox_tpu.ps.sharded import ShardedEmbeddingTable
    from paddlebox_tpu.train.sharded import ShardedTrainer
    n = 4
    table = ShardedEmbeddingTable(n, mf_dim=4, capacity_per_shard=2048,
                                  req_bucket_min=256, serve_bucket_min=256)
    packed = table.state.packed
    assert len(packed.sharding.device_set) == n
    assert {s.data.shape[0] for s in packed.addressable_shards} == {1}
    desc = DataFeedDesc.criteo(batch_size=32)
    desc.key_bucket_min = 32 * 26
    files = generate_criteo_files(str(tmp_path), num_files=1,
                                  rows_per_file=32 * n * 2,
                                  vocab_per_slot=50, seed=1)
    ds = DatasetFactory().create_dataset("InMemoryDataset", desc)
    ds.set_filelist(files)
    ds.load_into_memory()
    tr = ShardedTrainer(DeepFM(hidden=(8,)), table, desc, make_mesh(n),
                        tx=optax.adam(1e-3))
    for leaf in jax.tree.leaves(tr.state.auc):
        assert len(leaf.sharding.device_set) == n
        assert {s.data.shape[0] for s in leaf.addressable_shards} == {1}
    compiles = []

    def on_compile(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        tr.train_pass_resident(ds)
        first = len(compiles)
        tr.train_pass_resident(ds)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
    assert first > 0
    assert compiles[first:] == [], compiles[first:]


# ---- chip_smoke's seeded record generators ------------------------------

@pytest.mark.parametrize("shape", ["uniform", "ragged", "zipf"])
def test_smoke_records(shape, monkeypatch):
    """build_records: every key inside its slot's own vocabulary range,
    at least one key a slot, ragged counts with the asked mean, a Zipf
    draw whose commonest id dominates its slot; build_pv_records pages
    of 2-4 ads with ranks 1..n."""
    monkeypatch.syspath_prepend(REPO)
    import chip_smoke as cs
    slots, vocab, n, avg = 6, 500, 400, 3.0
    recs = cs.build_records(
        n, num_slots=slots, vocab_per_slot=vocab, seed=3,
        avg_keys_per_slot=avg if shape == "ragged" else 1.0,
        key_dist="zipf" if shape == "zipf" else "uniform")
    assert len(recs) == n
    counts = np.stack([np.diff(r.slot_offsets) for r in recs])
    assert counts.shape == (n, slots) and counts.min() >= 1
    by_slot = [[] for _ in range(slots)]
    for r in recs:
        assert r.keys.dtype == np.uint64
        assert len(r.keys) == r.slot_offsets[-1]
        for s in range(slots):
            by_slot[s].append(r.keys[r.slot_offsets[s]:
                                     r.slot_offsets[s + 1]])
    for s, chunks in enumerate(by_slot):
        keys = np.concatenate(chunks).astype(np.int64)
        assert keys.min() >= s * vocab and keys.max() < (s + 1) * vocab
        top_share = np.bincount(keys - s * vocab).max() / len(keys)
        if shape == "zipf":
            assert top_share > 0.05, top_share
        else:
            assert top_share < 0.05, top_share
    if shape == "ragged":
        assert counts.mean() == pytest.approx(avg, rel=0.10)
        assert counts.max() > 1
    else:
        assert (counts == 1).all()
    pv = cs.build_pv_records(20, slots, vocab, dense_dim=4, seed=3)
    pages = {}
    for r in pv:
        assert len(r.keys) == slots and r.cmatch == 222
        pages.setdefault(r.search_id, []).append(r.rank)
    assert sorted(pages) == list(range(20))
    for ranks in pages.values():
        assert 2 <= len(ranks) <= 4
        assert sorted(ranks) == list(range(1, len(ranks) + 1))


# ---- chip_smoke's phases stay runnable ---------------------------------

def test_chip_smoke_phases_at_toy_width(tmp_path, monkeypatch):
    """The phase functions main() runs at full width on the chip, at toy
    width on this CPU mesh — so an API drift breaks tier-1, not the next
    chip call. (The kernels phase is the tier-1 kernel tests' job.)"""
    monkeypatch.syspath_prepend(REPO)
    import chip_smoke as cs
    import jax
    w = cs.Widths(hidden=(16, 8), batch_size=64, capacity=1 << 17,
                  batches_per_pass=2, vocab_per_slot=100, bucket_min=256)
    n = len(jax.devices())
    work = str(tmp_path)
    watch = cs.CompileWatch()
    rows = w.batch_size * w.batches_per_pass
    ds_a, desc = cs.criteo_dataset(work, "a", rows, w, seed=1)
    assert cs.phase_resident(watch, ds_a, desc, w)["phase"] == "resident"
    _, tr = cs.phase_streaming(watch, ds_a, desc, w)
    cs.phase_serve(watch, tr, ds_a, desc, w, work)
    mesh_a, _ = cs.criteo_dataset(work, "ma", rows * n, w, seed=1)
    mesh_b, _ = cs.criteo_dataset(work, "mb", rows * n, w, seed=2,
                                  value_base=w.vocab_per_slot // 2)
    cs.phase_sharded(watch, mesh_a, desc, w)
    out = cs.phase_tiered(watch, mesh_a, mesh_b, desc, w)
    assert out["phase"] == "tiered"
