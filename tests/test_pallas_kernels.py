"""Pallas kernel correctness vs XLA references (interpret mode on CPU)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         os.pardir))

from paddlebox_tpu.config import flags_scope
from paddlebox_tpu.ops.pallas_kernels import (
    CVM_CONV, CVM_FULL, CVM_NONE, CVM_SHOW, fused_embed_pool_cvm,
    fused_pool_cvm_forward, gather_rows, segment_gather_mxu, segment_sum_mxu,
)


def test_gather_rows_matches_take():
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.normal(size=(64, 8)).astype(np.float32))
    rows = jnp.asarray(rng.integers(0, 64, size=37).astype(np.int32))
    out = gather_rows(table, rows)
    np.testing.assert_allclose(np.asarray(out), np.asarray(table)[rows])


def test_gather_rows_wide():
    rng = np.random.default_rng(1)
    table = jnp.asarray(rng.normal(size=(256, 128)).astype(np.float32))
    rows = jnp.asarray(rng.integers(0, 256, size=500).astype(np.int32))
    out = gather_rows(table, rows)
    np.testing.assert_allclose(np.asarray(out), np.asarray(table)[rows])


@pytest.mark.parametrize("k,s", [(100, 40), (700, 200), (7, 3), (1500, 3000)])
def test_segment_sum_mxu(k, s):
    rng = np.random.default_rng(4)
    vals = rng.normal(size=(k, 11)).astype(np.float32)
    # contract: segments nondecreasing (batch builder order); s > k cases
    # leave whole output blocks with no keys (must read back zero)
    segs = np.sort(rng.integers(0, s, size=k)).astype(np.int32)
    got = segment_sum_mxu(jnp.asarray(vals), jnp.asarray(segs), s)
    want = jax.ops.segment_sum(jnp.asarray(vals), jnp.asarray(segs),
                               num_segments=s)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_segment_sum_mxu_gap_blocks_zero():
    # keys only in the last segment range → earlier output blocks unvisited
    vals = jnp.ones((8, 4), jnp.float32)
    segs = jnp.full((8,), 999, jnp.int32)
    got = np.asarray(segment_sum_mxu(vals, segs, 1000))
    assert got[999].sum() == 32.0
    np.testing.assert_allclose(got[:999], 0.0)


def test_segment_sum_mxu_drop_negative():
    vals = jnp.ones((4, 3), jnp.float32)
    segs = jnp.asarray([0, 1, -1, -1], jnp.int32)
    got = segment_sum_mxu(vals, segs, 2)
    np.testing.assert_allclose(np.asarray(got), np.ones((2, 3)))


def test_segment_sum_mxu_leading_and_interleaved_drops():
    vals = jnp.asarray(np.arange(20, dtype=np.float32).reshape(5, 4))
    segs = jnp.asarray([-1, 0, -1, 0, 1], jnp.int32)
    got = segment_sum_mxu(vals, segs, 2)
    want = jax.ops.segment_sum(
        jnp.where(jnp.asarray([0, 1, 0, 1, 1], bool)[:, None], vals, 0),
        jnp.asarray([0, 0, 0, 0, 1], jnp.int32), num_segments=2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


def test_segment_sum_mxu_grad():
    rng = np.random.default_rng(6)
    vals = jnp.asarray(rng.normal(size=(50, 5)).astype(np.float32))
    segs = jnp.asarray(np.sort(rng.integers(0, 12, size=50)).astype(np.int32))
    w = jnp.asarray(rng.normal(size=(12, 5)).astype(np.float32))
    f = lambda v: (segment_sum_mxu(v, segs, 12) * w).sum()
    g = jax.grad(f)(vals)
    want = jax.grad(
        lambda v: (jax.ops.segment_sum(v, segs, num_segments=12) * w).sum()
    )(vals)
    np.testing.assert_allclose(np.asarray(g), np.asarray(want), rtol=1e-5)


def test_fused_seqpool_concat_grad_with_pallas():
    from paddlebox_tpu.ops import fused_seqpool_concat
    rng = np.random.default_rng(7)
    B, S, K = 3, 4, 30
    vals = jnp.asarray(rng.normal(size=(K, 6)).astype(np.float32))
    segs = jnp.asarray(np.sort(rng.integers(0, B * S, size=K)).astype(np.int32))
    f = lambda v: fused_seqpool_concat(v, segs, B, S).sum()
    want = jax.grad(f)(vals)
    with flags_scope(use_pallas_seqpool=True):
        got = jax.grad(f)(vals)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


def test_seqpool_cvm_pallas_backend_matches():
    from paddlebox_tpu.ops import fused_seqpool_cvm
    rng = np.random.default_rng(5)
    B, S, MF, K = 4, 3, 8, 50
    vals = jnp.asarray(rng.normal(size=(K, 3 + MF)).astype(np.float32))
    segs = jnp.asarray(rng.integers(0, B * S, size=K).astype(np.int32))
    sc = jnp.asarray(np.abs(rng.normal(size=(B, 2))).astype(np.float32))
    ref = fused_seqpool_cvm(vals, segs, sc, B, S)
    with flags_scope(use_pallas_seqpool=True):
        got = fused_seqpool_cvm(vals, segs, sc, B, S)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_table_pull_push_with_pallas_flags():
    from paddlebox_tpu.data.batch import SlotBatch
    from paddlebox_tpu.ps import EmbeddingTable, SparseSGDConfig

    def run(**flags):
        with flags_scope(**flags):
            t = EmbeddingTable(mf_dim=8, capacity=256,
                               cfg=SparseSGDConfig(), seed=7)
            keys = np.array([3, 9, 3, 77, 9, 1024], np.uint64)
            batch = SlotBatch(
                keys=keys, num_keys=len(keys),
                segments=np.arange(len(keys), dtype=np.int32),
                dense=np.zeros((2, 1), np.float32),
                label=np.zeros(2, np.float32),
                show=np.ones(2, np.float32), clk=np.zeros(2, np.float32),
                batch_size=2, num_slots=3)
            idx = t.prepare(batch)
            vals = t.pull(idx)
            g = jnp.ones((len(keys), 3 + 8), jnp.float32) * 0.1
            t.push(idx, g)
            return np.asarray(vals), np.asarray(t.pull(idx))

    v0, p0 = run()
    v1, p1 = run(use_pallas_gather=True)
    np.testing.assert_allclose(v0, v1, rtol=1e-6)
    np.testing.assert_allclose(p0, p1, rtol=1e-6)


# ---------------------------------------------------------------------------
# segment_gather_mxu (transposed one-hot backward kernel — ISSUE 12)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,k", [(40, 300), (12, 50), (200, 700), (5, 4)])
def test_segment_gather_mxu_matches_take(n, k):
    rng = np.random.default_rng(8)
    src = rng.normal(size=(n, 9)).astype(np.float32)
    ids = np.sort(rng.integers(0, n, size=k)).astype(np.int32)
    got = np.asarray(segment_gather_mxu(jnp.asarray(src),
                                        jnp.asarray(ids)))
    np.testing.assert_array_equal(got, src[ids])  # bitwise — a gather


def test_segment_gather_mxu_drops_and_oob_zero():
    rng = np.random.default_rng(9)
    src = rng.normal(size=(16, 5)).astype(np.float32)
    ids = np.sort(np.concatenate(
        [rng.integers(0, 16, size=20), [16, 40, 1000]])).astype(np.int32)
    ids[0] = -1  # drop marker anywhere
    got = np.asarray(segment_gather_mxu(jnp.asarray(src),
                                        jnp.asarray(ids)))
    ok = (ids >= 0) & (ids < 16)
    want = np.where(ok[:, None], src[np.clip(ids, 0, 15)], 0.0)
    np.testing.assert_array_equal(got, want)


def test_segment_gather_mxu_under_jit_and_empty():
    src = jnp.ones((8, 3), jnp.float32)
    ids = jnp.full((12,), -1, jnp.int32)  # all drops
    got = jax.jit(segment_gather_mxu)(src, ids)
    np.testing.assert_array_equal(np.asarray(got), np.zeros((12, 3)))


# ---------------------------------------------------------------------------
# fused_embed_pool_cvm (pool + CVM in one Pallas pass — the tentpole)
# ---------------------------------------------------------------------------

def _fused_case(k=700, B=5, S=3, mf=6, seed=0, zipf=False, pads=30):
    from paddlebox_tpu.ops import fused_seqpool_cvm
    rng = np.random.default_rng(seed)
    d = 2 + mf
    vals = rng.normal(size=(k, d)).astype(np.float32)
    vals[:, :2] = np.abs(vals[:, :2])  # show/clk columns nonnegative
    if zipf:
        lens = np.minimum(rng.zipf(1.5, size=B * S), 24)
        ids = np.repeat(np.arange(B * S, dtype=np.int32), lens)[:k - pads]
        segs = np.full(k, B * S, np.int32)
        segs[:len(ids)] = ids
    else:
        segs = np.sort(rng.integers(0, B * S, size=k)).astype(np.int32)
        if pads:
            segs[-pads:] = B * S  # partial-batch tail padding
    sc = np.abs(rng.normal(size=(B, 2))).astype(np.float32)
    return (jnp.asarray(vals), jnp.asarray(segs), jnp.asarray(sc),
            fused_seqpool_cvm)


@pytest.mark.parametrize("zipf", [False, True])
@pytest.mark.parametrize("use_cvm,need_filter,pad_value", [
    (True, False, 0.0), (True, True, 0.0), (False, False, 0.0),
    (True, False, 0.25), (False, True, 0.5),
])
def test_fused_embed_pool_cvm_matches_composition(use_cvm, need_filter,
                                                  pad_value, zipf):
    B, S = 5, 3
    vals, segs, sc, composition = _fused_case(zipf=zipf)
    ref = composition(vals, segs, sc, B, S, use_cvm, 2, pad_value,
                      need_filter, 0.2, 1.0, 0.96, 0)
    got = fused_embed_pool_cvm(vals, segs, sc, B, S, use_cvm, 2,
                               pad_value, need_filter, 0.2, 1.0, 0.96)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)


def test_fused_embed_pool_cvm_empty_segments():
    # every key is padding → CVM of an all-zero pool (the PaddingZeros
    # contract) — and no uninitialized output block may leak through
    B, S = 3, 4
    vals = jnp.ones((64, 6), jnp.float32)
    segs = jnp.full((64,), B * S, jnp.int32)
    sc = jnp.ones((B, 2), jnp.float32)
    got = np.asarray(fused_embed_pool_cvm(vals, segs, sc, B, S))
    np.testing.assert_allclose(got, np.zeros((B, S, 6)), atol=1e-7)


@pytest.mark.parametrize("use_cvm,need_filter", [
    (True, False), (True, True), (False, False)])
def test_fused_embed_pool_cvm_grads_bitwise(use_cvm, need_filter):
    """custom_vjp grads vs jax.grad of the XLA composition: the
    transposed one-hot backward is bitwise a gather, so given the same
    upstream cotangent the pushed grads match EXACTLY."""
    B, S = 5, 3
    vals, segs, sc, composition = _fused_case(seed=4, zipf=True)
    rng = np.random.default_rng(5)
    out_shape = np.asarray(composition(
        vals, segs, sc, B, S, use_cvm, 2, 0.0, need_filter,
        0.2, 1.0, 0.96, 0)).shape
    w = jnp.asarray(rng.normal(size=out_shape).astype(np.float32))

    def f_ref(v):
        return jnp.sum(composition(v, segs, sc, B, S, use_cvm, 2, 0.0,
                                   need_filter, 0.2, 1.0, 0.96, 0) * w)

    def f_new(v):
        return jnp.sum(fused_embed_pool_cvm(
            v, segs, sc, B, S, use_cvm, 2, 0.0, need_filter,
            0.2, 1.0, 0.96) * w)

    g_ref = np.asarray(jax.grad(f_ref)(vals))
    g_new = np.asarray(jax.grad(f_new)(vals))
    np.testing.assert_array_equal(g_new, g_ref)


def test_fused_embed_pool_cvm_wide_cvm_offset_grads():
    """cvm_offset > 2 with use_cvm: the output head is still the TWO
    transformed columns, so the backward must slice at 2 (not at
    cvm_offset) — regression for the head-width crash."""
    B, S, K, d, co = 2, 2, 40, 6, 3
    rng = np.random.default_rng(11)
    vals = jnp.asarray(np.abs(rng.normal(size=(K, d))).astype(np.float32))
    segs = jnp.asarray(np.sort(rng.integers(0, B * S, size=K))
                       .astype(np.int32))
    sc = jnp.asarray(np.abs(rng.normal(size=(B, co))).astype(np.float32))
    out = fused_embed_pool_cvm(vals, segs, sc, B, S, True, co)
    assert out.shape == (B, S, 2 + d - co)
    g = np.asarray(jax.grad(
        lambda v: jnp.sum(fused_embed_pool_cvm(v, segs, sc, B, S, True,
                                               co)))(vals))
    assert g.shape == (K, d)
    ins = np.minimum(np.asarray(segs) // S, B - 1)
    np.testing.assert_allclose(g[:, :co], np.asarray(sc)[ins])  # head
    np.testing.assert_allclose(g[:, co:], 1.0)                  # embedx


def test_fused_pool_cvm_forward_modes():
    """Raw forward head modes against hand-built references."""
    rng = np.random.default_rng(6)
    B, S, d = 2, 2, 7
    k = 40
    vals = np.abs(rng.normal(size=(k, d))).astype(np.float32)
    segs = np.sort(rng.integers(0, B * S, size=k)).astype(np.int32)
    pooled = np.zeros((B * S, d), np.float32)
    np.add.at(pooled, segs, vals)
    pooled = pooled.reshape(B, S, d)
    j = lambda x: jnp.asarray(x)
    # CVM_SHOW (clk_filter): [log1p(show), embedx…]
    got = np.asarray(fused_pool_cvm_forward(
        j(vals), j(segs), None, B, S, cvm_mode=CVM_SHOW, cvm_offset=2))
    want = np.concatenate([np.log1p(pooled[..., :1]), pooled[..., 2:]],
                          axis=-1)
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)
    # CVM_CONV: [log1p(show), log1p(clk), log1p(conv)-log1p(clk), …]
    got = np.asarray(fused_pool_cvm_forward(
        j(vals), j(segs), None, B, S, cvm_mode=CVM_CONV, cvm_offset=3))
    want = np.concatenate(
        [np.log1p(pooled[..., 0:1]), np.log1p(pooled[..., 1:2]),
         np.log1p(pooled[..., 2:3]) - np.log1p(pooled[..., 1:2]),
         pooled[..., 3:]], axis=-1)
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)
    # CVM_NONE + ets: width cut only
    got = np.asarray(fused_pool_cvm_forward(
        j(vals), j(segs), None, B, S, cvm_mode=CVM_NONE, cvm_offset=2,
        ets=1))
    np.testing.assert_allclose(got, pooled[..., 3:], rtol=3e-5, atol=3e-5)
    assert CVM_FULL == 1


def test_fused_pool_cvm_keep_mask_folds_into_matmul():
    B, S, k, d = 2, 2, 24, 5
    rng = np.random.default_rng(7)
    vals = np.abs(rng.normal(size=(k, d))).astype(np.float32)
    segs = np.sort(rng.integers(0, B * S, size=k)).astype(np.int32)
    keep = (rng.random(k) < 0.5).astype(np.float32)
    got = np.asarray(fused_pool_cvm_forward(
        jnp.asarray(vals), jnp.asarray(segs), jnp.asarray(keep), B, S,
        cvm_mode=CVM_NONE, cvm_offset=0))
    pooled = np.zeros((B * S, d), np.float32)
    np.add.at(pooled, segs, vals * keep[:, None])
    np.testing.assert_allclose(got, pooled.reshape(B, S, d),
                               rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# satellites: dead-flag regression + DMA demotion (ISSUE 12)
# ---------------------------------------------------------------------------

def test_use_pallas_flags_referenced_outside_config():
    """Every field of Flags (the use_pallas_* seams among them) must be
    READ somewhere under paddlebox_tpu/, scripts/, benchmarks/ or
    chip_smoke.py, outside config.py: a flag nothing consumes is a
    silent no-op for the user who sets it."""
    import dataclasses
    import pathlib
    import re

    from paddlebox_tpu.config import Flags
    root = pathlib.Path(REPO_ROOT)
    files = [root / "chip_smoke.py"]
    for d in ("paddlebox_tpu", "scripts", "benchmarks"):
        files += sorted((root / d).rglob("*.py"))
    text = "\n".join(p.read_text() for p in files
                     if p != root / "paddlebox_tpu" / "config.py")
    unread = [f.name for f in dataclasses.fields(Flags)
              if not re.search(rf"FLAGS\.{f.name}\b", text)]
    assert not unread, f"flags never read outside config.py: {unread}"


def test_kernel_dispatch_counter_books():
    """EVERY dispatch seam books pbox_kernel_dispatch_total{kernel,impl}
    for both implementations — the seqpool seam and the three CTR-family
    seams."""
    from paddlebox_tpu.obs import MemorySink
    from paddlebox_tpu.obs.hub import get_hub, reset_hub
    from paddlebox_tpu.ops import (batch_fc, cross_norm_hadamard,
                                   fused_seqpool_cvm,
                                   init_cross_norm_summary,
                                   rank_attention)
    reset_hub()
    hub = get_hub()
    hub.add_sink(MemorySink())
    try:
        vals = jnp.ones((8, 4), jnp.float32)
        segs = jnp.zeros((8,), jnp.int32)
        sc = jnp.ones((1, 2), jnp.float32)
        x_ra = jnp.ones((4, 3), jnp.float32)
        ro = jnp.asarray(np.tile(
            np.array([[1, 1, 0, 0, 0, 0, 0]], np.int32), (4, 1)))
        pm = jnp.ones((9, 3, 2), jnp.float32)
        x_fc = jnp.ones((2, 4, 3), jnp.float32)
        w_fc = jnp.ones((2, 3, 3), jnp.float32)
        b_fc = jnp.ones((2, 3), jnp.float32)
        x_cn = jnp.ones((4, 4), jnp.float32)
        summ = init_cross_norm_summary(1, 2)

        def run_all():
            fused_seqpool_cvm(vals, segs, sc, 1, 1)
            rank_attention(x_ra, ro, pm, 3)
            batch_fc(x_fc, w_fc, b_fc)
            cross_norm_hadamard(x_cn, summ, 1, 2)

        flags_on = dict(use_pallas_seqpool=True,
                        use_pallas_rank_attention=True,
                        use_pallas_batch_fc=True,
                        use_pallas_cross_norm=True)
        with flags_scope(**flags_on):
            run_all()
        with flags_scope(**{k: False for k in flags_on}):
            run_all()
        c = hub.counter("pbox_kernel_dispatch_total")
        for kernel in ("fused_embed_pool_cvm", "rank_attention",
                       "batch_fc", "cross_norm"):
            for impl in ("pallas", "xla"):
                assert c.value(kernel=kernel, impl=impl) >= 1, \
                    f"seam {kernel!r} never booked impl={impl!r}"
    finally:
        reset_hub()
