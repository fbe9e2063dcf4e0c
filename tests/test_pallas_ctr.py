"""Parity matrix for the device-side CTR op family (ISSUE 13):
fused Pallas rank_attention / batch_fc / cross_norm_hadamard vs the XLA
compositions, through the dispatch seams, interpret mode on CPU.

Contract being gated (docs/PERFORMANCE.md §Device kernels): forward
within f32 tolerance (the MXU one-hot matmuls sum in a different
order), grads BITWISE where the formulation is exact — the fused
backwards are hand-written jnp mirroring the XLA compositions' autodiff
ops, so given the same upstream cotangent rank_attention and batch_fc
grads match exactly; cross_norm's dX carries reassociation-level f32
drift (the composition's add ordering differs) and gates with rtol."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddlebox_tpu.config import flags_scope
from paddlebox_tpu.ops import (
    batch_fc, cross_norm_hadamard, cross_norm_update,
    init_cross_norm_summary, rank_attention, rank_attention2,
)
from paddlebox_tpu.ops.pallas_ctr import (batch_fc_fits, cross_norm_fits,
                                          rank_attention_fits)

MR = 3


def _rank_case(n=37, d=12, p=7, seed=0, all_invalid=False):
    """rank_offset with the full validity matrix: invalid own ranks
    (col 0 = 0), missing co-shown entries (rank 0 → faster = −1), and
    optionally every row invalid."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    param = rng.normal(size=(MR * MR, d, p)).astype(np.float32)
    ro = np.zeros((n, 1 + 2 * MR), np.int32)
    if not all_invalid:
        ro[:, 0] = rng.integers(0, MR + 1, size=n)
        for k in range(MR):
            on = rng.random(n) < 0.7
            ro[:, 1 + 2 * k] = np.where(
                on, rng.integers(1, MR + 1, size=n), 0)
            ro[:, 2 + 2 * k] = rng.integers(0, n, size=n)
    return jnp.asarray(x), jnp.asarray(ro), jnp.asarray(param)


@pytest.mark.parametrize("param_2d", [False, True])
@pytest.mark.parametrize("all_invalid", [False, True])
def test_rank_attention_forward_parity(param_2d, all_invalid):
    x, ro, param = _rank_case(all_invalid=all_invalid)
    if param_2d:
        param = param.reshape(MR * MR * x.shape[1], -1)
    ref = np.asarray(rank_attention(x, ro, param, MR))
    with flags_scope(use_pallas_rank_attention=True):
        got = np.asarray(rank_attention(x, ro, param, MR))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
    if all_invalid:
        np.testing.assert_array_equal(ref, 0.0)


@pytest.mark.parametrize("param_2d", [False, True])
@pytest.mark.parametrize("enable_input_bp", [False, True])
def test_rank_attention_grads_match_composition(param_2d, enable_input_bp):
    """Same upstream cotangent ⇒ the fused custom_vjp's grads match the
    XLA composition's autodiff to rtol 1e-6, atol 1e-6, not bit for bit:
    the two backward passes are the same einsums and scatter, but XLA:CPU
    fuses the composition's backward einsum and the custom_vjp's
    differently (1e-6 drift here; PR 21 read 1.5e-5 between them on the
    chip). dX is exactly zero without enable_input_bp."""
    x, ro, param = _rank_case(seed=3)
    if param_2d:
        param = param.reshape(MR * MR * x.shape[1], -1)
    rng = np.random.default_rng(4)
    w = jnp.asarray(rng.normal(size=(x.shape[0], 7)).astype(np.float32))

    def grads(flag):
        def f(xx, pp):
            with flags_scope(use_pallas_rank_attention=flag):
                return jnp.sum(rank_attention(
                    xx, ro, pp, MR, enable_input_bp=enable_input_bp) * w)
        return jax.grad(f, argnums=(0, 1))(x, param)

    gx0, gp0 = grads(False)
    gx1, gp1 = grads(True)
    np.testing.assert_allclose(np.asarray(gx1), np.asarray(gx0),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(gp1), np.asarray(gp0),
                               rtol=1e-6, atol=1e-6)
    assert np.asarray(gp1).shape == param.shape  # cotangent keeps layout
    if not enable_input_bp:
        np.testing.assert_array_equal(np.asarray(gx1), 0.0)
    else:
        assert np.abs(np.asarray(gx1)).max() > 0


def test_rank_attention2_param_only_under_flag():
    """rank_attention2 (param-only grads) through the Pallas seam: X
    grads exactly zero, param grads bitwise vs the XLA path."""
    x, ro, param = _rank_case(seed=5)

    def grads(flag):
        def f(xx, pp):
            with flags_scope(use_pallas_rank_attention=flag):
                return jnp.sum(rank_attention2(xx, ro, pp, MR) ** 2)
        return jax.grad(f, argnums=(0, 1))(x, param)

    gx0, gp0 = grads(False)
    gx1, gp1 = grads(True)
    np.testing.assert_array_equal(np.asarray(gx1), 0.0)
    # forward order differs (MXU block grouping), so the ²-loss
    # cotangent differs at f32 lsb — param grads gate with tolerance
    np.testing.assert_allclose(np.asarray(gp1), np.asarray(gp0),
                               rtol=1e-4, atol=1e-6)


def test_rank_attention_overflow_falls_back():
    """A shape past the VMEM residency budget must route to the XLA
    fallback under the flag (and produce identical results trivially)."""
    assert not rank_attention_fits(max_rank=5, d=1024, p=1024)
    assert rank_attention_fits(max_rank=3, d=128, p=128)
    n, d, p = 8, 1024, 1024
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    param = jnp.asarray(
        rng.normal(size=(25, d, p)).astype(np.float32) * 0.01)
    ro = jnp.asarray(np.tile(
        np.array([[1, 1, 0] + [0] * 8], np.int32), (n, 1)))
    ref = np.asarray(rank_attention(x, ro, param, 5))
    with flags_scope(use_pallas_rank_attention=True):
        got = np.asarray(rank_attention(x, ro, param, 5))
    np.testing.assert_array_equal(got, ref)  # same program — fallback


@pytest.mark.parametrize("mode", ["default", "batchcount", "transpose"])
def test_batch_fc_parity_forward_and_grads(mode):
    """All three batch_fc modes: fused forward bitwise (same dot
    ordering, bias added in-VMEM), grads bitwise (mirrored einsums)."""
    rng = np.random.default_rng(1)
    s, n, i_dim, o_dim = 3, 5, 4, 2
    x3 = rng.normal(size=(s, n, i_dim)).astype(np.float32)
    w = rng.normal(size=(s, i_dim, o_dim)).astype(np.float32)
    b = rng.normal(size=(s, o_dim)).astype(np.float32)
    if mode == "default":
        args = (jnp.asarray(x3), jnp.asarray(w), jnp.asarray(b))
        kw = {}
    elif mode == "batchcount":
        args = (jnp.asarray(x3.reshape(s * n, i_dim)), jnp.asarray(w),
                jnp.asarray(b))
        kw = dict(batchcount=s)
    else:
        wt = np.swapaxes(w, 1, 2).copy()
        args = (jnp.asarray(x3.reshape(s * n, i_dim)), jnp.asarray(wt),
                jnp.asarray(b))
        kw = dict(batchcount=s, transpose_weight=True)

    ref = np.asarray(batch_fc(*args, **kw))
    with flags_scope(use_pallas_batch_fc=True):
        got = np.asarray(batch_fc(*args, **kw))
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-6)

    def grads(flag):
        def f(xx, ww, bb):
            with flags_scope(use_pallas_batch_fc=flag):
                return jnp.sum(batch_fc(xx, ww, bb, **kw) * 0.7)
        return jax.grad(f, argnums=(0, 1, 2))(*args)

    for g_ref, g_got in zip(grads(False), grads(True)):
        np.testing.assert_array_equal(np.asarray(g_got),
                                      np.asarray(g_ref))


def test_batch_fc_overflow_falls_back():
    assert not batch_fc_fits(2048, 2048)
    assert batch_fc_fits(128, 128)


@pytest.mark.parametrize("flag", [False, True])
def test_batch_fc_transpose_without_batchcount_raises(flag):
    """transpose_weight is a batchcount-mode attr (the reference op);
    default mode must fail loudly on BOTH paths instead of contracting
    an [S, O, I] weight on the wrong axis."""
    x = jnp.ones((2, 4, 3), jnp.float32)
    w = jnp.ones((2, 3, 3), jnp.float32)
    b = jnp.ones((2, 3), jnp.float32)
    with flags_scope(use_pallas_batch_fc=flag):
        with pytest.raises(ValueError, match="transpose_weight"):
            batch_fc(x, w, b, transpose_weight=True)


def test_cross_norm_parity_forward_and_grads():
    """Fused one-VMEM-pass cross block: forward bitwise (same
    elementwise math + exact zero-padded dot), dX within f32
    reassociation tolerance (the composition's autodiff groups the
    three a-contributions differently)."""
    rng = np.random.default_rng(2)
    b, n, d = 9, 2, 5
    x = jnp.asarray(rng.normal(size=(b, 2 * n * d)).astype(np.float32))
    summ = cross_norm_update(init_cross_norm_summary(n, d), x, n, d,
                             decay=0.5)
    ref = np.asarray(cross_norm_hadamard(x, summ, n, d))
    with flags_scope(use_pallas_cross_norm=True):
        got = np.asarray(cross_norm_hadamard(x, summ, n, d))
    np.testing.assert_array_equal(got, ref)

    def grads(flag):
        def f(xx):
            with flags_scope(use_pallas_cross_norm=flag):
                return jnp.sum(cross_norm_hadamard(xx, summ, n, d) ** 2)
        return jax.grad(f)(x)

    np.testing.assert_allclose(np.asarray(grads(True)),
                               np.asarray(grads(False)),
                               rtol=1e-4, atol=1e-6)
    assert cross_norm_fits(128) and not cross_norm_fits(1 << 20)


def test_cross_norm_summary_grads_both_paths():
    """The summary cotangent chain survives the seam: the fused path
    derives mean/scale OUTSIDE the kernel, so d loss / d summary stays
    defined and close to the composition's."""
    rng = np.random.default_rng(6)
    b, n, d = 6, 1, 4
    x = jnp.asarray(rng.normal(size=(b, 2 * n * d)).astype(np.float32))
    summ = cross_norm_update(init_cross_norm_summary(n, d), x, n, d,
                             decay=0.5)

    def grads(flag):
        def f(s):
            with flags_scope(use_pallas_cross_norm=flag):
                return jnp.sum(cross_norm_hadamard(x, s, n, d) ** 2)
        return jax.grad(f)(summ)

    g0, g1 = grads(False), grads(True)
    for a, c in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(c), np.asarray(a),
                                   rtol=1e-4, atol=1e-5)


def test_cross_norm_sync_stats_psum_two_device_mesh():
    """sync_stats under a 2-device mesh: per-shard
    ``cross_norm_update(..., sync_axis=...)`` folds the GLOBAL batch
    stats (bit-identical summaries on every shard, equal to the
    single-host update over the concatenated batch), and the forward
    with the synced summary is Pallas-vs-XLA exact."""
    from jax.sharding import Mesh, PartitionSpec as P
    n, d = 2, 4
    rng = np.random.default_rng(7)
    xg = rng.normal(size=(8, 2 * n * d)).astype(np.float32)
    summ = init_cross_norm_summary(n, d)
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))

    def upd(x_blk):
        return cross_norm_update(summ, x_blk, n, d, decay=0.5,
                                 sync_axis="data")

    f = jax.jit(jax.shard_map(upd, mesh=mesh, in_specs=P("data"),
                              out_specs=P(), check_vma=False))
    synced = f(jnp.asarray(xg))
    want = cross_norm_update(summ, jnp.asarray(xg), n, d, decay=0.5)
    for a, c in zip(jax.tree.leaves(want), jax.tree.leaves(synced)):
        np.testing.assert_allclose(np.asarray(c), np.asarray(a),
                                   rtol=1e-6, atol=1e-6)

    ref = np.asarray(cross_norm_hadamard(jnp.asarray(xg), synced, n, d))
    with flags_scope(use_pallas_cross_norm=True):
        got = np.asarray(cross_norm_hadamard(jnp.asarray(xg), synced,
                                             n, d))
    np.testing.assert_array_equal(got, ref)


def test_ads_rank_full_tower_parity():
    """AdsRank with slot_fc + cross_norm (the PV bench configuration):
    one forward+backward, all three flags on vs off — logits within
    f32 tolerance, and every param grad finite and close."""
    from paddlebox_tpu.models import AdsRank
    b, s, d, dm = 16, 4, 6, 8
    rng = np.random.default_rng(8)
    pooled = jnp.asarray(rng.normal(size=(b, s, d)).astype(np.float32))
    dense = jnp.asarray(rng.normal(size=(b, 2)).astype(np.float32))
    ro = np.zeros((b, 1 + 2 * MR), np.int32)
    ro[:, 0] = rng.integers(0, MR + 1, size=b)
    ro[:, 1] = 1
    ro[:, 2] = rng.integers(0, b, size=b)
    ro = jnp.asarray(ro)
    summ = init_cross_norm_summary(1, dm)
    model = AdsRank(d_model=dm, max_rank=MR, hidden=(8,), slot_fc=True,
                    cross_norm=True)
    params = model.init(jax.random.PRNGKey(0), pooled, dense, ro, summ)

    def run(flag):
        with flags_scope(use_pallas_rank_attention=flag,
                         use_pallas_batch_fc=flag,
                         use_pallas_cross_norm=flag):
            out = model.apply(params, pooled, dense, ro, summ)
            g = jax.grad(lambda p: jnp.sum(model.apply(
                p, pooled, dense, ro, summ) ** 2))(params)
        return np.asarray(out), g

    o0, g0 = run(False)
    o1, g1 = run(True)
    np.testing.assert_allclose(o1, o0, rtol=1e-4, atol=1e-5)
    for a, c in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        assert np.all(np.isfinite(np.asarray(c)))
        np.testing.assert_allclose(np.asarray(c), np.asarray(a),
                                   rtol=5e-3, atol=1e-4)
