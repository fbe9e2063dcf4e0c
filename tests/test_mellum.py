"""The third sequence model (models/mellum.py: sliding-window and full
rotary grouped-query attention mixed, a rotary table a layer kind with
YaRN's on the full layers, softmax-routed gated experts in every layer
through parallel/moe.routed_experts) against the plain reference the
benchmark keeps (benchmarks/reference/models/mellum.py,
benchmarks/reference/lm.py), at tiny widths on the CPU in float32; and
what it forced in the ops the other models share: the window of
ops/causal_attention.py, the rotary table's arguments, the router's
scoring function."""

import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.reference import lm as ref_lm  # noqa: E402
from benchmarks.reference.models import mellum as ref  # noqa: E402
from benchmarks.reference.models import nemotron_h as ref_nemotron  # noqa: E402
from benchmarks.tests.test_family_lm_mellum import (  # noqa: E402
    TOY_YARN, toy_cell as family_toy_cell, toy_config)
from paddlebox_tpu.models import MellumMoe, NemotronH  # noqa: E402
from paddlebox_tpu.obs import trace  # noqa: E402
from paddlebox_tpu.ops.causal_attention import (  # noqa: E402
    _first_block, causal_gqa_attention, rotary_embedding,
    yarn_inv_freq)
from paddlebox_tpu.parallel.moe import route_top_k, routed_experts  # noqa: E402
from test_lfm2 import _held_reference  # noqa: E402
from test_nemotron_h import (_pass_text, _trainer,  # noqa: E402
                             assert_the_forward_sweep_runs_once, f32,
                             highest_precision,  # noqa: F401
                             program_flags_restored, rel)  # noqa: F401


def cfg_of(pattern: str, **over) -> dict:
    """The toy configuration of a stack: ``pattern`` of ``S`` (sliding)
    and ``F`` (full)."""
    return dict(toy_config(pattern), rms_norm_eps=1e-6, **over)


def program(cfg, dtype=jnp.float32):
    return MellumMoe(cfg, compute_dtype=dtype)


# ---- the window in the blockwise attention -----------------------------------

def dense_attention(q, k, v, window=None):
    """softmax(q k^T / sqrt(D)) v over ``0 <= t - s`` (``< window``),
    every score at once; query head h reads key/value head h // G."""
    bsz, t, h, d = q.shape
    g = h // k.shape[2]
    kk, vv = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q, kk) * d ** -0.5
    back = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    mask = back >= 0
    if window is not None:
        mask &= back < window
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhts,bshd->bthd", p, vv)


def _qkv(t, h, kv, d=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (2, t, h, d)),
            jax.random.normal(ks[1], (2, t, kv, d)),
            jax.random.normal(ks[2], (2, t, kv, d)),
            jax.random.normal(ks[3], (2, t, h, d)))


#: (T, block, window, query heads, key/value heads)
WINDOW_CASES = {
    "below-T": (64, 16, 24, 4, 2),
    "not-a-multiple-of-the-block": (64, 16, 21, 4, 2),
    "under-a-block": (64, 16, 5, 4, 2),
    "a-block": (64, 16, 16, 4, 2),
    "a-block-and-one": (64, 16, 17, 4, 2),
    "one-key": (64, 16, 1, 4, 2),
    "equal-to-T": (64, 16, 64, 4, 2),
    "above-T": (64, 16, 100, 4, 2),
    "blocks-of-32": (96, 32, 40, 4, 2),
    "blocks-of-32-one-kv-head": (64, 32, 33, 8, 1),
    "one-kv-head": (48, 16, 20, 8, 1),
    "one-block": (24, 512, 7, 4, 2),
}


@f32
@pytest.mark.parametrize("case", list(WINDOW_CASES))
def test_windowed_attention_value_and_gradients_equal_the_dense_softmax(case):
    t, block, window, h, kv = WINDOW_CASES[case]
    q, k, v, cot = _qkv(t, h, kv, seed=t + window)

    def ours(q, k, v):
        return causal_gqa_attention(q, k, v, block=block, window=window,
                                    mm_dtype=jnp.float32)

    def dense(q, k, v):
        return dense_attention(q, k, v, window)

    assert rel(ours(q, k, v), dense(q, k, v)) < 1e-5
    got = jax.grad(lambda *a: jnp.sum(ours(*a) * cot), argnums=(0, 1, 2))(
        q, k, v)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * cot), argnums=(0, 1, 2))(
        q, k, v)
    for name, g, r in zip("qkv", got, want):
        if window == 1 and name in "qk":
            # one key: its probability is 1 whatever the score
            assert not np.asarray(r).any()
            assert float(jnp.max(jnp.abs(g))) < 1e-5, name
            continue
        assert rel(g, r) < 1e-5, name
    if window == 1:
        # a query that reads itself alone returns its own value
        assert rel(ours(q, k, v), jnp.repeat(v, h // kv, axis=2)) < 1e-6


@f32
@pytest.mark.parametrize("window", [64, 65, 1000])
def test_no_window_is_a_window_of_the_whole_sequence(window):
    q, k, v, cot = _qkv(64, 4, 2, seed=3)

    def run(window):
        return jax.value_and_grad(
            lambda *a: jnp.sum(causal_gqa_attention(
                *a, block=16, window=window, mm_dtype=jnp.float32) * cot),
            argnums=(0, 1, 2))(q, k, v)

    (want, g_want), (got, g_got) = run(None), run(window)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for g, r in zip(g_got, g_want):
        assert rel(g, r) < 1e-6
    assert rel(causal_gqa_attention(q, k, v, block=16, mm_dtype=jnp.float32),
               dense_attention(q, k, v)) < 1e-5


def test_a_window_without_a_key_is_refused():
    q, k, v, _ = _qkv(16, 4, 2)
    with pytest.raises(ValueError, match="holds no query"):
        causal_gqa_attention(q, k, v, block=16, window=0)


@pytest.mark.parametrize("t,block,window", [
    (8192, 512, 1024), (64, 16, 24), (64, 16, 21), (64, 16, 1),
    (64, 16, 16), (64, 16, 17), (64, 16, 64), (64, 16, 1000), (24, 512, 7),
    (96, 32, 40)])
def test_the_loops_first_block_skips_nothing_of_a_window(t, block, window):
    """``_first_block``, block by block, against every query's window
    counted one by one: no key of a window lies left of the first block
    visited, and the block's first row does read that block."""
    blk = math.gcd(t, block)
    visited = needed = 0
    for i in range(t // blk):
        first = int(_first_block(i, blk, window))
        assert 0 <= first <= i
        for row in range(i * blk, (i + 1) * blk):
            oldest = max(0, row - (window - 1))
            assert oldest >= first * blk          # nothing needed is skipped
            needed += row - oldest + 1
        # the block's first row does read the first block visited
        assert max(0, i * blk - (window - 1)) < (first + 1) * blk
        visited += (i - first + 1) * blk * blk
    assert needed <= visited <= t * (t + blk) // 2
    if (t, block, window) == (8192, 512, 1024):
        # the cell's shape: 1,440 key positions visited a query for 960.06
        # inside its window; every causal block would be 4,352
        assert visited / t == 1440 and needed / t == 960.0625
        assert visited / needed == pytest.approx(1.5, abs=1e-3)
        assert _first_block(7, 512, 1024) == 5
        assert _first_block(1, 512, 1024) == 0
    assert _first_block(3, blk, None) == 0


# ---- a rotary table a layer kind ----------------------------------------------

def test_yarn_at_the_published_parameters():
    inv = yarn_inv_freq(128, 500000.0, 16.0, 8192, 32.0, 1.0)
    plain = 500000.0 ** (-np.arange(0, 128, 2) / 128)
    assert inv.shape == (64,) and inv.dtype == np.float32
    # c(32) = 18.08 and c(1) = 34.98: low 18, high 35
    c = lambda n: 128 * math.log(8192 / (2 * math.pi * n)) \
        / (2 * math.log(500000))                               # noqa: E731
    assert (math.floor(c(32)), math.ceil(c(1))) == (18, 35)
    np.testing.assert_allclose(inv[:19], plain[:19], rtol=1e-6)
    np.testing.assert_allclose(inv[35:], plain[35:] / 16, rtol=1e-6)
    # between them a blend along the ramp (i - 18) / 17
    for i in (19, 26, 34):
        ramp = (i - 18) / 17
        assert inv[i] == pytest.approx(
            (1 - ramp) * plain[i] + ramp * plain[i] / 16, rel=1e-6)
    assert np.all(np.diff(inv) < 0)
    cfg = {"rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}}
    model = program(dict(cfg_of("SF"), head_dim=128, **cfg))
    assert model.rotary["sliding_attention"] == {"theta": 500000.0}
    full = model.rotary["full_attention"]
    assert np.array_equal(full["inv_freq"], inv)
    assert full["amplitude"] == pytest.approx(0.1 * math.log(16) + 1,
                                              rel=1e-12)
    # a group that names no attention_factor gets YaRN's own
    del cfg["rope_parameters"]["full_attention"]["attention_factor"]
    model = program(dict(cfg_of("SF"), head_dim=128, **cfg))
    assert model.rotary["full_attention"]["amplitude"] == \
        0.1 * math.log(16) + 1
    # and the reference, which writes the table from the same equations
    # and the configuration's top-level copies, agrees to the digit
    z = ref.dims(dict(cfg_of("SF"), head_dim=128, rope_theta=500000,
                      yarn_factor=16,
                      yarn_original_max_position_embeddings=8192,
                      yarn_beta_fast=32, yarn_beta_slow=1))
    assert np.array_equal(ref.yarn_table(z), inv)


@pytest.mark.parametrize("kw", [{}, {"theta": 100.0, "inv_freq": [1.0] * 8}],
                         ids=["neither", "both"])
def test_rotary_wants_the_base_or_a_table_and_not_both(kw):
    with pytest.raises(ValueError, match="one and not both"):
        rotary_embedding(jnp.zeros((1, 4, 1, 16)), **kw)


@f32
def test_rotary_takes_a_table_and_an_amplitude():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 9, 2, 16))
    plain = 100.0 ** (-jnp.arange(0, 16, 2, dtype=jnp.float32) / 16)
    # the base alone and the same table handed in are one rotation
    assert rel(rotary_embedding(x, inv_freq=plain),
               rotary_embedding(x, 100.0)) < 1e-7
    # the amplitude multiplies cos and sin, so the rotated vector
    amp = TOY_YARN["attention_factor"]
    assert rel(rotary_embedding(x, 100.0, amplitude=amp),
               amp * rotary_embedding(x, 100.0)) < 1e-6
    inv = yarn_inv_freq(16, 100.0, 4.0, 64, 4.0, 1.0)
    assert list(np.round(inv / np.asarray(plain), 4)) == [
        1, 1, 0.8125, 0.625, 0.4375, 0.25, 0.25, 0.25]       # low 1, high 5
    y = rotary_embedding(x, inv_freq=inv, amplitude=amp)
    z = ref.dims(cfg_of("F"))
    assert rel(y, ref.rotary(x, "F", z)) < 1e-6
    assert rel(rotary_embedding(x, 100.0), ref.rotary(x, "S", z)) < 1e-6
    # pair (x_2, x_10) of position 5 turned by 5 * inv_2, times amp
    ang = 5 * float(inv[2])
    a, b = float(x[0, 5, 1, 2]), float(x[0, 5, 1, 10])
    assert float(y[0, 5, 1, 2]) == pytest.approx(
        amp * (a * math.cos(ang) - b * math.sin(ang)), abs=1e-5)
    assert float(y[0, 5, 1, 10]) == pytest.approx(
        amp * (b * math.cos(ang) + a * math.sin(ang)), abs=1e-5)
    # a full layer's scores carry the amplitude's square
    assert rel(jnp.sum(y * y, -1), amp ** 2 * jnp.sum(x * x, -1)) < 1e-6


# ---- the router -----------------------------------------------------------------

@f32
@pytest.mark.parametrize("top_k", [1, 3, 8])
def test_softmax_router_weights_are_softmax_over_the_chosen_logits(top_k):
    ks = jax.random.split(jax.random.PRNGKey(top_k), 2)
    x = jax.random.normal(ks[0], (40, 64))
    router = jax.random.normal(ks[1], (64, 16)) * 0.3
    idx, w = route_top_k(x, router, None, top_k, 1.0, score=jax.nn.softmax)
    logits = np.asarray(x @ router, np.float64)
    assert idx.shape == w.shape == (40, top_k)
    for n in range(40):
        chosen = list(np.asarray(idx[n]))
        assert len(set(chosen)) == top_k                  # distinct
        assert set(chosen) == set(np.argsort(-logits[n])[:top_k])
        # p_e / sum_E p is the softmax over the chosen logits alone
        e = np.exp(logits[n, chosen] - logits[n, chosen].max())
        np.testing.assert_allclose(np.asarray(w[n]), e / e.sum(), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(jnp.sum(w, -1)), 1.0, rtol=1e-6)
    # the reference's route is the same choice and the same weights
    lay = {"router": router}
    ridx, rw = ref.route(lay, x[None], {"top_k": top_k})
    assert np.array_equal(np.asarray(ridx[0]), np.asarray(idx))
    assert rel(w, rw[0]) < 1e-6
    # sigmoid scoring is what it was: a bias that only chooses
    sidx, sw = route_top_k(x, router, jnp.zeros(16), top_k, 2.5)
    s = jax.nn.sigmoid(x @ router)
    top = jnp.take_along_axis(s, sidx, -1)
    assert rel(sw, top / jnp.sum(top, -1, keepdims=True) * 2.5) < 1e-6
    nidx, nw = route_top_k(x, router, None, top_k, 2.5)
    assert np.array_equal(np.asarray(nidx), np.asarray(sidx))
    assert rel(nw, sw) < 1e-7


# ---- the whole stack: forward, loss, gradients -----------------------------

@f32
@pytest.mark.parametrize("pattern,t", [("S", 24), ("F", 24), ("SSSF", 24)])
def test_stack_matches_the_reference(pattern, t):
    cfg = cfg_of(pattern)
    params = ref.init(jax.random.PRNGKey(3), cfg)
    # norms away from 1, so that a norm left out would show
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a + 0.1 * jax.random.normal(
            jax.random.PRNGKey(len(jax.tree_util.keystr(path))), a.shape)
        if "norm" in jax.tree_util.keystr(path) else a, params)
    emb = jax.random.normal(jax.random.PRNGKey(1), (2, t, 64)) * 0.02
    labels = jax.random.randint(jax.random.PRNGKey(2), (2, t), 0, 96)
    model = program(cfg)
    assert jax.tree.map(jnp.shape, model.init(jax.random.PRNGKey(0))) \
        == jax.tree.map(jnp.shape, params)
    want, (gp_r, ge_r) = jax.value_and_grad(
        lambda p, e: ref.loss(p, e, labels, cfg), argnums=(0, 1))(params, emb)
    (got, scalars), (gp, ge) = jax.value_and_grad(
        lambda p, e: model.loss(p, e, labels, jnp.ones((2, t), bool)),
        argnums=(0, 1), has_aux=True)(params, emb)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    assert rel(model.logits(params, emb),
               ref.forward(params, emb, cfg)) < 1e-5
    assert rel(ge, ge_r) < 1e-4
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(gp_r)[0],
                            jax.tree.leaves(gp)):
        assert float(jnp.linalg.norm(a)) > 0, jax.tree_util.keystr(path)
        assert rel(b, a) < 2e-4, jax.tree_util.keystr(path)
    assert set(scalars) == set(model.step_scalars) \
        == set(NemotronH.step_scalars) | {"moe_choices"}
    _, loads, _ = model.hidden(params, emb)
    assert loads.shape == (len(pattern), 4)
    assert float(scalars["moe_choices_held"]) == float(jnp.sum(loads))
    blk = math.gcd(2 * t, 512)
    assert float(scalars["moe_rows_computed"]) == float(
        jnp.sum(-(-loads // blk) * blk))
    # every choice the routers made: 2 sequences of t, top_k a layer
    assert float(scalars["moe_choices"]) \
        == 2 * t * cfg["num_experts_per_tok"] * len(pattern)
    assert 0 < float(scalars["moe_choices_held"]) \
        <= float(scalars["moe_choices"])


#: of ``SWEEP_T``'s three blocks of 512: a window inside one block, one
#: that reaches into the block before and one that spans two blocks
@pytest.mark.parametrize("pattern,window", [
    ("F", 7), ("S", 7), ("S", 700), ("SF", 1100)])
def test_the_attention_sublayers_forward_sweep_runs_once_a_step(
        monkeypatch, pattern, window):
    from paddlebox_tpu.models import mellum
    cfg = cfg_of(pattern, sliding_window=window)
    assert_the_forward_sweep_runs_once(
        monkeypatch, mellum, program(cfg),
        ref.init(jax.random.PRNGKey(3), cfg), attn_layers=len(pattern))


@f32
def test_a_layers_kind_is_not_in_its_weights():
    """The same weights under ``S`` and under ``F`` are two functions
    (the window, the rotary table, the amplitude), and the reference
    follows the pattern string as the program follows ``layer_types``."""
    params = ref.init(jax.random.PRNGKey(3), cfg_of("S"))
    emb = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 64)) * 0.02
    outs = {}
    for pattern in "SF":
        cfg = cfg_of(pattern)
        outs[pattern] = program(cfg).logits(params, emb)
        assert rel(outs[pattern], ref.forward(params, emb, cfg)) < 1e-5
    assert rel(outs["S"], outs["F"]) > 1e-3
    with pytest.raises(ValueError, match="not all of"):
        program(dict(cfg_of("S"), layer_types=["conv"]))
    with pytest.raises(ValueError, match="layer types for"):
        program(dict(cfg_of("S"), num_hidden_layers=2))
    with pytest.raises(ValueError, match="is not of S, F"):
        ref.init(jax.random.PRNGKey(0), cfg_of("S", layer_pattern="SX"))


@f32
def test_attention_at_the_published_head_shape():
    """Heads of 128, 32 query heads on 4 key/value heads at hidden 2304
    and a window (the published shape but the lengths): head norms +
    the kind's rotary table + the blockwise op against the reference's
    dense masked softmax, values and gradients, both kinds."""
    t, d = 48, 2304
    cfg = cfg_of("SF", hidden_size=d, num_attention_heads=32,
                 num_key_value_heads=4, head_dim=128, sliding_window=20)
    z = ref.dims(cfg)
    ks = jax.random.split(jax.random.PRNGKey(4), 8)
    lay = {"q": jax.random.normal(ks[0], (d, 32 * 128)) * 0.02,
           "k": jax.random.normal(ks[1], (d, 4 * 128)) * 0.02,
           "v": jax.random.normal(ks[2], (d, 4 * 128)) * 0.02,
           "o": jax.random.normal(ks[3], (32 * 128, d)) * 0.02,
           "q_norm": 1 + 0.1 * jax.random.normal(ks[4], (128,)),
           "k_norm": 1 + 0.1 * jax.random.normal(ks[5], (128,)),
           "attn_norm": 1 + 0.1 * jax.random.normal(ks[6], (d,))}
    x = jax.random.normal(ks[6], (2, t, d))
    cot = jax.random.normal(ks[7], (2, t, d))
    model = program(cfg)
    for kind, letter in (("sliding_attention", "S"), ("full_attention", "F")):
        def ours(lay, x):
            return model._attention(kind, lay, x) - x    # less the residual

        def theirs(lay, x):
            return ref.attention(lay, ref_nemotron.rms_norm(
                x, lay["attn_norm"], z["eps"]), letter, z, None)

        assert rel(ours(lay, x), theirs(lay, x)) < 1e-5, kind
        got = jax.grad(lambda lay, x: jnp.sum(ours(lay, x) * cot),
                       argnums=(0, 1))(lay, x)
        want = jax.grad(lambda lay, x: jnp.sum(theirs(lay, x) * cot),
                        argnums=(0, 1))(lay, x)
        for (path, a), b in zip(
                jax.tree_util.tree_flatten_with_path(want)[0],
                jax.tree.leaves(got)):
            assert rel(b, a) < 1e-4, (kind, jax.tree_util.keystr(path))


# ---- the expert layer: softmax top-8, narrow experts ---------------------------

@f32
@pytest.mark.parametrize("case", ["top-8", "top-8-bfloat16-operands",
                                  "top-8-last-share", "top-8-blocks-of-2"])
def test_experts_of_a_width_off_the_block_at_top_8(case):
    """Width 56 (7 x 8, as 896 is 7 x 128: no multiple of the rows'
    block of 8) and eight choices a token: ``routed_experts``' gated
    loops, value and every gradient, against the dense masked form."""
    n = 14 if case.endswith("blocks-of-2") else 24
    held = (12, 16) if case.endswith("last-share") else (0, 4)
    bf16 = "bfloat16" in case
    cfg = cfg_of("S", moe_intermediate_size=56, num_experts=16,
                 num_experts_per_tok=8)
    lay = ref.init(jax.random.PRNGKey(8), cfg)["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(9), (n, 64))
    idx, w = route_top_k(x, lay["router"] * 20, None, 8, 1.0,
                         score=jax.nn.softmax)
    lo, hi = held
    gate, up, down = (lay[k][lo:hi] for k in ("gate", "up", "down"))
    assert up.shape == (4, 64, 56) and math.gcd(n, 512) in (8, 2)
    dtype = jnp.bfloat16 if bf16 else jnp.float32
    cot = jax.random.normal(jax.random.PRNGKey(11), x.shape)

    def ours(x, w, gate, up, down):
        return routed_experts(x, idx, w, up, down, held, mm_dtype=dtype,
                              gate=gate)[0]

    def dense(x, w, gate, up, down):
        return _held_reference(x, idx, w, gate, up, down, held,
                               "bfloat16" if bf16 else None)

    args = (x, w, gate, up, down)
    _, stats = routed_experts(x, idx, w, up, down, held, mm_dtype=dtype,
                              gate=gate)
    want_load = np.asarray(jnp.sum(idx[:, :, None] == jnp.arange(*held),
                                   axis=(0, 1)))
    assert list(np.asarray(stats["load"])) == list(want_load)
    assert 0 < int(stats["choices"]) < n * 8
    blk = math.gcd(n, 512)
    assert int(stats["rows"]) == sum(-(-int(c) // blk) * blk
                                     for c in want_load)
    assert rel(ours(*args), dense(*args)) < (1e-2 if bf16 else 1e-5)
    got = jax.grad(lambda *a: jnp.sum(ours(*a) * cot),
                   argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * cot),
                    argnums=(0, 1, 2, 3, 4))(*args)
    for name, g, r in zip(("x", "w", "gate", "up", "down"), got, want):
        assert float(jnp.linalg.norm(r)) > 0, name
        # bfloat16 operands: one rounding apart, as tests/test_lfm2.py says
        limit = 1e-4 if not bf16 else 1e-5 if name == "down" else 1e-2
        assert rel(g, r) < limit, name


@f32
def test_the_eight_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Eight chips hold eight of the sixty-four experts each: what the
    shares ``held = (8 i, 8 i + 8)`` give, summed, is the whole expert
    feed-forward as the reference computes it uncut (no shared expert
    and no dense part to count once)."""
    cfg = cfg_of("S", router_outputs=64, num_experts=64,
                 num_experts_per_tok=8)
    lay = ref.init(jax.random.PRNGKey(5), cfg)["layers"][0]
    u = jax.random.normal(jax.random.PRNGKey(6), (2, 12, 64))
    z = ref.dims(cfg)
    want = ref.moe(lay, u, z, None, (0, 64))
    flat = u.reshape(-1, 64)
    idx, w = route_top_k(flat, lay["router"], None, 8, 1.0,
                         score=jax.nn.softmax)
    ridx, rw = ref.route(lay, u, z)
    assert np.array_equal(np.asarray(idx), np.asarray(ridx).reshape(-1, 8))
    assert rel(w, rw.reshape(-1, 8)) < 1e-6
    total, choices = 0.0, 0
    for lo in range(0, 64, 8):
        part, stats = routed_experts(
            flat, idx, w, lay["up"][lo:lo + 8], lay["down"][lo:lo + 8],
            (lo, lo + 8), mm_dtype=jnp.float32, gate=lay["gate"][lo:lo + 8])
        # one share alone is the reference given the same share
        alone = ref.moe({**lay, **{k: lay[k][lo:lo + 8]
                                   for k in ("gate", "up", "down")}},
                        u, z, None, (lo, lo + 8))
        assert rel(part, alone.reshape(-1, 64)) < 1e-5
        total = total + part
        choices += int(stats["choices"])
    assert choices == flat.shape[0] * 8       # every choice fell somewhere
    assert rel(total, want.reshape(-1, 64)) < 1e-5
    # and through the model: a share's layer is the reference's same share
    for lo in (0, 56):
        share = dict(cfg, num_experts=8, first_expert_held=lo)
        part = {**lay, **{k: lay[k][lo:lo + 8]
                          for k in ("gate", "up", "down")}}
        x, load, _ = program(share)._moe(part, u)
        # less the residual, which is 3,000 times what the share adds:
        # float32 keeps four digits of the difference
        assert rel(x - u, ref.moe(
            part, ref_nemotron.rms_norm(u, part["ffn_norm"], z["eps"]), z,
            None, (lo, lo + 8))) < 1e-3
        assert load.shape == (8,)


# ---- one pass through Trainer + PassPreloader -------------------------------

def toy_cell():
    cell = family_toy_cell()
    cell["config"]["matmul_dtype"] = "float32"
    return cell


@f32
def test_one_pass_through_the_trainer_equals_the_reference_step_by_step():
    from benchmarks.families import lm_mellum as family
    from paddlebox_tpu.ps.table import NUM_FIXED
    cell = toy_cell()
    config, traffic = cell["config"], cell["traffic"]
    pool = family.make_pool(config, traffic, 5)
    params = family.seeded_params(ref, config, 5)
    host = jax.device_get(params)
    tr, table, pre = _trainer(cell, pool, params, program(config))
    try:
        out = tr.train_pass_resident(pre.wait())
    finally:
        pre.drain()
    want = ref_lm.run_pass(ref, config, pool[0].inputs, pool[0].labels, 2,
                           host["net"], host["embedding"], precision=None)
    assert len(out["losses"]) == 4
    np.testing.assert_allclose(out["losses"], want["loss_steps"], rtol=2e-5)
    assert out["tokens"] == 192
    assert out["moe_rows_computed"] >= out["moe_choices_held"] > 0
    assert out["moe_expert_load_max"] >= out["moe_expert_load_mean"] > 0
    # every choice of the pass: 192 tokens, top_k a layer
    assert out["moe_choices"] == 192 * config["num_experts_per_tok"] \
        * config["num_hidden_layers"]
    fin = [s for s in trace.recent_spans() if s.name == "pass.finish"][-1]
    for k in ("tokens", "documents", "moe_choices_held", "moe_rows_computed",
              "moe_expert_load_max", "moe_expert_load_mean", "moe_choices"):
        assert fin.attrs[k] == out[k], k
    # the dense weights after four Adam steps
    for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(want["params"])[0],
            jax.tree.leaves(tr.state.params)):
        assert rel(b, a) < 1e-4, jax.tree_util.keystr(path)
    # every row of the table: counts exactly, vectors and Adagrad sums
    rows = table.index.lookup(np.arange(96, dtype=np.uint64))
    got = np.asarray(tr.state.table.data)[rows]
    ref_rows = np.asarray(want["table"])
    assert np.array_equal(got[:, 0], ref_rows[:, 0])           # show
    moved = ref_rows[:, 0] > 0
    assert moved.sum() > 10
    d_got = got[:, NUM_FIXED:] - host["embedding"]
    d_ref = ref_rows[:, NUM_FIXED:] - host["embedding"]
    assert rel(d_got[moved], d_ref[moved]) < 1e-3
    assert not d_got[~moved].any()
    assert rel(got[:, 6], ref_rows[:, 6]) < 1e-3


def test_pass_program_carries_every_scope():
    cell = toy_cell()
    text = _pass_text(cell, ref, program(cell["config"]), True)
    missing = {s for s in trace.WINDOW_SEQ_STEP_SCOPES
               if not re.search(re.escape(s) + r"(?![A-Za-z0-9_])", text)}
    assert not missing, missing
    assert trace.SCOPE_ATTN_WINDOW == "pbox.attn_window"
    # neither mixer, no dense feed-forward, no shared expert in this model
    for s in (trace.SCOPE_SSM_SCAN, trace.SCOPE_CONV_MIX, trace.SCOPE_MLP,
              trace.SCOPE_MOE_SHARED):
        assert s not in text
    # a sublayer is one jax.checkpoint: the reducers count its backward
    # ops under the scope itself (PERF.md section 7)
    for s in (trace.SCOPE_ATTN, trace.SCOPE_ATTN_WINDOW,
              trace.SCOPE_MOE_EXPERTS):
        assert f"checkpoint/{s}/" in text, s
    # a stack of full layers alone has no op under the window's scope
    full = dict(cell, config=dict(cell["config"], **toy_config("FF")))
    text = _pass_text(full, ref, program(full["config"]), True)
    assert trace.SCOPE_ATTN_WINDOW not in text
    assert re.search(r"pbox\.attn(?![A-Za-z0-9_])", text)
