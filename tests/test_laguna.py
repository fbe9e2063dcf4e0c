"""The sixth sequence model (models/laguna.py: gated sliding-window and
full rotary attention with a head count a layer, half-rotary YaRN on the
full layers, a leading dense SwiGLU, softmax-routed gated experts beside
a shared one through parallel/moe.routed_experts) against the plain
reference the benchmark keeps (benchmarks/reference/models/laguna.py,
benchmarks/reference/lm.py), at tiny widths on the CPU in float32; and
what it forced in the code the other models share: a rotary width under
the head's in ops/causal_attention.py, the factor that sets it in
models/mellum.py's table, the head-wise gate of lm_parts.rotary_attention.
"""

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
from benchmarks.reference.models import laguna as ref  # noqa: E402
from benchmarks.reference.models import nemotron_h as ref_nemotron  # noqa: E402
from benchmarks.tests.test_family_lm_laguna import (  # noqa: E402
    toy_cell as family_toy_cell, toy_config)
from paddlebox_tpu.models import LagunaMoe, MellumMoe, laguna  # noqa: E402
from paddlebox_tpu.models.mellum import rotary_table  # noqa: E402
from paddlebox_tpu.obs import trace  # noqa: E402
from paddlebox_tpu.ops.causal_attention import (  # noqa: E402
    rotary_embedding, yarn_inv_freq)
from paddlebox_tpu.parallel.moe import route_top_k, routed_experts  # noqa: E402
from test_nemotron_h import (_pass_text, _trainer, f32,  # noqa: E402
                             highest_precision,  # noqa: F401
                             program_flags_restored, rel)  # noqa: F401


def cfg_of(pattern: str = "FSSSF", heads=(4, 6, 6, 6, 4), mlp="DEEEE",
           **over) -> dict:
    """The cell's configuration with a toy stack."""
    return dict(family_toy_cell()["config"],
                **toy_config(pattern, heads, mlp, **over))


def program(cfg, dtype=jnp.float32):
    return LagunaMoe(cfg, compute_dtype=dtype)


def _perturbed_norms(params):
    """Norms away from 1, so that a norm left out would show."""
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a + 0.1 * jax.random.normal(
            jax.random.PRNGKey(len(jax.tree_util.keystr(path))), a.shape)
        if "norm" in jax.tree_util.keystr(path) else a, params)


def _grads_agree(got, want, limit):
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(want)[0],
                            jax.tree.leaves(got)):
        assert float(jnp.linalg.norm(a)) > 0, jax.tree_util.keystr(path)
        assert rel(b, a) < limit, jax.tree_util.keystr(path)


# ---- a rotary width under the head's ------------------------------------------

@f32
def test_half_rotary_turns_the_first_half_as_a_head_of_its_own():
    """``rotary_dim`` 64 of a head of 128: dimensions 64..127 come out as
    they went in, 0..63 as a full rotation of a head of 64 turns them,
    with YaRN's table and amplitude as the published full layers give
    them; the reference's rotation is the same."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 40, 3, 128))
    table = rotary_table(128, {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 128,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.4852030263919618,
        "partial_rotary_factor": 0.5})
    assert table["rotary_dim"] == 64
    assert np.array_equal(table["inv_freq"], yarn_inv_freq(
        64, 500000.0, 128.0, 8192, 32.0, 1.0))
    y = rotary_embedding(x, **table)
    assert np.array_equal(np.asarray(y[..., 64:]), np.asarray(x[..., 64:]))
    half = rotary_embedding(x[..., :64], inv_freq=table["inv_freq"],
                            amplitude=table["amplitude"])
    assert np.array_equal(np.asarray(y[..., :64]), np.asarray(half))
    # pair (x_3, x_35) of position 7 turned by 7 * inv_3, times the amplitude
    ang, amp = 7 * float(table["inv_freq"][3]), table["amplitude"]
    a, b = float(x[0, 7, 1, 3]), float(x[0, 7, 1, 35])
    assert float(y[0, 7, 1, 3]) == pytest.approx(
        amp * (a * math.cos(ang) - b * math.sin(ang)), abs=1e-5)
    assert float(y[0, 7, 1, 35]) == pytest.approx(
        amp * (b * math.cos(ang) + a * math.sin(ang)), abs=1e-5)
    z = ref.dims(dict(cfg_of(), head_dim=128, yarn_rope_theta=500000,
                      yarn_factor=128,
                      yarn_original_max_position_embeddings=8192,
                      yarn_beta_fast=32, yarn_beta_slow=1,
                      yarn_attention_factor=amp))
    assert rel(ref.rotary(x, "F", z), y) < 1e-6
    # a whole-head factor is the plain rotation, the table's key absent
    plain = rotary_table(128, {"rope_type": "default", "rope_theta": 10000,
                               "partial_rotary_factor": 1})
    assert plain == {"theta": 10000.0}
    assert rel(ref.rotary(x, "S", dict(z, theta=10000.0)),
               rotary_embedding(x, **plain)) < 1e-6


@pytest.mark.parametrize("factor,match", [
    (0.0, r"not in \(0, 1\]"), (1.5, r"not in \(0, 1\]"),
    (-0.5, r"not in \(0, 1\]"), (0.4, "width of 51"), (0.01, "width of 1")])
def test_a_factor_that_leaves_no_whole_pairs_is_refused(factor, match):
    """A ``partial_rotary_factor`` outside (0, 1], or one whose width is
    odd, is an error, not a rotation of the whole head."""
    with pytest.raises(ValueError, match=match):
        rotary_table(128, {"rope_type": "default", "rope_theta": 100,
                           "partial_rotary_factor": factor})


def test_a_table_without_the_factor_is_the_table_it_was():
    """Cell 4's groups carry no ``partial_rotary_factor``: what the table
    tells the rotation is what it told it before the factor was read."""
    full = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782}
    got = rotary_table(128, full)
    assert set(got) == {"inv_freq", "amplitude"}
    assert np.array_equal(got["inv_freq"], yarn_inv_freq(
        128, 500000.0, 16.0, 8192, 32.0, 1.0))
    assert rotary_table(128, {"rope_type": "default",
                              "rope_theta": 500000}) == {"theta": 500000.0}


# ---- the attention sublayer: a head count a layer, the gate --------------------

def _attention_layer(key, d, qh, kvh, hd):
    ks = jax.random.split(key, 8)
    return {"q": jax.random.normal(ks[0], (d, qh * hd)) * 0.05,
            "k": jax.random.normal(ks[1], (d, kvh * hd)) * 0.05,
            "v": jax.random.normal(ks[2], (d, kvh * hd)) * 0.05,
            "o": jax.random.normal(ks[3], (qh * hd, d)) * 0.05,
            "q_norm": 1 + 0.1 * jax.random.normal(ks[4], (hd,)),
            "k_norm": 1 + 0.1 * jax.random.normal(ks[5], (hd,)),
            "attn_norm": 1 + 0.1 * jax.random.normal(ks[6], (d,)),
            "attn_gate": jax.random.normal(ks[7], (d, qh)) * 0.3}


@f32
def test_a_72_head_layer_in_groups_of_9():
    """72 query heads on 8 key/value heads (the published sliding layers'
    groups of 9) and 48 on 8 (the full layers' 6), heads of 16 at hidden
    64: norm, projections, head norms, the kind's rotation, the blockwise
    op, the gate, ``o``, against the reference's dense masked softmax,
    values and every gradient, both kinds."""
    t, d = 40, 64
    for kind, letter, qh in (("sliding_attention", "S", 72),
                             ("full_attention", "F", 48)):
        cfg = cfg_of(letter, (qh,), "E", num_key_value_heads=8,
                     sliding_window=13)
        z = ref.dims(cfg)
        lay = _attention_layer(jax.random.PRNGKey(qh), d, qh, 8, 16)
        x = jax.random.normal(jax.random.PRNGKey(5), (1, t, d))
        cot = jax.random.normal(jax.random.PRNGKey(6), (1, t, d))
        model = program(cfg)

        def ours(lay, x):
            return model._attention(kind, qh, lay, x) - x   # less the residual

        def theirs(lay, x):
            return ref.attention(lay, ref_nemotron.rms_norm(
                x, lay["attn_norm"], z["eps"]), letter, qh, z, None)

        def vjp(f):
            return jax.jit(jax.value_and_grad(
                lambda lay, x: jnp.sum(f(lay, x) * cot), argnums=(0, 1)))

        (out, got), (want_out, want) = vjp(ours)(lay, x), vjp(theirs)(lay, x)
        assert float(out) == pytest.approx(float(want_out), rel=1e-5), kind
        assert rel(jax.jit(ours)(lay, x), jax.jit(theirs)(lay, x)) < 1e-5
        _grads_agree(got, want, 1e-4)


@f32
def test_a_gate_open_all_the_way_is_the_ungated_layer():
    """Gate logits pushed to +inf (a positive input through a positive
    norm, a gate matrix of 1e3 everywhere: every logit over 1e3, its
    sigmoid 1 in float32) give the layer without a gate to the last bit;
    a gate at its seeded start does not."""
    t, d, qh = 24, 64, 6
    cfg = cfg_of("S", (qh,), "E")
    lay = _attention_layer(jax.random.PRNGKey(0), d, qh, 2, 16)
    lay["attn_norm"] = jnp.abs(lay["attn_norm"])
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (2, t, d)))
    model = program(cfg)
    ungated = {k: v for k, v in lay.items() if k != "attn_gate"}
    plain = model._attention("sliding_attention", qh, ungated, x)
    wide_open = dict(lay, attn_gate=jnp.full((d, qh), 1e3))
    assert np.array_equal(
        np.asarray(model._attention("sliding_attention", qh, wide_open, x)),
        np.asarray(plain))
    assert rel(model._attention("sliding_attention", qh, lay, x) - x,
               plain - x) > 0.1


# ---- the whole stack: forward, loss, gradients -----------------------------

#: the limits the loss and the gradients are held to: float32 on both
#: sides, so float32's own roundings through a few layers
LOSS_REL, GRAD_REL = 1e-5, 2e-4


@f32
def test_stack_matches_the_reference():
    """The cut's own stack (a full layer with the dense feed-forward,
    three sliding ones, a full one; experts after the first) over
    sequences of four windows."""
    pattern, heads, mlp, t = "FSSSF", (4, 6, 6, 6, 4), "DEEEE", 28
    cfg = cfg_of(pattern, heads, mlp)
    params = _perturbed_norms(ref.init(jax.random.PRNGKey(3), cfg))
    emb = jax.random.normal(jax.random.PRNGKey(1), (2, t, 64))
    labels = jax.random.randint(jax.random.PRNGKey(2), (2, t), 0, 96)
    model = program(cfg)
    assert model.heads == heads
    assert jax.tree.map(jnp.shape, model.init(jax.random.PRNGKey(0))) \
        == jax.tree.map(jnp.shape, params)
    want, (gp_r, ge_r) = jax.jit(jax.value_and_grad(
        lambda p, e: ref.loss(p, e, labels, cfg), argnums=(0, 1)))(params, emb)
    (got, scalars), (gp, ge) = jax.jit(jax.value_and_grad(
        lambda p, e: model.loss(p, e, labels, jnp.ones((2, t), bool)),
        argnums=(0, 1), has_aux=True))(params, emb)
    assert float(got) == pytest.approx(float(want), rel=LOSS_REL)
    assert rel(jax.jit(model.logits)(params, emb), jax.jit(
        lambda p, e: ref.forward(p, e, cfg))(params, emb)) < LOSS_REL
    assert rel(ge, ge_r) < GRAD_REL
    _grads_agree(gp, gp_r, GRAD_REL)
    assert set(scalars) == set(model.step_scalars) \
        == set(MellumMoe.step_scalars)
    expert_layers = mlp.count("E")
    _, loads, rows = jax.eval_shape(model.hidden, params, emb)
    assert loads.shape == (expert_layers, 4)
    assert rows.shape == (expert_layers,)
    # whole blocks of the step's positions an expert run
    blk = math.gcd(2 * t, 512)
    assert float(scalars["moe_rows_computed"]) % blk == 0
    assert float(scalars["moe_rows_computed"]) >= float(
        scalars["moe_choices_held"])
    assert float(scalars["moe_choices"]) == 2 * t * 3 * expert_layers
    assert 0 < float(scalars["moe_choices_held"]) \
        <= float(scalars["moe_choices"])


@f32
def test_float8_operands_would_fail_the_comparison():
    """The reference with float8 operands (the precision below the
    configuration's bfloat16, so below anything the program may compute
    in) misses the float32 reference by more than the limits the stack
    is held to, on the loss or on a gradient: the comparison can tell."""
    cfg = cfg_of("FS", (4, 6), "DE")
    params = _perturbed_norms(ref.init(jax.random.PRNGKey(3), cfg))
    emb = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 64))
    labels = jax.random.randint(jax.random.PRNGKey(2), (2, 24), 0, 96)

    def run(precision):
        return jax.jit(jax.value_and_grad(
            lambda p: ref.loss(p, emb, labels, cfg, precision)))(params)
    (want, g_want), (low, g_low) = run(None), run("float8_e4m3fn")
    worst = max(rel(b, a) for a, b in zip(jax.tree.leaves(g_want),
                                          jax.tree.leaves(g_low)))
    assert abs(float(low) / float(want) - 1) > LOSS_REL
    assert worst > 10 * GRAD_REL


def test_the_layers_kinds_heads_and_feed_forwards_are_checked():
    cfg = cfg_of()
    assert program(cfg).kinds == ("full_attention",) + (
        "sliding_attention",) * 3 + ("full_attention",)
    with pytest.raises(ValueError, match="not all of"):
        program(dict(cfg, layer_types=["conv"] * 5))
    with pytest.raises(ValueError, match="mlp layer types"):
        program(dict(cfg, mlp_layer_types=["moe"] * 5))
    with pytest.raises(ValueError, match="num_attention_heads_per_layer for"):
        program(dict(cfg, num_attention_heads_per_layer=[4, 6]))
    with pytest.raises(ValueError, match="not groups of"):
        program(dict(cfg, num_attention_heads_per_layer=[4, 6, 5, 6, 4]))
    with pytest.raises(ValueError, match="one scalar a head"):
        program(dict(cfg, gating_types=["per_head", "elementwise"]))
    with pytest.raises(ValueError, match="S, F and D, E"):
        ref.init(jax.random.PRNGKey(0), dict(cfg, layer_pattern="FSSSX"))
    with pytest.raises(ValueError, match="a layer"):
        ref.init(jax.random.PRNGKey(0), dict(cfg, layer_heads="4,6"))


# ---- the expert layer: a chip's share, and the shared expert once -------------

@f32
def test_the_32_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Thirty-two chips hold two of the 64 experts each, ten chosen a
    token: what the shares ``held = (2 i, 2 i + 2)`` give, summed, with
    the shared expert (which every chip computes alike) counted once, is
    the whole feed-forward as the reference computes it uncut."""
    cfg = cfg_of("SS", (4, 4), "EE", router_outputs=64, num_experts=64,
                 num_experts_per_tok=10)
    lay = ref.init(jax.random.PRNGKey(5), cfg)["layers"][1]
    u = jax.random.normal(jax.random.PRNGKey(6), (2, 12, 64))
    z = ref.dims(cfg)
    want = ref.moe(lay, u, z, None, (0, 64))
    flat = u.reshape(-1, 64)
    idx, w = route_top_k(flat, lay["router"], None, 10, z["scale"],
                         score=jax.nn.softmax)
    ridx, rw = ref.route(lay, u, z)
    assert np.array_equal(np.asarray(idx), np.asarray(ridx).reshape(-1, 10))
    assert rel(w, rw.reshape(-1, 10)) < 1e-6
    # renormalised over the chosen ten, times the scaling factor 2.5
    assert np.allclose(np.asarray(jnp.sum(w, -1)), 2.5, rtol=1e-6)
    shared = ref.swiglu_mlp(u, lay["shared_gate"], lay["shared_up"],
                            lay["shared_down"], None).reshape(-1, 64)

    @jax.jit
    def all_shares(flat, idx, w, lay):
        total, choices = shared, 0
        for lo in range(0, 64, 2):
            part, stats = routed_experts(
                flat, idx, w, lay["up"][lo:lo + 2], lay["down"][lo:lo + 2],
                (lo, lo + 2), mm_dtype=jnp.float32,
                gate=lay["gate"][lo:lo + 2])
            total = total + part
            choices += stats["choices"]
        return total, choices

    total, choices = all_shares(flat, idx, w, lay)
    assert int(choices) == flat.shape[0] * 10  # every choice fell somewhere
    assert rel(total, want.reshape(-1, 64)) < 1e-5
    # and through the model: a share's layer is the reference's same share
    for lo in (0, 62):
        share = dict(cfg, num_experts=2, first_expert_held=lo)
        part = {**lay, **{k: lay[k][lo:lo + 2]
                          for k in ("gate", "up", "down")}}
        x, load, _ = program(share)._moe(part, u)
        assert rel(x - u, ref.moe(
            part, ref_nemotron.rms_norm(u, part["ffn_norm"], z["eps"]), z,
            None, (lo, lo + 2))) < 1e-4
        assert load.shape == (2,)


# ---- one pass through Trainer + PassPreloader -------------------------------

def toy_cell():
    cell = family_toy_cell()
    cell["config"]["matmul_dtype"] = "float32"
    return cell


@f32
def test_one_pass_through_the_trainer_equals_the_reference_step_by_step():
    from benchmarks.families import lm_laguna as family
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
    assert out["moe_choices"] == 192 * 3          # one expert layer
    fin = [s for s in trace.recent_spans() if s.name == "pass.finish"][-1]
    for k in ("moe_choices_held", "moe_rows_computed", "moe_choices"):
        assert fin.attrs[k] == out[k], k
    for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(want["params"])[0],
            jax.tree.leaves(tr.state.params)):
        assert rel(b, a) < 1e-4, jax.tree_util.keystr(path)
    rows = table.index.lookup(np.arange(96, dtype=np.uint64))
    got = np.asarray(tr.state.table.data)[rows]
    ref_rows = np.asarray(want["table"])
    assert np.array_equal(got[:, 0], ref_rows[:, 0])           # show
    moved = ref_rows[:, 0] > 0
    d_got = got[:, NUM_FIXED:] - host["embedding"]
    d_ref = ref_rows[:, NUM_FIXED:] - host["embedding"]
    assert rel(d_got[moved], d_ref[moved]) < 1e-3
    assert not d_got[~moved].any()


def test_pass_program_carries_every_scope():
    cell = toy_cell()
    text = _pass_text(cell, ref, program(cell["config"]), True)
    missing = {s for s in laguna.STEP_SCOPES
               if not re.search(re.escape(s) + r"(?![A-Za-z0-9_])", text)}
    assert not missing, missing
    for s in (trace.SCOPE_SSM_SCAN, trace.SCOPE_CONV_MIX,
              trace.SCOPE_EXIT_GATE, trace.SCOPE_KDA_SCAN):
        assert s not in text
    # the gate sits inside either kind of attention layer's scope
    for s in (trace.SCOPE_ATTN, trace.SCOPE_ATTN_WINDOW):
        assert f"{s}/{trace.SCOPE_ATTN_GATE}/" in text, s
    # a sublayer is one jax.checkpoint: the reducers count its backward
    # ops under the scope itself
    for s in (trace.SCOPE_ATTN, trace.SCOPE_ATTN_WINDOW, trace.SCOPE_MLP,
              trace.SCOPE_MOE_EXPERTS, trace.SCOPE_MOE_SHARED):
        assert f"checkpoint/{s}/" in text, s
