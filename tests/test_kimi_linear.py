"""The fifth sequence model (models/kimi_linear.py: Kimi Delta Attention
mixers beside latent attention ones, a dense lead, sigmoid-routed gated
experts beside a shared one through parallel/moe.routed_experts) against
the plain reference the benchmark keeps
(benchmarks/reference/models/kimi_linear.py, benchmarks/reference/lm.py),
at tiny widths on the CPU in float32, the mixers also at their published
widths; and what it forced in the ops the other models share: values of
a width of their own in ops/causal_attention.py, the program of square
heads unchanged."""

import inspect
import math
import os
import re
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.reference import lm as ref_lm  # noqa: E402
from benchmarks.reference.models import kimi_linear as ref  # noqa: E402
from benchmarks.reference.models import nemotron_h as ref_nemotron  # noqa: E402
from benchmarks.tests.test_family_lm_kimi_linear import (  # noqa: E402
    toy_cell as family_toy_cell, toy_config)
from paddlebox_tpu.models import KimiLinear, MellumMoe  # noqa: E402
from paddlebox_tpu.models import kimi_linear  # noqa: E402
from paddlebox_tpu.obs import trace  # noqa: E402
from paddlebox_tpu.ops import causal_attention, kda  # noqa: E402
from paddlebox_tpu.ops.causal_attention import causal_gqa_attention  # noqa: E402
from paddlebox_tpu.parallel.moe import route_top_k, routed_experts  # noqa: E402
from test_mellum import dense_attention  # noqa: E402
from test_nemotron_h import (_pass_text, _trainer,  # noqa: E402
                             assert_the_forward_sweep_runs_once, f32,
                             highest_precision,  # noqa: F401
                             program_flags_restored, rel)  # noqa: F401


def cfg_of(pattern: str = "KKKMK", **over) -> dict:
    """The cell's configuration with a toy stack of ``pattern``."""
    return dict(family_toy_cell()["config"], **toy_config(pattern, **over))


def program(cfg, dtype=jnp.float32):
    return KimiLinear(cfg, compute_dtype=dtype)


def _perturbed_norms(params):
    """Norms away from 1, so that a norm left out would show."""
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a + 0.1 * jax.random.normal(
            jax.random.PRNGKey(len(jax.tree_util.keystr(path))), a.shape)
        if "norm" in jax.tree_util.keystr(path) else a, params)


def _leaves_agree(got, want, limit):
    """Every leaf's gradient within ``limit``; the router's correction
    bias, which only chooses, has none on either side."""
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(want)[0],
                            jax.tree.leaves(got)):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:
            assert not np.asarray(a).any() and not np.asarray(b).any()
            continue
        assert float(jnp.linalg.norm(a)) > 0, name
        assert rel(b, a) < limit, name


# ---- values of a width of their own in the blockwise attention ----------------

#: (T, block, query heads, key/value heads, D, Dv, window)
VALUE_CASES = {
    "narrower-values": (48, 16, 4, 4, 24, 16, None),
    "wider-grouped-values": (48, 16, 8, 2, 8, 12, None),
    "a-window": (64, 16, 4, 2, 16, 24, 21),
    "latent-shape-one-block": (32, 512, 2, 2, 192, 128, None),
}


@f32
@pytest.mark.parametrize("case", list(VALUE_CASES))
def test_attention_takes_values_of_a_width_of_their_own(case):
    t, block, h, kv, d, dv, window = VALUE_CASES[case]
    ks = jax.random.split(jax.random.PRNGKey(t + d + dv), 4)
    q = jax.random.normal(ks[0], (2, t, h, d))
    k = jax.random.normal(ks[1], (2, t, kv, d))
    v = jax.random.normal(ks[2], (2, t, kv, dv))
    cot = jax.random.normal(ks[3], (2, t, h, dv))

    def ours(q, k, v):
        return causal_gqa_attention(q, k, v, block=block, window=window,
                                    mm_dtype=jnp.float32)

    def dense(q, k, v):
        return dense_attention(q, k, v, window)

    out = ours(q, k, v)
    assert out.shape == (2, t, h, dv)
    assert rel(out, dense(q, k, v)) < 1e-5
    got = jax.grad(lambda *a: jnp.sum(ours(*a) * cot), argnums=(0, 1, 2))(
        q, k, v)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * cot), argnums=(0, 1, 2))(
        q, k, v)
    for name, g, r in zip("qkv", got, want):
        assert g.shape == r.shape
        assert rel(g, r) < 1e-5, name


def _square_blocks(q, k, v, block):
    """``_blocks`` as it was before values had a width of their own: k
    and v shaped by the queries' D."""
    bsz, t, kv, g, d = q.shape
    nb = t // block
    qb = q.reshape(bsz, nb, block, kv, g, d).transpose(1, 0, 3, 4, 2, 5)
    qb = qb.reshape(nb, bsz, kv, g * block, d)
    kb = k.reshape(bsz, nb, block, kv, d).transpose(1, 0, 3, 2, 4)
    vb = v.reshape(bsz, nb, block, kv, d).transpose(1, 0, 3, 2, 4)
    return qb, kb, vb


def _square_unblock_q(ob, shape, block):
    """``_unblock_q`` as it was: the output shaped like the queries."""
    bsz, t, kv, g, d = shape
    nb = t // block
    ob = ob.reshape(nb, bsz, kv, g, block, d).transpose(1, 0, 4, 2, 3, 5)
    return ob.reshape(bsz, t, kv, g, d)


@pytest.mark.parametrize("window", [None, 21])
def test_square_heads_are_the_program_they_were(window):
    """Where Dv == D (cells 2-5) the op is what it was: a fresh copy of
    the module with the square-head helpers put back traces to the same
    loss-and-gradient program, equation for equation."""
    old = types.ModuleType("square_heads")
    exec(compile(inspect.getsource(causal_attention), "square_heads",
                 "exec"), old.__dict__)
    old._blocks, old._unblock_q = _square_blocks, _square_unblock_q
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, 64, 8, 16))
    k = jax.random.normal(ks[1], (2, 64, 2, 16))
    v = jax.random.normal(ks[2], (2, 64, 2, 16))

    def text(mod):
        def f(q, k, v):
            return jnp.sum(mod.causal_gqa_attention(
                q, k, v, block=16, window=window) ** 2)
        return str(jax.make_jaxpr(jax.value_and_grad(
            f, argnums=(0, 1, 2)))(q, k, v))

    new = text(causal_attention)
    assert text(old) == new
    # and with values of another width the square helpers cannot run
    with pytest.raises(TypeError):
        old.causal_gqa_attention(q, k, v[..., :8], block=16)


# ---- the mixers at their published widths -----------------------------------

def _published(pattern, **over):
    return cfg_of(
        pattern, hidden_size=2304, num_attention_heads=32,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        kv_lora_rank=512, linear_attn_num_heads=32, linear_attn_head_dim=128,
        linear_attn_config=dict(cfg_of(pattern)["linear_attn_config"],
                                num_heads=32, head_dim=128), **over)


@f32
@pytest.mark.parametrize("kind,t", [("K", 80), ("M", 48)])
def test_a_mixer_at_the_published_widths(monkeypatch, kind, t):
    """A KDA mixer (32 heads of 128, over two chunks of 64: the rule's
    kernels) and a latent attention mixer (32 heads of 128 + 64 over
    values of 128, rank 512) at hidden 2304 against the reference's,
    values and every gradient."""
    # a kernel cell a head: the interpreter compiles a cell's body, in
    # seconds a head (tests/test_kda_kernel.py covers cells of several)
    monkeypatch.setattr(kda, "HEADS", 1)
    cfg = _published(kind)
    z = ref.dims(cfg)
    lay = _perturbed_norms(ref.init(jax.random.PRNGKey(4), cfg))["layers"][0]
    lay = {k: v for k, v in lay.items() if k not in
           ("w1", "w2", "w3", "ffn_norm")}
    x = jax.random.normal(jax.random.PRNGKey(5), (1, t, 2304))
    cot = jax.random.normal(jax.random.PRNGKey(6), (1, t, 2304))
    model = program(cfg)
    mixer = model._kda if kind == "K" else model._mla

    def ours(lay, x):
        return mixer(lay, x) - x                   # less the residual

    def theirs(lay, x):
        u = ref_nemotron.rms_norm(x, lay["attn_norm"], z["eps"])
        return (ref.kda_mixer if kind == "K" else ref.mla)(lay, u, z, None)

    assert rel(jax.jit(ours)(lay, x), jax.jit(theirs)(lay, x)) < 1e-5
    got = jax.jit(jax.grad(lambda lay, x: jnp.sum(ours(lay, x) * cot),
                           argnums=(0, 1)))(lay, x)
    want = jax.jit(jax.grad(lambda lay, x: jnp.sum(theirs(lay, x) * cot),
                            argnums=(0, 1)))(lay, x)
    # the log-uniform dt_bias starts deep in softplus' flat tail on some
    # channels: their gradient is float32's last digits
    _leaves_agree(got, want, 1e-4)


# ---- the whole stack: forward, loss, gradients -----------------------------

@f32
@pytest.mark.parametrize("pattern,t,chunk", [("KMK", 24, 64), ("MK", 40, 16)])
def test_stack_matches_the_reference(monkeypatch, pattern, t, chunk):
    """The stack against the reference; a chunk of 16 splits a KDA layer
    into several chunks at the toy's lengths."""
    monkeypatch.setattr(kimi_linear, "KDA_CHUNK", chunk)
    cfg = cfg_of(pattern)
    params = _perturbed_norms(ref.init(jax.random.PRNGKey(3), cfg))
    emb = jax.random.normal(jax.random.PRNGKey(1), (2, t, 64))
    labels = jax.random.randint(jax.random.PRNGKey(2), (2, t), 0, 96)
    model = program(cfg)
    assert model.kinds == pattern
    assert jax.tree.map(jnp.shape, model.init(jax.random.PRNGKey(0))) \
        == jax.tree.map(jnp.shape, params)
    want, (gp_r, ge_r) = jax.jit(jax.value_and_grad(
        lambda p, e: ref.loss(p, e, labels, cfg), argnums=(0, 1)))(params, emb)
    (got, scalars), (gp, ge) = jax.jit(jax.value_and_grad(
        lambda p, e: model.loss(p, e, labels, jnp.ones((2, t), bool)),
        argnums=(0, 1), has_aux=True))(params, emb)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    assert rel(jax.jit(model.logits)(params, emb), jax.jit(
        lambda p, e: ref.forward(p, e, cfg))(params, emb)) < 1e-5
    assert rel(ge, ge_r) < 1e-4
    _leaves_agree(gp, gp_r, 2e-4)
    assert set(scalars) == set(model.step_scalars) \
        == set(MellumMoe.step_scalars)
    _, loads, _ = jax.jit(model.hidden)(params, emb)
    expert_layers = len(pattern) - 1                     # layer 0 is dense
    assert loads.shape == (expert_layers, 4)
    assert float(scalars["moe_choices_held"]) == float(jnp.sum(loads))
    blk = math.gcd(2 * t, 512)
    assert float(scalars["moe_rows_computed"]) == float(
        jnp.sum(-(-loads // blk) * blk))
    assert float(scalars["moe_choices"]) == 2 * t * 3 * expert_layers
    assert 0 < float(scalars["moe_choices_held"]) \
        <= float(scalars["moe_choices"])


def test_layer_kinds_come_from_linear_attn_config():
    cfg = cfg_of("KKKMK")
    assert kimi_linear.layer_kinds(cfg) == "KKKMK"
    # the published lists over the first five layers give the cut
    lin = dict(cfg["linear_attn_config"],
               kda_layers=[1, 2, 3, 5, 6, 7, 9], full_attn_layers=[4, 8, 27])
    assert kimi_linear.layer_kinds(
        dict(cfg, linear_attn_config=lin)) == "KKKMK"
    for bad, what in (([1, 2, 3, 5], [4, 5]), ([1, 2, 3], [4])):
        lin = dict(cfg["linear_attn_config"], kda_layers=bad,
                   full_attn_layers=what)
        with pytest.raises(ValueError, match="both|neither"):
            kimi_linear.layer_kinds(dict(cfg, linear_attn_config=lin))
    with pytest.raises(ValueError, match="no rotary part"):
        program(dict(cfg, mla_use_nope=False))
    with pytest.raises(ValueError, match="no query latent"):
        program(dict(cfg, q_lora_rank=64))
    with pytest.raises(ValueError, match="is not of K, M"):
        ref.init(jax.random.PRNGKey(0), dict(cfg, layer_pattern="KX"))


def test_the_latent_layers_forward_sweep_runs_once_a_step(monkeypatch):
    cfg = cfg_of("MM")
    assert_the_forward_sweep_runs_once(
        monkeypatch, kimi_linear, program(cfg),
        ref.init(jax.random.PRNGKey(3), cfg), attn_layers=2)


# ---- the expert layer: a chip's share, and the shared expert once -------------

@f32
def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Four chips hold four of the sixteen experts each: what the shares
    ``held = (4 i, 4 i + 4)`` give, summed, with the shared expert (which
    every chip computes alike) counted once, is the whole feed-forward as
    the reference computes it uncut."""
    cfg = cfg_of("KK", num_experts=16)
    lay = ref.init(jax.random.PRNGKey(5), cfg)["layers"][1]
    u = jax.random.normal(jax.random.PRNGKey(6), (2, 12, 64))
    z = ref.dims(cfg)
    want = ref.moe(lay, u, z, None, (0, 16))
    flat = u.reshape(-1, 64)
    idx, w = route_top_k(flat, lay["router"], lay["router_bias"], 3,
                         z["scale"])
    ridx, rw = ref.route(lay, u, z)
    assert np.array_equal(np.asarray(idx), np.asarray(ridx).reshape(-1, 3))
    assert rel(w, rw.reshape(-1, 3)) < 1e-6
    # renormalised over the chosen three, times the scaling factor
    assert np.allclose(np.asarray(jnp.sum(w, -1)), z["scale"], rtol=1e-6)
    shared = ref.swiglu_mlp(u, lay["shared_gate"], lay["shared_up"],
                            lay["shared_down"], None).reshape(-1, 64)
    total, choices = shared, 0
    for lo in range(0, 16, 4):
        part, stats = routed_experts(
            flat, idx, w, lay["up"][lo:lo + 4], lay["down"][lo:lo + 4],
            (lo, lo + 4), mm_dtype=jnp.float32, gate=lay["gate"][lo:lo + 4])
        total = total + part
        choices += int(stats["choices"])
    assert choices == flat.shape[0] * 3       # every choice fell somewhere
    assert rel(total, want.reshape(-1, 64)) < 1e-5
    # and through the model: a share's layer is the reference's same share
    for lo in (0, 12):
        share = dict(cfg, num_experts=4, first_expert_held=lo)
        part = {**lay, **{k: lay[k][lo:lo + 4]
                          for k in ("gate", "up", "down")}}
        x, load, _ = program(share)._moe(part, u)
        assert rel(x - u, ref.moe(
            part, ref_nemotron.rms_norm(u, part["ffn_norm"], z["eps"]), z,
            None, (lo, lo + 4))) < 1e-4
        assert load.shape == (4,)


# ---- one pass through Trainer + PassPreloader -------------------------------

def toy_cell():
    cell = family_toy_cell()
    cell["config"]["matmul_dtype"] = "float32"
    return cell


@f32
def test_one_pass_through_the_trainer_equals_the_reference_step_by_step():
    from benchmarks.families import lm_kimi_linear as family
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
    assert out["moe_choices"] == 192 * 3 * 4
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
    missing = {s for s in trace.KDA_SEQ_STEP_SCOPES
               if not re.search(re.escape(s) + r"(?![A-Za-z0-9_])", text)}
    assert not missing, missing
    # neither Mamba's nor LFM2's mixer, no window, no exit gate
    for s in (trace.SCOPE_SSM_SCAN, trace.SCOPE_CONV_MIX,
              trace.SCOPE_ATTN_WINDOW, trace.SCOPE_EXIT_GATE):
        assert s not in text
    # a sublayer is one jax.checkpoint: the reducers count its backward
    # ops under the scope itself
    for s in (trace.SCOPE_KDA_SCAN, trace.SCOPE_KDA_PROJ, trace.SCOPE_ATTN,
              trace.SCOPE_MOE_EXPERTS):
        assert f"checkpoint/{s}/" in text, s
