"""The second sequence model (models/lfm2.py: gated short convolutions,
rotary grouped-query attention with normed queries and keys, a leading
dense SwiGLU layer, gated routed experts through
parallel/moe.routed_experts) against the plain reference the benchmark
keeps (benchmarks/reference/models/lfm2.py, benchmarks/reference/lm.py),
at tiny widths on the CPU in float32; and the first one's pass program,
which shares the expert loops, the attention, the head and the conv with
it, against the text it lowered to before the second came."""

import hashlib
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
from benchmarks.reference.models import lfm2 as ref  # noqa: E402
from benchmarks.reference.models import nemotron_h as ref_nemotron  # noqa: E402
from paddlebox_tpu.models import Lfm2Moe, NemotronH  # noqa: E402
from paddlebox_tpu.obs import trace  # noqa: E402
from paddlebox_tpu.ops.causal_attention import (  # noqa: E402
    causal_gqa_attention, rotary_embedding)
from paddlebox_tpu.ops.short_conv import gated_short_conv  # noqa: E402
from paddlebox_tpu.parallel.moe import route_top_k, routed_experts  # noqa: E402
from test_nemotron_h import (_pass_text,  # noqa: E402
                             assert_the_forward_sweep_runs_once,
                             _toy_cell as nemotron_toy_cell, _trainer, f32,
                             highest_precision,  # noqa: F401
                             program_flags_restored, rel)  # noqa: F401

KINDS = {"c": "conv", "f": "full_attention"}


def cfg_of(pattern: str, dense: int, **over) -> dict:
    """The toy configuration of a stack: ``pattern`` of ``c`` and ``f``,
    the first ``dense`` layers with the dense feed-forward."""
    cfg = dict(
        layer_types=[KINDS[c] for c in pattern],
        num_hidden_layers=len(pattern), num_dense_layers=dense,
        hidden_size=64, vocab_size=96, norm_eps=1e-5, conv_L_cache=3,
        num_attention_heads=4, num_key_value_heads=2,
        rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
        rope_theta=1000000, intermediate_size=80, moe_intermediate_size=48,
        router_outputs=16, num_experts=4, first_expert_held=0,
        num_experts_per_tok=3, routed_scaling_factor=1.0)
    cfg.update(over)
    return cfg


def program(cfg, dtype=jnp.float32):
    return Lfm2Moe(cfg, compute_dtype=dtype)


# ---- the whole stack: forward, loss, gradients -----------------------------

@f32
@pytest.mark.parametrize("pattern,dense,t", [
    ("c", 0, 20), ("c", 1, 20), ("f", 0, 16), ("f", 1, 20),
    ("cfcc", 0, 20), ("cfcc", 1, 20)])
def test_stack_matches_the_reference(pattern, dense, t):
    cfg = cfg_of(pattern, dense)
    params = ref.init(jax.random.PRNGKey(3), cfg)
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
        name = jax.tree_util.keystr(path)
        if "expert_bias" in name:
            # it chooses only: no gradient on either side
            assert not np.asarray(a).any() and not np.asarray(b).any()
            continue
        assert rel(b, a) < 2e-4, name
    assert set(scalars) == set(NemotronH.step_scalars)
    _, loads, _ = model.hidden(params, emb)
    assert loads.shape == (len(pattern) - dense, 4)
    assert float(scalars["moe_choices_held"]) == float(jnp.sum(loads))
    blk = math.gcd(2 * t, 512)
    assert float(scalars["moe_rows_computed"]) == float(
        jnp.sum(-(-loads // blk) * blk))


# ---- the gated short convolution ---------------------------------------------

@f32
@pytest.mark.parametrize("t,k", [(12, 3), (2, 3), (9, 4), (1, 3)])
def test_gated_conv_matches_a_loop_over_positions(t, k):
    """``C * conv(B * v)`` against the recurrence a decoder would run: a
    window of the last k gated inputs, one position at a time."""
    ks = jax.random.split(jax.random.PRNGKey(t), 3)
    bcv = jax.random.normal(ks[0], (2, t, 3 * 8))
    w = jax.random.normal(ks[1], (k, 8))

    def stepwise(bcv, w):
        b, c, v = jnp.split(bcv, 3, axis=-1)
        window = jnp.zeros((2, k, 8))
        out = []
        for i in range(t):
            window = jnp.concatenate(
                [window[:, 1:], (b[:, i] * v[:, i])[:, None]], axis=1)
            out.append(c[:, i] * jnp.sum(window * w[None], axis=1))
        return jnp.stack(out, axis=1)

    assert rel(gated_short_conv(bcv, w), stepwise(bcv, w)) < 1e-6
    cot = jax.random.normal(ks[2], (2, t, 8))
    got = jax.grad(lambda *a: jnp.sum(gated_short_conv(*a) * cot),
                   argnums=(0, 1))(bcv, w)
    want = jax.grad(lambda *a: jnp.sum(stepwise(*a) * cot),
                    argnums=(0, 1))(bcv, w)
    for g, r in zip(got, want):
        assert rel(g, r) < 1e-5


# ---- attention: heads of 64, 32 / 8, normed and rotated ----------------------

@f32
def test_rotary_attention_at_the_published_head_shape():
    """Heads of 64, 32 query heads on 8 key/value heads at hidden 2048
    (the published shape): q/k norms + rotary + the blockwise kernel
    against the reference's full softmax, values and gradients."""
    t, d = 48, 2048
    cfg = cfg_of("f", 0, hidden_size=d, num_attention_heads=32,
                 num_key_value_heads=8)
    z = ref.dims(cfg)
    assert z["hd"] == 64
    ks = jax.random.split(jax.random.PRNGKey(4), 8)
    lay = {"q": jax.random.normal(ks[0], (d, 32 * 64)) * 0.02,
           "k": jax.random.normal(ks[1], (d, 8 * 64)) * 0.02,
           "v": jax.random.normal(ks[2], (d, 8 * 64)) * 0.02,
           "o": jax.random.normal(ks[3], (32 * 64, d)) * 0.02,
           "q_norm": 1 + 0.1 * jax.random.normal(ks[4], (64,)),
           "k_norm": 1 + 0.1 * jax.random.normal(ks[5], (64,)),
           "operator_norm": 1 + 0.1 * jax.random.normal(ks[6], (d,))}
    x = jax.random.normal(ks[6], (2, t, d))
    model = program(cfg)

    def ours(lay, x):
        return model._attention(lay, x) - x        # less the residual

    def theirs(lay, x):
        return ref.attention(lay, ref_nemotron.rms_norm(
            x, lay["operator_norm"], z["eps"]), z, None)

    assert rel(ours(lay, x), theirs(lay, x)) < 1e-5
    cot = jax.random.normal(ks[7], (2, t, d))
    got = jax.grad(lambda lay, x: jnp.sum(ours(lay, x) * cot),
                   argnums=(0, 1))(lay, x)
    want = jax.grad(lambda lay, x: jnp.sum(theirs(lay, x) * cot),
                    argnums=(0, 1))(lay, x)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(want)[0],
                            jax.tree.leaves(got)):
        assert rel(b, a) < 1e-4, jax.tree_util.keystr(path)


@f32
def test_rotary_turns_pairs_by_the_positions_angle():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 2, 8))
    y = rotary_embedding(x, 100.0)
    assert rel(y, ref.rotary(x, 100.0)) < 1e-6
    assert rel(y[:, 0], x[:, 0]) < 1e-7           # position 0: no turn
    # the pair (x_1, x_5) of position 3 turned by 3 * 100^(-2/8)
    ang = 3 * 100.0 ** (-2 / 8)
    a, b = float(x[0, 3, 1, 1]), float(x[0, 3, 1, 5])
    assert float(y[0, 3, 1, 1]) == pytest.approx(
        a * math.cos(ang) - b * math.sin(ang), abs=1e-5)
    assert float(y[0, 3, 1, 5]) == pytest.approx(
        b * math.cos(ang) + a * math.sin(ang), abs=1e-5)
    # a rotation keeps each head's norm, and q . k reads the distance
    assert rel(jnp.linalg.norm(y, axis=-1), jnp.linalg.norm(x, axis=-1)) < 1e-6
    q = jnp.broadcast_to(x[:, :1], x.shape)
    rq = rotary_embedding(q, 100.0)
    near = jnp.sum(rq[0, 1] * rq[0, 2]), jnp.sum(rq[0, 3] * rq[0, 4])
    assert float(near[0]) == pytest.approx(float(near[1]), rel=1e-4)
    o = causal_gqa_attention(rq, rq[:, :, :1], x[:, :, :1],
                             mm_dtype=jnp.float32)
    assert o.shape == x.shape


# ---- the gated expert layer ---------------------------------------------------

def _expert_layer(key, held=16):
    cfg = cfg_of("c", 0, num_experts=held)
    lay = ref.init(key, cfg)["layers"][0]
    u = jax.random.normal(jax.random.fold_in(key, 7), (2, 12, 64))
    return cfg, lay, u


def _held_reference(x, idx, w, gate, up, down, held, precision):
    """The dense masked form of one share: every held expert computed for
    every token, weighted by what the token's choices gave it."""
    y = 0.0
    for j, e in enumerate(range(*held)):
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), -1)
        y = y + w_e[:, None] * ref.swiglu_mlp(x[None], gate[j], up[j],
                                              down[j], precision)[0]
    return y


def _expert_case(case):
    """(x, idx, w, gate, up, down, held, block) of a named routing."""
    n, held, same = {"routed-evenly": (24, (4, 8), False),
                     "skewed": (24, (0, 4), "skew"),
                     "every-token-the-same": (24, (0, 4), True),
                     "blocks-of-2": (14, (0, 4), True),
                     "no-choice-held": (24, (12, 16), True),
                     "bfloat16-operands": (24, (0, 4), True)}[case]
    _, lay, u = _expert_layer(jax.random.PRNGKey(8))
    flat = u.reshape(-1, 64)[:n]
    bias = lay["expert_bias"]
    if same == "skew":
        # expert 0 every token's first choice, the others as they fall
        bias = bias.at[0].set(10.0)
    elif same:
        bias = jnp.zeros(16).at[:3].set(10.0)
    idx, w = route_top_k(flat, lay["router"], bias, 3, 1.0, sum_eps=1e-6)
    lo, hi = held
    return (flat, idx, w, lay["gate"][lo:hi], lay["up"][lo:hi],
            lay["down"][lo:hi], held, {24: 8, 14: 2}[n])


EXPERT_CASES = ["routed-evenly", "skewed", "every-token-the-same",
                "blocks-of-2", "no-choice-held", "bfloat16-operands"]


@f32
@pytest.mark.parametrize("case", EXPERT_CASES)
def test_gated_experts_value_and_gradients_equal_the_dense_form(case):
    x, idx, w, gate, up, down, held, _ = _expert_case(case)
    bf16 = case == "bfloat16-operands"
    cot = jax.random.normal(jax.random.PRNGKey(11), x.shape)
    dtype = jnp.bfloat16 if bf16 else jnp.float32

    def ours(x, w, gate, up, down):
        return routed_experts(x, idx, w, up, down, held, mm_dtype=dtype,
                              gate=gate)[0]

    def dense(x, w, gate, up, down):
        return _held_reference(x, idx, w, gate, up, down, held,
                               "bfloat16" if bf16 else None)

    args = (x, w, gate, up, down)
    got = jax.grad(lambda *a: jnp.sum(ours(*a) * cot),
                   argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * cot),
                    argnums=(0, 1, 2, 3, 4))(*args)
    if case == "no-choice-held":
        y, stats = routed_experts(x, idx, w, up, down, held,
                                  mm_dtype=jnp.float32, gate=gate)
        assert int(stats["choices"]) == 0 and not np.asarray(y).any()
        for g, r in zip(got, want):
            assert not np.asarray(g).any() and not np.asarray(r).any()
        return
    assert rel(ours(*args), dense(*args)) < (1e-2 if bf16 else 1e-5)
    # bfloat16 operands: the reference rounds the weighted cotangent, the
    # loop rounds the cotangent and weighs the product: one rounding
    # apart, as for the ungated expert (tests/test_nemotron_h.py)
    for name, g, r in zip(("x", "w", "gate", "up", "down"), got, want):
        assert float(jnp.linalg.norm(r)) > 0, name
        limit = 1e-4 if not bf16 else 1e-5 if name == "down" else 1e-2
        assert rel(g, r) < limit, name


@pytest.mark.parametrize("case", EXPERT_CASES)
def test_gated_rows_computed_follow_the_choices(case):
    x, idx, w, gate, up, down, held, blk = _expert_case(case)
    _, stats = routed_experts(x, idx, w, up, down, held,
                              mm_dtype=jnp.float32, gate=gate)
    load = np.asarray(stats["load"])
    want = np.asarray(jnp.sum((idx[:, :, None] == jnp.arange(*held)),
                              axis=(0, 1)))
    assert list(load) == list(want)
    assert int(stats["choices"]) == load.sum()
    assert int(stats["rows"]) == sum(-(-int(c) // blk) * blk for c in load)
    if case == "no-choice-held":
        assert int(stats["rows"]) == 0
    elif case == "skewed":
        assert load[0] == x.shape[0] and load.sum() < 3 * x.shape[0]
    elif case != "routed-evenly":
        assert list(load) == [x.shape[0]] * 3 + [0]


@f32
def test_the_eight_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Eight chips hold eight of the sixty-four experts each: what the
    shares ``held = (8 i, 8 i + 8)`` give, summed, is the whole expert
    feed-forward as the reference computes it uncut (there is no shared
    expert to count once)."""
    cfg = cfg_of("c", 0, router_outputs=64, num_experts=64,
                 num_experts_per_tok=4)
    lay = ref.init(jax.random.PRNGKey(5), cfg)["layers"][0]
    u = jax.random.normal(jax.random.PRNGKey(6), (2, 12, 64))
    z = ref.dims(cfg)
    want = ref.moe(lay, u, z, None, (0, 64))
    flat = u.reshape(-1, 64)
    idx, w = route_top_k(flat, lay["router"], lay["expert_bias"], 4, 1.0,
                         sum_eps=1e-6)
    ridx, rw = ref.route(lay, u, z)
    assert np.array_equal(np.asarray(idx), np.asarray(ridx).reshape(-1, 4))
    assert rel(w, rw.reshape(-1, 4)) < 1e-6
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
    assert choices == flat.shape[0] * 4       # every choice fell somewhere
    assert rel(total, want.reshape(-1, 64)) < 1e-5


# ---- one pass through Trainer + PassPreloader -------------------------------

def toy_cell():
    from benchmarks.tests.test_family_lm_lfm2 import toy_cell as family_toy
    cell = family_toy()
    cell["config"]["matmul_dtype"] = "float32"
    return cell


@f32
def test_one_pass_through_the_trainer_equals_the_reference_step_by_step():
    from benchmarks.families import lm_lfm2 as family
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
    fin = [s for s in trace.recent_spans() if s.name == "pass.finish"][-1]
    for k in ("tokens", "documents", "moe_choices_held", "moe_rows_computed",
              "moe_expert_load_max", "moe_expert_load_mean"):
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
    missing = {s for s in trace.CONV_SEQ_STEP_SCOPES
               if not re.search(re.escape(s) + r"(?![A-Za-z0-9_])", text)}
    assert not missing, missing
    # no Mamba mixer and no shared expert in this model
    for s in (trace.SCOPE_SSM_SCAN, trace.SCOPE_MOE_SHARED):
        assert s not in text
    # a sublayer is one jax.checkpoint: the reducers count its backward
    # ops under the scope itself (PERF.md section 7)
    for s in (trace.SCOPE_CONV_MIX, trace.SCOPE_MOE_EXPERTS,
              trace.SCOPE_ATTN, trace.SCOPE_MLP):
        assert f"checkpoint/{s}/" in text, s


@pytest.mark.parametrize("pattern,dense", [("f", 0), ("fcf", 1)])
def test_the_attention_operators_forward_sweep_runs_once_a_step(
        monkeypatch, pattern, dense):
    """The conv operator's checkpoint is handed the same policy and has
    nothing of that name to keep."""
    from paddlebox_tpu.models import lfm2
    cfg = cfg_of(pattern, dense)
    assert_the_forward_sweep_runs_once(
        monkeypatch, lfm2, program(cfg), ref.init(jax.random.PRNGKey(3), cfg),
        attn_layers=pattern.count("f"))


# ---- the first sequence model's pass program is what it was -------------------

#: NemotronH's toy pass program (bfloat16 operands, the cell's own path)
#: as lowered at the parent commit 4f121a6, before ``routed_experts`` took
#: a gate, the conv became ``ops/short_conv.py``'s and the head + loss
#: ``models/lm_parts.py``'s: the relu^2 body, the un-rotated attention
#: call and the conv + silu lower as before. The op counts hold under any
#: jax, the sha256 under the jax it was recorded with
#: (tests/test_nemotron_h.py pins DeepFM's program the same way). PR 36
#: re-recorded it: the compact wire's decode and dedup_rows changed (the
#: chunk map's gather and two scatters went, two sorts and cmap_select's
#: product came); the model's ops are the parent's. PR 41 re-recorded it:
#: the one attention layer's checkpoint keeps the forward block loops'
#: two results, so the recomputed forward sweep went: its two ``while``
#: ops (65 before) and its two products (277 ``dot_general`` before);
#: no other count moved. Re-recorded when the expert loops took
#: ``parallel/moe._token_rows``' view: their gathers and scatter-adds take
#: [N, 1, 128] token rows where they took [N, 64] (the toy's 64 zero-padded
#: to one lane-row), with the pads, reshapes and slices around them; every
#: counted op is the parent's, and with ``_token_rows`` made the identity
#: the program lowers to the parent's text (sha256 b99aa576...).
NEMOTRON_PASS_JAX = "0.9.0"
NEMOTRON_PASS_TEXT = \
    "07d2a6427953c2e083dace9e5e2d6fc5e0d51559c4897abaf0412250112a7cbb"
NEMOTRON_PASS_OPS = {"while": 63, "gather": 170, "sort": 5, "scatter": 112,
                     "dot_general": 275, "custom_call": 12}


def test_nemotron_pass_program_lowers_to_the_parents_text():
    import collections
    cell = nemotron_toy_cell()
    text = _pass_text(cell, ref_nemotron, NemotronH(cell["config"]), False)
    ops = collections.Counter(re.findall(
        r"stablehlo\.(while|gather|scatter|sort|dot_general|custom_call)\b",
        text))
    assert dict(ops) == NEMOTRON_PASS_OPS
    if jax.__version__ == NEMOTRON_PASS_JAX:
        assert hashlib.sha256(text.encode()).hexdigest() == \
            NEMOTRON_PASS_TEXT
