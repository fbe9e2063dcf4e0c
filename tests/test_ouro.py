"""The looped sequence model (models/ouro.py: one stack of attention +
dense feed-forward layers with norms before and after each sublayer, run
``total_ut_steps`` times on the same weights by one ``jax.lax.scan``, the
head and an exit gate read after every run, the expected-exit loss with
its entropy term) against the plain reference the benchmark keeps
(benchmarks/reference/models/ouro.py, benchmarks/reference/lm.py), at tiny
widths on the CPU; and what it forced in ``models/lm_parts.py``: the head
read for several hidden states in one slab loop, attention without head
norms."""

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
from benchmarks.reference.models import ouro as ref  # noqa: E402
from benchmarks.tests.test_family_lm_ouro import (  # noqa: E402
    toy_cell as family_toy_cell, toy_config)
from paddlebox_tpu.models import OuroLoop, lm_parts  # noqa: E402
from paddlebox_tpu.models.ouro import exit_distribution  # noqa: E402
from paddlebox_tpu.obs import trace  # noqa: E402
from test_nemotron_h import (_pass_text, _trainer,  # noqa: E402
                             assert_the_forward_sweep_runs_once, f32,
                             highest_precision,  # noqa: F401
                             program_flags_restored, rel)  # noqa: F401


def cfg_of(layers: int = 2, **over) -> dict:
    return dict(toy_config(layers), rms_norm_eps=1e-6, exit_entropy_beta=0.1,
                **over)


def program(cfg, dtype=jnp.float32):
    return OuroLoop(cfg, compute_dtype=dtype)


def seeded(cfg, t=24, key=3):
    """(weights with norms and gate away from their start, token vectors,
    labels): a norm or a gate left out would show."""
    params = ref.init(jax.random.PRNGKey(key), cfg)

    def away(path, a):
        name = jax.tree_util.keystr(path)
        if "norm" not in name and "exit" not in name:
            return a
        return a + 0.1 * jax.random.normal(
            jax.random.PRNGKey(len(name)), a.shape)
    params = jax.tree_util.tree_map_with_path(away, params)
    emb = jax.random.normal(jax.random.PRNGKey(1), (2, t, 64)) * 0.02
    labels = jax.random.randint(jax.random.PRNGKey(2), (2, t), 0, 96)
    return params, emb, labels


# ---- the head read for several hidden states ---------------------------------

def accepted_head_loss(x, norm_weight, head, labels, valid, eps, dtype):
    """``lm_parts.head_loss`` as it was accepted before the slab loop
    yielded per-position values (commit c11fff2), copied whole."""
    _scope = jax.named_scope
    n = labels.size
    rows = math.gcd(n, lm_parts.HEAD_ROWS)
    x = x.reshape(n // rows, rows, x.shape[-1])
    lab = labels.reshape(n // rows, rows)
    ok = valid.reshape(n // rows, rows).astype(jnp.float32)

    @jax.checkpoint
    def some_rows(total, xs):
        x_r, lab_r, ok_r = xs
        with _scope(trace.SCOPE_HEAD):
            z = lm_parts.matmul(lm_parts.rms_norm(x_r, norm_weight, eps),
                                head, dtype)
        with _scope(trace.SCOPE_LOSS):
            logp = jax.nn.log_softmax(z, axis=-1)
            nll = -jnp.take_along_axis(logp, lab_r[:, None], -1)[:, 0]
            return total + jnp.sum(nll * ok_r), None

    total, _ = jax.lax.scan(some_rows, jnp.zeros((), jnp.float32),
                            (x, lab, ok))
    with _scope(trace.SCOPE_LOSS):
        return total / jnp.maximum(jnp.sum(ok), 1.0)


def _head_case(shape, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    s, t = shape
    return dict(
        x=jax.random.normal(ks[0], (s, t, 16)),
        norm=1 + 0.1 * jax.random.normal(ks[1], (16,)),
        head=jax.random.normal(ks[2], (16, 40)) * 0.3,
        labels=jax.random.randint(ks[3], (s, t), 0, 40),
        valid=jax.random.uniform(ks[4], (s, t)) < 0.8, dtype=dtype)


#: three slabs of HEAD_ROWS, one slab, a step smaller than a slab
HEAD_SHAPES = {"three-slabs": (3, 4096), "one-slab": (2, 2048),
               "under-a-slab": (2, 24)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("shape", list(HEAD_SHAPES))
def test_head_loss_of_one_state_is_the_accepted_function_bit_for_bit(
        shape, dtype):
    """Cells 2, 3 and 4 call ``head_loss`` with one hidden state: the
    value and every gradient are what the accepted body gave, to the
    bit, jitted as a step jits them."""
    c = _head_case(HEAD_SHAPES[shape], dtype)

    def run(fn):
        return jax.jit(jax.value_and_grad(
            lambda x, norm, head: fn(x, norm, head, c["labels"], c["valid"],
                                     1e-5, dtype), argnums=(0, 1, 2)))(
            c["x"], c["norm"], c["head"])

    (want, g_want), (got, g_got) = run(accepted_head_loss), \
        run(lm_parts.head_loss)
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
    for a, b in zip(g_got, g_want):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert float(want) > 0 and all(float(jnp.linalg.norm(g)) > 0
                                   for g in g_want)


@pytest.mark.parametrize("shape", list(HEAD_SHAPES))
def test_head_nll_of_four_states_is_four_separate_calls(shape):
    """Each exit's per-position values through the one slab loop equal
    the same state's handed in alone; and they are the cross-entropy."""
    c = _head_case(HEAD_SHAPES[shape], jnp.float32, seed=1)
    s, t = HEAD_SHAPES[shape]
    xs = jnp.stack([c["x"] * (1 + 0.3 * r) + 0.1 * r for r in range(4)])
    together = lm_parts.head_nll(xs, c["norm"], c["head"], c["labels"],
                                 1e-5, jnp.float32)
    rows = math.gcd(s * t, lm_parts.HEAD_ROWS)
    assert together.shape == (4, s * t // rows, rows)
    for r in range(4):
        alone = lm_parts.head_nll(xs[r:r + 1], c["norm"], c["head"],
                                  c["labels"], 1e-5, jnp.float32)
        assert np.array_equal(np.asarray(together[r]), np.asarray(alone[0]))
    with jax.default_matmul_precision("highest"):
        z = lm_parts.rms_norm(xs, c["norm"], 1e-5) @ c["head"]
        want = -jnp.take_along_axis(jax.nn.log_softmax(z, -1),
                                    c["labels"][None, ..., None], -1)[..., 0]
        got = lm_parts.head_nll(xs, c["norm"], c["head"], c["labels"],
                                1e-5, jnp.float32)
    assert rel(got.reshape(want.shape), want) < 1e-5
    # states that come normed are read as they are
    normed = lm_parts.rms_norm(xs, c["norm"], 1e-5)
    assert rel(lm_parts.head_nll(normed, None, c["head"], c["labels"], 1e-5,
                                 jnp.float32), together) < 1e-6


# ---- the exit distribution ---------------------------------------------------

@f32
def test_exit_distribution_is_the_survival_product():
    a = jax.random.normal(jax.random.PRNGKey(0), (4, 2, 5)) * 2
    p, log_p = exit_distribution(a)
    lam = jax.nn.sigmoid(a)
    want = jnp.stack([lam[0], (1 - lam[0]) * lam[1],
                      (1 - lam[0]) * (1 - lam[1]) * lam[2],
                      (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2])])
    assert rel(p, want) < 1e-6 and rel(log_p, jnp.log(want)) < 1e-5
    np.testing.assert_allclose(np.asarray(jnp.sum(p, 0)), 1.0, rtol=1e-6)
    # an untrained gate (logit 0) over four runs: .5, .25, .125, .125
    p0, _ = exit_distribution(jnp.zeros((4, 1)))
    assert list(np.asarray(p0[:, 0])) == [0.5, 0.25, 0.125, 0.125]
    assert float(jnp.sum(jnp.arange(1, 5) * p0[:, 0])) == 1.875
    # one run: all the mass on the only exit, whatever the gate says
    p1, log_p1 = exit_distribution(a[:1])
    assert np.all(np.asarray(p1) == 1.0) and not np.asarray(log_p1).any()

    # a gate that saturates either way: p log p is 0, nothing is NaN
    def entropy(a):
        p, log_p = exit_distribution(a)
        return -jnp.sum(p * log_p)
    hard = jnp.array([[200.0], [-200.0], [0.0], [3.0]])
    value, grad = jax.value_and_grad(entropy)(hard)
    assert float(value) == 0.0 and np.isfinite(np.asarray(grad)).all()
    value, grad = jax.value_and_grad(entropy)(-hard)
    assert np.isfinite(float(value)) and np.isfinite(np.asarray(grad)).all()


# ---- the whole model: exits, loss, gradients -----------------------------------

#: float32 throughout: the two sides differ by the order of their sums.
#: bfloat16 operands: the products themselves are exact in float32 on both
#: sides, but a sum that differs in its last bit rounds to the other
#: bfloat16 neighbour (2^-8 apart) where it sits on a boundary, and four
#: runs of two layers pass that on: one rounding, not several
TOLERANCE = {"float32": dict(logits=1e-5, p=1e-5, loss=1e-5, emb=1e-4,
                             leaf=2e-4),
             "bfloat16": dict(logits=1e-2, p=5e-3, loss=2e-4, emb=2e-2,
                              leaf=2.5e-2)}


@f32
@pytest.mark.parametrize("precision", ["float32", "bfloat16"])
def test_model_matches_the_reference_at_every_exit(precision):
    cfg = cfg_of(2)
    tol = TOLERANCE[precision]
    ref_precision = None if precision == "float32" else precision
    params, emb, labels = seeded(cfg)
    model = program(cfg, jnp.dtype(precision))
    assert jax.tree.map(jnp.shape, model.init(jax.random.PRNGKey(0))) \
        == jax.tree.map(jnp.shape, params)
    # every exit's logits and the exit distribution
    hs, gate = model.exits(params, emb)
    want_z = ref.forward(params, emb, cfg, ref_precision)
    assert hs.shape == (4, 2, 24, 64) and len(want_z) == 4
    for r in range(4):
        assert rel(model._mm(hs[r], params["head"]), want_z[r]) \
            < tol["logits"], r
    assert rel(model.logits(params, emb), want_z[-1]) < tol["logits"]
    p, _ = exit_distribution(gate)
    want_p = ref.exit_distribution(
        params, ref.hidden(params, emb, cfg, ref_precision))
    assert rel(p, want_p) < tol["p"]
    # the loss and every gradient
    want, (gp_r, ge_r) = jax.value_and_grad(
        lambda p, e: ref.loss(p, e, labels, cfg, ref_precision),
        argnums=(0, 1))(params, emb)
    (got, scalars), (gp, ge) = jax.value_and_grad(
        lambda p, e: model.loss(p, e, labels, jnp.ones((2, 24), bool)),
        argnums=(0, 1), has_aux=True)(params, emb)
    assert float(got) == pytest.approx(float(want), rel=tol["loss"])
    assert rel(ge, ge_r) < tol["emb"]
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(gp_r)[0],
                            jax.tree.leaves(gp)):
        assert float(jnp.linalg.norm(a)) > 0, jax.tree_util.keystr(path)
        assert rel(b, a) < tol["leaf"], jax.tree_util.keystr(path)
    # the step's scalars, from the reference's own exits
    assert set(scalars) == set(model.step_scalars)
    ce = ref.exit_losses(params, ref.hidden(params, emb, cfg, ref_precision),
                         labels, ref_precision)
    assert float(scalars["loop_positions"]) == 48
    assert float(scalars["loop_exit_step_sum"]) == pytest.approx(float(
        jnp.sum(jnp.arange(1, 5)[:, None, None] * want_p)), rel=tol["p"])
    assert float(scalars["loop_exit_entropy_sum"]) == pytest.approx(float(
        -jnp.sum(want_p * jnp.log(want_p))), rel=tol["p"])
    assert float(scalars["loop_last_exit_loss"]) == pytest.approx(
        float(jnp.mean(ce[-1])), rel=tol["loss"])


@f32
def test_positions_that_are_not_valid_are_left_out():
    cfg = cfg_of(1)
    params, emb, labels = seeded(cfg)
    valid = jnp.arange(24)[None, :] < jnp.array([[24], [10]])
    model = program(cfg)
    got, scalars = model.loss(params, emb, labels, valid)
    hs = ref.hidden(params, emb, cfg)
    p = ref.exit_distribution(params, hs)
    ce = ref.exit_losses(params, hs, labels, None)
    each = jnp.sum(p * ce, 0) + 0.1 * jnp.sum(p * jnp.log(p), 0)
    assert float(got) == pytest.approx(
        float(jnp.sum(each * valid) / 34), rel=1e-5)
    assert float(scalars["loop_positions"]) == 34


@f32
def test_a_loop_of_one_run_is_a_plain_decoder():
    """One run: the only exit takes all the mass, the entropy is 0, and
    the loss is the plain mean cross-entropy of an eight-layer decoder
    with sandwich norms, written here layer by layer; the gate gets no
    gradient."""
    cfg = cfg_of(8, total_ut_steps=1)
    params, emb, labels = seeded(cfg)
    z = ref.dims(cfg)
    x = emb
    for lay in params["layers"]:
        x = ref.layer(x, lay, z, None)
    logits = ref.rms_norm(x, params["norm"], z["eps"]) @ params["head"]
    plain = -jnp.mean(jnp.take_along_axis(
        jax.nn.log_softmax(logits, -1), labels[..., None], -1))
    (got, scalars), grads = jax.value_and_grad(
        lambda p: program(cfg).loss(p, emb, labels, jnp.ones((2, 24), bool)),
        has_aux=True)(params)
    assert float(got) == pytest.approx(float(plain), rel=1e-5)
    assert float(scalars["loop_last_exit_loss"]) == pytest.approx(
        float(plain), rel=1e-5)
    assert float(scalars["loop_exit_step_sum"]) == 48
    assert float(scalars["loop_exit_entropy_sum"]) == 0
    assert not np.asarray(grads["exit_w"]).any()
    assert float(grads["exit_b"]) == 0
    assert float(jnp.linalg.norm(grads["layers"][0]["q"])) > 0


@f32
def test_a_shared_weights_gradient_is_the_sum_over_four_untied_copies():
    """The same loss with a copy of the stack a run (the reference's
    layer in a Python loop): the gradients of the four copies differ, and
    their sum is what the scan's backward pass gives the one shared
    stack."""
    cfg = cfg_of(2)
    params, emb, labels = seeded(cfg)
    z = ref.dims(cfg)

    def untied(copies):
        h, hs = emb, []
        for layers in copies:
            x = h
            for lay in layers:
                x = ref.layer(x, lay, z, None)
            h = ref.rms_norm(x, params["norm"], z["eps"])
            hs.append(h)
        p = ref.exit_distribution(params, hs)
        ce = ref.exit_losses(params, hs, labels, None)
        return jnp.mean(jnp.sum(p * ce, 0) + 0.1 * jnp.sum(p * jnp.log(p), 0))

    copies = [params["layers"]] * 4
    value, g_copies = jax.value_and_grad(untied)(copies)
    got, _ = program(cfg).loss(params, emb, labels, jnp.ones((2, 24), bool))
    assert float(got) == pytest.approx(float(value), rel=1e-5)
    shared = jax.grad(lambda p: program(cfg).loss(
        p, emb, labels, jnp.ones((2, 24), bool))[0])(params)["layers"]
    summed = jax.tree.map(lambda *g: sum(g), *g_copies)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(summed)[0],
                            jax.tree.leaves(shared)):
        assert rel(b, a) < 2e-4, jax.tree_util.keystr(path)
    # a real sum: no copy's gradient is the whole, and the copies differ
    for r in range(4):
        part = g_copies[r][0]["down"]
        assert rel(part, summed[0]["down"]) > 0.3, r
    assert rel(g_copies[0][0]["down"], g_copies[3][0]["down"]) > 0.3


@pytest.mark.parametrize("layers", [1, 2])
def test_the_layers_forward_sweep_runs_once_a_step(monkeypatch, layers):
    """The program holds one copy of the stack whatever the runs, so the
    loops counted are a layer's, and the kept values stack over the runs
    of the ``jax.lax.scan`` around it."""
    from paddlebox_tpu.models import ouro
    cfg = cfg_of(layers)
    assert_the_forward_sweep_runs_once(
        monkeypatch, ouro, program(cfg), seeded(cfg)[0], attn_layers=layers)


@f32
def test_three_runs_are_not_four():
    """What the ``loop_short`` fault plants is visible: a loop one run
    short is another loss and another gradient, and the reference under
    the fault is the program configured with three runs."""
    cfg = cfg_of(2)
    params, emb, labels = seeded(cfg)
    ok = jnp.ones((2, 24), bool)
    four = jax.value_and_grad(
        lambda p: program(cfg).loss(p, emb, labels, ok)[0])(params)
    three = jax.value_and_grad(
        lambda p: program(dict(cfg, total_ut_steps=3)).loss(
            p, emb, labels, ok)[0])(params)
    short = jax.value_and_grad(
        lambda p: ref.loss(p, emb, labels, cfg, None, "loop_short"))(params)
    assert abs(float(four[0]) - float(three[0])) / float(four[0]) > 1e-3
    assert rel(three[1]["head"], four[1]["head"]) > 0.05
    assert float(three[0]) == pytest.approx(float(short[0]), rel=1e-5)
    assert rel(three[1]["head"], short[1]["head"]) < 2e-4
    # the last exit of three takes what exits three and four had of four
    p4 = ref.exit_distribution(params, ref.hidden(params, emb, cfg))
    p3 = ref.exit_distribution(
        params, ref.hidden(params, emb, cfg, None, "loop_short"))
    assert rel(p3[:2], p4[:2]) < 1e-6 and rel(p3[2], p4[2] + p4[3]) < 1e-6
    assert ref.runs_of(cfg, None) == 4 and ref.runs_of(cfg, "loop_short") == 3
    assert ref.runs_of(cfg, "state_unchanged") == 4


def test_a_configuration_of_another_kind_of_stack_is_refused():
    with pytest.raises(ValueError, match="not all full_attention"):
        program(cfg_of(2, layer_types=["full_attention",
                                       "sliding_attention"]))
    with pytest.raises(ValueError, match="layer types for"):
        program(cfg_of(2, num_hidden_layers=3))
    with pytest.raises(ValueError, match="has no exit"):
        program(cfg_of(2, total_ut_steps=0))


# ---- one pass through Trainer + PassPreloader -------------------------------

def toy_cell():
    cell = family_toy_cell()
    cell["config"]["matmul_dtype"] = "float32"
    return cell


@f32
def test_one_pass_through_the_trainer_equals_the_reference_step_by_step():
    from benchmarks.families import lm_ouro as family
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
    assert out["tokens"] == out["loop_positions"] == 192
    # four steps at the toy's rate of 1e-3 move the gate a little from an
    # untrained one's expected exit 1.875 and entropy 1.75 ln 2
    assert out["loop_exit_step_sum"] / 192 == pytest.approx(1.875, abs=0.25)
    assert out["loop_exit_entropy_sum"] / 192 == pytest.approx(
        1.75 * math.log(2), abs=0.1)
    assert out["loop_last_exit_loss"] == pytest.approx(math.log(96), rel=0.1)
    fin = [s for s in trace.recent_spans() if s.name == "pass.finish"][-1]
    for k in ("tokens", "documents", *OuroLoop.step_scalars):
        assert fin.attrs[k] == out[k], k
    # the dense weights after four Adam steps, the shared layers' too
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


def test_pass_program_carries_every_scope_and_one_copy_of_the_stack():
    cell = toy_cell()
    text = _pass_text(cell, ref, program(cell["config"]), True)
    missing = {s for s in trace.LOOP_SEQ_STEP_SCOPES
               if not re.search(re.escape(s) + r"(?![A-Za-z0-9_])", text)}
    assert not missing, missing
    assert trace.SCOPE_EXIT_GATE == "pbox.exit_gate"
    # no mixer, no window, no experts in this model
    for s in (trace.SCOPE_SSM_SCAN, trace.SCOPE_CONV_MIX,
              trace.SCOPE_ATTN_WINDOW, trace.SCOPE_MOE_EXPERTS):
        assert s not in text
    # a layer is one jax.checkpoint: the reducers count its backward ops
    # under the scope itself (PERF.md section 7)
    for s in (trace.SCOPE_ATTN, trace.SCOPE_MLP):
        assert f"checkpoint/{s}/" in text, s
    # the runs are a loop in the program, not copies of the stack: twice
    # the runs are the same matrix products, over twice the layers more
    def products(**over):
        config = dict(cell["config"], **over)
        plain = _pass_text(dict(cell, config=config), ref, program(config),
                           False)
        return plain.count("stablehlo.dot_general")
    four = products()
    assert products(total_ut_steps=8) == four
    assert products(**toy_config(4)) > four
