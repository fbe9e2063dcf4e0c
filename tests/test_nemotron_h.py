"""The hybrid language-model path (models/nemotron_h.py, ops/ssd.py,
ops/causal_attention.py, parallel/moe.routed_experts, wide table rows,
train/step.SeqTrainStep through Trainer + PassPreloader) against the plain
reference the benchmark keeps (benchmarks/reference/models/nemotron_h.py,
benchmarks/reference/lm.py), at tiny widths on the CPU in float32."""

import collections
import dataclasses
import hashlib
import itertools
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import checkpoint_name

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.reference import lm as ref_lm  # noqa: E402
from benchmarks.reference.models import nemotron_h as ref  # noqa: E402
from paddlebox_tpu.models import NemotronH  # noqa: E402
from paddlebox_tpu.models.kimi_linear import (  # noqa: E402
    KDA_CHUNK, KDA_OUT, KEEP_KDA_RULE)
from paddlebox_tpu.obs import trace  # noqa: E402
from paddlebox_tpu.ops import kda  # noqa: E402
from paddlebox_tpu.ops.kda import kda_normed  # noqa: E402
from paddlebox_tpu.ops.ssd import ssd_scan  # noqa: E402
from paddlebox_tpu.parallel.moe import route_top_k, routed_experts  # noqa: E402

CFG = dict(
    hybrid_override_pattern="MEMEM*EME", hidden_size=64, vocab_size=96,
    layer_norm_epsilon=1e-5, mamba_num_heads=8, mamba_head_dim=8,
    n_groups=2, ssm_state_size=16, conv_kernel=4, chunk_size=8,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    moe_intermediate_size=48, moe_shared_expert_intermediate_size=96,
    router_outputs=16, n_routed_experts=4, first_expert_held=0,
    num_experts_per_tok=3, routed_scaling_factor=2.5, time_step_min=0.001,
    time_step_max=0.1, time_step_floor=1e-4)


def rel(a, b):
    return float(jnp.linalg.norm(jnp.asarray(a) - jnp.asarray(b))
                 / jnp.maximum(jnp.linalg.norm(jnp.asarray(b)), 1e-30))


def program(cfg, **kw):
    return NemotronH(cfg, compute_dtype=jnp.float32, **kw)


@pytest.fixture(autouse=True)
def program_flags_restored():
    """``entries/common.program_flags`` sets process-wide flags; the
    worker's later test files must find them as they were."""
    from paddlebox_tpu.config import FLAGS
    saved = dataclasses.asdict(FLAGS)
    yield
    for name, value in saved.items():
        setattr(FLAGS, name, value)


@pytest.fixture
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


#: a float32 comparison needs float32 products on the CPU too
f32 = pytest.mark.usefixtures("highest_precision")


@f32
# ---- layers and the whole stack: forward, loss, gradients ------------------

@pytest.mark.parametrize("pattern,t", [
    ("M", 16), ("M", 20), ("*", 16), ("*", 20), ("E", 20),
    ("MEMEM*EME", 20)])
def test_stack_matches_the_reference(pattern, t):
    cfg = dict(CFG, hybrid_override_pattern=pattern)
    params = ref.init(jax.random.PRNGKey(3), cfg)
    emb = jax.random.normal(jax.random.PRNGKey(1), (2, t, 64)) * 0.02
    labels = jax.random.randint(jax.random.PRNGKey(2), (2, t), 0, 96)
    model = program(cfg)
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
        assert rel(b, a) < 2e-4, jax.tree_util.keystr(path)
    assert set(scalars) == set(model.step_scalars)
    _, loads, _ = model.hidden(params, emb)
    assert loads.shape == (pattern.count("E"), 4)
    assert float(scalars["moe_choices_held"]) == float(jnp.sum(loads))
    blk = math.gcd(2 * t, 512)
    assert float(scalars["moe_rows_computed"]) == float(
        jnp.sum(-(-loads // blk) * blk))


@f32
@pytest.mark.parametrize("t", [16, 24, 21, 5],
                         ids=["2-chunks", "3-chunks", "ragged", "short"])
def test_chunked_scan_matches_the_step_by_step_recurrence(t):
    ks = jax.random.split(jax.random.PRNGKey(t), 5)
    b_, g, k, p, n = 2, 2, 4, 8, 16
    x = jax.random.normal(ks[0], (b_, t, g * k, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b_, t, g * k)))
    a = -jnp.exp(jax.random.normal(ks[2], (g * k,)))
    b = jax.random.normal(ks[3], (b_, t, g, n))
    c = jax.random.normal(ks[4], (b_, t, g, n))

    def chunked(x, dt, a, b, c):
        return ssd_scan(x, dt, a, b, c, chunk=8, mm_dtype=jnp.float32)

    def stepwise(x, dt, a, b, c):
        y, _ = ref.recurrence(x.reshape(b_, t, g, k, p),
                              dt.reshape(b_, t, g, k), a.reshape(g, k), b, c)
        return y.reshape(b_, t, g * k, p)

    assert rel(chunked(x, dt, a, b, c), stepwise(x, dt, a, b, c)) < 1e-5
    w = jax.random.normal(jax.random.PRNGKey(9), (b_, t, g * k, p))
    grads = [jax.grad(lambda *v, f=f: jnp.sum(f(*v) * w),
                      argnums=(0, 1, 2, 3, 4))(x, dt, a, b, c)
             for f in (chunked, stepwise)]
    for got, want in zip(*grads):
        assert rel(got, want) < 1e-4


# ---- the expert layer: shares add up, nothing is dropped -------------------

def _expert_layer(key, experts=16):
    cfg = dict(CFG, hybrid_override_pattern="E", n_routed_experts=experts)
    lay = ref.init(key, cfg)["layers"][0]
    u = jax.random.normal(jax.random.fold_in(key, 7), (2, 12, 64))
    return cfg, lay, u


@f32
def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Four chips hold four of the sixteen experts each; what they give,
    with the shared expert (which every chip computes alike) counted
    once, is the whole layer as the reference computes it uncut."""
    cfg, lay, u = _expert_layer(jax.random.PRNGKey(5))
    z = ref.dims(cfg)
    want = ref.moe(lay, u, z, None, (0, 16))
    flat = u.reshape(-1, 64)
    idx, w = route_top_k(flat, lay["router"], lay["router_bias"], 3, 2.5)
    shared = ref.relu2_mlp(u, lay["shared_up"], lay["shared_down"],
                           None).reshape(-1, 64)
    total, choices = shared, 0
    for lo in range(0, 16, 4):
        part, stats = routed_experts(
            flat, idx, w, lay["up"][lo:lo + 4], lay["down"][lo:lo + 4],
            (lo, lo + 4), mm_dtype=jnp.float32)
        # one share alone is the reference given the same share
        alone = ref.moe({**lay, "up": lay["up"][lo:lo + 4],
                         "down": lay["down"][lo:lo + 4]}, u, z, None,
                        (lo, lo + 4)).reshape(-1, 64) - shared
        assert rel(part, alone) < 1e-5
        total = total + part
        choices += int(stats["choices"])
    assert choices == flat.shape[0] * 3       # every choice fell somewhere
    assert rel(total, want.reshape(-1, 64)) < 1e-5


@f32
@pytest.mark.parametrize("n", [24, 14, 7],
                         ids=["blocks-of-8", "blocks-of-2", "blocks-of-1"])
def test_routing_drops_nothing_when_every_token_picks_the_same_experts(n):
    cfg, lay, u = _expert_layer(jax.random.PRNGKey(6), experts=4)
    flat = u.reshape(-1, 64)[:n]
    # the correction bias makes experts 0, 1, 2 every token's choice
    bias = jnp.zeros(16).at[:3].set(10.0)
    idx, w = route_top_k(flat, lay["router"], bias, 3, 2.5)
    assert set(np.asarray(idx).reshape(-1)) == {0, 1, 2}
    y, stats = routed_experts(flat, idx, w, lay["up"], lay["down"], (0, 4),
                              mm_dtype=jnp.float32)
    assert int(stats["choices"]) == 3 * n
    assert list(np.asarray(stats["load"])) == [n, n, n, 0]
    want = sum(jnp.sum(jnp.where(idx == e, w, 0.0), -1)[:, None]
               * ref.relu2_mlp(flat[None], lay["up"][e], lay["down"][e],
                               None)[0] for e in range(3))
    assert rel(y, want) < 1e-5
    # the capacity gates of this module drop here; this layer may not
    kept = ref.drop_over_capacity(idx, w, ref.dims(cfg), 1.0)
    assert float(jnp.sum(kept > 0)) < 3 * n


# ---- the expert layer's hand-written backward pass, and what it computes -----

def _held_reference(x, idx, w, up, down, held, precision):
    """The dense reference of one share: every held expert computed for
    every token, weighted by what the token's choices gave it."""
    y = 0.0
    for j, e in enumerate(range(*held)):
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), -1)
        y = y + w_e[:, None] * ref.relu2_mlp(x[None], up[j], down[j],
                                             precision)[0]
    return y


def _expert_case(case):
    """(x, idx, w, up, down, held, block) of a named routing."""
    n, held, same = {"routed-evenly": (24, (4, 8), False),
                     "blocks-of-8": (24, (0, 4), True),
                     "blocks-of-2": (14, (0, 4), True),
                     "blocks-of-1": (7, (0, 4), True),
                     "no-choice-held": (24, (12, 16), True),
                     "bfloat16-operands": (24, (0, 4), True)}[case]
    _, lay, u = _expert_layer(jax.random.PRNGKey(8), experts=16)
    flat = u.reshape(-1, 64)[:n]
    # the correction bias makes experts 0, 1, 2 every token's choice
    bias = jnp.zeros(16).at[:3].set(10.0) if same else lay["router_bias"]
    idx, w = route_top_k(flat, lay["router"], bias, 3, 2.5)
    lo, hi = held
    return (flat, idx, w, lay["up"][lo:hi], lay["down"][lo:hi], held,
            {24: 8, 14: 2, 7: 1}[n])


EXPERT_CASES = ["routed-evenly", "blocks-of-8", "blocks-of-2", "blocks-of-1",
                "no-choice-held", "bfloat16-operands"]


@f32
@pytest.mark.parametrize("case", EXPERT_CASES)
def test_routed_experts_gradients_equal_the_dense_references(case):
    x, idx, w, up, down, held, _ = _expert_case(case)
    bf16 = case == "bfloat16-operands"
    cot = jax.random.normal(jax.random.PRNGKey(11), x.shape)

    def ours(x, w, up, down):
        y, _ = routed_experts(x, idx, w, up, down, held,
                              mm_dtype=jnp.bfloat16 if bf16 else jnp.float32)
        return jnp.sum(y * cot)

    def dense(x, w, up, down):
        return jnp.sum(_held_reference(x, idx, w, up, down, held,
                                       "bfloat16" if bf16 else None) * cot)

    got = jax.grad(ours, argnums=(0, 1, 2, 3))(x, w, up, down)
    want = jax.grad(dense, argnums=(0, 1, 2, 3))(x, w, up, down)
    if case == "no-choice-held":
        # zero trips: nothing is computed, not even a rounding
        y, stats = routed_experts(x, idx, w, up, down, held,
                                  mm_dtype=jnp.float32)
        assert int(stats["choices"]) == 0 and not np.asarray(y).any()
        for g, r in zip(got, want):
            assert not np.asarray(g).any() and not np.asarray(r).any()
        return
    # float32: the same arithmetic in another order. bfloat16 operands:
    # ``down``'s gradient takes the reference's very roundings; for the
    # other three the reference rounds the weighted cotangent, the loop
    # rounds the cotangent and weighs the product: one rounding apart
    # (read 0.0014-0.0032; plain float32 lies 0.0034-0.0042 from either)
    for name, g, r in zip(("x", "w", "up", "down"), got, want):
        assert float(jnp.linalg.norm(r)) > 0, name
        limit = 1e-4 if not bf16 else 1e-5 if name == "down" else 1e-2
        assert rel(g, r) < limit, name


@pytest.mark.parametrize("case", EXPERT_CASES)
def test_rows_computed_follow_the_choices(case):
    x, idx, w, up, down, held, blk = _expert_case(case)
    _, stats = routed_experts(x, idx, w, up, down, held, mm_dtype=jnp.float32)
    load = np.asarray(stats["load"])
    want = np.asarray(jnp.sum((idx[:, :, None] == jnp.arange(*held)),
                              axis=(0, 1)))
    assert list(load) == list(want)
    assert int(stats["choices"]) == load.sum()
    assert int(stats["rows"]) == sum(-(-int(c) // blk) * blk for c in load)
    if case == "no-choice-held":
        assert int(stats["rows"]) == 0
    elif case != "routed-evenly":
        assert list(load) == [x.shape[0]] * 3 + [0]


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (tuple, list)) else (val,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def test_the_gradient_program_walks_the_blocks_in_use_not_the_bound():
    """At 1,024 tokens, 8 experts held of 128: the program of the gradient
    has loops whose trip count is data and no scan over chunks of the
    bound, builds no stack of a weight matrix a block, and sizes no float
    value by the 6 x N choices that could fall here."""
    n, d, f, k, n_held = 1024, 64, 48, 6, 8
    ks = jax.random.split(jax.random.PRNGKey(12), 5)
    x = jax.random.normal(ks[0], (n, d))
    idx, w = route_top_k(x, jax.random.normal(ks[1], (d, 128)),
                         jnp.zeros(128), k, 2.5)
    up = jax.random.normal(ks[2], (n_held, d, f))
    down = jax.random.normal(ks[3], (n_held, f, d))

    def total(x, w, up, down):
        y, _ = routed_experts(x, idx, w, up, down, (0, n_held))
        return jnp.sum(y)

    jaxpr = jax.make_jaxpr(jax.grad(total, argnums=(0, 1, 2, 3)))(
        x, w, up, down)
    eqns = list(_eqns(jaxpr.jaxpr))
    names = collections.Counter(e.primitive.name for e in eqns)
    assert names["while"] == 2 * n_held and not names["scan"]
    assert not names["cond"]
    for eqn in eqns:
        for var in eqn.outvars:
            shape, dtype = var.aval.shape, var.aval.dtype
            if len(shape) == 3 and shape[1:] in ((d, f), (f, d)):
                # the held stack, or one expert's matrix sliced out of it
                assert shape[0] in (1, n_held), (eqn.primitive.name, shape)
            if shape and jnp.issubdtype(dtype, jnp.inexact):
                assert shape[0] < k * n, (eqn.primitive.name, shape)


# ---- table rows wider than one line -----------------------------------------

def test_wide_rows_pulled_pushed_and_read_back():
    """A table of 300-wide rows (three lines a row): gather, counted
    gather, the in-row rule's push and the host's row read and write
    equal the same on the logical table."""
    from paddlebox_tpu.ps.sgd import SparseSGDConfig
    from paddlebox_tpu.ps.table import (NUM_FIXED, TableState, apply_push,
                                        dispatch_packed_row_gather,
                                        gather_full_rows, pack_geometry,
                                        scatter_logical_rows)
    cap, mf = 50, 292
    feat = NUM_FIXED + mf
    assert pack_geometry(cap, feat) == (1, 384, 3 * (cap + 1))
    rng = np.random.default_rng(0)
    logical = rng.standard_normal((cap + 1, feat)).astype(np.float32)
    logical[:, 7] = 1.0
    logical[:, 5:7] = np.abs(logical[:, 5:7])
    logical[cap] = 0
    st = TableState.from_logical(logical)
    assert np.array_equal(np.asarray(st.data), logical)
    rows = np.array([3, 7, 49, 0, cap, cap + 5, cap + 9], np.int32)
    want = logical[np.minimum(rows, cap)]
    assert np.array_equal(np.asarray(gather_full_rows(st, jnp.asarray(rows))),
                          want)
    counted = np.asarray(gather_full_rows(st, jnp.asarray(rows),
                                          jnp.asarray(4, jnp.int32)))
    assert np.array_equal(counted[:4], want[:4]) and not counted[4:].any()
    out, k = dispatch_packed_row_gather(st, None, np.array([5, 9], np.int32))
    assert np.array_equal(np.asarray(out)[:k], logical[[5, 9]])

    cfg = SparseSGDConfig(mf_create_thresholds=0.0, mf_initial_range=0.0,
                          mf_learning_rate=0.05, mf_initial_g2sum=3.0)
    g = rng.standard_normal((7, 3 + mf)).astype(np.float32)
    g[:, 0], g[:, 1], g[:, 2] = 2.0, 0.0, 0.0
    real = rows[:4]
    exp = logical.copy()
    scaled = g[:4, 3:] / 2.0
    ratio = 0.05 * np.sqrt(3.0 / (3.0 + logical[real, 6]))
    exp[real, NUM_FIXED:] = np.clip(
        logical[real, NUM_FIXED:] + scaled * ratio[:, None], -10, 10)
    exp[real, 6] += np.mean(scaled * scaled, axis=1)
    exp[real, 0] += 2.0
    exp[real, 2] += 0.1 * 2.0
    pushed = [np.asarray(apply_push(st, jnp.asarray(rows), jnp.asarray(g),
                                    cfg, jax.random.PRNGKey(0),
                                    num_unique=nu).data)
              for nu in (None, jnp.asarray(4, jnp.int32))]
    assert np.array_equal(pushed[0], pushed[1])      # the counted loop
    np.testing.assert_allclose(pushed[0], exp, rtol=2e-6, atol=2e-6)
    st2 = scatter_logical_rows(st, None, np.array([1, 2], np.int32),
                               np.ones((2, feat), np.float32))
    d = np.asarray(st2.data)
    assert (d[[1, 2]] == 1).all() and np.array_equal(d[3:], logical[3:])


# ---- one pass through Trainer + PassPreloader -------------------------------

def _toy_cell():
    from benchmarks.tests.test_family_lm import toy_cell
    cell = toy_cell()
    cell["config"]["matmul_dtype"] = "float32"
    return cell


def _trainer(cell, pool, params, model=None):
    """(trainer, table, preloader) of a toy cell (hidden 64, 96 ids) over
    ``model``, this file's float32 ``NemotronH`` where none is given
    (tests/test_lfm2.py hands in the other sequence model)."""
    import optax
    from benchmarks.entries import common, resident_seq
    from paddlebox_tpu.ps import EmbeddingTable
    from paddlebox_tpu.train import PassPreloader, Trainer
    common.program_flags()
    config, traffic = cell["config"], cell["traffic"]
    desc = resident_seq.feed_desc(traffic)
    table = EmbeddingTable(mf_dim=64, capacity=96,
                           cfg=common.sparse_cfg(config),
                           unique_bucket_min=48, arena_slots=1)
    resident_seq.load_vocabulary(table, params["embedding"])
    tx = optax.adam(config["dense_optimizer"]["learning_rate"])
    tr = Trainer(model or program(config), table, desc, tx=tx)
    tr.state = tr.state._replace(params=params["net"],
                                 opt_state=tx.init(params["net"]))
    pre = PassPreloader(itertools.cycle(resident_seq.datasets(desc, pool)),
                        table, depth=2)
    pre.start_next()
    return tr, table, pre


@f32
def test_one_pass_through_the_trainer_equals_the_reference_step_by_step():
    from benchmarks.families import lm
    from paddlebox_tpu.ps.table import NUM_FIXED
    cell = _toy_cell()
    config, traffic = cell["config"], cell["traffic"]
    pool = lm.make_pool(config, traffic, 5)
    params = lm.seeded_params(ref, config, 5)
    host = jax.device_get(params)
    tr, table, pre = _trainer(cell, pool, params)
    try:
        rp = pre.wait()
        assert rp.wire == "compact" and rp.floats.dtype == np.int32
        out = tr.train_pass_resident(rp)
    finally:
        pre.drain()
    want = ref_lm.run_pass(ref, config, pool[0].inputs, pool[0].labels, 2,
                           host["net"], host["embedding"], precision=None)
    # every step's loss, out of the pass program
    assert len(out["losses"]) == 4
    np.testing.assert_allclose(out["losses"], want["loss_steps"], rtol=2e-5)
    assert out["tokens"] == 192
    assert out["documents"] == int((pool[0].inputs == 0).sum())
    assert out["moe_choices_held"] > 0
    assert out["moe_expert_load_max"] >= out["moe_expert_load_mean"] > 0
    fin = [s for s in trace.recent_spans() if s.name == "pass.finish"][-1]
    assert out["moe_rows_computed"] >= out["moe_choices_held"]
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


def _pass_text(cell, ref_model, model, debug_info: bool) -> str:
    """The lowered text of a toy cell's ``train_pass_resident`` program
    over ``model`` (tests/test_lfm2.py lowers both sequence models so)."""
    from benchmarks.families import lm
    from paddlebox_tpu.train.device_pass import ResidentPassRunner
    pool = lm.make_pool(cell["config"], cell["traffic"], 6, count=1)
    params = lm.seeded_params(ref_model, cell["config"], 6)
    tr, table, pre = _trainer(cell, pool, params, model)
    try:
        rp = pre.wait()
    finally:
        pre.drain()
    runner = ResidentPassRunner(tr.step_fn, table.capacity, True,
                                wire=rp.wire, num_slots=1,
                                chunk_bits=rp.chunk_bits)
    return runner._run(rp.num_batches).lower(
        tr.state, *rp.dev, jnp.asarray(0, jnp.int32),
        tr._rng).as_text(debug_info=debug_info)


def test_sequence_pass_program_carries_every_scope():
    cell = _toy_cell()
    text = _pass_text(cell, ref, program(cell["config"]), True)
    missing = {s for s in trace.SEQ_STEP_SCOPES
               if not re.search(re.escape(s) + r"(?![A-Za-z0-9_])", text)}
    assert not missing, missing
    # a layer is one jax.checkpoint: in its backward pass the forward ops
    # computed again and the backward ops carry the scope behind a
    # ``checkpoint`` part, so the reducers count them under the scope
    # itself, not under ``.bwd``
    for s in (trace.SCOPE_SSM_SCAN, trace.SCOPE_MOE_EXPERTS,
              trace.SCOPE_ATTN):
        assert f"checkpoint/{s}/" in text, s


# ---- what an attention layer's checkpoint keeps -------------------------------

#: three blocks of ``lm_parts.ATTN_BLOCK`` positions in one sequence: the
#: attention's two loops are loops in the compiled program too (a loop of
#: one trip is not)
SWEEP_T = 1536


def assert_the_forward_sweep_runs_once(monkeypatch, module, model, params,
                                       attn_layers: int):
    """The compiled loss-and-gradient program of ``model`` over one
    sequence of ``SWEEP_T`` positions, three ways: as ``module`` (the
    model's own) writes it, with its checkpoints keeping nothing by name
    (the program before the residuals had names: a name without a policy
    is the identity), and with no ``jax.checkpoint`` at all. The model's
    own holds as many ``while`` ops as the program without a checkpoint
    and two fewer an attention layer copy than the one that keeps nothing
    (the forward sweep's loop over query blocks and the loop over key
    blocks inside it), and its loss and every gradient are the
    keep-nothing program's bit for bit (tests/test_lfm2.py,
    test_mellum.py and test_ouro.py call this too)."""
    emb = jax.random.normal(jax.random.PRNGKey(1), (1, SWEEP_T, 64)) * 0.02
    labels = jax.random.randint(jax.random.PRNGKey(2), (1, SWEEP_T), 0, 96)
    valid = jnp.ones(labels.shape, bool)

    def program_of():
        compiled = jax.jit(jax.value_and_grad(
            lambda p, e: model.loss(p, e, labels, valid)[0],
            argnums=(0, 1))).lower(params, emb).compile()
        return (len(re.findall(r" while\(", compiled.as_text())),
                compiled(params, emb))

    own_loops, own = program_of()
    with monkeypatch.context() as m:
        m.setattr(module, "KEEP_ATTN_LOOPS", None)
        nothing_kept_loops, nothing_kept = program_of()
    with monkeypatch.context() as m:
        m.setattr(jax, "checkpoint", lambda f, **kw: f)
        no_checkpoint_loops, _ = program_of()
    assert own_loops == no_checkpoint_loops
    assert nothing_kept_loops == own_loops + 2 * attn_layers
    assert float(own[0]) > 0
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(own)[0],
                            jax.tree.leaves(nothing_kept)):
        assert np.array_equal(a, b), jax.tree_util.keystr(path)


def test_the_attention_layers_forward_sweep_runs_once_a_step(monkeypatch):
    from paddlebox_tpu.models import nemotron_h
    cfg = dict(CFG, hybrid_override_pattern="*E*")
    assert_the_forward_sweep_runs_once(
        monkeypatch, nemotron_h, program(cfg),
        ref.init(jax.random.PRNGKey(3), cfg), attn_layers=2)


# ---- the scan's kernels, as a chip is given them ------------------------------

#: Mamba widths at which the scan's cells tile (a group of eight heads of
#: 64, state 128, chunk 128), the rest of the toy model as it is
TILING = dict(CFG, hybrid_override_pattern="M", mamba_num_heads=16,
              mamba_head_dim=64, n_groups=2, ssm_state_size=128,
              chunk_size=128)


@pytest.fixture
def kernels_for_the_chip(monkeypatch):
    """The Pallas seams lower their Mosaic kernels, not the interpreter
    (which is what a process without a TPU otherwise gets)."""
    from paddlebox_tpu.ops import pallas_kernels
    monkeypatch.setattr(pallas_kernels, "_interpret", lambda: False)


def test_every_kernel_of_the_scan_sits_under_its_scope(kernels_for_the_chip):
    """The program of two Mamba layers' loss and gradient, lowered for a
    TPU: three custom calls (the forward sweep, the sweep that also writes
    the states, which the layers' ``jax.checkpoint`` runs in the backward
    pass, and the backward sweep), each under ``pbox.ssm_scan`` by its own
    naming, the hand-written backward rule's included (a ``custom_vjp``
    rule's ops carry what the rule gives them), so that the trace's
    readers count all three. Three, not six: the sweeps are jitted, and
    layers of one shape share one trace and one lowered function of each
    (tracing a kernel is set-up time in every process)."""
    cfg = dict(TILING, hybrid_override_pattern="MM")
    params = ref.init(jax.random.PRNGKey(3), cfg)
    emb = jax.random.normal(jax.random.PRNGKey(1), (2, 256, 64)) * 0.02
    labels = jnp.zeros((2, 256), jnp.int32)
    model = NemotronH(cfg)
    step = jax.jit(jax.value_and_grad(
        lambda p, e: model.loss(p, e, labels, jnp.ones((2, 256), bool))[0],
        argnums=(0, 1)))
    text = step.trace(params, emb).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    stacks = [locs[ref_] for ref_ in re.findall(
        r"@tpu_custom_call\(.*loc\((#loc\d+)\)$", text, re.M)]
    assert len(stacks) == 3, stacks
    for stack in stacks:
        assert trace.SCOPE_SSM_SCAN in stack.split("/"), stacks
    # each layer calls the shared functions (its two sequences are one
    # ``lax.map`` body; a forward call the gradient does not need is dead
    # code the compiler drops)
    calls = re.findall(r"call @(_forward|_backward)\w*\(", text)
    assert calls.count("_backward") == 2 and calls.count("_forward") >= 4


@pytest.fixture(scope="module")
def one_v5e():
    """One chip of a described (not attached) v5e: the TPU's compiler is
    installed here and refuses what the chip's would."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # pragma: no cover - no libtpu here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("what", ["forward", "gradient"])
def test_the_scan_kernels_compile_for_a_v5e(what, one_v5e,
                                             kernels_for_the_chip):
    """At the cell's shapes (one sequence of 8,192 steps, 64 heads of 64
    in 8 groups, state 128, chunk 128, float32 in, bfloat16 operands):
    Mosaic takes the forward and the backward kernel. It proves that they
    compile, not what they compute (tests/test_ssd_kernel.py) nor how
    fast (PERF.md)."""
    t, h, p, g, n = 8192, 64, 64, 8, 128

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_v5e)

    args = (arg(1, t, h, p), arg(1, t, h), arg(h), arg(1, t, g, n),
            arg(1, t, g, n))

    def scan(*v):
        return ssd_scan(*v, chunk=128, mm_dtype=jnp.bfloat16)

    f = scan if what == "forward" else jax.grad(
        lambda *v: jnp.sum(scan(*v)), argnums=(0, 1, 2, 3, 4))
    compiled = jax.jit(f).trace(*args).lower(
        lowering_platforms=("tpu",)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= (
        1 if what == "forward" else 2)


def _delta_rule(*args):
    return kda_normed(*args, eps=1e-5, chunk=64, mm_dtype=jnp.bfloat16)


def _flat_rule_shapes(t, h, d):
    """q, k, v, g [1, T, H d], beta [1, T, H], gate [1, T, H d], the output
    norm's weight [d]: what a KDA mixer hands the rule."""
    return [(1, t, h * d)] * 4 + [(1, t, h), (1, t, h * d), (d,)]


def _kept_rule(*args):
    """The delta rule under a KDA mixer's checkpoint: its policy keeps the
    states that the forward sweep writes and the rule's output
    (``test_kda_kernel.mixer_rule`` in bfloat16; that module imports this
    one through ``test_kda``)."""
    def rule(*a):
        return checkpoint_name(_delta_rule(*a), KDA_OUT)

    return jax.checkpoint(rule, policy=KEEP_KDA_RULE)(*args)


def test_every_kernel_of_the_delta_rule_sits_under_its_scope(
        kernels_for_the_chip):
    """Two calls of the delta rule at tile widths (two heads of 128), each
    under a KDA mixer's checkpoint and under another scope as a KDA
    mixer's projections are, and their gradient, lowered for a TPU: two
    custom calls, the sweep that writes the states (in the checkpoints'
    forward pass) and the backward sweep, which reads the states the
    checkpoints kept. No forward sweep is left in the checkpoints'
    recomputation. Each under ``pbox.kda_scan`` by the rule's own naming,
    so that the trace's readers count both and ``other`` neither."""
    def args(seed):
        return [jax.random.normal(jax.random.PRNGKey(seed + i), s)
                for i, s in enumerate(_flat_rule_shapes(128, 2, 128))]

    def layer(x, *rest):
        with jax.named_scope(trace.SCOPE_KDA_PROJ):
            return _kept_rule(x, *rest)

    def loss(a, b):
        return jnp.sum(layer(*a) * layer(*b))

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).trace(
        args(0), args(9)).lower(lowering_platforms=("tpu",)).as_text(
            debug_info=True)
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    stacks = [locs[ref_] for ref_ in re.findall(
        r"@tpu_custom_call\(.*loc\((#loc\d+)\)$", text, re.M)]
    assert len(stacks) == 2, stacks
    for stack in stacks:
        assert trace.SCOPE_KDA_SCAN in stack.split("/"), stacks
    calls = re.findall(r"call @(_forward|_backward)\w*\(", text)
    assert calls.count("_backward") == 2 and calls.count("_forward") == 2


@pytest.mark.parametrize("what", ["forward", "gradient"])
def test_the_delta_rule_kernels_compile_for_a_v5e(what, one_v5e,
                                                  kernels_for_the_chip):
    """At cell 6's shapes (one sequence of 8,192 steps, 32 heads of 128 as
    flat rows, chunks of 64, float32 in, bfloat16 operands, the norms
    inside): Mosaic takes the forward and the backward sweep. It proves
    that they compile, not what they compute (tests/test_kda_kernel.py)
    nor how fast (PERF.md)."""
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_v5e)
            for s in _flat_rule_shapes(8192, 32, 128)]
    f = _delta_rule if what == "forward" else jax.grad(
        lambda *v: jnp.sum(_delta_rule(*v)), argnums=tuple(range(7)))
    compiled = jax.jit(f).trace(*args).lower(
        lowering_platforms=("tpu",)).compile()
    assert compiled.as_text().count("tpu_custom_call") == (
        1 if what == "forward" else 2)


def _kda_model_at_the_cells_widths():
    """One KDA layer of cell 6's widths (hidden 2304, 32 heads of 128)
    over the toy family's other numbers (a dense feed-forward of 80)."""
    from benchmarks.tests.test_family_lm_kimi_linear import (toy_cell,
                                                              toy_config)
    from paddlebox_tpu.models import KimiLinear
    cfg = dict(toy_cell()["config"], **toy_config("K"))
    cfg.update(hidden_size=2304, linear_attn_config=dict(
        cfg["linear_attn_config"], num_heads=32, head_dim=128))
    return KimiLinear(cfg)


def test_a_kda_mixer_changes_no_layout_of_its_rows(one_v5e,
                                                   kernels_for_the_chip):
    """One KDA mixer's loss and gradient at cell 6's widths (hidden 2304,
    32 heads of 128; 512 steps) under its checkpoint, compiled for a v5e:
    no ``copy``, ``reshape`` or ``transpose`` that is not a bitcast is left
    on a float32 array of a flat row's or a head's shape (``[.., 4096]``,
    ``[.., 32, 128]``), labelled or not. Where per-head ops sat between
    the flat producers and the rule's flat operands, XLA changed the
    tiling of each such array on both sides (20 such ops here, 25 a
    sequence and mixer of cell 6's pass program)."""
    model = _kda_model_at_the_cells_widths()
    lay = jax.eval_shape(model.init, jax.random.PRNGKey(0))["layers"][0]
    lay = {k: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_v5e)
           for k, a in lay.items() if k not in ("w1", "w2", "w3", "ffn_norm")}
    x = jax.ShapeDtypeStruct((1, 512, 2304), jnp.float32, sharding=one_v5e)

    def loss(lay, x):
        return jnp.sum(jax.checkpoint(model._kda, policy=KEEP_KDA_RULE)(
            lay, x) ** 2)

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).trace(
        lay, x).lower(lowering_platforms=("tpu",)).compile().as_text()
    moved = re.findall(
        r"= f32\[([\d,]*(?:4096|32,128))\]\{[^}]*\} "
        r"(copy|reshape|transpose)\(", text)
    assert not moved, moved
    assert text.count("tpu_custom_call") == 2


#: the temporaries (bytes) of the program that the next test compiles for
#: a described v5e, as it compiled while the mixer's checkpoint kept no
#: state and ran the forward sweep again in the backward pass
KDA_LAYER_TEMP_RECOMPUTED = 3_421_032_960


def test_a_kda_layer_keeps_what_its_backward_sweep_reads(
        one_v5e, kernels_for_the_chip):
    """A KDA layer's loss and gradient at cell 6's widths and steps (two
    sequences of 8,192 through ``KimiLinear.hidden``, chunks of 64)
    compiled for a v5e. Two custom calls a sequence (the mixer's
    checkpoint is one a sequence): the forward sweep that writes the
    states and the backward sweep, none in the mixer's recomputation. The
    temporaries grow by what the checkpoint keeps, a sequence the float32
    state at the entry of every ``kda.STRIDE``-th chunk and the rule's
    bfloat16 output, and by no more than a tenth of it over the program
    that ran the sweep again: kept values that the compiler reserves twice
    over (as it does for a ``lax.map``'s stacked residuals) fail this."""
    model = _kda_model_at_the_cells_widths()
    seqs, t, h, d = 2, 8192, 32, 128
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_v5e),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    x = jax.ShapeDtypeStruct((seqs, t, model.d), jnp.float32,
                             sharding=one_v5e)

    def loss(params, x):
        return jnp.sum(model.hidden(params, x)[0] ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).trace(
        params, x).lower(lowering_platforms=("tpu",)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2 * seqs
    states = t // (KDA_CHUNK * kda.STRIDE) * h * d * d * 4
    kept = seqs * (states + t * h * d * 2)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < KDA_LAYER_TEMP_RECOMPUTED + 1.1 * kept, (temp, kept)


# ---- the click models' pass program is what it was --------------------------

#: DeepFM's resident pass program (the shapes below) as lowered at the
#: parent commit b97e57f, before the sequence path was added: shared code
#: adapts on what the input shows, and what a click model shows has not
#: changed. The structure (counts of the ops that cost) holds under any
#: jax; the sha256 of the whole text only under the jax it was recorded
#: with, since a new jax may print the same program differently. PR 36
#: re-recorded both: the decode and the dedup are what it changed (the
#: compact wire loses the chunk map's gather and dedup_rows' two scatters
#: for two sorts and cmap_select's product, the dedup wire the gather of
#: its u18 index's packed high bits); every other op is the parent's.
DEEPFM_PASS_JAX = "0.9.0"
DEEPFM_PASS_TEXT = {
    "compact": "058d301d67aaa26ff1e2f9fb2fa737af83a9a17338340d85a52fab38338a1785",
    "dedup": "a1b904e573ce76e7ad95253bee885b8f3b232f3bbb7233335768071b87741e44",
}
DEEPFM_PASS_OPS = {
    "compact": {"while": 5, "gather": 6, "scatter": 8, "sort": 3,
                "dot_general": 12},
    "dedup": {"while": 3, "gather": 6, "scatter": 8, "dot_general": 11},
}


def deepfm_pass_text(arena: bool) -> str:
    """The lowered text of a small DeepFM ``train_pass_resident`` program
    (also run by hand on the parent commit to record the pins)."""
    import optax
    from paddlebox_tpu.data import DataFeedDesc, InMemoryDataset, SlotDef
    from paddlebox_tpu.data.columnar import ColumnarRecords
    from paddlebox_tpu.models import DeepFM
    from paddlebox_tpu.ps import EmbeddingTable, SparseSGDConfig
    from paddlebox_tpu.train import Trainer
    from paddlebox_tpu.train.device_pass import (ResidentPass,
                                                 ResidentPassRunner)
    s, bs, r = 4, 64, 256
    slots = [SlotDef("label", "float", 1), SlotDef("dense", "float", 3)]
    slots += [SlotDef(f"C{i}", "uint64") for i in range(s)]
    desc = DataFeedDesc(slots=slots, batch_size=bs, label_slot="label",
                        key_bucket_min=bs * s)
    rng = np.random.default_rng(0)
    ds = InMemoryDataset(desc)
    ds.columnar = ColumnarRecords(
        keys=(rng.integers(0, 500, (r, s)) + 1000 * np.arange(s)
              ).reshape(-1).astype(np.uint64),
        key_slot=np.tile(np.arange(s, dtype=np.int32), r),
        offsets=np.arange(r + 1, dtype=np.int64) * s,
        dense=rng.standard_normal((r, 3)).astype(np.float32),
        label=(rng.random(r) < 0.3).astype(np.float32),
        show=np.ones(r, np.float32),
        clk=np.zeros(r, np.float32))
    table = EmbeddingTable(mf_dim=10, capacity=1 << 16, cfg=SparseSGDConfig(),
                           unique_bucket_min=256,
                           arena_slots=s if arena else None)
    tr = Trainer(DeepFM(hidden=(32, 16)), table, desc, tx=optax.adam(1e-3))
    rp = ResidentPass.build_streamed(ds, table, floats_dtype="q8")
    assert rp.wire == ("compact" if arena else "dedup")
    runner = ResidentPassRunner(tr.step_fn, table.capacity, rp.segs is None,
                                wire=rp.wire, num_slots=s,
                                chunk_bits=rp.chunk_bits)
    return runner._run(rp.num_batches).lower(
        tr.state, *rp.dev, jnp.asarray(0, jnp.int32), tr._rng).as_text()


@pytest.mark.parametrize("wire", ["compact", "dedup"])
def test_deepfm_pass_program_lowers_to_the_parents_text(wire):
    text = deepfm_pass_text(wire == "compact")
    ops = collections.Counter(re.findall(
        r"stablehlo\.(while|gather|scatter|sort|dot_general)\b", text))
    assert dict(ops) == DEEPFM_PASS_OPS[wire]
    if jax.__version__ == DEEPFM_PASS_JAX:
        assert hashlib.sha256(text.encode()).hexdigest() == \
            DEEPFM_PASS_TEXT[wire]
