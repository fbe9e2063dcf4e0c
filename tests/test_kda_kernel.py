"""The delta rule's kernels (ops/kda.py: Pallas, here in interpret mode),
which take a KDA mixer's flat rows and do its per-head norms themselves,
against the composition with the same norms in plain JAX and against the
token recurrence the benchmark's plain reference runs
(benchmarks/reference/models/kimi_linear.py) between the reference's own
norms, at tile widths: K = V = 128, chunks of 64, two heads; the value and
the gradients of q, k, v, g, beta, gate and the output norm's weight, also
under a KDA mixer's checkpoint, which keeps the states the forward sweep
wrote. And the shape test that chooses between the two forms."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.ad_checkpoint import checkpoint_name

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.reference.models.kimi_linear import recurrence  # noqa: E402
from paddlebox_tpu.models.kimi_linear import (KDA_OUT,  # noqa: E402
                                              KEEP_KDA_RULE)
from paddlebox_tpu.ops import kda  # noqa: E402
from paddlebox_tpu.ops.kda import kda_normed  # noqa: E402
from test_kda import inputs, rel  # noqa: E402
from test_ssd_kernel import outer_eqns  # noqa: E402

CHUNK = 64
#: the output norm's eps (the cell's configuration's ``rms_norm_eps``)
EPS = 1e-5
NAMES = ("y", "dq", "dk", "dv", "dg", "dbeta", "dgate", "do_norm")

#: (T, a decay down to -30 a step, ``kda.HEADS``, rows of q and k whose
#: norm is under the L2 norm's eps)
CASES = {"one-chunk": (64, False, 4, False),
         "two-chunks-and-a-tail": (150, False, 1, False),
         "decay-down-to-30-a-step": (150, True, 1, False),
         "a-row-of-a-tiny-norm": (64, False, 1, True)}


def flat_inputs(t, seed, strong=False, tiny=False, h=2, d=128):
    """q, k before their norms (rows of length 0.2 to 5; with ``tiny`` a
    few rows of 1e-5, whose L2 norm is set by its eps and whose output's
    RMS norm by its own), g, v and gate flat [1, T, H d], beta [1, T, H],
    the norm's weight [d] away from 1, and a cotangent of y."""
    (q, k, v, g, beta), cot = inputs(t, seed=seed, strong=strong, bsz=1,
                                     h=h, kd=d, vd=d)
    ks = jax.random.split(jax.random.PRNGKey(seed + 1), 4)
    length = jnp.exp(jax.random.uniform(ks[0], (2, 1, t, h, 1),
                                        minval=-1.6, maxval=1.6))
    q, k = q * d ** 0.5 * length[0], k * length[1]
    if tiny:
        q = q.at[:, 3::20].multiply(1e-5 / length[0][:, 3::20])
        k = k.at[:, 7::20].multiply(1e-5 / length[1][:, 7::20])
    gate = 2.0 * jax.random.normal(ks[1], v.shape)
    o_norm = 1.0 + 0.3 * jax.random.normal(ks[2], (d,))

    def flat(x):
        return x.reshape(1, t, h * d)

    return ((flat(q), flat(k), flat(v), flat(g), beta, flat(gate), o_norm),
            flat(cot))


def value_and_grads(f, args, cot):
    """The value as the gradient's forward pass computes it (the kernels:
    the sweep that writes the states) and the seven gradients."""
    (_, y), grads = jax.jit(jax.value_and_grad(
        lambda *a: (lambda y: (jnp.sum(y * cot), y))(f(*a)),
        argnums=tuple(range(7)), has_aux=True))(*args)
    return dict(zip(NAMES, (y,) + grads))


def fused(*args, mm):
    return kda_normed(*args, eps=EPS, chunk=CHUNK, mm_dtype=mm)


def composition(*args, mm):
    """The XLA form with the norms in plain JAX, at any shape: the
    kernels' oracle."""
    tiles = kda._tiles
    kda._tiles = lambda *shapes: False
    try:
        return fused(*args, mm=mm)
    finally:
        kda._tiles = tiles


def reference(q, k, v, g, beta, gate, o_norm):
    """The reference model's norms around its token recurrence."""
    b, t, h = beta.shape

    def heads(x):
        return x.reshape(b, t, h, -1)

    def l2(x):
        return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    kd = q.shape[-1] // h
    o = recurrence(l2(heads(q)) * kd ** -0.5, l2(heads(k)), heads(v),
                   heads(g), beta)
    y = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + EPS) \
        * o_norm * jax.nn.sigmoid(heads(gate))
    return y.reshape(b, t, -1)


@functools.lru_cache(maxsize=None)
def readings(t, strong, mm, heads, tiny=False):
    """{name: (the kernels', the composition's, the reference's)} of the
    value and the seven gradients, computed once and read by every case.
    ``heads``: ``kda.HEADS``; 4 puts both heads side by side in a cell of
    either sweep (the backward's works ``HEADS / STRIDE``), 1 one head in
    each (the interpreter compiles a cell's body once, in seconds a
    head)."""
    args, cot = flat_inputs(t, seed=t, strong=strong, tiny=tiny)
    mm = jnp.dtype(mm)
    before, kda.HEADS = kda.HEADS, heads
    try:
        with jax.default_matmul_precision("highest"):
            got = value_and_grads(functools.partial(fused, mm=mm), args, cot)
            want = value_and_grads(functools.partial(composition, mm=mm),
                                   args, cot)
            ref = value_and_grads(reference, args, cot)
    finally:
        kda.HEADS = before
    return {n: (got[n], want[n], ref[n]) for n in NAMES}


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("case", list(CASES))
def test_the_kernels_equal_the_composition_and_the_recurrence(case, name):
    t, strong, heads, tiny = CASES[case]
    got, want, ref = readings(t, strong, "float32", heads, tiny)[name]
    assert got.shape == want.shape == ref.shape
    assert bool(jnp.all(jnp.isfinite(got)))
    assert float(jnp.linalg.norm(want)) > 0
    # the log decay's gradient sums products of terms down to e^-1900
    limit = 1e-4 if strong and name == "dg" else 1e-5
    assert rel(got, want) < limit
    assert rel(got, ref) < limit


def test_a_row_of_a_tiny_norm_is_set_by_the_eps():
    """Where a row of q is 1e-5 long, its L2 norm divides by about
    sqrt(1e-6) and not by its length, and the output's RMS norm at that
    step by about sqrt(eps): both rows come out short, not unit length."""
    args, _ = flat_inputs(64, seed=64, tiny=True)
    q = args[0].reshape(1, 64, 2, 128)
    assert float(jnp.max(jnp.linalg.norm(q[:, 3::20], axis=-1))) < 2e-4
    y = readings(64, False, "float32", 1, True)["y"][0].reshape(1, 64, 2, 128)
    gated = jnp.sqrt(jnp.mean(y ** 2, -1))
    assert float(jnp.max(gated[:, 3::20])) < 0.1 * float(jnp.median(gated))


@pytest.mark.parametrize("name", NAMES)
def test_bfloat16_operands_stay_within_a_rounding(name):
    """The forward sweep rounds to bfloat16 where the composition rounds:
    its value is the composition's to float32's last digits. The backward
    sweep rounds each cotangent where it enters a product, which autodiff
    of the composition does not everywhere: one rounding of bfloat16
    (2^-8) at most. Both stay a rounding from the float32 recurrence."""
    got, want, ref = readings(150, False, "bfloat16", 1)[name]
    assert rel(got, want) < (1e-6 if name == "y" else 1e-2)
    assert rel(got, ref) < 2e-2


def test_the_sweep_that_writes_no_states_computes_the_same():
    """A call that is not differentiated runs the sweep without its
    states: the same value."""
    args, _ = flat_inputs(150, seed=150)
    before, kda.HEADS = kda.HEADS, 1
    try:
        with jax.default_matmul_precision("highest"):
            got = jax.jit(functools.partial(fused, mm=jnp.float32))(*args)
    finally:
        kda.HEADS = before
    assert rel(got, readings(150, False, "float32", 1)["y"][1]) < 1e-5


def mixer_rule(*args, mm):
    """The rule as a KDA mixer runs it: under the mixer's checkpoint, whose
    policy keeps the states that the forward sweep wrote and the rule's
    output, so that the backward pass reads the kept states and runs no
    forward sweep of its own."""
    def rule(*a):
        return checkpoint_name(fused(*a, mm=mm), KDA_OUT)

    return jax.checkpoint(rule, policy=KEEP_KDA_RULE)(*args)


#: (T, heads a grid cell works, rows of a tiny norm) of the rule under
#: the mixer's checkpoint: one chunk, two, and three with a tail (a count
#: of chunks that ``kda.STRIDE`` does not divide), each beside the readings
#: of the rule without a checkpoint at the same inputs
KEPT = {"one-chunk": (64, 1, True), "two-chunks": (128, 1, False),
        "three-chunks-and-a-tail": (150, 1, False)}


def kept_values(t, heads, tiny):
    """{name: the value or gradient} of the rule under the mixer's
    checkpoint, at ``readings(t, False, "float32", heads, tiny)``'s
    inputs."""
    args, cot = flat_inputs(t, seed=t, tiny=tiny)
    before, kda.HEADS = kda.HEADS, heads
    try:
        with jax.default_matmul_precision("highest"):
            return value_and_grads(
                functools.partial(mixer_rule, mm=jnp.dtype("float32")),
                args, cot)
    finally:
        kda.HEADS = before


kept_readings = functools.lru_cache(maxsize=None)(kept_values)


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("case", list(KEPT))
def test_the_mixers_checkpoint_gives_what_the_rule_gives(case, name):
    """The value and the seven gradients under the mixer's checkpoint are
    the rule's without one (the same sweeps at the same inputs, the
    states read back from where the forward pass kept them) and the
    composition's."""
    t, heads, tiny = KEPT[case]
    got = kept_readings(t, heads, tiny)[name]
    plain, want, _ = readings(t, False, "float32", heads, tiny)[name]
    assert got.shape == want.shape
    assert float(jnp.linalg.norm(want)) > 0
    assert rel(got, plain) < 1e-6
    assert rel(got, want) < 1e-5


def test_a_wrong_kept_state_leaves_the_composition(monkeypatch):
    """The backward sweep handed the state kept for the next run of
    ``kda.STRIDE`` chunks in place of its own (the last run the first's,
    zero): the value is the forward pass's and stays right, and every
    gradient leaves the composition's by far more than the limit the
    comparison holds it to. What the checkpoint keeps is what the backward
    sweep's result rests on."""
    backward = kda._backward

    def shifted(q, k, v, g, beta, gate, o_norm, entered, dy, **static):
        return backward(q, k, v, g, beta, gate, o_norm,
                        jnp.roll(entered, -1, axis=1), dy, **static)

    monkeypatch.setattr(kda, "_backward", shifted)
    got = kept_values(150, 1, False)
    want = {n: r[1] for n, r in readings(150, False, "float32", 1).items()}
    assert rel(got["y"], want["y"]) < 1e-5
    for name in NAMES[1:]:
        assert rel(got[name], want[name]) > 1e-3, name


SHAPES = {
    # name: (H, K, V, chunk) -> the form
    "the-cell": ((32, 128, 128, 64), "pallas"),
    "the-tests": ((2, 128, 128, 64), "pallas"),
    "keys-of-256": ((4, 256, 128, 64), "pallas"),
    "chunks-of-16": ((2, 128, 128, 16), "pallas"),
    "the-toy-widths": ((3, 16, 8, 64), "xla"),
    "keys-of-64": ((2, 64, 128, 64), "xla"),
    "values-of-192": ((2, 128, 192, 64), "xla"),
}


@pytest.mark.parametrize("name", SHAPES)
def test_the_shapes_choose_the_form_and_the_choice_is_booked(name):
    from paddlebox_tpu.obs import MemorySink
    from paddlebox_tpu.obs.hub import get_hub, reset_hub
    (h, kd, vd, chunk), impl = SHAPES[name]
    reset_hub()
    hub = get_hub()
    hub.add_sink(MemorySink())
    try:
        shapes = [jax.ShapeDtypeStruct(s, jnp.float32) for s in (
            (1, 100, h * kd), (1, 100, h * kd), (1, 100, h * vd),
            (1, 100, h * kd), (1, 100, h), (1, 100, h * vd), (vd,))]
        jaxpr = jax.make_jaxpr(lambda *a: kda_normed(
            *a, eps=EPS, chunk=chunk, mm_dtype=jnp.bfloat16))(*shapes)
        kernels = [e for e in outer_eqns(jaxpr.jaxpr)
                   if e.primitive.name == "pallas_call"]
        assert len(kernels) == (impl == "pallas"), name
        c = hub.counter("pbox_kernel_dispatch_total")
        other = {"pallas": "xla", "xla": "pallas"}[impl]
        assert c.value(kernel="kda_scan", impl=impl) == 1
        assert c.value(kernel="kda_scan", impl=other) == 0
    finally:
        reset_hub()
