"""The chunked scan's fused kernels (ops/ssd.py: Pallas, here in interpret
mode) against the composition they replace at tiling shapes and against
the step-by-step recurrence; the shape test that chooses between the two;
and the mechanism's own guard: the gradient's program keeps nothing the
size of the Q x Q tiles outside the kernels."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.reference.models import nemotron_h as ref  # noqa: E402
from paddlebox_tpu.ops import ssd  # noqa: E402
from paddlebox_tpu.ops.ssd import ssd_scan  # noqa: E402

#: a group of eight heads of 64 is four 128-lane tiles, as in the cell
#: (there: 64 heads, 8 groups, 8,192 steps)
B, H, P, G, N, CHUNK = 2, 16, 64, 2, 128, 128
NAMES = ("y", "dx", "ddt", "da", "db", "dc")


def rel(a, b):
    return float(jnp.linalg.norm(jnp.asarray(a, jnp.float32)
                                 - jnp.asarray(b, jnp.float32))
                 / jnp.maximum(jnp.linalg.norm(jnp.asarray(b, jnp.float32)),
                               1e-30))


def inputs(t, h=H, p=P, g=G, n=N):
    ks = jax.random.split(jax.random.PRNGKey(t), 6)
    x = jax.random.normal(ks[0], (B, t, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, t, h)) - 2.0)
    a = -jnp.exp(jax.random.normal(ks[2], (h,)))
    b = jax.random.normal(ks[3], (B, t, g, n))
    c = jax.random.normal(ks[4], (B, t, g, n))
    w = jax.random.normal(ks[5], (B, t, h, p))
    return (x, dt, a, b, c), w


def composed(x, dt, a, b, c, mm):
    """Today's composition at any T (``ssd_scan``'s own padding)."""
    t = x.shape[1]
    x, dt, b, c = (jnp.pad(v, ((0, 0), (0, -t % CHUNK))
                           + ((0, 0),) * (v.ndim - 2))
                   for v in (x, dt, b, c))
    return ssd._scan_composed(x, dt, a, b, c, CHUNK, mm)[:, :t]


def value_and_grads(f, args, w):
    y, g = jax.value_and_grad(lambda *v: jnp.sum(f(*v) * w),
                              argnums=(0, 1, 2, 3, 4))(*args)
    del y
    return dict(zip(NAMES, (f(*args),) + g))


@functools.lru_cache(maxsize=None)
def both_forms(t, mm):
    """{name: (kernels', composition's)} of the value and the five
    gradients, computed once a (T, operands) and read by every case."""
    args, w = inputs(t)
    mm = jnp.dtype(mm)
    with jax.default_matmul_precision("highest"):
        got = value_and_grads(
            lambda *v: ssd_scan(*v, chunk=CHUNK, mm_dtype=mm), args, w)
        want = value_and_grads(lambda *v: composed(*v, mm), args, w)
    return {k: (got[k], want[k]) for k in NAMES}


#: float32 operands: the two forms are the same sums in another order.
#: bfloat16: the forward pass rounds where the composition rounds, but its
#: cumulative sums are a triangle's product where the composition's are a
#: ``cumsum``: a float32 rounding apart, which turns a bfloat16 rounding
#: of ``m`` here and there; the backward pass rounds each cotangent where
#: it enters a product, which the composition's (autodiff's float32
#: cotangents) does not: one rounding of bfloat16 (2^-8) on the gradients
LIMIT = {("float32", False): 1e-5, ("float32", True): 1e-5,
         ("bfloat16", False): 1e-3, ("bfloat16", True): 1e-2}


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("mm", ["float32", "bfloat16"])
@pytest.mark.parametrize("t", [256, 320], ids=["2-chunks", "padded-tail"])
def test_kernels_equal_the_composition(t, mm, name):
    got, want = both_forms(t, mm)[name]
    assert got.shape == want.shape and got.dtype == want.dtype
    assert rel(got, want) < LIMIT[mm, name != "y"], name


@pytest.mark.parametrize("t", [256, 320], ids=["2-chunks", "padded-tail"])
def test_kernels_match_the_step_by_step_recurrence(t):
    args, w = inputs(t)
    k = H // G

    def stepwise(x, dt, a, b, c):
        y, _ = ref.recurrence(x.reshape(B, t, G, k, P),
                              dt.reshape(B, t, G, k), a.reshape(G, k), b, c)
        return y.reshape(B, t, H, P)

    with jax.default_matmul_precision("highest"):
        want = value_and_grads(stepwise, args, w)
    for name in NAMES:
        got = both_forms(t, "float32")[name][0]
        assert rel(got, want[name]) < (1e-5 if name == "y" else 1e-4), name


@pytest.mark.parametrize("mm", ["float32", "bfloat16"])
@pytest.mark.parametrize("form", ["kernels", "composition"])
def test_the_skip_is_added_and_differentiated(form, mm):
    """``skip`` [H] (Mamba's ``D``): ``y + skip x`` and the gradients of
    ``x`` and of ``skip`` itself, in either form, against the sum written
    out around a call without it."""
    t = 256 if form == "kernels" else 24
    h, p, g, n, chunk = (H, P, G, N, CHUNK) if form == "kernels" else (
        8, 8, 2, 16, 8)
    (x, dt, a, b, c), w = inputs(t, h, p, g, n)
    skip = jax.random.normal(jax.random.PRNGKey(5), (h,))
    mm = jnp.dtype(mm)

    def inside(x, skip):
        return ssd_scan(x, dt, a, b, c, chunk=chunk, mm_dtype=mm, skip=skip)

    def around(x, skip):
        return (ssd_scan(x, dt, a, b, c, chunk=chunk, mm_dtype=mm)
                + skip[:, None] * x)

    with jax.default_matmul_precision("highest"):
        got, want = (jax.value_and_grad(
            lambda *v: jnp.sum(f(*v) * w), argnums=(0, 1))(x, skip)
            for f in (inside, around))
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    for u, v in zip(got[1], want[1]):
        assert u.shape == v.shape and rel(u, v) < 1e-5


# ---- which form runs ---------------------------------------------------------

SHAPES = {
    # name: (H, P, G, N, chunk) -> the form
    "the-cell": ((64, 64, 8, 128, 128), "pallas"),
    "the-tests": ((H, P, G, N, CHUNK), "pallas"),
    "chunk-256": ((16, 64, 2, 128, 256), "pallas"),
    "head-128": ((16, 128, 2, 128, 128), "pallas"),
    "head-32": ((8, 32, 1, 128, 128), "pallas"),
    "chunk-8": ((8, 8, 2, 16, 8), "xla"),
    "state-16": ((16, 64, 2, 16, 128), "xla"),
    "state-64": ((16, 64, 2, 64, 128), "xla"),
    "chunk-64": ((16, 64, 2, 128, 64), "xla"),
    "four-heads-of-128-a-group": ((8, 128, 2, 128, 128), "pallas"),
    "four-heads": ((4, 64, 2, 128, 128), "xla"),
    "a-group-of-192-lanes": ((24, 64, 8, 128, 128), "xla"),
    "head-48": ((16, 48, 2, 128, 128), "xla"),
}


@pytest.mark.parametrize("name", SHAPES)
def test_the_shapes_choose_the_form_and_the_choice_is_booked(name):
    from paddlebox_tpu.obs import MemorySink
    from paddlebox_tpu.obs.hub import get_hub, reset_hub
    (h, p, g, n, chunk), impl = SHAPES[name]
    reset_hub()
    hub = get_hub()
    hub.add_sink(MemorySink())
    try:
        shapes = [jax.ShapeDtypeStruct(s, jnp.float32) for s in (
            (1, chunk, h, p), (1, chunk, h), (h,), (1, chunk, g, n),
            (1, chunk, g, n))]
        jaxpr = jax.make_jaxpr(
            lambda *v: ssd_scan(*v, chunk=chunk, mm_dtype=jnp.float32))(
                *shapes)
        kernels = [e for e in outer_eqns(jaxpr.jaxpr)
                   if e.primitive.name == "pallas_call"]
        assert len(kernels) == (impl == "pallas"), name
        c = hub.counter("pbox_kernel_dispatch_total")
        other = {"pallas": "xla", "xla": "pallas"}[impl]
        assert c.value(kernel="ssd_scan", impl=impl) == 1
        assert c.value(kernel="ssd_scan", impl=other) == 0
    finally:
        reset_hub()


def test_a_shape_that_does_not_tile_computes_what_it_did():
    """chunk 8, state 16: the composition, bit for bit."""
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (2, 21, 8, 8))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, 21, 8)))
    a = -jnp.exp(jax.random.normal(ks[2], (8,)))
    b = jax.random.normal(ks[3], (2, 21, 2, 16))
    c = jax.random.normal(ks[4], (2, 21, 2, 16))
    pad = [jnp.pad(v, ((0, 0), (0, 3)) + ((0, 0),) * (v.ndim - 2))
           for v in (x, dt, b, c)]
    want = ssd._scan_composed(pad[0], pad[1], a, pad[2], pad[3], 8,
                              jnp.float32)[:, :21]
    got = ssd_scan(x, dt, a, b, c, chunk=8, mm_dtype=jnp.float32)
    assert jnp.array_equal(got, want)


# ---- the mechanism's guard ---------------------------------------------------

def outer_eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it, but not
    inside a ``pallas_call`` (a kernel's values live in VMEM)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (tuple, list)) else (val,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from outer_eqns(sub)


@pytest.mark.parametrize("checkpointed", [False, True],
                         ids=["plain", "under-a-checkpoint"])
def test_the_gradient_program_keeps_no_tile_outside_the_kernels(checkpointed):
    """At 1,024 steps (8 chunks) of 16 heads: the program of the gradient
    is a forward sweep that writes the states, a backward sweep, and ops
    on per-head vectors. Outside the kernels no value is larger than the
    inputs packed side by side (the states that entered the chunks are as
    large as ``x`` where N = Q), so none holds a Q x Q tile a head a chunk;
    and no loop over the chunks is left to XLA."""
    t = 1024
    args, w = inputs(t)

    def scan(*v):
        return ssd_scan(*v, chunk=CHUNK, mm_dtype=jnp.bfloat16)

    f = jax.checkpoint(scan) if checkpointed else scan
    jaxpr = jax.make_jaxpr(jax.grad(lambda *v: jnp.sum(f(*v) * w),
                                    argnums=(0, 1, 2, 3, 4)))(*args)
    eqns = list(outer_eqns(jaxpr.jaxpr))
    names = [e.primitive.name for e in eqns]
    # the undifferentiated sweep of a checkpoint's forward pass is dead
    # code in the gradient's program: what is left is the rule's two
    assert names.count("pallas_call") == (3 if checkpointed else 2)
    assert "scan" not in names and "while" not in names
    # the largest value the rule may hold: a step's [x | B | C] packed
    packed = B * t * (H * P + 2 * G * N)
    tile = B * (t // CHUNK) * H * CHUNK * CHUNK
    assert tile > packed
    for eqn in eqns:
        for var in eqn.outvars:
            assert var.aval.size <= packed, (eqn.primitive.name,
                                             var.aval.shape)
    saved = [e for e in eqns if e.primitive.name == "pallas_call"]
    entered = [v.aval.shape for e in saved for v in e.outvars
               if len(v.aval.shape) == 4]
    assert entered == [(B, t // CHUNK, N, H * P)]
