"""The delta rule's kernels (ops/kda.py: Pallas, here in interpret mode)
against the composition they stand beside and against the token
recurrence the benchmark's plain reference runs
(benchmarks/reference/models/kimi_linear.py), at tile widths: K = V =
128, chunks of 64, two heads; values and the gradients of q, k, v, g and
beta. And the shape test that chooses between the two forms."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.reference.models.kimi_linear import recurrence  # noqa: E402
from paddlebox_tpu.ops import kda  # noqa: E402
from paddlebox_tpu.ops.kda import kda_chunked  # noqa: E402
from test_kda import inputs, rel  # noqa: E402
from test_ssd_kernel import outer_eqns  # noqa: E402

CHUNK = 64
NAMES = ("o", "dq", "dk", "dv", "dg", "dbeta")

#: (T, a decay down to -30 a step, heads a grid cell works)
CASES = {"one-chunk": (64, False, 2), "two-chunks-and-a-tail": (150, False, 1),
         "decay-down-to-30-a-step": (150, True, 1)}


def value_and_grads(f, args, cot):
    """The value as the gradient's forward pass computes it (the kernels:
    the sweep that writes the states) and the five gradients."""
    (_, o), grads = jax.jit(jax.value_and_grad(
        lambda *a: (lambda o: (jnp.sum(o * cot), o))(f(*a)),
        argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)
    return dict(zip(NAMES, (o,) + grads))


def composition(*args, mm):
    """The XLA form at any shape: the kernels' oracle."""
    tiles = kda._tiles
    kda._tiles = lambda *shapes: False
    try:
        return kda_chunked(*args, chunk=CHUNK, mm_dtype=mm)
    finally:
        kda._tiles = tiles


@functools.lru_cache(maxsize=None)
def readings(t, strong, mm, heads):
    """{name: (the kernels', the composition's, the recurrence's)} of the
    value and the five gradients, computed once and read by every case.
    ``heads``: a cell of two heads side by side, or a cell a head (the
    interpreter compiles a cell's body once, in seconds a head)."""
    args, cot = inputs(t, seed=t, strong=strong, bsz=1, h=2, kd=128, vd=128)
    mm = jnp.dtype(mm)
    before, kda.HEADS = kda.HEADS, heads
    try:
        with jax.default_matmul_precision("highest"):
            got = value_and_grads(
                lambda *a: kda_chunked(*a, chunk=CHUNK, mm_dtype=mm), args, cot)
            want = value_and_grads(functools.partial(composition, mm=mm),
                                   args, cot)
            ref = value_and_grads(recurrence, args, cot)
    finally:
        kda.HEADS = before
    return {n: (got[n], want[n], ref[n]) for n in NAMES}


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("case", list(CASES))
def test_the_kernels_equal_the_composition_and_the_recurrence(case, name):
    t, strong, heads = CASES[case]
    got, want, ref = readings(t, strong, "float32", heads)[name]
    assert got.shape == want.shape == ref.shape
    assert bool(jnp.all(jnp.isfinite(got)))
    # the log decay's gradient sums products of terms down to e^-1900
    limit = 1e-4 if strong and name == "dg" else 1e-5
    assert rel(got, want) < limit
    assert rel(got, ref) < limit


@pytest.mark.parametrize("name", NAMES)
def test_bfloat16_operands_stay_within_a_rounding(name):
    """The forward sweep rounds to bfloat16 where the composition rounds:
    its value is the composition's to float32's last digits. The backward
    sweep rounds each cotangent where it enters a product, which autodiff
    of the composition does not everywhere: one rounding of bfloat16
    (2^-8) at most. Both stay a rounding from the float32 recurrence."""
    got, want, ref = readings(150, False, "bfloat16", 1)[name]
    assert rel(got, want) < (1e-6 if name == "o" else 1e-2)
    assert rel(got, ref) < 2e-2


def test_the_sweep_that_writes_no_states_computes_the_same():
    """A call that is not differentiated, and the forward pass of a
    ``jax.checkpoint``, run the sweep without its states: the same
    value."""
    args, _ = inputs(150, seed=150, bsz=1, h=2, kd=128, vd=128)
    before, kda.HEADS = kda.HEADS, 1
    try:
        with jax.default_matmul_precision("highest"):
            got = jax.jit(lambda *a: kda_chunked(
                *a, chunk=CHUNK, mm_dtype=jnp.float32))(*args)
    finally:
        kda.HEADS = before
    assert rel(got, readings(150, False, "float32", 1)["o"][1]) < 1e-5


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
            (1, 100, h, kd), (1, 100, h, kd), (1, 100, h, vd),
            (1, 100, h, kd), (1, 100, h))]
        jaxpr = jax.make_jaxpr(lambda *a: kda_chunked(
            *a, chunk=chunk, mm_dtype=jnp.bfloat16))(*shapes)
        kernels = [e for e in outer_eqns(jaxpr.jaxpr)
                   if e.primitive.name == "pallas_call"]
        assert len(kernels) == (impl == "pallas"), name
        c = hub.counter("pbox_kernel_dispatch_total")
        other = {"pallas": "xla", "xla": "pallas"}[impl]
        assert c.value(kernel="kda_scan", impl=impl) == 1
        assert c.value(kernel="kda_scan", impl=other) == 0
    finally:
        reset_hub()
