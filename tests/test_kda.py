"""The chunked delta rule with a decay a key channel (ops/kda.py) against
the token recurrence the benchmark's plain reference runs
(benchmarks/reference/models/kimi_linear.py), at toy widths on the CPU:
values and the gradients of q, k, v, g and beta, at lengths that are no
multiple of the chunk, at several chunk sizes, and at a decay strong
enough that the factored form ``exp(G_r) exp(-G_s)`` would overflow."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.reference.models.kimi_linear import recurrence  # noqa: E402
from paddlebox_tpu.ops import kda  # noqa: E402
from paddlebox_tpu.ops.kda import kda_chunked  # noqa: E402
from test_nemotron_h import (f32, highest_precision,  # noqa: E402,F401
                             rel)


def inputs(t, seed=0, strong=False, bsz=2, h=3, kd=16, vd=8):
    """q, k L2-normed over the head (q scaled by K^-0.5) as the model
    hands them; a log decay in [-e^0.5, -e^-5] a channel, or with
    ``strong`` down to -30 a step; beta in (0, 1)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(jax.random.normal(ks[0], (bsz, t, h, kd))) * kd ** -0.5
    k = unit(jax.random.normal(ks[1], (bsz, t, h, kd)))
    v = jax.random.normal(ks[2], (bsz, t, h, vd))
    if strong:
        g = -30.0 * jax.random.uniform(ks[3], (bsz, t, h, kd))
    else:
        g = -jnp.exp(jax.random.uniform(ks[3], (bsz, t, h, kd),
                                        minval=-5.0, maxval=0.5))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (bsz, t, h)))
    cot = jax.random.normal(ks[5], (bsz, t, h, vd))
    return (q, k, v, g, beta), cot


def grads(fn, args, cot):
    return jax.jit(jax.grad(lambda *a: jnp.sum(fn(*a) * cot),
                            argnums=(0, 1, 2, 3, 4)))(*args)


#: (T, chunk, strong decay)
CASES = {"one-chunk": (64, 64, False), "a-chunk-and-a-tail": (100, 64, False),
         "under-a-chunk": (24, 64, False),
         "two-groups-of-chunks": (3 * 64 + 5, 64, False),
         "chunks-of-16": (70, 16, False), "chunks-of-32": (70, 32, False),
         "decay-down-to-30-a-step": (200, 64, True)}


@f32
@pytest.mark.parametrize("case", list(CASES))
def test_chunked_rule_equals_the_token_recurrence(monkeypatch, case):
    t, chunk, strong = CASES[case]
    # groups of two chunks: the loop over groups runs more than one trip,
    # the last one partly padding, at a length the CPU runs in seconds
    monkeypatch.setattr(kda, "GROUP", 2)
    args, cot = inputs(t, seed=t + chunk, strong=strong)

    def ours(*a):
        return kda_chunked(*a, chunk=chunk, mm_dtype=jnp.float32)

    got, want = jax.jit(ours)(*args), jax.jit(recurrence)(*args)
    assert got.shape == want.shape == (2, t, 3, 8)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert rel(got, want) < 1e-5
    for name, g, r in zip(("q", "k", "v", "g", "beta"), grads(ours, args, cot),
                          grads(recurrence, args, cot)):
        assert bool(jnp.all(jnp.isfinite(g))), name
        assert float(jnp.linalg.norm(r)) > 0, name
        # the log decay's gradient sums products of terms down to e^-1900
        assert rel(g, r) < (1e-4 if strong and name == "g" else 1e-5), name


@f32
def test_the_strong_decay_would_overflow_a_factored_chunk():
    """What the strong case guards: over a chunk of 64 the accumulated
    decay passes -88, so ``exp(-G)`` is inf in float32, and rebasing is
    what keeps the op finite; the state then forgets within a step, so
    o_t is nearly beta_t v_t k_t.q_t."""
    (q, k, v, g, beta), _ = inputs(200, strong=True)
    acc = jnp.cumsum(g[:, :64], axis=1)
    assert float(jnp.min(acc)) < -88
    assert not bool(jnp.all(jnp.isfinite(jnp.exp(-acc))))
    got = kda_chunked(q, k, v, g, beta, mm_dtype=jnp.float32)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert float(jnp.max(jnp.abs(got))) < 10


@f32
def test_the_chunk_does_not_change_the_result():
    args, _ = inputs(160, seed=4)
    outs = [kda_chunked(*args, chunk=c, mm_dtype=jnp.float32)
            for c in (16, 32, 64, 80)]
    for o in outs[1:]:
        assert rel(o, outs[0]) < 1e-5
    with pytest.raises(ValueError, match="sub-chunks"):
        kda_chunked(*args, chunk=40)


@f32
def test_bfloat16_operands_stay_within_a_rounding():
    """The stated precision (bfloat16 operands, float32 accumulation and
    state) against the float32 recurrence: about a rounding of a
    bfloat16 operand, no more."""
    args, cot = inputs(130, seed=7)
    got = kda_chunked(*args, mm_dtype=jnp.bfloat16)
    want = recurrence(*args)
    assert 1e-4 < rel(got, want) < 1e-2
    for name, g, r in zip("qkvgb", grads(
            lambda *a: kda_chunked(*a, mm_dtype=jnp.bfloat16), args, cot),
            grads(recurrence, args, cot)):
        assert rel(g, r) < 2e-2, name


@f32
def test_the_inverse_by_blocks_is_the_inverse():
    """``(I + A)^-1`` of a strictly lower A by forward substitution in
    blocks of 16, against the dense inverse, with entries as large as a
    delta rule's (|A_rs| <= 1)."""
    a = jnp.tril(jax.random.uniform(jax.random.PRNGKey(2), (3, 64, 64),
                                    minval=-1.0, maxval=1.0), -1) * 0.3
    want = jnp.linalg.inv(jnp.eye(64) + a)
    got = kda._block_inverse(a, kda.SUB)
    assert rel(got, want) < 1e-5
    np.testing.assert_array_equal(np.triu(np.asarray(got), 1), 0.0)
