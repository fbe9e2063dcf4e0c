"""MoE gate/dispatch tests (reference:
python/paddle/incubate/distributed/models/moe — naive/switch/gshard gates,
alltoall dispatch), run on the virtual 8-device CPU mesh."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddlebox_tpu.parallel.moe import (moe_forward_local,
                                        moe_forward_sharded, naive_gating,
                                        top1_gating, top2_gating)


def expert_identity_scale(x, scale):
    # x: [C, D]; scale: scalar per expert
    return x * scale


def test_top1_gating_routes_and_caps():
    # 4 tokens all preferring expert 1, capacity 2 → 2 dropped
    logits = jnp.array([[0.0, 5.0]] * 4)
    disp, comb, aux, metrics = top1_gating(logits, capacity=2)
    assert disp.shape == (4, 2, 2)
    assert float(metrics["dropped"]) == 2.0
    # kept tokens occupy distinct capacity slots of expert 1
    kept = np.asarray(disp[:, 1, :]).sum(axis=0)
    np.testing.assert_array_equal(kept, [1.0, 1.0])
    assert float(aux) > 0


def test_top2_gating_weights_sum_to_one():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(16, 4)).astype(np.float32))
    disp, comb, aux, _ = top2_gating(logits, capacity=16)
    # with ample capacity every token keeps both choices; combine weights
    # per token sum to 1
    w = np.asarray(comb).sum(axis=(1, 2))
    np.testing.assert_allclose(w, 1.0, rtol=1e-5)


def test_naive_gate_no_drops_no_aux():
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(size=(8, 4)).astype(np.float32))
    disp, comb, aux, metrics = naive_gating(logits)
    assert float(aux) == 0.0
    assert float(metrics["dropped"]) == 0.0


def test_moe_local_identity_experts_reconstruct():
    """With identity experts (scale=1) and ample capacity, MoE output ==
    input (combine weights sum to 1)."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(8, 6)).astype(np.float32))
    gate_w = jnp.asarray(rng.normal(size=(6, 4)).astype(np.float32))
    scales = jnp.ones((4,))
    y, aux = moe_forward_local(x, gate_w, expert_identity_scale, scales,
                               capacity=8, gate="gshard")
    np.testing.assert_allclose(np.asarray(y), np.asarray(x), rtol=1e-5)


def test_moe_sharded_matches_local():
    if len(jax.devices()) < 4:
        pytest.skip("needs >=4 devices")
    from jax.sharding import Mesh

    n = 4
    mesh = Mesh(np.array(jax.devices()[:n]), ("ep",))
    E, D, T = 8, 6, 16
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(T, D)).astype(np.float32))
    gate_w = jnp.asarray(rng.normal(size=(D, E)).astype(np.float32))
    scales = jnp.arange(1.0, E + 1.0)

    # drop-free capacities so per-shard routing equals global routing:
    # local sees all T tokens, each shard sees T/n
    y_local, aux_local = moe_forward_local(
        x, gate_w, expert_identity_scale, scales, capacity=T, gate="switch")

    fwd = moe_forward_sharded(mesh, "ep", expert_identity_scale,
                              capacity=T // n, gate="switch")
    with mesh:
        y_sh, aux_sh = jax.jit(fwd)(x, gate_w, scales)
    np.testing.assert_allclose(np.asarray(y_sh), np.asarray(y_local),
                               rtol=1e-4, atol=1e-5)
    assert np.isfinite(float(aux_sh))


# ---- routed_experts: a token's row moves as whole lane-rows -------------------

def _rows_case(d, gated, empty):
    """96 tokens (blocks of 32, so runs of several blocks), 3 choices of 8
    experts, experts 2..5 held; ``empty``: no token chooses expert 3."""
    from paddlebox_tpu.parallel.moe import route_top_k
    n, f, experts = 96, 48, 8
    ks = jax.random.split(jax.random.PRNGKey(d + 2 * gated + 4 * empty), 7)
    x = jax.random.normal(ks[0], (n, d))
    bias = jnp.zeros(experts).at[3].set(-1e9 if empty else 0.0)
    idx, w = route_top_k(x, jax.random.normal(ks[1], (d, experts)), bias,
                         3, 1.0)
    held = (2, 6)
    up, gate, down = (jax.random.normal(k, s) * 0.1 for k, s in zip(
        ks[2:5], ((4, d, f), (4, d, f), (4, f, d))))
    return x, idx, w, up, gate if gated else None, down, held, ks[5]


@pytest.mark.parametrize("d,gated,empty", [
    (256, False, False), (256, True, False), (200, False, False),
    (200, True, False), (256, True, True)],
    ids=["lanes-2-relu2", "lanes-2-swiglu", "d200-relu2", "d200-swiglu",
         "an-expert-empty"])
def test_tile_rows_give_the_flat_forms_bits(monkeypatch, d, gated, empty):
    """``routed_experts`` moves token rows in ``_token_rows``' view
    [N, ceil(D / 128), 128]; with the view taken away (the flat [N, D]
    form the loops had before) ``y`` and every gradient are the same to
    the bit: the same adds in the same order, in float32."""
    from paddlebox_tpu.parallel import moe
    x, idx, w, up, gate, down, held, key = _rows_case(d, gated, empty)
    load = np.asarray(jnp.sum(idx[:, :, None] == jnp.arange(*held), (0, 1)))
    assert (load[1] == 0) == empty and load.max() > 32
    cot = jax.random.normal(key, x.shape)

    def run():
        def total(x, w, up, gate, down):
            y, _ = moe.routed_experts(x, idx, w, up, down, held,
                                      mm_dtype=jnp.bfloat16, gate=gate)
            return jnp.sum(y * cot), y
        return jax.grad(total, argnums=(0, 1, 2, 3, 4), has_aux=True)(
            x, w, up, gate, down)

    tiled = run()
    monkeypatch.setattr(moe, "_token_rows", lambda a: a)
    flat = run()
    leaves = jax.tree.leaves(tiled)
    assert len(leaves) == (6 if gated else 5)
    for a, b in zip(leaves, jax.tree.leaves(flat)):
        assert a.shape == b.shape and np.asarray(a).any()
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
