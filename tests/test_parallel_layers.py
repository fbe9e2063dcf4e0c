"""TP/PP layers on the 8-device CPU mesh vs dense references
(meta_parallel/parallel_layers semantics)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from paddlebox_tpu.parallel.layers import (
    column_parallel_linear, pipeline_run, row_parallel_linear,
    vocab_parallel_embedding,
)


@pytest.fixture(scope="module")
def mesh():
    devs = np.array(jax.devices()[:8])
    return Mesh(devs, ("mp",))


def test_vocab_parallel_embedding(mesh):
    rng = np.random.default_rng(0)
    vocab, dim = 64, 16
    w = rng.normal(size=(vocab, dim)).astype(np.float32)
    ids = rng.integers(0, vocab, size=(4, 7)).astype(np.int32)

    f = jax.shard_map(
        functools.partial(vocab_parallel_embedding, axis="mp"),
        mesh=mesh, in_specs=(P(), P("mp", None)), out_specs=P())
    got = f(jnp.asarray(ids), jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(got), w[ids], rtol=1e-6)


def test_column_then_row_parallel_mlp(mesh):
    """col(gather=False) → row: the canonical megatron MLP block."""
    rng = np.random.default_rng(1)
    b, din, dh, dout = 8, 12, 32, 6
    x = rng.normal(size=(b, din)).astype(np.float32)
    w1 = rng.normal(size=(din, dh)).astype(np.float32)
    b1 = rng.normal(size=(dh,)).astype(np.float32)
    w2 = rng.normal(size=(dh, dout)).astype(np.float32)
    b2 = rng.normal(size=(dout,)).astype(np.float32)

    def block(x, w1, b1, w2, b2):
        h = column_parallel_linear(x, w1, b1, gather_output=False)
        h = jax.nn.relu(h)
        return row_parallel_linear(h, w2, b2)

    f = jax.shard_map(block, mesh=mesh,
                  in_specs=(P(), P(None, "mp"), P("mp"), P("mp", None), P()),
                  out_specs=P())
    got = f(*map(jnp.asarray, (x, w1, b1, w2, b2)))
    want = np.maximum(x @ w1 + b1, 0) @ w2 + b2
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-5)


def test_column_parallel_gather_output(mesh):
    rng = np.random.default_rng(2)
    x = rng.normal(size=(4, 10)).astype(np.float32)
    w = rng.normal(size=(10, 24)).astype(np.float32)
    f = jax.shard_map(
        functools.partial(column_parallel_linear, gather_output=True),
        mesh=mesh, in_specs=(P(), P(None, "mp")), out_specs=P(),
        check_vma=False)  # all_gather replication isn't statically inferred
    got = f(jnp.asarray(x), jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(got), x @ w, rtol=1e-4,
                               atol=1e-5)


def test_pipeline_matches_sequential():
    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, ("pp",))
    rng = np.random.default_rng(3)
    m, mb, d = 6, 5, 8
    x = rng.normal(size=(m, mb, d)).astype(np.float32)
    # 4 stages, each its own weight
    ws = rng.normal(size=(4, d, d)).astype(np.float32) * 0.5

    def stage(w, a):
        return jnp.tanh(a @ w)

    def run(x_micros, ws_sharded):
        out = pipeline_run(stage, ws_sharded[0], x_micros, axis="pp")
        return jax.lax.psum(out, "pp")  # only last stage is nonzero

    f = jax.shard_map(run, mesh=mesh, in_specs=(P(), P("pp", None, None)),
                  out_specs=P())
    got = f(jnp.asarray(x), jnp.asarray(ws))

    want = x
    for i in range(4):
        want = np.tanh(want @ ws[i])
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-5)


def test_hierarchical_allreduce_matches_flat_psum():
    """2-level [dcn, ici] allreduce (reduce-scatter → DCN sum →
    all-gather; boxps_worker.cc:1217-1234 ladder) must equal a flat psum
    over both axes — exercised on a 2x4 virtual mesh."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from paddlebox_tpu.parallel.mesh import (DCN_AXIS, ICI_AXIS,
                                             hierarchical_allreduce,
                                             make_hierarchical_mesh)
    mesh = make_hierarchical_mesh(n_slices=2)
    assert mesh.shape == {DCN_AXIS: 2, ICI_AXIS: 4}
    rng = np.random.default_rng(0)
    # odd length exercises the pad path (37 % 4 != 0)
    x = rng.normal(size=(8, 37)).astype(np.float32)

    def block(v):
        v = v.reshape(37)
        h = hierarchical_allreduce(v)
        f = jax.lax.psum(jax.lax.psum(v, ICI_AXIS), DCN_AXIS)
        return h[None], f[None]

    h, f = jax.jit(jax.shard_map(
        block, mesh=mesh,
        in_specs=P((DCN_AXIS, ICI_AXIS)),
        out_specs=(P((DCN_AXIS, ICI_AXIS)), P((DCN_AXIS, ICI_AXIS))),
        check_vma=False))(jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(h), np.asarray(f), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(h)[0], x.sum(axis=0), rtol=1e-4)


def test_pipeline_training_matches_sequential():
    """The pipeline must TRAIN, not just infer: several optimizer steps
    through pipeline_train_step must track sequential training of the
    same stacked model on the same data (GPipe is mathematically
    identical to sequential — grads accumulate over microbatches inside
    one step)."""
    import optax
    from paddlebox_tpu.parallel import pipeline_train_step

    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, ("pp",))
    rng = np.random.default_rng(5)
    m, mb, d = 4, 6, 8
    x = rng.normal(size=(m, mb, d)).astype(np.float32)
    y = rng.normal(size=(m, mb, d)).astype(np.float32)
    ws0 = (rng.normal(size=(4, d, d)).astype(np.float32) * 0.3)

    def stage(w, a):
        return jnp.tanh(a @ w)

    def loss_fn(out, y_micros):
        # plain single-device-style loss: pipeline_train_step masks it
        # to the last stage
        return jnp.mean((out - y_micros) ** 2)

    tx = optax.sgd(0.2)

    def train_step(ws_sharded, opt_state, x_micros, y_micros):
        def body(w_local, o_local):
            loss, g = pipeline_train_step(stage, loss_fn, w_local[0],
                                          x_micros, y_micros, axis="pp")
            up, o2 = tx.update(g, o_local, w_local[0])
            return loss, (optax.apply_updates(w_local[0], up)[None], o2)

        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P("pp", None, None), P("pp")),
            out_specs=(P(), (P("pp", None, None), P("pp"))))(
                ws_sharded, opt_state)

    # sequential reference: same model stacked, full-batch mse
    def seq_loss(ws, xx, yy):
        a = xx
        for i in range(4):
            a = jnp.tanh(a @ ws[i])
        return jnp.mean((a - yy) ** 2)

    ws_pipe = jnp.asarray(ws0)
    opt_pipe = jax.vmap(tx.init)(ws_pipe)
    ws_seq = jnp.asarray(ws0)
    opt_seq = tx.init(ws_seq)
    xx = x.reshape(m * mb, d)
    yy = y.reshape(m * mb, d)
    losses_p, losses_s = [], []
    for step in range(5):
        lp, (ws_pipe, opt_pipe) = train_step(ws_pipe, opt_pipe,
                                             jnp.asarray(x),
                                             jnp.asarray(y))
        ls, gs = jax.value_and_grad(seq_loss)(ws_seq, xx, yy)
        up, opt_seq = tx.update(gs, opt_seq, ws_seq)
        ws_seq = optax.apply_updates(ws_seq, up)
        losses_p.append(float(lp))
        losses_s.append(float(ls))
    np.testing.assert_allclose(losses_p, losses_s, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ws_pipe), np.asarray(ws_seq),
                               rtol=1e-4, atol=1e-5)
    assert losses_p[-1] < losses_p[0] * 0.98  # it actually learns
