"""One file a dense net: ``init(key, num_slots, mf_dim, dense_dim, args)``
makes the float32 weights from the seed, ``forward(params, pooled, dense,
tower_dtype)`` gives the logits. The parameter tree is laid out under the
names flax gives the program's module of the same class, so an entry can
hand the weights to the program leaf for leaf."""


def glorot(key, fan_in: int, fan_out: int):
    import jax
    import jax.numpy as jnp
    lim = (6.0 / (fan_in + fan_out)) ** 0.5
    return jax.random.uniform(key, (fan_in, fan_out), jnp.float32,
                              -lim, lim)


def dense_layer(key, fan_in: int, fan_out: int):
    import jax.numpy as jnp
    return {"kernel": glorot(key, fan_in, fan_out),
            "bias": jnp.zeros((fan_out,), jnp.float32)}
