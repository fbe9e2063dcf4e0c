"""DeepFM (Guo et al. 2017) over PaddleBox's pooled pull: first order
(the features' 1-dim weights + a linear layer on the dense input), FM
second order over the features' factors, and a ReLU tower over [pooled
CVM statistics and embeddings | dense]. The tower computes in
``tower_dtype``; the first-order layer and the tower's last layer are
float32 layers at the TPU's default matmul precision, as the
configuration's ``precision`` states."""

import jax
import jax.numpy as jnp

from benchmarks.reference.ctr import (default_dense, lowp_dense,
                                      straight_through)
from benchmarks.reference.models import dense_layer

#: cotangents stay in the precision the configurations state
GRAD_DTYPE = "bfloat16"
#: what the operands of those float32 layers' products are rounded to
#: (one bfloat16 pass); the CPU tests set None (a CPU's default precision
#: does not round them, so there the program does not either)
F32_MATMUL_OPERANDS = "bfloat16"


def init(key, num_slots: int, mf_dim: int, dense_dim: int, args: dict):
    hidden = list(args["hidden"])
    width = num_slots * (3 + mf_dim) + dense_dim
    ks = jax.random.split(key, len(hidden) + 2)
    p = {"Dense_0": dense_layer(ks[0], dense_dim, 1)}
    fan_in = width
    for i, h in enumerate(hidden):
        p[f"Dense_{i + 1}"] = dense_layer(ks[i + 1], fan_in, h)
        fan_in = h
    p[f"Dense_{len(hidden) + 1}"] = dense_layer(ks[-1], fan_in, 1)
    return {"params": p}


def forward(params, pooled, dense, tower_dtype=None):
    p = params["params"]
    b = pooled.shape[0]
    mm = None if tower_dtype is None else F32_MATMUL_OPERANDS
    wide = pooled[..., 2]
    vecs = pooled[..., 3:]
    lin = p["Dense_0"]
    first = jnp.sum(wide, axis=1) + default_dense(
        dense, lin["kernel"], lin["bias"], mm)[:, 0]
    fm = 0.5 * jnp.sum(jnp.square(jnp.sum(vecs, axis=1))
                       - jnp.sum(jnp.square(vecs), axis=1), axis=1)
    x = straight_through(
        jnp.concatenate([pooled.reshape(b, -1), dense], axis=1),
        tower_dtype)
    n = len(p)
    for i in range(1, n - 1):
        lay = p[f"Dense_{i}"]
        x = jax.nn.relu(lowp_dense(x, lay["kernel"], lay["bias"],
                                   tower_dtype, GRAD_DTYPE))
    last = p[f"Dense_{n - 1}"]
    deep = default_dense(x, last["kernel"], last["bias"], mm)[:, 0]
    return first + fm + deep
