"""A second family for the tests, as a later PR would bring one: a family
file, an entry kind and a plain reference in one module (the test puts it
under the three names the harness imports). Nothing of it is CTR's: a
record is one token of a sequence, every position has its own label (the
next token), the loss is a per-step mean cross-entropy and no AUC bucket,
no table row is read, and ``work`` counts tokens.

The "program" (``Entry``) trains a bigram net ``logits = E[token] @ W``
by SGD through ``jax.grad``; the reference works the same step out by
hand in float64 numpy.
"""

from __future__ import annotations

import itertools
import time

import numpy as np

from benchmarks import compare

FAULTS = ("state_unchanged",)


# ---- the plain reference net (``reference/models/<name>.py``'s part) ----

def init(key, config: dict):
    import jax
    v, w = int(config["vocab"]), int(config["width"])
    k1, k2 = jax.random.split(key)
    return {"embed": jax.random.normal(k1, (v, w)) * 0.1,
            "out": jax.random.normal(k2, (w, v)) * 0.1}


def forward(params, tokens, precision: str):
    """float64 logits [B, T, V] of ``tokens`` [B, T]; a lower
    ``precision`` rounds the hidden activations."""
    h = np.asarray(params["embed"], np.float64)[tokens]
    if precision != "float32":
        h = h.astype(precision).astype(np.float64)
    return h, h @ np.asarray(params["out"], np.float64)


# ---- the entry kind (``entries/<kind>.py``'s part): the "program" ----

class Entry:
    setup_parts: dict = {}

    def __init__(self, config, traffic, pool, params):
        import jax
        import jax.numpy as jnp
        self.batch = int(traffic["batch_per_chip"])
        self.passes = itertools.cycle(pool)
        self.params = params
        self.losses = []
        lr = float(config["learning_rate"])

        def loss_fn(p, seq):
            logits = p["embed"][seq[:, :-1]] @ p["out"]
            logp = jax.nn.log_softmax(logits)
            picked = jnp.take_along_axis(logp, seq[:, 1:, None], axis=-1)
            return -jnp.mean(picked)

        @jax.jit
        def step(p, seq):
            loss, g = jax.value_and_grad(loss_fn)(p, seq)
            return jax.tree.map(lambda a, b: a - lr * b, p, g), loss
        self.step = step

    def wait(self):
        return next(self.passes)

    def train(self, seqs) -> None:
        for i in range(0, len(seqs), self.batch):
            self.params, loss = self.step(self.params,
                                          seqs[i:i + self.batch])
            self.losses.append(loss)

    def block(self) -> None:
        import jax
        jax.block_until_ready(self.params)

    def counters(self) -> dict:
        return {"builds": 0, "build_s": 0.0, "stage_s": {}}

    def pass_info(self, seqs) -> dict:
        return {"records": int(seqs[:, 1:].size),
                "wire_bytes": int(seqs.nbytes),
                "batches": len(seqs) // self.batch}

    def read_state(self, steps) -> dict:
        import jax
        return {"losses": np.array([float(self.losses[i]) for i in steps]),
                "params": jax.device_get(self.params)}

    def close(self) -> None:
        self.params = None


def build(config, traffic, pool, params, chips):
    return Entry(config, traffic, pool, params)


# ---- the family (``families/<family>.py``'s part) ----

def make_pool(config, traffic, seed, count=None):
    n = int(traffic["pool_size"]) if count is None else count
    shape = (int(traffic["records_per_pass"]), int(config["seq_len"]) + 1)
    return [np.random.default_rng([int(seed), i]).integers(
        0, int(config["vocab"]), shape, dtype=np.int32) for i in range(n)]


def seeded_params(ref_model, config, seed):
    import jax
    return jax.jit(lambda key: ref_model.init(key, config))(
        jax.random.PRNGKey(seed % (2 ** 31 - 1)))


def sample(pool, traffic, seed):
    """Compared: the losses of the first pass's first three steps."""
    return np.arange(3)


def first_pass(entry, pool, traffic, seed):
    t0 = time.perf_counter()
    entry.train(entry.wait())
    entry.block()
    what = sample(pool, traffic, seed)
    return what, entry.read_state(what), {
        "first_pass_s": time.perf_counter() - t0}


def reference_pass(loaded, ref_model, pool, params, chips, what,
                   precision=None, fault=None):
    config, traffic = loaded["config"], loaded["traffic"]
    precision = precision or config["precision"]
    p = {k: np.asarray(v, np.float64) for k, v in params.items()}
    batch, lr = int(traffic["batch_per_chip"]) * chips, \
        float(config["learning_rate"])
    losses = []
    for i in range(0, len(pool[0]), batch):
        seq = pool[0][i:i + batch]
        tok, label = seq[:, :-1], seq[:, 1:]
        h, logits = ref_model.forward(p, tok, precision)
        z = logits - logits.max(-1, keepdims=True)
        prob = np.exp(z) / np.exp(z).sum(-1, keepdims=True)
        losses.append(-np.mean(np.log(np.take_along_axis(
            prob, label[..., None], -1))))
        if fault == "state_unchanged":
            continue
        d = prob.copy()
        np.put_along_axis(d, label[..., None], np.take_along_axis(
            d, label[..., None], -1) - 1.0, -1)
        d /= label.size
        g_embed = np.zeros_like(p["embed"])
        np.add.at(g_embed, tok, d @ p["out"].T)
        p = {"embed": p["embed"] - lr * g_embed,
             "out": p["out"] - lr * np.einsum("btw,btv->wv", h, d)}
    return {"losses": np.array(losses)[what], "params": p}


def numbers(prog_state, ref, init_params, loaded, pool, chips, what):
    return {"loss": float(np.max(np.abs(prog_state["losses"] - ref["losses"])
                                 / ref["losses"])),
            "dparam": compare.worst_leaf_gap(
                compare.tree_sub(prog_state["params"], init_params),
                compare.tree_sub(ref["params"], init_params))}


def diagnostics(prog_state, ref, init_params):
    return {}


def work(config, traffic, chips, param_shapes):
    v, w = int(config["vocab"]), int(config["width"])
    tokens = int(traffic["batch_per_chip"]) * int(config["seq_len"])
    per_token = 3 * 2 * w * v            # the head; the embedding is a gather
    return {"flops": per_token * tokens, "bytes": 3 * 2 * 4 * 2 * v * w,
            "flops_per_example": per_token, "keys_per_example": 1,
            "scopes": {"stub.head": {"flops": per_token * tokens,
                                     "bytes": 3 * 4 * v * w}}}


def control_precision(config):
    return {"float32": "float16"}[config["precision"]]
