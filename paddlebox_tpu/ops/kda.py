"""Kimi Delta Attention's recurrence, chunked, forward and backward: a
gated delta rule whose decay is one value a key channel.

Per head, with q_t, k_t in R^K, v_t in R^V, the log decay g_t in R^K
(every channel <= 0) and beta_t in (0, 1):

    S_t = Diag(exp g_t) S_{t-1}
    S_t = S_t + beta_t k_t (v_t - S_t^T k_t)^T          S [K, V], float32
    o_t = S_t^T q_t

is computed in chunks of ``chunk`` steps (the WY / UT form of
``flash-linear-attention``'s KDA; Kimi Linear report, arXiv:2510.26692).
With G_r the log decay accumulated inside a chunk up to and including
step r (a vector over K), S_0 the state that entered the chunk, and the
value each step writes ``u_s = beta_s (v_s - (Diag(exp g_s) S_{s-1})^T
k_s)``:

    S_r = Diag(exp G_r) S_0 + sum_{s<=r} Diag(exp(G_r - G_s)) k_s u_s^T
    (I + A) U = beta V - beta (exp(G) k) S_0,   A_rs = beta_r k_r.(exp(G_r - G_s) k_s), s < r
    O = (exp(G) q) S_0 + P U,                   P_rs = q_r.(exp(G_r - G_s) k_s), s <= r

so with ``T = (I + A)^-1``, ``W = T beta exp(G) k`` and ``U~ = T beta v``
(no state in them: every chunk at once), a chunk is ``U = U~ - W S_0``,
``O = (exp(G) q) S_0 + P U``, ``S_C = Diag(exp G_C) S_0 + (exp(G_C - G)
k)^T U``: four products a chunk in a ``lax.scan`` that carries the state
in float32 (``_states``).

**Any decay stays finite.** ``exp(G_r - G_s)`` is at most 1, but its
factors ``exp(G_r) exp(-G_s)`` overflow float32 once a chunk's decay
passes -88, which a gate reaches within a few steps. Nothing here
exponentiates a positive number: a chunk's rows are cut into sub-chunks
of ``SUB``, and a pair in different sub-chunks is rebased on the decay
accumulated before the row's sub-chunk, ``R_i``: ``(q_r exp(G_r - R_i))
. (k_s exp(R_i - G_s))``, both exponents <= 0 (a matrix product); a pair
inside one sub-chunk is ``sum_c q_rc k_sc exp(G_rc - G_sc)`` element by
element (the exponent masked to -inf above the diagonal before the exp).
``T`` is found by forward substitution inside each sub-chunk's diagonal
block and block substitution between them, in float32 at the highest
precision.

**Memory.** The pairwise decays ``[C, C, K]`` are never kept: the
``SUB x SUB x K`` terms of the diagonal blocks exist for ``GROUP``
chunks at a time inside a ``jax.checkpoint`` that the backward pass runs
again (``_intra``). Kept for the backward pass are the state at every
chunk boundary (``T / C`` of ``[K, V]`` float32 a head), ``W``, ``U~``,
``exp(G) q``, ``exp(G_C - G) k`` and ``P``: a few times the size of
``k``.

Precision: decays, cumulative sums, the pairwise terms of the diagonal
blocks, ``T`` and the carried state are float32; every other matrix
product (the scores across sub-chunks, ``W``, ``U~`` and the four state
products a chunk) takes ``mm_dtype`` operands (bfloat16) and accumulates
in float32, as ``ops/ssd.py`` does. No document reset: the state runs
through a packed sequence (ROADMAP.md R8).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
#: rows of a sub-chunk: pairs inside one are decayed element by element
SUB = 16
#: chunks of every (sequence, head) whose intra-chunk terms one trip of
#: the loop over chunks computes (and the backward pass recomputes)
GROUP = 8


def kda_chunked(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                beta: jax.Array, chunk: int = 64,
                mm_dtype=jnp.bfloat16) -> jax.Array:
    """q, k [B,T,H,K], v [B,T,H,V], g [B,T,H,K] (log decay, <= 0), beta
    [B,T,H] -> o [B,T,H,V] float32, the state starting at 0. Any T: the
    tail is padded with steps of k = v = g = beta = 0, which leave the
    state as it is. ``chunk`` is a multiple of ``SUB``."""
    bsz, t, h, _ = q.shape
    if chunk % SUB:
        raise ValueError(f"a chunk of {chunk} is no whole number of "
                         f"sub-chunks of {SUB}")
    n = -(-t // chunk)
    groups = -(-n // GROUP)
    pad = groups * GROUP * chunk - t

    def chunked(x):
        """[B,T,H,...] -> [groups, GROUP, B, H, chunk, ...], padded."""
        x = jnp.pad(x.astype(_F32),
                    ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((bsz, groups, GROUP, chunk, h) + x.shape[3:])
        return x.transpose((1, 2, 0, 4, 3) + tuple(range(5, x.ndim)))

    intra = jax.checkpoint(functools.partial(_intra, mm_dtype=mm_dtype))
    parts = jax.lax.map(lambda xs: intra(*xs),
                        tuple(chunked(x) for x in (q, k, v, g, beta)))
    # one run of chunks, in order: [chunks, B, H, ...]
    parts = jax.tree.map(lambda x: x.reshape((groups * GROUP,)
                                             + x.shape[2:]), parts)
    o = _states(parts, v.shape[-1], mm_dtype)       # [chunks,B,H,C,V]
    o = o.transpose(1, 0, 3, 2, 4)                  # [B,chunks,C,H,V]
    return o.reshape(bsz, -1, h, v.shape[-1])[:, :t]


def _cumsum_rows(x):
    """Inclusive sum over the rows (axis -2) of x [..., C, K], float32:
    a product with a triangle of ones at the highest precision."""
    c = x.shape[-2]
    tri = jnp.tril(jnp.ones((c, c), _F32))
    return jnp.einsum("rs,...sk->...rk", tri, x, precision=_HIGHEST)


def _unit_lower_inverse(a):
    """``(I + a)^-1`` of a strictly lower a [..., n, n], float32: forward
    substitution a row at a time (row r is ``e_r - a_r X``, the rows above
    it final; ``a_rs`` is 0 for s >= r)."""
    n = a.shape[-1]
    eye = jnp.eye(n, dtype=_F32)
    x = jnp.broadcast_to(eye, a.shape)
    for r in range(1, n):
        row = eye[r] - jnp.sum(a[..., r, :, None] * x, axis=-2)
        x = x.at[..., r, :].set(row)
    return x


def _block_inverse(a, sub: int):
    """``(I + a)^-1`` of a strictly lower a [..., C, C] by blocks of
    ``sub``: each diagonal block by forward substitution, then block row
    i from the inverse of the rows above it, ``X_i< = -X_ii A_i< X_<<``,
    float32 at the highest precision."""
    c = a.shape[-1]
    ns = c // sub
    blocks = a.reshape(a.shape[:-2] + (ns, sub, ns, sub))
    diag = _unit_lower_inverse(jnp.stack(
        [blocks[..., i, :, i, :] for i in range(ns)], axis=-3))
    lead = diag[..., 0, :, :]
    for i in range(1, ns):
        rows = a[..., i * sub:(i + 1) * sub, :i * sub]
        x_ii = diag[..., i, :, :]
        left = -jnp.einsum("...ab,...bc,...cd->...ad", x_ii, rows, lead,
                           precision=_HIGHEST)
        top = jnp.concatenate(
            [lead, jnp.zeros(lead.shape[:-1] + (sub,), _F32)], axis=-1)
        lead = jnp.concatenate(
            [top, jnp.concatenate([left, x_ii], axis=-1)], axis=-2)
    return lead


def _intra(q, k, v, g, beta, mm_dtype):
    """A group of chunks' terms that need no state: q, k, g [..., C, K],
    v [..., C, V], beta [..., C] -> (W, U~, exp(G) q, exp(G_C - G) k, P,
    exp(G_C)) as the module says, the products' operands in
    ``mm_dtype`` where they meet the state."""
    c, kd = q.shape[-2:]
    ns = c // SUB
    lead = q.shape[:-2]
    gam = _cumsum_rows(g)                                   # [..., C, K]
    gam_s = gam.reshape(lead + (ns, SUB, kd))
    # R_i: the decay accumulated before sub-chunk i's first row
    ref = jnp.concatenate([jnp.zeros(lead + (1, kd), _F32),
                           gam_s[..., :-1, -1, :]], axis=-2)  # [..., ns, K]
    down = jnp.exp(gam_s - ref[..., :, None, :])           # exponent <= 0

    # pairs in different sub-chunks: row r of sub-chunk i against every
    # column s before it, both sides rebased on R_i
    before = jnp.arange(c)[None, :] < SUB * jnp.arange(ns)[:, None]
    up = jnp.exp(jnp.where(before[:, :, None],
                           ref[..., :, None, :] - gam[..., None, :, :],
                           -jnp.inf))                     # [..., ns, C, K]
    cols = (k[..., None, :, :] * up).astype(mm_dtype)

    def across(x):
        rows = (x.reshape(lead + (ns, SUB, kd)) * down).astype(mm_dtype)
        return jnp.einsum("...iak,...ick->...iac", rows, cols,
                          preferred_element_type=_F32).reshape(lead + (c, c))

    # pairs inside one sub-chunk, s <= r, element by element
    incl = jnp.tril(jnp.ones((SUB, SUB), bool))
    dec = jnp.exp(jnp.where(incl[:, :, None],
                            gam_s[..., :, None, :] - gam_s[..., None, :, :],
                            -jnp.inf))                 # [..., ns, SUB, SUB, K]
    k_s = k.reshape(lead + (ns, SUB, kd))
    eye = jnp.eye(ns, dtype=_F32)

    def within(x):
        x_s = x.reshape(lead + (ns, SUB, kd))
        d = jnp.sum(x_s[..., :, None, :] * k_s[..., None, :, :] * dec, -1)
        return (d[..., :, :, None, :] * eye[:, None, :, None]).reshape(
            lead + (c, c))

    p = across(q) + within(q)
    strict = 1.0 - jnp.eye(c, dtype=_F32)
    a = beta[..., :, None] * (across(k) + within(k) * strict)
    tm = _block_inverse(a, SUB).astype(mm_dtype)
    eg = jnp.exp(gam)
    w = jnp.einsum("...rs,...sk->...rk", tm,
                   (k * eg * beta[..., None]).astype(mm_dtype),
                   preferred_element_type=_F32)
    u = jnp.einsum("...rs,...sv->...rv", tm,
                   (v * beta[..., None]).astype(mm_dtype),
                   preferred_element_type=_F32)
    last = gam[..., -1:, :]
    return (w.astype(mm_dtype), u, (q * eg).astype(mm_dtype),
            (k * jnp.exp(last - gam)).astype(mm_dtype),
            p.astype(mm_dtype), jnp.exp(last[..., 0, :]))


def _states(parts, vd: int, mm_dtype):
    """The chunks in order, the state [B, H, K, V] carried in float32:
    ``U = U~ - W S``, ``O = (exp(G) q) S + P U``, ``S <- exp(G_C) S +
    (exp(G_C - G) k)^T U``. Returns O [chunks, B, H, C, V]."""
    w = parts[0]
    s0 = jnp.zeros(w.shape[1:3] + (w.shape[-1], vd), _F32)

    def chunk(s, xs):
        w, u, qg, kc, p, dc = xs
        sm = s.astype(mm_dtype)
        un = u - jnp.einsum("bhck,bhkv->bhcv", w, sm,
                            preferred_element_type=_F32)
        um = un.astype(mm_dtype)
        o = jnp.einsum("bhck,bhkv->bhcv", qg, sm,
                       preferred_element_type=_F32) \
            + jnp.einsum("bhrs,bhsv->bhrv", p, um,
                         preferred_element_type=_F32)
        s = dc[..., :, None] * s + jnp.einsum(
            "bhck,bhcv->bhkv", kc, um, preferred_element_type=_F32)
        return s, o

    _, o = jax.lax.scan(chunk, s0, parts)
    return o
