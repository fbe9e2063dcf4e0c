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

A KDA mixer calls the rule between per-head norms (``kda_normed``):

    y = rms_norm_V(rule(l2(q) K^-1/2, l2(k), v, g, beta)) o_norm sigmoid(gate)

on flat rows ``[B, T, H K]``, the layout its projections and convs write
(a head is a run of K lanes of a step's row). Two forms of it, chosen by
what the shapes show (``_tiles``), booked as
``pbox_kernel_dispatch_total{kernel="kda_scan"}``:

* **The kernels** (``impl="pallas"``; Mosaic on a TPU, interpret mode
  elsewhere) where K and V are whole 128-lane tiles and the chunk whole
  8-row tiles. They take the rows as they are and do every per-head op on
  a head's lane slice in VMEM: a norm is a lane reduction a row, so XLA
  changes no array's layout between the mixer's producers and the
  kernels. A grid cell is one (sequence, block of ``HEADS`` heads,
  chunk); the chunk axis is sequential and each head's state lives in VMEM
  scratch across it, turned (``S^T [V, K]``, float32). Inside a cell, all
  in VMEM: q's and k's L2 norms, ``G`` (a triangle product at the highest
  precision), the scores across sub-chunks (MXU), the pairs inside them
  (element by element, a column at a time), ``T`` (the diagonal blocks'
  substitutions side by side, then block rows), ``W``, ``U~``, ``U``,
  ``O``, the state's update and the gated norm of ``O``. HBM sees q, k,
  v, g, beta and gate read and y written once. The backward pass is
  written by hand (``jax.custom_vjp``): it walks the chunks in reverse
  with the state's cotangent in scratch, recomputes every tile of a chunk
  (``O`` too) from its inputs and the state that entered it, and writes
  dq, dk, dv, dg, dbeta and dgate once, and each grid cell's part of
  o_norm's gradient; ``dA = -T^T dT T^T``, and the cotangent of ``G`` is
  summed to the chunk's end by the triangle turned. **Kept for the
  backward pass**: the inputs, and the float32 state that entered each
  chunk (``T / C`` of ``[V, K]`` a head, 268 MB a sequence of the
  benchmark's cell: 8,192 steps, 32 heads of 128, chunks of 64), written
  by the forward sweep that the rule's forward rule runs; a call that is
  not differentiated, and the forward pass of a ``jax.checkpoint``
  (``optimize_remat``), write none.
* **The composition** (``impl="xla"``) at every other shape (the tests'
  toy widths): the same norms in plain JAX around ``kda_chunked``, the
  rule alone on ``[B, T, H, K]``: ``GROUP`` chunks' intra-chunk terms at
  a time in a ``lax.map`` under a ``jax.checkpoint`` (``_intra``; the
  pairwise decays ``[C, C, K]`` are never kept), then a ``lax.scan`` over
  the chunks that carries the state (``_states``), differentiated by JAX.
  Kept for the backward pass: the state at every chunk boundary, ``W``,
  ``U~``, ``exp(G) q``, ``exp(G_C - G) k`` and ``P``, a few times the
  size of ``k``. It is the kernels' oracle.

Precision, in both forms alike: the norms (q's and k's ``L2_EPS``, the
output's ``eps``) and the gate's sigmoid, decays, cumulative sums, the
pairwise terms inside a sub-chunk, ``T`` and the carried state are
float32. Rounded to ``mm_dtype`` (bfloat16), where they enter a product
that accumulates in float32: the two sides of the scores across
sub-chunks, ``T``, ``k exp(G) beta``, ``v beta``, ``W``, the state, ``U``,
``exp(G) q``, ``exp(G_C - G) k`` and ``P``. In the hand-written backward
pass a cotangent is rounded where it enters a product (dO, which the
gated norm's backward gives in float32, the state's, U's, W's, and the
pair terms' where they meet the scores' sides), and nowhere else; ``T``'s own
cotangent is carried through ``-T^T dT T^T`` in float32 at the highest
precision. No document reset: the state runs through a packed sequence
(ROADMAP.md R8).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddlebox_tpu.obs import trace
from paddlebox_tpu.ops import pallas_kernels as pk

_F32 = jnp.float32
_LANES = 128
_HIGHEST = jax.lax.Precision.HIGHEST
#: rows of a sub-chunk: pairs inside one are decayed element by element
SUB = 16
#: chunks of every (sequence, head) whose intra-chunk terms one trip of
#: the loop over chunks computes (and the backward pass recomputes)
GROUP = 8
#: the most heads one grid cell of the kernels works, side by side: their
#: chains interleave (cell 6's three sweeps a sequence and layer, 45.8 ms
#: on a v5e at 8 against 48.2 at 2: PERF.md, PR 43)
HEADS = 8
#: what the L2 norm of queries and keys adds under its square root
L2_EPS = 1e-6


def _check_chunk(chunk: int) -> None:
    if chunk % SUB:
        raise ValueError(f"a chunk of {chunk} is no whole number of "
                         f"sub-chunks of {SUB}")


def _l2(x):
    """x over its last axis (a head's lanes) to unit length."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def _gated_norm(o, weight, gate, eps: float):
    """o RMS-normed over its last axis (a head's lanes), times the norm's
    weight and ``sigmoid(gate)``."""
    return o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) \
        * weight * jax.nn.sigmoid(gate)


def kda_normed(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
               beta: jax.Array, gate: jax.Array, o_norm: jax.Array, *,
               eps: float, chunk: int = 64, mm_dtype=jnp.bfloat16
               ) -> jax.Array:
    """The rule between a KDA mixer's per-head norms, on flat rows: q, k
    (before their L2 norms) and g (log decay, <= 0) [B, T, H K], v and
    gate [B, T, H V], beta [B, T, H], the output norm's weight o_norm [V]
    -> ``rms_norm(rule(l2(q) K^-1/2, l2(k), v, g, beta), o_norm, eps)
    sigmoid(gate)`` [B, T, H V] float32, the state starting at 0. Any T:
    the kernels' tail is padded with steps of zeros, which leave the state
    as it is. ``chunk`` is a multiple of ``SUB``."""
    bsz, t, h = beta.shape
    kd, vd = q.shape[-1] // h, v.shape[-1] // h
    _check_chunk(chunk)
    if _tiles(kd, vd, chunk):
        pk._book_dispatch("kda_scan", "pallas")
        pad = -t % chunk
        y = _rule_kernels(*(jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
                            for x in (q, k, v, g, beta, gate)),
                          o_norm, chunk, jnp.dtype(mm_dtype), eps)
        return y[:, :t]
    pk._book_dispatch("kda_scan", "xla")

    def heads(x):
        return x.reshape(bsz, t, h, -1)

    o = kda_chunked(_l2(heads(q)) * kd ** -0.5, _l2(heads(k)), heads(v),
                    heads(g), beta, chunk, mm_dtype)
    return _gated_norm(o, o_norm, heads(gate), eps).reshape(bsz, t, h * vd)


def kda_chunked(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                beta: jax.Array, chunk: int = 64,
                mm_dtype=jnp.bfloat16) -> jax.Array:
    """The rule alone, as the composition computes it at any shape: q, k
    [B,T,H,K], v [B,T,H,V], g [B,T,H,K] (log decay, <= 0), beta [B,T,H]
    -> o [B,T,H,V] float32, the state starting at 0. Any T: the tail is
    padded with steps of k = v = g = beta = 0, which leave the state as
    it is. ``chunk`` is a multiple of ``SUB``."""
    bsz, t, h, kd = q.shape
    _check_chunk(chunk)
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


# ---- the kernels -------------------------------------------------------------
#
# A grid cell is one (sequence, block of ``_heads`` heads, chunk); the chunk
# axis is sequential. q, k, g, v, gate and y cross HBM as [B, T, H K] (a
# head is a run of K lanes of a step's row: the model's own layout), beta as
# [B, H / heads, T, heads] (a head is a column of its block). Inside a cell
# every tile is [rows, lanes] with the chunk's steps in the rows; the state
# is held turned, S^T [V, K], so that the decay left of a chunk scales it
# as a row and every product is one of the MXU's three forms.


def _tiles(kd: int, vd: int, chunk: int) -> bool:
    """Whether a chunk's work is whole (8, 128) tiles: K and V whole
    128-lane tiles, the chunk's steps whole 8-row tiles."""
    return kd % _LANES == 0 and vd % _LANES == 0 and chunk % 8 == 0


def _heads(h: int) -> int:
    """Heads a grid cell works: the most, up to ``HEADS``, that divide H."""
    return max(d for d in range(1, min(h, HEADS) + 1) if h % d == 0)


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _pairs(q, k, gam, mm):
    """The chunk's pair terms, float32: P [C, C] (s <= r) and D [C, C]
    (s < r, beta not yet applied), as ``_intra`` makes them, and for each
    sub-chunk after the first what its pairs across sub-chunks were made
    of (the backward pass's operands)."""
    c, kd = q.shape
    row, lane = _iota((SUB, c), 0), _iota((SUB, c), 1)
    sub_row = _iota((SUB, kd), 0)
    step = _iota((c, kd), 0)
    ps, ds, across = [], [], [None]
    for i in range(c // SUB):
        rows = slice(i * SUB, (i + 1) * SUB)
        gi, qi, ki = gam[rows], q[rows], k[rows]
        if i:
            ref = gam[i * SUB - 1:i * SUB]                          # R_i
            down = jnp.exp(gi - ref)
            up = jnp.exp(jnp.where(step < i * SUB, ref - gam, -jnp.inf))
            cols = (k * up).astype(mm)
            rq, rk = (qi * down).astype(mm), (ki * down).astype(mm)
            p, d = pk.mxu_dot(rq, cols, 1, 1), pk.mxu_dot(rk, cols, 1, 1)
            across.append((down, up, cols, rq, rk))
        else:
            p = d = jnp.zeros((SUB, c), _F32)
        # pairs inside the sub-chunk, a column s at a time
        for j in range(SUB):
            s = i * SUB + j
            dec = jnp.exp(jnp.where(sub_row >= j, gi - gam[s:s + 1], -jnp.inf))
            f = k[s:s + 1] * dec
            at = lane == s
            p = jnp.where(at, jnp.sum(qi * f, 1, keepdims=True), p)
            d = jnp.where(at & (row > j), jnp.sum(ki * f, 1, keepdims=True), d)
        ps.append(p)
        ds.append(d)
    return jnp.concatenate(ps, 0), jnp.concatenate(ds, 0), across


def _inverse(a):
    """``(I + a)^-1`` of a strictly lower a [C, C], float32, as
    ``_block_inverse``: each diagonal block by forward substitution a row
    at a time (row r is ``e_r - sum_s a_rs X_s``), the blocks' chains side
    by side, then block row i as ``X_ii (E_i - A_i T)`` from the rows
    above it, at the highest precision."""
    c = a.shape[0]
    ns = c // SUB
    row = _iota((SUB, SUB), 0)
    eye = (row == _iota((SUB, SUB), 1)).astype(_F32)
    turned = [a[i * SUB:(i + 1) * SUB, i * SUB:(i + 1) * SUB].T
              for i in range(ns)]                     # column r is a's row r
    diag = [eye] * ns
    for r in range(1, SUB):
        for i in range(ns):
            x = eye[r:r + 1] - jnp.sum(turned[i][:, r:r + 1] * diag[i], 0,
                                       keepdims=True)
            diag[i] = jnp.where(row == r, x, diag[i])
    eye_c = (_iota((c, c), 0) == _iota((c, c), 1)).astype(_F32)
    blocks = [pk.mxu_dot(diag[0], eye_c[:SUB], 1, 0)]
    for i in range(1, ns):
        rows = slice(i * SUB, (i + 1) * SUB)
        above = jnp.concatenate(blocks + [jnp.zeros((c - i * SUB, c), _F32)],
                                0)
        blocks.append(pk.mxu_dot(
            diag[i], eye_c[rows] - pk.mxu_dot(a[rows], above, 1, 0), 1, 0))
    return jnp.concatenate(blocks, 0)


class _Chunk:
    """A chunk's terms that need no state, as ``_intra`` computes them, for
    one head: q, k, g [C, K], v [C, V], beta [C, 1], all float32."""

    def __init__(self, q, k, v, g, beta, mm):
        c = q.shape[0]
        tri = (_iota((c, c), 0) >= _iota((c, c), 1)).astype(_F32)
        self.q, self.k, self.mm = q, k, mm
        self.gam = pk.mxu_dot(tri, g, 1, 0)
        self.p, self.d, self.across = _pairs(q, k, self.gam, mm)
        self.t = _inverse(beta * self.d)
        self.tm = self.t.astype(mm)
        self.eg = jnp.exp(self.gam)
        self.kb = k * self.eg * beta
        self.kbm, self.vbm = self.kb.astype(mm), (v * beta).astype(mm)
        self.wm = pk.mxu_dot(self.tm, self.kbm, 1, 0).astype(mm)
        self.ut = pk.mxu_dot(self.tm, self.vbm, 1, 0)
        last = self.gam[c - 1:c]
        self.dc = jnp.exp(last)                               # [1, K]
        self.to_end = jnp.exp(last - self.gam)
        self.qgm = (q * self.eg).astype(mm)
        self.kcm = (k * self.to_end).astype(mm)
        self.pm = self.p.astype(mm)

    def written(self, sm):
        """``U = U~ - W S`` [C, V] in ``mm``, from the state that entered
        (turned, [V, K], in ``mm``)."""
        return (self.ut - pk.mxu_dot(self.wm, sm, 1, 1)).astype(self.mm)

    def output(self, sm, um):
        """``O = (exp(G) q) S_0 + P U`` [C, V] float32 from the state that
        entered and U (both in ``mm``)."""
        return pk.mxu_dot(self.qgm, sm, 1, 1) + pk.mxu_dot(self.pm, um, 1, 0)

    def step(self, st):
        """The state that entered, turned ([V, K] float32) -> (O [C, V],
        the state that leaves, turned)."""
        sm = st.astype(self.mm)
        um = self.written(sm)
        return (self.output(sm, um),
                st * self.dc + pk.mxu_dot(um, self.kcm, 0, 0))


def _normed_qk(q_ref, k_ref, kc):
    """A head's q and k [C, K] as the rule takes them: L2-normed, q times
    K^-1/2."""
    kd = kc.stop - kc.start
    return (_l2(q_ref[:, kc].astype(_F32)) * kd ** -0.5,
            _l2(k_ref[:, kc].astype(_F32)))


def _l2_bwd(x, dz):
    """The cotangent of x [C, K] from that of ``_l2(x)``."""
    r = jax.lax.rsqrt(jnp.sum(x * x, 1, keepdims=True) + L2_EPS)
    z = x * r
    return r * (dz - z * jnp.sum(dz * z, 1, keepdims=True))


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, gate_ref, w_ref, y_ref,
                *rest, mm, eps: float, save: bool):
    entered_ref = rest[0] if save else None
    state = rest[-1]                                       # [heads, V, K]
    heads, vd, kd = state.shape
    weight = w_ref[...]                                    # [1, V]

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    for j in range(heads):
        kc, vc = slice(j * kd, (j + 1) * kd), slice(j * vd, (j + 1) * vd)
        st = state[j]
        if save:
            entered_ref[vc, :] = st
        ch = _Chunk(*_normed_qk(q_ref, k_ref, kc),
                    v_ref[:, vc].astype(_F32), g_ref[:, kc].astype(_F32),
                    b_ref[:, j:j + 1].astype(_F32), mm)
        o, state[j] = ch.step(st)
        y_ref[:, vc] = _gated_norm(o, weight, gate_ref[:, vc].astype(_F32),
                                   eps)


def _pairs_bwd(ch, dp, dd):
    """The pair terms' cotangents (dp of P, dd of D, [C, C] float32) ->
    their parts of dq, dk and d(gam), [C, K] each."""
    q, k, gam, mm = ch.q, ch.k, ch.gam, ch.mm
    c, kd = q.shape
    sub_row = _iota((SUB, kd), 0)
    dk_all = jnp.zeros((c, kd), _F32)
    dgam_all = jnp.zeros((c, kd), _F32)
    dqs, dks, dgams = [], [], []
    for i in range(c // SUB):
        rows = slice(i * SUB, (i + 1) * SUB)
        gi, qi, ki = gam[rows], q[rows], k[rows]
        dpi, ddi = dp[rows], dd[rows]
        dqi = dki = dgi = jnp.zeros((SUB, kd), _F32)
        if i:
            down, up, cols, rq, rk = ch.across[i]
            dpm, ddm = dpi.astype(mm), ddi.astype(mm)
            drq, drk = pk.mxu_dot(dpm, cols, 1, 0), pk.mxu_dot(ddm, cols, 1, 0)
            dcols = pk.mxu_dot(dpm, rq, 0, 0) + pk.mxu_dot(ddm, rk, 0, 0)
            dqi, dki = drq * down, drk * down
            t_down = (drq * qi + drk * ki) * down
            t_up = dcols * k * up
            dk_all = dk_all + dcols * up
            dgam_all = dgam_all - t_up
            dgi = t_down
            # R_i is the decay accumulated up to the row before the block
            dref = (jnp.sum(t_up, 0, keepdims=True)
                    - jnp.sum(t_down, 0, keepdims=True))
            dgams[i - 1] = dgams[i - 1] + jnp.where(sub_row == SUB - 1, dref,
                                                    0.0)
        dks_cols = jnp.zeros((SUB, kd), _F32)
        for j in range(SUB):
            s = i * SUB + j
            dec = jnp.exp(jnp.where(sub_row >= j, gi - gam[s:s + 1], -jnp.inf))
            ks = k[s:s + 1]
            f = ks * dec
            pc, dc = dpi[:, s:s + 1], ddi[:, s:s + 1]
            dqi = dqi + pc * f
            dki = dki + dc * f
            h = dec * (pc * qi + dc * ki)
            e = h * ks
            dgi = dgi + e
            # what the column's own step receives, into its row
            dks_cols = jnp.where(sub_row == j, jnp.sum(h, 0, keepdims=True),
                                 dks_cols)
            dgi = dgi - jnp.where(sub_row == j, jnp.sum(e, 0, keepdims=True),
                                  0.0)
        dqs.append(dqi)
        dks.append(dki + dks_cols)
        dgams.append(dgi)
    return (jnp.concatenate(dqs, 0), dk_all + jnp.concatenate(dks, 0),
            dgam_all + jnp.concatenate(dgams, 0))


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, gate_ref, w_ref,
                entered_ref, dy_ref, dq_ref, dk_ref, dv_ref, dg_ref, db_ref,
                dgate_ref, dw_ref, dstate, *, mm, eps: float):
    """One chunk of the reverse walk. ``dstate`` holds the cotangent of the
    state (turned) that LEFT this chunk; it leaves holding that of the
    state that entered. Every tile is recomputed from the inputs and the
    state that entered the chunk. ``dw_ref`` sums the norm weight's
    cotangent over the cell's heads and chunks."""
    heads, vd, kd = dstate.shape
    c = q_ref.shape[0]
    causal = _iota((c, c), 0) >= _iota((c, c), 1)
    strict = _iota((c, c), 0) > _iota((c, c), 1)
    tri = causal.astype(_F32)
    last_row = _iota((c, kd), 0) == c - 1
    weight = w_ref[...]                                    # [1, V]

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    dw = jnp.zeros((1, vd), _F32)
    for j in range(heads):
        kc, vc = slice(j * kd, (j + 1) * kd), slice(j * vd, (j + 1) * vd)
        q, k = _normed_qk(q_ref, k_ref, kc)
        g = g_ref[:, kc].astype(_F32)
        v = v_ref[:, vc].astype(_F32)
        beta = b_ref[:, j:j + 1].astype(_F32)
        ch = _Chunk(q, k, v, g, beta, mm)
        st0 = entered_ref[vc, :]
        sm = st0.astype(mm)
        um = ch.written(sm)
        # y = n weight sigmoid(gate), n = O r, r = (mean O^2 + eps)^-1/2
        o = ch.output(sm, um)
        r = jax.lax.rsqrt(jnp.mean(o * o, 1, keepdims=True) + eps)
        n = o * r
        sg = jax.nn.sigmoid(gate_ref[:, vc].astype(_F32))
        dy = dy_ref[:, vc].astype(_F32)
        dgate_ref[:, vc] = (dy * n * weight * sg * (1.0 - sg)).astype(
            dgate_ref.dtype)
        dw = dw + jnp.sum(dy * n * sg, 0, keepdims=True)
        dn = dy * weight * sg
        do = r * (dn - n * jnp.mean(dn * n, 1, keepdims=True))
        left = dstate[j]                                   # [V, K] float32
        dom, dsm = do.astype(mm), left.astype(mm)
        # O = qg S + P U;  S' = exp(G_C) S + kc^T U;  U = U~ - W S
        dun = pk.mxu_dot(ch.pm, dom, 0, 0) + pk.mxu_dot(ch.kcm, dsm, 1, 1)
        dunm = dun.astype(mm)
        dstate[j] = (pk.mxu_dot(dom, ch.qgm, 0, 0) + left * ch.dc
                     - pk.mxu_dot(dunm, ch.wm, 0, 0))
        dqg = pk.mxu_dot(dom, sm, 1, 0)
        dwm = (-pk.mxu_dot(dunm, sm, 1, 0)).astype(mm)
        dkc = pk.mxu_dot(um, dsm, 1, 0)
        dp = jnp.where(causal, pk.mxu_dot(dom, um, 1, 1), 0.0)
        # W = T kb, U~ = T vb, T = (I + A)^-1: dA = -T^T dT T^T
        dt = pk.mxu_dot(dwm, ch.kbm, 1, 1) + pk.mxu_dot(dunm, ch.vbm, 1, 1)
        dkb = pk.mxu_dot(ch.tm, dwm, 0, 0)
        dvb = pk.mxu_dot(ch.tm, dunm, 0, 0)
        da = jnp.where(strict, -pk.mxu_dot(pk.mxu_dot(ch.t, dt, 0, 0), ch.t,
                                           1, 1), 0.0)
        # A = beta D
        dbeta = jnp.sum(da * ch.d, 1, keepdims=True)
        dq, dk, dgam = _pairs_bwd(ch, dp, beta * da)
        # qg = q exp(G), kb = k exp(G) beta, vb = v beta, kc = k exp(G_C - G)
        dq = dq + dqg * ch.eg
        dk = dk + dkb * ch.eg * beta + dkc * ch.to_end
        t_kc = dkc * k * ch.to_end
        dgam = dgam + dqg * q * ch.eg + dkb * ch.kb - t_kc
        dbeta = dbeta + jnp.sum(dkb * k * ch.eg, 1, keepdims=True) \
            + jnp.sum(dvb * v, 1, keepdims=True)
        ddc = jnp.sum(st0 * left, 0, keepdims=True)
        dlast = jnp.sum(t_kc, 0, keepdims=True) + ddc * ch.dc
        dgam = dgam + jnp.where(last_row, dlast, 0.0)
        # G = cumsum(g) within the chunk: its cotangent sums to the end;
        # q and k through their L2 norms (q's K^-1/2 first)
        dq_ref[:, kc] = _l2_bwd(q_ref[:, kc].astype(_F32),
                                dq * kd ** -0.5).astype(dq_ref.dtype)
        dk_ref[:, kc] = _l2_bwd(k_ref[:, kc].astype(_F32), dk).astype(
            dk_ref.dtype)
        dg_ref[:, kc] = pk.mxu_dot(tri, dgam, 0, 0).astype(dg_ref.dtype)
        dv_ref[:, vc] = (dvb * beta).astype(dv_ref.dtype)
        db_ref[:, j:j + 1] = dbeta.astype(db_ref.dtype)
    dw_ref[...] += dw


def _specs(kd, vd, chunk, heads, step):
    """Block specs of a (sequence, heads, chunk) cell; ``step`` maps the
    grid's last index to the chunk (the reverse walk turns it). ``norm``
    is the output norm's weight [1, V], whole in every cell; ``per_cell``
    one row [1, V] a (sequence, heads) pair, the same block down the
    chunks."""
    wide_k = pl.BlockSpec((None, chunk, heads * kd),
                          lambda s, j, i: (s, step(i), j))
    wide_v = pl.BlockSpec((None, chunk, heads * vd),
                          lambda s, j, i: (s, step(i), j))
    col = pl.BlockSpec((None, None, chunk, heads),
                       lambda s, j, i: (s, j, step(i), 0))
    norm = pl.BlockSpec((1, vd), lambda s, j, i: (0, 0))
    per_cell = pl.BlockSpec((None, None, 1, vd), lambda s, j, i: (s, j, 0, 0))
    entered = pl.BlockSpec((None, None, heads * vd, kd),
                           lambda s, j, i: (s, step(i), j, 0))
    return wide_k, wide_v, col, norm, per_cell, entered


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=100 * 1024 * 1024)


def _by_block(beta, heads: int):
    """beta [B, T, H] -> [B, H / heads, T, heads]."""
    bsz, t, h = beta.shape
    return beta.reshape(bsz, t, h // heads, heads).transpose(0, 2, 1, 3)


_sweep = pk.scoped_sweep(trace.SCOPE_KDA_SCAN)


@_sweep
def _forward(q, k, v, g, beta, gate, o_norm, *, chunk, mm, eps, save: bool):
    bsz, t, h = beta.shape
    kd, vd = q.shape[-1] // h, v.shape[-1] // h
    heads = _heads(h)
    nc = t // chunk
    wide_k, wide_v, col, norm, _, entered = _specs(kd, vd, chunk, heads,
                                                   lambda i: i)
    out_shape = [jax.ShapeDtypeStruct((bsz, t, h * vd), _F32)]
    out_specs = [wide_v]
    if save:
        out_shape.append(jax.ShapeDtypeStruct((bsz, nc, h * vd, kd), _F32))
        out_specs.append(entered)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, mm=mm, eps=eps, save=save),
        grid=(bsz, h // heads, nc),
        in_specs=[wide_k, wide_k, wide_v, wide_k, col, wide_v, norm],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((heads, vd, kd), _F32)],
        compiler_params=_params(), interpret=pk._interpret(),
    )(q, k, v, g, _by_block(beta, heads), gate, o_norm.reshape(1, vd))
    return out[0], (out[1] if save else None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _rule_kernels(q, k, v, g, beta, gate, o_norm, chunk, mm, eps):
    return _forward(q, k, v, g, beta, gate, o_norm, chunk=chunk, mm=mm,
                    eps=eps, save=False)[0]


def _rule_kernels_fwd(q, k, v, g, beta, gate, o_norm, chunk, mm, eps):
    y, entered = _forward(q, k, v, g, beta, gate, o_norm, chunk=chunk, mm=mm,
                          eps=eps, save=True)
    return y, (q, k, v, g, beta, gate, o_norm, entered)


def _rule_kernels_bwd(chunk, mm, eps, res, dy):
    return _backward(*res, dy, chunk=chunk, mm=mm, eps=eps)


@_sweep
def _backward(q, k, v, g, beta, gate, o_norm, entered, dy, *, chunk, mm,
              eps):
    bsz, t, h = beta.shape
    kd, vd = q.shape[-1] // h, v.shape[-1] // h
    heads = _heads(h)
    nc = t // chunk
    wide_k, wide_v, col, norm, per_cell, entered_spec = _specs(
        kd, vd, chunk, heads, lambda i: nc - 1 - i)
    flat_k = jax.ShapeDtypeStruct((bsz, t, h * kd), _F32)
    flat_v = jax.ShapeDtypeStruct((bsz, t, h * vd), _F32)
    dq, dk, dv, dg, db, dgate, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, mm=mm, eps=eps),
        grid=(bsz, h // heads, nc),
        in_specs=[wide_k, wide_k, wide_v, wide_k, col, wide_v, norm,
                  entered_spec, wide_v],
        out_specs=[wide_k, wide_k, wide_v, wide_k, col, wide_v, per_cell],
        out_shape=[flat_k, flat_k, flat_v, flat_k,
                   jax.ShapeDtypeStruct((bsz, h // heads, t, heads), _F32),
                   flat_v,
                   jax.ShapeDtypeStruct((bsz, h // heads, 1, vd), _F32)],
        scratch_shapes=[pltpu.VMEM((heads, vd, kd), _F32)],
        compiler_params=_params(), interpret=pk._interpret(),
    )(q, k, v, g, _by_block(beta, heads), gate, o_norm.reshape(1, vd),
      entered, dy.astype(_F32))
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            dg.astype(g.dtype),
            db.transpose(0, 2, 1, 3).reshape(beta.shape).astype(beta.dtype),
            dgate.astype(gate.dtype),
            jnp.sum(dw, (0, 1, 2)).astype(o_norm.dtype))


# optimize_remat: the forward pass of a ``jax.checkpoint`` around the rule
# (the model's mixers) runs the sweep that writes no states
_rule_kernels.defvjp(_rule_kernels_fwd, _rule_kernels_bwd,
                     optimize_remat=True)
