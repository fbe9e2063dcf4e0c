#!/usr/bin/env python
"""Device key-path cost decomposition — ALL the round-5 probe sets in
one harness (the former profile_keypath{,2,3}.py trio, consolidated).

Loop-shaped probes per DESIGN_NOTES §4h: every probe threads state
through a fori_loop with VARYING indices per iteration — single-shot
probes with repeated identical indices read 100x too fast. Prints one
JSON line per probe: {"probe": ..., "ms_per_iter": ...}. Run on the
real chip (no conftest).

Usage:
    python scripts/profile_keypath.py [--set 1|2|3|all]
                                      [--shape ragged|uniform|thousand]
                                      [--iters N]

Probe sets:
    1  step components: table gather/push, dedup, expand, seqpool
       fwd/bwd, slot-wire decode, dense fwd+bwd, hot-tier gathers
       (the original harness)
    2  grad-merge ordering, gather extract form, push variants (the
       levers left after the slot-wire decode fix)
    3  merge form/dtype, packed-line expand, dedup sort form (the
       levers left after the decode + gather-extract fixes)
    kernels  the Pallas embed-pool-CVM family vs the XLA composition
       (ISSUE 12): gather, pool+CVM forward, full fused fwd+bwd — one
       JSON row per probe, and with ``--record`` higher-is-better
       ``kernel.{gather,pool_cvm,fused}.{shape}.{backend}`` rows
       appended to BENCH_trajectory.json for scripts/perf_gate.py
       (--check --ignore-live gates them; interpret-mode CPU rows key
       separately from real-TPU rows via the backend suffix). When a
       trace span sink is attached each probe re-runs once inside a
       ``kernel.*`` span on the ``device.kernels`` lane.
    index  the device-resident key index (ISSUE 19): open-addressing
       insert / lookup / first-seen dedup over RAW 64-bit feature ids,
       device (Pallas/XLA) vs host (python oracle, native C, host kv) —
       with ``--record``, ``kernel.index.*.{shape}.{backend}`` raw
       keys/s rows append the same way.

``PROF_ITERS`` / ``PROF_SHAPE`` env vars keep working (CLI wins).
Sets 2 and 3 probe the ragged shape regardless of --shape (their
question is merge/extract form at the ragged working point).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

MF = 8
CAP = 1 << 23


def shape_dims(shape: str):
    """(B, S, AVG, VOCAB) for a bench shape name."""
    if shape == "ragged":
        return 4096, 26, 5.0, 100_000
    if shape == "thousand":
        return 512, 1000, 1.0, 4_000
    return 8192, 26, 1.0, 100_000


def make_timeit(n_iter: int, fetch_val: bool = False):
    """Warmup call + wall-timed second call / n_iter. ``fetch_val``
    device_gets the result (sets 2/3's anti-DCE discipline) instead of
    block_until_ready."""

    def timeit(name, fn, *args, **extra):
        r = fn(*args)
        if fetch_val:
            v = np.asarray(jax.device_get(r)).ravel()
        else:
            jax.block_until_ready(r)
        t0 = time.perf_counter()
        r = fn(*args)
        if fetch_val:
            v = np.asarray(jax.device_get(r)).ravel()
            extra["val"] = float(v[0])
        else:
            jax.block_until_ready(r)
        dt = (time.perf_counter() - t0) / n_iter * 1000
        print(json.dumps({"probe": name, "ms_per_iter": round(dt, 3),
                          **extra}), flush=True)
        return dt

    return timeit


def _ragged_rows(rng, n_iter, counts, k, k_pad, s, vocab):
    """Per-iteration key rows: slot-partitioned draws mapped into slot
    arenas; pads → the CAP sentinel."""
    slot_of_key = np.repeat(np.tile(np.arange(s), counts.shape[0]),
                            counts.reshape(-1))
    out = np.empty((n_iter, k_pad), np.int32)
    for i in range(n_iter):
        k_ids = rng.integers(0, vocab, size=k)
        out[i, :k] = (slot_of_key * vocab + k_ids).astype(np.int32) % CAP
        out[i, k:] = CAP
    return out, slot_of_key


def run_set1(shape: str, n_iter: int) -> None:
    from paddlebox_tpu.ops.device_unique import dedup_rows
    from paddlebox_tpu.ops.pallas_kernels import segment_sum
    from paddlebox_tpu.ps.sgd import SparseSGDConfig, opt_ext_width
    from paddlebox_tpu.ps.table import (TableState, apply_push,
                                        gather_full_rows,
                                        init_table_state,
                                        next_bucket_fine)

    timeit = make_timeit(n_iter)
    b, s, avg, vocab = shape_dims(shape)
    cfg = SparseSGDConfig(mf_create_thresholds=0.0, mf_initial_range=1e-3)
    ext = opt_ext_width(cfg, MF)
    feat = 8 + MF + ext

    rng = np.random.default_rng(0)
    if avg > 1.0:
        counts = 1 + rng.poisson(avg - 1.0, size=(b, s))
    else:
        counts = np.ones((b, s), np.int64)
    k = int(counts.sum())
    k_pad = next_bucket_fine(4096, k)
    rows_np, _ = _ragged_rows(rng, n_iter, counts, k, k_pad, s, vocab)
    rows_stack = jnp.asarray(rows_np)
    # segments per key: record*S + slot
    rec_of_key = np.repeat(np.arange(b, dtype=np.int32),
                           counts.sum(axis=1))
    slot_flat = np.repeat(np.tile(np.arange(s, dtype=np.int32), b),
                          counts.reshape(-1))
    segs_np = np.full(k_pad, b * s, np.int32)
    segs_np[:k] = rec_of_key * s + slot_flat
    segs = jnp.asarray(segs_np)

    # unique-rows stacks: dedup each iteration's rows on host
    uniqs, u_max = [], 0
    for i in range(n_iter):
        u = np.unique(rows_np[i][:k])
        uniqs.append(u)
        u_max = max(u_max, len(u))
    u_pad = next_bucket_fine(4096, u_max + 1)
    uniq_np = np.empty((n_iter, u_pad), np.int32)
    for i, u in enumerate(uniqs):
        uniq_np[i, :len(u)] = u
        uniq_np[i, len(u):] = CAP + 1 + np.arange(u_pad - len(u))
    uniq_stack = jnp.asarray(uniq_np)

    state = init_table_state(CAP, MF, ext=ext)
    grads = jnp.asarray(
        rng.normal(size=(u_pad, 3 + MF)).astype(np.float32))
    vals_k = jnp.asarray(
        rng.normal(size=(k_pad, 3 + MF)).astype(np.float32))
    prng = jax.random.PRNGKey(0)

    print(json.dumps({"probe": "shape", "B": b, "S": s, "K": k,
                      "K_pad": k_pad, "U": u_max, "U_pad": u_pad}),
          flush=True)

    @jax.jit
    def p_gather(state, uniq_stack):
        def body(i, acc):
            rows = gather_full_rows(state, uniq_stack[i])
            return acc + rows[0, 0] + rows[-1, -1]
        return jax.lax.fori_loop(0, n_iter, body, jnp.zeros(()))

    timeit("gather_U_big", p_gather, state, uniq_stack, U_pad=u_pad)

    @jax.jit
    def p_push(state, uniq_stack, grads, prng):
        def body(i, st):
            return apply_push(st, uniq_stack[i], grads, cfg, prng)
        return jax.lax.fori_loop(0, n_iter, body, state).packed[0, 0]

    timeit("push_U", p_push, state, uniq_stack, grads, prng, U_pad=u_pad)

    @jax.jit
    def p_dedup(rows_stack):
        def body(i, acc):
            u, g = dedup_rows(rows_stack[i], CAP)
            return acc + u[0] + g[-1]
        return jax.lax.fori_loop(0, n_iter, body,
                                 jnp.zeros((), jnp.int32))

    timeit("dedup_rows_K", p_dedup, rows_stack, K_pad=k_pad)

    gidx_np = rng.integers(0, u_max, size=(n_iter, k_pad)) \
        .astype(np.int32)
    gidx_stack = jnp.asarray(gidx_np)
    vals_u = jnp.asarray(
        rng.normal(size=(u_pad, 3 + MF)).astype(np.float32))

    @jax.jit
    def p_expand(vals_u, gidx_stack):
        def body(i, acc):
            v = vals_u[gidx_stack[i]]
            return acc + v[0, 0] + v[-1, -1]
        return jax.lax.fori_loop(0, n_iter, body, jnp.zeros(()))

    timeit("expand_K_from_U", p_expand, vals_u, gidx_stack)

    @jax.jit
    def p_segsum(vals_k, segs):
        def body(i, acc):
            pooled = segment_sum(vals_k * (1.0 + acc), segs,
                                 num_segments=b * s + 1)
            return acc + pooled[0, 0] + pooled[-1, -1]
        return jax.lax.fori_loop(0, n_iter, body, jnp.zeros(()))

    timeit("segsum_K", p_segsum, vals_k, segs)

    pooled_g = jnp.asarray(
        rng.normal(size=(b * s + 1, 3 + MF)).astype(np.float32))

    @jax.jit
    def p_seg_bwd(pooled_g, segs):
        def body(i, acc):
            v = pooled_g[segs] * (1.0 + acc)
            return acc + v[0, 0] + v[-1, -1]
        return jax.lax.fori_loop(0, n_iter, body, jnp.zeros(()))

    timeit("seg_bwd_gather_K", p_seg_bwd, pooled_g, segs)

    counts_u16 = jnp.asarray(counts.sum(axis=1).astype(np.int32))

    @jax.jit
    def p_slotwire(counts_u16):
        def body(i, acc):
            cum = jnp.cumsum(counts_u16 + acc.astype(jnp.int32))
            rec = jnp.searchsorted(cum,
                                   jnp.arange(k_pad, dtype=jnp.int32),
                                   side="right").astype(jnp.int32)
            return acc + rec[-1]
        return jax.lax.fori_loop(0, n_iter, body,
                                 jnp.zeros((), jnp.int32))

    timeit("slotwire_decode_K", p_slotwire, counts_u16)

    @jax.jit
    def p_slotwire2(counts_u16):
        def body(i, acc):
            cum = jnp.cumsum(counts_u16 + acc.astype(jnp.int32))
            marks = jnp.zeros(k_pad, jnp.int32).at[cum].add(
                1, mode="drop")
            rec = jnp.cumsum(marks)
            return acc + rec[-1]
        return jax.lax.fori_loop(0, n_iter, body,
                                 jnp.zeros((), jnp.int32))

    timeit("slotwire_scatter_cumsum_K", p_slotwire2, counts_u16)

    @jax.jit
    def p_expand_bwd(vals_k, gidx_stack):
        def body(i, acc):
            g = jax.ops.segment_sum(vals_k * (1.0 + acc),
                                    gidx_stack[i], num_segments=u_pad)
            return acc + g[0, 0] + g[-1, -1]
        return jax.lax.fori_loop(0, n_iter, body, jnp.zeros(()))

    timeit("expand_bwd_segsum_K_to_U", p_expand_bwd, vals_k, gidx_stack)

    half_stack = uniq_stack[:, :u_pad // 2]

    @jax.jit
    def p_gather_half(state, half_stack):
        def body(i, acc):
            rows = gather_full_rows(state, half_stack[i])
            return acc + rows[0, 0] + rows[-1, -1]
        return jax.lax.fori_loop(0, n_iter, body, jnp.zeros(()))

    timeit("gather_halfU_big", p_gather_half, state, half_stack,
           U=u_pad // 2)

    @jax.jit
    def p_gather_K_direct(state, rows_stack):
        def body(i, acc):
            rows = gather_full_rows(state, rows_stack[i])
            return acc + rows[0, 0] + rows[-1, -1]
        return jax.lax.fori_loop(0, n_iter, body, jnp.zeros(()))

    timeit("gather_K_direct_big", p_gather_K_direct, state, rows_stack,
           K_pad=k_pad)

    # ---- dense DeepFM fwd+bwd at this B ----
    import optax

    from paddlebox_tpu.models import DeepFM
    model = DeepFM(hidden=(512, 256, 128))
    pooled0 = jnp.zeros((b, s, 3 + MF))
    dense0 = jnp.zeros((b, 13))
    params = model.init(jax.random.PRNGKey(0), pooled0, dense0)
    pooled_in = jnp.asarray(
        rng.normal(size=(b, s, 3 + MF)).astype(np.float32))
    dense_in = jnp.asarray(rng.normal(size=(b, 13)).astype(np.float32))
    label = jnp.asarray((rng.random(b) < 0.25).astype(np.float32))

    @jax.jit
    def p_dense(params, pooled_in, dense_in, label):
        def body(i, carry):
            acc, params = carry

            def loss_fn(p):
                lg = model.apply(p, pooled_in * (1 + acc), dense_in)
                return optax.sigmoid_binary_cross_entropy(
                    lg, label).mean()

            l, g = jax.value_and_grad(loss_fn)(params)
            params = jax.tree.map(lambda a, b: a - 1e-9 * b, params, g)
            return acc + l * 1e-9, params

        acc, params = jax.lax.fori_loop(
            0, n_iter, body, (jnp.zeros(()), params))
        return acc

    timeit("dense_fwd_bwd", p_dense, params, pooled_in, dense_in, label)

    # ---- hot-tier probes ----
    h = int(os.environ.get("PROF_HOT_ROWS", 8192))
    hot_packed = jnp.asarray(
        rng.normal(size=(h // 8, 128)).astype(np.float32))
    hot_idx = jnp.asarray(
        rng.integers(0, h, size=(n_iter, k_pad)).astype(np.int32))

    @jax.jit
    def p_hot_gather(hot_packed, hot_idx):
        def body(i, acc):
            rows = hot_idx[i]
            lines = hot_packed[rows // 8]
            sub = (rows % 8).astype(jnp.int32)
            grouped = lines.reshape(k_pad, 8, 16)
            v = jnp.take_along_axis(grouped, sub[:, None, None],
                                    axis=1)[:, 0]
            return acc + v[0, 0] + v[-1, -1]
        return jax.lax.fori_loop(0, n_iter, body, jnp.zeros(()))

    timeit("hot_gather_smalltable_K", p_hot_gather, hot_packed, hot_idx,
           H=h)

    for hm in (512, 2048, 8192):
        hot_tab = jnp.asarray(
            rng.normal(size=(hm, 16)).astype(np.float32))
        hidx = jnp.asarray(
            rng.integers(0, hm, size=(n_iter, k_pad)).astype(np.int32))

        @jax.jit
        def p_onehot(hot_tab, hidx, hm=hm):
            def body(i, acc):
                oh = jax.nn.one_hot(hidx[i], hm, dtype=jnp.bfloat16)
                v = oh @ hot_tab.astype(jnp.bfloat16)
                return acc + v[0, 0].astype(jnp.float32) \
                    + v[-1, -1].astype(jnp.float32)
            return jax.lax.fori_loop(0, n_iter, body, jnp.zeros(()))

        timeit(f"onehot_matmul_gather_H{hm}", p_onehot, hot_tab, hidx,
               H=hm)

        @jax.jit
        def p_onehot_push(hot_tab, hidx, grads16, hm=hm):
            def body(i, tab):
                oh = jax.nn.one_hot(hidx[i], hm, dtype=jnp.bfloat16,
                                    axis=0)  # [H, K]
                return tab + (oh @ grads16).astype(jnp.float32)
            return jax.lax.fori_loop(0, n_iter, body, hot_tab)[0, 0]

        grads16 = jnp.asarray(
            rng.normal(size=(k_pad, 16)).astype(np.float32)).astype(
                jnp.bfloat16)
        timeit(f"onehot_matmul_push_H{hm}", p_onehot_push, hot_tab,
               hidx, grads16, H=hm)

    sorted_stack = jnp.asarray(np.sort(uniq_np, axis=1))

    @jax.jit
    def p_gather_sorted(state, sorted_stack):
        def body(i, acc):
            rows = gather_full_rows(state, sorted_stack[i])
            return acc + rows[0, 0] + rows[-1, -1]
        return jax.lax.fori_loop(0, n_iter, body, jnp.zeros(()))

    timeit("gather_U_big_sorted", p_gather_sorted, state, sorted_stack)

    state_bf = TableState(state.packed.astype(jnp.bfloat16), CAP, feat,
                          ext)

    @jax.jit
    def p_gather_bf16(state_bf, uniq_stack):
        def body(i, acc):
            rows = gather_full_rows(state_bf, uniq_stack[i])
            return acc + rows[0, 0].astype(jnp.float32)
        return jax.lax.fori_loop(0, n_iter, body, jnp.zeros(()))

    timeit("gather_U_big_bf16", p_gather_bf16, state_bf, uniq_stack)


def run_set2(n_iter: int) -> None:
    """Grad-merge ordering, gather extract form, push variants (the
    levers left after the slot-wire decode fix). Ragged shape."""
    from paddlebox_tpu.ps.table import (gather_full_rows,
                                        init_table_state,
                                        next_bucket_fine)
    from paddlebox_tpu.ps.sgd import SparseSGDConfig, opt_ext_width

    timeit = make_timeit(n_iter, fetch_val=True)
    b, s, avg, vocab = shape_dims("ragged")
    cfg = SparseSGDConfig(mf_create_thresholds=0.0, mf_initial_range=1e-3)
    ext = opt_ext_width(cfg, MF)

    rng = np.random.default_rng(0)
    counts = 1 + rng.poisson(avg - 1.0, size=(b, s))
    k = int(counts.sum())
    k_pad = next_bucket_fine(4096, k)
    rows_np, _ = _ragged_rows(rng, n_iter, counts, k, k_pad, s, vocab)

    # host-computed dedup per iteration (uniq sorted / gidx / perm /
    # uid_sorted)
    uniqs = [np.unique(rows_np[i][:k], return_inverse=True)
             for i in range(n_iter)]
    u_max = max(len(u) for u, _ in uniqs)
    u_pad = next_bucket_fine(4096, u_max + 1)
    gidx_np = np.zeros((n_iter, k_pad), np.int32)
    for i, (u, inv) in enumerate(uniqs):
        gidx_np[i, :k] = inv
        gidx_np[i, k:] = len(u)  # pad position
    gidx_stack = jnp.asarray(gidx_np)
    # sorted-by-row order: perm sorts keys by row id; uid_sorted
    # nondecreasing
    perm_np = np.empty((n_iter, k_pad), np.int32)
    uid_sorted_np = np.empty((n_iter, k_pad), np.int32)
    for i in range(n_iter):
        p = np.argsort(rows_np[i], kind="stable")
        perm_np[i] = p
        uid_sorted_np[i] = gidx_np[i][p]
    perm_stack = jnp.asarray(perm_np)
    uid_sorted_stack = jnp.asarray(uid_sorted_np)

    g_k = jnp.asarray(rng.normal(size=(k_pad, 3 + MF)).astype(np.float32))
    state = init_table_state(CAP, MF, ext=ext)
    uniq_pad_np = np.empty((n_iter, u_pad), np.int32)
    for i, (u, _) in enumerate(uniqs):
        uniq_pad_np[i, :len(u)] = u
        uniq_pad_np[i, len(u):] = CAP + 1 + np.arange(u_pad - len(u))
    uniq_stack = jnp.asarray(uniq_pad_np)

    print(json.dumps({"probe": "shape", "K": k, "K_pad": k_pad,
                      "U_pad": u_pad}), flush=True)

    @jax.jit
    def p_merge_unsorted(g_k, gidx_stack):
        def body(i, acc):
            g = jax.ops.segment_sum(g_k + acc * 1e-9, gidx_stack[i],
                                    num_segments=u_pad)
            return acc + g.sum()
        return jax.lax.fori_loop(0, n_iter, body, jnp.zeros(()))

    timeit("merge_unsorted", p_merge_unsorted, g_k, gidx_stack)

    @jax.jit
    def p_merge_sorted_hint(g_k, perm_stack, uid_sorted_stack):
        def body(i, acc):
            gs = g_k[perm_stack[i]] + acc * 1e-9
            g = jax.ops.segment_sum(gs, uid_sorted_stack[i],
                                    num_segments=u_pad,
                                    indices_are_sorted=True)
            return acc + g.sum()
        return jax.lax.fori_loop(0, n_iter, body, jnp.zeros(()))

    timeit("merge_perm_plus_sorted_hint", p_merge_sorted_hint, g_k,
           perm_stack, uid_sorted_stack)

    @jax.jit
    def p_merge_sorted_nohint(g_k, perm_stack, uid_sorted_stack):
        def body(i, acc):
            gs = g_k[perm_stack[i]] + acc * 1e-9
            g = jax.ops.segment_sum(gs, uid_sorted_stack[i],
                                    num_segments=u_pad)
            return acc + g.sum()
        return jax.lax.fori_loop(0, n_iter, body, jnp.zeros(()))

    timeit("merge_perm_plus_sorted_nohint", p_merge_sorted_nohint, g_k,
           perm_stack, uid_sorted_stack)

    @jax.jit
    def p_merge_sorted_only(g_k, uid_sorted_stack):
        def body(i, acc):
            g = jax.ops.segment_sum(g_k + acc * 1e-9,
                                    uid_sorted_stack[i],
                                    num_segments=u_pad,
                                    indices_are_sorted=True)
            return acc + g.sum()
        return jax.lax.fori_loop(0, n_iter, body, jnp.zeros(()))

    timeit("merge_sorted_ids_only_hint", p_merge_sorted_only, g_k,
           uid_sorted_stack)

    rand_small = jnp.asarray(
        rng.integers(0, b * s, size=(n_iter, k_pad)).astype(np.int32))

    @jax.jit
    def p_segsum_small_random(g_k, rand_small):
        def body(i, acc):
            g = jax.ops.segment_sum(g_k + acc * 1e-9, rand_small[i],
                                    num_segments=b * s + 1)
            return acc + g.sum()
        return jax.lax.fori_loop(0, n_iter, body, jnp.zeros(()))

    timeit("segsum_small_random_ids", p_segsum_small_random, g_k,
           rand_small)

    @jax.jit
    def p_gather_take(state, uniq_stack):
        def body(i, acc):
            rows = gather_full_rows(state, uniq_stack[i])
            return acc + rows.sum()
        return jax.lax.fori_loop(0, n_iter, body, jnp.zeros(()))

    timeit("gather_take_along_axis", p_gather_take, state, uniq_stack)

    @jax.jit
    def p_gather_maskex(state, uniq_stack):
        rpl, fp, _ = state.geometry

        def body(i, acc):
            rows = jnp.minimum(uniq_stack[i], CAP)
            lines = state.packed[rows // rpl]              # [U, 128]
            sub = (rows % rpl).astype(jnp.int32)
            grouped = lines.reshape(-1, rpl, fp)
            oh = (jnp.arange(rpl, dtype=jnp.int32)[None, :]
                  == sub[:, None]).astype(lines.dtype)     # [U, rpl]
            vals = jnp.einsum("urf,ur->uf", grouped, oh)
            return acc + vals.sum()
        return jax.lax.fori_loop(0, n_iter, body, jnp.zeros(()))

    timeit("gather_maskextract", p_gather_maskex, state, uniq_stack)

    @jax.jit
    def p_gather_lines_only(state, uniq_stack):
        rpl, fp, _ = state.geometry

        def body(i, acc):
            rows = jnp.minimum(uniq_stack[i], CAP)
            lines = state.packed[rows // rpl]
            return acc + lines.sum()
        return jax.lax.fori_loop(0, n_iter, body, jnp.zeros(()))

    timeit("gather_lines_only", p_gather_lines_only, state, uniq_stack)

    d_lines = jnp.asarray(
        rng.normal(size=(u_pad, 128)).astype(np.float32))

    @jax.jit
    def p_scatter_lines(state, uniq_stack, d_lines):
        rpl, fp, _ = state.geometry

        def body(i, packed):
            return packed.at[uniq_stack[i] // rpl].add(d_lines,
                                                       mode="drop")
        return jax.lax.fori_loop(0, n_iter, body, state.packed)[0, 0]

    timeit("scatter_add_lines_U", p_scatter_lines, state, uniq_stack,
           d_lines)

    # line-dedup'd scatter: merge co-resident rows' deltas first (uniq
    # is sorted, so line ids are nondecreasing → sorted segment_sum),
    # then scatter unique lines
    line_uid_np = np.empty((n_iter, u_pad), np.int32)
    n_ulines = 0
    for i in range(n_iter):
        lines_i = uniq_pad_np[i] // 8
        uid = np.zeros(u_pad, np.int32)
        uid[1:] = np.cumsum(lines_i[1:] != lines_i[:-1])
        line_uid_np[i] = uid
        n_ulines = max(n_ulines, uid[-1] + 1)
    from paddlebox_tpu.ps.table import next_bucket_fine as _nbf
    ul_pad = _nbf(4096, int(n_ulines) + 1)
    line_uid_stack = jnp.asarray(line_uid_np)

    @jax.jit
    def p_scatter_linededup(state, uniq_stack, line_uid_stack, d_lines):
        rpl, fp, _ = state.geometry

        def body(i, packed):
            uid = line_uid_stack[i]
            merged = jax.ops.segment_sum(d_lines, uid,
                                         num_segments=ul_pad,
                                         indices_are_sorted=True)
            first_pos = jnp.full(ul_pad, u_pad - 1, jnp.int32).at[
                uid].min(jnp.arange(u_pad, dtype=jnp.int32),
                         mode="drop")
            tgt_lines = (uniq_stack[i] // rpl)[first_pos]
            return packed.at[tgt_lines].add(merged, mode="drop")
        return jax.lax.fori_loop(0, n_iter, body, state.packed)[0, 0]

    timeit("scatter_add_linededup", p_scatter_linededup, state,
           uniq_stack, line_uid_stack, d_lines, UL_pad=ul_pad)


def run_set3(n_iter: int) -> None:
    """Merge form/dtype, packed-line expand, dedup sort form (the
    levers left after the decode + gather-extract fixes). Ragged."""
    from paddlebox_tpu.ops.device_unique import dedup_rows
    from paddlebox_tpu.ps.table import next_bucket_fine

    timeit = make_timeit(n_iter, fetch_val=True)
    b, s, avg, vocab = shape_dims("ragged")
    rng = np.random.default_rng(0)
    counts = 1 + rng.poisson(avg - 1.0, size=(b, s))
    k = int(counts.sum())
    k_pad = next_bucket_fine(4096, k)
    u_pad = 491520
    u_real = 481763

    gidx_stack = jnp.asarray(
        rng.integers(0, u_real, size=(n_iter, k_pad)).astype(np.int32))
    g_k = jnp.asarray(rng.normal(size=(k_pad, 11)).astype(np.float32))
    rows_np, _ = _ragged_rows(rng, n_iter, counts, k, k_pad, s, vocab)
    rows_stack = jnp.asarray(rows_np)

    print(json.dumps({"probe": "shape", "K_pad": k_pad,
                      "U_pad": u_pad}), flush=True)

    @jax.jit
    def p_merge_f32(g_k, gidx_stack):
        def body(i, acc):
            g = jax.ops.segment_sum(g_k + acc * 1e-9, gidx_stack[i],
                                    num_segments=u_pad)
            return acc + g.sum()
        return jax.lax.fori_loop(0, n_iter, body, jnp.zeros(()))

    timeit("merge_f32", p_merge_f32, g_k, gidx_stack)

    @jax.jit
    def p_merge_bf16(g_k, gidx_stack):
        def body(i, acc):
            g = jax.ops.segment_sum(
                (g_k + acc * 1e-9).astype(jnp.bfloat16), gidx_stack[i],
                num_segments=u_pad)
            return acc + g.astype(jnp.float32).sum()
        return jax.lax.fori_loop(0, n_iter, body, jnp.zeros(()))

    timeit("merge_bf16", p_merge_bf16, g_k, gidx_stack)

    @jax.jit
    def p_merge_at_add(g_k, gidx_stack):
        def body(i, acc):
            g = jnp.zeros((u_pad, 11), jnp.float32).at[
                gidx_stack[i]].add(g_k + acc * 1e-9)
            return acc + g.sum()
        return jax.lax.fori_loop(0, n_iter, body, jnp.zeros(()))

    timeit("merge_at_add", p_merge_at_add, g_k, gidx_stack)

    g_k16 = jnp.asarray(rng.normal(size=(k_pad, 16)).astype(np.float32))

    @jax.jit
    def p_merge_w16(g_k16, gidx_stack):
        def body(i, acc):
            g = jax.ops.segment_sum(g_k16 + acc * 1e-9, gidx_stack[i],
                                    num_segments=u_pad)
            return acc + g.sum()
        return jax.lax.fori_loop(0, n_iter, body, jnp.zeros(()))

    timeit("merge_w16", p_merge_w16, g_k16, gidx_stack)

    vals_u = jnp.asarray(rng.normal(size=(u_pad, 11)).astype(np.float32))

    @jax.jit
    def p_expand_plain(vals_u, gidx_stack):
        def body(i, acc):
            v = vals_u[gidx_stack[i]] + acc * 1e-9
            return acc + v.sum()
        return jax.lax.fori_loop(0, n_iter, body, jnp.zeros(()))

    timeit("expand_plain", p_expand_plain, vals_u, gidx_stack)

    vals_packed = jnp.asarray(
        rng.normal(size=(u_pad // 8, 128)).astype(np.float32))

    @jax.jit
    def p_expand_packedlines(vals_packed, gidx_stack):
        def body(i, acc):
            g = gidx_stack[i]
            lines = vals_packed[g // 8]                    # [K, 128]
            sub = (g % 8).astype(jnp.int32)
            grouped = lines.reshape(-1, 8, 16)
            oh = (jnp.arange(8, dtype=jnp.int32)[None, :]
                  == sub[:, None]).astype(lines.dtype)
            v = jnp.einsum("krf,kr->kf", grouped, oh) + acc * 1e-9
            return acc + v.sum()
        return jax.lax.fori_loop(0, n_iter, body, jnp.zeros(()))

    timeit("expand_packedlines_maskex", p_expand_packedlines,
           vals_packed, gidx_stack)

    @jax.jit
    def p_dedup_current(rows_stack):
        def body(i, acc):
            u, g = dedup_rows(rows_stack[i], CAP)
            return acc + (u.sum() + g.sum())
        return jax.lax.fori_loop(0, n_iter, body,
                                 jnp.zeros((), jnp.int32))

    timeit("dedup_current", p_dedup_current, rows_stack)

    @jax.jit
    def p_dedup_i64pack(rows_stack):
        def body(i, acc):
            rows = rows_stack[i]
            kk = rows.shape[0]
            pos = jnp.arange(kk, dtype=jnp.int64)
            packed = (rows.astype(jnp.int64) << 20) | pos
            sp = jax.lax.sort(packed)
            sr = (sp >> 20).astype(jnp.int32)
            perm = (sp & ((1 << 20) - 1)).astype(jnp.int32)
            is_first = jnp.concatenate([jnp.ones(1, bool),
                                        sr[1:] != sr[:-1]])
            uid_sorted = jnp.cumsum(is_first.astype(jnp.int32)) - 1
            gidx = jnp.zeros(kk, jnp.int32).at[perm].set(
                uid_sorted, unique_indices=True)
            oob = CAP + 1 + jnp.arange(kk, dtype=jnp.int32)
            uniq = oob.at[uid_sorted].set(sr)
            return acc + (uniq.sum() + gidx.sum())
        return jax.lax.fori_loop(0, n_iter, body,
                                 jnp.zeros((), jnp.int32))

    timeit("dedup_i64pack", p_dedup_i64pack, rows_stack)

    @jax.jit
    def p_merge_lines(g_k16, gidx_stack):
        def body(i, acc):
            g = gidx_stack[i]
            sub = (g % 8).astype(jnp.int32)
            oh = (jnp.arange(8, dtype=jnp.int32)[None, :]
                  == sub[:, None]).astype(jnp.float32)     # [K, 8]
            d = (oh[:, :, None] * (g_k16 + acc * 1e-9)[:, None, :]
                 ).reshape(-1, 128)                        # [K, 128]
            out = jnp.zeros((u_pad // 8, 128), jnp.float32).at[
                g // 8].add(d)
            return acc + out.sum()
        return jax.lax.fori_loop(0, n_iter, body, jnp.zeros(()))

    timeit("merge_lines_f32", p_merge_lines, g_k16, gidx_stack)

    @jax.jit
    def p_merge_bucketed64(g_k, gidx_stack):
        def body(i, acc):
            g = gidx_stack[i]
            col = (g % 64).astype(jnp.int32)
            oh_cols = (col[:, None] * 11
                       + jnp.arange(11, dtype=jnp.int32)[None, :])
            out = jnp.zeros((u_pad // 64, 64 * 11), jnp.float32).at[
                (g // 64)[:, None], oh_cols].add(g_k + acc * 1e-9)
            return acc + out.sum()
        return jax.lax.fori_loop(0, n_iter, body, jnp.zeros(()))

    timeit("merge_bucketed64", p_merge_bucketed64, g_k, gidx_stack)


def _kernel_segments(shape: str, rng, b: int, s: int, k: int,
                     n_iter: int) -> np.ndarray:
    """Stacked nondecreasing segment streams [n_iter, K]: ``uniform``
    draws one key per (ins, slot) bin in order, ``ragged`` Poisson
    lengths, ``zipf`` heavy-tailed lengths (the hot-sequence CTR
    shape); the tail of every stream is batch padding (→ B*S)."""
    out = np.full((n_iter, k), b * s, np.int32)
    for i in range(n_iter):
        if shape == "uniform":
            nk = min(k, b * s)
            out[i, :nk] = np.arange(nk, dtype=np.int32)
            continue
        if shape == "zipf":
            lens = np.minimum(rng.zipf(1.5, size=b * s), 32)
        else:
            lens = 1 + rng.poisson(4.0, size=b * s)
        ids = np.repeat(np.arange(b * s, dtype=np.int32), lens)[:k]
        out[i, :len(ids)] = ids
    return out


def _ctr_probes(probe, n_iter: int, backend: str) -> None:
    """The CTR op family (ISSUE 13): fused rank_attention / batch_fc /
    cross_norm_hadamard vs their XLA compositions, probed THROUGH the
    dispatch seams so the flag routing (and its
    ``pbox_kernel_dispatch_total`` booking) is what gets measured.
    Emits ``kernel.{rank_attention,batch_fc,cross_norm}[_xla]`` rows;
    the per-iter work unit is rows (instances), not keys."""
    from paddlebox_tpu.config import flags_scope
    from paddlebox_tpu.ops import (batch_fc, cross_norm_hadamard,
                                   cross_norm_update,
                                   init_cross_norm_summary,
                                   rank_attention)

    rng = np.random.default_rng(0)
    if backend == "tpu":
        n_ra, d_ra, s_fc, n_fc, io_fc = 4096, 128, 26, 4096, 128
        b_cn, f_cn, d_cn = 4096, 8, 64
    else:
        # interpret-mode round: keep it seconds (gate-history rows)
        n_ra, d_ra, s_fc, n_fc, io_fc = 256, 32, 8, 128, 64
        b_cn, f_cn, d_cn = 256, 4, 16
    mr = 3

    # ---- rank_attention: block-grouped Pallas vs XLA fallback ----
    x = jnp.asarray(rng.normal(size=(n_ra, d_ra)).astype(np.float32))
    param = jnp.asarray(
        rng.normal(size=(mr * mr, d_ra, d_ra)).astype(np.float32))
    ro_np = np.zeros((n_iter, n_ra, 1 + 2 * mr), np.int32)
    for i in range(n_iter):
        ro_np[i, :, 0] = rng.integers(0, mr + 1, size=n_ra)
        for k in range(mr):
            on = rng.random(n_ra) < 0.7
            ro_np[i, :, 1 + 2 * k] = np.where(
                on, rng.integers(1, mr + 1, size=n_ra), 0)
            ro_np[i, :, 2 + 2 * k] = rng.integers(0, n_ra, size=n_ra)
    ro_stack = jnp.asarray(ro_np)

    def make_ra(flag):
        @jax.jit
        def run(x, param, ro_stack):
            def body(i, acc):
                with flags_scope(use_pallas_rank_attention=flag):
                    out = rank_attention(x * (1.0 + acc * 1e-9),
                                         ro_stack[i], param, mr)
                return acc + out[0, 0] + out[-1, -1]
            return jax.lax.fori_loop(0, n_iter, body, jnp.zeros(()))
        return run

    probe("rank_attention", make_ra(True), x, param, ro_stack,
          keys=n_ra, unit="rows/sec")
    probe("rank_attention_xla", make_ra(False), x, param, ro_stack,
          keys=n_ra, unit="rows/sec")

    # ---- batch_fc: fused-bias blocked GEMM vs XLA einsum ----
    xb = jnp.asarray(
        rng.normal(size=(s_fc, n_fc, io_fc)).astype(np.float32))
    wb = jnp.asarray(
        rng.normal(size=(s_fc, io_fc, io_fc)).astype(np.float32))
    bb = jnp.asarray(rng.normal(size=(s_fc, io_fc)).astype(np.float32))

    def make_fc(flag):
        @jax.jit
        def run(xb, wb, bb):
            def body(i, acc):
                with flags_scope(use_pallas_batch_fc=flag):
                    out = batch_fc(xb * (1.0 + acc * 1e-9), wb, bb)
                return acc + out[0, 0, 0] + out[-1, -1, -1]
            return jax.lax.fori_loop(0, n_iter, body, jnp.zeros(()))
        return run

    probe("batch_fc", make_fc(True), xb, wb, bb, keys=s_fc * n_fc,
          unit="rows/sec")
    probe("batch_fc_xla", make_fc(False), xb, wb, bb,
          keys=s_fc * n_fc, unit="rows/sec")

    # ---- cross_norm_hadamard: one-VMEM-pass vs XLA composition ----
    xc = jnp.asarray(
        rng.normal(size=(b_cn, 2 * f_cn * d_cn)).astype(np.float32))
    summ = cross_norm_update(init_cross_norm_summary(f_cn, d_cn), xc,
                             f_cn, d_cn, decay=0.5)

    def make_cn(flag):
        @jax.jit
        def run(xc, summ):
            def body(i, acc):
                with flags_scope(use_pallas_cross_norm=flag):
                    out = cross_norm_hadamard(xc * (1.0 + acc * 1e-9),
                                              summ, f_cn, d_cn)
                return acc + out[0, 0] + out[-1, -1]
            return jax.lax.fori_loop(0, n_iter, body, jnp.zeros(()))
        return run

    probe("cross_norm", make_cn(True), xc, summ, keys=b_cn,
          unit="rows/sec")
    probe("cross_norm_xla", make_cn(False), xc, summ, keys=b_cn,
          unit="rows/sec")


def run_set_kernels(shape: str, n_iter: int, record: bool = False,
                    probes: str = "all") -> None:
    """Per-kernel device cost of the Pallas device-kernel suite vs the
    XLA compositions (ISSUE 12 + 13; docs/PERFORMANCE.md §Device
    kernels). ``probes``: "embed" = the embed-pool-CVM family,
    "ctr" = the rank_attention/batch_fc/cross_norm family, "all" =
    both."""
    import jax.numpy as jnp

    from paddlebox_tpu.config import flags_scope
    from paddlebox_tpu.obs import trace
    from paddlebox_tpu.ops import fused_seqpool_cvm
    from paddlebox_tpu.ops.pallas_kernels import (fused_pool_cvm_forward,
                                                  gather_rows)

    backend = jax.default_backend()
    if backend == "tpu":
        b, s, cap, k = 4096, 26, 1 << 20, 1 << 19
    else:
        # interpret-mode round: the kernel body runs as a python loop
        # per pair — keep it seconds, the row exists for gate HISTORY
        b, s, cap, k = 64, 8, 1 << 12, 1 << 11
    mf = MF
    d = 2 + mf
    rng = np.random.default_rng(0)

    timeit = make_timeit(n_iter)
    rows_out = []

    def probe(name, fn, *args, keys=k, unit="keys/sec"):
        if trace.tracing_active():
            with trace.span(f"kernel.{name}", lane=trace.LANE_KERNELS,
                            shape=shape, backend=backend):
                jax.block_until_ready(fn(*args))
        ms = timeit(f"kernel.{name}.{shape}", fn, *args, backend=backend)
        if record and ms > 0:
            # source="live" (the bench.py convention): a re-run on a
            # slower box appends a row that --check --ignore-live SKIPS
            # — the GATED history is the committed KERNELS_r0*.json
            # round (folded with its artifact name as source).
            # ``keys``/``unit`` name the probe's work item — the CTR
            # probes count rows (instances), not keys.
            rows_out.append({
                "source": "live",
                "metric": f"kernel.{name}.{shape}.{backend}",
                "value": round(keys / ms * 1000.0, 1),
                "unit": unit, "shape": shape,
            })

    print(json.dumps({"probe": "shape", "B": b, "S": s, "K": k,
                      "CAP": cap, "D": d, "backend": backend}),
          flush=True)

    if probes in ("all", "embed"):
        # ---- gather: pallas scalar-prefetch line gather vs XLA take ----
        table = jnp.asarray(rng.normal(size=(cap, 128)).astype(np.float32))
        rows_np = rng.integers(0, cap, size=(n_iter, k)).astype(np.int32)
        rows_stack = jnp.asarray(rows_np)

        @jax.jit
        def p_gather_pallas(table, rows_stack):
            def body(i, acc):
                v = gather_rows(table, rows_stack[i])
                return acc + v[0, 0] + v[-1, -1]
            return jax.lax.fori_loop(0, n_iter, body, jnp.zeros(()))

        @jax.jit
        def p_gather_xla(table, rows_stack):
            def body(i, acc):
                v = table[rows_stack[i]]
                return acc + v[0, 0] + v[-1, -1]
            return jax.lax.fori_loop(0, n_iter, body, jnp.zeros(()))

        probe("gather", p_gather_pallas, table, rows_stack)
        probe("gather_xla", p_gather_xla, table, rows_stack)

        # ---- pool+CVM forward: fused Pallas pass vs XLA composition ----
        vals = rng.normal(size=(k, d)).astype(np.float32)
        vals[:, :2] = np.abs(vals[:, :2])
        vals_j = jnp.asarray(vals)
        segs_stack = jnp.asarray(_kernel_segments(shape, rng, b, s, k, n_iter))
        sc = jnp.asarray(np.abs(rng.normal(size=(b, 2))).astype(np.float32))

        @jax.jit
        def p_pool_fused(vals_j, segs_stack):
            def body(i, acc):
                out = fused_pool_cvm_forward(vals_j * (1.0 + acc * 1e-9),
                                             segs_stack[i], None, b, s)
                return acc + out[0, 0, 0] + out[-1, -1, -1]
            return jax.lax.fori_loop(0, n_iter, body, jnp.zeros(()))

        def _xla_fwd(v, segs):
            with flags_scope(use_pallas_seqpool=False):
                return fused_seqpool_cvm(v, segs, sc, b, s)

        @jax.jit
        def p_pool_xla(vals_j, segs_stack):
            def body(i, acc):
                out = _xla_fwd(vals_j * (1.0 + acc * 1e-9), segs_stack[i])
                return acc + out[0, 0, 0] + out[-1, -1, -1]
            return jax.lax.fori_loop(0, n_iter, body, jnp.zeros(()))

        probe("pool_cvm", p_pool_fused, vals_j, segs_stack)
        probe("pool_cvm_xla", p_pool_xla, vals_j, segs_stack)

        # ---- full fused fwd+bwd (the train-step shape: pooled loss grad
        # feeding the push path) vs the XLA composition ----
        def make_fwd_bwd(flag):
            def step(v, segs):
                def loss(v):
                    out = fused_seqpool_cvm(v, segs, sc, b, s)
                    return jnp.sum(out * out)
                return jax.grad(loss)(v)

            @jax.jit
            def run(vals_j, segs_stack):
                def body(i, acc):
                    with flags_scope(use_pallas_seqpool=flag):
                        g = step(vals_j * (1.0 + acc * 1e-9), segs_stack[i])
                    return acc + g[0, 0] + g[-1, -1]
                return jax.lax.fori_loop(0, n_iter, body, jnp.zeros(()))
            return run

        probe("fused", make_fwd_bwd(True), vals_j, segs_stack)
        probe("fused_xla", make_fwd_bwd(False), vals_j, segs_stack)

    if probes in ("all", "ctr"):
        _ctr_probes(probe, n_iter, backend)

    if record and rows_out:
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        import perf_gate
        # bench.py's convention: BENCH_TRAJECTORY=0 disables the live
        # append (the rows still echo below for artifact capture)
        dest = os.environ.get("BENCH_TRAJECTORY", "")
        path = None if dest == "0" \
            else (dest or perf_gate.default_trajectory_path())
        for row in rows_out:
            if path:
                perf_gate.append_row(row, path)
            # echo the row as a bench line so a captured stdout artifact
            # (KERNELS_r0*.json) re-folds via perf_gate --fold
            print(json.dumps(row), flush=True)
        print(json.dumps({"probe": "recorded", "rows": len(rows_out),
                          "path": path or "(disabled)"}), flush=True)


def _index_keys(shape: str, rng, vocab: int, k: int,
                n_iter: int) -> np.ndarray:
    """Raw 64-bit feature-id streams [n_iter, K] for the index probes:
    ``uniform`` all-distinct ids (cold insert), ``zipf`` heavy-tailed
    repeats (the CTR hot-key shape), anything else uniform draws over a
    small vocab (collision-heavy warm stream). Every 7th id gets a
    high-32 bit set so the probe covers ids that collide mod 2^32."""
    out = np.empty((n_iter, k), np.uint64)
    for i in range(n_iter):
        if shape == "uniform":
            ids = (np.arange(k, dtype=np.uint64)
                   + np.uint64(i * k))
        elif shape == "zipf":
            ids = np.minimum(rng.zipf(1.3, size=k),
                             vocab).astype(np.uint64)
        else:
            ids = rng.integers(0, vocab, size=k).astype(np.uint64)
        ids[::7] |= np.uint64(1) << np.uint64(33)
        out[i] = ids
    return out


def run_set_index(shape: str, n_iter: int, record: bool = False) -> None:
    """The device-resident key index (ISSUE 19; ops/pallas_index.py):
    open-addressing insert / lookup / first-seen dedup over RAW feature
    ids, device (Pallas interpret or XLA while-loop) vs the host paths
    (python dedup oracle, native C dedup, host kv assign/lookup). Emits
    one JSON row per probe; with ``--record`` higher-is-better
    ``kernel.index.{insert,lookup,dedup}*.{shape}.{backend}`` raw-keys/s
    rows append to the perf_gate trajectory."""
    from paddlebox_tpu.obs import trace
    from paddlebox_tpu.ops.device_unique import dedup_keys_first_seen
    from paddlebox_tpu.ops.pallas_index import (_pad_to_block, insert,
                                                lookup, split_keys)
    from paddlebox_tpu.ps.kv import dedup_first_seen_native, make_kv
    from paddlebox_tpu.ps.table import (_dedup_first_seen_py,
                                        dedup_first_seen)

    backend = jax.default_backend()
    if backend == "tpu":
        k, vocab, cap = 1 << 17, 1 << 15, 1 << 20
    else:
        # interpret-mode round: the Pallas insert probes each key in a
        # python fori_loop — keep it seconds (the row is gate HISTORY)
        k, vocab, cap = 512, 192, 1 << 13
    n_buckets = 1 << int(2 * cap - 1).bit_length()
    rng = np.random.default_rng(0)
    keys_np = _index_keys(shape, rng, vocab, k, n_iter)

    timeit = make_timeit(n_iter)
    rows_out = []

    def probe(name, fn, *args, keys=k, unit="keys/sec"):
        if trace.tracing_active():
            with trace.span(f"kernel.{name}", lane=trace.LANE_KERNELS,
                            shape=shape, backend=backend):
                jax.block_until_ready(fn(*args))
        ms = timeit(f"kernel.{name}.{shape}", fn, *args, backend=backend)
        if record and ms > 0:
            rows_out.append({
                "source": "live",
                "metric": f"kernel.{name}.{shape}.{backend}",
                "value": round(keys / ms * 1000.0, 1),
                "unit": unit, "shape": shape,
            })

    kp = _pad_to_block(keys_np[0]).shape[0]
    hi_np = np.empty((n_iter, kp), np.int32)
    lo_np = np.empty((n_iter, kp), np.int32)
    for i in range(n_iter):
        hi, lo = split_keys(keys_np[i])
        hi_np[i] = _pad_to_block(hi)
        lo_np[i] = _pad_to_block(lo)
    hi_stack, lo_stack = jnp.asarray(hi_np), jnp.asarray(lo_np)

    print(json.dumps({"probe": "shape", "K": k, "K_pad": kp,
                      "VOCAB": vocab, "CAP": cap,
                      "BUCKETS": n_buckets, "backend": backend}),
          flush=True)

    # ---- insert: open-addressing claim over the whole stream, state
    # (buckets + row cursor) threaded through the loop — iteration 2+
    # measures the warm (mostly-hits) pass shape ----
    def make_insert(up):
        @jax.jit
        def run(hi_stack, lo_stack):
            def body(i, carry):
                bh, bl, br, nxt, acc = carry
                bh, bl, br, rows, new, ovf = insert(
                    bh, bl, br, hi_stack[i], lo_stack[i],
                    jnp.int32(k), nxt, use_pallas=up)
                nxt = nxt + jnp.sum(new[:k]).astype(jnp.int32)
                return (bh, bl, br, nxt, acc + rows[0] + rows[k - 1])
            init = (jnp.zeros(n_buckets, jnp.int32),
                    jnp.zeros(n_buckets, jnp.int32),
                    jnp.full(n_buckets, -1, jnp.int32),
                    jnp.int32(0), jnp.zeros((), jnp.int32))
            return jax.lax.fori_loop(0, n_iter, body, init)[4]
        return run

    probe("index.insert", make_insert(True), hi_stack, lo_stack)
    probe("index.insert_xla", make_insert(False), hi_stack, lo_stack)

    def p_insert_host():
        # the host half of the seam: python first-seen dedup + kv
        # assign (the EmbeddingTable.bulk_assign_unique host path)
        kv = make_kv(cap)
        acc = 0
        for i in range(n_iter):
            uniq, first, inv = dedup_first_seen(keys_np[i])
            rows = kv.assign(uniq)
            acc += int(rows[0])
        return np.int64(acc)

    probe("index.insert_host", p_insert_host)

    # ---- lookup: probe a table warmed with the full key population ----
    all_uniq = np.unique(keys_np.reshape(-1))
    from paddlebox_tpu.ops.pallas_index import DeviceKeyIndex
    dev = DeviceKeyIndex(cap, n_buckets=n_buckets)
    out = dev.assign_unique(all_uniq)
    assert out is not None, "probe table overflowed — raise CAP"

    def make_lookup(up):
        @jax.jit
        def run(bh, bl, br, hi_stack, lo_stack):
            def body(i, acc):
                rows = lookup(bh, bl, br, hi_stack[i], lo_stack[i],
                              jnp.int32(k), use_pallas=up)
                return acc + rows[0] + rows[k - 1]
            return jax.lax.fori_loop(0, n_iter, body,
                                     jnp.zeros((), jnp.int32))
        return run

    probe("index.lookup", make_lookup(True), dev.bh, dev.bl, dev.br,
          hi_stack, lo_stack)
    probe("index.lookup_xla", make_lookup(False), dev.bh, dev.bl,
          dev.br, hi_stack, lo_stack)

    kv_warm = make_kv(cap)
    kv_warm.assign(all_uniq)

    def p_lookup_host():
        acc = 0
        for i in range(n_iter):
            acc += int(kv_warm.lookup(keys_np[i])[0])
        return np.int64(acc)

    probe("index.lookup_host", p_lookup_host)

    # ---- first-seen dedup of raw ids: device sort-based kernel vs the
    # python oracle vs the native C open-addressing pass ----
    @jax.jit
    def p_dedup_dev(hi_stack, lo_stack):
        def body(i, acc):
            uh, ul, first, inv, nu = dedup_keys_first_seen(
                hi_stack[i], lo_stack[i], jnp.int32(k))
            return acc + uh[0] + inv[k - 1] + nu
        return jax.lax.fori_loop(0, n_iter, body,
                                 jnp.zeros((), jnp.int32))

    probe("index.dedup", p_dedup_dev, hi_stack, lo_stack)

    def p_dedup_host():
        # the pure-python oracle, NOT dedup_first_seen (which routes to
        # the native pass when available — probed separately below)
        acc = 0
        for i in range(n_iter):
            uniq, first, inv = _dedup_first_seen_py(keys_np[i])
            acc += len(uniq)
        return np.int64(acc)

    probe("index.dedup_host", p_dedup_host)

    if dedup_first_seen_native(keys_np[0]) is not None:
        def p_dedup_native():
            acc = 0
            for i in range(n_iter):
                uniq, first, inv = dedup_first_seen_native(keys_np[i])
                acc += len(uniq)
            return np.int64(acc)

        probe("index.dedup_native", p_dedup_native)
    else:
        print(json.dumps({"probe": "index.dedup_native",
                          "skipped": "native lib unavailable"}),
              flush=True)

    if record and rows_out:
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        import perf_gate
        dest = os.environ.get("BENCH_TRAJECTORY", "")
        path = None if dest == "0" \
            else (dest or perf_gate.default_trajectory_path())
        for row in rows_out:
            if path:
                perf_gate.append_row(row, path)
            print(json.dumps(row), flush=True)
        print(json.dumps({"probe": "recorded", "rows": len(rows_out),
                          "path": path or "(disabled)"}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="device key-path cost probes")
    ap.add_argument("--set", dest="probe_set", default="1",
                    choices=("1", "2", "3", "all", "kernels", "index"),
                    help="probe set to run (default 1)")
    ap.add_argument("--shape",
                    default=os.environ.get("PROF_SHAPE", "ragged"),
                    choices=("ragged", "uniform", "thousand", "zipf"),
                    help="workload shape for sets 1/kernels")
    ap.add_argument("--iters", type=int,
                    default=int(os.environ.get("PROF_ITERS", 16)),
                    help="fori_loop iterations per probe")
    ap.add_argument("--record", action="store_true",
                    help="(kernels set) append kernel.* rows to the "
                    "perf_gate trajectory (BENCH_TRAJECTORY overrides "
                    "the path)")
    ap.add_argument("--probes", default="all",
                    choices=("all", "embed", "ctr"),
                    help="(kernels set) probe family: the embed-pool-"
                    "CVM suite, the ISSUE 13 CTR op family, or both")
    args = ap.parse_args(argv)
    if args.probe_set == "kernels":
        shape = args.shape if args.shape != "thousand" else "ragged"
        print(json.dumps({"probe": "set", "set": "kernels"}), flush=True)
        run_set_kernels(shape, args.iters, record=args.record,
                        probes=args.probes)
        print(json.dumps({"probe": "done"}), flush=True)
        return 0
    if args.probe_set == "index":
        shape = args.shape if args.shape != "thousand" else "ragged"
        print(json.dumps({"probe": "set", "set": "index"}), flush=True)
        run_set_index(shape, args.iters, record=args.record)
        print(json.dumps({"probe": "done"}), flush=True)
        return 0
    if args.shape == "zipf":
        # shape_dims() has no zipf branch — sets 1-3 would silently run
        # the uniform workload while claiming the heavy-tailed one
        ap.error("--shape zipf is only valid with --set kernels/index")
    sets = ("1", "2", "3") if args.probe_set == "all" \
        else (args.probe_set,)
    for ps in sets:
        print(json.dumps({"probe": "set", "set": int(ps)}), flush=True)
        if ps == "1":
            run_set1(args.shape, args.iters)
        elif ps == "2":
            run_set2(args.iters)
        else:
            run_set3(args.iters)
    print(json.dumps({"probe": "done"}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
