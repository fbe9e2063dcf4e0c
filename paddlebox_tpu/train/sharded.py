"""Multi-chip fused train step: data-parallel dense + model-parallel
embedding shards, one jit program under shard_map.

Reference execution model being replaced (SURVEY.md §2.6): one worker thread
per GPU (BoxPSTrainer), NCCL allreduce for dense grads (SyncParam,
boxps_worker.cc:1191-1258), HeterComm P2P for sparse pull/push, MPI for
cross-node. Here ALL of it is one traced program over the mesh: two
``all_to_all`` collectives route embedding rows/grads between shards
(ps/sharded.py), a ``psum`` reduces dense grads, and XLA schedules the
collectives against compute on ICI.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddlebox_tpu.data.batch import SlotBatch
from paddlebox_tpu.metrics import AucState, auc_add_batch, init_auc_state
from paddlebox_tpu.obs import trace
from paddlebox_tpu.ops import fused_seqpool_cvm
from paddlebox_tpu.ops.seqpool_cvm import fused_seqpool_cvm_slot_group
from paddlebox_tpu.parallel.mesh import DATA_AXIS, stacked_zeros
from paddlebox_tpu.ps.sgd import SparseSGDConfig
from paddlebox_tpu.ps.sharded import (ShardedEmbeddingTable,
                                      ShardedPullIndex,
                                      chunk_local_positions,
                                      plan_sections, section_offsets)
from paddlebox_tpu.ops.bitpack import (pack_delta_auto, pack_u16m,
                                       pack_u24, unpack_delta16,
                                       unpack_u16m, unpack_u24)
from paddlebox_tpu.ps.table import (TableState, apply_push,
                                    expand_pull, fill_oob_pads,
                                    gather_full_rows, merge_rows,
                                    pull_values)
from paddlebox_tpu.train.step import quantize_floats


class GlobalBatch(NamedTuple):
    """One global batch: per-device blocks stacked on axis 0 (sharded dp)."""

    resp_idx: jax.Array     # int32 [N, N, A]
    serve_rows: jax.Array   # int32 [N, A2]
    serve_valid: jax.Array  # f32   [N, A2]
    serve_slot: jax.Array   # f32   [N, A2]
    gather_idx: jax.Array   # int32 [N, K]
    segments: jax.Array     # int32 [N, K]
    dense: jax.Array        # f32   [N, B, Dd]
    label: jax.Array        # f32   [N, B]
    show: jax.Array         # f32   [N, B]
    clk: jax.Array          # f32   [N, B]


def make_global_arrays(batches: List[SlotBatch],
                       idx: ShardedPullIndex) -> Dict[str, np.ndarray]:
    """Stack N local batches + routing plan into HOST arrays (the
    resident builder consumes these directly — never round-trip the
    plan through device arrays)."""
    dense, label, show, clk = [], [], [], []
    for b in batches:
        dense.append(b.dense)
        label.append(b.label)
        show.append(b.show)
        clk.append(b.clk)
    if getattr(idx, "key_segments", None) is not None:
        # grouped plan (a2a_chunks > 1): the key stream was re-laid
        # group-contiguous, so the matching segment stream comes from
        # the plan, not the batches' original-order segments
        segs = list(idx.key_segments)
        gi = idx.gather_idx
        return dict(
            resp_idx=idx.resp_idx, serve_rows=idx.serve_rows,
            serve_valid=idx.serve_valid, serve_slot=idx.serve_slot,
            gather_idx=gi, segments=np.stack(segs),
            dense=np.stack(dense), label=np.stack(label),
            show=np.stack(show), clk=np.stack(clk))
    k_pad = max(b.keys.shape[0] for b in batches)
    segs = []
    for b in batches:
        s = np.full(k_pad, b.pad_segment, np.int32)
        s[:b.segments.shape[0]] = b.segments
        segs.append(s)
    gi = idx.gather_idx
    if gi.shape[1] < k_pad:
        pad = ((0, 0), (0, k_pad - gi.shape[1]))
        gi = np.pad(gi, pad, constant_values=gi.max())
    return dict(
        resp_idx=idx.resp_idx, serve_rows=idx.serve_rows,
        serve_valid=idx.serve_valid, serve_slot=idx.serve_slot,
        gather_idx=gi, segments=np.stack(segs),
        dense=np.stack(dense), label=np.stack(label),
        show=np.stack(show), clk=np.stack(clk))


def make_global_batch(batches: List[SlotBatch],
                      idx: ShardedPullIndex) -> GlobalBatch:
    """make_global_arrays staged to device (streaming step path)."""
    host = make_global_arrays(batches, idx)
    return GlobalBatch(**{f: jnp.asarray(host[f])
                          for f in GlobalBatch._fields})


def _wire_spec(name: str, ndim: int) -> P:
    """Sharding spec for a packed-wire leaf: [nb, N, ...] with the
    device dim sharded; qmeta is pass-global (replicated)."""
    if name == "qmeta":
        return P()
    return P(*([None, DATA_AXIS] + [None] * (ndim - 2)))


class _LazyJit:
    """Defers jit construction until the wire pytree's structure is
    known (specs depend on it)."""

    def __init__(self, factory) -> None:
        self._factory = factory
        self._jit = None

    def __call__(self, state, wire, start, rng):
        if self._jit is None:
            self._jit = self._factory(wire)
        return self._jit(state, wire, start, rng)


def _decode_wire_step(wire, fmt, i, capacity: int) -> GlobalBatch:
    """Reassemble step i's GlobalBatch from the packed resident wire
    (in-trace; see ShardedResidentPass._encode_wire for the encodings)."""
    def dec_int(name):
        f = fmt[name]
        t = wire[name]
        if f == "u18":
            return unpack_u16m(t[0][i], t[1][i], 2)
        if f == "u24":
            return unpack_u24(t[0][i], t[1][i])
        return t[0][i]

    resp_idx = dec_int("resp_idx")
    if fmt["serve_rows"] == "delta":
        d = wire["serve_rows"]
        srm = wire["srmeta"][0][i]                    # [N, 2] count, base
        dec = jax.vmap(unpack_delta16)(d[0][i], d[1][i], d[2][i],
                                       srm[:, 1])
        a2 = dec.shape[-1]
        pos = jnp.arange(a2, dtype=jnp.int32)[None, :]
        # pads regenerate from the real count: distinct ascending OOB
        # ids (the fill_oob_pads contract)
        serve_rows = jnp.where(pos < srm[:, 0:1], dec,
                               capacity + 1 + pos)
    else:
        serve_rows = dec_int("serve_rows")
    gather_idx = dec_int("gather_idx")
    if fmt["serve_valid"] == "derive":
        serve_valid = (serve_rows <= capacity).astype(jnp.float32)
    else:
        serve_valid = wire["serve_valid"][0][i]
    serve_slot = wire["serve_slot"][0][i].astype(jnp.float32)
    if fmt["segments"] == "trivial":
        meta = wire["meta"][0][i]                     # [N_local, 2]
        k = gather_idx.shape[-1]
        pos = jnp.arange(k, dtype=jnp.int32)[None, :]
        segments = jnp.where(pos < meta[:, 0:1], pos, meta[:, 1:2])
    else:
        segments = dec_int("segments")
    if fmt["dense"] == "q8":
        qm = wire["qmeta"][0]                         # [2, Dd] replicated
        d = wire["dense"][0][i].astype(jnp.float32)
        dense = d * qm[0][None, None, :] + qm[1][None, None, :]
    else:
        dense = wire["dense"][0][i]
    lsc = {}
    for f in ("label", "show", "clk"):
        a = wire[f][0][i]
        lsc[f] = a.astype(jnp.float32)
    return GlobalBatch(resp_idx=resp_idx, serve_rows=serve_rows,
                       serve_valid=serve_valid, serve_slot=serve_slot,
                       gather_idx=gather_idx, segments=segments,
                       dense=dense, **lsc)


class ShardedStepState(NamedTuple):
    table: TableState   # leaves [N, C+1, …] sharded over dp
    params: Any         # replicated
    opt_state: Any      # replicated
    auc: AucState       # leaves [N, …] sharded over dp
    step: jax.Array


def init_sharded_auc(n: int, nbins: Optional[int] = None) -> AucState:
    s = init_auc_state(nbins)
    return AucState(*[stacked_zeros(n, l.shape, l.dtype) for l in s])


def _assert_elementwise_tx(tx: optax.GradientTransformation) -> None:
    """ZeRO-1 applies ``tx`` to each device's flat param CHUNK, which is
    only correct when the transform is elementwise (update of element i
    depends on grad/param element i alone — adam/adagrad/sgd/…). Probe:
    the update of a half-vector must equal the first half of the update
    of the full vector; transforms with global reductions
    (clip_by_global_norm, scale_by_trust_ratio, …) fail it."""
    g = jnp.linspace(0.5, 4.0, 8)
    p = jnp.ones(8)
    u_full, _ = tx.update(g, tx.init(p), p)
    u_half, _ = tx.update(g[:4], tx.init(p[:4]), p[:4])
    if not np.allclose(np.asarray(u_full)[:4], np.asarray(u_half),
                       rtol=1e-6, atol=1e-12):
        raise ValueError(
            "zero1=True requires an ELEMENTWISE optax transform: the "
            "optimizer runs on per-device param chunks, and this tx "
            "computes cross-element statistics (e.g. "
            "clip_by_global_norm), which would silently become "
            "per-chunk statistics. Apply such transforms before the "
            "reduce-scatter, or disable zero1.")


class ShardedTrainStep:
    """Builds the jitted multi-chip step for a mesh."""

    def __init__(
        self,
        model,
        tx: optax.GradientTransformation,
        sgd_cfg: SparseSGDConfig,
        mesh: Mesh,
        batch_size_per_device: int,
        num_slots: int,
        use_cvm: bool = True,
        cvm_offset: int = 2,
        zero1: bool = False,
        lr_scales: Any = None,
    ) -> None:
        """``lr_scales`` — per-leaf update multipliers (pytree matching
        params, from dense_modes.build_lr_scales): the per-param dense
        lr_map (box_wrapper.cc:1303-1335) applied after tx.update so it
        composes with Adam and the ZeRO-1 flat chunks."""
        self.model = model
        self.tx = tx
        self.lr_scales = lr_scales
        self._zero1_scaled = False  # set at init_state
        self.sgd_cfg = sgd_cfg
        self.mesh = mesh
        self.n = mesh.shape[DATA_AXIS]
        self.batch_size = batch_size_per_device
        self.num_slots = num_slots
        self.use_cvm = use_cvm
        self.cvm_offset = cvm_offset
        # ZeRO-1 dense sharding (reference: BoxPSWorker sharding stage,
        # boxps_worker.cc:601 BuildShardingDepends — params partitioned
        # across devices): each device owns a flat param chunk + its opt
        # state; grads reduce-scatter in, params all-gather out.
        # CONSTRAINT: tx must be an ELEMENTWISE transform (adam/adagrad/
        # sgd/…) — it is applied per flat per-device chunk, so transforms
        # needing a global reduction over the whole param tree (e.g.
        # clip_by_global_norm) would compute per-chunk statistics instead.
        # Enforced by probe: updating a half-vector must equal the first
        # half of updating the full vector.
        if zero1:
            _assert_elementwise_tx(tx)
        self.zero1 = zero1
        self._chunk = 0           # set at init_state
        self._unravel = None

        shard0 = P(DATA_AXIS)
        rep = P()
        state_spec = ShardedStepState(
            # spec-prefix: covers TableState's single packed leaf [N,L,128]
            table=shard0,
            params=rep, opt_state=(shard0 if zero1 else rep),
            auc=AucState(*([shard0] * len(AucState._fields))),
            step=rep)
        self._state_spec = state_spec  # shared with _resident_runner
        # public: multihost.globalize_state stages state by THIS spec
        self.state_spec = state_spec
        batch_spec = GlobalBatch(*([shard0] * len(GlobalBatch._fields)))
        stats_spec = {"loss": rep, "pred": shard0}
        self._batch_spec = batch_spec
        self._stats_spec = stats_spec
        self._sharded = jax.jit(
            jax.shard_map(
                self._device_step, mesh=mesh,
                in_specs=(state_spec, batch_spec, rep),
                out_specs=(state_spec, stats_spec),
                check_vma=False),
            donate_argnums=(0,))
        # chunked-schedule executables, one per distinct section layout
        # (FLAGS.a2a_chunks > 1; ps/sharded.plan_sections). The
        # monolithic ``self._sharded`` above stays byte-for-byte the
        # pre-chunking program — sections=() routes to it.
        self._sharded_chunked: Dict[tuple, object] = {}

    def init_params(self, mf_dim: int, dense_dim: int) -> Any:
        d = self.cvm_offset + 1 + mf_dim if self.use_cvm else 1 + mf_dim
        pooled = jnp.zeros((self.batch_size, self.num_slots, d))
        dense = jnp.zeros((self.batch_size, dense_dim))
        return self.model.init(jax.random.PRNGKey(0), pooled, dense)

    def init_state(self, table: ShardedEmbeddingTable, params: Any) -> ShardedStepState:
        if self.zero1:
            from jax.flatten_util import ravel_pytree

            flat, self._unravel = ravel_pytree(params)
            self._psize = int(flat.size)
            self._chunk = -(-self._psize // self.n)  # ceil
            pad = self.n * self._chunk - self._psize
            chunks = jnp.pad(flat, (0, pad)).reshape(self.n, self._chunk)
            opt_state = jax.vmap(self.tx.init)(chunks)
            self._zero1_scaled = self.lr_scales is not None
            if self._zero1_scaled:
                # lr_map through the flat-chunk layout: ravel per-leaf
                # multipliers exactly as params ravel, pad with 1s. The
                # chunks ride INSIDE opt_state (sharded over the mesh
                # axis) so each device holds only its own [chunk] slice —
                # a closure constant would replicate the full param-size
                # array per device, against ZeRO-1's point
                sflat, _ = ravel_pytree(jax.tree.map(
                    lambda x, s: jnp.full(x.shape, s, jnp.float32),
                    params, self.lr_scales))
                scale_chunks = jnp.pad(
                    sflat, (0, pad), constant_values=1.0).reshape(
                    self.n, self._chunk)
                opt_state = (opt_state, scale_chunks)
        else:
            opt_state = self.tx.init(params)
        return self._commit_state(ShardedStepState(
            table=table.state, params=params, opt_state=opt_state,
            auc=init_sharded_auc(self.n), step=jnp.zeros((), jnp.int32)))

    def _commit_state(self, state: ShardedStepState) -> ShardedStepState:
        """Place a freshly built state on the shardings the step program
        itself emits (``state_spec``). Fresh leaves are uncommitted
        single-device arrays — a different jit signature than the
        step's own output, so the SECOND call would recompile the whole
        step/pass program. A pod's processes stage through
        ``multihost.globalize_state`` instead."""
        if jax.process_count() > 1:
            return state
        shardings = jax.tree.map(
            lambda spec, sub: jax.tree.map(
                lambda _: NamedSharding(self.mesh, spec), sub),
            self.state_spec, state, is_leaf=lambda x: isinstance(x, P))
        return jax.device_put(state, shardings)

    # ---- dense grad sync + optimizer (shared by both schedules) ----
    def _dense_sync(self, state: ShardedStepState, g_params, me):
        """psum (SyncParam's allreduce) or ZeRO-1 reduce-scatter /
        update / all-gather → (params, opt_state). Extracted from
        ``_device_step`` unchanged (pure code motion at trace time) so
        the chunked schedule can interleave it with the push exchange."""
        if self.zero1:
            # ZeRO-1: reduce-scatter grads, update the owned flat chunk
            # with per-device opt state, all-gather fresh params
            from jax.flatten_util import ravel_pytree

            g_flat, _ = ravel_pytree(g_params)
            pad = self.n * self._chunk - self._psize
            g_mine = jax.lax.psum_scatter(
                jnp.pad(g_flat, (0, pad)).reshape(self.n, self._chunk),
                DATA_AXIS, scatter_dimension=0, tiled=True)[0]
            p_flat, _ = ravel_pytree(state.params)
            p_mine = jnp.pad(p_flat, (0, pad)).reshape(
                self.n, self._chunk)[me]
            opt_st = state.opt_state
            scale_mine = None
            if getattr(self, "_zero1_scaled", False):
                opt_st, scale_block = opt_st  # [1, chunk] device block
                scale_mine = scale_block[0]
            opt_mine = jax.tree.map(lambda l: l[0], opt_st)
            updates, opt_mine = self.tx.update(g_mine, opt_mine, p_mine)
            if scale_mine is not None:
                # per-param lr_map on this device's flat chunk
                updates = updates * scale_mine
            p_mine = optax.apply_updates(p_mine, updates)
            p_all = jax.lax.all_gather(p_mine, DATA_AXIS, tiled=True)
            params = self._unravel(p_all[:self._psize])
            opt_state = jax.tree.map(lambda l: l[None], opt_mine)
            if scale_mine is not None:
                opt_state = (opt_state, scale_block)
        else:
            # psum == SyncParam's allreduce
            g_params = jax.lax.psum(g_params, DATA_AXIS)
            updates, opt_state = self.tx.update(g_params, state.opt_state,
                                                state.params)
            if self.lr_scales is not None:
                # per-param lr_map (boxps_worker.cc:199-204)
                updates = jax.tree.map(lambda u, s: u * s, updates,
                                       self.lr_scales)
            params = optax.apply_updates(state.params, updates)
        return params, opt_state

    # ---- per-device block program (runs under shard_map) ----
    def _push(self, table, g_back, resp_idx, serve_rows, serve_valid,
              serve_slot, rows_full, rng):
        """The owner's side of the push, shared by both schedules:
        merge the routed grads into served rows, scale, update."""
        n, b = self.n, self.batch_size
        a = resp_idx.shape[1]
        g_serve = merge_rows(g_back.reshape(n * a, -1),
                             resp_idx.reshape(n * a),
                             num_segments=serve_rows.shape[0])
        # PushCopy scaling (box_wrapper.cu:368): negate embed grads ×
        # global batch size (the loss is the global mean)
        gb = jnp.concatenate(
            [g_serve[:, :2], g_serve[:, 2:] * (-1.0 * b * n)], axis=1)
        return apply_push(table, serve_rows, gb, self.sgd_cfg, rng,
                          rows_full=rows_full, touched=serve_valid > 0,
                          slot_val=serve_slot)

    def _device_step(self, state: ShardedStepState, batch: GlobalBatch,
                     rng: jax.Array, sections: tuple = ()):
        """``sections`` = () runs the monolithic pull → compute → push →
        dense-sync schedule (the pre-ISSUE-11 program, byte-for-byte).
        A grouped plan's ``(a2a_sections, key_sections, slot_sections)``
        runs the CHUNKED schedule: one all_to_all per slot group with
        the previous group's expand_pull → fused_seqpool_cvm pooling
        independent of it (the fused computation-collective
        decomposition), and the push grad all_to_all issued BEFORE the
        independent dense sync so exchange and psum/ZeRO-1 overlap.
        Both schedules are bit-identical (tests/test_sharded.py digest
        parity; docs/PERFORMANCE.md §Sharded-step overlap). Either
        schedule's pooling (fused_seqpool_cvm / the slot-group variant)
        rides the FLAGS.use_pallas_seqpool dispatch seam onto the fused
        Pallas MXU kernel (docs/PERFORMANCE.md §Device kernels)."""
        n, b, s = self.n, self.batch_size, self.num_slots
        me = jax.lax.axis_index(DATA_AXIS)
        # blocks arrive with leading dim 1; drop it
        table = state.table.with_packed(state.table.packed[0])
        auc = AucState(*[l[0] for l in state.auc])
        resp_idx = batch.resp_idx[0]       # [N, A]
        serve_rows = batch.serve_rows[0]   # [A2]
        serve_valid = batch.serve_valid[0]
        serve_slot = batch.serve_slot[0]
        gather_idx = batch.gather_idx[0]   # [K]
        segments = batch.segments[0]
        dense = batch.dense[0]
        label = batch.label[0]
        show = batch.show[0]
        clk = batch.clk[0]
        a = resp_idx.shape[1]
        d = 3 + table.mf_dim
        # the pbox.* scope catalog of obs/trace (metadata only): the
        # single-chip step's names plus the two exchanges
        scope = jax.named_scope

        if not sections:
            # ---- pull: serve my rows, exchange, reassemble ----
            # one AoS gather serves the pull AND the push optimizer state
            with scope(trace.SCOPE_PULL):
                rows_full = gather_full_rows(table, serve_rows)  # [A2, F]
                serve_vals = pull_values(rows_full,
                                         table.mf_dim)         # [A2, D]
                # lane-packed expand (ps/table.expand_pull): narrow-row
                # gathers and their autodiff transposes run at line
                # granularity
                resp = expand_pull(serve_vals,
                                   resp_idx.reshape(-1)).reshape(n, a, d)
            with scope(trace.SCOPE_A2A_PULL):
                recv = jax.lax.all_to_all(resp, DATA_AXIS, 0, 0,
                                          tiled=True)
            vals_flat = recv.reshape(n * a, d)

            ins_w = (show > 0).astype(jnp.float32)
            wsum_global = jax.lax.psum(jnp.sum(ins_w), DATA_AXIS)
            batch_show_clk = jnp.stack([show, clk], axis=1)

            def loss_fn(params, vals_flat):
                with scope(trace.SCOPE_PULL):
                    values_k = expand_pull(vals_flat, gather_idx)
                with scope(trace.SCOPE_POOL_CVM):
                    pooled = fused_seqpool_cvm(
                        values_k, segments, batch_show_clk, b, s,
                        self.use_cvm, self.cvm_offset)
                with scope(trace.SCOPE_DENSE):
                    logits = self.model.apply(params, pooled, dense)
                with scope(trace.SCOPE_LOSS):
                    ls = optax.sigmoid_binary_cross_entropy(logits, label)
                    loss_local = jnp.sum(ls * ins_w) / jnp.maximum(
                        wsum_global, 1.0)
                return loss_local, logits

            (loss_local, logits), (g_params, g_vals_flat) = \
                jax.value_and_grad(loss_fn, argnums=(0, 1),
                                   has_aux=True)(state.params, vals_flat)

            # ---- push: route grads back to owners, merge, update ----
            with scope(trace.SCOPE_A2A_PUSH):
                g_back = jax.lax.all_to_all(
                    g_vals_flat.reshape(n, a, d), DATA_AXIS, 0, 0,
                    tiled=True)
            with scope(trace.SCOPE_PUSH):
                table = self._push(table, g_back, resp_idx, serve_rows,
                                   serve_valid, serve_slot, rows_full,
                                   jax.random.fold_in(rng, me))

            # ---- dense sync ----
            with scope(trace.SCOPE_DENSE_OPT):
                params, opt_state = self._dense_sync(state, g_params, me)
        else:
            # ---- chunked exchange-compute schedule (ISSUE 11) ----
            # "Optimizing Distributed ML Communication with Fused
            # Computation-Collective Operations" (PAPERS.md): decompose
            # the pull all_to_all along slot groups; chunk g+1's
            # exchange has no data dependency on chunk g's pooling, so
            # XLA's latency-hiding scheduler can fly the ICI transfer
            # while the MXU pools the previous group.
            a_secs, k_secs, s_secs = sections
            a_off = section_offsets(a_secs)
            k_off = section_offsets(k_secs)
            s_off = section_offsets(s_secs)
            with scope(trace.SCOPE_PULL):
                rows_full = gather_full_rows(table, serve_rows)  # [A2, F]
                serve_vals = pull_values(rows_full,
                                         table.mf_dim)         # [A2, D]
            recvs = []
            for g, ag in enumerate(a_secs):
                lo = a_off[g]
                with scope(trace.SCOPE_PULL):
                    resp_g = expand_pull(
                        serve_vals,
                        resp_idx[:, lo:lo + ag].reshape(-1)
                    ).reshape(n, ag, d)
                with scope(trace.SCOPE_A2A_PULL):
                    recv_g = jax.lax.all_to_all(resp_g, DATA_AXIS, 0, 0,
                                                tiled=True)
                recvs.append(recv_g.reshape(n * ag, d))

            ins_w = (show > 0).astype(jnp.float32)
            wsum_global = jax.lax.psum(jnp.sum(ins_w), DATA_AXIS)
            batch_show_clk = jnp.stack([show, clk], axis=1)

            def loss_fn(params, recvs):
                # per-group expand → pool; blocks concat in canonical
                # slot order, bit-identical to the monolithic pool
                # (bins are per-slot; the grouped plan is stable)
                parts = []
                for g, (ag, kg, sg) in enumerate(
                        zip(a_secs, k_secs, s_secs)):
                    gi = gather_idx[k_off[g]:k_off[g] + kg]
                    seg = segments[k_off[g]:k_off[g] + kg]
                    # global position owner*A + j → chunk-local (ONE
                    # definition, shared with the probe)
                    with scope(trace.SCOPE_PULL):
                        local = chunk_local_positions(gi, a, a_off[g],
                                                      ag)
                        values_k = expand_pull(recvs[g], local)
                    with scope(trace.SCOPE_POOL_CVM):
                        parts.append(fused_seqpool_cvm_slot_group(
                            values_k, seg, batch_show_clk, b, s,
                            s_off[g], s_off[g] + sg,
                            self.use_cvm, self.cvm_offset))
                with scope(trace.SCOPE_POOL_CVM):
                    pooled = jnp.concatenate(parts, axis=1)
                with scope(trace.SCOPE_DENSE):
                    logits = self.model.apply(params, pooled, dense)
                with scope(trace.SCOPE_LOSS):
                    ls = optax.sigmoid_binary_cross_entropy(logits, label)
                    loss_local = jnp.sum(ls * ins_w) / jnp.maximum(
                        wsum_global, 1.0)
                return loss_local, logits

            (loss_local, logits), (g_params, g_recvs) = \
                jax.value_and_grad(loss_fn, argnums=(0, 1),
                                   has_aux=True)(state.params,
                                                 tuple(recvs))

            # ---- push: ONE grad all_to_all on the reassembled
            # canonical [n, A, d] wire, issued BEFORE the independent
            # dense sync so the exchange overlaps psum/ZeRO-1 (the
            # monolithic path runs them strictly in sequence); merge /
            # apply_push then see exactly the monolithic layout
            with scope(trace.SCOPE_A2A_PUSH):
                g_vals = jnp.concatenate(
                    [gr.reshape(n, ag, d)
                     for gr, ag in zip(g_recvs, a_secs)], axis=1)
                g_back = jax.lax.all_to_all(g_vals, DATA_AXIS, 0, 0,
                                            tiled=True)
            with scope(trace.SCOPE_DENSE_OPT):
                params, opt_state = self._dense_sync(state, g_params, me)
            with scope(trace.SCOPE_PUSH):
                table = self._push(table, g_back, resp_idx, serve_rows,
                                   serve_valid, serve_slot, rows_full,
                                   jax.random.fold_in(rng, me))

        with scope(trace.SCOPE_AUC):
            pred = jax.nn.sigmoid(logits)
            auc = auc_add_batch(auc, pred, label, ins_w)
        with scope(trace.SCOPE_LOSS):
            loss = jax.lax.psum(loss_local, DATA_AXIS)

        new_state = ShardedStepState(
            table=table.with_packed(table.packed[None]),
            params=params, opt_state=opt_state,
            auc=AucState(*[l[None] for l in auc]),
            step=state.step + 1)
        # pred stays device-sharded [N, B]; consumers (dump, registry)
        # fetch it only when configured
        return new_state, {"loss": loss, "pred": pred[None]}

    def _step_fn_for(self, sections: tuple):
        """The jitted step for a chunk-schedule key (() = monolithic).
        One executable per distinct section layout; the resident
        builder's uniform-shape contract keeps that to ~1 per pass."""
        if not sections:
            return self._sharded
        fn = self._sharded_chunked.get(sections)
        if fn is None:
            def step(state, batch, rng, _s=sections):
                return self._device_step(state, batch, rng, sections=_s)

            fn = self._sharded_chunked[sections] = jax.jit(
                jax.shard_map(
                    step, mesh=self.mesh,
                    in_specs=(self._state_spec, self._batch_spec, P()),
                    out_specs=(self._state_spec, self._stats_spec),
                    check_vma=False),
                donate_argnums=(0,))
        return fn

    def __call__(self, state: ShardedStepState, batch: GlobalBatch,
                 rng: jax.Array, sections: tuple = ()):
        return self._step_fn_for(sections)(state, batch, rng)

    # ---- forward-only mesh eval (test-phase run) ----
    def _device_eval(self, table_st: TableState, params, auc_st: AucState,
                     batch: GlobalBatch) -> AucState:
        n, b, s = self.n, self.batch_size, self.num_slots
        table = table_st.with_packed(table_st.packed[0])
        auc = AucState(*[l[0] for l in auc_st])
        resp_idx = batch.resp_idx[0]
        serve_rows = batch.serve_rows[0]
        gather_idx = batch.gather_idx[0]
        segments = batch.segments[0]
        dense = batch.dense[0]
        label = batch.label[0]
        show = batch.show[0]
        clk = batch.clk[0]
        a = resp_idx.shape[1]
        d = 3 + table.mf_dim

        serve_vals = pull_values(gather_full_rows(table, serve_rows),
                                 table.mf_dim)
        resp = serve_vals[resp_idx]
        recv = jax.lax.all_to_all(resp, DATA_AXIS, 0, 0, tiled=True)
        vals_flat = recv.reshape(n * a, d)
        values_k = vals_flat[gather_idx]
        pooled = fused_seqpool_cvm(
            values_k, segments, jnp.stack([show, clk], axis=1), b, s,
            self.use_cvm, self.cvm_offset)
        logits = self.model.apply(params, pooled, dense)
        ins_w = (show > 0).astype(jnp.float32)
        pred = jax.nn.sigmoid(logits)
        auc = auc_add_batch(auc, pred, label, ins_w)
        return AucState(*[l[None] for l in auc]), pred[None]

    def eval(self, table_st: TableState, params, auc_st: AucState,
             batch: GlobalBatch):
        """→ (AucState, pred [N, B]) — pred feeds the metric registry."""
        if not hasattr(self, "_eval_jit"):
            shard0 = P(DATA_AXIS)
            rep = P()
            auc_spec = AucState(*([shard0] * len(AucState._fields)))
            batch_spec = GlobalBatch(
                *([shard0] * len(GlobalBatch._fields)))
            self._eval_jit = jax.jit(jax.shard_map(
                self._device_eval, mesh=self.mesh,
                in_specs=(shard0, rep, auc_spec, batch_spec),
                out_specs=(auc_spec, shard0), check_vma=False),
                donate_argnums=(2,))
        return self._eval_jit(table_st, params, auc_st, batch)

    # ---- resident pass: the whole loop inside one shard_map program ----
    def _resident_runner(self, n_steps: int, fmt=None, capacity=0,
                         collect: bool = False, sections: tuple = ()):
        key = ("resident", n_steps, fmt, capacity, collect, sections)
        cached = getattr(self, "_resident_cache", None)
        if cached is None:
            cached = self._resident_cache = {}
        if key not in cached:
            rep = P()
            state_spec = self._state_spec
            fmt_d = dict(fmt) if fmt else None


            def run(state, wire, start, rng):
                def body(i, carry):
                    st, r, preds = carry
                    gb = (GlobalBatch(*[leaf[i] for leaf in wire])
                          if fmt_d is None else
                          _decode_wire_step(wire, fmt_d, i, capacity))
                    # per-step rng matching the streaming trainer exactly:
                    # it folds the PRE-incremented global_step (1-based)
                    st, stats = self._device_step(
                        st, gb, jax.random.fold_in(r, st.step + 1),
                        sections=sections)
                    if collect:
                        # per-batch predictions collected inside the loop
                        # (the single-chip collect_preds pattern,
                        # device_pass.py run_pass) — stays device-sharded
                        preds = jax.lax.dynamic_update_index_in_dim(
                            preds, stats["pred"], i - start, 0)
                    return st, r, preds

                preds0 = (jnp.zeros((n_steps, 1, self.batch_size),
                                    jnp.float32) if collect
                          else jnp.zeros((), jnp.float32))
                state, _, preds = jax.lax.fori_loop(
                    start, start + n_steps, body, (state, rng, preds0))
                return (state, preds) if collect else state

            def make_specs(we):
                if isinstance(we, dict):
                    return {name: tuple(_wire_spec(name, a.ndim)
                                        for a in arrs)
                            for name, arrs in we.items()}
                return jax.tree.map(
                    lambda a: _wire_spec("", a.ndim), we)

            out_specs = ((state_spec, P(None, DATA_AXIS, None))
                         if collect else state_spec)

            def jit_for(wire_example):
                return jax.jit(
                    jax.shard_map(run, mesh=self.mesh,
                                  in_specs=(state_spec,
                                            make_specs(wire_example),
                                            rep, rep),
                                  out_specs=out_specs, check_vma=False),
                    donate_argnums=(0,))

            # resolved lazily at first call (needs the wire pytree)
            cached[key] = _LazyJit(jit_for)
        return cached[key]

    def run_resident(self, state: ShardedStepState, rp, rng: jax.Array,
                     chunk: int = 0, collect_preds: bool = False):
        """Run every staged global batch of a ShardedResidentPass.
        ``collect_preds`` also returns [nb, N, B] per-batch predictions
        (device-sharded on axis 1) for the post-pass registry replay."""
        seq = getattr(rp, "pass_seq", None)
        with trace.span("pass.upload", pass_seq=seq,
                        staged=rp.dev is not None):
            rp.upload()
        nb = rp.num_batches
        fmt = getattr(rp, "fmt", None)
        fmt_key = tuple(sorted(fmt.items())) if fmt else None
        c = chunk or nb
        i = 0
        chunks = []
        with trace.span("pass.dispatch", pass_seq=seq,
                        chunks=-(-nb // c)):
            while i < nb:
                n = min(c, nb - i)
                out = self._resident_runner(
                    n, fmt_key, getattr(rp, "capacity", 0) or 0,
                    collect=collect_preds,
                    sections=getattr(rp, "sections", ()))(
                    state, rp.dev, jnp.asarray(i, jnp.int32), rng)
                if collect_preds:
                    state, preds = out
                    chunks.append(preds)
                else:
                    state = out
                i += n
        if not collect_preds:
            return state, None
        return state, (chunks[0] if len(chunks) == 1
                       else jnp.concatenate(chunks, axis=0))


def group_batches(batches, n: int):
    """Pack a batch stream into groups of ``n``; the tail group is padded
    by repeating the last batch with show=0 AND clk=0 (so neither loss,
    metrics, nor the pushed counters see the duplicated instances).
    Shared by every mesh trainer (ShardedTrainer, MultiMfShardedTrainer)."""
    import dataclasses as _dc
    group: List[SlotBatch] = []
    for bt in batches:
        group.append(bt)
        if len(group) == n:
            yield group
            group = []
    if group:
        filler = group[-1]
        dead = _dc.replace(filler, show=np.zeros_like(filler.show),
                           clk=np.zeros_like(filler.clk))
        while len(group) < n:
            group.append(dead)
        yield group


class ShardedTrainer:
    """Multi-chip trainer: groups the batch stream into N-device global
    batches, builds routing plans on host (prefetched), runs the sharded
    step. The BoxPSTrainer::Run role with the mesh replacing worker threads."""

    def __init__(self, model, table: ShardedEmbeddingTable, desc, mesh: Mesh,
                 tx: Optional[optax.GradientTransformation] = None,
                 use_cvm: bool = True, prefetch: int = 4, seed: int = 0,
                 zero1: bool = False, float_wire: str = "f32",
                 lr_map: Optional[dict] = None,
                 lr_map_base: float = 1.0) -> None:
        """``float_wire="q8"`` ships resident-pass dense/label/show/clk
        as the int8 affine wire (opt-in: ~1e-2 dense rounding).

        ``lr_map`` — per-param dense learning-rate overrides, name
        (path-substring) → lr, against ``lr_map_base`` (the tx's base
        lr): each matched leaf's UPDATE scales by lr/lr_map_base, so 0.0
        freezes a param (InitializeGPUAndLoadModel's lr_map,
        box_wrapper.cc:1303-1335; consumed boxps_worker.cc:199-204).
        Respected by both the psum mode and the zero1 flat chunks."""
        import threading as _threading

        from paddlebox_tpu.config import FLAGS
        # chunked exchange-compute schedule (ISSUE 11): slot-group
        # chunks for the pull all_to_all + push/dense-sync interleave.
        # Read once at construction; 1 = the monolithic schedule.
        self.a2a_chunks = max(1, int(FLAGS.a2a_chunks))
        self.float_wire = float_wire
        self.model = model
        self.table = table
        self.desc = desc
        self.mesh = mesh
        self.n = mesh.shape[DATA_AXIS]
        self.tx = tx or optax.adam(1e-3)
        lr_scales = None
        params = None
        if lr_map:
            from paddlebox_tpu.train.dense_modes import build_lr_scales
            from paddlebox_tpu.train.step import TrainStep
            # deterministic param init (same formula as init_params) so
            # the scales can ride the constructor, not a post-hoc poke
            params = TrainStep.init_params_for(
                model, desc.batch_size, len(desc.sparse_slots),
                table.mf_dim, desc.dense_dim, use_cvm=use_cvm)
            lr_scales = build_lr_scales(params, lr_map, lr_map_base)
        self.step_fn = ShardedTrainStep(
            model, self.tx, table.cfg, mesh, desc.batch_size,
            len(desc.sparse_slots), use_cvm=use_cvm, zero1=zero1,
            lr_scales=lr_scales)
        if params is None:
            params = self.step_fn.init_params(table.mf_dim, desc.dense_dim)
        self.state = self.step_fn.init_state(table, params)
        self._rng = jax.random.PRNGKey(seed + 1)
        self.global_step = 0
        self.prefetch = prefetch
        self._threading = _threading
        self._dump_cfg = None
        # metric-variant registry at pod scale (init_metric /
        # get_metric_msg — the AddAucMonitor feed runs per device row)
        from paddlebox_tpu.metrics import MetricRegistry
        self.metrics = MetricRegistry()

    def set_dump(self, cfg) -> None:
        """Enable per-sample prediction dump for subsequent streaming
        passes — the every-worker DumpField role (boxps_worker.cc:1595);
        pass None to disable. Each device row of the global batch dumps
        in device order (the mesh's worker order).

        On a multi-process pod each process dumps only its ADDRESSABLE
        device rows into its own ``.part-<rank>`` shard — the
        reference's per-worker dump channel (every worker writes its own
        file; no global addressing). Concatenating the rank shards in
        device order reproduces the single-controller dump
        line-for-line (tested 2-process in test_multihost_train.py)."""
        self._dump_cfg = cfg

    @staticmethod
    def _addressable_rows(arr, axis: int = 0):
        """Yield (device_row, row_slice) for the rows of a global array
        this process can address, in device order — the per-worker feed
        contract (each worker sees its own rows; single-controller sees
        all of them). Single-controller yields LAZY device slices (the
        metric feed then stays on device — no per-batch D2H in the hot
        loop); a pod yields np views of the local shards."""
        if jax.process_count() == 1:
            for d in range(arr.shape[axis]):
                yield d, (arr[d] if axis == 0
                          else jnp.take(arr, d, axis=axis))
            return
        seen = set()
        shards = sorted(arr.addressable_shards,
                        key=lambda s: s.index[axis].start or 0)
        for sh in shards:
            i0 = sh.index[axis].start or 0
            data = np.asarray(sh.data)
            for j in range(data.shape[axis]):
                d = i0 + j
                if d in seen:
                    continue  # replicated shard
                seen.add(d)
                yield d, np.take(data, j, axis=axis)

    def _group_iter(self, batches):
        return group_batches(batches, self.n)

    def _stage_batch(self, group, idx) -> "GlobalBatch":
        """Stage one global batch for the step: single-controller puts
        host arrays straight on the mesh; a multi-controller pod routes
        through make_array_from_process_local_data (every process built
        the identical host arrays — the SPMD prep contract,
        train/multihost.py)."""
        if jax.process_count() > 1:
            from paddlebox_tpu.train.multihost import stage_global_batch
            return stage_global_batch(
                self.mesh, make_global_arrays(group, idx))
        return make_global_batch(group, idx)

    def _prefetch_iter(self, batches):
        from paddlebox_tpu.utils.prefetch import prefetch_iter

        def prep(group):
            idx = self.table.prepare_global(group,
                                            groups=self.a2a_chunks)
            return (group, self._stage_batch(group, idx),
                    plan_sections(idx))

        return prefetch_iter(self._group_iter(batches), prep,
                             capacity=self.prefetch,
                             name="sharded.prepare")

    def train_pass(self, dataset, log_prefix: str = "") -> Dict[str, float]:
        from paddlebox_tpu.metrics import auc_compute
        from paddlebox_tpu.utils import Timer
        from paddlebox_tpu.utils.logging import get_logger
        log = get_logger(__name__)
        timer = Timer()
        timer.start()
        nb = 0
        stats = None
        # one DumpWriter per ADDRESSABLE device row — the reference's
        # one-dump-channel-per-worker model (boxps_worker.cc:1595: each
        # of the N per-GPU workers writes its own file). Part files are
        # keyed by DEVICE row, so a pod run's per-rank files are
        # byte-identical to the single-controller run's.
        dump_writers: Dict[int, object] = {}

        def writer_for(d: int):
            w = dump_writers.get(d)
            if w is None:
                import copy

                from paddlebox_tpu.utils.dump import DumpWriter
                cfg = copy.copy(self._dump_cfg)
                cfg.rank = cfg.rank + d
                w = dump_writers[d] = DumpWriter(cfg)
            return w

        if self._dump_cfg is not None:
            # eager part-file creation for every addressable device row:
            # a row whose batches are all tail filler must still leave
            # an (empty) shard, so device-order concatenation consumers
            # never hit a file gap
            for d, dev in enumerate(self.mesh.devices.ravel()):
                if dev.process_index == jax.process_index():
                    writer_for(d)

        for group, gb, secs in self._prefetch_iter(dataset.batches()):
            self.global_step += 1
            rng = jax.random.fold_in(self._rng, self.global_step)
            self.state, stats = self.step_fn(self.state, gb, rng, secs)
            nb += 1
            want_dump = (self._dump_cfg is not None
                         and nb % self._dump_cfg.interval == 0)
            if len(self.metrics) or want_dump:
                # ONE pass over this process's ADDRESSABLE device rows
                # (worker order) feeds the metric registry
                # (AddAucMonitor) and the dump — the per-worker model:
                # each process handles its own rows; registry partials
                # merge across the pod inside compute()
                # (metrics_ext._pod_sum_tree)
                for d, pred_d in self._addressable_rows(stats["pred"]):
                    b = group[d]
                    n_real = int((b.show > 0).sum())
                    if n_real == 0:
                        continue  # tail-group filler (dead batch)
                    if len(self.metrics):
                        self.metrics.add_batch(
                            pred_d, b.label,
                            (b.show > 0).astype(np.float32), uid=b.uid,
                            rank=b.rank, cmatch=b.cmatch)
                    if want_dump:
                        writer_for(d).add_batch(
                            b.ins_ids,
                            {"pred": pred_d, "label": b.label,
                             "show": b.show, "clk": b.clk}, n_real)
        for w in dump_writers.values():
            w.close()
        timer.pause()
        self.table.state = self.state.table
        res = auc_compute(self._finalize_auc(self.state.auc))
        out = res.as_dict()
        out.update(
            batches=nb, elapsed_sec=timer.elapsed_sec(),
            examples_per_sec=res.ins_num / max(timer.elapsed_sec(), 1e-9),
            last_loss=(self._host_scalar(stats["loss"])
                       if stats is not None else float("nan")))
        log.info("%ssharded pass done: %d global batches, %.0f ex/s, auc=%.4f",
                 log_prefix, nb, out["examples_per_sec"], res.auc)
        from paddlebox_tpu.obs.hub import emit_pass_event
        emit_pass_event("train_pass_sharded",
                        dict(out, global_step=self.global_step),
                        table=self.table, examples=int(res.ins_num))
        return out

    def _finalize_auc(self, auc) -> "AucState":
        """Per-shard AUC leaves → one host AucState. On a pod the leaves
        are global arrays whose shards live on other processes — eager
        reduction is illegal there, so the sum runs jitted with a
        replicated out-sharding every process can read."""
        if jax.process_count() > 1:
            if getattr(self, "_auc_reduce_jit", None) is None:
                from jax.sharding import NamedSharding, PartitionSpec
                self._auc_reduce_jit = jax.jit(
                    lambda ls: tuple(jnp.sum(l, axis=0) for l in ls),
                    out_shardings=NamedSharding(self.mesh,
                                                PartitionSpec()))
            reduced = self._auc_reduce_jit(tuple(auc))
            return AucState(*[np.asarray(jax.device_get(x))
                              for x in reduced])
        return AucState(*[jnp.sum(l, axis=0) for l in auc])

    @staticmethod
    def _host_scalar(x) -> float:
        """float() of a step stat that may be a non-fully-addressable
        global array on a pod (every process holds the same replicated
        value in its addressable shard)."""
        shards = getattr(x, "addressable_shards", None)
        if shards:
            return float(np.ravel(np.asarray(shards[0].data))[0])
        return float(x)

    def reset_metrics(self) -> None:
        self.state = self.state._replace(auc=init_sharded_auc(self.n))

    # ---- checkpoint hooks (CheckpointManager trainer contract) ----
    def sync_table(self) -> None:
        self.table.state = self.state.table

    def fence_table(self) -> None:
        """Drain the table's async end_pass epilogue (ps/epilogue);
        surfaces the first write-back failure. Checkpoint capture and
        every host-tier read fence implicitly — this is the explicit
        hook for scripts/benches that white-box the host stores."""
        fence = getattr(self.table, "fence", None)
        if fence is not None:
            fence()

    def adopt_table(self) -> None:
        """Point the jit state at the table's (re)built device state —
        called after a tiered table's begin_pass promotes a new pass
        window into the HBM shards."""
        self.state = self.state._replace(table=self.table.state)

    def globalize_dense_state(self) -> None:
        """Stage a locally-initialized step state onto the global mesh
        following the step's own sharding spec (globalize_state, now
        idempotent on already-global leaves — a multihost table's state
        passes through untouched)."""
        from paddlebox_tpu.train.multihost import globalize_state
        self.state = globalize_state(
            self.mesh, self.state._replace(table=self.table.state),
            self.step_fn.state_spec)

    def dense_snapshot(self):
        """Host snapshot of the dense checkpoint state (CheckpointManager
        hook). Pod-safe: params/opt_state are replicated (addressable
        everywhere); the per-shard AUC leaves are NOT, so they ship as
        the shard-REDUCED host AucState — additive state, restored as
        shard 0's content + zeros (identical totals)."""
        return jax.device_get((self.state.params, self.state.opt_state,
                               self._finalize_auc(self.state.auc)))

    def restore_state(self, params, opt_state, auc, step: int) -> None:
        auc = AucState(*[np.asarray(l) for l in auc])
        n_dims = jax.tree.leaves(init_auc_state())[0].ndim
        if auc[0].ndim == n_dims:
            # REDUCED host AucState (dense_snapshot): rebuild the
            # per-shard layout — all mass on shard 0, zeros elsewhere
            # (the finalize sum is invariant)
            auc = AucState(*[
                np.concatenate([l[None],
                                np.zeros((self.n - 1,) + l.shape,
                                         l.dtype)])
                for l in auc])
        self.state = ShardedStepState(
            table=self.table.state, params=params, opt_state=opt_state,
            auc=AucState(*[jnp.asarray(l) for l in auc])
            if jax.process_count() == 1 else auc,
            step=np.asarray(step, np.int32))
        if jax.process_count() > 1:
            # spec-driven staging (no hand-coded layout): the table leaf
            # — local after table.load, or already-global for multihost
            # tables — stages or passes through per globalize_state
            self.globalize_dense_state()
        else:
            self.state = self.state._replace(
                params=jax.device_put(params),
                opt_state=jax.device_put(opt_state),
                step=jnp.asarray(step, jnp.int32))
        self.global_step = step

    def eval_pass(self, dataset, log_prefix: str = "") -> Dict[str, float]:
        """Forward-only mesh pass: pull + model over the device axis,
        no pushes, no dense update; AUC reduced across shards (the
        test-phase run of the reference workers, at pod scale)."""
        from paddlebox_tpu.metrics import auc_compute
        from paddlebox_tpu.utils import Timer
        from paddlebox_tpu.utils.logging import get_logger
        log = get_logger(__name__)
        timer = Timer()
        timer.start()
        auc = init_sharded_auc(self.n)
        nb = 0
        for group, gb in self._prefetch_iter_eval(dataset.batches()):
            auc, preds = self.step_fn.eval(
                self.state.table, self.state.params, auc, gb)
            nb += 1
            if len(self.metrics):
                # test-phase AddAucMonitor feed over this process's
                # addressable rows (per-worker model — see set_dump)
                for d, pred_d in self._addressable_rows(preds):
                    b = group[d]
                    ins_w = (b.show > 0).astype(np.float32)
                    if not ins_w.any():
                        continue  # tail-group filler
                    self.metrics.add_batch(
                        pred_d, b.label, ins_w, uid=b.uid,
                        rank=b.rank, cmatch=b.cmatch)
        timer.pause()
        res = auc_compute(self._finalize_auc(auc))
        out = res.as_dict()
        out.update(batches=nb, elapsed_sec=timer.elapsed_sec(),
                   examples_per_sec=res.ins_num /
                   max(timer.elapsed_sec(), 1e-9))
        log.info("%ssharded eval pass: %d global batches, auc=%.4f",
                 log_prefix, nb, res.auc)
        return out

    def _prefetch_iter_eval(self, batches):
        from paddlebox_tpu.utils.prefetch import prefetch_iter

        def prep(group):
            # read-only routing: lookup instead of assign (unknown keys
            # serve the zero sentinel row, prepare_eval semantics)
            return group, self._stage_batch(
                group, self.table.prepare_global_eval(group))

        return prefetch_iter(self._group_iter(batches), prep,
                             capacity=self.prefetch,
                             name="sharded.prepare_eval")

    # ---- device-resident passes over the mesh ----
    def build_resident_pass(self, dataset) -> "ShardedResidentPass":
        """Build (and on preloader threads, overlap) one pass's staged
        plan. Tiered tables get the build bracketed in ``plan_scope``:
        new keys become value-less PENDING rows the next begin_pass
        reconciles with their staged host values — which makes
        ``PassPreloader(build_fn=trainer.build_resident_pass)`` legal
        over a pass-window table (preload_into_memory,
        box_wrapper.h:1142-1156). Depth-N preloaders may hold SEVERAL
        future passes' plans pending at once — each build gets its own
        plan_scope bracket, pendings promote at their own begin_pass,
        and the window capacity contract grows to the union of the
        open pass's and every queued pass's working set
        (ps/tiered.py module docstring)."""
        scope = getattr(self.table, "plan_scope", None)
        if scope is None:
            rp = ShardedResidentPass.build(dataset, self)
        else:
            with scope():
                rp = ShardedResidentPass.build(dataset, self)
        # SSD promote prefetch (ps/ssd.py): with a disk tier holding
        # rows, promote this pass's spilled working set host-ward NOW —
        # on a preloader worker this overlaps the open pass's training,
        # so the later stage fetch hits RAM and begin_pass never stalls
        # on segment reads (LoadSSD2Mem inside the build stage)
        pf = getattr(self.table, "prefetch_promote", None)
        if (pf is not None and hasattr(dataset, "pass_keys")
                and getattr(self.table, "has_spilled_rows",
                            lambda: False)()):
            from paddlebox_tpu.train.device_pass import poll_preload_abort
            poll_preload_abort()
            pf(dataset.pass_keys())
        return rp

    def tiered_pass_pipeline(self, datasets,
                             depth: "Optional[int]" = None):
        """The tiered pass pipeline (ISSUE 9): a
        ``train/device_pass.PassPipeline`` wired for this trainer's
        pass-window table — builds (plan_scope + prefetch_promote), the
        H2D wire and the host-tier feed-pass fetch all ride the
        depth-N preloader worker, begin_pass is reconcile-only, and
        end_pass's epilogue lane carries async capacity eviction.
        ``depth=0`` = the sequential kick-per-pass control."""
        from paddlebox_tpu.train.device_pass import PassPipeline
        return PassPipeline(iter(datasets),
                            build_fn=self.build_resident_pass,
                            window_table=self.table, trainer=self,
                            depth=depth)

    def train_passes_tiered(self, datasets, depth: "Optional[int]" = None,
                            log_prefix: str = "") -> list:
        """Drive tiered resident passes end to end through the unified
        pipeline: one call per dataset list, returns the per-pass
        result dicts (the tiered twin of
        Trainer.train_passes_resident)."""
        pipe = self.tiered_pass_pipeline(datasets, depth=depth)
        pipe.start_next()
        sequential = depth == 0   # the no-overlap kick-per-pass control
        results = []
        try:
            while True:
                rp = pipe.wait()
                if rp is None:
                    break
                pipe.begin_pass()
                if not sequential:
                    pipe.start_next()
                results.append(self.train_pass_resident(
                    rp, log_prefix=log_prefix))
                pipe.end_pass()
                if sequential:
                    # the next build+stage only AFTER this pass closed
                    pipe.start_next()
        finally:
            pipe.drain()
        return results

    def _feed_registry_resident(self, rp, preds) -> None:
        """Post-pass metric registry replay (the per-batch AddAucMonitor
        hook, boxps_worker.cc:1267,1337) from predictions collected
        inside the mesh fori_loop — the mesh analogue of the single-chip
        Trainer._feed_registry_resident. One D2H fetch per addressable
        device column ([nb, 1, B] each): on a pod every process replays
        only its own workers' rows (side channels are host-global per
        the SPMD prep contract) and the registry partials merge inside
        compute()."""
        sd = rp.side
        for dcol, pred_col in self._addressable_rows(preds, axis=1):
            # pred_col: [nb, B] — this device column across the pass
            for i in range(rp.num_batches):
                ins_w = (sd["show"][i, dcol] > 0).astype(np.float32)
                if not ins_w.any():
                    continue  # tail-group filler (dead batch)
                self.metrics.add_batch(
                    pred_col[i], sd["label"][i, dcol], ins_w,
                    uid=None if sd["uid"] is None else sd["uid"][i, dcol],
                    rank=(None if sd["rank"] is None
                          else sd["rank"][i, dcol]),
                    cmatch=(None if sd["cmatch"] is None
                            else sd["cmatch"][i, dcol]))

    def train_pass_resident(self, pass_or_dataset,
                            log_prefix: str = "") -> Dict[str, float]:
        """Mesh analogue of Trainer.train_pass_resident: the whole pass's
        global batches (routing plans + features) are staged to HBM,
        sharded over the device axis, and the pass runs as ONE
        lax.fori_loop inside the shard_map program — per-step host work
        and H2D hops are zero; embedding all_to_all / dense psum happen
        inside the loop body exactly as in the streaming step."""
        prebuilt = isinstance(pass_or_dataset, ShardedResidentPass)
        seq = (pass_or_dataset.pass_seq if prebuilt else None) \
            or trace.next_pass_seq()
        # the pass boundary under the same span names as
        # Trainer.train_pass_resident (obs/trace)
        with trace.span("pass.train", pass_seq=seq) as sp:
            out, rp = self._train_pass_resident(pass_or_dataset, seq,
                                                log_prefix)
            sp.attrs.update(records=rp.num_records,
                            batches=rp.num_batches)
        return out

    def _train_pass_resident(self, pass_or_dataset, seq: int,
                             log_prefix: str):
        """The body of ``train_pass_resident`` → (result, the pass)."""
        from paddlebox_tpu.metrics import auc_compute
        from paddlebox_tpu.utils import Timer
        from paddlebox_tpu.utils.logging import get_logger
        log = get_logger(__name__)
        timer = Timer()
        timer.start()
        if isinstance(pass_or_dataset, ShardedResidentPass):
            rp = pass_or_dataset
        else:
            rp = self.build_resident_pass(pass_or_dataset)
            rp.pass_seq = seq
        want_metrics = len(self.metrics) > 0
        if want_metrics and rp.side is None:
            log.warning(
                "registry metrics need the pass's side channels — this "
                "prebuilt ShardedResidentPass predates them; rebuild it "
                "with build_resident_pass, or use train_pass")
            want_metrics = False
        # consume span: links back to this pass's build span on the
        # preloader lane (obs/trace — the build→consume flow arrow)
        with trace.span("pass.consume",
                        link_from=getattr(rp, "_trace_span_id", 0)):
            self.state, preds = self.step_fn.run_resident(
                self.state, rp, self._rng, collect_preds=want_metrics)
            with trace.span("pass.device_wait"):
                jax.block_until_ready(self.state.step)
        rp.mark_trained_rows(self.table)
        if want_metrics:
            self._feed_registry_resident(rp, preds)
        self.global_step += rp.num_batches
        timer.pause()
        with trace.span("pass.finish"):
            self.table.state = self.state.table
            res = auc_compute(self._finalize_auc(self.state.auc))
            out = res.as_dict()
            out.update(batches=rp.num_batches,
                       elapsed_sec=timer.elapsed_sec(),
                       examples_per_sec=rp.num_records /
                       max(timer.elapsed_sec(), 1e-9))
            log.info("%ssharded resident pass: %d global batches, "
                     "%.0f ex/s, auc=%.4f", log_prefix, rp.num_batches,
                     out["examples_per_sec"], res.auc)
            from paddlebox_tpu.obs.hub import emit_pass_event
            emit_pass_event("train_pass_resident_sharded",
                            dict(out, global_step=self.global_step),
                            table=self.table, examples=rp.num_records)
        return out, rp


class ShardedResidentPass:
    """A pass's global batches stacked on a leading step axis: every
    GlobalBatch field becomes [nb, ...] (device dim sharded over the mesh
    at upload). Routing plans are rebuilt with forced uniform A/A2/K
    buckets when batches landed in different ones (gather_idx encodes
    owner*A + j, so A must match across the staged pass)."""

    def __init__(self, arrays: Dict[str, np.ndarray], num_records: int,
                 mesh: Mesh, capacity: Optional[int] = None,
                 trivial: bool = False,
                 float_wire: str = "f32") -> None:
        self.arrays = arrays
        self.num_records = num_records
        self.mesh = mesh
        self.dev = None
        # chunk-schedule key of the staged pass's (uniform) plans —
        # (a2a_sections, key_sections, slot_sections), or () for the
        # monolithic schedule. Set by build(); rides into
        # run_resident's per-schedule executable.
        self.sections: tuple = ()
        # the pass's identifier on every span of every lane (obs/trace)
        self.pass_seq: Optional[int] = None
        # host side channels for the post-pass registry replay
        # ({label, show, uid, rank, cmatch} as [nb, N, B], None where a
        # batch lacked the channel) — set by build(); kept OUT of the
        # wire (never uploaded)
        self.side: Optional[Dict[str, Optional[np.ndarray]]] = None
        # packed wire (same bit-diet as the single-chip ResidentPass —
        # the host→device hop is the scarce resource): fmt maps each
        # GlobalBatch field to its encoding, wire holds the host arrays
        self.fmt: Optional[Dict[str, str]] = None
        self.wire: Optional[Dict[str, tuple]] = None
        self.capacity = capacity
        if capacity is not None:
            self._encode_wire(capacity, trivial, float_wire)

    @property
    def num_batches(self) -> int:
        return self.arrays["label"].shape[0]

    @classmethod
    def build(cls, dataset, trainer: "ShardedTrainer"
              ) -> "ShardedResidentPass":
        from paddlebox_tpu.ps.table import next_bucket_fine
        from paddlebox_tpu.train.device_pass import poll_preload_abort
        table = trainer.table
        groups = list(trainer._group_iter(dataset.batches()))
        if not groups:
            raise ValueError("empty pass")
        # a background (preloader) build polls the stop flag between
        # groups — routing-plan prep is the mesh build's long stage, and
        # a SIGTERM must not wait out a multi-second plan build; the
        # plan_scope bracket in build_resident_pass rolls the aborted
        # build's pending rows back
        chunks = getattr(trainer, "a2a_chunks", 1)
        plans = []
        for g in groups:
            poll_preload_abort()
            plans.append(table.prepare_global(g, groups=chunks))
        poll_preload_abort()
        sections: tuple = ()
        if chunks > 1 and all(p.a2a_sections for p in plans):
            # chunked pass: uniform per-GROUP section widths across the
            # staged pass (max per section over plans, the grouped
            # analogue of the A/A2 re-bucket below). Plans off the
            # common shape re-route with forced sections — no grouped
            # _repad_plan surgery; re-preparing re-assigns idempotently.
            # The serve target uses max(serve_capacity) — the SAME pow2
            # ladder the grouped builder bucketed with — so plans of a
            # same-shaped workload usually already match and the
            # re-route is the exception, not the rule (the fine ladder
            # the monolithic branch uses would mismatch every plan and
            # re-route the whole pass).
            c = len(plans[0].a2a_sections)
            a2 = max(p.serve_capacity for p in plans)
            req_secs = tuple(max(p.a2a_sections[g] for p in plans)
                             for g in range(c))
            key_secs = tuple(max(p.key_sections[g] for p in plans)
                             for g in range(c))
            uniformed = []
            for g, p in zip(groups, plans):
                if (p.a2a_sections != req_secs
                        or p.key_sections != key_secs
                        or p.serve_capacity != a2):
                    poll_preload_abort()
                    p = table.prepare_global(
                        g, serve_capacity=a2, groups=chunks,
                        req_sections=req_secs, key_sections=key_secs)
                uniformed.append(p)
            plans = uniformed
            sections = plan_sections(plans[0])
        else:
            if chunks > 1:
                # a batch with non-slot-qualified keys fell back — the
                # whole pass runs the monolithic schedule (shapes must
                # be uniform across the staged pass). Fallen-back plans
                # ARE monolithic already; only the grouped survivors of
                # a mixed pass rebuild.
                rebuilt = []
                for g, p in zip(groups, plans):
                    if p.a2a_sections:
                        poll_preload_abort()
                        p = table.prepare_global(g)
                    rebuilt.append(p)
                plans = rebuilt
                poll_preload_abort()
            # ONE uniform shape per pass either way → the FINE bucket
            # ladder (≤~6% padding) replaces the streaming pow2 buckets
            # (≤100%) for the staged wire. Plans re-PAD host-side (pure
            # array surgery — no second routing/assignment pass on the
            # staging thread).
            a = next_bucket_fine(1, max(p.req_need for p in plans))
            a2 = next_bucket_fine(1, max(p.serve_need for p in plans))
            repadded = []
            for g, p in zip(groups, plans):
                rp = cls._repad_plan(p, a, a2, trainer.n, table.capacity)
                if rp is None:  # ambiguous full bucket — re-route group
                    rp = table.prepare_global(g, req_capacity=a,
                                              serve_capacity=a2)
                repadded.append(rp)
            plans = repadded
        gbs = [make_global_arrays(g, p) for g, p in zip(groups, plans)]
        k = max(gb["gather_idx"].shape[1] for gb in gbs)
        # pad values that stay inert: gather_idx pads → the recv sentinel
        # slot (n*A - 1, zero values), segments pads → the discarded
        # pooling bin (bs * num_slots). A chunked pass's forced uniform
        # sections already give every batch identical widths (and its
        # pads are per-SECTION, placed by the grouped plan builder) —
        # the pad loop is a no-op there.
        pad_of = ({} if sections else
                  {"gather_idx": trainer.n * a - 1,
                   "segments": trainer.desc.batch_size *
                   len(trainer.desc.sparse_slots)})
        arrays: Dict[str, np.ndarray] = {}
        for f in GlobalBatch._fields:
            parts = []
            for gb in gbs:
                arr = gb[f]
                if f in pad_of and arr.shape[1] < k:
                    arr = np.pad(arr, ((0, 0), (0, k - arr.shape[1])),
                                 constant_values=pad_of[f])
                parts.append(arr)
            arrays[f] = np.stack(parts)
        n_rec = sum(int((b.show > 0).sum()) for g in groups for b in g)
        # the trivial-segment meta wire assumes the ORIGINAL slot-ordered
        # key stream; a chunked pass re-laid it group-contiguous, so it
        # ships the (encoded) segment stream instead
        trivial = (not sections
                   and all(getattr(b, "segments_trivial", False)
                           for g in groups for b in g))
        if trivial:
            # num_keys/pad_segment per (step, device) — segments then
            # derive on device instead of shipping [nb, N, K] int32
            arrays["meta"] = np.stack([
                np.array([[b.num_keys, b.pad_segment] for b in g],
                         np.int32) for g in groups])
        rp = cls(arrays, n_rec, trainer.mesh,
                 capacity=trainer.table.capacity, trivial=trivial,
                 float_wire=getattr(trainer, "float_wire", "f32"))
        rp.sections = sections

        def stack_opt(field):
            if any(getattr(b, field) is None for g in groups for b in g):
                return None
            return np.stack([np.stack([getattr(b, field) for b in g])
                             for g in groups])

        # side channels only when the registry will replay them —
        # unconditionally pinning show + uid/rank/cmatch stacks would
        # reintroduce the host-memory cost _encode_wire exists to avoid
        # (double-buffered preloader keeps two passes alive)
        if len(getattr(trainer, "metrics", ())) > 0:
            # label/show reference the pre-encode host arrays (no copy);
            # optional channels stack only if every batch carries them
            rp.side = {"label": arrays["label"], "show": arrays["show"],
                       "uid": stack_opt("uid"), "rank": stack_opt("rank"),
                       "cmatch": stack_opt("cmatch")}
        return rp

    @staticmethod
    def _repad_plan(p: ShardedPullIndex, a: int, a2: int, n: int,
                    capacity: int) -> ShardedPullIndex:
        """Change a plan's A/A2 padding WITHOUT re-running the routing:
        the serve lists and slot indices are identical under any padded
        capacity — only pad regions, the resp_idx pad sentinel (A2-1)
        and gather_idx's owner*A+j stride encode the capacity. Safe
        because in the strict-repad case (new < old) every real index is
        strictly below the old pad value, so pads are unambiguous."""
        if p.req_capacity == a and p.serve_capacity == a2:
            return p
        a_old, a2_old = p.req_capacity, p.serve_capacity
        if p.req_need >= a_old:
            # an exactly-full request bucket makes the gather pad
            # sentinel (n*a_old - 1) ambiguous with a real (owner n-1,
            # j = a_old-1) position — signal the caller to re-prepare
            return None
        # serve side: real prefix length per owner from serve_valid
        # (always < a2_old: the builder's a2_max includes the +1 slot)
        u = p.serve_valid.astype(bool).sum(1)                  # [N]
        serve_rows = np.empty((n, a2), np.int32)
        serve_valid = np.zeros((n, a2), np.float32)
        serve_slot = np.zeros((n, a2), np.float32)
        resp_idx = np.full((n, n, a), a2 - 1, np.int32)
        w = min(a, a_old)
        for s in range(n):
            us = int(u[s])
            serve_rows[s, :us] = p.serve_rows[s, :us]
            fill_oob_pads(serve_rows[s], us, capacity)
            serve_valid[s, :us] = 1.0
            serve_slot[s, :us] = p.serve_slot[s, :us]
            # request prefix per (owner, dst): real serve indices are
            # < u < a2_old-1, so counting non-pad entries is exact
            cnt = (p.resp_idx[s] != a2_old - 1).sum(1)         # [N]
            m = np.arange(w)[None, :] < cnt[:, None]
            resp_idx[s][:, :w][m] = p.resp_idx[s][:, :w][m]
        # gather positions re-stride from owner*A_old + j to owner*A + j;
        # the pad sentinel (n*A_old - 1) maps to the new sentinel (no
        # real position can equal it: j < req_need < a_old)
        gi = p.gather_idx
        pad_mask = gi == n * a_old - 1
        owner, j = gi // a_old, gi % a_old
        gather_idx = np.where(pad_mask, n * a - 1,
                              owner * a + j).astype(np.int32)
        return p._replace(resp_idx=resp_idx, serve_rows=serve_rows,
                          serve_valid=serve_valid, serve_slot=serve_slot,
                          gather_idx=gather_idx, req_capacity=a,
                          serve_capacity=a2)

    def _encode_wire(self, capacity: int, trivial: bool,
                     float_wire: str) -> None:
        """Bit-pack the staged pass (ops/bitpack ladders): index arrays
        to 18/24-bit forms, serve_valid derived from the fill_oob_pads
        contract, slot ids to u16, floats to the q8 wire when exact —
        ~3x fewer host→device bytes per pass."""
        fmt: Dict[str, str] = {}
        wire: Dict[str, tuple] = {}

        def enc_int(name, arr):
            vmax = int(arr.max(initial=0))
            if int(arr.min(initial=0)) >= 0 and vmax < (1 << 18) \
                    and arr.shape[-1] % 4 == 0:
                fmt[name] = "u18"
                wire[name] = pack_u16m(arr, 2)
            elif int(arr.min(initial=0)) >= 0 and vmax < (1 << 24):
                fmt[name] = "u24"
                wire[name] = pack_u24(arr)
            else:
                fmt[name] = "raw"
                wire[name] = (arr,)

        a = self.arrays
        enc_int("resp_idx", a["resp_idx"])
        # serve_rows: per-(step, shard) rows are ASCENDING (np.unique +
        # ascending OOB pads) → the delta wire (~1 B/row) with the pads
        # REGENERATED on device from the real count (srmeta)
        sr = a["serve_rows"]
        nbk, n, a2 = sr.shape
        flat = sr.reshape(-1, a2)
        counts = (flat <= capacity).sum(1).astype(np.int32)
        from paddlebox_tpu.train.device_pass import ResidentPass
        # THE delta-wire gap-exception budgets (shared with the
        # single-chip uniq wire)
        delta = pack_delta_auto(flat, counts, ResidentPass._EXC8,
                                ResidentPass._EXC)
        if delta is not None:
            fmt["serve_rows"] = "delta"
            wire["serve_rows"] = tuple(
                d.reshape((nbk, n) + d.shape[1:]) for d in delta)
            wire["srmeta"] = (np.stack(
                [counts.reshape(nbk, n),
                 flat[:, 0].reshape(nbk, n).astype(np.int32)],
                axis=-1),)
        else:
            enc_int("serve_rows", sr)
        enc_int("gather_idx", a["gather_idx"])
        derived = (a["serve_rows"] <= capacity).astype(np.float32)
        if np.array_equal(derived, a["serve_valid"]):
            fmt["serve_valid"] = "derive"
        else:
            fmt["serve_valid"] = "raw"
            wire["serve_valid"] = (a["serve_valid"],)
        sl = a["serve_slot"]
        if (sl >= 0).all() and (sl == np.rint(sl)).all() \
                and (sl < 256).all():
            fmt["serve_slot"] = "u8"
            wire["serve_slot"] = (sl.astype(np.uint8),)
        elif (sl >= 0).all() and (sl < 65536).all() \
                and (sl == np.rint(sl)).all():
            fmt["serve_slot"] = "u16"
            wire["serve_slot"] = (sl.astype(np.uint16),)
        else:
            fmt["serve_slot"] = "raw"
            wire["serve_slot"] = (sl,)
        if trivial:
            fmt["segments"] = "trivial"
            wire["meta"] = (a["meta"],)
        else:
            enc_int("segments", a["segments"])
        nbk, n, b, dd = a["dense"].shape
        q = None
        if float_wire == "q8":  # opt-in, as on the single-chip wire
            q = quantize_floats(
                a["dense"].reshape(-1, dd),
                a["label"].reshape(-1), a["show"].reshape(-1),
                a["clk"].reshape(-1),
                valid=a["show"].reshape(-1) > 0)
        if q is not None:
            block, qmeta = q
            fmt["dense"] = "q8"
            wire["dense"] = (block[:, :-3].reshape(nbk, n, b, dd),)
            wire["qmeta"] = (qmeta,)
            for j, f in enumerate(("label", "show", "clk")):
                fmt[f] = "u8"
                wire[f] = (block[:, dd + j].reshape(nbk, n, b),)
        else:
            for f in ("dense", "label", "show", "clk"):
                fmt[f] = "raw"
                wire[f] = (a[f],)
        self.fmt = fmt
        self.wire = wire
        # the packed wire supersedes the unpacked host arrays — keep only
        # what post-pass hooks read (mark_trained_rows, num_batches);
        # under the double-buffered preloader the dead copies would
        # double host memory per staged pass
        self.arrays = {"serve_rows": a["serve_rows"],
                       "label": a["label"]}

    def nbytes(self) -> int:
        """Wire bytes of the staged pass (after upload packing)."""
        if self.dev is not None:
            return sum(a.nbytes for a in jax.tree.leaves(self.dev))
        src = self.wire if self.wire is not None else self.arrays
        return sum(a.nbytes for a in jax.tree.leaves(src))

    def mark_trained_rows(self, table: ShardedEmbeddingTable) -> None:
        """Per-shard touched flags for this pass's served rows, set AFTER
        training (same delta-save rationale as ResidentPass)."""
        sr = self.arrays["serve_rows"]  # [nb, N, A2]
        with trace.span("pass.mark_trained", pass_seq=self.pass_seq,
                        rows=int(sr.size)), table.host_lock:
            for s in range(sr.shape[1]):
                rows = np.unique(sr[:, s])
                rows = rows[rows < table.capacity]
                table._touched[s][rows] = True

    def upload(self, materialize: bool = False) -> None:
        """Stage to HBM with the device dim sharded over the mesh axis.
        ``materialize=True`` forces the transfers now (see
        ResidentPass.upload — lazy uploads serialize into the first
        consuming step)."""
        if self.dev is not None:
            pass
        elif self.wire is not None:
            put = {}
            for f, arrs in self.wire.items():
                put[f] = tuple(
                    jax.device_put(
                        jnp.asarray(a),
                        NamedSharding(self.mesh,
                                      _wire_spec(f, a.ndim)))
                    for a in arrs)
            self.dev = put
        else:
            put = {}
            for f in GlobalBatch._fields:
                arr = self.arrays[f]
                spec = P(*([None, DATA_AXIS] + [None] * (arr.ndim - 2)))
                put[f] = jax.device_put(
                    jnp.asarray(arr), NamedSharding(self.mesh, spec))
            self.dev = GlobalBatch(**put)
        if materialize:
            # ONE blocking wait for every in-flight transfer — per-leaf
            # forced fetches cost a host round-trip EACH
            jax.block_until_ready(list(jax.tree.leaves(self.dev)))
