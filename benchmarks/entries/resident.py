"""Entry ``resident``: one chip, the table whole in its memory.
``Trainer`` + ``PassPreloader`` + ``Trainer.train_pass_resident``."""

from __future__ import annotations

import itertools
import time

import numpy as np

from benchmarks.entries import common


class Resident(common.PassEntry):
    chips = 1

    def __init__(self, config: dict, traffic: dict, pool, params) -> None:
        import jax
        from paddlebox_tpu.ps import EmbeddingTable
        from paddlebox_tpu.train import PassPreloader, Trainer
        common.program_flags()
        self.desc = common.feed_desc(config, traffic)
        self.datasets = common.datasets(self.desc, pool)
        n_slots = len(config["slot_sizes"])
        self.table = EmbeddingTable(
            mf_dim=int(config["mf_dim"]),
            capacity=int(config["table_rows_per_chip"]),
            cfg=common.sparse_cfg(config), unique_bucket_min=1 << 12,
            arena_slots=n_slots if traffic.get("slot_arena") else None)
        self.setup_parts = {}
        if traffic.get("register_vocabulary"):
            t0 = time.perf_counter()
            common.register_vocabulary(self.table, config,
                                       bool(traffic.get("slot_arena")))
            self.setup_parts["vocabulary_s"] = time.perf_counter() - t0
        tx = common.dense_tx(config)
        self.trainer = Trainer(common.model(config), self.table, self.desc,
                               tx=tx, prefetch=8)
        common.check_same_tree(self.trainer.state.params, params)
        self.trainer.state = self.trainer.state._replace(
            params=params, opt_state=tx.init(params))
        jax.block_until_ready(self.trainer.state)
        self.pre = PassPreloader(
            itertools.cycle(self.datasets), self.table,
            floats_dtype=traffic["float_wire"],
            depth=int(traffic["preload_depth"]))
        self.pre.start_next()

    # ---- reading the trained state (for ``correct``) ----
    def read_state(self, keys: np.ndarray) -> dict:
        """Host copies of what the comparison reads: the table rows of
        ``keys`` (NaN rows for keys the table does not know), the dense
        parameters, Adam's first moment and the AUC bucket counts."""
        import jax
        from paddlebox_tpu.ps.table import dispatch_packed_row_gather
        st = self.trainer.state
        rows = self.table.index.lookup(np.ascontiguousarray(keys, np.uint64))
        known = rows >= 0
        out, k = dispatch_packed_row_gather(
            st.table, None, np.where(known, rows, 0).astype(np.int32))
        got = np.array(jax.device_get(out))[:k]
        got[~known] = np.nan
        return {"rows": got,
                "params": jax.device_get(st.params),
                "mu": jax.device_get(common.adam_mu(st.opt_state)),
                "auc_pos": np.asarray(jax.device_get(st.auc.pos), np.float64),
                "auc_neg": np.asarray(jax.device_get(st.auc.neg), np.float64)}


def build(config: dict, traffic: dict, pool, params, chips: int):
    if chips != 1:
        raise ValueError("the resident entry runs on one chip")
    return Resident(config, traffic, pool, params)
