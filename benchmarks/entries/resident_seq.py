"""Entry ``resident_seq``: one chip, a language model of family ``lm``
through the same ``Trainer`` + ``PassPreloader`` +
``Trainer.train_pass_resident`` as entry ``resident``. A record is one
token: its one key is the token's id, its label the next token's id; the
table holds one row an id of the configuration's vocabulary, each started
from the seeded vector (as a table loaded from a saved model holds
them). The program's model is the class of ``paddlebox_tpu.models`` that
the configuration's ``model.class`` names, built from the configuration
itself."""

from __future__ import annotations

import itertools
import time

import numpy as np

from benchmarks.entries import common

NUM_FIXED, MF_SIZE_COL = 8, 7


def feed_desc(traffic: dict):
    from paddlebox_tpu.data import DataFeedDesc, SlotDef
    bs = int(traffic["batch_per_chip"])
    return DataFeedDesc(
        slots=[SlotDef("label", "float", 1), SlotDef("token", "uint64")],
        batch_size=bs, label_slot="label", key_bucket_min=bs,
        seq_len=int(traffic["seq_len"]), bos_key=0)


def datasets(desc, pool):
    """The generator's passes as the program's columnar datasets: one
    record a position, its key the token, its label the next token."""
    from paddlebox_tpu.data import InMemoryDataset
    from paddlebox_tpu.data.columnar import ColumnarRecords
    out = []
    for p in pool:
        r = p.num_records
        ds = InMemoryDataset(desc)
        ds.columnar = ColumnarRecords(
            keys=np.ascontiguousarray(p.inputs.reshape(-1), np.uint64),
            key_slot=np.zeros(r, np.int32),
            offsets=np.arange(r + 1, dtype=np.int64),
            dense=np.zeros((r, 0), np.float32),
            label=np.ascontiguousarray(p.labels.reshape(-1), np.int32),
            show=np.ones(r, np.float32), clk=np.zeros(r, np.float32))
        out.append(ds)
    return out


def load_vocabulary(table, embedding) -> None:
    """Give every id its row (through the index's own assignment, as the
    pass build does it) and write the seeded vectors into them on the
    device: ``mf_size`` 1, every counter 0."""
    import jax
    import jax.numpy as jnp
    from paddlebox_tpu.ps.table import scatter_logical_rows
    vocab = embedding.shape[0]
    keys = np.arange(vocab, dtype=np.uint64)
    with table.host_lock:
        rows, local = table.index.assign_slotted(
            keys, np.zeros(vocab, np.uint16))
        if (local < 0).any():
            raise ValueError("an id's row left the slot's arena")
        table.slot_host[rows] = 0
    fixed = jnp.zeros((vocab, NUM_FIXED), jnp.float32
                      ).at[:, MF_SIZE_COL].set(1.0)
    values = jnp.concatenate([fixed, embedding.astype(jnp.float32)], axis=1)
    table.state = scatter_logical_rows(table.state, None, rows,
                                       jax.device_get(values), chunk=4096)


class ResidentSeq(common.PassEntry):
    chips = 1

    def __init__(self, config: dict, traffic: dict, pool, params) -> None:
        import jax
        import paddlebox_tpu.models as models
        from paddlebox_tpu.ps import EmbeddingTable
        from paddlebox_tpu.train import PassPreloader, Trainer
        common.program_flags()
        self.desc = feed_desc(traffic)
        self.datasets = datasets(self.desc, pool)
        self.table = EmbeddingTable(
            mf_dim=int(config["hidden_size"]),
            capacity=int(config["table_rows_per_chip"]),
            cfg=common.sparse_cfg(config),
            unique_bucket_min=int(traffic["batch_per_chip"]),
            arena_slots=1)
        t0 = time.perf_counter()
        load_vocabulary(self.table, params["embedding"])
        self.setup_parts = {"vocabulary_s": time.perf_counter() - t0}
        tx = common.dense_tx(config)
        model = getattr(models, config["model"]["class"])(config)
        self.trainer = Trainer(model, self.table, self.desc, tx=tx,
                               prefetch=8)
        # the trainer made weights of its own: let go of them before the
        # seeded ones and their moments take their place
        net = params["net"]
        common.check_same_tree(self.trainer.state.params, net)
        self.trainer.state = self.trainer.state._replace(
            params=None, opt_state=None)
        self.trainer.state = self.trainer.state._replace(
            params=net, opt_state=tx.init(net))
        jax.block_until_ready(self.trainer.state)
        self.first = None     # the first pass's result (its step losses)
        self.pre = PassPreloader(
            itertools.cycle(self.datasets), self.table,
            depth=int(traffic["preload_depth"]))
        self.pre.start_next()

    def train(self, rp) -> None:
        out = self.trainer.train_pass_resident(rp)
        if self.first is None:
            self.first = out

    # ---- reading the trained state (for ``correct``) ----
    def read_state(self, ids: np.ndarray) -> dict:
        """Host copies of what the comparison reads: the first pass's
        per-step losses, the table rows of ``ids`` (NaN rows for ids the
        table does not know) and the dense parameters."""
        import jax
        from paddlebox_tpu.ps.table import dispatch_packed_row_gather
        st = self.trainer.state
        rows = self.table.index.lookup(np.ascontiguousarray(ids, np.uint64))
        known = rows >= 0
        out, k = dispatch_packed_row_gather(
            st.table, None, np.where(known, rows, 0).astype(np.int32))
        got = np.array(jax.device_get(out))[:k]
        got[~known] = np.nan
        return {"losses": np.asarray(self.first["losses"], np.float64),
                "rows": got, "params": jax.device_get(st.params)}


def build(config: dict, traffic: dict, pool, params, chips: int):
    if chips != 1:
        raise ValueError("the resident_seq entry runs on one chip")
    return ResidentSeq(config, traffic, pool, params)
