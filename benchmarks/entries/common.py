"""What the entry kinds share: the program's data containers around the
generator's columns, the model by its class name, the optimizer's
settings, and the reading of a trainer's dense state."""

from __future__ import annotations

import numpy as np


def program_flags() -> None:
    """Process-wide settings of the program that every training entry
    runs under (bench.py's own): no per-step logging, AUC reduced on the
    device (8 scalars a pass instead of the 8 MB bucket tables)."""
    from paddlebox_tpu.config import FLAGS
    FLAGS.log_period_steps = 10 ** 9
    FLAGS.auc_device_reduce = True


def feed_desc(config: dict, traffic: dict):
    from paddlebox_tpu.data import DataFeedDesc, SlotDef
    sizes = config["slot_sizes"]
    bs = int(traffic["batch_per_chip"])
    slots = [SlotDef("label", "float", 1),
             SlotDef("dense", "float", int(config["dense_dim"]))]
    slots += [SlotDef(f"C{i + 1}", "uint64") for i in range(len(sizes))]
    # one key a slot: the exact key bucket, no padding and one program
    one_key = all(s == 1 for s in sizes)
    return DataFeedDesc(slots=slots, batch_size=bs, label_slot="label",
                        key_bucket_min=bs * len(sizes) if one_key else 4096)


def datasets(desc, pool):
    """The generator's passes as the program's columnar in-memory
    datasets (no record objects)."""
    from paddlebox_tpu.data import InMemoryDataset
    from paddlebox_tpu.data.columnar import ColumnarRecords
    out = []
    for cols in pool:
        r, k = cols.keys.shape
        ds = InMemoryDataset(desc)
        ds.columnar = ColumnarRecords(
            keys=np.ascontiguousarray(cols.keys.reshape(-1)),
            key_slot=np.tile(cols.key_slot, r),
            offsets=np.arange(r + 1, dtype=np.int64) * k,
            dense=cols.dense, label=cols.label,
            show=np.ones(r, np.float32), clk=cols.label.copy())
        out.append(ds)
    return out


def sparse_cfg(config: dict):
    from paddlebox_tpu.ps import SparseSGDConfig
    sp = config["sparse_optimizer"]
    if sp["name"] != "adagrad":
        raise ValueError(f"sparse optimizer {sp['name']!r} has no entry")
    return SparseSGDConfig(
        nonclk_coeff=sp["nonclk_coeff"], clk_coeff=sp["clk_coeff"],
        min_bound=-sp["bound"], max_bound=sp["bound"],
        learning_rate=sp["learning_rate"],
        initial_g2sum=sp["initial_g2sum"],
        mf_create_thresholds=sp["mf_create_thresholds"],
        mf_learning_rate=sp["mf_learning_rate"],
        mf_initial_g2sum=sp["mf_initial_g2sum"],
        mf_initial_range=sp["mf_initial_range"],
        mf_min_bound=-sp["bound"], mf_max_bound=sp["bound"])


def dense_tx(config: dict):
    import optax
    do = config["dense_optimizer"]
    if do["name"] != "adam":
        raise ValueError(f"dense optimizer {do['name']!r} has no entry")
    return optax.adam(do["learning_rate"])


def model(config: dict):
    import paddlebox_tpu.models as models
    m = config["model"]
    args = {k: tuple(v) if isinstance(v, list) else v
            for k, v in m["args"].items()}
    return getattr(models, m["class"])(**args)


def register_vocabulary(table, config: dict, arena: bool) -> int:
    """Give every id of the configuration's vocabularies its row, as a
    table loaded from a saved model has them (keys are 0 .. sum(vocab) - 1
    in slot order, see ``traffic.key_offsets``): the index and the table
    then hold what the deployment holds, whatever the passes touch.
    Through the index's own assignment, as the pass build does it."""
    from benchmarks.traffic import slot_vocab
    vocab = slot_vocab(config)
    keys = np.arange(int(vocab.sum()), dtype=np.uint64)
    slots = np.repeat(np.arange(len(vocab), dtype=np.uint16), vocab)
    with table.host_lock:
        if arena:
            rows, local = table.index.assign_slotted(keys, slots)
            if (local < 0).any():
                raise ValueError("a slot's rows left its arena")
        else:
            rows = table.index.assign(keys)
        table.slot_host[rows] = slots
    return len(keys)


def check_same_tree(theirs, ours) -> None:
    """The seeded weights must fit the program's own tree leaf for leaf."""
    import jax
    a = jax.tree.map(lambda x: tuple(x.shape), theirs)
    b = jax.tree.map(lambda x: tuple(x.shape), ours)
    if a != b:
        raise ValueError(f"seeded weights {b} do not fit the program's "
                         f"parameters {a}")


def adam_mu(opt_state):
    """First moment of optax.adam's state (ScaleByAdamState.mu)."""
    return opt_state[0].mu


class PassEntry:
    """What every pass-trainer entry shares: the window's calls, the
    preloader's counters and the shutdown. A subclass builds ``trainer``,
    ``table``, ``pre`` and ``datasets`` and reads the trained state."""

    trainer = table = pre = datasets = None
    #: seconds of set-up that ``build`` spent in parts worth their own
    #: name on the run's ``setup`` line
    setup_parts: dict = {}

    # ---- the window's calls ----
    def wait(self):
        rp = self.pre.wait()
        self.pre.start_next()
        return rp

    def train(self, rp) -> None:
        self.trainer.train_pass_resident(rp)

    def block(self) -> None:
        import jax
        jax.block_until_ready(self.trainer.state)

    # ---- counters ----
    def counters(self) -> dict:
        pre = self.pre
        return {"builds": int(pre.builds),
                "build_s": float(pre.build_sec_total),
                "stage_s": {k: float(v)
                            for k, v in pre.build_stage_sec.items()}}

    @staticmethod
    def pass_info(rp) -> dict:
        return {"records": int(rp.num_records),
                "wire_bytes": int(rp.nbytes()),
                "batches": int(rp.num_batches)}

    def close(self) -> None:
        self.pre.drain()
        self.trainer.state = None
        self.table.state = None
        self.trainer = self.table = self.pre = self.datasets = None
