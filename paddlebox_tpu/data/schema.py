"""Slot schema — the ``DataFeedDesc`` analogue.

Reference: paddle/fluid/framework/data_feed.proto:43-59 (``DataFeedDesc``:
multi_slot_desc with per-slot {name, type, is_dense, is_used, shape},
batch_size, pipe_command, pv_batch_size, rank_offset, ads fields).

TPU-native difference: instead of per-slot LoDTensors, the schema also fixes
the *static* padded key capacity per batch (XLA wants static shapes), chosen
from a geometric bucket ladder at batch-build time.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence


@dataclasses.dataclass(frozen=True)
class SlotDef:
    """One input slot. ``uint64`` slots carry sparse feature ids (feasigns);
    ``float`` slots carry fixed-dim dense values."""

    name: str
    type: str = "uint64"  # "uint64" | "float"
    dim: int = 1          # float slots: values per record; uint64: unused
    is_used: bool = True

    def __post_init__(self) -> None:
        if self.type not in ("uint64", "float"):
            raise ValueError(f"slot {self.name}: bad type {self.type}")


@dataclasses.dataclass
class DataFeedDesc:
    slots: List[SlotDef] = dataclasses.field(default_factory=list)
    batch_size: int = 512
    parser: str = "slot_text"        # registered parser name
    # shell command each reader pipes a file through before parsing its
    # stdout (data_feed.proto:45 pipe_command / LoadIntoMemoryByCommand)
    pipe_command: Optional[str] = None
    label_slot: Optional[str] = None  # which slot is the click label
    show_slot: Optional[str] = None
    clk_slot: Optional[str] = None
    pv_batch_size: int = 0            # page-view (PV) merged batching
    rank_offset: Optional[str] = None  # rank_offset tensor name for PV mode
    # static padding ladder for flattened sparse keys per batch
    key_bucket_min: int = 1024
    key_bucket_growth: float = 2.0
    # a SEQUENCE feed (a language model's): a record is one position of a
    # sequence of ``seq_len`` tokens (0: not a sequence feed), a batch is
    # whole sequences, the one sparse slot holds the position's token and
    # the label is an integer id (the next token), which crosses the
    # resident wire as int32 (train/device_pass)
    seq_len: int = 0
    # the key that opens a document in a packed sequence feed (counted
    # into the pass's ``documents``), or None
    bos_key: Optional[int] = None

    @property
    def sparse_slots(self) -> List[SlotDef]:
        return [s for s in self.slots if s.type == "uint64" and s.is_used]

    @property
    def dense_slots(self) -> List[SlotDef]:
        """Float feature slots — excludes the label/show/clk channels, which
        parsers route to their own record fields."""
        special = {self.label_slot, self.show_slot, self.clk_slot}
        return [s for s in self.slots
                if s.type == "float" and s.is_used and s.name not in special]

    @property
    def dense_dim(self) -> int:
        return sum(s.dim for s in self.dense_slots)

    def sparse_slot_index(self, name: str) -> int:
        for i, s in enumerate(self.sparse_slots):
            if s.name == name:
                return i
        raise KeyError(name)

    def key_capacity(self, num_keys: int) -> int:
        """Pick the padded key capacity bucket for a batch with num_keys keys.
        Geometric ladder bounds the number of distinct XLA compilations."""
        cap = self.key_bucket_min
        while cap < num_keys:
            cap = int(cap * self.key_bucket_growth)
        return cap

    @classmethod
    def criteo(cls, batch_size: int = 512) -> "DataFeedDesc":
        """Criteo display-ads schema: 13 dense ints (as one float slot of
        dim 13) + 26 categorical sparse slots + click label."""
        slots: List[SlotDef] = [SlotDef("label", "float", 1)]
        slots.append(SlotDef("dense", "float", 13))
        slots += [SlotDef(f"C{i}", "uint64") for i in range(1, 27)]
        return cls(slots=slots, batch_size=batch_size, parser="criteo",
                   label_slot="label")
