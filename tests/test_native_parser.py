"""Native C++ bulk parser vs the python per-line parsers: bit-parity on
the columnar result, malformed-line handling, and the dataset fast path."""

import numpy as np
import pytest

from paddlebox_tpu.config import flags_scope
from paddlebox_tpu.data import DataFeedDesc, DatasetFactory, SlotDef
from paddlebox_tpu.data.columnar import ColumnarRecords
from paddlebox_tpu.data.criteo import generate_criteo_files
from paddlebox_tpu.data.parser import CriteoParser, SlotTextParser
from paddlebox_tpu.native import load_native

requires_native = pytest.mark.skipif(load_native() is None,
                                     reason="native lib unavailable")


def _columnar_from_python(parser, path, dense_dim):
    recs = []
    with open(path) as fh:
        for line in fh:
            r = parser.parse(line)
            if r is not None:
                recs.append(r)
    return ColumnarRecords.from_records(recs, dense_dim)


@requires_native
def test_criteo_native_matches_python(tmp_path):
    files = generate_criteo_files(str(tmp_path), num_files=1,
                                  rows_per_file=500, vocab_per_slot=100,
                                  seed=3)
    desc = DataFeedDesc.criteo(batch_size=64)
    p = CriteoParser(desc)
    got = p.parse_file_columnar(files[0])
    assert got is not None
    ref = _columnar_from_python(p, files[0], desc.dense_dim)
    np.testing.assert_array_equal(got["keys"], ref.keys)
    np.testing.assert_array_equal(got["key_slot"], ref.key_slot)
    np.testing.assert_array_equal(got["offsets"], ref.offsets)
    np.testing.assert_allclose(got["dense"], ref.dense, rtol=1e-6)
    np.testing.assert_array_equal(got["label"], ref.label)
    np.testing.assert_array_equal(got["clk"], ref.clk)


@requires_native
def test_criteo_native_skips_malformed(tmp_path):
    good = "1\t" + "\t".join(str(i) for i in range(1, 14)) + "\t" + \
        "\t".join(f"{i:x}" for i in range(26))
    lines = ["garbage line", good, "too\tfew\tfields", good + "\n"]
    f = tmp_path / "bad.txt"
    f.write_text("\n".join(lines))
    desc = DataFeedDesc.criteo(batch_size=4)
    got = CriteoParser(desc).parse_file_columnar(str(f))
    assert len(got["label"]) == 2
    assert (got["label"] == 1.0).all()


@requires_native
def test_slot_text_native_matches_python(tmp_path):
    rng = np.random.default_rng(5)
    slots = [SlotDef("label", "float", 1), SlotDef("dense", "float", 3),
             SlotDef("s1", "uint64"), SlotDef("s2", "uint64"),
             SlotDef("unused", "uint64", is_used=False)]
    desc = DataFeedDesc(slots=slots, batch_size=16, label_slot="label")
    lines = []
    for i in range(200):
        n1 = int(rng.integers(0, 4))
        n2 = int(rng.integers(1, 3))
        parts = ["1", str(int(rng.integers(0, 2)))]
        parts += ["3"] + [f"{rng.normal():.4f}" for _ in range(3)]
        parts += [str(n1)] + [str(int(rng.integers(0, 10**12)))
                              for _ in range(n1)]
        parts += [str(n2)] + [str(int(rng.integers(0, 10**12)))
                              for _ in range(n2)]
        parts += ["2", "99", "98"]  # unused slot: tokens must be skipped
        lines.append(" ".join(parts))
    lines.insert(7, "1 bad 3 x y z 0 1 5 2 9 9")  # malformed → dropped
    f = tmp_path / "slots.txt"
    f.write_text("\n".join(lines) + "\n")
    p = SlotTextParser(desc)
    got = p.parse_file_columnar(str(f))
    ref = _columnar_from_python(p, str(f), desc.dense_dim)
    assert len(got["label"]) == ref.num_records == 200
    np.testing.assert_array_equal(got["keys"], ref.keys)
    np.testing.assert_array_equal(got["key_slot"], ref.key_slot)
    np.testing.assert_array_equal(got["offsets"], ref.offsets)
    np.testing.assert_allclose(got["dense"], ref.dense, rtol=1e-6)
    np.testing.assert_array_equal(got["label"], ref.label)


@requires_native
def test_dataset_native_load_matches_record_path(tmp_path):
    files = generate_criteo_files(str(tmp_path), num_files=2,
                                  rows_per_file=300, vocab_per_slot=50,
                                  seed=9)
    desc = DataFeedDesc.criteo(batch_size=64)

    def load(native: bool):
        with flags_scope(native_parse=native):
            ds = DatasetFactory().create_dataset("InMemoryDataset", desc)
            ds.set_filelist(files)
            ds.set_thread(2)
            ds.load_into_memory()
            ds.columnarize()
            return ds

    a, b = load(True), load(False)
    assert a.columnar.num_records == b.columnar.num_records
    # same multiset of records (thread interleaving may reorder files)
    ka = np.sort(a.columnar.keys)
    kb = np.sort(b.columnar.keys)
    np.testing.assert_array_equal(ka, kb)
    np.testing.assert_allclose(np.sort(a.columnar.label),
                               np.sort(b.columnar.label))
    # batches build fine from the native-loaded store
    batch = next(a.batches())
    assert batch.num_keys == 64 * 26 and batch.segments_trivial

@requires_native
def test_criteo_extra_tabs_and_bad_hex(tmp_path):
    """Lines with >=40 tabs must be skipped (not crash — regression for a
    stack OOB write); invalid/overlong hex must match python exactly."""
    good = "1\t" + "\t".join(str(i) for i in range(1, 14)) + "\t" + \
        "\t".join(f"{i:x}" for i in range(26))
    bad_hex = good.replace("\t0\t", "\tzz\t", 1)           # invalid hex
    overlong = good + "ffffffffffffffffff"                 # >16 hex digits
    many_tabs = good + "\t" * 5
    f = tmp_path / "edge.txt"
    f.write_text("\n".join([good, many_tabs, bad_hex, overlong]) + "\n")
    desc = DataFeedDesc.criteo(batch_size=4)
    p = CriteoParser(desc)
    got = p.parse_file_columnar(str(f))
    ref = _columnar_from_python(p, str(f), desc.dense_dim)
    assert len(got["label"]) == ref.num_records == 3  # many_tabs dropped
    np.testing.assert_array_equal(got["keys"], ref.keys)


@requires_native
def test_slot_text_truncated_line_no_bleed(tmp_path):
    """A line truncated mid-record must be dropped without consuming the
    NEXT line's tokens (regression: strtol skipping '\\n')."""
    slots = [SlotDef("label", "float", 1), SlotDef("s1", "uint64"),
             SlotDef("s2", "uint64")]
    desc = DataFeedDesc(slots=slots, batch_size=4, label_slot="label")
    lines = [
        "1 1 2 10 20 1 30",      # ok: label=1, s1=[10,20], s2=[30]
        "1 0 1 40",              # truncated: missing s2 group entirely
        "1 1 2 50 60 1 70",      # ok — must NOT be consumed by line 2
    ]
    f = tmp_path / "trunc.txt"
    f.write_text("\n".join(lines) + "\n")
    p = SlotTextParser(desc)
    got = p.parse_file_columnar(str(f))
    ref = _columnar_from_python(p, str(f), desc.dense_dim)
    assert len(got["label"]) == ref.num_records == 2
    np.testing.assert_array_equal(got["keys"], ref.keys)
    np.testing.assert_array_equal(got["offsets"], ref.offsets)


@requires_native
def test_token_garbage_parity(tmp_path):
    """Trailing-garbage tokens ('1x' label, '2.5' count) must be rejected
    by the native path exactly like the python parsers; empty clk group
    must yield clk=0.0 on both paths."""
    good = "1\t" + "\t".join(str(i) for i in range(1, 14)) + "\t" + \
        "\t".join(f"{i:x}" for i in range(26))
    bad_label = good.replace("1\t", "1x\t", 1)
    bad_dense = good.replace("\t3\t", "\t3x\t", 1)
    f = tmp_path / "garb.txt"
    f.write_text("\n".join([good, bad_label, bad_dense]) + "\n")
    desc = DataFeedDesc.criteo(batch_size=4)
    p = CriteoParser(desc)
    got = p.parse_file_columnar(str(f))
    ref = _columnar_from_python(p, str(f), desc.dense_dim)
    assert len(got["label"]) == ref.num_records
    np.testing.assert_allclose(got["dense"], ref.dense, rtol=1e-6)
    assert got["dropped"] == 3 - ref.num_records

    slots = [SlotDef("label", "float", 1), SlotDef("clk", "float", 1),
             SlotDef("s1", "uint64")]
    desc2 = DataFeedDesc(slots=slots, batch_size=4, label_slot="label",
                         clk_slot="clk")
    lines = [
        "1 1 1 0.0 1 5",      # normal
        "1 1 0 1 5",          # clk group PRESENT but empty → clk must be 0
        "2.5 1 1 1 1 5",      # float count → dropped
        "1 1 1 1 1 5x",       # trailing-garbage key → dropped
    ]
    f2 = tmp_path / "slots.txt"
    f2.write_text("\n".join(lines) + "\n")
    p2 = SlotTextParser(desc2)
    got2 = p2.parse_file_columnar(str(f2))
    ref2 = _columnar_from_python(p2, str(f2), desc2.dense_dim)
    assert len(got2["label"]) == ref2.num_records == 2
    np.testing.assert_array_equal(got2["clk"], ref2.clk)
    assert got2["clk"][1] == 0.0


@requires_native
def test_criteo_hex_form_parity(tmp_path):
    """Hex forms int(v,16) would take but parse_hex64 rejects ('0x..',
    '+1a') must map to the sentinel on BOTH paths."""
    base = "1\t" + "\t".join(str(i) for i in range(1, 14)) + "\t"
    cats = [f"{i:x}" for i in range(26)]
    cats[0] = "0x1a"
    cats[1] = "+1a"
    f = tmp_path / "hexforms.txt"
    f.write_text(base + "\t".join(cats) + "\n")
    desc = DataFeedDesc.criteo(batch_size=2)
    p = CriteoParser(desc)
    got = p.parse_file_columnar(str(f))
    ref = _columnar_from_python(p, str(f), desc.dense_dim)
    np.testing.assert_array_equal(got["keys"], ref.keys)
    sent = (np.uint64(1) << np.uint64(52)) | np.uint64(0xFFFFFFFF)
    assert got["keys"][0] == sent


@requires_native
def test_uint64_overflow_and_hexfloat_parity(tmp_path):
    """Over-range uint64 tokens and hex-float labels must be DROPPED by
    both paths (python raises OverflowError/ValueError; native checks
    ERANGE / hex markers)."""
    slots = [SlotDef("label", "float", 1), SlotDef("s1", "uint64")]
    desc = DataFeedDesc(slots=slots, batch_size=4, label_slot="label")
    lines = [
        "1 1 1 5",                          # ok
        "1 1 1 18446744073709551616",       # 2^64: over-range → drop
        "1 0x1p1 1 5",                      # hex-float label → drop
    ]
    f = tmp_path / "ovf.txt"
    f.write_text("\n".join(lines) + "\n")
    p = SlotTextParser(desc)
    got = p.parse_file_columnar(str(f))
    ref = _columnar_from_python(p, str(f), desc.dense_dim)
    assert len(got["label"]) == ref.num_records == 1
    np.testing.assert_array_equal(got["keys"], ref.keys)


def _real_criteo_fixture(path, rows=384, seed=7):
    """A fixture file with REAL Criteo day-file quirks (the reference's
    tolerant MultiSlot parse semantics, data_feed.cc): 8-hex-digit
    lowercase feature hashes, EMPTY dense fields, NEGATIVE ints in I2
    (present in the real dataset), EMPTY categorical fields (missing →
    sentinel), rows ending in an empty field (trailing tab), plus
    malformed lines (wrong field count / garbage label) that must drop."""
    rng = np.random.default_rng(seed)
    lines = []
    for r in range(rows):
        label = str(int(rng.random() < 0.3))
        dense = [str(int(v)) for v in rng.integers(0, 1500, size=13)]
        dense[1] = str(int(rng.integers(-3, 10)))   # I2 goes negative
        for i in rng.choice(13, size=4, replace=False):
            dense[i] = ""                            # missing dense
        cats = [format(int(v), "08x")
                for v in rng.integers(0, 1 << 32, size=26)]
        for i in rng.choice(25, size=2, replace=False):
            cats[i] = ""                             # missing categorical
        cats[25] = ""                                # trailing tab
        lines.append("\t".join([label] + dense + cats))
    # interleave malformed rows: all must be dropped, no bleed
    lines.insert(0, "")                              # blank line
    lines.insert(5, "\t".join(["1"] + ["1"] * 12))   # too few fields
    lines.insert(9, "abc\t" + "\t".join(["1"] * 39)) # garbage label
    path.write_text("\n".join(lines) + "\n")
    return rows


def test_real_criteo_fixture_end_to_end(tmp_path):
    """Real-format quirks parse through DataFeedDesc.criteo → columnar →
    one resident train step."""
    import optax

    from paddlebox_tpu.models import DeepFM
    from paddlebox_tpu.ps import EmbeddingTable, SparseSGDConfig
    from paddlebox_tpu.train import Trainer

    f = tmp_path / "day_quirks.txt"
    rows = _real_criteo_fixture(f)
    desc = DataFeedDesc.criteo(batch_size=128)
    desc.key_bucket_min = 4096

    # both parse paths agree line-for-line on the quirk fixture
    p = CriteoParser(desc)
    ref = _columnar_from_python(p, str(f), desc.dense_dim)
    assert ref.num_records == rows          # malformed lines dropped
    if load_native() is not None:
        got = p.parse_file_columnar(str(f))
        assert got["dropped"] == 3
        np.testing.assert_array_equal(got["keys"], ref.keys)
        np.testing.assert_allclose(got["dense"], ref.dense, rtol=1e-6)
        np.testing.assert_array_equal(got["label"], ref.label)

    # missing categoricals land on the slot-salted sentinel, missing /
    # negative dense on 0 (log1p clamps at 0)
    sent_low = np.uint64(0xFFFFFFFF)
    mask = (np.uint64(1) << np.uint64(52)) - np.uint64(1)
    assert ((ref.keys & mask) == sent_low).sum() == rows * 3
    assert (ref.dense >= 0).all() and np.isfinite(ref.dense).all()

    # → dataset → columnar → one resident pass on the quirk data
    ds = DatasetFactory().create_dataset("InMemoryDataset", desc)
    ds.set_filelist([str(f)])
    ds.load_into_memory()
    cfg = SparseSGDConfig(mf_create_thresholds=0.0, mf_initial_range=0.0)
    table = EmbeddingTable(mf_dim=4, capacity=1 << 15, cfg=cfg,
                           unique_bucket_min=4096)
    tr = Trainer(DeepFM(hidden=(16, 8)), table, desc, tx=optax.adam(1e-2))
    res = tr.train_pass_resident(ds)
    assert res["batches"] == rows // 128
    assert np.isfinite(res["auc"])
    assert tr.table.feature_count > 0
