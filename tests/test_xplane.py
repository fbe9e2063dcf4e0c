"""obs/xplane: a profiler trace reduced by the program's own names —
device self time by ``pbox.*`` scope (backward ops folded into ``.bwd``,
the unscoped remainder ``other``) and each device's idle gaps by the
innermost main-lane ``pass.*`` span (``(outside)`` for time in none) —
on a trace made by hand, against numbers counted by hand, and on a trace
recorded on the chip."""

import json
import os
import subprocess
import sys

import pytest

from paddlebox_tpu.obs import trace, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
BODY = "jit(run)/jit(main)/while/body/"

#: one device; a ``while`` spans its body of five ops, a lone op later
OPS = [
    ["while.1 (s32[], f32[8,128])", "", 1000.0, 8000.0],
    ["fusion.1 f32[64,128]", BODY + "pbox.pull/gather", 1000.0, 2000.0],
    ["fusion.2 f32[64,128]",
     BODY + "transpose(jvp(pbox.pull))/scatter-add", 3000.0, 1500.0],
    ["fusion.3 bf16[8,16]",
     BODY + "jvp(pbox.dense)/DeepFM/Dense_0/dot_general", 4500.0, 500.0],
    ["fusion.4 s32[208]", BODY + "pbox.decode/pbox.dedup/sort",
     5000.0, 2000.0],
    ["copy.5 f32[8,128]", "", 7000.0, 500.0],
    ["fusion.6 f32[8,128]", BODY + "pbox.push/scatter-add",
     20000.0, 4000.0],
]
HOST = [
    ["pass.train", "main", 500.0, 11500.0],
    ["pass.dispatch", "main", 600.0, 300.0],
    ["pass.device_wait", "main", 900.0, 8200.0],
    ["pass.mark_trained", "main", 9200.0, 2300.0],
    ["pass.wait", "main", 12500.0, 500.0],
    ["pass.train", "main", 14000.0, 12000.0],
    ["pass.dispatch", "main", 14100.0, 4900.0],
    ["pass.device_wait", "main", 19000.0, 5100.0],
    # another lane's span covers everything and must not count
    ["pass.build", "preload.worker", 0.0, 30000.0],
]


def hand_trace(devices=1):
    return {"devices": [{"name": f"/device:TPU:{i}", "ops": OPS}
                        for i in range(devices)],
            "host": HOST, "scope_stat": "tf_op"}


@pytest.mark.parametrize("stack,scope", [
    (BODY + "pbox.pull/gather", "pbox.pull"),
    (BODY + "jvp(pbox.pool_cvm)/mul", "pbox.pool_cvm"),
    (BODY + "transpose(jvp(pbox.pool_cvm))/mul", "pbox.pool_cvm.bwd"),
    (BODY + "pbox.decode/pbox.dedup/sort", "pbox.dedup"),
    (BODY + "transpose(jvp(pbox.dense))/DeepFM/jvp(x)/dot",
     "pbox.dense.bwd"),
    (BODY + "pbox.push/jit(apply_push)/scatter", "pbox.push"),
    ("jit(run)/jit(main)/while/body/add", "other"),
    ("", "other"),
])
def test_scope_of_a_name_stack(stack, scope):
    assert xplane.scope_of(stack) == scope


def test_every_catalog_scope_is_one_the_reducer_recognises():
    for s in trace.STEP_SCOPES + trace.SHARDED_SCOPES:
        assert xplane.scope_of(BODY + s + "/op") == s
        assert xplane.scope_of(
            BODY + f"transpose(jvp({s}))/op") == s + ".bwd"


@pytest.mark.parametrize("devices", [1, 2])
def test_reduce_by_hand(devices):
    red = xplane.reduce(hand_trace(devices))
    assert red["devices"] == devices
    assert red["window_s"] == pytest.approx(25500e-9)
    assert red["busy_s"] == pytest.approx(12000e-9)
    assert red["idle_s"] == pytest.approx(13500e-9)
    assert red["scope_stat"] == "tf_op"
    scopes = {k: round(v * 1e9) for k, v in red["scopes"]}
    assert scopes == {"pbox.push": 4000, "pbox.pull": 2000,
                      "pbox.dedup": 2000, "other": 2000,
                      "pbox.pull.bwd": 1500, "pbox.dense": 500}
    assert sum(scopes.values()) == 12000          # self times tile busy
    assert red["scopes"][0][0] == "pbox.push"     # largest first
    # the while's own 1500 ns and the copy are what has no scope
    assert {k: round(v * 1e9) for k, v in red["other_ops"]} == {
        "while.1 (s32[], f32[8,128])": 1500, "copy.5 f32[8,128]": 500}
    gaps = {k: round(v * 1e9) for k, v in red["gaps"]}
    assert gaps == {"pass.dispatch": 5200, "pass.train": 2700,
                    "pass.mark_trained": 2300, "(outside)": 1500,
                    "pass.device_wait": 1300, "pass.wait": 500}
    assert sum(gaps.values()) == 13500            # gaps tile the idle


def test_innermost_segments_flatten_nested_spans():
    segs = xplane.innermost_segments(
        [["a", 0.0, 100.0], ["b", 10.0, 30.0], ["c", 20.0, 5.0],
         ["d", 200.0, 10.0]])
    assert segs == [(0.0, 10.0, "a"), (10.0, 20.0, "b"),
                    (20.0, 25.0, "c"), (25.0, 40.0, "b"),
                    (40.0, 100.0, "a"), (200.0, 210.0, "d")]


def test_reduce_refuses_a_trace_without_device_ops():
    with pytest.raises(ValueError, match="no device plane"):
        xplane.reduce({"devices": [], "host": HOST})


def test_render_names_scopes_and_gaps():
    text = xplane.render(xplane.reduce(hand_trace()))
    assert "pbox.pull.bwd" in text and "(outside)" in text
    assert "33.3%" in text                        # push: 4000 of 12000
    assert "largest unscoped ops: while.1" in text


def test_recorded_chip_trace_reduces_by_the_catalog():
    """A trace recorded on the chip (TPU v5 lite, PR 26: one resident
    pass of four steps at toy widths under ``jax.profiler``, cut to
    lists by ``xplane.load``): the name stacks as this jaxlib writes
    them resolve to catalog scopes, the scopes tile the busy time and
    the gaps tile the idle time. (At toy size the compiler's own copies
    and the loop's bookkeeping, which carry no name, are a large share;
    at the benchmark's size ``other`` reads 2.5%, PERF.md.)"""
    with open(os.path.join(HERE, "data", "xplane_small.json")) as f:
        rec = json.load(f)
    red = xplane.reduce(rec)
    known = set(trace.STEP_SCOPES)
    known |= {s + ".bwd" for s in known} | {"other"}
    scopes = dict(red["scopes"])
    assert set(scopes) <= known
    assert {"pbox.pull", "pbox.push", "pbox.pool_cvm.bwd",
            "pbox.dense.bwd"} <= set(scopes)
    assert sum(scopes.values()) == pytest.approx(red["busy_s"], rel=1e-6)
    assert scopes["pbox.push"] > scopes["pbox.dense"]
    stacks = {op[1] for op in rec["devices"][0]["ops"] if op[1]}
    assert any(s.startswith("jit(run)/while/body/pbox.") for s in stacks)
    assert any("transpose(jvp(pbox." in s for s in stacks)
    gaps = dict(red["gaps"])
    assert sum(gaps.values()) == pytest.approx(red["idle_s"], rel=1e-6)
    assert set(gaps) - {"(outside)"} <= {
        "pass.train", "pass.consume", "pass.upload", "pass.dispatch",
        "pass.device_wait", "pass.mark_trained", "pass.finish",
        "pass.wait"}
    assert rec["scope_stat"] == xplane.SCOPE_STAT == "tf_op"


def test_wire_decoder_reads_nested_messages_and_maps():
    """The few lines of protobuf decoding that find the name stacks, on
    a message assembled by hand: plane{name, event_metadata{1: {name,
    stats[{metadata_id: 9, str_value}]}}, stat_metadata{9: {name}}}."""
    def ld(no, payload):          # a length-delimited field
        assert len(payload) < 128
        return bytes([no << 3 | 2, len(payload)]) + payload

    def vi(no, val):              # a varint field
        assert val < 128
        return bytes([no << 3, val])

    stat = vi(1, 9) + ld(5, b"jit(run)/while/body/pbox.pull/gather:")
    other = vi(1, 8) + ld(5, b"loop fusion")
    meta = vi(1, 1) + ld(2, b"%fusion.1 = f32[8]{0} fusion()") \
        + ld(5, other) + ld(5, stat)
    plane = (vi(1, 7) + ld(2, b"/device:TPU:0")
             + ld(4, vi(1, 1) + ld(2, meta))
             + ld(5, vi(1, 9) + ld(2, vi(1, 9) + ld(2, b"tf_op")))
             + ld(5, vi(1, 8) + ld(2, vi(1, 8) + ld(2, b"hlo_category"))))
    host = vi(1, 2) + ld(2, b"/host:CPU") + ld(4, vi(1, 1) + ld(2, meta))

    def wrap(plane_bytes):        # XSpace.planes, a two-byte length
        n = len(plane_bytes)
        return bytes([1 << 3 | 2, n & 0x7F | 0x80, n >> 7]) + plane_bytes
    import tempfile
    with tempfile.NamedTemporaryFile(suffix=".pb") as f:
        f.write(wrap(plane) + wrap(host))
        f.flush()
        got = xplane._op_names(f.name)
    assert got == {"/device:TPU:0": {
        "%fusion.1 = f32[8]{0} fusion()":
            "jit(run)/while/body/pbox.pull/gather:"}}


def test_telemetry_report_xplane_needs_a_trace(tmp_path):
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts",
                                      "telemetry_report.py"),
         "--xplane", str(tmp_path)],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert "no xplane.pb" in p.stderr
