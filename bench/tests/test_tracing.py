"""The trace reducer, on a hand-made trace and on a recorded one."""
import json
from pathlib import Path

import pytest

from bench import tracing

DATA = Path(__file__).parent / "data"


def test_short_name_keeps_instruction_and_shape():
    assert tracing.short_name(
        '%spmm_vpu.12 = f32[171824,256]{1,0:T(8,128)} custom-call(s32[8] '
        '%c), custom_call_target="tpu_custom_call"') == \
        "spmm_vpu.12 f32[171824,256]"
    assert tracing.short_name(
        "%copy-start = (f32[8]{0}, u32[]{:S(2)}) copy-start(f32[8] %x)") == \
        "copy-start tuple"


def test_reduce_by_hand():
    # Window 100..200 ns. Ops: a kernel 90..130 (clipped to 100..130), an
    # XLA op 120..150 overlapping it, a kernel 170..180, an op past the
    # window's end 195..260 (clipped to 195..200).
    trace = {
        "devices": {"/device:TPU:0": [
            ["k.1 f32[8]", 90, 40, 1],
            ["fusion.2 f32[8]", 120, 30, 0],
            ["k.3 f32[8]", 170, 10, 1],
            ["fusion.4 f32[8]", 195, 65, 0],
        ]},
        "host": [["bench.window", 100, 100],
                 ["bench.dispatch", 100, 5],
                 ["bench.wait", 150, 45]],
    }
    r = tracing.reduce(trace, steps=2)
    assert r["window_s"] == pytest.approx(100e-9)
    # Busy: 100..150 (50) + 170..180 (10) + 195..200 (5) = 65 ns.
    assert r["busy_s"] == pytest.approx(65e-9)
    # Kernels: 100..130 (30) + 170..180 (10) = 40 ns.
    assert r["pallas_s"] == pytest.approx(40e-9)
    gaps = r["breakdown"]["idle_gaps"]
    # Gaps: 150..170 (20) and 180..195 (15), both inside bench.wait.
    assert gaps == [["host: bench.wait", pytest.approx(20e-9)],
                    ["host: bench.wait", pytest.approx(15e-9)]]
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["k.1 f32[8]"] == pytest.approx(30e-9)
    assert ops["fusion.4 f32[8]"] == pytest.approx(5e-9)


def test_reduce_needs_the_window():
    with pytest.raises(ValueError, match="bench.window"):
        tracing.reduce({"devices": {}, "host": []}, steps=1)


def test_recorded_trace():
    # Ten GCN steps on a 4,096-node graph, traced on one TPU v5e by the
    # harness and cut down by tracing.extract.
    trace = json.loads((DATA / "small_trace.json").read_text())
    r = tracing.reduce(trace, steps=10)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.316804898, rel=1e-9)
    assert r["busy_s"] == pytest.approx(0.315289183, rel=1e-9)
    assert r["pallas_s"] == pytest.approx(0.278860492, rel=1e-9)
    assert 0 < r["pallas_s"] < r["busy_s"] < r["window_s"]
    top = r["breakdown"]["device_ops"]
    assert len(top) == 10 and top[0][0].startswith("spmm_vpu.")
    assert all(a[1] >= b[1] for a, b in zip(top, top[1:]))
    gaps = r["breakdown"]["idle_gaps"]
    assert len(gaps) == 10
    assert all(name.startswith("host: bench.") for name, _ in gaps)
