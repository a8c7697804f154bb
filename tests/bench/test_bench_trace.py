"""The trace reduction and the kernel roofline, on hand-made events and
on a trace recorded on a TPU v5e (the first 200 ms of a traced window)."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import cell as cell_mod, kernel_work, spec  # noqa: E402
from bench import trace as tr  # noqa: E402

DEV = "/device:TPU:0"
RECORDED = ROOT / "bench" / "recorded" / "trace_pa8k_bool_true.json"


def ev(plane, line, name, start, end):
    return tr.Event(plane, line, name, float(start), float(end))


def synthetic():
    return [
        ev("/host:CPU", "python", tr.WINDOW_SPAN, 0, 1000),
        ev(DEV, tr.OPS_LINE, "fusion.1", 100, 200),
        ev(DEV, tr.OPS_LINE, "%lane_matmul.3 = s32[8192,32] custom-call("
           "s32[8192,256] %a, s32[8192,32] %b)", 150, 300),   # overlaps
        ev(DEV, tr.OPS_LINE, "%while.2 = (s32[]) while(s32[] %t)", 480, 620),
        ev(DEV, tr.OPS_LINE, "fusion.1", 500, 600),
        ev(DEV, tr.OPS_LINE, "fusion.2", 900, 1100),     # runs past it
        ev(DEV, "XLA Modules", "jit_step", 0, 1000),     # not an op line
        ev("/host:CPU", "tdr-serve", "PjitFunction(step)", 300, 500),
        ev("/host:CPU", "tdr-serve", "inner", 350, 450),
    ]


def test_busy_is_the_union_of_op_intervals_in_the_window():
    s = tr.summarize(synthetic(), kernels=cell_mod.KERNELS)
    assert s["window_s"] == pytest.approx(1000e-9)
    assert s["busy_s"] == pytest.approx(440e-9)   # 200 + 140 + 100
    assert s["idle_share"] == pytest.approx(0.56)
    k = s["kernels"]["bitset_matmul"]
    assert k["calls"] == 1 and k["seconds"] == pytest.approx(150e-9)
    ops = dict(s["breakdown"]["device_ops"])
    assert ops["%lane_matmul.3"] == pytest.approx(150e-9)
    assert ops["%while.2"] == pytest.approx(40e-9)   # less the nested op
    assert ops["fusion.1"] == pytest.approx(200e-9)
    assert ops["fusion.2"] == pytest.approx(100e-9)  # clipped at the end
    gaps = dict(s["breakdown"]["idle_gaps"])
    assert gaps["tdr-serve: inner"] == pytest.approx(180e-9)
    assert gaps["no host span"] == pytest.approx(380e-9)
    assert sum(gaps.values()) == pytest.approx(560e-9)


def test_merged_intervals():
    iv = np.array([[5, 7], [1, 3], [2, 4], [6, 9], [12, 20]], float)
    m = tr.merged(iv, 0, 15)
    assert m.tolist() == [[1, 4], [5, 9], [12, 15]]
    assert tr.merged(np.zeros((0, 2)), 0, 1).shape == (0, 2)


def test_a_trace_without_a_window_or_device_ops_is_refused():
    with pytest.raises(ValueError):
        tr.summarize([e for e in synthetic() if e.name != tr.WINDOW_SPAN])
    with pytest.raises(ValueError):
        tr.summarize([e for e in synthetic() if e.plane != DEV])


def test_recorded_chip_trace_reduces_to_sound_numbers():
    events = tr.load_events(str(RECORDED))
    s = tr.summarize(events, kernels=cell_mod.KERNELS)
    assert 0 < s["busy_s"] <= s["window_s"]
    assert 0 <= s["idle_share"] < 1
    k = s["kernels"]["bitset_matmul"]
    assert k["calls"] > 0 and 0 < k["seconds"] <= s["busy_s"]
    cfg = spec.load_json(ROOT / "bench" / "configs" / "pa8k.json")
    # pa8k: 8 labels, all pinned, + the neutral class; 32 lanes per chunk
    work = kernel_work.bitset_matmul_bytes(
        k["calls"], cfg["n_vertices"], 32, 32_700, cfg["n_labels"] + 1)
    share = kernel_work.roofline_share(work, k["seconds"], "TPU v5 lite")
    assert 0 < share <= 100
    assert len(s["breakdown"]["device_ops"]) <= 10
    assert len(s["breakdown"]["idle_gaps"]) <= 10
