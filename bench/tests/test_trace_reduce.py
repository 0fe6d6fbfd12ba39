"""The trace reduction, on a small trace recorded on a TPU v5e: a served
BFS window of 0.3 s at R-MAT scale 10 (bench/tests/data)."""
from pathlib import Path

import numpy as np
import pytest

from bench import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"


def test_union_of_overlapping_intervals():
    s = np.array([5.0, 0.0, 1.0, 10.0, 12.0])
    e = np.array([6.0, 2.0, 3.0, 11.0, 12.5])
    got = tr.union_intervals(s, e)
    assert got.tolist() == [[0.0, 3.0], [5.0, 6.0], [10.0, 11.0], [12.0, 12.5]]
    assert tr.union_intervals(np.zeros(0), np.zeros(0)).shape == (0, 2)


def test_short_op_names():
    hlo = "%fusion.1 = s32[16777216]{0:T(1024)} fusion(s32[16] %a), kind=kCustom"
    assert tr._short_op(hlo) == "fusion.1 s32[16777216]"


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce_trace(tr.find_xplane(str(DATA)))


def test_recorded_trace_window_and_busy(reduced):
    assert reduced["chips"] == 1
    assert 0.29 < reduced["window_s"] < 0.32
    assert 0 < reduced["busy_s"] < reduced["window_s"]


def test_recorded_trace_breakdown(reduced):
    ops, gaps = reduced["device_ops"], reduced["idle_gaps"]
    assert 0 < len(ops) <= tr.TOP and 0 < len(gaps) <= tr.TOP
    assert [t for _, t in ops] == sorted((t for _, t in ops), reverse=True)
    assert all(name.startswith("jit_") for name, _ in ops)
    # ops are counted inside the window only, so they sum to at least busy
    assert sum(t for _, t in ops) <= reduced["window_s"]
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(t for _, t in gaps) == pytest.approx(idle, rel=0.2)
    # most of this window's idle time is the scheduler's fill-wait for a
    # lone query (max_wait_s), not a thread blocked on a queue
    assert "_take_batch" in gaps[0][0]
    assert not any(tr.BLOCKED.search(name) for name, _ in gaps)


def test_missing_trace_is_an_error(tmp_path):
    with pytest.raises(FileNotFoundError):
        tr.find_xplane(str(tmp_path))
