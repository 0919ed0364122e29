"""The trace reduction on a small profiler trace recorded on the CPU
(``testdata/make_trace.py``): its ``dev.`` spans stand for device
operations, its ``host.`` spans for what the host did in the gaps."""

import os

import pytest

import trace_reduce
from conftest import BENCH

TRACE = os.path.join(BENCH, "testdata", "cpu_trace.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    devices, host = trace_reduce.load(
        TRACE, device_prefix="/host:CPU", op_line=None,
        op_filter=lambda n: n.startswith("dev."))
    return trace_reduce.reduce(devices, host)


def test_busy_prox_and_window(reduced):
    # 3 × 30 ms prox + 2 × 20 ms fusion inside a ≈ 360 ms window
    assert reduced["n_devices"] == 1
    assert reduced["busy_s"] == pytest.approx(0.130919993)
    assert reduced["prox_s"] == pytest.approx(0.090549087)
    assert reduced["window_s"] == pytest.approx(0.362052588)
    assert reduced["busy_s"] == pytest.approx(0.130, abs=0.01)
    assert reduced["prox_s"] == pytest.approx(0.090, abs=0.006)
    assert reduced["idle_share"] == pytest.approx(
        1 - reduced["busy_s"] / reduced["window_s"])


def test_top_ops_and_gaps_are_named(reduced):
    ops = dict(reduced["device_ops"])
    assert set(ops) == {"dev.prox_pool", "dev.fusion"}
    gaps = reduced["idle_gaps"]
    assert [g[0] for g in gaps[:4]] == ["host.gather", "host.wait",
                                        "host.wait", "host.wait"]
    assert gaps[0][1] == pytest.approx(0.080, abs=0.005)
    assert len(gaps) <= 10


def test_union_merges_overlaps():
    assert trace_reduce._union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [
        [0, 4], [5, 10]]


def test_window_annotation_is_required():
    with pytest.raises(ValueError):
        trace_reduce.reduce({}, [(0, 10, "other")])
