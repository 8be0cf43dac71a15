"""The trace reduction on a trace recorded on a TPU v5e (three calls of the
mmtc_n1024 engine, K=64, N=1024, with the harness's spans), and the work
counts and peaks against hand counts."""
from pathlib import Path

import pytest

from bench import trace_reduce, work

TRACE = Path(__file__).with_name("data") / "mmtc_small.xplane.pb"


@pytest.fixture(scope="module")
def trace():
    return trace_reduce.read(str(TRACE))


def test_union_clip_total():
    iv = trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert iv == [[0, 3], [5, 8]]
    assert trace_reduce.clip(iv, 2, 6) == [(2, 3), (5, 6)]
    assert trace_reduce.total([(2, 3), (5, 6)]) == 2.0


def test_busy_and_window(trace):
    assert list(trace.chips) == [0]
    assert 0.0 < trace.busy_s < trace.window_s
    # the recording: a 12 ms window plus the last call, 7.7 ms of it busy
    assert trace.window_s == pytest.approx(0.01502, rel=0.01)
    assert trace.busy_s == pytest.approx(0.007743, rel=0.01)


def test_kernel_events(trace):
    """68 launches of the Pallas kernel, ~71 us each; the fusions that read
    its output (``%slice_add_fusion.2 = ... %sic_suffix_pallas.34 ...``)
    are not counted."""
    secs, launches = trace.kernel_s("sic_suffix")
    assert launches == 68
    assert secs / launches == pytest.approx(71.1e-6, rel=0.01)
    assert 0.0 < secs < trace.busy_s
    assert trace.kernel_s("no_such_kernel") == (0.0, 0)


def test_breakdown(trace):
    ops = trace.top_ops()
    assert ops[0][0] == "%while.347" and len(ops) <= 10
    # outermost ops only: their sum cannot pass the busy time
    assert sum(s for _, s in ops) <= trace.busy_s * 1.0001
    gaps = trace.idle_gaps()
    assert 0 < len(gaps) <= 10
    assert all(name in trace_reduce.SPANS + ("none",) for name, _ in gaps)
    idle = trace.window_s - trace.busy_s
    assert sum(s for _, s in gaps) <= idle * 1.0001
    assert gaps == sorted(gaps, key=lambda g: -g[1])


def test_suffix_sum_work():
    assert work.suffix_sum(1, 4) == {"flops": 3.0, "bytes": 32.0}
    w = work.suffix_sum(64, 1024)
    assert w["bytes"] == 2 * 4 * 64 * 1024 and w["flops"] == 64 * 1023


def test_roofline_share_by_hand():
    peak = work.peaks("TPU v5 lite")
    assert peak == {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9}
    w = {"flops": 0.0, "bytes": 819e9 * 1e-3}       # 1 ms of HBM traffic
    assert work.roofline_share(w, 4e-3, "TPU v5 lite") == pytest.approx(25.0)
    w = {"flops": 197e12 * 2e-3, "bytes": 1.0}      # compute bound, 2 ms
    assert work.roofline_share(w, 4e-3, "TPU v5 lite") == pytest.approx(50.0)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
