"""bench/trace_reduce.py on small traces committed beside this file:
busy time as a union of op intervals, idle gaps labelled by the host,
and a kernel's events found by name."""
import os

import pytest

from bench import trace_reduce

DATA = os.path.join(os.path.dirname(__file__), "data")


def test_union_merges_nested_and_touching():
    assert trace_reduce.union([(3, 4), (0, 2), (1, 1.5), (2, 2.5)]) == \
        [(0, 2.5), (3, 4)]


def test_two_chip_trace():
    r = trace_reduce.reduce(os.path.join(DATA, "two_chips.pbtxt"), 0.010)
    assert r["devices"] == 2
    # chip 0 busy 2 + 1 + 1 ms (the nested op counts once), chip 1 1 ms
    assert r["busy_s"] == pytest.approx((0.004 + 0.001) / 2)
    assert r["window_s"] == 0.010
    gaps = r["breakdown"]["idle_gaps"]
    assert [g[0] for g in gaps] == ["dispatch", "no host event"]
    assert [g[1] for g in gaps] == pytest.approx([0.003, 0.002])
    # the op at [1, 3] ms holds the one at [2, 2.5] ms: only the inner
    # one counts in the breakdown
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx((0.0005 + 0.001) / 2)
    assert trace_reduce.kernel_calls(r["ops"], "confidence") == \
        [(8, 1024, 4, pytest.approx(0.001))]


def test_recorded_chip_trace():
    """A trace recorded on one v5e (record_trace.py): three calls each of
    a bf16 matmul and the fused confidence kernel."""
    r = trace_reduce.reduce(os.path.join(DATA, "small.xplane.pb"), 10.0)
    assert r["devices"] == 1
    calls = trace_reduce.kernel_calls(r["ops"], "confidence_fused")
    assert [c[:3] for c in calls] == [(512, 4096, 4)] * 3
    assert 0 < sum(c[3] for c in calls) < r["busy_s"] < 0.01
    names = [n for n, _ in r["breakdown"]["device_ops"]]
    assert any("convolution" in n for n in names)
    assert len(r["breakdown"]["idle_gaps"]) == 10


def test_readers_on_the_recorded_trace():
    """The trace readers on the recorded chip trace: the kernel's share
    of its bytes-bound roofline and the idle share, both in (0, 100)."""
    from types import SimpleNamespace

    from bench import readers
    r = trace_reduce.reduce(os.path.join(DATA, "small.xplane.pb"), 10.0)
    run = SimpleNamespace(trace=r, peaks={"hbm_bytes_per_s": 819e9})
    calls = trace_reduce.kernel_calls(r["ops"], "confidence")
    least = 3 * (512 * 4096 * 4 + 512 * 16) / 819e9
    assert readers.conf_roofline(run) == \
        pytest.approx(100 * least / sum(c[3] for c in calls))
    assert 0 < readers.conf_roofline(run) < 100
    assert 0 < readers.idle_share(run) < 100
