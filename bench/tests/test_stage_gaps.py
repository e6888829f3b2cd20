"""The host-gap readers (``bench/stage_gaps.py``) on synthetic span
records: medians by hand, each shared span counted once per batch, only
the batches the window offered, and nothing read from a program whose
spans carry no stages."""
import importlib.util
import os
from types import SimpleNamespace

import pytest

from bench import run, stage_gaps


def _batch(asm_id, t0, block_gaps_us, rows):
    """Span events of one 4-block batch starting at ``t0`` (µs), shared
    by ``rows`` records: each dispatch takes 500 µs, each device wait
    10 ms, and ``dispatch[i+1]`` ends ``block_gaps_us[i]`` after
    ``device_wait[i]`` ends.  Returns (events, end of the last wait)."""
    ev = [{"name": "queue_wait", "ph": "X", "ts": t0 - 50.0, "dur": 40.0,
           "args": {"id": asm_id + 99, "parent": None}},
          {"name": "batch_assembly", "ph": "X", "ts": t0, "dur": 5.0,
           "args": {"batch_size": rows, "id": asm_id, "parent": None}}]
    end = t0 + 510.0
    for i in range(4):
        d_end = end if i == 0 else end + block_gaps_us[i - 1]
        ev.append({"name": f"dispatch[{i}]", "ph": "X", "ts": d_end - 500.0,
                   "dur": 500.0, "args": {"id": asm_id + 2 * i + 1}})
        ev.append({"name": f"device_wait[{i}]", "ph": "X", "ts": d_end,
                   "dur": 10000.0, "args": {"id": asm_id + 2 * i + 2}})
        end = d_end + 10000.0
    return ev, end


def _view(records, t1=100.0):
    return SimpleNamespace(mix={"arrivals": "backlog"}, records=records,
                           t0=0.0, t1=t1)


def _rec(spans, sent=1.0, status="ok"):
    return SimpleNamespace(ok=status == "ok", status=status, sent=sent,
                           spans=spans)


def _records():
    a, a_end = _batch(10, 1e6, [1000.0, 2000.0, 3000.0], rows=3)
    # the next batch's dispatch[0] ends 10 ms after a's last device wait
    b, b_end = _batch(30, a_end + 10000.0 - 510.0, [1500.0, 2500.0, 3500.0],
                      rows=1)
    late, _ = _batch(50, b_end + 5e5, [9e6, 9e6, 9e6], rows=1)
    return [_rec(a), _rec(a), _rec(a), _rec(b),
            _rec(late, sent=200.0),                     # after the window
            _rec([], status="cancelled")]


def test_block_gap_median_counts_each_batch_once():
    # per batch, not per request: a's three rows would pull it to 2.0
    assert stage_gaps.block_gap_ms(_view(_records())) == pytest.approx(2.25)


def test_batch_gap_median_over_boundaries_of_the_window():
    view = _view(_records())
    assert len(stage_gaps.window_batches(view)) == 2
    assert stage_gaps.batch_gap_ms(view) == pytest.approx(10.0)


def test_spans_without_stages_read_nothing():
    plain = [{"name": "batch_assembly", "ph": "X", "ts": 0.0, "dur": 1.0,
              "args": {"batch_size": 1}},
             {"name": "decode_block[0]", "ph": "X", "ts": 1.0, "dur": 9.0,
              "args": {"block": 0}}]
    view = _view([_rec(plain)])
    assert stage_gaps.block_gap_ms(view) is None
    assert stage_gaps.batch_gap_ms(view) is None


@pytest.mark.parametrize("name,want", [("block_gap_ms.offline", 2.25),
                                       ("batch_gap_ms.offline", 10.0)])
def test_metric_files_read_the_gaps(name, want):
    path = os.path.join(run.BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    assert module.read(_view(_records())) == pytest.approx(want)
