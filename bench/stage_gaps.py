"""Host time between one block program and the next, read from the
program's own stage spans (``GET /v1/trace/{rid}``, Chrome trace events
in microseconds on one clock for every request).

The engine records, per block *i* of a batch, ``dispatch[i]`` (block
*i*'s arguments built and its program enqueued) and ``device_wait[i]``
(the host blocked on the block's tokens).  From the end of
``device_wait[i]`` to the end of ``dispatch[i+1]`` the chip has no next
block to run: a block gap.  From the end of a batch's last
``device_wait`` to the end of the next batch's ``dispatch[0]`` it waits
through the finish, the emit, the next selection and assembly and the
new decode's set-up: a batch gap.

A batch's stages are shared by its requests and carry the same span
``id``; a batch is its ``batch_assembly`` span's id, so each shared span
counts once per batch.  A program without these spans (no ids, no
stages) gives no batches, and the readers ``None``.
"""
from __future__ import annotations

import re
import statistics
from typing import Dict, List, Optional

from bench.run import window_requests

_STAGE = re.compile(r"^(dispatch|device_wait)\[(\d+)\]$")


def window_batches(run) -> List[Dict[str, Dict[int, dict]]]:
    """One ``{"dispatch": {i: event}, "device_wait": {i: event}}`` per
    batch of the finished requests the window offered
    (``bench.run.window_requests``), in the order the batches ran.  Of a
    stage recorded twice (a retried attempt) the later one counts."""
    batches: Dict[int, Dict[str, Dict[int, dict]]] = {}
    for rec in window_requests(run):
        if not (rec.ok and rec.spans):
            continue
        spans = sorted((e for e in rec.spans if e.get("ph") == "X"),
                       key=lambda e: e["ts"])
        asm = [e for e in spans if e["name"] == "batch_assembly"
               and "id" in e.get("args", {})]
        if not asm:
            continue
        key = asm[-1]["args"]["id"]
        if key in batches:
            continue
        stages: Dict[str, Dict[int, dict]] = {"dispatch": {},
                                              "device_wait": {}}
        for e in spans:
            m = _STAGE.match(e["name"])
            if m and e["ts"] >= asm[-1]["ts"]:
                stages[m[1]][int(m[2])] = e
        if stages["dispatch"] and stages["device_wait"]:
            batches[key] = stages
    return sorted(batches.values(),
                  key=lambda b: b["dispatch"][min(b["dispatch"])]["ts"])


def _end_us(event: dict) -> float:
    return event["ts"] + event.get("dur", 0.0)


def block_gaps_s(batch: Dict[str, Dict[int, dict]]) -> List[float]:
    """Seconds from the end of ``device_wait[i]`` to the end of
    ``dispatch[i+1]``, for each block boundary of one batch."""
    wait, disp = batch["device_wait"], batch["dispatch"]
    return [(_end_us(disp[i + 1]) - _end_us(wait[i])) * 1e-6
            for i in sorted(wait) if i + 1 in disp]


def batch_gaps_s(batches) -> List[float]:
    """Seconds from the end of a batch's last ``device_wait`` to the end
    of the next batch's ``dispatch[0]``, for each pair of batches that
    ran one after the other."""
    out = []
    for a, b in zip(batches, batches[1:]):
        if 0 in b["dispatch"]:
            last = a["device_wait"][max(a["device_wait"])]
            out.append((_end_us(b["dispatch"][0]) - _end_us(last)) * 1e-6)
    return out


def median_ms(values) -> Optional[float]:
    return 1e3 * statistics.median(values) if values else None


def block_gap_ms(run) -> Optional[float]:
    return median_ms([g for b in window_batches(run)
                      for g in block_gaps_s(b)])


def batch_gap_ms(run) -> Optional[float]:
    return median_ms(batch_gaps_s(window_batches(run)))
