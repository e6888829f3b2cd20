"""The program's own spans, as a traced run's clients fetched them
(``GET /v1/trace/{rid}``, Chrome trace events in microseconds), grouped
into the batches that decoded them.

Every request of a batch carries the same ``batch_assembly``,
``decode_block[i]`` and ``decode_finish`` spans, so
a batch is recognised by its size and the exact durations of its decode
spans.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List


@dataclasses.dataclass
class Batch:
    rows: int                      # real requests in the batch
    prompt_len: int
    decode_s: float                # decode_block + decode_finish spans
    forward_equivalents: float     # the whole batch's, from SampleStats
    steps: int
    tokens: int                    # one request's generated tokens


def _events(rec) -> Dict[str, List[dict]]:
    out: Dict[str, List[dict]] = {}
    for ev in rec.spans:
        if ev.get("ph") == "X":
            out.setdefault(ev["name"].split("[")[0], []).append(ev)
    return out


def batches(records) -> List[Batch]:
    seen = {}
    for r in records:
        if not r.ok or not r.spans:
            continue
        ev = _events(r)
        asm = ev.get("batch_assembly", [])
        if not asm:
            continue
        rows = int(asm[-1]["args"]["batch_size"])
        decode = [e["dur"] for e in ev.get("decode_block", [])] \
            + [e["dur"] for e in ev.get("decode_finish", [])]
        key = (rows, tuple(decode))
        if key in seen:
            continue
        seen[key] = Batch(
            rows=rows, prompt_len=len(r.prompt),
            decode_s=sum(decode) * 1e-6,
            forward_equivalents=float(r.stats["forward_equivalents"]) * rows,
            steps=int(r.stats["steps"]),
            tokens=int(r.stats["tokens_generated"]))
    return list(seen.values())
