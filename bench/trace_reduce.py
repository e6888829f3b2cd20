"""From a profiler trace (``.xplane.pb``) to device busy time, idle gaps
and per-op device time.

The device planes are ``/device:TPU:<n>``; each holds an ``XLA Ops``
line whose events are the operations that ran on that chip, with their
start and duration.  Busy time is the union of those intervals (nested
or overlapping events count once); idle share is one minus busy over the
traced window.  An idle gap between two busy intervals is labelled with
the host event that overlaps it most (the host planes carry the
runtime's own annotations on the same clock), so the gaps say what the
host was doing while the chip waited.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Tuple

OPS_LINE = "XLA Ops"
# an op's name in the breakdown: the HLO instruction's text, cut here
NAME_CHARS = 160
DEVICE_PREFIX = "/device:TPU:"


def find_xplane(directory: str) -> str:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(paths, key=os.path.getmtime)


def load(path: str):
    """A profile from an ``.xplane.pb`` (or its text form, ``.pbtxt``)."""
    import jax
    if path.endswith(".pbtxt"):
        with open(path) as f:
            return jax.profiler.ProfileData.from_text_proto(f.read())
    return jax.profiler.ProfileData.from_file(path)


def _stat_text(event) -> str:
    """The event's string stats joined (the op's long name, the
    framework op and the kernel name live there)."""
    try:
        return " ".join(str(v) for _, v in event.stats
                        if isinstance(v, str))
    except (TypeError, ValueError):
        return ""


def device_ops(pd) -> Dict[str, List[Tuple[str, float, float, str]]]:
    """{device plane: [(op name, start_s, dur_s, stat text)]}."""
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        ops = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                ops.append((ev.name, ev.start_ns * 1e-9,
                            ev.duration_ns * 1e-9, _stat_text(ev)))
        out[plane.name] = sorted(ops, key=lambda o: o[1])
    return out


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def host_events(pd) -> List[Tuple[str, float, float]]:
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.duration_ns > 0:
                    out.append((ev.name, ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9))
    return out


def _label(gap: Tuple[float, float], hosts) -> str:
    a, b = gap
    best, most = "no host event", 0.0
    for name, s, e in hosts:
        ov = min(b, e) - max(a, s)
        # an event that spans the whole gap and far beyond it (a thread's
        # whole life) says nothing about the gap
        if ov > most and (e - s) < 50 * (b - a):
            best, most = name, ov
    return best


def leaves(ops):
    """The ops that hold no other op: a control-flow op (a while loop,
    a conditional) spans the ops of its body, which the trace lists
    too.  ``ops`` are one chip's, sorted by start."""
    out = []
    for i, op in enumerate(ops):
        end = op[1] + op[2]
        if i + 1 < len(ops) and ops[i + 1][1] < end - 1e-9:
            continue
        out.append(op)
    return out


def trace_start(pd) -> float:
    """The earliest event of any plane: the profiler's start, on the
    trace's clock."""
    return min(ev.start_ns * 1e-9 for plane in pd.planes
               for line in plane.lines for ev in line.events)


def reduce(path: str, window_s: float, top: int = 10) -> dict:
    """busy_s (mean over the traced chips), window_s, the ops, and the
    breakdown: the ``top`` ops by device time (leaf ops only, so a loop
    does not count its body twice) and the ``top`` longest
    idle gaps of the first chip, labelled.  ``window_s`` is the traced
    window's length on the host clock; device time after it (the
    profiler still records while it stops) is left out."""
    pd = load(path)
    per_dev = device_ops(pd)
    if not per_dev:
        raise ValueError(f"{path} has no {DEVICE_PREFIX}* plane")
    t0 = trace_start(pd)
    t1 = t0 + window_s
    busy = {}
    for name, ops in per_dev.items():
        busy[name] = [(max(a, t0), min(b, t1)) for a, b in
                      union([(s, s + d) for _, s, d, _ in ops])
                      if b > t0 and a < t1]
    busy_s = sum(sum(b - a for a, b in iv) for iv in busy.values()) \
        / len(busy)
    totals: Dict[str, float] = defaultdict(float)
    for ops in per_dev.values():
        for name, _, d, _ in leaves(ops):
            totals[name] += d / len(per_dev)
    first = busy[sorted(busy)[0]]
    gaps = sorted(((b0, a1) for (_, b0), (a1, _) in zip(first, first[1:])),
                  key=lambda g: g[0] - g[1])[:top]
    hosts = host_events(pd) if gaps else []
    ops_all = [o for ops in per_dev.values() for o in ops]
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "ops": ops_all,
        "devices": len(per_dev),
        "breakdown": {
            "device_ops": sorted(([n[:NAME_CHARS], t]
                                  for n, t in totals.items()),
                                 key=lambda x: -x[1])[:top],
            "idle_gaps": [[_label(g, hosts), g[1] - g[0]] for g in gaps],
        },
    }


# a custom call's first operand as the HLO text names it: f32[rows,cols]
_OPERAND = re.compile(r"custom-call\((bf16|f16|f32)\[(\d+),(\d+)\]")
_WIDTH = {"bf16": 2, "f16": 2, "f32": 4}


def kernel_calls(ops, needle: str) -> List[Tuple[int, int, int, float]]:
    """(rows, cols, bytes per element, device seconds) of each call of
    the custom-call kernel whose name or stats contain ``needle``, its
    shape read from the (rows, cols) operand the HLO text names.  A call
    whose operand is not named so is left out."""
    out = []
    for name, _, d, text in ops:
        if needle not in name and needle not in text:
            continue
        m = _OPERAND.search(name) or _OPERAND.search(text)
        if m:
            out.append((int(m[2]), int(m[3]), _WIDTH[m[1]], d))
    return out
