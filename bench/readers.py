"""Readings shared by per-layer metrics that two kinds of cell report.

A metric that offline and serving cells both report is split by suffix
(``step_mfu.offline`` moves ``gen_tok_s``, ``step_mfu.serve`` moves
``latency_p90_s``); each split keeps its own reader file under
``bench/metrics/``, and both read the quantity here.  Each function
takes a ``bench.run.RunView`` and returns a number, or ``None`` when
the run holds nothing to read.
"""
from __future__ import annotations

from bench import flops, spans, trace_reduce


def step_mfu(run):
    """Share of the chip's bf16 peak that the decode of each batch
    attains, in %: the operations the cell's algorithm requires for the
    batch's real rows (``bench/flops.py`` with the configuration's
    family's terms) over the batch's decode spans
    times the peak."""
    bs = spans.batches(run.records)
    if not bs:
        return None
    sizes, depth = run.config["sizes"], run.config["depth"]
    dec = run.mix["decode"]
    work = sum(b.rows * flops.request_flops(
        run.family, sizes, depth, dec, b.prompt_len,
        b.forward_equivalents / b.steps) for b in bs)
    seconds = sum(b.decode_s for b in bs)
    return 100.0 * work / (seconds * run.peaks["bf16_flops_per_s"])


def conf_roofline(run):
    """The fused confidence kernel's share of its roofline, in %: the
    least time its calls could take (bytes bound it: the logits read
    once, the scores written, over the HBM bandwidth) over their device
    time in the trace.  Each call's rows and vocabulary are read from
    the logits operand the trace names (``f32[rows,vocab]``)."""
    calls = trace_reduce.kernel_calls(run.trace["ops"], "confidence")
    if not calls:
        return None
    least = sum(flops.confidence_bytes(rows, vocab, width)
                for rows, vocab, width, _ in calls) \
        / run.peaks["hbm_bytes_per_s"]
    seconds = sum(d for *_, d in calls)
    return 100.0 * least / seconds if seconds > 0 else None


def idle_share(run):
    """Share of the traced window in which no operation ran on the chip,
    in %: one minus the union of the device's op intervals over the
    window."""
    t = run.trace
    if t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
