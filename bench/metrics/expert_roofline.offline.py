"""The held experts' grouped matmul's share of its roofline, in %.
Layer: kernels.  Moves ``gen_tok_s``.

The least time of one expert-layer call is the larger of two totals,
each a mean per call from the program's counters (``SampleStats``
``expert_rows``, ``experts_read`` and ``expert_calls``, summed over the
finished requests): the bytes of the ``experts_read`` held experts'
three matrices and of the routed rows in and out, over the HBM
bandwidth; and ``6 * d_model * moe_d_ff * expert_rows`` operations over
the bf16 peak.  Taking the larger of the totals reads low, never high.

That is divided by the kernel's device time per call in the trace: the
ops named for the grouped matmul (``gmm``, three per layer call: gate,
up, down), each with the op that staged its expert weights just before
it, if the compiler staged them (it copies a layer's slice of the
stacked weights into the chip's fast memory, and the kernel then reads
them there).  None where the program reports no counters or the trace
has no such op.
"""
import re

KERNEL = re.compile(r"^%gmm(\.\d+)? = .*custom-call\(")
# the kernel's rank-3 operand (the experts' weights) as the HLO names it
WEIGHTS = re.compile(r"\w+\[\d+,\d+,\d+\]\{[^}]*\} (%[\w.\-]+)")
KERNELS_PER_CALL = 3
WIDTH = {"bfloat16": 2, "float32": 4}


def counters(records):
    """(expert rows, experts read, calls) summed over finished requests,
    or None where the program reports none."""
    got = [r.stats for r in records
           if r.ok and r.stats and r.stats.get("expert_calls")]
    if not got:
        return None
    return tuple(sum(s[k] for s in got)
                 for k in ("expert_rows", "experts_read", "expert_calls"))


def kernel_seconds(ops):
    """(kernel ops, device seconds) of the grouped matmul, each op with
    its staged weights."""
    n, total = 0, 0.0
    recent = {}                  # ops since the last kernel op: name -> s
    for name, _, dur, _ in sorted(ops, key=lambda o: o[1]):
        if not KERNEL.match(name):
            recent[name.split(" ", 1)[0]] = dur
            continue
        n += 1
        total += dur + sum(recent.get(w, 0.0) for w in WEIGHTS.findall(name))
        recent = {}
    return n, total


def read(run):
    got = counters(run.records)
    n, seconds = kernel_seconds(run.trace["ops"])
    if got is None or not n or seconds <= 0:
        return None
    rows, experts, calls = (v / got[2] for v in got)
    s = run.config["sizes"]
    d, ff = s["d_model"], s["moe_d_ff"]
    width = WIDTH[run.config["weights_dtype"]]
    least = max((experts * 3 * d * ff + 2 * rows * d) * width
                / run.peaks["hbm_bytes_per_s"],
                6 * d * ff * rows / run.peaks["bf16_flops_per_s"])
    per_call = seconds * KERNELS_PER_CALL / n
    return 100.0 * least / per_call
