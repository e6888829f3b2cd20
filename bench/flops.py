"""Operations and bytes the cells' algorithms require, counted from shapes.

A multiply-add is two operations.  Counts are per sequence (one row of a
batch); callers multiply by the real rows served.  The terms that
depend on the architecture are the family's (``bench/families/``: a
layer over given rows and keys, the head, the keys a row attends to, a
cache refresh); here is the walk over the decode's geometry.  What is
counted:

* a forward over ``rows`` query rows, each attending to the keys the
  family gives, through every layer;
* the LM head only over the rows the strategy reads: at each step the
  block's still-masked rows for the scoring forward, and every
  still-masked row of the canvas for each foreseeing candidate forward
  (its global confidence sums over them);
* a cache refresh at each block's start, as the family costs it.

So a program that computes the head over rows nobody reads, or
recomputes rows a cache could have kept, executes more than is counted:
the share of peak these counts give can only read low, never above 100%.
"""
from __future__ import annotations

from bench.reference import commit_widths


def forward_flops(family, sizes: dict, depth: int, rows: int, keys: int,
                  head_rows: int) -> int:
    return depth * family.layer_flops(sizes, rows, keys) \
        + family.head_flops(sizes, head_rows)


def request_flops(family, sizes: dict, depth: int, geometry: dict,
                  prompt_len: int, fwd_per_step: float) -> float:
    """Operations one sequence's decode requires under the cell's
    geometry (``gen_length``, ``block_size``, ``steps``,
    ``cache_policy``).  ``fwd_per_step`` is the strategy's forwards per
    step over the full canvas (1 for confidence strategies, 1 + K for a
    foreseeing search); under the dual cache every step is one window
    forward."""
    gen, bs = geometry["gen_length"], geometry["block_size"]
    total = prompt_len + gen
    dual = geometry["cache_policy"] == "dual"
    done = 0
    flops = 0.0
    for blk, widths in enumerate(commit_widths(gen, bs, geometry["steps"])):
        lo = prompt_len + blk * bs
        if dual:
            flops += family.refresh_flops(sizes, depth, lo, bs, total)
            rows, first = bs, lo                  # the block's window
        else:
            rows, first = total, 0                # the whole canvas
        keys = family.keys(sizes, first, rows, total)
        in_block = 0
        for n in widths:
            m_blk = bs - in_block                 # block rows still masked
            m_all = gen - done                    # canvas rows still masked
            flops += forward_flops(family, sizes, depth, rows, keys, m_blk)
            if not dual:
                extra = fwd_per_step - 1.0
                flops += extra * forward_flops(family, sizes, depth, rows,
                                               keys, m_all)
            in_block += n
            done += n
    return flops


def confidence_bytes(rows: int, vocab: int, logit_bytes: int = 4) -> int:
    """HBM bytes one call of the fused confidence kernel must move: the
    (rows, vocab) logits read once, four (rows,) scores written.  The
    kernel does a handful of operations per logit, far below the chip's
    ridge point, so bytes bound it."""
    return rows * vocab * logit_bytes + rows * 4 * 4
