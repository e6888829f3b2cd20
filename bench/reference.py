"""The replay that decides ``correct``, over a family's plain reference.

The reference itself, the model's float32 forward at ``HIGHEST``
precision and its float8 control, belongs to the configuration's family
(``bench/families/<family>.py``: ``forward_rows``, ``capture``,
``forward_window``); it imports nothing of the program under test and
reads the weights that ``bench.weights`` draws from the seed.  What
does not depend on the architecture is here.

``replay`` checks served tokens.  A served token was committed as the
argmax of the model's logits at some step of its block, on the canvas
that held the tokens committed before it.  The order of commits is not
served, and on random weights the masked positions' confidences lie
within the program's bf16 rounding of each other, so the reference
cannot rebuild the program's order from its own confidences.  The
replay goes block by block, step by step with the program's commit
widths: it scores the canvas with the reference and commits the open
positions whose served token lies least below the reference's best
(the most confident first among equals), writing in the served tokens.
Each served token is read at the step, of those its position was open
in, where it lay least below the reference's best: the canvas closest
to the one it was committed on.  Its gap there is the reference's best
logit minus its own; the check compares the share of tokens with a gap
and reports the widest.  For
``cache_policy = dual`` the reference computes the same approximation:
the K/V of the whole canvas captured at each block's start, the block's
own rows recomputed at every step against them.

``control=True`` also runs the family's float8 control (both operands of
every projection and of the head rounded through float8 e4m3, a scale
per row of the activations and per column of the weights, as an fp8
matmul would) and reads, at each served token's step, the gap of the
token that the float8 model puts first.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def _read(logits, served, ctrl_logits):
    """Per row: the served token's gap below the best logit, the
    reference's confidence (softmax max), and the control's top token's
    gap (0 without a control)."""
    best = jnp.max(logits, -1)
    got = jnp.take_along_axis(logits, served[..., None], -1)[..., 0]
    conf = 1.0 / jnp.sum(jnp.exp(logits - best[..., None]), -1)
    if ctrl_logits is None:
        cgap = jnp.zeros_like(best)
    else:
        top = jnp.argmax(ctrl_logits, -1)
        cgap = best - jnp.take_along_axis(logits, top[..., None], -1)[..., 0]
    return best - got, conf, cgap


def commit_widths(gen: int, block: int, steps: int) -> list:
    """Per block, the tokens committed at each step: ``steps`` spread
    over the blocks and each block's width over its steps, remainders to
    the front."""
    nb = gen // block
    base, rem = divmod(steps, nb)
    out = []
    for b in range(nb):
        spb = base + (1 if b < rem else 0)
        w, wr = divmod(block, spb)
        out.append([w + 1] * wr + [w] * (spb - wr))
    return out


def pick(conf, gap, cand, n: int) -> np.ndarray:
    """The ``n`` positions of ``cand`` that one replayed step commits:
    the least gap first, the most confident first among equal gaps."""
    return cand[np.lexsort((-conf[cand], gap[cand]))[:n]]


def replay(family, flat, sizes: dict, prompts: np.ndarray,
           served: np.ndarray, geometry: dict, mask_id: int,
           control: bool = False):
    """Replay requests of one prompt length through ``family``'s
    reference. prompts (R, Lp) and served (R, gen) int arrays. Returns
    (gaps (R, gen), ctrl_gaps (R, gen)): each served token's least gap
    over the steps its position was open, and the control's top token's
    gap at that step (0 without a control); NaN where the served token
    is the mask id (a greedy step that picks the mask token as its argmax
    leaves the position masked, so nothing was served there)."""
    r, lp = prompts.shape
    gen, bs = geometry["gen_length"], geometry["block_size"]
    dual = geometry["cache_policy"] == "dual"
    canvas = np.concatenate(
        [prompts, np.full((r, gen), mask_id, prompts.dtype)], 1)
    gaps = np.full((r, gen), np.inf)
    cgaps = np.full((r, gen), np.nan)
    for blk, widths in enumerate(commit_widths(gen, bs, geometry["steps"])):
        lo = lp + blk * bs
        cols = slice(blk * bs, (blk + 1) * bs)
        block_served = served[:, cols]
        if dual:
            kv = family.capture(flat, jnp.asarray(canvas), sizes)
            ckv = family.capture(flat, jnp.asarray(canvas), sizes, True) \
                if control else None
        # a served mask id was never committed: the position stayed
        # masked (the argmax was the mask token itself)
        open_ = block_served != mask_id
        for n in widths:
            if dual:
                win = jnp.asarray(canvas[:, lo:lo + bs])
                lg = family.forward_window(flat, win, lo, kv, sizes)
                clg = family.forward_window(flat, win, lo, ckv, sizes,
                                            True) if control else None
            else:
                cv = jnp.asarray(canvas)
                lg = family.forward_rows(flat, cv, lo, sizes, bs)
                clg = family.forward_rows(flat, cv, lo, sizes, bs, True) \
                    if control else None
            g, conf, cg = jax.device_get(
                _read(lg, jnp.asarray(block_served), clg))
            better = open_ & (g < gaps[:, cols])
            gaps[:, cols] = np.where(better, g, gaps[:, cols])
            cgaps[:, cols] = np.where(better, cg, cgaps[:, cols])
            for i in range(r):
                cand = np.flatnonzero(open_[i])
                for j in pick(conf[i], g[i], cand, n):
                    canvas[i, lo + j] = block_served[i, j]
                    open_[i, j] = False
        del lg, clg
    gaps[served == mask_id] = np.nan
    return gaps, cgaps
