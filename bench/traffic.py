"""The one traffic generator: a mix file's parameters plus a seed give
the prompts and their arrival times.

A mix (``bench/traffic/<name>.json``) holds:

* ``arrivals``: ``"backlog"`` (an offline job: ``backlog`` requests are
  kept in flight, each replaced as soon as it ends) or ``"poisson"``
  (independent users: open-loop arrivals at ``rate_per_s``);
* ``prompt_lengths``: {length: share};
* ``decode``: the request's decode settings (``strategy``,
  ``cache_policy``, ``gen_length``, ``block_size``, ``steps`` and the
  strategy's own knobs such as ``k1``);
* ``max_batch``: the server's batch size;
* ``check_requests``: how many finished requests the reference replays;
* ``trace_seconds``: the length of the profiler window in a traced run.

Every seed gets the same work in the same order: the same prompt
lengths and, for Poisson arrivals, the same gaps between arrivals (the
exponential distribution's quantiles, scaled to fill the window
exactly), in one shuffled order that the mix fixes.  The seed draws the
token ids alone, so runs on different seeds queue alike and their spread
is the system's, not the schedule's.
"""
from __future__ import annotations

import json
import math
import os
from typing import List, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
# the seed of the schedule's order, the same for every run
ORDER_SEED = 0


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def _rs(seed: int, *tags: int) -> np.random.RandomState:
    words = np.random.SeedSequence(
        [int(seed) & (2**64 - 1), *tags]).generate_state(4)
    return np.random.RandomState(words)


def length_plan(mix: dict, n: int) -> List[int]:
    """``n`` prompt lengths in the mix's exact shares, in the fixed
    shuffled order."""
    shares = sorted((int(k), float(v))
                    for k, v in mix["prompt_lengths"].items())
    counts = [int(math.floor(p * n)) for _, p in shares]
    # largest remainders take the rounding, so counts always sum to n
    rest = sorted(range(len(shares)),
                  key=lambda i: -(shares[i][1] * n - counts[i]))
    for i in rest[:n - sum(counts)]:
        counts[i] += 1
    plan = [length for (length, _), c in zip(shares, counts)
            for _ in range(c)]
    _rs(ORDER_SEED, 1).shuffle(plan)
    return plan


def prompt(mix: dict, seed: int, index: int, length: int,
           mask_id: int) -> np.ndarray:
    """Request ``index``'s prompt: token ids below the mask id."""
    return _rs(seed, 2, index).randint(0, mask_id, length).astype(np.int32)


def poisson_due(mix: dict, seconds: float) -> List[float]:
    """Due times (s from the window's start) of the requests in a window
    of ``seconds``: round(rate * seconds) arrivals whose gaps are the
    exponential quantiles at (i + 1/2)/n, in the fixed shuffled order,
    scaled to sum to the window."""
    n = max(int(round(mix["rate_per_s"] * seconds)), 1)
    q = -np.log(1.0 - (np.arange(n) + 0.5) / n)
    _rs(ORDER_SEED, 3).shuffle(q)
    gaps = q * (seconds / q.sum())
    return list(np.concatenate([[0.0], np.cumsum(gaps)[:-1]]))


def schedule(mix: dict, seconds: float, seed: int,
             mask_id: int) -> List[Tuple[float, np.ndarray]]:
    """(due s, prompt) of every request a Poisson window offers."""
    due = poisson_due(mix, seconds)
    lengths = length_plan(mix, len(due))
    return [(t, prompt(mix, seed, i, n, mask_id))
            for i, (t, n) in enumerate(zip(due, lengths))]
