"""The one traffic generator: a data file of parameters in, requests out.

A serving mix (``"kind": "serve"``) states its loop (``open`` with a rate,
Poisson arrivals, or ``closed`` with a number of clients) and the laws of
its prompt and output lengths: ``uniform`` (min, max) or ``lognormal``
(median, sigma, clipped to min and max).

The lengths, their order and the arrival times are drawn from a generator
fixed here and are the same in every run; ``--seed`` draws the token ids
(and, in the driver, the weights).  Every seed therefore offers the same
work at the same times, and runs with different seeds spread like runs of
one (PERF.md, PR 26, finding 1: a reshuffled order is a different
workload).  In an open loop the gaps are scaled to fill the window
exactly, so that every request of the set is due inside it.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Planned:
    prompt: List[int]
    max_new_tokens: int
    due_s: Optional[float]   # open loop: offset from the window's start


def draw(law: dict, n: int, rng) -> np.ndarray:
    kind = law["dist"]
    if kind == "uniform":
        x = rng.integers(law["min"], law["max"] + 1, size=n).astype(float)
    elif kind == "lognormal":
        x = law["median"] * np.exp(law["sigma"] * rng.standard_normal(n))
    else:
        raise ValueError(f"unknown length law {kind!r}")
    return np.clip(np.rint(x), law["min"], law["max"]).astype(int)


def plan(mix: dict, seed: int, seconds: float, vocab: int) -> List[Planned]:
    """The requests of one run, in the order they are offered."""
    shape = np.random.default_rng(0)
    ids = np.random.default_rng(seed)
    if mix["loop"] == "open":
        n = max(1, int(round(mix["rate_per_s"] * seconds)))
    else:
        n = int(mix["closed_set"])
    p_len = draw(mix["prompt"], n, shape)
    o_len = draw(mix["output"], n, shape)
    due = None
    if mix["loop"] == "open":
        g = shape.exponential(1.0, size=n)
        due = np.cumsum(g) - g[0]
        due = due * (seconds / (due[-1] + g[0]))
    return [
        Planned(
            prompt=[int(t) for t in ids.integers(0, vocab, size=int(p_len[i]))],
            max_new_tokens=int(o_len[i]),
            due_s=None if due is None else float(due[i]),
        )
        for i in range(n)
    ]
