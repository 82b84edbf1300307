"""The one traffic generator: a mix file of parameters in, a schedule out.

A mix (``mixes/<name>.json``) gives the arrival process and the length
distributions.  For a run of ``seconds`` at ``rate`` requests/s it makes
``n = round(rate * seconds)`` requests:

* arrival gaps are drawn once from the mix's own ``pattern_seed`` (gamma
  with the stated coefficient of variation; 1 is Poisson) and scaled so
  that ``n`` requests fall due in ``[0, seconds)`` at exactly ``rate``;
* prompt and output lengths are the distribution's ``n`` quantiles at
  ``(i + 0.5) / n``, clipped to the stated range, dealt over the
  arrivals in an order drawn from the same ``pattern_seed``.

So every seed offers the same requests at the same times, and so the
same work.  A window holds a few tens of requests, and drawing their
order from the run's seed would move the latency statistics between
seeds by a fifth to a half: the order of the lengths alone sets which
micro-batch carries the long prompts.  A mix that wants another trace
names another ``pattern_seed``.  The run's seed draws the prompt tokens,
uniformly from ``[3, vocab)`` (ids 0-2 are padding, start and end of
sequence).
"""
from __future__ import annotations

import math
import statistics
from typing import Dict, List, NamedTuple

import numpy as np

FIRST_ID = 3


class Due(NamedTuple):
    """One request: when it is due (seconds from the window's start), its
    prompt ids, and how many tokens it asks for."""

    due_s: float
    prompt: List[int]
    max_new_tokens: int


def _quantiles(spec: Dict, n: int) -> List[int]:
    dist = spec["dist"]
    if dist == "fixed":
        return [int(spec["value"])] * n
    if dist != "lognormal":
        raise ValueError(f"unknown length distribution {dist!r}")
    z = statistics.NormalDist()
    out = []
    for i in range(n):
        x = spec["median"] * math.exp(spec["sigma"] * z.inv_cdf((i + 0.5) / n))
        out.append(int(min(max(round(x), spec["min"]), spec["max"])))
    return out


def _due_times(spec: Dict, rate: float, seconds: float, n: int
               ) -> List[float]:
    if spec["process"] != "gamma":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    shape = 1.0 / spec["cv"] ** 2
    rng = np.random.default_rng(spec["pattern_seed"])
    gaps = rng.gamma(shape, 1.0 / (rate * shape), size=n)
    gaps *= seconds / gaps.sum()
    return [float(t) for t in np.concatenate([[0.0], np.cumsum(gaps)[:-1]])]


def schedule(mix: Dict, rate: float, seconds: float, seed: int,
             vocab: int) -> List[Due]:
    """The requests of one run, in due order."""
    n = max(1, round(rate * seconds))
    deal = np.random.default_rng(mix["arrivals"]["pattern_seed"] + 1)
    prompts = deal.permutation(_quantiles(mix["prompt_tokens"], n))
    outputs = deal.permutation(_quantiles(mix["output_tokens"], n))
    dues = _due_times(mix["arrivals"], rate, seconds, n)
    rng = np.random.default_rng(int(seed) % (1 << 64))
    return [Due(t, [int(x) for x in rng.integers(FIRST_ID, vocab, int(p))],
                int(o))
            for t, p, o in zip(dues, prompts, outputs)]
