"""Traffic generated from a mix's parameters and the run's seed.

Every seed of a mix gets the same work: the same set of lengths and gaps
between arrivals, drawn as fixed quantiles of the mix's distributions, in an
order (and with token ids) that the seed chooses.  So two seeds differ in
order and content, never in how much work the window holds.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, List

import numpy as np

from .seeds import sub_seed


def token_rows(vocab: int, seq: int, seed: int, n_rows: int) -> np.ndarray:
    """[n_rows, seq] int32: the rows a ``SyntheticSource(vocab, seq, seed)``
    exports, made the way it makes them (one draw of ``seq`` per row)."""
    rng = np.random.default_rng(seed)
    out = np.empty((n_rows, seq), np.int32)
    for i in range(n_rows):
        out[i] = rng.integers(0, vocab, seq)
    return out


def _lognormal_set(spec: Dict[str, float], n: int) -> np.ndarray:
    """n lengths at the mid-quantiles of a clipped lognormal."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


@dataclass
class Request:
    due_s: float            # offset from the window's start
    prompt: List[int]
    max_new: int


def open_loop(traffic: Dict, vocab: int, seed: int, seconds: float,
              rate: float = None) -> List[Request]:
    """Requests due in ``[0, seconds)`` at ``rate`` per second (the mix's
    own unless given): exponential gaps (Poisson arrivals), lognormal prompt
    and output lengths."""
    rate = float(rate or traffic["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng(sub_seed(seed, "schedule"))
    q = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-q) / rate)
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    due *= min(1.0, 0.999 * seconds / max(due[-1], 1e-9))
    prompts = rng.permutation(_lognormal_set(traffic["prompt"], n))
    outs = rng.permutation(_lognormal_set(traffic["output"], n))
    return [Request(float(d), rng.integers(0, vocab, int(p)).tolist(), int(o))
            for d, p, o in zip(due, prompts, outs)]


def percentile(values, p: float) -> float:
    """The p-th percentile (linear between order statistics); NaN if empty."""
    v = np.asarray(values, np.float64)
    return float(np.percentile(v, p)) if v.size else math.nan
