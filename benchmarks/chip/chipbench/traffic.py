"""The one traffic generator: a mix file's parameters and a seed in,
requests and their due times out.

A schedule is made of parts, such as an open-loop mix's warm-up and its
measured window, each with a set of sizes and a set of gaps between
arrivals of its own: the lengths are the distribution's quantiles at
(i + 1/2)/n, and the seed permutes them within the part and draws the
token ids.  So every seed's window holds the same work in another order,
whatever the warm-up before it did.

Mix parameters (see ``traffic/*.json``):

- ``arrivals``: ``{"kind": "poisson", "rate_per_s": r}`` (open loop, gaps
  from the exponential distribution) or ``{"kind": "backlog", "requests":
  n}`` (all submitted at once);
- ``prompt_len`` and ``output_len``: ``{"dist": "lognormal", "median",
  "sigma", "min", "max"}`` or ``{"dist": "uniform", "min", "max"}``.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Dict, List, Sequence

import numpy as np

# independent random streams drawn from one seed
_PROMPT, _OUTPUT, _GAPS, _TOKENS, _SAMPLE = range(5)


def rng(seed: int, stream: int, part: int = 0) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream, part])


@dataclasses.dataclass
class Planned:
    rid: int
    due_s: float           # from the start of the schedule
    prompt: np.ndarray     # [S] int32
    max_new: int


def quantile_set(spec: Dict, n: int) -> np.ndarray:
    """``n`` lengths at the distribution's quantiles (i + 1/2)/n, clipped
    to [min, max], ascending."""
    q = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        v = spec["min"] + q * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)


def arrival_times(arrivals: Dict, span_s: float, seed: int,
                  part: int = 0) -> np.ndarray:
    """Due times in [0, span_s), ascending; a backlog is due at once."""
    if arrivals["kind"] == "backlog":
        return np.zeros(int(arrivals["requests"]))
    if arrivals["kind"] != "poisson":
        raise ValueError(f"unknown arrival kind {arrivals['kind']!r}")
    n = round(arrivals["rate_per_s"] * span_s)
    if n == 0:
        return np.zeros(0)
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    gaps = gaps / gaps.sum() * span_s          # exactly the offered rate
    gaps = rng(seed, _GAPS, part).permutation(gaps)
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def schedule(mix: Dict, spans_s: Sequence[float], seed: int, vocab: int
             ) -> List[Planned]:
    """Every request of one run in due order: part ``k`` is due in
    ``[sum(spans_s[:k]), sum(spans_s[:k + 1]))``, with sizes and gaps of
    its own."""
    out: List[Planned] = []
    tok = rng(seed, _TOKENS)
    start = 0.0
    for part, span in enumerate(spans_s):
        due = arrival_times(mix["arrivals"], span, seed, part)
        n = len(due)
        plen = rng(seed, _PROMPT, part).permutation(
            quantile_set(mix["prompt_len"], n))
        olen = rng(seed, _OUTPUT, part).permutation(
            quantile_set(mix["output_len"], n))
        out += [Planned(len(out) + i, start + float(due[i]),
                        tok.integers(0, vocab, size=int(plen[i]),
                                     dtype=np.int32), int(olen[i]))
                for i in range(n)]
        start += span
    return out


def sample_ids(positions: Dict[int, int], served: Dict[int, int],
               tokens: int, seed: int) -> List[int]:
    """Finished requests to check: the one with the most positions, then
    others drawn from the seed until their served tokens reach
    ``tokens``."""
    if not positions:
        return []
    longest = max(positions, key=lambda i: (positions[i], i))
    out, n = [longest], served[longest]
    for i in rng(seed, _SAMPLE).permutation(sorted(positions)):
        if n >= tokens:
            break
        if int(i) != longest:
            out.append(int(i))
            n += served[int(i)]
    return out
