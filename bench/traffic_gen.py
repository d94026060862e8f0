"""The one traffic generator: open-loop arrivals with heavy-tailed prompt
and output lengths, read from a mix's parameters.

Every seed gets the same work: the gaps between arrivals are the
quantiles of an exponential distribution at the mix's rate (a Poisson
process), and the lengths are the quantiles of a lognormal clipped to the
mix's range, put in one order drawn from the mix's ``order_seed``; the
run's seed draws the prompt tokens. At four fifths of the knee the tail
of the queue depends on which requests meet in a burst, so a seed that
reordered them would change the work itself (the 90th-percentile TTFT of
the chat cell ranged 1.6-6.0 s over six orders at one rate).

A mix file (``bench/traffic/<mix>.json``) holds::

    {"rate_per_s": 3.2,                       # fixed open-loop rate
     "prompt": {"median": 192, "sigma": 0.8, "min": 64, "max": 768},
     "output": {"median": 48, "sigma": 0.9, "min": 8, "max": 256},
     "fill_slots": false,                     # start the window with
                                              # every slot busy
     "order_seed": 1}                         # the schedule's order

Prompt lengths are rounded up to the cell's ``prompt_grid`` (the program
compiles prefill once per prompt length).
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import numpy as np


@dataclasses.dataclass
class Arrival:
    uid: int
    due_s: float              # offset from the window's opening
    prompt: np.ndarray        # int32 tokens
    max_new_tokens: int


def _lognormal_quantiles(n: int, spec: dict) -> np.ndarray:
    from statistics import NormalDist
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _round_up(x: int, grid: Sequence[int]) -> int:
    for g in sorted(grid):
        if x <= g:
            return int(g)
    raise ValueError(f"length {x} above the prompt grid {list(grid)}")


def _order(mix: dict, stream: str):
    return np.random.default_rng([int(mix.get("order_seed", 0)),
                                  sum(map(ord, stream))])


def lengths(n: int, mix: dict, grid: Sequence[int], stream: str) -> tuple:
    """n (prompt, output) length pairs in the mix's fixed order."""
    order = _order(mix, stream)
    p = _lognormal_quantiles(n, mix["prompt"])
    o = _lognormal_quantiles(n, mix["output"])
    p = np.array([_round_up(int(x), grid) for x in p])
    return order.permutation(p), order.permutation(o)


def arrivals(mix: dict, seconds: float, vocab: int, grid: Sequence[int],
             rng, max_seq: Optional[int] = None) -> List[Arrival]:
    """Requests due in ``[0, seconds)`` at the mix's rate; ``rng`` (the
    run's seed) draws the prompt tokens."""
    rate = float(mix["rate_per_s"])
    n = max(int(round(rate * seconds)), 1)
    gaps = _order(mix, "gaps").permutation(
        -np.log(1.0 - (np.arange(n) + 0.5) / n))
    gaps *= seconds / gaps.sum()          # exactly n arrivals in the window
    due = np.cumsum(gaps) - gaps
    plens, olens = lengths(n, mix, grid, "window")
    out = []
    for i in range(n):
        plen, olen = int(plens[i]), int(olens[i])
        if max_seq is not None:
            olen = min(olen, max_seq - 1 - plen)
        out.append(Arrival(i, float(due[i]),
                           rng.integers(0, vocab, plen, dtype=np.int32),
                           max(olen, 1)))
    return out


def fill(mix: dict, count: int, vocab: int, grid: Sequence[int], rng,
         max_seq: Optional[int] = None, uid0: int = 1 << 30) -> List[Arrival]:
    """``count`` requests from the mix's lengths, due before the window
    opens: they occupy the slots when it does."""
    plens, olens = lengths(max(count, 1), mix, grid, "fill")
    out = []
    for i in range(count):
        plen, olen = int(plens[i]), int(olens[i])
        if max_seq is not None:
            olen = min(olen, max_seq - 1 - plen)
        out.append(Arrival(uid0 + i, -1.0,
                           rng.integers(0, vocab, plen, dtype=np.int32),
                           max(olen, 1)))
    return out


def token_rows(mix: dict, rng, rows: int, cols: int, vocab: int) -> np.ndarray:
    """Training rows: ``rows`` x ``cols`` token ids, uniform over the
    vocabulary (the only distribution a mix names so far)."""
    if mix.get("tokens", "uniform") != "uniform":
        raise ValueError(f"unknown token distribution {mix['tokens']!r}")
    return rng.integers(0, vocab, (rows, cols), dtype=np.int32)
