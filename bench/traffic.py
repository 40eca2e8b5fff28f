"""The one traffic generator. A traffic mix is a JSON file of parameters
(``bench/traffic/<mix>.json``); this module turns it and ``--seed`` into
requests.

Every seed gets the same multiset of sizes and gaps, in another order:
lengths are the stratified quantiles ``(i + 0.5) / n`` of the mix's
distribution, and arrival gaps the stratified quantiles of an exponential.
The seed permutes each of them on its own and draws the token ids. So two
seeds do the same amount of work, and a difference between seeds is the
system's, not the draw's. The order is uniformly random, so the arrivals
cluster as a Poisson process's do, and long requests fall together by
chance. A closed-loop pool is built of blocks, each a whole stratified set,
so any prefix of it has nearly the mix's composition.

Keys of a mix:

- ``loop``: ``open`` (scheduled arrivals), ``closed`` (``outstanding``
  requests kept queued) or ``steps`` (a solver stepping back to back);
- ``rate_per_s`` (open): mean arrival rate of the Poisson process;
- ``outstanding``, ``pool``, ``block`` (closed);
- ``prompt``, ``output``: ``{"dist": "lognormal", "median", "sigma"}`` or
  ``{"dist": "uniform"}``, each with ``min``, ``max`` and an optional
  ``grid`` that lengths are rounded up to.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

_STD_NORMAL = NormalDist()


@dataclasses.dataclass
class Request:
    arrival_s: float          # scheduled, from the start of the window
    prompt: np.ndarray        # int32 token ids
    max_new: int


def _grid_up(x: float, grid: int | None) -> int:
    if grid:
        return int(math.ceil(x / grid) * grid)
    return int(round(x))


def quantile(dist: dict, u: float) -> int:
    """The length at quantile ``u`` of a length distribution, clipped to
    ``[min, max]`` and rounded up to its grid."""
    lo, hi, grid = dist["min"], dist["max"], dist.get("grid")
    if dist["dist"] == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * _STD_NORMAL.inv_cdf(u))
    elif dist["dist"] == "uniform":
        if grid:                          # uniform over the grid's points
            pts = list(range(_grid_up(lo, grid), hi + 1, grid))
            return pts[min(int(u * len(pts)), len(pts) - 1)]
        x = lo + u * (hi - lo)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return min(max(_grid_up(min(max(x, lo), hi), grid), lo), hi)


def stratified(dist: dict, n: int) -> list[int]:
    return [quantile(dist, (i + 0.5) / n) for i in range(n)]


def prompt_lengths(mix: dict, seconds: float) -> list[int]:
    """The distinct prompt lengths a run of this mix submits, whatever the
    seed: the shapes set-up has to warm."""
    n = _count(mix, seconds) if mix["loop"] == "open" else mix["block"]
    return sorted(set(stratified(mix["prompt"], n)))


def _count(mix: dict, seconds: float) -> int:
    if mix["loop"] == "open":
        return max(1, int(round(mix["rate_per_s"] * seconds)))
    if mix["loop"] == "closed":
        return int(mix["pool"])
    raise ValueError(f"a {mix['loop']!r} mix has no requests")


def generate(mix: dict, seed: int, seconds: float, vocab: int
             ) -> list[Request]:
    """The requests of one run, in submission order."""
    rng = np.random.default_rng(seed)
    n = _count(mix, seconds)
    if mix["loop"] == "open":
        prompts = rng.permutation(stratified(mix["prompt"], n))
        outputs = rng.permutation(stratified(mix["output"], n))
        # the stratified gaps sum to a little under n / rate: the schedule
        # spans the window
        gaps = [-math.log(1.0 - (i + 0.5) / n) / mix["rate_per_s"]
                for i in range(n)]
        arrivals = np.cumsum(rng.permutation(gaps))
    else:
        block = int(mix["block"])
        if n % block:
            raise ValueError(f"pool {n} is not a whole number of blocks "
                             f"of {block}")
        p_set = stratified(mix["prompt"], block)
        o_set = stratified(mix["output"], block)
        prompts = np.concatenate([rng.permutation(p_set)
                                  for _ in range(n // block)])
        outputs = np.concatenate([rng.permutation(o_set)
                                  for _ in range(n // block)])
        arrivals = np.zeros(n)
    return [Request(float(a), rng.integers(0, vocab, int(p), dtype=np.int32),
                    int(o))
            for a, p, o in zip(arrivals, prompts, outputs)]
