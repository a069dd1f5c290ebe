"""The open-loop request generator every serving cell uses.

A traffic file gives the parameters; this module turns them and a run's
seed into a schedule:

* arrivals: a Poisson process at ``rate`` requests/s.  The gaps are drawn
  once from the traffic file's own ``shape_seed`` and scaled so that they
  fill exactly ``--seconds``; the run's seed only shuffles their order, so
  every seed offers the same number of requests over the same time;
* request sizes: geometric with mean ``ids_mean``, capped at ``ids_max``,
  drawn and shuffled the same way;
* node ids: Zipf(``zipf_s``) over nodes ranked by degree, drawn from the
  run's seed, so popular requests ask for hubs.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Schedule:
    due: np.ndarray            # seconds from the window's start, ascending
    requests: list             # int64 node-id arrays, one per arrival

    @property
    def n(self) -> int:
        return len(self.due)


def make_schedule(tr: dict, degrees: np.ndarray, seconds: float,
                  seed: int) -> Schedule:
    n = max(int(round(tr["rate"] * seconds)), 1)
    shape = np.random.default_rng(tr["shape_seed"])
    gaps = shape.exponential(1.0, size=n)
    sizes = np.minimum(shape.geometric(1.0 / tr["ids_mean"], size=n),
                       tr["ids_max"])
    rng = np.random.default_rng(seed)
    gaps = rng.permutation(gaps)
    due = np.cumsum(gaps)
    due = (due - due[0]) * (seconds / max(due[-1] - due[0], 1e-9)) \
        if n > 1 else np.zeros(1)
    sizes = rng.permutation(sizes)
    ranked = np.argsort(-degrees, kind="stable")
    w = 1.0 / np.arange(1, len(ranked) + 1, dtype=np.float64) ** tr["zipf_s"]
    cdf = np.cumsum(w)
    cdf /= cdf[-1]
    ranks = np.searchsorted(cdf, rng.random(int(sizes.sum())), side="right")
    ids = ranked[np.minimum(ranks, len(ranked) - 1)].astype(np.int64)
    return Schedule(due=due, requests=np.split(ids, np.cumsum(sizes)[:-1]))
