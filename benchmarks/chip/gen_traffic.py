"""The general traffic generator: a traffic file (``traffic/<name>.json``)
of parameters in, a request stream and its arrival times out.

A traffic file holds:

* ``loop``: ``"closed"`` (a backlog of ``outstanding_launches`` full
  launches kept outstanding: each completion submits the next request) or
  ``"open"`` (arrivals on a schedule, whatever the service does);
* the GA budget: ``pop_size`` and ``generations`` for every request, or
  ``budgets``, a list of ``{"pop_size": P, "generations": G}`` that the
  requests cycle through (a mixed-signature queue);
* ``subsets``: which workload subsets the request cycle walks, in order,
  among ``"all"``, ``"singles"`` and ``"pairs"`` (pair i is workloads i and
  i+1, wrapping), and ``objectives``: the objective cycle;
* ``lead_launches`` (closed) or ``lead_s`` (open): how much traffic runs
  before the window opens, so the window starts in steady state;
* open loops: ``rate_per_s`` (the mean arrival rate) and ``arrivals``:
  ``"poisson"`` (exponential gaps), ``"uniform"`` (equal gaps) or
  ``"bursty"`` (bursts of ``burst_size`` requests, ``burst_spread_s``
  apart within a burst, whose starts arrive as a Poisson stream at
  ``rate_per_s / burst_size``).

Request i takes subset ``i % len(subsets)``, objective
``i % len(objectives)`` (the service's ``paper_request_mix`` cycle),
budget ``i % len(budgets)`` and a fresh seed drawn from the run's seed.
Every run seed gets the same set of request kinds, budgets and
inter-arrival gaps; the seed changes only the request seeds and the
order of the gaps.

A mix that these parameters cannot express is a file
``traffic/<name>.py`` of its own: a module with a ``TRAFFIC`` dict of its
parameters (at least ``loop`` and its lead), a ``Stream`` class and, for
an open loop, an ``arrival_offsets`` function with the signatures below.
It may import this module and replace only what differs.
"""
from __future__ import annotations

from typing import List, NamedTuple, Sequence, Tuple

import numpy as np

SEED_SPACE = 2 ** 31 - 1


class Req(NamedTuple):
    subset: Tuple[int, ...]
    objective: str
    seed: int
    pop_size: int
    generations: int


def subset_cycle(n_workloads: int, kinds: Sequence[str]) -> List[Tuple[int, ...]]:
    out: List[Tuple[int, ...]] = []
    W = n_workloads
    for kind in kinds:
        if kind == "all":
            out.append(tuple(range(W)))
        elif kind == "singles":
            out += [(i,) for i in range(W)]
        elif kind == "pairs":
            out += [(i, (i + 1) % W) for i in range(W)] if W > 1 else []
        else:
            raise ValueError(f"unknown subset kind {kind!r}")
    return out


def budgets(traffic: dict) -> List[Tuple[int, int]]:
    """The (P, G) budgets the requests cycle through."""
    if "budgets" in traffic:
        return [(int(b["pop_size"]), int(b["generations"]))
                for b in traffic["budgets"]]
    return [(int(traffic["pop_size"]), int(traffic["generations"]))]


class Stream:
    """Request i of a run, for i = 0, 1, ... (an endless cycle).  Warm-up
    requests come from a stream of their own (``warm=True``), so the
    window never repeats a warm-up seed."""

    def __init__(self, traffic: dict, n_workloads: int, run_seed: int,
                 warm: bool = False):
        self.subsets = subset_cycle(n_workloads, traffic["subsets"])
        self.objectives = list(traffic["objectives"])
        self.budgets = budgets(traffic)
        self._rng = np.random.default_rng([int(run_seed) % 2 ** 63, int(warm)])
        self._seeds: List[int] = []

    def seed(self, i: int) -> int:
        while len(self._seeds) <= i:
            self._seeds.extend(
                int(s) for s in self._rng.integers(0, SEED_SPACE, 1024))
        return self._seeds[i]

    def __getitem__(self, i: int) -> Req:
        P, G = self.budgets[i % len(self.budgets)]
        return Req(self.subsets[i % len(self.subsets)],
                   self.objectives[i % len(self.objectives)],
                   self.seed(i), P, G)

    def take(self, n: int, start: int = 0) -> List[Req]:
        return [self[i] for i in range(start, start + n)]


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    """n gaps of a Poisson stream at ``rate``: the exponential law's
    quantiles at (j + 0.5) / n, a fixed set whatever the seed."""
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


def arrival_offsets(traffic: dict, run_seed: int, span_s: float) -> np.ndarray:
    """Open loop: due times (seconds from the start of traffic) covering
    ``span_s``, from a fixed set of gaps in an order drawn from the seed."""
    law = traffic.get("arrivals", "poisson")
    rate = float(traffic["rate_per_s"])
    n = int(np.ceil(rate * span_s * 1.25)) + 16
    rng = np.random.default_rng([int(run_seed) % 2 ** 63, 2])
    if law == "poisson":
        t = np.cumsum(rng.permutation(exponential_gaps(rate, n)))
    elif law == "uniform":
        t = (np.arange(n) + 1) / rate
    elif law == "bursty":
        size = int(traffic["burst_size"])
        spread = float(traffic.get("burst_spread_s", 0.0))
        nb = n // size + 1
        starts = np.cumsum(rng.permutation(exponential_gaps(rate / size, nb)))
        t = (starts[:, None] + spread * np.arange(size)[None, :]).ravel()
    else:
        raise ValueError(f"unknown arrivals {law!r}")
    return t[t < span_s]
