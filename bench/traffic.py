"""The one traffic generator: requests of a mix, drawn from its parameters.

A mix is a JSON file under ``bench/traffic/`` whose ``kind`` names the
request it describes:

* ``tick`` — one provisioning tick: ``decisions_per_tick`` NodePools each
  ask for pods uniform in ``pods_mean·(1 ± pods_jitter)``, and
  ``excluded_share_of_decisions`` of them carry the §4.1 exclusion of
  ``excluded_share_of_offerings`` of the offerings, one set drawn per tick.
  Demands are stratified: the range is cut into one stratum per decision
  and each decision draws within its own, so every tick has distinct
  demands, the same spread and the same largest bucket whatever the seed,
  and the seed changes which pool asks for what.
* ``backtest`` — one fleet backtest: ``replicas`` interruption seeds over
  one market path.  The paths come from the mix's fixed list
  ``market_seeds``, one pass after another, each pass in an order drawn
  from the seed; so every run walks the same set of paths, and the seed
  changes their order.  The warm-up walks ``warmup_market_seeds``, which
  the window never uses.

A mix may pin ``catalog_seed``: the deployment's catalog is then the same
for every run, and the seed changes the requests, not the market they are
solved on.  Every mix here does: the market decides how much work a
decision is (how many probes its search makes, how many bundles survive
the prune into the cover DP), and for the storm backtest, whose interrupts
are deterministic given the catalog and the market path, how many
decisions a backtest makes.

Everything random comes from the ``numpy.random.Generator`` handed in,
which the harness derives from ``--seed``.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Set

import numpy as np


@dataclasses.dataclass
class Tick:
    demands: List[int]
    excluded: List[Set[str]]          # per decision (empty: no exclusion)


def demand_range(mix: Dict) -> range:
    lo = int(round(mix["pods_mean"] * (1.0 - mix["pods_jitter"])))
    hi = int(round(mix["pods_mean"] * (1.0 + mix["pods_jitter"])))
    return range(lo, hi + 1)


def strata(mix: Dict) -> List[range]:
    """One demand range per decision of a tick, together the whole range."""
    r, d = demand_range(mix), mix["decisions_per_tick"]
    if len(r) < d:
        raise ValueError(f"{len(r)} demand values cannot give {d} distinct "
                         "decisions per tick")
    edges = [r.start + (i * len(r)) // d for i in range(d + 1)]
    return [range(lo, hi) for lo, hi in zip(edges, edges[1:])]


def tick(mix: Dict, offering_ids: Sequence[str],
         rng: np.random.Generator) -> Tick:
    d = mix["decisions_per_tick"]
    demands = [int(rng.integers(s.start, s.stop)) for s in strata(mix)]
    demands = [demands[i] for i in rng.permutation(d)]
    n_dec = int(round(d * mix["excluded_share_of_decisions"]))
    n_off = int(round(len(offering_ids) * mix["excluded_share_of_offerings"]))
    excluded: List[Set[str]] = [set() for _ in range(d)]
    if n_dec and n_off:
        ids = {offering_ids[i] for i in rng.choice(len(offering_ids), n_off,
                                                   replace=False)}
        for i in rng.choice(d, n_dec, replace=False):
            excluded[int(i)] = ids
    return Tick(demands, excluded)


def largest_demands(mix: Dict, step: int = 64) -> List[int]:
    """Largest demands a tick can carry, sampled every ``step`` pods across
    the top stratum (ends included): a warm-up tick at each reaches every
    shape bucket the mix can, since buckets are wider than ``step``."""
    top = strata(mix)[-1]
    return sorted({*range(top.start, top.stop, step), top.stop - 1})


@dataclasses.dataclass
class Backtest:
    market_seed: int
    interrupt_seeds: List[int]


def backtest(mix: Dict, market_seed: int,
             rng: np.random.Generator) -> Backtest:
    return Backtest(market_seed, [int(s) for s in rng.integers(
        0, 2 ** 31 - 1, mix["replicas"])])


def market_pass(mix: Dict, rng: np.random.Generator) -> List[int]:
    """One pass over the mix's market paths, in an order drawn from
    ``rng``."""
    seeds = mix["market_seeds"]
    return [seeds[int(i)] for i in rng.permutation(len(seeds))]


def with_largest(t: Tick, demand: int) -> Tick:
    """The tick with its largest demand replaced by ``demand``."""
    demands = list(t.demands)
    i = int(np.argmax(demands))
    demands[i] = demand
    if len(set(demands)) < len(demands):
        raise ValueError(f"demand {demand} collides within the tick")
    return Tick(demands, t.excluded)


def reference_sample(n_total: int, n_check: int, largest: Optional[int],
                     rng: np.random.Generator) -> List[int]:
    """Indices of the window's results the reference checks: ``n_check``
    drawn from the seed, the largest request always among them."""
    picks = set(int(i) for i in rng.choice(n_total, min(n_check, n_total),
                                           replace=False))
    if largest is not None and largest not in picks:
        picks.discard(max(picks))
        picks.add(largest)
    return sorted(picks)
