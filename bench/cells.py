"""The two request kinds a mix can drive through KubePACS, and what the
reference checks of each.

* :class:`TickCell` — a provisioning tick: one ``KubePACSProvisioner`` per
  NodePool, all sharing one ``SolveBatch`` on the device backend.  Each
  pool's ``provision`` enqueues its decision (exclusions go through the
  pool's own ``UnavailableOfferingsCache``, the §4.1 path), then
  ``SolveBatch.execute()`` solves the tick and every token is resolved.
* :class:`BacktestCell` — a capacity planner's backtest: ``run_fleet`` of a
  scenario over the deployment's catalog on one market path, one replica
  per interruption seed, memo and batching on.

A cell builds the program's objects from the configuration and the mix,
serves one request at a time, and lists the decisions of a served request
with the inputs the reference needs to re-derive them.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from bench import catalog, reference, traffic

#: a decision as compared: ({offering_id: nodes}, α or None, and every
#: (α, E_Total) its search evaluated, in order)
Answer = Tuple[Dict[str, int], Optional[float], List[Tuple[float, float]]]


@dataclasses.dataclass
class Check:
    """One decision to re-derive: its inputs and the program's answer."""

    offerings: Sequence[Dict]     # the offering table it was solved over
    table_key: object             # equal keys: equal tables
    pods: int
    excluded: frozenset
    answer: Answer


def _offerings(config: Dict, mix: Dict, seed: int) -> List[Dict]:
    """The deployment's catalog: from the mix's ``catalog_seed`` where the
    mix pins one (so every seed gets the same amount of work), else from
    the run's seed."""
    return catalog.deployment_offerings(config, mix.get("catalog_seed", seed))


def _answer(decision) -> Answer:
    trace = decision.trace
    return (decision.pool.as_dict(), decision.alpha,
            list(zip(trace.alphas, trace.e_totals)))


class TickCell:
    kind = "tick"
    span = "bench.tick"
    #: hours the pools' clock advances per tick: the exclusion TTL, so a
    #: tick's exclusions are exactly the ones drawn for it
    TTL = 2.0

    def __init__(self, config: Dict, mix: Dict, seed: int, backend):
        from repro.core import (KubePACSProvisioner, Offering, Request,
                                SolveBatch, compile_market, preprocess)

        self.config, self.mix = config, mix
        self.offerings = _offerings(config, mix, seed)
        self.ids = [o["offering_id"] for o in self.offerings]
        self.catalog = [Offering(**o) for o in self.offerings]
        self._request = lambda pods: Request(
            pods=pods, cpu_per_pod=config["pod_cpu"],
            mem_per_pod=config["pod_mem_gib"])
        items = preprocess(self.catalog, self._request(1))
        self.precompiled = (items, compile_market(items))
        self.batch = SolveBatch(backend=backend)
        self.pools = []
        for _ in range(mix["decisions_per_tick"]):
            prov = KubePACSProvisioner(tolerance=mix["tolerance"],
                                       ttl_hours=self.TTL)
            prov.solve_batch = self.batch
            self.pools.append(prov)
        self.clock = 0.0

    def next_request(self, rng: np.random.Generator) -> traffic.Tick:
        return traffic.tick(self.mix, self.ids, rng)

    def warm_requests(self, rng: np.random.Generator
                      ) -> Iterator[traffic.Tick]:
        for demand in traffic.largest_demands(self.mix):
            yield traffic.with_largest(self.next_request(rng), demand)
        for _ in range(self.mix.get("warmup_requests", 1)):
            yield self.next_request(rng)

    def serve(self, t: traffic.Tick) -> List:
        self.clock += self.TTL
        tokens = []
        for prov, pods, excluded in zip(self.pools, t.demands, t.excluded):
            prov.clock = self.clock
            for oid in excluded:
                prov.cache.add(oid, self.clock)
            tokens.append(prov.provision(self._request(pods), self.catalog,
                                         precompiled=self.precompiled))
        self.batch.execute()
        return [tok.resolve() for tok in tokens]

    @staticmethod
    def decisions(served: List) -> int:
        return len(served)

    def checks(self, window: List[Tuple[traffic.Tick, List]],
               rng: np.random.Generator) -> List[Check]:
        flat = [(pods, excluded, decision)
                for t, served in window
                for pods, excluded, decision in zip(t.demands, t.excluded,
                                                    served)]
        largest = max(range(len(flat)), key=lambda i: flat[i][0])
        picks = traffic.reference_sample(
            len(flat), self.mix["check_decisions"], largest, rng)
        return [Check(self.offerings, "catalog", flat[i][0],
                      frozenset(flat[i][1]), _answer(flat[i][2]))
                for i in picks]


class BacktestCell:
    kind = "backtest"
    span = "bench.backtest"

    def __init__(self, config: Dict, mix: Dict, seed: int, backend):
        from repro.core import Offering
        from repro.sim.scenario import Scenario, Shock

        self.config, self.mix, self.backend = config, mix, backend
        self.offerings = _offerings(config, mix, seed)
        self.catalog = [Offering(**o) for o in self.offerings]
        spec = dict(mix["scenario"])
        spec["shocks"] = tuple(Shock(**s) for s in spec.get("shocks", ()))
        spec.update(cpu_per_pod=config["pod_cpu"],
                    mem_per_pod=config["pod_mem_gib"])
        self.scenario = Scenario(**spec)
        self._paths: List[int] = []

    def next_request(self, rng: np.random.Generator) -> traffic.Backtest:
        if not self._paths:
            self._paths = traffic.market_pass(self.mix, rng)
        return traffic.backtest(self.mix, self._paths.pop(), rng)

    def warm_requests(self, rng: np.random.Generator
                      ) -> Iterator[traffic.Backtest]:
        for market_seed in self.mix["warmup_market_seeds"]:
            yield traffic.backtest(self.mix, market_seed, rng)

    def serve(self, req: traffic.Backtest) -> List:
        from repro.sim import run_fleet

        scenario = dataclasses.replace(self.scenario,
                                       market_seed=req.market_seed)
        # keep_snapshots: each round keeps a reference to the snapshot it
        # decided on, which the reference re-solves after the window
        return run_fleet(scenario, req.interrupt_seeds, catalog=self.catalog,
                         backend=self.backend, keep_snapshots=True)

    @staticmethod
    def decisions(served: List) -> int:
        return sum(len(r.decisions) for r in served)

    def checks(self, window: List[Tuple[traffic.Backtest, List]],
               rng: np.random.Generator) -> List[Check]:
        """Every decision of every replica of the window."""
        del rng
        out: List[Check] = []
        tables: Dict[bytes, List[Dict]] = {}

        def table(snapshot) -> Tuple[bytes, List[Dict]]:
            spot = np.array([o.spot_price for o in snapshot])
            t3 = np.array([o.t3 for o in snapshot])
            key = spot.tobytes() + t3.tobytes()
            if key not in tables:
                tables[key] = [{f: getattr(o, f) for f in catalog.FIELDS}
                               for o in snapshot]
            return key, tables[key]

        for _req, results in window:
            for r in results:
                # the initial decision sees the catalog itself (state 0);
                # every later one the snapshot of the round it was made in
                snaps = [self.catalog] + [rd.snapshot for rd in r.rounds
                                          if rd.decision is not None]
                if len(snaps) != len(r.decisions):
                    raise RuntimeError("decisions and rounds do not align")
                for snap, (_reason, decision) in zip(snaps, r.decisions):
                    key, offerings = table(snap)
                    out.append(Check(offerings, key,
                                     decision.pool.request.pods,
                                     frozenset(decision.excluded_offerings),
                                     _answer(decision)))
        return out


KINDS = {"tick": TickCell, "backtest": BacktestCell}


@dataclasses.dataclass
class Comparison:
    decisions: int = 0
    decisions_mismatched: int = 0
    probes: int = 0
    probes_mismatched: int = 0


def answers(checks: Sequence[Check], config: Dict, mix: Dict,
            arithmetic: str = "exact") -> List[Answer]:
    """The reference's answer to every checked decision: each distinct
    input solved once, the decisions over one table in lockstep."""
    tables: Dict[object, Sequence[Dict]] = {}
    asks: Dict[object, Dict[tuple, None]] = {}
    for c in checks:
        tables.setdefault(c.table_key, c.offerings)
        asks.setdefault(c.table_key, {})[(c.pods, c.excluded)] = None
    solved: Dict[tuple, Answer] = {}
    for key, offerings in tables.items():
        market = reference.Market(offerings, config["pod_cpu"],
                                  config["pod_mem_gib"], arithmetic)
        todo = list(asks[key])
        for ask, ans in zip(todo, reference.decide_many(
                market, todo, tolerance=mix["tolerance"],
                prescan=mix["prescan"])):
            solved[(key,) + ask] = ans
    return [solved[(c.table_key, c.pods, c.excluded)] for c in checks]


def compare(checks: Sequence[Check], config: Dict, mix: Dict,
            got: Optional[Sequence[Answer]] = None) -> Comparison:
    """Count what differs between the exact reference and ``got``
    (default: the program's answers): decisions (pool and α), and the
    probes of each search (α and E_Total)."""
    out = Comparison()
    for c, ref, ans in zip(checks, answers(checks, config, mix),
                           got or [c.answer for c in checks]):
        out.decisions += 1
        out.decisions_mismatched += ans[:2] != ref[:2]
        out.probes += len(ref[2])
        out.probes_mismatched += (sum(a != b for a, b in zip(ans[2], ref[2]))
                                  + abs(len(ans[2]) - len(ref[2])))
    return out
