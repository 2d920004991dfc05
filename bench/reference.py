"""Plain reference of a KubePACS provisioning decision, independent of the
program: exact-integer guarded golden-section search over α around an
exact bounded-knapsack cover DP.

Restated from the paper (§3, Eq. 1–5, Alg. 1) and from the exact
arithmetic of ``src/repro/core/{exact,ilp,gss,efficiency}.py`` as of the
commit that added this benchmark.  It imports nothing of ``repro``, so a
later change to the engine cannot move it.  What it keeps of the engine is
what defines the answer, not how the engine computes it:

* α on the dyadic grid ``K / 2**40``; the golden update
  ``w = floor(PHI_Q·(b−a) / 2**22)``; the 9-point prescan grid, then
  golden search inside the grid cell around the best prescan point;
* objective coefficients ``C_i(K) = Q_i − floor(K·W_i / 2**40)`` with
  ``Q_i = rint(SP_i/SP_min · 2**F)``, ``W_i = rint(Perf_i/Perf_min · 2**F)
  + Q_i`` over the candidates that survive the §4.1 exclusion;
* items with a negative coefficient are taken at their T3 bound; the rest
  of the demand is covered by the minimum-cost subset of binary bundles
  (1, 2, 4, … copies of an item), and ties are broken by the improvement-
  bit backtrack: walking bundles last to first, bundle ``b`` is taken iff
  it strictly lowered the DP value at the remaining coverage;
* E_Total (Eq. 3) in float64, summed in the engine's order, since the
  search compares these floats.

What it leaves out of the engine: LP-bound pruning, the core DP, demand
coarsening and the device.  The DP runs over every bundle.  A pruned
bundle lies in no optimal solution, and removing such bundles changes no
improvement bit on the backtrack's path, so the selection is the same.
Many decisions are searched in lockstep (:func:`decide_many`), each still
the plain sequential search; a DP row depends only on α and the
exclusion, so the searches that need the same row share it.

A :class:`Market` built with ``arithmetic="int32"`` runs the same search
one precision step down in the cost arithmetic, the part the device
computes: the coefficients at the same fraction bits, cast to int32
(wrapping, as a device's int32 does), and bundle costs and DP sums in
int32.  E_Total stays float64, as the host scores it.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

ALPHA_BITS = 40
ALPHA_ONE = 1 << ALPHA_BITS
PHI_BITS = 22
PHI_Q = int(((math.sqrt(5.0) - 1.0) / 2.0) * (1 << PHI_BITS))
INF = 1 << 62
MIN_SCALE_BITS = 16
#: DP rows solved together: bounds the improvement bits kept at once
ROWS_PER_COVER = 8
#: cost dtype of each arithmetic: "exact" is the configuration's (int64),
#: "int32" the control's, one step down
ARITHMETIC = {"exact": np.int64, "int32": np.int32}


def golden_width(d: int) -> int:
    return (PHI_Q * d) >> PHI_BITS


def alpha_grid(points: int) -> List[int]:
    return [(i * ALPHA_ONE) // (points - 1) for i in range(points)]


def tolerance_k(tolerance: float) -> int:
    return min(int(math.floor(float(tolerance) * ALPHA_ONE)), INF)


class Market:
    """The candidate set of one request shape over one offering table
    (Alg. 1 lines 3–6): offerings with a live price and capacity that fit
    at least one pod, with ``Pod_i`` (Eq. 1) and ``Perf_i = BS_i·Pod_i``."""

    def __init__(self, offerings: Sequence[Dict], cpu_per_pod: float,
                 mem_per_pod: float, arithmetic: str = "exact"):
        if arithmetic not in ARITHMETIC:
            raise ValueError(f"arithmetic must be one of {tuple(ARITHMETIC)}")
        items = []
        for o in offerings:
            if o["spot_price"] <= 0 or o["t3"] <= 0:
                continue
            pods = int(min(o["vcpus"] // cpu_per_pod,
                           o["mem_gib"] // mem_per_pod))
            if pods >= 1:
                items.append((o, pods))
        self.ids = [o["offering_id"] for o, _ in items]
        self.pods = np.array([p for _, p in items], dtype=np.int64)
        self.bound = np.array([o["t3"] for o, _ in items], dtype=np.int64)
        # python floats, as the engine's scalar scorer sums them
        self.perf_f = [float(o["bs_core"]) * p for o, p in items]
        self.price_f = [float(o["spot_price"]) for o, _ in items]
        self.perf = np.array(self.perf_f, dtype=np.float64)
        self.price = np.array(self.price_f, dtype=np.float64)
        b_item, b_copies = [], []
        for i, bound in enumerate(self.bound.tolist()):
            k = 1
            while bound > 0:
                take = min(k, bound)
                b_item.append(i)
                b_copies.append(take)
                bound -= take
                k <<= 1
        self.b_item = np.array(b_item, dtype=np.int64)
        self.b_copies = np.array(b_copies, dtype=np.int64)
        self.b_pods = self.pods[self.b_item] * self.b_copies
        self.bits = self._scale_bits()
        self.cost_dtype = ARITHMETIC[arithmetic]

    def _scale_bits(self) -> int:
        """Fraction bits ``F`` of the quantized objective: ``W < 2**42``
        and every cost sum ``< 2**60``."""
        n = len(self.ids)
        if n == 0:
            return MIN_SCALE_BITS
        perf_min = float(self.perf[self.perf > 0].min())
        norms = np.concatenate([self.perf / perf_min,
                                self.price / float(self.price.min()), [1.0]])
        mbits = math.frexp(max(float(np.max(norms[np.isfinite(norms)])),
                               1.0))[1]
        tbits = int(np.sum(self.bound)).bit_length()
        f = min(41, 60 - tbits) - mbits
        if f < MIN_SCALE_BITS:
            raise ValueError(f"objective too wide: {f} fraction bits")
        return f

    def inputs(self, exclude: Optional[np.ndarray]) -> Tuple[List[int],
                                                             List[int],
                                                             np.ndarray]:
        """``(W, Q, active)`` of one exclusion mask: the normalising minima
        are taken over the surviving candidates."""
        if exclude is None or not np.any(exclude):
            keep = np.ones(len(self.ids), dtype=bool)
        else:
            keep = ~exclude
        perf_pos = self.perf[keep & (self.perf > 0)]
        perf_min = float(perf_pos.min()) if perf_pos.size else 1.0
        prices = self.price[keep]
        sp_min = float(prices.min()) if prices.size else 1.0
        pn, qn = self.perf / perf_min, self.price / sp_min
        active = keep & np.isfinite(pn) & np.isfinite(qn)
        scale = float(1 << self.bits)
        q = np.rint(np.where(active, qn, 0.0) * scale).astype(np.int64)
        w = np.rint(np.where(active, pn, 0.0) * scale).astype(np.int64) + q
        return [int(v) for v in w], [int(v) for v in q], active


def _cover(costs: np.ndarray, avail: np.ndarray, bpods: np.ndarray,
           targets: Sequence[Sequence[int]]) -> List[List[np.ndarray]]:
    """Exact minimum-cost covering, one DP row per (costs, avail):
    ``dp[j]`` = least cost of a bundle subset with at least ``j`` pods.
    Returns, for each row and each of its targets, the taken-bundle mask
    of the improvement-bit backtrack."""
    rows, nb = costs.shape
    width = max(max(t) for t in targets) + 1
    inf = INF if costs.dtype == np.int64 else 1 << 30
    dp = np.full((rows, width), inf, dtype=costs.dtype)
    dp[:, 0] = 0
    bits = np.zeros((nb, rows, width), dtype=bool)
    cand = np.empty_like(dp)
    cand[:, 0] = inf
    every = avail.all(axis=0)
    for b in np.flatnonzero(avail.any(axis=0)).tolist():
        p = int(bpods[b])
        c = costs[:, b:b + 1]
        cut = min(p, width)
        cand[:, 1:cut] = c
        if cut < width:
            np.add(dp[:, :width - p], c, out=cand[:, cut:])
        if not every[b]:
            cand[~avail[:, b]] = inf
            cand[:, 0] = inf
        np.less(cand, dp, out=bits[b])
        np.minimum(dp, cand, out=dp)
    takes = []
    for r, row_targets in enumerate(targets):
        row_takes = []
        for target in row_targets:
            take = np.zeros(nb, dtype=bool)
            j = target
            for b in range(nb - 1, -1, -1):
                if j == 0:
                    break
                if bits[b, r, j]:
                    take[b] = True
                    j = max(0, j - int(bpods[b]))
            row_takes.append(take)
        takes.append(row_takes)
    return takes


def solve(market: Market, asks: Sequence[Tuple[int, int]],
          exclude: Optional[np.ndarray]) -> List[Optional[List[int]]]:
    """Counts per candidate for each ``(demand, k)`` of ``asks`` at α =
    ``k / 2**40`` (None where the demand exceeds the bounded capacity).
    Asks at one ``k`` share one DP row, whatever their demands: the row's
    value at ``j`` pods does not depend on the width beyond ``j``."""
    w, q, active = market.inputs(exclude)
    out: List[Optional[List[int]]] = [None] * len(asks)
    rows: Dict[int, list] = {}
    for i, (req, k) in enumerate(asks):
        if k not in rows:
            coef = np.array([qi - (k * wi >> ALPHA_BITS)
                             for wi, qi in zip(w, q)],
                            dtype=np.int64).astype(market.cost_dtype)
            neg = (coef < 0) & active
            in_dp = active & ~neg
            rows[k] = [np.where(neg, market.bound, 0), coef, in_dp,
                       int(np.sum(market.pods[neg] * market.bound[neg])),
                       int(np.sum(market.pods[in_dp] * market.bound[in_dp])),
                       []]
        counts, _, _, covered, capacity, wants = rows[k]
        residual = max(0, req - covered)
        if residual == 0:
            out[i] = counts.tolist()
        elif capacity >= residual:
            wants.append((i, residual))
    dp_rows = [r for r in rows.values() if r[5]]
    copies = market.b_copies.astype(market.cost_dtype)
    for lo in range(0, len(dp_rows), ROWS_PER_COVER):
        chunk = dp_rows[lo:lo + ROWS_PER_COVER]
        costs = np.stack([coef[market.b_item] * copies
                          for _, coef, *_ in chunk])
        avail = np.stack([in_dp[market.b_item] for _, _, in_dp, *_ in chunk])
        takes = _cover(costs, avail, market.b_pods,
                       [[res for _, res in r[5]] for r in chunk])
        for (counts, *_, wants), row_takes in zip(chunk, takes):
            for (i, _), take in zip(wants, row_takes):
                got = counts.copy()
                np.add.at(got, market.b_item[take], market.b_copies[take])
                out[i] = got.tolist()
    return out


def e_total(market: Market, counts: List[int], req: int) -> float:
    """Eq. 3 of one pool, summed as the engine's scalar scorer sums it."""
    total_pods = sum(int(p) * c for p, c in zip(market.pods.tolist(), counts))
    if total_pods < req:
        return 0.0
    # Python's own sum of floats (compensated since 3.12), as the engine
    # sums
    perf = sum(pf * c for pf, c in zip(market.perf_f, counts) if c > 0)
    cost = sum(pr * c for pr, c in zip(market.price_f, counts) if c > 0)
    if cost <= 0:
        return 0.0
    return (perf / cost) * (req / total_pods)


def e_total_rows(market: Market, rows: List[List[int]], req: int,
                 ) -> np.ndarray:
    """Eq. 3 of a stack of pools, as the engine's batched scorer computes
    it (one matrix-vector product per sum)."""
    counts = np.array(rows, dtype=np.float64)
    perf_sum = counts @ market.perf
    cost_sum = counts @ market.price
    pods_sum = counts @ market.pods.astype(np.float64)
    with np.errstate(divide="ignore", invalid="ignore"):
        score = (perf_sum / cost_sum) * (req / pods_sum)
    score[(pods_sum < req) | (cost_sum <= 0) | (pods_sum <= 0)] = 0.0
    return score


def _search(market: Market, req: int, tolerance: float, prescan: int):
    """The guarded golden-section search of one decision, as a generator:
    it yields the list of α grid indices it needs next, is sent their
    counts, and returns ``({offering_id: nodes}, α, probes)``."""
    kgrid = alpha_grid(prescan)
    scan = yield kgrid
    feasible = [c for c in scan if c is not None]
    scores = iter(e_total_rows(market, feasible, req).tolist()
                  if feasible else [])
    scan_pool, scan_f, scan_k, best_idx = None, float("-inf"), None, 0
    probes: List[Tuple[float, float]] = []
    for gi, counts in enumerate(scan):
        score = float("-inf") if counts is None else next(scores)
        probes.append((kgrid[gi] / ALPHA_ONE, max(score, 0.0)))
        if score > scan_f:
            scan_pool, scan_f, scan_k, best_idx = counts, score, kgrid[gi], gi
    a = kgrid[max(0, best_idx - 1)]
    b = kgrid[min(len(kgrid) - 1, best_idx + 1)]
    tol = tolerance_k(tolerance)
    cache: Dict[int, Tuple[Optional[List[int]], float]] = {}

    def store(k: int, counts: Optional[List[int]]) -> None:
        cache[k] = ((counts, float("-inf")) if counts is None
                    else (counts, e_total(market, counts, req)))
        probes.append((k / ALPHA_ONE, max(cache[k][1], 0.0)))

    w = golden_width(b - a)
    x1, x2 = b - w, a + w
    first = list(dict.fromkeys((x1, x2)))
    for k, counts in zip(first, (yield first)):
        store(k, counts)
    (p1, f1), (p2, f2) = cache[x1], cache[x2]
    best, best_f, best_k = (p1, f1, x1) if f1 >= f2 else (p2, f2, x2)
    while (b - a) > tol:
        if f1 >= f2:
            b = x2
            x2, f2, p2 = x1, f1, p1
            x1 = b - golden_width(b - a)
            if x1 not in cache:
                store(x1, (yield [x1])[0])
            p1, f1 = cache[x1]
            if f1 > best_f:
                best, best_f, best_k = p1, f1, x1
        else:
            a = x1
            x1, f1, p1 = x2, f2, p2
            x2 = a + golden_width(b - a)
            if x2 not in cache:
                store(x2, (yield [x2])[0])
            p2, f2 = cache[x2]
            if f2 > best_f:
                best, best_f, best_k = p2, f2, x2
    inner_f = e_total(market, best, req) if best is not None else float("-inf")
    if scan_pool is not None and scan_f > inner_f:
        best, best_k = scan_pool, scan_k
    if best is None:
        return {}, None, probes
    return ({i: c for i, c in zip(market.ids, best) if c > 0},
            best_k / ALPHA_ONE, probes)


def decide_many(market: Market, asks: Sequence[Tuple[int, Set[str]]],
                tolerance: float = 0.01, prescan: int = 9,
                ) -> List[Tuple[Dict[str, int], Optional[float],
                                List[Tuple[float, float]]]]:
    """The decision for each ``(demand, excluded offering ids)`` of
    ``asks``: ``({offering_id: nodes}, α, probes)``, where ``probes`` lists
    every (α, E_Total) the search evaluated, in order, infeasible pools
    scoring 0; an infeasible demand gives ``({}, None, probes)``.  The
    searches run in lockstep, so that the DP rows searches of one exclusion
    need at one α are solved once."""
    masks: Dict[frozenset, Optional[np.ndarray]] = {}
    searches = []
    for req, excluded in asks:
        key = frozenset(excluded)
        if key not in masks:
            masks[key] = (np.array([i in key for i in market.ids],
                                   dtype=bool) if key else None)
        searches.append((key, req, _search(market, req, tolerance, prescan)))
    out: List = [None] * len(asks)
    wants = {i: next(s) for i, (_, _, s) in enumerate(searches)}
    while wants:
        by_mask: Dict[frozenset, List[int]] = {}
        for i in wants:
            by_mask.setdefault(searches[i][0], []).append(i)
        got: Dict[int, List] = {}
        for key, idx in by_mask.items():
            counts = solve(market, [(searches[i][1], k) for i in idx
                                    for k in wants[i]], masks[key])
            for i in idx:
                got[i], counts = counts[:len(wants[i])], counts[len(wants[i]):]
        wants = {}
        for i, counts in got.items():
            try:
                wants[i] = searches[i][2].send(counts)
            except StopIteration as stop:
                out[i] = stop.value
    return out


def decide(market: Market, req: int, excluded: Set[str] = frozenset(),
           tolerance: float = 0.01, prescan: int = 9,
           ) -> Tuple[Dict[str, int], Optional[float], List[Tuple[float,
                                                                  float]]]:
    """One provisioning decision (see :func:`decide_many`)."""
    return decide_many(market, [(req, excluded)], tolerance, prescan)[0]
