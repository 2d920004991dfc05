"""Independent references for the solver and search tests.

* :func:`solve_ilp_reference` — the seed history-matrix ILP solver, kept
  verbatim: a float DP with a full history matrix, sharing none of the
  engine's exact-integer plan, prune or backtracker.
* :func:`solve_ilp_pulp` — the paper's own tool (PuLP/CBC).
* :func:`guarded_gss_reference` — the guarded search (prescan grid, then
  Algorithm 1 inside the best grid cell) restated per α over any
  ``solver(items, req_pods, alpha)`` callable, independent of the
  lockstep batching of :func:`repro.core.gss.bracketed_gss_many`.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import exact
from repro.core.efficiency import CandidateItem, NodePool, e_total
from repro.core.gss import GssTrace, golden_section_search
from repro.core.ilp import (IlpStats, _binary_bundles,
                            objective_coefficients)

_INF = float("inf")


def solve_ilp_reference(items: Sequence[CandidateItem], req_pods: int,
                        alpha: float, return_stats: bool = False,
                        ) -> Optional[List[int]] | Tuple[Optional[List[int]],
                                                         IlpStats]:
    """The seed history-matrix solver, retained verbatim as the baseline for
    cross-validation tests.  Peak memory is O(bundles × residual): the
    ``history`` matrix below is exactly what the production engine
    eliminates."""
    n = len(items)
    counts = [0] * n
    if n == 0:
        result = None if req_pods > 0 else counts
        return (result, IlpStats(0, 0, req_pods, _INF)) if return_stats else result

    coef = objective_coefficients(items, alpha)
    pods = np.array([it.pods for it in items], dtype=np.int64)
    bound = np.array([it.t3 for it in items], dtype=np.int64)

    neg = (coef < 0) & (bound > 0)
    covered = 0
    for i in np.nonzero(neg)[0]:
        counts[i] = int(bound[i])
        covered += int(pods[i] * bound[i])

    residual = max(0, req_pods - covered)
    objective = float(np.sum(coef[neg] * bound[neg]))

    if residual == 0:
        stats = IlpStats(n, 0, 0, objective)
        return (counts, stats) if return_stats else counts

    idx = [i for i in range(n)
           if not neg[i] and bound[i] > 0 and pods[i] > 0]
    if int(np.sum(pods[idx] * bound[idx])) < residual:
        return (None, IlpStats(n, 0, residual, _INF)) if return_stats else None

    bundles: List[Tuple[int, int, float, int]] = []   # (item, pods, cost, copies)
    for i in idx:
        for copies in _binary_bundles(int(bound[i])):
            bundles.append((i, int(pods[i] * copies),
                            float(coef[i] * copies), copies))

    R = residual
    dp = np.full(R + 1, _INF)
    dp[0] = 0.0
    history = np.empty((len(bundles) + 1, R + 1))
    history[0] = dp
    for b, (_, pb, cb, _) in enumerate(bundles):
        shifted = np.empty(R + 1)
        cut = min(pb, R + 1)
        shifted[:cut] = dp[0]
        if cut <= R:
            shifted[cut:] = dp[: R + 1 - pb]
        dp = np.minimum(dp, shifted + cb)
        history[b + 1] = dp

    if not np.isfinite(dp[R]):
        return (None, IlpStats(n, len(bundles), residual, _INF)) if return_stats else None

    j = R
    for b in range(len(bundles) - 1, -1, -1):
        if j == 0:
            break
        if history[b + 1][j] < history[b][j] - 1e-12:
            i, pb, _, copies = bundles[b]
            counts[i] += copies
            j = max(0, j - pb)
    objective += float(dp[R])

    stats = IlpStats(n, len(bundles), residual, objective)
    return (counts, stats) if return_stats else counts


def solve_ilp_pulp(items: Sequence[CandidateItem], req_pods: int,
                   alpha: float) -> Optional[List[int]]:
    """Reference backend using PuLP/CBC (the paper's implementation, §4)."""
    import pulp

    coef = objective_coefficients(items, alpha)
    prob = pulp.LpProblem("kubepacs_node_selection", pulp.LpMinimize)
    xs = [pulp.LpVariable(f"x_{i}", lowBound=0, upBound=int(it.t3),
                          cat="Integer") for i, it in enumerate(items)]
    prob += pulp.lpSum(float(coef[i]) * xs[i] for i in range(len(items)))
    prob += pulp.lpSum(int(it.pods) * xs[i]
                       for i, it in enumerate(items)) >= int(req_pods)
    status = prob.solve(pulp.PULP_CBC_CMD(msg=False))
    if pulp.LpStatus[status] != "Optimal":
        return None
    return [int(round(x.value() or 0)) for x in xs]


def guarded_gss_reference(
    items: Sequence[CandidateItem],
    req_pods: int,
    tolerance: float,
    prescan: int,
    solver: Callable[[Sequence[CandidateItem], int, float],
                     Optional[List[int]]],
) -> Tuple[Optional[NodePool], GssTrace]:
    """The guarded GSS one α at a time: scan ``prescan`` equispaced grid α,
    run :func:`golden_section_search` inside the grid cell bracketing the
    best scan point, merge the two traces and return the global argmax."""
    grid = [exact.k_alpha(k) for k in exact.alpha_grid(prescan)]
    scan_trace = GssTrace()
    scores, pools = [], []
    for alpha in grid:
        counts = solver(items, req_pods, alpha)
        scan_trace.ilp_solves += 1
        if counts is None:
            scores.append(float("-inf"))
            pools.append(None)
        else:
            pool = NodePool(items=list(items), counts=counts, alpha=alpha)
            scores.append(e_total(pool, req_pods))
            pools.append(pool)

    best_pool, best_f, best_idx = None, float("-inf"), 0
    for gi, (alpha, score, pool) in enumerate(zip(grid, scores, pools)):
        scan_trace.alphas.append(alpha)
        scan_trace.e_totals.append(max(score, 0.0))
        if score > best_f:
            best_pool, best_f, best_idx = pool, score, gi

    lo = grid[max(0, best_idx - 1)]
    hi = grid[min(len(grid) - 1, best_idx + 1)]
    pool, trace = golden_section_search(items, req_pods, tolerance=tolerance,
                                        alpha_lo=lo, alpha_hi=hi,
                                        solver=solver)
    trace.alphas = scan_trace.alphas + trace.alphas
    trace.e_totals = scan_trace.e_totals + trace.e_totals
    trace.ilp_solves += scan_trace.ilp_solves
    inner_f = e_total(pool, req_pods) if pool is not None else float("-inf")
    if best_pool is not None and best_f > inner_f:
        return best_pool.nonzero(), trace
    return pool, trace
