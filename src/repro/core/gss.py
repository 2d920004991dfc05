"""Golden Section Search over the cost/performance weight α (paper §3.2, Alg. 1).

GSS maximizes E_Total(α) = E_PerfCost × E_OverPods of the ILP solution at α
over α ∈ [0, 1], shrinking the bracket by φ = (√5−1)/2 ≈ 0.618 per step and
reusing one interior evaluation per iteration (one ILP solve per iteration
after the two initial solves; ≈ 5n+1 iterations for tolerance ε = 10⁻ⁿ,
Eq. 6–7).  Brackets and probes live on the exact dyadic α grid of
:mod:`repro.core.exact` (integer golden update), so the device plane
reproduces every probe bit for bit.  The best pool over *all* evaluated α
is returned (Alg. 1's S*), which also guards against mild
non-unimodality of the empirical E_Total(α).

Engine wiring (DESIGN.md §8 + §12): ``bracketed_gss`` is the one-decision
case of :func:`bracketed_gss_many`,
the *cross-decision batched* search: D decisions (each with its own
demand and §4.1 exclusion mask) advance their prescans and golden-section
brackets in lockstep, and every round's pending α probes across all
decisions go to :func:`~repro.core.ilp.solve_ilp_many` as one stacked
engine invocation (one backend dispatch).  Each decision's (α, E_Total)
evaluation sequence — and therefore its selected pool and trace — is
exactly the sequential algorithm's; batching changes execution, never
content.  :func:`golden_section_search` keeps a ``solver`` channel (float
α, the seed calling convention) for the unguarded policy and for tests.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from . import events_log, exact
from .backend import CoarseningConfig, SolverBackend
from .efficiency import (CandidateItem, NodePool, e_total, nonzero_pool,
                         score_counts_batch, score_counts_many)
from .ilp import (CompiledMarket, compile_market, solve_ilp, solve_ilp_many)

PHI = (math.sqrt(5.0) - 1.0) / 2.0     # ≈ 0.618


@dataclasses.dataclass
class GssTrace:
    """Every (α, E_Total) the search evaluated — Fig. 6's black lines.

    ``wall_seconds`` is the search's host wall time.  Under a batched
    search (:func:`bracketed_gss_many`, a ``SolveBatch``) every decision
    of the batch is stamped with the whole batch's wall."""

    alphas: List[float] = dataclasses.field(default_factory=list)
    e_totals: List[float] = dataclasses.field(default_factory=list)
    ilp_solves: int = 0
    wall_seconds: float = 0.0


@functools.lru_cache(maxsize=256)
def expected_iterations(tolerance: float, a: float = 0.0, b: float = 1.0) -> int:
    """Eq. 6: k−1 ≥ ⌈log(ε/(b−a)) / log φ⌉  (≈ 4.784·n for ε=10⁻ⁿ).

    Cached: the (tolerance, bracket) universe of a run is tiny and callers
    historically re-derived it per provisioning cycle.
    """
    return int(math.ceil(math.log(tolerance / (b - a)) / math.log(PHI))) + 1


def _make_evaluator(items: Sequence[CandidateItem], req_pods: int,
                    solver: Callable, market: Optional[CompiledMarket],
                    exclude: Optional[np.ndarray], trace: GssTrace,
                    cache: dict,
                    backend: Optional[SolverBackend] = None,
                    coarsening: Optional[CoarseningConfig] = None,
                    ) -> Callable:
    """One (grid index → (pool, E_Total)) evaluator of the pure search.

    The engine path solves against the compiled market; a custom
    ``solver`` keeps the seed calling convention (float α) for tests and
    alternative backends.
    """
    use_engine = solver is solve_ilp
    if not use_engine and exclude is not None:
        raise ValueError("exclude masks require the default solve_ilp solver "
                         "(custom solvers have no exclusion channel)")
    if use_engine and market is None:
        market = compile_market(items)

    def evaluate(k: int) -> Tuple[Optional[NodePool], float]:
        if k in cache:
            return cache[k]
        alpha = exact.k_alpha(k)
        if use_engine:
            counts = solve_ilp(items, req_pods, alpha, market=market,
                               exclude=exclude, backend=backend,
                               coarsening=coarsening)
        else:
            counts = solver(items, req_pods, alpha)
        trace.ilp_solves += 1
        if counts is None:
            pool, score = None, float("-inf")
        else:
            pool = NodePool(items=list(items), counts=counts, alpha=alpha)
            score = e_total(pool, req_pods)
        trace.alphas.append(alpha)
        trace.e_totals.append(score if score != float("-inf") else 0.0)
        cache[k] = (pool, score)
        return pool, score

    return evaluate


def golden_section_search(
    items: Sequence[CandidateItem],
    req_pods: int,
    tolerance: float = 0.01,
    alpha_lo: float = 0.0,
    alpha_hi: float = 1.0,
    solver: Callable[[Sequence[CandidateItem], int, float], Optional[List[int]]] = solve_ilp,
    market: Optional[CompiledMarket] = None,
    exclude: Optional[np.ndarray] = None,
    timer: Callable[[], float] = time.perf_counter,
    backend: Optional[SolverBackend] = None,
    coarsening: Optional[CoarseningConfig] = None,
) -> Tuple[Optional[NodePool], GssTrace]:
    """Algorithm 1 (lines 7–27).  Returns (best pool S*, evaluation trace).

    ``timer`` stamps ``GssTrace.wall_seconds``; inject a fake for tests that
    assert full decision equality (wall time is diagnostic, never decision
    content)."""
    trace = GssTrace()
    t0 = timer()
    cache: dict[int, Tuple[Optional[NodePool], float]] = {}
    evaluate = _make_evaluator(items, req_pods, solver, market, exclude,
                               trace, cache, backend, coarsening)

    a, b = exact.alpha_k(alpha_lo), exact.alpha_k(alpha_hi)
    tol = exact.tolerance_k(tolerance)
    w = exact.golden_width(b - a)
    x1, x2 = b - w, a + w
    pool1, f1 = evaluate(x1)
    pool2, f2 = evaluate(x2)
    best_pool, best_f = (pool1, f1) if f1 >= f2 else (pool2, f2)

    while (b - a) > tol:
        if f1 >= f2:
            b = x2
            x2, f2, pool2 = x1, f1, pool1
            x1 = b - exact.golden_width(b - a)
            pool1, f1 = evaluate(x1)
            if f1 > best_f:
                best_pool, best_f = pool1, f1
        else:
            a = x1
            x1, f1, pool1 = x2, f2, pool2
            x2 = a + exact.golden_width(b - a)
            pool2, f2 = evaluate(x2)
            if f2 > best_f:
                best_pool, best_f = pool2, f2

    trace.wall_seconds = timer() - t0
    if best_pool is not None:
        best_pool = best_pool.nonzero()
    return best_pool, trace


def bracketed_gss(
    items: Sequence[CandidateItem],
    req_pods: int,
    tolerance: float = 0.01,
    prescan: int = 9,
    market: Optional[CompiledMarket] = None,
    exclude: Optional[np.ndarray] = None,
    timer: Callable[[], float] = time.perf_counter,
    backend: Optional[SolverBackend] = None,
    coarsening: Optional[CoarseningConfig] = None,
) -> Tuple[Optional[NodePool], GssTrace]:
    """Guarded GSS (beyond-paper robustness hardening, DESIGN.md §7).

    The paper's Fig. 6 landscapes are empirically unimodal; a synthetic or
    adversarial market can produce secondary bumps that trap pure GSS in the
    wrong bracket.  We first scan ``prescan`` equispaced α (one batched
    engine invocation), then run Algorithm 1 inside the grid cell
    bracketing the best scan point.  Degrades gracefully to pure GSS
    quality on unimodal landscapes; strictly better on bumpy ones.

    This *is* :func:`bracketed_gss_many` at ``D = 1`` — one
    implementation, so the batched tick phase of the fleet engine and the
    sequential path can never diverge (DESIGN.md §12).
    """
    return bracketed_gss_many(
        items, [req_pods], tolerance=tolerance, prescan=prescan,
        market=market, excludes=[exclude], timer=timer,
        backend=backend, coarsening=coarsening)[0]


class _GssState:
    """One decision's sequential-GSS state, advanced in lockstep."""

    __slots__ = ("req", "exclude", "idx", "t0", "scan_trace", "trace",
                 "cache", "scan_pool", "scan_f", "a", "b", "x1", "x2",
                 "f1", "f2", "pool1", "pool2", "best_pool", "best_f",
                 "done")

    def __init__(self, req: int, exclude: Optional[np.ndarray]):
        self.req = req
        self.exclude = exclude
        self.scan_trace = GssTrace()
        self.trace = GssTrace()
        self.cache: dict = {}
        self.scan_pool: Optional[NodePool] = None
        self.scan_f = float("-inf")
        self.best_pool: Optional[NodePool] = None
        self.best_f = float("-inf")
        self.done = False


def bracketed_gss_many(
    items: Sequence[CandidateItem],
    req_pods_list: Sequence[int],
    tolerance: float = 0.01,
    prescan: int = 9,
    market: Optional[CompiledMarket] = None,
    excludes: Optional[Sequence[Optional[np.ndarray]]] = None,
    timer: Callable[[], float] = time.perf_counter,
    backend: Optional[SolverBackend] = None,
    coarsening: Optional[CoarseningConfig] = None,
) -> List[Tuple[Optional[NodePool], GssTrace]]:
    """Cross-decision batched guarded GSS (DESIGN.md §12).

    Runs D guarded searches — one per (demand, exclusion mask) — in
    lockstep: the D prescans form one :func:`solve_ilp_many` invocation,
    and every golden-section round batches the decisions' pending α probes
    into the next one.  Per decision, the evaluation order, cache
    behaviour, trace content, and returned pool are *exactly* those of the
    sequential :func:`bracketed_gss`; only the dispatch granularity
    changes.  Scoring deliberately runs per decision with the same array
    shapes as the sequential path (``score_counts_batch`` over that
    decision's grid, scalar ``e_total`` per golden probe) so every float
    matches bit-for-bit.  The fused record's counts are arrays, and its
    pools hold only their non-zero entries (:func:`nonzero_pool`, which
    scores as the whole-market pool); the host engine's lists make
    whole-market pools.  Of the prescan grid only the best point's pool
    is built.
    """
    with events_log.span("kubepacs.gss"):
        n_dec = len(req_pods_list)
        if excludes is None:
            excludes = [None] * n_dec
        if len(excludes) != n_dec:
            raise ValueError("excludes must match len(req_pods_list)")
        kgrid = exact.alpha_grid(prescan)
        grid = [exact.k_alpha(k) for k in kgrid]
        tol = exact.tolerance_k(tolerance)
        if market is None:
            market = compile_market(items)

        states = [_GssState(req, ex)
                  for req, ex in zip(req_pods_list, excludes)]
        for i, st in enumerate(states):
            st.idx = i
            st.t0 = timer()

        # -- fused device plane (DESIGN.md §13): backends that support it run
        # the whole batch (prescan grid + speculative golden rounds) on device
        # and hand back a replay record; the lockstep loop below then consumes
        # recorded counts instead of dispatching per round.  Control flow,
        # scoring, traces, and selections are the sequential path's either way.
        record = None
        if backend is not None and getattr(backend, "supports_fused_gss",
                                           False):
            record = backend.fused_gss_record(items, market,
                                              list(req_pods_list),
                                              list(excludes), kgrid,
                                              tolerance,
                                              coarsening=coarsening)

        # -- prescan: one stacked engine invocation over every (decision, α).
        # The record's counts are the readback's arrays: its pools keep only
        # the few non-zero entries (DESIGN.md §13)
        if record is not None:
            all_counts = record.prescan
            make_pool = functools.partial(nonzero_pool, items)
        else:
            all_counts = solve_ilp_many(items, list(req_pods_list), grid,
                                        market=market, excludes=list(excludes),
                                        backend=backend, coarsening=coarsening)

            def make_pool(counts, alpha=None):
                return NodePool(items=list(items), counts=counts,
                                alpha=alpha)
        all_scores = score_counts_many(items, all_counts, list(req_pods_list),
                                       none_score=float("-inf"),
                                       arrays=market.metric_arrays)
        for st, counts_d, scores in zip(states, all_counts, all_scores):
            st.scan_trace.ilp_solves += len(grid)
            best_idx = 0
            for gi, (alpha, score) in enumerate(zip(grid, scores)):
                st.scan_trace.alphas.append(alpha)
                st.scan_trace.e_totals.append(max(score, 0.0))
                if score > st.scan_f:
                    st.scan_f, best_idx = score, gi
            if st.scan_f > float("-inf"):      # infeasible rows score -inf
                st.scan_pool = make_pool(counts_d[best_idx],
                                         alpha=grid[best_idx])
            st.a = kgrid[max(0, best_idx - 1)]
            st.b = kgrid[min(len(kgrid) - 1, best_idx + 1)]
            w = exact.golden_width(st.b - st.a)
            st.x1 = st.b - w
            st.x2 = st.a + w

        if record is not None:
            # speculative device golden rounds over the chosen brackets; the
            # probe α sequence is re-derived exactly below, so every cache
            # miss resolves from the record (host solve only on divergence)
            record.run_golden([st.a for st in states], [st.b for st in states])

        # -- lockstep golden-section refinement ----------------------------
        def eval_round(requests: List[Tuple[_GssState, List[int]]]) -> None:
            """Evaluate each state's pending grid-index list with
            sequential-evaluate semantics (cache first, one engine row per
            miss, per-state append order), batching all misses into one
            solve_ilp_many call."""
            miss_states: List[_GssState] = []
            miss_reqs: List[int] = []
            miss_ks: List[List[int]] = []
            miss_excludes: List[Optional[np.ndarray]] = []
            for st, klist in requests:
                pending: List[int] = []
                for k in klist:
                    if k not in st.cache and k not in pending:
                        pending.append(k)
                if pending:
                    miss_states.append(st)
                    miss_reqs.append(st.req)
                    miss_ks.append(pending)
                    miss_excludes.append(st.exclude)
            if not miss_states:
                return
            if record is not None:
                solved = record.solve_many([st.idx for st in miss_states],
                                           miss_ks)
            else:
                solved = solve_ilp_many(
                    items, miss_reqs,
                    [[exact.k_alpha(k) for k in ks] for ks in miss_ks],
                    market=market, excludes=miss_excludes, backend=backend,
                    coarsening=coarsening)
            for st, ks_d, counts_d in zip(miss_states, miss_ks, solved):
                for k, counts in zip(ks_d, counts_d):
                    alpha = exact.k_alpha(k)
                    st.trace.ilp_solves += 1
                    if counts is None:
                        pool, score = None, float("-inf")
                    else:
                        pool = make_pool(counts, alpha=alpha)
                        score = e_total(pool, st.req)
                    st.trace.alphas.append(alpha)
                    st.trace.e_totals.append(
                        score if score != float("-inf") else 0.0)
                    st.cache[k] = (pool, score)

        eval_round([(st, [st.x1, st.x2]) for st in states])
        for st in states:
            st.pool1, st.f1 = st.cache[st.x1]
            st.pool2, st.f2 = st.cache[st.x2]
            if st.f1 >= st.f2:
                st.best_pool, st.best_f = st.pool1, st.f1
            else:
                st.best_pool, st.best_f = st.pool2, st.f2

        while True:
            active = [st for st in states
                      if not st.done and (st.b - st.a) > tol]
            for st in states:
                if not st.done and (st.b - st.a) <= tol:
                    st.done = True
            if not active:
                break
            probes: List[Tuple[_GssState, List[int]]] = []
            for st in active:
                if st.f1 >= st.f2:
                    st.b = st.x2
                    st.x2, st.f2, st.pool2 = st.x1, st.f1, st.pool1
                    st.x1 = st.b - exact.golden_width(st.b - st.a)
                    probes.append((st, [st.x1]))
                else:
                    st.a = st.x1
                    st.x1, st.f1, st.pool1 = st.x2, st.f2, st.pool2
                    st.x2 = st.a + exact.golden_width(st.b - st.a)
                    probes.append((st, [st.x2]))
            eval_round(probes)
            for st, klist in probes:
                pool, f = st.cache[klist[0]]
                if klist[0] == st.x1:
                    st.pool1, st.f1 = pool, f
                    if f > st.best_f:
                        st.best_pool, st.best_f = pool, f
                else:
                    st.pool2, st.f2 = pool, f
                    if f > st.best_f:
                        st.best_pool, st.best_f = pool, f

        # -- per-decision finish: exactly the sequential epilogue ----------
        out: List[Tuple[Optional[NodePool], GssTrace]] = []
        for st in states:
            inner_pool = st.best_pool
            if inner_pool is not None:
                inner_pool = inner_pool.nonzero()
            trace = st.trace
            trace.alphas = st.scan_trace.alphas + trace.alphas
            trace.e_totals = st.scan_trace.e_totals + trace.e_totals
            trace.ilp_solves += st.scan_trace.ilp_solves
            trace.wall_seconds = timer() - st.t0
            inner_f = (e_total(inner_pool, st.req)
                       if inner_pool is not None else float("-inf"))
            if st.scan_pool is not None and st.scan_f > inner_f:
                out.append((st.scan_pool.nonzero(), trace))
            else:
                out.append((inner_pool, trace))
        return out
