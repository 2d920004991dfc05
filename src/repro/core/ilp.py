"""The ILP node-selection solver (paper §3.1, Eq. 4–5) — batched engine.

    minimize   Σ_i ( -α·Perf_i/Perf_min + (1-α)·SP_i/SP_min ) · x_i
    subject to Σ_i Pod_i·x_i ≥ Req_pod,   0 ≤ x_i ≤ T3_i,   x_i ∈ ℤ

One exact engine behind three entry points (DESIGN.md §8 + §12):

* :func:`solve_ilp` — single (α, demand) solve.  Items with negative
  objective coefficient are saturated at their T3 bound (any ILP optimum
  does this; it is exactly the high-α over-provisioning collapse of
  Table 2); the residual min-cost covering problem over non-negative items
  is a bounded knapsack solved exactly by LP-bound bundle pruning plus one
  forward min-plus value pass that emits *improvement bits*, from which
  the optimal counts are reconstructed in O(bundles) — the value pass runs
  on a pluggable :mod:`repro.core.backend` (numpy or JAX-jitted).
* :func:`solve_ilp_batch` — all α of a GSS prescan grid for one demand.
* :func:`solve_ilp_many` — the cross-decision batch: every pending
  decision of a FleetSim tick (each with its own demand, α grid, and §4.1
  exclusion mask) stacked into one engine invocation.  Rows that share
  (exclusion mask, α) share one objective row with its saturation
  analysis and rate ordering; rows that additionally share the residual
  share the whole plan — one LP prune, one DP, one decode per unique
  (objective, residual) pair, dispatched to the backend in stacked
  slices (accelerator backends take the stack whole, the host backend
  keeps each slice's working set cache-sized).

All three produce *bit-identical selections* for a given row regardless of
batching and backend: every value that decides a selection is an exact
integer (:mod:`repro.core.exact`: α on a dyadic grid, quantized objective
coefficients, int64 costs and DP values), and tie-breaking lives entirely
in the shared improvement-bit backtracker.  The seed history-matrix
solver and a PuLP/CBC wrapper live with the tests (``tests/oracles.py``)
as independent references.

All count-returning entry points return per-item integers, or ``None`` when
demand exceeds the total bounded capacity (the paper assumes the cloud
always has capacity; the provisioner surfaces this explicitly instead).

Preprocessing (bundle splitting, pod/bound arrays, normalised objective
terms) is hoisted into :class:`CompiledMarket`, built once per candidate
set and reused across every α evaluated by a provisioning cycle — and
across the re-optimisation cycles of §4.1 interrupt handling via the
provisioner-level cache.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
from typing import Hashable, List, Optional, Sequence, Tuple

import numpy as np

from . import exact
from .backend import (_CORE_MIN, _CORE_PAD, _CORE_TRIGGER,
                      DEFAULT_COARSENING, CoarseningConfig, SolverBackend,
                      get_backend)
from .efficiency import CandidateItem

__all__ = [
    "CoarseningConfig", "DEFAULT_COARSENING", "CompiledMarket", "IlpStats",
    "compile_market", "reweight_market", "objective_coefficients",
    "solve_ilp", "solve_ilp_batch", "solve_ilp_many",
]

_INF = float("inf")


@dataclasses.dataclass(frozen=True)
class IlpStats:
    """Solver introspection for the overhead study (paper Fig. 7 / §5.3).

    ``coarse`` records which demand-coarsening tier solved the row
    (DESIGN.md §14): ``"exact"`` (granularity 1), ``"gcd"`` (provably
    exact at granularity = the market pod GCD), ``"approx"`` (greedy
    rate-order prefix + exact DP over the boundary residual window,
    ``granularity`` = the window width and ``gap_bound`` the a-posteriori
    LP-certified objective gap), or ``"approx_fallback"`` (the
    certificate failed; the row was re-solved exactly)."""

    n_items: int
    n_bundles: int
    residual_demand: int
    objective: float
    coarse: str = "exact"
    granularity: int = 1
    gap_bound: float = 0.0


def objective_coefficients(items: Sequence[CandidateItem],
                           alpha: float) -> np.ndarray:
    """Eq. 4–5 coefficients: -α·Perf_i/Perf_min + (1-α)·SP_i/SP_min."""
    if not items:
        return np.zeros((0,))
    perf = np.array([it.perf for it in items], dtype=np.float64)
    sp = np.array([it.spot_price for it in items], dtype=np.float64)
    positive_perf = perf[perf > 0]
    perf_min = positive_perf.min() if positive_perf.size else 1.0
    sp_min = sp.min()
    if sp_min <= 0:
        raise ValueError("spot prices must be positive")
    return -alpha * perf / perf_min + (1.0 - alpha) * sp / sp_min


def _binary_bundles(count: int) -> List[int]:
    """Split a bound into power-of-two bundles (exact bounded knapsack)."""
    out, k = [], 1
    while count > 0:
        take = min(k, count)
        out.append(take)
        count -= take
        k <<= 1
    return out


# ---------------------------------------------------------------------------
# CompiledMarket: α-independent preprocessing, built once per candidate set
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CompiledMarket:
    """Everything about a candidate set that does not depend on α or demand.

    The ILP objective at any α is a linear reweighting of two fixed vectors
    (``perf_norm`` and ``price_norm``); the bounded-knapsack structure
    (per-item pods, T3 bounds, binary bundle splits) never changes.  Building
    this once per provisioning cycle and once per §4.1 re-optimisation is
    what lets GSS evaluate ~20 α values without re-running preprocessing.
    """

    items: Tuple[CandidateItem, ...]
    pods: np.ndarray          # (n,) int64   Pod_i
    bound: np.ndarray         # (n,) int64   T3_i
    perf: np.ndarray          # (n,) float64 Perf_i = BS_i·Pod_i
    price: np.ndarray         # (n,) float64 SP_i
    perf_min: float
    sp_min: float
    perf_norm: np.ndarray     # (n,) Perf_i / Perf_min
    price_norm: np.ndarray    # (n,) SP_i / SP_min
    structural: np.ndarray    # (n,) bool — pods > 0 and bound > 0
    b_item: np.ndarray        # (B,) int64  bundle -> item index
    b_pods: np.ndarray        # (B,) int64  bundle pod size
    b_copies: np.ndarray      # (B,) int64  bundle node count

    @property
    def n(self) -> int:
        return len(self.items)

    @property
    def n_bundles(self) -> int:
        return len(self.b_item)

    @property
    def metric_arrays(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(Perf_i, SP_i, Pod_i) float64 triple for ``score_counts_batch``."""
        return self.perf, self.price, self.pods.astype(np.float64)

    @functools.cached_property
    def pods_gcd(self) -> int:
        """GCD of every structural item's pod count (1 when there are
        none).  Any row's DP-active bundle set is a subset of the
        structural bundles, and every bundle's pod size is an item pod
        count times its copy count — so this market-wide GCD divides every
        active bundle of every row, which is exactly the divisibility
        condition under which gcd-coarsening is bit-exact (DESIGN.md §14).
        """
        p = self.pods[self.structural]
        return int(np.gcd.reduce(p)) if p.size else 1

    @functools.cached_property
    def digest(self) -> str:
        """Content digest of every solver-relevant array — the device-cache
        key of the fused backend (DESIGN.md §13): two markets with equal
        digests produce identical device uploads, so a recompiled but
        unchanged market re-uses its resident arrays, while any offering
        change invalidates the entry.  (``cached_property`` writes straight
        to ``__dict__``, which a frozen dataclass permits.)"""
        h = hashlib.blake2b(digest_size=16)
        for a in (self.pods, self.bound, self.perf, self.price,
                  self.structural, self.b_item, self.b_pods,
                  self.b_copies):
            h.update(np.ascontiguousarray(a).tobytes())
        return h.hexdigest()

    def norms(self, exclude: Optional[np.ndarray] = None,
              ) -> Tuple[np.ndarray, np.ndarray]:
        """(Perf_i/Perf_min, SP_i/SP_min) normalised objective vectors.

        With an ``exclude`` mask the Perf_min/SP_min normalisation is taken
        over the surviving candidates only — identical to rebuilding the
        candidate set without the excluded offerings (§4.1 cache semantics).
        :meth:`solve_inputs` quantizes this pair once per (market, mask).
        """
        if exclude is None or not np.any(exclude):
            return self.perf_norm, self.price_norm
        m = ~exclude
        perf_pos = self.perf[m & (self.perf > 0)]
        perf_min = float(perf_pos.min()) if perf_pos.size else 1.0
        prices = self.price[m]
        sp_min = float(prices.min()) if prices.size else 1.0
        if sp_min <= 0:
            raise ValueError("spot prices must be positive")
        return self.perf / perf_min, self.price / sp_min

    def coefficients(self, alphas: np.ndarray,
                     exclude: Optional[np.ndarray] = None) -> np.ndarray:
        """Broadcast Eq. 4–5 over an α grid: (n_alpha, n_items), float64
        (reporting only: the solver decides on :meth:`int_coefficients`)."""
        a = np.asarray(alphas, dtype=np.float64).reshape(-1, 1)
        perf_norm, price_norm = self.norms(exclude)
        return -a * perf_norm + (1.0 - a) * price_norm

    @functools.cached_property
    def scale_bits(self) -> int:
        """Fraction bits of the quantized objective (:func:`exact.scale_bits`):
        the unmasked norms bound every masked one, and the structural node
        count bounds every selection."""
        norms = np.concatenate([self.perf_norm, self.price_norm, [1.0]])
        return exact.scale_bits(float(np.max(norms[np.isfinite(norms)])),
                                int(np.sum(self.bound[self.structural])))

    def solve_inputs(self, exclude: Optional[np.ndarray] = None,
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """``(W, Q, active)`` for one exclusion mask: the int64 vectors of
        :func:`exact.quantize` — all any backend needs to rebuild an α's
        coefficients exactly — and the items a solve may select
        (structural, not excluded, finite objective terms).  Items outside
        ``active`` carry zeros: they never enter a solve, and their norms
        (corrupt, or renormalised by the unit fallback of :meth:`norms`)
        are not bounded by :attr:`scale_bits`."""
        perf_norm, price_norm = self.norms(exclude)
        active = (self.structural & np.isfinite(perf_norm)
                  & np.isfinite(price_norm))
        if exclude is not None:
            active &= ~exclude
        w, q = exact.quantize(np.where(active, perf_norm, 0.0),
                              np.where(active, price_norm, 0.0),
                              self.scale_bits)
        return w, q, active

    def int_coefficients(self, ks: Sequence[int],
                         exclude: Optional[np.ndarray] = None,
                         ) -> Tuple[np.ndarray, np.ndarray]:
        """Exact Eq. 4–5 coefficients at grid indices ``ks`` — the values
        every backend solves on — as (n_alpha, n_items) int64, with the
        mask's ``active`` items (:meth:`solve_inputs`)."""
        w, q, active = self.solve_inputs(exclude)
        k = np.asarray(ks, dtype=np.int64).reshape(-1, 1)
        return exact.coefficients(k, w, q), active


def compile_market(items: Sequence[CandidateItem]) -> CompiledMarket:
    """Hoist all α-independent solver preprocessing out of the hot path."""
    items = tuple(items)
    n = len(items)
    pods = np.array([it.pods for it in items], dtype=np.int64)
    bound = np.array([it.t3 for it in items], dtype=np.int64)
    perf = np.array([it.perf for it in items], dtype=np.float64)
    price = np.array([it.spot_price for it in items], dtype=np.float64)
    if n:
        positive_perf = perf[perf > 0]
        perf_min = float(positive_perf.min()) if positive_perf.size else 1.0
        sp_min = float(price.min())
        if sp_min <= 0:
            raise ValueError("spot prices must be positive")
    else:
        perf_min, sp_min = 1.0, 1.0
    structural = (pods > 0) & (bound > 0)

    b_item: List[int] = []
    b_copies: List[int] = []
    for i in np.nonzero(structural)[0]:
        for copies in _binary_bundles(int(bound[i])):
            b_item.append(int(i))
            b_copies.append(copies)
    b_item_arr = np.array(b_item, dtype=np.int64)
    b_copies_arr = np.array(b_copies, dtype=np.int64)
    b_pods_arr = (pods[b_item_arr] * b_copies_arr if len(b_item)
                  else np.zeros(0, dtype=np.int64))
    return CompiledMarket(
        items=items, pods=pods, bound=bound, perf=perf, price=price,
        perf_min=perf_min, sp_min=sp_min,
        perf_norm=perf / perf_min, price_norm=price / sp_min,
        structural=structural,
        b_item=b_item_arr, b_pods=b_pods_arr, b_copies=b_copies_arr)


def reweight_market(market: CompiledMarket, perf: np.ndarray,
                    price: np.ndarray,
                    items: Optional[Sequence[CandidateItem]] = None,
                    ) -> CompiledMarket:
    """Array-adjustment entry point: a compiled market with substituted
    (Perf_i, SP_i) objective vectors.

    The bounded-knapsack *structure* (Pod_i, T3_i, binary bundle splits) is
    independent of the objective, so swapping in adjusted performance/price
    vectors — the risk subsystem's uptime-discounted Perf and
    re-provision-charged SP (``repro.risk.objective``) — costs O(n) instead
    of a full :func:`compile_market`.  Pass ``items`` (e.g. from
    :func:`repro.core.efficiency.reweight_items`) to keep ``market.items``
    consistent with the new vectors; otherwise the original items are kept
    and only the solver-facing arrays change.
    """
    perf = np.asarray(perf, dtype=np.float64)
    price = np.asarray(price, dtype=np.float64)
    if len(perf) != market.n or len(price) != market.n:
        raise ValueError(f"adjusted vectors must have {market.n} entries")
    if market.n == 0:
        return market
    if np.any(price <= 0):
        raise ValueError("adjusted prices must be positive")
    positive_perf = perf[perf > 0]
    perf_min = float(positive_perf.min()) if positive_perf.size else 1.0
    sp_min = float(price.min())
    return dataclasses.replace(
        market,
        items=market.items if items is None else tuple(items),
        perf=perf, price=price, perf_min=perf_min, sp_min=sp_min,
        perf_norm=perf / perf_min, price_norm=price / sp_min)


# ---------------------------------------------------------------------------
# Covering knapsack: LP pruning + backend value pass + improvement-bit decode
# ---------------------------------------------------------------------------

def _cover_dp(bpods: np.ndarray, bcosts: np.ndarray, target: int,
              ) -> np.ndarray:
    """Reference forward value pass: dp[j] = min int64 cost of a bundle
    subset with ≥ j pods (``exact.INF`` = unreachable).  Kept as the
    plain-numpy spec of the backend kernel (``repro.core.backend``) for
    tests; the production path uses the backend's fused value pass with
    improvement bits instead.
    """
    dp = np.full(target + 1, exact.INF, dtype=np.int64)
    dp[0] = 0
    for b in range(len(bpods)):
        pb = int(bpods[b])
        cb = bcosts[b]
        if pb > target:
            np.minimum(dp[1:], cb, out=dp[1:])
            continue
        cand = dp[:target + 1 - pb] + cb
        np.minimum(dp[pb:], cand, out=dp[pb:])
        if pb > 1:
            np.minimum(dp[1:pb], cb, out=dp[1:pb])
    return dp


#: core-DP upper-bound tuning for :func:`_lp_prune` (``_CORE_PAD``,
#: ``_CORE_MIN``, ``_CORE_TRIGGER``) lives in :mod:`repro.core.backend`
#: — the fused device solver replicates the same pruning decisions and
#: importing them from here would create a cycle.


def _rate_order(bpods: np.ndarray, bcosts: np.ndarray):
    """Rate-order view of a bundle set: ``(order, p_sorted, c_sorted,
    r_sorted, cum_p, cum_c, cum_r)``.  Rates are the integer unit costs
    ``floor(cost / pods)`` (= ``floor(C_i / Pod_i)`` of the bundle's
    item); ``cum_r`` sums the *relaxed* costs ``rate·pods <= cost`` whose
    fractional greedy is the LP bound (rates are exact for the relaxed
    costs, so the stable integer sort is their true rate order and the
    bound is valid)."""
    rates = bcosts // bpods
    order = np.argsort(rates, kind="stable")
    p_sorted = bpods[order]
    c_sorted = bcosts[order]
    r_sorted = rates[order]
    return (order, p_sorted, c_sorted, r_sorted, np.cumsum(p_sorted),
            np.cumsum(c_sorted), np.cumsum(r_sorted * p_sorted))


def _lp_lower(cum_p: np.ndarray, cum_r: np.ndarray, r_sorted: np.ndarray,
              need):
    """Fractional greedy lower bound on the cost of covering ``need`` pods
    (scalar or array, each ``0 <= need <= cum_p[-1]``), in int64."""
    k = np.searchsorted(cum_p, need)
    prev_p = np.where(k > 0, cum_p[np.maximum(k - 1, 0)], 0)
    prev_r = np.where(k > 0, cum_r[np.maximum(k - 1, 0)], 0)
    return prev_r + (need - prev_p) * r_sorted[k]


def _lp_prune(bpods: np.ndarray, bcosts: np.ndarray, target: int,
              ) -> np.ndarray:
    """Exact LP-bound pruning: drop bundles no optimal solution can use.

    Sort by integer unit cost; the relaxed fractional greedy gives a lower
    bound LP(j) for covering j pods and the integral greedy a feasible
    upper bound UB.  Any solution containing bundle b costs ≥ c_b +
    LP(target − p_b), so bundles with c_b + LP(target − p_b) > UB are
    provably absent from *every* optimum and can be removed before the
    decode DP.  All optimal solutions survive for any valid UB, hence the
    pruned instance stays feasible and exact.  Integer arithmetic: the
    test needs no guard band.

    The greedy prefix can overshoot badly at awkward targets (a loose UB
    lets almost every bundle survive), so when it leaves more than
    ``_CORE_TRIGGER`` bundles alive the bound is tightened by a *core DP*:
    the exact cover DP over the best-rate core bundles (which contain the
    greedy prefix, so the core optimum covers the target and its cost is a
    valid — near-optimal in practice — UB).

    This standalone function is the reference statement of the prune rule
    (and the form the test suite exercises); the production engine inlines
    the same ingredients in :func:`_solve_rows`, where the argsort and
    cumulative arrays are shared across every residual of an objective.
    """
    B = len(bpods)
    if B == 0 or target <= 0:
        return np.ones(B, dtype=bool)
    order, p_sorted, c_sorted, r_sorted, cum_p, cum_c, cum_r = _rate_order(
        bpods, bcosts)
    if cum_p[-1] < target:                      # infeasible: caller handles
        return np.ones(B, dtype=bool)
    k_ub = int(np.searchsorted(cum_p, target))
    ub = int(cum_c[k_ub])                       # integral greedy prefix
    lp = _lp_lower(cum_p, cum_r, r_sorted, np.maximum(target - bpods, 0))
    keep = bcosts + lp <= ub
    if int(np.sum(keep)) <= _CORE_TRIGGER:
        return keep
    K = min(B, max(k_ub + _CORE_PAD, _CORE_MIN))
    core_ub = int(_cover_dp(p_sorted[:K], c_sorted[:K], target)[target])
    if core_ub < ub:
        keep = bcosts + lp <= core_ub
    return keep


def _backtrack_bits(bits: np.ndarray, bpods: np.ndarray, target: int,
                    ) -> np.ndarray:
    """Greedy improvement-bit backtrack (the seed backtracker's rule).

    Walking bundles last-to-first with remaining target ``j``: bundle ``b``
    is taken iff it *strictly improved* the value at coverage ``j`` when
    the forward pass processed it — equivalently, every optimal solution
    over bundles ``0..b`` uses it.
    This single rule is the engine's entire tie-breaking: backends produce
    bit-identical ``bits``, so selections are backend-invariant
    (DESIGN.md §12).
    """
    take = np.zeros(len(bpods), dtype=bool)
    j = target
    for b in range(len(bpods) - 1, -1, -1):
        if j == 0:
            break
        if bits[b, j]:
            take[b] = True
            j = max(0, j - int(bpods[b]))
    return take


def _plan_scale(cfg: Optional[CoarseningConfig], g: int,
                residual: int) -> Tuple[str, int]:
    """The demand-coarsening mode ladder (DESIGN.md §14), a deterministic
    function of (config, market gcd, residual) — so, like everything else
    in the engine, batch-composition-invariant.

    * residual ≤ threshold → ``("exact", 1)``: the coarsening layer is
      inert at the paper's scales.
    * gcd mode when the market GCD ``g`` shrinks the DP to at most
      ``max_rows`` rows → ``("gcd", g)``, provably bit-exact.
    * otherwise the approx tier → ``("approx", approx_rows)``: the bulk of
      the demand is covered by the rate-order greedy prefix (the integral
      form of the LP optimum, whose structure the engine's own pruning
      bound already trusts) down to a boundary window of ``approx_rows``
      pods, and only that window is solved by an exact cover DP — bounded
      suboptimality via an a-posteriori LP certificate, with an automatic
      exact fallback when the certificate fails.
    * approx disabled (or residual inside the window): degrade to gcd if
      available, else exact.
    """
    if cfg is None or not cfg.enabled or residual <= cfg.threshold:
        return "exact", 1
    if g > 1 and -(-residual // g) <= cfg.max_rows:
        return "gcd", g
    if cfg.allow_approx and residual > cfg.approx_rows:
        return "approx", cfg.approx_rows
    return ("gcd", g) if g > 1 else ("exact", 1)


# ---------------------------------------------------------------------------
# The row engine: every public solver is a view over _solve_rows
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SolveRow:
    """One (demand, objective) instance of the stacked engine invocation.

    ``key`` identifies the objective: rows with equal ``key`` MUST carry
    identical ``coef``/``icoef``/``active`` arrays (the caller's contract)
    and then share saturation analysis, bundle compaction, and — when
    their LP-pruned bundle sets coincide — one padded backend DP row.
    """

    req_pods: int
    alpha: float
    coef: np.ndarray                       # (n,) float Eq. 4–5 row (stats)
    icoef: np.ndarray                      # (n,) int64 exact row (solves)
    active: np.ndarray                     # (n,) structural & ~exclude
    key: Hashable                          # objective identity for grouping


def _solve_rows(market: CompiledMarket, rows: Sequence[SolveRow],
                backend: Optional[SolverBackend] = None,
                coarsening: Optional[CoarseningConfig] = None,
                ) -> Tuple[List[Optional[List[int]]], List[IlpStats]]:
    """Solve every row, deduplicating shared structure.

    Rows whose residual exceeds ``coarsening.threshold`` run the cover DP
    through the demand-coarsening ladder (:func:`_plan_scale`): the gcd
    tier is bit-exact; the approx tier carries a certified gap bound with
    an automatic exact fallback.  Everything below the threshold — all of
    the paper's scenarios under the default config — is byte-for-byte the
    uncoarsened engine.

    Pipeline (DESIGN.md §12), all on the exact int64 row ``icoef``.  Per
    objective key: saturation mask, covered capacity, residual-DP bundle
    compaction, and one integer rate-order argsort.  Per unique (key,
    residual): LP pruning — any bundle b with ``c_b + LP(residual − p_b)``
    above a feasible upper bound is provably in no optimal solution.  The
    bound starts as the integral greedy prefix; when that alone leaves
    more than ``_CORE_TRIGGER`` bundles alive, a *core DP* (value-only,
    over the ``max(k_greedy + _CORE_PAD, _CORE_MIN)`` best-rate bundles,
    where optimal solutions live in practice) tightens it to near-optimal,
    and the surviving set of the tighter test is re-derived (always a
    subset of the greedy keep).  The final improvement-bit DP then runs
    over each plan's kept bundles in market order and its bits decode the
    selection.  Both backend phases stack all plans into one dispatch
    each.  Every choice is a deterministic function of (objective,
    residual), so a row's selection is independent of what else shares the
    batch — the scalar path IS the one-row batch.  ``IlpStats.objective``
    is the float Eq. 4–5 objective of the returned counts.
    """
    backend = backend or get_backend()
    cfg = DEFAULT_COARSENING if coarsening is None else coarsening
    gcd = market.pods_gcd
    n = market.n
    unit = float(1 << market.scale_bits)   # integer cost units per 1.0
    results: List[Optional[List[int]]] = [None] * len(rows)
    stats: List[Optional[IlpStats]] = [None] * len(rows)

    # -- per-objective saturation analysis ---------------------------------
    obj_cache: dict = {}                   # key -> per-objective dict
    for r in rows:
        o = obj_cache.get(r.key)
        if o is None:
            neg = (r.icoef < 0) & r.active
            covered = int(np.sum(market.pods[neg] * market.bound[neg]))
            in_dp = r.active & ~neg
            capacity = int(np.sum(market.pods[in_dp] * market.bound[in_dp]))
            obj_cache[r.key] = o = {
                "neg": neg, "covered": covered, "in_dp": in_dp,
                "capacity": capacity, "icoef": r.icoef, "sat_counts": None,
                "bundles": None, "rate": None,
            }

    def _saturated(o) -> np.ndarray:
        if o["sat_counts"] is None:
            counts = np.zeros(n, dtype=np.int64)
            counts[o["neg"]] = market.bound[o["neg"]]
            o["sat_counts"] = counts
        return o["sat_counts"]

    def _bundles(o) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        if o["bundles"] is None:
            bidx = np.flatnonzero(o["in_dp"][market.b_item])
            o["bundles"] = (bidx, market.b_pods[bidx],
                            o["icoef"][market.b_item[bidx]]
                            * market.b_copies[bidx])
        return o["bundles"]

    def _rate(o):
        """Rate-order view of the objective's DP bundles (argsort shared
        across every residual of the objective)."""
        if o["rate"] is None:
            o["rate"] = _rate_order(*_bundles(o)[1:])
        return o["rate"]

    def _lp_bound(o, residual: int) -> np.ndarray:
        """Fractional greedy lower bound LP(residual − p_b) per bundle."""
        _, bpods, _bc = _bundles(o)
        _o, _p, _c, r_sorted, cum_p, _cc, cum_r = _rate(o)
        return _lp_lower(cum_p, cum_r, r_sorted,
                         np.maximum(residual - bpods, 0))

    # -- classify rows; one plan per unique (objective, residual) ----------
    plans: dict = {}
    row_plan: List = []       # per row: (kind, obj-or-plan, residual)
    for r in rows:
        o = obj_cache[r.key]
        residual = max(0, r.req_pods - o["covered"])
        if residual == 0:
            row_plan.append(("sat", o, 0))
            continue
        if o["capacity"] < residual:
            row_plan.append(("none", o, residual))
            continue
        mode, param = _plan_scale(cfg, gcd, residual)
        pkey = (r.key, residual)
        plan = plans.get(pkey)
        if plan is None:
            order, _p, _c, r_sorted, cum_p, cum_c, cum_r = _rate(o)
            _, _bp, bcosts = _bundles(o)
            if mode == "approx":
                # greedy rate-order prefix down to the boundary window:
                # the minimal prefix covering residual − window pods (its
                # cumulative arrays are shared by every residual of the
                # objective — the coarse work α-grid rows reuse).  Only
                # the ≤ window-pod remainder meets an exact cover DP.
                need = residual - param
                k_cut = (min(int(np.searchsorted(cum_p, need)) + 1,
                             len(order)) if need > 0 else 0)
                cov = int(cum_p[k_cut - 1]) if k_cut else 0
                tres = max(0, residual - cov)
                tail = order[k_cut:]
                # the window DP is the exact engine restated on the tail
                # subproblem (tail capacity ≥ tres by construction), so it
                # reuses the same greedy-UB / per-bundle-LP prune and the
                # phase-1 core tightening; lp = INF off-tail keeps the
                # committed prefix out of the DP (binary bundles are
                # use-once).
                lp = np.full(len(bcosts), exact.INF, dtype=np.int64)
                ub, core, keep = 0, None, np.zeros(len(bcosts), bool)
                if tres > 0 and len(tail):
                    def _tail(cum):
                        return cum[k_cut:] - (cum[k_cut - 1] if k_cut else 0)
                    cum_tp, cum_tc, cum_tr = (_tail(cum_p), _tail(cum_c),
                                              _tail(cum_r))
                    k_ub = int(np.searchsorted(cum_tp, tres))
                    ub = int(cum_tc[k_ub])
                    lp[tail] = _lp_lower(cum_tp, cum_tr, r_sorted[k_cut:],
                                         np.maximum(tres - _p[k_cut:], 0))
                    keep = bcosts + lp <= ub
                    if int(np.sum(keep)) > _CORE_TRIGGER:
                        K = min(len(tail), max(k_ub + _CORE_PAD, _CORE_MIN))
                        core = tail[:K]
                plans[pkey] = plan = {
                    "o": o, "resid": residual, "mode": "approx",
                    "window": param, "prefix": order[:k_cut],
                    "pcost": int(cum_c[k_cut - 1]) if k_cut else 0,
                    "tres": tres, "scale": 1, "sres": tres,
                    "lp": lp, "ub": ub, "core": core, "keep": keep,
                    "counts": None, "value": exact.INF, "n_bundles": 0,
                    "coarse": "approx", "gap": 0.0}
                row_plan.append(("dp", plan, residual))
                continue
            # exact / gcd tiers share one code path: the DP runs at
            # granularity ``scale`` (1 = exact; the market gcd = bitwise
            # identical to the unscaled DP, DESIGN.md §14).  Prune math
            # deliberately stays at unscaled pods/residual, so the keep
            # set is the exact engine's in both tiers.
            scale = param if mode == "gcd" else 1
            sres = -(-residual // scale)
            k_ub = int(np.searchsorted(cum_p, residual))
            lp = _lp_bound(o, residual)
            ub = int(cum_c[k_ub])              # integral greedy prefix
            keep = bcosts + lp <= ub
            core = None
            if int(np.sum(keep)) > _CORE_TRIGGER:
                # loose greedy bound: plan a core DP to tighten it first
                K = min(len(order), max(k_ub + _CORE_PAD, _CORE_MIN))
                core = order[:K]
            plans[pkey] = plan = {
                "o": o, "resid": residual, "mode": mode, "scale": scale,
                "sres": sres, "lp": lp, "ub": ub,
                "core": core, "keep": keep, "counts": None,
                "value": exact.INF, "n_bundles": 0,
                "coarse": "gcd" if scale > 1 else "exact", "gap": 0.0}
        row_plan.append(("dp", plan, residual))

    plan_list = list(plans.values())

    def _scaled(bpods: np.ndarray, scale: int) -> np.ndarray:
        return bpods if scale == 1 else bpods // scale

    # -- phase 1: core upper bounds (value-only, one dispatch) -------------
    # gcd-mode plans run the core DP at scaled pods/target: bitwise the
    # unscaled DP (DESIGN.md §14), so the tightened keep set is identical
    cored = [p for p in plan_list if p["core"] is not None]
    if cored:
        reqs = []
        for p in cored:
            _, bpods, bcosts = _bundles(p["o"])
            reqs.append((_scaled(bpods, p["scale"])[p["core"]],
                         bcosts[p["core"]], p["sres"]))
        for p, dp in zip(cored, backend.cover_values(reqs)):
            # the core contains the greedy cover prefix, so its optimum is
            # finite and ≤ the greedy bound; survivors of the tighter test
            # are a subset of the greedy keep
            core_ub = int(dp[p["sres"]])
            if core_ub < p["ub"]:
                p["ub"] = core_ub
                _, _bp, bcosts = _bundles(p["o"])
                p["keep"] = bcosts + p["lp"] <= core_ub

    def _exact_plan(o, residual: int):
        """One-row exact prune + DP + decode — the approx tier's fallback.
        A deterministic function of (objective, residual), identical to
        what the batched exact path produces for the same pair."""
        order, _p, _c, _r, cum_p, cum_c, _cr = _rate(o)
        bidx, bpods, bcosts = _bundles(o)
        k_ub = int(np.searchsorted(cum_p, residual))
        lp = _lp_bound(o, residual)
        ub = int(cum_c[k_ub])
        keep = bcosts + lp <= ub
        if int(np.sum(keep)) > _CORE_TRIGGER:
            K = min(len(order), max(k_ub + _CORE_PAD, _CORE_MIN))
            core = order[:K]
            dp = backend.cover_values(
                [(bpods[core], bcosts[core], residual)])[0]
            core_ub = int(dp[residual])
            if core_ub < ub:
                keep = bcosts + lp <= core_ub
        kept = np.flatnonzero(keep)
        dp, bits = backend.cover_bits(
            [(bpods[kept], bcosts[kept], residual)])[0]
        take = _backtrack_bits(bits, bpods[kept], residual)
        return bidx[kept[take]], int(dp[residual]), len(kept)

    def _approx_finish(p, tail_taken: Optional[np.ndarray],
                       tail_value: int) -> None:
        """Assemble an approx plan from its greedy prefix + boundary-DP
        take (``tail_taken`` in market bundle order), then check the LP
        certificate: the prefix + exact-window total is a feasible
        solution (cost ≥ optimum) and LP(residual) a lower bound (≤
        optimum), so ``total − LP`` bounds the true gap from above.
        Certificate violated → exact fallback."""
        o = p["o"]
        bidx, _bp, _bc = _bundles(o)
        _o, _p, _c, r_sorted, cum_p, _cc, cum_r = _rate(o)
        total = p["pcost"] + tail_value
        lp = int(_lp_lower(cum_p, cum_r, r_sorted, p["resid"]))
        gap = total - lp
        if gap <= cfg.rel_gap * max(abs(lp), 1):
            taken = (p["prefix"] if tail_taken is None else
                     np.concatenate([p["prefix"], tail_taken]))
            p["counts"] = bidx[taken]
            p["value"] = total
            p["n_bundles"] += len(p["prefix"])
            p["gap"] = max(gap, 0) / unit
        else:
            p["counts"], p["value"], p["n_bundles"] = _exact_plan(
                o, p["resid"])
            p["coarse"] = "approx_fallback"
            p["gap"] = 0.0

    # -- phase 2: the decode DP over each plan's kept set ------------------
    # dispatched in backend-preferred slices: the host backend keeps the
    # live bits working set small.  Approx plans ride the same dispatch:
    # their req is the exact boundary-window DP over the pruned non-prefix
    # bundles.
    chunk = max(1, getattr(backend, "max_group_batch", len(plan_list) or 1))
    for lo in range(0, len(plan_list), chunk):
        part = plan_list[lo:lo + chunk]
        reqs, ready = [], []
        for p in part:
            if p["mode"] == "approx" and p["tres"] == 0:
                _approx_finish(p, None, 0)  # prefix covers the demand
                continue
            _, bpods, bcosts = _bundles(p["o"])
            p["kept"] = np.flatnonzero(p["keep"])    # market bundle order
            p["n_bundles"] = len(p["kept"])
            reqs.append((_scaled(bpods, p["scale"])[p["kept"]],
                         bcosts[p["kept"]], p["sres"]))
            ready.append(p)
        for p, (dp, bits) in zip(ready, backend.cover_bits(reqs)):
            bidx, bpods, _bc = _bundles(p["o"])
            take = _backtrack_bits(
                bits, _scaled(bpods, p["scale"])[p["kept"]], p["sres"])
            if p["mode"] == "approx":
                _approx_finish(p, p["kept"][take], int(dp[p["sres"]]))
                continue
            p["counts"] = bidx[p["kept"][take]]
            p["value"] = int(dp[p["sres"]])

    # -- assemble rows (duplicates share decoded plans) --------------------
    for i, (r, (kind, ctx, residual)) in enumerate(zip(rows, row_plan)):
        o = ctx if kind in ("sat", "none") else ctx["o"]
        if kind == "none":
            stats[i] = IlpStats(n, 0, residual, _INF)
            continue
        counts = _saturated(o)
        if kind == "sat":
            results[i] = list(map(int, counts))
            stats[i] = IlpStats(n, 0, 0, float(np.dot(r.coef, counts)))
            continue
        plan = ctx
        counts = counts.copy()
        taken = plan["counts"]
        np.add.at(counts, market.b_item[taken], market.b_copies[taken])
        results[i] = list(map(int, counts))
        stats[i] = IlpStats(
            n, plan["n_bundles"], residual, float(np.dot(r.coef, counts)),
            coarse=plan["coarse"],
            granularity=(plan["window"] if plan["mode"] == "approx"
                         else plan["scale"]),
            gap_bound=plan["gap"])
    return results, stats


# ---------------------------------------------------------------------------
# Public solvers
# ---------------------------------------------------------------------------

def _empty_market_result(req_pods: int, return_stats: bool):
    result = None if req_pods > 0 else []
    stats = IlpStats(0, 0, req_pods, _INF if req_pods > 0 else 0.0)
    return (result, stats) if return_stats else result


def _checked_market(items: Sequence[CandidateItem],
                    market: Optional[CompiledMarket]) -> CompiledMarket:
    if market is None:
        return compile_market(items)
    if market.n != len(items):
        raise ValueError(f"market was compiled from {market.n} items but "
                         f"{len(items)} were passed — stale CompiledMarket?")
    return market


def solve_ilp(items: Sequence[CandidateItem], req_pods: int, alpha: float,
              return_stats: bool = False,
              market: Optional[CompiledMarket] = None,
              exclude: Optional[np.ndarray] = None,
              backend: Optional[SolverBackend] = None,
              coarsening: Optional[CoarseningConfig] = None,
              ) -> Optional[List[int]] | Tuple[Optional[List[int]], IlpStats]:
    """Exact solver for Eq. 5.  Returns x_i per item (None if infeasible).

    ``market`` reuses a :class:`CompiledMarket` (skips preprocessing);
    ``exclude`` is a per-item boolean mask of offerings barred from the
    solution (the §4.1 interrupted-offerings cache), applied at solve time
    so the compiled market survives interrupt churn.  α is solved at its
    nearest grid point :func:`exact.alpha_k` (exact on the GSS grid).
    ``coarsening`` overrides the demand-coarsening policy (default
    :data:`DEFAULT_COARSENING`, inert below 8192 residual pods).
    """
    out = solve_ilp_batch(items, req_pods, [alpha], market=market,
                          exclude=exclude, return_stats=True,
                          backend=backend, coarsening=coarsening)
    return (out[0][0], out[1][0]) if return_stats else out[0][0]


def solve_ilp_batch(items: Sequence[CandidateItem], req_pods: int,
                    alphas: Sequence[float],
                    market: Optional[CompiledMarket] = None,
                    exclude: Optional[np.ndarray] = None,
                    return_stats: bool = False,
                    backend: Optional[SolverBackend] = None,
                    coarsening: Optional[CoarseningConfig] = None,
                    ) -> List[Optional[List[int]]] | Tuple[
                        List[Optional[List[int]]], List[IlpStats]]:
    """Solve Eq. 5 for every α of a prescan grid in one engine invocation.

    The bundle structure is α-independent; only objective coefficients vary
    (one broadcast over the grid).  Rows that saturate the demand skip the
    DP entirely; the rest share LP-pruned backend DP rows wherever their
    pruned bundle sets coincide (:func:`_solve_rows`).
    """
    grid = [float(a) for a in alphas]
    market = _checked_market(items, market)
    if market.n == 0:
        single = _empty_market_result(req_pods, True)
        results = [single[0] for _ in grid]
        stats = [single[1] for _ in grid]
        return (results, stats) if return_stats else results
    coef2d = market.coefficients(np.asarray(grid, dtype=np.float64), exclude)
    icoef2d, active = market.int_coefficients(
        [exact.alpha_k(a) for a in grid], exclude)
    rows = [SolveRow(req_pods, a, coef2d[k], icoef2d[k], active, key=a)
            for k, a in enumerate(grid)]
    results, stats = _solve_rows(market, rows, backend,
                                 coarsening=coarsening)
    return (results, stats) if return_stats else results


def solve_ilp_many(items: Sequence[CandidateItem],
                   requests: Sequence[int],
                   alphas: Sequence[float] | Sequence[Sequence[float]],
                   market: Optional[CompiledMarket] = None,
                   excludes: Optional[Sequence[Optional[np.ndarray]]] = None,
                   backend: Optional[SolverBackend] = None,
                   return_stats: bool = False,
                   coarsening: Optional[CoarseningConfig] = None,
                   ) -> List[List[Optional[List[int]]]] | Tuple[
                       List[List[Optional[List[int]]]], List[List[IlpStats]]]:
    """The cross-decision batch (DESIGN.md §12): solve every (decision, α)
    pair of a FleetSim tick in one engine invocation.

    ``requests[d]`` is decision ``d``'s demand, ``alphas`` either one grid
    shared by all decisions or a per-decision list of grids, and
    ``excludes[d]`` its §4.1 exclusion mask (or None).  Decisions that
    share (mask, α) share one objective row and saturation analysis;
    those additionally sharing the residual share the entire prune + DP +
    decode plan — the (n_decisions × n_α) stack collapses to its unique
    (objective, residual) pairs before the backend dispatches.  Per-row
    selections are bit-identical to per-decision :func:`solve_ilp_batch`
    calls.

    Returns one list of per-α count vectors (``None`` = infeasible) per
    decision, ``alphas``-shaped.
    """
    n_dec = len(requests)
    shared_grid = not n_dec or np.isscalar(alphas[0]) or isinstance(
        alphas[0], (int, float))
    grids: List[List[float]] = (
        [[float(a) for a in alphas]] * n_dec if shared_grid
        else [[float(a) for a in g] for g in alphas])
    if len(grids) != n_dec:
        raise ValueError("per-decision alphas must match len(requests)")
    if excludes is None:
        excludes = [None] * n_dec
    if len(excludes) != n_dec:
        raise ValueError("excludes must match len(requests)")
    market = _checked_market(items, market)

    if market.n == 0:
        out, st = [], []
        for d in range(n_dec):
            single = _empty_market_result(requests[d], True)
            out.append([single[0] for _ in grids[d]])
            st.append([single[1] for _ in grids[d]])
        return (out, st) if return_stats else out

    # dedupe masks -> tokens; per (token, α) one coefficient row
    mask_tokens: dict = {}
    masks: List[Optional[np.ndarray]] = []
    token_of: List[int] = []
    for ex in excludes:
        mkey = None if ex is None else ex.tobytes()
        tok = mask_tokens.get(mkey)
        if tok is None:
            tok = len(masks)
            mask_tokens[mkey] = tok
            masks.append(ex)
        token_of.append(tok)
    per_tok_alphas: List[List[float]] = [[] for _ in masks]
    per_tok_seen: List[dict] = [{} for _ in masks]
    for d in range(n_dec):
        tok = token_of[d]
        for a in grids[d]:
            if a not in per_tok_seen[tok]:
                per_tok_seen[tok][a] = len(per_tok_alphas[tok])
                per_tok_alphas[tok].append(a)
    coef_rows: List[np.ndarray] = []
    icoef_rows: List[np.ndarray] = []
    actives: List[np.ndarray] = []
    for tok, mask in enumerate(masks):
        coef_rows.append(market.coefficients(
            np.asarray(per_tok_alphas[tok], dtype=np.float64), mask))
        icoef, active = market.int_coefficients(
            [exact.alpha_k(a) for a in per_tok_alphas[tok]], mask)
        icoef_rows.append(icoef)
        actives.append(active)

    rows: List[SolveRow] = []
    for d in range(n_dec):
        tok = token_of[d]
        for a in grids[d]:
            j = per_tok_seen[tok][a]
            rows.append(SolveRow(
                requests[d], a, coef_rows[tok][j], icoef_rows[tok][j],
                actives[tok], key=(tok, a)))
    flat, flat_stats = _solve_rows(market, rows, backend,
                                   coarsening=coarsening)

    out, st, pos = [], [], 0
    for d in range(n_dec):
        k = len(grids[d])
        out.append(flat[pos:pos + k])
        st.append(flat_stats[pos:pos + k])
        pos += k
    return (out, st) if return_stats else out
