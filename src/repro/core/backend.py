"""Solver backends for the min-plus cover DP (DESIGN.md §12–13).

The ILP engine reduces every solve — single-α, a GSS prescan grid, or the
cross-decision batches of ``solve_ilp_many`` — to one primitive: a forward
min-plus value pass over a bundle sequence that also emits *improvement
bits*, the per-(bundle, coverage) booleans the exact backtracker consumes.
Two backends implement it:

* :class:`NumpyBackend` — the host path and the reference: a Python loop
  over bundles with in-place vectorized row updates.
* :class:`FusedJaxBackend` (``jax:fused``) — the device-resident decision
  plane (DESIGN.md §13): whole GSS batches run as two jitted programs
  (prescan grid + golden ``lax.while_loop``) with the cover DP, backtrack,
  and pool scoring fused on device, market arrays uploaded once per
  content digest, and a host replay that consumes the recorded counts.

Canonical kernel semantics (int64 costs, :mod:`repro.core.exact`):

    dp[0] = 0, dp[j>0] = INF
    for b in 0..B-1:                       # bundle order is significant
        cand[j] = dp[max(j - pods[b], 0)] + cost[b]      (j >= 1)
        bits[b, j] = cand[j] < dp[j]                     (bits[b, 0] = False)
        dp[j]    = min(dp[j], cand[j])                   (dp[0] pinned at 0)

dp values are exact subset-cost sums, so a strict improvement at (b, j)
means every optimal solution of the bundle prefix uses b — the
backtracker's take-rule — and equality means skipping b is optimal.
Integer arithmetic is exact on every platform, so ``dp``/``bits`` — and
with them selections — cannot depend on the backend.  The ``j``-prefix of
``dp``/``bits`` does not depend on the padded target length, so solve
groups that share (costs, kept bundles) can share one padded row.

Importing this module never imports ``jax``; ``make_backend("jax:fused")``
does, and fails loudly where jax is missing
(``KUBEPACS_SOLVER_BACKEND=numpy|jax:fused`` overrides the default).
"""

from __future__ import annotations

import collections
import dataclasses
import math
import os
import pathlib
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import events_log, exact

#: one (bpods, costs, target) residual covering problem; ``bpods`` int64
#: (all >= 1), ``costs`` int64 (all >= 0), ``target`` >= 1
CoverGroup = Tuple[np.ndarray, np.ndarray, int]

#: the repository checkout (``src/repro/core/backend.py`` → root): the
#: fixed home of the persistent compilation cache
_CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


@dataclasses.dataclass(frozen=True)
class CoarseningConfig:
    """Demand-coarsening policy for the residual cover DP (DESIGN.md §14).

    The engine solves residuals at or below ``threshold`` exactly — the
    default keeps every paper-scale scenario (≤ 5 k pods) byte-identical to
    the uncoarsened engine.  Above it:

    * **gcd mode** (provably exact, bit-identical selections): when the
      market's structural pod counts share a gcd ``g > 1`` and
      ``ceil(residual / g) <= max_rows``, the DP runs at granularity ``g``
      — same keep set (pruning stays unscaled), same improvement bits,
      same backtrack, 1/g of the rows.
    * **approx mode** (bounded suboptimality): otherwise, when
      ``allow_approx``, a greedy rate-order prefix of whole bundles is
      committed until at most ``approx_rows`` pods of demand remain, and
      an *exact* cover DP over the remaining bundles closes that boundary
      window — so the DP cost is that of an ``approx_rows``-pod residual
      regardless of demand.  The only loss is committing whole prefix
      bundles where the fractional optimum would split one, and the
      returned objective carries an a-posteriori certificate
      ``gap_bound = objective - LP(residual)`` (LP = the fractional-greedy
      lower bound, so the true optimality gap is ≤ ``gap_bound``); if the
      certificate exceeds ``rel_gap·|LP|`` the row is silently re-solved
      exactly (``coarse == "approx_fallback"`` in
      :class:`~repro.core.ilp.IlpStats`).

    Lives in :mod:`repro.core.backend` (not ``ilp``) because the fused
    device programs replicate the same per-row mode decision from traced
    ``(threshold, max_rows, gcd)`` scalars; importing from ``ilp`` would
    create a cycle.  Frozen + hashable so configs can key solve-batch
    groups.
    """

    enabled: bool = True
    threshold: int = 8192
    max_rows: int = 4096
    approx_rows: int = 4096
    allow_approx: bool = True
    rel_gap: float = 0.05


#: process-wide default: coarsening on, but inert below 8192 residual pods,
#: so every existing scale solves byte-identically to the exact engine
DEFAULT_COARSENING = CoarseningConfig()

#: core-DP upper-bound tuning shared by the host engine (`repro.core.ilp`)
#: and the fused device program, which must replicate the host's prune
#: decisions exactly: the core DP runs over the best-rate
#: ``max(k_greedy + _CORE_PAD, _CORE_MIN)`` bundles and only triggers when
#: the greedy bound leaves more than ``_CORE_TRIGGER`` bundles alive.
_CORE_PAD = 33
_CORE_MIN = 96
_CORE_TRIGGER = 160

#: width of the device LP prune's distinct-bundle-size table (one TPU lane
#: row; DESIGN.md §13): a market with at most this many distinct bundle
#: pod sizes computes the LP bound once per size, a wider one per bundle
_SIZE_TABLE = 128
#: the table's pad entry, and the size a pad bundle takes in it: above
#: every real bundle's pod count
_SIZE_PAD = np.iinfo(np.int32).max


class SolverBackend:
    """Interface: batched cover-DP value passes with improvement bits."""

    name = "abstract"

    #: engine hint: decode in slices of at most this many DP groups so the
    #: bits arrays of one slice die before the next is computed
    max_group_batch = 1 << 30

    def cover_bits(self, groups: Sequence[CoverGroup],
                   ) -> List[Tuple[np.ndarray, np.ndarray]]:
        """For each group return ``(dp, bits)`` — ``dp`` int64 of shape
        ``(target+1,)`` and ``bits`` bool of shape ``(B, target+1)`` — per
        the canonical kernel above."""
        raise NotImplementedError

    def cover_values(self, groups: Sequence[CoverGroup]) -> List[np.ndarray]:
        """Value-only variant: just each group's final ``dp`` vector (used
        for the engine's core upper bounds, where bits are never read)."""
        return [dp for dp, _bits in self.cover_bits(groups)]


class NumpyBackend(SolverBackend):
    """Host reference implementation (ragged — no padding waste).

    Runs each group's forward pass with preallocated scratch rows (the
    pass is memory-bandwidth-bound; allocator churn is the only other
    cost worth removing).
    """

    name = "numpy"
    max_group_batch = 8      # keep the live bits working set cache-sized

    def cover_bits(self, groups):
        scratch = _scratch(groups)
        return [self._one(bpods, costs, target, scratch)
                for bpods, costs, target in groups]

    def cover_values(self, groups):
        scratch = _scratch(groups)
        return [self._values(bpods, costs, target, scratch)
                for bpods, costs, target in groups]

    @staticmethod
    def _values(bpods: np.ndarray, costs: np.ndarray, target: int,
                scratch: np.ndarray) -> np.ndarray:
        dp = np.full(target + 1, exact.INF, dtype=np.int64)
        dp[0] = 0
        for b in range(len(bpods)):
            cb = costs[b]
            pb = int(bpods[b])
            if pb <= target:
                k = target + 1 - pb
                cand = np.add(dp[:k], cb, out=scratch[:k])
                np.minimum(dp[pb:], cand, out=dp[pb:])
                if pb > 1:
                    np.minimum(dp[1:pb], cb, out=dp[1:pb])
            else:
                np.minimum(dp[1:], cb, out=dp[1:])
        return dp

    @staticmethod
    def _one(bpods: np.ndarray, costs: np.ndarray, target: int,
             scratch: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        B = len(bpods)
        dp = np.full(target + 1, exact.INF, dtype=np.int64)
        dp[0] = 0
        # every bundle's row is fully written below (j >= 1) and the j = 0
        # column is blanked at the end, so empty beats zeros here
        bits = np.empty((B, target + 1), dtype=bool)
        for b in range(B):
            cb = costs[b]
            pb = int(bpods[b])
            if pb <= target:
                # j in [pb, target]: cand = dp[j - pb] + cb (pre-update dp;
                # the scratch row materializes before the in-place writes)
                k = target + 1 - pb
                cand = np.add(dp[:k], cb, out=scratch[:k])
                np.less(cand, dp[pb:], out=bits[b, pb:])
                np.minimum(dp[pb:], cand, out=dp[pb:])
                if pb > 1:        # j in [1, pb-1]: cand = dp[0] + cb = cb
                    np.less(cb, dp[1:pb], out=bits[b, 1:pb])
                    np.minimum(dp[1:pb], cb, out=dp[1:pb])
            else:                 # pb > target: cand = cb for every j >= 1
                np.less(cb, dp[1:], out=bits[b, 1:])
                np.minimum(dp[1:], cb, out=dp[1:])
        bits[:, 0] = False
        return dp, bits


def _scratch(groups: Sequence[CoverGroup]) -> np.ndarray:
    return np.empty(max((g[2] for g in groups), default=0) + 1,
                    dtype=np.int64)


def _bucket(n: int, steps: Sequence[int]) -> int:
    """Round ``n`` up to the smallest bucket (bounds jit recompilation)."""
    for s in steps:
        if n <= s:
            return s
    step = steps[-1]
    return ((n + step - 1) // step) * step


def _ensure_x64(jax) -> None:
    """Backend-init x64 check: the device plane's int64 costs require
    ``jax_enable_x64``.  Enabling it is *process-wide* — a global-config
    mutation co-resident JAX code in the embedding application may not
    expect (default dtypes change, programs compiled before the flip
    retrace) — so the flip is announced with a one-time ``RuntimeWarning``
    (counted in ``repro.core.events_log``), and ``KUBEPACS_JAX_X64=0``
    forbids it outright: the embedder must then enable x64 itself before
    constructing a jax backend, and construction fails loudly rather than
    silently running the solver outside its int64 contract."""
    if jax.config.jax_enable_x64:
        return
    if os.environ.get("KUBEPACS_JAX_X64", "1").lower() in ("0", "false",
                                                           "no"):
        raise RuntimeError(
            "KubePACS jax backends require jax_enable_x64, and "
            "KUBEPACS_JAX_X64=0 forbids enabling it process-wide; run "
            "jax.config.update('jax_enable_x64', True) in the embedding "
            "application before constructing a jax backend")
    events_log.warn_once(
        "backend_x64_flip",
        "KubePACS jax backend is enabling jax_enable_x64 process-wide "
        "(the decision plane's int64 costs); set KUBEPACS_JAX_X64=0 to "
        "forbid this and manage the flag in the embedding application "
        "instead", RuntimeWarning, stacklevel=3)
    jax.config.update("jax_enable_x64", True)


def compile_cache_dir() -> pathlib.Path:
    """Where compiled device programs persist: ``JAX_COMPILATION_CACHE_DIR``
    when set (JAX reads the variable itself), else ``<checkout>/.jax_cache``
    — a fixed path, since the directory is part of the cache key."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    return pathlib.Path(env) if env else _CHECKOUT / ".jax_cache"


def _configure_compile_cache(jax) -> None:
    """Point JAX's persistent compilation cache at :func:`compile_cache_dir`
    before the first device program compiles (no-op when the variable is
    set, or when the embedding application chose a directory itself)."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    if jax.config.jax_compilation_cache_dir:
        return
    jax.config.update("jax_compilation_cache_dir", str(compile_cache_dir()))


# ---------------------------------------------------------------------------
# Fused device-resident decision plane (DESIGN.md §13)
# ---------------------------------------------------------------------------

_MISS = object()      # lookup sentinel (stored values include None)


def _program_key(kind: str, shape: Tuple[int, ...], table: bool) -> tuple:
    """A fused program's cache key: its kind and static shapes, and a
    ``"search"`` mark on a program that prunes by the per-bundle search
    (a market with more distinct bundle sizes than ``_SIZE_TABLE``).  A
    table program's key is ``(kind, *shape)`` alone, the form
    ``bench/layers.py`` rebuilds a program's arguments from."""
    return (kind, *shape) if table else (kind, *shape, "search")


def _rc_tiers(RC: int) -> List[int]:
    """Geometric DP-width ladder ``129, 513, 2049, …, RC``.

    The cover DP is prefix-closed in the pod index ``j``: every value the
    solver reads for a row with residual ``r`` lives in ``dp[: r + 1]``,
    so running the recurrence at any width ``W > r`` yields bitwise the
    same prefix.  Routing each row to the narrowest tier wider than its
    residual mirrors the host solver's residual-sized dp rows instead of
    paying the full ``RC``-wide vector ops for every probe.  x4 rungs:
    golden probes cluster near the winning alpha, whose residual sits in
    the top tier anyway, so finer rungs were measured compile-time-only.
    """
    tiers: List[int] = []
    w = 129
    while w < RC:
        tiers.append(w)
        w = (w - 1) * 4 + 1
    tiers.append(RC)
    return tiers


class FusedJaxBackend(NumpyBackend):
    """Fully device-resident decision plane (``make_backend("jax:fused")``).

    Runs the *entire* bracketed GSS of a ``bracketed_gss_many`` batch on
    device as two jitted programs:

    * **prescan** — every (decision, grid-α) objective row solved in one
      program: saturation analysis, LP-bound bundle pruning, core-DP bound
      tightening, the improvement-bit cover DP, and the bit backtrack are
      all on-device stages under one ``jit``.
    * **golden** — a single ``lax.while_loop`` over golden rounds advancing
      all decisions in lockstep: per round one fused solve of each active
      decision's probe α plus on-device pool scoring (the ``e_total``
      formula, float32) to steer the bracket update — no host round-trips
      between probes.

    **Exact by construction.**  Every value that decides a selection is
    an integer of :mod:`repro.core.exact` — grid-index α, quantized
    coefficients, int64 costs, prefix sums, LP bounds and DP values — and
    the device row solver computes the same expressions as
    ``repro.core.ilp._solve_rows`` (same stable integer sort, same prune
    comparisons, same backtrack).  Recorded counts therefore equal the
    host engine's on any platform with exact integer arithmetic; the chip
    supplies that, while its float64 is a double-float32 emulation.  Only
    the speculative scores that steer the device's bracket updates are
    float32; the host replay (:class:`_FusedGssRecord`) re-runs the
    control flow with exact host scores and resolves every probe by
    grid-index lookup.  A lookup miss (device scores ordered two probes
    differently) is solved on the host engine and counted
    (``fallback_solves``); every batch also re-solves one sampled prescan
    row on the host and raises :class:`PrescanMismatch` if it differs.

    Device errors propagate: no path here turns a compile or run failure
    into a host solve.  The plane implements ``bracketed_gss_many``; the
    documented limits run on this backend's inherited NumPy cover DP and
    are counted: a batch that needs the approximate coarsening tier (or
    has an empty market) is *declined* (``declined_batches``), and every
    cover-DP group solved through the inherited path — declined batches
    and entry points without a device program, such as a single
    ``solve_ilp`` — counts in ``host_dp_groups``.

    **Device residency.**  ``CompiledMarket`` arrays are uploaded once and
    cached on device keyed by ``(market.digest, N_pad, B_pad)`` (LRU,
    ``device_cache_info()`` exposes hit/miss counters), so FleetSim ticks
    re-dispatch onto resident arrays; per-decision coefficient vectors,
    masks, demands and brackets are the only per-tick upload.

    **Row counters.**  Each program call also returns the row solver's
    ``ROW_COUNTERS``, counted in the program where it decides a row's rung
    and tier; ``device_cache_info()`` exposes their totals, so a reader
    sees which rung the device took without re-deriving it on the host.
    """

    name = "jax:fused"
    supports_fused_gss = True

    #: the row solver's own counters, in the order both programs return
    #: them: rows that reached the DP stages, those of them solved at a
    #: granularity g > 1 (the gcd rung), the DP columns those rows needed
    #: (``ceil(residual / g) + 1``) and the columns their DPs computed (the
    #: static width of the residual tier each ran at)
    ROW_COUNTERS = ("dp_rows", "gcd_rows", "dp_cols_needed",
                    "dp_cols_computed")

    #: fused-program bucket ladders.  R is deliberately fine (512-multiples
    #: beyond 512): every vector op in the fused row solver is O(R_pad), so
    #: coarse padding would tax each row far more than extra recompiles.
    _N_STEPS = (16, 32, 64, 128, 256, 512, 1024)
    _BF_STEPS = (32, 64, 128, 192, 256, 384, 512, 640, 768, 896, 1024,
                 1152, 1280, 1536, 2048)
    _RF_STEPS = (128, 256, 512)
    _D_STEPS = (1, 2, 4, 8, 16, 32, 64)
    _MAX_MARKETS = 8

    def __init__(self):
        import jax  # deferred: importing this module never imports jax

        _ensure_x64(jax)
        _configure_compile_cache(jax)
        self._jax = jax
        self._jnp = jax.numpy
        self._market_cache: "collections.OrderedDict" = \
            collections.OrderedDict()
        self._fused_cache: dict = {}
        self._host = NumpyBackend()
        self.device_cache_hits = 0
        self.device_cache_misses = 0
        self.fallback_solves = 0
        self.fused_records = 0
        self.declined_batches = 0
        self.host_dp_groups = 0
        self.program_builds = 0
        self.table_prune_programs = 0
        self.verify_solves = 0
        self.row_counters = dict.fromkeys(self.ROW_COUNTERS, 0)
        self._rows_in_flight: List = []

    def cover_bits(self, groups):
        self.host_dp_groups += len(groups)
        return super().cover_bits(groups)

    def cover_values(self, groups):
        self.host_dp_groups += len(groups)
        return super().cover_values(groups)

    # -- device market cache -------------------------------------------------
    def _device_market(self, market, N: int, B: int):
        """Upload-once market arrays, keyed on (content digest, pad shape),
        and whether the market's distinct bundle sizes fit the LP prune's
        size table (the programs' ``table`` branch).  Pad items have no
        pods and no bound; pad bundles are not real."""
        key = (market.digest, N, B)
        ent = self._market_cache.get(key)
        if ent is not None:
            self.device_cache_hits += 1
            self._market_cache.move_to_end(key)
            return ent
        self.device_cache_misses += 1
        n, nb = market.n, market.n_bundles

        def pad(a, size, dtype, fill=0):
            out = np.full(size, fill, dtype=dtype)
            out[:len(a)] = a
            return out

        with events_log.span("kubepacs.device.upload"):
            md = tuple(self._jnp.asarray(a) for a in (
                pad(market.pods, N, np.int32),
                pad(market.bound, N, np.int32),
                pad(market.b_item, B, np.int32),
                pad(market.b_pods, B, np.int32, fill=1),
                pad(market.b_copies, B, np.int32),
                pad(np.ones(nb, bool), B, bool),
                pad(market.perf, N, np.float32),
                pad(market.price, N, np.float32, fill=1.0)))
            ent = md, len(np.unique(market.b_pods)) <= _SIZE_TABLE
        self._market_cache[key] = ent
        while len(self._market_cache) > self._MAX_MARKETS:
            self._market_cache.popitem(last=False)
        return ent

    def device_cache_info(self) -> Dict[str, int]:
        self._drain_rows()
        return {"hits": self.device_cache_hits,
                "misses": self.device_cache_misses,
                "entries": len(self._market_cache),
                "fused_records": self.fused_records,
                "declined_batches": self.declined_batches,
                "host_dp_groups": self.host_dp_groups,
                "fallback_solves": self.fallback_solves,
                "verify_solves": self.verify_solves,
                "program_builds": self.program_builds,
                "table_prune_programs": self.table_prune_programs,
                **self.row_counters}

    def _drain_rows(self) -> None:
        """Add the row counters of the calls :meth:`_dispatch` kept to the
        totals."""
        for rows in self._rows_in_flight:
            for name, n in zip(self.ROW_COUNTERS, np.asarray(rows).tolist()):
                self.row_counters[name] += n
        self._rows_in_flight.clear()

    # -- the device row solver (traced context) ------------------------------
    def _solver_core(self, md, N: int, B: int, RC: int, coarse,
                     table: bool):
        """Build the traced closures shared by both fused programs.

        Returns ``(solve_rows, score)``.  ``solve_rows(coefs, actives,
        reqs)`` solves a stack of engine rows — each one
        ``repro.core.ilp._solve_rows`` row end to end on its exact int64
        coefficient row — returning ``(counts int32 (D, N), feasible,
        rows)``, where ``rows`` holds the stack's ``ROW_COUNTERS`` as int32
        scalars: which rung each row took, counted where the program
        decides it.

        ``coarse`` is the traced ``(threshold, max_rows, gcd)`` int64
        triple of the active :class:`CoarseningConfig`.  Rows whose
        residual exceeds the threshold and whose pods all share the market
        gcd run the DP stages at granularity ``g`` — exactly the host
        engine's gcd mode (prune math stays unscaled, matching the host's
        identical keep sets; only the core-bound DP, decode DP, and
        backtrack use scaled pods/targets).  Traced scalars, not static:
        changing the config or the market gcd never recompiles.

        ``table`` (static: the market's distinct bundle sizes fit
        ``_SIZE_TABLE``) computes the LP prune's bound once per distinct
        bundle size instead of by a binary search per bundle; both give
        the same ``lp`` bit for bit (DESIGN.md §13).
        """
        jax, jnp = self._jax, self._jnp
        lax = jax.lax
        scope = jax.named_scope
        pods, bound, b_item, b_pods, b_copies, b_real, perf, price = md
        i32, i64 = jnp.int32, jnp.int64
        INF = i64(exact.INF)
        c_thr, c_maxr, c_gcd = coarse
        pods64 = pods.astype(i64)
        item_nodes = pods64 * bound.astype(i64)
        podsf = pods.astype(jnp.float32)

        # -- cover DP toolkit, one instance per residual-tier width ----------
        # dp lives as the back half of a (2*W,) extended vector whose front
        # half is zeros: the shifted read dp[j - pb] (with dp[0] = 0 for
        # j < pb) becomes one dynamic_slice at start W - clip(pb) — no
        # gather — and 0 + cb is the host's dp[0] + cb.  W is a static tier
        # width > the row's residual (``_rc_tiers``): the DP recurrence is
        # prefix-closed in j, so dp[j <= residual] — all a row ever reads
        # — is identical at any W > residual.
        def dp_tools(W):
            ext0 = jnp.concatenate([jnp.zeros(W, i64),
                                    jnp.full(W, INF).at[0].set(0)])
            first = jnp.arange(W) == 0

            def _relax(ext, pb, cb):
                pbc = jnp.clip(pb, 0, W)
                dp = lax.dynamic_slice(ext, (W,), (W,))
                sh = lax.dynamic_slice(ext, (W - pbc,), (W,))
                cand = jnp.where(first, INF, sh + cb)
                return (lax.dynamic_update_slice(
                    ext, jnp.minimum(dp, cand), (W,)), cand < dp)

            def cover_value(pseq, cseq, trip, target):
                def body(st):
                    i, ext = st
                    return i + 1, _relax(ext, pseq[i], cseq[i])[0]
                _i, ext = lax.while_loop(lambda st: st[0] < trip, body,
                                         (i32(0), ext0))
                return ext[W + target]

            def cover_bits(kp, kc, trip):
                def body(st):
                    i, ext, bits = st
                    ext, bit = _relax(ext, kp[i], kc[i])
                    bits = lax.dynamic_update_slice(bits, bit[None, :],
                                                    (i, i32(0)))
                    return i + 1, ext, bits
                _i, _e, bits = lax.while_loop(
                    lambda st: st[0] < trip, body,
                    (i32(0), ext0, jnp.zeros((B, W), dtype=bool)))
                return bits

            return cover_value, cover_bits

        tiers = _rc_tiers(RC)
        tier_tools = [dp_tools(W) for W in tiers]
        tier_w = jnp.asarray(tiers, i32)

        def cumsum(v):
            # integer prefix sums are exact in any association; the TPU
            # compiler's reduce-window lowering of jnp.cumsum on int64
            # overflows its scoped vector memory at B = 512
            return lax.associative_scan(jnp.add, v)

        # -- the LP prune's distinct-size table ------------------------------
        # once per program call, outside the row loop: the market's
        # distinct bundle pod sizes ascending, padded with _SIZE_PAD to
        # _SIZE_TABLE entries, and which entry each bundle's size is (a
        # pad bundle's hits read pad entries; bmask drops it in any case)
        if table:
            with scope("lp_prune"):
                sizes = jnp.where(b_real, b_pods, _SIZE_PAD)
                srt = jnp.sort(sizes)
                first = (srt != _SIZE_PAD) & jnp.concatenate(
                    [jnp.ones(1, bool), srt[1:] != srt[:-1]])
                slot = jnp.cumsum(first.astype(i32)) - 1
                size_tab = jnp.min(jnp.where(
                    first & (slot == jnp.arange(_SIZE_TABLE,
                                                dtype=i32)[:, None]),
                    srt, _SIZE_PAD), axis=1)
                size_hit = sizes[:, None] == size_tab

        # -- one engine row on device ----------------------------------------
        # each stage runs under a jax.named_scope (DESIGN.md §13: spans and
        # stage names), which the profiler's trace carries per operation
        def solve_row(coef, active, req):
            with scope("saturate"):
                neg = (coef < 0) & active
                sat = jnp.where(neg, bound, 0)
                covered = jnp.sum(jnp.where(neg, item_nodes, 0))
                residual = jnp.maximum(req.astype(i64) - covered, 0)
                in_dp = active & ~neg
                capacity = jnp.sum(jnp.where(in_dp, item_nodes, 0))
                feasible = capacity >= residual

                # gcd-mode coarsening decision, mirroring the host engine's
                # _plan_scale: the gcd divides every structural pod count,
                # so scaled DP/backtrack columns are bitwise the unscaled
                # ones (DESIGN.md §14); eff_g = 1 below the threshold
                rs_g = (residual + c_gcd - 1) // c_gcd
                use_g = (residual > c_thr) & (c_gcd > 1) & (rs_g <= c_maxr)
                eff_g = jnp.where(use_g, c_gcd, 1).astype(i64)
                eff_res = ((residual + eff_g - 1) // eff_g).astype(i32)
                # the narrowest tier wider than the effective (coarsened)
                # residual: the static width the row's DPs run at
                t_idx = jnp.minimum(
                    jnp.searchsorted(tier_w, eff_res, side="right"),
                    len(tiers) - 1)
                # the row's ROW_COUNTERS, as scalars: a vector here would
                # cost the row loop a kernel of its own per row
                dp_row = (residual > 0) & feasible
                row_counts = tuple(jnp.where(dp_row, v, 0) for v in (
                    i32(1), use_g.astype(i32), eff_res + 1, tier_w[t_idx]))

            def dp_part(_):
                # masked-not-compacted: non-DP bundles sort last (key INF)
                # with zero pods and cost, so the sorted prefix and its
                # prefix sums are the host's compacted arrays while shapes
                # stay static
                with scope("sort"):
                    bmask = in_dp[b_item] & b_real
                    bpods = jnp.where(bmask, b_pods, 0)
                    bcost = jnp.where(bmask, coef[b_item] * b_copies, 0)
                    rate = jnp.where(bmask, bcost // b_pods, INF)
                    order = jnp.argsort(rate, stable=True)
                    p_sorted = bpods[order]
                    c_sorted = bcost[order]
                    r_sorted = rate[order]
                    cum_p = cumsum(p_sorted.astype(i64))
                    cum_c = cumsum(c_sorted)
                    cum_r = cumsum(r_sorted * p_sorted)

                def lp_lower(k, need):
                    # fractional greedy bound on covering need pods, with
                    # k = searchsorted(cum_p, need): #{i : cum_p[i] < need}
                    km = jnp.maximum(k - 1, 0)
                    prev_p = jnp.where(k > 0, cum_p[km], 0)
                    prev_r = jnp.where(k > 0, cum_r[km], 0)
                    return prev_r + (need - prev_p) * r_sorted[k]

                with scope("lp_prune"):
                    k_ub = jnp.searchsorted(cum_p, residual)
                    ub = cum_c[k_ub]
                    if table:
                        # the bound once per distinct size, each k by a
                        # (_SIZE_TABLE, B) compare-and-count; a bundle
                        # reads its size's bound from the one-hot size_hit
                        need = jnp.maximum(residual - size_tab, 0)
                        lp_tab = lp_lower(jnp.sum(cum_p < need[:, None],
                                                  axis=1, dtype=i32), need)
                        lp = jnp.sum(jnp.where(size_hit, lp_tab, 0), axis=1)
                    else:
                        need = jnp.maximum(residual - b_pods, 0)
                        lp = lp_lower(jnp.searchsorted(cum_p, need), need)
                    keep0 = bmask & (bcost + lp <= ub)
                    # DP stages run at granularity eff_g (1 = exact); the
                    # prune math above stays unscaled so the keep set is the
                    # exact engine's
                    b_pods_s = (b_pods // eff_g).astype(i32)
                    # the core DP runs only when the greedy bound leaves
                    # more than _CORE_TRIGGER bundles alive: otherwise its
                    # loop takes zero trips and leaves dp[eff_res] = INF
                    K = jnp.minimum(jnp.sum(bmask),
                                    jnp.maximum(k_ub + _CORE_PAD, _CORE_MIN))
                    core_trip = jnp.where(jnp.sum(keep0) > _CORE_TRIGGER, K,
                                          0).astype(i32)

                def tier_case(tools):
                    cover_value, cover_bits = tools

                    def run(_o):
                        with scope("core_dp"):
                            core_ub = cover_value(b_pods_s[order], c_sorted,
                                                  core_trip, eff_res)
                            keep = jnp.where(core_ub < ub,
                                             bmask & (bcost + lp <= core_ub),
                                             keep0)
                        with scope("compact"):
                            # kept-first stable permutation preserves market
                            # bundle order within the kept prefix — the
                            # decode order the backtracker's tie-breaking
                            # depends on
                            ki = jnp.cumsum(keep.astype(i32))
                            ni = jnp.cumsum((~keep).astype(i32))
                            kept_n = ki[B - 1]
                            pos = jnp.where(keep, ki - 1, kept_n + ni - 1)
                            perm = jnp.zeros(B, i32).at[pos].set(
                                jnp.arange(B, dtype=i32))
                            kp = b_pods_s[perm]
                            kc = bcost[perm]
                        with scope("cover_dp"):
                            bits = cover_bits(kp, kc, kept_n)

                        def bt_body(st):
                            i, j, take = st
                            bit = bits[i, j]
                            take = take.at[i].set(bit)
                            j = jnp.where(bit, jnp.maximum(j - kp[i], 0), j)
                            return i - 1, j, take

                        with scope("backtrack"):
                            _i, _j, take = lax.while_loop(
                                lambda st: (st[0] >= 0) & (st[1] > 0),
                                bt_body,
                                (kept_n - 1, eff_res,
                                 jnp.zeros(B, dtype=bool)))
                            return sat.at[b_item[perm]].add(
                                jnp.where(take, b_copies[perm], 0))
                    return run

                # route the row to its tier (t_idx); lax.switch preserves
                # real branching, so a row pays only its own tier's width
                return lax.switch(t_idx, [tier_case(t) for t in tier_tools],
                                  None)

            counts = lax.cond(dp_row, dp_part, lambda _o: sat, None)
            return counts, feasible, row_counts

        # -- row batching ----------------------------------------------------
        def solve_rows(coefs, actives, reqs):
            """Solve a stack of engine rows sequentially (``lax.fori_loop``
            writing into preallocated outputs).  Sequential, not vmapped,
            so real ``lax.switch``/``lax.cond`` branching survives (the
            saturation fast path and the residual-tier ladder) and every
            while_loop carry stays un-batched, letting XLA update the
            dp/bits buffers in place."""
            D = coefs.shape[0]

            def body(i, out):
                cnts, feas, rows = out
                c, f, r = solve_row(coefs[i], actives[i], reqs[i])
                return (cnts.at[i].set(c), feas.at[i].set(f),
                        tuple(map(jnp.add, rows, r)))
            # "rows": the row loop and each row's branch routing; the
            # stages nested in it carry their own scopes
            with scope("rows"):
                return lax.fori_loop(
                    0, D, body,
                    (jnp.zeros((D, N), i32), jnp.zeros(D, bool),
                     (i32(0),) * len(self.ROW_COUNTERS)))

        # -- pool scoring ----------------------------------------------------
        def score(cnts, feas, reqf):
            # speculation-only e_total (float32): steers device bracket
            # control, never replayed to the host (which rescores exactly);
            # elementwise sums, so no matmul precision mode is involved
            with scope("score"):
                c = cnts.astype(jnp.float32)
                sp = jnp.sum(c * perf, axis=1)
                sc = jnp.sum(c * price, axis=1)
                sq = jnp.sum(c * podsf, axis=1)
                ok = (sq >= reqf) & (sc > 0.0) & (sq > 0.0)
                s = jnp.where(ok, (sp / sc) * (reqf / sq), 0.0)
                return jnp.where(feas, s, -jnp.inf)

        return solve_rows, score

    # -- fused programs ------------------------------------------------------
    def _prescan_program(self, N, B, RC, D, G, table=True):
        key = _program_key("prescan", (N, B, RC, D, G), table)
        fn = self._fused_cache.get(key)
        if fn is None:
            jnp = self._jnp

            # the function's name is the program's on the trace's
            # "XLA Modules" line (jit_kubepacs_prescan)
            def kubepacs_prescan(md, w, q, active, reqs, ks, coarse):
                solve_rows, _score = self._solver_core(md, N, B, RC, coarse,
                                                       table)
                di = jnp.arange(D * G) // G
                k = ks[jnp.arange(D * G) % G][:, None]
                coefs = exact.coefficients(k, w[di], q[di])
                counts, feas, rows = solve_rows(coefs, active[di],
                                                reqs[di])
                return (counts.reshape(D, G, N), feas.reshape(D, G),
                        jnp.stack(rows))

            fn = self._jax.jit(kubepacs_prescan)
            self._fused_cache[key] = fn
            self.program_builds += 1
            self.table_prune_programs += table
        return fn

    def _golden_program(self, N, B, RC, D, MAXR, table=True):
        key = _program_key("golden", (N, B, RC, D, MAXR), table)
        fn = self._fused_cache.get(key)
        if fn is None:
            jax, jnp = self._jax, self._jnp
            lax = jax.lax
            i32, i64 = jnp.int32, jnp.int64
            ME = MAXR + 2

            # "control": the bracket updates and the ev_* writes of the
            # rounds; the row solver and the scoring nest their own scopes
            def kubepacs_golden(md, w, q, active, reqs, a0, b0, tolk,
                                coarse):
                with jax.named_scope("control"):
                    solve_rows, score = self._solver_core(md, N, B, RC,
                                                          coarse, table)
                    reqf = reqs.astype(jnp.float32)
                    dn = jnp.arange(D)
                    g0 = exact.golden_width(b0 - a0)
                    x1, x2 = b0 - g0, a0 + g0     # the host's bracket init
                    neg_inf = jnp.full(D, -jnp.inf, jnp.float32)

                    # rounds 0 and 1 solve every decision's x1 and x2; each
                    # later round advances the active brackets exactly like
                    # the host loop and solves their one new probe — a single
                    # row-solver instance in the program
                    def cond(st):
                        r, a, b = st[0], st[1], st[2]
                        return (r < 2) | ((r < MAXR + 2)
                                          & jnp.any((b - a) > tolk))

                    def body(st):
                        (r, a, b, x1, x2, f1, f2, ev_k, ev_c, ev_f, evn,
                         rows) = st
                        init = r < 2
                        act = init | ((b - a) > tolk)
                        right = ~init & act & (f1 >= f2)  # shrink from right
                        left = ~init & act & ~(f1 >= f2)  # shrink from left
                        nb = jnp.where(right, x2, b)
                        na = jnp.where(left, x1, a)
                        g = exact.golden_width(nb - na)
                        nx1 = jnp.where(right, nb - g, jnp.where(left, x2, x1))
                        nx2 = jnp.where(left, na + g, jnp.where(right, x1, x2))
                        pf1 = jnp.where(left, f2, f1)
                        pf2 = jnp.where(right, f1, f2)
                        probe = jnp.where(
                            init, jnp.where(r == 0, x1, x2),
                            jnp.where(right, nx1, jnp.where(left, nx2, 0)))
                        # inactive decisions re-solve req=0 (the cheap
                        # saturation fast path) instead of a full row
                        reqv = jnp.where(act, reqs, 0)
                        cp, fep, rp = solve_rows(
                            exact.coefficients(probe[:, None], w, q), active,
                            reqv)
                        fp = score(cp, fep, reqf)
                        nf1 = jnp.where(right | (init & (r == 0)), fp, pf1)
                        nf2 = jnp.where(left | (init & (r == 1)), fp, pf2)
                        ev_k = ev_k.at[dn, evn].set(
                            jnp.where(act, probe, ev_k[dn, evn]))
                        ev_c = ev_c.at[dn, evn, :].set(
                            jnp.where(act[:, None], cp, ev_c[dn, evn, :]))
                        ev_f = ev_f.at[dn, evn].set(
                            jnp.where(act, fep, ev_f[dn, evn]))
                        evn = evn + act.astype(i32)
                        return (r + 1, na, nb, nx1, nx2, nf1, nf2,
                                ev_k, ev_c, ev_f, evn,
                                tuple(map(jnp.add, rows, rp)))

                    st = lax.while_loop(cond, body, (
                        i32(0), a0, b0, x1, x2, neg_inf, neg_inf,
                        jnp.zeros((D, ME), i64), jnp.zeros((D, ME, N), i32),
                        jnp.zeros((D, ME), bool), jnp.zeros(D, i32),
                        (i32(0),) * len(self.ROW_COUNTERS)))
                    return st[7], st[8], st[9], st[10], jnp.stack(st[11])

            fn = jax.jit(kubepacs_golden)
            self._fused_cache[key] = fn
            self.program_builds += 1
            self.table_prune_programs += table
        return fn

    # -- host-side drivers ---------------------------------------------------
    def _shape_key(self, market, reqs, n_dec, coarsening=None):
        """A batch's static shapes ``(N, B, RC, D)``; ``coarsening=None``
        is :data:`DEFAULT_COARSENING`, as in :meth:`fused_gss_record`, so
        every caller sizes the program the served path compiles."""
        cfg = DEFAULT_COARSENING if coarsening is None else coarsening
        N = _bucket(max(market.n, 1), self._N_STEPS)
        B = _bucket(max(market.n_bundles, 1), self._BF_STEPS)
        width = max(max(reqs, default=1), 1)
        if (cfg.enabled and width > cfg.threshold
                and market.pods_gcd > 1):
            # gcd-coarsened rows need ceil(req/g) DP rows; rows whose
            # residual stays below the threshold need the threshold width
            width = max(cfg.threshold, -(-width // market.pods_gcd))
        RC = _bucket(width, self._RF_STEPS) + 1
        D = _bucket(max(n_dec, 1), self._D_STEPS)
        return N, B, RC, D

    @staticmethod
    def _coarse_scalars(market, coarsening):
        """The ``(threshold, max_rows, gcd)`` triple handed to the compiled
        programs as *traced* scalars (config or market changes never force
        a recompile); ``None`` is :data:`DEFAULT_COARSENING`.  Coarsening
        off → an unreachable threshold, so every row takes the exact
        path."""
        cfg = DEFAULT_COARSENING if coarsening is None else coarsening
        if not cfg.enabled:
            return np.asarray([2 ** 62, 1, 1], np.int64)
        return np.asarray([cfg.threshold, cfg.max_rows,
                           max(market.pods_gcd, 1)], np.int64)

    def _decision_arrays(self, market, reqs, excludes, N, D):
        """Per-decision uploads: padded ``(W, Q, active)`` of each
        decision's mask (:meth:`CompiledMarket.solve_inputs`, one host
        quantization per distinct mask) and demands.  Pad decisions have
        no active item and zero demand."""
        n = market.n
        w = np.zeros((D, N), np.int64)
        q = np.zeros((D, N), np.int64)
        active = np.zeros((D, N), bool)
        rq = np.zeros(D, np.int64)
        rq[:len(reqs)] = reqs
        seen: dict = {}
        for d, mask in enumerate(excludes):
            mkey = None if mask is None else mask.tobytes()
            if mkey not in seen:
                seen[mkey] = market.solve_inputs(mask)
            w[d, :n], q[d, :n], active[d, :n] = seen[mkey]
        return w, q, active, rq

    def _dispatch(self, fn, args):
        """Run a program to completion.  Its last output, the row counters,
        starts for the host at once and joins the totals at the next call
        (or ``device_cache_info()``), by when it has landed: reading it at
        once would make every call wait on one more transfer."""
        self._drain_rows()
        out = fn(*args)
        out[-1].copy_to_host_async()
        self._rows_in_flight.append(out[-1])
        return self._jax.block_until_ready(out)

    def _run_prescan(self, market, reqs, excludes, kgrid, coarsening=None):
        Dr, G = len(reqs), len(kgrid)
        with events_log.span("kubepacs.device.inputs"):
            N, B, RC, D = self._shape_key(market, reqs, Dr, coarsening)
            md, table = self._device_market(market, N, B)
            w, q, active, rq = self._decision_arrays(market, reqs, excludes,
                                                     N, D)
            fn = self._prescan_program(N, B, RC, D, G, table)
            args = (md, w, q, active, rq, np.asarray(kgrid, np.int64),
                    self._coarse_scalars(market, coarsening))
        with events_log.span("kubepacs.device.prescan"):
            out = self._dispatch(fn, args)
        with events_log.span("kubepacs.device.readback"):
            counts, feas, _rows = out
            return (np.asarray(counts)[:Dr, :, :market.n],
                    np.asarray(feas)[:Dr])

    def _run_golden(self, market, reqs, excludes, a_list, b_list,
                    tolerance, coarsening=None):
        Dr = len(reqs)
        with events_log.span("kubepacs.device.inputs"):
            N, B, RC, D = self._shape_key(market, reqs, Dr, coarsening)
            md, table = self._device_market(market, N, B)
            w, q, active, rq = self._decision_arrays(market, reqs, excludes,
                                                     N, D)
            # round budget: any bracket is <= 1 wide and shrinks by at most
            # PHI per round, so ceil(log(tol)/log(PHI)) rounds suffice (+2)
            phi = exact.PHI_Q / (1 << exact.PHI_BITS)
            MAXR = (int(math.ceil(math.log(tolerance) / math.log(phi))) + 2
                    if 0.0 < tolerance < 1.0 else 3)
            a0 = np.zeros(D, np.int64)
            a0[:Dr] = a_list
            b0 = np.zeros(D, np.int64)
            b0[:Dr] = b_list
            fn = self._golden_program(N, B, RC, D, MAXR, table)
            args = (md, w, q, active, rq, a0, b0,
                    np.int64(exact.tolerance_k(tolerance)),
                    self._coarse_scalars(market, coarsening))
        with events_log.span("kubepacs.device.golden"):
            out = self._dispatch(fn, args)
        with events_log.span("kubepacs.device.readback"):
            ev_k, ev_c, ev_f, evn, _rows = out
            return (np.asarray(ev_k)[:Dr],
                    np.asarray(ev_c)[:Dr, :, :market.n],
                    np.asarray(ev_f)[:Dr], np.asarray(evn)[:Dr])

    # -- record entry point --------------------------------------------------
    def fused_gss_record(self, items, market, reqs, excludes, kgrid,
                         tolerance,
                         coarsening=None) -> Optional["_FusedGssRecord"]:
        """Run the device-resident prescan for a ``bracketed_gss_many``
        batch and return the replay record.  Returns None — a counted
        decline — only for an empty market or a batch whose coarsening
        ladder would need the approx tier, which the device does not
        implement; device errors propagate."""
        cfg = DEFAULT_COARSENING if coarsening is None else coarsening
        max_req = max((int(r) for r in reqs), default=0)
        approx = (cfg.enabled and max_req > cfg.threshold
                  and not (market.pods_gcd > 1
                           and -(-max_req // market.pods_gcd)
                           <= cfg.max_rows))
        if market.n == 0 or market.n_bundles == 0 or approx:
            self.declined_batches += 1
            return None
        rec = _FusedGssRecord(self, items, market, reqs, excludes, kgrid,
                              tolerance, cfg)
        self.fused_records += 1
        return rec


class PrescanMismatch(RuntimeError):
    """Device prescan counts failed the sampled host cross-check."""


class _FusedGssRecord:
    """Replay record binding one device-resident GSS batch to its host
    control loop (DESIGN.md §13).

    Construction runs the fused prescan; :meth:`run_golden` runs the fused
    golden program once the host has chosen brackets.  Both fill a
    grid-index → counts lookup per decision.  Counts stay the readback's
    arrays (a row is a view of the program's output, None where
    infeasible): no per-item Python list is built, and the replay turns
    only the rows that become pools into their few non-zero entries
    (:func:`~repro.core.efficiency.nonzero_pool`).  The host replay
    (``bracketed_gss_many``) then re-executes the sequential control flow
    with exact host scores and resolves every probe through
    :meth:`solve_many`: device-recorded counts on a hit, a counted NumPy
    engine solve on a miss (device/host control divergence) — so a
    speculation mismatch can only cost time, never change a selection.
    """

    def __init__(self, backend, items, market, reqs, excludes, kgrid,
                 tolerance, coarsening=None):
        self._backend = backend
        self._items = list(items)
        self._market = market
        self._reqs = [int(r) for r in reqs]
        self._excludes = list(excludes)
        self._tolerance = float(tolerance)
        self._coarsening = coarsening
        kgrid = [int(k) for k in kgrid]
        counts, feas = backend._run_prescan(market, self._reqs,
                                            self._excludes, kgrid,
                                            coarsening=coarsening)
        with events_log.span("kubepacs.device.readback"):
            self.prescan = [[row if ok else None
                             for row, ok in zip(counts_d, feas_d)]
                            for counts_d, feas_d in zip(counts,
                                                        feas.tolist())]
            self._lookup: List[dict] = [dict(zip(kgrid, row))
                                        for row in self.prescan]
        with events_log.span("kubepacs.fused.verify"):
            self._verify_sample(kgrid)

    def _host_solve(self, reqs, k_lists, excludes):
        from .ilp import solve_ilp_many   # deferred: no import cycle
        return solve_ilp_many(
            self._items, reqs,
            [[exact.k_alpha(k) for k in ks] for ks in k_lists],
            market=self._market, excludes=excludes,
            backend=self._backend._host, coarsening=self._coarsening)

    def _verify_sample(self, kgrid: List[int]) -> None:
        """Prescan cross-check: before the record is trusted, one sampled
        (decision, α) row per batch — rotated through decisions and grid
        points by the backend's ``verify_solves`` counter — is re-solved
        on the NumPy engine and compared exactly.  A divergence means the
        device broke the exact-integer contract: it raises
        :class:`PrescanMismatch` rather than let a selection change."""
        if not self._reqs or not kgrid:
            return
        be = self._backend
        d = be.verify_solves % len(self._reqs)
        g = be.verify_solves % len(kgrid)
        be.verify_solves += 1
        ref = self._host_solve([self._reqs[d]], [[kgrid[g]]],
                               [self._excludes[d]])[0][0]
        row = self.prescan[d][g]
        if ((ref is None) != (row is None)
                or (ref is not None and not np.array_equal(ref, row))):
            raise PrescanMismatch(
                f"device prescan counts diverged from the host engine at "
                f"decision {d}, alpha {exact.k_alpha(kgrid[g])!r}")

    def run_golden(self, a_list, b_list) -> None:
        ev_k, ev_c, ev_f, evn = self._backend._run_golden(
            self._market, self._reqs, self._excludes, a_list, b_list,
            self._tolerance, coarsening=self._coarsening)
        with events_log.span("kubepacs.device.readback"):
            for lut, ks, rows, oks, n in zip(self._lookup, ev_k.tolist(),
                                             ev_c, ev_f.tolist(),
                                             evn.tolist()):
                for k, row, ok in zip(ks[:n], rows, oks):
                    lut.setdefault(k, row if ok else None)

    def solve_many(self, idxs, k_lists):
        """``solve_ilp_many``-shaped resolution of a golden round's probes:
        one list of dense count arrays (None where infeasible) per
        (decision index, grid-index list)."""
        out = [[None] * len(ks) for ks in k_lists]
        miss_pos: List[Tuple[int, List[int]]] = []
        for i, (d, ks) in enumerate(zip(idxs, k_lists)):
            missing = []
            for j, k in enumerate(ks):
                hit = self._lookup[d].get(k, _MISS)
                if hit is _MISS:
                    missing.append(j)
                else:
                    out[i][j] = hit
            if missing:
                miss_pos.append((i, missing))
        if miss_pos:
            self._backend.fallback_solves += sum(len(js) for _i, js in
                                                 miss_pos)
            with events_log.span("kubepacs.fused.fallback"):
                solved = self._host_solve(
                    [self._reqs[idxs[i]] for i, _js in miss_pos],
                    [[k_lists[i][j] for j in js] for i, js in miss_pos],
                    [self._excludes[idxs[i]] for i, _js in miss_pos])
            for (i, js), counts_d in zip(miss_pos, solved):
                for j, c in zip(js, counts_d):
                    out[i][j] = None if c is None else np.asarray(c)
                    self._lookup[idxs[i]].setdefault(k_lists[i][j],
                                                     out[i][j])
        return out


# ---------------------------------------------------------------------------
# Default-backend registry (env-overridable)
# ---------------------------------------------------------------------------

_DEFAULT: Optional[SolverBackend] = None

#: spec → backend class of :func:`make_backend`
_SPECS = {"numpy": NumpyBackend, "jax:fused": FusedJaxBackend}


def jax_available() -> bool:
    try:
        import jax  # noqa: F401
        return True
    except ImportError:
        return False


def make_backend(spec: str) -> SolverBackend:
    """Build a backend from a spec string: ``numpy`` | ``jax:fused``.
    ``jax:fused`` imports jax and raises ``ImportError`` where it is
    missing."""
    cls = _SPECS.get(spec)
    if cls is None:
        raise ValueError(f"unknown solver backend spec {spec!r} "
                         f"(expected {' | '.join(_SPECS)})")
    return cls()


def get_backend() -> SolverBackend:
    """The process-default backend: ``KUBEPACS_SOLVER_BACKEND`` if set,
    else numpy (selections are backend-invariant; numpy keeps the default
    dependency surface of the control plane at exactly numpy)."""
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = make_backend(
            os.environ.get("KUBEPACS_SOLVER_BACKEND", "numpy"))
    return _DEFAULT


def set_backend(backend: Optional[SolverBackend | str]) -> SolverBackend:
    """Override the process default (string specs accepted); ``None``
    resets to the environment/default resolution on next use."""
    global _DEFAULT
    if isinstance(backend, str):
        backend = make_backend(backend)
    _DEFAULT = backend
    return get_backend() if backend is None else backend
