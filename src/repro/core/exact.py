"""Exact integer arithmetic of the decision plane (DESIGN.md §13).

Every value that decides a selection is an integer, computed by the same
expressions on the host (NumPy ``int64``) and on the device (JAX ``int64``,
which a TPU executes exactly as pairs of 32-bit words).  Selections are
therefore backend-invariant by construction; no step depends on how a
platform rounds floating point.

* **α** lives on the dyadic grid ``K / 2**ALPHA_BITS`` (``0 <= K <=
  ALPHA_ONE``); :func:`alpha_k` rounds any other α onto it.
* **Objective coefficients** (Eq. 4-5).  Per (market, exclusion mask) the
  host quantizes the normalised vectors once, ``Q_i = rint(qn_i·2**F)``
  and ``W_i = rint(pn_i·2**F) + Q_i`` (``F`` =
  :attr:`~repro.core.ilp.CompiledMarket.scale_bits`), and the coefficient
  at ``K`` is ``C_i(K) = Q_i - floor(K·W_i / 2**ALPHA_BITS)`` ≈
  ``2**F·(-α·pn_i + (1-α)·qn_i)`` (:func:`coefficients`).
* **Golden update**: a bracket ``[a, b]`` probes ``b - w`` and ``a + w``
  with ``w = floor(PHI_Q·(b - a) / 2**PHI_BITS)`` (:func:`golden_width`),
  so every probe is again a grid point.
* **Costs and the cover DP**: bundle costs ``C_i·copies``, their prefix
  sums, the LP bound and the DP values are ``int64`` with ``INF`` as the
  unreachable sentinel.  :func:`scale_bits` picks ``F`` so that no product
  or sum the engine forms can overflow.

Functions here take NumPy or JAX arrays alike (operators only).
"""

from __future__ import annotations

import math
from typing import List

import numpy as np

#: α resolution: α = K / 2**ALPHA_BITS
ALPHA_BITS = 40
ALPHA_ONE = 1 << ALPHA_BITS
#: limb width of the exact K·W product (K < 2**41 splits into two limbs
#: of at most 2**20, so each limb product stays below 2**62)
_LIMB = 20
#: golden ratio (√5-1)/2 as a PHI_BITS-bit fixed-point fraction (floor)
PHI_BITS = 22
PHI_Q = int(((math.sqrt(5.0) - 1.0) / 2.0) * (1 << PHI_BITS))
#: DP "unreachable" value; every finite cost sum stays below 2**60, so
#: INF + cost never overflows int64
INF = 1 << 62
#: bounds :func:`scale_bits` guarantees: W < 2**_W_BITS, sums < 2**_SUM_BITS
_W_BITS = 42
_SUM_BITS = 60
#: a market whose value spread leaves fewer fraction bits is refused
MIN_SCALE_BITS = 16


def alpha_k(alpha: float) -> int:
    """Grid index of α (round to nearest)."""
    alpha = float(alpha)
    if not 0.0 <= alpha <= 1.0:
        raise ValueError(f"alpha must lie in [0, 1], got {alpha!r}")
    return int(np.rint(alpha * ALPHA_ONE))


def k_alpha(k: int) -> float:
    """α of grid index ``k`` (exact: ``k < 2**53``)."""
    return int(k) / ALPHA_ONE


def alpha_grid(points: int) -> List[int]:
    """The ``points``-point prescan grid over [0, 1] as grid indices."""
    if points < 2:
        raise ValueError("an α grid needs at least 2 points")
    return [(i * ALPHA_ONE) // (points - 1) for i in range(points)]


def tolerance_k(tolerance: float) -> int:
    """Bracket-width threshold in grid units: for an integer width ``d``,
    ``d > tolerance_k(t)`` iff ``d / ALPHA_ONE > t``."""
    return min(int(math.floor(float(tolerance) * ALPHA_ONE)), INF)


def golden_width(d):
    """Golden-section step for a bracket of width ``d`` grid units."""
    return (PHI_Q * d) >> PHI_BITS


def coefficients(k, w, q):
    """``C(K) = Q - floor(K·W / 2**ALPHA_BITS)`` for ``0 <= K <= ALPHA_ONE``
    and ``0 <= W < 2**42``, exact in int64 (two-limb product)."""
    k1 = k >> _LIMB
    k0 = k & ((1 << _LIMB) - 1)
    return q - ((k1 * w + ((k0 * w) >> _LIMB)) >> (ALPHA_BITS - _LIMB))


def scale_bits(norm_max: float, total_nodes: int) -> int:
    """Fraction bits ``F`` of the quantized objective.

    ``norm_max`` bounds every ``pn_i`` and ``qn_i`` of any exclusion mask
    (masking only raises the normalising minima) and ``total_nodes`` the
    node count of any selection.  Then ``W_i < 2**42`` (the limb bound of
    :func:`coefficients`) and every cost sum stays below ``2**60``.
    """
    mbits = math.frexp(max(float(norm_max), 1.0))[1]   # norm_max < 2**mbits
    tbits = int(total_nodes).bit_length()             # total < 2**tbits
    f = min(_W_BITS - 1, _SUM_BITS - tbits) - mbits
    if f < MIN_SCALE_BITS:
        raise ValueError(
            f"market too wide for exact integer costs: objective spread "
            f"{norm_max!r} over {total_nodes} nodes leaves {f} fraction bits "
            f"(< {MIN_SCALE_BITS})")
    return f


def quantize(perf_norm: np.ndarray, price_norm: np.ndarray,
             bits: int) -> tuple:
    """``(W, Q)`` int64 coefficient vectors of one (market, mask)."""
    scale = float(1 << bits)
    q = np.rint(price_norm * scale).astype(np.int64)
    w = np.rint(perf_norm * scale).astype(np.int64) + q
    return w, q
