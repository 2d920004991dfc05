"""Process-wide registry of degradation events (DESIGN.md §16) and of the
hot path's spans (DESIGN.md §13).

The solver stack degrades in several deliberate ways — the NumPy fallback
when a jax backend is requested without jax, the process-wide x64 flip,
the Pallas kernel self-check disabling the kernel, the fused plane's
prescan cross-check disabling the device path, and the chaos guard's
ladder descents.  Each of those used to announce itself with a one-time
``warnings.warn`` and nothing else, which makes degradation invisible in
a fleet run's results: stderr is not a metrics channel.

This module centralizes those events into a tiny counter registry:

* every occurrence is **counted** (``count``), whether or not it warns;
* ``warn_once`` keeps the existing one-warning-per-process contract for
  human eyes while still counting every occurrence;
* the sim engines snapshot the registry at run start and merge the
  *delta* into ``SimResult.cache_stats`` under ``event_*`` keys, so a
  fleet sweep reports "the jax backend silently fell back to NumPy" as
  data, not as a line lost in CI logs.

Counters are process-global and monotonically increasing (like the
warning flags they replace).  They are deliberately **not** part of any
decision, trace record, or metric dict — the determinism contract
(DESIGN.md §9) is untouched; ``cache_stats`` is already exempt from
trace/equality comparisons.  ``reset`` exists for test isolation only.

Spans
-----
``span(name)`` times one stage of the hot path.  It always enters a
``jax.profiler.TraceAnnotation`` of the same name, so under a profiler
the stage lands on the trace's host plane on the device's clock, and it
always adds to in-memory aggregates per name: calls, total and *self*
nanoseconds (self = duration less the time of the spans nested in it,
kept with a per-thread stack of open spans).  ``span_totals`` and
``span_delta_since`` read them as ``snapshot``/``delta_since`` read the
counters.  A root span (``root=True``) hands the profiler a per-process
request number (``id``), which the spans nested in it share by time.
Spans sit at batch or phase granularity, never per probe or per row;
a span costs about a microsecond when nobody profiles.  This module
imports no jax: the annotation class is looked up on the first span,
and without jax a span keeps its aggregates only.
"""

from __future__ import annotations

import itertools
import threading
import time
import warnings
from typing import Dict, List, Tuple

_lock = threading.Lock()
_counters: Dict[str, int] = {}
_warned_keys = set()


def count(name: str, n: int = 1) -> int:
    """Increment counter ``name`` by ``n``; returns the new value."""
    with _lock:
        value = _counters.get(name, 0) + int(n)
        _counters[name] = value
        return value


def warn_once(name: str, message: str, category=RuntimeWarning,
              stacklevel: int = 2) -> bool:
    """Count this occurrence and emit ``message`` the first time only.

    Returns True when the warning was actually emitted (first occurrence
    for this key in the process), False on every repeat — the same
    contract the module-level ``_WARNED`` flags used to provide, minus
    the scattering.
    """
    count(name)
    with _lock:
        if name in _warned_keys:
            return False
        _warned_keys.add(name)
    warnings.warn(message, category, stacklevel=stacklevel + 1)
    return True


def counters() -> Dict[str, int]:
    """A point-in-time copy of every counter."""
    with _lock:
        return dict(_counters)


def snapshot() -> Dict[str, int]:
    """Alias of :func:`counters` that reads as intent at call sites that
    later diff against it with :func:`delta_since`."""
    return counters()


def delta_since(snap: Dict[str, int]) -> Dict[str, int]:
    """Counters that moved since ``snap`` (only non-zero deltas)."""
    now = counters()
    out = {}
    for name, value in now.items():
        moved = value - snap.get(name, 0)
        if moved:
            out[name] = moved
    return out


# -- spans --------------------------------------------------------------------

#: per name: [calls, total ns, self ns]
_spans: Dict[str, List[int]] = {}
_open = threading.local()            # .stack: this thread's open spans
_request_ids = itertools.count(1)
_annotation = None                   # TraceAnnotation class, or a null one


class _NullAnnotation:
    def __init__(self, name, **kwargs):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _annotation_class():
    global _annotation
    if _annotation is None:
        try:
            from jax.profiler import TraceAnnotation
        except ImportError:
            TraceAnnotation = _NullAnnotation
        _annotation = TraceAnnotation
    return _annotation


class span:
    """Context manager timing one stage under ``name`` (module docstring).
    An exception inside the span still closes it and is not swallowed."""

    __slots__ = ("name", "child_ns", "_t0", "_ann")

    def __init__(self, name: str, root: bool = False):
        self.name = name
        annotation = _annotation or _annotation_class()
        self._ann = (annotation(name, id=next(_request_ids)) if root
                     else annotation(name))

    def __enter__(self) -> "span":
        self._ann.__enter__()
        stack = getattr(_open, "stack", None)
        if stack is None:
            stack = _open.stack = []
        stack.append(self)
        self.child_ns = 0
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        took = time.perf_counter_ns() - self._t0
        stack = _open.stack
        if stack[-1] is self:
            stack.pop()
        else:                        # closed out of order: drop it alone
            stack.remove(self)
        if stack:
            stack[-1].child_ns += took
        with _lock:
            agg = _spans.get(self.name)
            if agg is None:
                agg = _spans[self.name] = [0, 0, 0]
            agg[0] += 1
            agg[1] += took
            agg[2] += took - self.child_ns
        self._ann.__exit__(*exc)
        return False


def span_totals() -> Dict[str, Tuple[int, int, int]]:
    """Per span name: ``(calls, total_ns, self_ns)`` since process start."""
    with _lock:
        return {name: tuple(agg) for name, agg in _spans.items()}


def span_delta_since(totals: Dict[str, Tuple[int, int, int]]
                     ) -> Dict[str, Tuple[int, int, int]]:
    """What each span name added since ``totals`` (names that ran)."""
    out = {}
    for name, now in span_totals().items():
        then = totals.get(name, (0, 0, 0))
        if now[0] != then[0]:
            out[name] = tuple(a - b for a, b in zip(now, then))
    return out


def reset() -> None:
    """Clear all counters, warn-once keys and span aggregates (test
    isolation only)."""
    with _lock:
        _counters.clear()
        _warned_keys.clear()
        _spans.clear()


__all__ = ["count", "counters", "delta_since", "reset", "snapshot", "span",
           "span_delta_since", "span_totals", "warn_once"]
