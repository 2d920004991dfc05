"""The hot path's spans (``repro.core.events_log.span``, DESIGN.md §13):
self time of nested spans, a span closed by an exception, one stack of
open spans per thread, the aggregates and their delta, the request number
of a root span, and the span on the profiler's trace."""

import threading
import time

import pytest

from repro.core import events_log

from ._optional import requires_jax


def _totals(*names):
    got = events_log.span_totals()
    return [got.get(n, (0, 0, 0)) for n in names]


def test_nested_spans_split_self_time():
    before = events_log.span_totals()
    with events_log.span("test.nest.outer"):
        time.sleep(0.01)
        with events_log.span("test.nest.inner"):
            time.sleep(0.02)
        with events_log.span("test.nest.inner"):
            time.sleep(0.005)
    delta = events_log.span_delta_since(before)
    outer, inner = delta["test.nest.outer"], delta["test.nest.inner"]
    assert outer[0] == 1 and inner[0] == 2
    # self = total less the nested spans' totals, to the nanosecond
    assert outer[2] == outer[1] - inner[1]
    assert inner[2] == inner[1]                   # nothing nested in it
    assert inner[1] >= 25e6 and outer[2] >= 10e6
    assert outer[1] >= inner[1] + 10e6


def test_exception_inside_a_span_closes_it():
    before = events_log.span_totals()
    with pytest.raises(ValueError, match="boom"):
        with events_log.span("test.raise.outer"):
            with events_log.span("test.raise.inner"):
                raise ValueError("boom")
    with events_log.span("test.raise.after"):
        pass
    delta = events_log.span_delta_since(before)
    assert delta["test.raise.outer"][0] == delta["test.raise.inner"][0] == 1
    # the stack is empty again: the next span has no parent to charge
    after = delta["test.raise.after"]
    assert after[2] == after[1]
    outer = delta["test.raise.outer"]
    assert outer[2] == outer[1] - delta["test.raise.inner"][1]


def test_each_thread_keeps_its_own_stack():
    """A span open in one thread is not the parent of spans that other
    threads run meanwhile."""
    before = events_log.span_totals()
    opened, done = threading.Event(), threading.Event()

    def hold():
        with events_log.span("test.thread.outer"):
            opened.set()
            assert done.wait(10)

    def work():
        assert opened.wait(10)
        for _ in range(3):
            with events_log.span("test.thread.inner"):
                time.sleep(0.002)
        done.set()

    threads = [threading.Thread(target=hold), threading.Thread(target=work)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10)
        assert not t.is_alive()
    delta = events_log.span_delta_since(before)
    outer = delta["test.thread.outer"]
    assert outer[2] == outer[1]                   # no child of its thread
    assert delta["test.thread.inner"][0] == 3


def test_totals_and_delta():
    [(n0, t0, s0)] = _totals("test.agg")
    before = events_log.span_totals()
    for _ in range(4):
        with events_log.span("test.agg"):
            pass
    [(n1, t1, s1)] = _totals("test.agg")
    assert (n1 - n0) == 4 and t1 >= t0 and s1 >= s0
    delta = events_log.span_delta_since(before)
    assert delta["test.agg"] == (4, t1 - t0, s1 - s0)
    # names that did not run since the snapshot are left out
    assert "test.nest.outer" not in delta
    assert events_log.span_delta_since(events_log.span_totals()) == {}


def test_root_spans_carry_a_request_number(monkeypatch):
    seen = []

    class Recorder:
        def __init__(self, name, **kwargs):
            seen.append((name, kwargs))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(events_log, "_annotation", Recorder)
    with events_log.span("test.root", root=True):
        with events_log.span("test.child"):
            pass
    with events_log.span("test.root", root=True):
        pass
    (r1, a1), (c, ac), (r2, a2) = seen
    assert (r1, c, r2) == ("test.root", "test.child", "test.root")
    assert ac == {}
    assert a2["id"] > a1["id"] >= 1


@requires_jax
def test_spans_land_on_the_profiler_trace(tmp_path):
    import glob

    import jax

    jax.profiler.start_trace(str(tmp_path))
    with events_log.span("kubepacs.test_root", root=True):
        with events_log.span("kubepacs.test_child"):
            pass
    jax.profiler.stop_trace()
    [path] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    profile = jax.profiler.ProfileData.from_file(path)
    found = {}
    for plane in profile.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("kubepacs.test_"):
                    found[ev.name] = (ev.start_ns, ev.end_ns, dict(ev.stats))
    root, child = found["kubepacs.test_root"], found["kubepacs.test_child"]
    assert root[0] <= child[0] <= child[1] <= root[1]
    assert root[2]["id"] >= 1 and "id" not in child[2]
