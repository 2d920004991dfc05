#!/usr/bin/env python3
"""Benchmark harness of the KubePACS decision plane on one TPU.

    python bench/run.py --workload <config>.<traffic> --seed <n> \\
        --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` once, in this process, and prints one
JSON object as the last line of standard output:

1. set-up: the deployment's catalog (``bench/configs/``, from the seed
   the mix pins),
   the program's objects for the mix (``bench/traffic/``, kinds in
   ``bench/cells.py``), and a warm-up over every shape bucket the mix can
   reach, from a seed stream the window does not use;
2. the window: a closed loop of requests drawn from ``--seed`` for
   ``--seconds``; with ``--trace 1`` followed by a segment of the same
   loop under the JAX profiler (``TRACE_SECONDS``), which the device
   metrics read;
3. the check: the program's decisions against the plain reference
   (``bench/reference.py``), plus the counters that must not move inside
   the window and the traced segment (no compile, no declined batch, no
   host DP group);
4. the metrics of the cell, each read by ``bench/metrics/<name>.py``:
   the end-to-end ones with ``--trace 0``, the per-layer ones with
   ``--trace 1``.

Exits non-zero, with no result, where JAX finds no TPU or fewer chips
than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: the one fixed home of JAX's persistent compilation cache: inside the
#: checkout, so only a checkout's first run of a cell compiles
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
#: backend methods timed (host clock) and spanned (trace) by the harness
DEVICE_CALLS = {"_run_prescan": "bench.prescan_call",
                "_run_golden": "bench.golden_call",
                "_device_market": "bench.market_upload"}
#: counters that must not grow inside the window, with their names here
FROZEN = {"program_builds": "window_program_builds",
          "declined_batches": "window_declined_batches",
          "host_dp_groups": "window_host_dp_groups"}
#: seconds of the traced segment that follows the window with --trace 1
TRACE_SECONDS = 2.0
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


class BenchError(RuntimeError):
    """A run that cannot be measured: it prints no result."""


@dataclasses.dataclass
class RunData:
    """What a metric reader sees of one run."""

    kind: str
    requests: int
    decisions: int
    latencies_s: List[float]
    window_s: float
    setup_s: float
    calls_wall: Dict[str, float]
    counters: Dict[str, int]          # device_cache_info() deltas
    trace: Optional[object]           # bench.trace.Reduction
    traced_decisions: int = 0         # decisions of the traced segment


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def find_cell(spec: Dict, name: str) -> Dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise BenchError(f"no workload {name!r} in BENCHMARK.json")


def cell_metrics(spec: Dict, section: str, cell: str) -> List[Dict]:
    """The metrics of ``section`` the cell reports: those that list it, and
    those with no ``workloads`` key."""
    return [m for m in spec[section]
            if "workloads" not in m or cell in m["workloads"]]


def load_reader(name: str):
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    if not os.path.isfile(path):
        raise BenchError(f"no reader for metric {name!r} ({path})")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def accelerator(jax, chips: int):
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise BenchError(f"no TPU: JAX found {dev.platform!r} "
                         f"({dev.device_kind}); the benchmark runs only on "
                         "the chip")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devices)}")
    return devices


class DeviceCalls:
    """Host-clock wall and a profiler span around each device call of the
    backend instance (the program itself carries no spans)."""

    def __init__(self, backend, annotation):
        self.wall = {span: 0.0 for span in DEVICE_CALLS.values()}
        for attr, span in DEVICE_CALLS.items():
            fn = getattr(backend, attr, None)
            if fn is not None:
                setattr(backend, attr, self._wrap(fn, span, annotation))

    def _wrap(self, fn, span, annotation):
        def call(*args, **kwargs):
            t0 = time.perf_counter()
            try:
                with annotation(span):
                    return fn(*args, **kwargs)
            finally:
                self.wall[span] += time.perf_counter() - t0
        return call


def _delta(after: Dict, before: Dict) -> Dict:
    return {k: after[k] - before.get(k, 0) for k in after
            if isinstance(after[k], (int, float))}


def measure(workload: str, seed: int, seconds: float, trace: bool,
            require_tpu: bool = True, spec: Optional[Dict] = None,
            config: Optional[Dict] = None, mix: Optional[Dict] = None,
            control: bool = False, keep_trace: Optional[str] = None) -> Dict:
    """One run of one cell; returns the result object.  ``spec``,
    ``config`` and ``mix`` default to the files of the checkout;
    ``require_tpu=False`` lets the tests drive a run on the CPU.  With
    ``control`` the comparison judges the control's answers (the reference
    one precision step down) in place of the program's, which must come
    out not correct.  ``keep_trace`` is a path the traced segment's
    ``.xplane.pb`` is copied to (``bench/tests/record_trace.py``)."""
    import numpy as np

    spec = spec or load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = find_cell(spec, workload)
    config = config or load_json(os.path.join(BENCH, "configs",
                                              cell["config"] + ".json"))
    mix = mix or load_json(os.path.join(BENCH, "traffic",
                                        cell["traffic"] + ".json"))
    section = "per_layer" if trace else "end_to_end"
    readers = {m["name"]: (m, load_reader(m["name"]))
               for m in cell_metrics(spec, section, workload)}

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise BenchError(f"no KubePACS program under {src}")
    if src not in sys.path:
        sys.path.insert(0, src)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR

    import jax
    from jax import monitoring
    from jax.profiler import TraceAnnotation

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = [0]
    events: Dict[str, float] = {}

    def on_duration(name, secs, **_kw):
        events[name] = events.get(name, 0.0) + secs
        if name in COMPILE_EVENTS:
            compiles[0] += 1

    def on_event(name, **_kw):
        events[name] = events.get(name, 0.0) + 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
    devices = accelerator(jax, cell["chips"]) if require_tpu else \
        jax.devices()
    t_devices = time.perf_counter()

    from bench import cells, trace as trace_mod
    from repro.core import make_backend

    backend = make_backend("jax:fused")
    calls = DeviceCalls(backend, TraceAnnotation)
    streams = np.random.SeedSequence(seed).spawn(3)
    warm_rng, window_rng, check_rng = (np.random.default_rng(s)
                                       for s in streams)
    kind = cells.KINDS[mix["kind"]]
    unit = kind(config, mix, seed, backend)
    t_unit = time.perf_counter()
    for req in unit.warm_requests(warm_rng):
        unit.serve(req)
    t_warm = time.perf_counter()
    setup_events = dict(events)

    def serve_for(span_s: float, out: List, lat: List[float]) -> float:
        """Closed loop of requests for ``span_s`` seconds; its wall."""
        t_start = time.perf_counter()
        while True:
            req = unit.next_request(window_rng)
            t0 = time.perf_counter()
            with TraceAnnotation(unit.span):
                served = unit.serve(req)
            t1 = time.perf_counter()
            out.append((req, served))
            lat.append(t1 - t0)
            if t1 - t_start >= span_s:
                return t1 - t_start

    counters0 = backend.device_cache_info()
    wall0 = dict(calls.wall)
    compiles[0] = 0
    window, latencies = [], []
    t_window = time.perf_counter()
    setup_s = t_window - T_START
    window_s = serve_for(seconds, window, latencies)
    counters = _delta(backend.device_cache_info(), counters0)
    wall1 = dict(calls.wall)
    run = RunData(kind=unit.kind, requests=len(window),
                  decisions=sum(unit.decisions(s) for _, s in window),
                  latencies_s=latencies, window_s=window_s, setup_s=setup_s,
                  calls_wall=_delta(wall1, wall0), counters=counters,
                  trace=None)

    trace_info = {}
    if trace:
        # the device is traced in a segment of its own after the measured
        # window, so the host-clock numbers carry no tracer overhead and
        # the trace stays small enough to read inside the run
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        traced: List = []
        with TraceAnnotation(trace_mod.WINDOW_SPAN):
            serve_for(min(seconds, TRACE_SECONDS), traced, [])
        t_trace = time.perf_counter()
        jax.profiler.stop_trace()
        trace_info["trace_stop_s"] = time.perf_counter() - t_trace
        xplane = trace_mod.find_xplane(trace_dir)
        if xplane is not None:
            trace_info["trace_bytes"] = os.path.getsize(xplane)
            if keep_trace:
                shutil.copyfile(xplane, keep_trace)
            profile = jax.profiler.ProfileData.from_file(xplane)
            run.trace = trace_mod.reduce_profile(profile)
            del profile
            if run.trace is not None:
                trace_info["busy_ops_s"] = run.trace.busy_ops_s
                trace_info["device_lines"] = run.trace.device_lines
        trace_info["trace_read_s"] = time.perf_counter() - t_trace
        shutil.rmtree(trace_dir, ignore_errors=True)
        run.traced_decisions = sum(unit.decisions(s) for _, s in traced)
        window += traced
    window_compiles = compiles[0]
    frozen = _delta(backend.device_cache_info(), counters0)
    reduction = run.trace
    attempted = run.decisions + run.traced_decisions

    stats = devices[0].memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))

    t_check = time.perf_counter()
    checks = unit.checks(window, check_rng)
    del window, unit
    got = cells.answers(checks, config, mix, "int32") if control else None
    cmp = cells.compare(checks, config, mix, got)
    check_s = time.perf_counter() - t_check
    compared = {"decisions_mismatched": (cmp.decisions_mismatched, 0),
                "probes_mismatched": (cmp.probes_mismatched, 0),
                "window_compiles": (window_compiles, 0)}
    for counter, name in FROZEN.items():
        compared[name] = (frozen.get(counter, 0), 0)
    correct = all(v <= lim for v, lim in compared.values())

    metrics = {}
    for name, (m, read) in readers.items():
        value = read(run)
        if value is not None:
            metrics[name] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": attempted,
              "failed": cmp.decisions_mismatched, "metrics": metrics,
              "device": device}
    if trace and reduction is not None:
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
        result["breakdown"] = {"device_ops": reduction.device_ops,
                               "idle_gaps": reduction.idle_gaps}
    result["info"] = {"requests": run.requests,
                      "mean_request_ms": 1e3 * window_s / run.requests,
                      "device_calls_s": run.calls_wall,
                      "setup_phases_s": {
                          "to_devices": t_devices - T_START,
                          "deployment": t_unit - t_devices,
                          "warmup": t_warm - t_unit,
                          "to_window": t_window - t_warm},
                      "setup_jax_events": setup_events,
                      "checked_decisions": cmp.decisions,
                      "checked_probes": cmp.probes,
                      "check_s": check_s,
                      "fallback_solves": counters.get("fallback_solves", 0),
                      "verify_solves": counters.get("verify_solves", 0),
                      "market_uploads": counters.get("misses", 0),
                      **trace_info}
    result["compared"] = {k: {"value": v, "limit": lim}
                          for k, (v, lim) in compared.items()}
    return result


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="judge the control's answers in place of the "
                         "program's (a check of the comparison itself)")
    args = ap.parse_args(argv)
    if args.seed < 0:
        print("bench: --seed must be a whole number >= 0", file=sys.stderr)
        return 2
    try:
        result = measure(args.workload, args.seed, args.seconds,
                         bool(args.trace), control=args.control)
    except BenchError as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 1
    info = result["info"]
    print("bench: " + " ".join(f"{k}={v}" for k, v in info.items()),
          file=sys.stderr)
    for k, c in result["compared"].items():
        print(f"bench: compared {k} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
