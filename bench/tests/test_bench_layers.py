"""The program's layers (``bench/layers.py``): the reduction of a traced
segment by the program's spans and stage scopes, on hand-built traces
with known answers; the host-clock readers of the program's span
aggregates, through the whole harness on the CPU and over a program that
has no spans."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from bench import layers, run, trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
SPEC = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CPU_ENV = dict(os.environ, JAX_PLATFORMS="cpu")
NS = 1e-9

#: the host-clock layer metrics read from the program's spans, by kind
SPAN_METRICS = {"tick": ("verify_host_ms_per_decision",
                         "replay_host_ms_per_decision",
                         "device_io_host_ms_per_decision"),
                "backtest": ("verify_host_ms_per_decision",
                             "replay_host_ms_per_decision",
                             "device_io_host_ms_per_decision",
                             "fleet_host_ms_per_decision")}


def _proto(host, modules, ops):
    """A text ``XSpace``: one host line of spans, one device plane with
    its program executions and operations (name, start ns, length ns)."""
    def plane(pid, name, lines):
        names = sorted({n for _, events in lines for n, _, _ in events})
        ids = {n: i + 1 for i, n in enumerate(names)}
        body = ""
        for lid, (line, events) in enumerate(lines, 1):
            evs = "".join(
                f"events {{ metadata_id: {ids[n]} offset_ps: {s * 1000} "
                f"duration_ps: {d * 1000} }}\n" for n, s, d in events)
            body += (f'lines {{ id: {lid} name: "{line}" timestamp_ns: 1000'
                     f'\n{evs}}}\n')
        meta = "".join(
            f'event_metadata {{ key: {i} value {{ id: {i} name: "'
            + n.replace("\\", "\\\\").replace('"', '\\"') + '" } }\n'
            for n, i in ids.items())
        return f'planes {{ id: {pid} name: "{name}"\n{body}{meta}}}\n'
    return (plane(1, "/host:CPU", [("python", host)])
            + plane(2, "/device:TPU:0", [("XLA Modules", modules),
                                          ("XLA Ops", ops)]))


def _hlo(name, path=None):
    """An operation named as the TPU names it: its HLO text, with the
    op_name of its metadata where it has one."""
    meta = f', metadata={{op_name="{path}"}}' if path else ""
    return f"%{name} = s32[8]{{0}} {name.split('.')[0]}(s32[8]{{0}} %p){meta}"


#: one tick of two decisions: solve_batch [11000, 59000) holds gss
#: [11500, 58500), which holds the prescan call [12000, 30000), the verify
#: solve [30000, 35000) and the golden call [35000, 55000)
SPANS = [("bench.window", 0, 100000), ("bench.tick", 10000, 50000),
         ("kubepacs.provision", 10100, 400),
         ("kubepacs.provision", 10500, 400),
         ("kubepacs.solve_batch", 11000, 48000),
         ("kubepacs.gss", 11500, 47000),
         ("kubepacs.device.prescan", 12000, 18000),
         ("kubepacs.fused.verify", 30000, 5000),
         ("kubepacs.device.golden", 35000, 20000)]
MODULES = [("jit_kubepacs_prescan(7)", 13000, 15000),
           ("jit_kubepacs_golden(8)", 36000, 18000)]
PRE = "jit(kubepacs_prescan)/rows/while/body"
GOLD = "jit(kubepacs_golden)/control/while/body/rows/while/body"
#: prescan: a sort fusion, an LP-prune fusion in a branch, a copy with no
#: op_name; golden: the cover DP's loop holding a fusion and a copy with
#: no op_name of its own, then a write of the control loop
OPS = [(_hlo("fusion.1", f"{PRE}/sort/argsort"), 13000, 7000),
       (_hlo("fusion.2", f"{PRE}/cond/branch_1_fun/lp_prune/add"), 20000,
        4000),
       (_hlo("copy.3"), 24000, 2000),
       (_hlo("while.4", f"{GOLD}/cover_dp/while"), 36000, 14000),
       (_hlo("fusion.5", f"{GOLD}/cover_dp/while/body/add"), 37000, 8000),
       (_hlo("copy.6"), 45000, 2000),
       (_hlo("fusion.7", "jit(kubepacs_golden)/control/dynamic_update_slice"),
        50000, 3000)]


@pytest.fixture(scope="module")
def known():
    from jax.profiler import ProfileData

    return layers.reduce_layers(ProfileData.from_text_proto(
        _proto(SPANS, MODULES, OPS)))


def test_stages_of_a_known_trace(known):
    """Each operation's own time goes to the innermost stage scope of its
    op_name, an operation without one to the stage of the loop that holds
    it, and one outside every stage to its program's unscoped time."""
    want = {"kubepacs_prescan/sort": 7000, "kubepacs_prescan/lp_prune": 4000,
            "kubepacs_prescan/unscoped": 2000,
            "kubepacs_golden/cover_dp": 14000, "kubepacs_golden/control": 3000}
    assert known.stage_s == pytest.approx({k: v * NS
                                           for k, v in want.items()})
    ops = dict((k, v) for k, v in known.device_ops)
    assert ops["kubepacs.device.golden:kubepacs_golden/cover_dp/fusion.5"] \
        == pytest.approx(8000 * NS)
    assert ops["kubepacs.device.golden:kubepacs_golden/cover_dp/copy.6"] \
        == pytest.approx(2000 * NS)
    assert ops["kubepacs.device.prescan:copy.3"] == pytest.approx(2000 * NS)


def test_idle_time_by_program_span(known):
    """Every idle instant of the segment goes to the innermost span open
    at it, so the parts add up to the idle time; the gaps are named by the
    program's spans."""
    assert known.busy_s == pytest.approx(33000 * NS)
    want = {"bench.window": 50000, "bench.tick": 1200,
            "kubepacs.provision": 800, "kubepacs.solve_batch": 1000,
            "kubepacs.gss": 4000, "kubepacs.device.prescan": 3000,
            "kubepacs.fused.verify": 5000, "kubepacs.device.golden": 2000}
    assert known.idle_by_span == pytest.approx(
        {k: v * NS for k, v in want.items()})
    assert sum(known.idle_by_span.values()) == pytest.approx(
        known.window_s - known.busy_s)
    assert ["kubepacs.fused.verify", pytest.approx(8000 * NS)] in \
        known.idle_gaps


def test_span_wall_busy_and_self(known):
    assert known.decisions == 2
    assert known.span_wall_s["kubepacs.device.prescan"] == pytest.approx(
        18000 * NS)
    assert known.span_busy_s["kubepacs.device.golden"] == pytest.approx(
        18000 * NS)
    assert known.span_busy_s["bench.tick"] == pytest.approx(33000 * NS)
    # self: the solve_batch less the gss in it; the gss less its calls
    assert known.span_self_s["kubepacs.solve_batch"] == pytest.approx(
        1000 * NS)
    assert known.span_self_s["kubepacs.gss"] == pytest.approx(4000 * NS)
    readings = known.readings()
    # (18000 - 15000) + (20000 - 18000) ns over 2 decisions
    assert readings["dispatch_overhead_ms_per_decision"] == pytest.approx(
        2500e-6)
    assert readings["cover_dp_device_ms_per_decision"] == pytest.approx(
        7000e-6)
    assert readings["prune_device_ms_per_decision"] == pytest.approx(5500e-6)
    assert readings["kubepacs_prescan.unscoped_share"] == pytest.approx(
        2 / 13)
    assert readings["kubepacs_golden.unscoped_share"] == 0
    # program spans' self times cover the tick but for its own 1200 ns
    assert readings["span_cover_share"] == pytest.approx(48800 / 50000)
    assert readings["idle_share_under_program_spans"] == pytest.approx(
        15800 / 67000)


def test_own_times_are_the_harness_reductions():
    """The layer reduction counts an operation's own time as the
    harness's trace reduction does."""
    evs = [(s * NS, (s + d) * NS, n) for n, s, d in OPS]
    clipped, own = trace._self_times(evs, 0.0, 1.0)
    mine, own2, _parents = layers._nesting(evs, 0.0, 1.0)
    assert mine == clipped and own2 == own


def test_op_path_and_stage():
    path = "jit(kubepacs_golden)/control/while/body/rows/sort/jit(x)/sort"
    # a recorded slice writes the op_name into the name
    assert layers.op_path(_hlo("fusion.9", path), "kubepacs_golden",
                          {}) == path
    # the TPU names an operation by its instruction, operands typed and no
    # metadata; the compiled text prints operands bare, with metadata
    compiled = ("HloModule jit_kubepacs_golden, is_scheduled=true\n\n"
                f'  ROOT %fusion.9 = (u32[1]{{0}}, s32[8]{{0}}) fusion(%p), '
                f'kind=kLoop, metadata={{op_name="{path}"}}, '
                'backend_config={"flag_configs":[]}\n'
                "  %copy.1 = s32[8]{0} copy(%p)\n"
                "  %add.2 = s32[] add(%a, %b), "
                'metadata={op_name="x/sort/add"}\n'
                "  %add.2 = s32[] add(%c, %d), "
                'metadata={op_name="x/rows/add"}\n')
    paths = layers.hlo_op_paths(compiled)
    traced = ("%fusion.9 = (u32[1]{0}, s32[8]{0}) fusion(s32[8]{0} %p), "
              "kind=kLoop")
    assert layers.op_path(traced, "kubepacs_golden", paths) == path
    assert layers.op_path(traced, "kubepacs_prescan", paths) == ""
    assert layers.op_path("%copy.1 = s32[8]{0} copy(s32[8]{0} %p)",
                          "kubepacs_golden", paths) == ""
    # one key, two op_names (two shapes of a program): no stage of its own
    assert layers.op_path("%add.2 = s32[] add(s32[] %a, s32[] %b)",
                          "kubepacs_golden", paths) == ""
    # the last part names the operation, not a scope
    assert layers.stage(path) == "sort"
    assert layers.stage("jit(kubepacs_prescan)/rows/while") == "rows"
    assert layers.stage("jit(kubepacs_prescan)/sort") == ""
    assert layers.program_name("jit_kubepacs_golden(1234)") == \
        "kubepacs_golden"


def _compiled_text(ops):
    """The compiled HLO text of the known trace's two programs: each
    operation's instruction with bare operands and its metadata."""
    def line(name):
        head, _, rest = name.partition(" = ")
        kind, _, call = rest.partition(" ")
        opcode, _, args = call.partition("(")
        meta = args.split(")", 1)[1]
        return f"  {head} = {kind} {opcode}(%p){meta}"
    return (f"HloModule jit_kubepacs_prescan\n"
            + "\n".join(line(n) for n, _s, _d in ops[:3])
            + "\nHloModule jit_kubepacs_golden\n"
            + "\n".join(line(n) for n, _s, _d in ops[3:]) + "\n")


def test_stages_from_the_compiled_programs_text():
    """Operations named without metadata, as on the TPU, take their
    op_name from the compiled programs' HLO text."""
    from jax.profiler import ProfileData

    bare = [(_hlo(n.split(" = ")[0][1:]), s, d) for n, s, d in OPS]
    red = layers.reduce_layers(
        ProfileData.from_text_proto(_proto(SPANS, MODULES, bare)),
        layers.hlo_op_paths(_compiled_text(OPS)))
    assert red.stage_s == pytest.approx(
        layers.reduce_layers(ProfileData.from_text_proto(
            _proto(SPANS, MODULES, OPS))).stage_s)
    assert layers.reduce_layers(ProfileData.from_text_proto(
        _proto(SPANS, MODULES, bare))).stage_s == pytest.approx(
        {"kubepacs_prescan/unscoped": 13000 * NS,
         "kubepacs_golden/unscoped": 17000 * NS})


def test_a_trace_without_the_programs_names_has_no_stages():
    """The harness's own spans and unnamed programs (the parent of the
    change that named them) reduce to no stage and no program span."""
    from jax.profiler import ProfileData

    red = layers.reduce_layers(ProfileData.from_text_proto(_proto(
        SPANS[:2], [("jit_run(3)", 13000, 15000)],
        [("fusion.1", 13000, 7000)])))
    assert red.stage_s == {"run/unscoped": pytest.approx(7000 * NS)}
    readings = red.readings()
    assert readings["dispatch_overhead_ms_per_decision"] is None
    assert readings["cover_dp_device_ms_per_decision"] is None
    assert readings["span_cover_share"] is None
    assert red.decisions == 0


# -- the host-clock readers of the program's span aggregates ------------------

def _data(kind):
    return run.RunData(kind=kind, requests=3, decisions=30,
                       latencies_s=[0.1] * 3, window_s=0.3, setup_s=1.0,
                       calls_wall={}, counters={}, trace=None,
                       traced_decisions=10)


def test_span_readers_per_decision(monkeypatch):
    from repro.core import events_log

    totals = {"kubepacs.provision": (40, 4_000_000, 4_000_000),
              "kubepacs.fused.verify": (2, 9_000_000, 8_000_000),
              "kubepacs.gss": (2, 90_000_000, 30_000_000),
              "kubepacs.decision.finish": (2, 2_000_000, 2_000_000),
              "kubepacs.device.inputs": (4, 5_000_000, 4_000_000),
              "kubepacs.device.readback": (4, 6_000_000, 6_000_000),
              "kubepacs.fleet.run": (1, 200_000_000, 40_000_000),
              "kubepacs.fleet.collect": (9, 8_000_000, 4_000_000)}
    monkeypatch.setattr(events_log, "span_totals", lambda: totals)
    want = {"verify_host_ms_per_decision": 8 / 40,
            "replay_host_ms_per_decision": 32 / 40,
            "device_io_host_ms_per_decision": 10 / 40,
            "fleet_host_ms_per_decision": 44 / 40}
    for kind, names in SPAN_METRICS.items():
        for name in names:
            assert run.load_reader(name)(_data(kind)) == pytest.approx(
                want[name])
    assert run.load_reader("fleet_host_ms_per_decision")(_data("tick")) \
        is None


def test_span_readers_read_nothing_of_a_program_without_spans(monkeypatch):
    """Over a program that keeps no span aggregates (the parent of the
    change that added them) each reader returns nothing."""
    from repro.core import events_log

    monkeypatch.delattr(events_log, "span_totals")
    for kind, names in SPAN_METRICS.items():
        for name in names:
            assert run.load_reader(name)(_data(kind)) is None


@pytest.fixture(scope="module")
def traced_results(tmp_path_factory):
    """A traced CPU run of a tiny tick cell and a tiny storm cell through
    the whole harness, each with the program's span totals at its end."""
    d = tmp_path_factory.mktemp("traced")
    spec = json.loads(json.dumps(SPEC))
    config = dict(run.load_json(os.path.join(
        BENCH, "configs", "karpenter_zone_m.json")), name="tiny_zone",
        generations=[5, 6], offerings=128)
    tick = dict(run.load_json(os.path.join(BENCH, "traffic", "tick32.json")),
                decisions_per_tick=4, pods_mean=60, check_decisions=8)
    storm = dict(run.load_json(os.path.join(BENCH, "traffic",
                                            "storm_fleet.json")),
                 replicas=3, market_seeds=[3, 4, 5, 11],
                 warmup_market_seeds=[31])
    storm["scenario"] = dict(storm["scenario"], pods=40)
    for name, mix, kind in (("tiny_tick", tick, "tick"),
                            ("tiny_storm", storm, "backtest")):
        cell = f"tiny_zone.{name}"
        spec["workloads"].append({"name": cell, "config": "tiny_zone",
                                  "traffic": name, "chips": 1,
                                  "why": "test"})
        for m in spec["per_layer"]:
            if m["name"] in SPAN_METRICS[kind]:
                m["workloads"].append(cell)
        with open(d / f"{name}.json", "w") as f:
            json.dump(mix, f)
    with open(d / "spec.json", "w") as f:
        json.dump(spec, f)
    with open(d / "config.json", "w") as f:
        json.dump(config, f)
    code = (
        "import json, sys, time\n"
        "t0 = time.perf_counter()\n"
        "from bench import run\n"
        "sys.path.insert(0, 'src')\n"
        "from repro.core import events_log\n"
        "spec, config = (json.load(open(p)) for p in sys.argv[1:3])\n"
        "for name in ('tiny_tick', 'tiny_storm'):\n"
        "    mix = json.load(open(sys.argv[3] + '/' + name + '.json'))\n"
        "    before = events_log.span_totals()\n"
        "    t1 = time.perf_counter()\n"
        "    result = run.measure('tiny_zone.' + name, 2 ** 33 + 7, 1.0,"
        " True, require_tpu=False, spec=spec, config=config, mix=mix)\n"
        "    totals = events_log.span_totals()\n"
        "    paths = {}\n"
        "    if name == 'tiny_tick':\n"
        "        from bench import layers\n"
        "        paths = layers.compiled_op_paths(config, mix, 5)\n"
        "    print(json.dumps({'result': result, 'wall_s':"
        " time.perf_counter() - t1, 'totals': totals,"
        " 'before': before, 'paths': paths}), flush=True)\n")
    out = subprocess.run(
        [sys.executable, "-c", code, str(d / "spec.json"),
         str(d / "config.json"), str(d)], cwd=ROOT, env=CPU_ENV,
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    tick_run, storm_run = (json.loads(line) for line in
                           out.stdout.strip().splitlines()[-2:])
    return {"tick": tick_run, "backtest": storm_run}


@pytest.mark.parametrize("kind", ["tick", "backtest"])
def test_traced_run_reports_the_span_metrics(traced_results, kind):
    """Through the whole harness, the program's spans give every
    host-clock layer metric of the cell: each is its spans' self time per
    ``kubepacs.provision`` span, and the spans' self times add up to no
    more than the process's wall."""
    got = traced_results[kind]
    result, totals = got["result"], got["totals"]
    assert result["correct"]
    decisions = totals["kubepacs.provision"][0]
    assert decisions >= result["attempted"]
    self_ms = {name: agg[2] * 1e-6 for name, agg in totals.items()}
    assert sum(self_ms.values()) <= (got["wall_s"] + sum(
        agg[2] for agg in got["before"].values()) * 1e-9) * 1e3
    for name in SPAN_METRICS[kind]:
        value = result["metrics"][name]["value"]
        assert value > 0
        assert value <= sum(self_ms.values()) / decisions
    assert result["metrics"]["verify_host_ms_per_decision"]["value"] == \
        pytest.approx(self_ms["kubepacs.fused.verify"] / decisions)
    assert {"kubepacs.solve_batch", "kubepacs.gss", "kubepacs.device.golden",
            "kubepacs.device.prescan"} <= set(totals)


def test_compiled_programs_name_every_stage(traced_results):
    """The compiled text of the programs a cell builds gives op_names
    under every stage scope of both programs (CPU compile)."""
    paths = traced_results["tick"]["paths"].values()
    for program in ("kubepacs_prescan", "kubepacs_golden"):
        mine = {layers.stage(p) for p in paths
                if p.startswith(f"jit({program})")}
        want = {"saturate", "sort", "lp_prune", "core_dp", "compact",
                "cover_dp", "backtrack", "rows"}
        if program == "kubepacs_golden":
            want |= {"score", "control"}
        assert want <= mine, (program, want - mine)


def test_slice_keeps_spans_and_op_paths():
    """``record_layers.py`` cuts a tick out of a trace with the program's
    spans, and writes each operation's op_name into its name: the slice
    reduces to the stages of the whole."""
    from jax.profiler import ProfileData

    from bench.tests import record_layers

    bare = [(_hlo(n.split(" = ")[0][1:]), s, d) for n, s, d in OPS]
    paths = layers.hlo_op_paths(_compiled_text(OPS))
    text = record_layers.slice_text(ProfileData.from_text_proto(
        _proto(SPANS, MODULES, bare)), paths)
    red = layers.reduce_layers(ProfileData.from_text_proto(text))
    assert red.stage_s == pytest.approx(layers.reduce_layers(
        ProfileData.from_text_proto(_proto(SPANS, MODULES, OPS))).stage_s)
    assert red.decisions == 2
    assert "kubepacs.fused.verify" in red.idle_by_span


def test_layers_of_a_recorded_v5e_tick():
    """One tick of ``karpenter_zone_m.tick32`` traced on a TPU v5e
    (``record_layers.py``, op_names from the compiled programs): the
    harness's spans read as the harness's reduction reads them, each
    program's stages add up to its own time with almost none unscoped,
    and the idle time by span adds up to the tick's idle time, nearly all
    of it under the program's spans."""
    from jax.profiler import ProfileData

    path = os.path.join(os.path.dirname(__file__),
                        "v5e_tick32_layers_slice.pbtxt")
    with open(path) as f:
        profile = ProfileData.from_text_proto(f.read())
    red = layers.reduce_layers(profile)
    harness = trace.reduce_profile(profile)
    assert red.decisions == 32
    assert red.busy_s == pytest.approx(harness.busy_s)
    for name, busy in harness.busy_by_span.items():
        assert red.span_busy_s[name] == pytest.approx(busy)

    [device] = [p for p in profile.planes if p.name.startswith("/device:")]
    events = {ln.name: [(ev.start_ns * NS, ev.end_ns * NS, ev.name)
                        for ev in ln.events] for ln in device.lines}
    clipped, owns = trace._self_times(events["XLA Ops"], 0.0, 1e30)
    own = {}
    for (s, _e, _n), secs in zip(clipped, owns):
        [module] = [m for m0, m1, m in events["XLA Modules"] if m0 <= s < m1]
        prog = layers.program_name(module)
        own[prog] = own.get(prog, 0.0) + secs
    assert set(own) == {"kubepacs_prescan", "kubepacs_golden"}
    for prog, total in own.items():
        mine = {k.split("/", 1)[1]: v for k, v in red.stage_s.items()
                if k.startswith(prog + "/")}
        assert sum(mine.values()) == pytest.approx(total)
        assert mine.get(layers.UNSCOPED, 0.0) < 0.01 * total
        assert set(mine) - {layers.UNSCOPED} <= layers.STAGES

    idle = red.window_s - red.busy_s
    assert sum(red.idle_by_span.values()) == pytest.approx(idle)
    assert sum(v for k, v in red.idle_by_span.items()
               if k.startswith(layers.PROGRAM_PREFIX)) >= 0.9 * idle
    assert all(name.startswith(layers.PROGRAM_PREFIX)
               for name, _secs in red.idle_gaps)
