# Telemetry subsystem (mpisppy_tpu/telemetry/, docs/telemetry.md):
# event bus + typed events + sinks, the back-compat Hub.trace/sp.trace
# views, on-device PDHG kernel counters with the telemetry=off HLO
# byte-identity contract (mirroring test_chaos.py's disarmed-plan
# check), profiler hooks, the metrics exporter's shared snapshot
# schema, the no-bare-print lint, and the phtracker atomic-flush fix.
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from mpisppy_tpu import telemetry
from mpisppy_tpu.algos import ph as ph_mod
from mpisppy_tpu.core import batch as batch_mod
from mpisppy_tpu.cylinders import (
    LagrangianOuterBound, PHHub, XhatXbarInnerBound,
)
from mpisppy_tpu.models import farmer
from mpisppy_tpu.ops import pdhg
from mpisppy_tpu.spin_the_wheel import WheelSpinner
from mpisppy_tpu.telemetry import console, counters as kcounters, metrics


def farmer_batch(num_scens=3):
    names = farmer.scenario_names_creator(num_scens)
    specs = [farmer.scenario_creator(nm, num_scens=num_scens)
             for nm in names]
    return batch_mod.from_specs(specs)


def hub_dict(batch, hub_extra=None, max_iterations=6, telemetry_on=False):
    opts = ph_mod.PHOptions(
        default_rho=1.0, max_iterations=max_iterations, conv_thresh=0.0,
        subproblem_windows=10,
        pdhg=pdhg.PDHGOptions(tol=1e-7, telemetry=telemetry_on))
    hub_opts = {"rel_gap": 5e-3}
    hub_opts.update(hub_extra or {})
    return {
        "hub_class": PHHub,
        "hub_kwargs": {"options": hub_opts},
        "opt_class": ph_mod.PH,
        "opt_kwargs": {"options": opts, "batch": batch},
    }


BOTH_SPOKES = [
    {"spoke_class": LagrangianOuterBound, "opt_kwargs": {"options": {}}},
    {"spoke_class": XhatXbarInnerBound, "opt_kwargs": {"options": {}}},
]


# ---------------------------------------------------------------------------
# Event schema round-trip + ordering (ISSUE 3 satellite)
# ---------------------------------------------------------------------------
def test_jsonl_event_schema_roundtrip_and_ordering(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    bus = telemetry.EventBus()
    bus.subscribe(telemetry.JsonlSink(path))
    run = telemetry.new_run_id()
    bus.emit(telemetry.HUB_ITERATION, run=run, cyl="hub", hub_iter=1,
             outer=-110.0, inner=float("inf"), rel_gap=float("nan"))
    bus.emit(telemetry.SPOKE_HARVEST, run=run, cyl="hub", hub_iter=1,
             spoke=0, sense="outer", bound=np.float32(-109.5))
    bus.emit(telemetry.CHECKPOINT_WRITE, run=run, cyl="hub", hub_iter=2,
             path="/x/y.npz", bytes=123)
    bus.close()

    rows = [json.loads(line) for line in open(path)]
    assert [r["kind"] for r in rows] == [
        "hub-iteration", "spoke-harvest", "checkpoint-write"]
    # every row carries the full envelope
    for r in rows:
        assert set(r) >= {"kind", "seq", "t_wall", "t_mono", "run",
                          "cyl", "data"}
        assert r["run"] == run
    # total order: seq strictly increasing, monotonic clock nondecreasing
    seqs = [r["seq"] for r in rows]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    monos = [r["t_mono"] for r in rows]
    assert monos == sorted(monos)
    # strict JSON: non-finite floats serialize as null, numpy scalars
    # as plain numbers
    assert rows[0]["data"]["inner"] is None
    assert rows[0]["data"]["rel_gap"] is None
    assert rows[1]["data"]["bound"] == pytest.approx(-109.5)
    assert rows[0]["iter"] == 1 and rows[2]["iter"] == 2
    # the file ends cleanly (closed sink) and every line re-serializes
    for r in rows:
        json.dumps(r)


def test_bus_isolates_failing_sink():
    class Bomb(telemetry.Sink):
        def handle(self, event):
            raise RuntimeError("boom")

    seen = []

    class Ok(telemetry.Sink):
        def handle(self, event):
            seen.append(event.kind)

    bus = telemetry.EventBus()
    bus.subscribe(Bomb())
    bus.subscribe(Ok())
    for _ in range(5):
        bus.emit(telemetry.CONSOLE, msg="x")
    assert len(seen) == 5          # healthy sink saw everything
    assert len(bus.sinks) == 1     # bomb detached after repeated fails


# ---------------------------------------------------------------------------
# On-device kernel counters
# ---------------------------------------------------------------------------
def test_kernel_counters_accumulate_and_harvest():
    batch = farmer_batch(3)
    opts = pdhg.PDHGOptions(tol=1e-6, max_iters=8_000, telemetry=True)
    st = pdhg.solve(batch.qp, opts)
    h = kcounters.harvest_state(st)
    assert h["pdhg_iterations_total"] > 0
    assert h["pdhg_restarts_total"] >= 1
    assert h["pdhg_windows_total"] >= 1
    ring = h["residual_ring"]
    assert ring.shape == (3, opts.telemetry_ring)
    assert np.isfinite(ring).any()
    # converged lanes' last scores sit at/below tolerance scale
    assert h["pdhg_last_score_median"] <= 1e-4

    # counters persist across a warm-started re-solve (PH's pattern)
    st2 = pdhg.solve(batch.qp, opts, st)
    h2 = kcounters.harvest_state(st2)
    assert h2["pdhg_iterations_total"] >= h["pdhg_iterations_total"]

    # off by default: zero-leaf None, and harvest says so
    st_off = pdhg.solve(batch.qp, pdhg.PDHGOptions(tol=1e-6,
                                                   max_iters=4_000))
    assert st_off.counters is None
    assert kcounters.harvest_state(st_off) is None


def test_kernel_counters_off_hlo_identical(tmp_path):
    """Overhead contract (mirrors test_chaos.py's disarmed-plan check):
    with telemetry off, the PH wheel step lowered from a fully
    telemetry-wired wheel is byte-identical to one lowered from a
    driver that never touched the telemetry layer; flipping the kernel
    counters ON must change the program (proof the flag gates real
    instrumentation)."""
    batch = farmer_batch(3)
    opts = ph_mod.kernel_opts(ph_mod.PHOptions(
        default_rho=1.0, conv_thresh=0.0, subproblem_windows=10,
        pdhg=pdhg.PDHGOptions(tol=1e-7)))
    rho = jnp.ones((batch.num_nonants,), batch.qp.c.dtype)
    st, _, _ = ph_mod.ph_iter0(batch, rho, opts)
    text_base = ph_mod.ph_iterk.lower(batch, st, opts).as_text()

    # the same step lowered from a wheel with a live bus + sinks
    # attached but counters off
    bus = telemetry.EventBus()
    bus.subscribe(telemetry.MetricsSnapshotSink(
        str(tmp_path / "m.prom"), registry=metrics.MetricsRegistry(),
        every_s=1e9))
    ws = WheelSpinner(
        hub_dict(batch, {"telemetry_bus": bus}, max_iterations=3),
        [dict(d) for d in BOTH_SPOKES]).spin()
    text_wired = ph_mod.ph_iterk.lower(
        batch, ws.opt.state, ph_mod.kernel_opts(ws.opt.options)).as_text()
    assert text_wired == text_base

    # counters ON: state gains leaves and the lowered program differs
    opts_on = ph_mod.kernel_opts(ph_mod.PHOptions(
        default_rho=1.0, conv_thresh=0.0, subproblem_windows=10,
        pdhg=pdhg.PDHGOptions(tol=1e-7, telemetry=True)))
    st_on, _, _ = ph_mod.ph_iter0(batch, rho, opts_on)
    assert st_on.solver.counters is not None
    text_on = ph_mod.ph_iterk.lower(batch, st_on, opts_on).as_text()
    assert text_on != text_base


# ---------------------------------------------------------------------------
# One spine: hub emits, legacy lists are subscriber views
# ---------------------------------------------------------------------------
def test_hub_trace_lists_are_bus_views(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    bus = telemetry.EventBus()
    bus.subscribe(telemetry.JsonlSink(path))
    batch = farmer_batch(3)
    ws = WheelSpinner(
        hub_dict(batch, {"telemetry_bus": bus}, max_iterations=5,
                 telemetry_on=True),
        [dict(d) for d in BOTH_SPOKES]).spin()
    bus.close()
    hub = ws.spcomm

    rows = [json.loads(line) for line in open(path)]
    kinds = {r["kind"] for r in rows}
    assert {"run-start", "hub-iteration", "spoke-harvest",
            "bound-accept", "kernel-counters", "run-end"} <= kinds

    # the legacy Hub.trace list is exactly the hub-iteration stream
    hub_rows = [r for r in rows if r["kind"] == "hub-iteration"]
    assert len(hub.trace) == len(hub_rows) == hub._iter
    for view_row, ev_row in zip(hub.trace, hub_rows):
        assert view_row["iter"] == ev_row["data"]["iter"]
        assert view_row["t"] == ev_row["t_mono"]   # bench reads row["t"]
        assert (view_row["rel_gap"] == ev_row["data"]["rel_gap"]
                or ev_row["data"]["rel_gap"] is None)

    # spoke traces are exactly the bound-accept stream, per spoke
    for j, sp in enumerate(hub.spokes):
        accepts = [(r["iter"], r["data"]["bound"]) for r in rows
                   if r["kind"] == "bound-accept"
                   and r["data"]["spoke"] == j]
        assert sp.trace == accepts
        assert len(sp.trace) >= 1

    # kernel counters made it into the global registry with nonzero
    # totals
    assert metrics.REGISTRY.get("pdhg_iterations_total", cyl="hub") > 0


def test_fused_plane_counters_harvested():
    """--kernel-counters must cover the fused bound planes, not only
    the hub's subproblems: plane solvers are harvested under their own
    cyl labels (the silent-undercount regression)."""
    import dataclasses
    from mpisppy_tpu.algos import fused_wheel as fw
    from mpisppy_tpu.cylinders import spoke as spoke_mod
    batch = farmer_batch(3)
    opts = ph_mod.PHOptions(
        default_rho=1.0, max_iterations=4, conv_thresh=0.0,
        subproblem_windows=10,
        pdhg=pdhg.PDHGOptions(tol=1e-7, telemetry=True))
    wd = fw.FusedWheelOptions()
    wopts = dataclasses.replace(
        wd,
        lag_pdhg=dataclasses.replace(wd.lag_pdhg, telemetry=True),
        xhat_pdhg=dataclasses.replace(wd.xhat_pdhg, telemetry=True))
    hub = {"hub_class": PHHub,
           "hub_kwargs": {"options": {"rel_gap": 5e-3}},
           "opt_class": fw.FusedPH,
           "opt_kwargs": {"options": opts, "batch": batch,
                          "wheel_options": wopts}}
    spokes = [
        {"spoke_class": spoke_mod.FusedLagrangianOuterBound,
         "opt_kwargs": {"options": {}}},
        {"spoke_class": spoke_mod.FusedXhatXbarInnerBound,
         "opt_kwargs": {"options": {}}},
    ]
    WheelSpinner(hub, spokes).spin()
    for cyl in ("hub", "lag", "xhat"):
        assert metrics.REGISTRY.get("pdhg_iterations_total",
                                    cyl=cyl) > 0, cyl


def test_fault_injections_reach_the_trace(tmp_path):
    from mpisppy_tpu.resilience import FaultPlan, SpokeBoundFault
    path = str(tmp_path / "trace.jsonl")
    bus = telemetry.EventBus()
    bus.subscribe(telemetry.JsonlSink(path))
    plan = FaultPlan(seed=1, spoke_bounds=(
        SpokeBoundFault("nan", spoke_index=0, at_iters=(3,)),))
    batch = farmer_batch(3)
    WheelSpinner(
        hub_dict(batch, {"telemetry_bus": bus, "fault_plan": plan,
                         "spoke_max_strikes": 10}, max_iterations=5),
        [dict(d) for d in BOTH_SPOKES]).spin()
    bus.close()
    rows = [json.loads(line) for line in open(path)]
    faults = [r for r in rows if r["kind"] == "fault-injected"]
    strikes = [r for r in rows if r["kind"] == "spoke-strike"]
    assert faults and faults[0]["data"]["seam"] == "spoke_bound"
    assert strikes and strikes[0]["data"]["spoke"] == 0
    # cause precedes response in the total order
    assert faults[0]["seq"] < strikes[0]["seq"]


# ---------------------------------------------------------------------------
# Console verbosity + global_toc routing
# ---------------------------------------------------------------------------
def test_console_levels_and_global_toc_capture(tmp_path, capsys):
    from mpisppy_tpu import global_toc
    path = str(tmp_path / "trace.jsonl")
    bus = telemetry.EventBus()
    bus.subscribe(telemetry.JsonlSink(path))
    bus.subscribe(telemetry.ConsoleSink(verbosity=console.INFO))
    console.attach(bus)
    try:
        global_toc("visible info line")
        console.log("debug line", level=console.DEBUG)
        console.log("suppressed", cond=False)
    finally:
        console.detach(bus)
        bus.close()
    out = capsys.readouterr().out
    assert "visible info line" in out
    assert "debug line" not in out       # below the verbosity bar
    assert "suppressed" not in out
    rows = [json.loads(line) for line in open(path)]
    msgs = [r["data"]["msg"] for r in rows]
    # the machine trace records BOTH levels (filtering is render-side)
    assert msgs == ["visible info line", "debug line"]
    assert rows[1]["level"] == console.DEBUG
    # detached: back to the classic direct print
    global_toc("after detach")
    assert "after detach" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# Metrics exporter: prom rendering + the schema bench.py embeds
# ---------------------------------------------------------------------------
def test_metrics_snapshot_schema_and_prom_render(tmp_path):
    reg = metrics.MetricsRegistry()
    reg.inc("events_total", kind="hub-iteration")
    reg.inc("events_total", kind="hub-iteration")
    reg.set_counter("pdhg_iterations_total", 1600, cyl="hub")
    reg.set_gauge("pdhg_last_score_median", 3e-7, cyl="hub")
    snap = reg.to_snapshot()
    assert snap["schema"] == metrics.SNAPSHOT_SCHEMA
    assert set(snap) == {"schema", "t_wall", "counters", "gauges"}
    assert snap["counters"]['events_total{kind="hub-iteration"}'] == 2.0
    json.dumps(snap)  # BENCH_*.json embeddability

    path = str(tmp_path / "m.prom")
    sink = telemetry.MetricsSnapshotSink(path, registry=reg, every_s=1e9)
    sink.close()  # close always writes a final snapshot
    text = open(path).read()
    assert "# TYPE pdhg_iterations_total counter" in text
    assert 'pdhg_iterations_total{cyl="hub"} 1600.0' in text
    assert "# TYPE pdhg_last_score_median gauge" in text

    # bench.py embeds the SAME schema object (shared code path)
    import bench
    assert bench.metrics_schema_probe() == metrics.SNAPSHOT_SCHEMA


# ---------------------------------------------------------------------------
# phtracker: atomic writes + flush on post_everything at any cadence
# ---------------------------------------------------------------------------
def test_phtracker_flushes_off_cadence_rows(tmp_path):
    """Regression (ISSUE 3 satellite): rows buffered past the last
    save_every*write_every boundary must land via post_everything, and
    the csv is written atomically (no partial/torn content)."""
    batch = farmer_batch(3)
    import functools
    from mpisppy_tpu.extensions.phtracker import PHTracker
    folder = str(tmp_path / "tr")
    opts = ph_mod.PHOptions(
        default_rho=1.0, max_iterations=5, conv_thresh=0.0,
        subproblem_windows=4, pdhg=pdhg.PDHGOptions(tol=1e-6))
    drv = ph_mod.PH(opts, batch,
                    extensions=functools.partial(
                        PHTracker, folder=folder, save_every=1,
                        write_every=4, track_nonants=True))
    drv.ph_main()
    # 5 iterations with write_every=4: iter 5's row is PAST the last
    # write boundary and only post_everything can flush it
    conv = open(os.path.join(folder, "hub", "convergence.csv")
                ).read().strip().splitlines()
    assert conv[0] == "iteration,conv"
    assert len(conv) == 1 + 5
    assert [int(line.split(",")[0]) for line in conv[1:]] == [1, 2, 3, 4, 5]
    non = open(os.path.join(folder, "hub", "nonants.csv")
               ).read().strip().splitlines()
    assert len(non) == 1 + 5
    # no stale tmp files left behind by the atomic writer
    leftovers = [f for f in os.listdir(os.path.join(folder, "hub"))
                 if ".tmp." in f]
    assert leftovers == []


# ---------------------------------------------------------------------------
# The no-bare-print lint (run in tier-1, as the satellite requires)
# ---------------------------------------------------------------------------
def test_no_bare_prints_in_library_code():
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    try:
        import lint_no_print
    finally:
        sys.path.pop(0)
    assert lint_no_print.find_violations() == []


def test_lint_catches_a_new_print(tmp_path):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    try:
        import lint_no_print
    finally:
        sys.path.pop(0)
    bad = tmp_path / "lib"
    os.makedirs(bad / "sub")
    (bad / "sub" / "mod.py").write_text(
        'x = 1\nprint("dbg")\n'
        'print(json.dumps({}))  # telemetry: allow-print\n'
        '# a comment mentioning print( is fine\n')
    vio = lint_no_print.find_violations(str(bad))
    assert len(vio) == 1 and "sub/mod.py:2" in vio[0]


# ---------------------------------------------------------------------------
# CLI smoke: the acceptance-criteria run (farmer wheel, telemetry on)
# ---------------------------------------------------------------------------
def test_cli_trace_jsonl_metrics_and_profile(tmp_path):
    trace = str(tmp_path / "trace.jsonl")
    prom = str(tmp_path / "metrics.prom")
    prof = str(tmp_path / "profile")
    ckpt = str(tmp_path / "wheel.npz")
    cmd = [sys.executable, "-m", "mpisppy_tpu",
           "--module-name", "mpisppy_tpu.models.farmer",
           "--num-scens", "3", "--max-iterations", "40",
           "--rel-gap", "0.01", "--convthresh", "0",
           "--lagrangian", "--xhatxbar",
           "--kernel-counters",
           "--trace-jsonl", trace,
           "--metrics-snapshot", prom, "--metrics-every-s", "0",
           "--profile-dir", prof, "--profile-iters", "2",
           "--checkpoint-path", ckpt, "--checkpoint-every-s", "0.1"]
    out = subprocess.run(cmd, capture_output=True, text=True,
                         cwd="/root/repo", timeout=600,
                         env={"PATH": "/usr/bin:/bin:/usr/local/bin",
                              "JAX_PLATFORMS": "cpu",
                              "HOME": "/root"})
    assert out.returncode == 0, out.stderr[-2000:]
    payload = json.loads(out.stdout.strip().splitlines()[-1])
    assert payload["rel_gap"] <= 0.01

    # parseable JSONL trace with the acceptance event kinds
    rows = [json.loads(line) for line in open(trace)]
    kinds = {r["kind"] for r in rows}
    assert "hub-iteration" in kinds
    assert "spoke-harvest" in kinds
    assert "checkpoint-write" in kinds
    assert "kernel-counters" in kinds
    assert "profile" in kinds
    # one run id correlates every hub-scoped event (console lines are
    # process-level and carry no run id)
    runs = {r["run"] for r in rows if r["kind"] != "console"}
    assert len(runs) == 1 and "" not in runs

    # metrics snapshot with NONZERO pdhg iteration/restart counters
    text = open(prom).read()
    vals = {}
    for line in text.splitlines():
        if line.startswith("#") or not line.strip():
            continue
        key, v = line.rsplit(" ", 1)
        vals[key] = float(v)
    assert vals['pdhg_iterations_total{cyl="hub"}'] > 0
    assert vals['pdhg_restarts_total{cyl="hub"}'] > 0

    # the profiler session produced an actual device trace artifact
    prof_files = []
    for dirpath, _, filenames in os.walk(prof):
        prof_files += [os.path.join(dirpath, f) for f in filenames]
    assert prof_files, "profiler session wrote no trace"


# ---------------------------------------------------------------------------
# Program spans and collector pauses (profiler.span, GCPauses; the hub's
# rows carry both — docs/telemetry.md)
# ---------------------------------------------------------------------------
class _Emitter:
    def __init__(self):
        self.calls = []

    def emit_span(self, name, dur_s, t_end=None):
        self.calls.append((name, dur_s, t_end))


def test_span_emits_one_event_and_opens_its_annotation(monkeypatch):
    from mpisppy_tpu.telemetry import profiler as prof
    opened = []

    class Ann:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            opened.append(("enter", self.name))

        def __exit__(self, *exc):
            opened.append(("exit", self.name))

    monkeypatch.setattr(prof, "annotate", Ann)
    em = _Emitter()
    import time
    t0 = time.perf_counter()
    with prof.span(em, "harvest"):
        pass
    assert opened == [("enter", "wheel/harvest"), ("exit", "wheel/harvest")]
    (name, dur, t_end), = em.calls
    assert name == "harvest" and 0.0 <= dur <= t_end - t0
    # a nested span suspends the outer one: disjoint leaf segments
    em.calls.clear()
    with prof.span(em, "subproblem_solve"):
        with prof.span(em, "scalar_wait"):
            time.sleep(0.01)
    names = [c[0] for c in em.calls]
    assert names == ["subproblem_solve", "scalar_wait", "subproblem_solve"]
    segs = [(t - d, t) for _, d, t in em.calls]
    assert all(a[1] <= b[0] for a, b in zip(segs, segs[1:]))
    assert em.calls[1][1] >= 0.01 > em.calls[0][1] + em.calls[2][1]
    # an emitter learned mid-span (the wheel build) still gets the event
    em.calls.clear()
    with prof.span(None, "wheel_build") as sp:
        sp.emitter = em
    assert [c[0] for c in em.calls] == ["wheel_build"]


def _sslp_fused_wheel(bus, max_iterations=12, extensions=None):
    from mpisppy_tpu.algos import fused_wheel as fw
    from mpisppy_tpu.cylinders.spoke import (
        FusedLagrangianOuterBound, FusedXhatXbarInnerBound,
    )
    from mpisppy_tpu.models import sslp
    inst = sslp.synthetic_instance(5, 15, seed=0)
    specs = [sslp.scenario_creator(nm, instance=inst, num_scens=16,
                                   lp_relax=True)
             for nm in sslp.scenario_names_creator(16)]
    batch = batch_mod.from_specs(specs)
    opts = ph_mod.PHOptions(default_rho=20.0, max_iterations=max_iterations,
                            conv_thresh=0.0, subproblem_windows=10,
                            pdhg=pdhg.PDHGOptions(tol=1e-7))
    hub = {"hub_class": PHHub,
           "hub_kwargs": {"options": {"rel_gap": 1e-9,
                                      "telemetry_bus": bus}},
           "opt_class": fw.FusedPH,
           "opt_kwargs": {"options": opts, "batch": batch,
                          "wheel_options": fw.FusedWheelOptions(),
                          "extensions": extensions}}
    spokes = [{"spoke_class": FusedLagrangianOuterBound,
               "opt_kwargs": {"options": {}}},
              {"spoke_class": FusedXhatXbarInnerBound,
               "opt_kwargs": {"options": {}}}]
    return WheelSpinner(hub, spokes).spin()


def test_fused_wheel_waits_once_per_iteration(tmp_path):
    from mpisppy_tpu.telemetry import analyze as an
    path = str(tmp_path / "trace.jsonl")
    bus = telemetry.EventBus()
    bus.subscribe(telemetry.JsonlSink(path))
    ws = _sslp_fused_wheel(bus)
    bus.close()
    hub = ws.spcomm
    # each row lists the spans until the next row: one scalar wait per
    # hub iteration, plus iter0's in the first row (its own is the
    # wait of iter0's sync) and finalize's flush in the last
    waits = [sum(s[0] == "scalar_wait" for s in r["spans"])
             for r in hub.trace]
    assert waits == [2] + [1] * (len(hub.trace) - 1)
    assert sum(len(r["spans"]) for r in hub.trace) == len(hub.spans)
    names = {s[0] for s in hub.spans}
    assert {"wheel_build", "iter0_solve", "subproblem_solve", "scalar_wait",
            "harvest", "checkpoint", "finalize"} <= names
    # leaf segments: the non-gc spans never overlap
    segs = sorted((t - d, t) for n, _, t, d in hub.spans if n != "gc")
    assert all(a[1] <= b[0] + 1e-9 for a, b in zip(segs, segs[1:]))
    rep = an.analyze_path(path)
    phases = rep["phases"]           # shares are rounded to 1e-6 each
    assert sum(a["share"] for a in phases.values()) \
        == pytest.approx(1.0, abs=1e-6 * len(phases))
    assert phases["scalar_wait"]["calls"] == len(hub.trace) + 1
    # every row carries the collector counters
    assert all("gc_s" in r and "gc_n" in r for r in hub.trace)


def test_forced_collection_reaches_the_next_row():
    import gc

    class CollectAt:
        def __init__(self, opt):
            self.opt = opt

        def miditer(self):
            if self.opt._iter == 3:
                gc.collect()

    before = list(gc.callbacks)
    wheel = hub_dict(farmer_batch(3), max_iterations=5)
    wheel["opt_kwargs"]["extensions"] = CollectAt
    ws = WheelSpinner(wheel, [dict(d) for d in BOTH_SPOKES]).spin()
    # loop iteration 3 ends in the row of hub iteration 4
    row = {r["iter"]: r for r in ws.spcomm.trace}[4]
    assert row["gc_n"] >= 1 and row["gc_s"] > 0.0
    assert gc.callbacks == before        # finalize took the hook away


def test_collection_under_the_bus_lock_cannot_deadlock():
    import gc
    import threading
    from mpisppy_tpu.telemetry import profiler as prof
    from mpisppy_tpu.telemetry.sinks import Sink

    class CollectingSink(Sink):
        def handle(self, event):       # runs with the bus lock held
            gc.collect()

    bus = telemetry.EventBus()
    bus.subscribe(CollectingSink())
    pauses = prof.GCPauses().start()
    done = []

    def run():
        with bus._lock:
            gc.collect()
        bus.emit(telemetry.CONSOLE, msg="x")
        done.append(pauses.take())

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(60)
    pauses.stop()
    assert not t.is_alive(), "collector hook deadlocked on the bus lock"
    n, secs, _ = done[0]
    assert n >= 2 and secs > 0.0


def test_fused_step_hlo_identical_with_spans():
    """The spans are host-side only: the fused step lowered from a
    spun, span-emitting wheel is byte-identical to the step lowered
    from iter0's state with no hub at all."""
    from mpisppy_tpu.algos import fused_wheel as fw
    bus = telemetry.EventBus()
    bus.subscribe(telemetry.FlightRecorder(capacity=64))
    ws = _sslp_fused_wheel(bus, max_iterations=3)
    opt = ws.opt
    kopts = ph_mod.kernel_opts(opt.options)
    st0, _, _ = fw.fused_iter0(opt.batch, opt.rho, kopts, opt.wheel_options)
    base = fw.fused_iterk.lower(opt.batch, st0, kopts,
                                opt.wheel_options).as_text()
    wired = fw.fused_iterk.lower(opt.batch, opt.wstate, kopts,
                                 opt.wheel_options).as_text()
    assert wired == base
    assert any(s[0] == "scalar_wait" for s in ws.spcomm.spans)


def test_span_cost_probe_runs():
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    try:
        import span_cost
    finally:
        sys.path.pop(0)
    out = span_cost.span_cost(50)
    assert out["us_per_iter"] > 0
    # 5 segments per iteration (subproblem_solve twice), warm-up included
    assert out["span_events"] >= 5 * 150
