"""The readers of the program's spans (perfbench/spanmap.py and the four
metrics that use it) on synthetic records, their map onto the committed
chip capture, and a traced tiny run on the CPU."""
from __future__ import annotations

import json
import os

import pytest

from perfbench import harness, spanmap, trace
from perfbench.tests import tiny

FIX = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
PB = os.path.join(FIX, "chip_trace.xplane.pb")
NEW = ("hub.host_busy_ms_per_iter", "hub.stall_ms_per_s",
       "host.gc_ms_per_s", "solve.outside_loop_ms")
TRACE = {"from_iteration": 5, "iterations": 20}


def _read(name, ctx):
    return harness.reader(tiny.REPO, name)(ctx)


def _solve(k, converged=True, spans=True):
    """Rows of one solve: iter0's row at t=1.0 after a 0.1 s build, a
    0.2 s iter0 dispatch and a 0.3 s wait; then four iterations of 0.02 s
    wall with 0.015 s of scalar_wait each, the third stalled by 0.1 s,
    of which a 0.06 s collector pause; finalize 0.05 s after the last."""
    rows = [{"iter": 1, "t": 1.0, "gc_s": 0.0, "gc_n": 0,
             "spans": [("wheel_build", 0, 0.2, 0.1),
                       ("iter0_solve", 0, 0.4, 0.2),
                       ("scalar_wait", 0, 0.7, 0.3),
                       ("iter0_solve", 0, 0.7001, 0.0001)]}]
    t = 1.0
    for it, extra in ((2, 0.0), (3, 0.0), (4, 0.1), (5, 0.0)):
        t0, t = t, t + 0.02 + extra
        rows[-1]["spans"] += [("subproblem_solve", it - 1, t0 + 0.002,
                               0.002),
                              ("scalar_wait", it - 1, t0 + 0.017, 0.015)]
        gc = 0.06 if extra else 0.0
        if gc:
            rows[-1]["spans"].append(("gc", it - 1, t - 0.001, gc))
        rows.append({"iter": it, "t": t, "gc_s": gc, "gc_n": int(gc > 0),
                     "spans": []})
    rows[-1]["spans"] += [("finalize", 5, t + 0.03, 0.03),
                          ("scalar_wait", 5, t + 0.05, 0.02),
                          ("gc", 5, t + 0.051, 0.001)]
    if not spans:
        for r in rows:
            del r["spans"], r["gc_s"], r["gc_n"]
    return {"solve": k, "rows": rows, "converged": converged,
            "iterations": 4}


def _ctx(solves, traced=0):
    return {"spec": {"traffic": {"trace": TRACE}},
            "rec": {"solves": solves, "t_w0": 0.0, "t_w1": 2.0},
            "traced_iterations": traced}


def test_iterations_split_wall_into_wait_and_host():
    its = spanmap.iterations(_solve(0))
    assert [it["iter"] for it in its] == [2, 3, 4, 5]
    assert [it["wait"] for it in its] == pytest.approx([0.015] * 4)
    assert [round(w, 6) for w in spanmap.host_busy(its)] \
        == [0.005, 0.005, 0.105, 0.005]
    assert its[2]["gc"] == 0.06 and its[2]["spans"]["gc"] == 0.06
    # the wait, the dispatch and the pause, as a union
    assert its[2]["covered"] == pytest.approx(0.017 + 0.06)


def test_the_four_readers_on_a_synthetic_record():
    ctx = _ctx([_solve(0), _solve(1), _solve(2, converged=False)])
    assert _read("hub.host_busy_ms_per_iter", ctx) == pytest.approx(5.0)
    # three stalls of 0.1 s over a 2 s window
    assert _read("hub.stall_ms_per_s", ctx) == pytest.approx(150.0)
    assert _read("host.gc_ms_per_s", ctx) == pytest.approx(90.0)
    # build 0.1 + iter0 0.2001 + wait 0.3 + finalize 0.03 + last wait 0.02
    assert _read("solve.outside_loop_ms", ctx) == pytest.approx(650.1)


def test_traced_run_leaves_out_the_profiler_iterations():
    first = TRACE["from_iteration"] + 1
    solve = _solve(0)
    for r in solve["rows"]:
        r["iter"] += first - 2       # the stalled row is now first + 2
    its = spanmap.window_iterations(_ctx([solve], traced=20))
    assert first not in [it["iter"] for it in its]
    assert len(its) == 3


def test_a_program_without_spans_reads_nothing():
    ctx = _ctx([_solve(0, spans=False)])
    for name in NEW:
        assert _read(name, ctx) is None, name


@pytest.fixture(scope="module")
def events():
    return trace.read_events(PB)


def test_clock_offsets_from_both_ends(events):
    _, host = events
    (lo, hi), = [(a, b) for a, b, n in host if n == trace.WINDOW_SPAN]
    off = 123_456_789.0
    assert spanmap.clock_offsets((lo, hi), (lo - off, hi - off)) \
        == (off, off)


def test_idle_by_span_puts_the_host_only_step_on_its_span(events):
    _, host = events
    with open(os.path.join(FIX, "chip_trace.json")) as f:
        facts = json.load(f)
    (lo, hi), = [(a, b) for a, b, n in host if n == trace.WINDOW_SPAN]
    (s, e), = [(a, b) for a, b, n in host if n == trace.SYNC_SPAN]
    off = -5e9                       # perf_counter reads 5 s ahead
    span = ("harvest", 1, (e - off) * 1e-9, (e - s) * 1e-9)
    rep = spanmap.capture_report(PB, (lo - off, hi - off), [span])
    assert rep["offset_disagree_us"] == 0.0
    idle = rep["idle_by_span"]
    assert idle["harvest"] >= 0.9 * facts["sleep_s"]
    assert sum(idle.values()) == pytest.approx(rep["idle_s"])
    assert rep["idle_by_annotation"]["wheel_sync"] >= 0.9 * facts["sleep_s"]
    assert rep["start_skew_us"] is None    # no wheel/harvest annotation


def test_module_device_time_lies_inside_the_window(events):
    _, host = events
    (lo, hi), = [(a, b) for a, b, n in host if n == trace.WINDOW_SPAN]
    mods = spanmap.module_seconds(spanmap.read_modules(PB), lo, hi)
    assert {n for n, _ in mods} == {"jit_window", "jit_xla_op"}
    assert 0 < sum(s for _, s in mods) <= (hi - lo) * 1e-9


def test_start_skew_against_same_named_annotations():
    host = [(1000.0, 5000.0, "wheel/harvest"), (9000.0, 9500.0,
                                                "wheel/checkpoint")]
    mapped = [(1004.0, 5000.0, "harvest"), (9020.0, 9500.0, "checkpoint"),
              (7000.0, 8000.0, "gc")]
    assert spanmap.start_skew_us(mapped, host) == pytest.approx(0.012)


def test_idle_by_label_takes_the_innermost_span():
    spans = [(0.0, 100.0, "iteration"), (10.0, 40.0, "scalar_wait"),
             (20.0, 25.0, "gc")]
    gaps = [(21.0, 23.0), (30.0, 34.0), (60.0, 70.0), (200.0, 210.0)]
    got = spanmap.idle_by_label(gaps, spans)
    assert got == pytest.approx({"gc": 2e-9, "scalar_wait": 4e-9,
                                 "iteration": 10e-9,
                                 spanmap.NO_SPAN: 10e-9})


def test_traced_tiny_run_reports_the_span_metrics(tmp_path, capsys):
    root = tiny.make_root(str(tmp_path))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            m["workloads"].append(tiny.CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    # a gap the tiny wheel certifies, so that solves end in the window
    traffic = os.path.join(root, "perfbench", "traffic", "tiny.json")
    with open(traffic) as f:
        tr = json.load(f)
    with open(traffic, "w") as f:
        json.dump(dict(tr, rel_gap=1.5), f)
    assert harness.main(tiny.argv(trace=1), root=root,
                        require_tpu=False) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    for name in NEW:
        assert line["metrics"][name]["value"] >= 0, name
