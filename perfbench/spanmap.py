"""The program's own spans, as the hub's iteration rows carry them, and
their map onto a profiler capture.

Each row of a solve's `rows` (the hub's trace view) holds, under
`spans`, the program spans that ended after it until the next row; the
first row also holds those before it.  A span is `(name, hub_iter,
t_end, dur_s)` on the perf_counter clock.  Rows also carry `gc_s` /
`gc_n`, the collector's pauses since the previous row.  A program
without spans leaves these keys out, and every function here then
returns None.

The capture side: an offset maps perf_counter nanoseconds onto the
capture's clock, taken where the benchmark opens and where it closes its
`perfbench/traced` annotation (`clock_offsets`) and interpolated between
the two.  With it each device
idle gap gets the innermost program span over it (`idle_by_span`),
collector pauses included, which have no annotation.

    python3 -m perfbench.spanmap --workload <cell> --seed <n> \\
        --seconds <s> [--keep DIR]

runs one traced window of a cell as the harness does (without the output
check), stamps `perfbench/traced` on the perf_counter clock, and prints
one JSON line: the cell's per-layer metrics, the two clock offsets, the
start skew of spans against their annotations, idle time by program span
and by annotation, device time by XLA module per traced iteration, span
coverage of each iteration and the slow iterations with their spans.
"""
from __future__ import annotations

import bisect
import re
import statistics

WAIT = "scalar_wait"
GC = "gc"
NO_SPAN = "no program span"
_MODULE_ID = re.compile(r"\(\d+\)$")


# -- the rows ----------------------------------------------------------
def solve_spans(rows) -> list | None:
    """Every span of one solve, in the order they ended (collector pauses
    are reported after they end); None when the rows carry no spans."""
    if not rows or "spans" not in rows[0]:
        return None
    return sorted((tuple(s) for r in rows for s in r["spans"]),
                  key=lambda s: s[2])


def iterations(solve: dict, skip_iters=()) -> list[dict] | None:
    """One entry per hub iteration of the solve's loop (row to row):
    `iter`, `wall`, `wait` (its scalar_wait), `gc` (its gc_s), `spans`
    (seconds by name) and `covered` (leaf spans and gc pauses, as a
    union).  Row iterations in `skip_iters` are left out."""
    rows = solve["rows"]
    spans = solve_spans(rows)
    if spans is None:
        return None
    ends = [s[2] for s in spans]
    out = []
    for prev, row in zip(rows, rows[1:]):
        if row.get("iter") in skip_iters:
            continue
        lo, hi = prev["t"], row["t"]
        inside = spans[bisect.bisect_right(ends, lo):
                       bisect.bisect_right(ends, hi)]
        by_name: dict[str, float] = {}
        for name, _, _, dur in inside:
            by_name[name] = by_name.get(name, 0.0) + dur
        covered = _union_len([(max(t - d, lo), t) for _, _, t, d in inside])
        out.append({"iter": row.get("iter"), "wall": hi - lo,
                    "wait": by_name.get(WAIT, 0.0),
                    "gc": row.get("gc_s"), "spans": by_name,
                    "covered": covered})
    return out


def window_iterations(ctx) -> list[dict] | None:
    """The loop iterations of every solve of the window.  In a traced
    run the two iterations in which the harness starts and stops the
    profiler (it waits for the device there, outside any program span)
    are left out of solve 0."""
    tr = ctx["spec"]["traffic"]["trace"]
    # the harness acts in loop iteration k, whose row is hub iteration
    # k + 1 (iter0's sync is hub iteration 1)
    first = tr["from_iteration"] + 1
    profiler_iters = (first, first + tr["iterations"]) \
        if ctx.get("traced_iterations") else ()
    out = []
    for s in ctx["rec"]["solves"]:
        its = iterations(s, profiler_iters if s.get("solve") == 0 else ())
        if its is None:
            return None
        out += its
    return out


def window_seconds(ctx) -> float:
    rec = ctx["rec"]
    return rec["t_w1"] - rec["t_w0"]


def host_busy(its) -> list[float]:
    """Host seconds of each iteration: its wall less its scalar wait."""
    return [it["wall"] - it["wait"] for it in its]


def outside_spans(solve: dict) -> list | None:
    """The solve's spans that end before its first row or after its
    last: the wheel build, iter0's dispatch, wait and sync, and finalize
    with its last scalar wait (collector pauses among them)."""
    rows = solve["rows"]
    spans = solve_spans(rows)
    if spans is None:
        return None
    lo, hi = rows[0]["t"], rows[-1]["t"]
    return [s for s in spans if s[2] <= lo or s[2] > hi]


def outside_loop(solve: dict) -> float | None:
    """Seconds of the solve outside its loop, collector pauses aside
    (they overlap the span they interrupted)."""
    spans = outside_spans(solve)
    if spans is None:
        return None
    return sum(d for name, _, _, d in spans if name != GC)


def _union_len(intervals) -> float:
    total, end = 0.0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


# -- the capture -------------------------------------------------------
def clock_offsets(window_ns, stamps_ns) -> tuple[float, float]:
    """Capture clock minus perf_counter, in ns, at the opening and at
    the closing end of `perfbench/traced`: its (start, end) in the
    capture and its two perf_counter_ns stamps."""
    (lo, hi), (t_open, t_close) = window_ns, stamps_ns
    return lo - t_open, hi - t_close


def map_spans(spans, stamps_ns, offsets_ns) -> list[tuple[float, float,
                                                          str]]:
    """Program spans as (start, end, name) on the capture's clock, the
    offset interpolated between its two ends (the clocks may drift)."""
    (t0, t1), (o0, o1) = stamps_ns, offsets_ns
    slope = (o1 - o0) / (t1 - t0) if t1 != t0 else 0.0

    def at(t_ns):
        return t_ns + o0 + slope * (t_ns - t0)
    return [(at((t - d) * 1e9), at(t * 1e9), name)
            for name, _, t, d in spans]


def idle_gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The device-idle gaps of one chip's busy union inside [lo, hi];
    under a microsecond is the clock's granularity, not idleness."""
    return [(a, b) for a, b in zip([lo] + [e for _, e in busy],
                                   [s for s, _ in busy] + [hi])
            if b - a >= 1e3]


def label_gaps(gaps, spans, none: str = NO_SPAN) -> list[str]:
    """The innermost (shortest) span over each gap's midpoint; `spans`
    are (start, end, name) on the capture's clock."""
    spans = sorted(spans)
    starts = [s[0] for s in spans]
    longest = max((e - s for s, e, _ in spans), default=0.0)
    out = []
    for a, b in gaps:
        mid = 0.5 * (a + b)
        j = bisect.bisect_right(starts, mid)
        i = bisect.bisect_left(starts, mid - longest)
        over = [(e - s, n) for s, e, n in spans[i:j] if mid <= e]
        out.append(min(over)[1] if over else none)
    return out


def idle_by_label(gaps, spans, none: str = NO_SPAN) -> dict[str, float]:
    """Idle seconds by the innermost span over each gap (`label_gaps`)."""
    out: dict[str, float] = {}
    for (a, b), name in zip(gaps, label_gaps(gaps, spans, none)):
        out[name] = out.get(name, 0.0) + (b - a) * 1e-9
    return out


def start_skew_us(mapped, host) -> float | None:
    """Median distance, in microseconds, from each mapped program span's
    start to the start of the nearest annotation of the same name
    (`wheel/<name>`)."""
    by_name: dict[str, list[float]] = {}
    for s, _, n in host:
        by_name.setdefault(n, []).append(s)
    for v in by_name.values():
        v.sort()
    skews = []
    for s, _, name in mapped:
        starts = by_name.get(f"wheel/{name}")
        if not starts:
            continue
        j = bisect.bisect_left(starts, s)
        near = [starts[k] for k in (j - 1, j) if 0 <= k < len(starts)]
        skews.append(min(abs(x - s) for x in near) * 1e-3)
    return statistics.median(skews) if skews else None


def read_modules(path: str) -> dict[str, list[tuple[float, float]]]:
    """The first chip's "XLA Modules" line: intervals (ns) by module name,
    the `(<fingerprint>)` suffix dropped."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = sorted((p for p in pd.planes
                     if p.name.startswith(("/device:TPU:", "/device:CPU:"))),
                    key=lambda p: p.name)
    out: dict[str, list] = {}
    for plane in planes[:1]:
        for line in plane.lines:
            if line.name != "XLA Modules":
                continue
            for e in line.events:
                name = _MODULE_ID.sub("", e.name)
                out.setdefault(name, []).append(
                    (e.start_ns, e.start_ns + e.duration_ns))
    return out


def module_seconds(modules, lo: float, hi: float) -> list[list]:
    """[[module, seconds inside [lo, hi]], ...], most time first."""
    secs = {n: sum(min(b, hi) - max(a, lo) for a, b in iv
                   if b > lo and a < hi) * 1e-9
            for n, iv in modules.items()}
    return sorted(([n, s] for n, s in secs.items() if s > 0),
                  key=lambda kv: -kv[1])


def capture_report(path: str, stamps_ns, spans) -> dict | None:
    """Everything the capture says about the program spans of the traced
    window: offsets, skew, idle by program span and by annotation (the
    harness's labelling, over every gap), device time by module."""
    from perfbench import trace
    devices, host = trace.read_events(path)
    windows = [(a, b) for a, b, n in host if n == trace.WINDOW_SPAN]
    if not windows:
        return None
    lo, hi = windows[0]
    off_open, off_close = clock_offsets((lo, hi), stamps_ns)
    mapped = [m for m in map_spans(spans, stamps_ns, (off_open, off_close))
              if m[1] > lo and m[0] < hi]
    out = {"window_s": (hi - lo) * 1e-9,
           "offset_open_ns": off_open, "offset_close_ns": off_close,
           "offset_disagree_us": abs(off_open - off_close) * 1e-3,
           "start_skew_us": start_skew_us(mapped, host)}
    if not devices:                  # a host-only capture (the CPU)
        return out
    ops = devices[sorted(devices)[0]]
    busy = trace.union((max(a, lo), min(b, hi)) for a, b, _ in ops
                       if b > lo and a < hi)
    gaps = idle_gaps(busy, lo, hi)
    annotations = [(a, b, n) for a, b, n in host if n != trace.WINDOW_SPAN]
    # the longest gaps: ms from the window's opening end, ms to its
    # closing end, length in ms, the program span over it
    longest = sorted(([(a - lo) * 1e-6, (hi - b) * 1e-6, (b - a) * 1e-6, n]
                      for (a, b), n in zip(gaps, label_gaps(gaps, mapped))),
                     key=lambda g: -g[2])
    return dict(out, idle_s=sum(b - a for a, b in gaps) * 1e-9,
                longest_gaps_ms=longest[:5],
                idle_by_span=idle_by_label(gaps, mapped),
                idle_by_annotation=idle_by_label(gaps, annotations,
                                                 "no host span"),
                modules=module_seconds(read_modules(path), lo, hi))


def _outside_by_name(solves) -> dict[str, float]:
    """Mean seconds per certified solve of each span outside its loop."""
    done = [s for s in solves if s["converged"]]
    out: dict[str, float] = {}
    for s in done:
        for name, _, _, d in outside_spans(s) or []:
            out[name] = out.get(name, 0.0) + d / len(done)
    return out


# -- one traced window, kept -------------------------------------------
class _Stamped:
    """`perfbench/traced` with a perf_counter_ns stamp at each end: the
    midpoint of two reads around the annotation's own start (it stamps
    itself when built, so it is built here) and around its end."""

    def __init__(self, make, stamps: list):
        self._make, self._stamps = make, stamps

    def __enter__(self):
        import time
        a = time.perf_counter_ns()
        self._inner = self._make()
        self._inner.__enter__()
        self._stamps.append((a + time.perf_counter_ns()) // 2)
        return self

    def __exit__(self, *exc):
        import time
        a = time.perf_counter_ns()
        self._inner.__exit__(*exc)
        self._stamps.append((a + time.perf_counter_ns()) // 2)
        return False


def main(argv=None) -> int:
    import argparse
    import contextlib
    import json
    import os
    import shutil
    import sys
    import tempfile
    import time

    import jax

    from perfbench import harness, trace
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", help="copy the capture into this directory")
    args = ap.parse_args(argv)
    t_start = time.perf_counter() - harness.process_age_s()
    root = os.getcwd()
    spec = harness.load_cell(root, args.workload)
    device = harness.device_info(int(spec["cell"]["chips"]), True)
    harness.enable_cache(root)
    trace_dir = tempfile.mkdtemp(prefix="perfbench-spanmap-")
    stamps: list[int] = []

    class StampedRun(harness.Run):
        def _stamping(self, fn, *a):
            orig = jax.profiler.TraceAnnotation
            jax.profiler.TraceAnnotation = lambda name: (
                _Stamped(lambda: orig(name), stamps)
                if name == trace.WINDOW_SPAN else orig(name))
            try:
                return fn(*a)
            finally:
                jax.profiler.TraceAnnotation = orig

        def on_iteration_start(self, hooks, k):
            return self._stamping(super().on_iteration_start, hooks, k)

    run = StampedRun(spec, args.seed, args.seconds, trace_dir, t_start)
    run.compiles = harness.CompileCounter()
    try:
        with contextlib.redirect_stdout(sys.stderr):
            rec = harness.measure(spec, args.seed, args.seconds, run)
        run.captured = []
        path = trace.newest_xplane(trace_dir)
        if args.keep and path:
            os.makedirs(args.keep, exist_ok=True)
            shutil.copy(path, os.path.join(
                args.keep, f"{args.workload}-{args.seed}.xplane.pb"))
        summary = trace.summarize(path) if path else None
        traced = [s for s in rec["solves"] if s["solve"] == 0]
        spans = solve_spans(traced[0]["rows"]) if traced else None
        report = capture_report(path, stamps, spans or []) \
            if path and len(stamps) == 2 else None
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = {"spec": spec, "rec": rec,
           "counts": harness.window_counts(spec, rec), "trace": summary,
           "device": device, "traced_iterations": run.traced_iterations,
           "seed": args.seed}
    metrics = {m["name"]: harness.reader(root, m["name"])(ctx)
               for m in spec["per_layer"]}
    its = window_iterations(ctx) or []
    cover = [it["covered"] / it["wall"] for it in its if it["wall"] > 0]
    busy = host_busy(its)
    med = statistics.median(busy) if busy else None
    slow = [dict(it, busy=b) for it, b in zip(its, busy) if b > 0.05]
    out = {"metrics": metrics, "capture": report, "stamps_ns": stamps,
           "traced_iterations": run.traced_iterations,
           "iterations": len(its), "solves": len(rec["solves"]),
           "coverage_median": statistics.median(cover) if cover else None,
           "coverage_min": min(cover) if cover else None,
           "busy_median_s": med, "slow_iterations": slow,
           "outside_loop_s": [outside_loop(s) for s in rec["solves"]],
           "outside_by_name_s": _outside_by_name(rec["solves"])}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
