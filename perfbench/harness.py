"""One run of one cell: set-up, the measured window, the output check, the
result line.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Everything a cell needs is found by name from BENCHMARK.json: the
configuration's file, perfbench/traffic/<traffic>.json,
perfbench/cells/<cell>.json (the check's limits) and one reader per
metric, perfbench/metrics/<metric>.py.  A new cell or metric is new files
and new entries; no file here changes.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

from perfbench import traffic as traffic_mod

BENCH_DIR = "perfbench"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def process_age_s() -> float:
    """Seconds since this process started (Linux /proc clock ticks)."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: str, workload: str) -> dict:
    """The cell's BENCHMARK.json entries and every file it names."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(os.path.join(root, configs[cell["config"]]["file"]))
    traffic = traffic_mod.validate(_load_json(os.path.join(
        root, BENCH_DIR, "traffic", cell["traffic"] + ".json")),
        cell["traffic"])
    limits = _load_json(os.path.join(root, BENCH_DIR, "cells",
                                     workload + ".json"))
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    # every per-layer metric here names its cells
    layer = [m for m in bench["per_layer"] if workload in m["workloads"]]
    return {"root": root, "name": workload, "cell": cell, "config": config,
            "traffic": traffic, "limits": limits, "end_to_end": e2e,
            "per_layer": layer}


def reader(root: str, metric: str):
    """perfbench/metrics/<metric>.py's read(ctx)."""
    path = os.path.join(root, BENCH_DIR, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX's first device is "
                         f"{devs[0].platform!r}; refusing to measure")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def enable_cache(root: str) -> None:
    """JAX's persistent compilation cache at a fixed path in the
    checkout (or where JAX_COMPILATION_CACHE_DIR says), every program
    cached, so only a checkout's first run of a cell compiles."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class CompileCounter:
    """Counts XLA compilations (persistent-cache loads included)."""

    def __init__(self):
        import jax
        self.names: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    @property
    def n(self) -> int:
        return len(self.names)

    def _on(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.names.append(str(kw.get("fun_name", "?")))


class Run:
    """The window's clock, the profiler and the check's capture list;
    system.Hooks call into it from inside the wheel."""

    def __init__(self, spec: dict, seed: int, seconds: float,
                 trace_dir: str | None, t_start: float):
        self.spec, self.seed, self.seconds = spec, seed, seconds
        self.traffic = spec["traffic"]
        self.trace_dir = trace_dir
        self.t_start = t_start
        self.t_w0 = self.deadline = None
        self.tracing = None            # the open perfbench/traced span
        self.traced_iterations = 0
        self.captured: list[dict] = []
        chk = self.traffic["check"]
        r = traffic_mod.rng(seed, 1 << 30)
        self.check_at = {(j, int(r.integers(chk["iter_lo"],
                                            chk["iter_hi"] + 1)))
                         for j in range(chk["solves"])}
        self.system = None
        self.compiles = None

    def start_window(self):
        self.compiles_before = self.compiles.n
        self.t_w0 = time.perf_counter()
        self.deadline = self.t_w0 + self.seconds

    def window_over(self) -> bool:
        return self.deadline is not None \
            and time.perf_counter() >= self.deadline

    def on_iteration_start(self, hooks, k: int):
        import jax
        tr = self.traffic
        if tr["unit"] == "iteration" and self.t_w0 is None \
                and k == tr["warmup_iterations"] + 1:
            self.system.warm_variants(hooks.opt)
            jax.block_until_ready(hooks.opt.wstate)
            self.start_window()
        if self.trace_dir is None or self.t_w0 is None or hooks.solve != 0:
            return
        first = tr["trace"]["from_iteration"]
        if k == first and self.tracing is None:
            jax.block_until_ready(hooks.opt.wstate)
            jax.profiler.start_trace(self.trace_dir,
                                     profiler_options=_profile_options())
            self.tracing = jax.profiler.TraceAnnotation("perfbench/traced")
            self.tracing.__enter__()
        elif self.tracing is not None:
            if k == first + tr["trace"]["iterations"]:
                jax.block_until_ready(hooks.opt.wstate)
                self.stop_trace()
                return
        if self.tracing is not None:
            self.traced_iterations += 1

    def stop_trace(self):
        import jax
        if self.tracing is not None:
            self.tracing.__exit__(None, None, None)
            self.tracing = None
            jax.profiler.stop_trace()


def _profile_options():
    import jax
    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0
    return o


def measure(spec: dict, seed: int, seconds: float, run: Run) -> dict:
    """Set-up and the measured window.  Returns the run's records."""
    import jax

    from perfbench import system
    tr, cfg = spec["traffic"], spec["config"]
    inst_cfg = cfg["instance"]
    inst = traffic_mod.siplib_instance(inst_cfg)
    sysm = system.System(cfg, tr, inst)
    run.system = sysm
    S = tr["scenarios"]
    solves = []
    if tr["unit"] == "solve":
        # warm-up: the cell's shapes through the same entry points, on a
        # sample no solve of the window uses
        cp = traffic_mod.client_present(inst_cfg, S, seed, -1)
        sysm.cfg.quick_assign("max_iterations", int,
                              tr["warmup_iterations"])
        hooks = system.Hooks(run, -1)
        rec = sysm.solve(cp, hooks)
        sysm.warm_variants(rec["wheel"].opt)
        del rec
        sysm.cfg.quick_assign("max_iterations", int, tr["max_iterations"])
        run.start_window()
        k = 0
        while not run.window_over():
            cp = traffic_mod.client_present(inst_cfg, S, seed, k)
            rec = sysm.solve(cp, system.Hooks(run, k))
            rec.pop("wheel")
            rec["solve"] = k
            solves.append(rec)
            k += 1
    else:
        cp = traffic_mod.client_present(inst_cfg, S, seed, 0)
        rec = sysm.solve(cp, system.Hooks(run, 0))
        rec.pop("wheel")
        rec["solve"] = 0
        solves.append(rec)
    t_w1 = time.perf_counter()
    run.stop_trace()
    compiles = run.compiles.names[run.compiles_before:]
    stats = jax.devices()[0].memory_stats() or {}
    return {"solves": solves, "t_w0": run.t_w0, "t_w1": t_w1,
            "setup_s": run.t_w0 - run.t_start, "compiles": compiles,
            "memory_peak_bytes": stats.get("peak_bytes_in_use"),
            "inst": inst}


def window_counts(spec: dict, rec: dict) -> dict:
    """attempted / failed and the hub iterations inside the window."""
    tr = spec["traffic"]
    solves = rec["solves"]
    if tr["unit"] == "solve":
        ended = [s for s in solves if s["converged"]
                 or s["iterations"] >= tr["max_iterations"]]
        iters = sum(s["iterations"] for s in solves)
        return {"attempted": len(ended),
                "failed": sum(not s["converged"] for s in ended),
                "iterations": iters}
    iters = solves[0]["iterations"] - tr["warmup_iterations"]
    return {"attempted": iters, "failed": 0, "iterations": iters}


def main(argv=None, root: str | None = None, require_tpu: bool = True,
         t_start: float | None = None) -> int:
    if t_start is None:
        t_start = time.perf_counter() - process_age_s()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = root or os.getcwd()
    spec = load_cell(root, args.workload)
    device = device_info(int(spec["cell"]["chips"]), require_tpu)
    enable_cache(root)
    trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-") \
        if args.trace else None
    run = Run(spec, args.seed, args.seconds, trace_dir, t_start)
    run.compiles = CompileCounter()
    try:
        # the program logs progress on stdout; the result line owns it
        with contextlib.redirect_stdout(sys.stderr):
            rec = measure(spec, args.seed, args.seconds, run)
            counts = window_counts(spec, rec)
            captured, run.captured = run.captured, []
            gc.collect()
            from perfbench import check, system
            checks = check.run_checks(spec, rec, captured, run.check_at,
                                      args.seed, system.probe)
            del captured
            summary = None
            if trace_dir is not None:
                from perfbench import trace
                path = trace.newest_xplane(trace_dir)
                summary = trace.summarize(path) if path else None
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = {"spec": spec, "rec": rec, "counts": counts, "trace": summary,
           "device": device,
           "traced_iterations": run.traced_iterations, "seed": args.seed}
    wanted = spec["per_layer"] if args.trace else spec["end_to_end"]
    metrics = {}
    for m in wanted:
        v = reader(root, m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = dict(device, memory_peak_bytes=rec["memory_peak_bytes"])
    out = {"correct": all(c["value"] <= c["limit"]
                          for c in checks.values()) and bool(checks),
           "attempted": counts["attempted"], "failed": counts["failed"],
           "metrics": metrics, "device": device}
    if args.trace and summary is not None:
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        out["breakdown"] = {"device_ops": summary["device_ops"],
                            "idle_gaps": summary["idle_gaps"]}
    out["checks"] = checks
    print(f"perfbench: {counts['iterations']} hub iterations, "
          f"{len(rec['solves'])} solves in "
          f"{rec['t_w1'] - rec['t_w0']:.3f} s; compilations inside the "
          f"window: {len(rec['compiles'])} {rec['compiles'][:20]}",
          file=sys.stderr)
    for s in rec["solves"]:
        ts = [r["t"] for r in s["rows"] if "t" in r]
        longest = max((b - a for a, b in zip(ts, ts[1:])), default=0.0)
        print(f"solve {s['solve']}: {s['iterations']} iterations, "
              f"converged {s['converged']}, {s['seconds']:.3f} s, "
              f"rel_gap {s['rel_gap']:.4g}, longest iteration "
              f"{longest:.3f} s", file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
