"""A cell defined only by files a test adds: a copy of the benchmark with
a tiny sslp configuration, a traffic mix, a cell and a per-layer metric
of its own, none of which the harness knows by name."""
from __future__ import annotations

import json
import os
import shutil

from perfbench import check

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
CELL = "tiny.t"


def make_root(dest: str, limits: dict | None = None,
              unit: str = "solve") -> str:
    """A checkout-like root at `dest` holding BENCHMARK.json and perfbench/
    with the tiny cell added, its check held to `limits` (the content of
    a perfbench/cells/<cell>.json) and its window counted in `unit`.  On
    the CPU the program and the reference agree to rounding, so the
    default limits can sit far below what any fault reads (the precision
    probe's is a multiple of a rounding's reading)."""
    shutil.copytree(BENCH, os.path.join(dest, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    pb = os.path.join(dest, "perfbench")
    with open(os.path.join(pb, "configs", "sslp_15_45.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "tiny"
    cfg["instance"].update(n_servers=3, n_clients=6)
    cfg["shape"].update(columns=24, rows=9, nonants=3)
    _dump(os.path.join(pb, "configs", "tiny.json"), cfg)
    _dump(os.path.join(pb, "traffic", "tiny.json"), {
        # 512 scenarios: the smallest batch on the split-dispatch path the
        # cells run
        "scenarios": 512, "rel_gap": 0.05,
        "max_iterations": 30, "unit": unit, "warmup_iterations": 2,
        "check": {"solves": 1, "iter_lo": 1, "iter_hi": 3},
        "trace": {"from_iteration": 2, "iterations": 2}})
    _dump(os.path.join(pb, "cells", CELL + ".json"), limits or {
        "limits": dict(dict.fromkeys(check.NAMES, 1e-5),
                       precision_probe=10.0)})
    with open(os.path.join(pb, "metrics", "tiny.solves.py"), "w") as f:
        f.write("def read(ctx):\n    return len(ctx['rec']['solves'])\n")
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "perfbench/configs/tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny",
                               "traffic": "tiny", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append(CELL)
    bench["per_layer"].append({
        "name": "tiny.solves", "unit": "solves", "better": "higher",
        "source": "program_counter", "layer": "test", "moves": "setup_s",
        "workloads": [CELL]})
    _dump(os.path.join(dest, "BENCHMARK.json"), bench)
    return dest


def _dump(path: str, obj) -> None:
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def argv(seconds: float = 6.0, trace: int = 0, seed: int = 2**33 + 5):
    return ["--workload", CELL, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace)]
