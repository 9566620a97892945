"""The readings the output check's limits are set from.

    python3 perfbench/control.py --workload <cell> --seeds 1,2,3 \
        --seconds <s> [--out perfbench_readings.jsonl]

In one process, for each seed: one run of the cell (set-up and a window
of `--seconds`, as the benchmark runs it), then for every step the check
draws, readings of the three numbers of perfbench/check.py:

    program  the program's step against the reference (the lower reading)
    control  the reference computed one precision step below the
             configuration's, put in the program's place (the upper
             reading): bf16 for the hub's bf16x3 iterations, bf16x3 for
             the planes that state highest
    faults   the program's step with a fault planted in its outputs on
             one tile of 128 scenarios: the hub window's iterates left as
             they came in, or W left as it came in

The benchmark's own runs never run this.  It prints one JSON line per
step and the per-number maxima (program) and minima (control) at the end.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from perfbench import check, harness  # noqa: E402

#: the scenarios of one tile of the Pallas window
TILE = 128
#: the precision one step below each stated one (emulated, see reference)
LOWER = {"highest": "emul-bf16x3", "bf16x3": "emul-bf16"}


def control_precision(wheel: dict) -> dict:
    return {k: LOWER[v] for k, v in check.stated_precision(wheel).items()}


def readings(spec: dict, seed: int, seconds: float) -> list[dict]:
    """One run of the cell, then both readings of every drawn step."""
    run = harness.Run(spec, seed, seconds, None, time.perf_counter())
    run.compiles = harness.CompileCounter()
    rec = harness.measure(spec, seed, seconds, run)
    captured, run.captured = run.captured, []
    gc.collect()
    wheel = spec["config"]["wheel"]
    out = []
    for step in captured:
        pre = step["pre"]
        post, ref, P = check.replay(spec, rec["inst"], step, seed,
                                    check.stated_precision(wheel))
        _, ctrl, _ = check.replay(spec, rec["inst"], step, seed,
                                  control_precision(wheel))
        hub = post["hub"]
        faults = {
            "tile_not_written": dict(post, hub=dict(
                hub, x=hub["x"].at[:TILE].set(pre["hub"]["x"][:TILE]),
                y=hub["y"].at[:TILE].set(pre["hub"]["y"][:TILE]))),
            "tile_W_stale": dict(post, W=post["W"].at[:TILE].set(
                pre["W"][:TILE]))}
        out.append({"seed": seed, "solve": step["solve"],
                    "iteration": step["iteration"],
                    "windows": list(step["windows"]),
                    "program": check.compare(post, ref, P, pre),
                    "control": check.compare(ctrl, ref, P, pre),
                    "faults": {k: check.compare(v, ref, P, pre)
                               for k, v in faults.items()}})
        del post, ref, ctrl, faults
    missing = sorted(run.check_at - {(s["solve"], s["iteration"])
                                     for s in captured})
    if missing:
        out.append({"seed": seed, "missing_steps": missing})
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    root = os.getcwd()
    spec = harness.load_cell(root, args.workload)
    harness.device_info(int(spec["cell"]["chips"]), require_tpu=True)
    harness.enable_cache(root)
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        with contextlib.redirect_stdout(sys.stderr):
            got = readings(spec, seed, args.seconds)
        for r in got:
            print(json.dumps(r), flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(dict(r, workload=args.workload))
                            + "\n")
        rows += [r for r in got if "program" in r]
    summary = {
        "workload": args.workload, "steps": len(rows),
        "program_max": {k: max(r["program"][k] for r in rows)
                        for k in check.NAMES} if rows else None,
        "control_min": {k: min(r["control"][k] for r in rows)
                        for k in check.NAMES} if rows else None,
        "faults_min": {f: {k: min(r["faults"][f][k] for r in rows)
                           for k in check.NAMES}
                       for f in rows[0]["faults"]} if rows else None,
    }
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
