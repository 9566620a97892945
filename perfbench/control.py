"""The readings the output check's limits are set from.

    python3 perfbench/control.py --workload <cell> --seeds 1,2,3 \
        --seconds <s> [--out perfbench_readings.jsonl]

In one process, for each seed: one run of the cell (set-up and a window
of `--seconds`, as the benchmark runs it), then for every step the check
draws, readings of every part of the four numbers of perfbench/check.py,
with each of these in the program's place:

    program        the program's step and probe (the lower reading)
    reordered      the reference at the precision the configuration
                   states, each contraction summed in two halves: as
                   precise as the program has to be, rounded in another
                   order than the reference and than the probe's
                   yardstick (thirds).  A sound program may read this
                   high
    control        the reference one precision step below the
                   configuration's (an upper reading): bf16 for the
                   hub's bf16x3 iterations, bf16x3 for the planes that
                   state highest, emulated
    program_lower  the program's step, and its probe with each plane's
                   iteration precision set one step lower (bf16,
                   bf16x3): the program's own path to a precision cut
    faults         the program's step and probe with a fault planted
                   in their outputs (`faults`)

The probe runs each plane from the same state, so each of its parts
reads one plane alone.  The benchmark's own runs never run this.  It
prints one JSON line per step (with the probe's yardstick), and at the
end the largest program and reordered readings and the smallest of the
others, per number and per part.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time

import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from perfbench import check, harness, reference, system  # noqa: E402

#: the scenarios of one tile of the Pallas window
TILE = 128
#: the precision one step below each stated one (emulated, see reference)
LOWER = {"highest": "emul-bf16x3", "bf16x3": "emul-bf16"}
#: the same step below, as the program names it
PROGRAM_LOWER = {"highest": "bf16x3", "bf16x3": "bf16"}
#: the readings that stand for sound programs (their largest counts)
SOUND = ("program", "reordered")


def _lower(table: dict, wheel: dict) -> dict:
    return {k: table[v] for k, v in check.stated_precision(wheel).items()}


def reordered_precision(wheel: dict) -> dict:
    return {k: "split2-" + v
            for k, v in check.stated_precision(wheel).items()}


def faults(post: dict, probe: dict, pre: dict, P: dict,
           wheel: dict) -> dict:
    """{fault: (step outputs, probe outputs)} planted in the program's:
    the hub step returning its state unchanged; x-bar averaged over half
    of the batch, W updated from it; one tile of 128 scenarios left as
    it came in by the probe's window (the wheel's windows would leave W
    and x-bar consistent with it, which only the probe reads); W left as
    it came in on a tile; each bound altered by 1%; and the x-hat
    plane's gates removed, so that it publishes the expectation of its
    iterates' compensated objective in every step."""
    def tile_stale(out, ref):
        return {f: out[f].at[:TILE].set(ref[f][:TILE]) for f in ("x", "y")}
    x_non = P["d_non"] * post["hub_x"][:, P["nonant_idx"]]
    half = jnp.broadcast_to(jnp.mean(x_non[:x_non.shape[0] // 2], 0),
                            x_non.shape)
    ungated = reference.gate_excess(
        P, jnp.reshape(post["xhat_cand"], (-1,)),
        {"x": post["xhat_x"], "y": post["xhat_y"],
         "status": post["xhat_status"]}, 0.0,
        reference.gates_of(wheel))["expect"]
    return {
        "state_unchanged": (
            dict(post, hub_x=pre["hub"]["x"], W=pre["W"], xbar=pre["xbar"]),
            dict(probe, hub={f: pre["hub"][f] for f in ("x", "y")})),
        "half_batch_mean": (
            dict(post, xbar=half, W=pre["W"] + pre["rho"] * (x_non - half)),
            probe),
        "tile_not_written": (
            post, {p: tile_stale(probe[p], pre[p]) for p in check.PLANES}),
        "tile_W_stale": (dict(post, W=post["W"].at[:TILE].set(
            pre["W"][:TILE])), probe),
        "bound_altered": (dict(post, lag_bound=post["lag_bound"] * 1.01),
                          probe),
        "inner_bound_altered": (
            dict(post, xhat_value=post["xhat_value"] * 1.01), probe),
        "gate_removed": (dict(post, xhat_value=ungated), probe),
    }


def readings(spec: dict, seed: int, seconds: float) -> list[dict]:
    """One run of the cell, then every reading of every drawn step."""
    run = harness.Run(spec, seed, seconds, None, time.perf_counter())
    run.compiles = harness.CompileCounter()
    rec = harness.measure(spec, seed, seconds, run)
    captured, run.captured = run.captured, []
    gc.collect()
    wheel = spec["config"]["wheel"]
    out = []
    stated = check.stated_precision(wheel)
    for step in captured:
        P = check.problem(spec, rec["inst"], step, seed)
        ref = check.replay(spec, P, step, stated)
        probe_ref = check.probe_reference(spec, P, step)
        post, prog = step["post"], step["program"]
        probe = system.probe(prog, post["xhat_cand"])
        stand_ins = {"program": (post, probe), "program_lower": (
            post, system.probe(prog, post["xhat_cand"],
                               _lower(PROGRAM_LOWER, wheel)))}
        for name, precision in (("reordered", reordered_precision(wheel)),
                                ("control", _lower(LOWER, wheel))):
            stand_ins[name] = (
                dict(check.replay(spec, P, step, precision),
                     xhat_cand=post["xhat_cand"]),
                check.probe(spec, P, step, precision))
        stand_ins.update(faults(post, probe, step["pre"], P, wheel))
        parts = {name: check.all_parts(spec, P, step, ref, o, p, probe_ref)
                 for name, (o, p) in stand_ins.items()}
        out.append({"seed": seed, "solve": step["solve"],
                    "iteration": step["iteration"],
                    "windows": list(step["windows"]),
                    "yardstick": probe_ref[1],
                    **{k: check.numbers(v) for k, v in parts.items()},
                    "parts": parts})
        del P, ref, probe_ref, probe, stand_ins
    missing = sorted(run.check_at - {(s["solve"], s["iteration"])
                                     for s in captured})
    if missing:
        out.append({"seed": seed, "missing_steps": missing})
    return out


def summary(rows: list[dict]) -> dict:
    """Per reading, per number and per part: the largest over the steps
    for the sound readings, the smallest for the others."""
    steps = [r for r in rows if "parts" in r]
    if not steps:
        return {"steps": 0}
    out = {"steps": len(steps)}
    for name in steps[0]["parts"]:
        pick = max if name in SOUND else min
        out[name] = {
            num: {"number": pick(r[name][num] for r in steps),
                  "parts": {p: pick(r["parts"][name][num][p] for r in steps)
                            for p in steps[0]["parts"][name][num]}}
            for num in check.NAMES}
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
        rows += got
    print(json.dumps(dict(summary(rows), workload=args.workload)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
