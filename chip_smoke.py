"""Bring-up check: the main path once on the chip, through the normal
entry points, at full sslp_15_45 width.

    python chip_smoke.py              # one chip (what the driver runs)
    python chip_smoke.py --chips 4    # the sharded scenario-axis path

Every phase runs in this one process (a chip belongs to one process at a
time), prints one informational JSON line when it passes, and a failed
phase exits nonzero at once with its reason on stderr.  The last stdout
line is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

One chip: device -> known answer (farmer 3-scenario EF through
generic_cylinders.main) -> main path (LP-relaxed sslp_15_45 at S=10,000,
fused wheel with Lagrangian + xhat-xbar bounds) -> Pallas window
(sslp_15_45 at S=100,000: one PH iteration on the auto-selected kernel
against the XLA window on the same inputs).

--chips 4: device -> the fused wheel at S=10,000 sharded over a 4-chip
mesh against the same wheel on one device.

Seconds printed here are informational, never a metric.  There is no
CPU fallback: without a TPU the device phase fails.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time

#: sslp_15_45 LP relaxation, as the benchmark and the README run it
SSLP_ARGS = ["--module-name", "mpisppy_tpu.models.sslp",
             "--n-servers", "15", "--n-clients", "45", "--sslp-lp-relax",
             "--default-rho", "20", "--lagrangian", "--xhatxbar",
             "--fused-wheel", "--iter-precision", "bf16x3",
             "--convthresh", "0"]
#: ClientPresent draws of the synthetic instance (sslp.kw_creator)
INST_SEED = 0
#: bf16x3 per-matvec relative error (docs/precision.md, measured on v5e)
BF16X3_REL_ERR = 4e-6


class PhaseFailed(Exception):
    pass


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


def _finite(v):
    """Strict JSON: a non-finite float (a gap before both bounds land)
    prints as null."""
    if isinstance(v, float) and not math.isfinite(v):
        return None
    if isinstance(v, dict):
        return {k: _finite(x) for k, x in v.items()}
    return v


def _emit(phase: str, **info) -> None:
    print(json.dumps(_finite({"phase": phase, **info})), flush=True)


def phase_device(chips: int) -> dict:
    import jax

    from mpisppy_tpu.telemetry import roofline
    devs = jax.devices()
    d0 = devs[0]
    _check(d0.platform == "tpu",
           f"no TPU: JAX's first device is {d0.platform!r}")
    _check(len(devs) >= chips, f"need {chips} chips, JAX sees {len(devs)}")
    # the peak table must know this chip (telemetry/roofline.py)
    peaks = roofline.device_peaks(d0.device_kind)
    info = {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}
    _emit("device", **info, peaks=peaks)
    return info


def phase_known_answer() -> None:
    from mpisppy_tpu import generic_cylinders
    t0 = time.perf_counter()
    ef = generic_cylinders.main(["--module-name", "mpisppy_tpu.models.farmer",
                                 "--num-scens", "3", "--EF"])
    obj = float(ef.get_objective_value())
    want = -108_390.0
    _check(abs(obj - want) <= 1e-3 * abs(want),
           f"farmer EF objective {obj} != {want} within 1e-3 relative")
    _emit("known_answer", ef_objective=obj, want=want,
          seconds=time.perf_counter() - t0)


def _wheel_rows(wheel) -> list:
    rows = wheel.spcomm.trace
    _check(len(rows) >= 2, f"only {len(rows)} hub iterations ran")
    return rows


def phase_main_path(num_scens: int = 10_000, iters: int = 40) -> None:
    from mpisppy_tpu import generic_cylinders
    t0 = time.perf_counter()
    wheel = generic_cylinders.main(
        SSLP_ARGS + ["--num-scens", str(num_scens),
                     "--max-iterations", str(iters)])
    rows = _wheel_rows(wheel)
    outer, inner = wheel.BestOuterBound, wheel.BestInnerBound
    _check(math.isfinite(outer) and math.isfinite(inner),
           f"bounds not finite: outer {outer} inner {inner}")
    _check(outer <= inner, f"outer {outer} > inner {inner}")
    first, last = rows[0]["rel_gap"], rows[-1]["rel_gap"]
    _check(last < first, f"rel_gap did not fall: {first} -> {last}")
    # rows carry the hub's perf_counter stamp; the first includes the
    # batch build, iter0 and every compile
    ts = [r["t"] for r in rows]
    k = min(2, len(ts) - 2)       # skip the iterk compile
    _emit("main_path", scenarios=num_scens, hub_iterations=len(rows),
          outer=outer, inner=inner, rel_gap_first=first,
          rel_gap_last=last,
          seconds_to_first_iteration=ts[0] - t0,
          steady_seconds_per_iteration=(ts[-1] - ts[k]) / (len(ts) - 1 - k))


def sslp_batch(num_scens: int):
    """sslp_15_45 LP-relaxed scenarios through the model's own creator
    (the same batch generic_cylinders builds)."""
    from mpisppy_tpu.core.batch import from_specs
    from mpisppy_tpu.models import sslp
    inst = sslp.synthetic_instance(15, 45, seed=INST_SEED)
    return from_specs([
        sslp.scenario_creator(nm, instance=inst, num_scens=num_scens,
                              lp_relax=True)
        for nm in sslp.scenario_names_creator(num_scens)])


def _ph_options(**kw):
    from mpisppy_tpu.algos import ph as ph_mod
    from mpisppy_tpu.ops import pdhg
    return ph_mod.PHOptions(
        default_rho=20.0, subproblem_windows=8,
        pdhg=pdhg.PDHGOptions(tol=1e-6, restart_period=40,
                              iter_precision="bf16x3"), **kw)


def _rel_diff(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def phase_pallas(num_scens: int = 100_000) -> None:
    import dataclasses

    import jax
    import jax.numpy as jnp

    from mpisppy_tpu.algos import ph as ph_mod
    t0 = time.perf_counter()
    batch = sslp_batch(num_scens)
    opts = _ph_options(iter0_windows=8)
    ko = ph_mod.kernel_opts(opts)
    rho = jnp.full((batch.num_nonants,), opts.default_rho)
    st, _, _ = ph_mod.ph_iter0(batch, rho, ko)
    t_build = time.perf_counter() - t0
    # engine auto-selection: ops/pdhg.py picks the kernel on a TPU at
    # S >= 32,768; the compiled step shows which engine it took
    compiled = ph_mod.ph_iterk.lower(batch, st, ko).compile()
    has_kernel = "tpu_custom_call" in compiled.as_text()
    on_tpu = jax.devices()[0].platform == "tpu"
    _check(has_kernel == on_tpu,
           f"tpu_custom_call in the compiled step: {has_kernel}")
    pal = compiled(batch, st)
    xla_opts = ph_mod.kernel_opts(dataclasses.replace(
        opts, pdhg=dataclasses.replace(opts.pdhg, use_pallas=False)))
    ref = ph_mod.ph_iterk(batch, st, xla_opts)
    jax.block_until_ready((pal.conv, ref.conv))
    # the windows differ only in bf16x3 rounding order: 2 matvecs per
    # PDHG iteration, subproblem_windows x restart_period iterations,
    # each within BF16X3_REL_ERR, with 4x slack
    n_mv = 2 * opts.subproblem_windows * opts.pdhg.restart_period
    tol = 4 * n_mv * BF16X3_REL_ERR
    dx = _rel_diff(pal.solver.x, ref.solver.x)
    dy = _rel_diff(pal.solver.y, ref.solver.y)
    dxbar = _rel_diff(pal.xbar_nodes, ref.xbar_nodes)
    _check(dx <= tol and dy <= tol,
           f"kernel vs XLA window: |dx| {dx:.3e} |dy| {dy:.3e} > {tol:.3e}")
    _emit("pallas_window", scenarios=num_scens, tpu_custom_call=has_kernel,
          rel_diff_x=dx, rel_diff_y=dy, rel_diff_xbar=dxbar, tol=tol,
          seconds_build_and_iter0=t_build,
          seconds=time.perf_counter() - t0)


def _fused_wheel(batch, iters: int):
    from mpisppy_tpu.algos import fused_wheel as fw
    from mpisppy_tpu.cylinders import PHHub
    from mpisppy_tpu.cylinders.spoke import (
        FusedLagrangianOuterBound, FusedXhatXbarInnerBound,
    )
    from mpisppy_tpu.spin_the_wheel import WheelSpinner
    hub = {"hub_class": PHHub,
           "hub_kwargs": {"options": {"rel_gap": 0.0}},
           "opt_class": fw.FusedPH,
           "opt_kwargs": {"options": _ph_options(max_iterations=iters,
                                                 conv_thresh=0.0),
                          "batch": batch,
                          "wheel_options": fw.FusedWheelOptions()}}
    spokes = [{"spoke_class": cls, "opt_kwargs": {"options": {}}}
              for cls in (FusedLagrangianOuterBound,
                          FusedXhatXbarInnerBound)]
    wheel = WheelSpinner(hub, spokes)
    wheel.spin()
    return wheel


def phase_sharded(num_scens: int = 10_000, iters: int = 20,
                  chips: int = 4) -> None:
    import jax

    from mpisppy_tpu.algos import ph as ph_mod
    from mpisppy_tpu.parallel import mesh as mesh_mod
    t0 = time.perf_counter()
    batch = sslp_batch(num_scens)
    mesh = mesh_mod.make_mesh(chips)
    sbatch = mesh_mod.shard_batch(batch, mesh, pad=True)
    sharded = _fused_wheel(sbatch, iters)
    t_sharded = time.perf_counter() - t0
    with jax.default_device(jax.devices()[0]):
        single = _fused_wheel(batch, iters)
    bounds = {}
    for side in ("BestOuterBound", "BestInnerBound"):
        a, b = getattr(sharded, side), getattr(single, side)
        _check(math.isfinite(a) and math.isfinite(b),
               f"{side}: sharded {a} single {b}")
        rel = abs(a - b) / max(abs(b), 1e-12)
        _check(rel <= 1e-3, f"{side}: sharded {a} vs single {b} "
                            f"({rel:.3e} > 1e-3 relative)")
        bounds[side] = {"sharded": a, "single": b, "rel_diff": rel}
    # every scenario-major array of the batch and the hub state is
    # split S/chips per device (nothing parked on the first device)
    S = sbatch.num_scenarios
    n_checked = 0
    for leaf in jax.tree.leaves((sbatch, sharded.opt.state,
                                 getattr(sharded.opt, "wstate", None))):
        if getattr(leaf, "ndim", 0) == 0 or leaf.shape[0] != S:
            continue
        shards = leaf.addressable_shards
        _check(len({sh.device for sh in shards}) == chips
               and all(sh.data.shape[0] == S // chips for sh in shards),
               f"array {leaf.shape} not split S/{chips} per device: "
               f"{[(str(sh.device), sh.data.shape) for sh in shards]}")
        n_checked += 1
    _check(n_checked > 0, "no scenario-major arrays found")
    hlo = ph_mod.ph_iterk.lower(
        sbatch, sharded.opt.state,
        ph_mod.kernel_opts(sharded.opt.options)).compile().as_text()
    _check("all-reduce" in hlo, "sharded ph_iterk has no all-reduce")
    _emit("sharded", scenarios=num_scens, chips=chips,
          hub_iterations=len(_wheel_rows(sharded)), bounds=bounds,
          scenario_major_arrays_checked=n_checked,
          seconds_sharded=t_sharded, seconds=time.perf_counter() - t0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path and its "
                         "single-device comparison")
    args = ap.parse_args(argv)
    try:
        device = phase_device(args.chips)
        from mpisppy_tpu.utils.compile_cache import enable_compile_cache
        enable_compile_cache()
        if args.chips == 4:
            phase_sharded(chips=4)
        else:
            phase_known_answer()
            phase_main_path()
            phase_pallas()
    except PhaseFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
