"""North-star benchmark (BASELINE.md): wall-clock to 1% CERTIFIED gap
and PH throughput on sslp + uc, on real hardware.

Prints ONE JSON line with the headline metric:
    {"metric", "value", "unit", "vs_baseline", "detail": {...}}
and writes the full suite (scenario sweep, uc FWPH config, MFU/HBM
estimates) to BENCH_DETAIL.json.  Methodology: BENCH_METHODOLOGY.md.

Headline: seconds to drive the certified relative gap (best certified
outer bound from trivial + Lagrangian bounds vs best feasible incumbent
from the xhat plane) under 1% on LP-relaxed sslp_15_45 at 10k scenarios
— the BASELINE.md item-2 configuration run the way the reference runs
it (PH hub + Lagrangian spoke + xhat spoke,
ref:paperruns + generic_cylinders decomp path), except every "cylinder"
is a batched device computation.

`vs_baseline` = estimated wall-clock of the reference's execution model
on the same run divided by ours.  The reference model is one sequential
CPU LP solve per scenario per PH iteration per cylinder rank
(ref:mpisppy/spopt.py:250-341); we time scipy/HiGHS on a sample of the
same LPs and charge the reference (iterations x scenarios x LPs/iter)
at that rate on 64 ranks (the BASELINE.md comparison cluster).  This is
an ESTIMATE, not a measured mpi-sppy run — Gurobi/MPI are not in this
image; see BENCH_METHODOLOGY.md for exactly what is and is not charged.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

QUICK = bool(int(os.environ.get("BENCH_QUICK", "0")))
SMOKE = bool(int(os.environ.get("BENCH_SMOKE", "0")))  # CI code-path check


def metrics_schema_probe() -> str:
    """The metrics-snapshot schema this bench embeds in BENCH_*.json —
    imported from the telemetry exporter, never duplicated, so artifact
    entries and live --metrics-snapshot files stay comparable by
    construction (tests/test_telemetry.py asserts it)."""
    from mpisppy_tpu.telemetry import metrics as metrics_mod
    return metrics_mod.SNAPSHOT_SCHEMA


#: Iteration precision for every wheel/sweep/mfu bench phase (ISSUE 8):
#: bf16x3 halves HBM bytes and MXU passes per iteration matvec — the
#: only lever left on a bandwidth-bound iteration (809 of 819 GB/s at
#: S=10k).  Certificates are unaffected by construction: restart
#: candidate scoring, convergence tests, and every published bound
#: re-check at full precision (ops/pdhg.py PDHGOptions.iter_precision;
#: accuracy contract in docs/precision.md).  Artifacts disclose the
#: mode next to every phase (iter_precision field).
ITER_PRECISION = os.environ.get("BENCH_ITER_PRECISION", "bf16x3") or None

SSLP_SERVERS, SSLP_CLIENTS = 15, 45
SSLP_SCENS = 16 if SMOKE else (1_000 if QUICK else 10_000)
SWEEP = [16] if SMOKE else ([1_000, 10_000] if QUICK
                            else [1_000, 10_000, 100_000])
UC_SCENS = 3 if SMOKE else (20 if QUICK else 100)
MAX_WHEEL_ITERS = 5 if SMOKE else 300
GAP_TARGET = 0.01
BASELINE_RANKS = 64


def _dist(times):
    """Distribution summary (VERDICT r4 #7: report the distribution of
    measured solve times, not just the mean)."""
    t = np.asarray(times)
    return {"n": int(t.size), "mean": float(t.mean()),
            "p10": float(np.percentile(t, 10)),
            "p50": float(np.percentile(t, 50)),
            "p90": float(np.percentile(t, 90)),
            "max": float(t.max())}


def _split_rows(sp):
    """ScenarioSpec constraint rows -> (A_ub, b_ub, A_eq, b_eq)."""
    A = sp.A.toarray() if hasattr(sp.A, "toarray") else np.asarray(sp.A)
    A_ub, b_ub, A_eq, b_eq = [], [], [], []
    for i in range(A.shape[0]):
        if sp.bl[i] == sp.bu[i]:
            A_eq.append(A[i]); b_eq.append(sp.bu[i])
            continue
        if np.isfinite(sp.bu[i]):
            A_ub.append(A[i]); b_ub.append(sp.bu[i])
        if np.isfinite(sp.bl[i]):
            A_ub.append(-A[i]); b_ub.append(-sp.bl[i])
    return A_ub, b_ub, A_eq, b_eq


def time_scipy_baseline(specs, sample=32):
    """Seconds per scenario LP via scipy/HiGHS (the reference's
    sequential per-rank solve model), MEASURED on the same LP instances
    the benchmarked batch solves.  Returns a distribution dict."""
    from scipy.optimize import linprog

    times = []
    for sp in specs[:sample]:
        A_ub, b_ub, A_eq, b_eq = _split_rows(sp)
        t0 = time.perf_counter()
        res = linprog(sp.c, A_ub=np.array(A_ub), b_ub=np.array(b_ub),
                      A_eq=np.array(A_eq) if A_eq else None,
                      b_eq=np.array(b_eq) if b_eq else None,
                      bounds=list(zip(sp.l, sp.u)), method="highs")
        times.append(time.perf_counter() - t0)
        assert res.status == 0
    return _dist(times)


def time_scipy_milp_baseline(specs, sample=16, time_limit=60.0):
    """Seconds per scenario MIP via scipy/HiGHS MILP — the anchor for
    what the reference's EXACT integer subproblem solves cost (its PH on
    sslp dispatches one MIQP per scenario per iteration to Gurobi,
    ref:mpisppy/spopt.py:99-247; HiGHS-without-prox is a lower bound on
    that cost).  Returns (distribution, objectives)."""
    from scipy.optimize import Bounds, LinearConstraint, milp

    times, objs = [], []
    for sp in specs[:sample]:
        A = sp.A.toarray() if hasattr(sp.A, "toarray") else np.asarray(sp.A)
        integrality = (np.asarray(sp.integer, float)
                       if sp.integer is not None
                       else np.zeros(sp.c.shape[0]))
        t0 = time.perf_counter()
        res = milp(c=sp.c,
                   constraints=LinearConstraint(A, sp.bl, sp.bu),
                   bounds=Bounds(sp.l, sp.u),
                   integrality=integrality,
                   options={"time_limit": time_limit})
        dt = time.perf_counter() - t0
        if res.status != 0:
            # censored sample (hit time_limit on a loaded host): record
            # the truncated time, flag it, keep the phase alive
            times.append(dt)
            objs.append(float("nan"))
            continue
        times.append(dt)
        objs.append(float(res.fun))
    return _dist(times), objs


def _sslp_batch(num_scens):
    from mpisppy_tpu.core import batch as batch_mod
    from mpisppy_tpu.models import sslp

    inst = sslp.synthetic_instance(SSLP_SERVERS, SSLP_CLIENTS, seed=0)
    names = sslp.scenario_names_creator(num_scens)
    specs = [sslp.scenario_creator(nm, instance=inst, num_scens=num_scens,
                                   lp_relax=True)
             for nm in names]
    from mpisppy_tpu.core.batch import from_specs
    return from_specs(specs), specs


def _flops_per_ph_iter(batch, ph_opts):
    """FLOPs model for one PH iteration: dominated by PDHG matvec pairs.

    Shared dense A (m, n): matvec + rmatvec = 4*m*n flops per scenario
    per PDHG iteration (2 flops per multiply-add).  ELL A: 4*m*k.
    PDHG iterations per PH iter = subproblem_windows * restart_period
    (+ the restart-candidate KKT evaluations, ~2 extra matvec pairs per
    window, counted below)."""
    S = batch.num_scenarios
    A = batch.qp.A
    if hasattr(A, "k"):
        per_mv = 4.0 * A.m * A.k
    else:
        per_mv = 4.0 * A.shape[-2] * A.shape[-1]
    iters = ph_opts.subproblem_windows * (ph_opts.pdhg.restart_period + 4)
    return S * per_mv * iters


def bench_wheel_to_gap(batch, label, spokes_cfg, ph_opts, wheel_opts=None,
                       extra_hub_opts=None, extra_opt_kwargs=None):
    """Wall-clock from wheel start to certified rel_gap <= GAP_TARGET.

    Crash-resilient: the wheel checkpoints its full state every ~30s
    (hub.save_checkpoint); if the TPU worker dies mid-phase, the parent
    retries the phase once and this function RESUMES from the
    checkpoint, with elapsed time carried across the crash so the
    reported seconds stay honest.  Returns dict with seconds,
    iterations, bounds, and a `metrics_snapshot` in the telemetry
    exporter's schema (telemetry/metrics.py SNAPSHOT_SCHEMA) with the
    on-device counter totals — BENCH_*.json entries and live
    --metrics-snapshot files are directly comparable."""
    import dataclasses
    import jax

    from mpisppy_tpu.algos import fused_wheel as fw
    from mpisppy_tpu.cylinders import hub as hub_mod
    from mpisppy_tpu.spin_the_wheel import WheelSpinner
    from mpisppy_tpu.telemetry import metrics as metrics_mod

    # on-device kernel counters for the hub's subproblem solves AND the
    # fused bound planes: a few elementwise ops per 40-iteration restart
    # window (docs/telemetry.md overhead contract) buys pdhg
    # iteration/restart/guard totals in the artifact, labeled per
    # cylinder (cyl="hub"/"lag"/"xhat"/...)
    ph_opts = dataclasses.replace(
        ph_opts, pdhg=dataclasses.replace(ph_opts.pdhg, telemetry=True))
    wheel_opts = wheel_opts or fw.FusedWheelOptions()
    wheel_opts = dataclasses.replace(
        wheel_opts,
        lag_pdhg=dataclasses.replace(wheel_opts.lag_pdhg, telemetry=True),
        xhat_pdhg=dataclasses.replace(wheel_opts.xhat_pdhg,
                                      telemetry=True))

    ckpt = os.path.abspath(f".bench_ckpt_{label}.npz")
    # checkpoint cadence trades crash-replay time against steady-state
    # overhead: a full-wheel snapshot at 10k scenarios is ~460 MB
    # (several seconds of device-to-host copy), so save sparsely
    hub_opts = {"rel_gap": GAP_TARGET,
                "checkpoint_path": ckpt,
                "checkpoint_every_s": 120.0}
    hub_opts.update(extra_hub_opts or {})
    opt_kwargs = {"options": ph_opts, "batch": batch,
                  "wheel_options": wheel_opts}
    opt_kwargs.update(extra_opt_kwargs or {})
    hub = {
        "hub_class": hub_mod.PHHub,
        "opt_class": fw.FusedPH,
        "opt_kwargs": opt_kwargs,
        "hub_kwargs": {"options": hub_opts},
    }
    wheel = WheelSpinner(hub, spokes_cfg)
    wheel.build()
    elapsed_prior, resumed = 0.0, False
    if os.path.exists(ckpt):
        extras = wheel.spcomm.load_checkpoint(ckpt)
        elapsed_prior = float(extras.get("elapsed", 0.0))
        resumed = True
    t0 = time.perf_counter()
    hub_opts["checkpoint_extra"] = lambda: {
        "elapsed": elapsed_prior + time.perf_counter() - t0}
    wheel.spin()
    jax.block_until_ready(wheel.opt.state.conv)
    elapsed = elapsed_prior + time.perf_counter() - t0
    if os.path.exists(ckpt):
        os.remove(ckpt)
    abs_gap, rel_gap = wheel.spcomm.compute_gaps()
    iters = wheel.spcomm._iter
    # the hub mirrored the cumulative device counters into the global
    # registry every sync (hub._harvest_kernel_counters); snapshot it
    # in the exporter's schema (each bench phase is its own process,
    # so the registry holds exactly this wheel's totals)
    # dispatch occupancy/recompile stats ride the artifact next to the
    # kernel counters (docs/dispatch.md): None when the wheel never
    # touched the MIP-oracle scheduler, a stats dict (batches, lanes,
    # occupancy, buckets, backend_compiles, unexpected_recompiles,
    # inflight_max) otherwise — the dispatch_* counters/gauges inside
    # metrics_snapshot are the same numbers, mirrored live
    from mpisppy_tpu import dispatch as dispatch_mod
    return {
        "label": label,
        # precision disclosure (ISSUE 8): the mode the ITERATION
        # matvecs ran at; certificates always re-check at full precision
        "iter_precision": ph_opts.pdhg.iter_precision or "bf16x6",
        "seconds_to_gap": round(elapsed, 3),
        "iterations": iters,
        # directly gateable steady-state proxy (telemetry/regress.py
        # GATES keys on sec_per_iter): to-gap wall over iterations —
        # includes compile+iter0 amortization, so compare like vs like
        "sec_per_iter": round(elapsed / max(1, iters), 6),
        "rel_gap": float(rel_gap),
        "certified": bool(rel_gap <= GAP_TARGET),
        "outer": float(wheel.BestOuterBound),
        "inner": float(wheel.BestInnerBound),
        "resumed_from_checkpoint": resumed,
        "metrics_snapshot": metrics_mod.REGISTRY.to_snapshot(),
        "dispatch": dispatch_mod.scheduler_stats(),
    }


def bench_sslp_gap():
    """Headline: sslp 15_45 at SSLP_SCENS scenarios, PH hub + FUSED
    Lagrangian outer + FUSED xhat-xbar inner (algos.fused_wheel: the
    spoke solves ride inside the hub's jitted step as fixed warm
    budgets), to 1% certified gap."""
    from mpisppy_tpu.algos import ph as ph_mod
    from mpisppy_tpu.cylinders import spoke as spoke_mod
    from mpisppy_tpu.ops import pdhg

    batch, specs = _sslp_batch(SSLP_SCENS)
    ph_opts = ph_mod.PHOptions(
        default_rho=20.0, max_iterations=MAX_WHEEL_ITERS, conv_thresh=0.0,
        subproblem_windows=8,
        pdhg=pdhg.PDHGOptions(tol=1e-6, restart_period=40,
                              iter_precision=ITER_PRECISION))
    spokes = [
        {"spoke_class": spoke_mod.FusedLagrangianOuterBound,
         "opt_kwargs": {"options": {}}},
        {"spoke_class": spoke_mod.FusedXhatXbarInnerBound,
         "opt_kwargs": {"options": {}}},
    ]
    out = bench_wheel_to_gap(batch, f"sslp_15_45_{SSLP_SCENS}scen",
                             spokes, ph_opts)

    # reference-model baseline: per-iteration the reference solves S LPs
    # on the hub + S on the Lagrangian spoke + S on the xhat spoke,
    # charged at the MEASURED HiGHS rate on these same LP instances
    lp_dist = time_scipy_baseline(specs)
    sec_per_lp = lp_dist["mean"]
    lps = out["iterations"] * batch.num_real * 3
    out["baseline_1rank_sec"] = round(sec_per_lp * lps, 1)
    out["baseline_64rank_sec"] = round(sec_per_lp * lps / BASELINE_RANKS, 1)
    # p90 variant: how the baseline moves if the tail rate governs
    out["baseline_64rank_sec_p90"] = round(
        lp_dist["p90"] * lps / BASELINE_RANKS, 1)
    out["sec_per_baseline_lp"] = sec_per_lp
    out["baseline_lp_dist"] = lp_dist
    return out


def bench_baseline_anchor():
    """Measured anchor for the reference execution model (VERDICT r4
    #7): HiGHS solve-time DISTRIBUTIONS on the real workload units —
    (a) the headline's own scenario LP relaxations, (b) the REAL SIPLIB
    sslp_15_45 scenario MIPs (exact integer recourse, the solves that
    give the reference its certified-gap quality), (c) the SIPLIB LP
    relaxations.  Everything here is a measurement on THIS host; no
    Gurobi/MPI modeling involved."""
    from mpisppy_tpu.models import sslp

    out = {}
    # (a) headline synthetic LPs (same generator + seed as the bench) —
    # host-side specs only: building the device batch would pay full
    # accelerator-backend init in a pure-scipy measurement phase
    inst = sslp.synthetic_instance(SSLP_SERVERS, SSLP_CLIENTS, seed=0)
    specs = [sslp.scenario_creator(nm, instance=inst, num_scens=64,
                                   lp_relax=True)
             for nm in sslp.scenario_names_creator(64)]
    out["headline_lp_sec"] = time_scipy_baseline(specs, sample=32)

    # (b)+(c) the real SIPLIB instance the certification pipeline runs
    dd = ("/root/reference/examples/sslp/data/"
          "sslp_15_45_10/scenariodata")
    if os.path.isdir(dd):
        names = sslp.scenario_names_creator(10)
        mips = [sslp.scenario_creator(nm, data_dir=dd, num_scens=10)
                for nm in names]
        lps = [sslp.scenario_creator(nm, data_dir=dd, num_scens=10,
                                     lp_relax=True) for nm in names]
        mip_dist, mip_objs = time_scipy_milp_baseline(mips, sample=10)
        out["siplib_15_45_10_mip_sec"] = mip_dist
        out["siplib_15_45_10_mip_objs"] = [round(v, 2) for v in mip_objs]
        out["siplib_15_45_10_lp_sec"] = time_scipy_baseline(lps, sample=10)
        # wait-and-see bound cross-check: E[per-scenario MIP optimum]
        # must lower-bound the published optimum -260.5 (sanity that the
        # MILP anchor solves the true SIPLIB scenarios); nan-mean in
        # case any sample was censored at time_limit
        out["siplib_15_45_10_ws_bound"] = round(
            float(np.nanmean(mip_objs)), 3)
        if any(np.isnan(v) for v in mip_objs):
            out["siplib_censored_samples"] = int(
                np.isnan(mip_objs).sum() if hasattr(mip_objs, "sum")
                else sum(np.isnan(v) for v in mip_objs))
    else:
        # make the missing key MEASUREMENT visible in the artifact —
        # the methodology doc's MIP-floor argument depends on it
        out["siplib_skipped_missing_dir"] = dd
    return out


def bench_sweep_one(S):
    """PH iters/sec at one scenario count (continuity with the round-2
    headline metric); each scale runs as its OWN subprocess phase so a
    worker crash at 100k cannot cost the smaller scales their numbers."""
    import jax
    import jax.numpy as jnp

    from mpisppy_tpu.algos import ph as ph_mod
    from mpisppy_tpu.ops import pdhg

    try:
        batch, _ = _sslp_batch(S)
        # keep every dispatch SHORT at 100k scale: a single 400-window
        # iter0 (~17.6k PDHG iterations in one while_loop) can outlive
        # the TPU worker's patience
        opts = ph_mod.PHOptions(
            default_rho=20.0, subproblem_windows=8,
            iter0_windows=80 if S >= 100_000 else 400,
            pdhg=pdhg.PDHGOptions(tol=1e-6, restart_period=40,
                              iter_precision=ITER_PRECISION))
        rho = jnp.full((batch.num_nonants,), opts.default_rho)
        state, _, _ = ph_mod.ph_iter0(batch, rho, opts)
        state = ph_mod.ph_iterk(batch, state, opts)   # compile
        jax.block_until_ready(state.conv)
        n_iters = 5 if S >= 100_000 else 20
        t0 = time.perf_counter()
        for _ in range(n_iters):
            state = ph_mod.ph_iterk(batch, state, opts)
        jax.block_until_ready(state.conv)
        dt = time.perf_counter() - t0
        ips = n_iters / dt
        flops = _flops_per_ph_iter(batch, opts) * ips
        return {
            "scenarios": S,
            "iter_precision": ITER_PRECISION or "bf16x6",
            "iters_per_sec": round(ips, 3),
            "achieved_tflops_est": round(flops / 1e12, 3),
        }
    except Exception as e:
        return {"scenarios": S, "error": repr(e)}


def _overhead_ph_opts(n_iters):
    """The PH config BOTH wheel_overhead phases run — one builder (with
    _overhead_wheel_options/_overhead_spokes/_bare_ph_sec_per_iter) so
    the async phase stays an apples-to-apples A/B against the
    synchronous baseline its gated overhead_factor is compared to."""
    from mpisppy_tpu.algos import ph as ph_mod
    from mpisppy_tpu.ops import pdhg
    return ph_mod.PHOptions(
        default_rho=20.0, max_iterations=n_iters, conv_thresh=0.0,
        subproblem_windows=8,
        pdhg=pdhg.PDHGOptions(tol=1e-6, restart_period=40,
                              iter_precision=ITER_PRECISION))


def _overhead_wheel_options():
    from mpisppy_tpu.algos import fused_wheel as fw
    return fw.FusedWheelOptions(slam_windows=2, shuffle_windows=4,
                                spoke_period=3)


def _overhead_spokes():
    from mpisppy_tpu.cylinders import spoke as spoke_mod
    return [
        {"spoke_class": spoke_mod.FusedLagrangianOuterBound,
         "opt_kwargs": {"options": {}}},
        {"spoke_class": spoke_mod.FusedXhatXbarInnerBound,
         "opt_kwargs": {"options": {}}},
        {"spoke_class": spoke_mod.FusedXhatShuffleInnerBound,
         "opt_kwargs": {"options": {}}},
        {"spoke_class": spoke_mod.FusedSlamHeuristic,
         "opt_kwargs": {"options": {}}},
    ]


def _bare_ph_sec_per_iter(batch, ph_opts, n_iters):
    """Bare-PH per-iteration wall clock (compile + iter0 excluded) —
    the shared denominator of both overhead factors."""
    import jax
    import jax.numpy as jnp

    from mpisppy_tpu.algos import ph as ph_mod

    rho = jnp.full((batch.num_nonants,), ph_opts.default_rho)
    state, _, _ = ph_mod.ph_iter0(batch, rho, ph_opts)
    state = ph_mod.ph_iterk(batch, state, ph_opts)
    jax.block_until_ready(state.conv)
    t0 = time.perf_counter()
    for _ in range(n_iters):
        state = ph_mod.ph_iterk(batch, state, ph_opts)
    jax.block_until_ready(state.conv)
    return (time.perf_counter() - t0) / n_iters


def bench_wheel_overhead():
    """Wheel overhead: per-iteration wall-clock of a full hub + 4-bound
    wheel vs bare PH on the same batch.  Round 3 measured 642x with
    every spoke a separate to-convergence device dispatch; the fused
    wheel (algos.fused_wheel — Lagrangian + xhat-xbar + slam + shuffle
    planes INSIDE the hub's jitted step, fixed warm budgets) is the
    round-4 answer.  Target: overhead factor <= 5x."""
    import jax

    from mpisppy_tpu.algos import fused_wheel as fw
    from mpisppy_tpu.cylinders import hub as hub_mod
    from mpisppy_tpu.spin_the_wheel import WheelSpinner

    batch, _ = _sslp_batch(SSLP_SCENS)
    n_iters = 3 if SMOKE else 10
    ph_opts = _overhead_ph_opts(n_iters)
    bare = _bare_ph_sec_per_iter(batch, ph_opts, n_iters)

    # full fused wheel: hub + Lagrangian + xhat-xbar + slam + shuffle
    hub = {
        "hub_class": hub_mod.PHHub,
        "opt_class": fw.FusedPH,
        "opt_kwargs": {"options": ph_opts, "batch": batch,
                       "wheel_options": _overhead_wheel_options()},
        "hub_kwargs": {"options": {"rel_gap": 0.0}},
    }
    wheel = WheelSpinner(hub, _overhead_spokes())
    wheel.spin()
    jax.block_until_ready(wheel.opt.state.conv)
    # steady-state per-iteration cost from the hub trace timestamps,
    # excluding iter0 + the first iterk (compile)
    ts = [row["t"] for row in wheel.spcomm.trace]
    steady = np.diff(ts[2:]) if len(ts) > 3 else np.diff(ts)
    per_iter = float(np.median(steady)) if len(steady) else float("nan")
    return {
        "bare_ph_sec_per_iter": round(bare, 4),
        "wheel_sec_per_iter": round(per_iter, 4),
        "overhead_factor": round(per_iter / bare, 3),
        "note": f"median over {len(steady)} steady-state iterations "
                "(compile + iter0 excluded); fused wheel carries 4 bound "
                "planes inside the hub step at spoke_period=3 (the same "
                "exchange cadence round 3's classic wheel used)",
    }


def bench_wheel_overhead_async():
    """Async-wheel overhead (ISSUE 11; ROADMAP item 4): per-iteration
    wall-clock of the async hub at staleness 0/1/2 vs bare PH on the
    same batch.  staleness 0 is the synchronous degrade (must match the
    wheel_overhead phase's structure); staleness >= 1 overlaps the host
    exchange with device iterations on the double-buffered plane.  The
    headline `overhead_factor` (staleness 1) carries the <= 1.3 ratchet
    MILESTONE (telemetry/regress.py)."""
    import jax

    from mpisppy_tpu.algos import async_wheel as aw
    from mpisppy_tpu.cylinders import hub as hub_mod
    from mpisppy_tpu.spin_the_wheel import WheelSpinner

    batch, _ = _sslp_batch(SSLP_SCENS)
    n_iters = 8 if SMOKE else 12
    ph_opts = _overhead_ph_opts(n_iters)
    bare = _bare_ph_sec_per_iter(batch, ph_opts, n_iters)

    out = {"bare_ph_sec_per_iter": round(bare, 4),
           "iter_precision": ITER_PRECISION or "bf16x6"}
    for s in (0, 1, 2):
        hub = {
            "hub_class": hub_mod.AsyncPHHub,
            "opt_class": aw.AsyncFusedPH,
            "opt_kwargs": {
                "options": ph_opts, "batch": batch,
                "wheel_options": _overhead_wheel_options(),
                "async_options": aw.AsyncWheelOptions(staleness=s)},
            "hub_kwargs": {"options": {"rel_gap": 0.0,
                                       "async_staleness": s}},
        }
        wheel = WheelSpinner(hub, _overhead_spokes())
        wheel.spin()
        jax.block_until_ready(wheel.opt.state.conv)
        ts = [row["t"] for row in wheel.spcomm.trace]
        # drop iter0 + the first TWO iterk rows: the stale-prox step
        # and the plane programs compile across the first two syncs
        drop = 3 if len(ts) > 5 else (2 if len(ts) > 3 else 1)
        steady = np.diff(ts[drop:]) if len(ts) > drop + 1 \
            else np.diff(ts)
        per_iter = float(np.median(steady)) if len(steady) \
            else float("nan")
        out[f"s{s}"] = {
            "staleness": s,
            "wheel_sec_per_iter": round(per_iter, 4),
            "overhead_factor": round(per_iter / bare, 3),
        }
    # the MILESTONE headline: staleness 1 at the spoke_period=3
    # exchange cadence (the same cadence wheel_overhead measures)
    out["overhead_factor"] = out["s1"]["overhead_factor"]
    out["note"] = ("async wheel (double-buffered exchange plane, "
                   "theta-damped stale-prox hub step) at staleness "
                   "0/1/2 vs bare PH; median steady-state sec/iter "
                   "(compile + iter0 excluded); headline "
                   "overhead_factor is staleness 1")
    return out


def bench_uc_fwph():
    """BASELINE.md item 5: uc, PH hub + FWPH outer + xhat-xbar inner
    (the paper-run cylinder mix, ref:paperruns/larger_uc/uc_cylinders.py)."""
    from mpisppy_tpu.algos import ph as ph_mod
    from mpisppy_tpu.core import batch as batch_mod
    from mpisppy_tpu.cylinders import spoke as spoke_mod
    from mpisppy_tpu.models import uc
    from mpisppy_tpu.ops import pdhg

    inst = uc.synthetic_instance(10, 24, seed=0)
    names = uc.scenario_names_creator(UC_SCENS)
    specs = [uc.scenario_creator(nm, instance=inst, num_scens=UC_SCENS)
             for nm in names]
    batch = batch_mod.from_specs(specs)
    from mpisppy_tpu.algos import fused_wheel as fw
    from functools import partial as _partial

    from mpisppy_tpu.extensions.rho_setters import SepRho
    # NO hand-tuned rho (round-4 needed rho=1000): SepRho (the
    # Watson-Woodruff cost/spread rule, multiplier 2 — the same
    # model-agnostic setting hydro uses) certifies from default_rho in
    # FEWER iterations than the hand-set constant (427 vs 564 measured)
    ph_opts = ph_mod.PHOptions(
        default_rho=1.0, max_iterations=2 * MAX_WHEEL_ITERS,
        conv_thresh=0.0,
        subproblem_windows=10,
        pdhg=pdhg.PDHGOptions(tol=1e-6, restart_period=40,
                              iter_precision=ITER_PRECISION))
    # full precision ON PURPOSE: this is a standalone run-to-tolerance
    # solve at tol=1e-6, which bf16x3 iterates cannot certify (they
    # stall ~7e-6..1e-5 and would burn the whole max_iters budget —
    # docs/precision.md "When to opt out").  Only the inexact-by-design
    # PH/FWPH hub windows run bf16x3.
    spoke_pdhg = pdhg.PDHGOptions(tol=1e-6, max_iters=4_000)
    # slam-max commits every unit any scenario wants: the conservative
    # feasible commitment (rounded-xbar undercommits against the
    # reserve rows and pays shortfall penalties).  Lagrangian + xhat +
    # slam ride fused in the hub step; FWPH stays a classic spoke
    # advancing one outer iteration per sync period.
    spokes = [
        {"spoke_class": spoke_mod.FWPHOuterBound,
         "opt_kwargs": {"options": {"rho": 200.0,
                                    "pdhg_opts": spoke_pdhg}}},
        {"spoke_class": spoke_mod.FusedLagrangianOuterBound,
         "opt_kwargs": {"options": {}}},
        {"spoke_class": spoke_mod.FusedXhatXbarInnerBound,
         "opt_kwargs": {"options": {}}},
        {"spoke_class": spoke_mod.FusedSlamHeuristic,
         "opt_kwargs": {"options": {}}},
    ]
    return bench_wheel_to_gap(
        batch, f"uc_10g24h_{UC_SCENS}scen", spokes, ph_opts,
        wheel_opts=fw.FusedWheelOptions(slam_windows=2),
        extra_hub_opts={"spoke_sync_period": 5},
        extra_opt_kwargs={"extensions": _partial(SepRho,
                                                 multiplier=2.0)})


def bench_uc_fwph_hub():
    """VERDICT r5 #5 straggler / ISSUE 8: uc the reference's way — FWPH
    as the DRIVING algorithm (BASELINE.md item 5; the reference's
    larger_uc paper runs are FWPH cylinders, ref:paperruns/larger_uc/
    uc_cylinders.py).  Round 3 measured 545 s UNCERTIFIED because the
    FWPH run published no inner bound; here FWPH's inner-iteration-0
    oracle supplies the certified dual (outer) bound and the incumbent
    side re-evaluates the rounded x̄ (nearest + ceil — ceil mirrors the
    slam-max over-commitment that is recourse-feasible against uc's
    reserve rows) through the honest xhat recourse evaluator with the
    comp_tight publication gate.  Recorded even if it loses to the
    PH+SepRho wheel (uc_fwph_to_1pct_gap, 193.9 s) — whichever
    certifies faster is the headline uc number."""
    from mpisppy_tpu.algos import fwph as fwph_mod
    from mpisppy_tpu.algos import xhat as xhat_mod
    from mpisppy_tpu.core import batch as batch_mod
    from mpisppy_tpu.models import uc
    from mpisppy_tpu.ops import pdhg

    inst = uc.synthetic_instance(10, 24, seed=0)
    specs = [uc.scenario_creator(nm, instance=inst, num_scens=UC_SCENS)
             for nm in uc.scenario_names_creator(UC_SCENS)]
    batch = batch_mod.from_specs(specs)
    # This phase runs ENTIRELY at full precision: FWPH's dual-bound
    # certificate reads the oracle's own dual residuals (rd <= 10*tol
    # = 1e-5) with no full-precision restart-recheck layer between
    # iterates and published bound, and bf16x3 iterates stall right at
    # that band (docs/precision.md) — engaging it could cost the
    # certification this phase exists to produce.
    opts = fwph_mod.FWPHOptions(
        fw_iter_limit=2, max_columns=16,
        max_iterations=3 if SMOKE else 2 * MAX_WHEEL_ITERS,
        conv_thresh=0.0,
        default_rho=200.0,   # the rho the FWPH spoke certifies with on uc
        oracle_windows=10,
        pdhg=pdhg.PDHGOptions(tol=1e-6, restart_period=40))
    # full precision ON PURPOSE (docs/precision.md "When to opt out"):
    # a standalone tol=1e-6 recourse evaluation stalls below tolerance
    # at bf16x3 and would burn max_iters + the rescue pass every eval
    xhat_opts = pdhg.PDHGOptions(tol=1e-6, max_iters=4_000)
    drv = fwph_mod.FWPH(opts, batch)
    eval_every = 1 if SMOKE else 5   # xhat evals per FWPH outer iters
    t0 = time.perf_counter()
    drv.fw_prep()
    best_outer = drv.best_bound      # -inf while uncertified
    best_inner = float("inf")
    rel_gap, iters = float("inf"), 0
    for itr in range(1, opts.max_iterations + 1):
        iters = itr
        drv.state = fwph_mod.fwph_iter(batch, drv.state, opts)
        best_outer = max(best_outer, float(drv.state.best_bound))
        if itr % eval_every == 0:
            for mode in ("nearest", "ceil"):
                cand = xhat_mod.round_integers(
                    batch, drv.state.xbar_nodes, mode)
                res = xhat_mod.evaluate(batch, cand, xhat_opts)
                if bool(res.feasible) and xhat_mod.comp_tight(batch,
                                                              res):
                    best_inner = min(best_inner, float(res.value))
        # gap check EVERY iteration: the dual bound improves between
        # xhat evals, and the recorded rel_gap must never go stale
        # against the artifact's own outer/inner fields
        if np.isfinite(best_inner) and np.isfinite(best_outer):
            rel_gap = (best_inner - best_outer) / max(
                abs(best_inner), abs(best_outer), 1e-12)
            if rel_gap <= GAP_TARGET:
                break
    elapsed = time.perf_counter() - t0

    def _fin(v):
        """strict-JSON artifacts: non-finite (no bound yet) -> None"""
        return float(v) if np.isfinite(v) else None

    return {
        "label": f"uc_10g24h_{UC_SCENS}scen_fwph_hub",
        "iter_precision": "bf16x6",   # see the opts comment above
        "seconds_to_gap": round(elapsed, 3),
        "iterations": iters,
        "sec_per_iter": round(elapsed / max(1, iters), 6),
        "rel_gap": _fin(rel_gap),
        "certified": bool(rel_gap <= GAP_TARGET),
        "outer": _fin(best_outer),
        "inner": _fin(best_inner),
        "note": "FWPH as the hub algorithm (reference uc recipe); "
                "outer = certified SDM inner-iteration-0 dual bound, "
                "inner = comp_tight-gated recourse evaluation of "
                "rounded xbar; compare against uc_fwph_to_1pct_gap "
                "(PH hub + FWPH spoke)",
    }


def bench_hydro():
    """BASELINE.md item 4: hydro 3-stage wheel (multistage path —
    node-segmented reductions) to 1% certified gap.  Scales the (3, 3)
    SIPLIB-style tree by widening the stage-2/3 branching."""
    from mpisppy_tpu.algos import fused_wheel as fw
    from mpisppy_tpu.algos import ph as ph_mod
    from mpisppy_tpu.core import batch as batch_mod
    from mpisppy_tpu.cylinders import spoke as spoke_mod
    from mpisppy_tpu.models import hydro
    from mpisppy_tpu.ops import pdhg

    bfs = (3, 3) if SMOKE else ((10, 10) if QUICK else (30, 30))
    num = bfs[0] * bfs[1]
    specs = [hydro.scenario_creator(nm, branching_factors=bfs)
             for nm in hydro.scenario_names_creator(num)]
    tree = hydro.make_tree(bfs)
    batch = batch_mod.from_specs(specs, tree=tree)
    from functools import partial as _partial

    from mpisppy_tpu.extensions.rho_setters import SepRho
    # NO hand-tuned rho (round-4 needed rho=2): the same SepRho
    # adapter as uc — certifies 0.36% in 95 iterations (round-5
    # measured; the flat-rho round-4 run needed 380)
    ph_opts = ph_mod.PHOptions(
        default_rho=1.0, max_iterations=2 * MAX_WHEEL_ITERS,
        conv_thresh=0.0, subproblem_windows=8,
        pdhg=pdhg.PDHGOptions(tol=1e-6, restart_period=40,
                              iter_precision=ITER_PRECISION))
    # the fused Lagrangian plateaus ~3.5% below the LP optimum on hydro
    # (PH's dual converges slowly on this tree); the EF-bound spoke's
    # warm dual solve provides the certified outer that closes the gap.
    # Inner: EFXhatInnerBound (root-fixed EF) — fixing ALL stages'
    # nonants is structurally infeasible on hydro (stage-2 reservoir
    # balance couples fixed nonants with stochastic inflow; duals ~1e6),
    # so the fused x-bar recourse plane is disabled (round 4's 184.25
    # "inner" at such points was an uncompensated-infeasibility artifact
    # sitting BELOW the EF optimum ~186.2 — not a valid bound).
    from mpisppy_tpu.algos.ef import build_ef
    efp = build_ef(specs, tree=tree)
    spokes = [
        {"spoke_class": spoke_mod.EFOuterBound,
         "opt_kwargs": {"options": {"ef_problem": efp,
                                    "n_windows": 20}}},
        {"spoke_class": spoke_mod.FusedLagrangianOuterBound,
         "opt_kwargs": {"options": {}}},
        {"spoke_class": spoke_mod.EFXhatInnerBound,
         "opt_kwargs": {"options": {"ef_problem": efp,
                                    "n_windows": 20}}},
    ]
    return bench_wheel_to_gap(
        batch, f"hydro_3stage_{num}scen", spokes, ph_opts,
        wheel_opts=fw.FusedWheelOptions(xhat_windows=0),
        extra_hub_opts={"spoke_sync_period": 5},
        extra_opt_kwargs={"extensions": _partial(SepRho,
                                                 multiplier=2.0)})


def bench_measured_mfu():
    """VERDICT r3 weak #6 / ISSUE 7: measured (not modeled) FLOP/s and
    HBM bandwidth for the PH step.  Uses XLA's compiled cost analysis
    (flops + bytes accessed of the EXACT program run) divided by
    measured wall-clock, PLUS the trace-derived device profile
    (telemetry/deviceprof.py + roofline.py) computed from the
    jax.profiler capture of one steady-state iteration: achieved HBM
    GB/s against the device's own peak, sustained stream bandwidth of
    the HBM-dominated movement ops, DMA/compute overlap fraction, and
    device sec/iter.  The round-5 hand-rolled two-op (matvec + saxpy)
    microbenchmarks are retired: the capture that was already saved as
    an artifact IS the measurement now, and the same numbers gate in
    CI (`telemetry gate`, docs/telemetry.md)."""
    import jax
    import jax.numpy as jnp

    from mpisppy_tpu.algos import ph as ph_mod
    from mpisppy_tpu.ops import pdhg

    out = {}
    scales = [16] if SMOKE else ([1_000] if QUICK else [10_000, 100_000])
    for S in scales:
        batch, _ = _sslp_batch(S)
        opts = ph_mod.PHOptions(
            default_rho=20.0, subproblem_windows=8,
            iter0_windows=80 if S >= 100_000 else 400,
            pdhg=pdhg.PDHGOptions(tol=1e-6, restart_period=40,
                              iter_precision=ITER_PRECISION))
        ko = ph_mod.kernel_opts(opts)
        rho = jnp.full((batch.num_nonants,), opts.default_rho)
        state, _, _ = ph_mod.ph_iter0(batch, rho, ko)
        compiled = ph_mod.ph_iterk.lower(batch, state, ko).compile()
        try:
            ca = compiled.cost_analysis()
            ca = ca[0] if isinstance(ca, (list, tuple)) else ca
            flops = float(ca.get("flops", float("nan")))
            bytes_acc = float(ca.get("bytes accessed", float("nan")))
        except Exception as e:  # pragma: no cover - backend-specific
            flops, bytes_acc = float("nan"), float("nan")
            out.setdefault("cost_analysis_error", repr(e))
        state = ph_mod.ph_iterk(batch, state, ko)
        jax.block_until_ready(state.conv)
        n = 3 if S >= 100_000 else 10
        # device trace artifact for one iteration
        trace_dir = os.path.join(
            os.path.dirname(os.path.abspath(__file__)) or ".",
            f"profile_trace_S{S}")
        try:
            with jax.profiler.trace(trace_dir):
                st2 = ph_mod.ph_iterk(batch, state, ko)
                jax.block_until_ready(st2.conv)
        except Exception as e:  # pragma: no cover
            out.setdefault("trace_error", repr(e))
        t0 = time.perf_counter()
        for _ in range(n):
            state = ph_mod.ph_iterk(batch, state, ko)
        jax.block_until_ready(state.conv)
        dt = (time.perf_counter() - t0) / n
        model_flops = _flops_per_ph_iter(batch, opts)

        entry = {
            "sec_per_iter": round(dt, 4),
            "iter_precision": ITER_PRECISION or "bf16x6",
            "xla_flops_per_iter_body_once": flops,
            "xla_bytes_per_iter_body_once": bytes_acc,
            "model_tflops": round(model_flops / dt / 1e12, 3),
            "trace_dir": trace_dir,
        }
        # trace-derived device profile: parse the capture just written
        # (stdlib-only, no TF/protobuf; telemetry/roofline.py defines
        # every metric).  measured_stream_gbps is hoisted to the entry
        # top level so r0N-over-r0N gates keep comparing the same key
        # the two-op estimate used to fill.
        try:
            from mpisppy_tpu.telemetry import roofline
            dev = roofline.roofline_path(trace_dir,
                                         jax.devices()[0].device_kind)
            entry["device_profile"] = dev
            entry["measured_stream_gbps"] = dev.get(
                "measured_stream_gbps")
        except (OSError, ValueError) as e:
            entry.setdefault("device_profile_error", repr(e))
        out[f"S{S}"] = entry
    out["note"] = ("xla_*_body_once are compiled cost-analysis figures "
                   "that count loop bodies once (no trip-count fold); "
                   "measured_stream_gbps and the device_profile "
                   "section are derived from the committed "
                   "jax.profiler capture by telemetry/roofline.py "
                   "(stream = HBM-dominated data-movement ops; "
                   "overlap_frac = DMA in-flight time hidden behind "
                   "compute)")
    # the device's published peaks for context (raises off the table)
    from mpisppy_tpu.telemetry import roofline
    out["device_peaks"] = {
        **roofline.device_peaks(jax.devices()[0].device_kind),
        "source": roofline.PEAKS_SOURCE}
    return out


def bench_wheel_scengen():
    """ISSUE 14 acceptance: seeded on-device scenario synthesis takes
    the wheel to S >= 1M (docs/scengen.md).  Three parts:

      * synthesized-vs-materialized A/B at the max COMMON scale both
        paths hold resident: PH iters/s on the same farmer batch as a
        concrete ScenarioBatch vs a VirtualBatch synthesizing inside
        the step — the ratio carries the >= 0.9 MILESTONE
        (telemetry/regress.py): recompute-instead-of-store must cost
        <= 10% throughput where both fit;
      * a synthesized S sweep up to >= 1M: iters/s, resident-bytes
        high-water estimate (program pytree + solver state) vs what
        host materialization would keep resident, and scaling
        efficiency (lane-throughput relative to the smallest scale);
      * the CERTIFIED run: the fused wheel (hub + Lagrangian outer +
        x̂ = x̄ recourse inner, one monolithic jitted step) at the top
        scale to rel_gap <= 1% — its presence at S1000000 is itself a
        MILESTONE (ratchet: the phase can never silently drop).

    CPU-smoke scale on this container; the ratchet milestones bind the
    numbers for the next hardware round (the PR-7 pattern)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from mpisppy_tpu import scengen
    from mpisppy_tpu.algos import fused_wheel as fw
    from mpisppy_tpu.algos import ph as ph_mod
    from mpisppy_tpu.models import farmer
    from mpisppy_tpu.ops import pdhg
    from mpisppy_tpu.telemetry import metrics as metrics_mod

    if SMOKE:
        sweep, common_s, big_s = [64, 256], 64, 256
    elif QUICK:
        sweep, common_s, big_s = [4_096, 65_536], 4_096, 65_536
    else:
        sweep, common_s, big_s = [10_000, 100_000, 1_000_000], \
            100_000, 1_000_000

    # throughput measurements run the SWEEP-standard PH config
    # (subproblem_windows=8, the same step every sweep_* phase times) so
    # the A/B ratio compares synthesis against the step the rest of the
    # bench reports; the certified 1M run below trades step weight for
    # exchange frequency (subproblem_windows=2 certifies in fewer
    # device-seconds on farmer)
    sweep_opts = ph_mod.PHOptions(
        default_rho=1.0, subproblem_windows=8, iter0_windows=20,
        pdhg=pdhg.PDHGOptions(tol=1e-6, restart_period=40,
                              iter_precision=ITER_PRECISION))
    ks = ph_mod.kernel_opts(sweep_opts)
    ph_opts = ph_mod.PHOptions(
        default_rho=1.0, subproblem_windows=2, iter0_windows=20,
        pdhg=pdhg.PDHGOptions(tol=1e-6, restart_period=40,
                              iter_precision=ITER_PRECISION))
    ko = ph_mod.kernel_opts(ph_opts)

    def state_bytes(st):
        return sum(int(getattr(a, "nbytes", 0) or 0)
                   for a in jax.tree_util.tree_leaves(st))

    def measure_ips(batch, n_iters):
        rho = jnp.ones(batch.num_nonants, jnp.float32)
        quick = dataclasses.replace(ks, iter0_windows=8)
        st, _, _ = ph_mod.ph_iter0(batch, rho, quick)
        st = ph_mod.ph_iterk(batch, st, ks)   # compile
        jax.block_until_ready(st.conv)
        t0 = time.perf_counter()
        for _ in range(n_iters):
            st = ph_mod.ph_iterk(batch, st, ks)
        jax.block_until_ready(st.conv)
        return n_iters / (time.perf_counter() - t0), st

    out = {"iter_precision": ITER_PRECISION or "bf16x6",
           "model": "farmer", "common_scenarios": common_s}

    # -- A/B at the common scale -----------------------------------------
    n_meas = 2 if SMOKE else 3
    prog_c = farmer.scenario_program(common_s, seed=0)
    vb_c = scengen.virtual_batch(prog_c)
    bm_c = scengen.materialize(prog_c)   # same bits, resident data
    ips_mat, st_m = measure_ips(bm_c, n_meas)
    ips_syn, _ = measure_ips(vb_c, n_meas)
    out["materialized"] = {
        "iters_per_sec": round(ips_mat, 4),
        "resident_data_bytes": vb_c.materialized_bytes(),
    }
    out["synthesized"] = {
        "iters_per_sec": round(ips_syn, 4),
        "resident_data_bytes": vb_c.persistent_bytes(),
    }
    out["synth_vs_materialized_ratio"] = round(ips_syn / ips_mat, 4)
    del bm_c, st_m

    # -- synthesized sweep to >= 1M --------------------------------------
    rows = []
    base_lanes = None
    for S in sweep:
        prog = farmer.scenario_program(S, seed=0)
        vb = scengen.virtual_batch(prog)
        ips, st = measure_ips(vb, n_meas if S < 1_000_000 else 2)
        lanes = ips * S
        if base_lanes is None:
            base_lanes = lanes
        rows.append({
            "scenarios": S,
            "iters_per_sec": round(ips, 4),
            "lane_iters_per_sec": round(lanes, 1),
            "scaling_efficiency": round(lanes / base_lanes, 4),
            "resident_bytes_synth": vb.persistent_bytes()
            + state_bytes(st),
            "resident_bytes_materialized_est": vb.materialized_bytes()
            + state_bytes(st),
        })
        del vb, st
    out["sweep"] = rows

    # -- the certified wheel at the top scale ----------------------------
    wopts = fw.FusedWheelOptions(lag_windows=4, xhat_windows=2,
                                 slam_windows=0, shuffle_windows=0,
                                 split_dispatch=False)
    prog_b = farmer.scenario_program(big_s, seed=0)
    vb_b = scengen.virtual_batch(prog_b)
    rho = jnp.ones(vb_b.num_nonants, jnp.float32)
    max_iters = 5 if SMOKE else 40
    t0 = time.perf_counter()
    wst, tb, cert = fw.fused_iter0(vb_b, rho, ko, wopts)
    outer = float(tb) if bool(cert) else float("-inf")
    inner, rel_gap, iters = float("inf"), float("inf"), 0
    for k in range(1, max_iters + 1):
        iters = k
        wst = fw.fused_iterk(vb_b, wst, ko, wopts)
        sc = dict(zip(fw.SCALAR_KEYS, np.asarray(wst.scalars)))
        if sc["lag_certified"] > 0.5 and np.isfinite(sc["lag_bound"]):
            outer = max(outer, float(sc["lag_bound"]))
        if sc["xhat_feasible"] > 0.5 and np.isfinite(sc["xhat_value"]):
            inner = min(inner, float(sc["xhat_value"]))
        if np.isfinite(inner) and np.isfinite(outer):
            rel_gap = (inner - outer) / max(abs(inner), abs(outer),
                                            1e-12)
            if rel_gap <= GAP_TARGET:
                break
    elapsed = time.perf_counter() - t0

    def _fin(v):
        return float(v) if np.isfinite(v) else None

    out["certified_run"] = {
        "scenarios": big_s,
        "seconds_to_gap": round(elapsed, 3),
        "iterations": iters,
        "sec_per_iter": round(elapsed / max(1, iters), 6),
        "rel_gap": _fin(rel_gap),
        "certified": bool(rel_gap <= GAP_TARGET),
        "outer": _fin(outer),
        "inner": _fin(inner),
        "resident_bytes_synth": vb_b.persistent_bytes()
        + state_bytes(wst),
        "resident_bytes_materialized_est": vb_b.materialized_bytes()
        + state_bytes(wst),
    }
    out["metrics_snapshot"] = metrics_mod.REGISTRY.to_snapshot()
    out["note"] = (
        "farmer scenarios synthesized on-device from counter-based "
        "keys (mpisppy_tpu/scengen): the A/B ratio compares PH "
        "iters/s on the SAME bits held resident vs synthesized "
        "in-step; the certified run is the fused wheel (hub + "
        "Lagrangian + x-bar recourse planes) at the top scale to "
        "rel_gap <= 1% with only the program pytree + solver state "
        "resident")
    return out


def bench_serve_load():
    """ISSUE 12 acceptance: the multi-tenant wheel server under load
    (docs/serving.md).  An in-process WheelServer (unix socket) serves
    N concurrent synthetic clients across tenants running the mixed
    farmer/sslp/uc workload; the phase reports p50/p99 client-observed
    time-to-1%-gap, then repeats the run with ONE adversarial tenant
    (flood through the ServeFault seam + hang + disconnect) and reports
    the healthy tenants' p99 against the clean baseline — the
    tenant-isolation ratio carries a <= 1.25 MILESTONE
    (telemetry/regress.py) and the latency keys gate at +-25%."""
    import tempfile

    from mpisppy_tpu.resilience.faults import FaultPlan, ServeFault
    from mpisppy_tpu.serve import loadgen
    from mpisppy_tpu.serve.server import ServeOptions, WheelServer

    n_clients = 4 if SMOKE else 8
    sessions_each = 1 if SMOKE else 2
    tenants = ("acme", "zeta")
    mix = loadgen.DEFAULT_MIX
    deadline_s = 600.0

    def run_round(fault_plan=None, adversary=None):
        td = tempfile.mkdtemp(prefix="serve_load_")
        # the isolation mechanism under test: per-tenant quota 1 over
        # max_running 3 means no tenant — adversarial or not — can
        # hold more than a third of the worker pool, and the WFQ pop
        # keeps the freed slots rotating fairly (docs/serving.md)
        srv = WheelServer(ServeOptions(
            unix_path=os.path.join(td, "wheel.sock"),
            trace_dir=os.path.join(td, "traces"),
            spool_dir=os.path.join(td, "spool"),
            max_running=3, tenant_quota=1,
            max_queued=24, max_queued_per_tenant=8,
            fault_plan=fault_plan, multiplex=True)).start()
        try:
            recs = loadgen.run_load(
                srv.address, n_clients=n_clients,
                sessions_each=sessions_each, tenants=tenants,
                mix=mix, gap_target=GAP_TARGET, max_iterations=300,
                deadline_s=deadline_s, adversary=adversary,
                adversary_sessions=6, fault_plan=fault_plan)
            stats = srv.stats()
        finally:
            srv.stop()
        return recs, stats

    t0 = time.perf_counter()
    # warm-up round (uncounted): every model in the mix compiles once
    # per process, so the baseline/adversarial A/B below compares
    # serving latency, not who paid the jit compiles
    run_round()
    base_recs, base_stats = run_round()
    base = loadgen.summarize(base_recs, healthy_tenants=tenants)

    plan = FaultPlan(seed=12, serves=(
        ServeFault("flood", tenant="mallory", flood_factor=3),
        ServeFault("hang", tenant="mallory", at_sessions=(0,),
                   hang_s=30.0),
        ServeFault("disconnect", tenant="mallory", at_sessions=(1,)),
    ))
    adv_recs, adv_stats = run_round(fault_plan=plan,
                                    adversary="mallory")
    healthy = loadgen.summarize(adv_recs, healthy_tenants=tenants)
    adversary = loadgen.summarize(adv_recs,
                                  healthy_tenants=("mallory",))
    ratio = None
    if base["time_to_gap_p99_s"] and healthy["time_to_gap_p99_s"]:
        ratio = round(healthy["time_to_gap_p99_s"]
                      / base["time_to_gap_p99_s"], 4)
    out = {
        "clients": n_clients,
        "tenants": len(tenants),
        "sessions": base["sessions"],
        "iter_precision": ITER_PRECISION or "bf16x6",
        "gap_target": GAP_TARGET,
        "reached_gap": base["reached_gap"],
        "time_to_gap_p50_s": base["time_to_gap_p50_s"],
        "time_to_gap_p99_s": base["time_to_gap_p99_s"],
        "outcomes": base["outcomes"],
        "dispatch": base_stats.get("dispatch"),
        "exchange_ring": base_stats.get("exchange_ring"),
        "isolation": {
            "adversary": "mallory",
            "healthy_sessions": healthy["sessions"],
            "healthy_reached_gap": healthy["reached_gap"],
            "baseline_p99_s": base["time_to_gap_p99_s"],
            "adversarial_healthy_p99_s": healthy["time_to_gap_p99_s"],
            "adversarial_healthy_p50_s": healthy["time_to_gap_p50_s"],
            "isolation_ratio": ratio,
            "milestone_isolation_ratio": 1.25,
            "adversary_outcomes": adversary["outcomes"],
            "admission_rejects": adv_stats["admission"]["rejected"],
        },
        "bench_serve_total_sec": round(time.perf_counter() - t0, 1),
        "note": "multi-tenant wheel server under load: mixed "
                "farmer/sslp/uc sessions over one shared device "
                "wheel stack; time_to_gap = client-observed wall "
                "from submit to the first streamed rel_gap <= 1%; "
                "isolation_ratio = healthy-tenant p99 with one "
                "adversarial tenant (flood+hang+disconnect "
                "ServeFaults) over the no-adversary baseline p99 "
                "(acceptance <= 1.25)",
    }
    # ISSUE 20: commit the per-class SLO burn rates alongside the raw
    # latencies so regress.py's slo.* gates bind on this artifact
    from mpisppy_tpu.telemetry import slo as _slo
    out["slo"] = _slo.bench_slo_section({"serve_load": out})
    return out


def bench_mesh_chaos():
    """ISSUE 17 acceptance: kill one host mid-wheel and prove the
    elastic re-shard (parallel/elastic.run_elastic) resumes on the
    survivors and certifies the SAME <= 1% gap as a fault-free
    baseline.  A/B on the device mesh split as 4 hosts: 4 x 2 of 8
    virtual CPU devices with no accelerator, one chip per host on a
    four-chip machine (fewer chips: the phase reports that it needs
    four and runs nothing).  The A side spins a sharded fused wheel on a synthesized
    farmer batch to the certified gap; the B side runs the identical
    program under a FaultPlan that kills host 1 mid-wheel — membership
    fences it, the MeshDegraded unwind lands the emergency checkpoint,
    and run_elastic rebuilds on the survivors (the batch re-pads with
    zero-probability lanes) and resumes holding the bracket.  Gates:
    mesh_reshards_lost_total carries an any-increase gate (0 resharded
    runs lost) and reshard_reached_gap_frac a 1.0 ratchet MILESTONE
    (telemetry/regress.py)."""
    import tempfile

    from mpisppy_tpu import scengen
    from mpisppy_tpu.algos import fused_wheel as fw
    from mpisppy_tpu.algos import ph as ph_mod
    from mpisppy_tpu.cylinders import PHHub
    from mpisppy_tpu.cylinders.spoke import (
        FusedLagrangianOuterBound, FusedXhatXbarInnerBound,
    )
    from mpisppy_tpu.models import farmer
    from mpisppy_tpu.ops import pdhg
    from mpisppy_tpu.parallel import mesh as mesh_mod
    from mpisppy_tpu.parallel.elastic import run_elastic
    from mpisppy_tpu.resilience import FaultPlan, MeshFault
    from mpisppy_tpu.spin_the_wheel import WheelSpinner
    from mpisppy_tpu.telemetry import EventBus
    from mpisppy_tpu.telemetry import metrics as _metrics

    import jax
    num_hosts = 4
    devs = jax.devices()
    if devs[0].platform != "cpu" and len(devs) < num_hosts:
        # one simulated host per chip: the A/B needs a four-chip host
        # (chiprun --chips 4); a smaller machine reports, never runs it
        return {"skipped": f"mesh_chaos needs {num_hosts} chips (one per "
                           f"simulated host); this process sees "
                           f"{len(devs)} {devs[0].platform} device(s)"}
    S = 256 if SMOKE else (10_000 if QUICK else 100_000)
    kill_iter = 2 if SMOKE else 6
    max_iters = 5 if SMOKE else MAX_WHEEL_ITERS
    prog = farmer.scenario_program(S, seed=0)
    wopts = fw.FusedWheelOptions(lag_windows=4, xhat_windows=2,
                                 slam_windows=0, shuffle_windows=0,
                                 split_dispatch=False,
                                 lag_pdhg=pdhg.PDHGOptions(tol=1e-7),
                                 xhat_pdhg=pdhg.PDHGOptions(
                                     tol=1e-7, omega0=0.1,
                                     restart_period=80))
    spokes = [
        {"spoke_class": FusedLagrangianOuterBound,
         "opt_kwargs": {"options": {}}},
        {"spoke_class": FusedXhatXbarInnerBound,
         "opt_kwargs": {"options": {}}},
    ]

    def build_fn(ckpt):
        def build(mesh):
            b = mesh_mod.shard_batch(scengen.virtual_batch(prog), mesh,
                                     pad=True)
            opts = ph_mod.PHOptions(
                default_rho=1.0, max_iterations=max_iters,
                conv_thresh=0.0, subproblem_windows=10,
                pdhg=pdhg.PDHGOptions(tol=1e-7,
                                      iter_precision=ITER_PRECISION))
            hub = {"hub_class": PHHub,
                   "hub_kwargs": {"options": {
                       "rel_gap": GAP_TARGET,
                       "checkpoint_path": ckpt,
                       "checkpoint_every_s": 1e9}},
                   "opt_class": fw.FusedPH,
                   "opt_kwargs": {"options": opts, "batch": b,
                                  "wheel_options": wopts}}
            return WheelSpinner(hub, spokes)
        return build

    def bracket(ws):
        inner = float(ws.BestInnerBound)
        outer = float(ws.BestOuterBound)
        gap = (inner - outer) / max(abs(inner), abs(outer), 1e-12)
        return inner, outer, gap

    td = tempfile.mkdtemp(prefix="mesh_chaos_")

    # A side: fault-free wheel at the full topology
    t0 = time.perf_counter()
    base = build_fn(os.path.join(td, "base.npz"))(mesh_mod.make_mesh())
    base.spin()
    base_s = round(time.perf_counter() - t0, 2)
    ib, ob, gb = bracket(base)

    # B side: identical program, host 1 dies at kill_iter
    bus = EventBus()
    lost0 = _metrics.REGISTRY.get("mesh_reshards_lost_total")
    resh0 = _metrics.REGISTRY.get("mesh_reshards_total")
    plan = FaultPlan(seed=11, meshes=(
        MeshFault("host_lost", host=1, at_iters=(kill_iter,)),))
    t1 = time.perf_counter()
    ws, info = run_elastic(
        build_fn(os.path.join(td, "chaos.npz")), num_hosts=num_hosts,
        checkpoint_path=os.path.join(td, "chaos.npz"), plan=plan,
        bus=bus, run_id="bench-mesh-chaos")
    chaos_s = round(time.perf_counter() - t1, 2)
    ic, oc, gc = bracket(ws)

    lost = _metrics.REGISTRY.get("mesh_reshards_lost_total") - lost0
    reshards = _metrics.REGISTRY.get("mesh_reshards_total") - resh0
    certified = bool(gc <= GAP_TARGET)
    return {
        "scenarios": S,
        "num_hosts": num_hosts,
        "iter_precision": ITER_PRECISION or "bf16x6",
        "gap_target": GAP_TARGET,
        "baseline": {
            "devices": len(devs), "inner": ib, "outer": ob,
            "rel_gap": round(gb, 6), "iters": base.spcomm._iter,
            "wall_s": base_s, "certified": bool(gb <= GAP_TARGET),
        },
        "chaos": {
            "chaos": f"kill host 1 at hub iter {kill_iter}",
            "final_devices": info["final_devices"],
            "epoch": info["epoch"],
            "inner": ic, "outer": oc, "rel_gap": round(gc, 6),
            "iters": ws.spcomm._iter, "wall_s": chaos_s,
            "certified": certified,
            "reshard_transitions": info["reshards"],
        },
        "reshard": {
            "mesh_reshards_total": reshards,
            "mesh_reshards_lost_total": lost,
            "reshard_reached_gap_frac": 1.0 if certified else 0.0,
        },
        "bench_mesh_chaos_total_sec": round(time.perf_counter() - t0, 1),
        "note": "elastic mesh A/B: fault-free sharded fused wheel vs "
                "the same wheel with host 1 killed mid-run; the "
                "MeshDegraded unwind lands the emergency checkpoint, "
                "run_elastic re-shards across the survivor devices "
                "(zero-probability pad lanes keep the bracket "
                "layout-invariant) and the resumed run must certify "
                "the same <= 1% gap; reshard_reached_gap_frac "
                "ratchets at 1.0 and mesh_reshards_lost_total must "
                "stay 0",
    }


def bench_fleet_serve_load():
    """ISSUE 16 acceptance: the replicated serve fleet under load with
    a replica killed mid-traffic (docs/serving.md fleet section).  A
    3-replica FleetRouter — each replica a full wheel server with its
    own engine and structure interner over one shared checkpoint spool
    — serves the mixed farmer/sslp/uc workload twice: a fault-free
    round (p50/p99 client-observed time-to-gap) and a chaos round
    where r0 dies mid-traffic and its running sessions LIVE-MIGRATE
    (emergency checkpoint -> requeue -> restore on a surviving
    replica).  Gates: the latency keys at +-25% and isolation_ratio
    (chaos p99 over fault-free p99) ride the serve_load patterns;
    fleet_migrations_lost_total carries an any-increase gate and
    migrated_reached_gap_frac a 1.0 ratchet MILESTONE
    (telemetry/regress.py)."""
    import json as _json
    import tempfile

    from mpisppy_tpu.fleet import FleetOptions, FleetRouter
    from mpisppy_tpu.resilience.faults import FaultPlan, ReplicaFault
    from mpisppy_tpu.serve import loadgen
    from mpisppy_tpu.telemetry import metrics as _metrics

    n_replicas = 3
    n_clients = 4 if SMOKE else 8
    sessions_each = 1 if SMOKE else 2
    tenants = ("acme", "zeta")
    deadline_s = 600.0
    heartbeat_s = 0.5

    def run_round(fault_plan=None):
        td = tempfile.mkdtemp(prefix="fleet_load_")
        router = FleetRouter(FleetOptions(
            unix_path=os.path.join(td, "fleet.sock"),
            n_replicas=n_replicas, max_running_per_replica=1,
            max_queued=24, max_queued_per_tenant=8, tenant_quota=2,
            trace_dir=os.path.join(td, "traces"),
            spool_dir=os.path.join(td, "spool"),
            heartbeat_s=heartbeat_s, drain_grace_s=60.0,
            fault_plan=fault_plan)).start()
        try:
            recs = loadgen.run_load(
                router.address, n_clients=n_clients,
                sessions_each=sessions_each, tenants=tenants,
                mix=loadgen.DEFAULT_MIX, gap_target=GAP_TARGET,
                max_iterations=300, deadline_s=deadline_s,
                fault_plan=fault_plan)
            stats = router.stats()
        finally:
            router.stop()
        # evidence scan: terminal session-state transitions and
        # migrations in the router stream (one file, every replica)
        terminals: dict = {}
        migrated: set = set()
        fleet_log = os.path.join(td, "traces", "fleet.jsonl")
        if os.path.exists(fleet_log):
            with open(fleet_log) as f:
                for line in f:
                    try:
                        row = _json.loads(line)
                    except ValueError:
                        continue
                    d = row.get("data", {})
                    if row.get("kind") == "session-state" \
                            and d.get("state") in ("DONE", "FAILED",
                                                   "REJECTED"):
                        sid = d.get("session")
                        terminals[sid] = terminals.get(sid, 0) + 1
                    elif row.get("kind") == "session-migrated" \
                            and not d.get("queued"):
                        migrated.add(d.get("session"))
        return recs, stats, terminals, migrated

    t0 = time.perf_counter()
    lost0 = _metrics.REGISTRY.get("fleet_migrations_lost_total")
    # warm-up round (uncounted): every model in the mix compiles once
    # per process, so the A/B below compares serving, not jit
    run_round()
    base_recs, base_stats, base_terms, _ = run_round()
    base = loadgen.summarize(base_recs, healthy_tenants=tenants)

    # chaos round: r0 stops heartbeating a few beats in — the router
    # fences it, drains it, and its sessions migrate mid-solve
    kill_beat = 2 if SMOKE else 8
    plan = FaultPlan(seed=12, replicas=(
        ReplicaFault("kill", replica="r0", at_beats=(kill_beat,)),))
    chaos_recs, chaos_stats, chaos_terms, migrated = run_round(plan)
    chaos = loadgen.summarize(chaos_recs, healthy_tenants=tenants)

    mig_recs = [r for r in chaos_recs if r.get("session") in migrated]
    mig_hit = sum(1 for r in mig_recs
                  if r["time_to_gap_s"] is not None)
    mig_frac = round(mig_hit / len(mig_recs), 4) if mig_recs else None
    ratio = None
    if base["time_to_gap_p99_s"] and chaos["time_to_gap_p99_s"]:
        ratio = round(chaos["time_to_gap_p99_s"]
                      / base["time_to_gap_p99_s"], 4)
    multi = {sid: n for sid, n in {**base_terms, **chaos_terms}.items()
             if n > 1}
    lost = _metrics.REGISTRY.get("fleet_migrations_lost_total") - lost0
    out = {
        "replicas": n_replicas,
        "clients": n_clients,
        "sessions": base["sessions"],
        "iter_precision": ITER_PRECISION or "bf16x6",
        "gap_target": GAP_TARGET,
        "reached_gap": base["reached_gap"],
        "time_to_gap_p50_s": base["time_to_gap_p50_s"],
        "time_to_gap_p99_s": base["time_to_gap_p99_s"],
        "outcomes": base["outcomes"],
        "placement": {
            # process-cumulative across the three rounds
            "affinity": _metrics.REGISTRY.get(
                "fleet_placement_affinity_total"),
            "spill": _metrics.REGISTRY.get(
                "fleet_placement_spill_total"),
        },
        "isolation": {
            "chaos": "kill r0 mid-traffic",
            "baseline_p99_s": base["time_to_gap_p99_s"],
            "chaos_p50_s": chaos["time_to_gap_p50_s"],
            "chaos_p99_s": chaos["time_to_gap_p99_s"],
            "chaos_reached_gap": chaos["reached_gap"],
            "chaos_outcomes": chaos["outcomes"],
            "isolation_ratio": ratio,
        },
        "migration": {
            "replica_deaths": 1,
            "migrated_sessions": len(migrated),
            "migration_counters": chaos_stats["migration"],
            "migrated_reached_gap_frac": mig_frac,
            "fleet_migrations_lost_total": lost,
            "sessions_multi_terminal": len(multi),
        },
        "single_replica_ref": {
            # BENCH_r09 serve_load on the same workload shape (one
            # 3-slot server vs this 3x1-slot fleet)
            "time_to_gap_p50_s": 1.9227,
            "time_to_gap_p99_s": 5.9392,
            "isolation_ratio": 0.9732,
        },
        "bench_fleet_total_sec": round(time.perf_counter() - t0, 1),
        "note": "replicated serve fleet under load: 3 replicas x 1 "
                "slot, each a full wheel server with its own engine/"
                "interner over one shared checkpoint spool; fault-free "
                "round vs chaos round with r0 killed mid-traffic; "
                "running sessions on r0 live-migrate (emergency "
                "checkpoint -> requeue -> restore elsewhere); "
                "isolation_ratio = chaos p99 / fault-free p99; every "
                "session must observe exactly one terminal outcome "
                "and fleet_migrations_lost_total must stay 0",
    }
    # ISSUE 20: per-class SLO burn rates over the fault-free round
    from mpisppy_tpu.telemetry import slo as _slo
    out["slo"] = _slo.bench_slo_section({"fleet_serve_load": out})
    return out


def bench_mpc_stream():
    """ISSUE 19 acceptance: rolling-horizon MPC streams as a latency
    class (docs/mpc.md).  Two parts:

    LATENCY A/B — for each committed horizon (uc 2g/4h stride 1 and
    ccopf --soc) a RollingDriver solves the same windows twice: WARM
    (the previous step's PH plane shifted by the horizon's ShiftPlan)
    and COLD (no plane, jit compiles already paid), at the same
    per-step iteration budget.  Per-model step-latency p50/p99 gate at
    +-25% (telemetry/regress.py); the pooled warm-over-cold mean
    ratio carries the <= 0.6 MILESTONE.

    CHAOS — one uc stream runs fault-free through the serve engine
    (WheelEngine -> mpc.stream), then a second identical stream is
    PREEMPTED mid-flight (preempt_event at a step boundary, the
    live-migration drain seam) and resumed from its stream checkpoint.
    Every per-step bound of the resumed stream must match the
    fault-free stream bit-for-bit (resumed_matched_frac ratchets at
    1.0) and the session must observe exactly one terminal verdict."""
    import tempfile

    from mpisppy_tpu.mpc.driver import RollingDriver
    from mpisppy_tpu.mpc.horizon import ccopf_horizon, uc_horizon
    from mpisppy_tpu.serve.engine import WheelEngine
    from mpisppy_tpu.serve.protocol import SubmitRequest
    from mpisppy_tpu.serve.session import Session

    steps = 2 if SMOKE else 4
    gap = 0.05
    budget = 300
    t0 = time.perf_counter()

    def latency_ab(horizon):
        drv = RollingDriver(horizon)
        tc = time.perf_counter()
        res = drv.run_step(0)
        cold0_s = time.perf_counter() - tc     # pays the jit compiles
        plane = drv.next_plane(res)
        warm, cold, degraded, warm_hit, cold_hit = [], [], 0, 0, 0
        for k in range(1, steps + 1):
            tw = time.perf_counter()
            r = drv.run_step(k, warm_plane=plane)
            warm.append(time.perf_counter() - tw)
            plane = drv.next_plane(r)
            degraded += 1 if r.degraded else 0
            warm_hit += 0 if r.degraded else 1
        for k in range(1, steps + 1):
            tw = time.perf_counter()
            r = drv.run_step(k)
            cold.append(time.perf_counter() - tw)
            cold_hit += 0 if r.degraded else 1
        wl, cl = np.asarray(warm), np.asarray(cold)
        return {
            "steps": steps,
            "cold_step0_s": round(cold0_s, 4),
            "warm_mean_s": round(float(wl.mean()), 4),
            "cold_mean_s": round(float(cl.mean()), 4),
            "step_latency_p50_s": round(float(np.percentile(wl, 50)), 4),
            "step_latency_p99_s": round(float(np.percentile(wl, 99)), 4),
            "model_warm_cold_ratio": round(
                float(wl.mean() / cl.mean()), 4),
            "warm_reached_gap_frac": round(warm_hit / steps, 4),
            "cold_reached_gap_frac": round(cold_hit / steps, 4),
            "degraded_steps": degraded,
        }, warm, cold

    uc_args = ("--uc-n-gens", "2", "--uc-n-hours", "4")
    uc_stats, uc_warm, uc_cold = latency_ab(uc_horizon(
        n_gens=2, n_hours=4, num_scens=3, gap_target=gap,
        max_step_iterations=budget))
    cc_stats, cc_warm, cc_cold = latency_ab(ccopf_horizon(
        soc=True, gap_target=gap, max_step_iterations=budget))
    pooled_warm = np.asarray(uc_warm + cc_warm)
    pooled_cold = np.asarray(uc_cold + cc_cold)
    ratio = round(float(pooled_warm.mean() / pooled_cold.mean()), 4)

    # -- chaos: preempt one uc stream mid-flight and resume it ----------
    td = tempfile.mkdtemp(prefix="mpc_stream_")
    engine = WheelEngine(multiplexed=False)

    def make_session(lines):
        s = Session(SubmitRequest(
            tenant="acme", sla="latency", model="uc", num_scens=3,
            gap_target=gap, max_iterations=budget, args=uc_args,
            mpc_steps=steps, step_deadline_s=600.0),
            outbox=lines.append)
        s.checkpoint_path = os.path.join(td, f"stream-{s.sid}.npz")
        return s

    def step_lines(lines):
        return {l["step"]: l for l in lines if l.get("event") == "step"}

    base_lines: list = []
    verdict, _ = engine.run(make_session(base_lines))
    base_steps = step_lines(base_lines)

    preempt_at = max(1, steps // 2)
    chaos_lines: list = []
    s2 = make_session(chaos_lines)
    s2.on_step = (lambda sess: sess.preempt_event.set()
                  if sess.mpc_step == preempt_at else None)
    v1, p1 = engine.run(s2)
    terminal = 1 if v1 == "done" else 0
    s2.preempt_event.clear()
    s2.restore = True
    s2.preemptions += 1
    v2, p2 = engine.run(s2)
    terminal += 1 if v2 == "done" else 0
    chaos_steps = step_lines(chaos_lines)

    def close(a, b):
        return abs(a - b) <= 1e-9 * max(1.0, abs(a), abs(b))

    matched = sum(
        1 for k, row in base_steps.items()
        if k in chaos_steps
        and close(row["outer"], chaos_steps[k]["outer"])
        and close(row["inner"], chaos_steps[k]["inner"])
        and close(row["rel_gap"], chaos_steps[k]["rel_gap"]))
    out = {
        "steps_per_stream": steps,
        "gap_target": gap,
        "iter_budget_per_step": budget,
        "warm_over_cold_ratio": ratio,
        "milestone_warm_over_cold_ratio": 0.6,
        "uc": uc_stats,
        "ccopf_soc": cc_stats,
        "chaos": {
            "chaos": f"preempt the stream entering step {preempt_at}, "
                     "resume from the stream checkpoint",
            "preempted_verdict": v1,
            "preempted_at_step": p1.get("step"),
            "resumed_verdict": v2,
            "steps_matched": matched,
            "steps_total": len(base_steps),
            "resumed_matched_frac": round(
                matched / max(1, len(base_steps)), 4),
            "terminal_outcomes": terminal,
            "resumed_step_latency_p99_s": p2.get("step_latency_p99_s"),
        },
        "bench_mpc_total_sec": round(time.perf_counter() - t0, 1),
        "note": "rolling-horizon MPC streams: per-model warm (shifted "
                "PH plane) vs cold (no plane, compiles paid) per-step "
                "latency at the same iteration budget; "
                "warm_over_cold_ratio pools both horizons' steps "
                "(acceptance <= 0.6) — uc is where the warm start "
                "pays (cold re-solves miss certification inside the "
                "budget), ccopf --soc certifies in 2 iterations either "
                "way (warm parity); the chaos round preempts a uc "
                "stream at a step boundary and the resumed stream "
                "must reproduce the fault-free per-step bounds "
                "bit-for-bit with exactly one terminal outcome",
    }
    # ISSUE 20: the mpc stream product's step-deadline SLO burn rate
    from mpisppy_tpu.telemetry import slo as _slo
    out["slo"] = _slo.bench_slo_section({"mpc_stream": out})
    return out


_PHASES = {
    "sslp_to_1pct_gap": lambda: bench_sslp_gap(),
    "uc_fwph_to_1pct_gap": lambda: bench_uc_fwph(),
    "uc_fwph_hub_to_1pct_gap": lambda: bench_uc_fwph_hub(),
    "hydro_to_1pct_gap": lambda: bench_hydro(),
    "wheel_overhead": lambda: bench_wheel_overhead(),
    "wheel_overhead_async": lambda: bench_wheel_overhead_async(),
    "measured_mfu": lambda: bench_measured_mfu(),
    "wheel_scengen": lambda: bench_wheel_scengen(),
    "serve_load": lambda: bench_serve_load(),
    "mpc_stream": lambda: bench_mpc_stream(),
    "fleet_serve_load": lambda: bench_fleet_serve_load(),
    "mesh_chaos": lambda: bench_mesh_chaos(),
    "baseline_anchor": lambda: bench_baseline_anchor(),
}

#: per-phase subprocess timeout overrides (seconds): the scengen phase
#: runs a certified S=1M wheel on whatever host it lands on — CPU smoke
#: needs ~30 min of honest device work, not a larger problem
_PHASE_TIMEOUTS = {"wheel_scengen": 5400}
for _S in SWEEP:
    _PHASES[f"sweep_{_S}"] = (lambda S=_S: bench_sweep_one(S))


def _run_phase_once(phase: str, timeout: int):
    import subprocess
    import sys
    try:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--phase", phase],
            capture_output=True, text=True, timeout=timeout,
            cwd=os.path.dirname(os.path.abspath(__file__)) or ".")
        for line in reversed(out.stdout.strip().splitlines()):
            line = line.strip()
            # global_toc trace lines also start with '[' — parse
            # leniently and keep scanning on failure
            if line.startswith("{") or line.startswith("[{"):
                try:
                    return json.loads(line)
                except json.JSONDecodeError:
                    continue
        return {"error": f"no JSON from phase (rc={out.returncode}): "
                         f"{out.stderr.strip()[-300:]}"}
    except subprocess.TimeoutExpired:
        return {"error": f"phase timed out after {timeout}s"}


def _run_phase_subprocess(phase: str, timeout: int = 2400, retries: int = 1):
    """Each phase runs in its own process with a fresh TPU client: the
    worker occasionally dies after sustained heavy use (observed
    'kernel fault' after ~10-15 min of back-to-back wheels), and one
    phase's crash must not cost the others their numbers.  A crashed
    phase is retried once; wheel phases resume from their periodic
    checkpoint, so the retry continues (not restarts) the run —
    VERDICT r3 #2's 'the official artifact must not record -1.0'."""
    import glob
    # a fresh phase must not resume some older run's leftover state;
    # checkpoints land in the CHILD's cwd (= this file's directory, set
    # below), but scan the parent cwd too in case of older runs
    dirs = {os.path.dirname(os.path.abspath(__file__)) or ".",
            os.getcwd()}
    for d in dirs:
        for stale in glob.glob(os.path.join(d, ".bench_ckpt_*.npz")):
            os.remove(stale)
    result = _run_phase_once(phase, timeout)
    for attempt in range(retries):
        if "error" not in result:
            break
        print(f"# phase {phase} attempt {attempt + 1} failed "
              f"({result['error'][:120]}); retrying from checkpoint",
              flush=True)
        result = _run_phase_once(phase, timeout)
    return result


def _device_record() -> dict:
    """The device this process ran on, as JAX reports it."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def main():
    import sys
    if len(sys.argv) >= 3 and sys.argv[1] == "--phase":
        # child: run one phase, emit its JSON as the last stdout line
        phase = sys.argv[2]
        if phase == "mesh_chaos":
            # with no accelerator the elastic A/B runs on 8 virtual
            # CPU devices (the flag only affects the host platform, so
            # on a chip the real devices run it); the phase's device
            # record says which
            flags = os.environ.get("XLA_FLAGS", "")
            if "host_platform_device_count" not in flags:
                os.environ["XLA_FLAGS"] = (
                    flags + " --xla_force_host_platform_device_count=8"
                ).strip()
        from mpisppy_tpu.utils.compile_cache import enable_compile_cache
        enable_compile_cache()
        result = _PHASES[phase]()
        result["device"] = _device_record()
        print(json.dumps(result))
        return

    # parent: never touches JAX, so each child owns the chip in turn
    t_start = time.time()
    detail = {}
    for phase in _PHASES:
        detail[phase] = _run_phase_subprocess(
            phase, timeout=_PHASE_TIMEOUTS.get(phase, 2400))
    failed = sorted(ph for ph, r in detail.items() if "error" in r)
    detail["sweep_iters_per_sec"] = [
        detail.pop(f"sweep_{S}") for S in SWEEP]
    detail["bench_total_sec"] = round(time.time() - t_start, 1)
    headline = detail["sslp_to_1pct_gap"]
    detail["device"] = headline.get("device")

    # never clobber the full-scale hardware artifact with reduced-scale
    # runs: quick mode writes its own file (ADVICE r3 low #2)
    if not SMOKE:
        fname = "BENCH_DETAIL.quick.json" if QUICK else "BENCH_DETAIL.json"
        with open(fname, "w") as f:
            json.dump(detail, f, indent=1)

    if failed:
        print(json.dumps({"error": f"phases failed: {failed}",
                          "detail": detail}))
        sys.exit(1)
    print(json.dumps({
        "metric": f"wallclock_to_1pct_certified_gap_sslp_15_45_"
                  f"{SSLP_SCENS}scen",
        "value": headline["seconds_to_gap"],
        "unit": "s",
        "vs_baseline": round(headline["baseline_64rank_sec"] / max(
            headline["seconds_to_gap"], 1e-9), 2),
        "detail": detail,
    }))


if __name__ == "__main__":
    main()
