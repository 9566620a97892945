"""Certified MIP brackets for the SIPLIB sslp_15_45 instances
(VERDICT r3 next #4: close the certified gaps toward <=0.5% with the
incumbent at the published optimum).

Two models of the SAME integer problem, one per bound side:

  * OUTER bounds run on the VUB-STRENGTHENED model (y_ij <= x_j rows,
    models/sslp.py strengthen=True).  Validity: with the SIPLIB
    penalty (1000/unit) far above any revenue, an optimal solution
    never serves a client from a closed server when any server is open
    (moving the assignment to an open server pays at most the same
    overflow penalty while keeping the revenue), and all-closed first
    stages cost ~penalty * total demand >> optimum — so the VUB cuts
    remove only suboptimal points and the strengthened optimum EQUALS
    the original.  Lower bounds for the strengthened problem are
    therefore valid lower bounds for the original, and its LP
    relaxation is far tighter (-268 vs -280 on sslp_15_45_5).
  * INNER bounds run on the ORIGINAL penalty-form model: its recourse
    is feasible for every first stage (the dummy columns absorb any
    overflow), so the dive/B&B incumbent search never mistakes a good
    candidate for infeasible under a truncated budget.

Pipeline per instance (every bound CERTIFIED):
  1. LP PH on the strengthened model -> multipliers W
  2. certified LP-Lagrangian outer at W (this alone beats round-3's
     integer-Lagrangian bound)
  3. candidate pool (wait-and-see MIP first stages + rounded xbar +
     slam) -> batched evaluate_mip_many on the ORIGINAL model
  4. 1-flip local search over the server-open binaries -> incumbent
  5. Polyak-step dual ascent on the strengthened INTEGER Lagrangian
  6. if still short of target: first-stage decomposition B&B

Writes SSLP_CERT.json.  Usage:
    python sslp_cert.py [--instances 5,10] [--ascent 12] [--quick]
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np


def certify(n_scens: int, ascent_steps: int, dd_nodes: int,
            target_gap: float = 0.005, verbose: bool = True,
            seed_cands=None) -> dict:
    import jax.numpy as jnp

    from mpisppy_tpu.algos import lagrangian as lag_mod
    from mpisppy_tpu.algos import mip as mip_mod
    from mpisppy_tpu.algos import ph as ph_mod
    from mpisppy_tpu.algos import xhat as xhat_mod
    from mpisppy_tpu.core import batch as batch_mod
    from mpisppy_tpu.models import sslp
    from mpisppy_tpu.ops import bnb, pdhg

    t_start = time.time()
    dd_dir = ("/root/reference/examples/sslp/data/"
              f"sslp_15_45_{n_scens}/scenariodata")
    names = sslp.scenario_names_creator(n_scens)
    specs = [sslp.scenario_creator(nm, data_dir=dd_dir, num_scens=n_scens,
                                   strengthen=True) for nm in names]
    batch = batch_mod.from_specs(specs)       # outer plane (tight LP)
    specs_o = [sslp.scenario_creator(nm, data_dir=dd_dir,
                                     num_scens=n_scens) for nm in names]
    batch_inner = batch_mod.from_specs(specs_o)  # inner plane (penalty)

    # -- 2. LP PH for W ----------------------------------------------------
    ph_opts = ph_mod.PHOptions(
        default_rho=50.0, max_iterations=200, conv_thresh=1e-6,
        subproblem_windows=8,
        pdhg=pdhg.PDHGOptions(tol=1e-6, restart_period=40))
    drv = ph_mod.PH(ph_opts, batch)
    _, _, trivial = drv.ph_main()
    W = drv.state.W
    if verbose:
        print(f"[cert{n_scens}] PH conv {float(drv.state.conv):.2e} "
              f"({time.time() - t_start:.0f}s)")

    # -- 3. certified LP-Lagrangian outer ----------------------------------
    lp_lag = lag_mod.lagrangian_bound(
        batch, W, pdhg.PDHGOptions(tol=1e-6, max_iters=100_000))
    outer = float(lp_lag.bound) if bool(lp_lag.certified) else -float("inf")
    if verbose:
        print(f"[cert{n_scens}] LP-lag outer {outer:.4f} "
              f"cert={bool(lp_lag.certified)}")

    # INNER-side evaluations need good integer-feasible incumbents
    # (res.inner is a valid upper bound at any truncation, but weak
    # incumbents inflate it — round 3 reached the published optima at
    # this budget); the OUTER side's bound quality scales with the
    # per-scenario B&B budget on the strengthened model.
    # pump_rounds=0: the feasibility pump's rapid small-dispatch host
    # loop stalled the round-5 device runtime on these instances; the
    # multistart + LNS polish provides the incumbent quality instead
    # swap repair enabled explicitly: this is final-candidate
    # certification (the polish context the default-0 swap_rounds
    # reserves it for)
    eval_opts = bnb.BnBOptions(max_rounds=400, pump_rounds=0,
                               swap_rounds=bnb.POLISH_SWAP_ROUNDS)
    lag_opts = bnb.BnBOptions(max_rounds=240, pump_rounds=0)

    # -- 4. candidate pool + batched MIP evaluation ------------------------
    x_non = batch.nonants(drv.state.solver.x)
    cands = [np.asarray(xhat_mod.round_integers(batch,
                                                drv.state.xbar_nodes[0])),
             np.asarray(xhat_mod.slam_candidate(batch, x_non, True)),
             np.asarray(xhat_mod.slam_candidate(batch, x_non, False))]
    # through the dispatch scheduler (docs/dispatch.md) like every
    # other oracle call in this driver: bucket-padded shapes + the
    # bounded in-flight queue are what un-wedge these runs (round 5)
    from mpisppy_tpu import dispatch as _dispatch
    ws = _dispatch.solve_mip(batch_inner.qp, batch_inner.d_col, np.nonzero(
        np.asarray(batch_inner.integer_full))[0].astype(np.int32),
        eval_opts)
    ws_x = np.asarray(ws.x)[:, np.asarray(batch_inner.nonant_idx)]
    for s in range(batch.num_real):
        if bool(np.asarray(ws.feasible)[s]):
            cands.append(np.round(ws_x[s]))
    if seed_cands is not None:
        # externally supplied candidate first stages (e.g. the
        # LP-ranked leaders of the exhaustive 2^15 enumeration)
        for c in np.asarray(seed_cands, float):
            cands.append(c)
    # dedup on the integer signature
    seen, pool = set(), []
    for c in cands:
        key = tuple(np.round(c).astype(int))
        if key not in seen:
            seen.add(key)
            pool.append(c)
    evs = mip_mod.evaluate_mip_many(batch_inner, pool, eval_opts)
    inner, xhat_best = float("inf"), pool[0]
    for e in evs:
        if e["feasible"] and e["value"] < inner:
            inner, xhat_best = e["value"], e["xhat"]
    if verbose:
        print(f"[cert{n_scens}] pool inner {inner:.4f} "
              f"({time.time() - t_start:.0f}s)")

    # -- 5. local search ---------------------------------------------------
    ls = mip_mod.first_stage_local_search(batch_inner, xhat_best, inner,
                                          eval_opts, max_rounds=4,
                                          verbose=verbose)
    inner, xhat_best = ls["value"], ls["xhat"]
    if verbose:
        print(f"[cert{n_scens}] local-search inner {inner:.4f} "
              f"({time.time() - t_start:.0f}s)")

    # -- 5b. FINAL-candidate polish (round 5): multistart dives + LNS
    # close the per-scenario recourse assignment slack that plain B&B
    # incumbents leave on the pathological scenarios
    pol = mip_mod.evaluate_mip_polished(
        batch_inner, jnp.asarray(xhat_best), eval_opts,
        multistart=24, lns_rounds=40, verbose=verbose)
    if pol["feasible"] and pol["value"] < inner:
        inner = pol["value"]
    if verbose:
        print(f"[cert{n_scens}] polished inner {inner:.4f} "
              f"({time.time() - t_start:.0f}s)")

    def gap_of(i, o):
        return (i - o) / max(1.0, abs(i))

    # -- 6. integer-Lagrangian dual: bundle (round 5) with Polyak
    # fallback — the bundle's cutting-plane master reuses every oracle
    # evaluation instead of forgetting it, where the subgradient ascent
    # stalled ~6 units short (round 4)
    if ascent_steps > 0 and gap_of(inner, outer) > target_gap:
        target = inner - target_gap * max(1.0, abs(inner))
        asc = mip_mod.mip_dual_bundle(
            batch, W, inner, ascent_steps, lag_opts,
            target=target, verbose=verbose)
        if not np.isfinite(asc["bound"]):
            asc = mip_mod.mip_dual_ascent_polyak(
                batch, W, inner, ascent_steps, lag_opts,
                target=target, verbose=verbose)
        outer = max(outer, asc["bound"])
        W_best = asc["W"]
    else:
        W_best = W
    if verbose:
        print(f"[cert{n_scens}] after ascent: outer {outer:.4f} "
              f"gap {gap_of(inner, outer):.4f} "
              f"({time.time() - t_start:.0f}s)")

    # -- 7. decomposition B&B ----------------------------------------------
    if dd_nodes > 0 and gap_of(inner, outer) > target_gap:
        dd = mip_mod.decomposition_bnb(
            batch, W_best, lag_opts, max_nodes=dd_nodes,
            target_gap=target_gap, inner0=inner, xhat0=xhat_best,
            verbose=verbose)
        inner = min(inner, dd["inner"])
        outer = max(outer, dd["outer"])
        if dd["xhat"] is not None and dd["inner"] <= inner:
            xhat_best = dd["xhat"]

    return {
        "inner": float(inner),
        "outer": float(outer),
        "gap": float(gap_of(inner, outer)),
        "seconds": round(time.time() - t_start, 1),
        "trivial": float(trivial),
        "first_stage": np.asarray(xhat_best)[
            :len(np.asarray(batch.nonant_idx))].tolist(),
        # occupancy/recompile evidence for the artifact: how many
        # megabatches the certification actually dispatched, at what
        # occupancy, against how many compiled buckets
        "dispatch": _dispatch.scheduler_stats(),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default=None,
                    help="force a jax platform (e.g. cpu) through the "
                         "config API")
    ap.add_argument("--instances", default="5,10")
    ap.add_argument("--ascent", type=int, default=12)
    ap.add_argument("--dd-nodes", type=int, default=20)
    ap.add_argument("--target-gap", type=float, default=0.005)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default="SSLP_CERT.json")
    ap.add_argument("--seed-cands", default=None,
                    help="npy of (K, 15) candidate first stages to "
                         "seed the incumbent pool")
    args = ap.parse_args()
    if args.platform:
        import jax
        jax.config.update("jax_platforms", args.platform)
    if args.quick:
        args.ascent, args.dd_nodes = 3, 0
    seeds = None if args.seed_cands is None else np.load(args.seed_cands)
    results = {}
    for inst in args.instances.split(","):
        n = int(inst)
        results[f"sslp_15_45_{n}"] = certify(
            n, args.ascent, args.dd_nodes, args.target_gap,
            seed_cands=seeds)
        print(json.dumps({f"sslp_15_45_{n}": results[f"sslp_15_45_{n}"]}))
    with open(args.out, "w") as f:
        json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
