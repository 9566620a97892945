###############################################################################
# Exact integer optimization over the scenario batch.
#
# The reference certifies integer solutions by delegating every scenario
# subproblem to Gurobi/CPLEX (ref:mpisppy/spopt.py:99-247,884) and gets
# its MIP gap from the hub's outer/inner bound bookkeeping
# (ref:mpisppy/cylinders/hub.py:82-166).  This module is the TPU-native
# equivalent, built on ops/bnb.py's batched branch-and-bound:
#
#   * lagrangian_mip_bound — a certified OUTER bound for the true MIP:
#       L(W) = E_s[ min over the INTEGER feasible set of f_s + W.x_non ]
#     with E_node[W] = 0 (PH's invariant).  Each scenario's inner min is
#     its own MIP; the batched B&B advances all of them in lockstep and
#     its per-scenario outer bounds make E[outer_s] <= L(W) <= z_MIP
#     valid even when the round budget truncates the search.
#   * evaluate_mip — a certified INNER bound: fix an integral first
#     stage and solve every scenario's integer recourse exactly
#     (the reference's Xhat_Eval with MIP subproblems,
#     ref:mpisppy/utils/xhat_eval.py:254-340).
#   * ef_mip — branch-and-bound on the assembled extensive form (one
#     "scenario" of size S*n): the oracle that replaces handing
#     sputils.create_EF to Gurobi (ref:mpisppy/opt/ef.py:75-104).
#   * certified_mip_gap — the driver: LP-relaxed PH for (W, xbar),
#     candidate first stages from the xhat plane, then the two bounds
#     above; reports a TRUE MIP gap, which the LP-relax framework of
#     rounds 1-2 could not produce.
###############################################################################
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from mpisppy_tpu import dispatch as _dispatch
from mpisppy_tpu.core.batch import ScenarioBatch
from mpisppy_tpu.telemetry import console as _console
from mpisppy_tpu.ops import bnb, pdhg
from mpisppy_tpu.ops.bnb import BnBOptions

# Every solve_mip in this module goes through the dispatch scheduler
# (docs/dispatch.md): batch shapes are padded up the bucket ladder so
# the oracle loops below cannot recompile-storm the device, and
# concurrent callers (spokes, threaded drivers, the decomposition-B&B
# node fanout) coalesce into megabatch dispatches bounded by the
# in-flight cap.  Results match the direct ops.bnb path within
# certified-bound tolerances, and every bound keeps its certificate
# (see the padding contract in dispatch/buckets.py).
#
# Failure semantics (docs/dispatch.md): under a configured dispatch
# fault domain a quarantined solve raises dispatch.SolveFailed instead
# of hanging.  decomposition_bnb absorbs per-node failures (the parent
# bound stays a certified stand-in); the one-shot oracles
# (lagrangian_mip_bound, evaluate_mip*, ef_mip) propagate SolveFailed
# to their caller — a typed, catchable outcome, never a wedge.

Array = jnp.ndarray


def _aggregate_inner(per_scenario, feas_s, p):
    """(value, feasible): the all-real-scenarios-feasible gate and
    p-weighted expectation shared by evaluate_mip and its polished
    variant (one place for the padded-scenario and inf-sentinel
    rules)."""
    real = p > 0.0
    feas = bool(np.all(np.where(real, np.asarray(feas_s), True)))
    inner_s = np.asarray(per_scenario)
    value = float(np.sum(np.where(real, p * inner_s, 0.0))) if feas \
        else float("inf")
    return value, feas, inner_s


def _int_cols(batch: ScenarioBatch) -> np.ndarray:
    cols = np.nonzero(np.asarray(batch.integer_full))[0]
    if cols.size == 0:
        raise ValueError("problem has no integer columns; use the LP path")
    return cols.astype(np.int32)


def lagrangian_mip_bound(batch: ScenarioBatch, W: Array,
                         opts: BnBOptions = BnBOptions()) -> dict:
    """Certified MIP outer bound at multiplier W (valid when the
    per-node probability-weighted mean of W is 0, the PH invariant —
    ref:mpisppy/phbase.py:114-179).  Unlike algos/lagrangian.py this
    solves each scenario's Lagrangian subproblem AS A MIP, so the bound
    is the (stronger) Lagrangian dual of the integer problem — the bound
    the reference gets from exact Gurobi subproblem solves
    (ref:mpisppy/cylinders/lagrangian_bounder.py:21-44)."""
    zeros = jnp.zeros_like(W)
    qp = batch.with_nonant_linear_quad(W, zeros)
    res = _dispatch.solve_mip(qp, batch.d_col, _int_cols(batch), opts)
    p = np.asarray(batch.p)
    outer_s = np.asarray(res.outer)
    # padded scenarios (p=0) may carry -inf outers; mask before weighing
    bound = float(np.sum(np.where(p > 0.0, p * outer_s, 0.0)))
    return {
        "bound": bound,
        "per_scenario": outer_s,
        "solved": np.asarray(res.gap) <= opts.gap_tol,
        "result": res,
    }


def _polish_swap(opts: BnBOptions) -> BnBOptions:
    """Resolve swap_rounds for a polish context: 0 (auto) promotes to
    bnb.POLISH_SWAP_ROUNDS; an explicit caller value — positive (tuned
    budget) or negative (force off) — is honored verbatim."""
    if opts.swap_rounds != 0:
        return opts
    return dataclasses.replace(opts, swap_rounds=bnb.POLISH_SWAP_ROUNDS)


def evaluate_mip(batch: ScenarioBatch, xhat: Array,
                 opts: BnBOptions = BnBOptions()) -> dict:
    """Certified MIP inner bound: E[f(xhat)] with INTEGER recourse.

    xhat ((N,) root-only or (num_nodes, N)) is rounded on integer slots
    first; each scenario's recourse MIP is then solved by the batched
    B&B.  `value` is +inf unless every real scenario found an
    integer-feasible recourse (matching the reference's all-feasible
    gate, ref:mpisppy/utils/xhat_eval.py:254-340).

    Candidate evaluation is a POLISH context (the value becomes a
    published certified inner bound), so the dual-guided SOS1 swap
    repair is enabled here explicitly (bnb.POLISH_SWAP_ROUNDS) — the
    base options default it to 0 = auto to keep the hot Lagrangian-
    oracle loops (lagrangian_mip_bound, mip_dual_bundle) lean; an
    explicit caller value (positive or negative) is honored verbatim
    (see BnBOptions.swap_rounds)."""
    opts = _polish_swap(opts)
    xhat = jnp.asarray(xhat)
    xhat = jnp.where(batch.integer_slot, jnp.round(xhat), xhat)
    qp = batch.with_fixed_nonants(xhat)
    res = _dispatch.solve_mip(qp, batch.d_col, _int_cols(batch), opts)
    p = np.asarray(batch.p)
    real = p > 0.0
    value, feas, inner_s = _aggregate_inner(res.inner, res.feasible, p)
    # the recourse B&B's outer bounds bracket the true E[f(xhat)]
    lower = float(np.sum(np.where(real, p * np.asarray(res.outer), 0.0)))
    return {
        "value": value,
        "value_lower": lower,
        "per_scenario": inner_s,
        "feasible": feas,
        "xhat": np.asarray(xhat),
        "result": res,
    }


def evaluate_mip_polished(batch: ScenarioBatch, xhat: Array,
                          opts: BnBOptions = BnBOptions(),
                          multistart: int = 24, lns_rounds: int = 60,
                          base: dict | None = None,
                          verbose: bool = False) -> dict:
    """evaluate_mip plus the heavy per-scenario incumbent polish for
    FINAL-candidate certification: jitter-diversified multistart dives
    (ops/bnb.dive_multistart) merged with the B&B incumbents, then
    large-neighborhood repair (ops/bnb.lns_repair).  Measured on
    sslp_15_45_5 at the published-optimal first stage: plain B&B
    incumbents E=-257.6, +swap/LNS -259.4, diversified-LNS merge
    reaches the per-scenario optima on 4 of 5 scenarios (scipy-MILP
    ground truth -262.4).

    The swap repair rides the internal evaluate_mip (a polish context,
    see its docstring); multistart/LNS are this function's own adds."""
    # polish context: the dual-guided SOS1 swap repair is enabled
    # explicitly (bnb.POLISH_SWAP_ROUNDS) for this function's own bnb
    # calls too (dive_multistart/lns_repair), not just the internal
    # evaluate_mip — callers passing `base` would otherwise polish with
    # the lean swap_rounds=0 defaults
    opts = _polish_swap(opts)
    # callers holding a fresh evaluate_mip dict for the SAME xhat can
    # pass it as `base` and skip the (expensive) internal re-solve
    if base is None:
        base = evaluate_mip(batch, xhat, opts)
    res = base["result"]
    inc = jnp.asarray(res.inner)
    x_inc = jnp.asarray(res.x)
    feas_s = jnp.asarray(res.feasible)
    qp = batch.with_fixed_nonants(jnp.asarray(base["xhat"]))
    int_cols = jnp.asarray(_int_cols(batch))
    sos1 = bnb.detect_sos1_groups(qp, batch.d_col, int_cols)
    if multistart > 0:
        ms = bnb.dive_multistart(qp, batch.d_col, int_cols, opts,
                                 K=multistart, sos1=sos1)
        inc, x_inc, feas_s = bnb.merge_incumbents(inc, x_inc, feas_s,
                                                  *ms)
        if verbose:
            _console.log(f"[polish] multistart merge: {np.asarray(inc)}")
    if lns_rounds > 0:
        rep = bnb.lns_repair(qp, batch.d_col, int_cols, x_inc, inc,
                             feas_s, opts, rounds=lns_rounds,
                             destroy_frac=0.35, sos1=sos1,
                             verbose=verbose)
        if rep is not None:
            inc, x_inc, feas_s = bnb.merge_incumbents(inc, x_inc,
                                                      feas_s, *rep)
    value, feas, inner_s = _aggregate_inner(inc, feas_s,
                                            np.asarray(batch.p))
    out = dict(base)
    out.update({"value": value, "per_scenario": inner_s,
                "feasible": feas,
                # the POLISHED per-scenario solutions achieving
                # per_scenario/value (base["result"].x is pre-polish)
                "x": np.asarray(x_inc)})
    return out


def evaluate_mip_many(batch: ScenarioBatch, xhats,
                      opts: BnBOptions = BnBOptions()) -> list[dict]:
    """Certified MIP inner bounds for K candidate first stages in ONE
    batched B&B of K*S subproblems (the TPU answer to the reference's
    shuffle looper trying candidates sequentially across ranks,
    ref:mpisppy/cylinders/xhatshufflelooper_bounder.py:23-157).
    Returns one evaluate_mip-style dict per candidate.

    Like its siblings this is a POLISH context (the values become
    published certified inner bounds), so swap_rounds=0 (auto)
    promotes to bnb.POLISH_SWAP_ROUNDS — pass a negative swap_rounds
    to force the repair off for cheap candidate screening."""
    opts = _polish_swap(opts)
    K = len(xhats)
    if K == 0:
        return []
    S = batch.num_scenarios
    n = batch.qp.c.shape[-1]
    qps = []
    for xh in xhats:
        xh = jnp.asarray(xh)
        xh = jnp.where(batch.integer_slot, jnp.round(xh), xh)
        qps.append(batch.with_fixed_nonants(xh))

    def tileS(x, batched_ndim):
        if hasattr(x, "vals"):  # EllMatrix
            return dataclasses.replace(x, vals=tileS(x.vals, batched_ndim))
        if getattr(x, "ndim", 0) != batched_ndim:
            return x  # shared: broadcasts across the K*S batch
        return jnp.tile(x, (K,) + (1,) * (batched_ndim - 1))

    qp0 = batch.qp
    qp = dataclasses.replace(
        qp0,
        c=tileS(qp0.c, 2), q=tileS(qp0.q, 2), A=tileS(qp0.A, 3),
        bl=tileS(qp0.bl, 2), bu=tileS(qp0.bu, 2),
        l=jnp.concatenate([q.l for q in qps], axis=0),
        u=jnp.concatenate([q.u for q in qps], axis=0))
    d_col = tileS(batch.d_col, 2)
    res = _dispatch.solve_mip(qp, d_col, _int_cols(batch), opts)
    p = np.asarray(batch.p)
    real = p > 0.0
    feas_ks = np.asarray(res.feasible).reshape(K, S)
    inner_ks = np.asarray(res.inner).reshape(K, S)
    outer_ks = np.asarray(res.outer).reshape(K, S)
    out = []
    for k in range(K):
        feas = bool(np.all(np.where(real, feas_ks[k], True)))
        value = float(np.sum(np.where(real, p * inner_ks[k], 0.0))) \
            if feas else float("inf")
        out.append({
            "value": value,
            "value_lower": float(np.sum(np.where(real, p * outer_ks[k],
                                                 0.0))),
            "per_scenario": inner_ks[k],
            "feasible": feas,
            "xhat": np.asarray(
                jnp.where(batch.integer_slot, jnp.round(jnp.asarray(
                    xhats[k])), jnp.asarray(xhats[k]))),
        })
    return out


def first_stage_local_search(batch: ScenarioBatch, xhat0, inner0: float,
                             opts: BnBOptions = BnBOptions(),
                             max_rounds: int = 8,
                             verbose: bool = False) -> dict:
    """1-flip local search over the INTEGER first-stage slots, each
    round one batched evaluate_mip_many over all neighbors — the
    batched analog of slam/looper-style incumbent improvement, and the
    standard local-branching move for closing the inner side of a MIP
    bracket (no reference analog: Gurobi's heuristics play this role
    for the reference, ref:mpisppy/spopt.py:884)."""
    int_slots = np.nonzero(np.asarray(batch.integer_slot))[0]
    lb, ub = batch.nonant_box()
    best = np.asarray(xhat0, float).copy()
    best_val = float(inner0)
    for rnd in range(max_rounds):
        cands = []
        for j in int_slots:
            for v in (best[j] - 1.0, best[j] + 1.0):
                if lb[j] - 1e-6 <= v <= ub[j] + 1e-6:
                    c = best.copy()
                    c[j] = v
                    cands.append(c)
        evs = evaluate_mip_many(batch, cands, opts)
        vals = [e["value"] if e["feasible"] else float("inf") for e in evs]
        k = int(np.argmin(vals)) if vals else 0
        if not vals or vals[k] >= best_val - 1e-9:
            break
        best_val = vals[k]
        best = np.asarray(cands[k], float)
        if verbose:
            _console.log(f"[ls] round {rnd}: inner -> {best_val:.6g}",
                         level=_console.DEBUG)
    return {"xhat": best, "value": best_val}


def mip_dual_ascent_polyak(batch: ScenarioBatch, W, inner: float,
                           steps: int, opts: BnBOptions = BnBOptions(),
                           lam0: float = 1.0, target: float | None = None,
                           verbose: bool = False) -> dict:
    """Level-target subgradient ascent on the INTEGER Lagrangian dual:

        level_t = best_t + level_frac * (inner - best_t)
        step_t  = lam * max(level_t - L(W_t), 0) / ||g_t||_p^2,
        g_t     = x_t - xbar_t   (p-weighted node-mean-zero by
                                  construction, preserving the PH
                                  invariant that makes L(W) valid)

    with lam halved after two non-improving steps.  The raw Polyak rule
    (target = inner) overshoots badly when the duality-gap estimate is
    large (measured on sslp_15_45: step 1 dropped L by 12); aiming at a
    level strictly between the best bound and the incumbent is the
    standard stabilization (level-method style).  This is the classical
    dual-decomposition recipe (Caroe & Schultz) the reference's exact
    solvers make unnecessary (ref:mpisppy/cylinders/
    lagrangian_bounder.py gets L(W) from Gurobi's bestbound).  Each
    step is one batched scenario-MIP solve.  Stops early at `target`.
    Returns {'bound','W','history'}."""
    W = jnp.asarray(W)
    best, best_W = -float("inf"), W
    lam, since = float(lam0), 0
    level_frac = 0.3
    p = np.asarray(batch.p)
    hist = []
    for t in range(steps):
        lag = lagrangian_mip_bound(batch, W, opts)
        L = lag["bound"]
        hist.append(L)
        if verbose:
            _console.log(f"[polyak] step {t}: L = {L:.6g} (best {max(best, L):.6g}"
                  f", lam {lam:.3g})",
                         level=_console.DEBUG)
        if L > best:
            best, best_W = L, W
            since = 0
        else:
            since += 1
            if since >= 2:
                lam *= 0.5
                since = 0
        if target is not None and best >= target:
            break
        res = lag["result"]
        feas = np.asarray(res.feasible)
        if not bool(np.all(feas[p > 0.0])):
            break  # no integer point to take a subgradient from
        x_non = jnp.asarray(res.x)[:, batch.nonant_idx]
        xbar, _ = batch.node_average(x_non)
        g = x_non - xbar
        gnorm2 = float(jnp.sum(jnp.asarray(p)[:, None] * g * g))
        if gnorm2 <= 1e-12 or not np.isfinite(inner):
            break
        base = best if np.isfinite(best) else L
        level = base + level_frac * max(inner - base, 0.0)
        step = lam * max(level - L, 0.0) / gnorm2
        if step <= 0.0:
            break
        W = W + step * g
    return {"bound": best, "W": best_W, "history": hist}


def mip_dual_bundle(batch: ScenarioBatch, W, inner: float,
                    steps: int, opts: BnBOptions = BnBOptions(),
                    target: float | None = None,
                    trust0: float = 2.0,
                    verbose: bool = False) -> dict:
    """Trust-region BUNDLE method on the INTEGER Lagrangian dual — the
    upgrade over mip_dual_ascent_polyak's subgradient steps, which
    stall well short of the dual optimum (round 4: ~6 units above the
    sslp_15_45 optima after 12 steps).

    The dual D(W) = E_s[min_x f_s(x) + W_s'x_non] is concave; every
    oracle call at W_k returns
      * a CERTIFIED bound E_s[outer_s] (per-scenario B&B lower bounds,
        valid at any truncation — this is what gets REPORTED), and
      * a cut D(V) <= E_s[f_s(x_k,s) + V_s'x_non,k,s] from the
        per-scenario incumbents x_k (min <= value at any feasible
        point, so the cut is valid even when B&B is truncated).
    The master maximizes the cutting-plane model over the PH-invariant
    subspace (p-weighted node-mean of W = 0, which keeps D a valid
    bound) inside an inf-norm trust region around the best W; it is a
    ~(S*N)-variable LP solved on the host with scipy/HiGHS — a pure
    direction-finder: ANY W it proposes yields a certified bound from
    the oracle, so master quality never affects validity.

    Serious steps (realized improvement) expand the trust region; null
    steps shrink it.  Two-stage trees only (the mean-zero restriction
    is applied per ROOT slot).  Returns {'bound','W','history'}."""
    from scipy.optimize import linprog

    if batch.tree.num_stages != 2:
        raise ValueError("mip_dual_bundle: two-stage batches only")
    W = np.asarray(jnp.asarray(W), np.float64)
    p = np.asarray(batch.p, np.float64)
    real = p > 0.0
    S, N = W.shape
    nv = S * N
    cuts_a, cuts_b = [], []     # cut k: D(V) <= b_k + a_k . V
    best, best_W = -np.inf, W.copy()
    trust = float(trust0)
    hist = []
    center = W.copy()
    for t in range(steps):
        lag = lagrangian_mip_bound(batch, jnp.asarray(center + 0.0),
                                   opts) if t == 0 else \
            lagrangian_mip_bound(batch, jnp.asarray(W_try), opts)
        Wk = center if t == 0 else W_try
        L = lag["bound"]
        hist.append(L)
        # plain > when best is still -inf (the relative-eps form is
        # NaN-poisoned at -inf: -inf + inf = nan, and L > nan is False
        # forever)
        serious = (L > best if not np.isfinite(best)
                   else L > best + 1e-9 * max(1.0, abs(best)))
        if serious:
            best, best_W = L, Wk.copy()
            center = Wk.copy()
            trust = min(trust * 1.6, 1e4)
        else:
            trust = max(trust * 0.5, 1e-5)
        if verbose:
            _console.log(f"[bundle] step {t}: L={L:.6g} best={best:.6g} "
                  f"trust={trust:.3g}",
                         level=_console.DEBUG)
        if target is not None and best >= target:
            break
        res = lag["result"]
        feas = np.asarray(res.feasible)
        if bool(np.all(feas[real])):
            x_non = np.asarray(res.x)[:, np.asarray(batch.nonant_idx)]
            # res.inner is the LAGRANGIAN objective f_s(x_k)+W_k.x_non
            # (the oracle folds W into c via with_nonant_linear_quad);
            # the cut needs the RAW f_s(x_k), so subtract the penalty
            # evaluated at the incumbent
            wdot = np.sum(np.asarray(Wk) * x_non, axis=-1)
            fvals = np.asarray(res.inner) - wdot
            # cut: D(V) <= sum_s p_s f_s(x_k) + sum_s p_s V_s.x_non
            a = (p[:, None] * x_non).reshape(nv)
            b = float(np.sum(np.where(real, p * fvals, 0.0)))
            cuts_a.append(a)
            cuts_b.append(b)
        if not cuts_a:
            break
        # master LP: max t  s.t. t <= b_k + a_k.V, mean-zero, trust box
        nc = len(cuts_a)
        # vars: [V (nv), t (1)]
        c_lp = np.zeros(nv + 1)
        c_lp[-1] = -1.0                      # maximize t
        A_ub = np.zeros((nc, nv + 1))
        b_ub = np.zeros(nc)
        for k in range(nc):
            A_ub[k, :nv] = -cuts_a[k]
            A_ub[k, -1] = 1.0
            b_ub[k] = cuts_b[k]
        A_eq = np.zeros((N, nv + 1))
        for j in range(N):
            for s in range(S):
                A_eq[j, s * N + j] = p[s]
        b_eq = np.zeros(N)
        lb = np.concatenate([(center - trust).reshape(nv), [-np.inf]])
        ub = np.concatenate([(center + trust).reshape(nv), [np.inf]])
        sol = linprog(c_lp, A_ub=A_ub, b_ub=b_ub, A_eq=A_eq, b_eq=b_eq,
                      bounds=np.stack([lb, ub], axis=1),
                      method="highs")
        if not sol.success:
            if verbose:
                _console.log(f"[bundle] master failed: {sol.message}")
            break
        W_try = sol.x[:nv].reshape(S, N)
        model_val = -sol.fun
        # model agrees with reality -> the dual is (locally) maxed out
        if np.isfinite(best) \
                and model_val <= best + 1e-7 * max(1.0, abs(best)) \
                and trust <= 1e-4:
            break
    return {"bound": best, "W": best_W, "history": hist}


def ef_mip(ef_problem, specs, opts: BnBOptions = BnBOptions(),
           verbose: bool = False) -> dict:
    """Exact MIP solve of an assembled extensive form (algos/ef.py
    EFProblem) — the correctness oracle for the decomposition bounds
    (ref:mpisppy/opt/ef.py:75-104's role).  Returns inner/outer/gap and
    the (S, n) per-scenario solution in original space.

    A one-shot oracle is a POLISH context (not a hot Lagrangian loop),
    so swap_rounds=0 (auto) promotes to bnb.POLISH_SWAP_ROUNDS here
    like the other final-candidate entry points."""
    opts = _polish_swap(opts)
    qp = ef_problem.qp
    n_tot = qp.c.shape[-1]
    n = ef_problem.n_per_scen
    S = len(specs)
    integer = np.zeros(n_tot, bool)
    for s, sp in enumerate(specs):
        if sp.integer is not None:
            integer[s * n:(s + 1) * n] = np.asarray(sp.integer, bool)
    cols = np.nonzero(integer)[0].astype(np.int32)
    qp1 = dataclasses.replace(
        qp, c=qp.c[None], q=qp.q[None], bl=qp.bl[None], bu=qp.bu[None],
        l=qp.l[None], u=qp.u[None])   # batch of one; A broadcasts
    d_col = jnp.asarray(ef_problem.scaling.d_col, qp.c.dtype)[None]
    res = _dispatch.solve_mip(qp1, d_col, cols, opts, verbose=verbose)
    x = np.asarray(res.x)[0].reshape(S, n)
    return {
        "inner": float(res.inner[0]),
        "outer": float(res.outer[0]),
        "gap": float(res.gap[0]),
        "x": x,
        "nodes": int(res.nodes_solved[0]),
        "result": res,
    }


def mip_dual_ascent(batch: ScenarioBatch, W: Array, rho: Array,
                    steps: int, opts: BnBOptions = BnBOptions()) -> dict:
    """Subgradient ascent on the MIP Lagrangian dual: each step solves
    the scenario MIPs at W (batched B&B), records the certified bound,
    and updates W += rho (x - xbar) from the INTEGER solutions — the
    exact-subproblem analog of the subgradient spoke
    (ref:mpisppy/cylinders/subgradient_bounder.py:12-54).  Returns the
    best certified bound and the W that achieved it."""
    best = -float("inf")
    best_W = W
    rho = jnp.asarray(rho)
    for _ in range(steps):
        lag = lagrangian_mip_bound(batch, W, opts)
        if lag["bound"] > best:
            best, best_W = lag["bound"], W
        res = lag["result"]
        feas = np.asarray(res.feasible)
        if not bool(np.all(feas[np.asarray(batch.p) > 0.0])):
            break  # no integer solution to take a subgradient from
        # res.x is already ORIGINAL space: gather the nonant columns
        x_non = jnp.asarray(res.x)[:, batch.nonant_idx]
        xbar, _ = batch.node_average(x_non)
        W = W + rho * (x_non - xbar)
    lag = lagrangian_mip_bound(batch, W, opts)
    if lag["bound"] > best:
        best, best_W = lag["bound"], W
    return {"bound": best, "W": best_W}


def _restrict_first_stage(batch: ScenarioBatch, qp, int_slots: np.ndarray,
                          lo: np.ndarray, hi: np.ndarray):
    """qp with the integer NONANT slots' box intersected with the
    ORIGINAL-space node box [lo, hi] (first-stage branching)."""
    S = batch.num_scenarios
    n = qp.c.shape[-1]
    l_full = jnp.broadcast_to(qp.l, (S, n))
    u_full = jnp.broadcast_to(qp.u, (S, n))
    cols = np.asarray(batch.nonant_idx)[int_slots]
    d = jnp.broadcast_to(batch.d_non, (S, batch.num_nonants))[:, int_slots]
    l_new = l_full.at[:, cols].max(jnp.asarray(lo, qp.c.dtype) / d)
    u_new = u_full.at[:, cols].min(jnp.asarray(hi, qp.c.dtype) / d)
    return dataclasses.replace(qp, l=l_new, u=u_new)


def decomposition_bnb(batch: ScenarioBatch, W,
                      opts: BnBOptions = BnBOptions(),
                      max_nodes: int = 30,
                      target_gap: float = 5e-3,
                      inner0: float = float("inf"),
                      xhat0=None,
                      node_fanout: int = 4,
                      verbose: bool = False) -> dict:
    """Branch-and-bound on the FIRST-STAGE integer nonants with
    scenario-decomposed bounds — the dual-decomposition B&B (ddsip /
    PIPS-SBB family) that closes duality gaps the root Lagrangian bound
    cannot.  This capability has no single reference call site: the
    reference outsources node solves to Gurobi on the EF or accepts the
    hub's root gap (ref:mpisppy/cylinders/hub.py:82-166); here every
    node's bound is itself a batched scenario-MIP solve (ops/bnb.py)
    and nodes are explored best-first on the host.

      node = a box on the integer first-stage slots (original space)
      bound(node) = E_s[ B&B outer bound of min f_s + W.x_non
                         s.t. x_non in node box ]   (valid: E[W] = 0)
      incumbent(node) = evaluate_mip at the node solution's rounded
                        probability-weighted mean, clipped into the box

    Node solves are COALESCED: up to `node_fanout` best-first nodes pop
    per round and their (fanout * S)-lane bound solves ride ONE
    megabatch dispatch through the scheduler (docs/dispatch.md) — the
    small-batch per-node dispatch storm was exactly what wedged the
    sslp_15_45 re-certification runs (round-5 verdict).  Fanning out
    only changes the SEARCH ORDER (standard parallel B&B: siblings
    solved before the best child's bound can prune them — at worst
    node_fanout-1 extra node solves per incumbent improvement); every
    bound remains certified and the bracket semantics are unchanged.

    Returns {'inner','outer','gap','xhat','nodes'}."""
    import heapq

    int_slots = np.nonzero(np.asarray(batch.integer_slot))[0]
    if int_slots.size == 0:
        raise ValueError("no integer first-stage slots to branch on")
    lb_all, ub_all = batch.nonant_box()
    lo0 = np.ceil(lb_all[int_slots] - 1e-6)
    hi0 = np.floor(ub_all[int_slots] + 1e-6)

    zeros = jnp.zeros_like(W)
    qp_W = batch.with_nonant_linear_quad(W, zeros)
    int_cols = _int_cols(batch)
    p = np.asarray(batch.p)
    real = p > 0.0

    inner = float(inner0)
    xhat_best = None if xhat0 is None else np.asarray(xhat0)
    fathom_floor = float("inf")
    tried: set[tuple] = set()
    heap: list = []
    counter = 0
    heapq.heappush(heap, (-np.inf, counter, lo0, hi0))
    nodes = 0
    failed_nodes = 0

    def scale(v):
        return max(1.0, abs(v)) if np.isfinite(v) else 1.0

    sched = _dispatch.get_scheduler()
    fanout = max(1, int(node_fanout))
    while heap and nodes < max_nodes:
        # pop up to `fanout` surviving best-first nodes and submit them
        # together: the scheduler coalesces the same-key submits into
        # ONE (popped * S)-lane megabatch dispatch (see docstring)
        popped = []
        while heap and len(popped) < fanout \
                and nodes + len(popped) < max_nodes:
            node_bound, _, lo, hi = heapq.heappop(heap)
            if np.isfinite(inner) \
                    and node_bound >= inner - target_gap * scale(inner):
                fathom_floor = min(fathom_floor, node_bound)
                continue
            popped.append((node_bound, lo, hi))
        if not popped:
            break
        # build every node qp BEFORE submitting: the submits then land
        # back-to-back inside one admission window instead of racing
        # the max-wait timer against qp construction
        qp_nodes = [_restrict_first_stage(batch, qp_W, int_slots, lo, hi)
                    for _, lo, hi in popped]
        tickets = [sched.submit(qpn, batch.d_col, int_cols, opts)
                   for qpn in qp_nodes]
        for (node_bound, lo, hi), ticket in zip(popped, tickets):
            try:
                res = ticket.result()
            except _dispatch.SolveFailed as e:
                # quarantined node solve (docs/dispatch.md failure
                # semantics): the node's PARENT bound is still a valid
                # lower bound on everything under it, so folding it
                # into the fathom floor keeps the reported outer bound
                # certified — the node is abandoned (never re-queued:
                # a poison node would loop forever), accounted, and the
                # healthy nodes proceed
                nodes += 1
                failed_nodes += 1
                fathom_floor = min(fathom_floor, node_bound)
                _console.log(f"[ddbnb] node solve quarantined "
                             f"({e.reason}): holding parent bound "
                             f"{node_bound:.6g}", level=_console.DEBUG)
                continue
            nodes += 1
            outer_s = np.asarray(res.outer)
            nb = float(np.sum(np.where(real, p * outer_s, 0.0)))
            nb = max(nb, node_bound)  # parent bound still valid

            feas_s = np.asarray(res.feasible)
            if bool(np.all(feas_s[real])):
                x_non = np.asarray(res.x)[:, np.asarray(batch.nonant_idx)]
                xbar = (p[:, None] * x_non).sum(0)
                cand = xbar.copy()
                cand[int_slots] = np.clip(np.round(xbar[int_slots]),
                                          lo, hi)
                key = tuple(np.round(cand[int_slots]).astype(int))
                if key not in tried:
                    tried.add(key)
                    try:
                        ev = evaluate_mip(batch,
                                          jnp.asarray(cand, np.float32),
                                          opts)
                    except _dispatch.SolveFailed as e:
                        # the incumbent candidate eval is optional work:
                        # a quarantined eval costs one candidate, never
                        # the run (the search keeps its bracket)
                        _console.log(f"[ddbnb] incumbent eval "
                                     f"quarantined ({e.reason}); "
                                     f"skipping candidate",
                                     level=_console.DEBUG)
                        ev = None
                    if ev is not None and ev["feasible"] \
                            and ev["value"] < inner:
                        inner, xhat_best = ev["value"], ev["xhat"]
                spread = (p[:, None] * np.abs(
                    x_non - xbar[None, :])).sum(0)[int_slots]
            else:
                # no integer solution in some scenario: branch on width
                spread = (hi - lo).astype(float)

            if np.isfinite(inner) \
                    and nb >= inner - target_gap * scale(inner):
                fathom_floor = min(fathom_floor, nb)
                if verbose:
                    _console.log(f"[ddbnb] node {nodes}: fathomed at "
                                 f"{nb:.6g} (inner {inner:.6g})",
                                 level=_console.DEBUG)
                continue
            branchable = hi > lo
            if not bool(np.any(branchable)):
                fathom_floor = min(fathom_floor, nb)  # leaf: exact-ish
                continue
            j = int(np.argmax(np.where(branchable, spread, -1.0)))
            if bool(np.all(feas_s[real])):
                split = float(np.clip(
                    np.floor((p[:, None] * x_non).sum(0)[int_slots][j]),
                    lo[j], hi[j] - 1))
            else:
                split = float(np.floor(0.5 * (lo[j] + hi[j])))
            lo_up = lo.copy()
            hi_dn = hi.copy()
            hi_dn[j] = split
            lo_up[j] = split + 1.0
            counter += 1
            heapq.heappush(heap, (nb, counter, lo, hi_dn))
            counter += 1
            heapq.heappush(heap, (nb, counter, lo_up, hi))
            if verbose:
                _console.log(f"[ddbnb] node {nodes}: bound {nb:.6g} "
                             f"inner {inner:.6g} branch slot "
                             f"{int_slots[j]} at {split}",
                             level=_console.DEBUG)

    open_min = min((b for b, *_ in heap), default=float("inf"))
    outer = min(open_min, fathom_floor, inner)
    gap = (inner - outer) / scale(inner) if np.isfinite(inner) else float("inf")
    return {"inner": inner, "outer": outer, "gap": gap,
            "xhat": xhat_best, "nodes": nodes,
            "failed_nodes": failed_nodes}


@dataclasses.dataclass
class MIPGapResult:
    inner: float          # certified upper bound (integer-feasible)
    outer: float          # certified lower bound
    gap: float            # (inner - outer) / max(1, |inner|)
    xhat: np.ndarray      # the first stage achieving `inner`
    trivial_bound: float  # LP wait-and-see bound from PH iter0
    ph_conv: float


def certified_mip_gap(batch: ScenarioBatch, ph_options=None,
                      opts: BnBOptions = BnBOptions(),
                      ascent_steps: int = 0,
                      n_shuffle: int = 2,
                      target_gap: float = 5e-3,
                      dd_nodes: int = 30,
                      verbose: bool = False) -> MIPGapResult:
    """End-to-end certified MIP gap for a two-stage integer problem:

      1. LP-relaxed PH for converged (W, xbar) — the hot TPU loop;
      2. candidate first stages (rounded xbar, slam-max/min, a few
         scenario vectors), ranked by cheap LP-recourse evaluation;
      3. the best candidate MIP-evaluated (certified inner bound);
      4. Lagrangian MIP bound at W (+ optional dual ascent steps);
      5. if the root gap still exceeds `target_gap`: first-stage
         branch-and-bound over the decomposition (decomposition_bnb)
         until the certified gap closes or `dd_nodes` runs out.

    This is the pipeline the reference runs as hub + xhatshuffle +
    Lagrangian spokes with exact MIP subproblem solves
    (ref:mpisppy/generic_cylinders.py:109-312), collapsed into batched
    tensor programs — plus the node search the reference leaves to the
    EF solver."""
    from mpisppy_tpu.algos import ph as ph_mod
    from mpisppy_tpu.algos import xhat as xhat_mod

    ph_options = ph_options or ph_mod.PHOptions(max_iterations=50)
    driver = ph_mod.PH(ph_options, batch)
    conv, _, trivial = driver.ph_main()
    st = driver.state

    # -- candidates --------------------------------------------------------
    x_non = batch.nonants(st.solver.x)
    cands = [xhat_mod.round_integers(batch, st.xbar_nodes[0])]
    cands.append(xhat_mod.slam_candidate(batch, x_non, sense_max=True))
    cands.append(xhat_mod.slam_candidate(batch, x_non, sense_max=False))
    S = batch.num_real
    for s in range(min(n_shuffle, S)):
        cands.append(xhat_mod.round_integers(batch, x_non[s]))
    # wait-and-see INTEGER candidates: a few scenarios' own exact-MIP
    # first stages (one cheap batched B&B on a SLICE of the plain
    # batch).  At a converged PH the shuffle candidates above all equal
    # the consensus point, whose integer-recourse value can be far off —
    # the WS solutions are the diverse, integral pool the reference's
    # shuffle looper effectively draws from (it solves subproblems as
    # MIPs).
    k_ws = min(S, 8)

    def _head(x, batched_ndim):
        if hasattr(x, "vals"):  # EllMatrix
            return dataclasses.replace(x, vals=_head(x.vals, batched_ndim))
        return x[:k_ws] if getattr(x, "ndim", 0) == batched_ndim else x

    qp_ws = dataclasses.replace(
        batch.qp, c=batch.qp.c[:k_ws], q=batch.qp.q[:k_ws],
        A=_head(batch.qp.A, 3),
        bl=_head(batch.qp.bl, 2), bu=_head(batch.qp.bu, 2),
        l=_head(batch.qp.l, 2), u=_head(batch.qp.u, 2))
    ws = _dispatch.solve_mip(qp_ws, _head(batch.d_col, 2), _int_cols(batch),
                       opts)
    ws_x = np.asarray(ws.x)[:, np.asarray(batch.nonant_idx)]
    ws_feas = np.asarray(ws.feasible)
    int_slot = np.asarray(batch.integer_slot)
    seen_keys = set()
    for s in range(k_ws):
        if not ws_feas[s]:
            continue
        # round only the INTEGER slots; continuous first-stage
        # coordinates keep the scenario's exact values
        cand = np.where(int_slot, np.round(ws_x[s]), ws_x[s])
        key = tuple(np.round(cand[int_slot]).astype(int))
        if key in seen_keys:
            continue
        seen_keys.add(key)
        cands.append(jnp.asarray(cand, batch.qp.c.dtype))
    lp_vals = [float(xhat_mod.evaluate(batch, c, opts.lp).value)
               for c in cands]
    order = np.argsort(lp_vals)

    # -- certified inner: MIP-evaluate candidates in LP rank order; try
    #    a few past the first success (LP rank is a good but imperfect
    #    predictor of the integer-recourse value) -----------------------
    inner, xhat_best = float("inf"), np.asarray(cands[int(order[0])])
    n_eval = 0
    for i in order:
        ev = evaluate_mip(batch, cands[int(i)], opts)
        n_eval += 1
        if ev["feasible"] and ev["value"] < inner:
            inner, xhat_best = ev["value"], ev["xhat"]
        if np.isfinite(inner) and n_eval >= 3:
            break

    # -- certified outer ---------------------------------------------------
    if ascent_steps > 0:
        asc = mip_dual_ascent(batch, st.W, st.rho, ascent_steps, opts)
        outer, W_best = asc["bound"], asc["W"]
    else:
        outer = lagrangian_mip_bound(batch, st.W, opts)["bound"]
        W_best = st.W

    gap = (inner - outer) / max(1.0, abs(inner))

    # -- close the duality gap with first-stage branching ------------------
    if gap > target_gap and dd_nodes > 0 \
            and bool(np.any(np.asarray(batch.integer_slot))):
        dd = decomposition_bnb(batch, W_best, opts, max_nodes=dd_nodes,
                               target_gap=target_gap, inner0=inner,
                               xhat0=xhat_best, verbose=verbose)
        inner = min(inner, dd["inner"])
        outer = max(outer, dd["outer"])
        if dd["xhat"] is not None and dd["inner"] <= inner:
            xhat_best = dd["xhat"]
        gap = (inner - outer) / max(1.0, abs(inner))

    return MIPGapResult(inner=inner, outer=outer, gap=gap, xhat=xhat_best,
                        trivial_bound=trivial, ph_conv=conv)
