###############################################################################
# Fused hub-and-spoke wheel step.
#
# The reference runs hub and spokes CONCURRENTLY on disjoint MPI ranks
# (ref:mpisppy/spin_the_wheel.py:224-242 _make_comms;
# ref:mpisppy/cylinders/hub.py:379-445 RMA windows), so spoke wall-clock
# is nearly free.  On one TPU chip every cylinder shares a single device
# queue — separate dispatches SERIALIZE, and a to-convergence Lagrangian
# or xhat solve per sync costs hundreds of times the hub iteration it
# decorates (measured 642x in round 3, BENCH_DETAIL.json).
#
# The TPU-native answer is fusion, not concurrency: the Lagrangian bound
# is the SAME subproblem kernel with W frozen and no prox, and the xhat
# recourse evaluation is the SAME kernel with the nonant box collapsed —
# so both ride inside the hub's single jitted step as fixed small
# restart-window budgets with WARM state carried across iterations.
# Per-iteration device cost becomes
#     (subproblem_windows + lag_windows + xhat_windows) restart windows
# ~ 2-3x bare PH, while the warm states converge across iterations just
# like the reference's continuously-running spoke processes.  Bounds are
# still gated by the same certificates as the standalone spokes
# (dual-residual for the Lagrangian, primal-residual feasibility for
# xhat), so nothing uncertified ever enters the gap.
###############################################################################
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from mpisppy_tpu.algos import aph as aph_mod
from mpisppy_tpu.algos import lagrangian as lag_mod
from mpisppy_tpu.algos import ph as ph_mod
from mpisppy_tpu.algos import xhat as xhat_mod
from mpisppy_tpu.core.batch import ScenarioBatch, concretize
from mpisppy_tpu.ops import boxqp, pdhg
from mpisppy_tpu.parallel.mesh import shard_like_batch
from mpisppy_tpu.telemetry import profiler as _prof

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class FusedWheelOptions:
    """Static per-iteration budgets for the fused spoke plane.

    A window is `restart_period` PDHG iterations; the defaults add
    ~2x the hub's own subproblem work per iteration.  The xhat profile
    uses omega0=0.1 / restart_period=80: the stalled-tail cure measured
    in round 3 (algos/xhat._RESCUE_TIERS) applied from the start, so the
    in-loop evaluation rarely needs a blocking rescue."""

    lag_windows: int = 8
    xhat_windows: int = 4
    slam_windows: int = 0        # 0 = slam plane disabled
    slam_sense_max: bool = True  # ref slam_heuristic max/min variants
    shuffle_windows: int = 0     # 0 = shuffle plane disabled
    # run the spoke planes only every spoke_period-th iteration (two
    # compiled variants, host-alternated) — the fused analog of the
    # hub's spoke_sync_period: bound freshness lags at most
    # spoke_period iterations, per-iteration cost amortizes by 1/p
    spoke_period: int = 1
    # Dispatch each plane as its OWN async device program instead of
    # one monolithic jit.  Measured on v5e at S=10k: the monolithic
    # 4-plane program costs +428 ms/iter over bare PH while the same
    # planes as separate dispatches cost +198 ms — XLA interleaves the
    # data-independent window loops and they evict each other's
    # VMEM-resident state, and async dispatch already hides the
    # per-dispatch latency.  Split mode is also what makes per-plane adaptive
    # budgets cheap (one small recompile per plane/budget pair).
    # None = AUTO: split at >=512 scenarios; below that per-dispatch
    # overhead dominates device time and the monolithic program wins
    # (uc at S=100: 0.33 s/iter monolithic vs 0.72 s/iter split,
    # measured).  True/False forces.
    split_dispatch: bool | None = None
    # Adaptive budgets (split mode only): a plane runs its full budget
    # until it has CERTIFIED (dual-residual / feasibility gate) for
    # `adapt_stall` consecutive exchanges — its warm solver is then
    # tracking its slowly moving target and the lean budget keeps it
    # certified; any uncertified exchange snaps it back to full.  The
    # certificates are identical either way — budgets only change how
    # fast the warm solver tracks, never what gets certified.
    adapt_budgets: bool = True
    # The Lagrangian plane does NOT lean by default: the outer bound's
    # QUALITY (not just its certificate) gates termination, and on
    # models with fast-moving duals (uc at rho=1000) a lean budget
    # tracks well enough to certify while the bound value lags —
    # measured: uc stalled at 2.5% with lag leaning vs 1.0% certified
    # without, while sslp's headline was unaffected by full lag
    # budgets.  Inner/heuristic planes keep leaning (their freshness
    # only delays incumbent discovery, never weakens a published bound).
    adapt_lag_budget: bool = False
    lean_lag_windows: int = 2
    lean_xhat_windows: int = 1
    lean_slam_windows: int = 1
    lean_shuffle_windows: int = 1
    adapt_stall: int = 3
    # Candidate FREEZING for the x̄ plane (split mode): the evaluated
    # candidate stays frozen across exchanges until it lands (publishes
    # feasible) or xhat_give_up exchanges pass, and only then does the
    # plane adopt a fresh round(x̄).  Without this the candidate churns
    # every exchange and the straggler scenarios' recourse solves never
    # accumulate enough iterations to clear the all-scenario feasibility
    # gate — measured on sslp-10k: 0/90 exchanges published and the
    # 80-second blocking rescue did all the inner-bound work.
    xhat_give_up: int = 25
    # In-loop STRAGGLER TAIL sub-solve: after the main fixed-budget
    # pass, gather the xhat_tail_k worst-primal-residual scenarios into
    # a tiny sub-batch and run them xhat_tail_windows windows at the
    # tier-2 rescue profile (omega0=0.03, restart_period=160), then
    # scatter the state back.  ~0.1-0.3% of sslp recourse LPs are
    # degenerate and need O(100k) PDHG iterations (measured r3/r5) —
    # on the full 10k batch that was only reachable by an 80-second
    # blocking rescue, but on a 64-scenario gather it costs ~1% of a
    # hub step per exchange and accumulates across exchanges on the
    # frozen candidate.  0 disables.
    xhat_tail_k: int = 64
    xhat_tail_windows: int = 12
    lag_pdhg: pdhg.PDHGOptions = pdhg.PDHGOptions(
        tol=1e-6, restart_period=40)
    xhat_pdhg: pdhg.PDHGOptions = pdhg.PDHGOptions(
        tol=1e-6, omega0=0.1, restart_period=80)
    xhat_feas_tol: float = 1e-3
    # max first-order infeasibility compensation (relative to the
    # value) a published inner bound may carry — see _eval_step
    xhat_comp_tol: float = 2e-3


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["ph", "lag_solver", "lag_bound", "lag_certified",
                 "xhat_solver", "xhat_cand", "xhat_value", "xhat_feasible",
                 "xhat_dead",
                 "slam_solver", "slam_cand", "slam_value", "slam_feasible",
                 "shuf_solver", "shuf_cand", "shuf_value", "shuf_feasible",
                 "scalars"],
    meta_fields=[],
)
@dataclasses.dataclass(frozen=True)
class FusedWheelState:
    ph: ph_mod.PHState
    lag_solver: pdhg.PDHGState   # warm iterates for L(W)
    lag_bound: Array             # () latest E[dual] at W
    lag_certified: Array         # () bool: dual residuals cleared tol
    xhat_solver: pdhg.PDHGState  # warm iterates for the recourse eval
    xhat_cand: Array             # (num_nodes, N) candidate evaluated
    xhat_value: Array            # () E[f(xhat)]; +inf unless feasible
    xhat_feasible: Array         # () bool
    xhat_dead: Array             # () bool: some scenario CERTIFIED
    #                              infeasible/unbounded at this candidate
    slam_solver: pdhg.PDHGState  # warm iterates for the slam candidate
    slam_cand: Array             # (N,) slammed candidate
    slam_value: Array            # ()
    slam_feasible: Array         # () bool
    shuf_solver: pdhg.PDHGState  # warm iterates for the shuffle candidate
    shuf_cand: Array             # (N,) candidate (one scenario's nonants)
    shuf_value: Array            # ()
    shuf_feasible: Array         # () bool
    # (10,) f32 — see SCALAR_KEYS for the layout: every per-iteration
    # host decision packed into ONE device array so the hub pays ONE
    # device->host transfer per iteration (each scalar read is a full
    # device round trip — ~10 reads/iter measurably dominated
    # wall-clock at small scale)
    scalars: Array


def _lag_step(batch: ScenarioBatch, W: Array, solver: pdhg.PDHGState,
              wopts: FusedWheelOptions, windows: int | None = None):
    """Advance the Lagrangian solve a fixed budget and certify the bound
    (same math as algos.lagrangian.lagrangian_bound, truncated)."""
    qp = lag_mod._lagrangian_qp(batch, W)
    n_win = wopts.lag_windows if windows is None else windows
    st = pdhg.solve_fixed(qp, n_win, wopts.lag_pdhg, solver)
    dual = boxqp.dual_objective(qp, st.x, st.y)
    _, rd, _ = boxqp.kkt_residuals(qp, st.x, st.y)
    tol = jnp.maximum(wopts.lag_pdhg.tol,
                      5.0 * jnp.finfo(st.x.dtype).eps)
    real = batch.p > 0.0
    certified = jnp.all(jnp.where(real, rd <= 10.0 * tol, True))
    return st, batch.expectation(dual), certified


def _gather_scen(tree, idx, S: int):
    """Index the leading scenario axis of every (S, ...)-shaped leaf.
    Safe for PDHGState (every array field is (S, ...) or a () scalar by
    construction); do NOT use on a BoxQP — see _gather_qp."""
    return jax.tree_util.tree_map(
        lambda a: a[idx] if (getattr(a, "ndim", 0) > 0
                             and a.shape[0] == S) else a, tree)


def _gather_qp(qp, idx, S: int):
    """Scenario-gather a BoxQP by FIELD LAYOUT, not dim-size guessing:
    a shared dense A is (m, n), and a model with m == S would trip a
    naive shape[0]-equals-S test into gathering the matrix by scenario
    index (wrong contraction downstream).  The same rule holds inside
    an EllMatrix: only a batched vals (S, m, k) is gathered — cols is
    a shared (m, k) index array whose leading dim is m, never a
    scenario axis (a tree_map over S-sized leading dims would silently
    corrupt it whenever m == S)."""
    def vec(a):       # c/q/l/u: (S, n) batched or (n,) shared
        return a[idx] if a.ndim == 2 else a

    A = qp.A
    if hasattr(A, "vals"):        # EllMatrix: gather by field layout
        if A.vals.ndim == 3:      # batched vals (S, m, k)
            A = dataclasses.replace(A, vals=A.vals[idx])
        # shared vals (m, k): keep; cols is NEVER scenario-indexed
    elif A.ndim == 3:             # per-scenario dense (S, m, n)
        A = A[idx]
    # else shared dense (m, n): keep
    return dataclasses.replace(
        qp, c=vec(qp.c), q=vec(qp.q), l=vec(qp.l), u=vec(qp.u),
        bl=vec(qp.bl), bu=vec(qp.bu), A=A)


def _scatter_scen(tree, sub, idx, S: int):
    """Write a gathered sub-tree back into the (S, ...) leaves."""
    return jax.tree_util.tree_map(
        lambda a, b: (a.at[idx].set(b)
                      if (getattr(a, "ndim", 0) > 0 and a.shape[0] == S)
                      else a), tree, sub)


def _tail_rescue(qp, st: pdhg.PDHGState, rp: Array, real: Array,
                 wopts: FusedWheelOptions,
                 feas_tol: float) -> pdhg.PDHGState:
    """In-loop straggler sub-solve (see FusedWheelOptions.xhat_tail_k):
    top-k worst residual scenarios get a large extra budget at the
    tier-2 rescue profile on a gathered sub-batch, state scattered
    back.  Runs inside the same jitted plane program.

    k is additionally capped at S/8: at small scenario counts a fixed
    64 would re-solve most of the batch (observed: 64 of uc's 100
    scenarios, ~0.7x the hub step, every exchange).  The whole
    sub-solve is lax.cond-gated on some real scenario actually missing
    tolerance, so exchanges whose main pass already cleared the gate
    pay nothing.

    k is quantized DOWN the dispatch bucket ladder — the CONFIGURED
    scheduler's ladder when one exists (--dispatch-bucket-growth
    governs both the oracle megabatches and these gathers), else the
    default: the gathered sub-batch is a fresh device shape per
    distinct k, and without quantization every S (10k sweep, padded
    variants, multi-model processes) mints its own tail executable —
    with it, all of them land on a handful of rungs and the jit cache
    stays bounded (docs/dispatch.md)."""
    from mpisppy_tpu import dispatch as _dispatch
    S = st.omega.shape[0]
    k = min(wopts.xhat_tail_k, max(8, S // 8), S)
    if k > 0:
        sched = _dispatch.get_scheduler(create=False)
        ladder = sched.ladder if sched is not None \
            else _dispatch.default_ladder()
        k = min(ladder.bucket_floor(k), S)
    if k <= 0 or wopts.xhat_tail_windows <= 0:
        return st

    def run(st):
        _, idx = jax.lax.top_k(jnp.where(real, rp, -1.0), k)
        sub_qp = _gather_qp(qp, idx, S)
        sub_st = _gather_scen(st, idx, S)
        topts = dataclasses.replace(
            wopts.xhat_pdhg, omega0=0.03, restart_period=160)
        sub_st = dataclasses.replace(
            sub_st, omega=jnp.full_like(sub_st.omega, topts.omega0))
        sub_st = pdhg.solve_fixed(sub_qp, wopts.xhat_tail_windows, topts,
                                  sub_st)
        return _scatter_scen(st, sub_st, idx, S)

    # engage only while some scenario actually MISSES the publication
    # gate — the tail exists to converge the straggler recourse LPs
    # that block all-scenario feasibility (sslp-10k), not to polish
    # already-feasible solves.  An always-on variant (engage at
    # feas_tol/100) cost uc 0.4 s/iteration for identical bounds,
    # measured: 427 iterations certified the same outer/inner with the
    # tail never improving anything.
    needed = jnp.any(jnp.where(real, rp > feas_tol, False))
    return jax.lax.cond(needed, run, lambda s: s, st)


def _eval_step(batch: ScenarioBatch, cand: Array,
               solver: pdhg.PDHGState, windows: int,
               wopts: FusedWheelOptions, tail: bool = False):
    """Advance the recourse evaluation of a fixed candidate a fixed
    budget.  The candidate moves every iteration, but consecutive
    candidates differ little, so the warm iterates (clipped into the new
    fixed box) track it — the fused analog of XhatXbarInnerBound's warm
    PDHG state.  Validity: the value only counts when EVERY real
    scenario's primal residual clears feas_tol, so a truncated or
    genuinely infeasible solve can never produce an incumbent.

    The published value is COMPENSATED for residual infeasibility: an
    rp-infeasible x can undershoot the true recourse optimum by up to
    ~|y*|'viol (first order), so COMP_SAFETY * E[sum_i |y_i| viol_i] is
    added before publication.  The reference never needs this (Gurobi
    returns exactly feasible candidates, ref:mpisppy/spopt.py:884); a
    truncated first-order solve does, or lean warm budgets can publish
    inner bounds below the optimum (observed on farmer: 8e-4 relative
    leak).  Exactly feasible solves pay zero.  Because the compensation
    reads the CURRENT truncated-solve dual iterate rather than a
    verified dual bound, the exact-penalty inequality holds only to
    first order — the safety factor (xhat.COMP_SAFETY) covers the
    inexact-dual slack, and the published inner bounds are
    APPROXIMATELY certified with error O(rp * |y - y*|); the
    comp-tightness gate below keeps that error a vanishing fraction of
    the value."""
    qp = batch.with_fixed_nonants(cand)
    st = dataclasses.replace(solver, x=jnp.clip(solver.x, qp.l, qp.u))
    # detect_infeas: a candidate that leaves ANY scenario without
    # feasible recourse gets a Farkas certificate within a few windows;
    # the host reads the `dead` flag and adopts a fresh candidate next
    # exchange instead of burning xhat_give_up exchanges (or an
    # 80-second blocking rescue, both observed on sslp-10k) on it.
    popts = dataclasses.replace(wopts.xhat_pdhg, detect_infeas=True)
    st = pdhg.solve_fixed(qp, windows, popts, st)
    real = batch.p > 0.0
    if tail:
        # straggler sub-solve: x-hat plane only — the slam/shuffle
        # planes rotate candidates and must stay cheap
        rp0, _, _ = boxqp.kkt_residuals(qp, st.x, st.y)
        st = _tail_rescue(qp, st, rp0, real, wopts, wopts.xhat_feas_tol)
    obj = jnp.sum(qp.c * st.x + 0.5 * qp.q * st.x * st.x, axis=-1)
    viol = boxqp.primal_residual(qp, st.x)
    comp = xhat_mod.COMP_SAFETY * jnp.sum(jnp.abs(st.y) * viol, axis=-1)
    obj = obj + comp
    rp, _, _ = boxqp.kkt_residuals(qp, st.x, st.y)
    bad_status = (st.status == pdhg.INFEASIBLE) \
        | (st.status == pdhg.UNBOUNDED)
    ok = (rp <= wopts.xhat_feas_tol) & ~bad_status
    feas = jnp.all(jnp.where(real, ok, True))
    dead = jnp.any(jnp.where(real, bad_status, False))
    value = jnp.where(feas, batch.expectation(obj),
                      jnp.asarray(jnp.inf, obj.dtype))
    # TIGHTNESS gate: the compensation is first-order, so a value whose
    # compensation is a material fraction of the bound itself is not
    # trustworthy (hydro measured +37% at stiff duals).  Feasible-but-
    # loose evaluations stay unpublished until the warm solver (or the
    # tail rescue, which engages on rp > feas_tol) tightens them.
    ecomp = batch.expectation(comp)
    tight = ecomp <= wopts.xhat_comp_tol * jnp.maximum(1.0,
                                                       jnp.abs(value))
    feas = feas & tight
    value = jnp.where(feas, value, jnp.asarray(jnp.inf, obj.dtype))
    return st, value, feas, dead


@partial(jax.jit, static_argnames=("opts", "wopts"))
def fused_iter0(batch: ScenarioBatch, rho: Array, opts: ph_mod.PHOptions,
                wopts: FusedWheelOptions):
    """PH Iter0 plus spoke-plane state init.  Both spoke solvers warm
    from the iter0 iterates (same A, so Lnorm/omega carry) — no extra
    power iterations, no cold starts."""
    batch = concretize(batch)  # scengen: synthesize in-trace
    phst, tb, cert = ph_mod.ph_iter0(batch, rho, opts)
    solver = phst.solver
    dt = batch.qp.c.dtype
    if solver.counters is not None:
        # the planes warm-start from the hub's iter0 ITERATES, but
        # their kernel counters must start at zero — copying the hub's
        # iter0 totals would inflate every cyl-labeled plane metric by
        # the full iter0 count (and multi-count it across planes)
        from mpisppy_tpu.telemetry import counters as _kc
        solver = dataclasses.replace(
            solver, counters=_kc.init_counters(
                solver.omega.shape, dt,
                ring_size=solver.counters.ring.shape[-1]))
    xhat_solver = dataclasses.replace(
        solver, omega=jnp.full_like(solver.omega, wopts.xhat_pdhg.omega0))
    st = FusedWheelState(
        ph=phst,
        lag_solver=solver,
        lag_bound=jnp.asarray(-jnp.inf, dt),
        lag_certified=jnp.asarray(False),
        xhat_solver=xhat_solver,
        xhat_cand=jnp.zeros((batch.tree.num_nodes, batch.num_nonants), dt),
        xhat_value=jnp.asarray(jnp.inf, dt),
        xhat_feasible=jnp.asarray(False),
        xhat_dead=jnp.asarray(False),
        slam_solver=xhat_solver,
        slam_cand=jnp.zeros((batch.num_nonants,), dt),
        slam_value=jnp.asarray(jnp.inf, dt),
        slam_feasible=jnp.asarray(False),
        shuf_solver=xhat_solver,
        shuf_cand=jnp.zeros((batch.num_nonants,), dt),
        shuf_value=jnp.asarray(jnp.inf, dt),
        shuf_feasible=jnp.asarray(False),
        scalars=jnp.zeros((10,), dt),
    )
    return dataclasses.replace(st, scalars=_pack_scalars(st)), tb, cert


def _pack_scalars(st: "FusedWheelState") -> Array:
    dt = st.ph.conv.dtype
    return jnp.stack([
        st.ph.conv.astype(dt),
        st.lag_bound.astype(dt),
        st.lag_certified.astype(dt),
        st.xhat_value.astype(dt),
        st.xhat_feasible.astype(dt),
        st.xhat_dead.astype(dt),
        st.slam_value.astype(dt),
        st.slam_feasible.astype(dt),
        st.shuf_value.astype(dt),
        st.shuf_feasible.astype(dt),
    ])


SCALAR_KEYS = ("conv", "lag_bound", "lag_certified", "xhat_value",
               "xhat_feasible", "xhat_dead", "slam_value",
               "slam_feasible", "shuf_value", "shuf_feasible")

# How many exchanges the pipelined scalar cache lags the dispatched
# iterate (FusedPH._cache_scalars reads the PREVIOUS iteration's packed
# scalars, which themselves describe the step before it).  Every host
# decision that attributes cached flags to a candidate must wait this
# many evaluations — _iterk_split's flags_fresh references this
# constant so a pipelining change cannot silently misattribute
# landed/dead flags (double rotation, skipped rounding tiers).
SCALAR_PIPELINE_DEPTH = 2


@partial(jax.jit, static_argnames=("opts", "wopts"))
def fused_iterk(batch: ScenarioBatch, st: FusedWheelState,
                opts: ph_mod.PHOptions, wopts: FusedWheelOptions,
                shuf_id: Array | None = None) -> FusedWheelState:
    """One wheel iteration as ONE compiled program: hub PH step, then
    the Lagrangian bound at the fresh W and the recourse values at the
    fresh candidates (rounded x̄ / slam / shuffled scenario), each a
    fixed warm budget."""
    batch = concretize(batch)  # scengen: synthesize in-trace
    phst = ph_mod.ph_iterk(batch, st.ph, opts)
    out = dataclasses.replace(st, ph=phst)

    # The planes are data-independent given phst, so XLA freely
    # interleaves their window loops — measured on v5e at S=10k this is
    # strongly superadditive (individual plane extras sum to 240 ms but
    # the 4-plane program costs +428 ms: interleaved loops evict each
    # other's VMEM-resident solver state).  `fence` threads each
    # plane's warm inputs through an optimization_barrier with the
    # previous plane's outputs, forcing the planes to run one after
    # another, each with the VMEM to itself.
    done_vals = [phst]

    def fence(*vals):
        fenced = jax.lax.optimization_barrier(tuple(done_vals) + vals)
        return fenced[len(done_vals):]

    if wopts.lag_windows > 0:
        (lag_in,) = fence(st.lag_solver)
        lag_solver, lag_bound, lag_cert = _lag_step(
            batch, phst.W, lag_in, wopts)
        out = dataclasses.replace(out, lag_solver=lag_solver,
                                  lag_bound=lag_bound,
                                  lag_certified=lag_cert)
        done_vals.append(lag_solver)
    if wopts.xhat_windows > 0:
        cand = xhat_mod.round_integers(batch, phst.xbar_nodes)
        (xhat_in,) = fence(st.xhat_solver)
        xs, value, feas, dead = _eval_step(batch, cand, xhat_in,
                                           wopts.xhat_windows, wopts,
                                           tail=True)
        out = dataclasses.replace(out, xhat_solver=xs, xhat_cand=cand,
                                  xhat_value=value, xhat_feasible=feas,
                                  xhat_dead=dead)
        done_vals.append(xs)
    if wopts.slam_windows > 0 or wopts.shuffle_windows > 0:
        x_non = batch.nonants(phst.solver.x)
    if wopts.slam_windows > 0:
        scand = xhat_mod.slam_candidate(batch, x_non, wopts.slam_sense_max)
        (slam_in,) = fence(st.slam_solver)
        ss, svalue, sfeas, _ = _eval_step(batch, scand, slam_in,
                                          wopts.slam_windows, wopts)
        out = dataclasses.replace(out, slam_solver=ss, slam_cand=scand,
                                  slam_value=svalue, slam_feasible=sfeas)
        done_vals.append(ss)
    if wopts.shuffle_windows > 0:
        # one rotating candidate per iteration (the host supplies the
        # deterministic shuffle index, seed 42 — ref:
        # xhatshufflelooper_bounder.py:74); over a run this visits
        # scenarios' own first stages like the reference's looper
        sid = jnp.asarray(0, jnp.int32) if shuf_id is None else shuf_id
        fcand = xhat_mod.round_integers(batch, x_non[sid])
        (shuf_in,) = fence(st.shuf_solver)
        fs, fvalue, ffeas, _ = _eval_step(batch, fcand, shuf_in,
                                          wopts.shuffle_windows, wopts)
        out = dataclasses.replace(out, shuf_solver=fs, shuf_cand=fcand,
                                  shuf_value=fvalue, shuf_feasible=ffeas)
    return dataclasses.replace(out, scalars=_pack_scalars(out))


# --- async exchange plane (ISSUE 11 tentpole; docs/async_wheel.md) ----
# One slot of the double-buffered host<->device exchange plane: the
# W/x̄/iterate view the spoke planes and the stale-prox hub step read at
# iteration k while the host completes the exchange for an earlier
# iteration.  Slots hold DEVICE REFS (arrays are immutable; a "plane
# write" is a host-side pointer swap, never a transfer), so the ring in
# algos/async_wheel.AsyncFusedPH costs no HBM beyond the generations it
# pins alive.

@partial(
    jax.tree_util.register_dataclass,
    data_fields=["W", "xbar", "xbar_nodes", "x"],
    meta_fields=[],
)
@dataclasses.dataclass(frozen=True)
class ExchangePlane:
    W: Array           # (S, N) duals at the plane's generation
    xbar: Array        # (S, N) per-scenario view of node averages
    xbar_nodes: Array  # (num_nodes, N)
    x: Array           # (S, n) full primal iterates (slam/shuf inputs)


def plane_of(phst: ph_mod.PHState) -> ExchangePlane:
    """The exchange-plane view of one PH state generation."""
    return ExchangePlane(W=phst.W, xbar=phst.xbar,
                         xbar_nodes=phst.xbar_nodes, x=phst.solver.x)


@partial(jax.jit, static_argnames=("opts", "nu", "gamma", "theta_floor"))
def ph_stale_step(batch: ScenarioBatch, st: ph_mod.PHState,
                  plane: ExchangePlane, opts: ph_mod.PHOptions,
                  nu: float = 1.0, gamma: float = 1.0,
                  theta_floor: float = 0.05):
    """One theta-damped PH hub step against a (possibly stale) exchange
    plane — the APH-class stale-plane hub iteration (ISSUE 11;
    docs/async_wheel.md).

    The subproblem proxes around the PLANE's x̄ (the center the device
    can form without waiting for the host exchange) instead of the
    state's own freshest average; the multiplier update is then damped
    by the APH projective step length (algos/aph.projective_theta):

        W_new = W + theta * rho * (x_new - x̄_new),  theta in [floor, 1]

    At plane == the previous iteration's output and theta == 1 this is
    EXACTLY ph_iterk (synchronous PH already proxes around the previous
    x̄), so staleness-1 deviates from the synchronous trajectory only by
    the damping; deeper staleness lags the prox center further, and
    theta contracts automatically when the stale direction stops making
    projective progress.  Returns (new_state, theta)."""
    batch = concretize(batch)  # scengen: synthesize in-trace
    smooth_p = opts.smooth_p if opts.smoothed else 0.0
    qp_eff = ph_mod._prox_qp(batch, st.W, plane.xbar, st.z, st.rho,
                             smooth_p)
    solver = pdhg.solve_fixed(qp_eff, opts.subproblem_windows, opts.pdhg,
                              st.solver)
    st2 = dataclasses.replace(st, solver=solver)
    x_non, xbar, xbar_nodes, xsqbar, W_full, z, conv = ph_mod._xbar_w_conv(
        batch, st2, opts.smooth_beta, opts.smoothed, opts.compute_xsqbar)
    theta = aph_mod.projective_theta(batch, x_non, xbar, st.W, plane.xbar,
                                     plane.W, st.rho, nu, gamma)
    # floor: near convergence phi ~ ||x - z_plane||^2 -> 0 would freeze
    # the duals entirely; a small floor keeps the (already tiny) PH
    # update flowing (docs/async_wheel.md theta-damping rationale)
    theta = jnp.maximum(theta, jnp.asarray(theta_floor, theta.dtype))
    # W_full is st.W + rho*(x - xbar) (masked for var_prob batches by
    # _xbar_w_conv), so blending recovers the damped update exactly
    W = st.W + theta * (W_full - st.W)
    out = dataclasses.replace(st2, W=W, z=z, xbar=xbar,
                              xbar_nodes=xbar_nodes, xsqbar=xsqbar,
                              conv=conv)
    return out, theta


# --- split-dispatch plane programs -----------------------------------
# Each plane as its own small jitted program (see
# FusedWheelOptions.split_dispatch).  `windows` is static: the adaptive
# controller only ever uses the {full, lean} pair per plane, so at most
# two compiles per plane exist per run.

@partial(jax.jit, static_argnames=("wopts", "windows"))
def lag_plane(batch, W, solver, wopts, windows):
    return _lag_step(concretize(batch), W, solver, wopts, windows)


@partial(jax.jit, static_argnames=("mode",))
def _round_xbar(batch, xbar_nodes, mode="nearest"):
    return xhat_mod.round_integers(concretize(batch), xbar_nodes, mode)


@partial(jax.jit, static_argnames=("wopts", "windows"))
def xhat_plane(batch, cand, solver, wopts, windows):
    st, value, feas, dead = _eval_step(concretize(batch), cand, solver,
                                       windows, wopts, tail=True)
    return st, value, feas, dead


@partial(jax.jit, static_argnames=("wopts", "windows", "sense_max"))
def slam_plane(batch, x, solver, wopts, windows, sense_max):
    batch = concretize(batch)
    x_non = batch.nonants(x)
    scand = xhat_mod.slam_candidate(batch, x_non, sense_max)
    st, value, feas, _ = _eval_step(batch, scand, solver, windows, wopts)
    return st, scand, value, feas


@partial(jax.jit, static_argnames=("wopts", "windows"))
def shuf_plane(batch, x, solver, sid, wopts, windows):
    batch = concretize(batch)
    x_non = batch.nonants(x)
    fcand = xhat_mod.round_integers(batch, x_non[sid])
    st, value, feas, _ = _eval_step(batch, fcand, solver, windows, wopts)
    return st, fcand, value, feas


@jax.jit
def _pack_scalars_jit(st: "FusedWheelState") -> Array:
    return _pack_scalars(st)


class _PlaneBudget:
    """Host-side controller driving one plane's {full, lean} budget off
    its CERTIFICATION streak.

    Rationale: once a plane's warm solver certifies (dual residual for
    the Lagrangian, primal feasibility for the candidate evaluations)
    for `stall_after` consecutive exchanges, it is tracking its slowly
    moving target and a lean budget keeps it certified; the moment
    certification is lost the budget snaps back to full.  Validity is
    unaffected either way — certificates gate every published value
    identically at any budget; lean can only cost bound freshness,
    and an under-budgeted plane immediately reveals itself by failing
    to certify (which restores the full budget)."""

    def __init__(self, full: int, lean: int, stall_after: int):
        self.full = full
        self.lean = max(1, min(lean, full)) if full > 0 else 0
        self.stall_after = stall_after
        self.streak = 0

    def windows(self) -> int:
        if self.full <= 0:
            return 0
        return self.lean if self.streak >= self.stall_after else self.full

    def observe(self, certified: bool) -> None:
        self.streak = self.streak + 1 if certified else 0


class FusedPH(ph_mod.PH):
    """PH driver whose iteration IS the whole wheel step.

    Use with the Fused* spoke classes (cylinders.spoke): they read
    bounds off `self.wstate` instead of launching their own device
    work.  Classic spokes still work alongside (the hub updates them on
    its sync period as before)."""

    def __init__(self, options, batch, wheel_options=None, **kw):
        super().__init__(options, batch, **kw)
        self.wheel_options = wheel_options or FusedWheelOptions()
        self.wstate: FusedWheelState | None = None
        self.scalar_cache: dict | None = None
        self.cand_cache: dict | None = None
        self._scalars_inflight = None
        self._shuf_order = np.random.default_rng(42).permutation(
            batch.num_real)
        self._shuf_cursor = 0
        self._xhat_frozen_for = 0
        self._xhat_has_cand = False
        self._xhat_round_mode = "nearest"
        w = self.wheel_options
        stall = w.adapt_stall if w.adapt_budgets else (1 << 30)
        lag_stall = stall if w.adapt_lag_budget else (1 << 30)
        self._budgets = {
            "lag": _PlaneBudget(w.lag_windows, w.lean_lag_windows,
                                lag_stall),
            "xhat": _PlaneBudget(w.xhat_windows, w.lean_xhat_windows,
                                 stall),
            "slam": _PlaneBudget(w.slam_windows, w.lean_slam_windows,
                                 stall),
            "shuf": _PlaneBudget(w.shuffle_windows,
                                 w.lean_shuffle_windows, stall),
        }

    def _cache_scalars(self, pipelined: bool = False):
        """ONE device->host transfer per iteration: everything the hub
        and the fused spokes decide on.  Pipelined mode reads the
        PREVIOUS iteration's packed scalars right after dispatching the
        next step (total read lag: SCALAR_PIPELINE_DEPTH exchanges), so
        the host never blocks on the in-flight program —
        the hub's decisions lag one iteration (bounds are valid at every
        iterate, so a one-iteration-late termination is still certified;
        this is exactly the reference's stale-window tolerance,
        ref:cylinders/hub.py write-id freshness).  The candidate tensors
        ride the same pipeline so a cached value is always paired with
        the candidate it was evaluated at."""
        inflight = (self.wstate.scalars, self.wstate.xhat_cand,
                    self.wstate.slam_cand, self.wstate.shuf_cand)
        if pipelined and self._scalars_inflight is not None:
            scalars, xc, sc_, fc = self._scalars_inflight
        else:
            scalars, xc, sc_, fc = inflight
        self._scalars_inflight = inflight
        # the ONE place the hub loop blocks on the device, timed as the
        # `scalar_wait` span (the enclosing subproblem_solve / iter0_solve
        # span then times dispatch alone).  With an elastic MeshRuntime
        # armed (parallel/elastic.py) the fetch is deadline-bounded and
        # chaos-seamed — a straggler or lost host trips a typed
        # MeshDegraded here instead of hanging the hub
        spcomm = getattr(self, "spcomm", None)
        rt = None if spcomm is None \
            else spcomm.options.get("mesh_runtime")
        with _prof.span(spcomm, "scalar_wait"):
            if rt is not None:
                vals = rt.harvest(lambda: np.asarray(scalars),
                                  hub_iter=self._iter)
            else:
                vals = np.asarray(scalars)
        self.scalar_cache = dict(zip(SCALAR_KEYS, (float(v) for v in vals)))
        # device refs, transferred only when a spoke actually offers
        self.cand_cache = {"xhat": xc, "slam": sc_, "shuf": fc}

    def flush_scalars(self):
        """Synchronize the cache to the LATEST iterate (final harvest)."""
        if self.wstate is not None:
            self._cache_scalars()

    def _read_conv(self) -> float:
        return self.scalar_cache["conv"]

    def state_template(self):
        st, _, _ = jax.eval_shape(
            partial(fused_iter0, opts=ph_mod.kernel_opts(self.options),
                    wopts=self.wheel_options),
            self.batch, self.rho)
        return st

    def _iter0_impl(self):
        wstate, tb, cert = fused_iter0(
            self.batch, self.rho, ph_mod.kernel_opts(self.options),
            self.wheel_options)
        self.wstate = shard_like_batch(wstate, self.batch)
        self._cache_scalars()
        return self.wstate.ph, tb, cert

    def _draw_spoke_cycle(self):
        """Advance the shuffle cursor one draw and evaluate the spoke
        cadence for this iteration — the ONE place the (sid, spoke_iter)
        pair comes from, shared with the async driver's stale path so
        shuffle/cadence semantics can never drift between the two
        iteration paths."""
        sid = jnp.asarray(
            int(self._shuf_order[self._shuf_cursor]), jnp.int32)
        self._shuf_cursor = (self._shuf_cursor + 1) % len(self._shuf_order)
        p = max(1, int(self.wheel_options.spoke_period))
        return sid, p <= 1 or (self._iter % p) == 0

    def _iterk_impl(self):
        sid, spoke_iter = self._draw_spoke_cycle()
        wopts = self.wheel_options
        split = wopts.split_dispatch
        if split is None:
            split = self.batch.num_real >= 512
        if split:
            self.wstate = self._iterk_split(wopts, sid, spoke_iter)
        else:
            w = wopts
            if not spoke_iter:
                # hub-only variant: spoke planes skipped, their
                # state/bounds carried untouched (harvests re-read last
                # values — folding is idempotent)
                w = dataclasses.replace(
                    w, lag_windows=0, xhat_windows=0, slam_windows=0,
                    shuffle_windows=0)
            # self.state may have been rebound by extensions/convergers
            # (e.g. rho updaters) — fold it back into the wheel state
            self.wstate = fused_iterk(
                self.batch,
                dataclasses.replace(self.wstate, ph=self.state),
                ph_mod.kernel_opts(self.options), w, sid)
        self._cache_scalars(pipelined=True)
        if spoke_iter:
            self._observe_progress()
        return self.wstate.ph

    def _next_xhat_cand(self, xbar_nodes, current_cand):
        """The x̂ plane's freeze/rotate candidate policy, shared by the
        split-dispatch pipeline and the async wheel (which derives
        xbar_nodes from its stale exchange plane).

        The pipelined scalar cache lags SCALAR_PIPELINE_DEPTH
        iterations (see _cache_scalars), so right after an adoption the
        landed/dead flags still describe the PREVIOUS candidate —
        acting on them would rotate twice and skip a rounding tier;
        trust them only once this candidate has been evaluated
        pipeline-depth exchanges."""
        sc = self.scalar_cache or {}
        wopts = self.wheel_options
        flags_fresh = self._xhat_frozen_for >= SCALAR_PIPELINE_DEPTH
        landed = flags_fresh and bool(sc.get("xhat_feasible", 0.0))
        dead = flags_fresh and bool(sc.get("xhat_dead", 0.0))
        give_up = self._xhat_frozen_for >= wopts.xhat_give_up
        if landed or dead or give_up or not self._xhat_has_cand:
            if landed:
                # a landed candidate validates the current rounding
                # direction — keep it
                pass
            elif dead or give_up:
                # escalate the rounding direction: on sslp-like models
                # nearest-rounding strands recourse demand and the
                # candidate is CERTIFIED dead; ceil opens every
                # fractional facility
                order = ("nearest", "ceil", "floor")
                i = order.index(self._xhat_round_mode)
                self._xhat_round_mode = order[(i + 1) % 3]
            cand = _round_xbar(self.batch, xbar_nodes,
                               self._xhat_round_mode)
            self._xhat_frozen_for = 0
            self._xhat_has_cand = True
        else:
            cand = current_cand  # frozen: keep accumulating
            self._xhat_frozen_for += 1
        return cand

    def _iterk_split(self, wopts: FusedWheelOptions, sid,
                     spoke_iter: bool) -> FusedWheelState:
        """One wheel iteration as a PIPELINE of async dispatches: the
        hub PH step, then each enabled plane as its own program, then
        the scalar pack.  Nothing here blocks the host — the device
        queue drains them back-to-back, and the per-dispatch latency
        hides behind execution (measured: the monolithic fused program
        is 1.8x slower at S=10k; see split_dispatch)."""
        batch = self.batch
        phst = ph_mod.ph_iterk(batch, self.state,
                               ph_mod.kernel_opts(self.options))
        out = dataclasses.replace(self.wstate, ph=phst)
        if spoke_iter:
            out = self._dispatch_spoke_planes(out, phst.W,
                                              phst.xbar_nodes,
                                              phst.solver.x, sid)
        return dataclasses.replace(out, scalars=_pack_scalars_jit(out))

    def _dispatch_spoke_planes(self, out, W, xbar_nodes, x, sid,
                               dispatch=None):
        """The four spoke-plane dispatches against one (W, x̄-nodes, x)
        view — the current step's outputs on the synchronous split
        path, the stale exchange plane on the async wheel.  `dispatch`
        wraps each plane call (the async wheel routes through
        fire-and-forget PlaneTickets); the default is the direct async
        XLA dispatch."""
        if dispatch is None:
            def dispatch(label, fn, *args):
                return fn(*args)
        wopts = self.wheel_options
        batch = self.batch
        b = self._budgets
        if b["lag"].windows() > 0:
            ls, lb, lc = dispatch("lag", lag_plane, batch, W,
                                  out.lag_solver, wopts,
                                  b["lag"].windows())
            out = dataclasses.replace(
                out, lag_solver=ls, lag_bound=lb, lag_certified=lc)
        if b["xhat"].windows() > 0:
            cand = self._next_xhat_cand(xbar_nodes, out.xhat_cand)
            xs, xv, xf, xd = dispatch("xhat", xhat_plane, batch, cand,
                                      out.xhat_solver, wopts,
                                      b["xhat"].windows())
            out = dataclasses.replace(
                out, xhat_solver=xs, xhat_cand=cand, xhat_value=xv,
                xhat_feasible=xf, xhat_dead=xd)
        if b["slam"].windows() > 0:
            ss, scand, sv, sf = dispatch(
                "slam", slam_plane, batch, x, out.slam_solver, wopts,
                b["slam"].windows(), wopts.slam_sense_max)
            out = dataclasses.replace(
                out, slam_solver=ss, slam_cand=scand, slam_value=sv,
                slam_feasible=sf)
        if b["shuf"].windows() > 0:
            fs, fcand, fv, ff = dispatch(
                "shuf", shuf_plane, batch, x, out.shuf_solver, sid,
                wopts, b["shuf"].windows())
            out = dataclasses.replace(
                out, shuf_solver=fs, shuf_cand=fcand, shuf_value=fv,
                shuf_feasible=ff)
        return out

    def _observe_progress(self):
        """Feed the (possibly one-iteration-stale, see _cache_scalars)
        certification flags to the budget controllers.  Staleness only
        delays a budget switch by one exchange — harmless."""
        sc = self.scalar_cache
        if not sc:
            return
        self._budgets["lag"].observe(bool(sc["lag_certified"]))
        self._budgets["xhat"].observe(bool(sc["xhat_feasible"]))
        self._budgets["slam"].observe(bool(sc["slam_feasible"]))
        self._budgets["shuf"].observe(bool(sc["shuf_feasible"]))
