###############################################################################
# Restarted PDHG (PDLP-style) for batched BoxQPs.
#
# This kernel plays the role Gurobi/CPLEX play in the reference
# (ref:mpisppy/spopt.py:99-247, spopt.py:876-960): it is THE subproblem
# solver.  Design points, all TPU-driven:
#
#   * One batched tensor program: every field carries an optional leading
#     scenario axis; matvecs become (S,m,n)x(S,n) einsums that XLA tiles
#     onto the MXU.  A thousand scenario LPs are one program, not a
#     thousand solver calls (contrast ref:mpisppy/spopt.py:250-341, a
#     sequential Python loop over per-scenario solver plugins).
#   * No data-dependent Python control flow: the solve is a
#     lax.while_loop over restart windows, each window a lax.fori_loop of
#     PDHG iterations.  Per-problem termination is a `done` mask, not an
#     early exit, so the batch stays rectangular for XLA.
#   * Warm starts are first-class: PH re-solves the same constraint data
#     with updated linear/diagonal-quadratic terms every iteration, so
#     PDHGState (iterates + step-size machinery) is carried across calls.
#
# Algorithm: Chambolle-Pock primal-dual hybrid gradient with
#   - exact prox of c'x + 1/2 q x^2 over [l,u] (diagonal q),
#   - dual prox of the [bl,bu] row-indicator via Moreau,
#   - ADAPTIVE restart-to-average: candidates (better of {current,
#     window average} by relative KKT score) are evaluated every
#     `restart_period` iterations, but a restart fires only on
#     sufficient score decay (or at a forced window cap) — per batch
#     element.  A fixed short restart cadence stalls on degenerate LPs
#     (observed on the sslp extensive form: 200k iters stuck at 1.7e-2
#     primal residual vs 1.6k iters with longer windows),
#   - adaptive primal weight omega rebalancing primal/dual step sizes
#     (tau = omega/||A||, sigma = 1/(omega ||A||)), updated at restarts,
# following the PDLP recipe (Applegate et al.; see also MPAX in
# PAPERS.md) re-implemented from the math, not from any codebase.
###############################################################################
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from mpisppy_tpu.ops.boxqp import (
    BoxQP, infeasibility_certificate, kkt_residuals,
    unboundedness_certificate,
)

Array = jax.Array

# Per-problem statuses (ref:mpisppy/spopt.py:76-96,194-231 reads these
# off Gurobi; here the kernel certifies them itself).
RUNNING = 0       # not terminated (hit max_iters => unconverged)
OPTIMAL = 1
INFEASIBLE = 2    # certified by a Farkas ray
UNBOUNDED = 3     # certified by a recession direction with c'd < 0


@dataclasses.dataclass(frozen=True)
class PDHGOptions:
    """Static solver options (hashable: safe as a jit static arg)."""

    tol: float = 1e-6  # floored at 5*eps of the working dtype at solve time
    max_iters: int = 20_000
    # Auto-chunking: a single XLA dispatch whose while_loop can run more
    # than this many iterations is split into multiple capped host
    # dispatches (round 5 saw ~100k-iteration single dispatches outlive
    # the device runtime — a library user must not need bench-harness
    # chunking to be safe).  Only applies to HOST-LEVEL solve() calls; inside a
    # jit trace the caller owns the budget.  0 disables.
    dispatch_cap: int = 60_000
    restart_period: int = 40   # candidate-check cadence (iterations)
    omega0: float = 1.0
    power_iters: int = 30
    omega_min: float = 1e-4
    omega_max: float = 1e4
    step_margin: float = 0.99  # tau*sigma*||A||^2 = step_margin^2 < 1
    restart_decay: float = 0.5  # restart on score <= decay * score@restart
    max_window: int = 16        # forced restart after this many periods
    detect_infeas: bool = False  # per-problem Farkas/recession certificates
    certificate_tol: float = 1e-4
    # window-iteration engine: None = auto (the Pallas VMEM-resident
    # window kernel on TPU for dense shared-A batches at scale — the
    # 100k-scenario HBM-bandwidth fix, ops/pdhg_pallas.py — else the
    # XLA fori_loop); True/False forces.
    use_pallas: bool | None = None
    # scenario-tile height for the Pallas window kernel; larger tiles
    # lift MXU utilization (bigger GEMM M dim, fewer grid steps) until
    # the tile's solver state outgrows VMEM
    pallas_tile_s: int = 128
    # Double-buffer the Pallas window kernel's scenario tiles: the next
    # tile's solver state is async-copied HBM->VMEM while the current
    # tile runs its restart window, and finished tiles write back
    # asynchronously — the S=100k fix for tile DMA serialized with
    # compute (ops/pdhg_pallas.py; measured 485 of 819 GB/s before).
    # False keeps the single-buffer grid kernel (same math bit-for-bit,
    # tests/test_pdhg_pallas.py).
    pallas_pipeline: bool = True
    # MXU precision for the ITERATION matvecs only (restart candidate
    # scoring and convergence tests always run at the boxqp module
    # default, HIGHEST = bf16x6, so a cheaper iteration precision can
    # never mis-certify a solution).  None = module default; "bf16x3"
    # (alias "high") = 3-pass bf16 — half the HBM bytes and MXU passes
    # per matvec, ~4e-6 relative error per matvec, measured on-chip to
    # reach ~1e-6 relative KKT on sslp-family LPs when scoring stays
    # exact.  Aliases resolve through ops/boxqp.py PRECISION_ALIASES;
    # unknown strings raise at trace time with the valid list.
    iter_precision: str | None = None
    # Per-lane divergence guard (resilience subsystem, docs/resilience.md):
    # at each restart boundary, lanes whose iterates are non-finite or
    # exceed guard_threshold in magnitude are QUARANTINE-RESET — primal
    # re-clipped from zero, dual zeroed, window sums/anchors cleared,
    # omega halved (a too-aggressive primal weight is the usual
    # divergence driver) — at most guard_max_resets times per lane,
    # after which the lane is frozen `done` with status RUNNING so it
    # can never certify a bound.  Healthy lanes are untouched and a
    # False flag compiles the exact pre-guard program.
    lane_guard: bool = False
    guard_threshold: float = 1e12
    guard_max_resets: int = 3
    # On-device kernel counters (telemetry subsystem, docs/telemetry.md):
    # accumulate per-lane iteration/restart/omega-adaptation counts plus
    # a small KKT-score ring at each restart boundary, inside the jit
    # graph, harvested host-side in ONE transfer
    # (telemetry.counters.harvest_state).  False leaves PDHGState.counters
    # None — zero extra leaves, and the lowered program is byte-identical
    # to a build that never imported telemetry (tests/test_telemetry.py).
    telemetry: bool = False
    telemetry_ring: int = 8   # score samples kept per lane


@partial(
    jax.tree_util.register_dataclass,
    data_fields=[
        "x", "y", "x_sum", "y_sum", "x_anchor", "y_anchor",
        "omega", "Lnorm", "k", "nwin", "restart_score", "score", "done",
        "status", "guard_resets", "counters",
    ],
    meta_fields=[],
)
@dataclasses.dataclass(frozen=True)
class PDHGState:
    x: Array        # (..., n) primal iterate
    y: Array        # (..., m) dual iterate
    x_sum: Array    # running window sums for restart-to-average
    y_sum: Array
    x_anchor: Array  # iterate at last restart (for omega adaptation)
    y_anchor: Array
    omega: Array    # (...,) primal weight
    Lnorm: Array    # (...,) ||A||_2 estimate
    k: Array        # () global iteration counter
    nwin: Array     # (...,) iterations since this problem's last restart
    restart_score: Array  # (...,) candidate score at last restart
    score: Array    # (...,) last max relative KKT residual
    done: Array     # (...,) bool
    status: Array   # (...,) int32 RUNNING/OPTIMAL/INFEASIBLE/UNBOUNDED
    guard_resets: Array   # (...,) int32 cumulative lane-guard quarantines
    # telemetry.counters.KernelCounters when opts.telemetry, else None
    # (None flattens to zero leaves: the off path's pytree and program
    # are exactly the pre-telemetry ones)
    counters: object = None


def _bshape(p: BoxQP):
    """Batch shape of a problem: () or (S,)."""
    return p.c.shape[:-1]


@partial(jax.jit, static_argnames=("iters",))
def estimate_norm(p: BoxQP, iters: int = 30) -> Array:
    """Power iteration for ||A||_2, batch-aware.

    Seeded with a fixed PRNG vector (an all-ones seed lies in null(A'A)
    for difference-row matrices — exactly the shape of nonanticipativity
    rows — and collapses the iterate to zero).  The result is floored by
    the max row/column 2-norms, both guaranteed lower bounds on ||A||_2,
    so a degenerate iterate can never produce an underestimate that makes
    tau explode.

    Jitted (shape-keyed): called eagerly, the fori_loop would otherwise
    close over p's VALUES as jaxpr constants and XLA would compile a
    fresh scan executable for every distinct QP — one silent recompile
    per solve_mip/dive call (found by the dispatch compile guard,
    docs/dispatch.md)."""
    v = jax.random.normal(jax.random.PRNGKey(7), p.c.shape, p.c.dtype)
    v = v / jnp.linalg.norm(v, axis=-1, keepdims=True)

    def body(_, carry):
        v, _ = carry
        w = p.rmatvec(p.matvec(v))
        nrm = jnp.linalg.norm(w, axis=-1, keepdims=True)
        nrm = jnp.maximum(nrm, 1e-30)
        return w / nrm, nrm[..., 0]

    _, lam = jax.lax.fori_loop(0, iters, body, (v, jnp.ones(_bshape(p), p.c.dtype)))
    if hasattr(p.A, "row_sqnorms"):   # ops.sparse.EllMatrix
        row_lb = jnp.sqrt(jnp.max(p.A.row_sqnorms(), axis=-1))
        col_lb = jnp.sqrt(jnp.max(p.A.col_sqnorms(), axis=-1))
    else:
        row_lb = jnp.sqrt(jnp.max(jnp.sum(p.A * p.A, axis=-1), axis=-1))
        col_lb = jnp.sqrt(jnp.max(jnp.sum(p.A * p.A, axis=-2), axis=-1))
    lb = jnp.maximum(jnp.maximum(row_lb, col_lb), 1e-12)
    # lb broadcasts when A is shared across a batched c
    return jnp.maximum(jnp.sqrt(lam), lb)


def init_state(p: BoxQP, opts: PDHGOptions = PDHGOptions(),
               x0: Array | None = None, y0: Array | None = None) -> PDHGState:
    bs = _bshape(p)
    dt = p.c.dtype
    if x0 is None:
        x0 = jnp.clip(jnp.zeros_like(p.c), p.l, p.u)
    if y0 is None:
        y0 = jnp.zeros(bs + (p.m,), dt)
    L = estimate_norm(p, opts.power_iters)
    return PDHGState(
        x=x0, y=y0,
        x_sum=jnp.zeros_like(x0), y_sum=jnp.zeros_like(y0),
        x_anchor=x0, y_anchor=y0,
        omega=jnp.full(bs, opts.omega0, dt),
        Lnorm=L.astype(dt),
        k=jnp.zeros((), jnp.int32),
        nwin=jnp.zeros(bs, jnp.int32),
        restart_score=jnp.full(bs, jnp.inf, dt),
        score=jnp.full(bs, jnp.inf, dt),
        done=jnp.zeros(bs, bool),
        status=jnp.zeros(bs, jnp.int32),
        guard_resets=jnp.zeros(bs, jnp.int32),
        counters=_init_counters(bs, dt, opts),
    )


def _init_counters(bs, dt, opts: PDHGOptions):
    if not opts.telemetry:
        return None
    from mpisppy_tpu.telemetry import counters as kcounters
    return kcounters.init_counters(bs, dt, ring_size=opts.telemetry_ring)


def _iter_precision(opts: PDHGOptions):
    from mpisppy_tpu.ops.boxqp import as_precision
    return as_precision(opts.iter_precision)


def _pdhg_iter(p: BoxQP, st: PDHGState, tau: Array, sigma: Array,
               precision=None) -> PDHGState:
    """One PDHG step; frozen for problems already `done`.

    The dual prox dispatches per row at TRACE time: pure box problems
    (p.cones is None) keep the two-sided clip; conic problems route
    through ops.cones.dual_prox, which clips box rows and applies the
    Moreau second-order-cone projection blockwise on SOC rows."""
    t = tau[..., None]
    s = sigma[..., None]
    v = st.x - t * p.rmatvec(st.y, precision=precision)
    x1 = jnp.clip((v - t * p.c) / (1.0 + t * p.q), p.l, p.u)
    w = st.y + s * p.matvec(2.0 * x1 - st.x, precision=precision)
    if p.cones is None:
        y1 = w - s * jnp.clip(w / s, p.bl, p.bu)
    else:
        from mpisppy_tpu.ops import cones as cones_mod
        y1 = cones_mod.dual_prox(p.cones, w, s, p.bl, p.bu)
    keep = st.done[..., None]
    x1 = jnp.where(keep, st.x, x1)
    y1 = jnp.where(keep, st.y, y1)
    return dataclasses.replace(
        st, x=x1, y=y1, x_sum=st.x_sum + x1, y_sum=st.y_sum + y1,
    )


def _restart(p: BoxQP, st: PDHGState, opts: PDHGOptions) -> PDHGState:
    """Adaptive restart-to-average + omega adaptation + convergence check.

    Every call evaluates the restart candidate (the better of the
    current iterate and the window average by relative KKT score), but
    the restart — adopt candidate, clear the window, adapt omega — only
    fires per batch element when the candidate score has decayed to
    `restart_decay` of the score at that element's last restart, or the
    window hits `max_window` periods (PDLP's artificial restart).  A
    short fixed cadence provably stalls on degenerate LPs; an
    ever-growing window goes stale — this is the standard middle ground.
    """
    navg = jnp.maximum(st.nwin, 1).astype(st.x.dtype)[..., None]
    xa, ya = st.x_sum / navg, st.y_sum / navg

    rp_c, rd_c, rg_c = kkt_residuals(p, st.x, st.y)
    rp_a, rd_a, rg_a = kkt_residuals(p, xa, ya)
    score_c = jnp.maximum(jnp.maximum(rp_c, rd_c), rg_c)
    score_a = jnp.maximum(jnp.maximum(rp_a, rd_a), rg_a)

    take_avg = (score_a < score_c)[..., None]
    xr = jnp.where(take_avg, xa, st.x)
    yr = jnp.where(take_avg, ya, st.y)
    score = jnp.minimum(score_a, score_c)

    # Dtype-aware tolerance floor: relative KKT residuals near eps are
    # unreachable in the working precision; without a floor a too-tight
    # `tol` silently burns max_iters with done=False.  5*eps (~6e-7 in
    # f32) sits below the 1e-6 default so ordinary tolerances are
    # honored exactly.
    tol = jnp.maximum(opts.tol, 5.0 * jnp.finfo(st.x.dtype).eps)
    newly_done = score <= tol

    fire = (score <= opts.restart_decay * st.restart_score) \
        | (st.nwin >= opts.max_window * opts.restart_period) \
        | newly_done

    # Primal-weight adaptation (theta = 0.5 log-space smoothing),
    # applied only at restarts.  Balance criterion: with tau = omega/L
    # and sigma = 1/(omega*L), equalizing per-window travel
    # |dx|/tau = |dy|/sigma gives omega ~ |dx|/|dy| — i.e. a fast-moving
    # DUAL shrinks omega (bigger dual steps).  The inverted ratio
    # (dy/dx) is a positive feedback loop that blew omega up to O(100)
    # and stalled fixed-nonant recourse solves.
    dx = jnp.linalg.norm(xr - st.x_anchor, axis=-1)
    dy = jnp.linalg.norm(yr - st.y_anchor, axis=-1)
    valid = fire & (dx > 1e-12) & (dy > 1e-12)
    omega_new = jnp.exp(0.5 * jnp.log(jnp.where(valid, dx / jnp.maximum(dy, 1e-30), 1.0))
                        + 0.5 * jnp.log(st.omega))
    omega = jnp.clip(jnp.where(valid, omega_new, st.omega),
                     opts.omega_min, opts.omega_max)

    status = jnp.where(~st.done & newly_done, OPTIMAL, st.status)
    if opts.detect_infeas:
        # Approximate rays: the per-window displacement converges to the
        # infimal displacement vector — nonzero dual part certifies
        # primal infeasibility, nonzero primal part + descent certifies
        # unboundedness (PDLP's detection recipe, from the math).
        # Detection is gated on the solve being far from converged: near
        # optimality q(y*) can round to +O(eps) in f32 and the iterate
        # test would false-positive; an infeasible/unbounded problem
        # never gets a small KKT score, so nothing real is lost.
        ctol = opts.certificate_tol
        far = score > jnp.maximum(1e-3, 10.0 * tol)
        infeas = far & (infeasibility_certificate(p, yr - st.y_anchor, ctol)
                        | infeasibility_certificate(p, yr, ctol))
        unbd = far & unboundedness_certificate(p, xr - st.x_anchor, ctol)
        status = jnp.where(~st.done & ~newly_done & infeas, INFEASIBLE,
                           status)
        status = jnp.where((status == RUNNING) & unbd, UNBOUNDED, status)
        newly_done = newly_done | ((status != RUNNING) & ~st.done)

    act = fire & ~st.done           # restart these elements
    actx = act[..., None]
    return dataclasses.replace(
        st,
        x=jnp.where(actx, xr, st.x),
        y=jnp.where(actx, yr, st.y),
        x_sum=jnp.where(actx, 0.0, st.x_sum),
        y_sum=jnp.where(actx, 0.0, st.y_sum),
        x_anchor=jnp.where(actx, xr, st.x_anchor),
        y_anchor=jnp.where(actx, yr, st.y_anchor),
        omega=jnp.where(st.done, st.omega, omega),
        nwin=jnp.where(act, 0, st.nwin),
        restart_score=jnp.where(act, score, st.restart_score),
        score=jnp.where(st.done, st.score, score),
        done=st.done | newly_done,
        status=status,
    )


def _lane_guard(p: BoxQP, st: PDHGState, opts: PDHGOptions) -> PDHGState:
    """Quarantine-reset diverged lanes (resilience subsystem).

    A lane (batch element) is DIVERGED when its iterates are non-finite
    or exceed guard_threshold in magnitude — the signature of a badly
    conditioned scenario, a poisoned warm start, or an omega runaway.
    Such a lane never converges on its own (NaN propagates; the done
    mask keeps the rest of the batch correct but the while_loop burns
    max_iters on the dead lane), so the guard re-initializes ONLY the
    bad lanes from scratch with halved omega, up to guard_max_resets
    times; past the budget the lane is frozen `done` with status
    RUNNING, which no certificate path ever accepts — the batch
    completes and the wheel degrades gracefully instead of stalling.
    Counters are surfaced in PDHGState.guard_resets (cumulative)."""
    mag = jnp.maximum(jnp.max(jnp.abs(st.x), axis=-1),
                      jnp.max(jnp.abs(st.y), axis=-1))
    bad = ~st.done & (~jnp.isfinite(mag) | (mag > opts.guard_threshold))
    give_up = bad & (st.guard_resets >= opts.guard_max_resets)
    # EVERY bad lane gets its iterates scrubbed — a frozen lane's x
    # feeds downstream consumers (PH's xbar/W node averages have no
    # NaN masking), so give-up must freeze a CLEAN unconverged point,
    # never the poisoned one
    rx = bad[..., None]
    x0 = jnp.clip(jnp.zeros_like(st.x), p.l, p.u)
    return dataclasses.replace(
        st,
        x=jnp.where(rx, x0, st.x),
        y=jnp.where(rx, 0.0, st.y),
        x_sum=jnp.where(rx, 0.0, st.x_sum),
        y_sum=jnp.where(rx, 0.0, st.y_sum),
        x_anchor=jnp.where(rx, x0, st.x_anchor),
        y_anchor=jnp.where(rx, 0.0, st.y_anchor),
        omega=jnp.where(bad,
                        jnp.maximum(jnp.where(jnp.isfinite(st.omega),
                                              0.5 * st.omega, opts.omega0),
                                    opts.omega_min),
                        st.omega),
        nwin=jnp.where(bad, 0, st.nwin),
        restart_score=jnp.where(bad, jnp.inf, st.restart_score),
        score=jnp.where(bad, jnp.inf, st.score),
        guard_resets=st.guard_resets + bad.astype(jnp.int32),
        done=st.done | give_up,
    )


def _use_pallas_window(p: BoxQP, st: PDHGState, opts: PDHGOptions) -> bool:
    """Engine choice, resolved at TRACE time (all inputs static)."""
    if opts.use_pallas is not None:
        # static options field, not a device value
        return bool(opts.use_pallas)      # graftlint: allow-host-sync
    from mpisppy_tpu.ops import pdhg_pallas
    # measured crossover on v5e (sslp shapes): XLA wins to ~10k
    # scenarios (partial VMEM residency), the kernel wins at ~100k
    # (1.45 vs 0.62 it/s) where the XLA loop is HBM-bandwidth-bound
    return (jax.default_backend() == "tpu"
            and pdhg_pallas.supported(p)
            and st.x.ndim == 2 and st.x.shape[0] >= 32768)


def _window(p: BoxQP, st: PDHGState, opts: PDHGOptions) -> PDHGState:
    tau = opts.step_margin * st.omega / st.Lnorm
    sigma = opts.step_margin / (st.omega * st.Lnorm)
    pre_done, pre_omega = st.done, st.omega
    if _use_pallas_window(p, st, opts):
        from mpisppy_tpu.ops import pdhg_pallas
        interp = jax.default_backend() != "tpu"
        x, y, xs, ys = pdhg_pallas.run_window(
            p, st.x, st.y, st.x_sum, st.y_sum, tau, sigma, st.done,
            opts.restart_period, tile_s=opts.pallas_tile_s,
            precision=opts.iter_precision,
            pipeline=opts.pallas_pipeline, interpret=interp)
        st = dataclasses.replace(st, x=x, y=y, x_sum=xs, y_sum=ys)
    else:
        prec = _iter_precision(opts)
        st = jax.lax.fori_loop(
            0, opts.restart_period,
            lambda _, s: _pdhg_iter(p, s, tau, sigma, prec), st)
    st = dataclasses.replace(st, nwin=st.nwin + opts.restart_period)
    st = _restart(p, st, opts)
    if opts.telemetry:
        # the restart boundary is the harvest point (MPAX, PAPERS.md):
        # nwin was just incremented by restart_period, so a zero here
        # means _restart's act mask fired for that lane.  Recorded
        # BEFORE the lane guard (a quarantine also clears nwin, and is
        # already counted separately in guard_resets).
        from mpisppy_tpu.telemetry import counters as kcounters
        st = dataclasses.replace(st, counters=kcounters.record_window(
            st.counters, active=~pre_done,
            restarted=st.nwin == 0,
            omega_moved=st.omega != pre_omega,
            score=st.score, period=opts.restart_period))
    if opts.lane_guard:
        st = _lane_guard(p, st, opts)
    return dataclasses.replace(st, k=st.k + opts.restart_period)


def will_chunk(opts: PDHGOptions) -> bool:
    """True when a host-level solve() with these options auto-chunks.
    Shared predicate so wrappers that pick a jitted fast path (e.g.
    lagrangian_bound) can never disagree with solve() about chunk
    eligibility — disagreement would reintroduce the oversized single
    dispatch the cap exists to prevent."""
    return 0 < opts.dispatch_cap < opts.max_iters


def solve(p: BoxQP, opts: PDHGOptions = PDHGOptions(),
          state: PDHGState | None = None) -> PDHGState:
    """Solve to tolerance (batch-aware).  Jit-friendly:
    ``jax.jit(solve, static_argnames='opts')``.

    Host-level calls with max_iters > dispatch_cap are automatically
    split into multiple capped dispatches (see PDHGOptions.dispatch_cap);
    traced calls keep the single while_loop — a jit caller owns its
    budget.
    """
    if state is None:
        st = init_state(p, opts)
    else:
        # Reuse iterates + step machinery; reset bookkeeping.
        st = dataclasses.replace(
            state,
            x_sum=jnp.zeros_like(state.x), y_sum=jnp.zeros_like(state.y),
            x_anchor=state.x, y_anchor=state.y,
            k=jnp.zeros((), jnp.int32),
            nwin=jnp.zeros_like(state.nwin),
            restart_score=jnp.full(state.omega.shape, jnp.inf, state.x.dtype),
            score=jnp.full(state.omega.shape, jnp.inf, state.x.dtype),
            done=jnp.zeros(state.omega.shape, bool),
            status=jnp.zeros_like(state.status),
        )
        if opts.telemetry and st.counters is None:
            # warm state built under telemetry-off options: counters
            # start at zero from here (totals are per solve lineage)
            st = dataclasses.replace(
                st, counters=_init_counters(st.omega.shape, st.x.dtype,
                                            opts))

    # a call is host-level only when NOTHING is traced — a concrete qp
    # with a traced state (vmap/jit over state with a captured problem)
    # must keep the in-trace while_loop
    traced = any(isinstance(leaf, jax.core.Tracer)
                 for leaf in jax.tree_util.tree_leaves((p, st)))
    if not traced and will_chunk(opts):
        while True:
            st = _dispatch_capped(p, opts, st)
            # the documented host seam of the auto-chunk loop: one
            # scalar read between capped dispatches decides whether to
            # re-dispatch                       # graftlint: allow-host-sync
            if int(st.k) >= opts.max_iters or bool(jnp.all(st.done)):
                return st

    # ALWAYS through the jitted, shape-keyed loop.  Called eagerly the
    # while_loop would close over p's VALUES as jaxpr constants — one
    # silent XLA compile per distinct QP per call, the same leak class
    # the dispatch compile guard caught in estimate_norm after PR 4
    # (now also flagged at lint time: tools/graftlint trace-purity).
    # Inside an outer trace the nested jit inlines, so traced callers
    # compile exactly what they did before.
    return _solve_loop_jit(p, opts, st)


@partial(jax.jit, static_argnames=("opts",))
def _solve_loop_jit(p: BoxQP, opts: PDHGOptions,
                    st: PDHGState) -> PDHGState:
    """The run-to-tolerance while_loop, jitted so host-level solve()
    calls key the compile cache on shapes+opts, never on QP values."""
    def cond(s):
        return (s.k < opts.max_iters) & ~jnp.all(s.done)

    return jax.lax.while_loop(cond, lambda s: _window(p, s, opts), st)


@partial(jax.jit, static_argnames=("opts",))
def _solve_capped_jit(p: BoxQP, opts: PDHGOptions,
                      st: PDHGState) -> PDHGState:
    """One capped dispatch: at most dispatch_cap MORE iterations past the
    entry count st.k (which persists across chunks, so restart windows
    and omega adaptation carry over seamlessly)."""
    k0 = st.k

    def cond(s):
        return (s.k < opts.max_iters) & ((s.k - k0) < opts.dispatch_cap) \
            & ~jnp.all(s.done)

    return jax.lax.while_loop(cond, lambda s: _window(p, s, opts), st)


def _dispatch_capped(p, opts, st):
    """Host seam for the auto-chunk loop (monkeypatchable in tests to
    observe dispatch granularity)."""
    return _solve_capped_jit(p, opts, st)


def solve_fixed(p: BoxQP, n_windows: int, opts: PDHGOptions,
                state: PDHGState) -> PDHGState:
    """Fixed budget: n_windows restart windows, no early exit.  This is
    the inner solver for PH hot loops (inexact subproblem solves with
    warm starts), where a static iteration count keeps the whole PH step
    a single compiled program."""
    st = dataclasses.replace(
        state,
        x_sum=jnp.zeros_like(state.x), y_sum=jnp.zeros_like(state.y),
        x_anchor=state.x, y_anchor=state.y,
        nwin=jnp.zeros_like(state.nwin),
        restart_score=jnp.full(state.omega.shape, jnp.inf, state.x.dtype),
        done=jnp.zeros(state.omega.shape, bool),
        status=jnp.zeros_like(state.status),
    )
    if opts.telemetry and st.counters is None:
        st = dataclasses.replace(
            st, counters=_init_counters(st.omega.shape, st.x.dtype, opts))
    return _solve_fixed_jit(p, n_windows, opts, st)


@partial(jax.jit, static_argnames=("n_windows", "opts"))
def _solve_fixed_jit(p: BoxQP, n_windows: int, opts: PDHGOptions,
                     st: PDHGState) -> PDHGState:
    """Fixed-budget window loop, jitted for the same reason as
    _solve_loop_jit: an eager fori_loop bakes QP values into the jaxpr
    and recompiles per call (PH hot loops call this inside their own
    jit, where the nested jit inlines — but host-level callers, e.g. a
    spoke's first warm-up solve, used to pay one silent backend
    compile per distinct QP)."""
    return jax.lax.fori_loop(0, n_windows,
                             lambda _, s: _window(p, s, opts), st)


solve_batch = solve  # batching is implicit via leading axes
