"""The plain reference of one fused-wheel step on sslp.

It follows the configuration's description of the algorithm and imports
nothing of the program: its own LP of the instance (the SIPLIB sslp
layout), its own Ruiz equilibration, its own restarted PDHG window, PH
update, Lagrangian bound and x-hat recourse evaluation, in float32 with
every matrix product at Precision.HIGHEST.  What it takes from the run
is what a served model's reference takes from its run, the tokens: the
state the wheel carried INTO the checked step (iterates, step sizes, W,
x-bar), the candidate the x-hat plane evaluated, and each plane's window
budget.  It then computes the step's outputs itself.

`precision` selects the iteration matvecs of each plane.  The reference
runs each plane at the precision the configuration states, as the chip
defines it ("highest": Precision.HIGHEST, "bf16x3": Precision.HIGH,
three bf16 passes).  The control of the output check is this reference
one step lower, emulated explicitly so that it means the same on any
backend: "emul-bf16x3" splits both operands into bf16 high and low parts
and sums three bf16 products, "emul-bf16" rounds both to bf16 once.
"split<n>-<p>" is <p> with the contraction summed in n consecutive parts,
each at <p>, then added in order: as precise as <p>, rounded in another
order.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
RUNNING, OPTIMAL, INFEASIBLE, UNBOUNDED = 0, 1, 2, 3


# --------------------------------------------------------------------------
# the LP of one sslp instance, scaled
# --------------------------------------------------------------------------
def sslp_lp(inst: dict) -> dict:
    """Columns: x_j open (n), y_ij allocation (i-major, m*n), d_j
    overflow (n).  Rows: capacity sum_i D_ij y_ij - d_j - Cap x_j <= 0
    (n), client sum_j y_ij = ClientPresent_i (m).  Objective
    FixedCost.x - Revenue.y + Penalty.d.  LP relaxation."""
    n, m = int(inst["NumServers"]), int(inst["NumClients"])
    D = np.asarray(inst["Demand"], float)
    R = np.asarray(inst["Revenue"], float)
    cap, pen = float(inst["Capacity"]), float(inst["Penalty"])
    ncol, nrow = n + m * n + n, n + m
    c = np.concatenate([np.asarray(inst["FixedCost"], float),
                        -R.reshape(-1), np.full(n, pen)])
    A = np.zeros((nrow, ncol))
    for j in range(n):
        A[j, j] = -cap
        A[j, n + j:n + m * n:n] = D[:, j]
        A[j, n + m * n + j] = -1.0
    for i in range(m):
        A[n + i, n + i * n:n + (i + 1) * n] = 1.0
    u = np.concatenate([np.ones(n + m * n), D.sum(axis=0)])
    return {"n": n, "m": m, "A": A, "c": c, "l": np.zeros(ncol), "u": u}


def ruiz(A: np.ndarray, iters: int = 10):
    """Row/column inf-norm equilibration: A_scaled = diag(dr) A diag(dc)."""
    A = A.copy()
    dr, dc = np.ones(A.shape[0]), np.ones(A.shape[1])
    for _ in range(iters):
        r = np.max(np.abs(A), axis=1)
        r = np.where(r <= 0.0, 1.0, r)
        A /= np.sqrt(r)[:, None]
        dr /= np.sqrt(r)
        cmax = np.max(np.abs(A), axis=0)
        cmax = np.where(cmax <= 0.0, 1.0, cmax)
        A /= np.sqrt(cmax)[None, :]
        dc /= np.sqrt(cmax)
    return A, dr, dc


def scaled_problem(inst: dict, client_present: np.ndarray) -> dict:
    """The batch of scenario LPs in scaled space, float32 on the device."""
    lp = sslp_lp(inst)
    n, m = lp["n"], lp["m"]
    As, dr, dc = ruiz(lp["A"])
    S = client_present.shape[0]
    f32 = np.float32
    h = np.asarray(client_present, np.float64)
    bl = np.concatenate([np.full((S, n), -np.inf), h], axis=1) * dr
    bu = np.concatenate([np.zeros((S, n)), h], axis=1) * dr
    c = (lp["c"] * dc).astype(f32)
    return {
        "A": jnp.asarray(As.astype(f32)),
        "c": jnp.broadcast_to(jnp.asarray(c), (S, c.shape[0])),
        "q": jnp.zeros((S, c.shape[0]), f32),
        "l": jnp.asarray((lp["l"] / dc).astype(f32)),
        "u": jnp.asarray((lp["u"] / dc).astype(f32)),
        "bl": jnp.asarray(bl.astype(f32)), "bu": jnp.asarray(bu.astype(f32)),
        "d_non": jnp.asarray(dc[:n].astype(f32)),
        "nonant_idx": np.arange(n),
        "p": jnp.full((S,), f32(1.0 / S)),
    }


# --------------------------------------------------------------------------
# matrix products
# --------------------------------------------------------------------------
def _split(v):
    r = jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)
    return r.astype(jnp.bfloat16), (v - r).astype(jnp.bfloat16)


def _dot(v, M, precision: str):
    """v @ M for (S, k) v and (k, j) M at the named precision."""
    if precision.startswith("split"):
        head, base = precision.split("-", 1)
        n, k = int(head[len("split"):]), v.shape[-1]
        cuts = [k * i // n for i in range(n + 1)]
        out = _dot(v[..., :cuts[1]], M[:cuts[1]], base)
        for a, b in zip(cuts[1:], cuts[2:]):
            out = out + _dot(v[..., a:b], M[a:b], base)
        return out
    if precision == "highest":
        return jnp.matmul(v, M, precision=HIGHEST)
    if precision == "bf16x3":
        return jnp.matmul(v, M, precision=jax.lax.Precision.HIGH)
    if precision == "emul-bf16":
        return jnp.matmul(v.astype(jnp.bfloat16), M.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    if precision == "emul-bf16x3":
        vh, vl = _split(v)
        Mh, Ml = _split(M)
        acc = jnp.matmul(vh, Mh, preferred_element_type=jnp.float32)
        acc += jnp.matmul(vh, Ml, preferred_element_type=jnp.float32)
        acc += jnp.matmul(vl, Mh, preferred_element_type=jnp.float32)
        return acc
    raise ValueError(f"unknown precision {precision!r}")


def Ax(P, x, precision="highest"):
    return _dot(x, P["A"].T, precision)


def ATy(P, y, precision="highest"):
    return _dot(y, P["A"], precision)


# --------------------------------------------------------------------------
# residuals and certificates
# --------------------------------------------------------------------------
def primal_residual(P, x):
    ax = Ax(P, x)
    return jnp.maximum(ax - P["bu"], 0.0) + jnp.maximum(P["bl"] - ax, 0.0)


def dual_objective(P, x, y):
    rc = P["c"] + P["q"] * x + ATy(P, y)
    yc = jnp.where(y > 0.0, P["bu"] * y, P["bl"] * y)
    yc = jnp.where(jnp.isfinite(yc), yc, 0.0)
    rcc = jnp.where(rc > 0.0, P["l"] * rc, P["u"] * rc)
    rcc = jnp.where(jnp.isfinite(rcc), rcc, 0.0)
    quad = 0.5 * jnp.sum(P["q"] * x * x, axis=-1)
    return -quad - jnp.sum(yc, axis=-1) + jnp.sum(rcc, axis=-1)


def kkt(P, x, y):
    """(relative primal, dual, gap) residuals, inf-norm."""
    rp = jnp.max(jnp.abs(primal_residual(P, x)), axis=-1)
    rc = P["c"] + P["q"] * x + ATy(P, y)
    rd_vec = jnp.where(jnp.isfinite(P["l"]), 0.0, jnp.maximum(rc, 0.0)) \
        + jnp.where(jnp.isfinite(P["u"]), 0.0, jnp.maximum(-rc, 0.0))
    rd = jnp.max(jnp.abs(rd_vec), axis=-1)
    b = jnp.maximum(jnp.where(jnp.isfinite(P["bl"]), jnp.abs(P["bl"]), 0.0),
                    jnp.where(jnp.isfinite(P["bu"]), jnp.abs(P["bu"]), 0.0))
    cmax = jnp.max(jnp.abs(P["c"]), axis=-1, initial=0.0)
    pobj = jnp.sum(P["c"] * x + 0.5 * P["q"] * x * x, axis=-1)
    dobj = dual_objective(P, x, y)
    return (rp / (1.0 + jnp.max(b, axis=-1, initial=0.0)),
            rd / (1.0 + cmax),
            jnp.abs(pobj - dobj) / (1.0 + jnp.abs(pobj) + jnp.abs(dobj)))


def infeasible(P, y, tol):
    """Farkas test on the (approximate) ray y."""
    nrm = jnp.sum(jnp.abs(y), axis=-1, keepdims=True)
    yn = y / jnp.maximum(nrm, 1e-30)
    z = ATy(P, yn)
    ztol = 32.0 * jnp.finfo(z.dtype).eps
    drop = jnp.abs(z) <= ztol
    z = jnp.where(drop, 0.0, z)
    inf_j = jnp.where(z > 0.0, z * P["l"], z * P["u"])
    inf_j = jnp.where(z == 0.0, 0.0, inf_j)
    sup_i = jnp.where(yn > 0.0, yn * P["bu"], yn * P["bl"])
    sup_i = jnp.where(yn == 0.0, 0.0, sup_i)
    bad = (~jnp.isfinite(inf_j)).any(-1) | (~jnp.isfinite(sup_i)).any(-1)
    qval = jnp.sum(inf_j, -1) - jnp.sum(sup_i, -1)
    absl = jnp.where(jnp.isfinite(P["l"]), jnp.abs(P["l"]), 0.0)
    absu = jnp.where(jnp.isfinite(P["u"]), jnp.abs(P["u"]), 0.0)
    dropped = jnp.sum(jnp.where(drop, ztol * jnp.maximum(absl, absu), 0.0),
                      -1)
    scale = 1.0 + jnp.sum(jnp.abs(inf_j), -1) + jnp.sum(jnp.abs(sup_i), -1)
    return ~bad & (qval > tol * scale + dropped) & (nrm[..., 0] > 1e-30)


def unbounded(P, d, tol):
    """Recession-direction test on d."""
    nrm = jnp.sum(jnp.abs(d), axis=-1, keepdims=True)
    dn = d / jnp.maximum(nrm, 1e-30)
    ad = Ax(P, dn)
    rows = jnp.all(jnp.where(jnp.isfinite(P["bu"]), ad <= tol, True)
                   & jnp.where(jnp.isfinite(P["bl"]), ad >= -tol, True), -1)
    box = jnp.all(jnp.where(jnp.isfinite(P["u"]), dn <= tol, True)
                  & jnp.where(jnp.isfinite(P["l"]), dn >= -tol, True), -1)
    curv = jnp.sum(P["q"] * dn * dn, -1) <= tol
    cscale = 1.0 + jnp.max(jnp.abs(P["c"]), -1)
    descent = jnp.sum(P["c"] * dn, -1) < -tol * cscale
    return rows & box & curv & descent & (nrm[..., 0] > 1e-30)


# --------------------------------------------------------------------------
# restarted PDHG
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Pdhg:
    """One plane's PDHG settings, from the configuration."""

    tol: float
    restart_period: int
    precision: str
    step_margin: float
    restart_decay: float
    max_window: int
    omega_min: float
    omega_max: float
    certificate_tol: float
    detect_infeas: bool = False


#: per-scenario fields of a fixed-nonant problem
LANE_FIELDS = ("c", "q", "l", "u", "bl", "bu")
#: what a fixed-budget solve carries in from the previous step (it resets
#: the window sums, anchors, counters and flags itself): all that `pre`
#: of wheel_step holds for each plane
CARRIED = ("x", "y", "omega", "Lnorm", "score")


def _iterate(P, s, tau, sigma, prec):
    t, g = tau[:, None], sigma[:, None]
    v = s["x"] - t * ATy(P, s["y"], prec)
    x1 = jnp.clip((v - t * P["c"]) / (1.0 + t * P["q"]), P["l"], P["u"])
    w = s["y"] + g * Ax(P, 2.0 * x1 - s["x"], prec)
    y1 = w - g * jnp.clip(w / g, P["bl"], P["bu"])
    keep = s["done"][:, None]
    x1 = jnp.where(keep, s["x"], x1)
    y1 = jnp.where(keep, s["y"], y1)
    return dict(s, x=x1, y=y1, x_sum=s["x_sum"] + x1, y_sum=s["y_sum"] + y1)


def _restart(P, s, o: Pdhg):
    navg = jnp.maximum(s["nwin"], 1).astype(s["x"].dtype)[:, None]
    xa, ya = s["x_sum"] / navg, s["y_sum"] / navg
    sc = jnp.max(jnp.stack(kkt(P, s["x"], s["y"])), axis=0)
    sa = jnp.max(jnp.stack(kkt(P, xa, ya)), axis=0)
    avg = (sa < sc)[:, None]
    xr = jnp.where(avg, xa, s["x"])
    yr = jnp.where(avg, ya, s["y"])
    score = jnp.minimum(sa, sc)
    tol = jnp.maximum(o.tol, 5.0 * jnp.finfo(s["x"].dtype).eps)
    newly = score <= tol
    fire = (score <= o.restart_decay * s["restart_score"]) \
        | (s["nwin"] >= o.max_window * o.restart_period) | newly
    dx = jnp.linalg.norm(xr - s["x_anchor"], axis=-1)
    dy = jnp.linalg.norm(yr - s["y_anchor"], axis=-1)
    valid = fire & (dx > 1e-12) & (dy > 1e-12)
    om = jnp.exp(0.5 * jnp.log(jnp.where(valid, dx / jnp.maximum(dy, 1e-30),
                                         1.0)) + 0.5 * jnp.log(s["omega"]))
    omega = jnp.clip(jnp.where(valid, om, s["omega"]), o.omega_min,
                     o.omega_max)
    done0 = s["done"]
    status = jnp.where(~done0 & newly, OPTIMAL, s["status"])
    if o.detect_infeas:
        far = score > jnp.maximum(1e-3, 10.0 * tol)
        inf = far & (infeasible(P, yr - s["y_anchor"], o.certificate_tol)
                     | infeasible(P, yr, o.certificate_tol))
        unb = far & unbounded(P, xr - s["x_anchor"], o.certificate_tol)
        status = jnp.where(~done0 & ~newly & inf, INFEASIBLE, status)
        status = jnp.where((status == RUNNING) & unb, UNBOUNDED, status)
        newly = newly | ((status != RUNNING) & ~done0)
    act = fire & ~done0
    ax = act[:, None]
    return dict(
        s, x=jnp.where(ax, xr, s["x"]), y=jnp.where(ax, yr, s["y"]),
        x_sum=jnp.where(ax, 0.0, s["x_sum"]),
        y_sum=jnp.where(ax, 0.0, s["y_sum"]),
        x_anchor=jnp.where(ax, xr, s["x_anchor"]),
        y_anchor=jnp.where(ax, yr, s["y_anchor"]),
        omega=jnp.where(done0, s["omega"], omega),
        nwin=jnp.where(act, 0, s["nwin"]),
        restart_score=jnp.where(act, score, s["restart_score"]),
        score=jnp.where(done0, s["score"], score),
        done=done0 | newly, status=status)


def _sweep(P, s, o: Pdhg):
    """The iterations of one restart window, before its restart."""
    tau = o.step_margin * s["omega"] / s["Lnorm"]
    sigma = o.step_margin / (s["omega"] * s["Lnorm"])
    s = jax.lax.fori_loop(0, o.restart_period,
                          lambda _, v: _iterate(P, v, tau, sigma,
                                                o.precision), s)
    return dict(s, nwin=s["nwin"] + o.restart_period)


def _window(P, s, o: Pdhg):
    return _restart(P, _sweep(P, s, o), o)


def _fresh(s):
    """A warm state as a fixed-budget solve starts from it: window sums,
    anchors, counters and flags reset."""
    return dict(s, x_sum=jnp.zeros_like(s["x"]), y_sum=jnp.zeros_like(s["y"]),
                x_anchor=s["x"], y_anchor=s["y"],
                nwin=jnp.zeros(s["omega"].shape, jnp.int32),
                restart_score=jnp.full_like(s["omega"], jnp.inf),
                done=jnp.zeros(s["omega"].shape, bool),
                status=jnp.zeros(s["omega"].shape, jnp.int32))


def solve_fixed(P, s, windows: int, o: Pdhg):
    """`windows` restart windows from a warm state, no early exit."""
    s = _fresh(s)
    return jax.lax.fori_loop(0, windows, lambda _, v: _window(P, v, o), s)


# --------------------------------------------------------------------------
# the three planes of one wheel step
# --------------------------------------------------------------------------
def _with_nonant_terms(P, lin, quad):
    idx, d = P["nonant_idx"], P["d_non"]
    return dict(P, c=P["c"].at[:, idx].add(d * lin),
                q=P["q"].at[:, idx].add(d * d * quad))


def _prox(P, pre):
    """The PH subproblems at the carried (W, x-bar)."""
    rho = pre["rho"]
    return _with_nonant_terms(P, pre["W"] - rho * pre["xbar"],
                              jnp.broadcast_to(rho, pre["xbar"].shape))


def ph_step(P, pre, windows: int, o: Pdhg):
    """PH: prox subproblems at (W, x-bar), then x-bar and W from the new
    iterates."""
    rho = pre["rho"]
    s = solve_fixed(_prox(P, pre), pre["hub"], windows, o)
    x_non = P["d_non"] * s["x"][:, P["nonant_idx"]]
    p = P["p"][:, None]
    xbar = jnp.sum(p * x_non, 0) / jnp.maximum(jnp.sum(
        jnp.broadcast_to(p, x_non.shape), 0), 1e-30)
    return {"hub_x": s["x"], "xbar": xbar,
            "W": pre["W"] + rho * (x_non - xbar)}


def lag_step(P, W, solver, windows: int, o: Pdhg):
    """The Lagrangian bound at W: scenario LPs with W on the nonants."""
    Pl = _with_nonant_terms(P, W, jnp.zeros_like(W))
    s = solve_fixed(Pl, solver, windows, o)
    return {"lag_bound": jnp.sum(P["p"] * dual_objective(Pl, s["x"],
                                                         s["y"]))}


def _fixed(P, cand):
    """The recourse LPs of the candidate first stage: nonants fixed."""
    idx, d = P["nonant_idx"], P["d_non"]
    S, ncol = P["c"].shape
    xs = cand / d
    lo = jnp.broadcast_to(P["l"], (S, ncol)).at[:, idx].set(xs)
    hi = jnp.broadcast_to(P["u"], (S, ncol)).at[:, idx].set(xs)
    return dict(P, l=lo, u=hi)


def _fixed_start(Pf, solver):
    """The warm state clipped into the candidate's box, as the x-hat
    plane starts."""
    return dict(solver, x=jnp.clip(solver["x"], Pf["l"], Pf["u"]))


def xhat_step(P, cand, solver, windows: int, o: Pdhg, tail: dict, gates):
    """The recourse value of the candidate first stage: nonants fixed,
    a warm fixed-budget solve, the straggler tail on the worst lanes,
    then the feasibility- and compensation-gated expectation, the
    expectation before the gates (`xhat_expect`), and the iterates they
    were taken from."""
    Pf = _fixed(P, cand)
    s = _fixed_start(Pf, solver)
    s = solve_fixed(Pf, s, windows, dataclasses.replace(o,
                                                        detect_infeas=True))
    real = P["p"] > 0
    rp0, _, _ = kkt(Pf, s["x"], s["y"])
    k = tail["k"]
    if k > 0 and tail["windows"] > 0:
        to = dataclasses.replace(o, restart_period=tail["restart_period"])

        def rescue(s):
            _, sel = jax.lax.top_k(jnp.where(real, rp0, -1.0), k)
            Ps = dict(Pf, **{key: Pf[key][sel] for key in LANE_FIELDS})
            sub = {key: v[sel] for key, v in s.items()}
            sub["omega"] = jnp.full_like(sub["omega"], tail["omega0"])
            sub = solve_fixed(Ps, sub, tail["windows"], to)
            return {key: s[key].at[sel].set(sub[key]) for key in s}

        need = jnp.any(jnp.where(real, rp0 > gates["feas_tol"], False))
        s = jax.lax.cond(need, rescue, lambda v: v, s)
    obj, comp, rp, bad = _recourse(Pf, s, gates)
    feas = jnp.all(jnp.where(real, (rp <= gates["feas_tol"]) & ~bad, True))
    value = jnp.where(feas, jnp.sum(P["p"] * obj), jnp.inf)
    tight = jnp.sum(P["p"] * comp) <= gates["comp_tol"] * jnp.maximum(
        1.0, jnp.abs(value))
    feas = feas & tight
    return {"xhat_value": jnp.where(feas, value, jnp.inf),
            "xhat_expect": jnp.sum(P["p"] * obj),
            "xhat_x": s["x"], "xhat_y": s["y"], "xhat_status": s["status"]}


def _recourse(Pf, s, gates):
    """Each scenario's recourse objective with its compensation for the
    residual infeasibility added, the compensation, the relative primal
    residual, and whether a certificate of infeasibility or
    unboundedness ended its solve."""
    obj = jnp.sum(Pf["c"] * s["x"] + 0.5 * Pf["q"] * s["x"] ** 2, -1)
    comp = gates["comp_safety"] * jnp.sum(
        jnp.abs(s["y"]) * primal_residual(Pf, s["x"]), -1)
    rp, _, _ = kkt(Pf, s["x"], s["y"])
    bad = (s["status"] == INFEASIBLE) | (s["status"] == UNBOUNDED)
    return obj + comp, comp, rp, bad


@partial(jax.jit, static_argnames=("gates",))
def gate_excess(P, cand, s, value, gates):
    """The gates that a published inner bound `value` has to pass, on the
    x-hat plane's iterates s ({"x", "y", "status"}) at `cand`: how far
    they fall short, each as a share of its threshold (the worst real
    scenario's primal residual over feas_tol, less 1; 1 for a scenario
    certified infeasible or unbounded; the expected compensation over
    comp_tol * max(1, |value|), less 1), 0 where all hold; and the
    expectation of the compensated objective, ungated."""
    gates = dict(gates)
    Pf = _fixed(P, cand)
    obj, comp, rp, bad = _recourse(Pf, s, gates)
    real = P["p"] > 0
    over = jnp.max(jnp.where(real, rp, 0.0)) / gates["feas_tol"] - 1.0
    dead = jnp.any(real & bad).astype(obj.dtype)
    loose = jnp.sum(P["p"] * comp) / (gates["comp_tol"] * jnp.maximum(
        1.0, jnp.abs(value))) - 1.0
    return {"excess": jnp.maximum(jnp.maximum(over, loose),
                                  jnp.maximum(dead, 0.0)),
            "expect": jnp.sum(P["p"] * obj)}


def gates_of(wheel: dict) -> tuple:
    """The x-hat plane's publication gates, from the configuration."""
    return (("feas_tol", wheel["xhat_feas_tol"]),
            ("comp_tol", wheel["xhat_comp_tol"]),
            ("comp_safety", wheel["xhat_comp_safety"]))


def plane_options(wheel: dict, precision: dict) -> dict:
    """Each plane's Pdhg from the configuration; `precision` maps plane
    to the iteration precision to run it at."""
    common = wheel["pdhg_common"]

    def make(key, plane):
        p = wheel[key]
        return Pdhg(tol=p["tol"], restart_period=p["restart_period"],
                    precision=precision[plane],
                    step_margin=common["step_margin"],
                    restart_decay=common["restart_decay"],
                    max_window=common["max_window"],
                    omega_min=common["omega_min"],
                    omega_max=common["omega_max"],
                    certificate_tol=common["certificate_tol"])
    return {"hub": make("hub_pdhg", "hub"), "lag": make("lag_pdhg", "lag"),
            "xhat": make("xhat_pdhg", "xhat")}


def tail_size(wheel: dict, S: int) -> int:
    """Lanes the straggler tail re-solves: the configured k, at most an
    eighth of the batch (at least 8), rounded down to a power of two."""
    k = min(wheel["xhat_tail_k"], max(8, S // 8), S)
    return 1 << (k.bit_length() - 1) if k > 0 else 0


_ph_jit = jax.jit(ph_step, static_argnames=("windows", "o"))
_lag_jit = jax.jit(lag_step, static_argnames=("windows", "o"))


@partial(jax.jit, static_argnames=("windows", "o", "tail", "gates"))
def _xhat_jit(P, cand, solver, windows, o, tail, gates):
    return xhat_step(P, cand, solver, windows, o, dict(tail), dict(gates))


def wheel_step(P, pre: dict, cand, windows: tuple, wheel: dict,
               precision: dict) -> dict:
    """One fused-wheel step from the carried state `pre`, one plane at a
    time.  The Lagrangian plane prices the W that this reference's PH
    plane computed, the x-hat plane evaluates `cand`; windows = (hub,
    lag, x-hat)."""
    opts = plane_options(wheel, precision)
    S = P["c"].shape[0]
    tail = (("k", tail_size(wheel, S)),
            ("windows", wheel["xhat_tail_windows"]),
            ("omega0", wheel["xhat_tail_omega0"]),
            ("restart_period", wheel["xhat_tail_restart_period"]))
    out = dict(_ph_jit(P, pre, windows=windows[0], o=opts["hub"]))
    out.update(_lag_jit(P, out["W"], pre["lag"], windows=windows[1],
                        o=opts["lag"]))
    out.update(_xhat_jit(P, cand, pre["xhat"], windows[2], opts["xhat"],
                         tail, gates_of(wheel)))
    return out


# --------------------------------------------------------------------------
# the precision probe: one restart window of each plane
# --------------------------------------------------------------------------
def _probe_window(P, s, o: Pdhg) -> dict:
    """One restart window from s, as solve_fixed runs its first: the
    iterates after it (`x`, `y`), and the two that its restart chooses
    between, the last iterate and the window's average.  The restart
    keeps one of them in each scenario, on a comparison of their KKT
    scores that a rounding can tip."""
    s = _sweep(P, _fresh(s), o)
    navg = jnp.maximum(s["nwin"], 1).astype(s["x"].dtype)[:, None]
    r = _restart(P, s, o)
    return {"x": r["x"], "y": r["y"], "x_last": s["x"], "y_last": s["y"],
            "x_avg": s["x_sum"] / navg, "y_avg": s["y_sum"] / navg}


@partial(jax.jit, static_argnames=("hub", "lag", "xhat"))
def _probe_jit(P, pre, cand, hub, lag, xhat):
    Pl = _with_nonant_terms(P, pre["W"], jnp.zeros_like(pre["W"]))
    Pf = _fixed(P, cand)
    return {"hub": _probe_window(_prox(P, pre), pre["hub"], hub),
            "lag": _probe_window(Pl, pre["lag"], lag),
            "xhat": _probe_window(Pf, _fixed_start(Pf, pre["xhat"]), xhat)}


def probe_step(P, pre: dict, cand, wheel: dict, precision: dict) -> dict:
    """One restart window of each plane, at its configured period, from
    the carried state `pre`: the hub at (W, x-bar), the Lagrangian plane
    at the W carried in, the x-hat plane at `cand` without its tail.
    {plane: {"x", "y", "x_last", "y_last", "x_avg", "y_avg"}}"""
    o = plane_options(wheel, precision)
    return _probe_jit(P, pre, cand, o["hub"], o["lag"], o["xhat"])
