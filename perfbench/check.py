"""The output check: the steps the window produced against the reference.

For each step drawn from the seed (a solve and an iteration of it, see
the traffic's `check`), the wheel states before and after the step were
kept on the device.  After the window the reference recomputes the step
from the state before it, and three numbers measure how far the
program's outputs lie from the reference's:

    ph_step      the PH plane: PDHG iterates of the subproblems (x and y),
                 x-bar (largest gap) and W; W and x-bar also against the
                 PH update of the program's own iterates
    lag_plane    the Lagrangian plane, which prices the reference's own
                 W: its iterates, and its expected dual value, the outer
                 bound
    xhat_plane   the x-hat recourse plane: its iterates, and its gated
                 recourse value, the inner bound (1 where one side
                 publishes a value and the other does not)

Iterates and W are judged scenario by scenario, by the worst: a fault on
a few scenarios (one tile of a kernel) reads as large as one on all.

A bound is folded into its plane's number rather than compared alone:
where neither side publishes (both gated off) a bound alone reads 0 for
the program and the control alike and could separate nothing.

Each is compared with its limit in perfbench/cells/<cell>.json; PERF.md
gives the readings each limit was set from.  `compare` also serves the
control (perfbench/control.py), which puts the reference at a lower
precision in the program's place.
"""
from __future__ import annotations

import math

import jax.numpy as jnp

from perfbench import reference
from perfbench import traffic as traffic_mod

NAMES = ("ph_step", "lag_plane", "xhat_plane")
#: the reading of a number that could not be computed (NaN, inf)
FAILED = 1e30


def lane_gaps(a, r):
    """Each scenario's distance over its size: the norm of its row of
    a - r over the larger of its row's norm in r and the median row's
    (some rows are all but zero)."""
    a = jnp.reshape(jnp.asarray(a, jnp.float32), (jnp.shape(a)[0], -1))
    r = jnp.reshape(jnp.asarray(r, jnp.float32), a.shape)
    rn = jnp.linalg.norm(r, axis=1)
    den = jnp.maximum(jnp.maximum(rn, jnp.median(rn)), 1e-30)
    return jnp.linalg.norm(a - r, axis=1) / den


def _worst(a, r) -> float:
    """The worst scenario's gap."""
    return float(jnp.max(lane_gaps(a, r)))


def _scalar_gap(a, r) -> float:
    a, r = float(a), float(r)
    if not (math.isfinite(a) and math.isfinite(r)):
        return 0.0 if a == r else 1.0
    return abs(a - r) / max(1.0, abs(r))


def _vec_gap(a, r) -> float:
    a = jnp.reshape(jnp.asarray(a, jnp.float32), (-1,))
    r = jnp.reshape(jnp.asarray(r, jnp.float32), (-1,))
    return float(jnp.max(jnp.abs(a - r))
                 / jnp.maximum(1.0, jnp.max(jnp.abs(r))))


def compare(out: dict, ref: dict, P: dict, pre: dict) -> dict:
    """The three numbers for one step; `out` carries the program's
    outputs (system.step_outputs), `ref` reference.wheel_step's, `pre`
    the state the step started from.  Per-scenario quantities are judged
    by the worst scenario; W and x-bar are also held, over every
    scenario, to the PH update of the program's own iterates."""
    xbar_o = jnp.reshape(jnp.asarray(out["xbar"]), (-1, ref["xbar"].shape[-1]))
    x_non = P["d_non"] * jnp.asarray(out["hub"]["x"])[:, P["nonant_idx"]]
    xbar_own = jnp.sum(P["p"][:, None] * x_non, 0)
    W_own = pre["W"] + pre["rho"] * (x_non - xbar_o[0])
    return {
        "ph_step": max(_worst(out["hub"]["x"], ref["hub"]["x"]),
                       _worst(out["hub"]["y"], ref["hub"]["y"]),
                       _worst(out["W"], ref["W"]),
                       _vec_gap(xbar_o[0], ref["xbar"]),
                       _worst(out["W"], W_own),
                       _vec_gap(xbar_o, jnp.broadcast_to(
                           xbar_own, xbar_o.shape))),
        "lag_plane": max(_worst(out["lag"]["x"], ref["lag"]["x"]),
                         _worst(out["lag"]["y"], ref["lag"]["y"]),
                         _scalar_gap(out["lag_bound"], ref["lag_bound"])),
        "xhat_plane": max(_worst(out["xhat"]["x"], ref["xhat"]["x"]),
                          _worst(out["xhat"]["y"], ref["xhat"]["y"]),
                          _scalar_gap(out["xhat_value"],
                                      ref["xhat_value"])),
    }


def replay(spec: dict, inst: dict, step: dict, seed: int,
           precision: dict) -> tuple[dict, dict, dict]:
    """(program's outputs, reference's outputs, the scaled problem) of
    one captured step."""
    cfg, tr = spec["config"], spec["traffic"]
    wheel = cfg["wheel"]
    cp = traffic_mod.client_present(cfg["instance"], tr["scenarios"], seed,
                                    step["solve"])
    P = reference.scaled_problem(inst, cp)
    pre, post = step["pre"], step["post"]
    lag_w, xhat_w = step["windows"]
    ref = reference.wheel_step(
        P, pre, jnp.reshape(post["xhat_cand"], (-1,)),
        (wheel["subproblem_windows"], lag_w, xhat_w), wheel, precision)
    return post, ref, P


def stated_precision(wheel: dict) -> dict:
    """Each plane's iteration precision as the configuration states it:
    the precision the reference runs it at."""
    return {"hub": wheel["hub_pdhg"]["iter_precision"],
            "lag": wheel["lag_pdhg"]["iter_precision"],
            "xhat": wheel["xhat_pdhg"]["iter_precision"]}


def run_checks(spec: dict, rec: dict, captured: list, check_at: set,
               seed: int) -> dict:
    """{name: {"value", "limit"}} over every captured step (the worst
    step per number); a drawn step that the window never reached makes
    `missing_steps` nonzero."""
    limits = spec["limits"]["limits"]
    worst = dict.fromkeys(NAMES, 0.0)
    got = set()
    for step in captured:
        got.add((step["solve"], step["iteration"]))
        post, ref, P = replay(spec, rec["inst"], step, seed,
                              stated_precision(spec["config"]["wheel"]))
        for k, v in compare(post, ref, P, step["pre"]).items():
            # a NaN reading fails; the result line carries finite numbers
            worst[k] = max(worst[k], v if math.isfinite(v) else FAILED)
    out = {k: {"value": worst[k], "limit": limits[k]} for k in NAMES}
    out["missing_steps"] = {"value": len(check_at - got), "limit": 0}
    return out
