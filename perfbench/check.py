"""The output check: the steps the window produced against the reference.

For each step drawn from the seed (a solve and an iteration of it, see
the traffic's `check`), the wheel states before and after the step were
kept on the device.  After the window the reference recomputes the step
from the state before it, at the precision the configuration states, and
four numbers measure how far the program lies from it:

    ph_step          W and x-bar against the PH update of the program's
                     own iterates, over every scenario
    lag_plane        the outer bound the Lagrangian plane publishes, the
                     reference's plane pricing the reference's own W
    xhat_plane       the inner bound the x-hat plane publishes: against
                     the reference's expectation of the same recourse
                     objective, against that expectation on the plane's
                     own iterates, and how far those iterates fall short
                     of the gates a published bound has to pass; 0 where
                     the program publishes none
    precision_probe  one restart window of each plane, at its configured
                     period, from the state before the step, through the
                     programs the wheel dispatches (`program_probe`,
                     system.probe), against the reference's
                     (reference.probe_step): each plane's x and y, by
                     the worst scenario, over the same gap of the
                     reference summed in another order

The step numbers judge only what rounding does not scatter.  The planes'
LPs are degenerate: over a step's hundreds of PDHG iterations, with a
restart after every window, their iterates drift along optimal faces,
and a matvec as precise as the configuration states but summed in
another order moves them, and every per-scenario quantity made of them,
as far as a fault or a precision cut does (PERF.md gives the readings).
The bounds, W and x-bar against the program's own iterates, and the
gates on the x-hat plane's own iterates, do not scatter.  What a
precision cut changes, the probe reads.  Over one window a rounding
carries into the iterates as far as the step's state lets it, which
differs from step to step by more than a precision cut multiplies it;
so the probe measures each plane's gap against what a rounding of the
stated precision, summed in another order, does in the same step.  The
one restart that ends the window keeps either the last iterate or the
window's average, on a comparison a rounding can tip, so each scenario
is judged against the nearer of the reference's two.

Each number is compared with its limit in perfbench/cells/<cell>.json;
PERF.md gives the readings each limit was set from.  `all_parts` also
serves perfbench/control.py, which puts other things in the program's
place.
"""
from __future__ import annotations

import math

import jax.numpy as jnp

from perfbench import reference
from perfbench import traffic as traffic_mod

NAMES = ("ph_step", "lag_plane", "xhat_plane", "precision_probe")
PLANES = ("hub", "lag", "xhat")
#: the reading of a number that could not be computed (NaN, inf)
FAILED = 1e30
#: the least yardstick gap of the precision probe: a few units of
#: float32's last place over a window's iterations
YARD_FLOOR = 1e-6


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


def _xhat_parts(out: dict, ref: dict, P: dict, wheel: dict) -> dict:
    """A published inner bound against the reference's expectation
    (`value`), against the same expectation on the plane's own iterates
    (`value_own`), and how far those iterates fall short of the gates
    (`gates`, reference.gate_excess)."""
    value = float(out["xhat_value"])
    if value == math.inf:
        # no bound published, which is late, not wrong
        return dict.fromkeys(("value", "value_own", "gates"), 0.0)
    own = reference.gate_excess(
        P, jnp.reshape(out["xhat_cand"], (-1,)),
        {"x": out["xhat_x"], "y": out["xhat_y"],
         "status": out["xhat_status"]}, value, reference.gates_of(wheel))
    return {"value": _scalar_gap(value, ref["xhat_expect"]),
            "value_own": _scalar_gap(value, own["expect"]),
            "gates": float(own["excess"])}


def step_parts(out: dict, ref: dict, P: dict, pre: dict,
               wheel: dict) -> dict:
    """The parts of the three step numbers; `out` carries the program's
    outputs (system.step_outputs), `ref` reference.wheel_step's, `pre`
    the state the step started from."""
    xbar_o = jnp.reshape(jnp.asarray(out["xbar"]),
                         (-1, pre["xbar"].shape[-1]))
    x_non = P["d_non"] * jnp.asarray(out["hub_x"])[:, P["nonant_idx"]]
    xbar_own = jnp.sum(P["p"][:, None] * x_non, 0)
    W_own = pre["W"] + pre["rho"] * (x_non - xbar_o[0])
    return {
        "ph_step": {"W_own": _worst(out["W"], W_own),
                    "xbar_own": _vec_gap(xbar_o, jnp.broadcast_to(
                        xbar_own, xbar_o.shape))},
        "lag_plane": {"bound": _scalar_gap(out["lag_bound"],
                                           ref["lag_bound"])},
        "xhat_plane": _xhat_parts(out, ref, P, wheel),
    }


def plane_gap(o: dict, r: dict) -> float:
    """The worst scenario's gap of a plane's iterates after the probe's
    window, `o` against the reference's `r`: each scenario against the
    nearer of r's last iterate and window average, by the larger of its
    x and y gaps."""
    g = {c: jnp.maximum(lane_gaps(o["x"], r["x_" + c]),
                        lane_gaps(o["y"], r["y_" + c]))
         for c in ("last", "avg")}
    return float(jnp.max(jnp.minimum(g["last"], g["avg"])))


def probe_parts(out: dict, ref: dict, yard: dict) -> dict:
    """The precision probe's parts: each plane's gap, `out` the
    stand-in's, `ref` the reference's, over the yardstick's gap `yard`
    in the same step (probe_reference)."""
    return {plane: plane_gap(out[plane], ref[plane]) / yard[plane]
            for plane in PLANES}


def numbers(parts: dict) -> dict:
    """Each number: the largest of its parts; a reading that is not
    finite fails."""
    return {name: max(v if math.isfinite(v) else FAILED
                      for v in parts[name].values())
            for name in NAMES}


def problem(spec: dict, inst: dict, step: dict, seed: int) -> dict:
    """The reference's scaled problem of one captured step's solve."""
    cfg, tr = spec["config"], spec["traffic"]
    cp = traffic_mod.client_present(cfg["instance"], tr["scenarios"], seed,
                                    step["solve"])
    return reference.scaled_problem(inst, cp)


def _cand(step: dict):
    return jnp.reshape(step["post"]["xhat_cand"], (-1,))


def replay(spec: dict, P: dict, step: dict, precision: dict) -> dict:
    """The reference's step from the captured state, its planes run at
    `precision`."""
    wheel = spec["config"]["wheel"]
    lag_w, xhat_w = step["windows"]
    return reference.wheel_step(
        P, step["pre"], _cand(step),
        (wheel["subproblem_windows"], lag_w, xhat_w), wheel, precision)


def probe(spec: dict, P: dict, step: dict, precision: dict) -> dict:
    """The reference's probe of one captured step (reference.probe_step)."""
    return reference.probe_step(P, step["pre"], _cand(step),
                                spec["config"]["wheel"], precision)


def probe_reference(spec: dict, P: dict, step: dict) -> tuple:
    """The reference's probe at the stated precision, and its yardstick:
    each plane's gap (at least YARD_FLOOR) of the same probe with every
    contraction summed in thirds, as precise and rounded in another
    order.  How far a rounding carries over a window differs from step
    to step by more than a precision cut multiplies it, so the probe
    reads each stand-in's gap over what such a rounding does in the
    same step."""
    stated = stated_precision(spec["config"]["wheel"])
    ref = probe(spec, P, step, stated)
    yard = probe(spec, P, step, {k: "split3-" + v for k, v in stated.items()})
    return ref, {p: max(plane_gap(yard[p], ref[p]), YARD_FLOOR)
                 for p in PLANES}


def stated_precision(wheel: dict) -> dict:
    """Each plane's iteration precision as the configuration states it:
    the precision the reference runs it at."""
    return {"hub": wheel["hub_pdhg"]["iter_precision"],
            "lag": wheel["lag_pdhg"]["iter_precision"],
            "xhat": wheel["xhat_pdhg"]["iter_precision"]}


def all_parts(spec: dict, P: dict, step: dict, ref: dict, out: dict,
              probe_out: dict, probe_ref: tuple) -> dict:
    """Every part of the four numbers for one step: `out` and
    `probe_out` are what stands in the program's place, `ref` the
    reference's step and `probe_ref` its probe and yardstick
    (probe_reference)."""
    parts = step_parts(out, ref, P, step["pre"], spec["config"]["wheel"])
    parts["precision_probe"] = probe_parts(probe_out, *probe_ref)
    return parts


def run_checks(spec: dict, rec: dict, captured: list, check_at: set,
               seed: int, program_probe) -> dict:
    """{name: {"value", "limit"}} over every captured step (the worst
    step per number); a drawn step that the window never reached makes
    `missing_steps` nonzero.  `program_probe(program, cand)` runs the
    program's side of the precision probe (system.probe)."""
    limits = spec["limits"]["limits"]
    stated = stated_precision(spec["config"]["wheel"])
    worst = dict.fromkeys(NAMES, 0.0)
    got = set()
    for step in captured:
        got.add((step["solve"], step["iteration"]))
        P = problem(spec, rec["inst"], step, seed)
        ref = replay(spec, P, step, stated)
        probe_out = program_probe(step["program"], step["post"]["xhat_cand"])
        for k, v in numbers(all_parts(
                spec, P, step, ref, step["post"], probe_out,
                probe_reference(spec, P, step))).items():
            worst[k] = max(worst[k], v)
        del P, ref, probe_out
    out = {k: {"value": worst[k], "limit": limits[k]} for k in NAMES}
    out["missing_steps"] = {"value": len(check_at - got), "limit": 0}
    return out
