"""The system under test, as the benchmark drives it.

This is the one file of the benchmark that imports the program
(`mpisppy_tpu`).  It builds the wheel that `generic_cylinders.main`
builds for `--fused-wheel --lagrangian --xhatxbar` (PHHub + FusedPH with
the fused Lagrangian and xhat-xbar planes), hands it a scenario batch
built vectorised from the traffic's draw, and spins it.  The benchmark's
own hooks ride the program's extension and converger seams: they close
the measured window, start and stop the profiler, and keep references to
the wheel states around the steps that the output check replays.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

import jax
import jax.numpy as jnp

from mpisppy_tpu import generic_cylinders
from mpisppy_tpu.algos import fused_wheel as fw
from mpisppy_tpu.algos import ph as ph_mod
from mpisppy_tpu.core import batch as batch_mod
from mpisppy_tpu.core.tree import two_stage_tree
from mpisppy_tpu.models import sslp as sslp_mod
from mpisppy_tpu.spin_the_wheel import WheelSpinner
from mpisppy_tpu.utils import cfg_vanilla as vanilla
from perfbench.reference import CARRIED

MODULE = "mpisppy_tpu.models.sslp"


def program_argv(config: dict, traffic: dict) -> list[str]:
    """The CLI arguments of the wheel each solve runs (chip_smoke.SSLP_ARGS
    with the traffic's gap and cap)."""
    inst, wheel = config["instance"], config["wheel"]
    argv = ["--module-name", MODULE,
            "--n-servers", str(inst["n_servers"]),
            "--n-clients", str(inst["n_clients"]),
            "--num-scens", str(traffic["scenarios"]),
            "--default-rho", repr(float(wheel["default_rho"])),
            "--subproblem-windows", str(wheel["subproblem_windows"]),
            "--iter-precision", wheel["hub_pdhg"]["iter_precision"],
            "--pdhg-tol", repr(float(wheel["hub_pdhg"]["tol"])),
            "--rel-gap", repr(float(traffic["rel_gap"])),
            "--max-iterations", str(traffic["max_iterations"]),
            "--convthresh", "0",
            "--lagrangian", "--xhatxbar", "--fused-wheel"]
    if config["lp_relax"]:
        argv.append("--sslp-lp-relax")
    return argv


def _precision_name(p) -> str:
    return "highest" if p is None else str(p).lower()


def stated_options(opt) -> dict:
    """What the built wheel will run, in the configuration's terms (the
    tail re-solve's omega0 and restart period are constants of
    fused_wheel._tail_rescue)."""
    from mpisppy_tpu.algos import xhat as xhat_mod
    o, w = opt.options, opt.wheel_options
    hub, lag, xh = o.pdhg, w.lag_pdhg, w.xhat_pdhg

    def pd(p):
        return {"tol": p.tol, "restart_period": p.restart_period,
                "omega0": p.omega0,
                "iter_precision": _precision_name(p.iter_precision)}
    return {
        "default_rho": o.default_rho,
        "subproblem_windows": o.subproblem_windows,
        "iter0_windows": o.iter0_windows,
        "hub_pdhg": pd(hub),
        "lag_windows": w.lag_windows, "lag_pdhg": pd(lag),
        "xhat_windows": w.xhat_windows,
        "lean_xhat_windows": w.lean_xhat_windows,
        "xhat_pdhg": pd(xh),
        "xhat_tail_k": w.xhat_tail_k,
        "xhat_tail_windows": w.xhat_tail_windows,
        "xhat_tail_omega0": 0.03, "xhat_tail_restart_period": 160,
        "xhat_feas_tol": w.xhat_feas_tol, "xhat_comp_tol": w.xhat_comp_tol,
        "xhat_comp_safety": xhat_mod.COMP_SAFETY,
        "pdhg_common": {"step_margin": hub.step_margin,
                        "restart_decay": hub.restart_decay,
                        "max_window": hub.max_window,
                        "omega_min": hub.omega_min,
                        "omega_max": hub.omega_max,
                        "certificate_tol": hub.certificate_tol},
    }


def check_options(opt, config: dict) -> None:
    """Refuse to run a wheel that departs from what the configuration
    states: the reference follows the configuration, not the program."""
    have = stated_options(opt)
    want = config["wheel"]
    if have != want:
        diff = {k: (have.get(k), want.get(k)) for k in set(have) | set(want)
                if have.get(k) != want.get(k)}
        raise SystemExit(f"the program's wheel departs from the "
                         f"configuration: {diff}")
    for lane_opts in (opt.options.pdhg, opt.wheel_options.lag_pdhg,
                      opt.wheel_options.xhat_pdhg):
        if lane_opts.lane_guard or lane_opts.telemetry:
            raise SystemExit("lane guard and kernel counters must be off")


class Template:
    """The shared part of every batch of one configuration: the scaled
    LP of scenario 0 through the program's own batch compiler.  sslp's
    randomness is in the client rows' bounds alone, so a batch of S
    scenarios is this template with (S, m) row bounds."""

    def __init__(self, inst: dict, lp_relax: bool):
        n = int(inst["NumServers"])
        m = int(inst["NumClients"])
        spec = sslp_mod._build_spec(inst, np.ones(m), "template", 1.0)
        if lp_relax:
            spec.integer = np.zeros_like(spec.integer)
        self.b1 = batch_mod.from_specs([spec])
        self.n_servers = n

    def batch(self, client_present: np.ndarray):
        """The batch `batch_mod.from_specs` builds for these scenarios,
        built vectorised.  Client-row bounds are 0/1, so scaling them in
        float32 is exact and equals from_specs' float64 product."""
        b1, n = self.b1, self.n_servers
        S = client_present.shape[0]
        d_row = b1.d_row
        cp = jnp.asarray(client_present, d_row.dtype)
        # one scenario's row bounds are shared, shape (m,)
        rows = jnp.concatenate(
            [jnp.broadcast_to(b1.qp.bl[:n], (S, n)), cp * d_row[n:]], 1)
        rows_u = jnp.concatenate(
            [jnp.broadcast_to(b1.qp.bu[:n], (S, n)), cp * d_row[n:]], 1)
        qp = dataclasses.replace(
            b1.qp, c=jnp.broadcast_to(b1.qp.c[0], (S, b1.qp.n)),
            q=jnp.zeros((S, b1.qp.n), b1.qp.q.dtype), bl=rows, bu=rows_u)
        N = b1.num_nonants
        tree = two_stage_tree(S, N)
        return dataclasses.replace(
            b1, qp=qp, p=jnp.full((S,), np.float32(1.0 / S), b1.p.dtype),
            node_of_slot=jnp.asarray(tree.node_of_slot()), tree=tree,
            num_real=S)


class Hooks:
    """The benchmark's seams into one wheel: the PH extension hooks
    (miditer / post_solve_loop) and the converger the PH loop asks each
    iteration.  Nothing here touches the device unless a check or the
    profiler asks for it."""

    def __init__(self, run, solve: int):
        self.run = run
        self.solve = solve
        self.opt = None
        self._pre = None

    # the PH driver calls the factory with itself
    def __call__(self, opt):
        self.opt = opt
        return self

    def _windows(self):
        """The Lagrangian and x-hat windows this step dispatches, as the
        program's budget controllers set them.  (A wheel on the
        monolithic, unsplit path would run its full budgets instead: the
        check would then read it as not correct, never the reverse.)"""
        b = self.opt._budgets
        return b["lag"].windows(), b["xhat"].windows()

    def miditer(self):
        k = self.opt._iter
        self.run.on_iteration_start(self, k)
        if (self.solve, k) in self.run.check_at:
            self._pre = (step_inputs(self.opt.wstate), self._windows(),
                         program_inputs(self.opt))

    def post_solve_loop(self):
        k = self.opt._iter
        if self._pre is not None and (self.solve, k) in self.run.check_at:
            pre, windows, program = self._pre
            self.run.captured.append(
                {"solve": self.solve, "iteration": k, "pre": pre,
                 "post": step_outputs(self.opt.wstate), "windows": windows,
                 "program": program})
            self._pre = None

    def is_converged(self) -> bool:
        return self.run.window_over()


class System:
    """Builds and spins wheels for one configuration and traffic mix."""

    def __init__(self, config: dict, traffic: dict, inst: dict):
        self.config, self.traffic, self.inst = config, traffic, inst
        self.cfg = generic_cylinders._parse_args(
            sslp_mod, program_argv(config, traffic))
        self.template = Template(inst, config["lp_relax"])

    def wheel(self, batch, hooks: Hooks) -> WheelSpinner:
        cfg = self.cfg
        names = [f"Scenario{i + 1}" for i in range(batch.num_real)]
        hub = vanilla.ph_hub(cfg, batch, scenario_names=names,
                             converger=hooks, extensions=hooks)
        spokes = [vanilla.lagrangian_spoke(cfg), vanilla.xhatxbar_spoke(cfg)]
        hub, spokes = generic_cylinders._fuse_wheel(cfg, hub, spokes,
                                                    tree=batch.tree)
        wheel = WheelSpinner(hub, spokes).build()
        check_options(wheel.opt, self.config)
        return wheel

    def solve(self, client_present: np.ndarray, hooks: Hooks) -> dict:
        """One solve: batch, wheel, spin.  Returns its record."""
        t0 = time.perf_counter()
        batch = self.template.batch(client_present)
        wheel = self.wheel(batch, hooks)
        wheel.spin()
        hub = wheel.spcomm
        rows = [dict(r) for r in hub.trace]
        return {"seconds": time.perf_counter() - t0,
                "iterations": int(wheel.opt._iter),
                "converged": getattr(hub, "_term_reason", None)
                == "converged",
                "outer": float(hub.BestOuterBound),
                "inner": float(hub.BestInnerBound),
                "rel_gap": float(hub.compute_gaps()[1]),
                "rows": rows, "wheel": wheel}

    def warm_variants(self, opt) -> None:
        """Compile, from the cache, the plane variants that a solve may
        switch to after set-up has ended: the lean x-hat budget and the
        ceil/floor candidate roundings, and the expected objective that
        the PH driver reads once its loop ends."""
        opt.Eobjective()
        st = opt.wstate
        lean = opt._budgets["xhat"].lean
        out = fw.xhat_plane(opt.batch, st.xhat_cand, st.xhat_solver,
                            opt.wheel_options, lean)
        for mode in ("nearest", "ceil", "floor"):
            out = (out, fw._round_xbar(opt.batch, st.ph.xbar_nodes, mode))
        jax.block_until_ready(out)


def probe(program: dict, cand, precision: dict | None = None) -> dict:
    """One restart window of each plane, at its configured period, from
    the state before a captured step (`program`, program_inputs),
    through the programs the wheel dispatches with the options it runs
    them with, but one window and the x-hat tail off: the Lagrangian
    plane prices the W carried into the step, the x-hat plane evaluates
    `cand`.  `precision` ({plane: iteration precision}, the control's)
    replaces what the wheel runs.  {plane: {"x", "y"}}, as
    reference.probe_step."""
    batch, o, w = program["batch"], program["options"], program["wheel"]
    precision = precision or {}

    def at(p, plane):
        return dataclasses.replace(p, iter_precision=precision.get(
            plane, p.iter_precision))
    hub = ph_mod.ph_iterk(batch, program["ph"], ph_mod.kernel_opts(
        dataclasses.replace(o, subproblem_windows=1,
                            pdhg=at(o.pdhg, "hub"))))
    w1 = dataclasses.replace(w, lag_pdhg=at(w.lag_pdhg, "lag"),
                             xhat_pdhg=at(w.xhat_pdhg, "xhat"),
                             xhat_tail_windows=0)
    lag = fw.lag_plane(batch, program["ph"].W, program["lag"], w1, 1)[0]
    xhat = fw.xhat_plane(batch, cand, program["xhat"], w1, 1)[0]
    return {"hub": _judged(hub.solver), "lag": _judged(lag),
            "xhat": _judged(xhat)}


def _judged(s) -> dict:
    """References to the iterates the probe is judged by."""
    return {"x": s.x, "y": s.y}


def _warm(s):
    """A solver state as a fixed-budget solve reads it: the window sums
    and anchors, which solve_fixed resets, alias the iterates, so that
    keeping the state keeps no buffer that step_inputs does not."""
    return dataclasses.replace(s, x_sum=s.x, y_sum=s.y, x_anchor=s.x,
                               y_anchor=s.y)


def program_inputs(opt) -> dict:
    """What `probe` needs of the wheel before a step: its batch and
    options, and references to its PH and plane states."""
    st = opt.wstate
    return {"batch": opt.batch, "options": opt.options,
            "wheel": opt.wheel_options,
            "ph": dataclasses.replace(st.ph, solver=_warm(st.ph.solver)),
            "lag": _warm(st.lag_solver), "xhat": _warm(st.xhat_solver)}


def step_inputs(wstate) -> dict:
    """References to what the reference needs of the state before a
    step: no copy, and only these buffers stay alive."""
    def solver(s):
        return {f: getattr(s, f) for f in CARRIED}
    ph = wstate.ph
    return {"hub": solver(ph.solver), "W": ph.W, "xbar": ph.xbar,
            "rho": ph.rho, "lag": solver(wstate.lag_solver),
            "xhat": solver(wstate.xhat_solver)}


def step_outputs(wstate) -> dict:
    """References to the step's outputs that the check compares."""
    ph, xs = wstate.ph, wstate.xhat_solver
    return {"hub_x": ph.solver.x, "W": ph.W, "xbar": ph.xbar,
            "lag_bound": wstate.lag_bound, "xhat_cand": wstate.xhat_cand,
            "xhat_value": wstate.xhat_value, "xhat_x": xs.x,
            "xhat_y": xs.y, "xhat_status": xs.status}
