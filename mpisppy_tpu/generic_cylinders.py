###############################################################################
# Generic driver CLI — the flagship entry point
# (ref:mpisppy/generic_cylinders.py:32-312,396-457):
#
#   python -m mpisppy_tpu --module-name mpisppy_tpu.models.farmer \
#          --num-scens 3 --default-rho 1.0 --lagrangian --xhatxbar \
#          --rel-gap 0.01 [--EF] [--solution-base-name out]
#
# The model module supplies the reference's 5-function API
# (ref:mpisppy/generic_cylinders.py:43-52): scenario_creator,
# scenario_names_creator, inparser_adder, kw_creator,
# scenario_denouement — returning ScenarioSpec instead of Pyomo models.
# Multistage modules additionally provide make_tree(branching_factors).
###############################################################################
from __future__ import annotations

import importlib
import json
import sys

from mpisppy_tpu import global_toc, telemetry
from mpisppy_tpu.core import batch as batch_mod
from mpisppy_tpu.resilience.faults import PreemptionError
from mpisppy_tpu.spin_the_wheel import WheelSpinner
from mpisppy_tpu.utils import cfg_vanilla as vanilla
from mpisppy_tpu.utils.config import Config


def _parse_args(module, args=None):
    """ref:generic_cylinders.py:32-80."""
    cfg = Config()
    cfg.add_to_config("module_name", "model module to import", str, None)
    cfg.add_to_config("EF", "solve the extensive form directly", bool,
                      False)
    cfg.add_to_config("solution_base_name",
                      "write first-stage solution files with this base",
                      str, None)
    # the model module declares its flags FIRST: add_to_config ignores
    # re-declaration, so a module's defaults (e.g. hydro's
    # branching_factors=[3,3]) win over the canned groups' None defaults
    module.inparser_adder(cfg)
    cfg.num_scens_optional()
    cfg.popular_args()
    cfg.ph_args()
    cfg.aph_args()
    cfg.two_sided_args()
    cfg.fwph_args()
    cfg.lagrangian_args()
    cfg.lagranger_args()
    cfg.subgradient_args()
    cfg.xhatxbar_args()
    cfg.fused_wheel_args()
    cfg.xhatshuffle_args()
    cfg.slama_args()
    cfg.gradient_args()
    cfg.dynamic_rho_args()
    cfg.reduced_costs_args()
    cfg.ph_ob_args()
    cfg.cross_scenario_cuts_args()
    cfg.lshaped_args()
    cfg.converger_args()
    cfg.presolve_args()
    cfg.resilience_args()
    cfg.telemetry_args()
    cfg.dispatch_args()
    cfg.wxbar_read_write_args()
    cfg.proper_bundle_config()
    cfg.multistage()
    cfg.parse_command_line("mpisppy_tpu.generic_cylinders", args)
    cfg.checker()
    return cfg


def _model_plumbing(cfg, module):
    """Names, creator kwargs, and tree — the scenario count may come
    from --num-scens, the instance (e.g. sslp_15_45_10), or the
    branching factors (multistage)."""
    num_scens = cfg.get("num_scens")
    kwargs = module.kw_creator(cfg)
    if num_scens is None:
        num_scens = kwargs.get("num_scens")
    if num_scens is None and cfg.get("branching_factors"):
        import math
        num_scens = math.prod(cfg["branching_factors"])
    if num_scens is None:
        raise SystemExit("need --num-scens (or an instance implying it)")
    names = module.scenario_names_creator(int(num_scens))
    tree = None
    if hasattr(module, "make_tree") and cfg.get("branching_factors"):
        tree = module.make_tree(tuple(cfg["branching_factors"]))
    elif hasattr(module, "make_tree"):
        tree = module.make_tree()
    return names, kwargs, tree


def _presolve_maybe(cfg, batch):
    if not cfg.get("presolve"):
        return batch
    from mpisppy_tpu.ops.fbbt import presolve_batch
    try:
        batch, info = presolve_batch(
            batch, n_sweeps=cfg.get("presolve_sweeps", 3))
    except ValueError as e:
        raise SystemExit(f"presolve: {e}")
    global_toc(f"presolve: tightened {info['tightened_bounds']} bounds",
               cfg.get("display_progress", False))
    return batch


def _build_batch(cfg, module):
    names, kwargs, tree = _model_plumbing(cfg, module)
    if cfg.get("scenarios_per_bundle"):
        # proper bundles: PH runs over bundle-EF subproblems
        # (ref:generic_cylinders.py:316-393 bundle paths)
        from mpisppy_tpu.utils.pickle_bundle import check_args
        from mpisppy_tpu.utils.proper_bundler import ProperBundler
        if tree is not None:
            raise SystemExit("proper bundles are two-stage only "
                             "(ref:proper_bundler.py:22); drop "
                             "--scenarios-per-bundle or the "
                             "branching factors")
        check_args(cfg)
        if cfg.get("num_scens") is None:
            cfg.quick_assign("num_scens", int, len(names))
        pb = ProperBundler(module)
        num_buns = len(names) // int(cfg["scenarios_per_bundle"])
        kwargs = pb.kw_creator(cfg)
        names = pb.bundle_names_creator(num_buns, cfg=cfg)
        specs = [pb.scenario_creator(nm, **kwargs) for nm in names]
        return _presolve_maybe(cfg, batch_mod.from_specs(specs)), \
            names, specs
    specs = [module.scenario_creator(nm, **kwargs) for nm in names]
    batch = _presolve_maybe(cfg, batch_mod.from_specs(specs, tree=tree))
    return batch, names, specs


def _do_EF(cfg, module):
    """ref:generic_cylinders.py:396-457."""
    from mpisppy_tpu.algos import ef as ef_mod
    # EF runs have no hub to emit wheel events, but --trace-jsonl /
    # --metrics-snapshot must not be silently ignored: the bus still
    # captures the console stream and writes a final metrics snapshot
    tel_bus = telemetry.from_cfg(cfg)
    try:
        names, kwargs, tree = _model_plumbing(cfg, module)
        ef = ef_mod.ExtensiveForm({"tol": cfg.get("pdhg_tol", 1e-6)},
                                  names, module.scenario_creator, kwargs,
                                  tree=tree)
        st = ef.solve_extensive_form()
        obj = ef.get_objective_value()
        global_toc(f"EF objective: {obj:.6g} "
                   f"(converged={bool(st.done.all())})", True)
        if cfg.get("solution_base_name"):
            import numpy as np
            np.save(cfg["solution_base_name"] + ".npy",
                    np.asarray(list(ef.get_root_solution().values())))
    finally:
        telemetry.close_bus(tel_bus)
    print(json.dumps({"EF_objective": obj,  # telemetry: allow-print
                      "converged": bool(st.done.all())}))
    return ef


def _fuse_wheel(cfg, hub, spokes, specs=None, tree=None):
    """Swap the PH hub for FusedPH and the fusable bound spokes
    (lagrangian / xhatxbar / slam / xhatshuffle) for their fused
    classes; everything else (cut providers, FWPH, reduced costs, ...)
    stays a classic spoke on the hub's sync period.

    MULTISTAGE: the x̄ recourse planes fix EVERY stage's nonants, which
    is structurally infeasible whenever a later-stage equality couples
    nonants with stage randomness (hydro's reservoir balance — measured
    recourse duals ~1e6); on trees deeper than two stages the x̄ spoke
    maps to EFXhatInnerBound (root-fixed EF with intra-tree
    nonanticipativity, the reference's xhatlooper stage2ef analog)
    instead of the fused all-stage-fixed plane."""
    from mpisppy_tpu.algos import fused_wheel as fw
    from mpisppy_tpu.cylinders import spoke as spoke_mod

    multistage = tree is not None and tree.num_stages > 2
    fusable = {
        spoke_mod.LagrangianOuterBound: spoke_mod.FusedLagrangianOuterBound,
        spoke_mod.XhatXbarInnerBound: spoke_mod.FusedXhatXbarInnerBound,
        spoke_mod.XhatShuffleInnerBound:
            spoke_mod.FusedXhatShuffleInnerBound,
        spoke_mod.SlamMaxHeuristic: spoke_mod.FusedSlamHeuristic,
        spoke_mod.SlamMinHeuristic: spoke_mod.FusedSlamHeuristic,
    }
    present = set()
    out_spokes = []
    for sd in spokes:
        cls = sd["spoke_class"]
        if cls is spoke_mod.XhatXbarInnerBound and multistage \
                and specs is not None:
            out_spokes.append({
                "spoke_class": spoke_mod.EFXhatInnerBound,
                "opt_kwargs": {"options": {"specs": specs,
                                           "tree": tree}}})
        elif cls in fusable:
            present.add(cls)
            out_spokes.append({"spoke_class": fusable[cls],
                               "opt_kwargs": {"options": {}}})
        else:
            out_spokes.append(sd)
    # --lane-guard and --kernel-counters must reach the fused planes'
    # PDHG options too, or the CLI knobs would silently cover only the
    # hub's subproblems
    import dataclasses as _dc
    _defaults = fw.FusedWheelOptions()
    _guard = {"lane_guard": bool(cfg.get("lane_guard", False)),
              "guard_max_resets": int(cfg.get("guard_max_resets", 3)),
              "telemetry": bool(cfg.get("kernel_counters", False))}
    wopts = fw.FusedWheelOptions(
        lag_pdhg=_dc.replace(_defaults.lag_pdhg, **_guard),
        xhat_pdhg=_dc.replace(_defaults.xhat_pdhg, **_guard),
        lag_windows=8 if spoke_mod.LagrangianOuterBound in present else 0,
        xhat_windows=4 if spoke_mod.XhatXbarInnerBound in present else 0,
        slam_windows=2 if (spoke_mod.SlamMaxHeuristic in present
                           or spoke_mod.SlamMinHeuristic in present)
        else 0,
        slam_sense_max=spoke_mod.SlamMinHeuristic not in present,
        shuffle_windows=4 if spoke_mod.XhatShuffleInnerBound in present
        else 0,
        spoke_period=max(1, int(cfg.get("fused_spoke_period", 1) or 1)))
    hub = dict(hub)
    hub["opt_class"] = fw.FusedPH
    hub["opt_kwargs"] = dict(hub.get("opt_kwargs", {}))
    hub["opt_kwargs"]["wheel_options"] = wopts
    # --async-staleness s >= 1: swap in the async exchange hub/driver
    # (ISSUE 11; docs/async_wheel.md).  0 keeps the synchronous pair —
    # AsyncPHHub/AsyncFusedPH at staleness 0 would be bit-identical
    # anyway, but the plain classes keep the common path untouched.
    staleness = max(0, int(cfg.get("async_staleness", 0) or 0))
    if staleness > 0:
        from mpisppy_tpu.algos import async_wheel as aw
        from mpisppy_tpu.cylinders import hub as hub_mod
        hub["hub_class"] = hub_mod.AsyncPHHub
        hub["opt_class"] = aw.AsyncFusedPH
        ddl = float(cfg.get("async_exchange_deadline_s", 0.0) or 0.0)
        hub["opt_kwargs"]["async_options"] = aw.AsyncWheelOptions(
            staleness=staleness,
            exchange_deadline_s=ddl if ddl > 0 else None)
        hub["hub_kwargs"] = dict(hub.get("hub_kwargs", {}))
        hub_opts = dict(hub["hub_kwargs"].get("options", {}))
        hub_opts["async_staleness"] = staleness
        hub["hub_kwargs"]["options"] = hub_opts
    return hub, out_spokes


def build_wheel(cfg, module):
    """Assemble (hub, spokes, names, specs, batch) from a parsed Config
    — the cylinder-construction half of the decomp driver, split out so
    other drivers (the multi-tenant serve engine, serve/engine.py)
    build sessions through the exact CLI recipe surface instead of a
    parallel hand-rolled one."""
    batch, names, specs = _build_batch(cfg, module)
    converger = None
    if cfg.get("use_primal_dual_converger"):
        import functools
        from mpisppy_tpu.convergers.primal_dual_converger import (
            PrimalDualConverger,
        )
        converger = functools.partial(
            PrimalDualConverger,
            tol=cfg.get("primal_dual_converger_tol", 1e-2))
    if cfg.get("lshaped_hub"):
        if converger is not None:
            global_toc("WARNING: converger options are ignored with "
                       "--lshaped-hub (Benders has its own termination)",
                       True)
        if cfg.get("aph_hub"):
            global_toc("WARNING: --aph-hub is ignored because "
                       "--lshaped-hub is also set", True)
        hub = vanilla.lshaped_hub(cfg, batch, scenario_names=names)
    elif cfg.get("aph_hub"):
        hub = vanilla.aph_hub(cfg, batch, scenario_names=names,
                              converger=converger)
    else:
        extensions = None
        ext_factories = []
        if cfg.get("cross_scenario_cuts"):
            ext_factories.append(vanilla.cross_scenario_extension(cfg))
        if cfg.get("reduced_costs"):
            ext_factories.append(vanilla.reduced_costs_fixer(cfg))
        if cfg.get("grad_rho"):
            import functools
            from mpisppy_tpu.extensions.rho_setters import (
                Gradient_extension,
            )
            ext_factories.append(functools.partial(
                Gradient_extension,
                grad_order_stat=cfg.get("grad_order_stat", 0.5),
                grad_rho_update_interval=cfg.get(
                    "grad_rho_update_interval", 5),
                indep_denom=cfg.get("grad_rho_indep_denom", False),
                grad_rho_relative_bound=cfg.get(
                    "grad_rho_relative_bound", 1e3)))
        if cfg.get("sensi_rho"):
            import functools
            from mpisppy_tpu.extensions.rho_setters import SensiRho
            ext_factories.append(functools.partial(
                SensiRho,
                sensi_rho_multiplier=cfg.get("sensi_rho_multiplier",
                                             1.0)))
        if cfg.get("mult_rho"):
            import functools
            from mpisppy_tpu.extensions.rho_setters import MultRhoUpdater
            ext_factories.append(functools.partial(
                MultRhoUpdater,
                mult_rho_update_factor=cfg.get("mult_rho_update_factor",
                                               2.0),
                mult_rho_update_interval=cfg.get(
                    "mult_rho_update_interval", 2)))
        if cfg.get("W_fname") or cfg.get("Xbar_fname"):
            import functools
            from mpisppy_tpu.extensions.wxbar_io import WXBarWriter
            ext_factories.append(functools.partial(
                WXBarWriter, W_fname=cfg.get("W_fname"),
                Xbar_fname=cfg.get("Xbar_fname")))
        if cfg.get("init_W_fname") or cfg.get("init_Xbar_fname"):
            import functools
            from mpisppy_tpu.extensions.wxbar_io import WXBarReader
            ext_factories.append(functools.partial(
                WXBarReader, init_W_fname=cfg.get("init_W_fname"),
                init_Xbar_fname=cfg.get("init_Xbar_fname")))
        if len(ext_factories) == 1:
            extensions = ext_factories[0]
        elif ext_factories:
            from mpisppy_tpu.extensions.extension import MultiExtension
            import functools
            extensions = functools.partial(MultiExtension,
                                           ext_classes=ext_factories)
        rho_setter = None
        if cfg.get("rho_file_in"):
            from mpisppy_tpu.utils.gradient import Set_Rho
            rho_setter = Set_Rho(cfg).rho_setter
        hub = vanilla.ph_hub(cfg, batch, scenario_names=names,
                             converger=converger, extensions=extensions,
                             rho_setter=rho_setter)
    spokes = []
    if not cfg.get("lshaped_hub") and not cfg.get("aph_hub"):
        if cfg.get("cross_scenario_cuts"):
            spokes.append(vanilla.cross_scenario_cuts_spoke(cfg))
        if cfg.get("reduced_costs"):
            spokes.append(vanilla.reduced_costs_spoke(cfg))
    if cfg.get("ph_ob"):
        spokes.append(vanilla.ph_ob_spoke(cfg))
    if cfg.get("xhatlshaped"):
        spokes.append(vanilla.xhatlshaped_spoke(cfg))
    if cfg.get("fwph"):
        spokes.append(vanilla.fwph_spoke(cfg))
    if cfg.get("lagrangian"):
        spokes.append(vanilla.lagrangian_spoke(cfg))
    if cfg.get("lagranger"):
        spokes.append(vanilla.lagranger_spoke(cfg))
    if cfg.get("subgradient"):
        spokes.append(vanilla.subgradient_spoke(cfg))
    if cfg.get("xhatxbar"):
        spokes.append(vanilla.xhatxbar_spoke(cfg))
    if cfg.get("xhatshuffle"):
        spokes.append(vanilla.xhatshuffle_spoke(cfg))
    if cfg.get("slammax"):
        spokes.append(vanilla.slammax_spoke(cfg))
    if cfg.get("slammin"):
        spokes.append(vanilla.slammin_spoke(cfg))

    if cfg.get("fused_wheel") and not cfg.get("lshaped_hub") \
            and not cfg.get("aph_hub"):
        hub, spokes = _fuse_wheel(cfg, hub, spokes, specs=specs,
                                  tree=batch.tree)
    elif int(cfg.get("async_staleness", 0) or 0) > 0:
        why = ("--fused-wheel is vetoed by --aph-hub/--lshaped-hub here"
               if cfg.get("fused_wheel") else "requires --fused-wheel")
        global_toc(f"WARNING: --async-staleness {why} "
                   "(the async exchange plane is the fused wheel's); "
                   "running synchronous", True)
    return hub, spokes, names, specs, batch


def _do_decomp(cfg, module):
    """ref:generic_cylinders.py:109-312."""
    hub, spokes, names, specs, batch = build_wheel(cfg, module)

    # telemetry spine (docs/telemetry.md): --trace-jsonl /
    # --metrics-snapshot build the run's event bus; the hub emits into
    # it and the finally below flushes the sinks even on preemption
    tel_bus = telemetry.from_cfg(cfg)
    # crash flight recorder (docs/telemetry.md): an always-on bounded
    # ring of the last ~512 events, even with --trace-jsonl OFF —
    # WheelSpinner.spin dumps it to flight-<runid>.jsonl when the wheel
    # dies, so every crash leaves a black box.  When no trace/metrics
    # bus exists, a private bus carries just the recorder (and the
    # console stream, so the black box holds the final log lines too —
    # stdout rendering is unchanged: the private bus has no ConsoleSink)
    wheel_bus, own_bus = tel_bus, False
    if cfg.get("flight_recorder", True):
        from mpisppy_tpu.telemetry import flightrec
        if wheel_bus is None:
            wheel_bus = telemetry.EventBus()
            telemetry.console.attach(wheel_bus)
            own_bus = True
        wheel_bus.subscribe(flightrec.FlightRecorder(
            capacity=int(cfg.get("flight_capacity", 512)),
            dump_dir=cfg.get("flight_dir", ".")))
    # dispatch scheduler (docs/dispatch.md): the --dispatch-* group
    # configures the process-default scheduler every MIP-oracle solve
    # routes through; with a bus attached each megabatch dispatch also
    # lands in the JSONL trace (and the flight recorder's ring)
    from mpisppy_tpu import dispatch as _dispatch
    _dispatch.from_cfg(cfg, bus=wheel_bus)
    if wheel_bus is not None:
        hub = dict(hub)
        hub["hub_kwargs"] = dict(hub.get("hub_kwargs", {}))
        hub_opts = dict(hub["hub_kwargs"].get("options", {}))
        hub_opts["telemetry_bus"] = wheel_bus
        hub["hub_kwargs"]["options"] = hub_opts
    try:
        return _spin_and_report(cfg, module, hub, spokes, names, specs)
    finally:
        if own_bus:
            telemetry.console.detach(wheel_bus)
            wheel_bus.close()
        telemetry.close_bus(tel_bus)


def _report_device_profile(profile_dir: str) -> None:
    """A --profile-dir run closes the loop itself (ISSUE 7): parse the
    capture the ProfilerSession just wrote, print the headline device
    numbers, and leave the full roofline report next to the capture as
    device_profile.json — the committed-artifact form the README lint
    and `telemetry gate` consume."""
    import os

    import jax

    from mpisppy_tpu.telemetry import deviceprof, roofline
    try:
        cap = deviceprof.newest_capture(profile_dir)
        if cap is None:
            return
        rep = roofline.roofline(deviceprof.build_timeline(cap),
                                jax.devices()[0].device_kind)
    except (OSError, ValueError) as e:
        global_toc(f"device profile unreadable under {profile_dir}: {e}",
                   True)
        return
    out_path = os.path.join(profile_dir, "device_profile.json")
    try:
        from mpisppy_tpu.utils.atomic_io import atomic_write_text
        atomic_write_text(out_path, json.dumps(rep, indent=1) + "\n")
    except OSError:
        out_path = "(unwritable)"
    def _g(v):
        return "-" if v is None else format(v, ".4g")
    global_toc(
        f"device profile: sec/iter {_g(rep.get('device_sec_per_iter'))}"
        f"  stream {_g(rep.get('measured_stream_gbps'))} GB/s"
        f"  hbm {_g(rep.get('achieved_hbm_gbps'))}/"
        f"{_g(rep.get('peak_hbm_gbps'))} GB/s"
        f"  overlap {_g(rep.get('overlap_frac'))}  -> {out_path}", True)


def _spin_and_report(cfg, module, hub, spokes, names, specs):
    wheel = WheelSpinner(hub, spokes)
    ckpt = cfg.get("checkpoint_path")
    if ckpt and cfg.get("checkpoint_restore"):
        wheel.build()
        if wheel.spcomm._checkpoint_candidates(ckpt):
            try:
                wheel.spcomm.load_checkpoint(ckpt)
                global_toc(f"restored checkpoint {ckpt} at hub iter "
                           f"{wheel.spcomm._iter}; resuming", True)
            except FileNotFoundError as e:
                # snapshots exist but NONE validates (bit rot, torn on
                # a non-atomic fs): a crash here would restart-storm
                # the pool scheduler against the same dead files —
                # degrade to a fresh run instead, loudly
                global_toc(f"WARNING: no valid checkpoint to restore "
                           f"({e}); starting fresh", True)
    try:
        wheel.spin()
    except PreemptionError as e:
        # state was already emergency-saved by WheelSpinner.spin; report
        # and exit with EX_TEMPFAIL so the pool scheduler restarts us
        # (--checkpoint-restore picks the run back up)
        global_toc(f"run preempted ({e}); restart with "
                   f"--checkpoint-restore to resume", True)
        print(json.dumps({"preempted": True,  # telemetry: allow-print
                          "checkpoint_path": ckpt,
                          "iterations": wheel.spcomm._iter}))
        raise SystemExit(75)
    abs_gap, rel_gap = wheel.spcomm.compute_gaps()
    global_toc(
        f"outer {wheel.BestOuterBound:.6g} inner {wheel.BestInnerBound:.6g}"
        f" rel_gap {rel_gap:.3e}", True)
    if cfg.get("profile_dir"):
        _report_device_profile(cfg["profile_dir"])
    if cfg.get("solution_base_name"):
        wheel.write_first_stage_solution(
            cfg["solution_base_name"] + ".csv")
    if cfg.get("rho_file_out") \
            and getattr(wheel.opt, "state", None) is not None \
            and hasattr(wheel.opt.state, "rho"):
        import numpy as _np
        from mpisppy_tpu.utils.rho_utils import rhos_to_csv
        rhos_to_csv(_np.asarray(wheel.opt.state.rho),
                    cfg["rho_file_out"])
    for rank0, nm in enumerate(names):
        module.scenario_denouement(0, nm, specs[rank0])

    def _finite(v):  # strict-JSON safe: a bound that never landed -> null
        import math
        return v if isinstance(v, (int, float)) and math.isfinite(v) \
            else None
    # fault-domain accounting (docs/resilience.md): a run that leaned
    # on dispatch retries/quarantine or tripped the watchdog says so in
    # its machine-readable result line, not only in the trace
    from mpisppy_tpu import dispatch as _dispatch
    dstats = _dispatch.scheduler_stats() or {}
    wd = getattr(wheel.spcomm, "_watchdog", None)
    print(json.dumps({  # telemetry: allow-print
        "outer_bound": _finite(wheel.BestOuterBound),
        "inner_bound": _finite(wheel.BestInnerBound),
        "abs_gap": _finite(abs_gap), "rel_gap": _finite(rel_gap),
        "iterations": wheel.spcomm._iter,
        "dispatch_retries": dstats.get("retries_total", 0),
        "dispatch_quarantined_lanes": dstats.get("quarantined_lanes", 0),
        "watchdog_trips": 0 if wd is None else wd.trips,
    }))
    return wheel


def main(args=None):
    argv = list(sys.argv[1:] if args is None else args)
    module_name = None
    for i, a in enumerate(argv):
        if a == "--module-name":
            module_name = argv[i + 1]
        elif a.startswith("--module-name="):
            module_name = a.split("=", 1)[1]
    if module_name is None:
        raise SystemExit(
            "usage: python -m mpisppy_tpu --module-name <module> ...")
    sys.path.insert(0, ".")
    module = importlib.import_module(module_name)
    cfg = _parse_args(module, argv)
    if cfg.get("EF"):
        return _do_EF(cfg, module)
    return _do_decomp(cfg, module)


if __name__ == "__main__":
    from mpisppy_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
