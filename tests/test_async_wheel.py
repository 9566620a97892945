# Async wheel (ISSUE 11): the double-buffered stale exchange plane
# (algos/async_wheel.AsyncFusedPH + cylinders/hub.AsyncPHHub) overlaps
# host exchange with device iterations.  Contracts tested here:
#
#   * staleness 0 is the synchronous degrade — BIT-IDENTICAL wheel
#     trajectories (bounds, trace rows, checkpoint bytes) on farmer and
#     hydro;
#   * staleness >= 1 still CERTIFIES: the published outer/inner bounds
#     match the synchronous wheel's within restart-recheck tolerance on
#     farmer, hydro, and uc (stale planes delay bounds, never
#     invalidate them — L(W) is certified at ANY W, every candidate
#     keeps its feasibility gate);
#   * the async-exchange fault seams (dropped plane write, torn swap,
#     slow harvest) never break the certified bracket, and a genuinely
#     wedged exchange still trips the PR-8 hub watchdog;
#   * the pipelined kernel-counter harvest (begin now / complete next
#     sync, flushed at finalize) never undercounts exported totals;
#   * plane staleness + host/device overlap are observable in
#     `telemetry analyze`, and PlaneTicket keeps the dispatch layer's
#     result-or-typed-failure contract.
import os
import time

import numpy as np
import pytest

import jax.numpy as jnp

from mpisppy_tpu.algos import async_wheel as aw
from mpisppy_tpu.algos import fused_wheel as fw
from mpisppy_tpu.algos import ph as ph_mod
from mpisppy_tpu.core import batch as batch_mod
from mpisppy_tpu.cylinders import AsyncPHHub, PHHub
from mpisppy_tpu.cylinders.spoke import (
    EFOuterBound, EFXhatInnerBound, FusedLagrangianOuterBound,
    FusedSlamHeuristic, FusedXhatShuffleInnerBound, FusedXhatXbarInnerBound,
)
from mpisppy_tpu.models import farmer, hydro, uc
from mpisppy_tpu.ops import pdhg
from mpisppy_tpu.resilience.faults import AsyncExchangeFault, FaultPlan
from mpisppy_tpu.spin_the_wheel import WheelSpinner

FARMER_EF_OBJ = -108390.0


def farmer_batch(num_scens=3):
    specs = [farmer.scenario_creator(nm, num_scens=num_scens)
             for nm in farmer.scenario_names_creator(num_scens)]
    return batch_mod.from_specs(specs)


def farmer_ph_opts(max_iterations=120):
    return ph_mod.PHOptions(
        default_rho=1.0, max_iterations=max_iterations, conv_thresh=0.0,
        subproblem_windows=10, pdhg=pdhg.PDHGOptions(tol=1e-7))


FARMER_WOPTS = fw.FusedWheelOptions(
    slam_windows=2, shuffle_windows=4,
    slam_sense_max=False,  # farmer: acreage minimization
    lag_pdhg=pdhg.PDHGOptions(tol=1e-7),
    xhat_pdhg=pdhg.PDHGOptions(tol=1e-7, omega0=0.1, restart_period=80))

ALL_FUSED_SPOKES = [
    {"spoke_class": FusedLagrangianOuterBound, "opt_kwargs": {"options": {}}},
    {"spoke_class": FusedXhatXbarInnerBound, "opt_kwargs": {"options": {}}},
    {"spoke_class": FusedXhatShuffleInnerBound,
     "opt_kwargs": {"options": {}}},
    {"spoke_class": FusedSlamHeuristic, "opt_kwargs": {"options": {}}},
]


def wheel_dict(batch, staleness=None, rel_gap=1e-2, max_iterations=120,
               ph_opts=None, wheel_options=None, hub_extra=None):
    """Hub dict for the synchronous pair (staleness None) or the async
    pair at the given staleness bound (0 = synchronous degrade)."""
    hub_opts = {"rel_gap": rel_gap}
    hub_opts.update(hub_extra or {})
    opts = ph_opts or farmer_ph_opts(max_iterations)
    d = {
        "hub_class": PHHub,
        "hub_kwargs": {"options": hub_opts},
        "opt_class": fw.FusedPH,
        "opt_kwargs": {"options": opts, "batch": batch,
                       "wheel_options": wheel_options or FARMER_WOPTS},
    }
    if staleness is not None:
        d["hub_class"] = AsyncPHHub
        d["opt_class"] = aw.AsyncFusedPH
        d["opt_kwargs"]["async_options"] = aw.AsyncWheelOptions(
            staleness=staleness)
        hub_opts["async_staleness"] = staleness
    return d


def spokes():
    return [dict(s) for s in ALL_FUSED_SPOKES]


#: the host-timing fields of a trajectory row: its clock stamp, the
#: program spans filed under it and the collector's pauses
HOST_TIMING = ("t", "spans", "gc_s", "gc_n")


def trace_rows(ws):
    """Hub trace rows with the host-timing fields stripped (the only
    nondeterministic fields in a trajectory row)."""
    return [{k: v for k, v in row.items() if k not in HOST_TIMING}
            for row in ws.spcomm.trace]


def assert_ckpt_bytes_equal(path_a, path_b):
    with np.load(path_a) as a, np.load(path_b) as b:
        assert sorted(a.files) == sorted(b.files)
        for k in a.files:
            assert a[k].tobytes() == b[k].tobytes(), \
                f"checkpoint member {k!r} differs"


# ---------------------------------------------------------------------------
# shared runs (module scope: the farmer wheels are reused across tests)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def sync_farmer(tmp_path_factory):
    batch = farmer_batch(3)
    ws = WheelSpinner(wheel_dict(batch), spokes()).spin()
    ckpt = str(tmp_path_factory.mktemp("sync") / "sync.npz")
    ws.spcomm.save_checkpoint(ckpt, background=False)
    return ws, ckpt


@pytest.fixture(scope="module")
def async1_farmer(tmp_path_factory):
    from mpisppy_tpu import telemetry
    path = str(tmp_path_factory.mktemp("async1") / "trace.jsonl")
    bus = telemetry.EventBus()
    bus.subscribe(telemetry.JsonlSink(path))
    batch = farmer_batch(3)
    ws = WheelSpinner(
        wheel_dict(batch, staleness=1,
                   hub_extra={"telemetry_bus": bus}),
        spokes()).spin()
    bus.close()
    return ws, path


# ---------------------------------------------------------------------------
# staleness 0: the synchronous degrade is bit-identical
# ---------------------------------------------------------------------------
def test_staleness0_bit_identical_farmer(sync_farmer, tmp_path):
    ws_sync, ckpt_sync = sync_farmer
    batch = farmer_batch(3)
    ws0 = WheelSpinner(wheel_dict(batch, staleness=0), spokes()).spin()
    # bounds and the full per-iteration trajectory rows are EXACTLY
    # equal — same jitted programs, same host loop
    assert ws0.BestOuterBound == ws_sync.BestOuterBound
    assert ws0.BestInnerBound == ws_sync.BestInnerBound
    assert trace_rows(ws0) == trace_rows(ws_sync)
    # and the persisted wheel state is byte-identical
    ckpt0 = str(tmp_path / "async0.npz")
    ws0.spcomm.save_checkpoint(ckpt0, background=False)
    assert_ckpt_bytes_equal(ckpt0, ckpt_sync)


def hydro_wheel(staleness, rel_gap=1e-2, max_iterations=60):
    num = 9
    specs = [hydro.scenario_creator(nm, branching_factors=(3, 3))
             for nm in hydro.scenario_names_creator(num)]
    tree = hydro.make_tree((3, 3))
    batch = batch_mod.from_specs(specs, tree=tree)
    from mpisppy_tpu.algos import ef as ef_mod
    efp = ef_mod.build_ef(specs, tree=tree)
    opts = ph_mod.PHOptions(default_rho=2.0, max_iterations=max_iterations,
                            conv_thresh=0.0, subproblem_windows=8,
                            pdhg=pdhg.PDHGOptions(tol=1e-6))
    # multistage: x̄-fixing recourse planes are structurally infeasible
    # on hydro (see generic_cylinders._fuse_wheel), so the bracket comes
    # from the classic EF spokes — which exercises the async hub's
    # classic-spoke exchange path too
    sp = [
        {"spoke_class": EFOuterBound,
         "opt_kwargs": {"options": {"ef_problem": efp, "n_windows": 30}}},
        {"spoke_class": EFXhatInnerBound,
         "opt_kwargs": {"options": {"ef_problem": efp, "n_windows": 30}}},
    ]
    hub = wheel_dict(batch, staleness=staleness, rel_gap=rel_gap,
                     ph_opts=opts, wheel_options=fw.FusedWheelOptions())
    return WheelSpinner(hub, sp).spin()


def test_staleness0_bit_identical_hydro(tmp_path):
    ws_sync = hydro_wheel(staleness=None)
    ws0 = hydro_wheel(staleness=0)
    assert ws0.BestOuterBound == ws_sync.BestOuterBound
    assert ws0.BestInnerBound == ws_sync.BestInnerBound
    assert trace_rows(ws0) == trace_rows(ws_sync)
    a, b = str(tmp_path / "sync.npz"), str(tmp_path / "async0.npz")
    ws_sync.spcomm.save_checkpoint(a, background=False)
    ws0.spcomm.save_checkpoint(b, background=False)
    assert_ckpt_bytes_equal(a, b)


# ---------------------------------------------------------------------------
# staleness >= 1: the stale-plane wheel still certifies, and its bounds
# match the synchronous wheel's within restart-recheck tolerance
# ---------------------------------------------------------------------------
def certified(ws, rel_gap=1e-2):
    inner, outer = ws.BestInnerBound, ws.BestOuterBound
    assert np.isfinite(inner) and np.isfinite(outer)
    # same consistency slack as the synchronous wheel tests: the two
    # sides are evaluated by different (comp-compensated) programs
    assert outer <= inner + 2e-3 * abs(inner)
    assert (inner - outer) / abs(inner) <= rel_gap + 1e-6
    return outer, inner


def test_staleness_certifies_and_matches_sync_farmer(sync_farmer,
                                                     async1_farmer):
    out_s, in_s = certified(sync_farmer[0])
    runs = {1: async1_farmer[0]}
    batch = farmer_batch(3)
    runs[2] = WheelSpinner(wheel_dict(batch, staleness=2),
                           spokes()).spin()
    for s, ws in runs.items():
        out_a, in_a = certified(ws)
        # both brackets certify <= 1% around the same optimum, so the
        # published bounds can differ at most at that order
        tol = 1.5e-2 * abs(in_s)
        assert abs(out_a - out_s) <= tol, f"staleness {s} outer drifted"
        assert abs(in_a - in_s) <= tol, f"staleness {s} inner drifted"
        slack = 1.5e-2 * abs(FARMER_EF_OBJ)
        assert out_a <= FARMER_EF_OBJ + slack
        assert in_a >= FARMER_EF_OBJ - slack
        # the theta damping actually engaged (pipelined host read)
        assert ws.opt.last_theta is not None
        assert 0.0 <= ws.opt.last_theta <= 1.0


def test_staleness_certifies_and_matches_sync_hydro():
    ws_sync = hydro_wheel(staleness=None)
    ws1 = hydro_wheel(staleness=1)
    out_s, in_s = certified(ws_sync)
    out_a, in_a = certified(ws1)
    tol = 1.5e-2 * abs(in_s)
    assert abs(out_a - out_s) <= tol
    assert abs(in_a - in_s) <= tol


def test_staleness_matches_sync_uc():
    inst = uc.synthetic_instance(4, 12, seed=1)
    specs = [uc.scenario_creator(nm, instance=inst, num_scens=3)
             for nm in uc.scenario_names_creator(3)]
    batch = batch_mod.from_specs(specs)
    opts = ph_mod.PHOptions(
        default_rho=200.0, max_iterations=40, conv_thresh=0.0,
        subproblem_windows=10, pdhg=pdhg.PDHGOptions(tol=1e-7))
    wopts = fw.FusedWheelOptions()
    sp = [dict(s) for s in ALL_FUSED_SPOKES[:2]]

    def run(staleness):
        return WheelSpinner(
            wheel_dict(batch, staleness=staleness, rel_gap=0.0,
                       ph_opts=opts, wheel_options=wopts),
            [dict(s) for s in sp]).spin()

    ws_sync, ws1 = run(None), run(1)
    # fixed-length runs (uc consensus is stiff — certifying 1% takes
    # hundreds of iterations): the certified bounds published at the
    # same cadence must agree within restart-recheck tolerance, and
    # each bracket must stay internally consistent
    for ws in (ws_sync, ws1):
        assert np.isfinite(ws.BestOuterBound)
        assert np.isfinite(ws.BestInnerBound)
        assert ws.BestOuterBound <= ws.BestInnerBound + 2e-3 * abs(
            ws.BestInnerBound)
    tol = 5e-2 * max(1.0, abs(ws_sync.BestInnerBound))
    assert abs(ws1.BestOuterBound - ws_sync.BestOuterBound) <= tol
    assert abs(ws1.BestInnerBound - ws_sync.BestInnerBound) <= tol


# ---------------------------------------------------------------------------
# chaos: async-exchange faults never break the certified bracket, and a
# wedged exchange still trips the hub watchdog
# ---------------------------------------------------------------------------
def test_async_exchange_faults_keep_certified_bounds():
    from mpisppy_tpu import telemetry

    plan = FaultPlan(seed=11, exchanges=(
        AsyncExchangeFault("drop_plane_write", at_iters=(3, 9)),
        AsyncExchangeFault("torn_swap", at_iters=(5, 12)),
        AsyncExchangeFault("slow_harvest", at_iters=(4,), delay_s=0.02),
    ))
    seen = []

    class _Probe:
        def handle(self, e):
            seen.append(e)

    bus = telemetry.EventBus()
    bus.subscribe(_Probe())
    batch = farmer_batch(3)
    ws = WheelSpinner(
        wheel_dict(batch, staleness=1,
                   hub_extra={"fault_plan": plan, "telemetry_bus": bus}),
        spokes()).spin()
    fired = {d for seam, d in plan.fired if seam == "exchange"}
    assert any("drop_plane_write" in d for d in fired)
    assert any("torn_swap" in d for d in fired)
    assert any("slow_harvest" in d for d in fired)
    # the dropped/torn writes must be OBSERVABLE: the plane-write
    # events report the generation the slot actually holds, so the
    # recorded staleness exceeds the configured bound at the faults
    stals = [e.data["staleness"] for e in seen
             if e.kind == "plane-write"]
    assert stals and max(stals) > 1
    # a dropped/torn plane perturbs the trajectory but can never
    # invalidate a published bound: the faulted wheel still certifies
    # the fault-free bracket
    out_a, in_a = certified(ws)
    slack = 1.5e-2 * abs(FARMER_EF_OBJ)
    assert out_a <= FARMER_EF_OBJ + slack
    assert in_a >= FARMER_EF_OBJ - slack


def test_watchdog_trips_on_wedged_exchange(async1_farmer, tmp_path):
    """A genuinely wedged exchange (slow_harvest >> watchdog budget)
    must still trip the PR-8 hub watchdog under the async hub — the
    pipelined halves may not hide a stalled host."""
    del async1_farmer  # ordering only: jit caches warm, no compile stall
    plan = FaultPlan(seed=12, exchanges=(
        AsyncExchangeFault("slow_harvest", at_iters=(4,), delay_s=2.5),))
    batch = farmer_batch(3)
    codes = []
    ws = WheelSpinner(
        wheel_dict(batch, staleness=1, max_iterations=8, rel_gap=0.0,
                   hub_extra={
                       "fault_plan": plan,
                       "checkpoint_path": str(tmp_path / "wd.npz"),
                       "watchdog_budget_s": 1.0,
                       "watchdog_interval_s": 0.05,
                       "watchdog_action": "abort"}),
        spokes()).build()
    ws.spcomm._watchdog.abort_fn = codes.append
    ws.spin()
    assert codes == [75], "watchdog never tripped on the wedged exchange"
    assert ws.spcomm._watchdog.trips >= 1


# ---------------------------------------------------------------------------
# checkpoint restore: the resumed async wheel re-seeds its plane slots
# ---------------------------------------------------------------------------
def test_async_checkpoint_resume(tmp_path):
    """load_checkpoint skips _iter0_impl (which seeds the exchange
    plane), so the async driver must lazily re-seed its slots from the
    restored state — a preempted --async-staleness run has to RESUME,
    not crash on its first iteration (the PR-2 preemption contract)."""
    batch = farmer_batch(3)
    ckpt = str(tmp_path / "aw.ckpt.npz")
    hub_extra = {"checkpoint_path": ckpt, "checkpoint_every_s": 0.0}
    ws1 = WheelSpinner(
        wheel_dict(batch, staleness=1, rel_gap=1e-4, max_iterations=12,
                   hub_extra=hub_extra), spokes()).spin()
    assert os.path.exists(ckpt)
    it1 = ws1.spcomm._iter

    ws2 = WheelSpinner(
        wheel_dict(batch, staleness=1, rel_gap=1e-4, max_iterations=30,
                   hub_extra=hub_extra), spokes()).build()
    ws2.spcomm.load_checkpoint(ckpt)
    assert 0 < ws2.spcomm._iter <= it1
    ws2.spin()
    assert ws2.spcomm._iter > it1
    assert np.isfinite(ws2.BestOuterBound)
    assert np.isfinite(ws2.BestInnerBound)
    assert ws2.BestOuterBound <= ws2.BestInnerBound + 2e-3 * abs(
        ws2.BestInnerBound)


# ---------------------------------------------------------------------------
# pipelined kernel-counter harvest: exported totals never undercount
# ---------------------------------------------------------------------------
def test_pipelined_counter_harvest_never_undercounts():
    from mpisppy_tpu import telemetry
    from mpisppy_tpu.telemetry import counters as kcounters
    from mpisppy_tpu.telemetry import metrics as metrics_mod
    seen = []

    class _Probe:
        def handle(self, e):
            seen.append(e)

    bus = telemetry.EventBus()
    bus.subscribe(_Probe())
    batch = farmer_batch(3)
    opts = ph_mod.PHOptions(
        default_rho=1.0, max_iterations=6, conv_thresh=0.0,
        subproblem_windows=10,
        pdhg=pdhg.PDHGOptions(tol=1e-7, telemetry=True))
    ws = WheelSpinner(
        wheel_dict(batch, staleness=1, rel_gap=0.0, ph_opts=opts,
                   hub_extra={"telemetry_bus": bus}),
        spokes()).spin()
    # finalize flushed the pending begin_harvest AND took one final
    # synchronous harvest: the registry mirror must equal a direct
    # harvest of the final device state exactly (no lag, no undercount)
    direct = kcounters.harvest_state(ws.opt.state.solver,
                                     include_ring=False)
    for name in ("pdhg_iterations_total", "pdhg_restarts_total",
                 "pdhg_windows_total"):
        assert metrics_mod.REGISTRY.get(name, cyl="hub") == direct[name]
    assert direct["pdhg_iterations_total"] > 0
    # the flush path discards the pending one-sync-stale snapshot
    # (superseded by the fresh synchronous harvest) instead of folding
    # it alongside: every sync stamps ONE kernel-counters row, and the
    # final hub_iter carries at most one extra — the flush's exact
    # catch-up row, not a stale duplicate
    from collections import Counter
    counts = Counter(e.hub_iter for e in seen
                     if e.kind == "kernel-counters" and e.cyl == "hub")
    assert counts
    final = max(counts)
    assert all(c == 1 for it, c in counts.items() if it != final)
    assert counts[final] <= 2


# ---------------------------------------------------------------------------
# observability: staleness + overlap in telemetry analyze
# ---------------------------------------------------------------------------
def test_analyze_reports_staleness_and_overlap(async1_farmer):
    from mpisppy_tpu.telemetry import analyze as an
    ws, path = async1_farmer
    rows = an.load_trace(path)
    rep = an.analyze(an.build_run_model(rows))
    sec = rep["async_wheel"]
    assert sec is not None
    n_iters = ws.spcomm._iter
    # one plane write per iterk; the iter0 sync has none
    assert sec["plane_writes"] == n_iters - 1
    # staleness bound 1 and no faults: every write lands exactly 1 stale
    assert sec["staleness_mean"] == 1.0
    assert sec["staleness_max"] == 1
    assert sec["syncs"] == n_iters
    assert 0.0 < sec["overlapped_host_frac"] <= 1.0
    assert 0.0 <= sec["theta_min"] <= sec["theta_last"] <= 1.0
    assert "async wheel" in an.render_report(rep)
    # raw event schema: plane-write + exchange-overlap rows are present
    kinds = {r["kind"] for r in rows}
    assert {"plane-write", "exchange-overlap"} <= kinds
    from mpisppy_tpu.telemetry import metrics as metrics_mod
    assert metrics_mod.REGISTRY.get("async_plane_writes_total") \
        >= n_iters - 1


# ---------------------------------------------------------------------------
# dispatch: PlaneTicket keeps result-or-typed-failure semantics
# ---------------------------------------------------------------------------
def test_plane_ticket_deadline_and_fast_path():
    from mpisppy_tpu.dispatch.scheduler import (
        DispatchOptions, SolveFailed, SolveScheduler,
    )
    sched = SolveScheduler(DispatchOptions())

    # fast path: the dispatch is async XLA work, value is usable
    # immediately and result() settles it
    t = sched.submit_plane(lambda a: a * 2, jnp.ones((4,)), label="ok")
    np.testing.assert_allclose(np.asarray(t.result()), 2.0)
    assert t.done()

    class Wedged:
        def block_until_ready(self):
            time.sleep(30)

        def is_ready(self):
            return False

    t0 = time.perf_counter()
    tk = sched.submit_plane(lambda: Wedged(), label="wedged",
                            deadline_s=0.1)
    with pytest.raises(SolveFailed) as ei:
        tk.result()
    assert ei.value.reason == "deadline"
    assert time.perf_counter() - t0 < 5.0, "wait was not bounded"

    # an expired deadline on a result that already LANDED is not a
    # miss: the readiness re-check must return the value (the
    # SolveTicket expired-deadline recovery semantics)
    late = sched.submit_plane(lambda a: a + 1, jnp.ones(()),
                              label="late", deadline_s=0.05)
    np.asarray(late.value)          # force the result to land
    time.sleep(0.1)                 # ... and the deadline to pass
    np.testing.assert_allclose(np.asarray(late.result()), 2.0)

    # ... and past the deadline an EXPLICIT timeout grants a fresh
    # recovery wait (the dispatch may still land late)
    class Slow:
        def __init__(self):
            self.t0 = time.perf_counter()

        def is_ready(self):
            return time.perf_counter() - self.t0 > 0.3

        def block_until_ready(self):
            while not self.is_ready():
                time.sleep(0.01)

    rec = sched.submit_plane(Slow, label="recover", deadline_s=0.05)
    time.sleep(0.1)                 # deadline expired, not yet ready
    assert rec.result(timeout=5.0) is rec.value   # recovery succeeds
    with pytest.raises(SolveFailed):
        sched.submit_plane(Slow, label="bare", deadline_s=-1.0).result()

    st = sched.stats()
    assert st["plane_tickets"] == 5
    assert st["plane_deadline_misses"] == 2


def test_projective_theta_rejects_adverse_plane():
    """APH's Step-16 rejection must be REACHABLE: a plane whose era
    duals point against the current iterate drives phi <= 0 and theta
    to exactly 0 (pre-floor).  Forming y from the current W instead of
    the plane-era W_plane degenerates phi to rho*E||x - z||^2 >= 0 and
    makes rejection impossible — the regression this test pins."""
    from mpisppy_tpu.algos import aph as aph_mod
    batch = farmer_batch(3)
    rng = np.random.default_rng(7)
    S, N = batch.num_scenarios, batch.num_nonants
    x = jnp.asarray(rng.normal(size=(S, N)))
    z = jnp.asarray(rng.normal(size=(S, N)))
    W = jnp.asarray(rng.normal(size=(S, N)))
    xbar, _ = batch.node_average(x)
    rho = jnp.ones((N,))
    # aligned plane (duals unchanged): phi = rho*E||x-z||^2 > 0
    th_aligned = aph_mod.projective_theta(batch, x, xbar, W, z, W, rho)
    assert float(th_aligned) > 0.0
    # adverse plane: W - W_plane = 2*rho*(x - z) makes
    # phi = -rho*E||x-z||^2 < 0 -> Step-16 rejection, theta == 0
    W_plane = W - 2.0 * rho * (x - z)
    th_adverse = aph_mod.projective_theta(batch, x, xbar, W, z,
                                          W_plane, rho)
    assert float(th_adverse) == 0.0


def test_plane_ticket_failed_dispatch_is_typed():
    """A plane dispatch whose async computation ERRORED surfaces at
    result() as SolveFailed('exception') — never as poisoned arrays
    returned as success (the result-or-typed-failure contract), on
    every wait path: unbounded, ready fast path, and the bounded
    waiter thread."""
    from mpisppy_tpu.dispatch.scheduler import (
        DispatchOptions, SolveFailed, SolveScheduler,
    )
    sched = SolveScheduler(DispatchOptions())

    class Failed:
        def is_ready(self):
            return True

        def block_until_ready(self):
            raise RuntimeError("XLA computation failed")

    class FailedUnready(Failed):
        def is_ready(self):
            return False

    # unbounded wait
    with pytest.raises(SolveFailed) as ei:
        sched.submit_plane(Failed, label="boom").result()
    assert ei.value.reason == "exception"
    # ready fast path under a live deadline
    with pytest.raises(SolveFailed) as ei2:
        sched.submit_plane(Failed, label="boom-fast",
                           deadline_s=30.0).result()
    assert ei2.value.reason == "exception"
    # bounded waiter-thread path
    with pytest.raises(SolveFailed) as ei3:
        sched.submit_plane(FailedUnready, label="boom-wait",
                           deadline_s=30.0).result(timeout=30.0)
    assert ei3.value.reason == "exception"
    # a failed dispatch is not a deadline miss
    assert sched.stats()["plane_deadline_misses"] == 0


# ---------------------------------------------------------------------------
# regress gates: the committed smoke artifact witnesses the milestone
# ---------------------------------------------------------------------------
def test_bench_r07_witnesses_overhead_milestone():
    """BENCH_r07.json is the committed witness for the ISSUE-11
    `wheel_overhead_async.overhead_factor <= 1.3` MILESTONE key
    (graftlint's schema-drift pass requires every MILESTONE pattern to
    match a committed artifact); its smoke value meets the bound, so a
    gate anchored on it BINDS the ratchet."""
    import os

    from mpisppy_tpu.telemetry import regress

    r07 = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_r07.json")
    rep = regress.gate_paths(r07, r07)
    assert rep["ok"], rep["regressions"]
    ms = {r["metric"]: r for r in rep["milestones"]}
    row = ms["wheel_overhead_async.overhead_factor"]
    assert row["status"] == "met" and row["binding"]
    assert row["milestone"] == 1.3

    # and a later artifact slipping past the acceptance line fails the
    # plain (ratchet) gate — no --milestones flag needed
    import json as _json
    import tempfile
    slipped = _json.load(open(r07))
    slipped["parsed"]["wheel_overhead_async"]["overhead_factor"] = 1.31
    with tempfile.NamedTemporaryFile("w", suffix=".json",
                                     delete=False) as f:
        _json.dump(slipped, f)
    rep2 = regress.gate_paths(r07, f.name)
    assert not rep2["ok"]
    assert any(r["metric"] == "wheel_overhead_async.overhead_factor"
               for r in rep2["regressions"])


# ---------------------------------------------------------------------------
# CLI wiring: --async-staleness swaps in the async pair
# ---------------------------------------------------------------------------
def test_fuse_wheel_swaps_async_classes():
    from mpisppy_tpu import generic_cylinders as gc
    from mpisppy_tpu.utils.config import Config

    def fused_cfg(extra):
        cfg = Config()
        cfg.popular_args()
        cfg.fused_wheel_args()
        cfg.parse_command_line("t", ["--fused-wheel"] + extra)
        return cfg

    base_hub = {"hub_class": PHHub, "hub_kwargs": {"options": {}},
                "opt_kwargs": {"options": farmer_ph_opts()}}
    sp = [{"spoke_class": __import__(
        "mpisppy_tpu.cylinders.spoke", fromlist=["x"]
    ).LagrangianOuterBound, "opt_kwargs": {"options": {}}}]

    hub, _ = gc._fuse_wheel(fused_cfg(["--async-staleness", "2",
                                       "--async-exchange-deadline-s",
                                       "2.5"]),
                            dict(base_hub), sp)
    assert hub["hub_class"] is AsyncPHHub
    assert hub["opt_class"] is aw.AsyncFusedPH
    assert hub["opt_kwargs"]["async_options"].staleness == 2
    assert hub["opt_kwargs"]["async_options"].exchange_deadline_s == 2.5
    assert hub["hub_kwargs"]["options"]["async_staleness"] == 2

    hub0, _ = gc._fuse_wheel(fused_cfg([]), dict(base_hub), sp)
    assert hub0["hub_class"] is PHHub
    assert hub0["opt_class"] is fw.FusedPH
    assert "async_options" not in hub0["opt_kwargs"]
