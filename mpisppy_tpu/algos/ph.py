###############################################################################
# Progressive Hedging, TPU-native.
#
# The reference's PH (ref:mpisppy/phbase.py, ref:mpisppy/opt/ph.py) is a
# Python loop over per-scenario Pyomo models: Compute_Xbar does one MPI
# Allreduce per tree node, Update_W is a loop over Pyomo Params, and
# solve_loop dispatches each subproblem to a CPU MIP solver
# sequentially.  Here ONE jitted step does all of it as tensor math over
# the scenario batch:
#
#   x_non   = gather nonants from the batched PDHG iterates   (S, N)
#   xbar    = node_average(x_non)           <- the psum/Allreduce analog
#   W      += rho * (x_non - xbar)          (ref:phbase.py:301-326)
#   conv    = E[ ||x_non - xbar||_1 ] / N   (ref:phbase.py:349-371)
#   qp_eff  = base qp + W·x + rho/2 (x - xbar)^2 on nonant slots
#             (ref:phbase.py:670-760 — exact diagonal prox, no
#              linearization cuts needed: the kernel natively solves QPs)
#   solver  = solve_fixed(qp_eff, n_windows) warm-started
#
# The step is compiled once and runs identically on 1 device or a pod
# mesh — scenario-axis reductions become XLA all-reduces via sharding.
# Iteration semantics match the reference: Iter0 solves WITHOUT W/prox
# and seeds W = rho(x - xbar) (ref:phbase.py:829-946); the trivial bound
# is the wait-and-see expectation E[min f_s] (ref:spopt.py:377).
###############################################################################
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from mpisppy_tpu import global_toc
from mpisppy_tpu.core.batch import ScenarioBatch, concretize
from mpisppy_tpu.ops import pdhg
from mpisppy_tpu.parallel.mesh import shard_like_batch
from mpisppy_tpu.telemetry import profiler as _prof

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class PHOptions:
    """Static PH options (ref Config group ph_args,
    ref:mpisppy/utils/config.py:250-315)."""

    default_rho: float = 1.0
    max_iterations: int = 100
    conv_thresh: float = 1e-4          # ref 'convthresh'
    subproblem_windows: int = 8        # PDHG restart windows per PH iter
    iter0_windows: int = 400           # budget for the cold iter0 solves
    pdhg: pdhg.PDHGOptions = pdhg.PDHGOptions(tol=1e-6)
    smoothed: bool = False             # ref 'smoothed' / Update_z
    smooth_beta: float = 0.2           # ref 'defaultPHbeta'
    smooth_p: float = 0.0              # ref 'defaultPHp' (coef of (x-z)^2/2)
    compute_xsqbar: bool = False       # node avg of x^2 (fixer variance test)
    display_progress: bool = False
    time_limit: float | None = None


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["solver", "W", "z", "xbar", "xbar_nodes", "xsqbar", "conv",
                 "rho"],
    meta_fields=[],
)
@dataclasses.dataclass(frozen=True)
class PHState:
    solver: pdhg.PDHGState  # scaled-space subproblem iterates
    W: Array                # (S, N) duals, original space
    z: Array                # (S, N) smoothing state (unused unless smoothed)
    xbar: Array             # (S, N) per-scenario view of node averages
    xbar_nodes: Array       # (num_nodes, N) node averages
    xsqbar: Array           # (S, N) node avg of x^2 (zeros unless enabled)
    conv: Array             # () scaled ||x - xbar||_1
    rho: Array              # (N,) per-slot penalty


def _xbar_w_conv(batch: ScenarioBatch, st: PHState, beta: float,
                 smoothed: bool, compute_xsqbar: bool):
    """Compute_Xbar + Update_W (+Update_z) + convergence_diff, fused.

    Semantics match ref:mpisppy/phbase.py:301-371: W += rho*(x - xbar)
    always; smoothing only updates z += beta*(x - z) (the (x-z)^2 term
    enters the objective separately).  The convergence metric is the
    probability-weighted mean of ||x - xbar||_1 per slot — identical to
    the reference's unweighted mean for uniform probabilities, and the
    correct generalization otherwise (padded p=0 scenarios drop out).
    xsqbar (the fixer variance statistic, ref:phbase.py:60-66) costs an
    extra segmented reduction, so it is only computed when an extension
    asks for it (compute_xsqbar).
    """
    x_non = batch.nonants(st.solver.x)
    xbar, xbar_nodes = batch.node_average(x_non)
    if compute_xsqbar:
        xsqbar, _ = batch.node_average(x_non * x_non)
    else:
        xsqbar = st.xsqbar
    W = st.W + st.rho * (x_non - xbar)
    if batch.var_prob is not None:
        # variable probability: mask W and the convergence metric on
        # absent (weight-0) slots (ref:mpisppy/spbase.py:398-441
        # prob0_mask; ref:aph.py W *= prob0_mask)
        mask = (batch.var_prob > 0.0).astype(W.dtype)
        W = W * mask
        conv = jnp.sum(batch.var_prob * jnp.abs(x_non - xbar)) \
            / batch.num_nonants
    else:
        conv = batch.expectation(
            jnp.sum(jnp.abs(x_non - xbar), axis=-1)) / batch.num_nonants
    z = (1.0 - beta) * st.z + beta * x_non if smoothed else st.z
    return x_non, xbar, xbar_nodes, xsqbar, W, z, conv


def _prox_qp(batch: ScenarioBatch, W: Array, xbar: Array, z: Array,
             rho: Array, smooth_p: float):
    """base objective + W·x + rho/2 (x-xbar)^2 [+ p/2 (x-z)^2] on nonant
    slots (ref:mpisppy/phbase.py:670-760, exact instead of cut-based)."""
    lin = W - rho * xbar - smooth_p * z
    quad = jnp.broadcast_to(rho + smooth_p, xbar.shape)
    return batch.with_nonant_linear_quad(lin, quad)


def iter0_solve_and_certify(batch: ScenarioBatch, windows: int,
                            pdhg_opts: pdhg.PDHGOptions):
    """Plain (no W, no prox) scenario solves + dual-certified trivial
    bound — shared by PH and APH Iter0.

    The trivial bound (wait-and-see expectation, ref:spopt.py:377) is
    taken from the DUAL side with a residual certificate: a truncated
    primal iterate can overshoot the scenario optimum, which would make
    E[obj] an INVALID outer bound; the Fenchel dual value at a
    dual-feasible iterate is always valid.  Returns
    (solver_state, trivial_bound, certified)."""
    from mpisppy_tpu.ops import boxqp as _boxqp
    st0 = pdhg.init_state(batch.qp, pdhg_opts)
    solver = pdhg.solve_fixed(batch.qp, windows, pdhg_opts, st0)
    dual = _boxqp.dual_objective(batch.qp, solver.x, solver.y)
    _, rd, _ = _boxqp.kkt_residuals(batch.qp, solver.x, solver.y)
    tol = jnp.maximum(pdhg_opts.tol, 5.0 * jnp.finfo(solver.x.dtype).eps)
    real = batch.p > 0.0
    certified = jnp.all(jnp.where(real, rd <= 10.0 * tol, True))
    return solver, batch.expectation(dual), certified


def kernel_opts(opts: PHOptions) -> PHOptions:
    """Normalize host-loop-only fields (iteration caps, display, time
    limits) to fixed values before an options object becomes a jit
    static argument: they do not affect the compiled program, and
    letting them into the hash caused spurious recompiles (a multi-
    minute compile per distinct max_iterations value)."""
    return dataclasses.replace(
        opts, default_rho=0.0, max_iterations=0, conv_thresh=0.0,
        display_progress=False, time_limit=None)


@partial(jax.jit, static_argnames=("opts",))
def ph_iter0(batch: ScenarioBatch, rho: Array, opts: PHOptions):
    """Iter0: plain scenario solves, xbar, W seed, trivial bound
    (ref:mpisppy/phbase.py:829-946).  Returns
    (state, trivial_bound, certified)."""
    batch = concretize(batch)  # scengen: synthesize in-trace
    solver, trivial_bound, certified = iter0_solve_and_certify(
        batch, opts.iter0_windows, opts.pdhg)
    zeros = jnp.zeros((batch.num_scenarios, batch.num_nonants),
                      batch.qp.c.dtype)
    zeros_nodes = jnp.zeros((batch.tree.num_nodes, batch.num_nonants),
                            batch.qp.c.dtype)
    st = PHState(solver=solver, W=zeros, z=zeros, xbar=zeros,
                 xbar_nodes=zeros_nodes, xsqbar=zeros,
                 conv=jnp.asarray(jnp.inf, batch.qp.c.dtype), rho=rho)
    x_non, xbar, xbar_nodes, xsqbar, W, z, conv = _xbar_w_conv(
        batch, st, opts.smooth_beta, False, opts.compute_xsqbar)
    return (dataclasses.replace(st, W=W, xbar=xbar, xbar_nodes=xbar_nodes,
                                xsqbar=xsqbar, conv=conv),
            trivial_bound, certified)


@partial(jax.jit, static_argnames=("opts",))
def ph_iterk(batch: ScenarioBatch, st: PHState, opts: PHOptions) -> PHState:
    """One PH iteration: solve subproblems with current (W, xbar), then
    refresh xbar/W/conv from the new iterates
    (ref:mpisppy/phbase.py:949-1061, with xbar computed *after* the
    solves so the returned state is self-consistent)."""
    batch = concretize(batch)  # scengen: synthesize in-trace
    smooth_p = opts.smooth_p if opts.smoothed else 0.0
    qp_eff = _prox_qp(batch, st.W, st.xbar, st.z, st.rho, smooth_p)
    solver = pdhg.solve_fixed(qp_eff, opts.subproblem_windows, opts.pdhg,
                              st.solver)
    st = dataclasses.replace(st, solver=solver)
    x_non, xbar, xbar_nodes, xsqbar, W, z, conv = _xbar_w_conv(
        batch, st, opts.smooth_beta, opts.smoothed, opts.compute_xsqbar)
    return dataclasses.replace(st, W=W, z=z, xbar=xbar,
                               xbar_nodes=xbar_nodes, xsqbar=xsqbar,
                               conv=conv)


@jax.jit
def ph_eobjective(batch: ScenarioBatch, st: PHState) -> Array:
    """E[f_s(x_s)] at current iterates (ref:mpisppy/spopt.py:344-376)."""
    batch = concretize(batch)
    return batch.expectation(batch.objective(st.solver.x))


class PH:
    """Host-side PH driver (ref:mpisppy/opt/ph.py:24-76).

    Supports the reference's extension plane: `extensions` is an object
    (or class) with the hook methods of ref:mpisppy/extensions/extension.py;
    missing hooks are skipped.  `converger` gets is_converged(self).
    `spcomm` (set by the cylinder layer) gets sync()/is_converged().
    """

    def __init__(self, options: PHOptions, batch: ScenarioBatch,
                 scenario_names=None, rho: Array | float | None = None,
                 extensions=None, converger=None, rho_setter=None):
        self.options = options
        self.batch = batch
        self.scenario_names = scenario_names or [
            f"scen{i}" for i in range(batch.num_real)]
        if rho is None:
            rho = options.default_rho
        rho_arr = jnp.broadcast_to(
            jnp.asarray(rho, batch.qp.c.dtype), (batch.num_nonants,))
        if rho_setter is not None:
            rho_arr = jnp.asarray(rho_setter(batch), batch.qp.c.dtype)
        self.rho = rho_arr
        # `extensions`/`converger` may be a class, a factory taking the
        # driver (e.g. functools.partial(MultiExtension, ext_classes=…)),
        # or an already-built object.
        def _build(thing):
            if thing is None:
                return None
            # classes, functions, and partials are factories taking the
            # driver; built objects (not callable) pass through
            if isinstance(thing, type) or callable(thing):
                return thing(self)
            return thing
        self.extobject = _build(extensions)
        self.converger_object = _build(converger)
        self.spcomm = None
        self.state: PHState | None = None
        self.trivial_bound: float | None = None
        self.trivial_bound_certified: bool = False
        self._iter = 0

    # -- extension callout plumbing (ref:extensions/extension.py:18-151) --
    def _ext(self, hook: str):
        obj = self.extobject
        if obj is not None and hasattr(obj, hook):
            getattr(obj, hook)()

    @property
    def local_scenarios(self):  # parity helper for extensions
        return self.scenario_names

    _label = "PH"

    def state_template(self):
        """Abstract (shape/dtype) pytree of this driver's state — the
        unflatten template for checkpoint restore (hub.load_checkpoint)
        without paying an Iter0 solve."""
        st, _, _ = jax.eval_shape(
            partial(ph_iter0, opts=kernel_opts(self.options)),
            self.batch, self.rho)
        return st

    # -- algorithm step hooks (overridden by APH) -------------------------
    def _iter0_impl(self):
        st, tb, cert = ph_iter0(self.batch, self.rho,
                                kernel_opts(self.options))
        return shard_like_batch(st, self.batch), tb, cert

    def _iterk_impl(self):
        return ph_iterk(self.batch, self.state, kernel_opts(self.options))

    def _iter_msg(self, k: int, conv: float) -> str:
        return f"{self._label} iter {k}: conv = {conv:.3e}"

    def _read_conv(self) -> float:
        """Per-iteration convergence read (one device scalar transfer;
        FusedPH serves it from the packed scalar cache instead)."""
        return float(self.state.conv)

    def Eobjective(self) -> float:
        return float(ph_eobjective(self.batch, self.state))

    def Iter0(self) -> float:
        self._ext("pre_iter0")
        # the batched kernel has no per-scenario solver objects; "solver
        # creation" is the jitted step build, which happens inside
        # _iter0_impl — the hook fires at the reference's point in the
        # sequence (ref:mpisppy/phbase.py:851 after _create_solvers)
        self._ext("iter0_post_solver_creation")
        with _prof.span(self.spcomm, "iter0_solve"):
            self.state, tb, cert = self._iter0_impl()
        self.trivial_bound = float(tb)
        self.trivial_bound_certified = bool(cert)
        self._ext("post_iter0")
        if self.spcomm is not None:
            self.spcomm.sync()
        self._ext("post_iter0_after_sync")
        global_toc(f"{self._label} Iter0: trivial bound = "
                   f"{self.trivial_bound:.6g}",
                   self.options.display_progress)
        return self.trivial_bound

    def iterk_loop(self):
        import time
        t0 = time.time()
        for k in range(self._iter + 1, self.options.max_iterations + 1):
            self._iter = k
            with _prof.annotate("wheel/iteration"):
                self._ext("miditer")
                # the fused step solves + recomputes xbar/W in one
                # program, so the solve-loop hooks bracket the whole
                # jitted step (ref callout points:
                # mpisppy/phbase.py:1016-1045)
                self._ext("pre_solve_loop")
                with _prof.span(self.spcomm, "subproblem_solve"):
                    self.state = self._iterk_impl()
                self._ext("post_solve_loop")
                conv = self._read_conv()
                self._ext("enditer")
                if self.spcomm is not None:
                    self.spcomm.sync()
            self._ext("enditer_after_sync")
            global_toc(self._iter_msg(k, conv),
                       self.options.display_progress)
            # The hub object takes precedence over the local convergence
            # metric (ref:mpisppy/phbase.py:996-1015 ordering).
            if self.spcomm is not None and self.spcomm.is_converged():
                break
            if (self.converger_object is not None
                    and self.converger_object.is_converged()):
                break
            if conv <= self.options.conv_thresh:
                global_toc(f"{self._label} converged at iter {k} "
                           f"(conv={conv:.3e})",
                           self.options.display_progress)
                if self.spcomm is not None:
                    self.spcomm._term_reason = "conv-thresh"
                break
            if (self.options.time_limit is not None
                    and time.time() - t0 > self.options.time_limit):
                if self.spcomm is not None:
                    self.spcomm._term_reason = "time-limit"
                break
        return float(self.state.conv)

    def post_loops(self) -> float:
        self._ext("post_everything")
        return self.Eobjective()

    def ph_main(self):
        """Returns (conv, Eobj, trivial_bound) (ref:opt/ph.py:31-76).

        Resume: when state was preloaded (checkpoint restore — see
        utils.wxbarutils.load_ph_state and the hub's checkpoint hooks),
        Iter0 is skipped and the loop continues from the restored
        iteration counter — the analog of the reference's
        solve-retry/restart semantics (ref:mpisppy/spopt.py:931-960)."""
        if self.state is None:
            tb = self.Iter0()
        else:
            tb = self.trivial_bound
        conv = self.iterk_loop()
        # the first segment of the wheel's `finalize` span; the second
        # is WheelSpinner.spin's tail
        with _prof.span(self.spcomm, "finalize"):
            eobj = self.post_loops()
        return conv, eobj, tb

    # -- solution access (ref:spbase.py:561-672 analogs) -----------------
    def nonant_values(self) -> np.ndarray:
        """(num_nodes, N) converged per-node nonant values (xbar)."""
        return np.asarray(self.state.xbar_nodes)

    def first_stage_solution(self) -> np.ndarray:
        """(n_root_slots,) root-node nonant values."""
        nodes = self.nonant_values()
        root = np.nonzero(self.batch.tree.slot_stage == 1)[0]
        return nodes[0, root]
