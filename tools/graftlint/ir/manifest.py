###############################################################################
# graftlint IR layer: the declarative KERNEL MANIFEST (ISSUE 15 tentpole).
#
# Every jitted entry point the wheel stack dispatches in anger is
# enumerated here once, with a builder that constructs the kernel on
# SMALL abstract shapes through the same fixture machinery the driver
# dry run uses (__graft_entry__._flagship_batch/_sslp_batch/
# _bnb_probe_state/_cross_scen_probe_impl) — so the manifest and
# `dryrun_multichip` can never drift: they trace the same code through
# the same builders, and the dry run's collective asserts read THIS
# file's per-kernel declarations (declared_collectives) instead of
# hard-coding them.
#
# A KernelSpec is pure data + a lazy builder; importing this module
# costs nothing (no jax import at module scope) so the CLI can print
# per-rule kernel counts on a jax-less host.  The IR audit
# (tools/graftlint/ir/audit.py) calls spec.build(fx) to get
# (jitted_fn, args) and derives per-kernel facts from the jaxpr and the
# CPU-lowered HLO; the five IR passes (passes.py) lint those facts.
#
# Declaring a new kernel (docs/static_analysis.md, "IR layer"):
#   1. write a builder `fx -> (fn, args)` below (reuse the Fixtures
#      batches; keep shapes small — the audit is about IR structure,
#      not numerics);
#   2. append a KernelSpec: `sharded=True` + `collectives={...}` when
#      the kernel is dispatched against sharded batches (EXACT set —
#      the collective-manifest pass checks both directions),
#      `virtual=True` + `temp_budget_bytes` when it is VirtualBatch-fed
#      (the scengen "data exists only as transients" contract),
#      `fast=True` when it belongs in the tier-1 subset (cheap trace +
#      compile);
#   3. regenerate KERNEL_IR.json: `python -m tools.graftlint.ir
#      --emit KERNEL_IR.json`.
###############################################################################
from __future__ import annotations

import dataclasses
import functools

#: collective HLO ops the collective-manifest pass recognizes — the
#: kinds XLA SPMD partitioning can emit for our reductions/gathers
COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute")

#: bytes threshold for the const-capture pass: a concrete array
#: constant at least this large baked into a kernel's jaxpr is a
#: finding (the PR-4/PR-9 recompile-leak class; small iota/eye-style
#: constants are idiomatic and exempt)
CONST_BYTES_THRESHOLD = 1024


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """One audited kernel: identity + lazy builder + declarations."""

    name: str
    build: object               # Fixtures -> (jitted_fn, args tuple)
    doc: str = ""
    #: EXACT collective kinds the sharded (>= 2 device) lowering must
    #: contain — both directions are linted.  Only read when `sharded`.
    collectives: frozenset = frozenset()
    sharded: bool = False
    #: VirtualBatch-fed kernel: the memory-high-water pass enforces the
    #: scengen transients contract against `temp_budget_bytes`
    virtual: bool = False
    #: ceiling on compiled temp bytes (memory_analysis high-water) for
    #: virtual kernels — a materialized S-major copy that outlives the
    #: realize() transient blows straight through it
    temp_budget_bytes: int | None = None
    #: member of the tier-1 fast subset (budget-asserted < 60 s total)
    fast: bool = False


# ---------------------------------------------------------------------------
# fixtures: small abstract-shape batches + derived states, shared
# across builders and cached per audit run.  `mesh` None = single
# device; a Mesh shards every batch (the collective facts path).
# ---------------------------------------------------------------------------
class Fixtures:
    """Lazily built, memoized kernel inputs on small shapes."""

    def __init__(self, mesh=None):
        self.mesh = mesh

    def _memo(fn):  # noqa: N805 — decorator, not a method
        name = fn.__name__

        @property
        @functools.wraps(fn)
        def wrapper(self):
            key = "_memo_" + name
            if not hasattr(self, key):
                setattr(self, key, fn(self))
            return getattr(self, key)
        return wrapper

    def _shard(self, batch):
        if self.mesh is None:
            return batch
        from mpisppy_tpu.parallel import mesh as mesh_mod
        return mesh_mod.shard_batch(batch, self.mesh)

    @_memo
    def farmer(self):
        import __graft_entry__ as ge
        from mpisppy_tpu.core import batch as batch_mod
        n_dev = 1 if self.mesh is None else self.mesh.devices.size
        b = ge._flagship_batch(num_scens=max(6, 2 * n_dev),
                               crops_multiplier=1)
        if self.mesh is not None:
            b = batch_mod.pad_to_multiple(b, n_dev)
        return self._shard(b)

    @_memo
    def sslp(self):
        import __graft_entry__ as ge
        n_dev = 1 if self.mesh is None else self.mesh.devices.size
        return self._shard(ge._sslp_batch(num_scens=max(4, 2 * n_dev)))

    @_memo
    def ph_opts(self):
        from mpisppy_tpu.algos import ph as ph_mod
        from mpisppy_tpu.ops import pdhg
        return ph_mod.PHOptions(
            subproblem_windows=2, iter0_windows=4,
            pdhg=pdhg.PDHGOptions(tol=1e-4, restart_period=10))

    @_memo
    def pdhg_opts(self):
        from mpisppy_tpu.ops import pdhg
        return pdhg.PDHGOptions(tol=1e-4, max_iters=40,
                                restart_period=10)

    @_memo
    def rho(self):
        import jax.numpy as jnp
        return jnp.ones(self.farmer.num_nonants, jnp.float32)

    @_memo
    def ph_state(self):
        from mpisppy_tpu.algos import ph as ph_mod
        st, _, _ = ph_mod.ph_iter0(self.farmer, self.rho, self.ph_opts)
        return st

    @_memo
    def wheel_opts(self):
        from mpisppy_tpu.algos import fused_wheel as fw
        return fw.FusedWheelOptions(lag_windows=2, xhat_windows=2,
                                    slam_windows=1, shuffle_windows=1)

    @_memo
    def fused_state(self):
        from mpisppy_tpu.algos import fused_wheel as fw
        fst, _, _ = fw.fused_iter0(self.farmer, self.rho, self.ph_opts,
                                   self.wheel_opts)
        return fst

    @_memo
    def shuffle_id(self):
        import jax.numpy as jnp
        return jnp.asarray(1, jnp.int32)

    @_memo
    def xhat_cand(self):
        from mpisppy_tpu.algos import fused_wheel as fw
        return fw._round_xbar(self.farmer, self.ph_state.xbar_nodes)

    @_memo
    def fwph_opts(self):
        from mpisppy_tpu.algos import fwph as fwph_mod
        return fwph_mod.FWPHOptions(fw_iter_limit=1, max_columns=4,
                                    iter0_windows=4, oracle_windows=2)

    @_memo
    def fwph_state(self):
        from mpisppy_tpu.algos import fwph as fwph_mod
        st, _, _ = fwph_mod.fwph_init(self.farmer, self.rho,
                                      self.fwph_opts)
        return st

    @_memo
    def bnb_opts(self):
        from mpisppy_tpu.ops import bnb as bnb_mod
        from mpisppy_tpu.ops import pdhg
        return bnb_mod.BnBOptions(
            max_rounds=1, pump_rounds=0,
            lp=pdhg.PDHGOptions(tol=1e-3, max_iters=200))

    @_memo
    def bnb_state(self):
        import __graft_entry__ as ge
        return ge._bnb_probe_state(self.sslp, self.bnb_opts)

    @_memo
    def virtual(self):
        from mpisppy_tpu import scengen
        from mpisppy_tpu.models import farmer as farmer_model
        n_dev = 1 if self.mesh is None else self.mesh.devices.size
        prog = farmer_model.scenario_program(max(8, 2 * n_dev), seed=0)
        vb = scengen.virtual_batch(prog, pad_to=n_dev)
        if self.mesh is not None:
            from mpisppy_tpu.parallel import mesh as mesh_mod
            vb = mesh_mod.shard_batch(vb, self.mesh)
        return vb

    @_memo
    def virtual_rho(self):
        import jax.numpy as jnp
        return jnp.ones(self.virtual.num_nonants, jnp.float32)

    @_memo
    def virtual_ph_state(self):
        from mpisppy_tpu.algos import ph as ph_mod
        st, _, _ = ph_mod.ph_iter0(self.virtual, self.virtual_rho,
                                   self.ph_opts)
        return st

    @_memo
    def pdhg_init(self):
        from mpisppy_tpu.ops import pdhg
        return pdhg.init_state(self.sslp.qp, self.pdhg_opts)

    # -- elastic mesh (ISSUE 17): the harvest kernels at the full and
    # the shrunk (survivor) topology.  Prime S so the pad count
    # genuinely differs between the two layouts.
    @_memo
    def elastic_mesh(self):
        from mpisppy_tpu.parallel import mesh as mesh_mod
        return self.mesh if self.mesh is not None \
            else mesh_mod.make_mesh(1)

    @_memo
    def elastic_shrunk_mesh(self):
        import jax

        from mpisppy_tpu.parallel import elastic, mesh as mesh_mod
        devs = elastic.survivor_devices(jax.devices(), 2, [1])
        return mesh_mod.make_mesh(devices=devs)

    def _elastic_batch(self, mesh):
        from mpisppy_tpu import scengen
        from mpisppy_tpu.models import farmer as farmer_model
        from mpisppy_tpu.parallel import mesh as mesh_mod
        prog = farmer_model.scenario_program(7, seed=0)
        return mesh_mod.shard_batch(scengen.virtual_batch(prog), mesh,
                                    pad=True)

    @_memo
    def elastic_full(self):
        return self._elastic_batch(self.elastic_mesh)

    @_memo
    def elastic_shrunk(self):
        return self._elastic_batch(self.elastic_shrunk_mesh)

    def _elastic_fused_state(self, batch):
        import jax.numpy as jnp

        from mpisppy_tpu.algos import fused_wheel as fw
        rho = jnp.ones(batch.num_nonants, jnp.float32)
        fst, _, _ = fw.fused_iter0(batch, rho, self.ph_opts,
                                   self.wheel_opts)
        return fst

    @_memo
    def elastic_full_state(self):
        return self._elastic_fused_state(self.elastic_full)

    @_memo
    def elastic_shrunk_state(self):
        return self._elastic_fused_state(self.elastic_shrunk)


# ---------------------------------------------------------------------------
# builders (each: Fixtures -> (jitted_fn, args))
# ---------------------------------------------------------------------------
def _b_ph_iter0(fx):
    from mpisppy_tpu.algos import ph as ph_mod
    return ph_mod.ph_iter0, (fx.farmer, fx.rho, fx.ph_opts)


def _b_ph_iterk(fx):
    from mpisppy_tpu.algos import ph as ph_mod
    return ph_mod.ph_iterk, (fx.farmer, fx.ph_state, fx.ph_opts)


def _b_ph_eobjective(fx):
    from mpisppy_tpu.algos import ph as ph_mod
    return ph_mod.ph_eobjective, (fx.farmer, fx.ph_state)


def _b_fused_iter0(fx):
    from mpisppy_tpu.algos import fused_wheel as fw
    return fw.fused_iter0, (fx.farmer, fx.rho, fx.ph_opts,
                            fx.wheel_opts)


def _b_fused_iterk(fx):
    from mpisppy_tpu.algos import fused_wheel as fw
    return fw.fused_iterk, (fx.farmer, fx.fused_state, fx.ph_opts,
                            fx.wheel_opts, fx.shuffle_id)


def _b_lag_plane(fx):
    from mpisppy_tpu.algos import fused_wheel as fw
    fst = fx.fused_state
    return fw.lag_plane, (fx.farmer, fst.ph.W, fst.lag_solver,
                          fx.wheel_opts, 2)


def _b_xhat_plane(fx):
    from mpisppy_tpu.algos import fused_wheel as fw
    fst = fx.fused_state
    return fw.xhat_plane, (fx.farmer, fx.xhat_cand, fst.xhat_solver,
                           fx.wheel_opts, 2)


def _b_slam_plane(fx):
    from mpisppy_tpu.algos import fused_wheel as fw
    fst = fx.fused_state
    return fw.slam_plane, (fx.farmer, fst.ph.solver.x, fst.slam_solver,
                           fx.wheel_opts, 1, True)


def _b_shuf_plane(fx):
    from mpisppy_tpu.algos import fused_wheel as fw
    fst = fx.fused_state
    return fw.shuf_plane, (fx.farmer, fst.ph.solver.x, fst.shuf_solver,
                           fx.shuffle_id, fx.wheel_opts, 1)


def _b_ph_stale_step(fx):
    from mpisppy_tpu.algos import fused_wheel as fw
    plane = fw.plane_of(fx.ph_state)
    return fw.ph_stale_step, (fx.farmer, fx.ph_state, plane,
                              fx.ph_opts)


def _b_xhat_evaluate(fx):
    from mpisppy_tpu.algos import xhat as xhat_mod
    return xhat_mod._evaluate_core, (fx.farmer, fx.xhat_cand,
                                     fx.pdhg_opts, 1e-3)


def _b_xhat_evaluate_warm(fx):
    from mpisppy_tpu.algos import xhat as xhat_mod
    fst = fx.fused_state
    return xhat_mod._evaluate_warm_core, (fx.farmer, fx.xhat_cand,
                                          fst.xhat_solver,
                                          fx.pdhg_opts, 1e-3)


def _b_xhat_shuffle(fx):
    import jax.numpy as jnp
    from mpisppy_tpu.algos import xhat as xhat_mod
    scen_ids = jnp.arange(2, dtype=jnp.int32)
    x_non = fx.farmer.nonants(fx.ph_state.solver.x)
    return xhat_mod.xhat_shuffle, (fx.farmer, x_non, scen_ids, 2,
                                   fx.pdhg_opts)


def _b_fwph_init(fx):
    from mpisppy_tpu.algos import fwph as fwph_mod
    return fwph_mod.fwph_init, (fx.farmer, fx.rho, fx.fwph_opts)


def _b_fwph_iter(fx):
    from mpisppy_tpu.algos import fwph as fwph_mod
    return fwph_mod.fwph_iter, (fx.farmer, fx.fwph_state, fx.fwph_opts)


def _b_lshaped_cuts(fx):
    from mpisppy_tpu.algos import lshaped as ls_mod
    xhat0 = fx.ph_state.xbar_nodes[0]
    return ls_mod._subproblem_cuts, (fx.farmer, xhat0, fx.pdhg_opts)


@functools.lru_cache(maxsize=1)
def _cross_scen_probe():
    """Module-level jit of the dry run's probe impl — one shared
    compile cache, same trace as dryrun_multichip's."""
    import jax
    import __graft_entry__ as ge
    return jax.jit(ge._cross_scen_probe_impl, static_argnames=("opts",))


def _b_cross_scen_cuts(fx):
    st = fx.ph_state
    return _cross_scen_probe(), (fx.farmer, st.xbar * 1.01, st.xbar,
                                 fx.pdhg_opts)


@functools.lru_cache(maxsize=1)
def _mpc_shift_kernel():
    """The MPC warm-start shift kernel's process-wide jit — the SAME
    executable mpc.shift.shift_state dispatches (shared lazy global,
    so the audit and a live stream trace one cache entry)."""
    import jax

    from mpisppy_tpu.mpc import shift as shift_mod
    if shift_mod._shift_state_jit is None:
        shift_mod._shift_state_jit = jax.jit(shift_mod._shift_state_impl)
    return shift_mod._shift_state_jit


def _b_mpc_shift(fx):
    import jax.numpy as jnp

    from mpisppy_tpu.mpc import shift as shift_mod
    st = fx.ph_state
    # a stride-1 persistence plan over the farmer nonant axis — the
    # same roll + fresh-tail gather shape every horizon emits
    plan = shift_mod.uc_plan(1, fx.farmer.num_nonants)
    x_non = fx.farmer.nonants(st.solver.x)
    return _mpc_shift_kernel(), (st.W, st.xbar_nodes, x_non,
                                 jnp.asarray(plan.src_idx),
                                 jnp.asarray(plan.fresh_mask))


def _b_bnb_round(fx):
    from mpisppy_tpu.ops import bnb as bnb_mod
    int_cols, bst = fx.bnb_state
    b = fx.sslp
    return bnb_mod.bnb_round, (b.qp, b.d_col, int_cols, bst,
                               fx.bnb_opts)


def _b_pdhg_solve_loop(fx):
    from mpisppy_tpu.ops import pdhg
    return pdhg._solve_loop_jit, (fx.sslp.qp, fx.pdhg_opts,
                                  fx.pdhg_init)


def _b_pdhg_solve_fixed(fx):
    from mpisppy_tpu.ops import pdhg
    return pdhg._solve_fixed_jit, (fx.sslp.qp, 2, fx.pdhg_opts,
                                   fx.pdhg_init)


def _b_pallas_window(fx):
    from mpisppy_tpu.ops import pdhg_pallas as pp
    st = fx.pdhg_init
    tau = 0.9 * st.omega / st.Lnorm
    sigma = 0.9 / (st.omega * st.Lnorm)
    return pp.run_window, (fx.sslp.qp, st.x, st.y, st.x_sum, st.y_sum,
                           tau, sigma, st.done, 4, 8, None, True,
                           True, None)


def _b_scengen_realize(fx):
    from mpisppy_tpu.scengen import virtual as virt
    return virt._realize_jit, (fx.virtual,)


def _b_ph_iter0_virtual(fx):
    from mpisppy_tpu.algos import ph as ph_mod
    return ph_mod.ph_iter0, (fx.virtual, fx.virtual_rho, fx.ph_opts)


def _b_ph_iterk_virtual(fx):
    from mpisppy_tpu.algos import ph as ph_mod
    return ph_mod.ph_iterk, (fx.virtual, fx.virtual_ph_state,
                             fx.ph_opts)


def _b_fused_iterk_elastic(fx):
    from mpisppy_tpu.algos import fused_wheel as fw
    return fw.fused_iterk, (fx.elastic_full, fx.elastic_full_state,
                            fx.ph_opts, fx.wheel_opts, fx.shuffle_id)


def _b_fused_iterk_reshard(fx):
    from mpisppy_tpu.algos import fused_wheel as fw
    return fw.fused_iterk, (fx.elastic_shrunk, fx.elastic_shrunk_state,
                            fx.ph_opts, fx.wheel_opts, fx.shuffle_id)


def _b_ckpt_gather(fx):
    # a scenario-sharded solver-x plane stands in for the fused state's
    # leaf: same sharding + dtype, no fused_iter0 compile on the fast
    # subset's critical path (the 60s tier-1 budget)
    import jax
    import jax.numpy as jnp

    from mpisppy_tpu.cylinders import hub as hub_mod
    from mpisppy_tpu.parallel import mesh as mesh_mod
    b = fx.elastic_full
    ndev = fx.elastic_mesh.devices.size
    s_pad = -(-b.num_scenarios // ndev) * ndev
    x = jax.device_put(
        jnp.zeros((s_pad, b.num_nonants), jnp.float32),
        mesh_mod.scen_sharding(fx.elastic_mesh))
    fn = hub_mod._replicated_gather(fx.elastic_mesh)
    return fn, (x,)


# ---------------------------------------------------------------------------
# the manifest
# ---------------------------------------------------------------------------
# Pinned to the installed XLA (0.9.0): every sharded kernel's
# scenario-axis reduction lowers to an all-reduce over both devices of
# the 2-device audit mesh (replica_groups [1,2]<=[2]).
_AR = frozenset({"all-reduce"})
_AG_AR = frozenset({"all-gather", "all-reduce"})

#: scengen transients budget: the audit programs realize a ~few-KB
#: farmer batch in-trace; a compiled high-water above this means an
#: S-major tensor outlived its transient (the contract the pass holds).
#: Generous vs the measured ~0.4-9 KB high-waters, tight vs any real
#: S-major residency creep — and the KERNEL_IR.json +10% temp-bytes
#: ratchet pins the actual number far below it.
_VIRTUAL_TEMP_BUDGET = 1 << 20      # 1 MiB

MANIFEST: tuple[KernelSpec, ...] = (
    KernelSpec("ph_iter0", _b_ph_iter0,
               "PH iter0: plain solves + W seed + trivial bound",
               collectives=_AR, sharded=True, fast=True),
    KernelSpec("ph_iterk", _b_ph_iterk,
               "one PH iteration (the hub hot step)",
               collectives=_AR, sharded=True, fast=True),
    KernelSpec("ph_eobjective", _b_ph_eobjective,
               "E[f_s(x_s)] at current iterates",
               collectives=_AR, sharded=True, fast=True),
    KernelSpec("fused_iter0", _b_fused_iter0,
               "fused wheel iter0 (hub + 4 bound planes)",
               collectives=_AR, sharded=True),
    KernelSpec("fused_iterk", _b_fused_iterk,
               "fused wheel iteration (monolithic plane program)",
               collectives=_AG_AR, sharded=True),
    KernelSpec("lag_plane", _b_lag_plane,
               "split-dispatch Lagrangian bound plane",
               collectives=_AR, sharded=True),
    KernelSpec("xhat_plane", _b_xhat_plane,
               "split-dispatch xhat recourse-evaluation plane",
               collectives=_AG_AR, sharded=True),
    KernelSpec("slam_plane", _b_slam_plane,
               "split-dispatch slam-heuristic plane",
               collectives=_AR, sharded=True),
    KernelSpec("shuf_plane", _b_shuf_plane,
               "split-dispatch shuffle-candidate plane",
               collectives=_AG_AR, sharded=True),
    KernelSpec("ph_stale_step", _b_ph_stale_step,
               "APH-class stale-plane hub step (async wheel)",
               collectives=_AR, sharded=True, fast=True),
    KernelSpec("xhat_evaluate", _b_xhat_evaluate,
               "xhat evaluate core (fixed-nonant recourse)",
               collectives=_AR, sharded=True, fast=True),
    KernelSpec("xhat_evaluate_warm", _b_xhat_evaluate_warm,
               "warm-state xhat evaluate core",
               collectives=_AR, sharded=True),
    KernelSpec("xhat_shuffle", _b_xhat_shuffle,
               "k-candidate shuffle evaluation",
               collectives=_AR, sharded=True),
    KernelSpec("fwph_init", _b_fwph_init,
               "FWPH init (iter0 solves + column seed)",
               collectives=_AR, sharded=True),
    KernelSpec("fwph_iter", _b_fwph_iter,
               "FWPH SDM iteration",
               collectives=_AG_AR, sharded=True),
    KernelSpec("lshaped_cuts", _b_lshaped_cuts,
               "L-shaped per-scenario cut extraction",
               collectives=_AR, sharded=True, fast=True),
    KernelSpec("cross_scen_cuts", _b_cross_scen_cuts,
               "cross-scenario cut launch (winner argmax)",
               collectives=_AG_AR, sharded=True),
    KernelSpec("bnb_round", _b_bnb_round,
               "batched-MIP best-first B&B round",
               collectives=_AR, sharded=True, fast=True),
    KernelSpec("pdhg_solve_loop", _b_pdhg_solve_loop,
               "host-level PDHG solve loop (shape-keyed jit)",
               fast=True),
    KernelSpec("pdhg_solve_fixed", _b_pdhg_solve_fixed,
               "fixed-window PDHG solve (shape-keyed jit)",
               fast=True),
    KernelSpec("pallas_window", _b_pallas_window,
               "Pallas restart window, interpret mode (CPU trace of "
               "the double-buffered pipeline engine)"),
    KernelSpec("scengen_realize", _b_scengen_realize,
               "VirtualBatch.realize jitted whole-batch synthesis",
               virtual=True, temp_budget_bytes=_VIRTUAL_TEMP_BUDGET,
               fast=True),
    KernelSpec("ph_iter0_virtual", _b_ph_iter0_virtual,
               "PH iter0 fed by a VirtualBatch (concretize path)",
               collectives=_AR, sharded=True, virtual=True,
               temp_budget_bytes=_VIRTUAL_TEMP_BUDGET, fast=True),
    KernelSpec("ph_iterk_virtual", _b_ph_iterk_virtual,
               "PH iteration fed by a VirtualBatch (concretize path)",
               collectives=_AR, sharded=True, virtual=True,
               temp_budget_bytes=_VIRTUAL_TEMP_BUDGET, fast=True),
    KernelSpec("fused_iterk_elastic", _b_fused_iterk_elastic,
               "elastic hub hot step at the FULL topology (sharded "
               "VirtualBatch, prime S padded for the full mesh)",
               collectives=_AG_AR, sharded=True, virtual=True,
               temp_budget_bytes=_VIRTUAL_TEMP_BUDGET),
    KernelSpec("fused_iterk_reshard", _b_fused_iterk_reshard,
               "elastic hub hot step at the SHRUNK (survivor) "
               "topology — the shape run_elastic recompiles after a "
               "host loss; single survivor, so no collectives",
               virtual=True, temp_budget_bytes=_VIRTUAL_TEMP_BUDGET),
    KernelSpec("mpc_shift_state", _b_mpc_shift,
               "MPC warm-start shift: (W, xbar_nodes, x) rolled along "
               "the nonant axis by a traced src_idx/fresh_mask gather "
               "— every stream step re-dispatches one executable",
               fast=True),
    KernelSpec("ckpt_gather", _b_ckpt_gather,
               "replicated checkpoint gather (hub._replicated_gather "
               "— the bounded collective under emergency saves)",
               collectives=frozenset({"all-gather"}), sharded=True,
               fast=True),
)

_BY_NAME = {s.name: s for s in MANIFEST}


def spec(name: str) -> KernelSpec:
    return _BY_NAME[name]


def declared_collectives(kernel: str) -> frozenset | None:
    """The exact collective kinds declared for a sharded kernel, or
    None when the kernel is not in the manifest / not sharded (the
    __graft_entry__ dry run falls back to its legacy check then)."""
    s = _BY_NAME.get(kernel)
    if s is None or not s.sharded:
        return None
    return s.collectives


def names(subset: str = "full") -> list[str]:
    """Kernel names in `subset` ('full' or the tier-1 'fast' set)."""
    return [s.name for s in MANIFEST if subset == "full" or s.fast]
