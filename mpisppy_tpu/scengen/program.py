###############################################################################
# Seeded scenario synthesis: the ScenarioProgram (ROADMAP item 3a).
#
# The survey's problem statement — min E_s[ f(x, y_s, xi_s) ] — treats
# xi_s as data DRAWN FROM A DISTRIBUTION, yet the whole framework so far
# materializes every draw on the host (one numpy ScenarioSpec per
# scenario) and keeps the stacked result HBM-resident for the life of
# the run.  That is the 100k-scenario ceiling.  A ScenarioProgram is the
# recompute-instead-of-store answer (the idiom of the TPU
# distributed-linear-algebra line, PAPERS.md arXiv 2112.09017): a
# declarative, trace-pure recipe mapping a counter-based PRNG key to one
# scenario's data, so xi_s can be synthesized *inside* the iteration
# kernels and scenario count decouples from memory entirely.
#
# Key/counter scheme (docs/scengen.md):
#
#     key_s = jax.random.fold_in(PRNGKey(base_seed), start + s)
#
# threefry is counter-based and stateless, so draw s depends only on
# (base_seed, start + s) — never on which tile, device shard, or
# replication batch evaluates it.  This is the determinism +
# resharding-invariance contract: a batch synthesized tile-by-tile in a
# Pallas kernel, vmapped whole on one chip, sharded over a mesh, or
# materialized scenario-by-scenario on the host produces bit-identical
# data (tests/test_scengen.py holds every model's program to it).
#
# Two consumers share one program:
#
#   * `to_specs(program)` — the HOST materialization path: evaluates the
#     sampler per scenario (same threefry bits) and emits ordinary
#     ScenarioSpec objects for core.batch.from_specs.  This is the
#     compatibility bridge: anything that wants specs (EF builds, the
#     confidence-interval estimators) can draw through scengen keys.
#   * `scengen.virtual_batch(program)` — the DEVICE synthesis path: a
#     VirtualBatch whose realize() vmaps the sampler over the scenario
#     axis in-trace (see scengen/virtual.py).
#
# Bit-identity between the two paths holds by construction: both apply
# the program's shared template Scaling with the same f32 arithmetic
# (core.batch.scale_qp / from_specs(scaling=...)), and both draw each
# scenario's fields from the same folded key.
###############################################################################
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.random import threefry2x32_p

from mpisppy_tpu.core.tree import ScenarioTree, two_stage_tree

Array = jax.Array

#: qp fields a sampler may produce (ScenarioSpec field names).
FIELDS = ("c", "q", "A", "bl", "bu", "l", "u")


def scen_key(base_key: Array, idx) -> Array:
    """The ONE key derivation of the subsystem: scenario `idx`'s
    counter-based key.  fold_in is threefry-backed and stateless, so
    this is invariant to tiling/sharding/replication order.

    Bit for bit jax.random.fold_in(base_key, idx) on a raw uint32[2]
    key, spelled through the threefry2x32 primitive: vmapped, it stays
    elementwise and lowers inside a Mosaic kernel (the Pallas tile
    synth), where the batched random_fold_in primitive does not."""
    d = jnp.asarray(idx).astype(jnp.uint32)
    k1, k2 = _threefry2x32(base_key[0], base_key[1], d * np.uint32(0), d)
    return jnp.stack([k1, k2])


def uniform_at(key: Array, counter: Array) -> Array:
    """jax.random.uniform(key, shape, float32).ravel()[counter], bit for
    bit (partitionable threefry, the installed default), for a uint32
    counter array of any shape — from the threefry2x32 primitive alone,
    for the same reason as scen_key."""
    b1, b2 = _threefry2x32(key[0], key[1], counter * np.uint32(0), counter)
    bits = ((b1 ^ b2) >> np.uint32(9)) | np.uint32(0x3F800000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32) - 1.0


def _threefry2x32(k1, k2, x1, x2):
    """The primitive with its four operands broadcast to one shape
    first: its lowering needs equal shapes, which vmap over a mix of
    batched and unbatched operands does not give it."""
    z = (k1 ^ k2 ^ x1 ^ x2) * np.uint32(0)   # keeps vmap's batch axis
    return threefry2x32_p.bind(k1 + z, k2 + z, x1 + z, x2 + z)


@dataclasses.dataclass(frozen=True, eq=False)
class ScenarioProgram:
    """A declarative recipe: scenario index -> one scenario's data.

    template: f64 numpy (or scipy-sparse A) DETERMINISTIC skeleton of
        every qp field; varying fields hold the values the sampler
        overwrites (their deterministic entries must match what the
        sampler embeds, bit-for-bit after f64->f32 conversion).
    varying: which fields the sampler produces.
    sampler: trace-pure `(base_key, idx) -> {field: f32 array}` built
        from jnp + jax.random only — it runs vmapped inside jitted
        iteration kernels, per-scenario on the host (to_specs), and
        per-tile inside the Pallas window pipeline.  It receives the
        BASE key (not the folded one) so multistage models can fold
        per tree NODE (aircond) while two-stage models use
        scen_key(base_key, idx).
    start: index offset — replication r of a confidence-interval run
        draws scenarios [start, start+num_scenarios) of the same base
        key, the seed-provenance contract of docs/scengen.md.

    eq=False keeps the object hashable by identity so it can ride jit
    static args (VirtualBatch meta field) — build a program once and
    reuse it; a fresh identical program keys a fresh compile.
    """

    name: str
    num_scenarios: int
    base_seed: int
    template: dict
    varying: tuple
    sampler: Callable
    nonant_idx: np.ndarray
    tree: ScenarioTree | None = None
    integer: np.ndarray | None = None
    start: int = 0
    dtype: Any = jnp.float32
    #: rolling-horizon step (mpc/): step k re-keys EVERY draw through
    #: fold_in(PRNGKey(base_seed), k) BEFORE the per-scenario fold, so
    #: consecutive MPC steps resample independently while staying bit-
    #: reproducible from {base_seed, step} alone (resharding-invariant
    #: like the per-scenario fold — threefry is stateless).
    step: int = 0

    def __post_init__(self):
        unknown = set(self.varying) - set(FIELDS)
        if unknown:
            raise ValueError(f"unknown varying fields: {sorted(unknown)}")
        if self.tree is None:
            object.__setattr__(self, "tree", two_stage_tree(
                self.num_scenarios, len(self.nonant_idx)))
        if self.tree.num_scenarios != self.num_scenarios:
            raise ValueError(
                f"tree has {self.tree.num_scenarios} scenarios, program "
                f"declares {self.num_scenarios}")

    # -- keys -------------------------------------------------------------
    def base_key(self) -> Array:
        key = jax.random.PRNGKey(self.base_seed)
        if self.step:
            key = jax.random.fold_in(key, self.step)
        return key

    def advance(self, step: int) -> "ScenarioProgram":
        """The MPC step re-key helper (ISSUE 19): the SAME program with
        its base key folded to step `step` — every scenario draw of the
        advanced program is bit-identical to synthesizing directly from
        fold_in(PRNGKey(base_seed), step), under any sharding
        (tests/test_scengen.py pins this).  Absolute, not relative:
        advance(k).advance(j) samples step j, not k+j."""
        if step == self.step:
            return self
        return dataclasses.replace(self, step=int(step))

    def indices(self) -> np.ndarray:
        return np.arange(self.start, self.start + self.num_scenarios)

    def provenance(self) -> dict:
        """Seed-provenance record (confidence_intervals outputs carry
        it): everything needed to regenerate the exact draws."""
        prov = {"scheme": "threefry2x32/fold_in",
                "program": self.name,
                "base_seed": int(self.base_seed),
                "start": int(self.start),
                "num_scenarios": int(self.num_scenarios)}
        if self.step:
            prov["step"] = int(self.step)
        return prov

    # -- scaling ----------------------------------------------------------
    @property
    def scaling(self):
        """Template Ruiz Scaling, computed ONCE from scenario `start`'s
        realized spec and shared by every scenario — any positive
        scaling is a valid equilibration, and a SHARED one is what lets
        d_col/d_non stay (n,)/(N,) for any scenario count.  Cached on
        the instance (programs are identity-hashed, so this is safe)."""
        sc = self.__dict__.get("_scaling")
        if sc is None:
            from mpisppy_tpu.ops.boxqp import BoxQP, ruiz_scale
            sp = self.spec_at(self.start)
            qp = BoxQP(
                c=sp.c, q=np.zeros_like(sp.c), A=_as_ell_or_dense(sp.A),
                bl=sp.bl, bu=sp.bu, l=sp.l, u=sp.u)
            _, sc = ruiz_scale(qp)
            object.__setattr__(self, "_scaling", sc)
        return sc

    # -- host materialization ---------------------------------------------
    def _host_sampler(self):
        fn = self.__dict__.get("_host_jit")
        if fn is None:
            fn = jax.jit(partial(_sample_one, self))
            object.__setattr__(self, "_host_jit", fn)
        return fn

    def _spec_from_fields(self, idx: int, fields: dict):
        """ScenarioSpec assembly from one scenario's drawn varying
        fields: f32 values upcast to f64 (exact), deterministic fields
        the SHARED template objects — so from_specs' identity fast
        path fires and the stacked batch bit-matches device
        synthesis."""
        from mpisppy_tpu.core.batch import ScenarioSpec
        vals = dict(self.template)
        for k in self.varying:
            vals[k] = np.asarray(fields[k], np.float64)
        return ScenarioSpec(
            name=f"{self.name}_scengen{idx}",
            c=vals["c"], A=vals["A"], bl=vals["bl"], bu=vals["bu"],
            l=vals["l"], u=vals["u"],
            q=vals.get("q"),
            nonant_idx=np.asarray(self.nonant_idx, np.int32),
            probability=1.0 / self.num_scenarios,
            integer=self.integer,
        )

    def spec_at(self, idx: int):
        """One scenario's ScenarioSpec, drawn through the program's
        keys (one device dispatch; bulk consumers use to_specs)."""
        fields = jax.device_get(self._host_sampler()(jnp.asarray(
            idx, jnp.int32)))
        return self._spec_from_fields(idx, fields)

    def to_specs(self) -> list:
        """The whole sampled set as host ScenarioSpecs (the from_specs
        bridge; O(S) host memory — the path synthesis exists to avoid,
        kept for EF builds and the bit-identity contract test).  ONE
        vmapped device dispatch draws every varying field; the python
        loop only assembles host spec objects."""
        idx = self.indices()
        fields = jax.device_get(_sample_fields_jit(
            self, jnp.asarray(idx, jnp.int32)))
        return [self._spec_from_fields(
            i, {k: fields[k][row] for k in self.varying})
            for row, i in enumerate(idx)]


def _as_ell_or_dense(A):
    import scipy.sparse as sps
    if sps.issparse(A):
        from mpisppy_tpu.ops import sparse as sparse_mod
        return sparse_mod.ell_from_scipy(A, jnp.float32)
    return A


def _sample_one(program: ScenarioProgram, idx: Array) -> dict:
    return program.sampler(program.base_key(), idx)


def sample_fields(program: ScenarioProgram, idx: Array,
                  base_key: Array | None = None) -> dict:
    """Vmapped draw of the varying fields for an index vector — THE
    device synthesis primitive (trace-pure; VirtualBatch.realize and
    the Pallas tile synth route through it).  `base_key` lets callers
    supply an already-placed key array (a VirtualBatch's replicated
    data leaf) instead of rebuilding it from the seed."""
    base = program.base_key() if base_key is None else base_key
    return jax.vmap(lambda i: program.sampler(base, i))(idx)


@partial(jax.jit, static_argnames=("program",))
def _sample_fields_jit(program: ScenarioProgram, idx: Array) -> dict:
    return sample_fields(program, idx)


def program_for(module, num_scens: int, seed: int = 0, start: int = 0,
                **kw) -> ScenarioProgram | None:
    """The model-module bridge: modules that ship a scenario-synthesis
    branch expose `scenario_program(num_scens, seed=..., start=..., ...)`
    (models/farmer, sslp, uc, aircond).  Returns None when the module
    has no program — callers fall back to host materialization."""
    factory = getattr(module, "scenario_program", None)
    if factory is None:
        return None
    return factory(num_scens, seed=seed, start=start, **kw)


def has_program(module) -> bool:
    return getattr(module, "scenario_program", None) is not None


def program_from_cfg(module, cfg, num: int, start: int = 0,
                     seed: int | None = None, drop: tuple = (),
                     **overrides) -> ScenarioProgram | None:
    """THE cfg-gated resolver the confidence-interval layer shares
    (ciutils + sample_tree): honor the `use_scengen` opt-in, forward
    the cfg's MODEL kwargs (kw_creator) so the program samples the
    instance the legacy path would build, and fall back to None — with
    a console warning, never silently — when the program cannot cover
    this sample (multistage index windows, on-disk data kwargs).

    drop: kw_creator keys the caller supplies itself / that must not
    reach the factory; overrides: explicit factory kwargs."""
    if not bool(cfg.get("use_scengen", False)):
        return None
    if not has_program(module):
        return None
    kw = {}
    if hasattr(module, "kw_creator"):
        try:
            kw = dict(module.kw_creator(cfg))
        except Exception:
            kw = {}
    kw.pop("num_scens", None)
    for k in drop:
        kw.pop(k, None)
    kw.update(overrides)
    if seed is None:
        seed = int(cfg.get("scengen_seed", 0))
    try:
        return program_for(module, num, seed=int(seed), start=int(start),
                           **kw)
    except (TypeError, ValueError) as e:
        # an EXPLICIT opt-in that cannot be honored must be audible:
        # the caller falls back to the legacy host stream and the
        # output will carry no seed_provenance
        from mpisppy_tpu.telemetry import console
        console.log(
            f"scengen: use_scengen requested but "
            f"{getattr(module, '__name__', module)!s} has no program "
            f"covering this sample ({e}); drawing from the legacy "
            f"host stream instead", level=console.INFO)
        return None


def estimate_materialized_bytes(program: ScenarioProgram,
                                itemsize: int = 4) -> int:
    """What a host-materialized from_specs batch would keep resident
    for the qp DATA alone (c/q always stack batched; varying fields
    batched; shared fields counted once) — the HBM high-water term
    synthesis removes.  Analytic, never allocates."""
    S = program.num_scenarios
    n = int(np.asarray(program.template["c"]).shape[-1])
    A = program.template["A"]
    m = A.shape[0]
    total = 2 * S * n * itemsize                      # c, q stack batched
    for f in ("l", "u"):
        mult = S if f in program.varying else 1
        total += mult * n * itemsize
    for f in ("bl", "bu"):
        mult = S if f in program.varying else 1
        total += mult * m * itemsize
    import scipy.sparse as sps
    if sps.issparse(A):
        k = max(int(np.diff(A.tocsr().indptr).max()), 1)
        a_elems = m * k * 2                           # vals + cols
    else:
        a_elems = m * n
    total += (S if "A" in program.varying else 1) * a_elems * itemsize
    return total
