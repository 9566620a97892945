###############################################################################
# The device mesh: this framework's entire "MPI".
#
# The reference's communication layer is mpi4py plus a numpy mock
# (ref:mpisppy/MPI.py:10-90), with scenarios block-partitioned over a
# cylinder communicator (ref:mpisppy/spbase.py:188-220) and every
# reduction an explicit Allreduce (ref:mpisppy/phbase.py:88-92,
# ref:mpisppy/spopt.py:344-556).  The TPU design needs none of that
# machinery: scenario arrays are sharded over a 1-D mesh axis 'scen'
# (ICI/DCN underneath), every jitted step takes sharded inputs, and XLA's
# SPMD partitioner turns the p-weighted reductions into all-reduce
# collectives automatically.  One seam — `shard_batch` — replaces the
# whole of MPI.py: called with a 1-device mesh it is the "mock" serial
# backend; with a TPU pod mesh it is the production backend.  Tests run
# the same code on a virtual 8-device CPU mesh
# (ref:.github/workflows/test_pr_and_main.yml:27-48 analog).
###############################################################################
from __future__ import annotations

import dataclasses

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

SCEN_AXIS = "scen"


def init_multihost(coordinator_address: str,
                   num_processes: int,
                   process_id: int,
                   cpu_devices_per_process: int | None = None) -> None:
    """Initialize the multi-host (DCN) runtime — the analog of the
    reference's `mpiexec` + COMM_WORLD bootstrap
    (ref:mpisppy/spin_the_wheel.py:224-242): after this, jax.devices()
    is the GLOBAL device list, make_mesh() spans all hosts, and the
    scenario-axis reductions inside jitted steps ride ICI within a host
    and DCN across hosts via the same collectives.

    cpu_devices_per_process: when set (tests / dry runs), forces a
    virtual CPU topology — N devices per process with gloo collectives
    — so a 2-process x 4-device mesh runs on one machine with no TPU,
    the multi-host analog of the conftest virtual mesh.  Must be called
    before any other jax API touches the backend."""
    import jax as _jax

    if cpu_devices_per_process is not None:
        _jax.config.update("jax_platforms", "cpu")
        _jax.config.update("jax_num_cpu_devices",
                           int(cpu_devices_per_process))
        _jax.config.update("jax_cpu_collectives_implementation", "gloo")
    _jax.distributed.initialize(coordinator_address=coordinator_address,
                                num_processes=num_processes,
                                process_id=process_id)


def process_local_slice(S: int) -> slice:
    """This process's contiguous scenario block under the canonical
    process-major layout (the analog of the reference's
    _calculate_scenario_ranks block partitioning,
    ref:mpisppy/spbase.py:188-220)."""
    import jax as _jax

    P_ = _jax.process_count()
    if S % P_ != 0:
        raise ValueError(f"{S} scenarios not divisible by "
                         f"{P_} processes; pad first")
    per = S // P_
    i = _jax.process_index()
    return slice(i * per, (i + 1) * per)


def make_mesh(n_devices: int | None = None, devices=None) -> Mesh:
    """A 1-D mesh over the scenario axis.  n_devices=None uses all
    available devices; n_devices=1 is the serial/mock path."""
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.array(devices), (SCEN_AXIS,))


def scen_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P(SCEN_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_batch(batch, mesh: Mesh, pad: bool = False):
    """Place a ScenarioBatch on the mesh: scenario-major arrays sharded on
    their leading axis, shared arrays replicated.  Scenario-carrying
    fields are recognized by leading-axis length == num_scenarios with the
    field's batched rank (mirrors pad_to_multiple's ndim logic).

    pad=True re-pads the scenario axis to the mesh's multiple first —
    the elastic-reshard path onto a SURVIVOR set whose device count
    does not divide S (docs/resilience.md).  Padding lanes carry ZERO
    probability mass (never a replicated real lane's probability), so
    every p-weighted reduction — eobjective, conv, the certified
    bounds — is value-identical to the pre-loss layout."""
    S = batch.num_scenarios
    if S % mesh.size != 0:
        if pad:
            if getattr(batch, "is_virtual", False):
                from mpisppy_tpu.scengen.virtual import repartition
                batch = repartition(batch, mesh.size)
            else:
                from mpisppy_tpu.core.batch import pad_to_multiple
                batch = pad_to_multiple(batch, mesh.size)
            S = batch.num_scenarios
        else:
            raise ValueError(
                f"{S} scenarios not divisible by mesh size {mesh.size}; "
                "use core.batch.pad_to_multiple first"
                + (" (scengen: virtual_batch(pad_to=mesh.size))"
                   if getattr(batch, "is_virtual", False) else ""))
    shard = scen_sharding(mesh)
    repl = replicated(mesh)

    if getattr(batch, "is_virtual", False):
        # scengen VirtualBatch (docs/scengen.md sharded synthesis):
        # only the probabilities (and the multistage node map) carry
        # the scenario axis — shard those, replicate the key + shared
        # template.  Inside a jitted step, realize()'s fold_in/sampler
        # chain partitions along the same axis via SPMD propagation, so
        # each device synthesizes only its shard's scenarios from the
        # same base key (the counter scheme makes the draws
        # layout-invariant).
        repl_tree = jax.tree_util.tree_map(
            lambda a: jax.device_put(a, repl), batch.shared)
        return dataclasses.replace(
            batch,
            base_key=jax.device_put(batch.base_key, repl),
            p=jax.device_put(batch.p, shard),
            d_col=jax.device_put(batch.d_col, repl),
            d_row=jax.device_put(batch.d_row, repl),
            d_non=jax.device_put(batch.d_non, repl),
            nonant_idx=jax.device_put(batch.nonant_idx, repl),
            node_of_slot=(None if batch.node_of_slot is None
                          else jax.device_put(batch.node_of_slot,
                                              shard)),
            integer_slot=jax.device_put(batch.integer_slot, repl),
            integer_full=jax.device_put(batch.integer_full, repl),
            shared=repl_tree,
        )

    def put(x, batched_ndim):
        if hasattr(x, "vals"):  # ops.sparse.EllMatrix: shard the values
            return dataclasses.replace(
                x, vals=put(x.vals, batched_ndim),
                cols=jax.device_put(x.cols, repl))
        return jax.device_put(x, shard if x.ndim == batched_ndim else repl)

    qp = batch.qp
    qp = dataclasses.replace(
        qp,
        c=put(qp.c, 2), q=put(qp.q, 2), A=put(qp.A, 3),
        bl=put(qp.bl, 2), bu=put(qp.bu, 2), l=put(qp.l, 2), u=put(qp.u, 2),
    )
    return dataclasses.replace(
        batch,
        qp=qp,
        d_col=put(batch.d_col, 2),
        d_row=put(batch.d_row, 2),
        d_non=put(batch.d_non, 2),
        p=jax.device_put(batch.p, shard),
        nonant_idx=jax.device_put(batch.nonant_idx, repl),
        node_of_slot=put(batch.node_of_slot, 2),
        integer_slot=jax.device_put(batch.integer_slot, repl),
        integer_full=jax.device_put(batch.integer_full, repl),
        var_prob=None if batch.var_prob is None
        else jax.device_put(batch.var_prob, shard),
    )


def shard_like_batch(tree, batch):
    """Re-place the scenario-major leaves (leading axis == S) of `tree`
    that came out of a jitted step fully replicated onto the batch's
    scenario sharding.  SPMD propagation leaves a state field that only
    ever holds a broadcast constant (PDHG guard_resets, PH's z/xsqbar
    with smoothing off, an untouched plane's omega) on every device in
    full; once placed, the jitted steps keep it split S/n per device.
    A no-op for a batch on one device."""
    sh = getattr(batch.p, "sharding", None)
    if not isinstance(sh, NamedSharding) or sh.mesh.size < 2:
        return tree
    S = batch.num_scenarios

    def place(a):
        if isinstance(a, jax.Array) and a.ndim >= 1 and a.shape[0] == S \
                and a.sharding.is_fully_replicated:
            return jax.device_put(a, sh)
        return a
    return jax.tree_util.tree_map(place, tree)
