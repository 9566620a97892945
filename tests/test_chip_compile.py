# Compile-for-chip guards: the main path's kernels compiled, shapes
# only, for a described (not attached) TPU v5e chip at the real sslp_15_45
# widths.  Interpret-mode tests run the same kernel traces on the CPU but
# never see what Mosaic refuses (unaligned slices, VMEM overruns); these
# do, at no chip time.
#
# The topology is described inside a module-scoped fixture and nowhere
# else: only one process may load libtpu, and the driver runs the suite
# under several xdist workers that all import this file.  Keep every
# such compile in this one file, so one worker owns the library.
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from mpisppy_tpu.ops import pdhg, pdhg_pallas
from mpisppy_tpu.ops.boxqp import BoxQP

# sslp_15_45 (LP-relaxed): n = 15 + 45*15 + 15 columns, m = 15 + 45 rows
N_COLS, M_ROWS = 705, 60
S_PALLAS = 100_000      # where ops/pdhg.py auto-selects the kernel
S_XLA = 10_000          # the chip_smoke main path


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache off
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                       sharding=sharding), tree)


def _window_args(sharding, S, qp=None):
    f32 = jnp.float32

    def sd(shape, dt=f32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    if qp is None:
        qp = BoxQP(c=sd((S, N_COLS)), q=sd((S, N_COLS)),
                   A=sd((M_ROWS, N_COLS)), bl=sd((S, M_ROWS)),
                   bu=sd((S, M_ROWS)), l=sd((N_COLS,)), u=sd((N_COLS,)))
    return (qp, sd((S, N_COLS)), sd((S, M_ROWS)), sd((S, N_COLS)),
            sd((S, M_ROWS)), sd((S,)), sd((S,)), sd((S,), jnp.bool_))


@pytest.mark.parametrize("precision", [None, "bf16x3"])
@pytest.mark.parametrize("pipeline", [True, False])
def test_pallas_window_compiles_for_v5e(one_chip, pipeline, precision):
    compiled = pdhg_pallas.run_window.lower(
        *_window_args(one_chip, S_PALLAS), n_iters=40,
        pipeline=pipeline, precision=precision).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the kernel's HBM working set must leave room on a 16 GB chip
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 8e9


def test_scengen_synth_window_compiles_for_v5e(one_chip):
    from mpisppy_tpu import scengen
    from mpisppy_tpu.models import sslp
    prog = sslp.scenario_program(S_PALLAS, seed=0, n_servers=15,
                                 n_clients=45, lp_relax=True)
    qp_proxy, synth = scengen.window_inputs(scengen.virtual_batch(prog))
    args = _window_args(one_chip, S_PALLAS, qp=_sds(qp_proxy, one_chip))
    compiled = pdhg_pallas.run_window.lower(
        *args, n_iters=40, pipeline=True, precision="bf16x3",
        synth=synth).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ph_iterk_compiles_for_v5e(one_chip):
    from mpisppy_tpu.algos import ph as ph_mod
    from mpisppy_tpu.core.batch import from_specs
    from mpisppy_tpu.models import sslp
    inst = sslp.synthetic_instance(15, 45, seed=0)
    specs = [sslp.scenario_creator(nm, instance=inst, num_scens=S_XLA,
                                   lp_relax=True)
             for nm in sslp.scenario_names_creator(S_XLA)]
    batch = from_specs(specs)
    assert batch.qp.c.shape[-1] == N_COLS
    opts = ph_mod.PHOptions(
        subproblem_windows=8,
        pdhg=pdhg.PDHGOptions(tol=1e-6, restart_period=40,
                              iter_precision="bf16x3"))
    rho = jnp.full((batch.num_nonants,), 20.0)
    st = jax.eval_shape(lambda b, r: ph_mod.ph_iter0(b, r, opts)[0],
                        batch, rho)
    compiled = ph_mod.ph_iterk.lower(
        _sds(batch, one_chip), _sds(st, one_chip), opts=opts).compile()
    # at S = 10k the XLA window runs (the kernel starts at 32,768)
    assert "tpu_custom_call" not in compiled.as_text()
