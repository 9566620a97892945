"""The window kernel's operations/bytes model against the kernel itself:
compile ops/pdhg_pallas.run_window for a described v5e at a small size
and read the model's inputs off the compiled custom call, as the trace
reduction reads them off a traced one."""
from __future__ import annotations

import json
import os

import pytest

from perfbench import roofline
from perfbench.tests import tiny

S, N, M = 512, 705, 60


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    return SingleDeviceSharding(topo.devices[0])


def _wheel():
    with open(os.path.join(tiny.BENCH, "configs", "sslp_15_45.json")) as f:
        return json.load(f)["wheel"]


def _custom_call(one_chip, precision, fixed: bool) -> str:
    import jax
    import jax.numpy as jnp
    from mpisppy_tpu.ops import pdhg_pallas
    from mpisppy_tpu.ops.boxqp import BoxQP

    def sd(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    lu = (S, N) if fixed else (N,)

    def f(c, q, A, bl, bu, lo, hi, x, y, xs, ys, tau, sigma, done):
        p = BoxQP(c=c, q=q, A=A, bl=bl, bu=bu, l=lo, u=hi)
        return pdhg_pallas.run_window(p, x, y, xs, ys, tau, sigma, done,
                                      40, precision=precision)
    args = (sd((S, N)), sd((S, N)), sd((M, N)), sd((S, M)), sd((S, M)),
            sd(lu), sd(lu), sd((S, N)), sd((S, M)), sd((S, N)), sd((S, M)),
            sd((S,)), sd((S,)), sd((S,), jnp.bool_))
    text = jax.jit(f).lower(*args).compile().as_text()
    calls = [ln.strip() for ln in text.splitlines()
             if "tpu_custom_call" in ln and " custom-call(" in ln]
    assert len(calls) == 1
    return calls[0]


@pytest.mark.parametrize("precision,fixed,plane", [
    ("bf16x3", False, "hub"), (None, False, "lag"), (None, True, "xhat")])
def test_model_reads_the_kernels_block_shapes(one_chip, precision, fixed,
                                              plane):
    text = _custom_call(one_chip, precision, fixed)
    call = roofline.window_call(text, S, N, M, _wheel())
    assert call["plane"] == plane
    n_p, m_p, lanes = 768, 128, 128          # padded to the (8, 128) tiling
    wide = 6 if fixed else 4                 # c, q, x, x_sum (+ l, u)
    streamed = S * 4 * (lanes + wide * n_p + 4 * m_p)   # scal, bl, bu, y, ys
    results = S * 4 * (2 * n_p + 2 * m_p)
    shared_rows = 0 if fixed else 2 * n_p * 4
    mats = (4 * m_p * n_p * 2) if plane == "hub" else (2 * m_p * n_p * 4)
    assert call["bytes"] == streamed + results + shared_rows + mats
    iters = _wheel()[plane + "_pdhg"]["restart_period"]
    passes = 3 if plane == "hub" else 6
    assert call["ops"] == 4 * M * N * S * iters * passes


def test_least_time_is_the_larger_bound():
    peaks = roofline.device_peaks("TPU v5 lite")
    t, bound = roofline.least_time({"ops": 197e12, "bytes": 1.0}, peaks)
    assert bound == "compute" and t == pytest.approx(1.0)
    t, bound = roofline.least_time({"ops": 1.0, "bytes": 819e9}, peaks)
    assert bound == "hbm" and t == pytest.approx(1.0)
    with pytest.raises(ValueError):
        roofline.device_peaks("TPU v4")
