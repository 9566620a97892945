# Interpret-mode coverage for the Pallas VMEM window kernel
# (ops/pdhg_pallas.py) — the TPU engine behind PDHGOptions.use_pallas.
# The real-chip path differs only in lowering; interpret mode runs the
# same kernel trace on CPU, so the math (hoisted invariants, folded
# done-masking, the manual bf16x3 three-pass matvec) is exercised in CI.
# Role model: the reference tests its solver plumbing on tiny instances
# without real solvers (ref:mpisppy/tests/test_ef_ph.py builds 3-scenario
# farmer models); here the "solver" is ours, so we check it directly.
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpisppy_tpu.ops import pdhg
from mpisppy_tpu.ops.boxqp import make_boxqp


def _random_batch_lp(S=5, m=7, n=11, seed=0):
    """Small feasible batched LP with a SHARED dense A (the Pallas
    kernel's supported shape) and per-scenario c/rhs."""
    rng = np.random.default_rng(seed)
    A = rng.normal(size=(m, n))
    x_feas = rng.uniform(0.2, 0.8, size=(S, n))
    slack = rng.uniform(0.5, 1.5, size=(S, m))
    b = np.einsum("mn,sn->sm", A, x_feas)
    return make_boxqp(
        c=rng.normal(size=(S, n)),
        A=A,
        bl=b - slack,
        bu=b + slack,
        l=np.zeros((S, n)),
        u=np.ones((S, n)),
    )


def _run(p, opts, n_windows=2):
    st0 = pdhg.init_state(p, opts)
    return pdhg.solve_fixed(p, n_windows, opts, st0)


@pytest.mark.parametrize("iter_precision", [None, "high", "bf16x3"])
def test_window_kernel_matches_xla_path(iter_precision):
    p = _random_batch_lp()
    xla = _run(p, pdhg.PDHGOptions(use_pallas=False,
                                   iter_precision=iter_precision))
    pal = _run(p, pdhg.PDHGOptions(use_pallas=True,
                                   iter_precision=iter_precision))
    # same math up to float reassociation (None) or the bf16x3 manual
    # decomposition standing in for Precision.HIGH ("high"/"bf16x3" —
    # the bench-engaged alias, ops/boxqp.py PRECISION_ALIASES)
    tol = 1e-4 if iter_precision is None else 5e-2
    np.testing.assert_allclose(pal.x, xla.x, atol=tol, rtol=tol)
    np.testing.assert_allclose(pal.y, xla.y, atol=tol, rtol=tol)
    np.testing.assert_allclose(pal.x_sum, xla.x_sum, atol=80 * tol,
                               rtol=tol)


@pytest.mark.parametrize("iter_precision", [None, "bf16x3"])
def test_pipelined_kernel_bit_matches_single_buffer(iter_precision):
    """The double-buffered engine (ISSUE 8 tentpole) is a pure data-
    movement restructure: both engines run the same _tile_math trace
    per tile, so their outputs must BIT-match on CPU interpret — any
    drift means the pipeline touched math, not just DMA.  Covers
    multiple tiles, a tile count that doesn't divide the batch, and
    the bf16x3 three-pass mode."""
    for S, tile, seed in ((13, 4, 0), (8, 8, 1), (6, 2, 2)):
        p = _random_batch_lp(S=S, seed=seed)
        opts = pdhg.PDHGOptions(use_pallas=True, restart_period=9,
                                pallas_tile_s=tile)
        st = pdhg.init_state(p, opts)
        tau = opts.step_margin * st.omega / st.Lnorm
        sigma = opts.step_margin / (st.omega * st.Lnorm)

        from mpisppy_tpu.ops import pdhg_pallas
        args = (p, st.x, st.y, st.x_sum, st.y_sum, tau, sigma, st.done,
                opts.restart_period)
        single = pdhg_pallas.run_window(
            *args, tile_s=tile, precision=iter_precision,
            pipeline=False, interpret=True)
        piped = pdhg_pallas.run_window(
            *args, tile_s=tile, precision=iter_precision,
            pipeline=True, interpret=True)
        for a, b in zip(single, piped):
            assert np.array_equal(np.asarray(a), np.asarray(b))


def test_unknown_iter_precision_rejected_with_alias_list():
    """ISSUE 8 satellite: a typo'd precision string must fail with the
    valid aliases in the message, never silently trace at the module
    default."""
    from mpisppy_tpu.ops import boxqp
    with pytest.raises(ValueError, match="bf16x3"):
        boxqp.as_precision("bf16x4")
    with pytest.raises(ValueError, match="valid aliases"):
        p = _random_batch_lp(S=2)
        opts = pdhg.PDHGOptions(use_pallas=False, iter_precision="hihg")
        pdhg.solve_fixed(p, 1, opts, pdhg.init_state(p, opts))
    # the engaged aliases resolve (and agree with their Precision twins)
    import jax
    assert boxqp.as_precision("bf16x3") == jax.lax.Precision.HIGH
    assert boxqp.as_precision("bf16x3") == boxqp.as_precision("high")
    assert boxqp.as_precision("bf16x6") == jax.lax.Precision.HIGHEST


def test_done_scenarios_are_frozen():
    """The folded done-masking (tau=sigma=0) must be an exact no-op on
    frozen scenarios while window sums keep accumulating the frozen
    iterate — the same contract as the XLA path's where-blend."""
    p = _random_batch_lp(S=4)
    opts = pdhg.PDHGOptions(use_pallas=True, restart_period=6)
    st0 = pdhg.init_state(p, opts)
    # mark scenarios 1 and 3 done with distinctive iterates
    x_mark = jnp.clip(st0.x + 0.25, p.l, p.u)
    done = jnp.array([False, True, False, True])
    st0 = dataclasses.replace(st0, x=x_mark, done=done)

    from mpisppy_tpu.ops import pdhg_pallas
    tau = opts.step_margin * st0.omega / st0.Lnorm
    sigma = opts.step_margin / (st0.omega * st0.Lnorm)
    x, y, xs, ys = pdhg_pallas.run_window(
        p, st0.x, st0.y, st0.x_sum, st0.y_sum, tau, sigma, st0.done,
        opts.restart_period, interpret=True)
    np.testing.assert_allclose(x[1], x_mark[1], atol=1e-6)
    np.testing.assert_allclose(x[3], x_mark[3], atol=1e-6)
    np.testing.assert_allclose(y[1], st0.y[1], atol=1e-6)
    # frozen scenarios accumulate their frozen iterate every iteration
    np.testing.assert_allclose(
        xs[1], opts.restart_period * x_mark[1], atol=1e-5)
    # live scenarios actually moved
    assert float(jnp.max(jnp.abs(x[0] - x_mark[0]))) > 1e-6


def test_padding_is_exact_noop():
    """Scenario counts and row/col dims that don't divide the hardware
    tiles must give the same answer as an aligned problem (pad scenarios
    frozen, pad columns pinned at 0, pad rows dual-pinned at 0)."""
    p = _random_batch_lp(S=3, m=5, n=9, seed=1)
    xla = _run(p, pdhg.PDHGOptions(use_pallas=False))
    pal = _run(p, pdhg.PDHGOptions(use_pallas=True, pallas_tile_s=8))
    np.testing.assert_allclose(pal.x, xla.x, atol=1e-4, rtol=1e-4)


def test_bf16x3_wheel_publishes_same_certified_bounds():
    """ISSUE 8 satellite: the certificate-unaffected contract.  A wheel
    run with bf16x3 ITERATION matvecs (through the real Pallas kernel,
    interpret mode) must publish the same certified outer/inner bounds
    as the full-precision wheel within the restart-recheck tolerance —
    restart candidate scoring, convergence tests, and every published
    bound always re-evaluate at the boxqp module default (bf16x6), so
    a cheaper iteration path can shift the ITERATES it proposes but
    never what gets certified."""
    from mpisppy_tpu.core import batch as batch_mod
    from mpisppy_tpu.cylinders import (
        LagrangianOuterBound, PHHub, XhatXbarInnerBound,
    )
    from mpisppy_tpu.models import sslp
    from mpisppy_tpu.spin_the_wheel import WheelSpinner
    from mpisppy_tpu.algos import ph as ph_mod

    inst = sslp.synthetic_instance(5, 10, seed=0)
    specs = [sslp.scenario_creator(nm, instance=inst, num_scens=6,
                                   lp_relax=True)
             for nm in sslp.scenario_names_creator(6)]

    def run(iter_precision, use_pallas):
        batch = batch_mod.from_specs(specs)
        opts = ph_mod.PHOptions(
            default_rho=20.0, max_iterations=100, conv_thresh=0.0,
            subproblem_windows=8,
            pdhg=pdhg.PDHGOptions(tol=1e-6, use_pallas=use_pallas,
                                  pallas_tile_s=8,
                                  iter_precision=iter_precision))
        spokes = [
            {"spoke_class": LagrangianOuterBound,
             "opt_kwargs": {"options": {}}},
            {"spoke_class": XhatXbarInnerBound,
             "opt_kwargs": {"options": {}}},
        ]
        hub = {"hub_class": PHHub,
               "hub_kwargs": {"options": {"rel_gap": 0.01}},
               "opt_class": ph_mod.PH,
               "opt_kwargs": {"options": opts, "batch": batch}}
        ws = WheelSpinner(hub, spokes).spin()
        assert np.isfinite(ws.BestOuterBound)
        assert np.isfinite(ws.BestInnerBound)
        rel_gap = (ws.BestInnerBound - ws.BestOuterBound) \
            / abs(ws.BestInnerBound)
        assert rel_gap <= 0.01 + 1e-6   # both runs actually certify
        return ws.BestOuterBound, ws.BestInnerBound

    out_full, in_full = run(None, use_pallas=False)
    out_b3, in_b3 = run("bf16x3", use_pallas=True)
    # restart-recheck tolerance: candidates are scored at full
    # precision against tol=1e-6 relative KKT, so published bounds of
    # the two runs may differ only at that order, not at bf16 order
    tol = 2e-3 * max(1.0, abs(in_full))
    assert abs(out_b3 - out_full) <= tol
    assert abs(in_b3 - in_full) <= tol


def test_three_pass_dot_accuracy():
    """The manual bf16x3 decomposition must be far more accurate than a
    single bf16 pass (it mirrors Precision.HIGH, which Mosaic rejects)."""
    rng = np.random.default_rng(3)
    v32 = rng.normal(size=(16, 64)).astype(np.float32)
    M32 = rng.normal(size=(64, 32)).astype(np.float32)
    # f64 numpy reference: jnp matmul is NOT a trustworthy reference
    # here (some backends run DEFAULT-precision f32 matmuls as bf16
    # passes — measured on v5e)
    exact = (v32.astype(np.float64) @ M32.astype(np.float64)).astype(
        np.float32)
    v, M = jnp.asarray(v32), jnp.asarray(M32)
    from mpisppy_tpu.ops.pdhg_pallas import _dot3, _split_bf16
    hi, lo = _split_bf16(M)
    got = jax.jit(lambda v, hi, lo: _dot3(_split_bf16(v), hi, lo))(
        v, hi, lo)
    one_pass = jax.jit(lambda a, b: jax.lax.dot_general(
        a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32))(v, M)
    err3 = float(jnp.max(jnp.abs(got - exact)))
    err1 = float(jnp.max(jnp.abs(one_pass - exact)))
    assert err3 < err1 / 50
    assert err3 < 5e-4
