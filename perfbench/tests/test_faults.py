"""The check sees each fault that these cells can have, planted under a
run that skips the look for a chip, at the tiny size and against each
chip cell's own limits: a step that returns its state unchanged, the PH
average taken over half of the batch, an answer (a bound) altered where
it is produced, and two faults confined to one tile of 128 scenarios (a
sixteenth of the batch here, 0.13% at S = 100,000): the window's output
not written back there, and W not updated there.  The cells run on one
chip, so no exchange between chips can be left out."""
from __future__ import annotations

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import pytest

from perfbench import harness
from perfbench.tests import tiny

CELLS = ("sslp_10_50.s2000",)
TILE = 128


@pytest.fixture(scope="module", params=CELLS)
def root(request, tmp_path_factory):
    with open(os.path.join(tiny.BENCH, "cells", request.param + ".json")) as f:
        limits = json.load(f)
    return tiny.make_root(str(tmp_path_factory.mktemp("root")), limits)


def _unchanged(batch, st, opts):
    return st


def _half_batch(real):
    def xbar_w_conv(batch, st, beta, smoothed, compute_xsqbar):
        x_non = batch.nonants(st.solver.x)
        half = x_non.shape[0] // 2
        avg = jnp.mean(x_non[:half], axis=0)
        xbar = jnp.broadcast_to(avg, x_non.shape)
        W = st.W + st.rho * (x_non - xbar)
        conv = jnp.mean(jnp.abs(x_non - xbar))
        return x_non, xbar, avg[None, :], st.xsqbar, W, st.z, conv
    return xbar_w_conv


def _altered_bound(real):
    def lag_step(*a, **k):
        st, bound, cert = real(*a, **k)
        return st, bound * 1.01, cert
    return lag_step


def _tile_stale(real):
    def solve_fixed(p, windows, opts, st):
        out = real(p, windows, opts, st)
        return dataclasses.replace(out, x=out.x.at[:TILE].set(st.x[:TILE]),
                                   y=out.y.at[:TILE].set(st.y[:TILE]))
    return solve_fixed


def _tile_W(real):
    def xbar_w_conv(batch, st, *a):
        x_non, xbar, nodes, xsq, W, z, conv = real(batch, st, *a)
        return x_non, xbar, nodes, xsq, W.at[:TILE].set(st.W[:TILE]), z, conv
    return xbar_w_conv


FAULTS = {
    "state_unchanged": ("mpisppy_tpu.algos.ph", "ph_iterk",
                        lambda real: _unchanged),
    "half_batch_mean": ("mpisppy_tpu.algos.ph", "_xbar_w_conv", _half_batch),
    "bound_altered": ("mpisppy_tpu.algos.fused_wheel", "_lag_step",
                      _altered_bound),
    "tile_not_written": ("mpisppy_tpu.ops.pdhg", "solve_fixed", _tile_stale),
    "tile_W_stale": ("mpisppy_tpu.algos.ph", "_xbar_w_conv", _tile_W),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_reads_not_correct(fault, root, monkeypatch, capsys):
    import importlib
    mod_name, attr, make = FAULTS[fault]
    mod = importlib.import_module(mod_name)
    monkeypatch.setattr(mod, attr, make(getattr(mod, attr)))
    jax.clear_caches()
    try:
        assert harness.main(tiny.argv(), root=root, require_tpu=False) == 0
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is False, line["checks"]


def test_sound_run_reads_correct(root, capsys):
    jax.clear_caches()
    assert harness.main(tiny.argv(), root=root, require_tpu=False) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True, line["checks"]
