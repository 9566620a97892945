"""The check sees each fault that these cells can have, planted under a
run that skips the look for a chip, at the tiny size and against each
chip cell's own limits: a step that returns its state unchanged, the PH
average taken over half of the batch, an answer (a bound) altered where
it is produced, two faults confined to one tile of 128 scenarios (a
sixteenth of the batch here, 0.13% at S = 100,000): the window's output
not written back there, and W not updated there; the x-hat plane's
publication gates removed, so that it publishes bounds that its own
iterates do not certify; and the Lagrangian plane's iterations run one
precision step below the configuration's (bf16x3 for highest), which the
precision probe has to catch.  The cells run on one chip, so no exchange
between chips can be left out."""
from __future__ import annotations

import dataclasses
import importlib
import json
import os

import jax
import jax.numpy as jnp
import pytest

from perfbench import harness, reference
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


def _gates_removed(real):
    """The x-hat plane with its feasibility and compensation gates
    removed: tolerances at infinity."""
    def xhat_plane(batch, cand, solver, wopts, windows):
        return real(batch, cand, solver, dataclasses.replace(
            wopts, xhat_feas_tol=float("inf"),
            xhat_comp_tol=float("inf")), windows)
    return xhat_plane


#: the Lagrangian plane's iteration precision under the precision cut:
#: three bf16 passes, emulated so that it means that on the CPU too, whose
#: XLA runs every float32 product at full precision whatever it is asked
CUT = "emulated-bf16x3"


def _lag_cut(real):
    def lag_plane(batch, W, solver, wopts, windows):
        wopts = dataclasses.replace(wopts, lag_pdhg=dataclasses.replace(
            wopts.lag_pdhg, iter_precision=CUT))
        return real(batch, W, solver, wopts, windows)
    return lag_plane


def _resolve_cut(real):
    def iter_precision(opts):
        return CUT if opts.iter_precision == CUT else real(opts)
    return iter_precision


def _cut_product(real, transpose: bool):
    """BoxQP.matvec (A @ x) or rmatvec (A.T @ y) at the emulated three
    passes where the iteration asks for CUT."""
    def product(self, v, precision=None):
        if precision != CUT:
            return real(self, v, precision=precision)
        return reference._dot(v, self.A if transpose else self.A.T,
                              "emul-bf16x3")
    return product


FAULTS = {
    "state_unchanged": [("mpisppy_tpu.algos.ph", "ph_iterk",
                         lambda real: _unchanged)],
    "half_batch_mean": [("mpisppy_tpu.algos.ph", "_xbar_w_conv",
                         _half_batch)],
    "bound_altered": [("mpisppy_tpu.algos.fused_wheel", "_lag_step",
                       _altered_bound)],
    "tile_not_written": [("mpisppy_tpu.ops.pdhg", "solve_fixed",
                          _tile_stale)],
    "tile_W_stale": [("mpisppy_tpu.algos.ph", "_xbar_w_conv", _tile_W)],
    "gate_removed": [("mpisppy_tpu.algos.fused_wheel", "xhat_plane",
                      _gates_removed)],
    "lag_precision_cut": [
        ("mpisppy_tpu.algos.fused_wheel", "lag_plane", _lag_cut),
        ("mpisppy_tpu.ops.pdhg", "_iter_precision", _resolve_cut),
        ("mpisppy_tpu.ops.boxqp:BoxQP", "matvec",
         lambda real: _cut_product(real, transpose=False)),
        ("mpisppy_tpu.ops.boxqp:BoxQP", "rmatvec",
         lambda real: _cut_product(real, transpose=True))],
}
#: the number that has to catch each fault
CAUGHT_BY = {"state_unchanged": "ph_step", "half_batch_mean": "ph_step",
             "bound_altered": "lag_plane",
             "tile_not_written": "precision_probe", "tile_W_stale": "ph_step",
             "gate_removed": "xhat_plane",
             "lag_precision_cut": "precision_probe"}


def _target(name: str):
    mod_name, _, cls = name.partition(":")
    mod = importlib.import_module(mod_name)
    return getattr(mod, cls) if cls else mod


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_reads_not_correct(fault, root, monkeypatch, capsys):
    for name, attr, make in FAULTS[fault]:
        obj = _target(name)
        monkeypatch.setattr(obj, attr, make(getattr(obj, attr)))
    jax.clear_caches()
    try:
        assert harness.main(tiny.argv(), root=root, require_tpu=False) == 0
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is False, line["checks"]
    c = line["checks"][CAUGHT_BY[fault]]
    assert c["value"] > c["limit"], line["checks"]


def test_sound_run_reads_correct(root, capsys):
    jax.clear_caches()
    assert harness.main(tiny.argv(), root=root, require_tpu=False) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True, line["checks"]
