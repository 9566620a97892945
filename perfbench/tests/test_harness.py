"""The harness end to end on the CPU, at a tiny size."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from perfbench import harness
from perfbench.tests import tiny

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("root")))


def _last_line(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def test_tiny_cell_runs_from_its_own_files(root, capsys):
    assert harness.main(tiny.argv(), root=root, require_tpu=False) == 0
    line = _last_line(capsys)
    assert list(line) == KEYS                 # checks comes last
    assert line["correct"] is True
    assert set(line["metrics"]) >= {"setup_s", "wheel_iters_per_s"}
    assert line["device"]["platform"] == "cpu"
    assert line["checks"]["missing_steps"]["value"] == 0


def test_window_in_hub_iterations(tmp_path, capsys):
    """The unit the deferred s100k cell counts in (PERF.md): one solve,
    its window opened after the warm-up iterations."""
    root = tiny.make_root(str(tmp_path), unit="iteration")
    assert harness.main(tiny.argv(seconds=3.0), root=root,
                        require_tpu=False) == 0
    line = _last_line(capsys)
    assert line["correct"] is True
    assert line["attempted"] >= 1 and line["failed"] == 0


def test_traced_run_reports_the_cells_own_metric(root, capsys):
    assert harness.main(tiny.argv(trace=1), root=root,
                        require_tpu=False) == 0
    line = _last_line(capsys)
    # the CPU has no device trace: the trace readers find nothing and
    # their metrics are left out; the added reader still reports
    assert line["metrics"]["tiny.solves"]["value"] >= 1
    assert "step.device_ms_per_iter" not in line["metrics"]


def test_measured_run_refuses_without_a_tpu(root, capsys):
    with pytest.raises(SystemExit) as e:
        harness.main(tiny.argv(), root=root)
    assert "no TPU" in str(e.value.code)
    assert capsys.readouterr().out == ""


def test_benchmark_files_alone_refuse(tmp_path):
    """A directory with only BENCHMARK.json and perfbench/ exits non-zero
    and prints no result."""
    tiny.make_root(str(tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "perfbench/run.py"] + tiny.argv(),
                       cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0 and p.stdout == ""


def test_vectorised_batch_is_the_program_batch():
    from mpisppy_tpu.core import batch as batch_mod
    from mpisppy_tpu.models import sslp
    from perfbench import system, traffic
    inst_cfg = {"n_servers": 3, "n_clients": 6,
                "generator": "siplib_synthetic", "instance_seed": 0,
                "client_present_p": 0.5, "penalty": 1000.0}
    inst = traffic.siplib_instance(inst_cfg)
    cp = traffic.client_present(inst_cfg, 6, 11, 0)
    specs = []
    for s in range(6):
        sp = sslp._build_spec(inst, cp[s].astype(float), f"s{s}", 1.0 / 6)
        sp.integer = np.zeros_like(sp.integer)
        specs.append(sp)
    want = batch_mod.from_specs(specs)
    got = system.Template(inst, True).batch(cp)
    import jax
    for a, b in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert want.tree == got.tree and want.num_real == got.num_real
