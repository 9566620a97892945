"""The control of the output check reads far above the program: on the
CPU, at the tiny size, the program agrees with the reference to the last
bit, and the reference one precision step lower does not."""
from __future__ import annotations

import pytest

from perfbench import check, control, harness
from perfbench.tests import tiny


@pytest.fixture(scope="module")
def spec(tmp_path_factory):
    root = tiny.make_root(str(tmp_path_factory.mktemp("root")))
    return harness.load_cell(root, tiny.CELL)


def test_control_fails_every_number_the_program_passes(spec):
    rows = control.readings(spec, seed=2**32 + 3, seconds=3.0)
    steps = [r for r in rows if "program" in r]
    assert steps and not [r for r in rows if "missing_steps" in r]
    limits = spec["limits"]["limits"]
    for r in steps:
        for name in check.NAMES:
            assert r["program"][name] <= limits[name]
        assert any(r["control"][n] > 10 * limits[n] for n in check.NAMES)
