"""The readings the output check's limits are set from, at the tiny size
on the CPU: the program agrees with the reference to the last bit, the
reference as precise but summed in another order reads correct, and the
reference one precision step lower does not."""
from __future__ import annotations

import pytest

from perfbench import check, control, harness
from perfbench.tests import tiny


@pytest.fixture(scope="module")
def rows(tmp_path_factory):
    root = tiny.make_root(str(tmp_path_factory.mktemp("root")))
    spec = harness.load_cell(root, tiny.CELL)
    got = control.readings(spec, seed=2**32 + 3, seconds=3.0)
    assert not [r for r in got if "missing_steps" in r]
    steps = [r for r in got if "program" in r]
    assert steps
    return spec["limits"]["limits"], steps


@pytest.mark.parametrize("reading", ["program", "reordered"])
def test_sound_reading_reads_correct(reading, rows):
    limits, steps = rows
    for r in steps:
        for name in check.NAMES:
            assert r[reading][name] <= limits[name], (reading, r[reading])


def test_control_fails_every_number_the_program_passes(rows):
    limits, steps = rows
    for r in steps:
        for name in check.NAMES:
            assert r["program"][name] <= limits[name]
        assert any(r["control"][n] > 10 * limits[n] for n in check.NAMES)
