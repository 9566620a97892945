"""The trace reduction on a small capture recorded on the chip
(record_trace.py): one traced window with a host-only `wheel_sync` step,
one Pallas window call and one XLA op."""
from __future__ import annotations

import json
import os

import pytest

from perfbench import roofline, trace

FIX = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
PB = os.path.join(FIX, "chip_trace.xplane.pb")


@pytest.fixture(scope="module")
def facts():
    with open(os.path.join(FIX, "chip_trace.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def summary():
    return trace.summarize(PB)


def test_busy_union_lies_inside_the_window(summary, facts):
    assert 0.0 < summary["busy_s"] < summary["window_s"]
    assert summary["window_s"] <= facts["host_wall_s"] * 1.01


def test_host_only_step_reads_as_idle_inside_wheel_sync(summary, facts):
    assert summary["sync_spans"] == 1
    assert summary["sync_idle_s"] >= 0.9 * facts["sleep_s"]
    label, seconds = summary["idle_gaps"][0]
    assert label == "wheel_sync" and seconds >= 0.9 * facts["sleep_s"]


def test_the_one_mosaic_call_is_found_and_modelled(summary, facts):
    assert len(summary["kernel_calls"]) == 1
    text, seconds = summary["kernel_calls"][0]
    assert 0.0 < seconds <= summary["busy_s"]
    wheel = {"hub_pdhg": {"restart_period": 40, "iter_precision": "bf16x3"}}
    call = roofline.window_call(text, facts["scenarios"], facts["columns"],
                                facts["rows"], wheel)
    assert call["plane"] == "hub"
    least, _ = roofline.least_time(
        call, roofline.device_peaks(facts["device_kind"]))
    assert least < seconds           # a share of the roofline below 100%


def test_ranked_ops_exclude_nothing_but_containers(summary):
    names = [n for n, _ in summary["device_ops"]]
    assert names and all(s > 0 for _, s in summary["device_ops"])
    assert sum(s for _, s in summary["device_ops"]) <= summary["busy_s"] \
        * 1.0001 + 1e-9


def test_op_text_parses():
    name, op = trace.parse_op(
        "%copy.132 = f32[100000,705]{0,1:T(8,128)} copy(f32[100000,705]"
        "{0,1:T(8,128)} %batch_qp_c.1)")
    assert (name, op) == ("copy.132", "copy")
    name, op = trace.parse_op(
        "%copy-start.53 = (f32[100000]{0:T(1024)S(1)}, u32[]{:S(2)}) "
        "copy-start(f32[100000]{0:T(1024)} %st_solver_omega.1)")
    assert op == "copy-start"
