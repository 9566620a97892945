"""Peaks and the operations/bytes model of the Pallas PDHG window kernel.

The peaks table is copied from mpisppy_tpu/telemetry/roofline.py
DEVICE_PEAKS (source: Google Cloud documentation, "TPU v5e"); a device
that is not in it is an error, never a default.

One call of ops/pdhg_pallas.run_window runs `iters` PDHG iterations over
S scenarios.  Each iteration is two matrix-vector products with the
shared (m, n) constraint matrix, A'y and A(2x1 - x): 2 * 2mn operations
per scenario on the real, unpadded sizes.  On the MXU an f32 product at
bf16x3 is three bf16 passes and at highest six, so the operations the
chip must execute are 4mn * S * iters * passes, against the bf16 peak.
The elementwise work of the iteration (clips, step updates) runs on the
VPU, which has no entry in the table: the model leaves it out, so the
share it gives is of the MXU and HBM roofline only.

The bytes are the call's HBM traffic, read off the kernel's own operand
and result shapes in the trace (the HLO text of the custom call): every
operand is read once (per-scenario operands tile by tile, the shared
matrix and shared rows once into VMEM) and every result written once.

A call's least time is the larger of operations over peak FLOP/s and
bytes over peak bandwidth.  Which plane a call belongs to, and so its
iteration count and precision, follows from its operands: bf16 matrix
operands mean the three-pass hub plane; six per-scenario (S, n) operands
mean fixed nonant boxes, the x-hat plane; otherwise the Lagrangian plane.
"""
from __future__ import annotations

import re

PEAKS_SOURCE = 'Google Cloud documentation, "TPU v5e"'
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_tflops": 197.0, "hbm_gbps": 819.0},
}
PASSES = {"bf16": 1, "bf16x3": 3, "highest": 6}
_BYTES = {"f32": 4, "bf16": 2, "s32": 4, "pred": 1}
_SHAPE = re.compile(r"\b(f32|bf16|s32|pred)\[([\d,]*)\]")


def device_peaks(kind: str) -> dict:
    if kind not in DEVICE_PEAKS:
        raise ValueError(f"no published peaks for device_kind {kind!r}")
    return DEVICE_PEAKS[kind]


def _shapes(text: str) -> list[tuple[str, tuple[int, ...]]]:
    return [(dt, tuple(int(d) for d in dims.split(",") if d))
            for dt, dims in _SHAPE.findall(text)]


def _nbytes(shape) -> int:
    dt, dims = shape
    size = _BYTES[dt]
    for d in dims:
        size *= d
    return size


def call_shapes(text: str):
    """(results, operands) shapes of a tpu_custom_call HLO instruction."""
    lhs, rhs = text.split(" custom-call(", 1)
    results = _shapes(lhs.split(" = ", 1)[1])
    key = "operand_layout_constraints={"
    start = rhs.find(key)
    if start < 0:
        return results, []
    depth, i = 1, start + len(key)
    while depth and i < len(rhs):
        depth += {"{": 1, "}": -1}.get(rhs[i], 0)
        i += 1
    return results, _shapes(rhs[start + len(key):i - 1])


def plane_of(results, operands, wheel: dict) -> tuple[str, int, str]:
    """(plane, iterations, precision) of one window call."""
    S_p, n_p = results[0][1]
    wide = sum(1 for dt, d in operands if dt == "f32" and d == (S_p, n_p))
    if any(dt == "bf16" for dt, _ in operands):
        p = wheel["hub_pdhg"]
        return "hub", p["restart_period"], p["iter_precision"]
    if wide >= 6:
        p = wheel["xhat_pdhg"]
        return "xhat", p["restart_period"], p["iter_precision"]
    p = wheel["lag_pdhg"]
    return "lag", p["restart_period"], p["iter_precision"]


def window_call(text: str, S: int, n: int, m: int, wheel: dict) -> dict:
    """Operations, bytes and plane of one traced window call."""
    results, operands = call_shapes(text)
    plane, iters, prec = plane_of(results, operands, wheel)
    ops = 2 * (2 * m * n) * S * iters * PASSES[prec]
    nbytes = sum(_nbytes(s) for s in results) \
        + sum(_nbytes(s) for s in operands)
    return {"plane": plane, "ops": ops, "bytes": nbytes}


def least_time(call: dict, peaks: dict) -> tuple[float, str]:
    """(seconds, bound) — the bound is "compute" or "hbm"."""
    t_ops = call["ops"] / (peaks["bf16_tflops"] * 1e12)
    t_mem = call["bytes"] / (peaks["hbm_gbps"] * 1e9)
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "hbm")
