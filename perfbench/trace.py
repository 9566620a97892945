"""Reduction of a profiler capture to the numbers the per-layer metrics read.

Input: the `.xplane.pb` that `jax.profiler.start_trace` writes, read with
`jax.profiler.ProfileData` (nothing but JAX).  Output: one summary dict

    window_s      length of the traced window (the benchmark's own
                  `perfbench/traced` host annotation around it)
    busy_s        union of the intervals in which an op ran on the device
                  ("XLA Ops" line), inside the window, averaged over chips
    kernel_s      summed device time of the Mosaic kernels (custom calls
                  with target tpu_custom_call) inside the window
    kernel_calls  their [(hlo_text, seconds), ...], for the roofline model
    sync_idle_s   device-idle time inside the hub's `wheel_sync` host spans
    sync_spans    number of those spans inside the window
    device_ops    top ops by summed device time, [[name, seconds], ...]
    idle_gaps     longest device-idle gaps, [[host span, seconds], ...],
                  each named by the innermost host annotation over it

Container ops (while / conditional / call) span their children: they
count towards the busy union but not towards the op ranking.
"""
from __future__ import annotations

import glob
import os
import re

WINDOW_SPAN = "perfbench/traced"
SYNC_SPAN = "wheel_sync"
#: host annotations the idle attribution knows (the wheel's own and ours)
HOST_PREFIXES = ("wheel", "perfbench/")
CONTAINERS = frozenset({"while", "conditional", "call"})
_OPCODE = re.compile(r"(?<![\w.\-])([a-z][a-z0-9\-]*)\(")


def newest_xplane(trace_dir: str) -> str | None:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def parse_op(text: str) -> tuple[str, str]:
    """(short name, opcode) of an "XLA Ops" event, whose name is the HLO
    instruction text `%name = shape opcode(operands), attrs`."""
    if " = " not in text:
        return text.lstrip("%"), ""
    lhs, rhs = text.split(" = ", 1)
    m = _OPCODE.search(rhs)
    return lhs.strip().lstrip("%"), (m.group(1) if m else "")


def is_mosaic(text: str) -> bool:
    return "custom-call(" in text and "tpu_custom_call" in text


def union(intervals) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def _overlap(iv, lo, hi) -> float:
    return sum(b - a for a, b in _clip(iv, lo, hi))


def read_events(path: str):
    """(device_ops per chip, host spans) as plain tuples, in ns."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") or (
                plane.name.startswith("/device:CPU:")):
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                ops = devices.setdefault(plane.name, [])
                for e in line.events:
                    ops.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIXES):
                        host.append((e.start_ns, e.start_ns + e.duration_ns,
                                     e.name))
    return devices, host


def summarize(path: str, top: int = 10) -> dict | None:
    """The summary of one capture, or None when it holds no traced window
    or no device op inside it."""
    devices, host = read_events(path)
    windows = [(a, b) for a, b, n in host if n == WINDOW_SPAN]
    if not windows or not devices:
        return None
    lo, hi = windows[0]
    busy_by_chip, kernel_calls, by_name = [], [], {}
    for ops in devices.values():
        inside = [(max(a, lo), min(b, hi), t) for a, b, t in ops
                  if b > lo and a < hi]
        busy_by_chip.append(union((a, b) for a, b, _ in inside))
        for a, b, text in inside:
            name, opcode = parse_op(text)
            if opcode in CONTAINERS:
                continue
            by_name[name] = by_name.get(name, 0.0) + (b - a) * 1e-9
            if is_mosaic(text):
                kernel_calls.append((text, (b - a) * 1e-9))
    if not any(busy_by_chip):
        return None
    chips = len(busy_by_chip)
    busy = sum(sum(b - a for a, b in iv) for iv in busy_by_chip) / chips
    # idle attribution on the first chip (one-chip cells have only it)
    busy0 = busy_by_chip[0]
    # gaps under a microsecond are the clock's granularity, not idleness
    gaps = [(a, b) for a, b in zip([lo] + [e for _, e in busy0],
                                   [s for s, _ in busy0] + [hi])
            if b - a >= 1e3]
    spans = [(a, b, n) for a, b, n in host if n != WINDOW_SPAN]

    def label(a, b):
        mid = 0.5 * (a + b)
        over = [(e - s, n) for s, e, n in spans if s <= mid <= e]
        return min(over)[1] if over else "no host span"
    gaps.sort(key=lambda g: g[0] - g[1])
    syncs = [(a, b) for a, b, n in spans if n == SYNC_SPAN and a >= lo
             and b <= hi]
    sync_idle = sum(_overlap(gaps, a, b) for a, b in syncs)
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy * 1e-9,
        "kernel_s": sum(s for _, s in kernel_calls),
        "kernel_calls": kernel_calls,
        "sync_idle_s": sync_idle * 1e-9,
        "sync_spans": len(syncs),
        "device_ops": [[n, s] for n, s in ranked],
        "idle_gaps": [[label(a, b), (b - a) * 1e-9] for a, b in gaps[:top]],
    }
