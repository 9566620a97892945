###############################################################################
# Roofline attribution over a device timeline (ISSUE 7 tentpole,
# part 2; docs/telemetry.md).
#
# Turns deviceprof.DeviceTimeline into the gateable device-side report
# the perf era argues from ("Large Scale Distributed Linear Algebra
# With TPUs" / MPAX discipline, PAPERS.md): achieved HBM GB/s against
# the device's own published peak, measured MFU, per-category
# byte/time attribution, and the DMA/compute overlap fraction that is
# the acceptance metric for the Pallas double-buffer work (ROADMAP
# item 2).
#
# Metric definitions (all derived, none hand-timed):
#
#   device_sec_per_iter   median StepTraceAnnotation step duration when
#                         the capture has step markers (wheel runs via
#                         --profile-dir), else device module time per
#                         module execution (bench one-iteration traces).
#   measured_stream_gbps  duration-weighted HBM bandwidth of the PURE
#                         data-movement ops (hlo_category "data
#                         formatting" / "non-fusion elementwise" /
#                         "broadcast"), restricted — when memory spaces
#                         are known — to HBM-DOMINATED ops (>= half
#                         their traffic in HBM).  The trace analog of a
#                         stream (saxpy) microbenchmark: what the
#                         device actually sustains when an op does
#                         nothing but move HBM.  Replaces bench.py's
#                         hand-rolled two-op estimate (ISSUE 7).
#   achieved_hbm_gbps     total leaf-op HBM bytes / device module time:
#                         the true streaming rate of the WHOLE step
#                         (the roofline y-axis).
#   overlap_frac          |union(DMA in-flight) ∩ union(compute busy)|
#                         / |union(DMA in-flight)| — the fraction of
#                         async-transfer time hidden behind compute.
#                         Exposed (un-overlapped) DMA time is the
#                         double-buffer target.
#   mfu                   XLA-visible flops / module time / peak
#                         TFLOP/s.  Pallas custom-call flops are NOT
#                         attributed by the profiler, so this is a
#                         lower bound on true MFU (noted in the report).
#
# Byte accounting uses the xplane HBM-space split when the sidecar is
# present (deviceprof.py); the json-only fallback uses bytes_accessed
# (all spaces) and flags itself, because bytes_accessed counts
# VMEM-resident reuse and can exceed the physical HBM roofline.
###############################################################################
from __future__ import annotations

from mpisppy_tpu.telemetry import deviceprof as dp

DEVPROF_SCHEMA = "mpisppy-tpu-deviceprof/1"

#: hlo_category values whose ops are pure memory movement — the
#: streaming-bandwidth sample set
STREAM_CATEGORIES = frozenset({"data formatting",
                               "non-fusion elementwise", "broadcast"})

#: Published single-chip peaks keyed by jax's `device_kind`: bf16
#: TFLOP/s and HBM GB/s.  A capture's own plane stats win; this table
#: serves captures that carry none (json-only traces).  A device that is
#: not here is an error, never a default.
PEAKS_SOURCE = 'Google Cloud documentation, "TPU v5e"'
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_tflops": 197.0, "hbm_gbps": 819.0},
}


def device_peaks(device_kind: str | None) -> dict:
    """The published peaks of `device_kind` (see DEVICE_PEAKS)."""
    if device_kind not in DEVICE_PEAKS:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}: add it "
            f"to telemetry/roofline.py DEVICE_PEAKS with its source")
    return DEVICE_PEAKS[device_kind]


def _union(intervals):
    """Total length (and merged list) of a set of [a, b) intervals."""
    merged = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged), merged


def _intersect_len(mg1, mg2) -> float:
    i = j = 0
    tot = 0.0
    while i < len(mg1) and j < len(mg2):
        a = max(mg1[i][0], mg2[j][0])
        b = min(mg1[i][1], mg2[j][1])
        if b > a:
            tot += b - a
        if mg1[i][1] <= mg2[j][1]:
            i += 1
        else:
            j += 1
    return tot


def _median(xs):
    xs = sorted(xs)
    return xs[len(xs) // 2] if xs else None


def roofline(tl: dp.DeviceTimeline, device_kind: str | None = None) -> dict:
    """DeviceTimeline -> the machine report (JSON-able dict).
    `device_kind` (jax's) keys the peak table for a capture that
    carries no peaks of its own."""
    hbm_exact = tl.has_memory_spaces

    def op_bytes(op):
        return op.hbm_bytes if hbm_exact else op.bytes_accessed

    leaves = [op for op in tl.ops
              if op.category not in dp.CONTAINER_CATEGORIES]
    module_s = sum(m.dur_us for m in tl.modules) * 1e-6
    n_modules = len(tl.modules)
    if module_s <= 0.0:
        # no module line (heavily trimmed fixture): the op envelope is
        # the best available denominator
        if leaves:
            module_s = (max(o.end_us for o in leaves)
                        - min(o.start_us for o in leaves)) * 1e-6
        n_modules = n_modules or 1

    # -- per-category attribution ---------------------------------------
    cats: dict[str, dict] = {}
    for op in leaves:
        c = cats.setdefault(op.category, {
            "ops": 0, "busy_s": 0.0, "hbm_gb": 0.0, "flops": 0})
        c["ops"] += 1
        c["busy_s"] += op.dur_us * 1e-6
        c["hbm_gb"] += (op_bytes(op) or 0) / 1e9
        c["flops"] += op.flops or 0
    for c in cats.values():
        c["gbps"] = (round(c["hbm_gb"] / c["busy_s"], 1)
                     if c["busy_s"] > 0 else None)
        c["busy_s"] = round(c["busy_s"], 6)
        c["hbm_gb"] = round(c["hbm_gb"], 3)
    cats = dict(sorted(cats.items(), key=lambda kv: -kv[1]["hbm_gb"]))

    # -- whole-step achieved HBM rate ------------------------------------
    total_gb = sum(c["hbm_gb"] for c in cats.values())
    achieved = total_gb / module_s if module_s > 0 else None
    peak_hbm, peak_tf = tl.peak_hbm_gbps, tl.peak_tflops
    if not (peak_hbm and peak_tf):
        pk = device_peaks(device_kind)
        peak_hbm = peak_hbm or pk["hbm_gbps"]
        peak_tf = peak_tf or pk["bf16_tflops"]

    # -- stream (pure-data-movement) bandwidth ---------------------------
    # with exact memory spaces the sample keeps only HBM-DOMINATED
    # movement ops (>= half their traffic in HBM): a VMEM-resident copy
    # tells you about VMEM, not about what the HBM bus sustains
    stream_gb = stream_s = 0.0
    for op in leaves:
        if op.category not in STREAM_CATEGORIES or op.dur_us <= 0:
            continue
        if hbm_exact and (op.hbm_bytes or 0) < max(1, op.bytes_accessed // 2):
            continue
        stream_gb += (op_bytes(op) or 0) / 1e9
        stream_s += op.dur_us * 1e-6
    stream_gbps = stream_gb / stream_s if stream_s > 0 else None

    # -- MFU (XLA-visible flops only) ------------------------------------
    flops_total = sum(c["flops"] for c in cats.values())
    mfu = (flops_total / module_s / (peak_tf * 1e12)
           if module_s > 0 and flops_total else None)
    # opaque time: leaf execution with no byte attribution in ANY
    # memory space — almost entirely Pallas custom-calls (run_window)
    # whose internal DMA/flops the profiler cannot see.  An op that is
    # merely all-VMEM (hbm 0, on-chip > 0) is attributed, not opaque.
    opaque_s = sum(op.dur_us for op in leaves
                   if not (op.bytes_accessed or op.hbm_bytes
                           or op.onchip_bytes)
                   and op.category not in dp.DMA_CATEGORIES) * 1e-6

    # -- DMA/compute overlap ---------------------------------------------
    dma_iv = [(d.start_us, d.end_us) for d in tl.dma]
    comp_iv = [(op.start_us, op.end_us) for op in leaves
               if op.category not in dp.DMA_CATEGORIES]
    dma_len, dma_merged = _union(dma_iv)
    comp_len, comp_merged = _union(comp_iv)
    overlap_us = _intersect_len(dma_merged, comp_merged)
    overlap_frac = (overlap_us / dma_len) if dma_len > 0 else None
    dma_gb = sum(d.bytes for d in tl.dma) / 1e9

    # -- per-iteration device time ---------------------------------------
    step_durs = [s.dur_us * 1e-6 for s in tl.steps]
    by_iter = sorted((s.step_num, round(s.dur_us * 1e-6, 6))
                     for s in tl.steps if s.step_num is not None)
    if step_durs:
        dev_sec_per_iter = _median(step_durs)
        iter_source = "steps"
    elif n_modules and module_s > 0:
        dev_sec_per_iter = module_s / n_modules
        iter_source = "modules"
    else:
        dev_sec_per_iter, iter_source = None, "none"

    rep = {
        "schema": DEVPROF_SCHEMA,
        "trace": tl.trace_path,
        "device": tl.device_name,
        "byte_source": ("xplane-memory-spaces" if hbm_exact
                        else "bytes-accessed-all-spaces"),
        "device_sec_per_iter": _round(dev_sec_per_iter, 6),
        "iter_source": iter_source,
        "modules": {"count": n_modules,
                    "total_s": round(module_s, 6)},
        "measured_stream_gbps": _round(stream_gbps, 1),
        "stream_sample": {"gb": round(stream_gb, 3),
                          "busy_s": round(stream_s, 6)},
        "achieved_hbm_gbps": _round(achieved, 1),
        "peak_hbm_gbps": round(peak_hbm, 1),
        "hbm_roofline_frac": _round(
            achieved / peak_hbm if achieved is not None else None, 4),
        "mfu": _round(mfu, 5),
        "flops_total": flops_total,
        "peak_tflops": round(peak_tf, 1),
        "opaque_s": round(opaque_s, 6),
        "opaque_frac": _round(
            opaque_s / module_s if module_s > 0 else None, 4),
        "overlap_frac": _round(overlap_frac, 4),
        "dma": {
            "spans": len(tl.dma),
            "gb": round(dma_gb, 3),
            "inflight_s": round(dma_len * 1e-6, 6),
            "exposed_s": round((dma_len - overlap_us) * 1e-6, 6),
        },
        "steps": {"count": len(tl.steps),
                  "sec_per_iter_median": _round(_median(step_durs), 6),
                  "by_iter_tail": by_iter[-8:]},
        "categories": cats,
    }
    notes = []
    if not leaves:
        notes.append("capture has no device-plane ops (host-only "
                     "trace — CPU backend?): device metrics are empty")
    if not hbm_exact:
        notes.append("no xplane sidecar: bytes are XLA bytes_accessed "
                     "(all memory spaces, counts VMEM reuse) — rates "
                     "can exceed the physical HBM roofline")
    if opaque_s > 0.05 * module_s:
        notes.append(f"{100 * opaque_s / module_s:.0f}% of device time "
                     "is byte-opaque custom-calls (Pallas kernels): "
                     "their internal HBM traffic and flops are "
                     "invisible to the profiler, so achieved_hbm_gbps "
                     "and mfu are lower bounds")
    if opaque_s > 0.5 * module_s and overlap_frac is not None \
            and overlap_frac > 0.9:
        # ISSUE 8 caveat, measured on the committed S=100k capture:
        # profiler-VISIBLE DMA was already 98.9% hidden while the
        # window kernel's internal tile DMA (the double-buffer target)
        # is inside the opaque custom-call — a high overlap_frac here
        # does NOT certify the kernel pipeline
        notes.append("overlap_frac covers only profiler-visible DMA; "
                     "most device time is opaque Pallas custom-calls "
                     "whose internal tile DMA the profiler cannot see "
                     "— judge the kernel double-buffer by "
                     "device_sec_per_iter / iters_per_sec, not by "
                     "overlap_frac alone")
    rep["notes"] = notes
    return rep


def _round(v, nd):
    return None if v is None else round(v, nd)


def roofline_path(profile_dir: str, device_kind: str | None = None) -> dict:
    """Newest capture under `profile_dir` -> roofline report."""
    return roofline(dp.build_timeline(profile_dir), device_kind)


# ---------------------------------------------------------------------------
# the human rendering
# ---------------------------------------------------------------------------
def _fmt(v, spec=".6g"):
    return "-" if v is None else format(v, spec)


def render_device(rep: dict) -> str:
    L: list[str] = []
    L.append(f"device {rep.get('device') or '?'}  "
             f"[{rep.get('byte_source')}]  trace {rep.get('trace')}")
    m = rep["modules"]
    L.append(f"modules: {m['count']}  device time {m['total_s']:.6g}s  "
             f"device_sec_per_iter {_fmt(rep['device_sec_per_iter'])} "
             f"({rep['iter_source']})")
    L.append(f"measured_stream_gbps {_fmt(rep['measured_stream_gbps'])} "
             f"  (pure data-movement ops: "
             f"{rep['stream_sample']['gb']:.6g} GB over "
             f"{rep['stream_sample']['busy_s']:.6g}s)")
    L.append(f"achieved_hbm_gbps {_fmt(rep['achieved_hbm_gbps'])} of "
             f"peak {rep['peak_hbm_gbps']} "
             f"(roofline_frac {_fmt(rep['hbm_roofline_frac'])})")
    L.append(f"mfu {_fmt(rep['mfu'])}  (xla-visible flops "
             f"{rep['flops_total']:.6g} vs peak "
             f"{rep['peak_tflops']} TFLOP/s)")
    d = rep["dma"]
    L.append(f"overlap_frac {_fmt(rep['overlap_frac'])}  (dma "
             f"{d['spans']} spans, {d['gb']:.6g} GB, in-flight "
             f"{d['inflight_s']:.6g}s, exposed {d['exposed_s']:.6g}s)")
    if rep["steps"]["count"]:
        s = rep["steps"]
        L.append(f"steps: {s['count']}  sec/iter median "
                 f"{_fmt(s['sec_per_iter_median'])}  tail "
                 f"{s['by_iter_tail']}")
    L.append("categories (device busy, HBM bytes):")
    for name, c in rep["categories"].items():
        L.append(f"  {name:<24} x{c['ops']:<5d} {c['busy_s']:9.5f}s"
                 f"  {c['hbm_gb']:9.3f} GB"
                 f"  {_fmt(c['gbps'], '8.1f') if c['gbps'] is not None else '       -'} GB/s")
    for n in rep.get("notes", []):
        L.append(f"  ! {n}")
    return "\n".join(L)
