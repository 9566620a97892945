# Device-time observability (ISSUE 7; mpisppy_tpu/telemetry/
# {deviceprof,roofline,watch}.py): the chrome-trace + xplane-sidecar
# parsers over the COMMITTED jax.profiler captures, the roofline
# report's acceptance metrics (trace-derived measured_stream_gbps
# anchored to BENCH_DETAIL.json, overlap_frac in [0,1]), the device
# gates in `telemetry gate` (overlap/bandwidth regressions exit 2),
# the `telemetry watch --once` smoke against the golden farmer trace,
# and the ProfilerSession hardening contract.
import gzip
import json
import os
import subprocess
import sys

import pytest

from mpisppy_tpu.telemetry import deviceprof as dp
from mpisppy_tpu.telemetry import regress, roofline

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
GOLDEN_DEVICE = os.path.join(HERE, "fixtures",
                             "golden_device_trace.json.gz")
GOLDEN_FARMER = os.path.join(HERE, "fixtures",
                             "golden_farmer_trace.jsonl")
PROFILE_S100K = os.path.join(REPO, "profile_trace_S100000")
PROFILE_S10K = os.path.join(REPO, "profile_trace_S10000")
CLI = [sys.executable, "-m", "mpisppy_tpu.telemetry"]
# the golden json-only capture came from a v5e chip and carries no peaks
V5E = "TPU v5 lite"
ENV = {"PATH": "/usr/bin:/bin:/usr/local/bin", "JAX_PLATFORMS": "cpu",
       "HOME": os.path.expanduser("~")}


def _run(args, **kw):
    return subprocess.run(CLI + args, capture_output=True, text=True,
                          cwd=REPO, env=ENV, timeout=120, **kw)


# ---------------------------------------------------------------------------
# parser: committed real captures (trace.json.gz + xplane.pb sidecar)
# ---------------------------------------------------------------------------
def test_parse_committed_capture_with_xplane_sidecar():
    caps = dp.discover_captures(PROFILE_S100K)
    assert caps, "committed S=100k capture missing"
    cap = caps[-1]
    assert cap["trace"].endswith(".trace.json.gz")
    assert cap["xplane"] and cap["xplane"].endswith(".xplane.pb")
    tl = dp.build_timeline(cap)
    assert tl.device_name.startswith("/device:")
    assert len(tl.ops) > 1000
    assert len(tl.modules) == 1
    # the sidecar delivered the per-memory-space split and the
    # device's own peaks — no tensorflow/protobuf import involved
    assert tl.has_memory_spaces
    assert tl.peak_hbm_gbps == pytest.approx(819.16, abs=0.1)
    assert tl.peak_tflops == pytest.approx(202.7, abs=0.1)
    assert "tensorflow" not in sys.modules
    # DMA spans were recovered and carry bytes
    assert tl.dma and sum(d.bytes for d in tl.dma) > 1e9


def test_hbm_split_consistent_with_bytes_accessed():
    tl = dp.build_timeline(PROFILE_S10K)
    checked = 0
    for op in tl.ops:
        if op.hbm_bytes is None or not op.bytes_accessed:
            continue
        # per-space bytes can never exceed the all-space total
        assert op.hbm_bytes <= op.bytes_accessed + 1024
        checked += 1
    assert checked > 500


# ---------------------------------------------------------------------------
# roofline: the ISSUE 7 acceptance criteria
# ---------------------------------------------------------------------------
def test_roofline_s100k_stream_matches_committed_bench_detail():
    """`analyze --profile-dir profile_trace_S100000` must report a
    trace-derived measured_stream_gbps within 10% of the committed
    BENCH_DETAIL.json value (485.1) and an overlap_frac in [0, 1]."""
    with open(os.path.join(REPO, "BENCH_DETAIL.json")) as f:
        committed = json.load(f)["measured_mfu"]["S100000"]
    rep = roofline.roofline_path(PROFILE_S100K)
    got = rep["measured_stream_gbps"]
    want = committed["measured_stream_gbps"]
    assert abs(got - want) / want <= 0.10, (got, want)
    assert 0.0 <= rep["overlap_frac"] <= 1.0
    # device time per iteration is bounded by the committed host
    # sec/iter (host adds dispatch + python overhead on top)
    assert 0.0 < rep["device_sec_per_iter"] <= committed["sec_per_iter"]
    # the S=100k step is Pallas-dominated: the report must disclose the
    # byte-opaque fraction instead of presenting a false roofline
    assert rep["opaque_frac"] > 0.5
    assert any("byte-opaque" in n for n in rep["notes"])


def test_roofline_s10k_sane():
    rep = roofline.roofline_path(PROFILE_S10K)
    assert rep["byte_source"] == "xplane-memory-spaces"
    # achieved HBM flux can never exceed the device's physical peak
    assert 0 < rep["achieved_hbm_gbps"] <= rep["peak_hbm_gbps"]
    assert 0.0 <= rep["overlap_frac"] <= 1.0
    assert rep["mfu"] is None or 0.0 <= rep["mfu"] <= 1.0


def test_roofline_golden_fixture_json_only_fallback():
    rep = roofline.roofline(dp.build_timeline(GOLDEN_DEVICE), V5E)
    assert rep["byte_source"] == "bytes-accessed-all-spaces"
    assert rep["measured_stream_gbps"] > 0
    assert 0.0 <= rep["overlap_frac"] <= 1.0
    assert rep["dma"]["spans"] > 0
    # the fallback must announce its VMEM-reuse caveat
    assert any("bytes_accessed" in n for n in rep["notes"])


def test_roofline_peaks_keyed_by_device_kind():
    rep = roofline.roofline(dp.build_timeline(GOLDEN_DEVICE), V5E)
    assert rep["peak_hbm_gbps"] == 819.0 and rep["peak_tflops"] == 197.0
    # a capture without peaks on a device the table lacks is an error,
    # not the v5e numbers
    for kind in (None, "cpu", "TPU v4"):
        with pytest.raises(ValueError, match="no published peaks"):
            roofline.roofline(dp.build_timeline(GOLDEN_DEVICE), kind)


def test_xplane_walker_rejects_garbage(tmp_path):
    bad = tmp_path / "vm.xplane.pb"
    bad.write_bytes(os.urandom(4096))
    assert dp._read_xplane_sidecar(str(bad)) is None
    # a corrupt sidecar degrades to the json-only path, not a crash
    with gzip.open(GOLDEN_DEVICE, "rt") as f:
        raw = f.read()
    trace = tmp_path / "vm.trace.json.gz"
    with gzip.open(trace, "wt") as f:
        f.write(raw)
    tl = dp.build_timeline({"dir": str(tmp_path), "trace": str(trace),
                            "xplane": str(bad)})
    assert tl.ops and not tl.has_memory_spaces


# ---------------------------------------------------------------------------
# CI gate: device-metric regressions must exit 2 (ISSUE 7 satellite)
# ---------------------------------------------------------------------------
def _golden_report(tmp_path):
    rep = roofline.roofline(dp.build_timeline(GOLDEN_DEVICE), V5E)
    p = tmp_path / "device_golden.json"
    p.write_text(json.dumps(rep))
    return rep, p


@pytest.mark.parametrize("key,factor", [("overlap_frac", 0.5),
                                        ("measured_stream_gbps", 0.8)])
def test_gate_fails_synthetic_device_regression(tmp_path, key, factor):
    rep, p = _golden_report(tmp_path)
    bad = dict(rep)
    bad[key] = rep[key] * factor
    pb = tmp_path / f"device_bad_{key}.json"
    pb.write_text(json.dumps(bad))
    out = _run(["gate", str(p), str(pb)])
    assert out.returncode == 2, out.stdout + out.stderr
    assert key in out.stdout and "REGRESSED" in out.stdout


def test_gate_passes_identical_device_report(tmp_path):
    _, p = _golden_report(tmp_path)
    out = _run(["gate", str(p), str(p)])
    assert out.returncode == 0, out.stdout + out.stderr
    assert "PASS" in out.stdout


def test_device_metrics_direction_aware():
    mets = regress.extract_metrics(
        roofline.roofline(dp.build_timeline(GOLDEN_DEVICE), V5E))
    assert "device.measured_stream_gbps" in mets
    assert "device.overlap_frac" in mets
    # bandwidth falling regresses, rising does not
    d, _ = regress._gate_for("device.measured_stream_gbps")
    assert d == "down"
    d, _ = regress._gate_for("device.device_sec_per_iter")
    assert d == "up"


# ---------------------------------------------------------------------------
# CLI: analyze --profile-dir (device-only + joined) and watch --once
# ---------------------------------------------------------------------------
def test_cli_analyze_profile_dir_device_only():
    out = _run(["analyze", "--profile-dir", "profile_trace_S100000",
                "--json"])
    assert out.returncode == 0, out.stderr
    rep = json.loads(out.stdout)
    assert rep["schema"].startswith("mpisppy-tpu-deviceprof/")
    assert rep["measured_stream_gbps"] == pytest.approx(485.1, rel=0.10)
    # the human rendering names the acceptance metrics verbatim
    out2 = _run(["analyze", "--profile-dir", "profile_trace_S100000"])
    assert "measured_stream_gbps" in out2.stdout
    assert "overlap_frac" in out2.stdout


def test_cli_analyze_joins_device_section_onto_trace():
    out = _run(["analyze", "--trace-jsonl", GOLDEN_FARMER,
                "--profile-dir", "profile_trace_S10000", "--json"])
    assert out.returncode == 0, out.stderr
    rep = json.loads(out.stdout)
    assert rep["schema"].startswith("mpisppy-tpu-analyze/")
    dev = rep["device"]
    assert dev["schema"].startswith("mpisppy-tpu-deviceprof/")
    assert 0.0 <= dev["overlap_frac"] <= 1.0
    # device metrics ride the analyzer report into the gate
    mets = regress.extract_metrics(rep)
    assert "device.achieved_hbm_gbps" in mets


def test_cli_analyze_needs_an_input():
    out = _run(["analyze"])
    assert out.returncode == 1
    assert "--profile-dir" in out.stderr


def test_cli_watch_once_golden_farmer():
    out = _run(["watch", "--trace-jsonl", GOLDEN_FARMER, "--once"])
    assert out.returncode == 0, out.stderr
    assert "rel_gap" in out.stdout
    assert "36c89caf6cf7" in out.stdout       # the fixture's run id
    assert "RUN ENDED" in out.stdout          # fixture ends with run-end
    assert "quarantine" in out.stdout


def test_cli_watch_once_with_metrics_snapshot(tmp_path):
    prom = tmp_path / "metrics.prom"
    prom.write_text('# HELP dispatch_batches_total x\n'
                    'dispatch_batches_total 7\n'
                    'wheel_iterations_total 12\n'
                    'not a sample line\n')
    out = _run(["watch", "--trace-jsonl", GOLDEN_FARMER,
                "--metrics-snapshot", str(prom), "--once"])
    assert out.returncode == 0, out.stderr
    assert "dispatch_batches_total=7" in out.stdout


def test_cli_watch_missing_trace_exits_1(tmp_path):
    out = _run(["watch", "--trace-jsonl", str(tmp_path / "nope.jsonl"),
                "--once"])
    assert out.returncode == 1


# ---------------------------------------------------------------------------
# ProfilerSession hardening (ISSUE 7 satellite)
# ---------------------------------------------------------------------------
class _RecBus:
    def __init__(self):
        self.events = []

    def emit(self, kind, **kw):
        self.events.append((kind, kw))


def test_profiler_unwritable_dir_degrades_to_warning(tmp_path):
    from mpisppy_tpu.telemetry.profiler import ProfilerSession
    # a FILE where the profile dir should go: makedirs cannot succeed
    # (works under root too, where chmod-based read-only is bypassed)
    blocker = tmp_path / "blocker"
    blocker.write_text("")
    bus = _RecBus()
    ps = ProfilerSession(str(blocker / "prof"), num_iters=1,
                         start_iter=0, bus=bus)
    ps.on_sync(0)      # must not raise
    ps.on_sync(1)
    ps.close()
    assert ps.failed and not ps.active
    # no profile event may claim a capture that never happened
    assert not any(kw.get("action") == "captured"
                   for _, kw in bus.events)


def test_profiler_emits_captured_only_after_files_land(tmp_path):
    from mpisppy_tpu.telemetry import events as ev
    from mpisppy_tpu.telemetry.profiler import ProfilerSession
    prof = tmp_path / "prof"
    bus = _RecBus()
    ps = ProfilerSession(str(prof), num_iters=1, start_iter=0, bus=bus)
    ps.on_sync(0)
    if ps.failed:      # no profiler backend in this env: contract held
        return
    import jax
    import jax.numpy as jnp
    jax.block_until_ready(jnp.arange(8) * 2)
    ps.on_sync(1)
    ps.close()
    actions = [kw.get("action") for k, kw in bus.events
               if k == ev.PROFILE]
    assert actions[0] == "start"
    if "captured" in actions:
        cap = next(kw for _, kw in bus.events
                   if kw.get("action") == "captured")
        assert os.path.isdir(cap["trace_dir"])
        assert dp.discover_captures(str(prof))


def test_profiler_never_rearms_after_window(tmp_path):
    """One capture window per session: after stop, later syncs must
    NOT restart tracing (a re-arming session writes a junk capture
    every ~2 iterations for the rest of the run)."""
    from mpisppy_tpu.telemetry.profiler import ProfilerSession
    starts = []
    ps = ProfilerSession(str(tmp_path / "prof"), num_iters=2,
                         start_iter=3, bus=_RecBus())
    real_stop = ps._stop

    def fake_stop(hub_iter):
        ps.done = True
        ps.active = False
    ps._stop = fake_stop
    import unittest.mock as mock
    with mock.patch("jax.profiler.start_trace",
                    side_effect=lambda d: starts.append(d)):
        for it in range(30):
            ps.on_sync(it)
    ps._stop = real_stop
    assert len(starts) == 1, f"session re-armed {len(starts)} times"
    assert ps.done and not ps.active


def test_dma_pairing_is_fifo():
    ops = [
        dp.DeviceOp("copy-start.1", "copy-start", 0.0, 0.001),
        dp.DeviceOp("copy-start.1", "copy-start", 2.0, 0.001),
        dp.DeviceOp("copy-done.1", "copy-done", 3.0, 0.001),
        dp.DeviceOp("copy-done.1", "copy-done", 5.0, 0.001),
    ]
    spans = sorted(dp._pair_dma(ops), key=lambda s: s.start_us)
    # transfers complete in issue order: (0 -> 3), (2 -> 5) — never
    # the crossed (2 -> 3), (0 -> 5)
    assert [(s.start_us, round(s.end_us, 3)) for s in spans] == \
        [(0.0, 3.001), (2.0, 5.001)]


def test_golden_fixture_stays_small():
    # the committed fixture is a trimmed capture, not a full trace
    assert os.path.getsize(GOLDEN_DEVICE) < 200_000
    with gzip.open(GOLDEN_DEVICE, "rt") as f:
        n = len(json.load(f)["traceEvents"])
    assert 100 <= n <= 1000
