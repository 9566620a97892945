"""Host cost of the wheel's program spans, per hub iteration.

    python tools/span_cost.py [--iterations N] [--capture DIR]

Replays the instrumentation of one fused-wheel hub iteration N times
through a real PHHub and its event bus: the `wheel/iteration`
annotation, the subproblem_solve span with the scalar_wait span inside
it (three segments), the harvest and checkpoint spans, and the
collector flush.  Prints one JSON line with the microseconds per
iteration, outside a profiler capture and, with --capture, inside one
written to DIR.  Nothing runs on the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def _iterations(hub, n: int) -> float:
    """Seconds for n iterations' worth of spans."""
    from mpisppy_tpu.telemetry import profiler as prof
    t0 = time.perf_counter()
    for _ in range(n):
        with prof.annotate("wheel/iteration"):
            with hub._span("subproblem_solve"):
                with hub._span("scalar_wait"):
                    pass
            with hub._span("harvest"):
                pass
            with hub._span("checkpoint"):
                pass
            hub._flush_gc()
    return time.perf_counter() - t0


def span_cost(n: int = 2000, capture_dir: str | None = None) -> dict:
    """Microseconds per iteration of the spans, `n` iterations each way
    after a warm-up; the hub keeps every span, as a run's hub does."""
    from mpisppy_tpu.cylinders.hub import PHHub
    hub = PHHub(types.SimpleNamespace(), {})
    hub.watch_gc()
    try:
        _iterations(hub, 100)
        out = {"iterations": n,
               "us_per_iter": 1e6 * _iterations(hub, n) / n}
        if capture_dir is not None:
            import jax
            jax.profiler.start_trace(capture_dir)
            try:
                out["us_per_iter_captured"] = \
                    1e6 * _iterations(hub, n) / n
            finally:
                jax.profiler.stop_trace()
    finally:
        hub._flush_gc(stop=True)
    out["span_events"] = len(hub.spans)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--iterations", type=int, default=2000)
    ap.add_argument("--capture", help="also measure inside a capture "
                    "written to this directory")
    args = ap.parse_args(argv)
    print(json.dumps(span_cost(args.iterations, args.capture)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
