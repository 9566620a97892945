###############################################################################
# Profiler hooks (ISSUE 3 tentpole, part 3; ISSUE 7 hardening;
# docs/telemetry.md).
#
# Three layers:
#   * annotate(name) / step(name, n) — thin wrappers over
#     jax.profiler.TraceAnnotation / StepTraceAnnotation that NEVER
#     raise (and degrade to no-ops without jax).  The wheel brackets
#     its phases — hub sync, spoke update, harvest, checkpoint,
#     subproblem solve — so any externally-started device trace (e.g.
#     bench.py's jax.profiler.trace) shows named spans instead of an
#     undifferentiated dispatch soup.  An annotation outside an active
#     trace is a few ns of host work; nothing enters the jit graph.
#   * span(emitter, name) — the one program span: the annotation plus
#     a `span` event (perf_counter end + length) per leaf segment;
#     GCPauses — collector pauses from gc.callbacks, which the hub
#     folds into its iteration rows (docs/telemetry.md).
#   * ProfilerSession — the --profile-dir CLI flag: brackets N wheel
#     iterations with jax.profiler.start_trace/stop_trace, skipping the
#     compile-heavy first iterations so the trace shows steady state.
#
# Hardening contract (ISSUE 7): a missing or unwritable profile_dir —
# a read-only pod filesystem, a typo'd path — degrades to a console
# warning, never an unhandled exception; and the `profile` event that
# advertises a capture (action "captured", carrying the capture dir
# for `telemetry analyze` auto-discovery) is emitted ONLY after the
# trace files are verified on disk, so a trace row never points at a
# capture that silently failed to materialize.
###############################################################################
from __future__ import annotations

import contextlib
import gc
import os
import threading
import time


class _OpenSpans(threading.local):
    def __init__(self):
        self.stack = []          # this thread's open spans, innermost last


_OPEN = _OpenSpans()


def annotate(name: str):
    """Named host-span context manager (shows as a range in the device
    trace's host timeline)."""
    try:
        import jax.profiler
        return jax.profiler.TraceAnnotation(name)
    except Exception:
        return contextlib.nullcontext()


def step(name: str, step_num: int):
    """StepTraceAnnotation: marks one wheel iteration as a training-
    style 'step' so trace viewers (and telemetry/deviceprof.py) compute
    per-step device statistics keyed by hub_iter."""
    try:
        import jax.profiler
        return jax.profiler.StepTraceAnnotation(name, step_num=step_num)
    except Exception:
        return contextlib.nullcontext()


class span:
    """One wheel phase: the `wheel/<name>` annotation plus, on exit, a
    `span` event through `emitter.emit_span` (None: annotation only).

    Spans are LEAF phases: opening one inside another suspends the
    outer (its annotation closes and its segment is reported) and
    resumes it on exit, so every reported segment is disjoint host wall
    and a phase that is interrupted reports one event per segment.  The
    event's `t_mono` is the segment's end and `dur_s` its length, both
    on the perf_counter clock.  `emitter` may be set after entry (a
    wheel build learns its hub mid-span)."""

    __slots__ = ("emitter", "name", "_ann", "_t0")

    def __init__(self, emitter, name: str):
        self.emitter = emitter
        self.name = name

    def __enter__(self):
        stack = _OPEN.stack
        if stack:
            stack[-1]._close()
        stack.append(self)
        self._open()
        return self

    def __exit__(self, *exc):
        self._close()
        stack = _OPEN.stack
        stack.pop()
        if stack:
            stack[-1]._open()
        return False

    def _open(self):
        self._ann = annotate(f"wheel/{self.name}")
        self._ann.__enter__()
        self._t0 = time.perf_counter()

    def _close(self):
        t = time.perf_counter()
        self._ann.__exit__(None, None, None)
        if self.emitter is not None:
            self.emitter.emit_span(self.name, t - self._t0, t_end=t)


class GCPauses:
    """Collector pauses, recorded from `gc.callbacks` while started.

    The callback only writes plain fields — a start stamp, cumulative
    totals, a fixed ring of long pauses — and never emits: a collection
    can start inside EventBus.emit, whose lock is not re-entrant.
    Readers take differences of the cumulative fields (`take`), so a
    collection that lands mid-take is counted in the next one."""

    LONG_NS = 1_000_000          # pauses of 1 ms or more are kept one by one

    def __init__(self, ring: int = 64):
        self._t0 = 0
        self.n = 0               # pauses since start
        self.ns = 0              # their summed length
        self._end = [0] * ring   # the long pauses, ring-buffered
        self._dur = [0] * ring
        self._gen = [0] * ring
        self._k = 0              # long pauses recorded since start
        self._seen = (0, 0, 0)   # (n, ns, k) at the last take

    def _on_gc(self, phase, info):
        t = time.perf_counter_ns()
        if phase == "start":
            self._t0 = t
            return
        d = t - self._t0
        self.n += 1
        self.ns += d
        if d >= self.LONG_NS:
            i = self._k % len(self._end)
            self._end[i], self._dur[i] = t, d
            self._gen[i] = info.get("generation", -1)
            self._k += 1

    def start(self) -> "GCPauses":
        if self._on_gc not in gc.callbacks:
            gc.callbacks.append(self._on_gc)
        return self

    def stop(self) -> None:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def take(self):
        """(count, seconds, [(t_end_s, dur_s, generation), ...]) of the
        pauses since the last take; a ring overrun keeps the newest
        long pauses (the totals stay exact)."""
        n, ns, k = self.n, self.ns, self._k
        n0, ns0, k0 = self._seen
        self._seen = (n, ns, k)
        ring = len(self._end)
        long = []
        for j in range(max(k0, k - ring), k):
            i = j % ring
            long.append((self._end[i] * 1e-9, self._dur[i] * 1e-9,
                         self._gen[i]))
        return n - n0, (ns - ns0) * 1e-9, long


class ProfilerSession:
    """Bracket wheel iterations [start_iter, start_iter + num_iters)
    with a jax.profiler trace written to `profile_dir`.

    Driven by the hub: on_sync(hub_iter) every sync, close() at
    finalize (stops a still-open trace when the wheel terminates before
    the window completes).  start_iter defaults past Iter0 + the first
    compiled iterk so steady-state iterations dominate the trace."""

    def __init__(self, profile_dir: str, num_iters: int = 5,
                 start_iter: int = 3, bus=None, run: str = ""):
        self.profile_dir = profile_dir
        self.num_iters = max(1, int(num_iters))
        self.start_iter = int(start_iter)
        self.active = False
        self.failed = False
        self.done = False      # window completed: never re-arm
        self._bus = bus
        self._run = run
        self._known_captures: set = set()

    def _emit(self, action: str, hub_iter: int | None, **extra):
        if self._bus is not None:
            from mpisppy_tpu.telemetry import events as ev
            self._bus.emit(ev.PROFILE, run=self._run, cyl="hub",
                           hub_iter=hub_iter, action=action,
                           profile_dir=self.profile_dir, **extra)

    def _warn(self, msg: str) -> None:
        from mpisppy_tpu.telemetry import console
        console.log(f"WARNING: profiler: {msg}", cyl="hub")

    def _capture_dirs(self) -> set:
        try:
            from mpisppy_tpu.telemetry import deviceprof
            return {c["dir"]
                    for c in deviceprof.discover_captures(
                        self.profile_dir)}
        except (OSError, ValueError):
            return set()

    def _fail(self, msg: str) -> None:
        # a broken profiler backend / filesystem must never kill the
        # run: warn once, then stand down for the rest of the wheel
        self._warn(f"{msg} — device profiling disabled for this run")
        self.failed = True
        self.active = False

    def on_sync(self, hub_iter: int) -> None:
        if self.failed or self.done:
            return
        if not self.active and hub_iter >= self.start_iter:
            try:
                os.makedirs(self.profile_dir, exist_ok=True)
            except OSError as e:
                return self._fail(
                    f"cannot create --profile-dir "
                    f"{self.profile_dir!r} ({e})")
            if not os.access(self.profile_dir, os.W_OK):
                return self._fail(f"--profile-dir {self.profile_dir!r} "
                                  "is not writable")
            self._known_captures = self._capture_dirs()
            try:
                import jax.profiler
                jax.profiler.start_trace(self.profile_dir)
            except Exception as e:
                return self._fail(f"start_trace failed ({e})")
            self.active = True
            self._emit("start", hub_iter)
        elif self.active \
                and hub_iter >= self.start_iter + self.num_iters:
            self._stop(hub_iter)

    def _stop(self, hub_iter: int | None) -> None:
        self.done = True       # one window per session: never re-arm
        try:
            import jax.profiler
            jax.profiler.stop_trace()
        except Exception as e:
            self.active = False
            return self._fail(f"stop_trace failed ({e})")
        self.active = False
        # the `profile` "captured" event is a claim that analyzable
        # trace files EXIST — verify before advertising (ISSUE 7)
        new = self._capture_dirs() - self._known_captures
        if new:
            self._emit("captured", hub_iter,
                       trace_dir=sorted(new)[-1])
        else:
            self._warn(f"trace stopped but no capture landed under "
                       f"{self.profile_dir!r} (backend wrote nothing)")

    def close(self) -> None:
        if self.active and not self.failed:
            self._stop(None)
