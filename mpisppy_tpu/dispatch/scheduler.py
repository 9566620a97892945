###############################################################################
# SolveScheduler: coalescing queue + bounded in-flight dispatch.
#
# Every host-driven MIP solve (algos/mip.py oracle loops, ops/bnb.py
# megabatches, decomposition-B&B node re-solves) routes through one of
# these instead of calling ops.bnb.solve_mip directly.  Three layers:
#
#   * ADMISSION (coalescing windows).  Requests are keyed by their
#     mergeable identity — (n, m), dtype, A storage/identity, integer
#     signature, BnBOptions — and same-key requests land in one open
#     WINDOW.  A window dispatches when it reaches max_batch lanes,
#     when max_wait_ms passes, or the moment a caller blocks on one of
#     its tickets (a sync caller never waits out the admission timer
#     for coalescence that cannot arrive).  Dispatch concatenates the
#     window's requests along the batch axis into one MEGABATCH solve
#     and splits the result back per ticket.
#   * BACKPRESSURE (bounded in-flight).  A semaphore of max_inflight
#     outstanding dispatches gates every window: when the device
#     pipeline is full, dispatching threads queue on the semaphore and
#     their windows KEEP ACCUMULATING requests while they wait — load
#     turns into batch occupancy instead of queue depth, which is the
#     whole point.  max_inflight=2 is the classic double buffer: one
#     dispatch executing, one staged.
#   * SHAPE DISCIPLINE (buckets + compile watch).  Megabatches pad up
#     the geometric ladder (buckets.py) before dispatch, the padded
#     shape signature is recorded in the bucket registry, and a
#     CompileWatch attributes backend compiles: a compile against an
#     already-warm bucket increments dispatch_unexpected_recompiles
#     (and raises under --dispatch-compile-guard) instead of silently
#     storming.
#   * FAULT DOMAIN (deadlines / retry / bisection quarantine;
#     ISSUE 9, docs/dispatch.md failure semantics).  Every ticket may
#     carry a deadline (per-submit or the options default) and
#     result() takes a timeout — a caller can NEVER block past the
#     earlier of the two; expiry raises a typed SolveFailed instead of
#     hanging.  Every megabatch dispatch may carry a timeout
#     (dispatch_timeout_s): a hung or raising dispatch (XLA
#     RuntimeError, OOM, a NaN-poisoned batch) is retried with
#     exponential backoff up to retry_max; a window still failing after
#     its budget is BISECTED — split in request halves and re-solved —
#     until the poison request(s) are isolated and QUARANTINED (their
#     tickets resolve with SolveFailed, the healthy halves proceed).
#     The dispatcher daemon is supervised: if the thread dies, every
#     queued ticket fails fast with SolveFailed("dispatcher-died")
#     instead of waiting forever, and the next submit restarts it.
#     PreemptionError and AssertionError (the compile guard) are never
#     retried — they must stay loud.
#
# Everything is recorded in the process metrics REGISTRY (gauges:
# queue depth, in-flight, occupancy; counters: batches, lanes, pad
# lanes, compiles, retries, quarantined lanes) and, when a bus is
# attached, emitted as "dispatch" / "dispatch-retry" /
# "dispatch-quarantine" / "watchdog" events — see docs/dispatch.md for
# the field tables.
###############################################################################
from __future__ import annotations

import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from mpisppy_tpu.dispatch import buckets as _buckets
from mpisppy_tpu.dispatch import compilewatch as _cw
from mpisppy_tpu.telemetry import metrics as _metrics


# -- hub-iteration stamp (ISSUE 5 satellite), generalized to a
# per-session context token (ISSUE 12 satellite) ----------------------------
# Single-wheel processes: the hub calls set_hub_iter at the top of every
# sync and every dispatch event carries the value, so the analyzer joins
# megabatches to the iteration timeline exactly.  -1 = pre-wheel
# (warm-up compiles, iter0 oracle work).  A plain int write/read — no
# lock needed for a monotone diagnostic stamp.
#
# Multi-session processes (the serve layer, docs/serving.md): several
# concurrent wheels share one scheduler, so a single global stamp would
# be whichever hub wrote last — garbage joins.  Each session's hub
# instead installs a THREAD-LOCAL DispatchContext (run id + hub iter)
# on its driver thread; submit() captures the submitting thread's token
# per request, and the megabatch event carries a per-session breakdown
# (`sessions`) so the analyzer joins every dispatch to the right
# session exactly — no seq heuristics (telemetry/analyze.py keeps a
# dispatch row whenever its sessions mention the analyzed run).
_hub_iter = -1
_ctx_local = threading.local()


@dataclasses.dataclass(frozen=True)
class DispatchContext:
    """One session's dispatch stamp: the run id of the hub driving this
    thread, its current hub iteration (-1 pre-wheel), and — ISSUE 20 —
    the causal trace/span ids of the session's current segment, so a
    MIXED megabatch's event row attributes every lane to the right
    trace, not just the right run."""

    run: str = ""
    hub_iter: int = -1
    trace_id: str = ""
    span_id: str = ""


def set_session_context(run: str, hub_iter: int = -1,
                        trace_id: str = "", span_id: str = "") -> None:
    """Install the calling thread's session token (the hub calls this
    each sync on its driver thread; the serve engine calls it before
    iter0 so warm-up dispatches already join the session)."""
    _ctx_local.ctx = DispatchContext(run=str(run), hub_iter=int(hub_iter),
                                     trace_id=str(trace_id or ""),
                                     span_id=str(span_id or ""))


def clear_session_context() -> None:
    _ctx_local.ctx = None


def current_context() -> DispatchContext:
    """The submitting thread's token; falls back to the process-global
    hub-iteration stamp (run resolved by the scheduler's own run id)."""
    ctx = getattr(_ctx_local, "ctx", None)
    return ctx if ctx is not None else DispatchContext(hub_iter=_hub_iter)


def set_hub_iter(it: int) -> None:
    global _hub_iter
    _hub_iter = int(it)
    # a thread that carries a session token advances it in lockstep so
    # the two stamps can never disagree on the same thread
    ctx = getattr(_ctx_local, "ctx", None)
    if ctx is not None:
        _ctx_local.ctx = dataclasses.replace(ctx, hub_iter=int(it))


def current_hub_iter() -> int:
    return _hub_iter


@dataclasses.dataclass(frozen=True)
class DispatchOptions:
    """Scheduler knobs (CLI: the --dispatch-* group, utils/config.py)."""

    coalesce: bool = True        # merge same-key requests into megabatches
    max_batch: int = 4096        # lane cap per megabatch dispatch
    max_wait_ms: float = 2.0     # admission window for async submits
    max_inflight: int = 2        # outstanding dispatches (double buffer)
    pad_batch: bool = True       # pad megabatches up the bucket ladder
    bucket_growth: float = 2.0   # geometric ladder growth factor
    compile_guard: bool = False  # raise on a warm-bucket recompile
    # -- fault domain (ISSUE 9; docs/dispatch.md failure semantics) ------
    dispatch_timeout_s: float | None = None  # per-attempt solve timeout
    retry_max: int = 2           # retries per request set before bisecting
    retry_backoff_s: float = 0.05  # base backoff, doubled per retry
    deadline_s: float | None = None  # default per-ticket deadline


class SolveFailed(RuntimeError):
    """Typed terminal outcome of a failed solve request — what a
    `solve_mip`/`result()` caller observes instead of a hang
    (docs/dispatch.md failure-semantics table).

    reason: 'deadline'         ticket deadline / result(timeout) expired
            'timeout'          every dispatch attempt hit
                               dispatch_timeout_s (retries exhausted)
            'exception'        every dispatch attempt raised (retries
                               exhausted; `detail` holds the last error)
            'dispatcher-died'  the dispatcher daemon died with this
                               request queued (fail fast, not forever)
    attempts counts the solve attempts this request rode in (0 for
    deadline/dispatcher failures); lanes is the request's batch size —
    the quarantine accounting unit."""

    def __init__(self, reason: str, detail: str = "", attempts: int = 0,
                 lanes: int = 0):
        self.reason = reason
        self.detail = detail
        self.attempts = attempts
        self.lanes = lanes
        super().__init__(
            f"solve failed ({reason}"
            + (f" after {attempts} attempt(s)" if attempts else "")
            + (f"): {detail}" if detail else ")"))


class _DispatchTimeout(RuntimeError):
    """Internal: one dispatch attempt exceeded dispatch_timeout_s."""


class SolveTicket:
    """Future for one submitted solve; result() blocks (and, when the
    owning window is still open, dispatches it — inline on the caller's
    thread for unbounded waits, via the dispatcher daemon when a
    deadline/timeout bounds the wait so the caller can never be pinned
    inside a hung solve)."""

    def __init__(self, scheduler, window, lanes: int = 0,
                 deadline: float | None = None, sid: int = -1):
        self._scheduler = scheduler
        self._window = window
        self._event = threading.Event()
        self._result = None
        self._exc = None
        self._lanes = lanes
        self._deadline = deadline     # absolute perf_counter stamp
        self.sid = sid                # scheduler-assigned submit id
                                      # (joins quarantine events and
                                      # FaultPlan dispatch seams)

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: float | None = None):
        """Block for the result.  A LIVE ticket deadline and `timeout`
        (seconds) each bound the wait — past the earlier one this
        raises SolveFailed('deadline'): a ticket can never hang its
        caller.  After the deadline has expired, a bare result() keeps
        raising, but an EXPLICIT timeout grants a fresh recovery wait
        (the solve may still land late) and a call after it lands
        returns the result.  A quarantined/failed request raises its
        SolveFailed."""
        if not self._event.is_set():
            now = time.perf_counter()
            expired = self._deadline is not None \
                and self._deadline <= now
            if expired and timeout is None:
                raise SolveFailed(
                    "deadline", lanes=self._lanes,
                    detail="ticket deadline expired with the solve "
                           "still outstanding")
            bound = None if timeout is None else now + timeout
            if self._deadline is not None and not expired:
                bound = self._deadline if bound is None \
                    else min(bound, self._deadline)
            if bound is None:
                self._scheduler._drive(self._window, cause="inline")
                self._event.wait()
            else:
                # bounded wait: hand the window to the dispatcher (a
                # caller driving inline would sit inside solve_fn past
                # its own deadline) and wait out the bound
                self._scheduler._expedite(self._window)
                if not self._event.wait(
                        max(0.0, bound - time.perf_counter())):
                    raise SolveFailed(
                        "deadline", lanes=self._lanes,
                        detail="ticket deadline/timeout expired with "
                               "the solve still outstanding")
        if self._exc is not None:
            raise self._exc
        return self._result


class PlaneTicket:
    """Fire-and-forget future over one async XLA plane dispatch — the
    async wheel's exchange tickets (ISSUE 11; docs/async_wheel.md).

    Unlike SolveTicket there is no queue to drive: XLA dispatch is
    already asynchronous, so the dispatch ran inline at submit_plane
    and `value` holds the (future-valued) device arrays immediately —
    the caller threads them into later dispatches without waiting.
    The ticket exists for the PR-8 failure semantics: result(timeout=)
    is a BOUNDED readiness wait — past the earlier of the ticket
    deadline and the explicit timeout it raises SolveFailed('deadline')
    instead of pinning the caller inside a wedged device queue.  The
    abandoned waiter thread keeps blocking until XLA returns (the same
    'wait out the budget, then surface a typed failure' contract the
    dispatch timeout documents; docs/dispatch.md)."""

    def __init__(self, scheduler, value, label: str = "plane",
                 deadline: float | None = None):
        self._scheduler = scheduler
        self.value = value
        self.label = label
        self._deadline = deadline     # absolute perf_counter stamp

    def done(self) -> bool:
        """Best-effort readiness probe (no blocking)."""
        leaves = jax.tree_util.tree_leaves(self.value)
        try:
            return all(bool(x.is_ready()) for x in leaves
                       if hasattr(x, "is_ready"))
        except RuntimeError:
            # an errored/deleted buffer: nothing left to WAIT on —
            # result()'s landing check types the failure
            return True

    def _landed(self):
        """The one observation point for a ready value: a dispatch
        whose async computation ERRORED (or whose buffers died) must
        surface here as a typed SolveFailed — never be handed back as
        success to poison an arbitrary later use of the plane."""
        try:
            jax.block_until_ready(self.value)
        except Exception as e:
            raise SolveFailed(
                "exception",
                detail=f"plane ticket {self.label!r} dispatch "
                       f"failed: {e!r}") from e
        return self.value

    def result(self, timeout: float | None = None):
        """Block until the dispatched arrays are ready, bounded by the
        earlier of the LIVE ticket deadline and `timeout` — expiry
        raises SolveFailed('deadline') (and counts a plane deadline
        miss).  After the deadline has expired, a bare result() keeps
        raising (unless the arrays already landed), but an EXPLICIT
        timeout grants a fresh recovery wait — exactly SolveTicket's
        expired-deadline semantics, so a slow iteration can never
        convert a healthy exchange into a spurious miss."""
        now = time.perf_counter()
        expired = self._deadline is not None and self._deadline <= now
        bound = None if timeout is None else now + float(timeout)
        if self._deadline is not None and not expired:
            bound = self._deadline if bound is None \
                else min(bound, self._deadline)
        if bound is None and not expired:
            return self._landed()
        if self.done():
            # fast path: the dispatch landed a full iteration ago in
            # the steady state — no waiter thread, no handshake
            return self._landed()
        if bound is None:
            # expired deadline, no explicit timeout, not ready
            self._scheduler._note_plane_miss(self.label)
            raise SolveFailed(
                "deadline",
                detail=f"plane ticket {self.label!r} deadline expired "
                       f"with the dispatch still outstanding")
        done = threading.Event()
        err: list = []

        def waiter():
            try:
                jax.block_until_ready(self.value)
            except Exception as e:   # typed below, on the caller thread
                err.append(e)
            finally:
                done.set()

        t = threading.Thread(target=waiter, daemon=True,
                             name="mpisppy-tpu-plane-wait")
        t.start()
        if not done.wait(max(0.0, bound - time.perf_counter())):
            # expired bound: one readiness re-check before declaring a
            # miss — a result that LANDED before the caller got here
            # must never read as a wedged exchange (the SolveTicket
            # expired-deadline recovery semantics, PR-8; with an
            # already-past deadline the 0 ms wait above loses the race
            # against the just-started waiter thread every time)
            if not self.done():
                self._scheduler._note_plane_miss(self.label)
                raise SolveFailed(
                    "deadline",
                    detail=f"plane ticket {self.label!r} still not "
                           f"ready at its deadline (wedged exchange)")
            return self._landed()
        if err:
            raise SolveFailed(
                "exception",
                detail=f"plane ticket {self.label!r} dispatch "
                       f"failed: {err[0]!r}") from err[0]
        return self.value


class _Window:
    """One open coalescing window for a key: requests accumulate until
    the window is claimed by a dispatching thread and frozen."""

    __slots__ = ("key", "reqs", "tickets", "t0", "claimed", "frozen",
                 "due", "cause")

    def __init__(self, key):
        self.key = key
        # (qp, d_col, int_cols, opts, kwargs, sid, ctx) per request —
        # ctx is the submitting thread's DispatchContext token
        self.reqs: list = []
        self.tickets: list = []
        self.t0 = time.perf_counter()
        self.claimed = False
        self.frozen = False
        self.due = False          # a bounded result() wait expedites
        self.cause = "timer"      # why the window dispatched (stats)


class SolveScheduler:
    """See the module header.  `solve_fn` is injectable for tests (a
    synthetic storm needs to observe concurrency without paying for
    real branch-and-bound); the default is ops.bnb.solve_mip."""

    def __init__(self, options: DispatchOptions = DispatchOptions(),
                 solve_fn=None, bus=None, run: str = "",
                 fault_plan=None):
        if solve_fn is None:
            from mpisppy_tpu.ops import bnb as _bnb
            solve_fn = _bnb.solve_mip
        self.options = options
        self.solve_fn = solve_fn
        self.bus = bus
        self.run = run
        # chaos seams (resilience/faults.DispatchFault; armed by tests
        # and by the hub when its options carry a fault_plan) — host
        # dispatch path only, zero jit-graph impact
        self.fault_plan = fault_plan
        self.ladder = _buckets.BucketLadder(options.bucket_growth)
        # Lock discipline is lint-enforced (tools/graftlint
        # lock-discipline pass, docs/static_analysis.md): every field
        # below annotated `# guarded-by: _lock` may only be touched
        # inside `with self._lock` (or `with self._wake` — a Condition
        # over the same lock), or in a method marked
        # `# holds-lock: _lock` whose caller holds it.  Deliberately
        # UNannotated shared state: `options` (immutable dataclass,
        # swapped atomically under the lock by degrade(); bare reads
        # see either complete value), the sync primitives themselves,
        # and init-frozen handles (ladder/_watch/solve_fn/bus/run).
        self._lock = threading.Lock()
        self._sem = threading.Semaphore(max(1, options.max_inflight))
        self._pending: dict = {}          # guarded-by: _lock
        self._watch = _cw.CompileWatch()
        self._dispatcher = None           # guarded-by: _lock
        self._wake = threading.Condition(self._lock)
        self._closed = False              # guarded-by: _lock
        self._degraded = False            # guarded-by: _lock
        self._next_sid = 0                # guarded-by: _lock
        self._attempts = 0                # guarded-by: _lock
        # -- stats (all also mirrored into the metrics REGISTRY) ----------
        self._buckets: dict = {}          # guarded-by: _lock
        self._inflight = 0                # guarded-by: _lock
        self._inflight_max = 0            # guarded-by: _lock
        self._batches = 0                 # guarded-by: _lock
        self._lanes = 0                   # guarded-by: _lock
        self._pad_lanes = 0               # guarded-by: _lock
        self._coalesced_lanes = 0         # guarded-by: _lock
        self._unexpected_recompiles = 0   # guarded-by: _lock
        self._dispatch_compiles = 0       # guarded-by: _lock
        self._retries = 0                 # guarded-by: _lock
        self._quarantined_lanes = 0       # guarded-by: _lock
        self._quarantined_requests = 0    # guarded-by: _lock
        self._dispatcher_deaths = 0       # guarded-by: _lock
        # async-wheel exchange tickets (ISSUE 11): counted here, missed
        # deadlines noted from whichever thread timed the wait out
        self._plane_tickets = 0           # guarded-by: _lock
        self._plane_deadline_misses = 0   # guarded-by: _lock
        # why windows dispatched: timer (admission deadline expiry),
        # size (max_batch reached), inline (a caller's unbounded
        # result()), expedite (a deadline-bounded result()), overflow
        # (displaced by the lane cap), close (scheduler flush) — the
        # stats() split that lets the analyzer attribute occupancy loss
        # to admission timeouts vs size-forced dispatch (ISSUE 9
        # satellite)
        self._by_cause: dict = {}         # guarded-by: _lock
        # per-coalesce-key occupancy breakdown (ISSUE 12 satellite):
        # which mergeable identities actually shared megabatches, and
        # how many distinct sessions rode each one — the attribution
        # behind cross-session megabatch sharing in `telemetry
        # analyze`'s dispatch audit (docs/serving.md)
        self._by_key: dict = {}           # guarded-by: _lock

    # -- public API -------------------------------------------------------
    def solve_mip(self, qp, d_col, int_cols, opts=None, **kwargs):
        """Synchronous solve through the scheduler: bucket-padded, and
        coalesced with whatever compatible requests are already queued
        (a lone caller dispatches immediately — the admission timer
        only ever delays fire-and-forget submits)."""
        return self.submit(qp, d_col, int_cols, opts, **kwargs).result()

    def submit(self, qp, d_col, int_cols, opts=None,
               deadline_s: float | None = None, **kwargs) -> SolveTicket:
        """Enqueue one solve; returns a ticket.  Same-key submits
        coalesce into one megabatch dispatch.  The caller may submit
        many and then collect results — the first result() call drives
        the (single, coalesced) dispatch.  `deadline_s` (default:
        options.deadline_s) bounds how long result() may ever block on
        this ticket; expiry raises SolveFailed('deadline')."""
        if opts is None:
            from mpisppy_tpu.ops.bnb import BnBOptions
            opts = BnBOptions()
        S = int(qp.c.shape[0])
        key = self._request_key(qp, d_col, int_cols, opts, kwargs)
        if deadline_s is None:
            deadline_s = self.options.deadline_s
        deadline = None if deadline_s is None \
            else time.perf_counter() + float(deadline_s)
        overflow = None
        with self._lock:
            if self._closed:
                raise RuntimeError("scheduler is closed")
            win = self._pending.get(key)
            lanes = sum(r[0].c.shape[0] for r in win.reqs) if win else 0
            if (win is None or win.frozen
                    or not self.options.coalesce
                    or lanes + S > self.options.max_batch):
                # a frozen predecessor is already owned by a dispatching
                # thread; an OPEN one displaced by the lane cap would be
                # orphaned (the dispatcher only scans _pending) — this
                # thread dispatches it below, after the lock drops
                if win is not None and not win.frozen \
                        and not win.claimed:
                    overflow = win
                win = _Window(key)
                self._pending[key] = win
            sid = self._next_sid
            self._next_sid += 1
            ticket = SolveTicket(self, win, lanes=S, deadline=deadline,
                                 sid=sid)
            win.reqs.append((qp, d_col, int_cols, opts, kwargs, sid,
                             current_context()))
            win.tickets.append(ticket)
            full = (sum(r[0].c.shape[0] for r in win.reqs)
                    >= self.options.max_batch)
            if not full:
                # the admission-timer daemon covers fire-and-forget
                # submits whether or not coalescing is on
                self._ensure_dispatcher()
            self._wake.notify_all()
        # full/overflow windows normally dispatch on THIS thread (the
        # submitting threads are what provide dispatch concurrency up
        # to max_inflight) — but a deadline-carrying submit with NO
        # dispatch timeout would then sit inside an unbounded solve_fn
        # before ever reaching result(), wedging past its own deadline;
        # in that mode hand the window to the dispatcher instead
        inline_ok = deadline is None \
            or self.options.dispatch_timeout_s is not None
        if overflow is not None:
            if inline_ok:
                self._drive(overflow, cause="overflow")
            else:
                self._expedite(overflow)
        if full:
            if inline_ok:
                self._drive(win, cause="size")
            else:
                self._expedite(win)
        return ticket

    def submit_plane(self, fn, *args, label: str = "plane",
                     deadline_s: float | None = None,
                     **kwargs) -> PlaneTicket:
        """Fire-and-forget ticket over one async XLA plane dispatch
        (the async wheel's exchange programs; ISSUE 11).  `fn` is
        called INLINE — XLA dispatch is already asynchronous, so this
        returns immediately with the future-valued arrays in
        ticket.value; `deadline_s` bounds any later result() wait with
        the PR-8 typed-failure semantics."""
        value = fn(*args, **kwargs)
        deadline = None if deadline_s is None \
            else time.perf_counter() + float(deadline_s)
        with self._lock:
            self._plane_tickets += 1
        _metrics.REGISTRY.inc("dispatch_plane_tickets_total")
        return PlaneTicket(self, value, label=label, deadline=deadline)

    def _note_plane_miss(self, label: str) -> None:
        """A plane ticket's bounded wait expired (PlaneTicket.result —
        may run on any caller thread)."""
        with self._lock:
            self._plane_deadline_misses += 1
        _metrics.REGISTRY.inc("dispatch_plane_deadline_misses_total")
        self._emit_event("watchdog", component="exchange",
                         action="deadline", label=label)

    def stats(self) -> dict:
        """Point-in-time snapshot for bench artifacts and the hub's
        per-sync telemetry (docs/dispatch.md field table)."""
        with self._lock:
            lanes = max(1, self._lanes + self._pad_lanes)
            return {
                "batches": self._batches,
                "lanes": self._lanes,
                "pad_lanes": self._pad_lanes,
                "coalesced_lanes": self._coalesced_lanes,
                "occupancy": self._lanes / lanes,
                "buckets": len(self._buckets),
                # compiles observed WHILE a dispatch executed — the
                # dispatch-attributable count (other threads' compiles
                # can land in the window; see _solve_merged's caveat).
                # The raw process total is CompileWatch.total().
                "backend_compiles": self._dispatch_compiles,
                "unexpected_recompiles": self._unexpected_recompiles,
                "inflight_max": self._inflight_max,
                "queue_depth": sum(len(w.reqs)
                                   for w in self._pending.values()),
                # -- fault domain (ISSUE 9) -------------------------------
                "retries_total": self._retries,
                "quarantined_lanes": self._quarantined_lanes,
                "quarantined_requests": self._quarantined_requests,
                "dispatcher_deaths": self._dispatcher_deaths,
                "plane_tickets": self._plane_tickets,
                "plane_deadline_misses": self._plane_deadline_misses,
                "degraded": self._degraded,
                # why windows dispatched (timer = admission deadline
                # expiry, size = lane cap, inline/expedite = a blocking
                # caller, overflow, close) — the occupancy-attribution
                # split (a timer-heavy mix under load means the window
                # never fills before its admission deadline)
                "by_cause": dict(self._by_cause),
                # per-coalesce-key occupancy: which mergeable
                # identities shared megabatches, across how many
                # distinct sessions (ISSUE 12 satellite)
                "by_key": {
                    label: {
                        "batches": a["batches"],
                        "lanes": a["lanes"],
                        "pad_lanes": a["pad_lanes"],
                        "coalesced_lanes": a["coalesced_lanes"],
                        "occupancy": round(
                            a["lanes"] / max(1, a["lanes"]
                                             + a["pad_lanes"]), 4),
                        "sessions": len(a["runs"]),
                    } for label, a in self._by_key.items()},
            }

    def degrade(self) -> None:
        """Watchdog action (resilience/watchdog.py): drop to direct,
        un-coalesced dispatch — every later submit dispatches solo, the
        coalescing/admission machinery leaves the suspect path.  Shape
        padding stays on (the jit cache must stay bounded even in the
        degraded mode)."""
        with self._lock:
            self.options = dataclasses.replace(self.options,
                                               coalesce=False)
            self._degraded = True
        # observability rides the tripping watchdog's own event plus
        # the degraded flag in stats()/the hub's per-sync stats row —
        # a synthetic megabatch row here would pollute the audit

    def close(self):
        """Flush every open window and stop the dispatcher thread."""
        with self._lock:
            self._closed = True
            wins = [w for w in self._pending.values() if not w.claimed]
            self._wake.notify_all()
        for w in wins:
            self._drive(w, cause="close")
        with self._lock:
            t = self._dispatcher
        if t is not None and t.is_alive():
            t.join(timeout=5.0)

    # -- request identity -------------------------------------------------
    def _request_key(self, qp, d_col, int_cols, opts, kwargs) -> tuple:
        """Mergeable identity.  Batched per-lane fields concatenate
        freely; SHARED structure (a broadcast A, the ELL column index
        array, a ConeSpec) must be the same object across a window —
        object identity is exact for the oracle loops, which rebuild
        c/l/u per call but thread the same A through (see
        mip.lagrangian_mip_bound), and a miss only costs coalescence,
        never correctness.  Requests with kwargs never coalesce (a
        warm-start array is per-request state)."""
        A = qp.A
        if hasattr(A, "vals"):
            a_id = ("ell", id(A.cols),
                    None if A.vals.ndim == 3 else id(A.vals))
        else:
            a_id = ("dense", None if A.ndim == 3 else id(A))
        cones = getattr(qp, "cones", None)
        shared = tuple(
            None if getattr(f, "ndim", 0) == nd else id(f)
            for f, nd in ((qp.c, 2), (qp.q, 2), (qp.bl, 2), (qp.bu, 2),
                          (qp.l, 2), (qp.u, 2), (d_col, 2)))
        ints = np.asarray(int_cols)
        return (qp.n, qp.m, str(qp.c.dtype), a_id, shared,
                None if cones is None else id(cones),
                ints.shape, hash(ints.tobytes()), opts,
                ("solo", id(kwargs)) if kwargs else ())

    # -- dispatch machinery -----------------------------------------------
    def _ensure_dispatcher(self):        # holds-lock: _lock
        """Lazy daemon that fires windows whose admission timer lapsed
        (callers that block in result() drive their own windows; this
        thread only covers fire-and-forget submits).  Caller holds the
        lock."""
        if self._dispatcher is not None and self._dispatcher.is_alive():
            return
        self._dispatcher = threading.Thread(
            target=self._dispatch_loop, daemon=True,
            name="mpisppy-tpu-dispatch")
        self._dispatcher.start()

    def _dispatch_loop(self):
        """Supervised daemon body: the inner loop fires due windows;
        ANY escape — including an injected dispatcher kill — fails
        every queued ticket fast (SolveFailed('dispatcher-died'))
        instead of leaving them to wait on a dead thread.  The next
        submit restarts the daemon (see _ensure_dispatcher)."""
        try:
            self._dispatch_loop_inner()
        except BaseException as e:  # noqa: BLE001 — the supervisor seam
            self._on_dispatcher_death(e)

    def _dispatch_loop_inner(self):
        wait_s = max(self.options.max_wait_ms, 0.1) / 1e3
        while True:
            plan = self.fault_plan
            if plan is not None:
                plan.maybe_kill_dispatcher()
            with self._lock:
                now = time.perf_counter()
                open_w = [w for w in self._pending.values()
                          if not w.claimed]
                due = [w for w in open_w
                       if w.due or now - w.t0 >= wait_s]
                if not due:
                    if self._closed:
                        return
                    if open_w:
                        # sleep exactly to the earliest admission
                        # deadline
                        deadline = min(w.t0 + wait_s for w in open_w)
                        self._wake.wait(timeout=max(deadline - now,
                                                    1e-4))
                    else:
                        # idle: block until a submit (or close)
                        # notifies — no polling
                        self._wake.wait()
                    continue
            for w in due:
                self._drive(w, cause="expedite" if w.due else "timer")

    def _on_dispatcher_death(self, exc: BaseException):
        """Fail-fast fan-out for a dead dispatcher thread: every ticket
        still queued in an unclaimed window resolves with a typed
        SolveFailed NOW (never a hang), the queue empties, and a
        watchdog event records the death."""
        with self._lock:
            wins = [w for w in self._pending.values() if not w.claimed]
            for w in wins:
                w.claimed = True
                w.frozen = True
            self._pending = {}
            self._dispatcher_deaths += 1
        failed = 0
        for w in wins:
            for t in w.tickets:
                if not t.done():
                    t._exc = SolveFailed(
                        "dispatcher-died", lanes=t._lanes,
                        detail=f"{type(exc).__name__}: {exc}")
                    t._event.set()
                    failed += 1
        _metrics.REGISTRY.inc("dispatch_dispatcher_deaths_total")
        self._emit_event(
            "watchdog", component="dispatcher", action="fail-fast",
            failed_tickets=failed,
            error=f"{type(exc).__name__}: {exc}")

    def _expedite(self, win: _Window):
        """A deadline-bounded result() wait: mark the window due and
        wake the dispatcher so it fires without the caller having to
        sit inside solve_fn itself."""
        with self._lock:
            if win.claimed:
                return
            win.due = True
            self._ensure_dispatcher()
            self._wake.notify_all()

    def _drive(self, win: _Window, cause: str = "inline"):
        """Claim-and-run a window; loses the race gracefully when
        another thread (or the dispatcher) got there first."""
        with self._lock:
            if win.claimed:
                return
            win.claimed = True
            win.cause = cause
        try:
            self._run_window(win)
        except BaseException as e:  # noqa: BLE001 — fanned out below
            with self._lock:
                win.frozen = True
                if self._pending.get(win.key) is win:
                    del self._pending[win.key]
            for t in win.tickets:
                if not t.done():
                    t._exc = e
                    t._event.set()
            raise

    def _run_window(self, win: _Window):
        # backpressure FIRST: while this thread queues on the in-flight
        # semaphore the window is still open, so a storm accumulates
        # into occupancy rather than device queue depth
        self._sem.acquire()
        try:
            with self._lock:
                win.frozen = True
                if self._pending.get(win.key) is win:
                    del self._pending[win.key]
                reqs = list(win.reqs)
                tickets = list(win.tickets)
                self._inflight += 1
                self._inflight_max = max(self._inflight_max,
                                         self._inflight)
                _metrics.REGISTRY.set_gauge("dispatch_inflight",
                                            self._inflight)
            t_launch = time.perf_counter()
            self._solve_recover(win, reqs, tickets, t_launch)
        finally:
            with self._lock:
                self._inflight -= 1
                _metrics.REGISTRY.set_gauge("dispatch_inflight",
                                            self._inflight)
            self._sem.release()

    def _solve_recover(self, win: _Window, reqs, tickets,
                       t_launch: float, bisected: bool = False):
        """The fault-domain driver (ISSUE 9): solve this request set
        with retry + exponential backoff; a set still failing after its
        budget BISECTS into request halves (each with a fresh budget —
        recursion depth is log2(requests), total attempts bounded by
        (retry_max+1) * (2*requests - 1)); a single request that still
        fails is QUARANTINED — its ticket resolves with a typed
        SolveFailed and every healthy sibling proceeds.  Non-retryable
        escapes (preemption, the compile guard's AssertionError,
        KeyboardInterrupt/SystemExit) propagate immediately to _drive's
        fan-out."""
        from mpisppy_tpu.resilience.faults import PreemptionError
        last: BaseException | None = None
        attempts = 0
        for attempt in range(max(0, self.options.retry_max) + 1):
            if attempt:
                backoff = self.options.retry_backoff_s * (2 ** (attempt - 1))
                self._retry_note(win, reqs, attempt, last, backoff)
                time.sleep(backoff)
            attempts += 1
            try:
                res, sizes, S_pad, sig = self._solve_merged(reqs)
            except (PreemptionError, AssertionError):
                raise          # must stay loud: shutdown / compile guard
            except Exception as e:  # noqa: BLE001 — the retryable class
                last = e
                continue
            self._deliver(win, reqs, tickets, res, sizes)
            self._record(win, reqs, sizes, S_pad, sig, t_launch)
            return
        if len(reqs) > 1:
            # the poison is somewhere in this set: isolate by
            # lane-balanced halves (buckets.balanced_split)
            mid = _buckets.balanced_split(
                [int(r[0].c.shape[0]) for r in reqs])
            self._solve_recover(win, reqs[:mid], tickets[:mid],
                                t_launch, bisected=True)
            self._solve_recover(win, reqs[mid:], tickets[mid:],
                                t_launch, bisected=True)
            return
        self._quarantine(win, reqs[0], tickets[0], attempts, last,
                         bisected)

    def _solve_attempt(self, reqs, qp, d_col, int_cols, opts, kwargs):
        """One bounded solve attempt.  With dispatch_timeout_s set the
        solve runs on a worker thread and a hang becomes a typed
        _DispatchTimeout after the budget (the abandoned worker keeps
        the device busy until XLA returns — retry semantics on real
        hardware are therefore 'wait out the budget, then re-enqueue',
        not a device-side cancel; docs/dispatch.md).  The chaos seam
        runs INSIDE the attempt so injected hangs consume the timeout
        exactly like real ones."""
        with self._lock:      # concurrent dispatch threads share the
            idx = self._attempts          # attempt index sequence
            self._attempts += 1
        plan = self.fault_plan

        def run():
            if plan is not None:
                plan.before_dispatch(idx, [r[5] for r in reqs])
            return self.solve_fn(qp, d_col, int_cols, opts, **kwargs)

        timeout = self.options.dispatch_timeout_s
        if timeout is None:
            return run()
        box: dict = {}
        done = threading.Event()

        def worker():
            try:
                box["res"] = run()
            except BaseException as e:  # noqa: BLE001 — re-raised below
                box["exc"] = e
            finally:
                done.set()

        t = threading.Thread(target=worker, daemon=True,
                             name="mpisppy-tpu-dispatch-solve")
        t.start()
        if not done.wait(float(timeout)):
            raise _DispatchTimeout(
                f"dispatch exceeded its {timeout}s timeout "
                f"(attempt {idx})")
        if "exc" in box:
            raise box["exc"]
        return box["res"]

    def _deliver(self, win: _Window, reqs, tickets, res, sizes):
        off = 0
        plan = self.fault_plan
        for t, S, r in zip(tickets, sizes, reqs):
            if plan is not None and plan.drop_ticket(r[5]):
                # injected result loss: the ticket stays unresolved and
                # its deadline converts the would-be hang into a typed
                # SolveFailed at the caller
                off += S
                continue
            # per-request slices exclude the pad lanes automatically
            # (pads sit past the last real lane)
            t._result = jax.tree_util.tree_map(
                lambda a, o=off, s=S: a[o:o + s]
                if getattr(a, "ndim", 0) >= 1 else a, res)
            t._event.set()
            off += S

    def _retry_note(self, win: _Window, reqs, attempt: int,
                    exc: BaseException | None, backoff_s: float):
        with self._lock:
            self._retries += 1
        _metrics.REGISTRY.inc("dispatch_retries_total")
        self._emit_event(
            "dispatch-retry", attempt=attempt,
            requests=len(reqs),
            lanes=sum(int(r[0].c.shape[0]) for r in reqs),
            backoff_s=backoff_s,
            error="" if exc is None else f"{type(exc).__name__}: {exc}")

    def _quarantine(self, win: _Window, req, ticket, attempts: int,
                    exc: BaseException | None, bisected: bool):
        """Terminal isolation of one poisoned request: the ticket
        resolves with SolveFailed (reason timeout/exception), the lanes
        are accounted, and the quarantine is observable."""
        lanes = int(req[0].c.shape[0])
        reason = "timeout" if isinstance(exc, _DispatchTimeout) \
            else "exception"
        detail = "" if exc is None else f"{type(exc).__name__}: {exc}"
        with self._lock:
            self._quarantined_lanes += lanes
            self._quarantined_requests += 1
        _metrics.REGISTRY.inc("dispatch_quarantined_lanes_total", lanes)
        _metrics.REGISTRY.inc("dispatch_quarantined_requests_total")
        self._emit_event(
            "dispatch-quarantine", submit=req[5], lanes=lanes,
            attempts=attempts, reason=reason, bisected=bisected,
            error=detail)
        if not ticket.done():
            ticket._exc = SolveFailed(reason, detail=detail,
                                      attempts=attempts, lanes=lanes)
            ticket._event.set()

    def _emit_event(self, kind: str, **data):
        if self.bus is None:
            return
        self.bus.emit(kind, run=self.run, cyl="dispatch",
                      hub_iter=_hub_iter, **data)

    def _solve_merged(self, reqs):
        """Concatenate the window's requests, pad up the ladder, solve.
        Returns (result, per-request sizes, padded lane count, shape
        signature)."""
        qps = [r[0] for r in reqs]
        sizes = [int(q.c.shape[0]) for q in qps]
        S_tot = sum(sizes)
        qp, d_col = self._merge(reqs) if len(reqs) > 1 \
            else (reqs[0][0], reqs[0][1])
        int_cols, opts, kwargs = reqs[0][2], reqs[0][3], reqs[0][4]
        S_pad = self.ladder.bucket(S_tot) if self.options.pad_batch \
            else S_tot
        S_pad = max(S_pad, S_tot)
        qp, d_col = _buckets.pad_qp_batch(qp, d_col, S_pad)
        if S_pad > S_tot and kwargs:
            # per-lane kwargs (x_warm/y_warm) must ride the same
            # padding or their lane count no longer matches the qp's
            kwargs = {
                k: _buckets.pad_leading_rows(v, S_tot, S_pad)
                for k, v in kwargs.items()}
        sig = _buckets.shape_signature(qp, d_col) + (opts,)
        with self._lock:
            warm = sig in self._buckets
        before = self._watch.total()
        res = self._solve_attempt(reqs, qp, d_col, int_cols, opts,
                                  kwargs)
        compiled = self._watch.total() - before
        with self._lock:
            # += on a counter from concurrent dispatch threads is a
            # lost-update race without the lock — found by the
            # lock-discipline lint when the guarded-by audit landed
            # (ISSUE 10); same for the warm-bucket read above and the
            # solo-inflight read below
            self._dispatch_compiles += compiled
            solo = self._inflight == 1
        if warm and compiled and solo:
            # ADVISORY attribution: the counter is only read with one
            # dispatch in flight, but compiles from OTHER threads (a
            # hub step compiling a wheel kernel) and legitimately
            # value-derived shapes inside a bucket (detect_sos1_groups'
            # (G, L) arrays follow A's VALUES, not its shape) can still
            # land in the window.  That is why the default only counts;
            # compile_guard is the strict dev/test mode that turns the
            # count into an assertion on workloads known to be clean.
            with self._lock:
                self._unexpected_recompiles += compiled
            _metrics.REGISTRY.inc("dispatch_unexpected_recompiles_total",
                                  compiled)
            if self.options.compile_guard:
                raise AssertionError(
                    f"compile-cache discipline violated: {compiled} "
                    f"backend compile(s) against warm bucket {sig[:3]} "
                    "(if this workload legitimately varies value-"
                    "derived kernel shapes inside a bucket, run "
                    "without --dispatch-compile-guard)")
        with self._lock:
            self._buckets[sig] = self._buckets.get(sig, 0) + 1
        return res, sizes, S_pad, sig

    def _merge(self, reqs):
        """One megabatch BoxQP from same-key requests: batched fields
        concatenate along the lane axis, shared fields (same object by
        key construction) pass through; a field shared in one request
        but batched in another broadcasts before the concat."""
        qps = [r[0] for r in reqs]
        d_cols = [r[1] for r in reqs]
        sizes = [int(q.c.shape[0]) for q in qps]

        def cat(fields, batched_ndim):
            if all(getattr(f, "ndim", 0) < batched_ndim
                   for f in fields) and \
                    all(f is fields[0] for f in fields):
                return fields[0]
            return jnp.concatenate(
                [jnp.broadcast_to(f, (s,) + f.shape[-(batched_ndim - 1):])
                 if f.ndim < batched_ndim else f
                 for f, s in zip(fields, sizes)], axis=0)

        A0 = qps[0].A
        if hasattr(A0, "vals"):
            if A0.vals.ndim == 3:
                A = dataclasses.replace(
                    A0, vals=jnp.concatenate([q.A.vals for q in qps],
                                             axis=0))
            else:
                A = A0  # shared vals/cols: key guarantees identity
        elif A0.ndim == 3:
            A = jnp.concatenate([q.A for q in qps], axis=0)
        else:
            A = A0      # shared dense A: key guarantees identity
        qp = dataclasses.replace(
            qps[0],
            c=cat([q.c for q in qps], 2), q=cat([q.q for q in qps], 2),
            A=A,
            bl=cat([q.bl for q in qps], 2), bu=cat([q.bu for q in qps], 2),
            l=cat([q.l for q in qps], 2), u=cat([q.u for q in qps], 2))
        return qp, cat(d_cols, 2)

    def _key_label(self, win: _Window) -> str:
        """Compact stable-within-a-run render of a coalesce key for the
        by_key stats breakdown: the human-meaningful shape/dtype parts
        plus a short digest separating keys that only differ in shared
        structure identity (two tenants with same-shape but different
        shared-A problems must not fold into one row)."""
        n, m, dtype = win.key[0], win.key[1], win.key[2]
        digest = abs(hash(win.key)) & 0xFFFF
        return f"n{n}m{m}:{dtype}:k{digest:04x}"

    def _session_breakdown(self, reqs, sizes) -> list[dict]:
        """Per-session (run, iter, lanes) aggregation of a megabatch's
        requests from their captured DispatchContext tokens — the exact
        join the analyzer uses for concurrent sessions."""
        agg: dict[tuple, dict] = {}
        for r, S in zip(reqs, sizes):
            ctx = r[6]
            a = agg.setdefault((ctx.run, ctx.hub_iter),
                               {"run": ctx.run, "iter": ctx.hub_iter,
                                "lanes": 0, "requests": 0})
            a["lanes"] += S
            a["requests"] += 1
            # per-trace attribution for mixed megabatches (ISSUE 20):
            # the session token carries its segment's trace/span ids
            if ctx.trace_id and "trace_id" not in a:
                a["trace_id"] = ctx.trace_id
                a["span_id"] = ctx.span_id
        return list(agg.values())

    def _record(self, win: _Window, reqs, sizes, S_pad: int, sig,
                t_launch: float):
        real = sum(sizes)
        occ = real / max(1, S_pad)
        sessions = self._session_breakdown(reqs, sizes)
        key_label = self._key_label(win)
        with self._lock:
            self._batches += 1
            self._lanes += real
            self._pad_lanes += S_pad - real
            if len(sizes) > 1:
                self._coalesced_lanes += real
            self._by_cause[win.cause] = \
                self._by_cause.get(win.cause, 0) + 1
            bk = self._by_key.setdefault(
                key_label, {"batches": 0, "lanes": 0, "pad_lanes": 0,
                            "coalesced_lanes": 0, "runs": set()})
            bk["batches"] += 1
            bk["lanes"] += real
            bk["pad_lanes"] += S_pad - real
            if len(sizes) > 1:
                bk["coalesced_lanes"] += real
            bk["runs"].update(s["run"] for s in sessions)
            queue_depth = sum(len(w.reqs) for w in self._pending.values())
            # snapshot everything the unlocked metric/event writes
            # below read — the renders must see one consistent point
            # in time (lock-discipline lint, ISSUE 10)
            n_buckets = len(self._buckets)
            dispatch_compiles = self._dispatch_compiles
            inflight_max = self._inflight_max
        R = _metrics.REGISTRY
        R.inc("dispatch_batches_total")
        R.inc("dispatch_lanes_total", real)
        R.inc("dispatch_pad_lanes_total", S_pad - real)
        R.set_gauge("dispatch_batch_occupancy", occ)
        R.set_gauge("dispatch_queue_depth", queue_depth)
        R.set_gauge("dispatch_buckets_active", n_buckets)
        R.set_counter("dispatch_backend_compiles_total",
                      dispatch_compiles)
        if self.bus is not None:
            from mpisppy_tpu import telemetry as tel
            # the megabatch's run/iter stamp: when every riding request
            # carries ONE session token, the event joins that session's
            # timeline directly; a mixed (cross-tenant) batch keeps the
            # scheduler's own run with the per-session breakdown
            # carrying the exact attribution (ISSUE 12 satellite)
            runs = {s["run"] for s in sessions}
            ev_run, ev_iter, ev_trace = self.run, _hub_iter, None
            if len(sessions) == 1 and sessions[0]["run"]:
                ev_run = sessions[0]["run"]
                ev_iter = sessions[0]["iter"]
                # single-session batch: stamp the row with that
                # session's segment span (a DispatchContext quacks
                # like a TraceContext for make_event)
                ctx0 = reqs[0][6]
                ev_trace = ctx0 if ctx0.trace_id else None
            self.bus.emit(
                tel.DISPATCH, run=ev_run, cyl="dispatch",
                hub_iter=ev_iter, trace=ev_trace,
                requests=len(sizes), lanes=real, padded_to=S_pad,
                occupancy=occ, bucket=list(sig[:3]), key=key_label,
                wait_ms=1e3 * (t_launch - win.t0),
                queue_depth=queue_depth, cause=win.cause,
                inflight_max=inflight_max,
                **({"sessions": sessions}
                   if any(s["run"] for s in sessions)
                   and (len(runs) > 1 or runs != {self.run}) else {}))


# -- the process-default scheduler (prometheus_client-style global) ---------
_default_lock = threading.Lock()
_default: SolveScheduler | None = None


def get_scheduler(create: bool = True) -> SolveScheduler | None:
    """The process-default scheduler every library call site routes
    through; created lazily with default options on first use."""
    global _default
    with _default_lock:
        if _default is None and create:
            _default = SolveScheduler()
        return _default


def configure(options: DispatchOptions | None = None, bus=None,
              run: str = "") -> SolveScheduler:
    """(Re)build the process-default scheduler — the CLI wiring seam
    (generic_cylinders calls this off the --dispatch-* group).  Any
    previous default is flushed first."""
    global _default
    with _default_lock:
        old, _default = _default, None
    if old is not None:
        old.close()
    # a fresh scheduler means a fresh run: drop the previous wheel's
    # final hub-iteration stamp (and the calling thread's stale session
    # token) or the new run's warm-up dispatches would join a bogus old
    # iteration instead of reading pre-wheel
    clear_session_context()
    set_hub_iter(-1)
    sched = SolveScheduler(options or DispatchOptions(), bus=bus, run=run)
    with _default_lock:
        _default = sched
    return sched


def from_cfg(cfg, bus=None, run: str = "") -> SolveScheduler:
    """Build + install the default scheduler from the dispatch_args
    Config group (utils/config.py)."""
    timeout = cfg.get("dispatch_timeout_s")
    deadline = cfg.get("dispatch_deadline_s")
    return configure(DispatchOptions(
        coalesce=bool(cfg.get("dispatch_coalesce", True)),
        max_batch=int(cfg.get("dispatch_max_batch", 4096)),
        max_wait_ms=float(cfg.get("dispatch_max_wait_ms", 2.0)),
        max_inflight=int(cfg.get("dispatch_max_inflight", 2)),
        pad_batch=bool(cfg.get("dispatch_pad", True)),
        bucket_growth=float(cfg.get("dispatch_bucket_growth", 2.0)),
        compile_guard=bool(cfg.get("dispatch_compile_guard", False)),
        dispatch_timeout_s=None if timeout is None else float(timeout),
        retry_max=int(cfg.get("dispatch_retry_max", 2)),
        retry_backoff_s=float(cfg.get("dispatch_retry_backoff_s", 0.05)),
        deadline_s=None if deadline is None else float(deadline),
    ), bus=bus, run=run)


def solve_mip(qp, d_col, int_cols, opts=None, **kwargs):
    """Module-level convenience: one solve through the process-default
    scheduler (the drop-in for ops.bnb.solve_mip at every oracle call
    site — algos/mip.py routes here)."""
    return get_scheduler().solve_mip(qp, d_col, int_cols, opts, **kwargs)


def scheduler_stats() -> dict | None:
    """stats() of the default scheduler, None when none exists yet —
    bench.py embeds this in its artifact entries."""
    sched = get_scheduler(create=False)
    return None if sched is None else sched.stats()
