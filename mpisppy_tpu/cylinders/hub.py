###############################################################################
# Hub: runs the hub algorithm (PH), feeds spokes, tracks bounds, decides
# termination (ref:mpisppy/cylinders/hub.py:28-724).
#
# The reference hub Puts W/nonants into RMA windows and Gets bounds back,
# with write-id consensus; here `sync()` hands the spokes a host-side
# snapshot dict (device arrays — zero-copy) and harvests their previous
# results.  On ONE chip, classic spokes' separate device dispatches
# SERIALIZE against the hub (round-3 measured 642x bare PH per
# iteration for a 4-spoke wheel — async dispatch does NOT overlap work
# on a single queue); the production answer is algos/fused_wheel.py,
# which carries the bound planes INSIDE the hub's jitted step
# (measured <=4.5x bare PH for the same 4 bound planes).  Classic
# spokes remain for cut/rc providers and multi-process deployments.
#
# Termination semantics match ref:mpisppy/cylinders/hub.py:82-166:
#   * rel_gap  <= options['rel_gap']   (gap = (inner-outer)/|inner|;
#     when |inner| ~ 0 the denominator widens to max(|inner|,|outer|)
#     so shifted-objective models can still terminate — see compute_gaps)
#   * abs_gap  <= options['abs_gap']
#   * inner bounds stalled for 'max_stalled_iters' hub iterations
###############################################################################
from __future__ import annotations

import contextlib
import functools
import math
import time

import numpy as np

from mpisppy_tpu import global_toc, telemetry as tel
from mpisppy_tpu.cylinders.spcommunicator import SPCommunicator
from mpisppy_tpu.cylinders.spoke import ConvergerSpokeType
from mpisppy_tpu.telemetry import profiler as _prof


def _checkpoint_crc(data: dict) -> np.ndarray:
    """CRC32 over every array in key order — the checkpoint integrity
    stamp (docs/resilience.md).  Deterministic: keys sorted, raw bytes.
    Zero-copy: crc32 reads the array buffers directly (tobytes() would
    duplicate the full ~460 MB snapshot inside the time-critical
    emergency-save path)."""
    import zlib
    crc = 0
    for k in sorted(data):
        crc = zlib.crc32(k.encode(), crc)
        arr = np.ascontiguousarray(data[k])
        crc = zlib.crc32(memoryview(arr).cast("B"), crc)
    return np.asarray(crc, np.uint32)


def _identity(a):
    return a


@functools.lru_cache(maxsize=8)
def _replicated_gather(mesh):
    """Jitted identity with fully-replicated output on `mesh` — the
    cross-host allgather that makes a scenario-sharded leaf fetchable
    on every process (multi-process checkpointing, ISSUE 17).  Cached
    per mesh so repeated saves reuse one executable."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    return jax.jit(_identity,
                   out_shardings=NamedSharding(mesh, PartitionSpec()))


def _fetch_leaf(x, timeout_s: float | None = None) -> np.ndarray:
    """Fetch one state leaf to host.  Single-process (every shard
    addressable) this is a plain np.asarray.  On a multi-process mesh a
    scenario-sharded leaf spans NON-addressable devices, so it is first
    replicated through a jitted identity collective — which every
    process must enter (save_checkpoint runs at a deterministic
    iteration cadence there, options['checkpoint_every_iters']).  The
    gather is bounded by timeout_s: with a peer host dead the
    collective never completes, and a last-gasp emergency save must
    skip (and fall back to the last rotated snapshot) rather than hang
    the survivor (docs/resilience.md failure-semantics table)."""
    if getattr(x, "is_fully_addressable", True) \
            or getattr(x, "is_fully_replicated", False):
        return np.asarray(x)
    gather = _replicated_gather(x.sharding.mesh)
    if timeout_s is None:
        return np.asarray(gather(x))
    import threading
    box: list = []

    def run():
        box.append(np.asarray(gather(x)))

    t = threading.Thread(target=run, daemon=True,
                         name="mpisppy-tpu-ckpt-gather")
    t.start()
    t.join(float(timeout_s))
    if not box:
        raise TimeoutError(
            f"cross-host checkpoint gather exceeded {timeout_s}s "
            "(peer host unreachable?)")
    return box[0]


class Hub(SPCommunicator):
    """Bound bookkeeping + termination (ref:cylinders/hub.py:28-243)."""

    def __init__(self, opt, options: dict | None = None, spokes=None):
        super().__init__(opt, options)
        self.spokes = spokes or []
        self.BestOuterBound = -math.inf  # min problems: lower bound
        self.BestInnerBound = math.inf
        self.latest_ib_char = ""
        self.latest_ob_char = ""
        self._inner_bound_update_iter = 0
        self._iter = 0
        # telemetry spine (docs/telemetry.md): every hub observation —
        # iterations, harvests, bound decisions, checkpoints — is
        # EMITTED through the event bus; the legacy `trace` list here
        # and each spoke's `(iter, bound)` trace are subscriber views
        # (telemetry/views.py), so existing consumers read them
        # unchanged.  A bus arrives via options['telemetry_bus'] (the
        # CLI's --trace-jsonl / --metrics-snapshot wiring); otherwise
        # the hub gets a private sink-less bus whose only subscriber is
        # the view.
        self.trace: list[dict] = []
        # (name, hub_iter, t_end, dur_s) of every span event of the run
        self.spans: list[tuple] = []
        self.telemetry = self.options.get("telemetry_bus") \
            or tel.EventBus()
        # a serve session passes its own id so the session's lifecycle
        # events and its wheel's events share ONE run in the per-session
        # trace (docs/serving.md); standalone wheels mint a fresh one
        self.run_id = self.options.get("run_id") or tel.new_run_id()
        # causal trace (ISSUE 20): a serve session's bus arrives
        # already scoped to the session's segment span — adopt it; a
        # standalone wheel mints a fresh root so even a bare CLI run
        # is one complete trace
        if getattr(self.telemetry, "trace", None) is None \
                and hasattr(self.telemetry, "set_trace"):
            self.telemetry.set_trace(tel.TraceContext.mint())
        self._trace_view = tel.WheelTraceView(self)
        self.telemetry.subscribe(self._trace_view)
        self._last_guard_total = 0
        plan = self.options.get("fault_plan")
        if plan is not None:
            # fault injections report through the same spine
            plan.telemetry = self.telemetry
            plan.telemetry_run = self.run_id
        self._last_dispatch_batches = 0
        # adopt the process-default dispatch scheduler into this run:
        # its megabatch events then carry this hub's run id and join
        # the trace exactly (the scheduler is configured by the CLI
        # before any hub exists, so it cannot know the id itself) —
        # and arm the run's fault plan on its dispatch seams so chaos
        # runs fault the dispatch layer through the same plan object
        try:
            from mpisppy_tpu import dispatch as _dispatch
            sched = _dispatch.get_scheduler(create=False)
            if sched is not None and not sched.run:
                sched.run = self.run_id
            if sched is not None and plan is not None \
                    and sched.fault_plan is None:
                sched.fault_plan = plan
            # per-session context token (ISSUE 12 satellite): a SERVE
            # session's hub (marked by the injected run_id) stamps its
            # driver thread — including pre-wheel iter0 oracle work —
            # with THIS run's id, so concurrent sessions sharing one
            # scheduler stay joinable per session.  Standalone wheels
            # keep the process-global stamp untouched (their run
            # already matches the scheduler's).
            if self.options.get("run_id"):
                _dispatch.set_session_context(
                    self.run_id, -1, **self._trace_token())
        except Exception:
            pass
        # hub progress watchdog (docs/resilience.md): no hub iteration
        # or certified-bound movement for watchdog_budget_s wall
        # seconds -> flight-recorder dump + the configured action
        # (checkpoint-and-abort exit 75, or degrade the dispatch
        # scheduler to direct un-coalesced mode)
        self._watchdog = None
        budget = self.options.get("watchdog_budget_s")
        if budget:
            from mpisppy_tpu.resilience.watchdog import HubWatchdog
            self._watchdog = HubWatchdog(
                self, float(budget),
                action=self.options.get("watchdog_action", "abort"),
                interval_s=self.options.get("watchdog_interval_s"),
                shrink_fn=self.options.get("watchdog_shrink_fn"),
            ).start()
        self._profiler = None
        self._gc = None
        if self.options.get("profile_dir"):
            self._profiler = _prof.ProfilerSession(
                self.options["profile_dir"],
                num_iters=int(self.options.get("profile_iters", 5)),
                bus=self.telemetry, run=self.run_id)
        self._emit(tel.RUN_START, hub_class=type(self).__name__,
                   num_spokes=len(self.spokes))
        # sense-contradiction bookkeeping (docs/resilience.md): a
        # rejected bound is ambiguous evidence — EITHER the incoming
        # value or the standing opposite-sense incumbent is garbage.
        # _contra[side] records the DISTINCT spokes whose bounds
        # contradicted the CURRENT incumbent of `side`; enough of them
        # evict it (see _note_contradiction).
        self._contra: dict[str, list] = {"outer": [], "inner": []}

    def _emit(self, kind: str, _cyl: str = "hub", _t: float | None = None,
              **data):
        """Publish one event for this hub's run (no-op without sinks);
        `_t` back-dates its perf_counter stamp."""
        self.telemetry.emit(kind, run=self.run_id, cyl=_cyl,
                            hub_iter=self._iter, t_mono=_t, **data)

    def _trace_token(self) -> dict:
        """The bus's current trace/span ids as set_session_context
        kwargs — how `options['run_id']` hands the causal context to
        the thread-local DispatchContext (ISSUE 20)."""
        ctx = getattr(self.telemetry, "trace", None)
        if ctx is None:
            return {}
        return {"trace_id": ctx.trace_id, "span_id": ctx.span_id}

    def emit_span(self, name: str, dur_s: float,
                  t_end: float | None = None):
        """One timed wheel phase (host wall seconds, ending at the
        perf_counter instant `t_end`, default now) onto the stream —
        the analyzer's per-phase breakdown input.  Spans are disjoint
        leaf phases (profiler.span); on the fused wheel the device wait
        is the `scalar_wait` span, the loop's one blocking read
        (docs/telemetry.md)."""
        self._emit(tel.SPAN, _t=t_end, name=name, dur_s=dur_s)

    def _span(self, name: str):
        """Profiler annotation + SPAN event for one wheel phase."""
        return _prof.span(self, name)

    # -- collector pauses (docs/telemetry.md) -----------------------------
    def watch_gc(self):
        """Count the collector's pauses for this run (WheelSpinner.spin
        starts it; the run's end stops it)."""
        if self._gc is None:
            self._gc = _prof.GCPauses().start()

    def _flush_gc(self, stop: bool = False) -> dict:
        """gc_n / gc_s since the last flush for the iteration row; each
        pause of 1 ms or more becomes a `gc` span at its own time."""
        pauses = self._gc
        if pauses is None:
            return {}
        if stop:
            pauses.stop()
            self._gc = None
        n, secs, long = pauses.take()
        for t_end, dur, gen in long:
            self._emit(tel.SPAN, _t=t_end, name="gc", dur_s=dur,
                       generation=gen)
        return {"gc_s": secs, "gc_n": n}

    def emit_run_end(self, reason: str, **extra):
        """Emit the run-end record (exit reason + final gap) exactly
        once — the normal path reaches here via finalize(), a dying
        wheel via WheelSpinner.spin's unwind (reason "preemption" /
        "exception"), so a trace always ends with an explicit verdict
        instead of run termination being inferred from stream
        truncation (ISSUE 5 satellite)."""
        if getattr(self, "_run_ended", False):
            return
        self._run_ended = True
        self._flush_gc(stop=True)
        abs_gap, rel_gap = self.compute_gaps()
        self._emit(tel.RUN_END, reason=reason,
                   outer=self.BestOuterBound, inner=self.BestInnerBound,
                   abs_gap=abs_gap, rel_gap=rel_gap,
                   iterations=self._iter, **extra)

    # -- bound bookkeeping (ref:hub.py:207-243) ---------------------------
    # Non-finite values never enter the bookkeeping: a NaN outer bound
    # would poison every later max() comparison silently, and a +inf
    # outer (or -inf inner) would fire gap termination on garbage.
    # Sense CROSS-validation (outer vs inner) lives in _harvest_all where
    # the per-spoke strike counters are (docs/resilience.md).
    def OuterBoundUpdate(self, new_bound: float, char: str = "*"):
        if math.isfinite(new_bound) and new_bound > self.BestOuterBound:
            self.BestOuterBound = new_bound
            self.latest_ob_char = char
        return self.BestOuterBound

    def InnerBoundUpdate(self, new_bound: float, char: str = "*"):
        if math.isfinite(new_bound) and new_bound < self.BestInnerBound:
            self.BestInnerBound = new_bound
            self.latest_ib_char = char
            self._inner_bound_update_iter = self._iter
        return self.BestInnerBound

    def _validate_bound(self, sense: str, b: float) -> str | None:
        """None when `b` is acceptable, else a rejection reason.

        A bound is rejected when non-finite or SENSE-VIOLATING: an
        outer (lower) bound above the incumbent, or an inner bound
        below the certified outer bound, by more than `bound_slack`
        relative (default 5e-3 — legitimate f32 crossings measured up
        to ~2e-3 on the farmer wheel must pass)."""
        if not math.isfinite(b):
            return f"non-finite {sense} bound {b!r}"
        slack = float(self.options.get("bound_slack", 5e-3))
        if sense == "outer" and math.isfinite(self.BestInnerBound):
            lim = self.BestInnerBound \
                + slack * max(1.0, abs(self.BestInnerBound))
            if b > lim:
                return (f"sense-violating outer bound {b:.6g} > "
                        f"inner {self.BestInnerBound:.6g} + slack")
        if sense == "inner" and math.isfinite(self.BestOuterBound):
            lim = self.BestOuterBound \
                - slack * max(1.0, abs(self.BestOuterBound))
            if b < lim:
                return (f"sense-violating inner bound {b:.6g} < "
                        f"outer {self.BestOuterBound:.6g} - slack")
        return None

    # -- gaps + termination (ref:hub.py:82-166) ---------------------------
    def compute_gaps(self) -> tuple[float, float]:
        abs_gap = self.BestInnerBound - self.BestOuterBound
        nano = 1e-10
        if self.BestInnerBound in (math.inf, -math.inf):
            rel_gap = math.inf
        else:
            # Reference semantics: divide by |inner| (ref:hub.py:96-101).
            # That blows up when the optimal objective is near zero
            # (legit for shifted models) and rel_gap termination can then
            # never fire — ONLY in that degenerate case fall back to the
            # larger bound magnitude, so every normal run keeps the exact
            # reference gap convention (the one BENCH numbers use).
            denom = abs(self.BestInnerBound)
            ob = abs(self.BestOuterBound)
            near_zero = denom < 1e-6 * max(1.0, ob if math.isfinite(ob)
                                           else 0.0)
            if near_zero and math.isfinite(ob):
                denom = max(denom, ob)
            rel_gap = abs_gap / max(nano, denom)
        return abs_gap, rel_gap

    def determine_termination(self) -> bool:
        abs_gap, rel_gap = self.compute_gaps()
        opt = self.options
        if "rel_gap" in opt and rel_gap <= opt["rel_gap"]:
            global_toc(f"Terminating: rel_gap {rel_gap:.4e} <= "
                       f"{opt['rel_gap']}", True)
            self._term_reason = "converged"
            return True
        if "abs_gap" in opt and abs_gap <= opt["abs_gap"]:
            global_toc(f"Terminating: abs_gap {abs_gap:.4e} <= "
                       f"{opt['abs_gap']}", True)
            self._term_reason = "converged"
            return True
        if "max_stalled_iters" in opt:
            # spokes only produce results on exchange iterations, so the
            # stall budget counts in EXCHANGE rounds (with
            # spoke_sync_period=k, intermediate iterations cannot update
            # the inner bound and must not count as stalled)
            period = max(1, int(opt.get("spoke_sync_period", 1)))
            if (self._iter - self._inner_bound_update_iter
                    >= opt["max_stalled_iters"] * period
                    and self.BestInnerBound < math.inf):
                global_toc("Terminating: inner bound stalled", True)
                self._term_reason = "stalled"
                return True
        return False

    def is_converged(self) -> bool:
        return self.determine_termination()


class PHHub(Hub):
    """PH as the hub algorithm (ref:cylinders/hub.py:462-573).

    `opt` is an algos.ph.PH driver; the hub installs itself as
    `opt.spcomm` so the PH loop calls sync()/is_converged() each
    iteration (the cylinder seam, ref:phbase.py:1040-1056).
    """

    def setup_hub(self):
        self.opt.spcomm = self
        for sp in self.spokes:
            sp.make_windows()
        # hub-side extension hooks (ref:mpisppy/cylinders/hub.py:476-516
        # setup_hub drives the extension's setup + spoke-index wiring)
        ext = getattr(self.opt, "extobject", None)
        if ext is not None:
            if hasattr(ext, "setup_hub"):
                ext.setup_hub()
            if hasattr(ext, "initialize_spoke_indices"):
                ext.initialize_spoke_indices()

    def _snapshot(self) -> dict:
        """Device-array snapshot for spokes (ref:hub.py:517-532 sends
        Ws + nonants + bounds).  xbar views are reused from the PH state
        — ph_iterk already reduced them."""
        st = self.opt.state
        batch = self.opt.batch
        return {
            "W": st.W,
            "nonants": batch.nonants(st.solver.x),
            "xbar_scen": st.xbar,
            "xbar_nodes": st.xbar_nodes,
            "iter": self._iter,
            "bounds": (self.BestOuterBound, self.BestInnerBound),
        }

    def _harvest_all(self, only=None):
        """Fold every spoke's latest result into the bound bookkeeping.

        Harvested bounds are VALIDATED before they can move
        BestOuterBound/BestInnerBound.  Non-finite values (unambiguous
        garbage) count a strike against the producing spoke; after
        `spoke_max_strikes` the spoke is auto-disabled (skipped by
        harvest AND update) and the wheel continues on the remaining
        spokes — the analog of the reference simply not reading a dead
        cylinder's window.  Sense-violating values are rejected without
        blame and recorded as contradictions against the standing
        opposite incumbent (_note_contradiction).  The optional
        options['fault_plan'] harvest seam injects poisoned bounds
        HERE, between the spoke and the validation (resilience/faults)."""
        plan = self.options.get("fault_plan")
        max_strikes = int(self.options.get("spoke_max_strikes", 3))
        for j, sp in enumerate(self.spokes):
            if only is not None and sp not in only:
                continue
            if getattr(sp, "disabled", False):
                continue
            b = sp.harvest()
            if b is None:
                continue
            types = sp.converger_spoke_types
            if ConvergerSpokeType.OUTER_BOUND in types:
                sense = "outer"
            elif ConvergerSpokeType.INNER_BOUND in types:
                sense = "inner"
            else:
                continue  # cut/rc providers publish no bound
            self._emit(tel.SPOKE_HARVEST, spoke=j,
                       spoke_class=type(sp).__name__, sense=sense,
                       bound=float(b))
            if plan is not None:
                b = plan.filter_bound(j, sense, float(b), self._iter)
            reason = self._validate_bound(sense, b)
            if reason is not None:
                self._emit(tel.BOUND_REJECT, spoke=j, sense=sense,
                           bound=float(b), reason=reason)
                # scrub the offending value from the spoke's monotone
                # cache: harvests re-return the cache even with no new
                # result, so one transient spike would otherwise
                # re-offer itself every sync forever
                if getattr(sp, "bound", None) is not None:
                    sp.bound = None
                if reason.startswith("sense-violating"):
                    # ambiguous evidence (either the incoming value or
                    # the standing opposite incumbent is garbage):
                    # never a strike — blame needs corroboration
                    self._note_contradiction(sense, sp, reason)
                else:
                    self._strike(j, sp, reason, max_strikes)
                continue
            # spokes may declare their trace char (ref spoke classes'
            # converger_spoke_char); default to the class initial
            ch = getattr(sp, "converger_spoke_char",
                         type(sp).__name__[0])
            if sense == "outer":
                before = self.BestOuterBound
                self.OuterBoundUpdate(b, ch)
                improved = self.BestOuterBound > before
            else:
                before = self.BestInnerBound
                self.InnerBoundUpdate(b, ch)
                improved = self.BestInnerBound < before
                # hub-side incumbent cache: BestInnerBound must always
                # have a backing solution, even after the producing
                # spoke's cache is later scrubbed or the spoke disabled
                # (best_nonants falls back to this before xbar)
                if (self.BestInnerBound < before
                        and getattr(sp, "best_xhat", None) is not None):
                    self._best_inner_xhat = np.asarray(sp.best_xhat)
            # an accepted bound is CONSISTENT with the opposite-sense
            # incumbent: clear the suspicion that had built against it
            other = "inner" if sense == "outer" else "outer"
            self._contra[other] = []
            # the view appends (iter, bound) to sp.trace (views.py)
            self._emit(tel.BOUND_ACCEPT, spoke=j, sense=sense,
                       bound=float(b), char=ch, improved=bool(improved))

    def _strike(self, j: int, sp, reason: str, max_strikes: int):
        """One unambiguously-garbage (non-finite) bound = one strike; K
        strikes disable the spoke (ref analog: a misbehaving cylinder's
        window is never read again).  Counters survive on the spoke
        object so finalize() and tests can inspect them.  Only fresh
        invalid results accumulate strikes — the caller scrubs rejected
        values from the spoke cache, and the hub's own Best*Bound keeps
        every previously accepted value."""
        sp.strikes = getattr(sp, "strikes", 0) + 1
        self._emit(tel.SPOKE_STRIKE, spoke=j,
                   spoke_class=type(sp).__name__, reason=reason,
                   strikes=sp.strikes, max_strikes=max_strikes)
        global_toc(f"hub: rejected bound from spoke {j} "
                   f"({type(sp).__name__}): {reason} "
                   f"[strike {sp.strikes}/{max_strikes}]",
                   self.options.get("display_progress", False))
        if sp.strikes >= max_strikes and not getattr(sp, "disabled",
                                                     False):
            sp.disabled = True
            self._emit(tel.SPOKE_DISABLE, spoke=j,
                       spoke_class=type(sp).__name__, strikes=sp.strikes)
            global_toc(f"hub: DISABLED spoke {j} ({type(sp).__name__}) "
                       f"after {sp.strikes} strikes; continuing with "
                       f"the remaining spokes", True)

    def _note_contradiction(self, sense: str, sp, reason: str):
        """A finite sense-violating bound is ambiguous: EITHER the
        incoming value or the standing opposite incumbent is garbage —
        e.g. a wrong-sense outer bound accepted at iter 1 (before any
        inner existed to validate against) would poison the monotone
        BestOuterBound forever.  Contradictions from enough DISTINCT
        spokes flip the verdict and evict the incumbent.  Distinctness
        matters: one persistently rogue spoke repeating garbage every
        sync must never out-vote a repeatedly-confirmed incumbent (a
        count-based trigger let exactly that happen), so a lone
        contradictor can only ever log its dissent — in a two-spoke
        wheel a poisoned early incumbent stands, the wheel honestly
        never certifies, and the report shows the missing side as null
        rather than lying."""
        global_toc(f"hub: rejected {reason}",
                   self.options.get("display_progress", False))
        other = "outer" if sense == "inner" else "inner"
        rec = self._contra[other]
        if sp not in rec:
            rec.append(sp)
        limit = int(self.options.get("bound_evict_contras", 3))
        if len(rec) >= limit:
            self._evict_incumbent(other, rec)

    def _evict_incumbent(self, side: str, contradictors: list):
        """Reset a contradicted incumbent — no strikes, no blame: the
        evidence stays ambiguous, so nothing is charged to anyone and
        the surviving producers simply re-establish the bound on the
        next exchange."""
        val = self.BestOuterBound if side == "outer" \
            else self.BestInnerBound
        self._emit(tel.BOUND_EVICT, side=side, value=float(val),
                   contradictors=len(contradictors))
        global_toc(f"hub: EVICTING the {side} incumbent ({val:.6g}) — "
                   f"contradicted by {len(contradictors)} distinct "
                   f"spokes", True)
        if side == "outer":
            self.BestOuterBound = -math.inf
            self.latest_ob_char = ""
            # re-fold the hub's own certified trivial bound: it never
            # came from a spoke and is the one outer value we trust
            if (getattr(self, "_trivial_bound_folded", False)
                    and getattr(self.opt, "trivial_bound_certified",
                                False)
                    and self.opt.trivial_bound is not None):
                self.OuterBoundUpdate(self.opt.trivial_bound, "T")
        else:
            self.BestInnerBound = math.inf
            self.latest_ib_char = ""
            # the solution backing the evicted (distrusted) incumbent
            # goes with it — best_nonants must never write it out
            self._best_inner_xhat = None
            # don't let the eviction read as an instant stall
            self._inner_bound_update_iter = self._iter
        self._contra[side] = []

    def _fold_own_bounds(self):
        """Fold bounds the hub algorithm itself produces (PH: none —
        the trivial bound enters via is_converged)."""

    def _trace_extra(self) -> dict:
        return {"conv": self.opt._read_conv()}

    def _apply_warm_plane(self, plane: dict):
        """Seed a rolling-horizon stream's shifted W/x̄ plane
        (mpc/shift.py) into the PH state at the FIRST sync — the
        WXBarReader.post_iter0 timing (iter0 has run, so the seeded
        duals price iteration 1 onward) without the file round-trip:
        mpc/driver.py threads the plane through options['warm_plane'].
        Mirrors _restore_from_arrays' fused-state pattern so a fused
        wheel's wstate stays consistent with opt.state."""
        import dataclasses

        import jax.numpy as jnp
        opt = self.opt
        st = getattr(opt, "state", None)
        if st is None:
            return
        batch = opt.batch
        dt = st.W.dtype
        kw = {}
        if plane.get("W") is not None:
            kw["W"] = jnp.asarray(np.asarray(plane["W"]), dt)
        xbj = plane.get("xbar_nodes")
        if xbj is not None:
            xbj = jnp.asarray(np.asarray(xbj), dt)
            kw["xbar_nodes"] = xbj
            kw["xbar"] = (
                jnp.take_along_axis(xbj, batch.node_of_slot, axis=0)
                if batch.tree.num_nodes > 1
                else jnp.broadcast_to(xbj[0], st.xbar.shape))
        if not kw:
            return
        new = dataclasses.replace(st, **kw)
        wstate = getattr(opt, "wstate", None)
        if wstate is not None:
            opt.wstate = dataclasses.replace(wstate, ph=new)
        opt.state = new

    def sync(self):
        """One hub<->spoke exchange: harvest the spokes' previous async
        results, then launch their next round on a fresh snapshot.

        options['spoke_sync_period'] = k exchanges with the spokes only
        every k-th sync: their device work launched at the previous
        exchange keeps running across the intervening hub iterations
        (XLA async dispatch), which is exactly the reference's
        slower-cylinder overlap (ref:hub.py write-id freshness checks —
        a spoke that hasn't produced a new result simply isn't read).

        Telemetry (docs/telemetry.md): the wheel phases are bracketed
        with profiler spans, the --profile-dir session is advanced, and
        the per-iteration trace row is EMITTED as a hub-iteration event
        (the legacy self.trace list is a subscriber view)."""
        self._iter += 1
        if self._iter == 1 and self.options.get("warm_plane") is not None:
            self._apply_warm_plane(self.options["warm_plane"])
        if self._profiler is not None:
            self._profiler.on_sync(self._iter)
        with _prof.step("wheel_sync", self._iter):
            self._sync_body()

    def _sync_body(self):
        self._sync_prologue()
        self._sync_exchange()
        self._sync_epilogue()

    def _sync_prologue(self):
        # stamp the current hub iteration onto the out-of-band emitters
        # (dispatch megabatches, fault seams) so their events join the
        # iteration timeline exactly, not by seq-window heuristics
        # (ISSUE 5 satellite); -1 remains the pre-wheel stamp.  A
        # serve session's hub additionally carries a per-THREAD token
        # (run, iter) so concurrent sessions never clobber each
        # other's stamp (see __init__)
        from mpisppy_tpu import dispatch as _dispatch
        if self.options.get("run_id"):
            _dispatch.set_session_context(
                self.run_id, self._iter, **self._trace_token())
        _dispatch.set_hub_iter(self._iter)
        # live-migration drain (ISSUE 16): the fleet router sets the
        # session's preempt_event to move this wheel; raising here
        # lands the emergency checkpoint at a consistent sync boundary
        # (WheelSpinner.spin's preemption path), after which the
        # session restores on another replica via load_checkpoint
        drain = self.options.get("preempt_event")
        if drain is not None and drain.is_set():
            from mpisppy_tpu.resilience.faults import PreemptionError
            raise PreemptionError(
                f"migration drain requested at iter {self._iter}")
        plan = self.options.get("fault_plan")
        if plan is not None:
            plan.telemetry_iter = self._iter
            # chaos seams (resilience/faults): a simulated preemption
            # unwinds to WheelSpinner.spin's emergency save; lane
            # corruption mutates the solver state host-side so the
            # pdhg lane guard has something real to catch
            plan.maybe_preempt(self._iter)
            plan.corrupt_lanes(self._iter, self.opt)

    def _sync_exchange(self):
        """The host exchange: harvest -> validate -> publish ->
        checkpoint.  The async hub runs this as its host-complete half
        while the next device step is already in flight."""
        period = max(1, int(self.options.get("spoke_sync_period", 1)))
        do_spokes = (self._iter <= 2) or (self._iter % period == 0)
        # fused spokes (algos.fused_wheel) compute inside the hub's own
        # jitted step — harvesting them is a scalar read, so they fold
        # EVERY iteration; classic spokes keep the sync period
        fused = [sp for sp in self.spokes if getattr(sp, "fused", False)]
        classic = [sp for sp in self.spokes if not getattr(sp, "fused",
                                                           False)]
        with self._span("harvest"):
            self._harvest_all(only=fused)
            if do_spokes:
                self._harvest_all(only=classic)
        if do_spokes:
            # extension exchange with the spokes it cares about
            # (ref:mpisppy/cylinders/hub.py:517-532 drives the
            # extension's sync_with_spokes every sync)
            ext = getattr(self.opt, "extobject", None)
            if ext is not None and hasattr(ext, "sync_with_spokes"):
                ext.sync_with_spokes()
        self._fold_own_bounds()
        # building the snapshot dispatches a (small) device gather; with
        # an all-fused wheel no consumer exists, so skip it off-sync
        if (do_spokes and classic) or self.options.get("publish_snapshots"):
            with self._span("hub_sync"):
                payload = self._snapshot()
                self.from_hub.put(payload)  # for API parity / inspection
            if do_spokes:
                with self._span("spoke_update"):
                    for sp in classic:
                        if not getattr(sp, "disabled", False):
                            sp.update(payload)
        with self._span("checkpoint"):
            self._maybe_checkpoint()

    def _sync_epilogue(self):
        """Off-critical-path bookkeeping: the pipelined kernel-counter
        harvest, dispatch stats, watchdog beat, and the per-iteration
        trace row."""
        self._harvest_kernel_counters()
        self._harvest_dispatch_stats()
        abs_gap, rel_gap = self.compute_gaps()
        if self._watchdog is not None:
            self._watchdog.beat(self._iter, self.BestOuterBound,
                                self.BestInnerBound)
        extra = self._trace_extra()
        gc_row = self._flush_gc()
        self._emit(tel.HUB_ITERATION, **{
            "iter": self._iter, **extra, **gc_row,
            "outer": self.BestOuterBound, "inner": self.BestInnerBound,
            "abs_gap": abs_gap, "rel_gap": rel_gap,
            "ob_char": self.latest_ob_char, "ib_char": self.latest_ib_char,
        })
        if self.options.get("display_progress"):
            conv_str = (f" conv {extra['conv']:9.3e}"
                        if "conv" in extra else "")
            global_toc(
                f"iter {self._iter:4d}{conv_str}"
                f" outer {self.BestOuterBound:12.5g}"
                f" inner {self.BestInnerBound:12.5g} rel_gap {rel_gap:8.3e}"
                f" ({self.latest_ob_char}/{self.latest_ib_char})", True)

    # -- on-device kernel counter harvest (docs/telemetry.md) -------------
    def _counter_solvers(self):
        """(label, PDHGState) pairs carrying kernel counters: the hub's
        subproblem solver plus any fused bound planes' warm solvers
        (--kernel-counters arms them all via _fuse_wheel, so they must
        all be harvested or the exported totals silently undercount)."""
        out = []
        st = getattr(self.opt, "state", None)
        solver = getattr(st, "solver", None) if st is not None else None
        if solver is not None:
            out.append(("hub", solver))
        wstate = getattr(self.opt, "wstate", None)
        wopts = getattr(self.opt, "wheel_options", None)
        if wstate is not None and wopts is not None:
            # gate each plane on ITS options' telemetry flag: plane
            # states warm-start from the hub's iter0 solver and can
            # carry a counters pytree their own solve never updates —
            # harvesting that would report stale iter0 numbers forever
            plane_on = {
                "lag": wopts.lag_pdhg.telemetry and wopts.lag_windows,
                "xhat": wopts.xhat_pdhg.telemetry and wopts.xhat_windows,
                "slam": wopts.xhat_pdhg.telemetry and wopts.slam_windows,
                "shuf": wopts.xhat_pdhg.telemetry
                and wopts.shuffle_windows,
            }
            for name, on in plane_on.items():
                s = getattr(wstate, f"{name}_solver", None)
                if on and s is not None:
                    out.append((name, s))
        return [(cyl, s) for cyl, s in out
                if getattr(s, "counters", None) is not None]

    def _harvest_kernel_counters(self, flush: bool = False):
        """Mirror cumulative on-device counters into the metrics
        registry and the event stream — one small transfer per solver
        per sync (the ring stays in HBM), and a strict no-op unless the
        kernels were built with telemetry=True (counters None
        otherwise).

        PIPELINED off the hub critical path (ISSUE 11 satellite): each
        sync COMPLETES the harvest begun the previous sync (its async
        host copies have long landed — no block on the in-flight step)
        and BEGINS a fresh one on the current state.  Totals therefore
        lag one sync in the stream; they are cumulative mirrors
        (set_counter, monotone), and finalize calls with flush=True —
        DISCARDING the pending one-sync-stale snapshot and taking one
        synchronous harvest of the final state instead (folding both
        would stamp duplicate kernel-counters rows on the final sync)
        — so exported totals can never undercount the run
        (regression-tested in tests/test_async_wheel.py)."""
        from mpisppy_tpu.telemetry import counters as kcounters
        solvers = self._counter_solvers()
        pending = getattr(self, "_counters_pending", None)
        if pending and not flush:
            for cyl, handle in pending:
                self._fold_counter_harvest(
                    cyl, kcounters.complete_harvest(handle))
        # on flush the pending one-sync-stale snapshot is discarded:
        # the fresh synchronous harvest below supersedes it (totals are
        # cumulative set_counter mirrors), and folding both would stamp
        # two kernel-counters rows with different totals on the same
        # final sync
        self._counters_pending = [
            (cyl, kcounters.begin_harvest(s, include_ring=False))
            for cyl, s in solvers]
        if flush:
            for cyl, handle in self._counters_pending:
                self._fold_counter_harvest(
                    cyl, kcounters.complete_harvest(handle))
            self._counters_pending = []

    def _fold_counter_harvest(self, cyl: str, h: dict | None):
        if h is None:
            return
        from mpisppy_tpu.telemetry import counters as kcounters
        from mpisppy_tpu.telemetry import metrics as metrics_mod
        kcounters.fold_into_registry(metrics_mod.REGISTRY, h, cyl=cyl)
        if cyl != "hub":
            return
        guard_total = h["pdhg_guard_resets_total"]
        if guard_total > self._last_guard_total:
            self._emit(tel.LANE_QUARANTINE,
                       resets=guard_total - self._last_guard_total,
                       total=guard_total)
        self._last_guard_total = guard_total
        self._emit(tel.KERNEL_COUNTERS, **h)

    # -- dispatch-scheduler stats harvest (docs/dispatch.md) --------------
    def _harvest_dispatch_stats(self):
        """One per-sync snapshot of the solve-dispatch scheduler
        (queue depth, batch occupancy, in-flight, compile counts) onto
        the event stream.  The scheduler mirrors its gauges into the
        metrics registry itself; this only adds the per-iteration
        trace row, and only when dispatches actually happened since
        the last sync — a wheel that never touches the MIP oracle pays
        one dict lookup."""
        from mpisppy_tpu import dispatch as _dispatch
        stats = _dispatch.scheduler_stats()
        if not stats or stats["batches"] == self._last_dispatch_batches:
            return
        self._last_dispatch_batches = stats["batches"]
        self._emit(tel.DISPATCH, **stats)

    # -- crash-resilient checkpointing (VERDICT r3 #2; the analog of the
    # reference surviving solver/license hiccups, ref:spopt.py:931-960) --
    def _maybe_checkpoint(self):
        import time as _time
        path = self.options.get("checkpoint_path")
        if not path:
            return
        every_it = self.options.get("checkpoint_every_iters")
        if every_it:
            # deterministic iteration cadence, SYNCHRONOUS save: the
            # multi-process mesh path (ISSUE 17).  The leaf fetch is a
            # cross-host collective there, so every process must enter
            # it at the same point in program order — wall-clock
            # cadence and background writer threads both desync the
            # collective streams and deadlock gloo.
            if self._iter > 0 and self._iter % int(every_it) == 0 \
                    and self._iter != getattr(self, "_last_ckpt_iter", -1):
                if self.save_checkpoint(path):
                    self._last_ckpt_iter = self._iter
            return
        every = float(self.options.get("checkpoint_every_s", 60.0))
        now = _time.perf_counter()
        last = getattr(self, "_last_ckpt_t", None)
        if last is None:
            # first sync: start the clock, don't save yet
            self._last_ckpt_t = now
            return
        if now - last < every:
            return
        # only consume the cadence slot when a save actually LAUNCHES:
        # a skipped save (previous write thread still alive) must retry
        # next sync, or a slow write silently halves the checkpoint
        # frequency
        if self.save_checkpoint(path, background=True):
            self._last_ckpt_t = now

    def save_checkpoint(self, path: str, background: bool = False,
                        tmp_tag: str = ".tmp"):
        """Atomic npz snapshot of the full wheel: solver state (wstate
        for FusedPH, else PHState), hub bound bookkeeping, spoke bests,
        and caller extras (options['checkpoint_extra'] -> dict).

        background=True writes from a daemon thread: a full-wheel
        snapshot at 10k scenarios is ~460 MB, and fetching it from the
        device synchronously (~50 s measured in round 5) would gate the
        hub loop.  The state pytree is immutable and device_get is
        thread-safe, so the transfer overlaps compute; at most one save
        is in flight (later requests are skipped, not queued).

        Returns True when a write launched (or completed, for
        synchronous saves), False when it was skipped — the cadence
        bookkeeping in _maybe_checkpoint depends on this."""
        import threading

        import jax
        st = getattr(self.opt, "wstate", None)
        which = "wstate" if st is not None else "state"
        if st is None:
            st = self.opt.state
        if st is None:
            return False  # preempted before Iter0: nothing to persist
        # created here (always the main thread) so the two possible
        # writers — the background daemon and a later emergency save —
        # share one lock without a creation race
        if not hasattr(self, "_ckpt_lock"):
            self._ckpt_lock = threading.Lock()
        leaves, _ = jax.tree.flatten(st)
        if background:
            prev = getattr(self, "_ckpt_thread", None)
            if prev is not None and prev.is_alive():
                return False
            host_meta = self._checkpoint_meta(which)
            t = threading.Thread(
                target=self._write_checkpoint,
                args=(path, leaves, host_meta, tmp_tag), daemon=True)
            self._ckpt_thread = t
            t.start()
            return True
        self._write_checkpoint(path, leaves, self._checkpoint_meta(which),
                               tmp_tag)
        return True

    def emergency_checkpoint(self, path: str) -> bool:
        """Synchronous last-gasp save for SIGTERM/SIGINT/preemption.

        Deliberately does NOT wait for an in-flight background write: at
        10k scenarios a snapshot write is ~50 s (see save_checkpoint),
        longer than the eviction grace window, so joining would forfeit
        the save.  A distinct tmp name keeps the two writers from
        clobbering each other's staging file; if the slow background
        write lands after us its (older) snapshot becomes `path` and
        ours rotates to path.1 — load_checkpoint validates and falls
        back, so a complete snapshot survives either ordering.  Returns
        True when a snapshot landed.

        Best effort BY CONTRACT: on a multi-process mesh whose peer
        just died, the leaf-fetch gather cannot complete (bounded by
        checkpoint_gather_timeout_s, _fetch_leaf) — the save is
        reported skipped and the restore path falls back to the last
        rotated periodic snapshot instead of hanging the survivor."""
        try:
            return self.save_checkpoint(path, background=False,
                                        tmp_tag=".emergency.tmp")
        except Exception as e:  # noqa: BLE001 — last-gasp, logged
            global_toc(f"emergency checkpoint failed ({e}); "
                       "falling back to last rotated snapshot", True)
            return False

    def _checkpoint_meta(self, which: str) -> dict:
        """Host-side bookkeeping captured SYNCHRONOUSLY (the mutable
        bits; device leaves are immutable and can transfer later)."""
        data = {}
        data["which"] = np.frombuffer(which.encode(), np.uint8)
        data["hub_iter"] = np.asarray(self._iter)
        data["opt_iter"] = np.asarray(self.opt._iter)
        data["bounds"] = np.asarray([self.BestOuterBound,
                                     self.BestInnerBound])
        data["ib_update_iter"] = np.asarray(self._inner_bound_update_iter)
        tb = self.opt.trivial_bound
        data["trivial"] = np.asarray([
            np.nan if tb is None else tb,
            1.0 if self.opt.trivial_bound_certified else 0.0,
            1.0 if getattr(self, "_trivial_bound_folded", False) else 0.0])
        for j, sp in enumerate(self.spokes):
            if sp.bound is not None:
                data[f"spoke{j}_bound"] = np.asarray(sp.bound)
                bx = getattr(sp, "best_xhat", None)
                if bx is not None:
                    data[f"spoke{j}_xhat"] = np.asarray(bx)
        bx = getattr(self, "_best_inner_xhat", None)
        if bx is not None:
            data["hub_best_xhat"] = np.asarray(bx)
        extra = self.options.get("checkpoint_extra")
        if callable(extra):
            for k, v in extra().items():
                data[f"extra_{k}"] = np.asarray(v)
        return data

    def _write_checkpoint(self, path: str, leaves, data: dict,
                          tmp_tag: str = ".tmp"):
        """Atomic rotated write: tmp -> rotate path->path.1->... ->
        rename tmp to path.  The meta carries a CRC32 over every array
        so load_checkpoint can reject silent corruption (a torn zip
        already fails np.load; bit rot inside a member does not)."""
        import os
        gather_timeout = self.options.get("checkpoint_gather_timeout_s")
        for i, x in enumerate(leaves):
            data[f"leaf{i}"] = _fetch_leaf(x, gather_timeout)
        data["crc"] = _checkpoint_crc(data)
        tmp = path + tmp_tag
        with open(tmp, "wb") as f:
            np.savez(f, **data)
        # rotate + final rename under the shared writer lock: without
        # it the background daemon could rename its OLDER tmp over a
        # just-landed emergency snapshot without rotating it aside,
        # destroying the newest state outright (distinct tmp names only
        # protect the staging files, not this sequence)
        import threading
        lock = getattr(self, "_ckpt_lock", None) or threading.Lock()
        with lock:
            # keep floor of 2: with a single slot a slow background
            # write finishing after an emergency save would still
            # CLOBBER it — the both-orderings survival guarantee
            # (emergency_checkpoint) needs >= 2 slots
            keep = max(2, int(self.options.get("checkpoint_keep", 2)))
            for i in range(keep - 1, 0, -1):
                src = path if i == 1 else f"{path}.{i - 1}"
                try:
                    if os.path.exists(src):
                        os.replace(src, f"{path}.{i}")
                except OSError:
                    # a stolen rotation slot is harmless — every
                    # completed snapshot is self-validating; only
                    # losing a WRITE would matter
                    pass
            os.replace(tmp, path)
            # durability: flush the directory inode so a host crash
            # right after this rename cannot roll the entry back and
            # lose the newest snapshot (utils/atomic_io.fsync_dir;
            # tests/test_chaos.py crash-ordering test)
            from mpisppy_tpu.utils.atomic_io import fsync_dir
            fsync_dir(path)
        # may run on the background writer daemon: the bus is
        # thread-safe, and the snapshot's own hub_iter (not the
        # possibly-advanced live self._iter) stamps the event
        self.telemetry.emit(
            tel.CHECKPOINT_WRITE, run=self.run_id, cyl="hub",
            hub_iter=int(data["hub_iter"]), path=path,
            bytes=os.path.getsize(path))
        from mpisppy_tpu.telemetry import metrics as metrics_mod
        metrics_mod.REGISTRY.inc("checkpoint_writes_total")
        plan = self.options.get("fault_plan")
        if plan is not None:
            plan.on_checkpoint_written(path)

    def _checkpoint_candidates(self, path: str) -> list[str]:
        """Existing snapshots, newest first: path, path.1, path.2, ..."""
        import os
        out = [path] if os.path.exists(path) else []
        i = 1
        while os.path.exists(f"{path}.{i}"):
            out.append(f"{path}.{i}")
            i += 1
        return out

    def load_checkpoint(self, path: str, transform=None) -> dict:
        """Restore a save_checkpoint snapshot into the built (unspun)
        wheel; ph_main then skips Iter0 and resumes the loop.  Returns
        the extras dict.

        transform: optional arrays-dict -> arrays-dict hook applied
        after integrity checks and before shape validation — the
        elastic-reshard seam (parallel/elastic.adapt_checkpoint_arrays
        re-partitions scenario-major leaves onto a shrunk mesh).

        Falls back through the rotated candidates (path, path.1, ...)
        on a torn/corrupt/incompatible file instead of crashing — the
        preemption-tolerance contract: the newest VALID snapshot wins.
        "Newest" is decided by the hub_iter stored in each snapshot's
        meta, not by filename: an emergency save racing a slow
        background write can leave the OLDER snapshot at `path` (the
        background writer's rotation lands last), and filename order
        would silently discard the iterations the emergency save
        preserved."""
        cands = self._checkpoint_candidates(path)
        order = []
        for i, cand in enumerate(cands):
            try:  # cheap lazy read of one meta scalar, no validation
                with np.load(cand) as d:
                    it = int(d["hub_iter"])
            except Exception:
                it = -1  # unreadable here: full validation gets it last
            order.append((it, -i, cand))
        order.sort(reverse=True)
        errors = []
        for _, _, cand in order:
            try:
                arrays = self._read_checkpoint_arrays(cand)
            except Exception as e:  # torn zip, bad crc, IO error, ...
                errors.append(f"{cand}: {type(e).__name__}: {e}")
                continue
            try:
                if transform is not None:
                    arrays = transform(arrays)
                extras = self._restore_from_arrays(arrays)
            except ValueError as e:  # wrong shapes/dtypes/leaf count
                errors.append(f"{cand}: {e}")
                continue
            if cand != path:
                global_toc(f"checkpoint: {path} invalid, restored the "
                           f"older rotated snapshot {cand}", True)
            self._emit(tel.CHECKPOINT_RESTORE, path=cand,
                       fallback=cand != path)
            return extras
        detail = "; ".join(errors) if errors else "no snapshot files"
        raise FileNotFoundError(
            f"no valid checkpoint under {path!r}: {detail}")

    def _read_checkpoint_arrays(self, path: str) -> dict:
        """Load + integrity-check one snapshot file (no state mutation).
        The NpzFile is a context manager — it holds an open zip handle
        that was previously never closed."""
        with np.load(path) as data:
            arrays = {k: np.asarray(data[k]) for k in data.files}
        if "crc" in arrays:
            stored = int(arrays.pop("crc"))
            actual = int(_checkpoint_crc(arrays))
            if actual != stored:
                raise ValueError(
                    f"checksum mismatch (stored {stored:#x}, "
                    f"recomputed {actual:#x})")
        if "which" not in arrays:
            raise ValueError("not a wheel checkpoint (missing 'which')")
        return arrays

    def _restore_from_arrays(self, data: dict) -> dict:
        import jax
        import jax.numpy as jnp
        from mpisppy_tpu.utils.wxbarutils import validate_state_leaves
        which = bytes(data["which"]).decode()
        template = self.opt.state_template()
        leaves, treedef = jax.tree.flatten(template)
        validate_state_leaves(data, leaves)
        new = [jnp.asarray(data[f"leaf{i}"]) for i in range(len(leaves))]
        st = jax.tree.unflatten(treedef, new)
        if which == "wstate":
            self.opt.wstate = st
            self.opt.state = st.ph
        else:
            self.opt.state = st
        self._iter = int(data["hub_iter"])
        self.opt._iter = int(data["opt_iter"])
        ob, ib = [float(v) for v in data["bounds"]]
        self.BestOuterBound, self.BestInnerBound = ob, ib
        self._inner_bound_update_iter = int(data["ib_update_iter"])
        tb, cert, folded = [float(v) for v in data["trivial"]]
        self.opt.trivial_bound = None if math.isnan(tb) else tb
        self.opt.trivial_bound_certified = bool(cert)
        self._trivial_bound_folded = bool(folded)
        if "hub_best_xhat" in data:
            self._best_inner_xhat = np.asarray(data["hub_best_xhat"])
        # re-baseline the quarantine delta tracker: the restored solver
        # carries its historical cumulative guard_resets, and without
        # this the first post-restore sync would emit a spurious
        # lane-quarantine event re-reporting all past resets as fresh
        solver = getattr(self.opt.state, "solver", None)
        if solver is not None:
            self._last_guard_total = int(
                np.asarray(solver.guard_resets).sum())
        for j, sp in enumerate(self.spokes):
            key = f"spoke{j}_bound"
            if key in data:
                sp.bound = float(data[key])
                if f"spoke{j}_xhat" in data:
                    sp.best_xhat = np.asarray(data[f"spoke{j}_xhat"])
        return {k[len("extra_"):]: data[k] for k in data
                if k.startswith("extra_")}

    def is_converged(self) -> bool:
        # use the PH trivial bound as the initial outer bound
        # (ref:hub.py:544) — but only when its dual-residual certificate
        # held: a truncated iter0 primal value can exceed the optimum,
        # and an invalid outer bound here would fire the "certified" gap
        # termination wrongly.  A once-flag, not an iteration-count gate:
        # the driver also syncs after Iter0 (ref:phbase.py:905-910), so
        # by the first is_converged call _iter is already 2.
        if (self.opt.trivial_bound is not None
                and not getattr(self, "_trivial_bound_folded", False)
                and getattr(self.opt, "trivial_bound_certified", False)):
            self._trivial_bound_folded = True
            self.OuterBoundUpdate(self.opt.trivial_bound, "T")
        return self.determine_termination()

    def main(self):
        """ref:cylinders/hub.py:571-573."""
        return self.opt.ph_main()

    def finalize(self):
        # the run is terminating on purpose: the watchdog must not
        # trip on the (possibly long) finalization work
        if self._watchdog is not None:
            self._watchdog.stop()
        # one last harvest so late async results count; fused drivers
        # first sync their pipelined scalar cache to the final iterate
        if hasattr(self.opt, "flush_scalars"):
            self.opt.flush_scalars()
        self._harvest_all()
        # settle any in-flight background checkpoint write so the file
        # on disk is complete before the caller inspects/deletes it
        t = getattr(self, "_ckpt_thread", None)
        if t is not None and t.is_alive():
            t.join()
        if self._profiler is not None:
            self._profiler.close()
        # final totals after the last iterk: complete the pipelined
        # pending harvest AND take one synchronous final one, so the
        # exported totals exactly match the device state
        self._harvest_kernel_counters(flush=True)
        self.emit_run_end(getattr(self, "_term_reason", None)
                          or "max-iter")
        return self.BestInnerBound

    def hub_finalize(self):
        abs_gap, rel_gap = self.compute_gaps()
        global_toc(f"Final bounds: outer {self.BestOuterBound:.6g} "
                   f"inner {self.BestInnerBound:.6g} rel_gap {rel_gap:.3e}",
                   self.options.get("display_progress", False))

    # -- solution access --------------------------------------------------
    def best_nonants(self) -> np.ndarray:
        """(num_nodes, N) nonants of the solution that achieved
        BestInnerBound — the inner-bound winner's cached x̂
        (ref:spin_the_wheel.py:171-195 _determine_innerbound_winner);
        falls back to the final xbar when no incumbent exists."""
        winner, best = None, math.inf
        for sp in self.spokes:
            # a NaN cached bound must never enter the winner scan (every
            # NaN comparison is False, so depending on spoke order it
            # could silently shadow — or be shadowed by — a real
            # incumbent), and neither may a disabled spoke's cache or a
            # value the hub's validation would reject: the written
            # solution must be consistent with the reported bounds
            if (ConvergerSpokeType.INNER_BOUND in sp.converger_spoke_types
                    and not getattr(sp, "disabled", False)
                    and sp.bound is not None and math.isfinite(sp.bound)
                    and sp.bound < best
                    and self._validate_bound("inner", sp.bound) is None
                    and getattr(sp, "best_xhat", None) is not None):
                winner, best = sp, sp.bound
        xhat = None
        if winner is not None:
            xhat = np.asarray(winner.best_xhat)
        elif getattr(self, "_best_inner_xhat", None) is not None:
            # the spoke that produced BestInnerBound was scrubbed or
            # disabled since: the hub-side cache (stored the moment the
            # bound was ACCEPTED, _harvest_all) still backs the
            # reported bound with its actual solution
            xhat = self._best_inner_xhat
        if xhat is not None:
            if xhat.ndim == 1:
                num_nodes = self.opt.batch.tree.num_nodes
                return np.broadcast_to(xhat, (num_nodes, xhat.shape[0]))
            return xhat
        return self._fallback_nonants()

    def _fallback_nonants(self) -> np.ndarray:
        return np.asarray(self.opt.state.xbar_nodes)


class AsyncPHHub(PHHub):
    """Asynchronous exchange hub (ISSUE 11 tentpole;
    docs/async_wheel.md).  Pair with algos.async_wheel.AsyncFusedPH.

    options['async_staleness'] = s >= 1 splits every sync into a
    device-issue half (iteration stamping, fault seams, the driver's
    plane write — all while the just-dispatched step runs) and a
    host-complete half (harvest -> validate -> publish -> checkpoint,
    all against information the depth-2 scalar pipeline already
    landed), so the host exchange overlaps device iterations instead
    of serializing between dispatches.  The kernel-counter harvest is
    pipelined in the base hub already (begin now / complete next sync);
    here the plane-write and overlap attribution additionally land in
    the trace (`plane-write`, `exchange-overlap` events).

    s = 0 routes every sync through the synchronous PHHub body —
    trajectories, trace events and checkpoints are bit-identical to a
    plain PHHub wheel by construction (tested)."""

    def _async_staleness(self) -> int:
        """The ONE staleness source of truth is the driver's
        AsyncWheelOptions (it owns the delay line and decides between
        the sync and stale iteration paths); options['async_staleness']
        is only the CLI mirror.  Deriving the hub's routing from the
        driver — and refusing a contradictory mirror — means an
        AsyncFusedPH paired with this hub can never silently run the
        synchronous body while the driver queues plane tickets and
        events nobody drains."""
        aopts = getattr(self.opt, "async_options", None)
        drv = None if aopts is None else int(aopts.staleness)
        mirror = self.options.get("async_staleness")
        if drv is not None and mirror is not None and int(mirror) != drv:
            raise ValueError(
                f"async_staleness mismatch: hub options carry "
                f"{int(mirror)} but the driver's AsyncWheelOptions "
                f"carry {drv} — set one (the driver's is "
                f"authoritative)")
        if drv is not None:
            return drv
        return int(mirror or 0)

    def _exchange_gate(self):
        """Context guarding the host-complete half.  The default is a
        no-op; the serve layer's multiplexer (serve/multiplex.py)
        overrides it with a token ring so only one session at a time
        runs its host exchange while every other session's device
        issue half keeps feeding the wheel — one device stream
        advances several tenants between host exchanges."""
        return contextlib.nullcontext()

    def _sync_body(self):
        staleness = self._async_staleness()
        if staleness <= 0:
            return super()._sync_body()
        from mpisppy_tpu.telemetry import metrics as metrics_mod
        t0 = time.perf_counter()
        with self._span("exchange_issue"):
            self._sync_prologue()
            plan = self.options.get("fault_plan")
            # the driver recorded its plane writes while dispatching
            # this iteration; stamp them onto the stream here (the
            # driver has no bus)
            for evd in getattr(self.opt, "take_plane_events",
                               lambda: [])():
                self._emit(tel.PLANE_WRITE, **evd)
                metrics_mod.REGISTRY.inc("async_plane_writes_total")
                metrics_mod.REGISTRY.set_gauge(
                    "async_plane_staleness",
                    float(evd.get("staleness", 0)))
        t1 = time.perf_counter()
        with self._span("exchange_complete"), self._exchange_gate():
            if plan is not None:
                # chaos seam: a slow host harvest (resilience/faults
                # AsyncExchangeFault) — the wedged-exchange case the
                # hub watchdog must still catch
                plan.before_harvest(self._iter)
            # settle the PREVIOUS iteration's plane tickets with the
            # PR-8 bounded-wait semantics (a wedged exchange surfaces
            # as SolveFailed('deadline'), never a silent hang)
            if hasattr(self.opt, "result_exchange"):
                self.opt.result_exchange()
            self._sync_exchange()
        t2 = time.perf_counter()
        self._sync_epilogue()
        theta = getattr(self.opt, "last_theta", None)
        self._emit(tel.EXCHANGE_OVERLAP,
                   staleness=staleness,
                   issue_s=round(t1 - t0, 6),
                   complete_s=round(t2 - t1, 6),
                   **({} if theta is None else {"theta": float(theta)}))


class APHHub(PHHub):
    """APH as the hub algorithm (ref:mpisppy/cylinders/hub.py:712-724
    APHHub): identical exchange surface to PHHub — Ws and nonants out,
    bounds in — minus the barrier-synchronized write-id protocol the
    reference skips for APH (ref:hub.py:396,420,427-431), which has no
    analog here anyway."""

    def _trace_extra(self) -> dict:
        return {"conv": float(self.opt.state.conv),
                "theta": float(self.opt.state.theta)}

    def main(self):
        """ref:cylinders/hub.py:722-724."""
        return self.opt.APH_main()


class LShapedHub(PHHub):
    """L-shaped (Benders) as the hub algorithm
    (ref:mpisppy/cylinders/hub.py:618-710 LShapedHub): sends only
    NONANTS (the master's current candidate) to spokes — no W exists —
    and folds the Benders lb/ub into the bound bookkeeping."""

    def setup_hub(self):
        self.opt.spcomm = self
        for sp in self.spokes:
            types = sp.converger_spoke_types
            if ConvergerSpokeType.W_GETTER in types:
                raise RuntimeError(
                    "LShapedHub cannot feed W-getter spokes "
                    "(ref:hub.py:618-710 sends nonants only)")
            sp.make_windows()

    def _snapshot(self) -> dict:
        ls = self.opt  # an algos.lshaped.LShapedMethod
        batch = ls.batch
        xhat = np.asarray(ls.xhat)
        S = batch.num_scenarios
        return {
            "nonants": np.broadcast_to(xhat, (S, xhat.shape[0])),
            "xbar_scen": np.broadcast_to(xhat, (S, xhat.shape[0])),
            "xbar_nodes": xhat[None, :],
            "iter": self._iter,
            "bounds": (self.BestOuterBound, self.BestInnerBound),
        }

    def _fold_own_bounds(self):
        # the hub algorithm itself produces both bounds
        self.OuterBoundUpdate(self.opt.lb, "B")
        if np.isfinite(self.opt.ub):
            self.InnerBoundUpdate(self.opt.ub, "B")

    def _trace_extra(self) -> dict:
        return {}

    def is_converged(self) -> bool:
        return self.determine_termination()

    def main(self):
        return self.opt.lshaped_algorithm()

    def _fallback_nonants(self) -> np.ndarray:
        return np.asarray(self.opt.xhat)[None, :]
