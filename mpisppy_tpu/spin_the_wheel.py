###############################################################################
# WheelSpinner: top-level orchestration (ref:mpisppy/spin_the_wheel.py:18-242).
#
# The reference splits COMM_WORLD into a (strata x cylinder) process grid
# and runs one opt object + SPCommunicator per rank
# (ref:spin_the_wheel.py:224-242 _make_comms).  Here all cylinders drive
# ONE device mesh from one host process: the hub's PH loop and every
# spoke's batched solve are enqueued on the same XLA stream, overlapping
# like the reference's concurrent cylinders, and the scenario axis is the
# mesh axis.  hub_dict / list_of_spoke_dicts keep the reference's shape:
#
#   hub_dict = {"hub_class": PHHub, "hub_kwargs": {"options": {...}},
#               "opt_class": PH, "opt_kwargs": {...}}
#   spoke_dict = {"spoke_class": LagrangianOuterBound,
#                 "opt_kwargs": {"options": {...}}}
###############################################################################
from __future__ import annotations

import numpy as np

from mpisppy_tpu import global_toc
from mpisppy_tpu.resilience.faults import PreemptionError
from mpisppy_tpu.telemetry import profiler as _prof


class WheelSpinner:
    """ref:mpisppy/spin_the_wheel.py:18."""

    def __init__(self, hub_dict: dict, list_of_spoke_dict=None):
        self.hub_dict = hub_dict
        self.list_of_spoke_dict = list_of_spoke_dict or []
        self.spcomm = None
        self.opt = None
        self.on_hub = True  # single-process: we always "are" the hub
        self.preempted = False

    def build(self):
        """Construct opt + spokes + hub without running (split out so a
        checkpoint can be restored into the built objects before
        spin())."""
        if self.spcomm is not None:
            return self
        # the `wheel_build` span reports through the hub it builds
        with _prof.span(None, "wheel_build") as build_span:
            hd = self.hub_dict
            opt_class = hd["opt_class"]
            self.opt = opt_class(**hd.get("opt_kwargs", {}))

            spokes = []
            for sd in self.list_of_spoke_dict:
                spoke_class = sd["spoke_class"]
                kw = dict(sd.get("opt_kwargs", {}))
                spokes.append(spoke_class(self.opt, kw.get("options", kw)))

            hub_class = hd["hub_class"]
            hub_kwargs = dict(hd.get("hub_kwargs", {}))
            self.spcomm = hub_class(self.opt,
                                    hub_kwargs.get("options", {}),
                                    spokes=spokes)
            build_span.emitter = self.spcomm
            self.spcomm.make_windows()
            self.spcomm.setup_hub()
        return self

    def spin(self, comm_world=None):
        """Build opt + hub + spokes, run the hub algorithm to
        completion, terminate + finalize the spokes
        (ref:spin_the_wheel.py:43-149 run()).

        Preemption tolerance (docs/resilience.md): when the hub is
        configured with a checkpoint_path, SIGTERM/SIGINT are converted
        to PreemptionError, which triggers one SYNCHRONOUS emergency
        checkpoint before re-raising — on a preemptible TPU pool the
        eviction signal arrives seconds before the kill, exactly enough
        for a last-gasp save.  A later run restores via
        hub.load_checkpoint and resumes mid-loop."""
        self.build()
        global_toc("Starting wheel spin", False)
        ckpt_path = self.spcomm.options.get("checkpoint_path")
        prev_handlers = self._install_preemption_handlers() \
            if ckpt_path else None
        self.spcomm.watch_gc()
        try:
            self.spcomm.main()
        except PreemptionError as e:
            self.preempted = True
            if ckpt_path:
                saved = self.spcomm.emergency_checkpoint(ckpt_path)
                global_toc(
                    f"preempted: emergency checkpoint "
                    f"{'written to ' + ckpt_path if saved else 'SKIPPED'}"
                    f" at hub iter {self.spcomm._iter}", True)
            # run-end with an explicit exit reason + black-box dump,
            # AFTER the emergency save (the save must win the race for
            # the eviction grace window; docs/telemetry.md)
            self._record_crash("preemption", e)
            raise
        except BaseException as e:  # noqa: BLE001 — recorded, re-raised
            self._record_crash("exception", e)
            raise
        finally:
            self._restore_preemption_handlers(prev_handlers)
            # drop this thread's dispatch session token (ISSUE 12):
            # the run is over — a later wheel (or bare scheduler use)
            # on this thread must not inherit a dead run's stamp
            try:
                from mpisppy_tpu import dispatch as _dispatch
                _dispatch.clear_session_context()
            except Exception:
                pass
        # the second segment of the `finalize` span (the first times the
        # PH algorithm's post_loops)
        with _prof.span(self.spcomm, "finalize"):
            self.spcomm.send_terminate()
            self.spcomm.finalize()
            self.spcomm.hub_finalize()
            self.spcomm.free_windows()
        return self

    def _record_crash(self, reason: str, exc: BaseException) -> None:
        """Last words of a dying wheel: emit the run-end event (exit
        reason + final gap) and dump any flight-recorder black box
        subscribed to the hub's bus to flight-<runid>.jsonl.  Best
        effort by construction — the original exception keeps
        propagating whatever happens here."""
        detail = f"{type(exc).__name__}: {exc}"
        try:
            # the wheel is dying on an explicit exception, not a hang:
            # the progress watchdog must not also trip (and abort the
            # process out from under the caller's unwind)
            wd = getattr(self.spcomm, "_watchdog", None)
            if wd is not None:
                wd.stop()
        except Exception:
            pass
        try:
            self.spcomm.emit_run_end(reason, error=detail)
        except Exception:
            pass
        try:
            from mpisppy_tpu.telemetry import flightrec
            bus = getattr(self.spcomm, "telemetry", None)
            for path in flightrec.dump_all(bus, reason=detail):
                if path:
                    global_toc(f"flight recorder: black box written "
                               f"to {path}", True)
        except Exception:
            pass

    # -- preemption signal plumbing ---------------------------------------
    @staticmethod
    def _install_preemption_handlers():
        """SIGTERM/SIGINT -> PreemptionError (raised at the next
        bytecode boundary of the host loop, i.e. between device
        dispatches).  Returns the previous handlers for restoration;
        None when not on the main thread (signal.signal would raise)."""
        import signal
        import threading
        if threading.current_thread() is not threading.main_thread():
            return None

        fired = []

        def _handler(signum, frame):
            # latch: a second SIGTERM/SIGINT (impatient scheduler,
            # double Ctrl-C) must not unwind the emergency save that
            # the FIRST signal triggered — the partial .tmp would never
            # be renamed and the last-gasp snapshot would be lost
            if fired:
                return
            fired.append(signum)
            raise PreemptionError(f"received signal {signum}")

        prev = {}
        for sig in (signal.SIGTERM, signal.SIGINT):
            prev[sig] = signal.signal(sig, _handler)
        return prev

    @staticmethod
    def _restore_preemption_handlers(prev):
        if not prev:
            return
        import signal
        for sig, h in prev.items():
            signal.signal(sig, h)

    # -- results (ref:spin_the_wheel.py:151-222) --------------------------
    @property
    def BestInnerBound(self):
        return self.spcomm.BestInnerBound

    @property
    def BestOuterBound(self):
        return self.spcomm.BestOuterBound

    def write_first_stage_solution(self, solution_file_name: str):
        """npy/csv first-stage (ROOT) solution
        (ref:spin_the_wheel.py:171-195)."""
        nodes = self.spcomm.best_nonants()
        root = nodes[0]
        stage1 = root[np.nonzero(
            self.opt.batch.tree.slot_stage == 1)[0]]
        if solution_file_name.endswith(".npy"):
            np.save(solution_file_name, stage1)
        else:
            with open(solution_file_name, "w") as f:
                for i, v in enumerate(stage1):
                    f.write(f"x{i},{v}\n")

    def write_tree_solution(self, directory_name: str):
        """Per-node nonant values, one file per tree node
        (ref:spin_the_wheel.py:197-222)."""
        import os
        os.makedirs(directory_name, exist_ok=True)
        nodes = self.spcomm.best_nonants()
        tree = self.opt.batch.tree
        for nid in range(tree.num_nodes):
            name = tree.node_name(nid)
            stage = int(np.searchsorted(
                np.cumsum(tree.nodes_per_stage), nid, side="right")) + 1
            slots = np.nonzero(tree.slot_stage == stage)[0]
            with open(os.path.join(directory_name, f"{name}.csv"), "w") as f:
                for i in slots:
                    f.write(f"slot{i},{nodes[nid, i]}\n")
