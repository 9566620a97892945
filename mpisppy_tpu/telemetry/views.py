###############################################################################
# Back-compat views: the pre-telemetry in-memory trace surfaces
# (`Hub.trace` list of per-iteration dict rows, `Spoke.trace` list of
# (hub_iter, bound) tuples) are now SUBSCRIBERS of the event bus — one
# spine, with the legacy lists as a derived view (ISSUE 3 satellite).
# bench.py and the cylinder tests keep reading the lists unchanged.
# The view also keeps the run's `span` events (`Hub.spans`) and files
# each under the newest iteration row (`row["spans"]`), so a consumer
# holding only the rows still has every span of the run.
###############################################################################
from __future__ import annotations

from mpisppy_tpu.telemetry import events as ev
from mpisppy_tpu.telemetry.sinks import Sink


class WheelTraceView(Sink):
    """Maintains one hub's legacy trace lists from its event stream.

    Run-scoped: events carry the emitting hub's run id, so several
    wheels sharing one bus (or one configured global bus) can never
    cross-pollinate each other's lists."""

    def __init__(self, hub):
        self._hub = hub

    def handle(self, event: ev.Event) -> None:
        hub = self._hub
        if event.run != hub.run_id:
            return
        if event.kind == ev.HUB_ITERATION:
            row = dict(event.data)
            row["t"] = event.t_mono
            # the spans that end after this row, until the next; the
            # first row also holds those before it (build, iter0)
            row["spans"] = [] if hub.trace else list(hub.spans)
            hub.trace.append(row)
        elif event.kind == ev.SPAN:
            d = event.data
            span = (d.get("name"), event.hub_iter, event.t_mono,
                    d.get("dur_s"))
            hub.spans.append(span)
            if hub.trace:
                hub.trace[-1]["spans"].append(span)
        elif event.kind == ev.BOUND_ACCEPT:
            j = event.data.get("spoke")
            if j is not None and 0 <= j < len(hub.spokes):
                hub.spokes[j].trace.append(
                    (event.hub_iter, event.data.get("bound")))
