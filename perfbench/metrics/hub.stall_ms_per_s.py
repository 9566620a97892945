"""hub.stall_ms_per_s: host stalls per window second, the sum over the
window's hub iterations of their host time (row-to-row wall less the
`scalar_wait` span) above the window's median host time, in ms per s
(program spans in the hub's rows)."""

import statistics

from perfbench import spanmap


def read(ctx):
    its = spanmap.window_iterations(ctx)
    if not its:
        return None
    busy = spanmap.host_busy(its)
    med = statistics.median(busy)
    return 1e3 * sum(max(0.0, b - med) for b in busy) \
        / spanmap.window_seconds(ctx)
