"""hub.host_busy_ms_per_iter: host milliseconds of a hub iteration, the
median over the window's iterations of its row-to-row wall less its
`scalar_wait` span, the loop's one device wait (program spans in the
hub's rows).  The host sets the pace once this nears the device step."""

import statistics

from perfbench import spanmap


def read(ctx):
    its = spanmap.window_iterations(ctx)
    if not its:
        return None
    return 1e3 * statistics.median(spanmap.host_busy(its))
