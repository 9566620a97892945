"""solve.outside_loop_ms: milliseconds per certified solve in program
spans outside the hub loop: the wheel build, iter0 (dispatch, scalar wait,
its sync) and finalize with its last scalar wait.  The benchmark's own
batch build (`Template.batch`) is no program span and is left out."""

from perfbench import spanmap


def read(ctx):
    done = [spanmap.outside_loop(s) for s in ctx["rec"]["solves"]
            if s["converged"]]
    if not done or None in done:
        return None
    return 1e3 * sum(done) / len(done)
