"""host.gc_ms_per_s: the Python collector's pauses during the window's
solves, from the `gc_s` of the hub's rows (a program counter), in ms per
window second."""

from perfbench import spanmap


def read(ctx):
    rows = [r for s in ctx["rec"]["solves"] for r in s["rows"]]
    if not rows or "gc_s" not in rows[-1]:
        return None
    return 1e3 * sum(r.get("gc_s", 0.0) for r in rows) \
        / spanmap.window_seconds(ctx)
