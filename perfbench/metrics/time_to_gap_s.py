"""time_to_gap_s: wall seconds per solve that certified the cell's gap
inside the window, from handing its scenarios to the wheel (batch and
iter0 included) to the first certified rel_gap; the solve the window
cut is not counted (host clock)."""


def read(ctx):
    done = [s["seconds"] for s in ctx["rec"]["solves"] if s["converged"]]
    return sum(done) / len(done) if done else None
