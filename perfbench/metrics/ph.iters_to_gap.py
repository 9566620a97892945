"""ph.iters_to_gap: hub iterations per solve that certified the gap
(the hub's own trace rows; a program counter)."""


def read(ctx):
    done = [s["iterations"] for s in ctx["rec"]["solves"] if s["converged"]]
    return sum(done) / len(done) if done else None
