"""wheel_iters_per_s: hub iterations (PH step plus the fused bound
planes) completed in the window, over the window's length, which runs
until the last of them has finished on the device (host clock)."""


def read(ctx):
    rec = ctx["rec"]
    return ctx["counts"]["iterations"] / (rec["t_w1"] - rec["t_w0"])
