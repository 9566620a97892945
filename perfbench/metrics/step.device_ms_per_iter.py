"""step.device_ms_per_iter: union of the device-op intervals in the
traced window, per traced hub iteration."""


def read(ctx):
    t, n = ctx["trace"], ctx["traced_iterations"]
    if not t or not n:
        return None
    return 1e3 * t["busy_s"] / n
