"""pdhg_window.step_share_pct: device time of the Pallas window kernel
over the device's busy time, in the traced window."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["kernel_calls"] or t["busy_s"] <= 0:
        return None
    return 100.0 * t["kernel_s"] / t["busy_s"]
