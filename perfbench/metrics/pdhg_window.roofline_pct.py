"""pdhg_window.roofline_pct: least time of the traced Pallas window calls
by the benchmark's operations/bytes model and the peaks table
(perfbench/roofline.py), over their summed device time."""

from perfbench import roofline


def read(ctx):
    t = ctx["trace"]
    if not t or not t["kernel_calls"] or t["kernel_s"] <= 0:
        return None
    cfg = ctx["spec"]["config"]
    S = ctx["spec"]["traffic"]["scenarios"]
    n, m = cfg["shape"]["columns"], cfg["shape"]["rows"]
    peaks = roofline.device_peaks(ctx["device"]["kind"])
    least = sum(roofline.least_time(
        roofline.window_call(text, S, n, m, cfg["wheel"]), peaks)[0]
        for text, _ in t["kernel_calls"])
    return 100.0 * least / t["kernel_s"]
