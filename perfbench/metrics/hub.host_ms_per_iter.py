"""hub.host_ms_per_iter: device-idle milliseconds inside the hub's
`wheel_sync` host spans (harvest, fold, publish), per span, in the traced
iterations."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["sync_spans"]:
        return None
    return 1e3 * t["sync_idle_s"] / t["sync_spans"]
