"""setup_s: process start to the first timed iteration (host clock)."""


def read(ctx):
    return ctx["rec"]["setup_s"]
