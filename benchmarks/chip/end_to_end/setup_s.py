"""Seconds from process start to the window's first submit: JAX start-up,
compile or compile-cache load, the frame pool, service start, warm-up and
priming."""


def read(ctx):
    return ctx["setup_s"]
