"""Frames delivered without error inside the window, over its seconds."""


def read(ctx):
    w = ctx["window"]
    return w.delivered_in_window() / w.seconds
