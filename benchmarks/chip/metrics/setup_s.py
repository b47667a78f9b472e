"""Seconds from process start to the window opening: loading, data,
warm-up and, in a run that compiles, compilation."""


def read(r):
    return r.setup_s
