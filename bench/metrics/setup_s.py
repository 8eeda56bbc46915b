"""Set-up seconds: process start to the window's start (loading, weights,
warm-up, compilation or loading from the compile cache)."""


def read(run):
    return run.setup_s
