"""Set-up: process start to the window's opening (imports, registry,
traffic, loading and warming every shape), in seconds."""


def read(run):
    return run.window[0] - run.t_start
