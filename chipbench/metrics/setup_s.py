"""Set-up: seconds from the start of the process to the window's start
(loading, weights, compilation or compile-cache loads, warm-up)."""


def read(run):
    return run.setup_s
