"""Set-up: process start to the window's start (weights, warm-up of
every program and prompt length, caches, the traffic's own warm-up)."""


def read(run):
    return run.setup_s
