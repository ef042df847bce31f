"""Engine steps the client drove inside the window (a fixture metric)."""


def read(run):
    return len(run.steps)
