"""Device time, in ms per traced mixed step, under the step's named
scope ``attention`` (the ragged paged attention; ``bench/phases.py``)."""
from bench import phases


def read(run):
    return phases.scope_ms(run, "attention")
