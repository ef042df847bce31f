"""Device time, in ms per traced mixed step, under the step's named
scope ``kv_write`` (the K/V pool writes; ``bench/phases.py``)."""
from bench import phases


def read(run):
    return phases.scope_ms(run, "kv_write")
