"""Device time, in ms per traced mixed step, under the step's named
scope ``lora``: the grouped aLoRA delta of Q, K and V
(``bench/phases.py``)."""
from bench import phases


def read(run):
    return phases.scope_ms(run, "lora")
