"""Share, in percent, of the traced window in which the device was idle
while the engine had a phase open (an ``engine.*`` span,
``bench/phases.py``): idle time the host's own work left, without the
client's waits for traffic."""
from bench import phases


def read(run):
    p = phases.of_run(run)
    if p is None or not p.engine_spans or p.window_s <= 0:
        return None
    return 100.0 * p.engine_idle_s / p.window_s
