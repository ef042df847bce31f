"""Device time of one mixed step: the summed device time of the traced
``_mixed_impl`` executions over their count."""


def read(run):
    t = run.trace
    if t is None or not t.step_count:
        return None
    return t.step_s / t.step_count * 1e3
