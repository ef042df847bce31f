"""The whole mixed step's share, in percent, of the chip's bf16 peak."""
from bench.flops import step_flops


def step_mfu(run):
    t = run.trace
    if t is None or not t.step_count or not run.peak_flops:
        return None
    steps = [s for s in run.profile_steps if s.spans]
    if not steps:
        return None
    need = sum(step_flops(run.model, s.spans, run.rank) for s in steps)
    return 100.0 * need / len(steps) / (t.step_s / t.step_count) \
        / run.peak_flops
