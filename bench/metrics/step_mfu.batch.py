"""Share of the chip's bf16 peak the mixed steps reach: the FLOPs the
traced steps needed (``bench/flops.py``) over their device time."""
from bench.metrics._mfu import step_mfu


def read(run):
    return step_mfu(run)
