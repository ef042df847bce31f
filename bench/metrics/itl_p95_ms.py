"""95th percentile over every gap between consecutive output tokens of
every request due in the window."""
from bench.driver import gaps_ms
from bench.metrics._common import percentile


def read(run):
    return percentile((g for r in run.recs for g in gaps_ms(r)), 95)
