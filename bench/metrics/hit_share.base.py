"""Share, in percent, of base requests' prompt tokens the prefix cache served, from
the engine's cache-reuse ledger: reused / (reused + recomputed), over
requests due in the window."""
from bench.metrics._hits import hit_share


def read(run):
    v = hit_share(run, adapter=False)
    return None if v is None else 100.0 * v
