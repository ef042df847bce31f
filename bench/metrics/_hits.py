"""Prefix-cache hit share from the engine's cache-reuse ledger."""


def hit_share(run, adapter: bool):
    # ledger rows: (req_id, adapter_uid, reused, recomputed, ...)
    rows = [r for r in run.ledger if (r[1] is not None) == adapter]
    total = sum(r[2] + r[3] for r in rows)
    if not total:
        return None
    return sum(r[2] for r in rows) / total
