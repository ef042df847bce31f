"""Tokens per engine step over the window, from the engine's counters:
(decode + prefill tokens) / steps."""


def read(run):
    c = run.counters
    steps = c.get("steps_total", 0.0)
    if not steps:
        return None
    return (c.get("decode_tokens_total", 0.0)
            + c.get("prefill_tokens_total", 0.0)) / steps
