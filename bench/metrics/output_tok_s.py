"""Output tokens that became visible on the host inside the window, of
any request, over the window's length."""


def read(run):
    t0, t1 = run.window
    n = sum(1 for r in run.all_recs for s in r.stamps if t0 <= s < t1)
    return n / (t1 - t0)
