"""tok_s: output tokens returned in the window, each prefill's first token
included, per second of the window (host clock)."""


def read(run):
    n = sum(1 for r in run.requests for t in r.stamps if t <= run.window_s)
    return n / run.window_s
