"""ttft_p95_ms: 95th percentile over every request due in the window of
the time from its scheduled arrival to the return of the poll that gave its
first token (host clock). A request still without a first token when the
benchmark stopped waiting counts with the time it had waited."""
import numpy as np


def read(run):
    if run.loop != "open" or not run.requests:
        return None
    t = [(r.stamps[0] if r.stamps else run.last_poll_end) - r.sched
         for r in run.requests]
    return float(np.percentile(t, 95)) * 1e3
