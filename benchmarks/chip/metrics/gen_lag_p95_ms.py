"""gen_lag_p95_ms: how late the traffic generator submitted each request
after it was due (95th percentile, host clock). The generator runs between
polls, so a long poll shows here before it shows in queueing."""
import numpy as np


def read(run):
    if run.loop != "open" or not run.requests:
        return None
    return float(np.percentile([r.submit - r.sched for r in run.requests],
                               95)) * 1e3
