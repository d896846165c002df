"""queue_p95_ms: scheduled arrival to the start of the poll that admitted
the request and returned its first token (95th percentile, host clock):
the scheduler's admission wait."""
import math

import numpy as np


def read(run):
    w = [r.first_poll - r.sched for r in run.requests
         if not math.isnan(r.first_poll)]
    if run.loop != "open" or not w:
        return None
    return float(np.percentile(w, 95)) * 1e3
