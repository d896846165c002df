"""first_token_hold_p95_ms: how long a first token waits inside the
engine's poll after it is on the host (``ServeRequest.t_first``) until
the poll returns it (the benchmark's stamp, put on the engine's clock by
``stamps.window_start``; 95th percentile, host clock). For a new session
that is the decode step the poll runs after the prefill. Nothing to read
from an engine without the stamps."""
import numpy as np

import stamps


def read(run):
    reqs = stamps.stamped(run)
    if not reqs:
        return None
    t0 = stamps.window_start(reqs)
    t = [t0 + r.stamps[0] - r.obj.t_first for r in reqs
         if r.stamps and r.obj.t_first]
    return float(np.percentile(t, 95)) * 1e3 if t else None
