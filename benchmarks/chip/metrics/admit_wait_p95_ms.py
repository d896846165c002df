"""admit_wait_p95_ms: the scheduler's admission wait, from the engine's
own stamps: ``submit`` to the tick that bound the request to a slot
(``ServeRequest.t_admit - t_submit``; 95th percentile, host clock). A
request never bound counts with its wait to the end of the last poll.
Nothing to read from an engine without the stamps."""
import numpy as np

import stamps


def read(run):
    reqs = stamps.stamped(run)
    if not reqs:
        return None
    end = stamps.window_start(reqs) + run.last_poll_end
    return float(np.percentile([(r.obj.t_admit or end) - r.obj.t_submit
                                for r in reqs], 95)) * 1e3
