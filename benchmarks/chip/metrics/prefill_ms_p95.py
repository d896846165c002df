"""prefill_ms_p95: admission to first token of the requests that open a
session, each session's first request (``ServeRequest.t_first -
t_admit``; 95th percentile, host clock): the poll's prefills up to and
including this one, which ends with the argmax read of its first token.
Nothing to read from an engine without the stamps."""
import numpy as np

import stamps


def read(run):
    opened, t = set(), []
    for r in stamps.stamped(run):
        if r.sid not in opened and r.obj.t_first:
            t.append(r.obj.t_first - r.obj.t_admit)
        opened.add(r.sid)
    return float(np.percentile(t, 95)) * 1e3 if t else None
