"""itl_p95_ms: 95th percentile of the gaps between consecutive output
tokens of one request, both returned in the window (host clock; a token is
stamped when the poll that made it returns). Gaps across a preemption or a
restore are included."""
import numpy as np


def read(run):
    gaps = [b - a for r in run.requests
            for a, b in zip(r.stamps, r.stamps[1:]) if b <= run.window_s]
    return float(np.percentile(gaps, 95)) * 1e3 if gaps else None
