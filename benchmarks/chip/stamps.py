"""The engine's own request stamps beside the benchmark's.

``ServeRequest.t_submit``, ``t_admit`` and ``t_first`` are the engine's
``time.perf_counter()`` readings, 0 until set; the benchmark's stamps
(``Req.submit``, ``Req.stamps``) count from the window's start. The
benchmark stamps ``submit`` right after ``Engine.submit`` returns, and the
engine stamps ``t_submit`` inside it, so the window's start on the
engine's clock is at least ``t_submit - submit`` for every request: the
largest of these lies within one ``submit`` call of it.
"""


def stamped(run) -> list:
    """The window's requests that carry the engine's stamps: none from an
    engine without them, and the metrics that read them are left out."""
    return [r for r in run.requests
            if getattr(r.obj, "t_admit", None) is not None]


def window_start(reqs) -> float:
    """The window's start on the engine's clock (see above)."""
    return max(r.obj.t_submit - r.submit for r in reqs)
