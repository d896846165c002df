"""attend_roofline: the model step's share of its roofline (device trace):
for each decode step the larger of its FLOPs over peak FLOP/s and its bytes
over HBM bandwidth (``counts.attend_step``, on live lengths), summed over
the window and divided by the attend program's device time."""


def read(run):
    sec = run.layer_s("attend") if run.trace is not None else 0.0
    if not sec or not run.steps or run.peaks is None:
        return None
    c, pk = run.counts, run.peaks
    least = 0.0
    for s in run.steps:
        flops, byts = c.attend_step(run.dims, s["lens"])
        least += max(flops / pk["flops_per_s"],
                     byts / pk["hbm_bytes_per_s"])
    return 100.0 * least / sec
