"""mfu_pct: the whole step's share of the chip's peak: model FLOPs of
every prompt prefilled and every token decoded in the window (``counts``,
on live lengths) over the host-clock time of the polls that did them (the
window's polls, each an admission pass and a decode step) times the chips'
peak FLOP/s. Time the loop spends waiting for arrivals does not count, so
a faster step reads higher at the same offered load."""


def read(run):
    busy = sum(e - s for s, e in run.polls if s < run.window_s)
    if run.peaks is None or not busy:
        return None
    c, m = run.counts, run.dims
    flops = sum(c.prefill_flops(m, n) for n in run.prefills)
    flops += sum(c.decode_flops(m, n + 1) for s in run.steps
                 for n in s["lens"])
    return 100.0 * flops / (busy * run.chips * run.peaks["flops_per_s"])
