"""batch_occupancy_pct: mean over the window's decode steps of the slots
bound to a sequence, over the engine's decode slots (scheduler counters)."""


def read(run):
    if not run.steps:
        return None
    bound = sum(len(s["lens"]) for s in run.steps)
    return 100.0 * bound / (len(run.steps) * run.max_batch)
