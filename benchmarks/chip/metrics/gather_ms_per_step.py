"""gather_ms_per_step: device time of the page gather's programs (see
``opnames.json``) per decode step of the window (device trace)."""


def read(run):
    if run.trace is None or not run.steps:
        return None
    return 1e3 * run.layer_s("gather") / len(run.steps)
