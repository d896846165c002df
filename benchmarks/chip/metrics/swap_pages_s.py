"""swap_pages_s: KV pages preempted to the VM's host swap tier plus pages
restored from it, per second of the window (VM counters)."""


def read(run):
    return (run.swap_pages["out"] + run.swap_pages["in"]) / run.window_s
