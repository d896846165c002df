"""setup_s: process start to the first timed poll (host clock): imports,
engine and pool, weights drawn on the device, compiles or compile-cache
loads, and the warm-up of every shape the cell's traffic uses."""


def read(run):
    return run.setup_s
