"""Shared kernel utilities: interpret-mode selection, tiling helpers."""
from __future__ import annotations

import functools

import jax


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return ceil_div(a, b) * b


@functools.cache
def use_interpret() -> bool:
    """Pallas kernels lower natively on TPU and run in interpret mode on
    the CPU backend (tests, examples). There is no switch: a check that must
    prove the chip ran (``chip_smoke.py``) asserts this is False."""
    return jax.default_backend() != "tpu"


def pick_block(n: int, preferred: int) -> int:
    """Largest divisor of n that is <= preferred (keeps grids exact)."""
    b = min(preferred, n)
    while n % b:
        b -= 1
    return b
