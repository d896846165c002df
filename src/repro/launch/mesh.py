"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — required because the dry-run must set
``xla_force_host_platform_device_count`` *before* first jax init.

Mesh axes:
  * single pod: (data=16, model=16) — 256 chips (one v5e pod slice)
  * multi-pod:  (pod=2, data=16, model=16) — 512 chips; the 'pod' axis is
    pure data parallelism across pods (gradient all-reduce crosses DCN).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto(n: int) -> tuple[AxisType, ...]:
    """Auto axis types: ``jax.make_mesh`` now defaults to Explicit, which
    puts shardings into array types and rejects the plain gathers and
    scatters of the data plane (``x[inv]``, ``.at[inv].set``)."""
    return (AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_host_mesh(model_axis: int = 1):
    """A mesh over whatever devices exist (tests / single host)."""
    n = len(jax.devices())
    assert n % model_axis == 0
    return jax.make_mesh((n // model_axis, model_axis), ("data", "model"),
                         axis_types=_auto(2))


def make_banks_mesh(num_banks: int):
    """1-D ``banks`` mesh for the sharded CREAM data plane (CREAM-Shard).

    Uses the first ``num_banks`` devices. On CPU, export
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (before first jax
    init) to expose N virtual devices — CI and the repo conftest do.
    """
    devices = jax.devices()
    if len(devices) < num_banks:
        raise ValueError(
            f"need {num_banks} devices for a {num_banks}-bank mesh, have "
            f"{len(devices)}; on CPU set "
            "XLA_FLAGS=--xla_force_host_platform_device_count")
    return jax.make_mesh((num_banks,), ("banks",),
                         devices=devices[:num_banks], axis_types=_auto(1))


# TPU v5e hardware constants (roofline denominators; see EXPERIMENTS.md)
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW_PER_LINK = 50e9            # bytes/s per link (~ per-axis effective)
