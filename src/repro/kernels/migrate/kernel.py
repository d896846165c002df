"""Pallas TPU kernel for live page migration: gather + SECDED re-encode, fused.

A protection *upgrade* (boundary shrinks, SECDED region grows) evicts extra
pages whose storage lived in reclaimed code lanes. The VM's migration engine
relocates them into SECDED frames instead of dropping them — which needs, per
page: (1) the bridge-chip wrap gather of its 8 (row, lane) slices and (2) the
Hsiao code plane for its new SECDED home. Doing these as two passes would
stream each page HBM→VMEM→HBM→VMEM; this kernel fuses them so every slice is
touched once:

  * grid = (n_pages, 8 slices), page ids scalar-prefetched (the same
    paged-attention pattern as ``repro.kernels.interwrap``), slices
    streamed from the Mosaic-legal ``(R·9, 1, W)`` view of
    :func:`repro.kernels.mixed.kernel.pool_views`;
  * the storage BlockSpec index map performs the paper's §4.1.3 translation
    ℓ = 8·slot + k, lane = ℓ mod 9, row = 8·group + ℓ div 9;
  * the code output is computed per slice: with W % 8 == 0 each W-word slice
    covers an exact sub-range of the page's packed code plane (W/2 beats →
    W/8 packed code words), so encode needs no cross-slice state.

Outputs land in migration order — ready for a batched scatter into the
destination pool's rows and code lane.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.layouts import DATA_LANES, GROUP_ROWS, LANES
from repro.kernels.common import use_interpret
from repro.kernels.mixed.kernel import out_shape, pool_views
from repro.kernels.secded.kernel import _encode_lanes


def _coords(page, k, num_rows: int):
    """Bridge-chip translation for slice k of logical `page` (traced scalars)."""
    is_extra = page >= num_rows
    e = page - num_rows
    group = jnp.where(is_extra, e, page // GROUP_ROWS)
    slot = jnp.where(is_extra, GROUP_ROWS, page % GROUP_ROWS)
    linear = 8 * slot + k
    return GROUP_ROWS * group + linear // LANES, linear % LANES


def _gather_encode_kernel(pages_ref, storage_ref, data_ref, codes_ref):
    blk = storage_ref[0]                         # (1, W)
    data_ref[0] = blk
    codes_ref[0] = _encode_lanes(blk)            # (1, W/8) packed


@functools.partial(jax.jit, static_argnames=("num_rows",))
def gather_encode(storage: jax.Array, pages: jax.Array, num_rows: int
                  ) -> tuple[jax.Array, jax.Array]:
    """(R, 9, W) InterWrap pool, (n,) page ids -> (data (n, 8W), codes (n, W)).

    ``codes`` is the packed SECDED plane for each page's future conventional
    row (what ``secded.encode_block`` would produce over ``data``).
    """
    n = pages.shape[0]
    W = storage.shape[2]

    def storage_index(i, k, pages_ref):
        row, lane = _coords(pages_ref[i], k, num_rows)
        return row * LANES + lane, 0, 0

    def out_index(i, k, pages_ref):
        return i * DATA_LANES + k, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n, DATA_LANES),
        in_specs=[pl.BlockSpec((1, 1, W), storage_index)],
        out_specs=[pl.BlockSpec((1, 1, W), out_index),
                   pl.BlockSpec((1, 1, W // 8), out_index)],
    )
    data, codes = pl.pallas_call(
        _gather_encode_kernel,
        grid_spec=grid_spec,
        out_shape=[out_shape((n * DATA_LANES, 1, W), storage, pages),
                   out_shape((n * DATA_LANES, 1, W // 8), storage, pages)],
        interpret=use_interpret(),
    )(pages.astype(jnp.int32), pool_views(storage)[0])
    return data.reshape(n, 8 * W), codes.reshape(n, W)
