"""Pallas TPU kernel fusing batched hash probing with the mixed-pool gather.

The objcache get path as one pass over HBM: instead of resolving keys to
pages on the host (or in a separate device dispatch) and then gathering,
the *BlockSpec index map itself runs the probe* — the scalar-prefetched
slot-key and slot-page arrays are scanned with the canonical bounded linear
probe of :mod:`repro.objcache.hash_index`, and the winning page id feeds the
same universal coordinate translation the ``mixed`` kernel uses. The kernel
body re-runs the (cheap, SMEM-resident) probe to recover the per-query
``is_secded`` bit and fuses the Hsiao SECDED check+correct exactly as
:mod:`repro.kernels.mixed` does:

  * grid = (n_queries, 8 slices); scalar-prefetch: query keys, slot keys,
    slot pages (the paged-attention pattern, with the page table replaced by
    a probed hash table),
  * the storage BlockSpec fetches slice k of the *matched* page straight
    from its physical (row, lane) home — probe and gather fused,
  * the codes BlockSpec streams the matching ``W/8``-word code sub-range;
    non-SECDED and unmatched pages fetch a clamped dummy block that the
    body masks off,
  * unmatched queries resolve to page 0 (callers mask rows on their own
    found bit; the jnp oracle agrees bit-for-bit on those rows).

Geometry, layout, boundary, and the probe window are static; keys and the
index contents stay fully dynamic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.layouts import DATA_LANES, LANES, Layout, extra_base_row
from repro.kernels.common import use_interpret
from repro.kernels.mixed.kernel import _coords, out_shape, pool_views
from repro.kernels.secded.kernel import decode_correct_block
from repro.objcache.hash_index import hash_u32


def _probe_page(q, keys_ref, pages_ref, capacity: int, probe: int):
    """Scalar probe of the prefetched index -> (page, found) traced scalars.

    Mirrors :func:`repro.objcache.hash_index.find` one query at a time —
    ``capacity``/``probe`` are static, so the window unrolls at trace time
    into ``probe`` SMEM loads.
    """
    qk = q.astype(jnp.uint32)
    h = (hash_u32(qk) % jnp.uint32(capacity)).astype(jnp.int32)
    slot = jnp.int32(capacity)
    for r in range(probe):
        s = (h + r) % capacity
        hit = (slot == capacity) & (keys_ref[s] == qk)
        slot = jnp.where(hit, s, slot)
    found = slot < capacity
    page = jnp.where(found, pages_ref[jnp.minimum(slot, capacity - 1)], 0)
    return page.astype(jnp.int32), found


def _make_body(capacity: int, probe: int, num_rows: int, boundary: int):
    def body(q_ref, keys_ref, pages_ref, storage_ref, codes_ref, out_ref):
        i = pl.program_id(0)
        page, _ = _probe_page(q_ref[i], keys_ref, pages_ref, capacity, probe)
        is_sec = (page >= boundary) & (page < num_rows)
        blk = storage_ref[0]                              # (1, W)
        fixed = decode_correct_block(blk, codes_ref[0])
        out_ref[0] = jnp.where(is_sec, fixed, blk)
    return body


@functools.partial(jax.jit, static_argnames=("layout", "num_rows",
                                             "boundary", "probe"))
def lookup_read(storage: jax.Array, slot_keys: jax.Array,
                slot_pages: jax.Array, queries: jax.Array, layout: Layout,
                num_rows: int, boundary: int, probe: int) -> jax.Array:
    """(R, 9, W) pool + (C,) index arrays + (n,) keys -> (n, 8W) page data."""
    n = queries.shape[0]
    capacity = slot_keys.shape[0]
    w = storage.shape[2]
    ebase = extra_base_row(layout, boundary, w)

    def storage_index(i, k, q_ref, keys_ref, pages_ref):
        page, _ = _probe_page(q_ref[i], keys_ref, pages_ref, capacity, probe)
        row, lane = _coords(page, k, layout, num_rows, boundary, ebase)
        return row * LANES + lane, 0, 0

    def codes_index(i, k, q_ref, keys_ref, pages_ref):
        page, _ = _probe_page(q_ref[i], keys_ref, pages_ref, capacity, probe)
        return jnp.clip(page, 0, num_rows - 1) * DATA_LANES + k, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n, DATA_LANES),
        in_specs=[pl.BlockSpec((1, 1, w), storage_index),
                  pl.BlockSpec((1, 1, w // 8), codes_index)],
        out_specs=pl.BlockSpec(
            (1, 1, w), lambda i, k, q, ks, ps: (i * DATA_LANES + k, 0, 0)),
    )
    out = pl.pallas_call(
        _make_body(capacity, probe, num_rows, boundary),
        grid_spec=grid_spec,
        out_shape=out_shape((n * DATA_LANES, 1, w), storage, slot_keys,
                            slot_pages, queries),
        interpret=use_interpret(),
    )(queries.astype(jnp.uint32), slot_keys.astype(jnp.uint32),
      slot_pages.astype(jnp.int32), *pool_views(storage))
    return out.reshape(n, DATA_LANES * w)
