"""Pallas TPU kernel for the fused mixed-pool page read.

Extends ``repro.kernels.interwrap``'s scalar-prefetch pattern from the pure
InterWrap pool to *any* boundary: the BlockSpec index map performs the
universal coordinate translation of :func:`repro.core.layouts.page_coords`
— SECDED rows, CREAM regular pages under every layout, and reclaimed extra
pages — and the kernel body fuses the Hsiao SECDED check+correct for the
slices that need it, so a mixed batch is one pass over HBM:

  * grid = (n_pages, 8 slices); the page-id vector and a per-page
    ``is_secded`` mask are scalar-prefetched (the paged-attention pattern),
  * the storage BlockSpec fetches slice k of page i straight from its
    physical (row, lane) home — the paper's §4.3 bridge-chip translation
    for mixed layouts as a pure index map, over the Mosaic-legal
    ``(R·9, 1, W)`` view of :func:`pool_views`,
  * a second BlockSpec streams the matching ``W/8``-word sub-range of the
    page's code plane (each W-word slice covers an exact code sub-range,
    as in ``repro.kernels.migrate``); non-SECDED pages fetch a clamped
    dummy block whose decode result is masked off,
  * the VPU decode (popcount syndromes + select-chain action table, shared
    with ``repro.kernels.secded``) corrects in VMEM before write-back — no
    second pass, no host round-trip.

Layout, boundary, and geometry are static (they live in pool metadata), so
each pool mode compiles once and page ids stay fully dynamic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.layouts import (CODE_LANE, DATA_LANES, GROUP_ROWS, LANES,
                                Layout, extra_base_row)
from repro.kernels.common import use_interpret
from repro.kernels.secded.kernel import decode_correct_block


def _coords(page, k, layout: Layout, num_rows: int, boundary: int,
            ebase: int):
    """Universal translation for slice k of `page` (traced scalars).

    Mirrors :func:`repro.core.layouts.page_coords` one (page, k) at a time —
    ``layout``/``boundary``/``ebase`` are static, so the branch structure
    resolves at trace time.
    """
    is_extra = page >= num_rows
    e = page - num_rows
    if layout == Layout.INTERWRAP:
        is_sec = jnp.logical_and(page >= boundary, page < num_rows)
        group = jnp.where(is_extra, e, page // GROUP_ROWS)
        slot = jnp.where(is_extra, GROUP_ROWS, page % GROUP_ROWS)
        linear = 8 * slot + k
        row = jnp.where(is_sec, page, GROUP_ROWS * group + linear // LANES)
        lane = jnp.where(is_sec, k, linear % LANES)
        return row, lane
    row = jnp.where(is_extra, ebase + GROUP_ROWS * e + k, page)
    lane = jnp.where(is_extra, CODE_LANE, k)
    return row, lane


def _route(page, num_rows: int, num_shards: int):
    """Shard-router translation for one traced global page id.

    Mirrors :func:`repro.shard.router.route` one scalar at a time —
    round-robin striping, extras routed by their extra index. Static
    ``num_rows`` (global) and ``num_shards`` resolve at trace time.
    """
    rows_local = num_rows // num_shards
    is_extra = page >= num_rows
    e = page - num_rows
    shard = jnp.where(is_extra, e % num_shards, page % num_shards)
    local = jnp.where(is_extra, rows_local + e // num_shards,
                      page // num_shards)
    return shard, local


def pool_views(storage: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(R, 9, W) pool -> the block views the per-slice kernels stream.

    Mosaic requires a block's last two dims to be (8, 128)-divisible or
    whole, so a ``(1, 1, W)`` block of the ``(R, 9, W)`` array is refused.
    Over ``(R·9, 1, W)`` the same (row, lane) slice is a whole trailing
    ``(1, W)`` block at index ``row·9 + lane``; the code lane gets its own
    ``(R·8, 1, W/8)`` view, where the code words of slice ``k`` of row ``r``
    sit at index ``r·8 + k``. The storage format itself is unchanged.
    """
    R, lanes, W = storage.shape
    return (storage.reshape(R * lanes, 1, W),
            storage[:, CODE_LANE, :].reshape(R * DATA_LANES, 1, W // 8))


def out_shape(shape: tuple[int, ...], *operands) -> jax.ShapeDtypeStruct:
    """uint32 kernel output varying over every mesh axis its operands vary
    over — what ``shard_map``'s type check needs to accept a kernel called
    per shard (the sharded pool's per-bank reads)."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, jnp.uint32, vma=vma)


def _read_correct_kernel(pages_ref, is_sec_ref, storage_ref, codes_ref,
                         out_ref):
    i = pl.program_id(0)
    blk = storage_ref[0]                                  # (1, W)
    fixed = decode_correct_block(blk, codes_ref[0])
    out_ref[0] = jnp.where(is_sec_ref[i] != 0, fixed, blk)


@functools.partial(jax.jit,
                   static_argnames=("layout", "num_rows", "boundary"))
def read_correct(storage: jax.Array, pages: jax.Array, layout: Layout,
                 num_rows: int, boundary: int) -> jax.Array:
    """(R, 9, W) pool, (n,) int32 page ids -> (n, 8W) corrected page data."""
    n = pages.shape[0]
    W = storage.shape[2]
    ebase = extra_base_row(layout, boundary, W)

    def storage_index(i, k, pages_ref, sec_ref):
        row, lane = _coords(pages_ref[i], k, layout, num_rows, boundary,
                            ebase)
        return row * LANES + lane, 0, 0

    def codes_index(i, k, pages_ref, sec_ref):
        # SECDED codes live at (page, CODE_LANE); non-SECDED pages fetch a
        # clamped in-range block that the kernel masks off.
        return jnp.clip(pages_ref[i], 0, num_rows - 1) * DATA_LANES + k, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n, DATA_LANES),
        in_specs=[pl.BlockSpec((1, 1, W), storage_index),
                  pl.BlockSpec((1, 1, W // 8), codes_index)],
        out_specs=pl.BlockSpec((1, 1, W),
                               lambda i, k, p, s: (i * DATA_LANES + k, 0, 0)),
    )
    is_sec = ((pages >= boundary) & (pages < num_rows)).astype(jnp.int32)
    out = pl.pallas_call(
        _read_correct_kernel,
        grid_spec=grid_spec,
        out_shape=out_shape((n * DATA_LANES, 1, W), storage, pages),
        interpret=use_interpret(),
    )(pages.astype(jnp.int32), is_sec, *pool_views(storage))
    return out.reshape(n, DATA_LANES * W)


def _read_routed_kernel(pages_ref, flags_ref, sid_ref, storage_ref,
                        codes_ref, out_ref):
    # flags: 0 = not owned by this shard (zeroed), 1 = owned non-SECDED,
    # 2 = owned SECDED (decode-correct)
    i = pl.program_id(0)
    blk = storage_ref[0]                                  # (1, W)
    fixed = decode_correct_block(blk, codes_ref[0])
    f = flags_ref[i]
    out = jnp.where(f == 2, fixed, blk)
    out_ref[0] = jnp.where(f == 0, jnp.zeros_like(out), out)


@functools.partial(jax.jit,
                   static_argnames=("layout", "num_rows", "boundary",
                                    "num_shards"))
def read_correct_routed(storage: jax.Array, pages: jax.Array, layout: Layout,
                        num_rows: int, boundary: int, num_shards: int,
                        shard_id: jax.Array) -> jax.Array:
    """Router-fused shard-local read: ONE pass from global ids to page data.

    ``storage`` is one shard's ``(R_local, 9, W)`` slice, ``pages`` are
    ``(n,)`` *global* ids, ``num_rows`` / ``boundary`` the *global*
    geometry. The BlockSpec index map composes the shard router's
    global-id -> (shard, local) translation with the universal layout
    translation of :func:`_coords`, so the two-pass
    route-then-read chain collapses into the scalar-prefetch index map —
    no separate translation dispatch, no per-shard full-batch replication.
    Rows not owned by ``shard_id`` (a traced int32 scalar, typically
    ``jax.lax.axis_index``) fetch a clamped dummy block and come back
    zeroed, so a cross-shard ``psum`` assembles the replicated result.
    Returns ``(n, 8W)`` uint32.
    """
    n = pages.shape[0]
    W = storage.shape[2]
    rows_local = num_rows // num_shards
    boundary_local = boundary // num_shards
    ebase = extra_base_row(layout, boundary_local, W)
    pages = pages.astype(jnp.int32)
    sid = jnp.asarray(shard_id, jnp.int32).reshape(1)

    def storage_index(i, k, pages_ref, flags_ref, sid_ref):
        shard, local = _route(pages_ref[i], num_rows, num_shards)
        local = jnp.where(shard == sid_ref[0], local, 0)
        row, lane = _coords(local, k, layout, rows_local, boundary_local,
                            ebase)
        return row * LANES + lane, 0, 0

    def codes_index(i, k, pages_ref, flags_ref, sid_ref):
        shard, local = _route(pages_ref[i], num_rows, num_shards)
        local = jnp.where(shard == sid_ref[0], local, 0)
        return jnp.clip(local, 0, rows_local - 1) * DATA_LANES + k, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n, DATA_LANES),
        in_specs=[pl.BlockSpec((1, 1, W), storage_index),
                  pl.BlockSpec((1, 1, W // 8), codes_index)],
        out_specs=pl.BlockSpec(
            (1, 1, W), lambda i, k, p, f, s: (i * DATA_LANES + k, 0, 0)),
    )
    # region is shard-invariant (global region == local region), so the
    # owned/SECDED flags vectorise outside the grid walk
    shard_v, local_v = _route(pages, num_rows, num_shards)
    owned = shard_v == sid[0]
    is_sec = (local_v >= boundary_local) & (local_v < rows_local)
    flags = jnp.where(owned, jnp.where(is_sec, 2, 1), 0).astype(jnp.int32)
    out = pl.pallas_call(
        _read_routed_kernel,
        grid_spec=grid_spec,
        out_shape=out_shape((n * DATA_LANES, 1, W), storage, pages, sid),
        interpret=use_interpret(),
    )(pages, flags, sid, *pool_views(storage))
    return out.reshape(n, DATA_LANES * W)
