"""Pallas TPU kernels for the fused mixed-pool page read.

:func:`read_correct` gathers whole pages for *any* boundary: the universal
coordinate translation of :func:`repro.core.layouts.page_coords` — SECDED
rows, CREAM regular pages under every layout, and reclaimed extra pages —
runs on the scalar core, and the Hsiao SECDED check+correct is fused for
the pages that need it, so a mixed batch is one pass over HBM:

  * the ``(R, 9, W)`` pool is read through its ``(9, R, W)`` plane view.
    The pool lies plane-major on the device (``{2,0,1:T(8,128)}``), so the
    view is a bitcast and the pool stays in HBM (``pl.ANY``), uncopied,
  * the page ids are scalar-prefetched; one grid step handles
    ``PAGES_PER_STEP`` whole pages, its output block ``(PAGES_PER_STEP,
    8W)`` rows of the ``(n, 8W)`` result,
  * slice k of a page (its physical ``(row, lane)`` home, the paper's §4.3
    bridge-chip translation) is fetched by DMA as the aligned 8-row tile
    of plane ``lane`` — Mosaic refuses row slices that are not 8-aligned —
    and its row is picked in VMEM. The next page's DMAs are in flight
    while a page is copied out,
  * an id equal to the one before it (the padded block-table tail, all
    the scratch page) is not fetched again: its row is copied,
  * only SECDED pages are decoded: their ``(8, W)`` data with the
    ``(8, W/8)`` codes of the code plane's row, fetched the same way
    (popcount syndromes + select-chain action table, shared with
    ``repro.kernels.secded``); every other page passes through.

:func:`read_correct_routed` (the sharded pool's router-fused read) keeps
the per-slice form: grid ``(n_pages, 8 slices)`` whose BlockSpec index maps
fetch each slice from the ``(R·9, 1, W)`` view of :func:`pool_views`.

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
from repro.kernels.common import round_up, use_interpret
from repro.kernels.secded.kernel import decode_correct_block

#: Pages one grid step of :func:`read_correct` gathers (its output block).
#: 8, 16 and 32 gather a decode step's block tables in the same time on a
#: TPU v5e; 8 keeps the block at 512 KiB for 2048-word lanes.
PAGES_PER_STEP = 8


def _select(cond, a, b):
    """Scalar ``where`` as one ``lax.select``: ``jnp.where`` (like ``//`` and
    ``%``) traces a nested jit, and kernels that translate many slices per
    grid step pay for each one in trace and lowering time."""
    return jax.lax.select(cond, jnp.int32(a), jnp.int32(b))


def _coords(page, k, layout: Layout, num_rows: int, boundary: int,
            ebase: int):
    """Universal translation for slice k of `page` (traced int32 scalar,
    non-negative).

    Mirrors :func:`repro.core.layouts.page_coords` one (page, k) at a time —
    ``layout``/``boundary``/``ebase`` are static, so the branch structure
    resolves at trace time.
    """
    return _slices(page, (k,), layout, num_rows, boundary, ebase)[0]


def _slices(page, ks, layout: Layout, num_rows: int, boundary: int,
            ebase: int) -> list:
    """:func:`_coords` of the slices ``ks`` of one page, the page's own
    terms computed once. Non-negative operands let ``lax.div`` /
    ``lax.rem`` stand in for floor division and modulo."""
    is_extra = page >= num_rows
    e = page - num_rows
    if layout == Layout.INTERWRAP:
        is_sec = (page >= boundary) & (page < num_rows)
        group = _select(is_extra, e, jax.lax.div(page, GROUP_ROWS))
        slot = _select(is_extra, GROUP_ROWS, jax.lax.rem(page, GROUP_ROWS))
        first = 8 * slot
        out = []
        for k in ks:
            linear = first + k
            out.append((_select(is_sec, page, GROUP_ROWS * group
                                + jax.lax.div(linear, LANES)),
                        _select(is_sec, k, jax.lax.rem(linear, LANES))))
        return out
    extra_row = ebase + GROUP_ROWS * e
    return [(_select(is_extra, extra_row + k, page),
             _select(is_extra, CODE_LANE, k)) for k in ks]


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


def _read_pages_kernel(pages_ref, planes_ref, out_ref, buf, cbuf, picks,
                       page_blk, code_blk, sems, *, n: int, group: int,
                       layout: Layout, num_rows: int, boundary: int,
                       ebase: int):
    """One grid step reads ``group`` whole pages (gather order ``j``).

    Slice k of a page is fetched as the aligned 8-row tile of its plane
    into ``buf[slot, k]``, and its row in the tile (``picks[slot, k]``) is
    picked in VMEM; pages alternate between two buffer slots, so page
    ``j + 1``'s DMAs are in flight while page ``j`` is copied out (across
    grid steps too: the grid runs in order). ``page_blk`` keeps the last
    fetched page's ``(8, W)`` data, so an id equal to the one before it is
    not fetched again.
    """
    W = page_blk.shape[1]
    cw = W // 8

    def is_sec(page):
        return (page >= boundary) & (page < num_rows)

    def fetched(j):
        return (j == 0) | (pages_ref[j] != pages_ref[jax.lax.max(j - 1, 0)])

    def tile(plane, start):
        start = pl.multiple_of(start, GROUP_ROWS)
        return planes_ref.at[plane, pl.ds(start, GROUP_ROWS), :]

    def data_copy(slot, k, lane=0, start=0):
        return pltpu.make_async_copy(tile(lane, start), buf.at[slot, k],
                                     sems.at[slot, k])

    def code_copy(slot, start=0):
        return pltpu.make_async_copy(tile(CODE_LANE, start), cbuf.at[slot],
                                     sems.at[slot, CODE_LANE])

    def start(j, slot):
        page = pages_ref[j]

        @pl.when(fetched(j))
        def _():
            for k, (row, lane) in enumerate(_slices(
                    page, range(DATA_LANES), layout, num_rows, boundary,
                    ebase)):
                pick = jax.lax.rem(row, GROUP_ROWS)
                picks[slot, k] = pick
                data_copy(slot, k, lane, row - pick).start()

            @pl.when(is_sec(page))
            def _():
                code_copy(slot, page - jax.lax.rem(page, GROUP_ROWS)).start()

    step = pl.program_id(0)

    @pl.when(step == 0)
    def _():
        start(0, 0)

    def page_step(g, carry):
        j = step * group + g
        slot = jax.lax.rem(j, 2)

        @pl.when(j + 1 < n)
        def _():
            start(j + 1, 1 - slot)

        @pl.when(fetched(j))
        def _():
            # a wait needs only the copy's destination and semaphore
            page = pages_ref[j]
            for k in range(DATA_LANES):
                data_copy(slot, k).wait()
            for k in range(DATA_LANES):
                page_blk[pl.ds(k, 1), :] = buf[slot, k,
                                               pl.ds(picks[slot, k], 1), :]

            @pl.when(is_sec(page))
            def _():
                # SECDED codes of slice k: words [k·W/8, (k+1)·W/8) of the
                # page's code-plane row
                code_copy(slot).wait()
                for k in range(DATA_LANES):
                    code_blk[pl.ds(k, 1), :] = cbuf[
                        slot, pl.ds(jax.lax.rem(page, GROUP_ROWS), 1),
                        pl.ds(k * cw, cw)]
                page_blk[...] = decode_correct_block(page_blk[...],
                                                     code_blk[...])

        for k in range(DATA_LANES):
            out_ref[pl.ds(g, 1), pl.ds(k * W, W)] = page_blk[pl.ds(k, 1), :]
        return carry

    jax.lax.fori_loop(0, group, page_step, 0)


@functools.partial(jax.jit,
                   static_argnames=("layout", "num_rows", "boundary"))
def read_correct(storage: jax.Array, pages: jax.Array, layout: Layout,
                 num_rows: int, boundary: int) -> jax.Array:
    """(R, 9, W) pool, (n,) int32 page ids -> (n, 8W) corrected page data."""
    n = pages.shape[0]
    R, _, W = storage.shape
    assert R % GROUP_ROWS == 0, R
    ebase = extra_base_row(layout, boundary, W)
    pages = pages.astype(jnp.int32)
    n_pad = round_up(n, PAGES_PER_STEP)
    if n_pad != n:
        # repeats of the last id: never fetched, sliced off below
        pages = jnp.concatenate(
            [pages, jnp.broadcast_to(pages[-1:], (n_pad - n,))])
    # the pool's device layout is plane-major ({2,0,1:T(8,128)}), so this
    # transpose is a bitcast: nine (R, W) planes, tiled (8, 128)
    planes = jnp.transpose(storage, (1, 0, 2))
    kernel = functools.partial(
        _read_pages_kernel, n=n_pad, group=PAGES_PER_STEP, layout=layout,
        num_rows=num_rows, boundary=boundary, ebase=ebase)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_pad // PAGES_PER_STEP,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((PAGES_PER_STEP, DATA_LANES * W),
                               lambda i, p: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, DATA_LANES, GROUP_ROWS, W), jnp.uint32),
            pltpu.VMEM((2, GROUP_ROWS, W), jnp.uint32),
            pltpu.SMEM((2, DATA_LANES), jnp.int32),
            pltpu.VMEM((DATA_LANES, W), jnp.uint32),
            pltpu.VMEM((DATA_LANES, W // 8), jnp.uint32),
            pltpu.SemaphoreType.DMA((2, LANES)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape((n_pad, DATA_LANES * W), storage, pages),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=use_interpret(),
    )(pages, planes)
    return out if n_pad == n else out[:n]


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
