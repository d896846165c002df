"""Fused mixed-pool read: Pallas kernel vs. jnp oracle vs. per-page reads.

Runs in interpret mode on CPU; the kernel must match the oracle bit-exactly
for every layout and boundary, including SECDED correction fused into the
gather.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pool as P
from repro.core.layouts import Layout
from repro.kernels.mixed import kernel, ops, ref

RNG = np.random.default_rng(23)
ROW_WORDS = 64
ALL_LAYOUTS = [Layout.PACKED, Layout.RANK_SUBSET, Layout.INTERWRAP,
               Layout.PARITY]


def _filled_pool(layout, boundary):
    pool = P.make_pool(16, layout, boundary=boundary, row_words=ROW_WORDS)
    for page in range(pool.num_pages):
        pool = P.write_page(pool, page, jnp.asarray(
            RNG.integers(0, 2**32, pool.page_words, dtype=np.uint32)))
    return pool


@pytest.mark.parametrize("layout", ALL_LAYOUTS)
@pytest.mark.parametrize("boundary", [0, 8, 16])
def test_kernel_matches_ref_all_modes(layout, boundary):
    pool = _filled_pool(layout, boundary)
    # 13 ids, as in the block-table cases below: one compile serves both
    ids = jnp.asarray(list(RNG.permutation(pool.num_pages)[:13]), jnp.int32)
    d_ref = ref.read_correct(pool.storage, ids, layout, pool.num_rows,
                             boundary)
    d_ker = kernel.read_correct(pool.storage, ids, layout, pool.num_rows,
                                boundary)
    np.testing.assert_array_equal(np.asarray(d_ref), np.asarray(d_ker))


def _block_table_ids(case, pool, flipped):
    """13 page ids shaped like a decode step's padded block tables: live
    ids, then runs of one padding id (the scratch page), which the kernel
    fetches once and copies. 13 is not a multiple of
    ``kernel.PAGES_PER_STEP``, and every run crosses a grid step.
    ``flipped`` comes right after a run."""
    live = [p for p in RNG.permutation(pool.num_pages) if p != flipped]
    pad = int(live[-1])
    if case == "padding_run":
        ids = [live[0]] + [pad] * 9 + [live[1], live[2], pad]
    elif case == "live_repeats":
        ids = [live[0], live[1], pad, pad, pad, live[2], live[2], pad, pad,
               pad, pad, live[3], pad]
    else:                                    # "flip_after_run"
        ids = [live[0], pad, pad, pad, pad, pad, flipped, live[1], live[1],
               pad, pad, pad, pad]
    assert len(ids) % kernel.PAGES_PER_STEP
    return jnp.asarray(ids, jnp.int32)


@pytest.mark.parametrize("case", ["padding_run", "live_repeats",
                                  "flip_after_run"])
@pytest.mark.parametrize("layout", ALL_LAYOUTS)
@pytest.mark.parametrize("boundary", [0, 8, 16])
def test_kernel_matches_ref_on_block_tables(layout, boundary, case):
    """Repeated ids are copied, not fetched again, and a decoded page
    right after a run is decoded from its own data: bit-exact with the
    oracle, and a planted flip in a SECDED page comes back corrected."""
    pool = _filled_pool(layout, boundary)
    flipped = min(boundary, pool.num_rows - 1)   # SECDED where any row is
    ids = _block_table_ids(case, pool, flipped)
    storage = pool.storage
    clean, _ = P.read_page(pool, flipped)
    if case == "flip_after_run":
        arr = np.asarray(storage).copy()
        arr[flipped, 3, 9] ^= np.uint32(1 << 17)   # its row, data lane 3
        storage = jnp.asarray(arr)
    d_ref = ref.read_correct(storage, ids, layout, pool.num_rows, boundary)
    d_ker = kernel.read_correct(storage, ids, layout, pool.num_rows,
                                boundary)
    np.testing.assert_array_equal(np.asarray(d_ref), np.asarray(d_ker))
    if case == "flip_after_run" and boundary < pool.num_rows:
        np.testing.assert_array_equal(np.asarray(d_ker[6]),
                                      np.asarray(clean))


def test_kernel_matches_page_reads_mixed_ids():
    pool = _filled_pool(Layout.INTERWRAP, 8)
    ids = [0, 7, 8, 15, pool.num_pages - 1]      # CREAM, SECDED, extra
    data = kernel.read_correct(pool.storage, jnp.asarray(ids, jnp.int32),
                               Layout.INTERWRAP, pool.num_rows, 8)
    for j, page in enumerate(ids):
        expect, _ = P.read_page(pool, page)
        np.testing.assert_array_equal(np.asarray(data[j]), np.asarray(expect))


def test_kernel_corrects_secded_flip_in_fused_pass():
    pool = _filled_pool(Layout.INTERWRAP, 8)
    clean, _ = P.read_page(pool, 12)
    arr = np.asarray(pool.storage).copy()
    arr[12, 4, 20] ^= np.uint32(1 << 11)         # data-lane flip, SECDED row
    flipped = dataclasses.replace(pool, storage=jnp.asarray(arr))
    out = kernel.read_correct(flipped.storage, jnp.asarray([12, 0], jnp.int32),
                              Layout.INTERWRAP, pool.num_rows, 8)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(clean))


def test_kernel_leaves_unprotected_pages_raw():
    """A flip in a CREAM page must pass through undisturbed (no protection)."""
    pool = _filled_pool(Layout.INTERWRAP, 8)
    arr = np.asarray(pool.storage).copy()
    arr[1, 1, 0] ^= np.uint32(1)                 # inside the CREAM span
    flipped = jnp.asarray(arr)
    d_ref = ref.read_correct(flipped, jnp.asarray([0, 1, 2], jnp.int32),
                             Layout.INTERWRAP, pool.num_rows, 8)
    d_ker = kernel.read_correct(flipped, jnp.asarray([0, 1, 2], jnp.int32),
                                Layout.INTERWRAP, pool.num_rows, 8)
    np.testing.assert_array_equal(np.asarray(d_ref), np.asarray(d_ker))


def test_ops_dispatch_agrees_with_engine():
    pool = _filled_pool(Layout.PARITY, 8)
    ids = jnp.asarray([0, 9, 15], jnp.int32)
    via_ops = ops.read_pool(pool, ids)                   # auto dispatch
    via_engine = P.read_pages_any(pool, ids)
    np.testing.assert_array_equal(np.asarray(via_ops), np.asarray(via_engine))
