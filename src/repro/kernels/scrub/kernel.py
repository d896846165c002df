"""Pallas TPU kernel: fused scrub sweep (decode + correct + census) in one pass.

A scrub pass over an unfused pipeline costs 3 HBM round-trips (read, decode
status write, corrected write-back). This kernel fuses the whole sweep: one
(BR, 9, W) pool tile in, corrected tile + per-beat status out — the minimum
possible traffic for a repairing scrub (read + write). With the default
BR=16 the VMEM working set is 16 × 9KB × 2 + status ≈ 0.5MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.layouts import CODE_LANE, DATA_LANES
from repro.kernels.common import pick_block, use_interpret
from repro.kernels.secded.kernel import (_compress, _correct_lanes,
                                         _expand_codes, _fix_codes,
                                         _pack_codes, _status)

DEFAULT_BLOCK_ROWS = 16


def _scrub_kernel(storage_ref, out_ref, status_ref):
    # slice k of a row (lane k's W words) owns code words [k·W/8, (k+1)·W/8)
    # of the code lane and beats [k·W/2, (k+1)·W/2) of the status row
    w = storage_ref.shape[2]
    cw, beats = w // DATA_LANES, w // 2
    packed = storage_ref[:, CODE_LANE, :]          # (BR, W)
    codes = []
    for k in range(DATA_LANES):
        stored = _expand_codes(packed[:, k * cw:(k + 1) * cw], w)
        fixed, action = _correct_lanes(storage_ref[:, k, :], stored)
        out_ref[:, k, :] = fixed
        codes.append(_pack_codes(_fix_codes(stored, action)))
        status_ref[:, k * beats:(k + 1) * beats] = _compress(
            _status(action), 2, beats)[:, :beats]
    out_ref[:, CODE_LANE, :] = jnp.concatenate(codes, axis=-1)


@functools.partial(jax.jit, static_argnames=("block_rows",))
def scrub_rows(storage: jax.Array, block_rows: int = DEFAULT_BLOCK_ROWS
               ) -> tuple[jax.Array, jax.Array]:
    """(R, 9, W) SECDED rows -> (corrected storage, per-beat status (R, 4W))."""
    R, lanes, W = storage.shape
    br = pick_block(R, block_rows)
    beats = DATA_LANES * W // 2
    return pl.pallas_call(
        _scrub_kernel,
        grid=(R // br,),
        in_specs=[pl.BlockSpec((br, lanes, W), lambda i: (i, 0, 0))],
        out_specs=[pl.BlockSpec((br, lanes, W), lambda i: (i, 0, 0)),
                   pl.BlockSpec((br, beats), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((R, lanes, W), jnp.uint32),
                   jax.ShapeDtypeStruct((R, beats), jnp.int32)],
        interpret=use_interpret(),
    )(storage)
