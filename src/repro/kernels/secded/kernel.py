"""Pallas TPU kernels for Hsiao SECDED(72,64) encode / decode-correct.

TPU mapping (DESIGN.md §2.2): SECDED is pure VPU work — per-beat popcounts
against 8 bit-masks, syndrome matching, and XOR fix-ups. Arithmetic intensity
is low (~30 VPU ops per 8 bytes), so the kernels are strictly memory-bound:
the BlockSpec tiling streams rows HBM→VMEM in large aligned tiles and fuses
encode/correct into a single pass (the paper's "performed entirely in
hardware as part of every memory request").

Three TPU-specific adaptations vs. the reference:
  * the per-parity bit-masks are baked in as scalar literals (VREG splats),
  * the 256-entry syndrome→action table becomes a 72-way compare/select
    chain — per-element gathers don't vectorise on the VPU, whereas a select
    tree is pure element-wise work,
  * words never leave their lane: a 64-bit beat is the word pair
    ``(2b, 2b+1)``, and each word sees its partner through a lane rotation
    (``pltpu.roll``) plus a lane-parity select, so both words of a beat
    compute the same syndrome. Packed code bytes (4 per word, one per beat)
    are moved to and from the data lanes by a log-step rotate/select
    butterfly (:func:`_spread` / :func:`_compress`). Mosaic cannot lower the
    trailing-axis reshapes ``(…, W) -> (…, W/2, 2)`` a gather-based
    formulation needs; rotations and selects are native VPU/XLU work.

Tiling: data rows are (N, D) uint32. Blocks are (BLOCK_ROWS, D): for
BLOCK_ROWS=32 and a pool row D=2048 (8 lanes × 256 words) the working set is
32×8KB data + codes + status ≈ 0.6MB of VMEM — comfortably double-buffered
on a v5e core, with 128-multiple minor dims.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.secded import _COLUMNS, _MASK_HI, _MASK_LO, NUM_CODE_BITS
from repro.kernels.common import pick_block, use_interpret

DEFAULT_BLOCK_ROWS = 32

# Python-int constants — splatted into VREGs at trace time.
MASKS = [(int(_MASK_LO[p]), int(_MASK_HI[p])) for p in range(NUM_CODE_BITS)]
COLUMNS = [int(c) for c in _COLUMNS]


def _encode_beats(lo: jax.Array, hi: jax.Array) -> jax.Array:
    code = jnp.zeros_like(lo)
    for p, (mlo, mhi) in enumerate(MASKS):
        ones = jax.lax.population_count(lo & jnp.uint32(mlo)) + \
            jax.lax.population_count(hi & jnp.uint32(mhi))
        code = code | ((ones & jnp.uint32(1)) << p)
    return code


def _syndrome_action(syn: jax.Array) -> jax.Array:
    """Syndrome -> action via select chain: -1 clean, 0..63 data bit,
    64..71 code bit, -2 detected-uncorrectable."""
    action = jnp.full(syn.shape, -2, jnp.int32)
    action = jnp.where(syn == 0, -1, action)
    for i, col in enumerate(COLUMNS):
        action = jnp.where(syn == jnp.uint32(col), i, action)
    for p in range(NUM_CODE_BITS):
        action = jnp.where(syn == jnp.uint32(1 << p), 64 + p, action)
    return action


# ---------------------------------------------------------------------------
# Lane-level beat helpers (2-D (m, w) uint32 values, beats along the last axis)
# ---------------------------------------------------------------------------


def _lane(shape: tuple[int, ...]) -> jax.Array:
    return jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)


def _rot(x: jax.Array, s: int) -> jax.Array:
    """``out[..., j] = x[..., (j - s) mod w]`` (``jnp.roll`` semantics)."""
    return pltpu.roll(x, s % x.shape[-1], x.ndim - 1)


def _spread(x: jax.Array, f: int, n: int) -> jax.Array:
    """Move lane ``i`` to lane ``f*i`` for every ``i < n`` (others: junk).

    MSB-first butterfly: step ``b`` moves the elements whose index has bit
    ``b`` set forward by ``(f-1)·2^b``. Before step ``b`` element ``i`` sits
    at ``f·(i & ~(2^{b+1}-1)) + (i mod 2^{b+1})``, so its destinations are
    the lanes ``d`` with ``d mod f·2^{b+1}`` in ``[f·2^b, f·2^b + 2^b)`` —
    disjoint from every lane still occupied, hence one select per step.
    """
    lane = _lane(x.shape)
    for b in reversed(range((n - 1).bit_length())):
        r = lane % (f << (b + 1))
        dest = (r >= f << b) & (r < (f << b) + (1 << b))
        x = jnp.where(dest, _rot(x, (f - 1) << b), x)
    return x


def _compress(x: jax.Array, f: int, n: int) -> jax.Array:
    """Move lane ``f*i`` to lane ``i`` for every ``i < n`` — the inverse of
    :func:`_spread`, its steps undone LSB-first."""
    lane = _lane(x.shape)
    for b in range((n - 1).bit_length()):
        r = lane % (f << (b + 1))
        src = (r >= 1 << b) & (r < 2 << b)
        x = jnp.where(src, _rot(x, -((f - 1) << b)), x)
    return x


def _pair(x: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Word ``j`` is the low (even ``j``) or high (odd ``j``) half of beat
    ``j // 2``. Returns every lane's beat ``(lo, hi)`` and the even mask."""
    even = _lane(x.shape) % 2 == 0
    lo = jnp.where(even, x, _rot(x, 1))
    hi = jnp.where(even, _rot(x, -1), x)
    return lo, hi, even


def _byte_shift(shape: tuple[int, ...]) -> jax.Array:
    """Bit offset of lane ``j``'s beat byte in its packed code word."""
    return (8 * ((_lane(shape) // 2) % 4)).astype(jnp.uint32)


def _expand_codes(packed: jax.Array, w: int) -> jax.Array:
    """(m, w//8) packed code words -> (m, w): lane ``j`` holds the code byte
    of beat ``j // 2`` (byte ``(j//2) % 4`` of word ``j // 8``)."""
    m, c = packed.shape
    x = jnp.concatenate([packed, jnp.zeros((m, w - c), packed.dtype)], -1)
    lane = _lane(x.shape)
    x = jnp.where(lane % 8 == 0, _spread(x, 8, c), 0)
    for s in (1, 2, 4):
        x = x | _rot(x, s)
    return (x >> _byte_shift(x.shape)) & jnp.uint32(0xFF)


def _pack_codes(code: jax.Array) -> jax.Array:
    """(m, w) per-lane beat code bytes (equal on both words of a beat) ->
    (m, w//8) packed code words — the inverse of :func:`_expand_codes`."""
    w = code.shape[-1]
    even = _lane(code.shape) % 2 == 0
    x = jnp.where(even, code << _byte_shift(code.shape), 0)
    for s in (1, 2, 4):
        x = x | _rot(x, -s)
    return _compress(x, 8, w // 8)[:, :w // 8]


def _encode_lanes(x: jax.Array) -> jax.Array:
    """(m, w) words -> (m, w//8) packed SECDED codes."""
    lo, hi, _ = _pair(x)
    return _pack_codes(_encode_beats(lo, hi))


def _correct_lanes(x: jax.Array, stored: jax.Array
                   ) -> tuple[jax.Array, jax.Array]:
    """Per-lane Hsiao check+correct: (m, w) words and per-lane stored code
    bytes (:func:`_expand_codes`) -> (words with single-bit data errors
    fixed, per-lane action of :func:`_syndrome_action`)."""
    lo, hi, even = _pair(x)
    syndrome = (_encode_beats(lo, hi) ^ stored) & jnp.uint32(0xFF)
    action = _syndrome_action(syndrome)
    is_data = (action >= 0) & (action < 64)
    bit = jnp.where(action >= 0, action, 0).astype(jnp.uint32)
    mine = (even & (bit < 32)) | (~even & (bit >= 32))
    flip = jnp.where(is_data & mine, jnp.uint32(1) << (bit & 31), 0)
    return x ^ flip, action


def _status(action: jax.Array) -> jax.Array:
    is_data = (action >= 0) & (action < 64)
    return jnp.where(action == -1, 0, jnp.where(
        is_data, 1, jnp.where(action >= 64, 2, 3))).astype(jnp.int32)


def _fix_codes(stored: jax.Array, action: jax.Array) -> jax.Array:
    bit = jnp.where(action >= 64, action - 64, 0).astype(jnp.uint32)
    return stored ^ jnp.where(action >= 64, jnp.uint32(1) << bit, 0)


def decode_correct_block(blk: jax.Array, packed_codes: jax.Array
                         ) -> jax.Array:
    """Fused Hsiao check+correct of an ``(m, w)`` block (VPU-only work).

    Each row's words pair into 64-bit beats; ``packed_codes`` is the
    matching ``(m, w//8)`` packed code plane (one byte per beat, 4 per
    word). Returns the block with single-bit *data* errors corrected in
    place — code-bit and uncorrectable beats pass through unchanged. Shared
    by every kernel that fuses correction into a gather
    (``kernels.mixed``, ``kernels.hash``).
    """
    fixed, _ = _correct_lanes(blk, _expand_codes(packed_codes,
                                                 blk.shape[-1]))
    return fixed


def _encode_kernel(data_ref, codes_ref):
    codes_ref[...] = _encode_lanes(data_ref[...])


def _decode_kernel(data_ref, codes_ref, out_data_ref, out_codes_ref,
                   status_ref):
    data = data_ref[...]
    stored = _expand_codes(codes_ref[...], data.shape[-1])
    fixed, action = _correct_lanes(data, stored)
    out_data_ref[...] = fixed
    out_codes_ref[...] = _pack_codes(_fix_codes(stored, action))
    beats = data.shape[-1] // 2
    status_ref[...] = _compress(_status(action), 2, beats)[:, :beats]


@functools.partial(jax.jit, static_argnames=("block_rows",))
def encode(data: jax.Array, block_rows: int = DEFAULT_BLOCK_ROWS) -> jax.Array:
    """(N, D) uint32 -> (N, D//8) packed SECDED codes."""
    n, d = data.shape
    br = pick_block(n, block_rows)
    return pl.pallas_call(
        _encode_kernel,
        grid=(n // br,),
        in_specs=[pl.BlockSpec((br, d), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, d // 8), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d // 8), jnp.uint32),
        interpret=use_interpret(),
    )(data)


@functools.partial(jax.jit, static_argnames=("block_rows",))
def decode(data: jax.Array, codes: jax.Array,
           block_rows: int = DEFAULT_BLOCK_ROWS
           ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused check+correct. (N,D),(N,D//8) -> (data', codes', status (N,D//2))."""
    n, d = data.shape
    br = pick_block(n, block_rows)
    return pl.pallas_call(
        _decode_kernel,
        grid=(n // br,),
        in_specs=[pl.BlockSpec((br, d), lambda i: (i, 0)),
                  pl.BlockSpec((br, d // 8), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((br, d), lambda i: (i, 0)),
                   pl.BlockSpec((br, d // 8), lambda i: (i, 0)),
                   pl.BlockSpec((br, d // 2), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((n, d), jnp.uint32),
                   jax.ShapeDtypeStruct((n, d // 8), jnp.uint32),
                   jax.ShapeDtypeStruct((n, d // 2), jnp.int32)],
        interpret=use_interpret(),
    )(data, codes)
