"""8-bit interleaved parity per 64-byte line — the paper's detection-only code.

Paper §4.2: detection-only regions store an 8-bit parity code per 64B cache
line (bit *i* of the parity byte = XOR of all data bits congruent to *i* mod 8),
detecting one error per bit-lane — "up to eight errors per cache line" — at a
1/64 storage cost, which is what leaves +10.7% of reclaimable capacity.

A line here is 16 consecutive uint32 (64 bytes). The parity byte is the XOR of
the line's 64 bytes, computed by XOR-folding the 16 words to a single byte.
Pure jnp; oracle for ``repro.kernels.parity8``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

WORDS_PER_LINE = 16  # 64 bytes
LINE_OK = 0
LINE_CORRUPT = 1


def _fold_byte(word: jax.Array) -> jax.Array:
    """XOR-fold a uint32 to its byte-wise XOR (one byte)."""
    word = word ^ (word >> 16)
    word = word ^ (word >> 8)
    return word & jnp.uint32(0xFF)


def encode_lines(data: jax.Array) -> jax.Array:
    """Parity bytes for lines of 16 words.

    Args:
      data: uint32 (..., 16k).
    Returns:
      uint32 (..., k) parity bytes.
    """
    if data.shape[-1] % WORDS_PER_LINE:
        raise ValueError(f"last dim must be a multiple of 16, got {data.shape}")
    lines = data.reshape(*data.shape[:-1], data.shape[-1] // WORDS_PER_LINE,
                         WORDS_PER_LINE)
    folded = jax.lax.reduce_xor(lines.astype(jnp.uint32),
                                axes=(lines.ndim - 1,))
    return _fold_byte(folded)


def check_lines(data: jax.Array, parity: jax.Array) -> jax.Array:
    """Per-line status: LINE_OK or LINE_CORRUPT (detection only — no repair).

    Args:
      data:   uint32 (..., 16k).
      parity: uint32 (..., k) stored parity bytes.
    Returns:
      int32 (..., k).
    """
    expected = encode_lines(data)
    return jnp.where(
        (expected ^ (parity.astype(jnp.uint32) & 0xFF)) == 0, LINE_OK, LINE_CORRUPT
    ).astype(jnp.int32)


def encode_lines_packed(data: jax.Array) -> jax.Array:
    """Parity bytes packed 4-per-uint32 (chip-8 storage format).

    (..., 16k) -> (..., k//4); requires k % 4 == 0. A pool row's 2048 data
    words (128 lines) pack to 32 code-lane words — 1/64 of the data, the
    paper's detection-mode overhead.
    """
    from repro.core.secded import pack_codes

    return pack_codes(encode_lines(data))


def check_lines_packed(data: jax.Array, packed_parity: jax.Array) -> jax.Array:
    """Per-line status against packed parity; (..., 16k), (..., k//4) -> (..., k)."""
    from repro.core.secded import unpack_codes

    return check_lines(data, unpack_codes(packed_parity))
