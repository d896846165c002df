"""Pallas TPU kernel: SECDED decode-on-load fused into a matmul (beyond-paper).

The paper's SECDED check rides along with every DRAM burst for free in
hardware. In software, protecting weights with a *separate* decode pass
doubles their HBM traffic (read for decode + read for use). This kernel
restores the paper's economics on TPU: the A operand is fetched HBM→VMEM
once per (i, k) tile, corrected in-register on the VPU, bitcast to bf16 and
fed straight to the MXU — so serving with SECDED-protected weights costs
only the +12.5% code-lane bytes, not 2× weight traffic.

Grid (M/BM, N/BN, K/BK), K minor (sequential on TPU): the f32 accumulator
lives in the revisited output block; `pl.when(k == 0)` zero-init. Default
tiles (256, 256, 512): VMEM = A bits 256×256×4 + codes + B 512×256×2 +
out 256×256×4 ≈ 0.8MB; MXU dims all 128-multiples.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import pick_block, use_interpret
from repro.kernels.secded.kernel import decode_correct_block

DEFAULT_BM, DEFAULT_BN, DEFAULT_BK = 256, 256, 512


def _decode_tile(bits: jax.Array, packed_codes: jax.Array) -> jax.Array:
    """(BM, BK/2) uint32 + (BM, BK/16) codes -> corrected bf16 (BM, BK)."""
    bm, kw = bits.shape
    fixed = decode_correct_block(bits, packed_codes)
    halves = jax.lax.bitcast_convert_type(fixed, jnp.bfloat16)  # (BM, kw, 2)
    return halves.reshape(bm, kw * 2)


def _ecc_matmul_kernel(a_bits_ref, a_codes_ref, b_ref, o_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = _decode_tile(a_bits_ref[...], a_codes_ref[...])
    o_ref[...] += jnp.dot(a, b_ref[...],
                          preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk"))
def ecc_matmul(a_bits: jax.Array, a_codes: jax.Array, b: jax.Array,
               bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
               bk: int = DEFAULT_BK) -> jax.Array:
    """Corrected-A matmul: (M,K) bf16 A (as bits+codes) @ (K,N) bf16 -> f32."""
    m, kw = a_bits.shape
    k2, n = b.shape
    if k2 != kw * 2:
        raise ValueError(f"K mismatch: bits {a_bits.shape} vs b {b.shape}")
    bm = pick_block(m, bm)
    bn = pick_block(n, bn)
    bk = pick_block(k2, bk)
    grid = (m // bm, n // bn, k2 // bk)
    return pl.pallas_call(
        _ecc_matmul_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk // 2), lambda i, j, k: (i, k)),
            pl.BlockSpec((bm, bk // 16), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=use_interpret(),
    )(a_bits, a_codes, b)
