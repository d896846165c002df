"""Operations and bytes that the served work needs, counted from shapes.

These are the yardstick of the roofline and utilisation metrics: what the
algorithm needs for the work done, not what the program happens to move.
A program that pads, or reads blocks it masks away, spends more than this
and reads a lower share; none can read above 100% of its roofline.

Widths come from the configuration's published keys (see
``refs/dense_gqa.dims``); lengths are the live cache lengths of the run.
"""
from __future__ import annotations

F32 = 4


def layer_params(m: dict) -> int:
    """Matrix parameters of one layer (attention + SwiGLU MLP)."""
    d, hq, hkv, hd, ff = m["d"], m["hq"], m["hkv"], m["hd"], m["ff"]
    return d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * ff


def weight_bytes(m: dict) -> int:
    """Bytes of every weight a decode step reads once (float32): the layer
    matrices and the output head (embedding rows read are negligible)."""
    return F32 * (m["L"] * layer_params(m) + m["d"] * m["V"])


def decode_flops(m: dict, ctx: int) -> int:
    """One decode token that attends ``ctx`` positions (itself included):
    2 FLOPs per multiply-add of every matrix and the head, plus QK^T and
    PV over the context."""
    mat = 2 * (m["L"] * layer_params(m) + m["d"] * m["V"])
    return mat + m["L"] * 4 * m["hq"] * m["hd"] * ctx


def prefill_flops(m: dict, n: int) -> int:
    """A prompt of ``n`` tokens: every matrix for each token, causal
    attention over ``n (n + 1) / 2`` pairs, and the head for the last
    position only (the one token the prefill serves)."""
    mat = 2 * n * m["L"] * layer_params(m) + 2 * m["d"] * m["V"]
    return mat + m["L"] * 4 * m["hq"] * m["hd"] * (n * (n + 1) // 2)


def kv_token_bytes(m: dict) -> int:
    """K and V of one token across all layers, float32."""
    return F32 * m["L"] * 2 * m["hkv"] * m["hd"]


def attend_step(m: dict, lens: list[int]) -> tuple[int, int]:
    """(FLOPs, bytes) the model step needs for one decode step over the
    bound sequences with cache lengths ``lens`` (before the step): weights
    read once, each sequence's live K/V read, the new token's K/V
    written."""
    flops = sum(decode_flops(m, n + 1) for n in lens)
    kv = kv_token_bytes(m)
    byts = weight_bytes(m) + sum(n * kv for n in lens) + len(lens) * kv
    return flops, byts


def live_blocks(lens: list[int], block_tokens: int) -> int:
    """Blocks per layer that hold live tokens, after the step's token:
    ``ceil((n + 1) / block_tokens)`` for each length ``n``."""
    return sum(-(-(n + 1) // block_tokens) for n in lens)


def gather_step(page_bytes: int, code_bytes: int, live_pages: int,
                secded_pages: int) -> int:
    """Bytes the page gather needs for one step: each live page's data
    read and written once, plus the code words of the live pages that
    SECDED protects. Padded block-table entries count nothing."""
    return live_pages * 2 * page_bytes + secded_pages * code_bytes
