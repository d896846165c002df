"""Plain reference of a dense decoder with grouped-query attention.

The published architecture of Llama-family models (deepseek-coder) and of
Qwen3 (the same block with RMS norms on each head's query and key): token
embedding; per layer a pre-norm attention with rotary positions (the
rotate-half form) and a pre-norm SwiGLU MLP, both residual; a final RMS
norm; the output head (tied to the embedding where the config says so).

Written in straightforward ``jax.numpy`` in float32 with the matrix
products at ``highest`` precision, from the configuration's published keys
alone: no kernel, no cache, no batching across requests. It imports nothing
of the system under test. ``make_weights`` draws the weights from a seed in
the pytree layout the serving engine takes, so that both run the same
numbers; ``w8a8`` runs the same mathematics with every weight product in
int8 (the control that must fail the comparison: one step below the
bfloat16 products that the configurations state).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def dims(c: dict) -> dict:
    """The widths the reference needs, from the published keys."""
    hd = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
    return {"L": c["num_hidden_layers"], "d": c["hidden_size"],
            "hq": c["num_attention_heads"], "hkv": c["num_key_value_heads"],
            "hd": hd, "ff": c["intermediate_size"], "V": c["vocab_size"],
            "eps": c["rms_norm_eps"], "theta": float(c["rope_theta"]),
            "qk_norm": bool(c.get("qk_norm", False)),
            "tied": bool(c["tie_word_embeddings"])}


def weight_shapes(c: dict) -> dict:
    """Shapes of every weight, in the engine's layout (layers stacked on a
    leading axis, matrices stored ``(in, out)``)."""
    m = dims(c)
    L, d, hq, hkv, hd, ff, V = (m[k] for k in
                                ("L", "d", "hq", "hkv", "hd", "ff", "V"))
    block = {"wq": (L, d, hq * hd), "wk": (L, d, hkv * hd),
             "wv": (L, d, hkv * hd), "wo": (L, hq * hd, d)}
    if m["qk_norm"]:
        block.update(q_norm=(L, hd), k_norm=(L, hd))
    tree = {"embed": {"table": (V, d)}, "final_norm": (d,),
            "stages": {"pos0": {
                "norm1": (L, d), "norm2": (L, d), "block": block,
                "mixer": {"w_gate": (L, d, ff), "w_up": (L, d, ff),
                          "w_down": (L, ff, d)}}}}
    if not m["tied"]:
        tree["lm_head"] = {"w": (d, V)}
    return tree


def _fan_in(path: str, shape: tuple) -> int:
    return shape[-1] if path.endswith("table") else shape[-2]


@functools.partial(jax.jit, static_argnums=(0,))
def _make(shapes_items: tuple, key: jax.Array) -> dict:
    out = {}
    keys = jax.random.split(key, len(shapes_items))
    for k, (path, shape) in zip(keys, shapes_items):
        if "norm" in path.rsplit("/", 1)[-1]:
            # gains near 1 but not 1, so that a norm left out shows
            out[path] = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
        else:
            scale = _fan_in(path, shape) ** -0.5
            out[path] = scale * jax.random.normal(k, shape, jnp.float32)
    return out


def flatten(tree: dict, prefix: str = "") -> list:
    items = []
    for k in sorted(tree):
        v = tree[k]
        p = f"{prefix}/{k}" if prefix else k
        items += flatten(v, p) if isinstance(v, dict) else [(p, tuple(v))]
    return items


def _unflatten(flat: dict) -> dict:
    tree: dict = {}
    for path, v in flat.items():
        node = tree
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return tree


def make_weights(c: dict, key: jax.Array) -> dict:
    """Every weight drawn on the device from ``key`` in ONE jitted call:
    matrices ~ N(0, 1/fan_in), norm gains ~ 1 + N(0, 0.01), float32."""
    return _unflatten(_make(tuple(flatten(weight_shapes(c))), key))


def _rms(x, g, eps):
    x32 = x.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * r * g.astype(jnp.float32)).astype(x.dtype)


def _rope(x, pos, theta):
    """Rotate-half rotary embedding; x (S, H, D), pos (S,)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _mm_w8a8(a, b):
    """The product with both sides rounded to int8, symmetric, a scale per
    row of ``a`` (token) and per column of ``b`` (output channel); exact
    integer accumulation, rescaled in float32."""
    sa = jnp.max(jnp.abs(a), axis=-1, keepdims=True) / 127.0
    sb = jnp.max(jnp.abs(b), axis=-2, keepdims=True) / 127.0
    qa = jnp.round(a / jnp.where(sa > 0, sa, 1.0))
    qb = jnp.round(b / jnp.where(sb > 0, sb, 1.0))
    return jnp.matmul(qa, qb, precision=HIGHEST,
                      preferred_element_type=jnp.float32) * sa * sb


def logits_fn(c: dict, w8a8: bool = False):
    """``f(weights, tokens (S,)) -> logits (S, V)`` float32, causal; with
    ``w8a8`` every weight product (projections, MLP, head) in int8."""
    m = dims(c)
    mm = _mm_w8a8 if w8a8 else _mm

    def layer(x, lw):
        S = x.shape[0]
        pos = jnp.arange(S)
        b = lw["block"]
        h = _rms(x, lw["norm1"], m["eps"])
        q = mm(h, b["wq"]).reshape(S, m["hq"], m["hd"])
        k = mm(h, b["wk"]).reshape(S, m["hkv"], m["hd"])
        v = mm(h, b["wv"]).reshape(S, m["hkv"], m["hd"])
        if m["qk_norm"]:
            q = _rms(q, b["q_norm"], m["eps"])
            k = _rms(k, b["k_norm"], m["eps"])
        q, k = _rope(q, pos, m["theta"]), _rope(k, pos, m["theta"])
        g = m["hq"] // m["hkv"]
        k = jnp.repeat(k, g, axis=1)
        v = jnp.repeat(v, g, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST,
                       preferred_element_type=jnp.float32)
        s = s / jnp.sqrt(jnp.float32(m["hd"]))
        s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST,
                       preferred_element_type=jnp.float32)
        x = x + mm(o.reshape(S, -1), b["wo"])
        mx = lw["mixer"]
        h = _rms(x, lw["norm2"], m["eps"])
        a = jax.nn.silu(mm(h, mx["w_gate"]))
        u = mm(h, mx["w_up"])
        x = x + mm(a * u, mx["w_down"])
        return x, None

    def f(w, tokens):
        x = w["embed"]["table"][tokens]
        x, _ = jax.lax.scan(layer, x, w["stages"]["pos0"])
        x = _rms(x, w["final_norm"], m["eps"])
        head = w["embed"]["table"].T if m["tied"] else w["lm_head"]["w"]
        return mm(x, head)

    return f
