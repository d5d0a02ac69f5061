"""Plain float32 Qwen3 decoder and its weights, independent of ``src/``.

Qwen3 as its public ``config.json`` describes it: pre-norm blocks of
grouped-query attention with per-head RMS norm of queries and keys
(qk-norm), rotate-half RoPE, and a SiLU-gated MLP; tied input and output
embeddings. ``hidden(params, cfg, tokens)`` runs the whole sequence, causal,
in float32 at "highest" matmul precision, with no cache and no batching.

With ``bits=(w, a)`` every projection and the output head first pass their
weight and their input through the paper's Eq. 2 min/max quantizer, per
tensor, dequantized: the arithmetic of a ``<W:I>`` deployment, in float.
The benchmark uses it at a lower precision than the one served, as the
control that the comparison must fail.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def init(key, cfg: dict) -> dict:
    """Weights in the served type (matrices bfloat16, norm scales float32),
    in the engine's tree layout: one scan-stacked block. Call under jit."""
    d, f, v, L = cfg["hidden_size"], cfg["intermediate_size"], \
        cfg["vocab_size"], cfg["num_hidden_layers"]
    hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    ks = iter(jax.random.split(key, 16))

    def mat(shape, fan_in):
        return (jax.random.normal(next(ks), shape) * fan_in ** -0.5
                ).astype(jnp.bfloat16)

    def scale(shape):
        return jax.random.uniform(next(ks), shape, minval=0.8, maxval=1.2)

    block = {
        "norm1": {"scale": scale((L, d))},
        "attn": {"wq": mat((L, d, hq * hd), d), "wk": mat((L, d, hkv * hd), d),
                 "wv": mat((L, d, hkv * hd), d),
                 "wo": mat((L, hq * hd, d), hq * hd),
                 "q_norm": scale((L, hd)), "k_norm": scale((L, hd))},
        "norm2": {"scale": scale((L, d))},
        "ffn": {"w_in": mat((L, d, f), d), "w_gate": mat((L, d, f), d),
                "w_out": mat((L, f, d), f)},
    }
    return {"embed": mat((v, d), d), "scan": [block], "rest": [],
            "final_norm": {"scale": scale((d,))}}


def fake_quant(x, bits: int):
    """Eq. 2 per tensor, back to float."""
    lo, hi = jnp.min(x), jnp.max(x)
    s = jnp.maximum(hi - lo, jnp.finfo(jnp.float32).tiny) / (2**bits - 1)
    return jnp.clip(jnp.round((x - lo) / s), 0, 2**bits - 1) * s + lo


def linear(x, w, bits=None):
    w = w.astype(jnp.float32)
    if bits is not None:
        w, x = fake_quant(w, bits[0]), fake_quant(x, bits[1])
    return jnp.dot(x, w, precision=HIGHEST)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x (S, H, D): rotate-half RoPE at positions 0..S-1."""
    s, _, d = x.shape
    half = d // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def hidden(params, cfg: dict, tokens, bits=None):
    """Final-norm hidden states (S, d) of ``tokens`` (S,), float32."""
    hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    s = tokens.shape[0]
    x = params["embed"][tokens].astype(jnp.float32)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def block(x, p):
        a, m = p["attn"], p["ffn"]
        h = rmsnorm(x, p["norm1"]["scale"], eps)
        q = linear(h, a["wq"], bits).reshape(s, hq, hd)
        k = linear(h, a["wk"], bits).reshape(s, hkv, hd)
        v = linear(h, a["wv"], bits).reshape(s, hkv, hd)
        q = rope(rmsnorm(q, a["q_norm"], eps), theta)
        k = rope(rmsnorm(k, a["k_norm"], eps), theta)
        k = jnp.repeat(k, hq // hkv, axis=1)
        v = jnp.repeat(v, hq // hkv, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) * hd ** -0.5
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", pr, v, precision=HIGHEST)
        x = x + linear(o.reshape(s, hq * hd), a["wo"], bits)
        h = rmsnorm(x, p["norm2"]["scale"], eps)
        g = jax.nn.silu(linear(h, m["w_gate"], bits)) * linear(h, m["w_in"],
                                                               bits)
        return x + linear(g, m["w_out"], bits), None

    x, _ = jax.lax.scan(block, x, params["scan"][0])
    return rmsnorm(x, params["final_norm"]["scale"], eps)


def logits(params, h, bits=None):
    """Tied output head: (P, d) hidden states -> (P, vocab) logits."""
    return linear(h, params["embed"].T, bits)
