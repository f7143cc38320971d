"""Plain reference of a dense decoder (OLMo-1B): weights and forward pass.

Written from the OLMo paper (arXiv:2402.00838) and its published config,
not from the program: non-parametric LayerNorm before attention and before
the MLP, rotary embeddings on the two halves of each head (theta 10000),
causal multi-head attention, SwiGLU MLP, tied input and output embedding.
Each layer is one jitted call over a block of whole sequences, so the
forward fits beside nothing else on the chip. Matrix products run at
``precision`` (HIGHEST for the reference, DEFAULT in bfloat16 for the
control).

The weights are laid out as the program's ``decode_step`` reads them
(stacked on a leading layer axis, embedding rows rounded up to a multiple
of 128); the reference reads the same arrays.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

VOCAB_ROWS_MULTIPLE = 128


def embed_rows(vocab: int) -> int:
    m = VOCAB_ROWS_MULTIPLE
    return (vocab + m - 1) // m * m


def init(m: dict, key):
    """Seeded weights: embedding N(0, 0.02), projections truncated normal
    with std 1/sqrt(fan-in). Call under ``jax.jit``."""
    L, D = m.num_layers, m.d_model
    H, Hkv, Dh, F = m.num_heads, m.num_kv_heads, m.head_dim, m.d_ff
    ks = jax.random.split(key, 8)

    def proj(k, shape, fan_in):
        return jax.random.truncated_normal(k, -2.0, 2.0, shape,
                                           jnp.float32) / fan_in ** 0.5

    return {
        "embed": {"tok": 0.02 * jax.random.normal(
            ks[0], (embed_rows(m.vocab_size), D), jnp.float32)},
        "final_norm": {},
        "blocks": {
            "ln1": {}, "ln2": {},
            "attn": {
                "wq": proj(ks[1], (L, D, H, Dh), D),
                "wk": proj(ks[2], (L, D, Hkv, Dh), D),
                "wv": proj(ks[3], (L, D, Hkv, Dh), D),
                "wo": proj(ks[4], (L, H, Dh, D), H * Dh),
            },
            "ffn": {
                "gate": proj(ks[5], (L, D, F), D),
                "up": proj(ks[6], (L, D, F), D),
                "down": proj(ks[7], (L, F, D), F),
            },
        },
    }


def _layernorm(x, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps)


def _rotary(x, theta):
    """x (R, T, heads, Dh): rotate the pair (first half, second half)."""
    T, Dh = x.shape[1], x.shape[-1]
    half = Dh // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.cos(ang)[None, :, None].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None].astype(x.dtype)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("m", "dtype", "precision"))
def _layer(blocks, i, x, *, m, dtype, precision):
    w = jax.tree.map(lambda a: a[i].astype(dtype), blocks)
    mm = functools.partial(jnp.einsum, precision=precision)
    eps, H, Hkv = m.norm_eps, m.num_heads, m.num_kv_heads
    h = _layernorm(x, eps)
    q = _rotary(mm("rtd,dhe->rthe", h, w["attn"]["wq"]), m.rope_theta)
    k = _rotary(mm("rtd,dhe->rthe", h, w["attn"]["wk"]), m.rope_theta)
    v = mm("rtd,dhe->rthe", h, w["attn"]["wv"])
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    T, Dh = x.shape[1], q.shape[-1]
    s = mm("rqhe,rkhe->rhqk", q, k) / jnp.asarray(Dh ** 0.5, dtype)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, jnp.asarray(-jnp.inf, dtype))
    p = jax.nn.softmax(s, axis=-1)
    o = mm("rhqk,rkhe->rqhe", p, v)
    x = x + mm("rqhe,hed->rqd", o, w["attn"]["wo"])
    h = _layernorm(x, eps)
    g = mm("rtd,df->rtf", h, w["ffn"]["gate"])
    u = mm("rtd,df->rtf", h, w["ffn"]["up"])
    return x + mm("rtf,fd->rtd", jax.nn.silu(g) * u, w["ffn"]["down"])


def hidden(m, params, tokens, *, dtype, precision):
    """tokens (R, T) int32 → final normed hidden states (R, T, D) in
    ``dtype``, one jitted call per layer."""
    x = params["embed"]["tok"][tokens].astype(dtype)
    for i in range(m.num_layers):
        x = _layer(params["blocks"], i, x, m=m, dtype=dtype,
                   precision=precision)
    return _layernorm(x, m.norm_eps)
