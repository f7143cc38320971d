"""Core neural-net layers in pure JAX: norms, MLPs, embeddings, RoPE.

Parameters are plain pytrees (nested dicts of jnp arrays). Every function is
pure: ``apply_*(params, x, cfg)``. Layer stacks are stacked on a leading
``L`` axis and consumed with ``lax.scan`` (keeps HLO size O(1) in depth —
essential both for TPU compile times and for this CPU container).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, shape, in_axis_size: Optional[int] = None, dtype=jnp.float32):
    """Truncated-normal fan-in init (LeCun-ish, matches common LM inits)."""
    fan_in = in_axis_size if in_axis_size is not None else shape[0]
    scale = 1.0 / max(1, fan_in) ** 0.5
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * scale).astype(dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rms_norm(x, weight, eps: float):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    if weight is not None:
        y = y * weight.astype(jnp.float32)
    return y.astype(dt)


def np_layernorm(x, eps: float):
    """OLMo-style non-parametric LayerNorm (no scale, no bias)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps)).astype(dt)


def layer_norm(x, weight, bias, eps: float):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    if weight is not None:
        y = y * weight.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(dt)


def init_norm(cfg: ModelConfig, key):
    if cfg.norm_type == "np_layernorm":
        return {}
    p = {"scale": jnp.ones((cfg.d_model,), jnp.float32)}
    if cfg.norm_type == "layernorm":
        p["bias"] = jnp.zeros((cfg.d_model,), jnp.float32)
    return p


def apply_norm(cfg: ModelConfig, params, x):
    if cfg.norm_type == "rmsnorm":
        return rms_norm(x, params["scale"], cfg.norm_eps)
    if cfg.norm_type == "np_layernorm":
        return np_layernorm(x, cfg.norm_eps)
    return layer_norm(x, params["scale"], params.get("bias"), cfg.norm_eps)


# ---------------------------------------------------------------------------
# activations / MLP
# ---------------------------------------------------------------------------

def activation(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}[name]


def init_mlp(cfg: ModelConfig, key, d_ff: Optional[int] = None):
    d_ff = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {
        "up": dense_init(ks[0], (cfg.d_model, d_ff)),
        "down": dense_init(ks[1], (d_ff, cfg.d_model), in_axis_size=d_ff),
    }
    if cfg.mlp_type == "glu":
        p["gate"] = dense_init(ks[2], (cfg.d_model, d_ff))
    return p


def apply_mlp(cfg: ModelConfig, params, x):
    act = activation(cfg.act)
    up = x @ params["up"].astype(x.dtype)
    if cfg.mlp_type == "glu":
        h = act(x @ params["gate"].astype(x.dtype)) * up
    else:
        h = act(up)
    return h @ params["down"].astype(x.dtype)


# ---------------------------------------------------------------------------
# embeddings / head
# ---------------------------------------------------------------------------

VOCAB_PAD = 128   # pad vocab to a multiple (Megatron-style) so the vocab dim
                  # always tiles the 16-way model axis; pad logits are masked
                  # to -1e30 so loss/sampling are bit-equivalent to unpadded.


def padded_vocab(vocab_size: int) -> int:
    return ((vocab_size + VOCAB_PAD - 1) // VOCAB_PAD) * VOCAB_PAD


def init_embed(cfg: ModelConfig, key):
    ks = jax.random.split(key, 2)
    vp = padded_vocab(cfg.vocab_size)
    p = {"tok": embed_init(ks[0], (vp, cfg.d_model))}
    if not cfg.tie_embeddings:
        p["head"] = dense_init(ks[1], (cfg.d_model, vp))
    return p


def embed_tokens(params, tokens, dtype):
    return params["tok"].astype(dtype)[tokens]


def lm_logits(cfg: ModelConfig, params, x):
    w = params["tok"].T if cfg.tie_embeddings else params["head"]
    # logits accumulate in f32: vocab reductions in bf16 lose ~2 bits of logit
    logits = jnp.einsum("...d,dv->...v", x, w.astype(x.dtype),
                        preferred_element_type=jnp.float32)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    vp = logits.shape[-1]
    if vp != cfg.vocab_size:
        pad_mask = jnp.arange(vp) >= cfg.vocab_size
        logits = jnp.where(pad_mask, jnp.float32(-1e30), logits)
    return logits


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_angles(positions, head_dim: int, theta: float):
    """positions (...,) int32 → (cos, sin) of shape (..., head_dim//2), f32."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x (..., n_heads, head_dim); cos/sin broadcastable (..., 1, head_dim//2)."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)
