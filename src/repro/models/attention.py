"""GQA attention layer: projections, RoPE (or none: ``cfg.use_rope``),
qk-norm, the softmax scale (``cfg.attention_multiplier``), cache handling.

The attention math itself lives in repro.kernels.ops (naive oracle /
chunked flash twin / Pallas kernel); this module owns parameters and the
KV-cache insert-then-attend protocol shared by train, prefill and decode.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops as kops
from repro.models import kvcache
from repro.models.layers import dense_init, rms_norm, rope_angles, apply_rope


def init_attn(cfg: ModelConfig, key):
    H, Hkv, Dh, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.d_model
    ks = jax.random.split(key, 4)
    Hp, Hkvp = cfg.q_heads_eff, cfg.kv_heads_eff
    if Hp == H and Hkvp == Hkv:
        p = {
            "wq": dense_init(ks[0], (D, H, Dh)),
            "wk": dense_init(ks[1], (D, Hkv, Dh)),
            "wv": dense_init(ks[2], (D, Hkv, Dh)),
            "wo": dense_init(ks[3], (H, Dh, D), in_axis_size=H * Dh),
        }
    else:
        # head padding (function-preserving): real heads keep their (kv, j)
        # group layout inside the padded (kv_pad, g_pad) grid; pad q rows and
        # pad wo rows are ZERO, so pad heads contribute exactly 0 to the
        # output. Pad kv heads produce k=v=0 keys only pad q heads see.
        g, gp = H // Hkv, Hp // Hkvp
        assert Hkvp >= Hkv and gp >= g, (H, Hkv, Hp, Hkvp)
        wq = jnp.zeros((D, Hkvp, gp, Dh), jnp.float32)
        wq = wq.at[:, :Hkv, :g].set(
            dense_init(ks[0], (D, Hkv, g, Dh)))
        wo = jnp.zeros((Hkvp, gp, Dh, D), jnp.float32)
        wo = wo.at[:Hkv, :g].set(
            dense_init(ks[3], (Hkv, g, Dh, D), in_axis_size=H * Dh))
        wk = jnp.zeros((D, Hkvp, Dh), jnp.float32)
        wk = wk.at[:, :Hkv].set(dense_init(ks[1], (D, Hkv, Dh)))
        wv = jnp.zeros((D, Hkvp, Dh), jnp.float32)
        wv = wv.at[:, :Hkv].set(dense_init(ks[2], (D, Hkv, Dh)))
        p = {"wq": wq.reshape(D, Hp, Dh), "wk": wk, "wv": wv,
             "wo": wo.reshape(Hp, Dh, D)}
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((Dh,), jnp.float32)
        p["k_norm"] = jnp.ones((Dh,), jnp.float32)
    return p


def _project_qkv(cfg: ModelConfig, p, x, x_kv=None):
    """x (B,S,D) → q (B,S,H,Dh), k/v (B,Skv,Hkv,Dh). x_kv for cross-attn."""
    xk = x if x_kv is None else x_kv
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhe->bshe", xk, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhe->bshe", xk, p["wv"].astype(x.dtype))
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.attention_multiplier is not None:
        # the kernels scale scores by 1/sqrt(head_dim): fold the ratio into q
        q = q * (cfg.attention_multiplier * cfg.head_dim ** 0.5)
    return q, k, v


def _rope_qk(cfg: ModelConfig, q, k, q_pos, kv_pos):
    cq, sq = rope_angles(q_pos, cfg.head_dim, cfg.rope_theta)
    ck, sk = rope_angles(kv_pos, cfg.head_dim, cfg.rope_theta)
    # positions (B,S) → angles (B,S,half) → broadcast over heads (B,S,1,half)
    q = apply_rope(q, cq[:, :, None], sq[:, :, None])
    k = apply_rope(k, ck[:, :, None], sk[:, :, None])
    return q, k


def _out_proj(p, o):
    return jnp.einsum("bshe,hed->bsd", o, p["wo"].astype(o.dtype))


def apply_attn(cfg: ModelConfig, p, x, *, positions, causal=True,
               use_rope=True, impl="chunked", q_chunk=128, kv_chunk=128):
    """Full-sequence self-attention (train / prefill)."""
    q, k, v = _project_qkv(cfg, p, x)
    if use_rope and cfg.use_rope:
        q, k = _rope_qk(cfg, q, k, positions, positions)
    o = kops.attention(q, k, v, positions, positions, causal=causal,
                       window=cfg.swa_window, impl=impl,
                       q_chunk=q_chunk, kv_chunk=kv_chunk)
    return _out_proj(p, o)


def apply_cross_attn(cfg: ModelConfig, p, x, enc_out, enc_pos, *,
                     impl="chunked", q_chunk=128, kv_chunk=128):
    """Decoder → encoder cross-attention (non-causal, no rope, no window)."""
    q, k, v = _project_qkv(cfg, p, x, x_kv=enc_out)
    B, S = x.shape[:2]
    q_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    o = kops.attention(q, k, v, q_pos, enc_pos, causal=False, window=None,
                       impl=impl, q_chunk=q_chunk, kv_chunk=kv_chunk)
    return _out_proj(p, o)


def prefill_attn(cfg: ModelConfig, p, x, cache, *, positions, use_rope=True,
                 impl="chunked", q_chunk=128, kv_chunk=128):
    """Self-attention that also fills a dense cache starting at position 0."""
    q, k, v = _project_qkv(cfg, p, x)
    if use_rope and cfg.use_rope:
        q, k = _rope_qk(cfg, q, k, positions, positions)
    cache = kvcache.dense_cache_insert(cache, k, v, jnp.int32(0))
    o = kops.attention(q, k, v, positions, positions, causal=True,
                       window=cfg.swa_window, impl=impl,
                       q_chunk=q_chunk, kv_chunk=kv_chunk)
    return _out_proj(p, o), cache


def decode_attn(cfg: ModelConfig, p, x_new, cache, pos, *, use_rope=True,
                impl="naive", cross=False, kv_chunk=1024, layer=None):
    """Single-token decode. x_new (B,1,D); ``pos`` = index of the new token —
    scalar int32 (uniform batch: the dry-run/serve_step fast path) or (B,)
    per-slot positions (continuous batching). Dense cache → insert then
    attend over valid slots; ring cache → insert at pos % W with absolute
    slot positions doing the masking (scalar pos only).
    ``cross=True`` skips insertion (static encoder KV).
    ``layer`` given: ``cache`` stacks every layer's, the new rows go into
    that layer of it and attention reads the layer by index; the whole
    stack is returned."""
    B = x_new.shape[0]
    per_slot = getattr(pos, "ndim", 0) == 1
    q, k, v = _project_qkv(cfg, p, x_new)
    if per_slot:
        q_pos = pos.astype(jnp.int32)[:, None]
    else:
        q_pos = jnp.broadcast_to(pos.astype(jnp.int32)[None, None], (B, 1))

    if cross:
        enc_len = cache["k"].shape[1]
        kv_pos = jnp.broadcast_to(jnp.arange(enc_len, dtype=jnp.int32)[None], (B, enc_len))
        o = kops.attention(q, cache["k"].astype(q.dtype), cache["v"].astype(q.dtype),
                           q_pos, kv_pos, causal=False, window=None, impl=impl,
                           kv_chunk=kv_chunk)
        return _out_proj(p, o), cache

    if use_rope and cfg.use_rope:
        q, k = _rope_qk(cfg, q, k, q_pos, q_pos)

    if "slot_pos" in cache:                       # SWA ring buffer
        assert not per_slot, "ring caches require uniform decode positions"
        cache = kvcache.ring_cache_insert(cache, k, v, pos, layer)
        kv = kvcache.cache_layer(cache, layer)
        kv_pos = jnp.broadcast_to(kv["slot_pos"][None], (B, kv["k"].shape[1]))
    elif per_slot:                                # dense, continuous batching
        cache = kvcache.dense_cache_insert_rows(cache, k, v, pos, layer)
        kv = kvcache.cache_layer(cache, layer)
        kv_pos = kvcache.dense_cache_positions_rows(kv, pos + 1)
    else:                                         # dense, uniform
        cache = kvcache.dense_cache_insert(cache, k, v, pos, layer)
        kv = kvcache.cache_layer(cache, layer)
        kv_pos = jnp.broadcast_to(
            kvcache.dense_cache_positions(kv, pos + 1)[None],
            (B, kv["k"].shape[1]))

    o = kops.attention(q, kv["k"].astype(q.dtype), kv["v"].astype(q.dtype),
                       q_pos, kv_pos, causal=True, window=cfg.swa_window,
                       impl=impl, kv_chunk=kv_chunk)
    return _out_proj(p, o), cache
