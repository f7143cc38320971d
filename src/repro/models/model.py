"""Top-level model API: init / forward / loss / prefill / decode for all
ten architecture families.

Batch contract (see launch/dryrun.py input_specs):
  train/prefill: {"tokens" (B,S) i32, "labels" (B,S) i32}
                 + vlm: {"vision_embeds" (B, Vtok, Vdim)} — replaces the
                   first Vtok sequence positions (labels there are masked)
                 + audio: {"frames" (B, enc_ctx, d_model)} — encoder input
  decode:        serve_step(params, state, token (B,1)) with ``state`` built
                 by init_decode_state (caches sized for the cell's seq_len).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import kvcache
from repro.models import ssm as ssm_mod
from repro.models import transformer as tf
from repro.models.layers import (apply_norm, dense_init, embed_tokens,
                                 init_embed, init_mlp, init_norm, lm_logits)
from repro.models.transformer import Impl


def sinusoid(seq_len: int, d_model: int, offset=0):
    pos = jnp.arange(seq_len, dtype=jnp.float32) + offset
    half = d_model // 2
    freq = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None] * freq[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key):
    ks = jax.random.split(key, 8)
    params = {"embed": init_embed(cfg, ks[0]), "final_norm": init_norm(cfg, ks[1])}

    if cfg.mixers:
        params["blocks"] = tf.init_stack(cfg, ks[2])
    elif cfg.enc_dec:
        params["enc_blocks"] = tf.init_stack(cfg, ks[2],
                                             ("attention",) * cfg.enc_layers)
        params["blocks"] = tf.init_layers(
            ks[3], cfg.num_layers, lambda k: tf.init_dec_block(cfg, k))
        params["enc_final_norm"] = init_norm(cfg, ks[4])
    else:
        def init_mamba_block(k):
            k1, k2 = jax.random.split(k)
            return {"ln1": init_norm(cfg, k1), "mamba": ssm_mod.init_mamba(cfg, k2)}
        params["blocks"] = tf.init_layers(ks[2], cfg.num_layers, init_mamba_block)
        sk = jax.random.split(ks[3], 4)
        params["shared_attn"] = {
            "ln1": init_norm(cfg, sk[0]), "attn": attn_mod.init_attn(cfg, sk[1]),
            "ln2": init_norm(cfg, sk[2]), "ffn": init_mlp(cfg, sk[3]),
        }

    if cfg.vision_tokens:
        params["vision_proj"] = {
            "w": dense_init(ks[5], (cfg.vision_dim, cfg.d_model)),
            "b": jnp.zeros((cfg.d_model,), jnp.float32),
        }
    return params


# ---------------------------------------------------------------------------
# forward (train / prefill logits)
# ---------------------------------------------------------------------------

def _embed(cfg: ModelConfig, params, tokens, dtype):
    x = embed_tokens(params["embed"], tokens, dtype)
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, dtype)
    return x


def _embed_input(cfg: ModelConfig, params, batch, dtype):
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = _embed(cfg, params, tokens, dtype)
    if cfg.vision_tokens and "vision_embeds" in batch:
        ve = batch["vision_embeds"].astype(dtype)
        vp = params["vision_proj"]
        v = ve @ vp["w"].astype(dtype) + vp["b"].astype(dtype)
        x = jnp.concatenate([v, x[:, cfg.vision_tokens:]], axis=1)
    return x


def encode(cfg: ModelConfig, params, frames, *, impl: Impl):
    """Audio encoder: precomputed frame embeddings (stub frontend) + sinusoid."""
    B, Se, D = frames.shape
    x = frames + sinusoid(Se, D).astype(frames.dtype)[None]
    positions = jnp.broadcast_to(jnp.arange(Se, dtype=jnp.int32)[None], (B, Se))
    x, _ = tf.apply_stack(cfg, params["enc_blocks"], x, positions=positions,
                          impl=impl, kinds=("attention",) * cfg.enc_layers,
                          causal=False, use_rope=False)
    return apply_norm(cfg, params["enc_final_norm"], x)


def forward(cfg: ModelConfig, params, batch, *, impl: Impl = Impl(),
            dtype=jnp.bfloat16, last_only: bool = False):
    """→ (logits (B,S,V) f32, aux dict). ``last_only`` computes logits for the
    final position only (serving prefill: the next-token head is all a
    prefill needs, and it keeps the (B,S,V) tensor out of memory)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    x = _embed_input(cfg, params, batch, dtype)

    if cfg.mixers:
        x, aux = tf.apply_stack(cfg, params["blocks"], x, positions=positions,
                                impl=impl)
    elif cfg.enc_dec:
        enc_out = encode(cfg, params, batch["frames"].astype(dtype), impl=impl)
        x = x + sinusoid(S, cfg.d_model).astype(dtype)[None]
        x, aux = tf.apply_dec_stack(cfg, params["blocks"], x, enc_out,
                                    positions=positions, impl=impl)
    else:
        x, aux = tf.apply_hybrid_stack(cfg, params["blocks"], params["shared_attn"],
                                       x, positions=positions, impl=impl)

    if last_only:
        x = x[:, -1:]
    x = apply_norm(cfg, params["final_norm"], x)
    logits = lm_logits(cfg, params["embed"], x)
    return logits, aux


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

def loss_fn(cfg: ModelConfig, params, batch, *, impl: Impl = Impl(),
            dtype=jnp.bfloat16):
    """Next-token CE (labels == -1 masked) + MoE aux losses. → (loss, metrics)."""
    logits, aux = forward(cfg, params, batch, impl=impl, dtype=dtype)
    labels = batch["labels"]
    logits = logits[:, :-1].astype(jnp.float32)
    targets = labels[:, 1:]
    mask = (targets >= 0).astype(jnp.float32)
    tgt = jnp.maximum(targets, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
    ce = jnp.sum((lse - picked) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    loss = ce
    metrics = {"ce": ce, **aux}
    for k in ("moe_lb_loss", "moe_z_loss"):
        if k in aux:
            loss = loss + aux[k]
    metrics["loss"] = loss
    return loss, metrics


# ---------------------------------------------------------------------------
# decode state + step (serving)
# ---------------------------------------------------------------------------

def _attn_cache_spec(cfg: ModelConfig, batch: int, max_seq: int, dtype):
    """Ring cache when SWA is enabled and the context exceeds the window."""
    if cfg.swa_window is not None and max_seq > cfg.swa_window:
        return kvcache.init_ring_cache(batch, cfg.swa_window, cfg.kv_heads_eff,
                                       cfg.head_dim, dtype)
    return kvcache.init_dense_cache(batch, max_seq, cfg.kv_heads_eff,
                                    cfg.head_dim, dtype)


def _ssm_state_spec(cfg: ModelConfig, batch: int, dtype):
    s = cfg.ssm
    return kvcache.init_ssm_state(batch, cfg.ssm_heads, s.head_dim, s.d_state,
                                  s.conv_width,
                                  cfg.d_inner + 2 * s.n_groups * s.d_state,
                                  dtype)


def init_decode_state(cfg: ModelConfig, params, batch: int, max_seq: int, *,
                      dtype=jnp.bfloat16, impl: Impl = Impl(),
                      enc_out: Optional[jnp.ndarray] = None):
    extra = {}
    if cfg.mixers:
        n_attn = cfg.mixers.count("attention")
        caches = {}
        if cfg.num_layers - n_attn:
            caches["mamba"] = kvcache.stack_caches(
                [_ssm_state_spec(cfg, batch, dtype)] * (cfg.num_layers - n_attn))
        if n_attn:
            caches["attn"] = kvcache.stack_caches(
                [_attn_cache_spec(cfg, batch, max_seq, dtype)] * n_attn)
        if cfg.ffn_kind == "held":
            # routed (token, expert) choices a layer: one column per held
            # expert, the last for the experts held elsewhere; counted
            # over the rows that ``occupied`` marks
            extra = {"expert_load": jnp.zeros(
                         (cfg.num_layers, cfg.moe.held + 1), jnp.int32),
                     "occupied": jnp.ones((batch,), jnp.int32)}
    elif cfg.enc_dec:
        assert enc_out is not None, "enc-dec decode state needs encoder output"
        self_one = kvcache.init_dense_cache(batch, max_seq, cfg.kv_heads_eff,
                                            cfg.head_dim, dtype)

        def cross_kv(layer_p):
            k = jnp.einsum("bsd,dhe->bshe", enc_out,
                           layer_p["cross"]["wk"].astype(enc_out.dtype))
            v = jnp.einsum("bsd,dhe->bshe", enc_out,
                           layer_p["cross"]["wv"].astype(enc_out.dtype))
            return {"k": k, "v": v}

        cross = jax.vmap(cross_kv)(params["blocks"])      # map over L axis
        caches = {
            "self": kvcache.stack_caches([self_one] * cfg.num_layers),
            "cross": cross,
        }
    else:
        n_seg = cfg.num_layers // cfg.attn_every
        attn_one = _attn_cache_spec(cfg, batch, max_seq, dtype)
        caches = {
            "mamba": kvcache.stack_caches(
                [_ssm_state_spec(cfg, batch, dtype)] * cfg.num_layers),
            "attn": kvcache.stack_caches([attn_one] * n_seg),
        }
    return {"caches": caches, "pos": jnp.int32(0), **extra}


def decode_step(cfg: ModelConfig, params, state, token, *, impl: Impl = Impl(),
                dtype=jnp.bfloat16):
    """token (B,1) i32 at position state["pos"] → (logits (B,1,V) f32, state)."""
    pos = state["pos"]
    x = _embed(cfg, params, token, dtype)
    state = dict(state)

    if cfg.mixers:
        x, new_caches, load = tf.decode_stack(
            cfg, params["blocks"], state["caches"], x, pos, impl=impl)
        if load is not None:
            occupied = state["occupied"].astype(load.dtype)[None, :, None, None]
            state["expert_load"] = state["expert_load"] + jnp.sum(
                load * occupied, axis=(1, 2)).astype(jnp.int32)
    elif cfg.enc_dec:
        half = cfg.d_model // 2
        freq = jnp.exp(-math.log(10000.0)
                       * jnp.arange(half, dtype=jnp.float32) / half)
        ang = pos.astype(jnp.float32)[..., None] * freq      # scalar or (B,)
        pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
        pe = pe[None, None] if pe.ndim == 1 else pe[:, None]
        x = x + pe.astype(dtype)
        caches = state["caches"]
        x, new_caches = tf.decode_dec_stack(
            cfg, params["blocks"],
            {"self": caches["self"], "cross": caches["cross"]}, x, pos, impl=impl)
        new_caches = {"self": new_caches["self"], "cross": caches["cross"]}
    else:
        x, new_caches = tf.decode_hybrid_stack(cfg, params["blocks"],
                                               params["shared_attn"],
                                               state["caches"], x, pos, impl=impl)

    x = apply_norm(cfg, params["final_norm"], x)
    logits = lm_logits(cfg, params["embed"], x)
    return logits, dict(state, caches=new_caches, pos=pos + 1)
