"""Block assembly and scan-over-layers stacks for every family.

Layer parameters are stacked on a leading L axis (init via vmap over keys)
and consumed with lax.scan — the HLO contains ONE block body regardless of
depth, which keeps XLA compile time flat across the 4..64-layer archs and is
what makes the 512-device dry-run tractable. Activation rematerialization
wraps the scan body (``remat="block"`` saves only block boundaries).

Families:
  dense/vlm : [attn → ffn] × L
  moe       : [attn → moe-ffn] × L (+ aux losses accumulated through the scan)
  ssm       : [mamba2] × L
  hybrid    : segments of ``attn_every`` mamba blocks with a SHARED attention
              block applied between segments (zamba2); or, with
              ``layer_types``, one mixer a layer (mamba or attention) and the
              dropless held-expert FFN after every mixer (granite-4.0-h)
  audio     : encoder [attn → ffn] × Le, decoder [self → cross → ffn] × Ld
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import kvcache
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import apply_mlp, apply_norm, init_mlp, init_norm


@dataclass(frozen=True)
class Impl:
    """Kernel implementation selection (see kernels/ops.py).

    ``act_dp``: mesh axes the activation batch dim is sharded over. When set,
    scan-over-layers bodies re-anchor x with a sharding constraint — without
    it GSPMD may leave the while-loop carry replicated and compute every
    layer redundantly on all devices (measured 256× on grok prefill)."""
    attention: str = "chunked"
    decode_attention: str = "naive"
    ssd: str = "chunked"
    q_chunk: int = 128
    kv_chunk: int = 128
    remat: bool = True
    act_dp: Optional[tuple] = None

    def anchor(self, x):
        if self.act_dp is None:
            return x
        from jax.sharding import PartitionSpec as P
        dpe = self.act_dp if len(self.act_dp) > 1 else self.act_dp[0]
        return jax.lax.with_sharding_constraint(
            x, P(dpe, *([None] * (x.ndim - 1))))


def zero_aux(cfg: ModelConfig):
    if cfg.moe:
        return {"moe_lb_loss": jnp.float32(0), "moe_z_loss": jnp.float32(0),
                "moe_drop_frac": jnp.float32(0)}
    return {}


def _add_aux(a, b):
    return {k: a[k] + b[k] for k in a}


# ---------------------------------------------------------------------------
# single blocks
# ---------------------------------------------------------------------------

def init_block(cfg: ModelConfig, key):
    ks = jax.random.split(key, 4)
    if cfg.family == "ssm":
        return {"ln1": init_norm(cfg, ks[0]), "mamba": ssm_mod.init_mamba(cfg, ks[1])}
    p = {"ln1": init_norm(cfg, ks[0]), "attn": attn_mod.init_attn(cfg, ks[1]),
         "ln2": init_norm(cfg, ks[2])}
    if cfg.moe:
        p["ffn"] = moe_mod.init_moe(cfg, ks[3])
    else:
        p["ffn"] = init_mlp(cfg, ks[3])
    return p


def apply_block(cfg: ModelConfig, p, x, *, positions, impl: Impl,
                causal=True, use_rope=True):
    """Full-sequence block. Returns (x, aux)."""
    aux = zero_aux(cfg)
    if cfg.family == "ssm":
        x = x + ssm_mod.apply_mamba(cfg, p["mamba"], apply_norm(cfg, p["ln1"], x),
                                    impl=impl.ssd)
        return x, aux
    h = attn_mod.apply_attn(cfg, p["attn"], apply_norm(cfg, p["ln1"], x),
                            positions=positions, causal=causal, use_rope=use_rope,
                            impl=impl.attention, q_chunk=impl.q_chunk,
                            kv_chunk=impl.kv_chunk)
    x = x + h
    h = apply_norm(cfg, p["ln2"], x)
    if cfg.moe:
        h, aux = moe_mod.apply_moe(cfg, p["ffn"], h)
    else:
        h = apply_mlp(cfg, p["ffn"], h)
    return x + h, aux


# ---------------------------------------------------------------------------
# stacked init
# ---------------------------------------------------------------------------

def init_stack(cfg: ModelConfig, key, n_layers: int, init_one=None):
    init_one = init_one or (lambda k: init_block(cfg, k))
    keys = jax.random.split(key, n_layers)
    return jax.vmap(init_one)(keys)


# ---------------------------------------------------------------------------
# forward stacks (train / prefill without cache)
# ---------------------------------------------------------------------------

def apply_stack(cfg: ModelConfig, stacked, x, *, positions, impl: Impl,
                causal=True, use_rope=True):
    def body(carry, layer_p):
        h, aux = carry
        h = impl.anchor(h)
        h, aux_l = apply_block(cfg, layer_p, h, positions=positions, impl=impl,
                               causal=causal, use_rope=use_rope)
        return (h, _add_aux(aux, aux_l)), None

    if impl.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    (x, aux), _ = jax.lax.scan(body, (impl.anchor(x), zero_aux(cfg)), stacked)
    return x, aux


def apply_hybrid_stack(cfg: ModelConfig, mamba_stack, shared_block, x, *,
                       positions, impl: Impl):
    """zamba2: segments of ``attn_every`` mamba layers, shared attn between."""
    L, every = cfg.num_layers, cfg.attn_every
    n_seg = L // every
    assert n_seg * every == L, (L, every)
    seg_params = jax.tree.map(lambda a: a.reshape((n_seg, every) + a.shape[1:]),
                              mamba_stack)

    def mamba_body(h, layer_p):
        h = h + ssm_mod.apply_mamba(cfg, layer_p["mamba"],
                                    apply_norm(cfg, layer_p["ln1"], h),
                                    impl=impl.ssd)
        return h, None

    def shared_attn(h):
        a = attn_mod.apply_attn(cfg, shared_block["attn"],
                                apply_norm(cfg, shared_block["ln1"], h),
                                positions=positions, causal=True, use_rope=True,
                                impl=impl.attention, q_chunk=impl.q_chunk,
                                kv_chunk=impl.kv_chunk)
        h = h + a
        h = h + apply_mlp(cfg, shared_block["ffn"],
                          apply_norm(cfg, shared_block["ln2"], h))
        return h

    def seg_body(h, seg_p):
        h = impl.anchor(h)
        h, _ = jax.lax.scan(mamba_body, h, seg_p)
        h = shared_attn(h)
        return h, None

    if impl.remat:
        seg_body = jax.checkpoint(seg_body, prevent_cse=False)
    x, _ = jax.lax.scan(seg_body, impl.anchor(x), seg_params)
    return x, zero_aux(cfg)


# ---------------------------------------------------------------------------
# decode blocks (single new token through a cached stack)
# ---------------------------------------------------------------------------

def decode_block(cfg: ModelConfig, p, x, cache, pos, *, impl: Impl,
                 use_rope=True, layer=None):
    """Returns (x, new_cache). ``layer`` given: ``cache`` is the whole
    stack, read and written at that layer, and the stack is returned."""
    if cfg.family == "ssm":
        h, new_state = ssm_mod.decode_mamba(
            cfg, p["mamba"], apply_norm(cfg, p["ln1"], x),
            kvcache.cache_layer(cache, layer))
        if layer is not None:
            new_state = kvcache.cache_put_layer(cache, new_state, layer)
        return x + h, new_state
    h, new_cache = attn_mod.decode_attn(cfg, p["attn"], apply_norm(cfg, p["ln1"], x),
                                        cache, pos, use_rope=use_rope,
                                        impl=impl.decode_attention,
                                        kv_chunk=impl.kv_chunk, layer=layer)
    x = x + h
    h = apply_norm(cfg, p["ln2"], x)
    if cfg.moe:
        h, _ = moe_mod.apply_moe(cfg, p["ffn"], h)
    else:
        h = apply_mlp(cfg, p["ffn"], h)
    return x + h, new_cache


def decode_stack(cfg: ModelConfig, stacked, caches, x, pos, *, impl: Impl,
                 use_rope=True):
    """Scan the layer stack carrying the token activation and the stacked
    caches: layer l reads and writes its own cache by index (a KV cache
    takes only its new rows), so with the state donated every write is in
    place and no layer of the cache is copied."""
    def body(carry, inp):
        h, c = carry
        layer_p, l = inp
        return decode_block(cfg, layer_p, h, c, pos, impl=impl,
                            use_rope=use_rope, layer=l), None

    layers = jnp.arange(jax.tree.leaves(stacked)[0].shape[0])
    (x, caches), _ = jax.lax.scan(body, (x, caches), (stacked, layers))
    return x, caches


def decode_hybrid_stack(cfg: ModelConfig, mamba_stack, shared_block, caches,
                        x, pos, *, impl: Impl):
    """caches = {"mamba": stacked ssm states (L,...), "attn": stacked dense/ring
    caches (n_seg, ...) — one KV cache per shared-block insertion}."""
    L, every = cfg.num_layers, cfg.attn_every
    n_seg = L // every
    seg_params = jax.tree.map(lambda a: a.reshape((n_seg, every) + a.shape[1:]),
                              mamba_stack)
    seg_mamba_caches = jax.tree.map(
        lambda a: a.reshape((n_seg, every) + a.shape[1:]), caches["mamba"])

    def mamba_body(h, inp):
        layer_p, st = inp
        y, new_st = ssm_mod.decode_mamba(cfg, layer_p["mamba"],
                                         apply_norm(cfg, layer_p["ln1"], h), st)
        return h + y, new_st

    def seg_body(h, inp):
        seg_p, seg_c, attn_c = inp
        h, new_seg_c = jax.lax.scan(mamba_body, h, (seg_p, seg_c))
        a, new_attn_c = attn_mod.decode_attn(
            cfg, shared_block["attn"], apply_norm(cfg, shared_block["ln1"], h),
            attn_c, pos, use_rope=True, impl=impl.decode_attention,
            kv_chunk=impl.kv_chunk)
        h = h + a
        h = h + apply_mlp(cfg, shared_block["ffn"],
                          apply_norm(cfg, shared_block["ln2"], h))
        return h, (new_seg_c, new_attn_c)

    x, (new_mamba, new_attn) = jax.lax.scan(
        seg_body, x, (seg_params, seg_mamba_caches, caches["attn"]))
    new_caches = {
        "mamba": jax.tree.map(lambda a: a.reshape((L,) + a.shape[2:]), new_mamba),
        "attn": new_attn,
    }
    return x, new_caches


# ---------------------------------------------------------------------------
# pattern hybrid (layer_types): mixer + held-expert FFN in every layer
# ---------------------------------------------------------------------------
#
#   h  = x + r * Mixer_l(norm1_l(x))          Mixer_l: mamba or attention
#   x' = h + r * (MoE_l + Shared_l)(norm2_l(h))          r: residual_multiplier
#
# Parameters: ``ln1``, ``ln2`` and ``ffn`` stacked over all L layers,
# ``mamba`` and ``attn`` each over the layers of its kind. Each run of
# consecutive layers of one kind is one scan whose body reads its layer's
# weights (and its cache) by index: no slice of a stack is copied.

def layer_runs(layer_types):
    """→ [(kind, first layer, first index among that kind's layers, count)]
    for each run of consecutive layers of one kind."""
    runs, seen = [], {"mamba": 0, "attention": 0}
    for i, kind in enumerate(layer_types):
        if runs and runs[-1][0] == kind:
            k, l0, j0, n = runs[-1]
            runs[-1] = (k, l0, j0, n + 1)
        else:
            runs.append((kind, i, seen[kind], 1))
        seen[kind] += 1
    return runs


_MIXER = {"mamba": "mamba", "attention": "attn"}


def init_pattern_stack(cfg: ModelConfig, key):
    L = cfg.num_layers
    n_attn = cfg.layer_types.count("attention")
    ks = jax.random.split(key, 5)
    blocks = {
        "ln1": init_stack(cfg, ks[0], L, lambda k: init_norm(cfg, k)),
        "ln2": init_stack(cfg, ks[1], L, lambda k: init_norm(cfg, k)),
        "ffn": init_stack(cfg, ks[2], L,
                          lambda k: moe_mod.init_held_experts(cfg, k)),
    }
    if L - n_attn:
        blocks["mamba"] = init_stack(cfg, ks[3], L - n_attn,
                                     lambda k: ssm_mod.init_mamba(cfg, k))
    if n_attn:
        blocks["attn"] = init_stack(cfg, ks[4], n_attn,
                                    lambda k: attn_mod.init_attn(cfg, k))
    return blocks


def _at(tree, i):
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), tree)


def _pattern_ffn(cfg: ModelConfig, blocks, i, h):
    """The layer's feed-forward on h → (h', load (..., held + 1))."""
    u = apply_norm(cfg, _at(blocks["ln2"], i), h)
    y, load = moe_mod.apply_held_experts(cfg, _at(blocks["ffn"], i), u)
    return h + cfg.residual_multiplier * y, load


def _run_index(l0, j0, n):
    return (jnp.arange(l0, l0 + n), jnp.arange(j0, j0 + n))


def apply_pattern_stack(cfg: ModelConfig, blocks, x, *, positions, impl: Impl):
    """Full-sequence pattern hybrid (train / prefill)."""
    for kind, l0, j0, n in layer_runs(cfg.layer_types):
        def body(h, idx, kind=kind):
            i, j = idx
            h = impl.anchor(h)
            u = apply_norm(cfg, _at(blocks["ln1"], i), h)
            p = _at(blocks[_MIXER[kind]], j)
            with jax.named_scope(kind):
                if kind == "mamba":
                    y = ssm_mod.apply_mamba(cfg, p, u, impl=impl.ssd)
                else:
                    y = attn_mod.apply_attn(
                        cfg, p, u, positions=positions, causal=True,
                        impl=impl.attention, q_chunk=impl.q_chunk,
                        kv_chunk=impl.kv_chunk)
            h, _ = _pattern_ffn(cfg, blocks, i, h + cfg.residual_multiplier * y)
            return h, None

        if impl.remat:
            body = jax.checkpoint(body, prevent_cse=False)
        x, _ = jax.lax.scan(body, impl.anchor(x), _run_index(l0, j0, n))
    return x, zero_aux(cfg)


def decode_pattern_stack(cfg: ModelConfig, blocks, caches, x, pos, *,
                         impl: Impl):
    """caches = {"mamba": SSM states stacked over the mamba layers, "attn":
    KV caches stacked over the attention layers} → (x, new caches, load
    (L, B, 1, held + 1)): each layer's routed choices per token."""
    caches, loads = dict(caches), []
    for kind, l0, j0, n in layer_runs(cfg.layer_types):
        name = _MIXER[kind]

        def body(carry, idx, kind=kind, name=name):
            h, c = carry
            i, j = idx
            u = apply_norm(cfg, _at(blocks["ln1"], i), h)
            p = _at(blocks[name], j)
            with jax.named_scope(kind):
                if kind == "mamba":
                    y, cj = ssm_mod.decode_mamba(
                        cfg, p, u, kvcache.cache_layer(c, j))
                    c = kvcache.cache_put_layer(c, cj, j)
                else:
                    y, c = attn_mod.decode_attn(
                        cfg, p, u, c, pos, impl=impl.decode_attention,
                        kv_chunk=impl.kv_chunk, layer=j)
            h, load = _pattern_ffn(cfg, blocks, i,
                                   h + cfg.residual_multiplier * y)
            return (h, c), load

        (x, caches[name]), load = jax.lax.scan(
            body, (x, caches[name]), _run_index(l0, j0, n))
        loads.append(load)
    return x, caches, jnp.concatenate(loads)


# ---------------------------------------------------------------------------
# encoder-decoder (whisper)
# ---------------------------------------------------------------------------

def init_dec_block(cfg: ModelConfig, key):
    ks = jax.random.split(key, 6)
    return {
        "ln1": init_norm(cfg, ks[0]), "attn": attn_mod.init_attn(cfg, ks[1]),
        "ln2": init_norm(cfg, ks[2]), "cross": attn_mod.init_attn(cfg, ks[3]),
        "ln3": init_norm(cfg, ks[4]), "ffn": init_mlp(cfg, ks[5]),
    }


def apply_dec_block(cfg: ModelConfig, p, x, enc_out, enc_pos, *, positions,
                    impl: Impl):
    h = attn_mod.apply_attn(cfg, p["attn"], apply_norm(cfg, p["ln1"], x),
                            positions=positions, causal=True, use_rope=False,
                            impl=impl.attention, q_chunk=impl.q_chunk,
                            kv_chunk=impl.kv_chunk)
    x = x + h
    h = attn_mod.apply_cross_attn(cfg, p["cross"], apply_norm(cfg, p["ln2"], x),
                                  enc_out, enc_pos, impl=impl.attention,
                                  q_chunk=impl.q_chunk, kv_chunk=impl.kv_chunk)
    x = x + h
    return x + apply_mlp(cfg, p["ffn"], apply_norm(cfg, p["ln3"], x))


def apply_dec_stack(cfg: ModelConfig, stacked, x, enc_out, *, positions, impl: Impl):
    B, Se = enc_out.shape[:2]
    enc_pos = jnp.broadcast_to(jnp.arange(Se, dtype=jnp.int32)[None], (B, Se))

    def body(h, layer_p):
        return apply_dec_block(cfg, layer_p, impl.anchor(h), enc_out, enc_pos,
                               positions=positions, impl=impl), None

    if impl.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, _ = jax.lax.scan(body, impl.anchor(x), stacked)
    return x, {}


def decode_dec_block(cfg: ModelConfig, p, x, cache, pos, *, impl: Impl):
    """cache = {"self": dense cache, "cross": precomputed enc K/V}."""
    h, new_self = attn_mod.decode_attn(cfg, p["attn"], apply_norm(cfg, p["ln1"], x),
                                       cache["self"], pos, use_rope=False,
                                       impl=impl.decode_attention,
                                       kv_chunk=impl.kv_chunk)
    x = x + h
    h, _ = attn_mod.decode_attn(cfg, p["cross"], apply_norm(cfg, p["ln2"], x),
                                cache["cross"], pos, cross=True,
                                impl=impl.decode_attention, kv_chunk=impl.kv_chunk)
    x = x + h
    x = x + apply_mlp(cfg, p["ffn"], apply_norm(cfg, p["ln3"], x))
    return x, {"self": new_self, "cross": cache["cross"]}


def decode_dec_stack(cfg: ModelConfig, stacked, caches, x, pos, *, impl: Impl):
    def body(h, inp):
        layer_p, cache_l = inp
        h, new_cache = decode_dec_block(cfg, layer_p, h, cache_l, pos, impl=impl)
        return h, new_cache

    return jax.lax.scan(body, x, (stacked, caches))
