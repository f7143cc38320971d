"""Scan-over-layers stacks: the layer stack of every decoder-only family,
zamba2's shared-block hybrid, and whisper's encoder-decoder.

Layer parameters are stacked on a leading L axis (init via vmap over keys)
and consumed with lax.scan — the HLO contains ONE layer body per run of
layers regardless of depth, which keeps XLA compile time flat across the
4..64-layer archs and is what makes the 512-device dry-run tractable.
Activation rematerialization wraps the scan body (``remat="block"`` saves
only block boundaries).

Stacks:
  layer stack  : each layer a mixer (``cfg.mixers``: mamba or attention)
                 and then a feed-forward (``cfg.ffn_kind``): dense/vlm
                 [attn → mlp], moe [attn → capacity moe, aux losses
                 accumulated through the scan], ssm [mamba], granite-4.0-h
                 [mamba or attn → held-share experts]; whisper's encoder
  shared block : zamba2 — segments of ``attn_every`` mamba blocks with a
                 SHARED attention block applied between segments
  enc-dec      : whisper's decoder [self → cross → ffn] × Ld
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
# the layer stack: a mixer and then a feed-forward in every layer
# ---------------------------------------------------------------------------
#
#   h  = x + r * Mixer_l(norm1_l(x))          Mixer_l: mamba or attention
#   x' = h + r * FFN_l(norm2_l(h))            r: residual_multiplier
#
# Parameters: ``ln1`` (and ``ln2``, ``ffn`` where the layers have a
# feed-forward) stacked over all L layers, ``mamba`` and ``attn`` each over
# the layers of its kind. Each run of consecutive layers of one kind is one
# scan; a homogeneous model is one run.

def init_layers(key, n_layers: int, init_one):
    """``n_layers`` draws of ``init_one(key)`` stacked on a leading axis."""
    return jax.vmap(init_one)(jax.random.split(key, n_layers))


def layer_runs(kinds):
    """→ [(kind, first layer, first index among that kind's layers, count)]
    for each run of consecutive layers of one kind."""
    runs, seen = [], {"mamba": 0, "attention": 0}
    for i, kind in enumerate(kinds):
        if runs and runs[-1][0] == kind:
            k, l0, j0, n = runs[-1]
            runs[-1] = (k, l0, j0, n + 1)
        else:
            runs.append((kind, i, seen[kind], 1))
        seen[kind] += 1
    return runs


_MIXER = {"mamba": "mamba", "attention": "attn"}
_INIT_MIXER = {"mamba": ssm_mod.init_mamba, "attention": attn_mod.init_attn}
_INIT_FFN = {"held": moe_mod.init_held_experts, "moe": moe_mod.init_moe,
             "mlp": init_mlp}


def init_stack(cfg: ModelConfig, key, kinds=None):
    """The stack of layers whose mixers are ``kinds`` (default
    ``cfg.mixers``), each followed by a ``cfg.ffn_kind`` feed-forward."""
    kinds = cfg.mixers if kinds is None else kinds
    L = len(kinds)
    ks = jax.random.split(key, 5)
    blocks = {"ln1": init_layers(ks[0], L, lambda k: init_norm(cfg, k))}
    if cfg.ffn_kind:
        init_ffn = _INIT_FFN[cfg.ffn_kind]
        blocks["ln2"] = init_layers(ks[1], L, lambda k: init_norm(cfg, k))
        blocks["ffn"] = init_layers(ks[2], L, lambda k: init_ffn(cfg, k))
    for (kind, name), k in zip(_MIXER.items(), ks[3:]):
        if kind in kinds:
            blocks[name] = init_layers(
                k, kinds.count(kind),
                lambda kk, kind=kind: _INIT_MIXER[kind](cfg, kk))
    return blocks


def _at(tree, i):
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), tree)


def _per_layer(blocks):
    """The stacks over all L layers (``ln1``, ``ln2``, ``ffn``)."""
    return {k: v for k, v in blocks.items() if k not in ("mamba", "attn")}


def _residual(cfg: ModelConfig, x, y):
    # r == 1 adds no multiply to the step
    r = cfg.residual_multiplier
    return x + y if r == 1.0 else x + r * y


def _ffn(cfg: ModelConfig, w, h):
    """The layer's feed-forward after its mixer; ``w`` holds the layer's
    ``ln2`` and ``ffn`` → (h', what it reports: the held experts' load
    (..., held + 1), the capacity layer's aux losses, or None)."""
    if not cfg.ffn_kind:
        return h, None
    u = apply_norm(cfg, w["ln2"], h)
    if cfg.ffn_kind == "held":
        y, out = moe_mod.apply_held_experts(cfg, w["ffn"], u)
    elif cfg.ffn_kind == "moe":
        y, out = moe_mod.apply_moe(cfg, w["ffn"], u)
    else:
        y, out = apply_mlp(cfg, w["ffn"], u), None
    return _residual(cfg, h, y), out


def _slice(tree, i0, n):
    return jax.tree.map(lambda a: a[i0:i0 + n], tree)


def apply_stack(cfg: ModelConfig, blocks, x, *, positions, impl: Impl,
                kinds=None, causal=True, use_rope=True):
    """Full-sequence layer stack (train / prefill) → (x, aux). Each run
    scans its slice of the stacks as the scan's input (a one-run model's
    slice is the whole stack): read by index from the closed-over stack,
    the backward pass would carry a cotangent the size of the stack."""
    kinds = cfg.mixers if kinds is None else kinds
    aux = zero_aux(cfg)
    for kind, l0, j0, n in layer_runs(kinds):
        def body(carry, w, kind=kind):
            h, aux = carry
            lw, p = w
            h = impl.anchor(h)
            u = apply_norm(cfg, lw["ln1"], h)
            with jax.named_scope(kind):
                if kind == "mamba":
                    y = ssm_mod.apply_mamba(cfg, p, u, impl=impl.ssd)
                else:
                    y = attn_mod.apply_attn(
                        cfg, p, u, positions=positions, causal=causal,
                        use_rope=use_rope, impl=impl.attention,
                        q_chunk=impl.q_chunk, kv_chunk=impl.kv_chunk)
            h, out = _ffn(cfg, lw, _residual(cfg, h, y))
            if cfg.ffn_kind == "moe":
                aux = _add_aux(aux, out)
            return (h, aux), None

        if impl.remat:
            body = jax.checkpoint(body, prevent_cse=False)
        xs = (_slice(_per_layer(blocks), l0, n),
              _slice(blocks[_MIXER[kind]], j0, n))
        (x, aux), _ = jax.lax.scan(body, (impl.anchor(x), aux), xs)
    return x, aux


def decode_stack(cfg: ModelConfig, blocks, caches, x, pos, *, impl: Impl):
    """One token through the layer stack. caches = {"mamba": SSM states
    stacked over the mamba layers, "attn": KV caches stacked over the
    attention layers}, carried through each run's scan: a layer reads and
    writes its own by index (a KV cache takes only its new rows), so with
    the state donated every write is in place and no layer of a cache is
    copied. → (x, new caches, load (L, B, 1, held + 1): each layer's
    routed choices per token, where the feed-forward is held experts, else
    None)."""
    caches, loads = dict(caches), []
    per_layer = _per_layer(blocks)
    for kind, l0, j0, n in layer_runs(cfg.mixers):
        name = _MIXER[kind]

        def body(carry, idx, kind=kind, name=name):
            h, c = carry
            i, j = idx
            lw = _at(per_layer, i)
            u = apply_norm(cfg, lw["ln1"], h)
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
            h, out = _ffn(cfg, lw, _residual(cfg, h, y))
            return (h, c), out if cfg.ffn_kind == "held" else None

        (x, caches[name]), load = jax.lax.scan(
            body, (x, caches[name]),
            (jnp.arange(l0, l0 + n), jnp.arange(j0, j0 + n)))
        loads.append(load)
    load = jnp.concatenate(loads) if cfg.ffn_kind == "held" else None
    return x, caches, load


# ---------------------------------------------------------------------------
# shared-block hybrid (zamba2)
# ---------------------------------------------------------------------------

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
