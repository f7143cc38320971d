"""Plain reference of a Mamba-2 / attention hybrid with a held share of
routed experts (Granite-4.0-H-Small): weights and forward pass.

Written from GraniteMoeHybrid's layer equations and published config, not
from the program:

    x0      = E[tokens] * embedding_multiplier
    layer l : h  = x + r * Mixer_l(RMSNorm(x))       Mamba-2 or NoPE GQA
              x' = h + r * (MoE(u) + SharedMLP(u)),  u = RMSNorm(h)
    MoE(u)  = sum over the top-k e of the router logits u W_r of
              softmax(top-k logits)_e * (SiLU(u Wg_e) * (u Wu_e)) Wd_e
    hidden  = RMSNorm(x_L) / logits_scaling, so hidden E^T are the logits

with r the residual multiplier. The Mamba-2 mixer: one input projection
into [z | x B C | dt], a depthwise causal convolution with bias over x B
C and SiLU, the selective recurrence one position at a time (state <-
exp(dt A) state + dt x B^T, y = state C + D x, A = -exp(A_log), dt =
softplus(dt + dt_bias)), a gated RMSNorm of y * SiLU(z), the output
projection. The attention: 32 query heads over 8 shared key/value heads,
no positional encoding, scores scaled by ``attention_multiplier``, causal.
The router ranks all ``num_experts``; the layer adds only the experts held
here (``first_expert`` up to ``held_experts`` of them), as the chip does.
Each layer is one jitted call over a block of whole sequences. Matrix
products run at ``precision``; activations and state are kept in
``dtype``.

The weights are laid out as the program's ``decode_step`` reads them
(``ln1``, ``ln2`` and ``ffn`` stacked over all layers, ``mamba`` and
``attn`` over the layers of their kind, embedding rows rounded up to a
multiple of 128); the reference reads the same arrays.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench.reference.dense import embed_rows
from bench.reference.ssm import _rmsnorm, dims


def _counts(m):
    n_attn = list(m.layer_types).count("attention")
    return m.num_layers - n_attn, n_attn


def init(m, key):
    """Seeded weights: embedding N(0, (0.02 / embedding_multiplier)^2),
    so that the scaled input has the 0.02 std of the other configurations'
    embeddings (at N(0, 0.02) the tied output head's own row would win
    every greedy step: each served token would be the token fed);
    projections and the router truncated normal with std 1/sqrt(fan-in),
    the conv bias like the conv weights, A uniform in [1, 16], dt's bias
    the inverse softplus of a log-uniform draw in [dt_min, dt_max]. Call
    under ``jax.jit``."""
    s, di, H, gn = dims(m)
    e = m.moe
    L, D, F, Fs, E = m.num_layers, m.d_model, m.d_ff, e.shared_d_ff, \
        e.held_experts
    Lm, La = _counts(m)
    Hq, Hkv, Dh = m.num_heads, m.num_kv_heads, m.head_dim
    conv_ch = di + 2 * gn
    ks = iter(jax.random.split(key, 18))

    def proj(shape, fan_in):
        return jax.random.truncated_normal(next(ks), -2.0, 2.0, shape,
                                           jnp.float32) / fan_in ** 0.5

    lo, hi = math.log(s.dt_min), math.log(s.dt_max)
    dt0 = jnp.exp(jax.random.uniform(next(ks), (Lm, H)) * (hi - lo) + lo)
    return {
        "embed": {"tok": 0.02 / m.embedding_multiplier * jax.random.normal(
            next(ks), (embed_rows(m.vocab_size), D), jnp.float32)},
        "final_norm": {"scale": jnp.ones((D,), jnp.float32)},
        "blocks": {
            "ln1": {"scale": jnp.ones((L, D), jnp.float32)},
            "ln2": {"scale": jnp.ones((L, D), jnp.float32)},
            "ffn": {
                "router": proj((L, D, e.num_experts), D),
                "gate": proj((L, E, D, F), D),
                "up": proj((L, E, D, F), D),
                "down": proj((L, E, F, D), F),
                "shared": {"gate": proj((L, D, Fs), D),
                           "up": proj((L, D, Fs), D),
                           "down": proj((L, Fs, D), Fs)},
            },
            "mamba": {
                "in_proj": proj((Lm, D, 2 * di + 2 * gn + H), D),
                "conv_w": proj((Lm, s.conv_width, conv_ch), s.conv_width),
                "conv_b": proj((Lm, conv_ch), s.conv_width),
                "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
                "A_log": jnp.log(jax.random.uniform(next(ks), (Lm, H),
                                                    minval=1.0, maxval=16.0)),
                "D": jnp.ones((Lm, H), jnp.float32),
                "gate_norm": jnp.ones((Lm, di), jnp.float32),
                "out_proj": proj((Lm, di, D), di),
            },
            "attn": {
                "wq": proj((La, D, Hq, Dh), D),
                "wk": proj((La, D, Hkv, Dh), D),
                "wv": proj((La, D, Hkv, Dh), D),
                "wo": proj((La, Hq, Dh, D), Hq * Dh),
            },
        },
    }


def _mamba(p, h, m, mm, dtype):
    s, di, H, gn = dims(m)
    R, T, _ = h.shape
    zxbcdt = mm("rtd,de->rte", h, p["in_proj"])
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di:2 * di + 2 * gn]
    dt = zxbcdt[..., 2 * di + 2 * gn:]
    cw = s.conv_width
    padded = jnp.pad(xbc, ((0, 0), (cw - 1, 0), (0, 0)))
    conv = sum(padded[:, j:j + T] * p["conv_w"][j] for j in range(cw))
    xbc = jax.nn.silu(conv + p["conv_b"])
    xs = xbc[..., :di].reshape(R, T, H, s.head_dim)
    Bm = jnp.repeat(xbc[..., di:di + gn].reshape(R, T, s.n_groups, s.d_state),
                    H // s.n_groups, axis=2)
    Cm = jnp.repeat(xbc[..., di + gn:].reshape(R, T, s.n_groups, s.d_state),
                    H // s.n_groups, axis=2)
    dt = jax.nn.softplus(dt + p["dt_bias"])                     # (R, T, H)
    decay = jnp.exp(dt * -jnp.exp(p["A_log"]))

    def step(state, inp):
        x_t, b_t, c_t, dt_t, a_t = inp
        state = (a_t[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return state, mm("rhpn,rhn->rhp", state, c_t)

    state0 = jnp.zeros((R, H, s.head_dim, s.d_state), dtype)
    seq = tuple(jnp.moveaxis(a, 1, 0) for a in (xs, Bm, Cm, dt, decay))
    _, ys = jax.lax.scan(step, state0, seq)
    y = jnp.moveaxis(ys, 0, 1) + p["D"][:, None] * xs
    y = _rmsnorm(y.reshape(R, T, di) * jax.nn.silu(z), p["gate_norm"],
                 m.norm_eps)
    return mm("rte,ed->rtd", y, p["out_proj"])


def _attention(p, h, m, mm, dtype):
    T = h.shape[1]
    group = m.num_heads // m.num_kv_heads
    q = mm("rtd,dhe->rthe", h, p["wq"])
    k = jnp.repeat(mm("rtd,dhe->rthe", h, p["wk"]), group, axis=2)
    v = jnp.repeat(mm("rtd,dhe->rthe", h, p["wv"]), group, axis=2)
    s = mm("rqhe,rkhe->rhqk", q, k) * jnp.asarray(m.attention_multiplier,
                                                  dtype)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s,
                  jnp.asarray(-jnp.inf, dtype))
    o = mm("rhqk,rkhe->rqhe", jax.nn.softmax(s, axis=-1), v)
    return mm("rqhe,hed->rqd", o, p["wo"])


def _swiglu(u, gate, up, down, mm):
    return mm("rtf,fd->rtd", jax.nn.silu(mm("rtd,df->rtf", u, gate))
              * mm("rtd,df->rtf", u, up), down)


def _moe(p, u, m, mm):
    """The held experts' part of the routed mixture, plus the shared
    expert."""
    e = m.moe
    logits = mm("rtd,de->rte", u, p["router"])
    top, chosen = jax.lax.top_k(logits, e.top_k)
    weight = jax.nn.softmax(top, axis=-1)                       # (R, T, k)
    out = _swiglu(u, p["shared"]["gate"], p["shared"]["up"],
                  p["shared"]["down"], mm)
    for j in range(e.held_experts):
        gate = jnp.sum(jnp.where(chosen == e.first_expert + j, weight, 0),
                       axis=-1)
        out = out + gate[..., None] * _swiglu(u, p["gate"][j], p["up"][j],
                                              p["down"][j], mm)
    return out


@functools.partial(jax.jit,
                   static_argnames=("m", "kind", "dtype", "precision"))
def _layer(blocks, i, j, x, *, m, kind, dtype, precision):
    """Layer ``i``, the ``j``-th of its kind."""
    mm = functools.partial(jnp.einsum, precision=precision)
    w = jax.tree.map(lambda a: a[i].astype(dtype),
                     {k: blocks[k] for k in ("ln1", "ln2", "ffn")})
    mixer = jax.tree.map(lambda a: a[j].astype(dtype),
                         blocks["mamba" if kind == "mamba" else "attn"])
    r = jnp.asarray(m.residual_multiplier, dtype)
    h = _rmsnorm(x, w["ln1"]["scale"], m.norm_eps)
    mix = _mamba if kind == "mamba" else _attention
    x = x + r * mix(mixer, h, m, mm, dtype)
    u = _rmsnorm(x, w["ln2"]["scale"], m.norm_eps)
    return x + r * _moe(w["ffn"], u, m, mm)


def hidden(m, params, tokens, *, dtype, precision):
    """tokens (R, T) int32 → final normed hidden states over the logits
    scaling (R, T, D) in ``dtype``, one jitted call per layer."""
    x = params["embed"]["tok"][tokens].astype(dtype) \
        * jnp.asarray(m.embedding_multiplier, dtype)
    seen = {"mamba": 0, "attention": 0}
    for i, kind in enumerate(m.layer_types):
        x = _layer(params["blocks"], i, seen[kind], x, m=m, kind=kind,
                   dtype=dtype, precision=precision)
        seen[kind] += 1
    scale = params["final_norm"]["scale"].astype(dtype)
    return _rmsnorm(x, scale, m.norm_eps) / jnp.asarray(m.logits_scaling,
                                                        dtype)
