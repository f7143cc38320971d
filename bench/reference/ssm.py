"""Plain reference of a Mamba-2 stack (Mamba2-1.3B): weights and forward.

Written from the Mamba-2 paper (arXiv:2405.21060) and its reference
block, not from the program: RMSNorm, one input projection into
[z | x B C | dt], a depthwise causal convolution of width 4 over x B C
followed by SiLU, the selective state-space recurrence taken one position
at a time (state <- exp(dt A) state + dt x B^T, y = state C + D x), a
gated RMSNorm of y * SiLU(z), the output projection, and tied embeddings.
Each layer is one jitted call over a block of whole sequences. Matrix
products run at ``precision``; the state is kept in ``dtype``.

The weights are laid out as the program's ``decode_step`` reads them
(stacked on a leading layer axis, embedding rows rounded up to a multiple
of 128); the reference reads the same arrays.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench.reference.dense import embed_rows


def dims(m):
    s = m.ssm
    di = s.expand * m.d_model
    heads = di // s.head_dim
    gn = s.n_groups * s.d_state
    return s, di, heads, gn


def init(m, key):
    """Seeded weights. A is uniform in [1, 16] (the published init range),
    dt's bias is the inverse softplus of a log-uniform draw in [dt_min,
    dt_max]. Call under ``jax.jit``."""
    s, di, H, gn = dims(m)
    L, D = m.num_layers, m.d_model
    conv_ch = di + 2 * gn
    ks = jax.random.split(key, 6)

    def proj(k, shape, fan_in):
        return jax.random.truncated_normal(k, -2.0, 2.0, shape,
                                           jnp.float32) / fan_in ** 0.5

    lo, hi = math.log(s.dt_min), math.log(s.dt_max)
    dt0 = jnp.exp(jax.random.uniform(ks[3], (L, H)) * (hi - lo) + lo)
    return {
        "embed": {"tok": 0.02 * jax.random.normal(
            ks[0], (embed_rows(m.vocab_size), D), jnp.float32)},
        "final_norm": {"scale": jnp.ones((D,), jnp.float32)},
        "blocks": {
            "ln1": {"scale": jnp.ones((L, D), jnp.float32)},
            "mamba": {
                "in_proj": proj(ks[1], (L, D, 2 * di + 2 * gn + H), D),
                "conv_w": proj(ks[2], (L, s.conv_width, conv_ch), s.conv_width),
                "conv_b": jnp.zeros((L, conv_ch), jnp.float32),
                "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
                "A_log": jnp.log(jax.random.uniform(ks[4], (L, H),
                                                    minval=1.0, maxval=16.0)),
                "D": jnp.ones((L, H), jnp.float32),
                "gate_norm": jnp.ones((L, di), jnp.float32),
                "out_proj": proj(ks[5], (L, di, D), di),
            },
        },
    }


def _rmsnorm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * scale


@functools.partial(jax.jit, static_argnames=("m", "dtype", "precision"))
def _layer(blocks, i, x, *, m, dtype, precision):
    w = jax.tree.map(lambda a: a[i].astype(dtype), blocks)
    p = w["mamba"]
    s, di, H, gn = dims(m)
    R, T, _ = x.shape
    mm = functools.partial(jnp.einsum, precision=precision)
    h = _rmsnorm(x, w["ln1"]["scale"], m.norm_eps)
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
    decay = jnp.exp(dt * -jnp.exp(p["A_log"]))                  # (R, T, H)

    def step(state, inp):
        x_t, b_t, c_t, dt_t, a_t = inp
        state = (a_t[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return state, mm("rhpn,rhn->rhp", state, c_t)

    state0 = jnp.zeros((R, H, s.head_dim, s.d_state), dtype)
    seq = tuple(jnp.moveaxis(a, 1, 0) for a in (xs, Bm, Cm, dt, decay))
    _, ys = jax.lax.scan(step, state0, seq)
    y = jnp.moveaxis(ys, 0, 1) + p["D"][:, None] * xs          # (R,T,H,P)
    y = _rmsnorm(y.reshape(R, T, di) * jax.nn.silu(z), p["gate_norm"],
                 m.norm_eps)
    return x + mm("rte,ed->rtd", y, p["out_proj"])


def hidden(m, params, tokens, *, dtype, precision):
    """tokens (R, T) int32 → final normed hidden states (R, T, D) in
    ``dtype``, one jitted call per layer."""
    x = params["embed"]["tok"][tokens].astype(dtype)
    for i in range(m.num_layers):
        x = _layer(params["blocks"], i, x, m=m, dtype=dtype,
                   precision=precision)
    scale = params["final_norm"]["scale"].astype(dtype)
    return _rmsnorm(x, scale, m.norm_eps)
