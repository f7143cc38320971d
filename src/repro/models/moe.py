"""Mixture-of-experts FFNs: the capacity layer of the ``moe`` family, and
the dropless layer that holds a share of the experts (pattern hybrid).

Capacity layer: top-k routed, GShard/Switch-style dense dispatch.

Dispatch/combine are expressed as einsums against a (T, E, C) one-hot
dispatch tensor — the formulation XLA SPMD partitions well (dispatch
contraction lowers to an all-to-all-free sharded matmul under TP; the true
EP all_to_all variant is the MPKLink-fabric hillclimb, core/fabric.py).

Capacity: C = ceil(capacity_factor · T · k / E); overflow tokens drop to the
residual path (standard). Aux losses: Switch load-balance + router z-loss.

Dropless layer (``init_held_experts`` / ``apply_held_experts``): routes
each token over all ``num_experts`` (top-k of the router logits, a softmax
over those k), and computes the part of the result that the experts this
chip holds give, dropping nothing; every held expert runs on every token,
weighted by a gate that is zero where the token did not choose it, which
reads each held expert's weights once. With every expert held it is the
whole layer; with a share, the other shares' parts are what the chips
holding them add. A shared expert, if any, runs on every token.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import activation, apply_mlp, dense_init, init_mlp


def init_moe(cfg: ModelConfig, key):
    m = cfg.moe
    D, F, E = cfg.d_model, cfg.d_ff, m.num_experts
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], (D, E)),
        "gate": dense_init(ks[1], (E, D, F), in_axis_size=D),
        "up": dense_init(ks[2], (E, D, F), in_axis_size=D),
        "down": dense_init(ks[3], (E, F, D), in_axis_size=F),
    }


def _route(cfg: ModelConfig, p, x_flat, min_capacity: int = 1):
    """x_flat (T, D) → (dispatch (T,E,C), combine (T,E,C), aux dict)."""
    m = cfg.moe
    T = x_flat.shape[0]
    E, k = m.num_experts, m.top_k
    C = max(min_capacity, int(m.capacity_factor * T * k / E))

    logits = (x_flat @ p["router"].astype(x_flat.dtype)).astype(jnp.float32)  # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)

    # top-k choices per token
    top_p, top_e = jax.lax.top_k(probs, k)                    # (T,k)
    top_p = top_p / jnp.maximum(jnp.sum(top_p, -1, keepdims=True), 1e-9)

    # position of each (token, choice) within its expert queue, choice-major so
    # first choices fill capacity before second choices steal slots
    disp = jnp.zeros((T, E, C), jnp.float32)
    comb = jnp.zeros((T, E, C), jnp.float32)
    fill = jnp.zeros((E,), jnp.int32)
    for j in range(k):                                        # static, k=2
        e_j = top_e[:, j]                                     # (T,)
        onehot = jax.nn.one_hot(e_j, E, dtype=jnp.int32)      # (T,E)
        pos_in_e = jnp.cumsum(onehot, axis=0) - onehot + fill[None, :]  # (T,E)
        pos = jnp.sum(pos_in_e * onehot, axis=1)              # (T,)
        keep = pos < C
        slot = jax.nn.one_hot(e_j, E, dtype=jnp.float32)[:, :, None] * \
            jax.nn.one_hot(jnp.where(keep, pos, 0), C, dtype=jnp.float32)[:, None, :]
        slot = slot * keep[:, None, None]
        disp = disp + slot
        comb = comb + slot * top_p[:, j][:, None, None]
        fill = fill + jnp.sum(onehot, axis=0)

    # aux losses
    frac_tokens = jnp.mean(jax.nn.one_hot(top_e[:, 0], E, dtype=jnp.float32), axis=0)
    mean_probs = jnp.mean(probs, axis=0)
    lb = E * jnp.sum(frac_tokens * mean_probs) * m.load_balance_loss
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2) * m.router_z_loss
    dropped = 1.0 - jnp.sum(disp) / (T * k)
    return disp, comb, {"moe_lb_loss": lb, "moe_z_loss": z, "moe_drop_frac": dropped}


def _moe_ffn_flat(cfg: ModelConfig, p, xf, min_capacity: int = 1
                  ) -> Tuple[jnp.ndarray, dict]:
    """One routing group: xf (T, D) → (out (T, D), aux)."""
    act = activation(cfg.act)
    disp, comb, aux = _route(cfg, p, xf, min_capacity)
    d = disp.astype(xf.dtype)
    expert_in = jnp.einsum("tec,td->ecd", d, xf)              # (E,C,D)
    h = act(jnp.einsum("ecd,edf->ecf", expert_in, p["gate"].astype(xf.dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", expert_in, p["up"].astype(xf.dtype))
    out_e = jnp.einsum("ecf,efd->ecd", h, p["down"].astype(xf.dtype))
    y = jnp.einsum("tec,ecd->td", comb.astype(xf.dtype), out_e)
    return y, aux


def apply_moe(cfg: ModelConfig, p, x) -> Tuple[jnp.ndarray, dict]:
    """x (B, S, D) → (out (B,S,D), aux).

    With ``moe.group_size`` set, tokens route in independent groups (GShard):
    the (T,E,C) dispatch einsums are T·E·C_g·D per group — LINEAR in total
    tokens — and stay local to each group's data shard (no cross-shard
    reduction in dispatch/combine). The ungrouped baseline is quadratic and
    all-reduces every dispatch (measured 47 TB/step on mixtral train_4k)."""
    B, S, D = x.shape
    T = B * S
    # decode (S == 1) never drops tokens: capacity covers the worst case so
    # serving matches the full-sequence forward exactly (test_models.py)
    min_cap = T if S == 1 else 1
    g = cfg.moe.group_size
    if not g or T <= g:
        y, aux = _moe_ffn_flat(cfg, p, x.reshape(T, D), min_cap)
        return y.reshape(B, S, D), aux
    assert T % g == 0, (T, g)
    xg = x.reshape(T // g, g, D)

    def per_group(xf):
        return _moe_ffn_flat(cfg, p, xf)

    y, aux = jax.vmap(per_group)(xg)
    aux = {k: jnp.mean(v) for k, v in aux.items()}
    return y.reshape(B, S, D), aux


# ---------------------------------------------------------------------------
# dropless layer over a held share of the experts
# ---------------------------------------------------------------------------

def init_held_experts(cfg: ModelConfig, key):
    m = cfg.moe
    D, F, E = cfg.d_model, cfg.d_ff, m.held
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (D, m.num_experts)),
        "gate": dense_init(ks[1], (E, D, F), in_axis_size=D),
        "up": dense_init(ks[2], (E, D, F), in_axis_size=D),
        "down": dense_init(ks[3], (E, F, D), in_axis_size=F),
    }
    if m.shared_d_ff:
        p["shared"] = init_mlp(cfg, ks[4], d_ff=m.shared_d_ff)
    return p


def route_top_k(cfg: ModelConfig, router, x):
    """x (..., D) → gates (..., num_experts) f32: a softmax over each
    token's top-k router logits, zero for the experts it did not choose."""
    m = cfg.moe
    logits = (x @ router.astype(x.dtype)).astype(jnp.float32)
    top_v, top_e = jax.lax.top_k(logits, m.top_k)
    w = jax.nn.softmax(top_v, axis=-1)
    return jnp.sum(jax.nn.one_hot(top_e, m.num_experts, dtype=jnp.float32)
                   * w[..., None], axis=-2)


def apply_held_experts(cfg: ModelConfig, p, x):
    """x (..., D) → (out (..., D), load (..., held + 1) f32): ``load``
    counts each token's routed choices, one column per held expert and
    the last for the experts held elsewhere."""
    m = cfg.moe
    act = activation(cfg.act)
    lo, hi = m.first_expert, m.first_expert + m.held
    with jax.named_scope("moe.router"):
        gates = route_top_k(cfg, p["router"], x)
        chosen = (gates > 0).astype(jnp.float32)
        load = jnp.concatenate(
            [chosen[..., lo:hi],
             m.top_k - jnp.sum(chosen[..., lo:hi], -1, keepdims=True)], -1)
    with jax.named_scope("moe.experts"):
        g = act(jnp.einsum("...d,edf->...ef", x, p["gate"].astype(x.dtype)))
        h = g * jnp.einsum("...d,edf->...ef", x, p["up"].astype(x.dtype))
        h = h * gates[..., lo:hi, None].astype(x.dtype)
        out = jnp.einsum("...ef,efd->...d", h, p["down"].astype(x.dtype))
    if "shared" in p:
        with jax.named_scope("moe.shared"):
            out = out + apply_mlp(cfg, p["shared"], x)
    return out, load
