"""Expert-parallel MoE over an MPKLink all_to_all channel.

The dense-dispatch MoE (models/moe.py) computes every expert's FFN on every
device with TP-sharded weights. Expert parallelism instead places experts on
devices and moves TOKENS between them — the exchange the paper would call a
microservice interaction: token batches leave one "service" (device group),
cross the fabric through a pre-established protected channel, and return.

Layout (inside shard_map over the expert axis, size ep, E % ep == 0,
le = E/ep local experts):

  route locally → per-expert send slots (E, C, D)
    → all_to_all (split E over devices)   [guarded channel]
    → local experts run their FFN on (ep·C) received rows
    → all_to_all back
    → combine locally

Numerically identical to dense dispatch at equal capacity
(tests/test_moe_ep.py asserts parity on an 8-device mesh).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.domains import DomainKey
from repro.core.fabric import FabricChannel, MPKLinkFabric, all_to_all
from repro.models.layers import activation
from repro.models.moe import _route


def apply_moe_ep(cfg: ModelConfig, local_weights, x_local, *,
                 fabric: MPKLinkFabric, chan: FabricChannel, key: DomainKey,
                 min_capacity: int = 1) -> Tuple[jnp.ndarray, dict]:
    """Call inside shard_map over chan.axis.

    local_weights: {"router" (D,E) replicated, "gate"/"up" (le,D,F),
    "down" (le,F,D)} — expert dims pre-split by shard_map in_specs.
    x_local (B_loc, S, D) → (out (B_loc, S, D), aux)."""
    fabric.check(chan, key)
    ep = jax.lax.axis_size(chan.axis)
    m = cfg.moe
    E = m.num_experts
    assert E % ep == 0, (E, ep)
    le = E // ep

    B, S, D = x_local.shape
    act = activation(cfg.act)
    xf = x_local.reshape(B * S, D)

    disp, comb, aux = _route(cfg, local_weights, xf, min_capacity)
    C = disp.shape[-1]

    # (E, C, D) send slots → all_to_all moves slot-groups to expert owners
    send = jnp.einsum("tec,td->ecd", disp.astype(x_local.dtype), xf)
    recv = all_to_all(fabric, chan, key, send, split_axis=0, concat_axis=1)
    # recv (le, ep·C, D): rows destined for MY experts, grouped by source
    h = act(jnp.einsum("ecd,edf->ecf", recv, local_weights["gate"].astype(x_local.dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", recv, local_weights["up"].astype(x_local.dtype))
    out_e = jnp.einsum("ecf,efd->ecd", h, local_weights["down"].astype(x_local.dtype))
    # return trip: back to the token owners
    back = all_to_all(fabric, chan, key, out_e, split_axis=1, concat_axis=0)
    # back (E, C, D) in the original slot layout
    y = jnp.einsum("tec,ecd->td", comb.astype(x_local.dtype), back)
    return y.reshape(B, S, D), aux


def split_expert_weights(weights, ep: int):
    """Host helper: dense MoE weights → per-device EP slices (for shard_map
    in_specs: P("ep") on the expert dim; router replicated)."""
    return {
        "router": weights["router"],
        "gate": weights["gate"], "up": weights["up"], "down": weights["down"],
    }
