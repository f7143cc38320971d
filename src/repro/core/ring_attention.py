"""Ring attention — sequence/context parallelism over MPKLink channels.

Q, K, V are sharded along the SEQUENCE dim across the channel's mesh axis.
Each of the n ring steps computes a local flash partial (out, lse) for the
resident KV block, then rotates the KV block (and its positions) to the
next neighbor through the guarded channel — after n steps every Q shard has
attended to the full sequence while only ever holding 1/n of K/V.

This is the paper's pattern at pod scale: instead of the compiler's global
all-gather of K/V ("the network stack"), n-1 explicit neighbor pushes
through a pre-established protected channel move exactly the bytes the
algorithm needs. It is also the escape hatch for attention shapes TP can't
shard (non-divisible head counts — smollm/whisper): shard the sequence
instead of heads.

Forward-only (serving/prefill); partials merge by the standard logsumexp
rule. Validated against the full-attention oracle on an 8-device mesh
(tests/test_ring_attention.py).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.fabric import FabricChannel, MPKLinkFabric, neighbor_exchange
from repro.core.domains import DomainKey
from repro.kernels.flash_jnp import _fwd_core, _pad_to
from repro.kernels.ref import NEG_INF
from repro.utils import match_vma


def _merge(out1, lse1, out2, lse2):
    """Combine two attention partials over the same queries."""
    m = jnp.maximum(lse1, lse2)
    m_safe = jnp.maximum(m, NEG_INF / 2)
    w1 = jnp.exp(lse1 - m_safe)
    w2 = jnp.exp(lse2 - m_safe)
    denom = jnp.maximum(w1 + w2, 1e-30)
    out = (out1 * w1[..., None] + out2 * w2[..., None]) / denom[..., None]
    lse = jnp.where(m > NEG_INF / 2, m_safe + jnp.log(denom), NEG_INF)
    return out, lse


def ring_attention(fabric: MPKLinkFabric, chan: FabricChannel, key: DomainKey,
                   q, k, v, q_pos, kv_pos, *, causal: bool = True,
                   window: Optional[int] = None, q_chunk: int = 128,
                   kv_chunk: int = 128):
    """Call inside shard_map with q/k/v sequence-sharded over chan.axis.

    q (B, Sq_loc, H, Dh); k/v (B, Skv_loc, Hkv, Dh); positions (B, S*_loc)
    hold ABSOLUTE positions (so causal/window masks stay exact across
    blocks). → (out (B, Sq_loc, H, Dh), ok flag)."""
    fabric.check(chan, key)
    n = jax.lax.axis_size(chan.axis)
    B, Sq, H, Dh = q.shape

    qc = min(q_chunk, Sq)
    kc = min(kv_chunk, k.shape[1])
    qp = _pad_to(q_pos.astype(jnp.int32), 1, qc, -2)
    qpad = _pad_to(q, 1, qc, 0)

    def local_partial(kb, vb, kpb):
        kp = _pad_to(kpb.astype(jnp.int32), 1, kc, -1)
        out, lse = _fwd_core(qpad, _pad_to(kb, 1, kc, 0), _pad_to(vb, 1, kc, 0),
                             qp, kp, causal, window, qc, kc)
        return out, lse

    out, lse = local_partial(k, v, kv_pos)

    def step(carry, _):
        out, lse, kb, vb, kpb, ok = carry
        kb, ok1 = neighbor_exchange(fabric, chan, key, kb, shift=1)
        vb, ok2 = neighbor_exchange(fabric, chan, key, vb, shift=1)
        kpb, ok3 = neighbor_exchange(fabric, chan, key, kpb, shift=1)
        o2, l2 = local_partial(kb, vb, kpb)
        out, lse = _merge(out, lse, o2, l2)
        return (out, lse, kb, vb, kpb, ok & ok1 & ok2 & ok3), None

    init = (out, lse, k, v, kv_pos.astype(jnp.int32),
            match_vma(jnp.int32(1), q))
    (out, lse, _, _, _, ok), _ = jax.lax.scan(step, init, None, length=n - 1)
    out = out[:, :Sq].astype(q.dtype)
    out = jnp.where(q_pos[:, :, None, None] < 0, 0, out)
    return out, ok
