"""KV-cache / recurrent-state structures for serving.

Three cache kinds, all pure pytrees:

* ``dense``  — (B, S_max, H_kv, Dh) K/V per layer; supports full and windowed
               attention; sequence dim is the context-parallel shard axis.
* ``ring``   — (B, W, H_kv, Dh) sliding-window ring buffer (SWA archs at 500k:
               O(W) memory instead of O(S)). Slot positions are tracked so
               masking stays exact.
* ``ssm``    — Mamba2 conv tail + SSD state, O(1) in sequence length.

Caches for a layer stack are stacked on a leading L axis and carried through
the layer scan: each insert takes ``layer`` and writes that layer of the
stack by index, and ``cache_layer`` reads one, so a donated state is updated
in place.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


# -- dense ------------------------------------------------------------------

def init_dense_cache(batch: int, max_seq: int, n_kv: int, head_dim: int, dtype):
    return {
        "k": jnp.zeros((batch, max_seq, n_kv, head_dim), dtype),
        "v": jnp.zeros((batch, max_seq, n_kv, head_dim), dtype),
    }


def _lead(layer):
    """Index prefix of a layer of a stacked cache (none for one layer's)."""
    return () if layer is None else (layer,)


def _write(c, x, lead, idx):
    """``c`` with ``x`` written at ``idx``, in layer ``lead`` of a stack."""
    x = x.reshape((1,) * len(lead) + x.shape).astype(c.dtype)
    return jax.lax.dynamic_update_slice(c, x, (*lead, *idx))


def dense_cache_insert(cache, k_new, v_new, pos: jnp.ndarray, layer=None):
    """Insert (B, S_new, H, D) at sequence offset ``pos`` (scalar int32);
    into layer ``layer`` of a stacked cache where that is given."""
    lead, idx = _lead(layer), (0, pos, 0, 0)
    return {"k": _write(cache["k"], k_new, lead, idx),
            "v": _write(cache["v"], v_new, lead, idx)}


def dense_cache_positions(cache, length: jnp.ndarray):
    """kv positions (S_max,) with slots >= length masked as -1."""
    s = cache["k"].shape[1]
    pos = jnp.arange(s, dtype=jnp.int32)
    return jnp.where(pos < length, pos, -1)


def dense_cache_insert_rows(cache, k_new, v_new, pos_b: jnp.ndarray,
                            layer=None):
    """Per-slot insert for continuous batching: row b gets its token at its
    own position pos_b[b]. k_new/v_new (B, 1, H, D); pos_b (B,) int32; a
    position past the end writes the last row, as a clamped slice would.

    ``layer`` given: ``cache`` is the stack of every layer's cache
    (L, B, S_max, H, D) and only that layer's B rows are written — in
    place where the stack is a loop carry of a donated state, so a step
    writes its new rows and copies no layer."""
    rows = (*_lead(layer), jnp.arange(k_new.shape[0]),
            pos_b.astype(jnp.int32))

    def put(c, x):
        return c.at[rows].set(x[:, 0].astype(c.dtype), mode="clip",
                              unique_indices=True)

    return {"k": put(cache["k"], k_new), "v": put(cache["v"], v_new)}


def cache_layer(stack, layer):
    """Layer ``layer`` of a stacked cache, read by index (None: ``stack``
    is one layer's cache already). Inside a jitted step the read fuses
    into its consumers: no copy of the layer is made."""
    if layer is None:
        return stack
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer, keepdims=False),
        stack)


def cache_put_layer(stack, cache, layer):
    """``stack`` with layer ``layer`` replaced by ``cache`` (one layer's)."""
    return jax.tree.map(
        lambda a, c: jax.lax.dynamic_update_index_in_dim(
            a, c.astype(a.dtype), layer, 0), stack, cache)


def dense_cache_positions_rows(cache, lengths: jnp.ndarray):
    """(B, S_max) kv positions with per-row valid lengths."""
    s = cache["k"].shape[1]
    pos = jnp.arange(s, dtype=jnp.int32)[None]
    return jnp.where(pos < lengths.astype(jnp.int32)[:, None], pos, -1)


# -- ring (SWA) ---------------------------------------------------------------

def init_ring_cache(batch: int, window: int, n_kv: int, head_dim: int, dtype):
    return {
        "k": jnp.zeros((batch, window, n_kv, head_dim), dtype),
        "v": jnp.zeros((batch, window, n_kv, head_dim), dtype),
        "slot_pos": jnp.full((window,), -1, jnp.int32),   # absolute position per slot
    }


def ring_cache_insert(cache, k_new, v_new, pos: jnp.ndarray, layer=None):
    """Insert a single token (B, 1, H, D) at absolute position ``pos``;
    into layer ``layer`` of a stacked cache where that is given."""
    lead = _lead(layer)
    slot = jnp.mod(pos, cache["k"].shape[len(lead) + 1])
    return {"k": _write(cache["k"], k_new, lead, (0, slot, 0, 0)),
            "v": _write(cache["v"], v_new, lead, (0, slot, 0, 0)),
            "slot_pos": _write(cache["slot_pos"], pos[None], lead, (slot,))}


# -- ssm ----------------------------------------------------------------------

def init_ssm_state(batch: int, n_heads: int, head_dim: int, d_state: int,
                   conv_width: int, conv_channels: int, dtype):
    return {
        "ssd": jnp.zeros((batch, n_heads, head_dim, d_state), jnp.float32),
        "conv": jnp.zeros((batch, conv_width - 1, conv_channels), dtype),
    }


# -- assembly -----------------------------------------------------------------

def stack_caches(caches):
    """[cache_pytree] * L → one pytree with leading L axis (scan-ready)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *caches)
