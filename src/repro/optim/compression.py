"""Gradient compression for the slow cross-pod link: int8 quantization with
error feedback.

Scheme (per tensor, inside shard_map over the ``pod`` axis):
  1. reduce-scatter the raw gradient over the pod axis (bf16/f32) — the
     reduction leg stays exact;
  2. add the local error-feedback residual, quantize the local shard to int8
     with one f32 scale per tensor (symmetric, max-abs);
  3. all-gather the INT8 shards (+ scales) — this leg moves 4× fewer bytes
     than f32 / 2× fewer than bf16, which is where cross-DCI bandwidth goes;
  4. dequantize; the residual (what quantization lost) is carried to the
     next step (error feedback keeps the scheme unbiased over time).

On a 2-pod mesh the all-gather leg is half the all-reduce traffic, so this
cuts cross-pod bytes ≈ 1.6-1.9× total (EXPERIMENTS.md §Perf measures it via
HLO collective bytes).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def quantize_int8(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale


def init_error_feedback(grads, axis_size: int):
    """EF residual holds the LOCAL reduce-scatter shard (leading dim / n)."""
    def shard_zeros(g):
        lead = g.shape[0] // axis_size if g.ndim and g.shape[0] % axis_size == 0 \
            else g.shape[0] if g.ndim else 1
        shape = (lead,) + tuple(g.shape[1:]) if g.ndim else (1,)
        return jnp.zeros(shape, jnp.float32)
    return jax.tree.map(shard_zeros, grads)


def compressed_reduce(g: jnp.ndarray, ef: jnp.ndarray, axis: str):
    """All-reduce-mean of one tensor over ``axis`` with an int8 all-gather leg.
    Call inside shard_map. Falls back to exact psum when the leading dim
    doesn't tile. → (reduced (same shape as g), new_ef)."""
    n = jax.lax.axis_size(axis)
    if g.ndim == 0 or g.shape[0] % n != 0:
        return jax.lax.pmean(g, axis), ef

    rs = jax.lax.psum_scatter(g.astype(jnp.float32), axis,
                              scatter_dimension=0, tiled=True) / n
    q, scale = quantize_int8(rs + ef)
    new_ef = (rs + ef) - dequantize_int8(q, scale)
    qg = jax.lax.all_gather(q, axis, tiled=True)
    sg = jax.lax.all_gather(scale[None], axis)                     # (n,)
    idx = jnp.repeat(jnp.arange(n), rs.shape[0])
    deq = qg.astype(jnp.float32) * sg[idx].reshape(
        (-1,) + (1,) * (qg.ndim - 1))
    return deq.astype(g.dtype), new_ef


def compressed_tree_reduce(grads, ef_tree, axis: str):
    """Tree version: → (reduced_grads, new_ef_tree)."""
    flat_g, treedef = jax.tree.flatten(grads)
    flat_e = jax.tree.leaves(ef_tree)
    out = [compressed_reduce(g, e, axis) for g, e in zip(flat_g, flat_e)]
    return (jax.tree.unflatten(treedef, [o[0] for o in out]),
            jax.tree.unflatten(treedef, [o[1] for o in out]))
