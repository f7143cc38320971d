"""Pipeline parallelism: GPipe schedule with MPKLink stage-handoff channels.

Layers are split into contiguous stages sharded over a mesh axis; at each
tick every stage runs its layer slice on one microbatch and pushes the
activation to its successor through a guarded neighbor channel — the
paper's "microservice interaction" at its most literal: stage s and stage
s+1 are co-located services exchanging one message per tick over a
pre-established protected channel instead of a compiler-scheduled
collective.

Schedule: n_micro + n_stages − 1 ticks, the classic GPipe bubble. The whole
pipeline is one differentiable scan (ppermute transposes cleanly), so
jax.grad through it yields the GPipe backward automatically.

Dense/VLM blocks only (MoE inside a stage would nest EP; compose
models/moe_ep.py per stage for that). Verified against the single-device
layer stack in tests/test_pipeline.py.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.domains import DomainKey
from repro.core.fabric import FabricChannel, MPKLinkFabric, neighbor_exchange
from repro.models.transformer import Impl, apply_stack
from repro.utils import match_vma


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _broadcast_from(x, axis, src):
    """psum-broadcast ``x`` from shard ``src`` to every shard of ``axis``.

    Explicit VJP because the transpose of a masked psum is version-dependent:
    pre-0.5 shard_map transposes psum to psum, which multiplies the cotangent
    by the axis size when the downstream loss is computed redundantly on the
    replicated output. The true adjoint — cotangent masked back to the source
    shard — is spelled out here so gradients are right on every jax pin."""
    sid = jax.lax.axis_index(axis)
    return jax.lax.psum(jnp.where(sid == src, x, jnp.zeros_like(x)), axis)


def _broadcast_from_fwd(x, axis, src):
    return _broadcast_from(x, axis, src), None


def _broadcast_from_bwd(axis, src, _res, ct):
    sid = jax.lax.axis_index(axis)
    return (jnp.where(sid == src, ct, jnp.zeros_like(ct)),)


_broadcast_from.defvjp(_broadcast_from_fwd, _broadcast_from_bwd)


def pipeline_apply(cfg: ModelConfig, local_params, x_micro, *,
                   fabric: MPKLinkFabric, chan: FabricChannel, key: DomainKey,
                   impl: Impl) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Call inside shard_map over chan.axis (the stage axis).

    local_params: block stack sliced per stage — leading dims
    (1, L/n_stages, ...). x_micro (n_micro, mb, S, D) replicated (stage 0
    consumes it). → (outputs (n_micro, mb, S, D) — valid everywhere after a
    final broadcast from the last stage, ok flag)."""
    fabric.check(chan, key)
    assert not cfg.moe, "pipeline stages compose with moe_ep, not dense MoE"
    n = jax.lax.axis_size(chan.axis)
    sid = jax.lax.axis_index(chan.axis)
    params = jax.tree.map(lambda a: a[0], local_params)      # (L/n, ...)
    n_micro, mb, S, D = x_micro.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (mb, S))
    T = n_micro + n - 1

    # a stage holds L/n layers of a one-kind stack
    kinds = cfg.mixers[:jax.tree.leaves(params)[0].shape[0]]

    def run_stage(h):
        return apply_stack(cfg, params, h, positions=positions, impl=impl,
                           kinds=kinds)[0]

    def tick(carry, t):
        held, ok = carry
        # stage 0 injects microbatch t (clipped; masked after n_micro)
        inject = x_micro[jnp.clip(t, 0, n_micro - 1)]
        h_in = jnp.where(sid == 0, inject, held)
        h_out = run_stage(h_in)
        # guarded push to the next stage (ring wrap: stage 0 ignores what
        # the last stage sends back — it injects instead)
        held_next, ok_i = neighbor_exchange(fabric, chan, key, h_out, shift=1)
        return (held_next, ok & ok_i), h_out

    # anchor the carry's varying axes on the stage-sharded params (x_micro is
    # replicated, so it carries no VMA)
    anchor = jax.tree.leaves(params)[0]
    held0 = match_vma(jnp.zeros((mb, S, D), x_micro.dtype), anchor)
    ok0 = match_vma(jnp.int32(1), anchor)
    (_, ok), emits = jax.lax.scan(tick, (held0, ok0), jnp.arange(T))

    # microbatch m exits the last stage at tick m + n - 1
    outs = emits[n - 1:]                                     # (n_micro, mb, S, D)
    outs = _broadcast_from(outs, chan.axis, n - 1)
    return outs, ok


def stage_split(stacked_params, n_stages: int):
    """Host helper: (L, ...) block stack → (n_stages, L/n, ...) for
    shard_map in_specs P("stage") on dim 0."""
    def split(a):
        L = a.shape[0]
        assert L % n_stages == 0, (L, n_stages)
        return a.reshape((n_stages, L // n_stages) + a.shape[1:])
    return jax.tree.map(split, stacked_params)
