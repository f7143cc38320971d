"""MPKLinkFabric — the paper's protected shared-buffer channels, mapped onto
a TPU mesh.

The baseline model path lets XLA-GSPMD insert generic collectives (the
"network stack"). The fabric is the MPKLink alternative: *explicit*,
pre-established, capability-checked channels between device groups, lowered
to the minimal collective (ppermute / psum_scatter / all_to_all) inside
``shard_map``. Three properties carry over from the paper:

1. **Establishment before use** — a channel is created once (CA-verified
   endpoints, domain allocated, keys issued). Using a channel without its
   key raises AccessViolation *at trace time* — the staging-time PKRU.
2. **Guarded transfer** — optionally every message carries a MAC row seeded
   by domain tag ⊕ epoch; receivers verify on-device (kernels/mpk_guard on
   TPU, mac_ref in the jnp path) and surface an ok-flag that the runtime's
   fault-tolerance layer consumes (a failed guard triggers step retry —
   corrupted-collective detection).
3. **Explicit sync schedule** — ring collectives are built from chained
   ppermutes, so the number of neighbor exchanges per step is a visible,
   tunable quantity (the paper's per-chunk key-sync count), not compiler
   magic. The §Perf hillclimb tunes exactly this.

All functions here must be called INSIDE shard_map with the named axis
present. (jax.lax.psum etc. with axis names.)
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.ca import CertificateAuthority, enroll
from repro.core.domains import (AccessViolation, DomainKey, KeyRegistry,
                                ProtectionDomain, RW, mac_seed)
from repro.kernels.ref import mac_ref
from repro.utils import match_vma

LANES = 128


# ---------------------------------------------------------------------------
# channel establishment (host / trace time)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class FabricChannel:
    name: str
    axis: str                  # mesh axis the channel spans
    domain: ProtectionDomain
    epoch: int
    guard: bool                # runtime MAC verification on/off

    @property
    def seed(self) -> int:
        return mac_seed(self.domain, self.epoch)


class MPKLinkFabric:
    def __init__(self, mesh, *, guard: bool = False, max_channels: int = 64):
        self.mesh = mesh
        self.guard = guard
        # TPUs have no 16-domain hardware limit; allow more channels (DESIGN.md)
        self.registry = KeyRegistry(max_keys=max_channels)
        self.ca = CertificateAuthority(self.registry)
        self._keys = {}

    def establish(self, name: str, axis: str,
                  guard: Optional[bool] = None) -> Tuple[FabricChannel, DomainKey]:
        """CA-verified channel over a mesh axis. Returns (channel, key)."""
        a, b = f"{name}@{axis}:even", f"{name}@{axis}:odd"
        enroll(self.ca, a)
        enroll(self.ca, b)
        dom, key, _ = self.ca.grant_channel(a, b, RW)
        chan = FabricChannel(name, axis, dom, self.registry.epoch(dom),
                             self.guard if guard is None else guard)
        self._keys[(name, axis)] = key
        return chan, key

    def check(self, chan: FabricChannel, key: DomainKey, rights: int = RW):
        """Trace-time capability check — the zero-cost PKRU analogue."""
        self.registry.check(key, rights)
        if key.domain != chan.domain:
            raise AccessViolation(
                f"key for domain {key.domain.name} used on channel {chan.name}")

    def revoke(self, chan: FabricChannel):
        key = self._keys.pop((chan.name, chan.axis), None)
        if key is not None:
            self.registry.revoke(key)


# ---------------------------------------------------------------------------
# on-device guard (MAC attach / verify)
# ---------------------------------------------------------------------------

def _as_u32_rows(x: jnp.ndarray) -> jnp.ndarray:
    """Bitcast any tensor to (rows, 128) uint32, zero-padded."""
    flat = x.reshape(-1)
    nbits = flat.dtype.itemsize * 8
    if nbits == 32:
        u = jax.lax.bitcast_convert_type(flat, jnp.uint32)
    elif nbits == 16:
        if flat.shape[0] % 2:
            flat = jnp.concatenate([flat, jnp.zeros((1,), flat.dtype)])
        u = jax.lax.bitcast_convert_type(flat.reshape(-1, 2), jnp.uint32)
    elif nbits == 64:
        u = jax.lax.bitcast_convert_type(flat, jnp.uint64)
        u = jnp.stack([(u & 0xFFFFFFFF).astype(jnp.uint32),
                       (u >> 32).astype(jnp.uint32)], -1).reshape(-1)
    else:
        raise ValueError(f"unsupported itemsize {nbits}")
    pad = (-u.shape[0]) % LANES
    if pad:
        u = jnp.concatenate([u, jnp.zeros((pad,), jnp.uint32)])
    return u.reshape(-1, LANES)


def attach_mac(x: jnp.ndarray, seed: int) -> jnp.ndarray:
    """MAC of x's bits under the channel seed (scalar uint32)."""
    return mac_ref(_as_u32_rows(x), jnp.uint32(seed))


def verify_mac(x: jnp.ndarray, mac: jnp.ndarray, seed: int) -> jnp.ndarray:
    """→ ok flag (int32 scalar). Runtime consumes it for retry-on-corruption."""
    return (attach_mac(x, seed) == mac).astype(jnp.int32)


# ---------------------------------------------------------------------------
# guarded collectives (call inside shard_map)
# ---------------------------------------------------------------------------

def _perm(axis_size: int, shift: int):
    return [(i, (i + shift) % axis_size) for i in range(axis_size)]


def neighbor_exchange(fabric: MPKLinkFabric, chan: FabricChannel, key: DomainKey,
                      x: jnp.ndarray, *, shift: int = 1):
    """Ring shift over chan.axis with capability check + optional MAC guard.
    Returns (received, ok_flag)."""
    fabric.check(chan, key)
    n = jax.lax.axis_size(chan.axis)
    perm = _perm(n, shift)
    if not chan.guard:
        return jax.lax.ppermute(x, chan.axis, perm), jnp.int32(1)
    mac = attach_mac(x, chan.seed)
    y = jax.lax.ppermute(x, chan.axis, perm)
    mac_y = jax.lax.ppermute(mac, chan.axis, perm)
    return y, verify_mac(y, mac_y, chan.seed)


def ring_all_gather(fabric: MPKLinkFabric, chan: FabricChannel, key: DomainKey,
                    x: jnp.ndarray, *, axis_index: Optional[jnp.ndarray] = None):
    """All-gather built from n-1 chained neighbor pushes (bandwidth-optimal
    ring; each step is an MPKLink channel hop). Returns (gathered, ok)."""
    fabric.check(chan, key)
    n = jax.lax.axis_size(chan.axis)
    idx = jax.lax.axis_index(chan.axis) if axis_index is None else axis_index

    def body(carry, _):
        buf, cur, ok = carry
        cur, ok_i = neighbor_exchange(fabric, chan, key, cur, shift=1)
        return (buf, cur, ok & ok_i), cur

    init = (x, x, match_vma(jnp.int32(1), x))
    (_, _, ok), rest = jax.lax.scan(body, init, None, length=n - 1)
    # piece j originated at device (idx - j) mod n; roll into position
    parts = jnp.concatenate([x[None], rest], axis=0)         # (n, ...) by hop count
    order = (idx - jnp.arange(n)) % n
    gathered = jnp.zeros((n,) + x.shape, x.dtype).at[order].set(parts)
    return gathered.reshape((n * x.shape[0],) + x.shape[1:]), ok


def reduce_scatter_ring(fabric: MPKLinkFabric, chan: FabricChannel, key: DomainKey,
                        x: jnp.ndarray):
    """Ring reduce-scatter over leading dim (must be divisible by axis size).
    n-1 hops, each hop sends one shard — the collective the §Perf pass uses
    to replace all-reduce where only shards are needed. Returns (shard, ok)."""
    fabric.check(chan, key)
    n = jax.lax.axis_size(chan.axis)
    idx = jax.lax.axis_index(chan.axis)
    shards = x.reshape((n, x.shape[0] // n) + x.shape[1:])

    def body(carry, j):
        acc, ok = carry
        # step j: push the partial for chunk (idx-1-j); what arrives is the
        # partial for chunk (idx-2-j), which is what we push next — after
        # n-1 hops the arriving partial is chunk idx summed over all peers.
        send = jnp.take(shards, (idx - 1 - j) % n, axis=0) + acc
        recv, ok_i = neighbor_exchange(fabric, chan, key, send, shift=1)
        return (recv, ok & ok_i), None

    (acc, ok), _ = jax.lax.scan(
        body, match_vma((jnp.zeros(shards.shape[1:], x.dtype), jnp.int32(1)), x),
        jnp.arange(n - 1))
    own = jnp.take(shards, idx, axis=0)
    return own + acc, ok


def all_to_all(fabric: MPKLinkFabric, chan: FabricChannel, key: DomainKey,
               x: jnp.ndarray, *, split_axis: int, concat_axis: int):
    """EP dispatch/return channel (mixtral/grok token exchange)."""
    fabric.check(chan, key)
    return jax.lax.all_to_all(x, chan.axis, split_axis, concat_axis, tiled=True)


def psum_guarded(fabric: MPKLinkFabric, chan: FabricChannel, key: DomainKey,
                 x: jnp.ndarray):
    fabric.check(chan, key)
    return jax.lax.psum(x, chan.axis)
