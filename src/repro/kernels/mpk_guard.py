"""mpk_guard — the MPKLink data plane as a Pallas TPU kernel.

The paper's hot spot is the *protected copy*: moving a message through a
shared region while enforcing access control and authenticity. On x86 that
is pkey-tagged pages + PKRU checks + a signature pass. On TPU we fuse all
three into the copy itself:

  * the channel's domain **tag** seeds the MAC state, so a receiver holding
    the wrong key computes a wrong MAC — access control and authentication
    collapse into one check;
  * a 128-lane **Horner MAC** is updated per tile while it is resident in
    VMEM, then folded with a precomputed power vector (Σ h_i·P^(127-i),
    algebraically identical to scalar Horner but one vector multiply-add —
    no 128-step scalar loop on the VPU);
  * the payload is **copied** HBM→VMEM→HBM tile by tile.

The MAC arithmetic rides under the tile loads: the kernel stays memory-bound,
so authenticated transport costs ≈ a plain copy (benchmarks/kernel_bench.py
measures exactly this delta — the paper's Table-X "security for free" claim).

Grid is 1-D over row tiles, sequential; the MAC state is VMEM scratch.
Validated in interpret mode against ref.mac_ref / ref.guard_copy_ref, and
compiled for the chip by tests/test_tpu_compile.py.

Batch variant (the pipelined data plane): :func:`mac_batch_pallas` MACs a
whole (N, rows, 128) stack of frames in one launch — grid (N, row-tiles),
one VMEM Horner state per frame, N MAC words out. :func:`mac_batch_jnp` is
the shape-polymorphic jnp twin. Both are bit-identical to
``core.framing.mac_batch`` (the host path the transports use) and to the
scalar ``ref.mac_ref`` — tests/test_batching.py asserts all four agree.

Streaming variant (the zero-copy seal path): :func:`mac_init_state` /
:func:`mac_update_pallas` / :func:`mac_update_jnp` / :func:`mac_finalize`
expose the Horner recurrence as an explicit running state, so a large
payload is MAC'd block-wise as each chunk lands in the region — no staging
copy of the whole message. Feeding the blocks of a payload through
``mac_update`` and folding with ``mac_finalize`` is bit-identical to one
``mac_ref`` pass over the concatenation (tests/test_zero_copy.py asserts
it for pallas, jnp and the host twins in ``core.framing``).
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import MAC_PRIME, MAC_INIT
from repro.utils import pallas_interpret

LANES = 128


def _fold_powers() -> np.ndarray:
    """PRIME^(127-i) mod 2^32 for the vectorized Horner fold."""
    p = np.uint64(MAC_PRIME)
    out = np.zeros(LANES, np.uint64)
    acc = np.uint64(1)
    for i in range(LANES - 1, -1, -1):
        out[i] = acc
        acc = (acc * p) & np.uint64(0xFFFFFFFF)
    return out.astype(np.uint32)


FOLD_POWERS = _fold_powers()


def _fold_i32(acc, powers):
    """Σ acc_i·P^(127-i) mod 2^32 as an int32 scalar. Mosaic reduces signed
    integers only; wrapping int32 addition is the same sum mod 2^32, so the
    bits equal the uint32 fold of ``mac_finalize``."""
    return jnp.sum(jax.lax.bitcast_convert_type(acc * powers, jnp.int32))


def _guard_kernel(tag_ref, expect_ref, powers_ref, in_ref, out_ref, mac_ref,
                  ok_ref, h, *, rows_per_tile):
    i = pl.program_id(0)
    n = pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        h[...] = (jnp.full((1, LANES), MAC_INIT, jnp.uint32)
                  + tag_ref[0].astype(jnp.uint32))

    tile = in_ref[...]                                  # (rows, 128) uint32
    acc = h[0, :]
    for r in range(rows_per_tile):                      # static unroll
        acc = acc * MAC_PRIME + tile[r, :]
    h[0, :] = acc
    out_ref[...] = tile                                 # the copy

    @pl.when(i == n - 1)
    def _final():
        mac = _fold_i32(h[0, :], powers_ref[...])
        mac_ref[0] = mac
        ok_ref[0] = (mac == expect_ref[0]).astype(jnp.int32)


def _as_i32(x):
    """A uint32 word → its (1,) int32 bit pattern, for an SMEM operand."""
    return jax.lax.bitcast_convert_type(
        jnp.asarray(x).astype(jnp.uint32).reshape(-1), jnp.int32)


def guard_copy_pallas(payload_u32, tag, expected_mac, *, rows_per_tile=256):
    """payload (n, 128) uint32 with n % rows_per_tile == 0 (ops.py pads).
    Returns (copy, mac (1,) uint32, ok (1,) int32). The scalars ride in
    SMEM as int32 bit patterns."""
    n, lanes = payload_u32.shape
    assert lanes == LANES and payload_u32.dtype == jnp.uint32
    rt = min(rows_per_tile, n)
    assert n % rt == 0, (n, rt)
    grid = (n // rt,)
    kernel = functools.partial(_guard_kernel, rows_per_tile=rt)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    out, mac, ok = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            smem,                                       # tag
            smem,                                       # expected mac
            pl.BlockSpec((LANES,), lambda i: (0,)),     # fold powers
            pl.BlockSpec((rt, LANES), lambda i: (i, 0)),
        ],
        out_specs=[pl.BlockSpec((rt, LANES), lambda i: (i, 0)), smem, smem],
        out_shape=[
            jax.ShapeDtypeStruct((n, LANES), jnp.uint32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((1, LANES), jnp.uint32)],
        interpret=pallas_interpret(),
    )(_as_i32(tag), _as_i32(expected_mac), jnp.asarray(FOLD_POWERS),
      payload_u32)
    return out, jax.lax.bitcast_convert_type(mac, jnp.uint32), ok


# ---------------------------------------------------------------------------
# batched MAC: N frames in one launch (the vectorized data-plane pass)
# ---------------------------------------------------------------------------

def _batch_mac_kernel(tag_ref, powers_ref, in_ref, mac_ref, h,
                      *, rows_per_tile):
    i = pl.program_id(0)
    j = pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        h[...] = (jnp.full((1, LANES), MAC_INIT, jnp.uint32)
                  + tag_ref[0].astype(jnp.uint32))

    tile = in_ref[0]                                    # (rows, 128) uint32
    acc = h[0, :]
    for r in range(rows_per_tile):                      # static unroll
        acc = acc * MAC_PRIME + tile[r, :]
    h[0, :] = acc

    @pl.when(j == nt - 1)
    def _final():
        mac_ref[i] = _fold_i32(h[0, :], powers_ref[...])


def mac_batch_pallas(stack_u32, tag, *, rows_per_tile=256):
    """(N, rows, 128) uint32 stack → (N,) uint32 MACs, one kernel launch.

    Grid is (frame, row-tile); the row-tile axis is innermost so each
    frame's Horner state lives in VMEM scratch across its tiles exactly like
    the scalar kernel — the batch axis just replays that schedule N times
    without N dispatches. The N MAC words collect in one SMEM output.
    ``rows`` must divide by ``rows_per_tile`` (snapped down here, never
    padded: padding rows would change the Horner MAC)."""
    n, rows, lanes = stack_u32.shape
    assert lanes == LANES and stack_u32.dtype == jnp.uint32
    rt = min(rows_per_tile, max(1, rows))
    while rows % rt:
        rt -= 1
    grid = (n, rows // rt)
    kernel = functools.partial(_batch_mac_kernel, rows_per_tile=rt)
    macs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),          # tag
            pl.BlockSpec((LANES,), lambda i, j: (0,)),      # fold powers
            pl.BlockSpec((1, rt, LANES), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.int32),
        scratch_shapes=[pltpu.VMEM((1, LANES), jnp.uint32)],
        interpret=pallas_interpret(),
    )(_as_i32(tag), jnp.asarray(FOLD_POWERS), stack_u32)
    return jax.lax.bitcast_convert_type(macs, jnp.uint32)


def mac_batch_jnp(stack_u32, tag):
    """jnp twin of :func:`mac_batch_pallas`: (N, rows, 128) → (N,) uint32.
    One lax.scan over the row axis, vectorized across frames."""
    assert stack_u32.dtype == jnp.uint32 and stack_u32.shape[-1] == LANES

    def row_step(h, row):                               # h, row: (N, 128)
        return h * jnp.uint32(MAC_PRIME) + row, None

    n = stack_u32.shape[0]
    h0 = jnp.full((n, LANES), MAC_INIT, jnp.uint32) + tag.astype(jnp.uint32)
    h, _ = jax.lax.scan(row_step, h0, stack_u32.transpose(1, 0, 2))
    return jnp.sum(h * jnp.asarray(FOLD_POWERS)[None, :], axis=1,
                   dtype=jnp.uint32)


# ---------------------------------------------------------------------------
# streaming MAC: explicit running state, blocks MAC'd as they land
# ---------------------------------------------------------------------------

def mac_init_state(tag) -> jnp.ndarray:
    """Fresh (LANES,) uint32 Horner state for a channel ``tag`` — the
    device twin of ``core.framing.mac_init_np``."""
    return (jnp.full((LANES,), MAC_INIT, jnp.uint32)
            + jnp.asarray(tag).astype(jnp.uint32))


def mac_update_jnp(h, block_u32) -> jnp.ndarray:
    """Advance a (LANES,) uint32 Horner state over an (m, 128) uint32
    block: the shape-polymorphic twin of :func:`mac_update_pallas`."""
    assert block_u32.dtype == jnp.uint32 and block_u32.shape[-1] == LANES

    def row_step(acc, row):
        return acc * jnp.uint32(MAC_PRIME) + row, None

    h, _ = jax.lax.scan(row_step, h.astype(jnp.uint32), block_u32)
    return h


def _mac_update_kernel(h_ref, in_ref, out_ref, acc, *, rows_per_tile):
    i = pl.program_id(0)
    n = pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        acc[...] = h_ref[...].reshape(1, LANES)

    tile = in_ref[...]                                  # (rows, 128) uint32
    a = acc[0, :]
    for r in range(rows_per_tile):                      # static unroll
        a = a * MAC_PRIME + tile[r, :]
    acc[0, :] = a

    @pl.when(i == n - 1)
    def _final():
        out_ref[...] = acc[0, :]


def mac_update_pallas(h, block_u32, *, rows_per_tile=256):
    """Advance a (LANES,) uint32 Horner state over an (m, 128) uint32
    block in one launch. The state rides in VMEM scratch across row tiles
    exactly like the one-shot kernels — this is the same schedule with the
    init/fold peeled off, so ``mac_finalize(update(update(init, b0), b1))``
    is bit-identical to ``mac_ref(concat(b0, b1))`` for any block split.
    ``m`` is snapped down to a divisor tile (padding would change the
    Horner MAC); an empty block returns the state unchanged."""
    m, lanes = block_u32.shape
    assert lanes == LANES and block_u32.dtype == jnp.uint32
    if m == 0:
        return h.astype(jnp.uint32)
    rt = min(rows_per_tile, m)
    while m % rt:
        rt -= 1
    kernel = functools.partial(_mac_update_kernel, rows_per_tile=rt)
    return pl.pallas_call(
        kernel,
        grid=(m // rt,),
        in_specs=[
            pl.BlockSpec((LANES,), lambda i: (0,)),     # running state
            pl.BlockSpec((rt, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((LANES,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((LANES,), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((1, LANES), jnp.uint32)],
        interpret=pallas_interpret(),
    )(h.astype(jnp.uint32), block_u32)


def mac_finalize(h) -> jnp.ndarray:
    """Fold a (LANES,) Horner state to the single uint32 MAC word
    (Σ h_i·P^(127-i) — one vector multiply-add, shared by every impl)."""
    return jnp.sum(h.astype(jnp.uint32) * jnp.asarray(FOLD_POWERS),
                   dtype=jnp.uint32)
