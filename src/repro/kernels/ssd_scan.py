"""Mamba2 SSD chunked scan as a Pallas TPU kernel.

Grid (B, H, nc) with the chunk dim innermost/sequential: the (P, N) SSM state
for a fixed (b, h) lives in VMEM scratch and is carried across chunk steps —
the inter-chunk recurrence never touches HBM. Each chunk step does three
MXU matmuls (C·Bᵀ → Q×Q, att·x → Q×P, state in/out → Q×N·N×P-shaped work)
on (Q=128)-aligned tiles, which is exactly the SSD restructuring insight:
turn an O(S) elementwise recurrence into O(S/Q) matmul steps.

B/C group sharing (n_groups G ≤ H) is handled in the index_map (h → h // R),
same trick as GQA in the flash kernel — no repeat materialized.

Validated in interpret mode against ref.ssd_ref (tests/test_kernels_ssd.py);
compiled for v5e by tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.utils import pallas_interpret


def _ssd_kernel(x_ref, dtr_ref, dtc_ref, a_ref, b_ref, c_ref, d_ref, s0_ref,
                y_ref, sf_ref, state, *, out_dtype):
    h = pl.program_id(1)
    c = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(c == 0)
    def _init():
        state[...] = s0_ref[0, 0].astype(jnp.float32)

    xb = x_ref[0, 0].astype(jnp.float32)                # (P, Q)
    dtr = dtr_ref[0, 0].astype(jnp.float32)             # (1, Q)
    dtc = dtc_ref[0, 0].astype(jnp.float32)             # (Q, 1)
    Bb = b_ref[0, 0].astype(jnp.float32)                # (Q, N)
    Cb = c_ref[0, 0].astype(jnp.float32)                # (Q, N)
    A = a_ref[h]                                        # SMEM scalar, < 0
    Dc = d_ref[h]
    Q = xb.shape[1]

    # within-chunk cumulative log-decay, as a column and as a row: a
    # lower-triangular matmul (the TPU has no vector cumsum)
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    causal = col <= row
    tri = causal.astype(jnp.float32)
    nt = (((1,), (1,)), ((), ()))                       # a @ b.T
    cum_c = jnp.dot(tri, dtc * A, preferred_element_type=jnp.float32)
    cum_r = jax.lax.dot_general(dtr * A, tri, nt,
                                preferred_element_type=jnp.float32)

    s_in = state[...]                                   # (P, N)
    # intra-chunk quadratic form: att[i, j] = C_i·B_j · exp(L_i − L_j) · dt_j
    dec = jnp.exp(jnp.where(causal, cum_c - cum_r, -1e30))
    cb = jax.lax.dot_general(Cb, Bb, nt, preferred_element_type=jnp.float32)
    att = cb * dec * dtr
    y = jax.lax.dot_general(xb, att, nt, preferred_element_type=jnp.float32)
    # inter-chunk contribution: exp(L_i) · S_in · C_i
    y += jax.lax.dot_general(s_in, Cb, nt,
                             preferred_element_type=jnp.float32) * jnp.exp(cum_r)
    y += Dc * xb
    y_ref[0, 0] = y.astype(out_dtype)                   # (P, Q)

    # state carry: S_out = exp(L_Q)·S_in + Σ_j exp(L_Q − L_j)·dt_j·(x_j ⊗ B_j)
    last = cum_r[:, Q - 1:]                             # (1, 1)
    w = jnp.exp(last - cum_r) * dtr                     # (1, Q)
    s_c = jnp.dot(xb * w, Bb, preferred_element_type=jnp.float32)  # (P, N)
    # reduced to a scalar: Mosaic broadcasts a (1, 1) vector along lanes or
    # sublanes, not both
    state[...] = jnp.sum(jnp.exp(last)) * s_in + s_c

    @pl.when(c == nc - 1)
    def _final():
        sf_ref[0, 0] = state[...]


def ssd_scan_pallas(x, dt, A_log, B, C, D, init_state=None, *, chunk=128):
    """Shapes as ref.ssd_ref; requires S % chunk == 0 (ops.py pads).
    Returns (y, final_state (B,H,P,N) f32).

    The kernel works head-major: x and y as (B, H, P, S) so a block's last
    two dims are (P, chunk), B/C as (B, G, S, N), dt as both a row and a
    column, and the per-head scalars A, D in SMEM."""
    Bb, S, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    R = H // G
    Q = chunk
    assert S % Q == 0, (S, Q)
    nc = S // Q
    if init_state is None:
        init_state = jnp.zeros((Bb, H, P, N), jnp.float32)
    dt_hs = dt.transpose(0, 2, 1)                       # (B, H, S)

    grid = (Bb, H, nc)
    kernel = functools.partial(_ssd_kernel, out_dtype=x.dtype)
    y, sf = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, P, Q), lambda b, h, c: (b, h, 0, c)),       # x
            pl.BlockSpec((1, 1, 1, Q), lambda b, h, c: (b, h, 0, c)),       # dt row
            pl.BlockSpec((1, 1, Q, 1), lambda b, h, c: (b, h, c, 0)),       # dt col
            pl.BlockSpec(memory_space=pltpu.SMEM),                           # A
            pl.BlockSpec((1, 1, Q, N), lambda b, h, c: (b, h // R, c, 0)),  # B
            pl.BlockSpec((1, 1, Q, N), lambda b, h, c: (b, h // R, c, 0)),  # C
            pl.BlockSpec(memory_space=pltpu.SMEM),                           # D
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),       # init_state
        ],
        out_specs=[
            pl.BlockSpec((1, 1, P, Q), lambda b, h, c: (b, h, 0, c)),       # y
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),       # final_state
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bb, H, P, S), x.dtype),
            jax.ShapeDtypeStruct((Bb, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=pallas_interpret(),
    )(x.transpose(0, 2, 3, 1), dt_hs[:, :, None, :], dt_hs[..., None],
      -jnp.exp(A_log.astype(jnp.float32)), B.transpose(0, 2, 1, 3),
      C.transpose(0, 2, 1, 3), D.astype(jnp.float32), init_state)
    return y.transpose(0, 3, 1, 2), sf
