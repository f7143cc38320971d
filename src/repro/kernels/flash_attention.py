"""Flash attention as a Pallas TPU kernel.

Target: TPU v5e MXU/VMEM. Grid (B, nq, nk) with nk innermost — TPU grids
iterate sequentially, so the per-head (m, l, acc) online-softmax state
lives in VMEM scratch and persists across the nk sweep for a fixed
(b, iq); the output block is written once on the last nk step.

Tiling: q blocks (qc, H, Dh) and kv blocks (kc, Hkv, Dh) keep the
activation layout, so their last two dims are whole (the TPU's tiling
rule) and nothing is transposed; the kernel walks the heads statically.
All matmuls are qc×Dh·Dh×kc and qc×kc·kc×Dh — MXU shapes. f32
accumulation. GQA reads kv head h // g, so no KV repeat is ever
materialized. Query positions ride as a (qc, 1) column and kv positions
as a (1, kc) row.

SWA/causal masking uses explicit position vectors (works for ring caches);
fully-masked kv blocks skip the dots (`pl.when`) — on TPU this saves the MXU
issue for the lower triangle's empty blocks and everything outside the SWA
band.

Validated in interpret mode against ref.attention_ref (tests/test_kernels_flash.py);
compiled for v5e by tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import NEG_INF
from repro.utils import pallas_interpret


def _flash_kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, out_ref,
                  m_ref, l_ref, acc_ref, *, causal, window, out_dtype):
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qp = qpos_ref[0]                                       # (qc, 1)
    kp = kpos_ref[0]                                       # (1, kc)
    mask = kp >= 0
    if causal:
        mask = mask & (kp <= qp)
    if window is not None:
        mask = mask & ((qp - kp) < window)
    H, Hkv = q_ref.shape[2], k_ref.shape[2]
    g = H // Hkv

    @pl.when(jnp.any(mask))
    def _compute():
        for h in range(H):                                 # static heads
            qb = q_ref[0, :, h, :].astype(jnp.float32)     # (qc, Dh)
            kb = k_ref[0, :, h // g, :].astype(jnp.float32)
            vb = v_ref[0, :, h // g, :].astype(jnp.float32)
            scale = qb.shape[-1] ** -0.5
            s = jax.lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_ref[h]                              # (qc, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * corr + jnp.dot(
                p, vb, preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        for h in range(H):
            out = acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)
            out = jnp.where(qp < 0, 0.0, out)
            out_ref[0, :, h, :] = out.astype(out_dtype)


def flash_attention_pallas(q, k, v, q_pos, kv_pos, *, causal=True, window=None,
                           q_chunk=128, kv_chunk=128):
    """q (B,Sq,H,Dh); k/v (B,Skv,Hkv,Dh); positions (B,S*) int32.

    Requires Sq % q_chunk == 0 and Skv % kv_chunk == 0 (ops.py pads).
    """
    B, Sq, H, Dh = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    qc, kc = q_chunk, kv_chunk
    assert Sq % qc == 0 and Skv % kc == 0, (Sq, qc, Skv, kc)
    nq, nk = Sq // qc, Skv // kc

    grid = (B, nq, nk)
    kernel = functools.partial(_flash_kernel, causal=causal, window=window,
                               out_dtype=q.dtype)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, qc, 1), lambda b, iq, ik: (b, iq, 0)),        # qpos
            pl.BlockSpec((1, 1, kc), lambda b, iq, ik: (b, 0, ik)),        # kpos
            pl.BlockSpec((1, qc, H, Dh), lambda b, iq, ik: (b, iq, 0, 0)),
            pl.BlockSpec((1, kc, Hkv, Dh), lambda b, iq, ik: (b, ik, 0, 0)),
            pl.BlockSpec((1, kc, Hkv, Dh), lambda b, iq, ik: (b, ik, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, qc, H, Dh), lambda b, iq, ik: (b, iq, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Sq, H, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((H, qc, 1), jnp.float32),     # m
            pltpu.VMEM((H, qc, 1), jnp.float32),     # l
            pltpu.VMEM((H, qc, Dh), jnp.float32),    # acc
        ],
        interpret=pallas_interpret(),
    )(q_pos.astype(jnp.int32).reshape(B, Sq, 1),
      kv_pos.astype(jnp.int32).reshape(B, 1, Skv), q, k, v)
