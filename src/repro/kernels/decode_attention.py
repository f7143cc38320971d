"""Single-token decode attention as a Pallas TPU kernel.

The serving hot spot: one new query per sequence attending over a long KV
cache. Memory-bound by design — the cache is read exactly once per step —
so the kernel's job is to stream (S_cache, Dh) tiles through VMEM with the
online-softmax state in scratch and never materialize the (B, H, S) score
tensor in HBM (the jnp decode path writes it, visible in the decode cells'
memory terms).

Grid (B, nk), kv innermost. A k/v block is (kc, Hkv, Dh) in the cache's
own layout, so its last two dims are whole (the TPU's tiling rule) and
the cache is never transposed; the kernel walks the kv heads statically,
each with its g query rows (GQA) resident as a (Hkv, g, Dh) block. The
query position is an SMEM scalar; the kv positions ride as a (1, kc) row
that masks unfilled slots and SWA windows (works for ring buffers, where
slot_pos carries absolute positions).

Validated in interpret mode against ref.attention_ref
(tests/test_kernels_decode.py); compiled for v5e by tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import NEG_INF
from repro.utils import pallas_interpret


def _decode_kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, out_ref,
                   m_ref, l_ref, acc_ref, *, causal, window, out_dtype):
    b = pl.program_id(0)
    ik = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qp = qpos_ref[b]                                       # SMEM scalar
    kp = kpos_ref[0]                                       # (1, kc)
    mask = kp >= 0
    if causal:
        mask = mask & (kp <= qp)
    if window is not None:
        mask = mask & ((qp - kp) < window)

    @pl.when(jnp.any(mask))
    def _compute():
        for h in range(k_ref.shape[2]):                    # static kv heads
            q = q_ref[0, h].astype(jnp.float32)            # (g, Dh)
            kb = k_ref[0, :, h, :].astype(jnp.float32)     # (kc, Dh)
            vb = v_ref[0, :, h, :].astype(jnp.float32)
            scale = q.shape[-1] ** -0.5
            s = jax.lax.dot_general(
                q, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # (g, kc)
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_ref[h]                              # (g, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * corr + jnp.dot(
                p, vb, preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        out_ref[0] = out.astype(out_dtype)


def decode_attention_pallas(q, k, v, q_pos, kv_pos, *, causal=True,
                            window=None, kv_chunk=512):
    """q (B, 1, H, Dh); k/v (B, S, Hkv, Dh); q_pos (B, 1); kv_pos (B, S).
    Requires S % kv_chunk == 0 (ops.py pads). → (B, 1, H, Dh)."""
    B, one, H, Dh = q.shape
    assert one == 1
    S, Hkv = k.shape[1], k.shape[2]
    g = H // Hkv
    kc = kv_chunk
    assert S % kc == 0, (S, kc)
    grid = (B, S // kc)
    kernel = functools.partial(_decode_kernel, causal=causal, window=window,
                               out_dtype=q.dtype)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                    # q_pos
            pl.BlockSpec((1, 1, kc), lambda b, ik: (b, 0, ik)),       # kv_pos
            pl.BlockSpec((1, Hkv, g, Dh), lambda b, ik: (b, 0, 0, 0)),
            pl.BlockSpec((1, kc, Hkv, Dh), lambda b, ik: (b, ik, 0, 0)),
            pl.BlockSpec((1, kc, Hkv, Dh), lambda b, ik: (b, ik, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hkv, g, Dh), lambda b, ik: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((Hkv, g, 1), jnp.float32),     # m
            pltpu.VMEM((Hkv, g, 1), jnp.float32),     # l
            pltpu.VMEM((Hkv, g, Dh), jnp.float32),    # acc
        ],
        interpret=pallas_interpret(),
    )(q_pos.reshape(B).astype(jnp.int32),
      kv_pos.reshape(B, 1, S).astype(jnp.int32),
      q.reshape(B, Hkv, g, Dh), k, v)
    return out.reshape(B, 1, H, Dh)
