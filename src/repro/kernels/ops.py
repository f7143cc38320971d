"""Public kernel entry points with implementation selection.

impl choices:
  attention: "naive" (oracle, O(S²) memory — smoke/small only)
             "chunked" (flash_jnp custom_vjp twin — differentiable, what the
                        dry-run lowers; the default for train/prefill)
             "pallas"  (TPU kernel, fwd-only; interpreted on the CPU backend,
                        compiled on a TPU — repro.utils.pallas_interpret)
  ssd:       "ref" | "chunked" | "pallas"
  guard:     "ref" | "pallas"

The jnp paths are shape-polymorphic; pallas paths pad to block multiples here
so kernels only ever see divisible shapes.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels import flash_jnp as _fj
from repro.kernels import ssd_jnp as _sj
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas
from repro.kernels.mpk_guard import guard_copy_pallas, LANES

mac = _ref.mac_ref


def attention(q, k, v, q_pos, kv_pos, *, causal=True, window=None,
              impl="chunked", q_chunk=128, kv_chunk=128):
    if impl == "naive":
        return _ref.attention_ref(q, k, v, q_pos, kv_pos, causal=causal, window=window)
    if impl == "chunked":
        return _fj.flash_attention_jnp(q, k, v, q_pos, kv_pos, causal=causal,
                                       window=window, q_chunk=q_chunk, kv_chunk=kv_chunk)
    if impl == "pallas":
        B, Sq = q.shape[:2]
        qc = min(q_chunk, max(1, Sq))
        kc = min(kv_chunk, max(1, k.shape[1]))
        qp = _fj._pad_to(q_pos.astype(jnp.int32), 1, qc, -2)
        kp = _fj._pad_to(kv_pos.astype(jnp.int32), 1, kc, -1)
        out = flash_attention_pallas(
            _fj._pad_to(q, 1, qc, 0), _fj._pad_to(k, 1, kc, 0),
            _fj._pad_to(v, 1, kc, 0), qp, kp, causal=causal, window=window,
            q_chunk=qc, kv_chunk=kc)
        return out[:, :Sq]
    if impl == "pallas_decode":
        assert q.shape[1] == 1, "pallas_decode is the single-token path"
        kc = min(kv_chunk, max(1, k.shape[1]))
        kp = _fj._pad_to(kv_pos.astype(jnp.int32), 1, kc, -1)
        return decode_attention_pallas(
            q, _fj._pad_to(k, 1, kc, 0), _fj._pad_to(v, 1, kc, 0),
            q_pos, kp, causal=causal, window=window, kv_chunk=kc)
    raise ValueError(f"unknown attention impl {impl!r}")


def ssd(x, dt, A_log, B, C, D, init_state=None, *, chunk=128, impl="chunked"):
    if impl == "ref":
        return _ref.ssd_ref(x, dt, A_log, B, C, D, init_state)
    if impl == "chunked":
        return _sj.ssd_chunked(x, dt, A_log, B, C, D, init_state, chunk=chunk)
    if impl == "pallas":
        S = x.shape[1]
        Q = min(chunk, S)
        xp = _sj._pad_seq(x, Q)
        dtp = _sj._pad_seq(dt, Q)       # dt=0 padding → identity steps
        Bp = _sj._pad_seq(B, Q)
        Cp = _sj._pad_seq(C, Q)
        y, sf = ssd_scan_pallas(xp, dtp, A_log, Bp, Cp, D, init_state,
                                chunk=Q)
        return y[:, :S], sf
    raise ValueError(f"unknown ssd impl {impl!r}")


def ssd_decode_step(x_t, dt_t, A_log, B_t, C_t, D, state):
    return _sj.ssd_decode_step(x_t, dt_t, A_log, B_t, C_t, D, state)


def guard_copy(payload_u32, tag, expected_mac, *, rows_per_tile=256,
               impl="pallas"):
    """(copy, mac, ok). The tile size is snapped down to the largest divisor
    of the row count ≤ rows_per_tile, so the kernel never pads (padding
    would change the Horner MAC). Frames are LANES-padded by core.framing,
    so real row counts are benign; a pathological prime degrades to rt=1,
    never to a wrong MAC."""
    if impl == "ref":
        return _ref.guard_copy_ref(payload_u32, tag, expected_mac)
    n = payload_u32.shape[0]
    rt = min(rows_per_tile, max(1, n))
    while n % rt:
        rt -= 1
    return guard_copy_pallas(payload_u32, tag, expected_mac,
                             rows_per_tile=rt)


def guard_mac_batch(stack_u32, tag, *, rows_per_tile=256, impl="pallas"):
    """(N, rows, 128) uint32 stack of frame payloads → (N,) uint32 MACs.

    The device side of the batched data plane: N frames MAC'd in one fused
    launch instead of N scalar kernel calls. ``impl="jnp"`` is the
    shape-polymorphic twin (what the dry-run lowers); both are bit-identical
    to the host path ``core.framing.mac_batch``. Zero-row frames (empty
    payloads) fall through to the jnp twin — a zero-size grid would skip the
    kernel epilogue entirely."""
    from repro.kernels.mpk_guard import mac_batch_jnp, mac_batch_pallas
    if impl == "jnp" or stack_u32.shape[1] == 0:
        return mac_batch_jnp(stack_u32, tag)
    if impl == "pallas":
        return mac_batch_pallas(stack_u32, tag, rows_per_tile=rows_per_tile)
    raise ValueError(f"unknown guard_mac_batch impl {impl!r}")


def guard_mac_init(tag):
    """Fresh (LANES,) uint32 streaming-MAC state for ``tag``."""
    from repro.kernels.mpk_guard import mac_init_state
    return mac_init_state(tag)


def guard_mac_update(h, block_u32, *, rows_per_tile=256, impl="pallas"):
    """Advance a streaming-MAC state over one (m, 128) uint32 block.

    The device side of the zero-copy seal path: a payload too large to
    stage is MAC'd block-wise as each chunk lands, with the Horner state
    carried between launches. ``impl="jnp"`` is the shape-polymorphic twin.
    Both are bit-identical to the one-shot ``mac_ref`` over the
    concatenated blocks (and to ``core.framing.mac_update_np``)."""
    from repro.kernels.mpk_guard import mac_update_jnp, mac_update_pallas
    if impl == "jnp" or block_u32.shape[0] == 0:
        return mac_update_jnp(h, block_u32)
    if impl == "pallas":
        return mac_update_pallas(h, block_u32, rows_per_tile=rows_per_tile)
    raise ValueError(f"unknown guard_mac_update impl {impl!r}")


def guard_mac_finalize(h):
    """Fold a streaming-MAC state to the single uint32 MAC word."""
    from repro.kernels.mpk_guard import mac_finalize
    return mac_finalize(h)
