"""Operations and bytes one decode step needs, computed from shapes.

A step processes one token for each occupied slot. What it needs is the
least any implementation must do: read every weight once, read the cache
or recurrent state of the occupied slots up to their positions, and write
the new row or state. Copies an implementation adds (an un-donated state,
a whole-cache reset) and padded vocabulary rows are not counted, so a
change that removes them moves the measured time towards these counts.

FLOPs count a multiply-add as two: matrix products against every weight
(the tied embedding as the output head, over the real vocabulary),
attention against the ``p + 1`` positions a token at position ``p`` sees,
and the state-space update and read-out.
"""
from __future__ import annotations

from typing import Iterable, Tuple

F32 = 4


def _ssm_dims(m):
    s = m.ssm
    di = s.expand * m.d_model
    H = di // s.head_dim
    gn = s.n_groups * s.d_state
    return s, di, H, gn


def matmul_params(m) -> int:
    """Weights that enter a matrix product per token (output head included)."""
    D, L = m.d_model, m.num_layers
    if m.family == "ssm":
        s, di, H, gn = _ssm_dims(m)
        per = D * (2 * di + 2 * gn + H) + di * D
    else:
        H, Hkv, Dh, F = m.num_heads, m.num_kv_heads, m.head_dim, m.d_ff
        per = 2 * D * H * Dh + 2 * D * Hkv * Dh + 3 * D * F
    return L * per + m.vocab_size * D


def param_count(m) -> int:
    """Every weight the step has to read (the real vocabulary's rows of
    the tied embedding, read once as the output head)."""
    D, L = m.d_model, m.num_layers
    n = matmul_params(m)
    if m.family == "ssm":
        s, di, H, gn = _ssm_dims(m)
        conv_ch = di + 2 * gn
        n += D + L * (D + s.conv_width * conv_ch + conv_ch + 3 * H + di)
    return n


def slot_state_bytes(m, position: int) -> Tuple[int, int]:
    """(bytes read, bytes written) of one slot's cache or state for a
    token at ``position`` (0-based)."""
    L = m.num_layers
    if m.family == "ssm":
        s, di, H, gn = _ssm_dims(m)
        state = H * s.head_dim * s.d_state + (s.conv_width - 1) * (di + 2 * gn)
        return L * state * F32, L * state * F32
    row = 2 * m.num_kv_heads * m.head_dim * F32        # one K and one V row
    return L * row * position, L * row


def token_flops(m, position: int) -> int:
    f = 2 * matmul_params(m)
    L = m.num_layers
    if m.family == "ssm":
        s, di, H, gn = _ssm_dims(m)
        f += L * (2 * s.conv_width * (di + 2 * gn)
                  + 5 * H * s.head_dim * s.d_state)
    else:
        f += L * 4 * m.num_heads * m.head_dim * (position + 1)
    return f


def step_counts(m, positions: Iterable[int]) -> Tuple[int, int]:
    """(FLOPs, HBM bytes) one step needs for occupied slots at
    ``positions``."""
    flops = 0
    nbytes = param_count(m) * F32
    for p in positions:
        flops += token_flops(m, p)
        r, w = slot_state_bytes(m, p)
        nbytes += r + w
    return flops, nbytes
