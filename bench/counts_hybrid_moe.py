"""Operations and bytes one decode step of the Mamba-2 / attention hybrid
with held experts needs, computed from shapes (``bench/counts.py`` counts
the dense and Mamba-2 configurations).

A step processes one token for each occupied slot. What it needs is the
least any implementation must do:
- read every weight outside the routed experts once (the real
  vocabulary's rows of the tied embedding, read once as the output head);
- read the held experts that the step's tokens reach: under uniform
  routing each token picks ``top_k`` of ``num_experts``, so of the
  ``held`` experts of a layer ``held * (1 - (1 - top_k / num_experts)^n)``
  are expected to be touched by ``n`` tokens;
- read and write each occupied slot's SSM state in every Mamba-2 layer,
  read its K and V rows up to its position in every attention layer, and
  write the new row.

FLOPs count a multiply-add as two: matrix products against every weight a
token passes through, with ``top_k * held / num_experts`` held experts
expected per token, attention against the ``p + 1`` positions a token at
position ``p`` sees, and the state-space update and read-out.
"""
from __future__ import annotations

from typing import Iterable, Tuple

F32 = 4


def _dims(m):
    s = m.ssm
    di = s.expand * m.d_model
    H = di // s.head_dim
    gn = s.n_groups * s.d_state
    return s, di, H, gn


def layer_counts(m) -> Tuple[int, int]:
    """(Mamba-2 layers, attention layers)."""
    n_attn = list(m.layer_types).count("attention")
    return m.num_layers - n_attn, n_attn


def mamba_matmul_params(m) -> int:
    s, di, H, gn = _dims(m)
    return m.d_model * (2 * di + 2 * gn + H) + di * m.d_model


def mamba_other_params(m) -> int:
    """Conv weights and bias, A_log, D, dt bias, the gate norm's scale."""
    s, di, H, gn = _dims(m)
    conv_ch = di + 2 * gn
    return (s.conv_width + 1) * conv_ch + 3 * H + di


def attn_params(m) -> int:
    return 2 * m.d_model * m.head_dim * (m.num_heads + m.num_kv_heads)


def expert_params(m) -> int:
    """One routed expert: gate, up, down."""
    return 3 * m.d_model * m.d_ff


def dense_ffn_params(m) -> int:
    """The router and the shared expert, which every token passes."""
    return m.d_model * m.moe.num_experts + 3 * m.d_model * m.moe.shared_d_ff


def experts_touched(m, n_tokens: int) -> float:
    """Held experts of one layer expected to be reached by ``n_tokens``
    tokens under uniform routing."""
    e = m.moe
    return e.held_experts * (1 - (1 - e.top_k / e.num_experts) ** n_tokens)


def shared_weights(m) -> int:
    """Every weight outside the routed experts."""
    Lm, La = layer_counts(m)
    D, L = m.d_model, m.num_layers
    return (Lm * (mamba_matmul_params(m) + mamba_other_params(m))
            + La * attn_params(m) + L * (dense_ffn_params(m) + 2 * D)
            + D + m.vocab_size * D)


def slot_state_bytes(m, position: int) -> Tuple[int, int]:
    """(bytes read, bytes written) of one slot's states and cache for a
    token at ``position`` (0-based)."""
    s, di, H, gn = _dims(m)
    Lm, La = layer_counts(m)
    state = Lm * (H * s.head_dim * s.d_state
                  + (s.conv_width - 1) * (di + 2 * gn)) * F32
    row = La * 2 * m.num_kv_heads * m.head_dim * F32   # one K and one V row
    return state + row * position, state + row


def token_flops(m, position: int) -> int:
    s, di, H, gn = _dims(m)
    Lm, La = layer_counts(m)
    e = m.moe
    experts = e.top_k * e.held_experts / e.num_experts
    matmul = (Lm * mamba_matmul_params(m) + La * attn_params(m)
              + m.num_layers * (dense_ffn_params(m)
                                + experts * expert_params(m))
              + m.vocab_size * m.d_model)
    return (2 * matmul
            + Lm * (2 * s.conv_width * (di + 2 * gn)
                    + 5 * H * s.head_dim * s.d_state)
            + La * 4 * m.num_heads * m.head_dim * (position + 1))


def step_counts(m, positions: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) one step needs for occupied slots at
    ``positions``."""
    positions = list(positions)
    nbytes = (shared_weights(m) + m.num_layers * expert_params(m)
              * experts_touched(m, len(positions))) * F32
    flops = 0
    for p in positions:
        flops += token_flops(m, p)
        r, w = slot_state_bytes(m, p)
        nbytes += r + w
    return flops, nbytes
