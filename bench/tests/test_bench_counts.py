"""FLOPs and bytes of one decode step against counts made by hand from
the published shapes."""
import json
from pathlib import Path

import pytest

from bench import counts
from bench.manifest import Spec

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def model(name):
    return Spec(json.loads((CONFIGS / f"{name}.json").read_text())["model"])


def test_olmo_1b_by_hand():
    m = model("olmo-1b")
    D, L, F, V = 2048, 16, 8192, 50304
    attn = 4 * D * D                    # q, k, v, o: 16 heads x 128 = D
    mlp = 3 * D * F                     # gate, up, down
    assert counts.matmul_params(m) == L * (attn + mlp) + V * D
    assert counts.param_count(m) == 1_176_764_416      # published 1.177B
    # a token at position 99 sees 100 positions in each of 16 layers
    assert counts.token_flops(m, 99) == \
        2 * (L * (attn + mlp) + V * D) + L * 4 * D * 100
    # reads 99 K and V rows of 2048 f32 per layer, writes one of each
    assert counts.slot_state_bytes(m, 99) == (L * 2 * D * 4 * 99,
                                              L * 2 * D * 4)
    flops, nbytes = counts.step_counts(m, [0, 99])
    assert flops == counts.token_flops(m, 0) + counts.token_flops(m, 99)
    assert nbytes == 4 * counts.param_count(m) + L * 2 * D * 4 * (99 + 2)


def test_mamba2_1p3b_by_hand():
    m = model("mamba2-1.3b")
    D, L, V = 2048, 48, 50288
    di, N, H, P, cw = 4096, 128, 64, 64, 4
    conv_ch = di + 2 * N
    in_proj = D * (2 * di + 2 * N + H)
    out_proj = di * D
    assert counts.matmul_params(m) == L * (in_proj + out_proj) + V * D
    per_layer_rest = D + cw * conv_ch + conv_ch + 3 * H + di
    assert counts.param_count(m) == \
        counts.matmul_params(m) + D + L * per_layer_rest
    assert abs(counts.param_count(m) / 1.344e9 - 1) < 0.001   # published
    state = (H * P * N + (cw - 1) * conv_ch) * 4
    # the state does not grow with position
    assert counts.slot_state_bytes(m, 0) == counts.slot_state_bytes(m, 400) \
        == (L * state, L * state)
    assert counts.token_flops(m, 7) == \
        2 * (L * (in_proj + out_proj) + V * D) \
        + L * (2 * cw * conv_ch + 5 * H * P * N)


@pytest.mark.parametrize("name", ["olmo-1b", "mamba2-1.3b"])
def test_empty_step_reads_the_weights(name):
    m = model(name)
    assert counts.step_counts(m, []) == (0, 4 * counts.param_count(m))
