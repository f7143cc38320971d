"""The Granite-4.0-H-Small configuration's pieces of the benchmark: its
plain reference against the program's forward, its bfloat16 control, its
counts by hand, and its cells and readers as the harness finds them."""
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import counts as dense_counts
from bench import counts_hybrid_moe as counts
from bench import manifest, readings
from bench.manifest import Spec
from bench.probe import Probe, Tick
from bench.tests.tiny import tiny_cell

CONFIG = Path(__file__).resolve().parents[1] / "configs" / \
    "granite-4.0-h-small.json"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _program_config(model: dict):
    """The configuration as ``bench/system.py`` builds it."""
    from repro.configs.base import ModelConfig, SSMConfig
    md = dict(model)
    if "ssm" in md:
        md["ssm"] = SSMConfig(**md["ssm"])
    return ModelConfig(name="tiny", **md)


def _tiny():
    cell = tiny_cell("granite4h.decode")
    m, ref = cell.model, cell.reference()
    params = jax.jit(lambda k: ref.init(m, k))(jax.random.PRNGKey(3))
    return cell, m, ref, params


def test_reference_matches_program_forward():
    from repro.models import forward
    from repro.models.transformer import Impl

    cell, m, ref, params = _tiny()
    cfg = _program_config(cell.config["model"])
    assert cfg.moe.held == 4 and cfg.layer_types[1] == "attention"
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 24), 0,
                                m.vocab_size)
    with jax.default_matmul_precision("highest"):
        got = forward(cfg, params, {"tokens": tokens},
                      impl=Impl(remat=False), dtype=jnp.float32)[0]
    h = ref.hidden(m, params, tokens, dtype=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)
    want = jnp.einsum("rtd,vd->rtv", h, params["embed"]["tok"][:m.vocab_size],
                      precision=jax.lax.Precision.HIGHEST)
    got = np.asarray(got[..., :m.vocab_size])
    want = np.asarray(want)
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 1e-4 * scale


def test_control_precision_differs():
    """The bfloat16 control really computes in bfloat16."""
    cell, m, ref, params = _tiny()
    tokens = jax.random.randint(jax.random.PRNGKey(6), (1, 16), 0,
                                m.vocab_size)
    hi = ref.hidden(m, params, tokens, dtype=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST)
    lo = ref.hidden(m, params, tokens, dtype=jnp.bfloat16,
                    precision=jax.lax.Precision.DEFAULT)
    assert lo.dtype == jnp.bfloat16
    scale = float(jnp.abs(hi).max())
    diff = float(jnp.abs(hi - lo.astype(jnp.float32)).max())
    assert 1e-3 * scale < diff < 0.5 * scale


def _small():
    return Spec({
        "num_layers": 3, "layer_types": ["mamba", "attention", "mamba"],
        "d_model": 8, "num_heads": 2, "num_kv_heads": 1, "head_dim": 4,
        "d_ff": 3, "vocab_size": 10,
        "moe": {"num_experts": 6, "top_k": 2, "held_experts": 3,
                "shared_d_ff": 5},
        "ssm": {"d_state": 2, "head_dim": 4, "expand": 2, "n_groups": 1,
                "conv_width": 4}})


def test_counts_by_hand():
    m = _small()
    # mamba: d_inner 16, 4 heads, in_proj 8 x (32 + 4 + 4), out_proj 16 x 8
    mamba = 8 * 40 + 16 * 8
    mamba_rest = 5 * 20 + 12 + 16            # conv + bias, A/D/dt, gate norm
    attn = 8 * 2 * 4 * 2 + 8 * 1 * 4 * 2     # q, o; k, v
    ffn = 8 * 6 + 3 * 8 * 5                  # router, shared expert
    expert = 3 * 8 * 3
    shared = 2 * (mamba + mamba_rest) + attn + 3 * (ffn + 16) + 8 + 10 * 8
    assert counts.shared_weights(m) == shared
    # 2 tokens reach 1 - (2/3)^2 = 5/9 of the 3 held experts a layer
    assert math.isclose(counts.experts_touched(m, 2), 3 * 5 / 9)
    state = 2 * (4 * 4 * 2 + 3 * 20) * 4
    assert counts.slot_state_bytes(m, 5) == (state + 2 * 4 * 4 * 5,
                                             state + 2 * 4 * 4)
    flops = counts.token_flops(m, 5)
    assert math.isclose(
        flops, 2 * (2 * mamba + attn + 3 * (ffn + 2 * 3 / 6 * expert) + 80)
        + 2 * (2 * 4 * 20 + 5 * 4 * 4 * 2) + 4 * 2 * 4 * 6)
    f, b = counts.step_counts(m, [5, 0])
    assert math.isclose(f, flops + counts.token_flops(m, 0))
    assert math.isclose(b, 4 * (shared + 3 * expert * 3 * 5 / 9)
                        + sum(counts.slot_state_bytes(m, 5))
                        + sum(counts.slot_state_bytes(m, 0)))


def test_published_sizes():
    """The configuration holds 2.41B of the published 32.2B parameters."""
    cfg = json.loads(CONFIG.read_text())
    m = Spec(cfg["model"])
    held = counts.shared_weights(m) + m.num_layers * 9 * counts.expert_params(m)
    assert abs(held / 2.4147e9 - 1) < 1e-3
    whole = _program_config(dict(
        cfg["model"], num_layers=40, layer_types=cfg["model"]["layer_types"] * 4,
        moe=dict(cfg["model"]["moe"], held_experts=None)))
    assert abs(whole.param_count() / cfg["published"]["params"] - 1) < 1e-9
    assert cfg["num_local_experts"] == m.moe.held_experts == 9
    assert m.moe.num_experts == 72 and m.moe.top_k == 10


@pytest.mark.parametrize("cell_name,kind", [("granite4h.decode", "granite"),
                                            ("olmo1b.decode", "olmo_backlog")])
def test_cells_and_readers(cell_name, kind):
    cell = manifest.find_cell(cell_name)
    assert cell.chips == 1 and cell.traffic_name == "decode"
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert names == {f"{q}.{kind}" for q in (
        "engine.tick_ms", "step.device_ms", "decode_step_roofline", "mfu")}

    probe = Probe()
    probe.ticks = [Tick(0, 1.0 + 0.02 * i, 1.0 + 0.02 * i + 0.018,
                        list(range(16 * i, 16 * i + 16))) for i in range(40)]
    run = readings.Run(cell, cell.model, 1.0, (1.0, 2.0), 20.0, [], "closed",
                       probe, PEAKS)
    run.trace = {"modules": {"jit_engine_decode_step":
                             {"seconds": 0.5, "count": 40}}}
    run.trace_host_window = (1.0, 2.0)
    got = manifest.read_metrics(cell.per_layer, run)
    assert set(got) == names
    assert got[f"engine.tick_ms.{kind}"]["value"] == pytest.approx(18.0)
    assert got[f"step.device_ms.{kind}"]["value"] == pytest.approx(12.5)
    count = counts.step_counts if kind == "granite" else dense_counts.step_counts
    step_bytes = np.mean([count(cell.model, t.positions)[1]
                          for t in probe.ticks])
    flops = sum(count(cell.model, t.positions)[0] for t in probe.ticks)
    assert got[f"decode_step_roofline.{kind}"]["value"] == pytest.approx(
        100 * step_bytes / 819e9 / 0.0125)
    assert got[f"mfu.{kind}"]["value"] == pytest.approx(
        100 * flops / 197e12)
