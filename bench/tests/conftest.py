"""Test-only: the tiny size of the pattern hybrid with held experts
(``granite4h.decode``), beside the families ``tiny.py`` sizes. The harness
tests that run every cell take ``tiny_cell`` from ``tiny.py``; it is
extended here, before they import it, so that they run this cell too.

And each test leaves JAX's default matmul precision as it found it:
``bench/run.py`` sets it for its whole process (one run a process), and a
test that calls ``run`` in the test process would otherwise hand it to
the tests that follow on the same worker (Pallas refuses it)."""
from __future__ import annotations

import jax
import pytest

from bench.tests import tiny

# The logit's spread is sqrt(d_model) x the embedding's std / logits_scaling:
# at width 512 the scaling is cut by sqrt(512 / 4096), so that the logits
# spread as at the published width (the aim of tiny.py's widths).
TINY_HYBRID = {
    "num_layers": 3, "layer_types": ["mamba", "attention", "mamba"],
    "d_model": 512, "num_heads": 4, "num_kv_heads": 2, "head_dim": 64,
    "d_ff": 128, "vocab_size": 512, "attention_multiplier": 1 / 64,
    "logits_scaling": 16 * (512 / 4096) ** 0.5,
    "moe": {"num_experts": 16, "top_k": 4, "held_experts": 4,
            "first_expert": 4, "shared_d_ff": 256},
    "ssm": {"d_state": 32, "head_dim": 64, "expand": 2, "chunk_size": 16,
            "n_groups": 1, "conv_width": 4, "dt_min": 0.001, "dt_max": 0.1},
}

_tiny_cell = tiny.tiny_cell


def tiny_cell(*args, **kwargs):
    """``tiny.tiny_cell``, which also sizes the hybrid family."""
    tiny.TINY_MODELS["hybrid"] = TINY_HYBRID
    try:
        return _tiny_cell(*args, **kwargs)
    finally:
        del tiny.TINY_MODELS["hybrid"]


tiny.tiny_cell = tiny_cell


@pytest.fixture(autouse=True)
def _keep_matmul_precision():
    was = jax.config.jax_default_matmul_precision
    yield
    jax.config.update("jax_default_matmul_precision", was)
