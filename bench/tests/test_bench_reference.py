"""Each plain reference against the program's own full forward, at a
small size on the CPU, on the benchmark's seeded weights."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.manifest import Spec
from bench.tests.tiny import TINY_MODELS, tiny_cell


@pytest.mark.parametrize("cell_name", ["olmo1b.chat", "mamba2.decode"])
def test_reference_matches_program_forward(cell_name):
    from repro.configs.base import ModelConfig, SSMConfig
    from repro.models import forward
    from repro.models.transformer import Impl

    cell = tiny_cell(cell_name)
    m = cell.model
    ref = cell.reference()
    params = jax.jit(lambda k: ref.init(m, k))(jax.random.PRNGKey(3))
    md = dict(cell.config["model"])
    if "ssm" in md:
        md["ssm"] = SSMConfig(**md["ssm"])
    cfg = ModelConfig(name="tiny", **md)
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


@pytest.mark.parametrize("family", sorted(TINY_MODELS))
def test_control_precision_differs(family):
    """The bfloat16 control really computes in bfloat16."""
    cell = tiny_cell({"dense": "olmo1b.chat", "ssm": "mamba2.decode"}[family])
    m, ref = cell.model, cell.reference()
    params = jax.jit(lambda k: ref.init(m, k))(jax.random.PRNGKey(5))
    tokens = jax.random.randint(jax.random.PRNGKey(6), (1, 16), 0,
                                m.vocab_size)
    hi = ref.hidden(m, params, tokens, dtype=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST)
    lo = ref.hidden(m, params, tokens, dtype=jnp.bfloat16,
                    precision=jax.lax.Precision.DEFAULT)
    assert lo.dtype == jnp.bfloat16
    diff = float(jnp.abs(hi - lo.astype(jnp.float32)).max())
    assert 1e-4 < diff < 1.0


def test_spec_is_hashable_and_read_only():
    a = Spec({"x": 1, "ssm": {"d_state": 2}})
    b = Spec({"ssm": {"d_state": 2}, "x": 1})
    assert a == b and hash(a) == hash(b)
    assert a.ssm.d_state == 2
    with pytest.raises(AttributeError):
        a.missing
