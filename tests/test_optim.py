"""AdamW, schedules, clipping, gradient compression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import OptimizerConfig
from repro.optim import (adamw_update, clip_by_global_norm, cosine_lr,
                         dequantize_int8, global_norm, init_opt_state,
                         quantize_int8)


def test_adamw_converges_quadratic():
    p = {"w": jnp.array([3.0, -2.0]), "b": jnp.ones((2, 2))}
    st = init_opt_state(p)
    cfg = OptimizerConfig(lr=0.1, warmup_steps=5, total_steps=200,
                          weight_decay=0.0, grad_clip=10.0)
    for _ in range(200):
        g = jax.tree.map(lambda x: 2 * x, p)
        p, st, m = adamw_update(p, g, st, cfg)
    assert all(float(jnp.max(jnp.abs(x))) < 0.05 for x in jax.tree.leaves(p))
    assert int(st["step"]) == 200


def test_weight_decay_skips_1d():
    p = {"mat": jnp.ones((2, 2)), "vec": jnp.ones((4,))}
    st = init_opt_state(p)
    cfg = OptimizerConfig(lr=0.1, warmup_steps=0, total_steps=10,
                          weight_decay=1.0, grad_clip=1e9)
    zero_g = jax.tree.map(jnp.zeros_like, p)
    p2, _, _ = adamw_update(p, zero_g, st, cfg)
    assert float(jnp.max(jnp.abs(p2["vec"] - 1.0))) < 1e-6    # no decay
    assert float(jnp.max(p2["mat"])) < 1.0                     # decayed


def test_cosine_schedule_shape():
    cfg = OptimizerConfig(lr=1.0, warmup_steps=10, total_steps=100,
                          min_lr_ratio=0.1)
    assert float(cosine_lr(jnp.int32(0), cfg)) == 0.0
    assert abs(float(cosine_lr(jnp.int32(10), cfg)) - 1.0) < 1e-6
    assert abs(float(cosine_lr(jnp.int32(100), cfg)) - 0.1) < 1e-6
    assert float(cosine_lr(jnp.int32(55), cfg)) > 0.1


def test_clip_by_global_norm():
    g = {"a": jnp.full((4,), 10.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert abs(float(norm) - 20.0) < 1e-4
    assert abs(float(global_norm(clipped)) - 1.0) < 1e-4
    g2 = {"a": jnp.full((4,), 0.01)}
    same, _ = clip_by_global_norm(g2, 1.0)
    np.testing.assert_allclose(same["a"], g2["a"])


def test_quantize_roundtrip_bound():
    x = jax.random.normal(jax.random.PRNGKey(0), (4096,))
    q, s = quantize_int8(x)
    err = jnp.max(jnp.abs(dequantize_int8(q, s) - x))
    assert float(err) <= float(s) * 0.5 + 1e-7


def test_bf16_moment_dtype():
    p = {"w": jnp.ones((4, 4))}
    st = init_opt_state(p, jnp.bfloat16)
    assert st["m"]["w"].dtype == jnp.bfloat16
    cfg = OptimizerConfig(lr=0.01, warmup_steps=0, total_steps=10)
    p2, st2, _ = adamw_update(p, jax.tree.map(jnp.ones_like, p), st, cfg)
    assert st2["m"]["w"].dtype == jnp.bfloat16
    assert p2["w"].dtype == p["w"].dtype


def test_compressed_reduce_multidevice():
    """int8+EF all-reduce across 8 fake devices (subprocess)."""
    import subprocess, sys, os
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.launch.mesh import make_test_mesh
from repro.optim import compressed_reduce

mesh = make_test_mesh((8,), ("pod",))
g = jax.random.normal(jax.random.PRNGKey(0), (8, 16, 4))

def f(gl, ef):
    out, new_ef = compressed_reduce(gl[0], ef[0], "pod")
    return out[None], new_ef[None]

ef0 = jnp.zeros((8, 2, 4))
out, ef = jax.jit(shard_map(f, mesh=mesh, in_specs=(P("pod"), P("pod")),
                            out_specs=(P("pod"), P("pod"))))(g, ef0)
exact = np.asarray(g).mean(0)
for d in range(8):
    got = np.asarray(out[d])
    # int8 quantization error bounded by ~scale
    assert np.abs(got - exact).max() < np.abs(exact).max() / 50, d
# error feedback captures the residual
assert np.abs(np.asarray(ef)).max() > 0
print("OK")
"""
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=os.path.join(os.path.dirname(__file__), ".."),
                       env=env, timeout=300)
    assert "OK" in r.stdout, r.stdout + r.stderr
