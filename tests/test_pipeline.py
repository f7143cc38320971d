"""GPipe pipeline over MPKLink stage channels vs the single-device layer
stack — 8-device subprocess (8 stages, 1 layer each), fwd and grad."""
import os
import subprocess
import sys

_ROOT = os.path.join(os.path.dirname(__file__), "..")

CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.configs import get_reduced, replace
from repro.core.fabric import MPKLinkFabric
from repro.models import transformer as tf
from repro.models.transformer import Impl
from repro.runtime.pipeline import pipeline_apply, stage_split

cfg = replace(get_reduced("llama3.2-1b"), num_layers=8)
impl = Impl(attention="naive", remat=False)
key0 = jax.random.PRNGKey(0)
stacked = tf.init_stack(cfg, key0)

n_micro, mb, S = 4, 2, 16
x = jax.random.normal(jax.random.PRNGKey(1), (n_micro, mb, S, cfg.d_model))
positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (mb, S))

# single-device reference over each microbatch
ref = jnp.stack([tf.apply_stack(cfg, stacked, x[i], positions=positions,
                                impl=impl)[0] for i in range(n_micro)])

mesh = jax.make_mesh((8,), ("stage",))
fab = MPKLinkFabric(mesh, guard=True)
chan, key = fab.establish("stage-handoff", "stage")
staged = stage_split(stacked, 8)
specs = jax.tree.map(lambda a: P("stage"), staged)

def pipe(sp, xm):
    out, ok = pipeline_apply(cfg, sp, xm, fabric=fab, chan=chan, key=key,
                             impl=impl)
    return out, (jax.lax.psum(1 - ok, "stage") == 0).astype(jnp.int32)

out, ok = jax.jit(shard_map(pipe, mesh=mesh, in_specs=(specs, P()),
                            out_specs=(P(), P())))(staged, x)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
assert int(ok) == 1

# gradients flow through the pipeline (GPipe backward via AD)
def loss_pipe(sp, xm):
    out, _ = pipeline_apply(cfg, sp, xm, fabric=fab, chan=chan, key=key,
                            impl=impl)
    return (out ** 2).sum()

def loss_ref(params, xm):
    outs = [tf.apply_stack(cfg, params, xm[i], positions=positions,
                           impl=impl)[0] for i in range(n_micro)]
    return sum((o ** 2).sum() for o in outs)

g_pipe = jax.jit(shard_map(jax.grad(loss_pipe), mesh=mesh,
                           in_specs=(specs, P()), out_specs=specs))(staged, x)
g_ref = jax.grad(loss_ref)(stacked, x)
g_ref_staged = stage_split(g_ref, 8)
for a, b in zip(jax.tree.leaves(g_pipe), jax.tree.leaves(g_ref_staged)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4)
print("OK")
"""


def test_pipeline_matches_stack():
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", CODE], capture_output=True,
                       text=True, cwd=_ROOT, env=env, timeout=560)
    assert "OK" in r.stdout, r.stdout + r.stderr
