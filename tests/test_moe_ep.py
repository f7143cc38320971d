"""Expert-parallel MoE (fabric all_to_all) parity with dense dispatch —
8-device subprocess, 8 experts, 1 per device."""
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
from repro.configs.base import MoEConfig
from repro.core.fabric import MPKLinkFabric
from repro.models import moe as moe_mod
from repro.models.moe_ep import apply_moe_ep

cfg = get_reduced("mixtral-8x7b")
# 8 experts (one per device), loose capacity so nothing drops on either path
cfg = replace(cfg, moe=MoEConfig(num_experts=8, top_k=2, capacity_factor=16.0))
p = moe_mod.init_moe(cfg, jax.random.PRNGKey(0))
B, S = 8, 16                                      # one batch row per device
x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model))

# dense reference (per-row groups == per-device routing in the EP path)
cfg_g = replace(cfg, moe=replace(cfg.moe, group_size=S))
y_ref, aux_ref = moe_mod.apply_moe(cfg_g, p, x)

mesh = jax.make_mesh((8,), ("ep",))
fab = MPKLinkFabric(mesh, guard=False)
chan, key = fab.establish("moe-dispatch", "ep")

def ep_fn(xl, router, gate, up, down):
    w = {"router": router, "gate": gate, "up": up, "down": down}
    y, aux = apply_moe_ep(cfg, w, xl, fabric=fab, chan=chan, key=key)
    return y, jax.tree.map(lambda a: jax.lax.pmean(a, "ep"), aux)

y_ep, aux_ep = jax.jit(shard_map(
    ep_fn, mesh=mesh,
    in_specs=(P("ep"), P(), P("ep"), P("ep"), P("ep")),
    out_specs=(P("ep"), P())))(x, p["router"], p["gate"], p["up"], p["down"])

np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref),
                           rtol=2e-4, atol=2e-4)
print("OK")
"""


def test_moe_ep_parity():
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", CODE], capture_output=True,
                       text=True, cwd=_ROOT, env=env, timeout=480)
    assert "OK" in r.stdout, r.stdout + r.stderr
