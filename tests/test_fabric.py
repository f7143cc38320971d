"""MPKLinkFabric guarded collectives on an 8-device mesh (subprocess —
jax locks the device count per process)."""
import os
import subprocess
import sys

import pytest

_ROOT = os.path.join(os.path.dirname(__file__), "..")

FABRIC_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.core.fabric import (MPKLinkFabric, neighbor_exchange, ring_all_gather,
                               reduce_scatter_ring, all_to_all)
from repro.core.domains import AccessViolation

mesh = jax.make_mesh((8,), ("x",))
fab = MPKLinkFabric(mesh, guard=True)
chan, key = fab.establish("tp", "x")
x = jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4)

def allok(ok):
    return (jax.lax.psum(1 - ok, "x") == 0).astype(jnp.int32)

def ne(xl):
    y, ok = neighbor_exchange(fab, chan, key, xl, shift=1)
    return y, allok(ok)
y, ok = jax.jit(shard_map(ne, mesh=mesh, in_specs=P("x"), out_specs=(P("x"), P())))(x)
np.testing.assert_allclose(y, jnp.roll(x, 1, axis=0))
assert int(ok) == 1

def ag(xl):
    g, ok = ring_all_gather(fab, chan, key, xl)
    return g, allok(ok)
g, ok = jax.jit(shard_map(ag, mesh=mesh, in_specs=P("x"), out_specs=(P("x"), P())))(x)
g = np.asarray(g).reshape(8, 8, 4)
for d in range(8):
    np.testing.assert_allclose(g[d], x)
assert int(ok) == 1

xs = jnp.arange(8 * 8 * 4, dtype=jnp.float32).reshape(8, 8, 4)
def rs(xl):
    s, ok = reduce_scatter_ring(fab, chan, key, xl[0])
    return s, allok(ok)
s, ok = jax.jit(shard_map(rs, mesh=mesh, in_specs=P("x"), out_specs=(P("x"), P())))(xs)
np.testing.assert_allclose(np.asarray(s), np.asarray(xs).sum(0))
assert int(ok) == 1

# all_to_all (EP dispatch channel): local (1, 8) split on dim 1, concat on
# dim 0 → device d collects element d of every source row == transpose
def a2a(xl):
    return all_to_all(fab, chan, key, xl, split_axis=1, concat_axis=0)
t = jnp.arange(8 * 8, dtype=jnp.float32).reshape(8, 8)
out = jax.jit(shard_map(a2a, mesh=mesh, in_specs=P("x"), out_specs=P("x")))(t)
np.testing.assert_allclose(np.asarray(out).reshape(8, 8), np.asarray(t).T)

# trace-time violations
chan2, key2 = fab.establish("other", "x")
try:
    jax.jit(shard_map(lambda xl: neighbor_exchange(fab, chan, key2, xl)[0],
                      mesh=mesh, in_specs=P("x"), out_specs=P("x")))(x)
    raise SystemExit("FAIL: foreign key accepted")
except AccessViolation:
    pass
fab.revoke(chan2)
try:
    jax.jit(shard_map(lambda xl: neighbor_exchange(fab, chan2, key2, xl)[0],
                      mesh=mesh, in_specs=P("x"), out_specs=P("x")))(x)
    raise SystemExit("FAIL: revoked key accepted")
except AccessViolation:
    pass
print("OK")
"""


def test_fabric_collectives_and_capabilities():
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", FABRIC_CODE], capture_output=True,
                       text=True, cwd=_ROOT, env=env, timeout=480)
    assert "OK" in r.stdout, r.stdout + r.stderr
