"""Ring attention (sequence parallelism over MPKLink channels) vs the
full-attention oracle — 8-device subprocess."""
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
from repro.core.fabric import MPKLinkFabric
from repro.core.ring_attention import ring_attention
from repro.kernels.ref import attention_ref

mesh = jax.make_mesh((8,), ("sp",))
fab = MPKLinkFabric(mesh, guard=True)
chan, key = fab.establish("ring-kv", "sp")

B, S, H, Hkv, Dh = 2, 64, 4, 2, 16          # 8 tokens per device
ks = jax.random.split(jax.random.PRNGKey(0), 3)
q = jax.random.normal(ks[0], (B, S, H, Dh))
k = jax.random.normal(ks[1], (B, S, Hkv, Dh))
v = jax.random.normal(ks[2], (B, S, Hkv, Dh))
pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

for causal, window in [(True, None), (True, 24), (False, None)]:
    def ring(ql, kl, vl, qpl, kpl):
        out, ok = ring_attention(fab, chan, key, ql, kl, vl, qpl, kpl,
                                 causal=causal, window=window,
                                 q_chunk=8, kv_chunk=8)
        return out, (jax.lax.psum(1 - ok, "sp") == 0).astype(jnp.int32)

    out, ok = jax.jit(shard_map(
        ring, mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp"),
                  P(None, "sp"), P(None, "sp")),
        out_specs=(P(None, "sp"), P())))(q, k, v, pos, pos)
    ref = attention_ref(q, k, v, pos, pos, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)
    assert int(ok) == 1, (causal, window)
print("OK")
"""


def test_ring_attention_matches_oracle():
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", CODE], capture_output=True,
                       text=True, cwd=_ROOT, env=env, timeout=480)
    assert "OK" in r.stdout, r.stdout + r.stderr
