"""MPKLink fabric parallelism showcase — 8 simulated devices.

Every distributed pattern in the framework running over guarded MPKLink
channels instead of compiler-inserted collectives:

  1. SP  — ring attention: sequence-sharded Q/K/V, K/V rotating through a
           protected neighbor channel (vs full-attention oracle)
  2. EP  — expert-parallel MoE: tokens dispatched between expert-owning
           devices via a guarded all_to_all (vs dense dispatch)
  3. PP  — GPipe pipeline: 8 stages handing activations through the
           channel per tick (vs the single-device layer stack)
  4. DP  — int8+error-feedback compressed gradient reduce across the
           "pod" axis (vs exact all-reduce)

This script re-execs itself with XLA_FLAGS for 8 host devices.
PYTHONPATH=src python examples/fabric_parallel_demo.py
"""
import os
import sys

if os.environ.get("XLA_FLAGS", "").find("device_count=8") < 0:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.execv(sys.executable, [sys.executable] + sys.argv)

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs import get_reduced, replace
from repro.configs.base import MoEConfig
from repro.core.fabric import MPKLinkFabric
from repro.core.ring_attention import ring_attention
from repro.kernels.ref import attention_ref
from repro.models import moe as moe_mod
from repro.models import transformer as tf
from repro.models.moe_ep import apply_moe_ep
from repro.models.transformer import Impl
from repro.optim import compressed_reduce
from repro.runtime.pipeline import pipeline_apply, stage_split

mesh = jax.make_mesh((8,), ("x",))
fab = MPKLinkFabric(mesh, guard=True)
impl = Impl(attention="naive", remat=False)


def demo_ring_attention():
    chan, key = fab.establish("sp-kv", "x")
    B, S, H, Hkv, Dh = 2, 64, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, Dh))
    k = jax.random.normal(ks[1], (B, S, Hkv, Dh))
    v = jax.random.normal(ks[2], (B, S, Hkv, Dh))
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    def f(ql, kl, vl, pl):
        out, ok = ring_attention(fab, chan, key, ql, kl, vl, pl, pl,
                                 causal=True, q_chunk=8, kv_chunk=8)
        return out, (jax.lax.psum(1 - ok, "x") == 0).astype(jnp.int32)

    out, ok = jax.jit(shard_map(f, mesh=mesh,
                                in_specs=(P(None, "x"),) * 4,
                                out_specs=(P(None, "x"), P())))(q, k, v, pos)
    ref = attention_ref(q, k, v, pos, pos, causal=True)
    err = float(jnp.max(jnp.abs(out - ref)))
    print(f"1. SP ring attention : 8-way seq-sharded, max|Δ| vs oracle = "
          f"{err:.2e}, guard ok={int(ok)}")


def demo_moe_ep():
    cfg = replace(get_reduced("mixtral-8x7b"),
                  moe=MoEConfig(num_experts=8, top_k=2, capacity_factor=16.0))
    p = moe_mod.init_moe(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, cfg.d_model))
    cfg_g = replace(cfg, moe=replace(cfg.moe, group_size=16))
    y_ref, _ = moe_mod.apply_moe(cfg_g, p, x)
    chan, key = fab.establish("ep-dispatch", "x")

    def f(xl, router, gate, up, down):
        w = {"router": router, "gate": gate, "up": up, "down": down}
        y, _ = apply_moe_ep(cfg, w, xl, fabric=fab, chan=chan, key=key)
        return y

    y = jax.jit(shard_map(f, mesh=mesh,
                          in_specs=(P("x"), P(), P("x"), P("x"), P("x")),
                          out_specs=P("x")))(x, p["router"], p["gate"],
                                             p["up"], p["down"])
    err = float(jnp.max(jnp.abs(y - y_ref)))
    print(f"2. EP MoE dispatch   : 8 experts on 8 devices, max|Δ| vs dense = "
          f"{err:.2e}")


def demo_pipeline():
    cfg = replace(get_reduced("llama3.2-1b"), num_layers=8)
    stacked = tf.init_stack(cfg, jax.random.PRNGKey(0))
    n_micro, mb, S = 4, 2, 16
    x = jax.random.normal(jax.random.PRNGKey(1), (n_micro, mb, S, cfg.d_model))
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (mb, S))
    ref = jnp.stack([tf.apply_stack(cfg, stacked, x[i], positions=positions,
                                    impl=impl)[0] for i in range(n_micro)])
    chan, key = fab.establish("pp-handoff", "x")
    staged = stage_split(stacked, 8)
    specs = jax.tree.map(lambda a: P("x"), staged)

    def f(sp, xm):
        out, ok = pipeline_apply(cfg, sp, xm, fabric=fab, chan=chan, key=key,
                                 impl=impl)
        return out, (jax.lax.psum(1 - ok, "x") == 0).astype(jnp.int32)

    out, ok = jax.jit(shard_map(f, mesh=mesh, in_specs=(specs, P()),
                                out_specs=(P(), P())))(staged, x)
    err = float(jnp.max(jnp.abs(out - ref)))
    print(f"3. PP GPipe          : 8 stages × {n_micro} microbatches "
          f"({8 + n_micro - 1} ticks), max|Δ| vs stack = {err:.2e}, "
          f"guard ok={int(ok)}")


def demo_compressed_dp():
    g = jax.random.normal(jax.random.PRNGKey(2), (8, 64, 16))
    ef0 = jnp.zeros((8, 8, 16))

    def f(gl, ef):
        out, new_ef = compressed_reduce(gl[0], ef[0], "x")
        return out[None], new_ef[None]

    out, ef = jax.jit(shard_map(f, mesh=mesh, in_specs=(P("x"), P("x")),
                                out_specs=(P("x"), P("x"))))(g, ef0)
    exact = np.asarray(g).mean(0)
    err = np.abs(np.asarray(out[0]) - exact).max()
    print(f"4. DP int8+EF reduce : cross-pod gradient mean, max|Δ| vs exact = "
          f"{err:.2e} (int8 leg = 4× fewer bytes)")


if __name__ == "__main__":
    print(f"devices: {jax.device_count()}  mesh: 8×('x')  guard: MAC on\n")
    demo_ring_attention()
    demo_moe_ep()
    demo_pipeline()
    demo_compressed_dp()
    print("\nfabric_parallel_demo OK")
