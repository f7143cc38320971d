"""Compile-only checks for a described TPU v5e chip (no chip needed).

The TPU compiler is installed with JAX and compiles for a topology that is
described rather than attached. These tests compile every Pallas kernel at
real widths (OLMo-1B attention, mamba2-1.3b SSD, the MPKLink MAC kernels)
with ``interpret=False`` — the tiling and VMEM rules interpret mode never
checks — and compile OLMo-1B's decode step at the size ``chip_smoke.py``
serves, checking that it fits one chip's HBM. Nothing runs: a compile
that passes is not a chip run.

The topology is described inside a module fixture (never at import): only
one process may load the TPU library at a time, and every test worker
imports this file.
"""
import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

ROOT = os.path.join(os.path.dirname(__file__), "..")
HBM_BYTES = 16 * 2**30                  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip(one_chip, monkeypatch):
    """Shapes on the described chip, with the kernels steered to their
    compiled path (``pallas_interpret`` reads the backend, which is the CPU
    here) and the persistent cache off (a chip compile written here could
    not be read back without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                         sharding=one_chip)
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _olmo():
    from repro.configs import get_config
    return get_config("olmo-1b")


def _mamba():
    from repro.configs import get_config
    return get_config("mamba2-1.3b")


def _decode_attention(sds, dtype):
    from repro.kernels.decode_attention import decode_attention_pallas
    from repro.models.transformer import Impl
    c = _olmo()
    B, S, H, Hkv, Dh = 8, 2048, c.num_heads, c.num_kv_heads, c.head_dim
    fn = functools.partial(decode_attention_pallas,
                           kv_chunk=Impl().kv_chunk)
    return fn, (sds((B, 1, H, Dh), dtype), sds((B, S, Hkv, Dh), dtype),
                sds((B, S, Hkv, Dh), dtype), sds((B, 1), jnp.int32),
                sds((B, S), jnp.int32))


def _flash_attention(sds, dtype):
    from repro.kernels.flash_attention import flash_attention_pallas
    c = _olmo()
    B, S, H, Hkv, Dh = 2, 2048, c.num_heads, c.num_kv_heads, c.head_dim
    return flash_attention_pallas, (
        sds((B, S, H, Dh), dtype), sds((B, S, Hkv, Dh), dtype),
        sds((B, S, Hkv, Dh), dtype), sds((B, S), jnp.int32),
        sds((B, S), jnp.int32))


def _ssd_scan(sds, dtype):
    from repro.kernels.ssd_scan import ssd_scan_pallas
    c = _mamba()
    s = c.ssm
    B, S, H, P, G, N = 1, 2048, c.ssm_heads, s.head_dim, s.n_groups, s.d_state
    fn = functools.partial(ssd_scan_pallas, chunk=s.chunk_size)
    return fn, (sds((B, S, H, P), dtype), sds((B, S, H), jnp.float32),
                sds((H,), jnp.float32), sds((B, S, G, N), dtype),
                sds((B, S, G, N), dtype), sds((H,), jnp.float32))


def _guard_copy(sds, _):
    from repro.kernels.mpk_guard import guard_copy_pallas
    return guard_copy_pallas, (sds((4096, 128), jnp.uint32),
                               sds((), jnp.uint32), sds((), jnp.uint32))


def _mac_batch(sds, _):
    from repro.kernels.mpk_guard import mac_batch_pallas
    return mac_batch_pallas, (sds((16, 256, 128), jnp.uint32),
                              sds((), jnp.uint32))


def _mac_update(sds, _):
    from repro.kernels.mpk_guard import mac_update_pallas
    return mac_update_pallas, (sds((128,), jnp.uint32),
                               sds((4096, 128), jnp.uint32))


@pytest.mark.parametrize("make,dtype", [
    (_decode_attention, jnp.float32),
    (_decode_attention, jnp.bfloat16),
    (_flash_attention, jnp.float32),
    (_flash_attention, jnp.bfloat16),
    (_ssd_scan, jnp.float32),
    (_guard_copy, None),
    (_mac_batch, None),
    (_mac_update, None),
], ids=["decode_attention-f32", "decode_attention-bf16", "flash_attention-f32",
        "flash_attention-bf16", "ssd_scan-f32", "guard_copy", "mac_batch",
        "mac_update"])
def test_kernel_compiles_for_v5e(chip, make, dtype):
    fn, args = make(chip, dtype)
    compiled = _compile(fn, *args)
    assert "tpu_custom_call" in compiled.as_text()


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _engine_step(chip, cfg, B, S, dtype=jnp.float32):
    """The decode step as ``ServingEngine`` jits it (state donated, per-slot
    positions), compiled for one chip → (compiled, state shapes)."""
    from repro.models import decode_step, init_decode_state, init_params
    from repro.models.transformer import Impl
    impl = Impl(remat=False)
    params = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))

    def state_of(p):
        st = init_decode_state(cfg, p, B, S, dtype=dtype, impl=impl)
        st["pos"] = jnp.zeros((B,), jnp.int32)
        return st

    state = jax.eval_shape(state_of, params)
    on_chip = lambda t: jax.tree.map(lambda a: chip(a.shape, a.dtype), t)
    step = jax.jit(
        lambda p, s, t: decode_step(cfg, p, s, t, impl=impl, dtype=dtype),
        donate_argnums=(1,))
    compiled = step.lower(on_chip(params), on_chip(state),
                          chip((B, 1), jnp.int32)).compile()
    return compiled, state


def _live_bytes(m):
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _granite_chip_cut():
    """granite-4.0-h-small as one chip holds it: layers 0-9, experts 0-8."""
    from repro.configs import get_config, replace
    full = get_config("granite-4.0-h-small")
    return replace(full, num_layers=10, layer_types=full.layer_types[:10],
                   moe=replace(full.moe, held_experts=9))


def test_olmo_decode_step_fits_one_chip(chip):
    """OLMo-1B's decode step at chip_smoke's serving size, as the engine
    jits it, compiles for v5e and fits one chip's 16 GiB."""
    smoke = _chip_smoke()
    compiled, _ = _engine_step(chip, _olmo(), smoke.MAX_BATCH, smoke.MAX_SEQ,
                               getattr(jnp, smoke.DTYPE))
    m = compiled.memory_analysis()
    assert _live_bytes(m) < HBM_BYTES, m


def test_granite_decode_step_fits_one_chip(chip):
    """granite-4.0-h-small's decode step at one chip's cut (layers 0-9,
    experts 0-8 of 72, 16 slots x 512 positions, f32), as the engine jits
    it, compiles for v5e and fits one chip's 16 GiB."""
    compiled, _ = _engine_step(chip, _granite_chip_cut(), 16, 512)
    m = compiled.memory_analysis()
    assert 9.5e9 < m.argument_size_in_bytes and _live_bytes(m) < HBM_BYTES, m


@pytest.mark.parametrize("arch", ["olmo-1b", "mamba2-1.3b",
                                  "granite-4.0-h-small"])
def test_decode_step_updates_its_state_in_place(chip, arch):
    """At the benchmark cells' size and precision (16 slots x 512
    positions, f32, matrix products at "high"), the engine's step writes
    every cache back into the buffer it was given and copies no stacked
    cache; the dense step holds no buffer of a layer's K or V (it writes
    its new rows, and reads the layer inside the reductions)."""
    from repro.configs import get_config
    cfg = (_granite_chip_cut() if arch.startswith("granite")
           else get_config(arch))
    with jax.default_matmul_precision("high"):
        compiled, state = _engine_step(chip, cfg, 16, 512)
    m = compiled.memory_analysis()
    caches = jax.tree.leaves(state["caches"])
    assert m.alias_size_in_bytes >= sum(a.size * a.dtype.itemsize
                                        for a in caches), m
    stacks = {f"f32[{','.join(map(str, a.shape))}]" for a in caches}
    for line in compiled.as_text().splitlines():
        if " copy(" in line or " copy-start(" in line:
            # the copy's destination; one into memory space 1 (``S(1)``,
            # the core's own memory) is a prefetch, not a second buffer
            dest = line.split(" = ", 1)[-1].lstrip("(").split("}", 1)[0]
            assert "S(" in dest or not any(t in dest for t in stacks), line
    if cfg.family == "dense":
        k = state["caches"]["attn"]["k"]
        assert m.temp_size_in_bytes < k.size // k.shape[0] * 4, m
