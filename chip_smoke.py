#!/usr/bin/env python3
"""Chip smoke test: OLMo-1B served through the MPKLink gateway on a TPU.

Drives the system's main path once, in this one process (a chip belongs to
one process, so nothing here forks):

  GatewayClient → core/framing seal + MAC → mpklink_opt ring → core/gateway
  → runtime/serve.EngineService → ServingEngine → jitted decode_step

  python chip_smoke.py            # one chip: served path, logits parity,
                                  # Pallas kernels compiled on the chip
  python chip_smoke.py --chips 4  # only the replica fleet: 4 in-process
                                  # engine replicas, one per chip, vs 1

OLMo-1B runs at its published widths (arXiv:2402.00838: 16 layers, d_model
2048, 16 heads x 128, d_ff 8192, vocab 50304) with random weights made from
--seed. Without a TPU the script exits non-zero and prints no result. The
last line of stdout is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "olmo-1b"
# Serving size: f32 params (4.7 GB) + an f32 cache of MAX_BATCH x MAX_SEQ
# (1.07 GB) + the step's un-donated outputs and temporaries fit one v5e
# chip's 16 GiB; tests/test_tpu_compile.py checks the compiled step's
# memory_analysis against that. f32 so the parity phase compares like with
# like.
MAX_BATCH = 8
MAX_SEQ = 512
DTYPE = "float32"
# served traffic: concurrent gateway clients, prompts of a few hundred tokens
N_CLIENTS = 8
N_REQUESTS = 16
PROMPT_LEN = (192, 320)
MAX_NEW = 16
# fleet traffic (--chips 4): one request per client, so every replica
# wire is busy; prompts are shorter because a replica wire carries one
# request at a time (the fleet's session model)
FLEET_REQUESTS = 16
FLEET_PROMPT_LEN = (48, 96)
# parity: prompt tokens fed one per tick through the engine's own step
PARITY_LEN = 48
# Tolerance of the parity phase, relative to the largest reference logit.
# Both sides run in f32 with f32-accurate matmuls ("highest"); they differ
# only in summation order (one token at a time through the cache vs the
# whole prompt at once, naive vs chunked attention), which over 16 layers
# leaves ~1e-5 of the logit scale. A wrong cache row, position or mask
# moves logits by O(1) of their scale — the mutated-prompt control below
# shows the bound can fail.
PARITY_RTOL = 2e-3
# Decode-attention kernel vs the f32 reference at "highest": the kernel's
# in-VMEM dots may run as single bf16 passes (2^-8 relative on q·k and
# p·v), so outputs — convex combinations of N(0, 1) values — may move by
# ~1e-2; a wrong block, head or mask moves them by O(1).
KERNEL_ATOL = 3e-2
KERNEL_CACHE_LEN = 2048         # decode-attention cache length (OLMo context)
MAC_ROWS = 4096                 # 2 MiB uint32 payload for the MAC kernels


def _fail(msg: str, code: int = 1):
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def _say(msg: str):
    print(msg, flush=True)


class CompileStats:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events (compiles run on the engine thread too)."""

    def __init__(self, jax):
        self._lock = threading.Lock()
        self.secs = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.secs += duration

    def _event(self, event, **_):
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

    def line(self) -> str:
        return (f"compile: {self.secs:.2f} s backend compile, "
                f"persistent cache hits={self.hits} misses={self.misses}")


def make_prompts(n, vocab, lo, hi, seed):
    import numpy as np
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=int(rng.integers(lo, hi + 1)))
            .tolist() for _ in range(n)]


def build_params(cfg, seed, device):
    """Seeded random f32 params, made on ``device`` in one jitted program."""
    import jax
    from jax.sharding import SingleDeviceSharding
    from repro.models import init_params
    init = jax.jit(lambda k: init_params(cfg, k),
                   out_shardings=SingleDeviceSharding(device))
    params = init(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    return params


def serve_through_gateway(gw, service, prompts, max_new, vocab, n_clients):
    """``n_clients`` concurrent GatewayClients send ``prompts``; every
    answer must be exactly ``max_new`` in-vocab tokens. → (tokens, wall s,
    response MACs the clients verified)."""
    from repro.runtime.serve import decode_tokens, encode_prompt
    clients = [gw.connect(f"smoke-client-{i}") for i in range(n_clients)]
    for c in clients:
        c.open(service)
    results = [None] * len(prompts)
    errors = []

    def worker(i):
        for j in range(i, len(prompts), n_clients):
            try:
                results[j] = decode_tokens(clients[i].call(
                    service, encode_prompt(prompts[j], max_new)))
            except Exception as e:        # reported below, fails the run
                errors.append(f"request {j}: {type(e).__name__}: {e}")

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    if errors:
        raise RuntimeError("requests failed:\n  " + "\n  ".join(errors))
    for j, r in enumerate(results):
        if r is None or r.shape != (max_new,):
            raise RuntimeError(f"request {j}: expected {max_new} tokens, "
                               f"got {None if r is None else r.shape}")
        if r.min() < 0 or r.max() >= vocab:
            raise RuntimeError(f"request {j}: token outside the vocab: {r}")
    macs = sum(c.macs_verified for c in clients)
    if macs < len(prompts):
        raise RuntimeError(f"only {macs} response MACs verified for "
                           f"{len(prompts)} requests")
    return sum(r.size for r in results), wall, macs


def warm_up(services):
    """First request per engine, straight to its handler: compiles the
    decode step off the gateway's clock (and off its response deadline)."""
    from repro.runtime.serve import encode_prompt
    errors = []

    def first(svc):
        try:
            svc.handler(encode_prompt([1, 2, 3], max_new=2))
        except Exception as e:            # reported below, fails the run
            errors.append(f"{svc.engine.device}: {type(e).__name__}: {e}")

    t0 = time.perf_counter()
    threads = [threading.Thread(target=first, args=(svc,))
               for svc in services]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise RuntimeError("warm-up failed:\n  " + "\n  ".join(errors))
    return time.perf_counter() - t0


def memory_line(label, devices):
    """bytes_in_use / peak_bytes_in_use per device after a phase (the peak
    is the process's running maximum)."""
    stats = [d.memory_stats() or {} for d in devices]
    _say(f"memory after {label}: bytes_in_use="
         f"{[s.get('bytes_in_use') for s in stats]} peak_bytes_in_use="
         f"{[s.get('peak_bytes_in_use') for s in stats]}")


def check_crashes(services):
    for svc in services:
        if svc.crashes:
            raise RuntimeError(
                f"EngineService on {svc.engine.device} crashed "
                f"{svc.crashes}x (the self-healing loop swallowed a device "
                "failure)")


def served_phase(cfg, engine, stats):
    from repro.core.gateway import ServiceGateway
    from repro.runtime.serve import EngineService
    svc = EngineService(engine).start()
    gw = ServiceGateway("mpklink_opt")
    try:
        gw.register_service("olmo", svc.handler,
                            batch_handler=svc.handler_batch)
        warm = warm_up([svc])
        _say(f"warm-up: {warm:.2f} s (first request, decode-step compile)")
        prompts = make_prompts(N_REQUESTS, cfg.vocab_size, *PROMPT_LEN,
                               seed=1)
        ntok, wall, macs = serve_through_gateway(
            gw, "olmo", prompts, MAX_NEW, cfg.vocab_size, N_CLIENTS)
        check_crashes([svc])
        _say(f"served: {len(prompts)} requests from {N_CLIENTS} clients, "
             f"prompts {min(map(len, prompts))}-{max(map(len, prompts))} "
             f"tokens, {ntok} tokens generated, wall {wall:.3f} s "
             f"(host clock), {engine.ticks} engine ticks, "
             f"{macs} response MACs verified, gateway "
             f"macs_verified={gw.stats['macs_verified']}, crashes=0")
    finally:
        gw.close()
        svc.close()
    _say(stats.line())


def parity_phase(cfg, engine):
    """The engine's own jitted decode step, fed a prompt one token per
    step through a fresh cache, vs a full forward — both f32 at
    "highest" matmul precision; last-position logits compared."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import forward, init_decode_state
    from repro.models.transformer import Impl

    rng = np.random.default_rng(2)
    toks = rng.integers(0, cfg.vocab_size, size=(engine.B, PARITY_LEN),
                        dtype=np.int32)
    other = toks.copy()
    other[:, -1] = (other[:, -1] + 1) % cfg.vocab_size
    fwd = jax.jit(lambda p, t: forward(
        cfg, p, {"tokens": t}, impl=Impl(remat=False), dtype=jnp.float32,
        last_only=True)[0][:, -1])
    with jax.default_matmul_precision("highest"):
        with jax.default_device(engine.device):
            state = init_decode_state(cfg, engine.params, engine.B,
                                      engine.max_seq, dtype=engine.dtype,
                                      impl=engine.impl)
            state["pos"] = jnp.zeros((engine.B,), jnp.int32)
        for t in range(PARITY_LEN):
            logits, state = engine._step(
                engine.params, state,
                jax.device_put(toks[:, t:t + 1], engine.device))
        got = np.asarray(logits[:, -1], np.float64)
        ref = np.asarray(fwd(engine.params, toks), np.float64)
        ctl = np.asarray(fwd(engine.params, other), np.float64)
    scale = float(np.abs(ref).max())
    err = float(np.abs(got - ref).max())
    err_ctl = float(np.abs(got - ctl).max())
    tol = PARITY_RTOL * scale
    _say(f"parity: decode_step x{PARITY_LEN} vs forward, max|dlogit| "
         f"{err:.3e} (tol {tol:.3e} = {PARITY_RTOL} x max|logit| "
         f"{scale:.3e}); mutated-prompt control {err_ctl:.3e}")
    if not np.isfinite(got).all() or err > tol:
        raise RuntimeError(f"decode/forward parity failed: {err} > {tol}")
    if err_ctl <= tol:
        raise RuntimeError("parity control did not fail: the bound "
                           "cannot tell a different prompt apart")


def _compiled(fn, *args):
    """jit + compile ``fn`` for the chip; require a Mosaic kernel in the
    compiled HLO (a compiled kernel, not an interpreted one)."""
    import jax
    compiled = jax.jit(fn).lower(*args).compile()
    if "tpu_custom_call" not in compiled.as_text():
        raise RuntimeError(f"{getattr(fn, '__name__', fn)}: no "
                           "tpu_custom_call in the compiled HLO")
    return compiled


def kernel_phase(cfg, device):
    """Each main-path Pallas kernel, compiled, once at OLMo-1B widths,
    against its reference in kernels/ref.py."""
    import functools
    import jax
    import jax.numpy as jnp
    from repro.kernels.decode_attention import decode_attention_pallas
    from repro.kernels.mpk_guard import (guard_copy_pallas, mac_batch_pallas,
                                         mac_finalize, mac_init_state,
                                         mac_update_pallas)
    from repro.kernels.ref import attention_ref, mac_ref
    from repro.models.transformer import Impl

    with jax.default_device(device):
        B, S = MAX_BATCH, KERNEL_CACHE_LEN
        H, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        ks = jax.random.split(jax.random.PRNGKey(3), 4)
        q = jax.random.normal(ks[0], (B, 1, H, Dh), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, Hkv, Dh), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, Hkv, Dh), jnp.float32)
        lengths = jax.random.randint(ks[3], (B,), 1, S + 1)
        slots = jnp.arange(S, dtype=jnp.int32)[None]
        kp = jnp.where(slots < lengths[:, None], slots, -1)
        qp = (lengths - 1)[:, None].astype(jnp.int32)
        kc = Impl().kv_chunk
        attn = functools.partial(decode_attention_pallas, kv_chunk=kc)
        got = _compiled(attn, q, k, v, qp, kp)(q, k, v, qp, kp)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(attention_ref)(q, k, v, qp, kp)
        err = float(jnp.abs(got - ref).max())
        _say(f"kernel decode_attention: B={B} S={S} H={H}x{Dh} kv_chunk={kc} "
             f"f32, compiled (tpu_custom_call), max|err| vs attention_ref "
             f"{err:.3e} (atol {KERNEL_ATOL})")
        if not err <= KERNEL_ATOL:
            raise RuntimeError(f"decode attention kernel off: {err}")

        payload = jax.random.bits(jax.random.PRNGKey(4), (MAC_ROWS, 128),
                                  jnp.uint32)
        tag = jnp.uint32(0x6A7E)
        want = int(jax.jit(mac_ref)(payload, tag))
        guard = _compiled(guard_copy_pallas, payload, tag, jnp.uint32(want))
        out, mac, ok = guard(payload, tag, jnp.uint32(want))
        tampered = payload.at[7, 5].set(payload[7, 5] ^ jnp.uint32(1))
        ok_bad = guard(tampered, tag, jnp.uint32(want))[2]
        if not (bool((out == payload).all()) and int(mac[0]) == want
                and int(ok[0]) == 1 and int(ok_bad[0]) == 0):
            raise RuntimeError("guard_copy kernel disagrees with mac_ref")

        stack = payload.reshape(16, MAC_ROWS // 16, 128)
        macs = _compiled(mac_batch_pallas, stack, tag)(stack, tag)
        want_each = [int(m) for m in jax.jit(jax.vmap(
            mac_ref, in_axes=(0, None)))(stack, tag)]
        if [int(m) for m in macs] != want_each:
            raise RuntimeError("mac_batch kernel disagrees with mac_ref")

        h = mac_init_state(tag)
        split = MAC_ROWS // 4
        h = _compiled(mac_update_pallas, h, payload[:split])(
            h, payload[:split])
        h = _compiled(mac_update_pallas, h, payload[split:])(
            h, payload[split:])
        if int(mac_finalize(h)) != want:
            raise RuntimeError("mac_update kernel disagrees with mac_ref")
        _say(f"kernel mpk_guard: guard_copy, mac_batch (16 frames) and "
             f"mac_update over a {MAC_ROWS}x128 uint32 payload, compiled "
             f"(tpu_custom_call), bit-identical to mac_ref; tampered frame "
             f"rejected")


def fleet_phase(cfg, devices, seed, stats):
    """4 in-process engine replicas, one per chip, behind one service name
    vs 1 replica, with the same requests."""
    import jax
    from repro.core.gateway import ServiceGateway
    from repro.runtime.serve import ServingEngine, register_engine_fleet

    params = build_params(cfg, seed, devices[0])
    engines = [ServingEngine(cfg, params, max_batch=MAX_BATCH,
                             max_seq=MAX_SEQ, device=d) for d in devices]
    del params
    prompts = make_prompts(FLEET_REQUESTS, cfg.vocab_size,
                           *FLEET_PROMPT_LEN, seed=5)
    for n in (1, len(engines)):
        gw = ServiceGateway("mpklink_opt")
        fleet = register_engine_fleet(gw, "olmo", engines[:n])
        try:
            warm = warm_up(fleet.values())
            ntok, wall, macs = serve_through_gateway(
                gw, "olmo", prompts, MAX_NEW, cfg.vocab_size,
                FLEET_REQUESTS)
            check_crashes(fleet.values())
            replicas = gw.fleet("olmo")._replicas
            for rid, svc in fleet.items():
                eng = svc.engine
                placed = {d for leaf in jax.tree.leaves(
                    (eng.params, eng.state)) for d in leaf.devices()}
                if placed != {eng.device}:
                    raise RuntimeError(f"replica {rid} arrays on {placed}, "
                                       f"not only {eng.device}")
                _say(f"  replica {rid}: device id {eng.device.id} "
                     f"({eng.device.device_kind}), served "
                     f"{replicas[rid].served}, crashes {svc.crashes}, "
                     f"params+state only on device {eng.device.id}")
            ids = [svc.engine.device.id for svc in fleet.values()]
            if len(set(ids)) != n:
                raise RuntimeError(f"replicas share devices: {ids}")
            if n > 1 and min(replicas[r].served for r in fleet) == 0:
                raise RuntimeError("a replica served nothing")
            _say(f"fleet {n} replica(s): {len(prompts)} requests ok, "
                 f"{ntok} tokens, wall {wall:.3f} s (host clock), warm-up "
                 f"{warm:.2f} s, {macs} response MACs verified, crashes=0")
            memory_line(f"fleet {n}", devices)
        finally:
            gw.close()
            for svc in fleet.values():
                svc.close()
                svc.engine.reset()
    _say(stats.line())


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the four-chip replica-fleet phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    try:
        from repro.utils import enable_compile_cache
    except ImportError:
        _fail("the repro package (src/repro) is not next to this script", 2)
    import jax

    devices = jax.devices()
    dev = devices[0]
    _say(f"device: platform={dev.platform} kind={dev.device_kind} "
         f"count={len(devices)}")
    if dev.platform != "tpu":
        _fail(f"no TPU found (JAX backend is {dev.platform!r}); this check "
              "runs only on the chip")
    if len(devices) < args.chips:
        _fail(f"--chips {args.chips} needs {args.chips} devices, found "
              f"{len(devices)}")
    _say(f"compile cache: {enable_compile_cache()}")
    stats = CompileStats(jax)

    from repro.configs import get_config
    cfg = get_config(ARCH)
    _say(f"model: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
         f"heads={cfg.num_heads}x{cfg.head_dim} kv_heads={cfg.num_kv_heads} "
         f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
         f"params={cfg.param_count() / 1e9:.3f}B, random weights seed "
         f"{args.seed}")
    _say(f"serving size: max_batch={MAX_BATCH} max_seq={MAX_SEQ} "
         f"dtype={DTYPE}")
    t0 = time.perf_counter()

    if args.chips == 4:
        fleet_phase(cfg, devices[:4], args.seed, stats)
    else:
        import jax.numpy as jnp
        from repro.runtime.serve import ServingEngine
        params = build_params(cfg, args.seed, dev)
        engine = ServingEngine(cfg, params, max_batch=MAX_BATCH,
                               max_seq=MAX_SEQ, dtype=getattr(jnp, DTYPE),
                               device=dev)
        del params
        memory_line("params and engine state", [dev])
        served_phase(cfg, engine, stats)
        memory_line("served phase", [dev])
        parity_phase(cfg, engine)
        memory_line("parity phase", [dev])
        kernel_phase(cfg, dev)
        memory_line("kernel phase", [dev])
        _say(stats.line())
    _say(f"wall: {time.perf_counter() - t0:.1f} s after device check")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
