"""Runtime: trainer restart semantics, stragglers, serving engine, elastic."""
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import OptimizerConfig, TrainConfig, get_reduced
from repro.models import init_params
from repro.models.transformer import Impl
from repro.runtime import (FailureInjector, HeartbeatMonitor, Request,
                           ServingEngine, StragglerDetector, Trainer,
                           plan_remesh)

IMPL = Impl(attention="naive", remat=False)
TCFG = TrainConfig(microbatch_size=2, dtype="float32",
                   optimizer=OptimizerConfig(lr=1e-3, warmup_steps=2,
                                             total_steps=50),
                   log_every=0, checkpoint_every=3, keep_checkpoints=2)


def test_training_reduces_loss():
    cfg = get_reduced("smollm-360m")
    tr = Trainer(cfg, TCFG, global_batch=4, seq_len=32, impl=IMPL)
    rep = tr.run(20)
    first = np.mean(rep.losses[:4])
    last = np.mean(rep.losses[-4:])
    assert last < first, (first, last)


def test_restart_equivalence():
    """A failed+restarted run ends on the same trajectory as a clean run."""
    cfg = get_reduced("llama3.2-1b")
    with tempfile.TemporaryDirectory() as d:
        inj = FailureInjector({5: ["w1"]})
        tr = Trainer(cfg, TCFG, global_batch=4, seq_len=16, checkpoint_dir=d,
                     impl=IMPL, workers=["w0", "w1"], injector=inj)
        rep = tr.run(8)
        assert rep.restarts == 1
    clean = Trainer(cfg, TCFG, global_batch=4, seq_len=16, impl=IMPL)
    rep2 = clean.run(8)
    assert abs(rep.losses[-1] - rep2.losses[-1]) < 1e-4


def test_heartbeat_detection():
    mon = HeartbeatMonitor(["a", "b"], timeout=10.0)
    t0 = 1000.0
    mon.beat("a", at=t0)
    mon.beat("b", at=t0)
    assert mon.check(at=t0 + 5) == set()
    mon.beat("a", at=t0 + 11)
    assert mon.check(at=t0 + 12) == {"b"}
    assert mon.alive() == ["a"]


def test_straggler_detector():
    det = StragglerDetector(window=16, factor=2.0)
    flags = [det.observe(0.1) for _ in range(10)]
    assert not any(flags)
    assert det.observe(0.5)                       # 5× the median
    assert not det.observe(0.11)


def test_plan_remesh():
    assert plan_remesh(256, tp=16) == ((16, 16), ("data", "model"))
    assert plan_remesh(255, tp=16) == ((15, 16), ("data", "model"))
    assert plan_remesh(15, tp=16) is None


def test_guard_trip_recovers_from_checkpoint():
    """A tripped channel guard (corrupted exchange) restores the last
    checkpoint and resumes — same machinery as worker failures."""
    cfg = get_reduced("llama3.2-1b")
    with tempfile.TemporaryDirectory() as d:
        tr = Trainer(cfg, TCFG, global_batch=4, seq_len=16, checkpoint_dir=d,
                     impl=IMPL)
        real_fn = tr._fn()
        trip_at = {"step": 5, "armed": True}

        def wrapped(params, opt, batch):
            p, o, m = real_fn(params, opt, batch)
            m = dict(m)
            if trip_at["armed"] and int(tr.straggler._times.maxlen or 0) >= 0 \
                    and len(tr.straggler._times) == trip_at["step"]:
                m["guard_ok"] = 0.0
                trip_at["armed"] = False
            return p, o, m

        tr._step_fn = wrapped
        rep = tr.run(10)
        assert rep.guard_trips == 1
        assert any("guard tripped" in e for e in rep.events)
        assert rep.steps_run >= 10


def test_serving_continuous_batching():
    cfg = get_reduced("llama3.2-1b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, max_batch=4, max_seq=64, impl=IMPL)
    for i in range(6):
        eng.submit(Request(rid=i, prompt=[1 + i, 2, 3], max_new=5))
    done = eng.run_until_drained()
    assert len(done) == 6
    assert all(len(r.generated) == 5 for r in done)
    # batching actually happened: fewer ticks than sequential execution
    assert eng.ticks < 6 * (3 + 5)


def test_serving_determinism_vs_decode():
    """Engine output for one request == plain greedy decode."""
    from repro.models import decode_step, init_decode_state
    cfg = get_reduced("mamba2-1.3b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompt, n_new = [5, 9, 2], 4

    eng = ServingEngine(cfg, params, max_batch=2, max_seq=32, impl=IMPL)
    eng.submit(Request(rid=0, prompt=prompt, max_new=n_new))
    done = eng.run_until_drained()

    st = init_decode_state(cfg, params, 1, 32, dtype=jnp.float32, impl=IMPL)
    toks = list(prompt)
    out = []
    for t in range(len(prompt) + n_new - 1):
        cur = jnp.asarray([[toks[t] if t < len(toks) else out[-1]]], jnp.int32)
        lg, st = decode_step(cfg, params, st, cur, impl=IMPL, dtype=jnp.float32)
        nxt = int(jnp.argmax(lg[0, -1]))
        if t >= len(prompt) - 1:
            out.append(nxt)
    assert done[0].generated == out


def test_serving_engine_places_on_its_device():
    """No device → the backend's first device, explicitly; params, caches
    and every step's outputs live there."""
    cfg = get_reduced("olmo-1b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=16, impl=IMPL)
    assert eng.device == jax.devices()[0]
    # committed from the start, so the first admission's row reset cannot
    # drift to the default device
    assert all(leaf.committed for leaf in jax.tree.leaves(eng.state))
    eng.submit(Request(rid=0, prompt=[3, 4], max_new=2))
    eng.run_until_drained()
    placed = {d for leaf in jax.tree.leaves((eng.params, eng.state))
              for d in leaf.devices()}
    assert placed == {eng.device}
    dev = jax.devices()[-1]
    assert ServingEngine(cfg, params, max_batch=2, max_seq=16, impl=IMPL,
                         device=dev).device == dev


def test_serving_engine_rejects_device_of_another_platform():
    class ForeignDevice:
        platform = "tpu" if jax.default_backend() != "tpu" else "cpu"

    cfg = get_reduced("olmo-1b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="backend"):
        ServingEngine(cfg, params, max_batch=2, max_seq=16, impl=IMPL,
                      device=ForeignDevice())


def test_engine_fleet_replicas_run_in_process():
    """register_engine_fleet puts one EngineService per engine behind an
    in-process mpklink_opt replica transport: nothing is forked (a chip
    belongs to one process), answers decode to max_new int32 tokens."""
    import multiprocessing
    import threading

    from repro.core import ServiceGateway
    from repro.core.transports import MPKLinkOptTransport
    from repro.runtime.serve import (decode_tokens, encode_prompt,
                                     register_engine_fleet)

    cfg = get_reduced("olmo-1b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    engines = [ServingEngine(cfg, params, max_batch=2, max_seq=32, impl=IMPL)
               for _ in range(2)]
    gw = ServiceGateway("mpklink_opt")
    fleet = register_engine_fleet(gw, "llm", engines, timeout=60.0)
    try:
        assert sorted(fleet) == [0, 1]
        reps = gw.fleet("llm")._replicas
        assert all(isinstance(reps[r].transport, MPKLinkOptTransport)
                   for r in fleet)
        outs = [None] * 6

        def call(i):
            cli = gw.connect(f"fleet-client-{i}")
            outs[i] = decode_tokens(cli.call(
                "llm", encode_prompt([1 + i, 2, 3], max_new=4)))

        threads = [threading.Thread(target=call, args=(i,)) for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert all(o is not None and o.dtype == np.int32 and o.shape == (4,)
                   for o in outs), outs
        assert sum(reps[r].served for r in fleet) == 6
        assert not multiprocessing.active_children()
        assert all(svc.crashes == 0 for svc in fleet.values())
    finally:
        gw.close()
        for svc in fleet.values():
            svc.close()


def test_pallas_interpret_follows_backend(monkeypatch):
    from repro.utils import pallas_interpret
    assert pallas_interpret() is (jax.default_backend() == "cpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pallas_interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(NotImplementedError, match="gpu"):
        pallas_interpret()


def test_compile_cache_dir_from_env_or_fixed_repo_path(monkeypatch):
    from repro import utils
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert utils.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = utils.enable_compile_cache()
        assert got == str(utils.REPO_CACHE_DIR) \
            == jax.config.jax_compilation_cache_dir
        assert utils.REPO_CACHE_DIR.name == ".jax_cache"
        assert utils.REPO_CACHE_DIR.parent == Path(__file__).resolve().parents[1]
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
