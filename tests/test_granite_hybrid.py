"""The pattern hybrid with held experts (granite-4.0-h-small, reduced):
engine decode against the full forward, the expert shares against the
uncut layer, the routing by hand, the slot reset and the expert-load
counter."""
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_reduced, replace
from repro.models import forward, init_params
from repro.models import moe as moe_mod
from repro.models.layers import apply_mlp
from repro.models.transformer import Impl
from repro.runtime.serve import Request, ServingEngine

IMPL = Impl(attention="naive", remat=False)


def _share_cfg(first=2, held=4):
    cfg = get_reduced("granite-4.0-h-small")
    return replace(cfg, moe=replace(cfg.moe, first_expert=first,
                                    held_experts=held))


def _engine(cfg, batch=3, max_seq=32, seed=0):
    params = init_params(cfg, jax.random.PRNGKey(seed))
    return params, ServingEngine(cfg, params, max_batch=batch,
                                 max_seq=max_seq, impl=IMPL)


def _prompt(seed, n, vocab):
    return np.random.default_rng(seed).integers(0, vocab, n).tolist()


def test_engine_decode_matches_forward():
    """Prompts fed one token a tick, two slots admitted at different
    ticks: every logit the engine computed equals the full forward's at
    that position."""
    cfg = _share_cfg()
    params, eng = _engine(cfg)
    seen = []                                  # (request, position, logits)
    step = eng._step

    def spy(p, s, t):
        fed = [(r, eng.position(b), b) for b, r in enumerate(eng.slots)
               if r is not None]
        logits, s = step(p, s, t)
        rows = np.asarray(logits[:, 0])
        seen.extend((r, pos, rows[b]) for r, pos, b in fed)
        return logits, s

    eng._step = spy
    first = Request(0, _prompt(1, 7, cfg.vocab_size), max_new=5)
    eng.submit(first)
    for _ in range(3):
        eng.tick()
    second = Request(1, _prompt(2, 4, cfg.vocab_size), max_new=6)
    eng.submit(second)
    eng.run_until_drained()
    assert first.done and second.done
    assert first.slot != second.slot

    for req in (first, second):
        seq = req.prompt + req.generated
        want = np.asarray(forward(cfg, params, {"tokens": jnp.asarray([seq])},
                                  impl=IMPL, dtype=jnp.float32)[0][0])
        got = [(pos, row) for r, pos, row in seen if r is req]
        assert [pos for pos, _ in got] == list(range(len(seq) - 1))
        for pos, row in got:
            np.testing.assert_allclose(row[:cfg.vocab_size],
                                       want[pos, :cfg.vocab_size],
                                       rtol=2e-4, atol=2e-5)


def test_expert_shares_add_up_to_the_uncut_layer():
    """Held ranges that tile the experts: their parts, with the shared
    expert counted once, are the whole layer; so are their loads."""
    cfg = get_reduced("granite-4.0-h-small")
    E, k = cfg.moe.num_experts, cfg.moe.top_k
    p = moe_mod.init_held_experts(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, cfg.d_model))
    whole, whole_load = moe_mod.apply_held_experts(cfg, p, x)
    shared = apply_mlp(cfg, p["shared"], x)
    parts, held_load = 0.0, []
    for lo, hi in ((0, 3), (3, 6), (6, E)):
        c = replace(cfg, moe=replace(cfg.moe, first_expert=lo,
                                     held_experts=hi - lo))
        ps = dict(p, **{w: p[w][lo:hi] for w in ("gate", "up", "down")})
        out, load = moe_mod.apply_held_experts(c, ps, x)
        parts = parts + (out - shared)
        held_load.append(load[..., :-1])
        np.testing.assert_array_equal(load[..., -1],
                                      k - load[..., :-1].sum(-1))
    np.testing.assert_allclose(parts + shared, whole, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(jnp.concatenate(held_load, -1),
                                  whole_load[..., :-1])
    np.testing.assert_array_equal(whole_load.sum(-1), k)
    assert float(jnp.abs(whole - shared).max()) > 1e-3    # experts add


def test_routing_is_top_k_then_softmax():
    cfg = get_reduced("granite-4.0-h-small")        # 8 experts, top 3
    logits = [0.3, -1.0, 2.0, 0.7, 1.5, -0.2, 0.0, 0.69]
    gates = moe_mod.route_top_k(
        cfg, jnp.eye(8, cfg.d_model).T, jnp.asarray([logits + [0.0] * 56]))
    top = [2, 4, 3]
    z = sum(math.exp(logits[e]) for e in top)
    want = [math.exp(logits[e]) / z if e in top else 0.0 for e in range(8)]
    np.testing.assert_allclose(np.asarray(gates[0]), want, rtol=1e-6)


def test_slot_reset_zeroes_both_state_kinds_of_that_slot_only():
    params, eng = _engine(_share_cfg())
    eng.state["caches"] = jax.tree.map(jnp.ones_like, eng.state["caches"])
    eng.submit(Request(0, [1, 2, 3], max_new=2))
    eng._admit()
    b = eng.slots.index(next(r for r in eng.slots if r is not None))
    caches = eng.state["caches"]
    assert set(caches) == {"mamba", "attn"}
    for leaf in jax.tree.leaves(caches):
        leaf = np.asarray(leaf)
        assert (leaf[:, b] == 0).all()
        assert (np.delete(leaf, b, axis=1) == 1).all()
    np.testing.assert_array_equal(np.asarray(eng.state["occupied"]),
                                  [int(i == b) for i in range(eng.B)])


def test_expert_load_counts_occupied_slots_without_a_host_sync():
    cfg = _share_cfg()
    params, eng = _engine(cfg, batch=4)
    assert eng.expert_load().sum() == 0
    req = Request(0, _prompt(3, 6, cfg.vocab_size), max_new=4)
    eng.submit(req)
    eng.run_until_drained()
    syncs = eng.host_syncs
    load = eng.expert_load()
    assert eng.host_syncs == syncs == eng.ticks     # one sync a tick
    assert load.shape == (cfg.num_layers, cfg.moe.held + 1)
    # one slot of four was occupied for every step that fed a token
    fed = len(req.prompt) + len(req.generated) - 1
    assert eng.ticks == fed
    np.testing.assert_array_equal(load.sum(-1), cfg.moe.top_k * fed)
    assert load[:, -1].sum() > 0 and load[:, :-1].sum() > 0
    eng.tick()                                       # empty grid: no step
    np.testing.assert_array_equal(eng.expert_load(), load)


def test_param_count_is_what_init_makes():
    cfg = get_reduced("granite-4.0-h-small")
    params = init_params(cfg, jax.random.PRNGKey(0))
    assert cfg.param_count() == sum(x.size for x in jax.tree.leaves(params))
