"""The engine tick keeps one step in flight: step t+1 is dispatched before
step t's tokens reach the host. Against a lockstep loop written here from
the same jitted step (upload, step, sample, read, then the slot loop), the
served tokens are the same, greedy and sampled, for a dense, an SSM and a
hybrid model with held experts; retirements by ``max_new`` and
``max_seq`` throw no step away, an EOS exactly one; a tick that dies
before it reads a request's last token fails that request. Tiny models on
the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced, replace
from repro.models import init_params
from repro.models.transformer import Impl
from repro.core.transports import ServiceCrashed
from repro.runtime import (EngineService, Request, ServingEngine,
                           encode_prompt)

IMPL = Impl(attention="naive", remat=False)
B, MAX_SEQ, SEED = 3, 16, 2147483713


def _config(arch):
    cfg = get_reduced(arch)
    if cfg.moe is not None:          # hold a share of the experts
        cfg = replace(cfg, moe=replace(cfg.moe, first_expert=2,
                                       held_experts=4))
    return cfg


@pytest.fixture(scope="module",
                params=["olmo-1b", "mamba2-1.3b", "granite-4.0-h-small"])
def model(request):
    cfg = _config(request.param)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _engine(model, greedy=True):
    cfg, params = model
    return ServingEngine(cfg, params, max_batch=B, max_seq=MAX_SEQ,
                         impl=IMPL, greedy=greedy, seed=SEED)


def _requests(vocab, eos=None):
    """More requests than slots, of mixed lengths; two run into max_seq
    (prompt + max_new > MAX_SEQ). ``eos``: {rid: eos id}."""
    shapes = [(3, 4), (1, 2), (10, 20), (5, 3), (2, 5), (14, 9), (4, 1),
              (6, 6)]
    reqs = [Request(rid=i, prompt=[(7 * i + j) % (vocab - 1) + 1
                                   for j in range(n)], max_new=m)
            for i, (n, m) in enumerate(shapes)]
    for rid, tok in (eos or {}).items():
        reqs[rid].eos_id = tok
    return reqs


def _lockstep(eng, reqs, greedy):
    """The tick without a step in flight, from the engine's own jitted
    step: → {rid: served tokens}."""
    state = eng._fresh_state()
    key = jax.random.PRNGKey(SEED)
    slots, queue = [None] * B, list(reqs)
    tok = np.zeros((B, 1), np.int32)
    cursor, pos = [0] * B, [0] * B
    out = {r.rid: [] for r in reqs}
    while queue or any(s is not None for s in slots):
        for b in range(B):
            if slots[b] is None and queue:
                slots[b] = r = queue.pop(0)
                state["caches"] = jax.tree.map(
                    lambda c: c.at[:, b].set(0) if c.ndim >= 2 else c,
                    state["caches"])
                state["pos"] = state["pos"].at[b].set(0)
                tok[b, 0], cursor[b], pos[b] = r.prompt[0], 1, 0
        logits, state = eng._step(eng.params, state,
                                  jax.device_put(tok.copy(), eng.device))
        if greedy:
            nxt = np.asarray(jnp.argmax(logits[:, -1], -1))
        else:
            key, k = jax.random.split(key)
            nxt = np.asarray(jax.random.categorical(k, logits[:, -1]))
        for b, r in enumerate(slots):
            if r is None:
                continue
            pos[b] += 1
            if cursor[b] < len(r.prompt):
                tok[b, 0] = r.prompt[cursor[b]]
                cursor[b] += 1
                continue
            t = int(nxt[b])
            out[r.rid].append(t)
            tok[b, 0] = t
            if (len(out[r.rid]) >= r.max_new or t == r.eos_id
                    or pos[b] >= MAX_SEQ - 1):
                slots[b] = None
    return out


def _serve(eng, reqs):
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    assert all(r.done for r in reqs) and not eng.in_flight
    return {r.rid: r.generated for r in reqs}


def _eos_ids(served):
    """For two requests, an EOS id that first appears at a served token
    with more after it, the latest such → {rid: (eos id, tokens up to
    it)}."""
    eos = {}
    for rid, toks in sorted(served.items()):
        k = max((k for k in range(len(toks) - 1)
                 if toks[k] not in toks[:k]), default=None)
        if k is not None:
            eos[rid] = (toks[k], toks[:k + 1])
        if len(eos) == 2:
            return eos
    raise AssertionError(f"no two requests to stop early: {served}")


def test_next_step_is_dispatched_before_the_tokens_are_read(model):
    eng = _engine(model)
    events, step, sample = [], eng._step, eng._sample

    class Read:
        """The samples of a step, logged when they reach the host."""

        def __init__(self, drawn):
            self.drawn = drawn

        def __array__(self, dtype=None, copy=None):
            events.append("read")
            return np.asarray(self.drawn)

    def spy_step(p, s, t):
        events.append("step")
        return step(p, s, t)

    def spy_sample(logits, feed, key):
        tokens, drawn, key = sample(logits, feed, key)
        return tokens, Read(drawn), key

    eng._step, eng._sample = spy_step, spy_sample
    for r in _requests(model[0].vocab_size):
        eng.submit(r)
    ticks = []
    while eng.queue or any(eng.slots) or eng.in_flight:
        del events[:]
        assert eng.tick()
        ticks.append(list(events))
    # the first tick has nothing to read, the last nothing to feed; every
    # other dispatches its step, then reads the one before it
    assert ticks[0] == ["step"] and ticks[-1] == ["read"]
    assert ticks[1:-1] == [["step", "read"]] * (len(ticks) - 2)
    assert eng.ticks == len(ticks) - 1 == eng.host_syncs
    assert eng.overlapped_ticks == eng.ticks - 1


@pytest.mark.parametrize("greedy", [True, False])
def test_served_tokens_are_the_lockstep_loops(model, greedy):
    cfg, _ = model
    got = _serve(_engine(model, greedy), _requests(cfg.vocab_size))
    want = _lockstep(_engine(model, greedy), _requests(cfg.vocab_size),
                     greedy)
    assert got == want
    long = [r for r in _requests(cfg.vocab_size)
            if len(r.prompt) + r.max_new > MAX_SEQ]
    assert [len(got[r.rid]) for r in long] == \
        [MAX_SEQ - len(r.prompt) for r in long]


def test_max_new_and_max_seq_discard_no_slot_step(model):
    cfg, _ = model
    eng = _engine(model)
    served = _serve(eng, _requests(cfg.vocab_size))
    assert eng.discarded_slot_steps == 0
    assert eng.decode_slot_ticks == sum(map(len, served.values()))
    assert eng.host_syncs == eng.ticks


@pytest.mark.parametrize("greedy", [True, False])
def test_eos_discards_one_slot_step_and_serves_nothing_past_it(model,
                                                               greedy):
    """The requests that stop at an EOS serve the tokens up to it. Greedy,
    every served token is the lockstep loop's; sampled, a request that
    boards after an EOS may draw other tokens, since the key is split once
    a step and its step comes one later than the lockstep loop's."""
    cfg, _ = model
    plain = _serve(_engine(model, greedy), _requests(cfg.vocab_size))
    eos = _eos_ids(plain)
    eng = _engine(model, greedy)
    reqs = _requests(cfg.vocab_size, {r: t for r, (t, _) in eos.items()})
    got = _serve(eng, reqs)
    for rid, (_, upto) in eos.items():
        assert got[rid] == upto
    if greedy:
        assert got == _lockstep(_engine(model), _requests(
            cfg.vocab_size, {r: t for r, (t, _) in eos.items()}), True)
    assert eng.discarded_slot_steps == len(eos)
    assert eng.decode_slot_ticks == \
        sum(map(len, got.values())) + len(eos)


def test_reset_loses_the_request_waiting_for_its_last_token(model):
    """A slot freed at its last token's step holds no request; the request
    still waits for that token, and a reset fails it with the rest."""
    cfg, _ = model
    eng = _engine(model)
    short = Request(rid=0, prompt=[1, 2], max_new=1)
    eng.submit(short)
    eng.submit(Request(rid=1, prompt=[3, 4, 5, 6], max_new=4))
    assert eng.tick() and eng.tick()         # the second makes its token
    assert all(s is not short for s in eng.slots)
    assert not short.done and eng.in_flight
    queued = Request(rid=2, prompt=[7], max_new=2)
    eng.submit(queued)
    lost = eng.reset()
    assert {r.rid for r in lost} == {0, 1, 2}
    assert not eng.in_flight and eng.tick() is False
    # the engine serves again from the reset grid
    assert _serve(eng, _requests(cfg.vocab_size)) == \
        _lockstep(_engine(model), _requests(cfg.vocab_size), True)


@pytest.mark.parametrize("dies_in", ["step", "read"])
def test_a_tick_that_dies_before_the_last_token_is_read_fails_its_request(
        model, dies_in):
    """Request 0 makes its one token in step 2 and its slot is freed right
    after that dispatch; tick 3 dies in its step, or in the read of step
    2's tokens. The service fails request 0 with ServiceCrashed, and
    serves again after."""
    eng = _engine(model)
    step, sample, calls = eng._step, eng._sample, {"step": 0, "read": 0}

    class Unreadable:
        def __array__(self, dtype=None, copy=None):
            raise RuntimeError("device read failed")

    def failing_step(p, s, t):
        calls["step"] += 1
        if dies_in == "step" and calls["step"] == 3:
            raise RuntimeError("device step failed")
        return step(p, s, t)

    def failing_sample(logits, feed, key):
        calls["read"] += 1
        tokens, drawn, key = sample(logits, feed, key)
        if dies_in == "read" and calls["read"] == 2:
            drawn = Unreadable()
        return tokens, drawn, key

    eng._step, eng._sample = failing_step, failing_sample
    svc = EngineService(eng, timeout=30.0).start()
    try:
        with pytest.raises(ServiceCrashed):
            svc.handler_batch([encode_prompt([1, 2], max_new=1),
                               encode_prompt([3, 4, 5, 6], max_new=4)])
        assert svc.crashes == 1 and calls[dies_in] == 3 - (dies_in == "read")
        assert not eng.in_flight and not eng._unread
        eng._step, eng._sample = step, sample
        out = svc.handler(encode_prompt([7, 1], max_new=3))
    finally:
        svc.close()
    assert np.asarray(out).size == 3
