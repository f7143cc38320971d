"""Spans and counters inside the engine tick and the service lock, on a
tiny model with a recording sink in place of the profiler."""
import contextlib
import threading

import jax
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.models import init_params
from repro.models.transformer import Impl
from repro.runtime import EngineService, Request, ServingEngine, telemetry
from repro.runtime.serve import encode_prompt

IMPL = Impl(attention="naive", remat=False)
PHASES = ["engine.admit", "engine.dispatch", "engine.sample",
          "engine.bookkeep"]


class Recorder:
    """A sink that records each span's enter and exit, in order, with the
    thread that made it."""

    def __init__(self):
        self.events = []            # ("B" | "E", name, thread name)
        self._lock = threading.Lock()

    def _mark(self, kind, name):
        with self._lock:
            self.events.append((kind, name, threading.current_thread().name))

    @contextlib.contextmanager
    def _span(self, name):
        self._mark("B", name)
        try:
            yield
        finally:
            self._mark("E", name)

    def __call__(self, name):
        return self._span(name)

    def tree(self, thread=None):
        """The spans as nested [name, children] lists, for one thread."""
        root, stack = [], []
        for kind, name, th in self.events:
            if thread is not None and th != thread:
                continue
            if kind == "B":
                node = [name, []]
                (stack[-1][1] if stack else root).append(node)
                stack.append(node)
            else:
                assert stack and stack.pop()[0] == name
        assert not stack
        return root


@pytest.fixture
def recorder():
    rec = Recorder()
    telemetry.enable(rec)
    try:
        yield rec
    finally:
        telemetry.disable()


@pytest.fixture(scope="module")
def model():
    cfg = get_reduced("llama3.2-1b")
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _engine(model, **kw):
    cfg, params = model
    kw.setdefault("max_batch", 3)
    return ServingEngine(cfg, params, max_seq=32, impl=IMPL, **kw)


def _submit_mixed(eng):
    """Prompts of several lengths, more requests than slots."""
    for i, (n, max_new) in enumerate([(3, 4), (1, 2), (5, 3), (2, 5),
                                      (4, 1)]):
        eng.submit(Request(rid=i, prompt=list(range(1 + i, 1 + i + n)),
                           max_new=max_new))


def test_spans_off_return_the_shared_null_context(model):
    rec = Recorder()
    telemetry.enable(rec)
    telemetry.disable()
    a, b = telemetry.span("engine.tick:0"), telemetry.span("other")
    assert a is b and isinstance(a, contextlib.nullcontext)
    eng = _engine(model)
    _submit_mixed(eng)
    eng.run_until_drained()
    assert eng.ticks > 0 and rec.events == []


def test_default_sink_is_the_profiler_annotation():
    telemetry.enable()
    try:
        assert isinstance(telemetry.span("engine.tick:0"),
                          jax.profiler.TraceAnnotation)
    finally:
        telemetry.disable()


def test_each_tick_holds_its_phases_in_order(model, recorder):
    eng = _engine(model)
    dev = eng.device.id
    _submit_mixed(eng)
    eng.run_until_drained()
    ticks = recorder.tree()
    # one tick more than steps: the last dispatches only the sampling of
    # the last step, and reads its tokens
    assert len(ticks) == eng.ticks + 1
    resets = 0
    for i, (name, children) in enumerate(ticks):
        assert name == f"engine.tick:{dev}"
        phases = PHASES
        if i == 0:                  # no step in flight: nothing to read
            phases = PHASES[:2]
        assert [c[0] for c in children] == [f"{p}:{dev}" for p in phases]
        admit = children[0][1]
        assert all(c == [f"engine.slot_reset:{dev}", []] for c in admit)
        resets += len(admit)
    assert resets == 5          # one per admitted request
    # a tick with no slot to serve stops after admission
    recorder.events.clear()
    assert eng.tick() is False
    assert recorder.tree() == [[f"engine.tick:{dev}",
                                [[f"engine.admit:{dev}", []]]]]


def test_counters_and_stamps_follow_the_slots(model):
    eng = _engine(model)
    _submit_mixed(eng)
    reqs = list(eng.queue)
    occupied, step = [], eng._step

    def counting_step(p, s, t):
        occupied.append(sum(r is not None for r in eng.slots))
        return step(p, s, t)
    eng._step = counting_step
    first_tick, admit_tick, n = {}, {}, 0
    while eng.queue or any(s is not None for s in eng.slots) \
            or eng.in_flight:
        syncs, in_flight = eng.host_syncs, eng.in_flight
        queued = {r.rid for r in eng.queue}
        assert eng.tick()
        n += 1
        for r in reqs:
            if r.rid in queued and r.slot >= 0:
                admit_tick[r.rid] = n
            if r.generated and r.rid not in first_tick:
                first_tick[r.rid] = n
        # the token copy alone, of the step in flight: positions are known
        # on the host
        assert eng.host_syncs - syncs == in_flight
    for r in reqs:
        # the len(prompt)-th tick makes it, counting the one that admitted
        # it; the next reads it
        assert first_tick[r.rid] - admit_tick[r.rid] == len(r.prompt)
        assert (r.submitted_at <= r.admitted_at <= r.first_token_at
                <= r.finished_at)
    assert eng.prompt_slot_ticks == sum(len(r.prompt) - 1 for r in reqs)
    assert eng.decode_slot_ticks == sum(len(r.generated) for r in reqs)
    assert eng.prompt_slot_ticks + eng.decode_slot_ticks == sum(occupied)
    assert eng.ticks == len(occupied)
    assert eng.overlapped_ticks == eng.ticks - 1 == eng.host_syncs - 1
    assert eng.discarded_slot_steps == 0


def test_step_module_has_a_stable_name(model):
    eng = _engine(model, max_batch=2)
    text = eng._step.lower(eng.params, eng.state,
                           np.zeros((2, 1), np.int32)).as_text()
    assert "@jit_engine_decode_step" in text


def test_service_lock_waits_are_spans(model, recorder):
    eng = _engine(model, max_batch=2)
    dev = eng.device.id
    svc = EngineService(eng, timeout=60.0).start()
    try:
        out = svc.handler(encode_prompt([1, 2], max_new=2))
        outs = svc.handler_batch([encode_prompt([3], max_new=1),
                                  encode_prompt([4, 5], max_new=3)])
    finally:
        svc.close()
    assert np.asarray(out).size == 2 and [o.size for o in outs] == [1, 3]
    assert svc.cohorts_seen == 1 and svc.max_cohort == 2
    names = {(n, th) for k, n, th in recorder.events if k == "B"}
    assert (f"service.lock_wait.tick:{dev}", "engine-service") in names
    main = threading.current_thread().name
    assert (f"service.lock_wait.caller:{dev}", main) in names
    # a wait span closes before the work done under the lock: none holds
    # an engine span
    for th in {"engine-service", main}:
        for name, children in recorder.tree(th):
            if name.startswith("service.lock_wait."):
                assert children == []
    ticks = [t for t in recorder.tree("engine-service")
             if t[0].startswith("engine.tick:")]
    assert ticks and all(t[0] == f"engine.tick:{dev}" for t in ticks)
