"""The check that decides ``correct``, against the timed path broken
underneath it. Each fault is planted in the program's own path (the
engine's jitted step, or the service's answer) of a tiny cell on the CPU,
past the harness's look for a chip; the run must then come out not
correct, on the number that should catch it."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import manifest
from bench import run as bench_run
from bench.probe import Probe
from bench.tests.tiny import tiny_cell

CELLS = [w["name"] for w in manifest.load_manifest()["workloads"]
         if w["chips"] == 1]


def _token_shifted(step):
    """Every served token altered where it is produced: the step's logits
    rolled by one, so greedy picks the neighbour of the best."""
    def faulty(params, state, token):
        logits, new = step(params, state, token)
        return jnp.roll(logits, 1, axis=-1), new
    return faulty


def _state_unchanged(step):
    """A step that returns its state unchanged: no cache or recurrent
    state is written, no position advances."""
    def faulty(params, state, token):
        logits, _ = step(params, state, token)
        return logits, state
    return faulty


FAULTS = {"token_altered": ("max_gap", _token_shifted),
          "state_unchanged": ("max_gap", _state_unchanged)}


def _run(cell):
    return bench_run.run(cell, 2**31 + 13, 2.0, False, jax.devices()[:1],
                         None, t_start=time.perf_counter())


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell_name", CELLS)
def test_step_fault_is_not_correct(monkeypatch, cell_name, fault):
    check, wrap = FAULTS[fault]
    attach = Probe.attach_engine

    def attach_faulty(self, engine, idx):
        engine._step = wrap(engine._step)
        attach(self, engine, idx)

    monkeypatch.setattr(Probe, "attach_engine", attach_faulty)
    res = _run(tiny_cell(cell_name))
    assert res["correct"] is False
    c = res["checks"][check]
    assert c["value"] is not None and c["value"] > c["limit"]


@pytest.mark.parametrize("cell_name", CELLS)
def test_answer_altered_on_delivery_is_not_correct(monkeypatch, cell_name):
    """The engine's tokens are right, the answer the service returns is
    not: its last token is changed after the engine produced it."""
    attach = Probe.attach_service

    def alter(out):
        out = np.array(out, np.int32)
        out[-1] = out[-1] + 1
        return out

    def attach_faulty(self, svc):
        handler, batch = svc.handler, svc.handler_batch
        svc.handler = lambda req: alter(handler(req))
        svc.handler_batch = lambda reqs: [alter(o) for o in batch(reqs)]
        attach(self, svc)

    monkeypatch.setattr(Probe, "attach_service", attach_faulty)
    res = _run(tiny_cell(cell_name))
    assert res["correct"] is False
    assert res["checks"]["tokens_not_the_engines"]["value"] > 0
