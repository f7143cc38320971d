"""The reduction from a profiler trace to busy time, idle gaps and the
step's device time: on a hand-made trace with known answers, and on a
small slice of a trace recorded on the chip (``data/``)."""
import json
from pathlib import Path

import pytest

from bench import readings
from bench import trace as tm

FIXTURE = Path(__file__).parent / "data" / "trace_olmo1b_chat.json"
LABELS = {label for _, label in tm.GAP_CAUSES} | {tm.OUTSIDE_TICK}


def _recorded():
    fx = json.loads(FIXTURE.read_text())
    return {"devices": {int(d): v for d, v in fx["devices"].items()},
            "spans": fx["spans"]}


def test_hand_made_trace():
    ex = {"devices": {7: {
        "ops": [("a", 1.0, 2.0), ("b", 2.5, 1.0), ("c", 3.0, 1.0)],
        "modules": [("jit_step", 1.0, 3.0), ("jit_small", 3.0, 1.0)]}},
        "spans": [(tm.WINDOW_SPAN, 0.0, 10.0),
                  ("bench.tick:0", 0.0, 6.0),
                  ("bench.admit:0", 0.0, 0.6),
                  ("bench.step_dispatch:0", 0.6, 1.0),
                  ("bench.tick:1", 4.0, 9.0)]}
    red = tm.reduce(ex, {7: 0})
    # busy: [1, 3] and [2.5, 4] merge into [1, 4]
    assert red["busy_s"] == pytest.approx(3.0)
    assert red["window_s"] == pytest.approx(10.0)
    gaps = dict(red["idle_gaps"])
    # [0, 1] is idle with its middle in the admit span; [4, 10] has its
    # middle at 7, after engine 0's tick (engine 1's tick does not count)
    assert gaps == {"admit: slot reset outside jit": pytest.approx(1.0),
                    tm.OUTSIDE_TICK: pytest.approx(6.0)}
    assert red["device_ops"][0] == ["a", 2.0]
    assert tm.step_module(red)[0] == "jit_step"


def test_no_device_events_reduce_to_nothing():
    ex = {"devices": {0: {"ops": [], "modules": []}},
          "spans": [(tm.WINDOW_SPAN, 0.0, 1.0)]}
    assert tm.reduce(ex, {0: 0}) is None
    assert tm.reduce({"devices": {}, "spans": []}, {0: 0}) is None


def test_recorded_slice():
    red = tm.reduce(_recorded(), {0: 0})
    assert red["window_s"] == pytest.approx(0.1)
    gaps = sum(v for _, v in red["idle_gaps"])
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["busy_s"] + gaps == pytest.approx(red["window_s"])
    assert {k for k, _ in red["idle_gaps"]} <= LABELS
    secs = [v for _, v in red["device_ops"]]
    assert secs == sorted(secs, reverse=True) and len(secs) <= 10
    # the decode step is the module with most device time: four executions
    # of ~26 ms in this slice (default precision, 16 slots x 512)
    name, step = tm.step_module(red)
    assert name.startswith("jit__lambda") and step["count"] == 4
    run = readings.Run(None, None, 0.1, (0.0, 0.1), 0.0, [], "open",
                       None, None, trace=red)
    assert readings.step_device_s(run) == pytest.approx(0.02648, rel=1e-3)
