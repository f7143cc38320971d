"""Readers of the program's own stamps on each request: what they read
from a run, and nothing (without raising) from a program that lacks the
stamps."""
from types import SimpleNamespace

import pytest

from bench import manifest
from bench.probe import Admitted, Probe

WINDOW = (10.0, 20.0)


def _run(requests):
    probe = Probe()
    for i, r in enumerate(requests):
        probe.admitted[(i,)] = Admitted(0, r, r.admitted_at)
    return SimpleNamespace(probe=probe, window=WINDOW)


def _req(admitted_at, first_token_at):
    return SimpleNamespace(admitted_at=admitted_at,
                           first_token_at=first_token_at)


def test_prefill_reads_admission_to_first_token_in_the_window():
    read = manifest.metric_reader("engine.prefill_s.chat")
    inside = [_req(9.0 + k, 10.0 + 2 * k) for k in range(5)]   # 1..5 s
    outside = [_req(1.0, 9.5),          # first token before the window
               _req(15.0, 20.0),        # at its close
               _req(18.0, 0.0)]         # no token yet
    got = read(_run(inside + outside))
    assert got == pytest.approx(4.6)    # p90 of 1, 2, 3, 4, 5


def test_prefill_is_silent_without_the_stamps():
    read = manifest.metric_reader("engine.prefill_s.chat")
    assert read(_run([])) is None
    # a Request from before the stamps: admitted_at is not the program's
    assert read(_run([SimpleNamespace(admitted_at=12.0)])) is None
