"""Spans and counts the benchmark takes around the program's layers.

Nothing in the program changes: the probe wraps ``ServingEngine.tick``,
``._admit`` and ``._step`` on each engine instance (``EngineService._run``
and ``tick`` look them up through ``self``), and ``EngineService.handler``
and ``.handler_batch`` before the gateway registers them. Each span is a
host-clock interval kept in memory; in a traced run it is also a
``jax.profiler.TraceAnnotation``, so the trace shows what the host was
doing in each idle gap of the device.

Span names (the trace reduction attributes gaps by them; ``:<i>`` is the
index of the engine, which runs on the i-th chip of the cell):
  bench.tick:<i>            ServingEngine.tick, under EngineService's lock
  bench.admit:<i>           ServingEngine._admit, inside a tick
  bench.step_dispatch:<i>   ServingEngine._step, the jitted step's dispatch
  bench.handler             EngineService.handler or .handler_batch, on a
                            gateway thread
  bench.client_call         GatewayClient.call, on a client thread
"""
from __future__ import annotations

import contextlib
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple


def request_key(prompt, max_new: int) -> Tuple:
    return (max_new, *map(int, prompt))


@dataclass
class Tick:
    engine: int
    t0: float
    t1: float
    positions: List[int]        # position of the token each occupied slot fed


@dataclass
class Admitted:
    engine: int
    request: object             # the engine's Request
    admitted_at: float


@dataclass
class Probe:
    annotate: bool = False
    window: Optional[Tuple[float, float]] = None
    ticks: List[Tick] = field(default_factory=list)
    admitted: Dict[Tuple, Admitted] = field(default_factory=dict)
    tokens_in_window: Dict[Tuple, int] = field(default_factory=dict)
    # request key → (enter, exit) of the service handler call that served it
    handled: Dict[Tuple, Tuple[float, float]] = field(default_factory=dict)

    def __post_init__(self):
        self._lock = threading.Lock()

    def span(self, name: str):
        if self.annotate:
            import jax
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    def in_window(self, t: float) -> bool:
        return self.window is not None and self.window[0] <= t < self.window[1]

    def attach_engine(self, engine, idx: int):
        tick, admit, step = engine.tick, engine._admit, engine._step
        current = {}

        def _admit():
            before = {id(r) for r in engine.slots if r is not None}
            with self.span(f"bench.admit:{idx}"):
                admit()
            now = time.perf_counter()
            for r in engine.slots:
                if r is not None and id(r) not in before:
                    key = request_key(r.prompt, r.max_new)
                    with self._lock:
                        self.admitted[key] = Admitted(idx, r, now)

        def _step(params, state, token):
            positions, gen = [], []
            for b, r in enumerate(engine.slots):
                if r is None:
                    continue
                cursor = int(engine.prompt_cursor[b])
                positions.append(cursor - 1 + len(r.generated))
                if cursor >= len(r.prompt):
                    gen.append(r)
            current["positions"], current["gen"] = positions, gen
            with self.span(f"bench.step_dispatch:{idx}"):
                return step(params, state, token)

        def _tick():
            current.clear()
            t0 = time.perf_counter()
            with self.span(f"bench.tick:{idx}"):
                progressed = tick()
            t1 = time.perf_counter()
            if "positions" in current:
                gen = current["gen"]
                with self._lock:
                    self.ticks.append(Tick(idx, t0, t1, current["positions"]))
                    if self.in_window(t1):
                        for r in gen:
                            k = request_key(r.prompt, r.max_new)
                            self.tokens_in_window[k] = \
                                self.tokens_in_window.get(k, 0) + 1
            return progressed

        engine._admit, engine._step, engine.tick = _admit, _step, _tick

    def attach_service(self, svc):
        from repro.runtime.serve import decode_tokens
        handler, batch = svc.handler, svc.handler_batch

        def _served(reqs, call):
            # keys first: a request's payload may be a view of a ring slot
            keys = []
            for req in reqs:
                arr = decode_tokens(req)
                keys.append(request_key(arr[1:], int(arr[0])))
            t0 = time.perf_counter()
            try:
                with self.span("bench.handler"):
                    return call()
            finally:
                t1 = time.perf_counter()
                with self._lock:
                    for k in keys:
                        self.handled[k] = (t0, t1)

        svc.handler = lambda req: _served([req], lambda: handler(req))
        svc.handler_batch = lambda reqs: _served(reqs, lambda: batch(reqs))
