"""The system under test, built as its users deploy it, and the load.

One chip: ``ServingEngine`` → ``EngineService`` → ``ServiceGateway
("mpklink_opt")`` with ``register_service(..., batch_handler=...)``.
Several chips: one engine per chip behind one service name, through
``register_engine_fleet`` (the replica router). Clients are
``GatewayClient``s calling ``encode_prompt(prompt, max_new)``.
"""
from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

from bench.probe import Probe, request_key

SERVICE = "model"
# a request still unanswered this long after the window closed has failed
DRAIN_LIMIT_S = 60.0


def make_params(ref, m, seed: int, devices):
    """Seeded f32 weights in one jitted call, made on the devices (one
    copy per device). → one weight tree per device."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    key = jax.random.PRNGKey(int(np.random.default_rng(
        [seed % 2**64, 7]).integers(0, 2**31)))
    mesh = Mesh(np.array(devices), ("replica",))
    init = jax.jit(lambda k: ref.init(m, k),
                   out_shardings=NamedSharding(mesh, PartitionSpec()))
    params = init(key)
    jax.block_until_ready(params)
    per_device = []
    for d in devices:
        per_device.append(jax.tree.map(
            lambda a: next(s.data for s in a.addressable_shards
                           if s.device == d), params))
    return per_device


@dataclass
class System:
    gw: object
    services: list
    engines: list

    def close(self):
        self.gw.close()
        for svc in self.services:
            svc.close()


def build(cell, params_per_device, devices, probe: Probe) -> System:
    import jax.numpy as jnp
    from repro.configs.base import ModelConfig, SSMConfig
    from repro.core.gateway import ServiceGateway
    from repro.runtime.serve import (EngineService, ServingEngine,
                                     register_engine_fleet)

    md = dict(cell.config["model"])
    if "ssm" in md:
        md["ssm"] = SSMConfig(**md["ssm"])
    cfg = ModelConfig(name=cell.config["name"], **md)
    serving = cell.config["serving"]
    engines = [ServingEngine(cfg, p, max_batch=serving["slots"],
                             max_seq=serving["positions"],
                             dtype=getattr(jnp, serving["dtype"]), device=d)
               for p, d in zip(params_per_device, devices)]
    for i, e in enumerate(engines):
        probe.attach_engine(e, i)
    gw = ServiceGateway("mpklink_opt")
    if len(engines) == 1:
        svc = EngineService(engines[0]).start()
        probe.attach_service(svc)
        gw.register_service(SERVICE, svc.handler,
                            batch_handler=svc.handler_batch)
        services = [svc]
    else:
        services = list(register_engine_fleet(gw, SERVICE, engines).values())
    return System(gw, services, engines)


def warm_up(system: System):
    """One request per engine straight to its handler: loads or compiles
    the step, the admission and the argmax programs."""
    from repro.runtime.serve import encode_prompt
    errors = []

    def first(svc):
        try:
            svc.handler(encode_prompt([1, 2, 3], max_new=2))
        except Exception as e:          # reported below, fails the run
            errors.append(f"{type(e).__name__}: {e}")

    threads = [threading.Thread(target=first, args=(s,))
               for s in system.services]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise RuntimeError("warm-up failed: " + "; ".join(errors))


@dataclass
class Result:
    req: object                 # generator.Req
    due: float                  # perf_counter time it was due to be sent
    sent: float = 0.0
    done: float = 0.0
    tokens: Optional[np.ndarray] = None
    error: Optional[str] = None

    @property
    def key(self):
        return request_key(self.req.prompt, self.req.max_new)


@dataclass
class Load:
    """Drives the traffic through gateway clients from ``t_zero``; the
    window is [t_zero + preroll, + seconds)."""
    system: System
    traffic: object
    probe: Probe
    t_zero: float = 0.0
    results: List[Result] = field(default_factory=list)
    clients: list = field(default_factory=list)

    def _call(self, client, res: Result):
        from repro.runtime.serve import decode_tokens, encode_prompt
        res.sent = time.perf_counter()
        try:
            with self.probe.span("bench.client_call"):
                out = client.call(SERVICE, encode_prompt(
                    res.req.prompt.tolist(), res.req.max_new))
            res.tokens = decode_tokens(out).copy()
        except Exception as e:          # a failed request, counted
            res.error = f"{type(e).__name__}: {e}"
        res.done = time.perf_counter()

    def start(self):
        """Open the clients and start sending now; the window opens after
        the mix's pre-roll. → (window start, window end)."""
        tr = self.traffic
        self.clients = [self.system.gw.connect(f"bench-client-{i}")
                        for i in range(tr.clients)]
        for c in self.clients:
            c.open(SERVICE)
        self._threads = []
        if tr.loop == "open":
            self._schedule = tr.schedule()
            self._due = queue.Queue()
            for c in self.clients:
                self._threads.append(threading.Thread(
                    target=self._open_worker, args=(c,), daemon=True))
            self._threads.append(threading.Thread(
                target=self._dispatch, daemon=True))
        else:
            for i, c in enumerate(self.clients):
                self._threads.append(threading.Thread(
                    target=self._closed_worker, args=(i, c), daemon=True))
        self.t_zero = time.perf_counter()
        w0 = self.t_zero + tr.preroll_s
        self.window = self.probe.window = (w0, w0 + tr.seconds)
        for t in self._threads:
            t.start()
        return self.window

    def _dispatch(self):
        for req in self._schedule:
            due = self.t_zero + req.send_at
            delay = due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            res = Result(req, due)
            self.results.append(res)
            self._due.put(res)
        for _ in self.clients:
            self._due.put(None)

    def _open_worker(self, client):
        while True:
            res = self._due.get()
            if res is None:
                return
            self._call(client, res)

    def _closed_worker(self, i, client):
        delay = self.t_zero - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        for req in self.traffic.client_requests(i):
            now = time.perf_counter()
            if now >= self.window[1]:
                return
            res = Result(req, now)
            self.results.append(res)
            self._call(client, res)

    def join(self) -> bool:
        """Wait for every request sent to be answered, at most
        DRAIN_LIMIT_S past the window's close. → all threads ended."""
        end = self.window[1] + DRAIN_LIMIT_S
        for t in self._threads:
            t.join(timeout=max(0.0, end - time.perf_counter()))
        return not any(t.is_alive() for t in self._threads)

    def macs_verified(self) -> int:
        return sum(c.macs_verified for c in self.clients)
