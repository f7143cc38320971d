"""Serving engine: continuous batching over a fixed slot grid.

Requests (prompts) occupy slots of a size-B decode batch; every engine tick
runs ONE jitted decode_step for all slots with per-slot positions, its
state donated so that each layer's new KV rows are written in place
(kvcache.dense_cache_insert_rows into the carried stack). New requests join
as slots free up — no batch-wide barrier, the production pattern for
high-throughput decode. Prompt tokens are fed incrementally through the
same decode path (teacher-forced), then generation continues from the
model's samples until EOS/max_new.

The tick keeps one step in flight: it dispatches step t+1 before it reads
step t's tokens. A jitted sampling program (``engine_sample``) picks each
generating slot's next input from step t's logits on the device, so the
host uploads only prompt tokens and reads one int32 array a tick, while
step t+1 runs. Retirement by ``max_new`` or ``max_seq`` is known when the
step is dispatched: the slot is freed right after it, and its request
retires when the next tick reads the last token. An EOS is seen one step
late: the token the slot made in the step after it is discarded.

Serves every configuration whose caches take per-slot positions: dense
KV caches, SSM states, and both side by side in the hybrids (ring caches
need uniform positions and are served by the batch path / dry-run cells).
A model with held experts also counts, on the device, the routed choices
of the occupied slots (``expert_load``).
"""
from __future__ import annotations

import itertools
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.transports import ServiceCrashed
from repro.models import decode_step, init_decode_state
from repro.models.transformer import Impl
from repro.runtime import telemetry


@dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new: int = 16
    eos_id: Optional[int] = None
    # lane-12 QoS class (framing.PRIO_*): urgent requests are admitted to
    # freed decode slots ahead of older bulk work (docs/protocol.md §10)
    priority: int = 0
    # filled by the engine
    generated: List[int] = field(default_factory=list)
    slot: int = -1
    done: bool = False
    # perf_counter stamps: queued, given a slot, first token, retired
    submitted_at: float = 0.0
    admitted_at: float = 0.0
    first_token_at: float = 0.0
    finished_at: float = 0.0


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, *, max_batch: int = 8,
                 max_seq: int = 256, impl: Impl = Impl(remat=False),
                 dtype=jnp.float32, greedy: bool = True, seed: int = 0,
                 device: Optional[jax.Device] = None):
        """``device`` is where the params, caches and every decode step
        live; None places them on the default backend's first device. A
        device of another platform than the backend raises: the engine
        would otherwise serve from a device nobody meant to use."""
        assert cfg.swa_window is None or max_seq <= cfg.swa_window, \
            "ring caches need uniform positions; lower max_seq or use dense"
        if device is None:
            device = jax.devices()[0]
        elif device.platform != jax.default_backend():
            raise ValueError(
                f"ServingEngine device {device} is a {device.platform!r} "
                f"device but the backend is {jax.default_backend()!r}")
        self.device = device
        self.cfg, self.params = cfg, jax.device_put(params, device)
        self.B, self.max_seq = max_batch, max_seq
        self.impl, self.dtype = impl, dtype
        self.key = jax.device_put(jax.random.PRNGKey(seed), device)

        self.state = self._fresh_state()

        def engine_decode_step(p, s, t):    # its module: jit_engine_decode_step
            return decode_step(cfg, p, s, t, impl=impl, dtype=dtype)
        # the state is donated: the step writes its caches in place, and
        # the caller's state is gone once the step is dispatched
        self._step = jax.jit(engine_decode_step, donate_argnums=(1,))

        def engine_sample(logits, feed, key):   # its module: jit_engine_sample
            """→ the next step's tokens (the host's feed where it gives
            one, ≥ 0, else the slot's own sample), the samples, the key."""
            if greedy:
                drawn = jnp.argmax(logits[:, -1], -1)
            else:
                key, k = jax.random.split(key)
                drawn = jax.random.categorical(k, logits[:, -1])
            drawn = drawn.astype(jnp.int32)[:, None]
            return jnp.where(feed >= 0, feed, drawn), drawn, key
        self._sample = jax.jit(engine_sample)

        self.slots: List[Optional[Request]] = [None] * max_batch
        self.queue: List[Request] = []
        # prompt tokens fed by dispatched steps, and all tokens fed (the
        # slot's position in ``state["pos"]``)
        self.prompt_cursor = np.zeros(max_batch, np.int64)
        self._pos = np.zeros(max_batch, np.int64)
        self.completed: List[Request] = []
        # the logits of the step in flight; for each dispatched step whose
        # tokens the host has not read, oldest first, (slot, request, last)
        # for each token it makes: kept until its tokens are taken in, so
        # a tick that dies before then still knows who waits for them
        self._logits = None
        self._unread: List[List[Tuple[int, Request, bool]]] = []
        # written by the tick alone: ticks that ran the step, and those
        # that ran it with the previous step's tokens still unread;
        # occupied slots a step that fed a prompt token and made none, and
        # those that made one; blocking device-to-host reads (the sampled
        # tokens); tokens made after an EOS, thrown away
        self.ticks = 0
        self.overlapped_ticks = 0
        self.prompt_slot_ticks = 0
        self.decode_slot_ticks = 0
        self.host_syncs = 0
        self.discarded_slot_steps = 0
        # span names, tagged with the chip whose idle time they explain
        (self._sp_tick, self._sp_admit, self._sp_slot_reset,
         self._sp_dispatch, self._sp_sample, self._sp_bookkeep) = (
            f"engine.{phase}:{device.id}" for phase in
            ("tick", "admit", "slot_reset", "dispatch", "sample", "bookkeep"))

    def _fresh_state(self):
        """An empty decode state, made on the engine's device and committed
        to it: an uncommitted cache would let the first admission's
        un-jitted row reset run on the default device (a copy of the whole
        cache on device 0 per replica)."""
        with jax.default_device(self.device):
            state = init_decode_state(self.cfg, self.params, self.B,
                                      self.max_seq, dtype=self.dtype,
                                      impl=self.impl)
            state["pos"] = jnp.zeros((self.B,), jnp.int32)
            if "occupied" in state:          # the expert-load counter's mask
                state["occupied"] = jnp.zeros((self.B,), jnp.int32)
        return jax.device_put(state, self.device)

    def _state_lost(self) -> bool:
        """The state was donated to a step whose output never came back: a
        step that failed after dispatch, or a caller of ``_step`` that kept
        its input in place of the output. Its buffers are gone; the engine
        starts again from an empty state, and any slot still in flight
        continues without what the lost state held. The logits of the step
        in flight are its output, not its state, and are still read."""
        return any(a.is_deleted() for a in jax.tree.leaves(self.state))

    # -- request management -----------------------------------------------
    def submit(self, req: Request):
        req.submitted_at = time.perf_counter()
        self.queue.append(req)

    def _admit(self):
        from repro.core.gateway import priority_rank    # lazy: no cycle
        for b in range(self.B):
            if self.slots[b] is None and self.queue:
                # priority-aware admission (docs/protocol.md §10): the
                # most urgent class boards first, FIFO within a class —
                # the stable (rank, arrival) key means pure-FIFO behavior
                # is unchanged when every request is PRIO_NORMAL
                i = min(range(len(self.queue)),
                        key=lambda k: (priority_rank(self.queue[k].priority),
                                       k))
                req = self.queue.pop(i)
                req.slot = b
                req.admitted_at = time.perf_counter()
                self.slots[b] = req
                # reset slot: zero its cache rows + position
                with telemetry.span(self._sp_slot_reset):
                    self.state["caches"] = jax.tree.map(
                        lambda c: c.at[:, b].set(0) if c.ndim >= 2 else c,
                        self.state["caches"])
                    self.state["pos"] = self.state["pos"].at[b].set(0)
                    self._mark_occupied(b, 1)
                self.prompt_cursor[b] = 0
                self._pos[b] = 0

    def _release(self, b: int):
        """Free slot ``b`` for admission; its request may still wait for
        its last token."""
        self.slots[b] = None
        self._mark_occupied(b, 0)

    def _retire(self, req: Request):
        req.done = True
        req.finished_at = time.perf_counter()
        self.completed.append(req)
        if self.slots[req.slot] is req:
            self._release(req.slot)

    def _mark_occupied(self, b: int, flag: int):
        occupied = self.state.get("occupied")
        # temporary, until the harness's state_unchanged fault hands back a
        # copy of its donated input (PERF.md §7): a state lost to the step
        # just dispatched is made anew next tick, so its mask is not marked
        if occupied is not None and not occupied.is_deleted():
            self.state["occupied"] = occupied.at[b].set(flag)

    def expert_load(self) -> Optional[np.ndarray]:
        """Routed (token, expert) choices of the occupied slots since the
        engine was made: (layers, held experts + 1), the last column for
        the experts held elsewhere; None for a model without held experts.
        A blocking device read, so never called inside the tick."""
        load = self.state.get("expert_load")
        return None if load is None else np.asarray(load)

    # -- engine tick ---------------------------------------------------------
    def tick(self):
        """One step of the slot grid, one step in flight: admit; build the
        feed for the slots the next step runs; dispatch the sampling of the
        step in flight and then the next step; read the samples of the step
        in flight (the tick's one host sync, while the next step runs);
        take them into their requests. Its spans, in order, cover it: admit
        (with each slot reset), dispatch, sample, bookkeep; a tick with no
        step in flight reads nothing, one with no slot to feed dispatches
        only the sampling. An EOS is read one step late: the token its
        slot made in the step after it is discarded
        (``discarded_slot_steps``).
        → False when there was nothing to do."""
        span = telemetry.span
        with span(self._sp_tick):
            with span(self._sp_admit):
                if self._state_lost():
                    self.state = self._fresh_state()
                self._admit()
                feeding = any(s is not None for s in self.slots)
                if not feeding and not self.in_flight:
                    return False
            with span(self._sp_dispatch):
                drawn = self._dispatch(feeding)
            if drawn is not None:
                with span(self._sp_sample):
                    drawn = np.asarray(drawn)
                    self.host_syncs += 1
                with span(self._sp_bookkeep):
                    self._bookkeep(drawn)
        return True

    def _feed(self):
        """The host's tokens for the next step, a fresh array each tick
        (a step in flight may still read the last one): each occupied
        slot's next prompt token, or −1 where it takes its own sample.
        Advances the prompt cursors. → (feed, (slot, request, last) for
        each token the step makes)."""
        feed = np.zeros((self.B, 1), np.int32)
        makes = []
        for b, req in enumerate(self.slots):
            if req is None:
                continue
            cur, n = int(self.prompt_cursor[b]), len(req.prompt)
            feed[b, 0] = req.prompt[cur] if cur < n else -1
            self.prompt_cursor[b] = cur = min(cur + 1, n)
            if cur < n:
                self.prompt_slot_ticks += 1
                continue
            # the step makes token number pos + 2 - n, at position pos + 1
            pos = self.position(b)
            last = pos + 2 - n >= req.max_new or pos + 1 >= self.max_seq - 1
            makes.append((b, req, last))
            self.decode_slot_ticks += 1
        return feed, makes

    def _dispatch(self, feeding: bool):
        """Dispatch the sampling of the step in flight, if any, then, when
        a slot is fed, the next step; free the slots whose last token that
        step makes. → the samples of the step that was in flight, on the
        device, or None."""
        feed, makes = self._feed()
        tokens, drawn = jax.device_put(feed, self.device), None
        if self.in_flight:
            tokens, drawn, self.key = self._sample(self._logits, tokens,
                                                   self.key)
            self._logits = None
        if not feeding:
            return drawn
        self.overlapped_ticks += drawn is not None
        self._logits, self.state = self._step(self.params, self.state,
                                              tokens)
        self.ticks += 1
        self._unread.append(makes)
        for b, req in enumerate(self.slots):
            if req is not None:
                self._pos[b] += 1
        for b, _, last in makes:
            if last:
                self._release(b)
        return drawn

    def _bookkeep(self, drawn: np.ndarray):
        """Take the oldest unread step's samples into the requests it made
        tokens for; retire a request at its last token or an EOS."""
        now = time.perf_counter()
        for b, req, last in self._unread[0]:
            if req.done:                      # retired by an EOS a step ago
                self.discarded_slot_steps += 1
                continue
            tok = int(drawn[b, 0])
            if not req.generated:
                req.first_token_at = now
            req.generated.append(tok)
            if last or (req.eos_id is not None and tok == req.eos_id):
                self._retire(req)
        del self._unread[0]

    def position(self, b: int) -> int:
        """Slot ``b``'s position in ``state["pos"]``, known on the host:
        admission zeroes it and every step adds one, so it counts the
        tokens the dispatched steps have fed the slot."""
        return int(self._pos[b])

    @property
    def in_flight(self) -> bool:
        """A step is dispatched whose tokens the host has not read."""
        return self._logits is not None

    def run_until_drained(self, max_ticks: int = 10_000):
        while (self.queue or any(s is not None for s in self.slots)
               or self.in_flight) and self.ticks < max_ticks:
            self.tick()
        return self.completed

    def reset(self) -> List[Request]:
        """Crash recovery: drop all in-flight work and return to an empty
        slot grid (caches/positions are re-zeroed per slot on admit; a
        state lost to a failed donated step is made anew).
        → the requests that were lost (queued, slotted, and those waiting
        for their last token)."""
        lost = {}
        for r in itertools.chain(
                self.slots, (r for makes in self._unread for _, r, _ in makes),
                self.queue):
            if r is not None and not r.done:
                lost.setdefault(id(r), r)
        self.slots = [None] * self.B
        self.queue = []
        self._logits, self._unread = None, []
        self.prompt_cursor[:] = 0
        self._pos[:] = 0
        if self._state_lost():
            self.state = self._fresh_state()
        self.state["pos"] = jax.device_put(
            np.zeros((self.B,), np.int32), self.device)
        if "occupied" in self.state:
            self.state["occupied"] = jax.device_put(
                np.zeros((self.B,), np.int32), self.device)
        return list(lost.values())


# ---------------------------------------------------------------------------
# gateway-facing front-end
# ---------------------------------------------------------------------------

def encode_prompt(prompt: List[int], max_new: int = 16) -> np.ndarray:
    """Gateway wire format for EngineService: int32 [max_new, *prompt]."""
    return np.asarray([max_new, *prompt], np.int32)


def decode_tokens(arr) -> np.ndarray:
    """A wire payload of int32 words → flat int32 array: an EngineService
    request at its handler, or its answer at the client. Transport hops
    are byte-oriented, so either can arrive as raw bytes (a fleet-routed
    answer always does, often as a read-only view of a region/arena
    slot); a contiguous payload is reinterpreted in place."""
    arr = np.asarray(arr)
    if arr.dtype != np.int32:
        if arr.flags.c_contiguous and arr.nbytes % 4 == 0:
            arr = arr.reshape(-1).view(np.uint8).view(np.int32)
        else:
            arr = np.frombuffer(np.ascontiguousarray(arr).tobytes(),
                                np.int32)
    return arr.reshape(-1)


class _SpannedLock:
    """A lock whose acquisition, and not its hold, is one named span:
    ``with`` takes the lock inside ``span(name)`` and releases it on exit."""

    __slots__ = ("_lock", "_name")

    def __init__(self, lock: threading.Lock, name: str):
        self._lock, self._name = lock, name

    def __enter__(self):
        with telemetry.span(self._name):
            self._lock.acquire()

    def __exit__(self, *exc):
        self._lock.release()


class EngineService:
    """Thread-safe inference service over a :class:`ServingEngine`.

    The engine itself is single-threaded (one jitted decode step over the
    slot grid). This wrapper runs the tick loop on ONE background thread and
    lets N concurrent callers (gateway service threads) submit prompts and
    block until their request retires — continuous batching absorbs the
    concurrency: all admitted prompts share every decode step, so aggregate
    throughput scales with occupancy, not callers.

    ``handler`` is the gateway/transport service handler: request payload is
    int32 ``[max_new, tok0, tok1, ...]`` (see :func:`encode_prompt`),
    response is the int32 generated-token array.

    Self-healing: if the tick loop dies mid-decode (a crashed engine
    worker), the loop marks every in-flight request failed with a typed
    :class:`ServiceCrashed` (so gateway retry layers fail over immediately
    instead of waiting out the deadline), resets the slot grid, and keeps
    serving — the next submit decodes on the recovered engine.
    """

    def __init__(self, engine: ServingEngine, *, timeout: float = 300.0,
                 idle_wait: float = 0.02):
        self.engine = engine
        self.timeout = timeout
        self._idle_wait = idle_wait
        self._lock = threading.Lock()           # guards engine + tables
        # the same lock, with the wait for it a span: the tick loop's, and
        # the handler threads'
        dev = engine.device.id
        self._tick_lock = _SpannedLock(self._lock,
                                       f"service.lock_wait.tick:{dev}")
        self._caller_lock = _SpannedLock(self._lock,
                                         f"service.lock_wait.caller:{dev}")
        self._events: Dict[int, threading.Event] = {}
        self._done: Dict[int, Request] = {}
        self._failed: Dict[int, BaseException] = {}
        self._abandoned: set = set()            # timed-out rids: drop results
        self._rid = itertools.count()
        self._consumed = 0                      # engine.completed drained so far
        self._work = threading.Event()          # submit signal for idle loop
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.crashes = 0                        # tick-loop crashes survived
        self.cohorts_seen = 0                   # batch submissions taken
        self.max_cohort = 0                     # the largest of them
        self._inject_crash = False              # test hook: die on next tick

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> "EngineService":
        if self._thread is None:
            self._thread = threading.Thread(target=self._run, daemon=True,
                                            name="engine-service")
            self._thread.start()
        return self

    def close(self):
        self._stop.set()
        self._work.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
        # fail every still-blocked caller fast instead of letting them sit
        # out the full timeout against a dead tick loop
        with self._lock:
            pending = list(self._events.values())
            self._events.clear()
        for ev in pending:
            ev.set()

    # -- tick loop (one thread owns the engine) -----------------------------
    def inject_crash(self):
        """Chaos hook: make the next engine tick die (deterministically)."""
        self._inject_crash = True
        self._work.set()

    def _recover(self, cause: BaseException):
        """Crash containment: deliver anything that finished during the
        dying tick, fail every truly in-flight request with a typed
        ServiceCrashed NOW (no deadline stall), reset the engine, keep
        serving."""
        with self._lock:
            self.crashes += 1
            events = []
            # requests the crashing tick already retired completed honestly
            # — deliver them, don't strand their callers for the deadline
            for req in self.engine.completed[self._consumed:]:
                if req.rid in self._abandoned:
                    self._abandoned.discard(req.rid)
                    continue
                self._done[req.rid] = req
                events.append(self._events.pop(req.rid, None))
            del self.engine.completed[:]
            self._consumed = 0
            lost = self.engine.reset()
            exc = ServiceCrashed(
                f"engine worker crashed mid-decode ({type(cause).__name__}: "
                f"{cause}); request lost — safe to retry")
            for req in lost:
                if req.rid in self._abandoned:
                    self._abandoned.discard(req.rid)
                    continue
                self._failed[req.rid] = exc
                events.append(self._events.pop(req.rid, None))
        for ev in events:
            if ev is not None:
                ev.set()

    def _run(self):
        while not self._stop.is_set():
            try:
                with self._tick_lock:
                    if self._inject_crash:
                        self._inject_crash = False
                        raise RuntimeError("injected engine crash")
                    progressed = self.engine.tick()
                    fresh = self.engine.completed[self._consumed:]
                    # drain: the service owns the engine, and an unbounded
                    # completed list is a leak at serving timescales
                    del self.engine.completed[:]
                    self._consumed = 0
                    for req in fresh:
                        if req.rid in self._abandoned:  # caller timed out
                            self._abandoned.discard(req.rid)
                            continue
                        self._done[req.rid] = req
                    events = [self._events.pop(r.rid, None) for r in fresh]
            except Exception as e:      # a dead tick loop strands callers —
                self._recover(e)        # heal and keep serving instead
                continue
            for ev in events:
                if ev is not None:
                    ev.set()
            if not progressed:
                self._work.wait(timeout=self._idle_wait)
                self._work.clear()

    # -- service handler (called from N transport/gateway threads) ----------
    @staticmethod
    def _parse_req(req: np.ndarray):
        """Wire payload int32 ``[max_new, tok0, ...]`` → (max_new, prompt).

        The zero-copy data plane hands requests in as read-only views of a
        transport region/arena slot; a contiguous whole-word payload is
        reinterpreted in place (no tobytes() snapshot — the prompt ints are
        consumed before the handler returns, within the view's lifetime)."""
        arr = decode_tokens(req)
        if arr.size < 2:
            raise ValueError("inference request needs [max_new, tok0, ...]")
        return int(arr[0]), [int(t) for t in arr[1:]]

    def _cancel(self, rid: int):
        """Forget an in-flight request: already finished → drop its result;
        still queued → remove outright; already decoding in a slot → mark
        abandoned so its result is dropped at retirement instead of leaking
        into the done table."""
        self._events.pop(rid, None)
        if self._done.pop(rid, None) is not None \
                or self._failed.pop(rid, None) is not None:
            return                      # retired already — nothing to abandon
        before = len(self.engine.queue)
        self.engine.queue = [r for r in self.engine.queue if r.rid != rid]
        if len(self.engine.queue) == before:
            self._abandoned.add(rid)

    def _await(self, rid: int, ev: threading.Event,
               deadline: float) -> np.ndarray:
        """Block until ``rid`` retires (bounded by ``deadline``); return its
        generated tokens or raise its typed failure."""
        ev.wait(timeout=max(0.0, deadline - time.monotonic()))
        with self._caller_lock:
            done = self._done.pop(rid, None)
            failed = self._failed.pop(rid, None)
        if done is not None:
            return np.asarray(done.generated, np.int32)
        if failed is not None:          # engine crashed mid-decode: typed,
            raise failed                # immediate — retry layers fail over
        if self._stop.is_set():
            raise RuntimeError(
                f"EngineService closed while request {rid} was in flight")
        with self._caller_lock:
            self._cancel(rid)
        from repro.core import gateway as _gw     # no import cycle: lazy
        from repro.core.transports import DeadlineExpired
        remaining = _gw.remaining_budget()
        if remaining is not None and remaining <= 0:
            raise DeadlineExpired(
                f"inference request {rid}: caller's propagated deadline "
                "expired while decoding — request cancelled")
        raise TimeoutError(f"inference request {rid} timed out "
                           f"after {self.timeout}s")

    def _deadline(self) -> float:
        """This request's retirement deadline: the service's configured
        bound, TIGHTENED by the caller's propagated budget when the request
        arrived through the gateway with a deadline word (docs/protocol.md
        §9) — a 1 s caller budget bounds the decode wait at ~1 s instead of
        the service-wide default."""
        from repro.core import gateway as _gw     # no import cycle: lazy
        remaining = _gw.remaining_budget()
        bound = self.timeout if remaining is None \
            else min(self.timeout, max(0.0, remaining))
        return time.monotonic() + bound

    def handler(self, req: np.ndarray) -> np.ndarray:
        """One prompt in, one int32 token array out (the gateway/transport
        service handler). Blocks until the request retires from the shared
        decode batch or the service deadline expires."""
        max_new, prompt = self._parse_req(req)
        if self._stop.is_set():
            raise RuntimeError("EngineService is closed")
        # the caller's MAC-covered lane-12 class, published thread-locally
        # by the gateway's execution core — urgent prompts board freed
        # decode slots ahead of queued bulk work (docs/protocol.md §10)
        from repro.core import gateway as _gw     # no import cycle: lazy
        prio = _gw.current_priority()
        ev = threading.Event()
        with self._caller_lock:
            rid = next(self._rid)
            self._events[rid] = ev
            self.engine.submit(Request(rid=rid, prompt=prompt,
                                       max_new=max_new, priority=prio))
        self._work.set()
        return self._await(rid, ev, self._deadline())

    def handler_batch(self, reqs) -> List[np.ndarray]:
        """Batched prompt submission (the gateway's ``batch_handler``).

        All N prompts enter the engine queue under ONE lock acquisition and
        one wake signal, so they join the decode slot grid as a cohort and
        share every decode step from the first tick — continuous batching
        absorbs the whole batch instead of trickling it in per call. Both
        the explicit batch envelope AND an auto-coalesced cohort of inline
        calls (the gateway mux's scatter group) land here, so transparent
        coalescing reaches the decode grid as one admission unit
        (``cohorts_seen`` counts the submissions, ``max_cohort`` keeps the
        largest).
        Returns the N generated-token arrays in request order; if any
        request fails (engine crash mid-decode, timeout) its typed error is
        raised and the rest of the cohort is cancelled — the gateway turns
        that into per-item typed errors for the whole batch."""
        parsed = [self._parse_req(r) for r in reqs]
        if self._stop.is_set():
            raise RuntimeError("EngineService is closed")
        from repro.core import gateway as _gw     # no import cycle: lazy
        prio = _gw.current_priority()   # the cohort's most-urgent class
        waits = []
        with self._caller_lock:
            self.cohorts_seen += 1
            self.max_cohort = max(self.max_cohort, len(parsed))
            for max_new, prompt in parsed:
                rid = next(self._rid)
                ev = threading.Event()
                self._events[rid] = ev
                self.engine.submit(
                    Request(rid=rid, prompt=prompt, max_new=max_new,
                            priority=prio))
                waits.append((rid, ev))
        self._work.set()
        deadline = self._deadline()
        outs: List[np.ndarray] = []
        for k, (rid, ev) in enumerate(waits):
            try:
                outs.append(self._await(rid, ev, deadline))
            except BaseException:
                with self._caller_lock:  # don't strand the rest of the cohort
                    for later_rid, _ in waits[k + 1:]:
                        self._cancel(later_rid)
                raise
        return outs

    __call__ = handler


# ---------------------------------------------------------------------------
# replica fleets (N engines behind one service name)
# ---------------------------------------------------------------------------

def register_engine_fleet(gw, name: str, engines: List[ServingEngine], *,
                          timeout: float = 300.0) -> Dict[int, EngineService]:
    """Register one in-process replica per engine behind one service name
    on ``gw`` (a :class:`repro.core.gateway.ServiceGateway`). Build the
    engines one per device (``ServingEngine(..., device=d)``): a chip
    belongs to one process, so engine replicas are never forked — each is
    an :class:`EngineService` tick loop in this process behind its own
    ``mpklink_opt`` transport instance (own protection domain and epoch),
    routed by the fleet's :class:`repro.core.gateway.ReplicaRouter`.
    Forked ``procwire`` replicas remain for handlers that do not touch
    JAX. → {replica id: its started EngineService}, in join order."""
    fleet = {}
    for engine in engines:
        svc = EngineService(engine, timeout=timeout).start()
        fleet[gw.register_replica(name, svc.handler,
                                  transport="mpklink_opt")] = svc
    return fleet
