"""Chaos conformance suite: the fault-injection fabric vs all six transports.

Every test drives a gateway through a seeded :class:`FaultPlan` and asserts
the three contract clauses:

  (a) no client ever hangs — every run finishes inside an explicit
      wall-clock budget (transports all have bounded response waits now);
  (b) every injected security fault surfaces as the CORRECT typed
      exception (FrameError vs AccessViolation vs ServiceCrashed vs
      ResponseTimeout — see faultwire.EXPECTED), enforced inside
      FaultyClient (a mis-typed or accepted fault raises FaultLeak);
  (c) an identical seed produces the identical fault schedule AND the
      identical outcome sequence.

On failure, the printed ``FaultPlan.from_spec(...)`` line replays the run.
"""
import time

import numpy as np
import pytest

from repro.core import PROC_TRANSPORTS, TRANSPORTS, ServiceGateway
from repro.core.faultwire import (ALL_KINDS, EXPECTED, FaultFabric, FaultPlan,
                                  FaultyClient)
from repro.core.transports import (HandlerCrash, MPKLinkOptTransport,
                                   ResponseTimeout, ServiceCrashed,
                                   ShmTransport)
from repro.core.wordcount import make_text, parse_count, wordcount_handler

TIMEOUT = 0.4                      # transport response deadline under chaos
WALL_BUDGET = 60.0                 # hard per-run bound: nothing may hang


def _chaos_gateway(transport: str) -> ServiceGateway:
    gw = ServiceGateway(transport, transport_kwargs={"timeout": TIMEOUT})
    gw.register_service("wordcount", wordcount_handler,
                        factory=lambda: wordcount_handler)
    return gw.start()


def _run(transport: str, plan: FaultPlan, *, retries: int = 0):
    """→ (outcome signature list, wall seconds). The signature is the
    deterministic fingerprint used by the replay test."""
    gw = _chaos_gateway(transport)
    fab = FaultFabric(plan).attach(gw)
    fc = FaultyClient(gw.connect("chaos-client", retries=retries), fab,
                      "wordcount")
    t0 = time.perf_counter()
    try:
        for i in range(plan.n_requests):
            n = 4 + i % 9
            out = fc.step(make_text(n, seed=i))
            if out.status == "ok":
                assert parse_count(out.value) == n, \
                    f"wrong answer at request {i} — replay: {plan.describe()}"
    finally:
        wall = time.perf_counter() - t0
        gw.close()
    sig = [(o.index, o.status, o.kind, type(o.value).__name__)
           for o in fc.outcomes]
    return sig, wall, fc


@pytest.mark.parametrize("name", sorted(TRANSPORTS))
def test_chaos_all_kinds_bounded_and_typed(name):
    """(a)+(b): full-kind plan on every transport — bounded wall clock,
    correct types (typing is enforced by FaultyClient: anything off raises
    FaultLeak), and zero collateral failures on non-faulted requests."""
    plan = FaultPlan(seed=2024, n_requests=40, rate=0.25)
    sig, wall, fc = _run(name, plan)
    assert wall < WALL_BUDGET, f"hung? {wall}s — replay: {plan.describe()}"
    counts = fc.counts()
    assert counts["error"] == 0, \
        (f"non-faulted request failed: "
         f"{[s for s in sig if s[1] == 'error']} — replay: {plan.describe()}")
    assert counts["fault"] + counts["recovered"] == len(plan.events)
    # every fault kind that fired surfaced as its EXPECTED type
    for o in fc.outcomes:
        if o.status == "fault":
            assert isinstance(o.value, EXPECTED[o.kind]), \
                f"{o} — replay: {plan.describe()}"


@pytest.mark.parametrize("name", sorted(TRANSPORTS))
@pytest.mark.parametrize("kind", ALL_KINDS)
def test_chaos_single_kind(name, kind):
    """(b) per cell: one fault kind × one transport, ≥2 injections."""
    plan = FaultPlan(seed=hash((name, kind)) & 0xFFFF, n_requests=12,
                     rate=0.25, kinds=(kind,))
    assert len(plan.events) >= 2
    sig, wall, fc = _run(name, plan)
    assert wall < WALL_BUDGET, f"hung? — replay: {plan.describe()}"
    assert fc.counts()["error"] == 0, f"replay: {plan.describe()}"
    expected = EXPECTED[kind]
    for o in fc.outcomes:
        if o.kind != kind:
            continue
        if expected is None:                       # delay: must complete
            assert o.ok, f"{o} — replay: {plan.describe()}"
        elif o.status == "fault":
            assert isinstance(o.value, expected), \
                f"{o} — replay: {plan.describe()}"


@pytest.mark.parametrize("name", ["mpklink_opt", "pipe", "shm"])
def test_chaos_identical_seed_identical_outcomes(name):
    """(c): the fault schedule AND the outcome sequence are pure functions
    of (seed, plan) — two full runs fingerprint identically."""
    spec = FaultPlan(seed=777, n_requests=30, rate=0.3).spec()
    p1, p2 = FaultPlan.from_spec(spec), FaultPlan.from_spec(spec)
    assert [e for e in p1.schedule()] == [e for e in p2.schedule()]
    sig1, _, _ = _run(name, p1)
    sig2, _, _ = _run(name, p2)
    assert sig1 == sig2, f"nondeterministic — replay: {p1.describe()}"


def test_chaos_retries_heal_liveness_faults():
    """With bounded retries + idempotency tokens, liveness faults (crash/
    drop) are transparently healed: the answer is still correct and the
    handler is never double-executed for an already-completed request."""
    calls = []

    def counting(req):
        calls.append(1)
        return wordcount_handler(req)

    gw = ServiceGateway("mpklink_opt", transport_kwargs={"timeout": TIMEOUT})
    gw.register_service("wordcount", counting, factory=lambda: counting)
    gw.start()
    plan = FaultPlan(seed=5, n_requests=20, rate=0.3,
                     kinds=("drop_response", "crash_handler"))
    fab = FaultFabric(plan).attach(gw)
    fc = FaultyClient(gw.connect("healer", retries=3), fab, "wordcount")
    try:
        for i in range(plan.n_requests):
            n = 5 + i % 4
            out = fc.step(make_text(n, seed=i))
            assert out.ok, f"{out} — replay: {plan.describe()}"
            assert parse_count(out.value) == n
    finally:
        gw.close()
    n_drops = sum(1 for e in plan.events.values()
                  if e.kind == "drop_response")
    # dropped responses were answered from the dedup window on retry —
    # executed exactly once; only crashes (pre-execution kills) re-execute
    assert gw.stats["deduped"] == n_drops
    assert len(calls) == plan.n_requests


# ---------------------------------------------------------------------------
# process-backed transports: the crash fault is now a REAL kill -9 of the
# service process (docs/protocol.md §6) — same contract clauses (a)/(b)/(c).
# Assertions are client-observable only: server-side fabric state (`fired`)
# lives in the forked child and dies with it.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(PROC_TRANSPORTS))
def test_chaos_proc_all_kinds_bounded_and_typed(name):
    """Full-kind plan against a real multiprocessing service: every fault
    typed, every wait bounded, zero collateral failures — with crash
    faults killing (and heals re-forking) actual OS processes."""
    plan = FaultPlan(seed=2024, n_requests=40, rate=0.25)
    sig, wall, fc = _run(name, plan)
    assert wall < WALL_BUDGET, f"hung? {wall}s — replay: {plan.describe()}"
    counts = fc.counts()
    assert counts["error"] == 0, \
        (f"non-faulted request failed: "
         f"{[s for s in sig if s[1] == 'error']} — replay: {plan.describe()}")
    for o in fc.outcomes:
        if o.status == "fault":
            assert isinstance(o.value, EXPECTED[o.kind]), \
                f"{o} — replay: {plan.describe()}"


@pytest.mark.parametrize("name", sorted(PROC_TRANSPORTS))
@pytest.mark.parametrize("kind", ALL_KINDS)
def test_chaos_proc_single_kind(name, kind):
    """8 fault kinds × 3 process-backed transports, ≥2 injections each,
    replayable from (seed, plan)."""
    plan = FaultPlan(seed=hash((name, kind)) & 0xFFFF, n_requests=12,
                     rate=0.25, kinds=(kind,))
    assert len(plan.events) >= 2
    sig, wall, fc = _run(name, plan)
    assert wall < WALL_BUDGET, f"hung? — replay: {plan.describe()}"
    assert fc.counts()["error"] == 0, f"replay: {plan.describe()}"
    expected = EXPECTED[kind]
    for o in fc.outcomes:
        if o.kind != kind:
            continue
        if expected is None:                       # delay: must complete
            assert o.ok, f"{o} — replay: {plan.describe()}"
        elif o.status == "fault":
            assert isinstance(o.value, expected), \
                f"{o} — replay: {plan.describe()}"


@pytest.mark.parametrize("name", ["mpklink_opt_proc", "shm_proc"])
def test_chaos_proc_identical_seed_identical_outcomes(name):
    """(c) across process boundaries: the shared-memory fault index keeps
    the schedule monotonic across forks and heals, so two full runs still
    fingerprint identically."""
    spec = FaultPlan(seed=777, n_requests=30, rate=0.3).spec()
    p1, p2 = FaultPlan.from_spec(spec), FaultPlan.from_spec(spec)
    sig1, _, _ = _run(name, p1)
    sig2, _, _ = _run(name, p2)
    assert sig1 == sig2, f"nondeterministic — replay: {p1.describe()}"


def test_chaos_proc_crash_is_a_real_sigkill():
    """The crash fault kind must actually kill -9 the service process —
    not just raise in a thread. Verified via the dead child's exitcode."""
    import signal as _signal

    gw = _chaos_gateway("mpklink_opt_proc")
    sessions = []
    orig_connect = gw.transport.connect

    def tracking_connect(*a, **kw):
        s = orig_connect(*a, **kw)
        sessions.append(s)
        return s

    gw.transport.connect = tracking_connect
    plan = FaultPlan(seed=9, n_requests=8, rate=0.5,
                     kinds=("crash_handler",))
    assert len(plan.events) >= 2
    fab = FaultFabric(plan).attach(gw)
    fc = FaultyClient(gw.connect("chaos-client"), fab, "wordcount")
    try:
        for i in range(plan.n_requests):
            n = 4 + i % 9
            out = fc.step(make_text(n, seed=i))
            if out.status == "fault":
                assert isinstance(out.value, ServiceCrashed), \
                    f"{out} — replay: {plan.describe()}"
    finally:
        gw.close()
    kills = [s for s in sessions
             if s._proc is not None and s._proc.exitcode == -_signal.SIGKILL]
    assert len(kills) >= 2, \
        (f"crash faults fired but no service process died by SIGKILL "
         f"— replay: {plan.describe()}")


# ---------------------------------------------------------------------------
# satellite: "handler died" is typed, immediate — never a deadline stall
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cls", [ShmTransport, MPKLinkOptTransport])
def test_session_crash_is_typed_and_immediate(cls):
    """A service thread that dies mid-request must surface ServiceCrashed
    at once — the client must NOT wait out the (long) response deadline."""
    def die(req):
        raise HandlerCrash("boom")

    tr = cls(die, timeout=30.0)
    tr.start()
    try:
        t0 = time.perf_counter()
        with pytest.raises(ServiceCrashed):
            tr.request(np.arange(4, dtype=np.uint8))
        elapsed = time.perf_counter() - t0
        assert elapsed < 5.0, f"sat out the deadline: {elapsed}s"
        # the dead session is refused immediately too (no new deadline wait)
        t0 = time.perf_counter()
        with pytest.raises(ServiceCrashed):
            tr._sessions[0].request(np.arange(4, dtype=np.uint8))
        assert time.perf_counter() - t0 < 1.0
        # ...and the transport-level API transparently reconnects; the new
        # session crashes again (same handler) but stays typed and fast
        with pytest.raises(ServiceCrashed):
            tr.request(np.arange(4, dtype=np.uint8))
    finally:
        tr.close()


def test_pipe_send_side_is_deadline_bounded():
    """A wedged service thread stops draining the request pipe; a large
    send must hit the deadline (typed), not block forever in os.write."""
    import threading

    gate = threading.Event()

    def wedged(req):
        gate.wait(10)                   # stuck handler: pipe not drained
        return np.asarray(req)

    tr = TRANSPORTS["pipe"](wedged, timeout=0.3)
    tr.start()
    s = tr.connect("w")
    try:
        first_err = []

        def occupy():                   # park the service thread in wedged()
            try:
                s.request(np.zeros(8, np.uint8))
            except Exception as e:
                first_err.append(e)

        t = threading.Thread(target=occupy, daemon=True)
        t.start()
        time.sleep(0.05)
        t0 = time.perf_counter()
        with pytest.raises(ResponseTimeout):
            # 1 MiB ≫ the pipe buffer: the send itself must be bounded
            s.request(np.zeros(1 << 20, np.uint8))
        assert time.perf_counter() - t0 < 5.0
    finally:
        gate.set()
        tr.close()


def test_timeout_vs_crash_are_distinct_types():
    """A slow handler is a ResponseTimeout; a dead handler is a
    ServiceCrashed — retry layers treat them differently."""
    def slow(req):
        time.sleep(0.5)
        return np.asarray(req)

    tr = ShmTransport(slow, timeout=0.05)
    tr.start()
    try:
        with pytest.raises(ResponseTimeout):
            tr.request(np.arange(4, dtype=np.uint8))
    finally:
        tr.close()


# ---------------------------------------------------------------------------
# EngineService: a killed engine worker recovers mid-decode
# ---------------------------------------------------------------------------

def test_engine_service_recovers_from_midflight_crash():
    import jax
    from repro.configs import get_reduced
    from repro.models import init_params
    from repro.models.transformer import Impl
    from repro.runtime import EngineService, ServingEngine, encode_prompt

    cfg = get_reduced("llama3.2-1b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    engine = ServingEngine(cfg, params, max_batch=2, max_seq=32,
                           impl=Impl(attention="naive", remat=False))
    svc = EngineService(engine, timeout=60.0).start()
    gw = ServiceGateway("mpklink_opt", transport_kwargs={"timeout": 60.0})
    gw.register_service("infer", svc.handler)
    gw.start()
    try:
        c = gw.connect("driver", retries=2)
        out = c.call("infer", encode_prompt([1, 2, 3], max_new=4))
        assert np.asarray(out).size == 4

        # kill the engine worker mid-decode: the in-flight request fails
        # typed + immediately, and the retrying client transparently
        # resubmits on the healed engine
        svc.inject_crash()
        out = c.call("infer", encode_prompt([4, 5], max_new=3))
        assert np.asarray(out).size == 3
        assert svc.crashes >= 1
        # engine keeps serving new work after the crash
        out = c.call("infer", encode_prompt([7], max_new=2))
        assert np.asarray(out).size == 2
    finally:
        gw.close()
        svc.close()

    # crash-recovery delivery semantics (unit, on an un-started service
    # sharing the same engine): work the dying tick already retired is
    # DELIVERED; queued/slotted work fails typed — nobody is stranded
    import threading
    from repro.runtime import Request
    from repro.runtime.serve import EngineService as ES

    svc2 = ES(engine, timeout=5.0)
    finished = Request(rid=1, prompt=[1])
    finished.generated = [42]
    doomed = Request(rid=2, prompt=[2])
    ev1, ev2 = threading.Event(), threading.Event()
    svc2._events = {1: ev1, 2: ev2}
    engine.completed.append(finished)
    engine.queue.append(doomed)
    svc2._recover(RuntimeError("boom"))
    assert ev1.is_set() and ev2.is_set()
    assert svc2._done[1] is finished               # delivered, not dropped
    assert isinstance(svc2._failed[2], ServiceCrashed)
    assert svc2.crashes == 1 and engine.queue == []


def test_engine_service_recovers_from_a_step_that_failed_after_donation():
    """The step donates the engine's state: one that dies after dispatch
    leaves the caches deleted. Recovery makes the state anew, and the
    engine then serves exactly what a fresh engine serves."""
    import jax
    from repro.configs import get_reduced
    from repro.models import init_params
    from repro.models.transformer import Impl
    from repro.runtime import (EngineService, Request, ServingEngine,
                               encode_prompt)

    cfg = get_reduced("llama3.2-1b")
    params = init_params(cfg, jax.random.PRNGKey(0))

    def engine():
        return ServingEngine(cfg, params, max_batch=2, max_seq=32,
                             impl=Impl(attention="naive", remat=False))

    eng = engine()
    step, donated = eng._step, []

    def dies_after_dispatch(p, s, t):
        step(p, s, t)
        donated.append(all(a.is_deleted() for a in jax.tree.leaves(s)))
        raise RuntimeError("device step failed")

    eng._step = dies_after_dispatch
    svc = EngineService(eng, timeout=60.0).start()
    prompts = [[4, 5], [7, 1, 2], [3]]
    try:
        with pytest.raises(ServiceCrashed):
            svc.handler(encode_prompt([1, 2, 3], max_new=4))
        assert donated == [True] and svc.crashes == 1
        eng._step = step
        served = svc.handler_batch([encode_prompt(p, max_new=5)
                                    for p in prompts])
    finally:
        svc.close()
    assert not any(a.is_deleted() for a in jax.tree.leaves(eng.state))

    fresh = engine()
    reqs = [Request(rid=i, prompt=p, max_new=5) for i, p in enumerate(prompts)]
    for r in reqs:
        fresh.submit(r)
    fresh.run_until_drained()
    assert [list(np.asarray(o)) for o in served] == \
        [r.generated for r in reqs]


# ---------------------------------------------------------------------------
# supervisor wiring: gateway health → heartbeat view → recovery plan
# ---------------------------------------------------------------------------

def test_gateway_supervisor_restarts_open_circuits():
    from repro.runtime import GatewaySupervisor, plan_gateway_recovery

    healthy = {"a": {"state": "closed"}, "b": {"state": "open"},
               "c": {"state": "open"}, "d": {"state": "half_open"}}
    assert plan_gateway_recovery(healthy, {"b"}) == \
        [("restart", "b"), ("shed", "c"), ("probe", "d")]

    boom = {"n": 0}

    def flaky(req):
        boom["n"] += 1
        if boom["n"] <= 3:
            raise ValueError("flaky")
        return wordcount_handler(req)

    gw = ServiceGateway("uds")
    # no factory → the breaker opens instead of self-restarting inline;
    # the supervisor sweep is what heals it
    gw.register_service("wc", flaky, failure_threshold=3, probe_after=100)
    gw.start()
    sup = GatewaySupervisor(gw)
    try:
        c = gw.connect("x")
        for i in range(3):
            with pytest.raises(Exception):
                c.call("wc", make_text(4, seed=i))
        assert gw.health()["wc"]["state"] == "open"
        assert sup.observe()["wc"]["state"] == "open"
        assert "wc" not in sup.monitor.alive()
        gw._services["wc"].factory = lambda: flaky     # operator intervenes
        assert sup.heal() == [("restart", "wc")]
        assert gw.health()["wc"]["state"] == "closed"
        # epoch was bumped by the restart: the client re-keys transparently
        assert parse_count(c.call("wc", make_text(9, seed=9))) == 9
        assert "wc" in sup.monitor.alive() or sup.observe()["wc"]["state"] == "closed"
    finally:
        gw.close()
