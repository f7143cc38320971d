"""The engine tick takes each slot's position from the host: the position
it uses is the one on the device, retirements at ``max_seq`` follow the
device's position, and the served tokens do not change. Tiny dense and
SSM models on the CPU."""
import jax
import pytest

from repro.configs import get_reduced
from repro.models import init_params
from repro.models.transformer import Impl
from repro.runtime import Request, ServingEngine

IMPL = Impl(attention="naive", remat=False)
FAMILIES = ["olmo-1b", "mamba2-1.3b"]          # dense, SSM
MAX_SEQ = 16


@pytest.fixture(scope="module", params=FAMILIES)
def model(request):
    cfg = get_reduced(request.param)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


class DeviceReadEngine(ServingEngine):
    """The retirement rule as it was: each generating slot's position read
    back from the device, one blocking read a slot."""

    def position(self, b):
        return int(self.state["pos"][b])


def _requests():
    """More requests than slots, of mixed lengths; two run into max_seq
    (prompt + max_new > MAX_SEQ), one stops early on an EOS id."""
    shapes = [(3, 4), (1, 2), (10, 20), (5, 3), (2, 5), (14, 9), (4, 1),
              (6, 6)]
    reqs = [Request(rid=i, prompt=[(7 * i + j) % 50 + 1 for j in range(n)],
                    max_new=m) for i, (n, m) in enumerate(shapes)]
    reqs[4].eos_id = 0
    return reqs


def _engine(model, cls=ServingEngine, **kw):
    cfg, params = model
    return cls(cfg, params, max_batch=3, max_seq=MAX_SEQ, impl=IMPL, **kw)


def _occupied(eng):
    return [b for b, r in enumerate(eng.slots) if r is not None]


def _busy(eng):
    return eng.queue or _occupied(eng) or eng.in_flight


def test_host_position_is_the_devices(model):
    eng = _engine(model)
    reqs = _requests()
    for r in reqs[:5]:
        eng.submit(r)
    served = {b: set() for b in range(eng.B)}     # rids each slot held
    for _ in range(12):
        assert eng.tick()
        for b in _occupied(eng):
            assert eng.position(b) == int(eng.state["pos"][b])
            served[b].add(eng.slots[b].rid)
    # requests retired, and freed slots admitted others
    assert eng.completed and max(map(len, served.values())) > 1
    lost = eng.reset()
    assert lost and not _occupied(eng)
    assert [int(p) for p in eng.state["pos"]] == [0] * eng.B
    for r in reqs[5:]:
        eng.submit(r)
    while _busy(eng):
        assert eng.tick()
        for b in _occupied(eng):
            assert eng.position(b) == int(eng.state["pos"][b])
    assert {r.rid for r in eng.completed} >= {r.rid for r in reqs[5:]}


def test_max_seq_retires_on_the_device_reads_tick(model):
    """A request whose last token the dispatched step makes, by max_new
    or because the device's position after it reaches max_seq - 1, leaves
    its slot right after that step and retires on the next tick, when its
    token reaches the host."""
    eng = _engine(model)
    reqs = _requests()
    for r in reqs:
        eng.submit(r)
    step, made, last_on, n = eng._step, {}, {}, [0]

    def spy(p, s, t):
        making = [(b, r) for b, r in enumerate(eng.slots) if r is not None
                  and eng.prompt_cursor[b] >= len(r.prompt)]
        logits, s = step(p, s, t)
        for b, r in making:
            made[r.rid] = made.get(r.rid, 0) + 1
            pos = int(s["pos"][b])           # the device read, after it
            by_max_seq = pos >= MAX_SEQ - 1
            if made[r.rid] >= r.max_new or by_max_seq:
                last_on.setdefault(r.rid, (n[0], by_max_seq
                                           and made[r.rid] < r.max_new))
        return logits, s

    eng._step = spy
    retired_on = {}
    while _busy(eng):
        n[0] += 1
        assert eng.tick()
        for r in eng.completed:
            retired_on.setdefault(r.rid, n[0])
        for rid, (t, _) in last_on.items():
            r = reqs[rid]
            if t == n[0] and not r.done:     # slot freed, token unread
                assert eng.slots[r.slot] is not r
    for r in reqs:
        if r.eos_id is not None and r.generated[-1] == r.eos_id \
                and len(r.generated) < made[r.rid]:
            continue                         # ended by its EOS
        assert retired_on[r.rid] == last_on[r.rid][0] + 1, r.rid
        assert len(r.generated) == made[r.rid]
    assert sum(by for _, by in last_on.values()) == 2
    long = [r for r in reqs if len(r.prompt) + r.max_new > MAX_SEQ]
    assert [len(r.generated) for r in long] == \
        [MAX_SEQ - len(r.prompt) for r in long]


@pytest.mark.parametrize("greedy", [True, False])
def test_served_tokens_match_the_device_read_path(model, greedy):
    runs = []
    for cls in (ServingEngine, DeviceReadEngine):
        eng = _engine(model, cls, greedy=greedy, seed=2147483713)
        reqs = _requests()
        for r in reqs:
            eng.submit(r)
        retired_at, syncs = {}, []
        while _busy(eng):
            before = eng.host_syncs
            assert eng.tick()
            syncs.append(eng.host_syncs - before)
            for r in eng.completed:
                retired_at.setdefault(r.rid, eng.ticks)
        runs.append(({r.rid: r.generated for r in reqs}, retired_at))
        if cls is ServingEngine:
            # the sampled tokens alone, read from the tick after the first
            assert syncs == [0] + [1] * eng.ticks
    assert runs[0] == runs[1]
