"""The engine tick takes each slot's position from the host: the position
it uses is the one on the device, retirements at ``max_seq`` fall on the
same tick as under a device read, and the served tokens do not change.
Tiny dense and SSM models on the CPU."""
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
    while eng.queue or _occupied(eng):
        assert eng.tick()
        for b in _occupied(eng):
            assert eng.position(b) == int(eng.state["pos"][b])
    assert {r.rid for r in eng.completed} >= {r.rid for r in reqs[5:]}


def test_max_seq_retires_on_the_device_reads_tick(model):
    eng = _engine(model)
    reqs = _requests()
    for r in reqs:
        eng.submit(r)
    hit_max_seq = 0
    while eng.queue or _occupied(eng):
        generating = {b: r for b, r in enumerate(eng.slots) if r is not None
                      and eng.prompt_cursor[b] >= len(r.prompt)}
        queued = {id(r) for r in eng.queue}
        assert eng.tick()
        for r in eng.slots + eng.completed:
            # admitted this tick with a one-token prompt: generated at once
            if r is not None and id(r) in queued and len(r.prompt) == 1:
                generating[r.slot] = r
        for b, r in generating.items():
            pos = int(eng.state["pos"][b])   # the old rule's device read
            by_max_seq = pos >= MAX_SEQ - 1
            old_rule = (len(r.generated) >= r.max_new
                        or (r.eos_id is not None and r.generated[-1] == r.eos_id)
                        or by_max_seq)
            assert r.done == old_rule, (r.rid, pos)
            hit_max_seq += r.done and by_max_seq \
                and len(r.generated) < r.max_new
    assert hit_max_seq == 2
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
        while eng.queue or _occupied(eng):
            before = eng.host_syncs
            assert eng.tick()
            syncs.append(eng.host_syncs - before)
            for r in eng.completed:
                retired_at.setdefault(r.rid, eng.ticks)
        runs.append(({r.rid: r.generated for r in reqs}, retired_at))
        if cls is ServingEngine:
            assert syncs == [1] * eng.ticks     # the sampled tokens alone
    assert runs[0] == runs[1]
