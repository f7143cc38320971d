"""The engine's decode step updates its state in place: the state is
donated, the caches are carried through the layer loop, and a dense cache
takes only its new rows. Checked on the compiled step (aliasing, no copy of
a layer or of the stack) and on the tokens served, against the plain walk
that runs each layer on its own one-layer cache, scanned as an input. Tiny
models on the CPU."""
import re

import jax
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.models import init_params
from repro.models import attention as attn_mod
from repro.models import ssm as ssm_mod
from repro.models import transformer as tf
from repro.models.layers import apply_mlp, apply_norm
from repro.models.transformer import Impl
from repro.runtime import Request, ServingEngine

IMPL = Impl(attention="naive", remat=False)
B, MAX_SEQ = 4, 64


def _model(arch):
    cfg = get_reduced(arch)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module", params=["olmo-1b", "mamba2-1.3b",
                                        "granite-4.0-h-small"])
def model(request):
    return _model(request.param)


@pytest.fixture(scope="module", params=["olmo-1b", "mamba2-1.3b"])
def stacked_model(request):
    """A model whose layers are all of one kind (dense, SSM)."""
    return _model(request.param)


def _engine(model, **kw):
    cfg, params = model
    return ServingEngine(cfg, params, max_batch=kw.pop("max_batch", B),
                         max_seq=kw.pop("max_seq", MAX_SEQ), impl=IMPL, **kw)


def _shape(a, shape=None):
    """The array's type as HLO writes it: ``f32[2,4,64,4,16]``."""
    kind = {"f": "f", "i": "s", "u": "u"}[a.dtype.kind]
    dims = ",".join(map(str, a.shape if shape is None else shape))
    return f"{kind}{a.dtype.itemsize * 8}[{dims}]"


def _instructions(hlo):
    """→ [(fused, root, result type, opcode)] for every instruction of the
    compiled module; ``fused`` marks those inside a fusion's body, whose
    results are no buffer of their own unless they are its root."""
    fused = set(re.findall(r"calls=%([\w.\-]+)", hlo))
    out, comp = [], None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            comp = head.group(1)
            continue
        ins = re.match(r"\s*(ROOT )?%[\w.\-]+ = (\w+\[[\d,]*\])\S* "
                       r"([\w\-]+)\(", line)
        if ins:
            out.append((comp in fused, bool(ins.group(1)), ins.group(2),
                        ins.group(3)))
    return out


def _aliased_params(hlo):
    """Result types of the entry parameters that an output aliases."""
    header = hlo.splitlines()[0]
    aliased = {int(n) for n in re.findall(r"\}: \((\d+), \{", header)}
    entry = hlo[hlo.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    params = re.findall(r"= (\w+\[[\d,]*\])\S* parameter\((\d+)\)", entry)
    return sorted(t for t, n in params if int(n) in aliased)


def test_step_aliases_its_state_and_copies_no_layer(model):
    cfg, _ = model
    eng = _engine(model)
    hlo = eng._step.lower(eng.params, eng.state,
                          np.zeros((B, 1), np.int32)).compile().as_text()
    named = jax.tree_util.tree_leaves_with_path(eng.state["caches"])
    caches = [a for _, a in named]
    aliased = _aliased_params(hlo)
    for leaf in caches:                       # every cache leaf written back
        assert _shape(leaf) in aliased, (_shape(leaf), aliased)
    assert len(aliased) >= len(caches)
    # XLA's CPU compiler copies the stack of SSM conv tails: fusions it
    # schedules after the in-place write re-read the old window. The chip's
    # compiler does not (test_tpu_compile checks every stack, at the cells'
    # size); every other stack is checked here too.
    stacks = {_shape(a) for path, a in named
              if "conv" not in jax.tree_util.keystr(path)}
    ins = _instructions(hlo)
    copies = [t for _, _, t, op in ins if op in ("copy", "copy-start")]
    assert not stacks & set(copies), copies
    if cfg.family == "dense":
        k = eng.state["caches"]["attn"]["k"]
        whole = {_shape(k), _shape(k, (1,) + k.shape[1:])}
        # no buffer holds a layer of the cache, and nothing writes one:
        # the layer is read inside the reductions, the rows scattered
        for in_fusion, root, t, op in ins:
            if op == "dynamic-update-slice" or (
                    op == "dynamic-slice" and (root or not in_fusion)):
                assert t not in whole, (t, op)


def _plain_decode_stack(cfg, blocks, caches, x, pos, *, impl):
    """The plain walk of a one-kind stack: each layer's weights and its own
    one-layer cache scanned as inputs, its new cache emitted."""
    name = "mamba" if "mamba" in blocks else "attn"

    def body(h, inp):
        w, cache = inp
        u = apply_norm(cfg, w["ln1"], h)
        if name == "mamba":
            y, cache = ssm_mod.decode_mamba(cfg, w["mamba"], u, cache)
            return h + y, cache
        y, cache = attn_mod.decode_attn(cfg, w["attn"], u, cache, pos,
                                        impl=impl.decode_attention,
                                        kv_chunk=impl.kv_chunk)
        h = h + y
        return h + apply_mlp(cfg, w["ffn"], apply_norm(cfg, w["ln2"], h)), \
            cache

    x, new = jax.lax.scan(body, x, (blocks, caches[name]))
    return x, {name: new}, None


def _requests(vocab):
    """More requests than slots, of mixed lengths, arriving over time: slots
    are taken and freed on different ticks."""
    shapes = [(3, 9), (1, 4), (12, 20), (5, 3), (2, 11), (9, 9), (4, 1),
              (6, 14), (7, 6), (1, 17)]
    return [Request(rid=i, prompt=[(7 * i + j) % (vocab - 1) + 1
                                   for j in range(n)], max_new=m)
            for i, (n, m) in enumerate(shapes)]


def _serve(eng, vocab):
    """Serve the requests, two joining every third tick → (tokens a
    request, tick each retired on, (host syncs, a step in flight at its
    start) each tick)."""
    reqs = _requests(vocab)
    pending, retired_at, syncs = list(reqs), {}, []
    for t in range(1000):
        if not (pending or eng.queue or any(eng.slots) or eng.in_flight):
            break
        if t % 3 == 0:
            for r in pending[:2]:
                eng.submit(r)
            del pending[:2]
        before, in_flight = eng.host_syncs, eng.in_flight
        if eng.tick():
            syncs.append((eng.host_syncs - before, in_flight))
        for r in eng.completed:
            retired_at.setdefault(r.rid, eng.ticks)
    return {r.rid: r.generated for r in reqs}, retired_at, syncs


@pytest.mark.parametrize("greedy", [True, False])
def test_in_place_serves_the_plain_walks_tokens(stacked_model, greedy,
                                                 monkeypatch):
    cfg, _ = stacked_model
    kw = dict(greedy=greedy, seed=2147483713, max_batch=3)
    got = _serve(_engine(stacked_model, **kw), cfg.vocab_size)
    monkeypatch.setattr(tf, "decode_stack", _plain_decode_stack)
    want = _serve(_engine(stacked_model, **kw), cfg.vocab_size)
    assert got[0] == want[0]
    assert got[1] == want[1] and len(got[1]) == len(_requests(2))
    assert len(got[2]) >= 40
    # one host sync a tick, of the step in flight
    assert got[2] == want[2] and all(n == f for n, f in got[2])
