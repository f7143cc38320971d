"""Service gateway: concurrent multi-service routing, per-service domain
isolation, revocation — plus regression tests for the seed-suite bugfixes
(zlib-fallback checkpoints, shard_map import on this jax pin, oversized shm
responses raising instead of hanging)."""
import tempfile
import threading

import numpy as np
import pytest

from repro.core import TRANSPORTS, AccessViolation, ServiceGateway, framing
from repro.core.gateway import GW_MAGIC, _ROUTE_BYTES
from repro.core.transports import (CapacityError, ShmTransport, TransportError,
                                   _raise_remote)
from repro.core.wordcount import make_text, parse_count, wordcount_handler


def _reverse(req: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(np.asarray(req)[::-1])


def _make_gateway(transport: str) -> ServiceGateway:
    gw = ServiceGateway(transport)
    gw.register_service("wordcount", wordcount_handler)
    gw.register_service("reverse", _reverse)
    return gw.start()


@pytest.mark.parametrize("name", sorted(TRANSPORTS))
def test_gateway_concurrent_two_services(name):
    """N client threads hammer two services at once over each transport;
    every response is cross-checked against its own request."""
    gw = _make_gateway(name)
    n_clients, reps = 6, 3
    errors = []

    def worker(i):
        try:
            c = gw.connect(f"client-{i}")
            for j in range(reps):
                n = 40 * (i + 1) + j
                assert parse_count(c.call("wordcount", make_text(n, seed=j))) == n
                arr = np.arange(i * 10, i * 10 + 9, dtype=np.int32)
                rev = c.call("reverse", arr)
                np.testing.assert_array_equal(np.asarray(rev), arr[::-1])
            c.close()
        except Exception as e:          # pragma: no cover - surfaced below
            errors.append((i, repr(e)))

    try:
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errors, errors
        assert gw.stats["responses"] == n_clients * reps * 2
        assert gw.stats["macs_verified"] == n_clients * reps * 2
        assert gw.stats["rejected"] == 0
    finally:
        gw.close()


def test_transport_sessions_are_independent():
    """Raw transport layer: concurrent sessions each keep their own framing
    sequence and never see each other's traffic."""
    tr = TRANSPORTS["mpklink_opt"](wordcount_handler, max_keys=16)
    tr.start()
    errors = []

    seeds = []

    def worker(i):
        try:
            s = tr.connect(f"peer-{i}")
            for j in range(3):
                n = 25 * (i + 1) + j
                assert parse_count(s.request(make_text(n, seed=i))) == n
            assert s._seq == 3
            seeds.append(s.seed)
            s.close()
        except Exception as e:          # pragma: no cover
            errors.append((i, repr(e)))

    try:
        threads = [threading.Thread(target=worker, args=(i,)) for i in range(5)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errors, errors
        # every session got its own domain-derived MAC seed
        assert len(set(seeds)) == 5 and tr.seed not in seeds
    finally:
        tr.close()


def test_gateway_foreign_key_rejected():
    """A client holding a key for service A gets AccessViolation/guard
    rejection from service B — never B's (or anyone's) data."""
    gw = _make_gateway("mpklink_opt")
    gw.register_service("secret", lambda r: r, allow={"vip"})
    try:
        vip = gw.connect("vip")
        vip.open("secret")
        intruder = gw.connect("intruder")

        # control plane: the CA refuses to issue the key at all
        with pytest.raises(AccessViolation):
            intruder.call("secret", np.arange(4, dtype=np.int32))

        # data plane: forge an envelope addressed to 'secret' using the
        # intruder's wordcount channel key/seed (the foreign-key attack)
        chan_wc = intruder.open("wordcount")
        sid_secret = vip._channels["secret"].sid
        frame = framing.build_frame(np.arange(4, dtype=np.int32),
                                    seed=chan_wc.seed, seq=0)
        env = np.concatenate([
            np.array([GW_MAGIC, sid_secret, intruder.cid, 0], "<u4")
            .view(np.uint8),
            frame.reshape(-1).view(np.uint8)])
        resp = np.ascontiguousarray(np.asarray(intruder._session.request(env)))
        route = resp[:_ROUTE_BYTES].view("<u4")
        assert int(route[1]) == 1                  # error status, no data
        with pytest.raises((AccessViolation, framing.FrameError)):
            _raise_remote(resp[_ROUTE_BYTES:
                               _ROUTE_BYTES + int(route[3])].tobytes())

        # data plane: right service id, wrong MAC seed → guard rejection
        chan = vip._channels["secret"]
        bad = framing.build_frame(np.arange(4, dtype=np.int32),
                                  seed=chan.seed ^ 0xDEAD, seq=chan.seq)
        env2 = np.concatenate([
            np.array([GW_MAGIC, chan.sid, vip.cid, 0], "<u4").view(np.uint8),
            bad.reshape(-1).view(np.uint8)])
        resp2 = np.ascontiguousarray(np.asarray(vip._session.request(env2)))
        route2 = resp2[:_ROUTE_BYTES].view("<u4")
        assert int(route2[1]) == 1
        with pytest.raises(framing.FrameError):
            _raise_remote(resp2[_ROUTE_BYTES:
                                _ROUTE_BYTES + int(route2[3])].tobytes())
        # the ACL denial happens at the CA (control plane); the two forged
        # envelopes are the server-side rejects
        assert gw.stats["rejected"] == 2
    finally:
        gw.close()


def test_gateway_revocation():
    gw = _make_gateway("mpklink_opt")
    try:
        a, b = gw.connect("alice"), gw.connect("bob")
        assert parse_count(a.call("wordcount", make_text(10, seed=0))) == 10
        assert parse_count(b.call("wordcount", make_text(11, seed=0))) == 11
        gw.revoke(a, "wordcount")
        # epoch bumped: bob's cached key is stale, but he is still certified
        # — call() re-keys through the CA transparently and succeeds
        epoch_key = b._channels["wordcount"].client_key
        assert parse_count(b.call("wordcount", make_text(12, seed=0))) == 12
        assert b._channels["wordcount"].client_key is not epoch_key
        # a BANNED client cannot re-key: the CA refuses the certificate
        # (alice's channel is gone after the revoke, so her next call must
        # go through the CA again)
        gw.ca.revoke_service("alice")
        with pytest.raises(AccessViolation):
            a.call("wordcount", make_text(13, seed=0))
    finally:
        gw.close()


def test_gateway_handler_errors_propagate():
    def boom(req):
        raise ValueError("handler exploded")

    gw = ServiceGateway("uds")
    gw.register_service("boom", boom)
    gw.start()
    try:
        c = gw.connect("c")
        with pytest.raises(TransportError):
            c.call("boom", np.arange(3, dtype=np.int32))
        # the session survives the error — next call works
        gw.register_service("ok", lambda r: r)
        np.testing.assert_array_equal(
            np.asarray(c.call("ok", np.arange(3, dtype=np.int32))),
            np.arange(3, dtype=np.int32))
    finally:
        gw.close()


# ---------------------------------------------------------------------------
# regression: the three seed-suite bugfixes
# ---------------------------------------------------------------------------

def test_checkpoint_codec_fallback_roundtrip():
    """Checkpoints save/restore without the optional zstandard package
    (stdlib zlib fallback) and record their codec in the manifest."""
    import repro.checkpoint.checkpointer as cp

    tree = {"w": np.arange(12, dtype=np.float32).reshape(3, 4),
            "b": np.ones(4, np.float32)}
    with tempfile.TemporaryDirectory() as d:
        ck = cp.Checkpointer(d, keep=2)
        ck.save(3, tree, blocking=True)
        path, codec = cp._find_meta(f"{d}/step_3")
        expected = "zstd" if cp.zstd is not None else "zlib"
        assert codec == expected, (path, codec)
        step, restored = ck.restore(tree)
        assert step == 3
        np.testing.assert_array_equal(restored["w"], tree["w"])
        np.testing.assert_array_equal(restored["b"], tree["b"])


def test_shard_map_importable_on_this_jax():
    import jax
    from jax import shard_map
    assert callable(shard_map) and callable(jax.lax.axis_size)


def test_shm_oversized_response_raises_not_hangs():
    """A handler response larger than the region used to strand the client
    in resp_ready.wait() forever; now it raises CapacityError promptly."""
    big = np.zeros(4096, np.uint8)
    tr = ShmTransport(lambda req: big, capacity=1024, timeout=5.0)
    tr.start()
    try:
        with pytest.raises(CapacityError):
            tr.request(np.zeros(8, np.uint8))
        # request-side capacity check still intact
        with pytest.raises(CapacityError):
            tr.request(np.zeros(2048, np.uint8))
    finally:
        tr.close()


def test_shm_handler_exception_propagates():
    def boom(req):
        raise ValueError("nope")

    tr = ShmTransport(boom, capacity=1024, timeout=5.0)
    tr.start()
    try:
        with pytest.raises(ValueError, match="nope"):
            tr.request(np.zeros(8, np.uint8))
    finally:
        tr.close()


def test_shm_timeout_poisons_session_and_transport_recovers():
    """A timed-out session must never hand a late (stale) response to the
    NEXT request; the legacy transport-level request() recovers by opening
    a fresh session."""
    import time

    slow_once = []

    def handler(req):
        if not slow_once:
            slow_once.append(1)
            time.sleep(0.5)
        return np.asarray(req)

    tr = ShmTransport(handler, capacity=1024, timeout=0.05)
    tr.start()
    try:
        with pytest.raises(TransportError, match="timed out"):
            tr.request(np.arange(4, dtype=np.uint8))
        time.sleep(0.6)                   # let the stale response land
        # direct reuse of the poisoned session fails loudly...
        with pytest.raises(TransportError, match="poisoned"):
            tr._sessions[0].request(np.arange(4, dtype=np.uint8))
        # ...but the transport transparently reconnects
        out = tr.request(np.asarray([9, 8, 7], np.uint8))
        assert list(out) == [9, 8, 7]
    finally:
        tr.close()


def test_ca_refuses_reregistration_of_revoked_identity():
    """A ban survives reconnects: gw.connect() under a revoked name raises
    instead of minting a fresh verified certificate."""
    gw = _make_gateway("uds")
    try:
        mallory = gw.connect("mallory")
        assert parse_count(mallory.call("wordcount", make_text(5, seed=0))) == 5
        gw.ca.revoke_service("mallory")
        with pytest.raises(AccessViolation, match="revoked"):
            gw.connect("mallory")
    finally:
        gw.close()


def test_client_results_are_owned_snapshots():
    """GatewayClient results must not alias transport region storage: on
    the zero-copy mpklink plane, an aliased r1 would silently flip to
    r2's bytes when the next call reuses the response region."""
    gw = ServiceGateway("mpklink_opt")
    gw.register_service("echo", lambda req: np.asarray(req))
    gw.start()
    try:
        c = gw.connect("snap")
        a = np.arange(64, dtype=np.uint8)
        b = np.full(64, 7, np.uint8)
        r1 = np.asarray(c.call("echo", a))
        expect = r1.copy()
        r2 = c.call("echo", b)                      # reuses the region
        np.testing.assert_array_equal(r1, expect)   # r1 must not mutate
        np.testing.assert_array_equal(np.asarray(r2), b)
        # batch and scatter results carry the same ownership guarantee
        rb = c.call_batch("echo", [a, b])
        rm = c.call_many([("echo", a), ("echo", b)])
        snaps = [np.asarray(r).copy() for r in rb + rm]
        c.call("echo", np.full(64, 99, np.uint8))
        for got, r in zip(snaps, rb + rm):
            np.testing.assert_array_equal(np.asarray(r), got)
    finally:
        gw.close()
