"""Wire-native PSI (ISSUE 5): entity resolution over the transport layer
must be bit-identical to the in-process engine, survive protocol chaos
(reordered chunks, mid-round owner crashes, degenerate sets) with correct
results or clean surfaced errors, keep its frame layouts frozen (golden
conformance), and leak nothing but blinded bytes onto the wire."""
import struct
import threading
import time

import numpy as np
import pytest

from repro.testing.hypo import given, settings, strategies as st

from repro.core.modexp import ModexpPool
from repro.core.psi import GROUPS, PSIClient, PSIServer, psi_round
from repro.federation import transport
from repro.federation.psi_transport import (CLIENT_KINDS, SERVER_KINDS,
                                            WIRE_KINDS, PSIServerEndpoint,
                                            blind_tag, serve_psi,
                                            wire_psi_round)
from repro.federation.transport import _pack, _unpack

GROUP = "modp512"
NB = GROUPS[GROUP][2]


def _wire_round(xs, ys, *, mode="noinv", chunk_size=16, latency_s=0.0,
                pool=None, timeout=120.0):
    """One full wire round over a fresh queue channel pair.  Returns
    (intersection, stats, client_endpoint, worker)."""
    client = PSIClient(xs, GROUP, mode=mode)
    server = PSIServer(ys, group=GROUP)
    ep_c, ep_s = transport.channel_pair("scientist", "owner0",
                                        backend="queue",
                                        latency_s=latency_s)
    worker, th = serve_psi("owner0", server, ep_s)
    try:
        inter, stats = wire_psi_round(client, ep_c, worker=worker,
                                      pool=pool, chunk_size=chunk_size,
                                      timeout=timeout)
    finally:
        ep_c.send("psi_stop", {})
        th.join(timeout=10.0)
    return inter, stats, ep_c, worker


# ---------------------------------------------------------------------------
# bit-identity: wire engine == in-process engine
# ---------------------------------------------------------------------------


@given(st.lists(st.text(min_size=1, max_size=8), min_size=0, max_size=40),
       st.lists(st.text(min_size=1, max_size=8), min_size=0, max_size=40),
       st.integers(1, 17),
       st.sampled_from(["noinv", "bloom"]))
@settings(max_examples=8)
def test_wire_round_bit_identical_to_in_process(xs, ys, chunk, mode):
    """Random uneven sets (duplicates allowed), both protocol variants,
    any chunk size: the wire engine returns the exact intersection list
    — same elements, same client order, same duplicate multiplicity —
    as the in-process PR 4 engine."""
    ref, _ = psi_round(PSIClient(xs, GROUP, mode=mode),
                       PSIServer(ys, group=GROUP), chunk_size=chunk)
    got, stats = _wire_round(xs, ys, mode=mode, chunk_size=chunk)[:2]
    assert got == ref
    assert sorted(set(got)) == sorted(set(xs) & set(ys))
    assert stats["n_chunks"] == max(1, -(-len(xs) // chunk))


def test_wire_round_parallel_pool_bit_identical():
    """A parallel client-side modexp pool changes nothing about the
    intersection the wire engine returns."""
    xs = [f"id-{i}" for i in range(120)] + ["dup"] * 3
    ys = [f"id-{i + 40}" for i in range(120)] + ["dup"]
    ref, _ = psi_round(PSIClient(xs, GROUP), PSIServer(ys, group=GROUP),
                       chunk_size=32)
    with ModexpPool(2) as pool:
        got, stats, _, _ = _wire_round(xs, ys, chunk_size=32, pool=pool)
    assert got == ref
    assert got.count("dup") == 3


@pytest.mark.parametrize("chunk_size", [13, 64, 4096])
def test_session_resolve_queue_matches_direct(chunk_size):
    """session.resolve(backend="queue") aligns every party to the exact
    ID list the in-process engine produces, at any chunk size."""
    from repro.data import make_vertical_mnist_parties
    from repro.federation import VerticalSession, feature_parties

    def build():
        sci, owners = make_vertical_mnist_parties(180, seed=5,
                                                  keep_frac=0.8)
        return VerticalSession(*feature_parties(sci, owners))

    s_d, s_q = build(), build()
    st_d = s_d.resolve(group=GROUP)
    st_q = s_q.resolve(group=GROUP, backend="queue",
                       chunk_size=chunk_size)
    assert s_d.scientist.ids == s_q.scientist.ids
    assert (st_d["global_intersection"] == st_q["global_intersection"])
    for o_d, o_q in zip(s_d.owners, s_q.owners):
        assert o_d.ids == o_q.ids
    assert st_q["backend"] == "queue"
    # protocol-data byte accounting matches the in-process engine's
    for r_d, r_q in zip(st_d["rounds"], st_q["rounds"]):
        assert r_q["client_upload_bytes"] == r_d["client_upload_bytes"]
        assert r_q["upload_wire_bytes"] > 0
        assert r_q["download_wire_bytes"] > 0


def test_session_resolve_queue_parallel_pool_matches_serial():
    """parallelism on the queue backend: ONE modexp pool is shared by
    the client driver and every owner actor thread (executors are
    thread-safe), and the result stays bit-identical to the serial
    direct engine."""
    from repro.data import make_vertical_mnist_parties
    from repro.federation import VerticalSession, feature_parties

    def build():
        sci, owners = make_vertical_mnist_parties(160, seed=7,
                                                  keep_frac=0.85)
        return VerticalSession(*feature_parties(sci, owners))

    s_q, s_d = build(), build()
    st_q = s_q.resolve(group=GROUP, backend="queue", parallelism=2,
                       chunk_size=32)
    s_d.resolve(group=GROUP)
    assert s_q.scientist.ids == s_d.scientist.ids
    if st_q["parallelism"]:                      # host allowed workers
        assert st_q["parallelism"] == 2


def test_session_resolve_queue_bloom_mode():
    from repro.data import make_vertical_mnist_parties
    from repro.federation import VerticalSession, feature_parties
    sci, owners = make_vertical_mnist_parties(120, seed=2, keep_frac=0.9)
    s_d = VerticalSession(*feature_parties(sci, owners))
    sci2, owners2 = make_vertical_mnist_parties(120, seed=2,
                                                keep_frac=0.9)
    s_q = VerticalSession(*feature_parties(sci2, owners2))
    st_d = s_d.resolve(group=GROUP, mode="bloom")
    st_q = s_q.resolve(group=GROUP, mode="bloom", backend="queue",
                       chunk_size=32)
    assert s_d.scientist.ids == s_q.scientist.ids
    assert st_q["rounds"][0]["bloom_bytes"] == \
        st_d["rounds"][0]["bloom_bytes"]
    kinds = {m["kind"] for m in s_q.transcript}
    assert "psi_bloom_shard" in kinds
    assert "psi_server_set_chunk" not in kinds


def test_session_resolve_backend_guardrails():
    from repro.data import make_vertical_mnist_parties
    from repro.federation import VerticalSession, feature_parties
    sci, owners = make_vertical_mnist_parties(60, seed=0)
    session = VerticalSession(*feature_parties(sci, owners))
    with pytest.raises(ValueError, match="backend"):
        session.resolve(group=GROUP, backend="carrier-pigeon")
    with pytest.raises(ValueError, match="queue"):
        session.resolve(group=GROUP, backend="direct", latency_s=0.01)


# ---------------------------------------------------------------------------
# blinded-upload memoization on the wire (measured bytes, not code)
# ---------------------------------------------------------------------------


def test_repeat_round_same_owner_skips_upload_bytes():
    """Round 2 against the same owner transfers ZERO psi_blind_chunk
    bytes: the server cached the upload by content tag.  Asserted on
    measured channel stats across two owner rounds."""
    xs = [f"id-{i}" for i in range(90)]
    ys = [f"id-{i + 30}" for i in range(90)]
    client = PSIClient(xs, GROUP)
    server = PSIServer(ys, group=GROUP)
    ep_c, ep_s = transport.channel_pair("scientist", "owner0",
                                        backend="queue")
    worker, th = serve_psi("owner0", server, ep_s)
    try:
        i1, st1 = wire_psi_round(client, ep_c, worker=worker,
                                 chunk_size=16)
        sent_after_r1 = ep_c.sent_stats["by_kind"]["psi_blind_chunk"].copy()
        i2, st2 = wire_psi_round(client, ep_c, worker=worker,
                                 chunk_size=16)
    finally:
        ep_c.send("psi_stop", {})
        th.join(timeout=10.0)
    assert i1 == i2
    assert not st1["upload_skipped"] and st2["upload_skipped"]
    after_r2 = ep_c.sent_stats["by_kind"]["psi_blind_chunk"]
    # byte saving: round 2 added no blind-chunk traffic at all
    assert after_r2["payload_bytes"] == sent_after_r1["payload_bytes"]
    assert after_r2["count"] == sent_after_r1["count"]
    # and round 1's upload was exactly the packed blinded set (+ the
    # 8-byte base header per chunk)
    n_chunks = -(-len(xs) // 16)
    assert sent_after_r1["payload_bytes"] == \
        st1["client_upload_bytes"] + 8 * n_chunks
    assert worker.rounds_served == 2


def test_owner_level_blind_cache_survives_actor_recreation():
    """The upload cache lives on the DataOwner, not the actor: a fresh
    channel + fresh PSIServerEndpoint for the same owner still skips the
    re-upload (the session creates actors per resolve)."""
    from repro.federation.parties import DataOwner
    owner = DataOwner("o0", [f"id-{i}" for i in range(40)],
                      np.zeros((40, 2), np.float32))
    client = PSIClient([f"id-{i + 10}" for i in range(40)], GROUP)
    uploads = []
    for _ in range(2):
        ep_c, ep_s = transport.channel_pair("scientist", "o0",
                                            backend="queue")
        worker = owner.psi_endpoint(ep_s, GROUP)
        th = threading.Thread(target=worker.run, daemon=True)
        th.start()
        try:
            _, stats = wire_psi_round(client, ep_c, worker=worker,
                                      chunk_size=8)
        finally:
            ep_c.send("psi_stop", {})
            th.join(timeout=10.0)
        uploads.append(
            ep_c.sent_stats["by_kind"].get(
                "psi_blind_chunk", {"payload_bytes": 0})["payload_bytes"])
    assert uploads[0] > 0 and uploads[1] == 0


def test_session_resolve_logs_blind_reuse_transcript_entry():
    """Owner rounds 2..N reuse the memoized blind — the session must say
    so in the transcript (the PR 4 gap this PR closes), on both
    backends."""
    from repro.data import make_vertical_mnist_parties
    from repro.federation import VerticalSession, feature_parties
    for backend in ("direct", "queue"):
        sci, owners = make_vertical_mnist_parties(100, seed=1, n_owners=4)
        session = VerticalSession(*feature_parties(sci, owners))
        stats = session.resolve(group=GROUP, chunk_size=32,
                                backend=backend)
        reuse = [m for m in session.transcript
                 if m["kind"] == "psi_blind_reuse"]
        assert [m["to"] for m in reuse] == ["owner1", "owner2", "owner3"]
        for m in reuse:
            assert m["recompute_skipped"] is True
            assert m["reused_upload_bytes"] == \
                stats["rounds"][0]["client_upload_bytes"]


# ---------------------------------------------------------------------------
# chaos: reordering, interleaving, crashes, timeouts, degenerate sets
# ---------------------------------------------------------------------------


class _ScramblingEndpoint:
    """Wraps an owner-side endpoint, reordering the first two outgoing
    messages of one kind (chaos: a misbehaving network/owner)."""

    def __init__(self, inner, kind):
        self._inner, self._kind, self._held = inner, kind, None

    def send(self, kind, payload, *, seq=0):
        if kind == self._kind and self._held is None:
            self._held = (kind, payload, seq)
            return None
        out = self._inner.send(kind, payload, seq=seq)
        if self._held is not None and kind == self._kind:
            k, p, s = self._held
            self._held = None
            self._inner.send(k, p, seq=s)
        return out

    def __getattr__(self, name):
        return getattr(self._inner, name)


@pytest.mark.parametrize("kind", ["psi_double_chunk",
                                  "psi_server_set_chunk"])
def test_reordered_chunks_raise_clean_desync(kind):
    """Swapped same-kind chunks must fail loudly with a protocol-desync
    error on the scientist side — never a silently wrong intersection."""
    xs = [f"id-{i}" for i in range(60)]
    ys = [f"id-{i + 20}" for i in range(60)]
    client = PSIClient(xs, GROUP)
    server = PSIServer(ys, group=GROUP)
    ep_c, ep_s = transport.channel_pair("scientist", "owner0",
                                        backend="queue")
    worker = PSIServerEndpoint("owner0", server,
                               _ScramblingEndpoint(ep_s, kind))
    th = threading.Thread(target=worker.run, daemon=True)
    th.start()
    try:
        with pytest.raises(RuntimeError, match="desync"):
            wire_psi_round(client, ep_c, worker=worker, chunk_size=8,
                           timeout=30.0)
    finally:
        ep_c.send("psi_stop", {})
        th.join(timeout=10.0)


class _DelayingEndpoint:
    """Holds back every message of one kind until ``psi_done`` — the
    legal-but-hostile arrival order (kinds fully interleaved/inverted)."""

    def __init__(self, inner, kind):
        self._inner, self._kind, self._held = inner, kind, []

    def send(self, kind, payload, *, seq=0):
        if kind == self._kind:
            self._held.append((kind, payload, seq))
            return None
        if kind == "psi_done":
            for k, p, s in self._held:
                self._inner.send(k, p, seq=s)
            self._held = []
        return self._inner.send(kind, payload, seq=seq)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def test_desynchronized_kind_arrival_still_exact():
    """Cross-kind arrival order is NOT part of the protocol contract:
    with the whole server-set stream arriving after every double-blind
    response, the stash-based receive still produces the exact
    intersection."""
    xs = [f"id-{i}" for i in range(50)] + ["dup"] * 2
    ys = [f"id-{i + 15}" for i in range(50)] + ["dup"]
    ref, _ = psi_round(PSIClient(xs, GROUP), PSIServer(ys, group=GROUP),
                       chunk_size=8)
    client = PSIClient(xs, GROUP)
    server = PSIServer(ys, group=GROUP)
    ep_c, ep_s = transport.channel_pair("scientist", "owner0",
                                        backend="queue")
    worker = PSIServerEndpoint(
        "owner0", server,
        _DelayingEndpoint(ep_s, "psi_server_set_chunk"))
    th = threading.Thread(target=worker.run, daemon=True)
    th.start()
    try:
        inter, _ = wire_psi_round(client, ep_c, worker=worker,
                                  chunk_size=8, timeout=30.0)
    finally:
        ep_c.send("psi_stop", {})
        th.join(timeout=10.0)
    assert inter == ref


def test_owner_crash_mid_round_surfaces_cleanly(monkeypatch):
    """An owner actor that dies mid-round (after its first double-blind
    chunk) surfaces as a named RuntimeError on the scientist side within
    the poll interval — not a hang, not a full-timeout stall."""
    calls = {"n": 0}
    real = PSIServer.respond_chunk

    def flaky(self, packed):
        calls["n"] += 1
        if calls["n"] > 1:
            raise ValueError("owner-side kaboom")
        return real(self, packed)

    monkeypatch.setattr(PSIServer, "respond_chunk", flaky)
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="PSI owner worker 'owner0'"):
        _wire_round([f"id-{i}" for i in range(60)],
                    [f"id-{i + 20}" for i in range(60)], chunk_size=8,
                    timeout=60.0)
    assert time.monotonic() - t0 < 30.0


def test_session_resolve_queue_surfaces_owner_crash(monkeypatch):
    """The same crash through the full session.resolve surface."""
    from repro.data import make_vertical_mnist_parties
    from repro.federation import VerticalSession, feature_parties

    def boom(self, packed):
        raise ValueError("owner-side kaboom")

    monkeypatch.setattr(PSIServer, "respond_chunk", boom)
    sci, owners = make_vertical_mnist_parties(80, seed=0)
    session = VerticalSession(*feature_parties(sci, owners))
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="PSI owner worker"):
        session.resolve(group=GROUP, backend="queue", chunk_size=16)
    assert time.monotonic() - t0 < 30.0


def test_unresponsive_owner_times_out_cleanly():
    """A wedged owner (thread never started) bounds the round by the
    receive deadline instead of hanging the scientist forever."""
    client = PSIClient(["a", "b"], GROUP)
    ep_c, ep_s = transport.channel_pair("scientist", "owner0",
                                        backend="queue")
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="timed out"):
        wire_psi_round(client, ep_c, chunk_size=1, timeout=2.5)
    assert 2.0 < time.monotonic() - t0 < 10.0


def test_group_mismatch_surfaces_cleanly():
    client = PSIClient(["a", "b"], "modp512")
    server = PSIServer(["b", "c"], group="modp2048")
    ep_c, ep_s = transport.channel_pair("scientist", "owner0",
                                        backend="queue")
    worker, th = serve_psi("owner0", server, ep_s)
    try:
        with pytest.raises(RuntimeError, match="PSI owner worker"):
            wire_psi_round(client, ep_c, worker=worker, chunk_size=1,
                           timeout=30.0)
        assert "mismatch" in repr(worker.error)
    finally:
        ep_c.send("psi_stop", {})
        th.join(timeout=10.0)


@pytest.mark.parametrize("mode", ["noinv", "bloom"])
def test_degenerate_sets_over_the_wire(mode):
    """Empty / disjoint / duplicate-heavy sets round-trip the wire with
    the exact in-process results."""
    cases = [([], ["a"]), (["a"], []), ([], []),
             (["a", "b"], ["c", "d"]),                      # disjoint
             (["x"] * 5 + ["y"], ["x", "z"]),               # duplicates
             (["solo"], ["solo"])]
    for xs, ys in cases:
        ref, _ = psi_round(PSIClient(xs, GROUP, mode=mode),
                           PSIServer(ys, group=GROUP), chunk_size=2)
        got, stats = _wire_round(xs, ys, mode=mode, chunk_size=2)[:2]
        assert got == ref, (xs, ys, mode)
        assert stats["client_upload_bytes"] == NB * len(xs)


# ---------------------------------------------------------------------------
# golden wire-frame conformance (frozen layouts)
# ---------------------------------------------------------------------------

# Byte-exact frames for fixed payloads: any change to the frame format
# OR to a PSI kind's payload schema (entry names, order, dtypes) fails
# these.  Layout: [u32 n_entries] then per entry [u16 len][name]
# [u16 len][dtype.name][u8 ndim][i64 dims...][i64 nbytes][buffer],
# little-endian throughout (docs/WIRE_PROTOCOL.md §1).
GOLDEN_FRAMES = {
    "psi_hello":
        "0900000004006d6f6465050075696e7438010500000000000000050000000000"
        "00006e6f696e76050067726f7570050075696e74380107000000000000000700"
        "0000000000006d6f64703531320900626c696e645f746167050075696e743801"
        "1000000000000000100000000000000030313233343536373839616263646566"
        "0800626173655f746167050075696e7438011000000000000000100000000000"
        "0000000000000000000000000000000000000a007365727665725f7461670500"
        "75696e7438011000000000000000100000000000000000000000000000000000"
        "0000000000000900686176655f72657370050075696e74380101000000000000"
        "0001000000000000000007006e5f6974656d730500696e743634010100000000"
        "000000080000000000000003000000000000000a006368756e6b5f73697a6505"
        "00696e7436340101000000000000000800000000000000020000000000000002"
        "006e620500696e74363401010000000000000008000000000000004000000000"
        "000000",
    "psi_blind_chunk":
        "02000000040064617461050075696e7438010800000000000000080000000000"
        "000000010203040506070400626173650500696e743634010100000000000000"
        "08000000000000000000000000000000",
    "psi_delta_chunk":
        "03000000040064617461050075696e7438010800000000000000080000000000"
        "00000001020304050607070072656d6f7665640500696e743634010200000000"
        "0000001000000000000000010000000000000003000000000000000a006e5f72"
        "657461696e65640500696e743634010100000000000000080000000000000002"
        "00000000000000",
    "psi_lift_chunk":
        "02000000040064617461050075696e7438010400000000000000040000000000"
        "0000000102030400626173650500696e74363401010000000000000008000000"
        "000000000200000000000000",
    "psi_hello_ack_noinv":
        "060000000c00626c696e645f636163686564050075696e743801010000000000"
        "0000010000000000000000080064656c74615f6f6b050075696e743801010000"
        "00000000000100000000000000000d007365727665725f636163686564050075"
        "696e74380101000000000000000100000000000000000a007365727665725f74"
        "6167050075696e74380110000000000000001000000000000000666564636261"
        "393837363534333231300e006e5f7365727665725f6974656d730500696e7436"
        "34010100000000000000080000000000000003000000000000000f006e5f7365"
        "727665725f6368756e6b730500696e7436340101000000000000000800000000"
        "0000000200000000000000",
    "psi_hello_ack_bloom":
        "080000000c00626c696e645f636163686564050075696e743801010000000000"
        "0000010000000000000001080064656c74615f6f6b050075696e743801010000"
        "00000000000100000000000000000d007365727665725f636163686564050075"
        "696e74380101000000000000000100000000000000000a007365727665725f74"
        "6167050075696e74380110000000000000001000000000000000666564636261"
        "393837363534333231300e006e5f7365727665725f6974656d730500696e7436"
        "340101000000000000000800000000000000030000000000000008006e5f7368"
        "617264730500696e743634010100000000000000080000000000000001000000"
        "000000000c0073686172645f6e5f626974730500696e74363401010000000000"
        "0000080000000000000080000000000000000e0073686172645f6e5f68617368"
        "65730500696e74363401010000000000000008000000000000001e0000000000"
        "0000",
    "psi_server_set_chunk":
        "02000000040064617461050075696e7438010400000000000000040000000000"
        "0000000102030400626173650500696e74363401010000000000000008000000"
        "000000000200000000000000",
    "psi_double_chunk":
        "02000000040064617461050075696e7438010400000000000000040000000000"
        "0000000102030400626173650500696e74363401010000000000000008000000"
        "000000000200000000000000",
    "psi_delta_ack":
        "02000000040064617461050075696e7438010400000000000000040000000000"
        "00000001020307006e5f746f74616c0500696e74363401010000000000000008"
        "000000000000000300000000000000",
    "psi_keep_mask":
        "0200000004006b6565700500696e743634010300000000000000180000000000"
        "00000000000000000000020000000000000005000000000000000400726f7773"
        "0500696e74363401030000000000000018000000000000000700000000000000"
        "01000000000000000400000000000000",
    "psi_bloom_shard":
        "01000000040064617461050075696e7438010200000000000000020000000000"
        "0000ff00",
    "psi_done":
        "0200000008006e5f6368756e6b730500696e7436340101000000000000000800"
        "00000000000002000000000000000a006d6f646578705f6f70730500696e7436"
        "3401010000000000000008000000000000000500000000000000",
    "empty": "00000000",
}


def _u8(b):
    return np.frombuffer(b, np.uint8)


def _canonical_payloads():
    """The fixed payloads the goldens were frozen from — mirroring the
    exact dict construction order of the live actors."""
    zero_tag = b"\x00" * 16
    return {
        "psi_hello": {"mode": _u8(b"noinv"), "group": _u8(b"modp512"),
                      "blind_tag": _u8(b"0123456789abcdef"),
                      "base_tag": _u8(zero_tag),
                      "server_tag": _u8(zero_tag),
                      "have_resp": np.uint8(0),
                      "n_items": np.int64(3), "chunk_size": np.int64(2),
                      "nb": np.int64(64)},
        "psi_blind_chunk": {"data": _u8(bytes(range(8))),
                            "base": np.int64(0)},
        "psi_delta_chunk": {"data": _u8(bytes(range(8))),
                            "removed": np.array([1, 3], np.int64),
                            "n_retained": np.int64(2)},
        "psi_lift_chunk": {"data": _u8(bytes(range(4))),
                           "base": np.int64(2)},
        "psi_hello_ack_noinv": {"blind_cached": np.uint8(0),
                                "delta_ok": np.uint8(0),
                                "server_cached": np.uint8(0),
                                "server_tag": _u8(b"fedcba9876543210"),
                                "n_server_items": np.int64(3),
                                "n_server_chunks": np.int64(2)},
        "psi_hello_ack_bloom": {"blind_cached": np.uint8(1),
                                "delta_ok": np.uint8(0),
                                "server_cached": np.uint8(0),
                                "server_tag": _u8(b"fedcba9876543210"),
                                "n_server_items": np.int64(3),
                                "n_shards": np.int64(1),
                                "shard_n_bits": np.int64(128),
                                "shard_n_hashes": np.int64(30)},
        "psi_server_set_chunk": {"data": _u8(bytes(range(4))),
                                 "base": np.int64(2)},
        "psi_double_chunk": {"data": _u8(bytes(range(4))),
                             "base": np.int64(2)},
        "psi_delta_ack": {"data": _u8(bytes(range(4))),
                          "n_total": np.int64(3)},
        "psi_keep_mask": {"keep": np.array([0, 2, 5], np.int64),
                          "rows": np.array([7, 1, 4], np.int64)},
        "psi_bloom_shard": {"data": _u8(b"\xff\x00")},
        "psi_done": {"n_chunks": np.int64(2),
                     "modexp_ops": np.int64(5)},
        "empty": {},
    }


def _parse_frame(blob):
    """Independent minimal parser of the documented layout (deliberately
    NOT _unpack — this is the conformance oracle)."""
    (n,) = struct.unpack_from("<I", blob, 0)
    off = 4
    entries = []
    for _ in range(n):
        (ln,) = struct.unpack_from("<H", blob, off)
        off += 2
        name = blob[off:off + ln].decode()
        off += ln
        (ld,) = struct.unpack_from("<H", blob, off)
        off += 2
        dtype = blob[off:off + ld].decode()
        off += ld
        (ndim,) = struct.unpack_from("<B", blob, off)
        off += 1
        shape = struct.unpack_from(f"<{ndim}q", blob, off)
        off += 8 * ndim
        (nbytes,) = struct.unpack_from("<q", blob, off)
        off += 8
        entries.append((name, dtype, shape, blob[off:off + nbytes]))
        off += nbytes
    assert off == len(blob), "trailing bytes in frame"
    return entries


def test_golden_frames_byte_exact():
    for kind, payload in _canonical_payloads().items():
        assert _pack(payload).hex() == GOLDEN_FRAMES[kind], \
            f"wire frame layout changed for {kind}"


def test_golden_frames_parse_and_round_trip():
    for kind, payload in _canonical_payloads().items():
        blob = bytes.fromhex(GOLDEN_FRAMES[kind])
        entries = _parse_frame(blob)
        assert [e[0] for e in entries] == list(payload)
        back = _unpack(blob)
        assert set(back) == set(payload)
        for name in payload:
            np.testing.assert_array_equal(np.asarray(back[name]),
                                          np.asarray(payload[name]))
            assert back[name].dtype == np.asarray(payload[name]).dtype


def test_pack_round_trips_zero_length_and_max_chunk_payloads():
    # empty payload dict and a zero-length chunk (an owner with no rows)
    assert _pack({}) == b"\x00\x00\x00\x00"
    assert _unpack(_pack({})) == {}
    zero = {"data": np.zeros(0, np.uint8), "base": np.int64(0)}
    back = _unpack(_pack(zero))
    assert back["data"].shape == (0,) and back["data"].dtype == np.uint8
    # a full DEFAULT_CHUNK noinv chunk at modp2048 width (the largest
    # frame the protocol emits): exact payload + header-overhead budget
    from repro.core.psi import DEFAULT_CHUNK
    data = np.arange(DEFAULT_CHUNK * 256, dtype=np.uint64)
    data = (data % 251).astype(np.uint8)
    blob = _pack({"data": data, "base": np.int64(12345)})
    back = _unpack(blob)
    np.testing.assert_array_equal(back["data"], data)
    assert back["base"].reshape(-1)[0] == 12345
    overhead = len(blob) - data.nbytes - 8
    assert overhead < 128                      # headers stay tiny


def test_live_traffic_conforms_to_frame_schema():
    """Parse every frame of a real round with the independent parser and
    check each kind's entry schema (names, dtypes) — the conformance
    gate on actual traffic, not synthetic payloads."""
    captured = []
    xs = [f"id-{i}" for i in range(20)]
    ys = [f"id-{i + 5}" for i in range(20)]
    client = PSIClient(xs, GROUP)
    server = PSIServer(ys, group=GROUP)
    ep_c, ep_s = transport.channel_pair(
        "scientist", "owner0", backend="queue",
        tap=lambda msg, blob: captured.append((msg.kind, blob)))
    worker, th = serve_psi("owner0", server, ep_s)
    try:
        wire_psi_round(client, ep_c, worker=worker, chunk_size=4)
    finally:
        ep_c.send("psi_stop", {})
        th.join(timeout=10.0)
    schema = {
        "psi_hello": [("mode", "uint8"), ("group", "uint8"),
                      ("blind_tag", "uint8"), ("base_tag", "uint8"),
                      ("server_tag", "uint8"), ("have_resp", "uint8"),
                      ("n_items", "int64"),
                      ("chunk_size", "int64"), ("nb", "int64")],
        "psi_hello_ack": [("blind_cached", "uint8"),
                          ("delta_ok", "uint8"),
                          ("server_cached", "uint8"),
                          ("server_tag", "uint8"),
                          ("n_server_items", "int64"),
                          ("n_server_chunks", "int64")],
        "psi_blind_chunk": [("data", "uint8"), ("base", "int64")],
        "psi_server_set_chunk": [("data", "uint8"), ("base", "int64")],
        "psi_double_chunk": [("data", "uint8"), ("base", "int64")],
        "psi_done": [("n_chunks", "int64"), ("modexp_ops", "int64")],
        "psi_stop": [],
    }
    seen = set()
    for kind, blob in captured:
        seen.add(kind)
        entries = _parse_frame(blob)
        assert [(e[0], e[1]) for e in entries] == schema[kind], kind
        assert kind in WIRE_KINDS or kind == "psi_stop"
    assert {"psi_hello", "psi_hello_ack", "psi_blind_chunk",
            "psi_server_set_chunk", "psi_double_chunk",
            "psi_done"} <= seen


# ---------------------------------------------------------------------------
# privacy on the wire (observed traffic, not code inspection)
# ---------------------------------------------------------------------------


def _resolve_with_tap(mode):
    """session.resolve(backend="queue") with every serialized frame
    captured.  Returns (session, [(sender, kind, blob)])."""
    from repro.data import make_vertical_mnist_parties
    from repro.federation import VerticalSession, feature_parties
    captured = []
    orig = transport.channel_pair

    def tapped(a, b, **kw):
        kw["tap"] = lambda msg, blob: captured.append(
            (msg.sender, msg.kind, blob))
        return orig(a, b, **kw)

    transport.channel_pair = tapped
    try:
        sci, owners = make_vertical_mnist_parties(80, seed=4,
                                                  keep_frac=0.9)
        session = VerticalSession(*feature_parties(sci, owners))
        session.resolve(group=GROUP, mode=mode, backend="queue",
                        chunk_size=16)
    finally:
        transport.channel_pair = orig
    return session, captured


@pytest.mark.parametrize("mode", ["noinv", "bloom", "hidden"])
def test_no_raw_ids_on_the_wire(mode):
    """Every byte of every frame of a full resolve: raw IDs never cross
    in any encoding the protocol could accidentally emit — plaintext,
    sha256(id), or the unblinded group element H(id).  (Populations, not
    the aligned view — in hidden mode the view holds pseudonyms.)"""
    import hashlib
    from repro.core.psi import hash_to_group
    session, captured = _resolve_with_tap(mode)
    assert captured, "tap captured no traffic"
    all_ids = set(session.scientist._full.ids)
    for o in session.owners:
        all_ids |= set(o._full.ids)
    p = GROUPS[GROUP][0]
    needles = []
    for i in sorted(all_ids)[:40]:                    # bound test cost
        needles.append(i.encode())
        needles.append(hashlib.sha256(i.encode()).digest())
        needles.append(hash_to_group(i.encode(), p, NB).to_bytes(NB,
                                                                 "big"))
    blobs = b"\x00".join(blob for _, _, blob in captured)
    for needle in needles:
        assert needle not in blobs, \
            f"identifying bytes leaked onto the wire: {needle[:16]!r}"


def test_bloom_mode_server_set_crosses_only_compressed():
    """In bloom mode the owner's set reaches the scientist ONLY as bloom
    shard bitmaps, within the Angelou et al. byte budget (~12x under the
    raw packed set) — asserted on the measured frames."""
    session, captured = _resolve_with_tap("bloom")
    owner_kinds = {k for s, k, _ in captured if s != "scientist"}
    assert "psi_server_set_chunk" not in owner_kinds
    assert "psi_bloom_shard" in owner_kinds
    for owner in session.owners:
        raw = NB * owner.n_rows
        shard_bytes = sum(
            len(b) for s, k, b in captured
            if s == owner.name and k == "psi_bloom_shard")
        assert 0 < shard_bytes < raw / 8, \
            "bloom frames exceed the compression byte budget"


def test_only_protocol_kinds_cross_the_boundary():
    _, captured = _resolve_with_tap("noinv")
    assert {k for _, k, _ in captured} <= set(WIRE_KINDS)
    assert set(CLIENT_KINDS) & {k for s, k, _ in captured
                                if s == "scientist"}
    assert set(SERVER_KINDS) & {k for s, k, _ in captured
                                if s != "scientist"}


# ---------------------------------------------------------------------------
# pipelining under injected latency
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_pipelined_chunks_amortize_latency():
    """With 8 ms one-way latency and 12 chunks in flight, the round pays
    O(1) RTTs, not one RTT per chunk (the sequential floor).  Bounded
    generously for CI noise; the tight version is the BENCH_psi wire
    gate."""
    xs = [f"id-{i}" for i in range(96)]
    ys = [f"id-{i + 32}" for i in range(96)]
    lat = 8e-3
    n_chunks = 12

    def once(latency):
        t0 = time.perf_counter()
        inter = _wire_round(xs, ys, chunk_size=8, latency_s=latency)[0]
        assert sorted(set(inter)) == sorted(set(xs) & set(ys))
        return time.perf_counter() - t0

    base = min(once(0.0) for _ in range(2))
    timed = min(once(lat) for _ in range(2))
    seq_floor = n_chunks * 2 * lat                    # per-chunk RTTs
    assert timed - base < 0.75 * seq_floor, \
        (f"latency not amortized: {1e3 * (timed - base):.0f} ms added "
         f"vs sequential floor {1e3 * seq_floor:.0f} ms")


# ---------------------------------------------------------------------------
# delta resolution (ISSUE 10): O(Δ) repeat rounds after population churn
# ---------------------------------------------------------------------------


def _tapped_pair(ys):
    """Queue pair + running worker with a both-directions frame tap.
    Returns (client-endpoint, worker, thread, captured [(kind, nbytes)])."""
    captured = []
    server = PSIServer(ys, group=GROUP)
    ep_c, ep_s = transport.channel_pair(
        "scientist", "owner0", backend="queue",
        tap=lambda m, b: captured.append((m.kind, len(b))))
    worker, th = serve_psi("owner0", server, ep_s)
    return ep_c, worker, th, captured


def test_delta_round_after_small_churn_is_o_delta():
    """±4 churn on a 200-item set: the repeat round ships one small
    psi_delta_chunk (no blind chunks, no server-set leg), costs O(Δ)
    modexp on both sides, and returns the exact from-scratch result."""
    xs = [f"id-{i}" for i in range(200)]
    ys = [f"id-{i + 50}" for i in range(200)]
    client = PSIClient(xs, GROUP)
    ep_c, worker, th, captured = _tapped_pair(ys)
    try:
        _, st1 = wire_psi_round(client, ep_c, worker=worker,
                                chunk_size=32)
        mark = len(captured)
        ops_mark = client.ops
        xs2 = xs[4:] + [f"new-{i}" for i in range(4)]
        client.update_items(xs2)
        i2, st2 = wire_psi_round(client, ep_c, worker=worker,
                                 chunk_size=32)
    finally:
        ep_c.send("psi_stop", {})
        th.join(timeout=10.0)
    ref, _ = psi_round(PSIClient(list(client.items), GROUP),
                       PSIServer(ys, group=GROUP), chunk_size=32)
    assert i2 == ref
    assert st2["delta_used"] and not st2["upload_skipped"]
    assert st2["server_leg_skipped"]
    # O(Δ) modexp: 4 fresh client blinds (spent in update_items) + the
    # server's 4 responses; nothing else on either side
    client_delta_ops = client.ops - ops_mark
    assert client_delta_ops == 4
    assert st2["server_modexp_ops"] == 4
    assert st2["client_modexp_ops"] == 0          # server leg cached
    assert client_delta_ops + st2["server_modexp_ops"] \
        <= 0.05 * st1["modexp_ops"]
    # O(Δ) wire: no full upload, no server-set re-ship, tiny delta frame
    kinds2 = [k for k, _ in captured[mark:]]
    assert "psi_blind_chunk" not in kinds2
    assert "psi_server_set_chunk" not in kinds2
    delta_bytes = sum(n for k, n in captured[mark:]
                      if k == "psi_delta_chunk")
    assert 0 < delta_bytes < 0.05 * st1["client_upload_bytes"]


def test_unchanged_update_is_empty_delta_and_hello_only_round():
    """update_items with the identical list records no delta; the repeat
    round degenerates to the O(hello) cached path: zero modexp, zero
    chunk frames in either direction."""
    xs = [f"id-{i}" for i in range(60)]
    ys = [f"id-{i + 20}" for i in range(60)]
    client = PSIClient(xs, GROUP)
    ep_c, worker, th, captured = _tapped_pair(ys)
    try:
        i1, _ = wire_psi_round(client, ep_c, worker=worker, chunk_size=16)
        mark = len(captured)
        client.update_items(list(xs))
        assert client._delta is None
        i2, st2 = wire_psi_round(client, ep_c, worker=worker,
                                 chunk_size=16)
    finally:
        ep_c.send("psi_stop", {})
        th.join(timeout=10.0)
    assert i2 == i1
    assert st2["upload_skipped"] and st2["resp_skipped"]
    assert not st2["delta_used"]
    assert st2["modexp_ops"] == 0
    kinds2 = {k for k, _ in captured[mark:]}
    assert kinds2 <= {"psi_hello", "psi_hello_ack", "psi_done",
                      "psi_stop"}


def test_removal_only_delta_costs_zero_modexp():
    """A shrink-only churn (tombstones, nothing added) still splices:
    zero modexp anywhere, exact intersection."""
    xs = [f"id-{i}" for i in range(80)]
    ys = [f"id-{i + 10}" for i in range(80)]
    client = PSIClient(xs, GROUP)
    ep_c, worker, th, captured = _tapped_pair(ys)
    try:
        wire_psi_round(client, ep_c, worker=worker, chunk_size=16)
        ops_mark = client.ops
        client.update_items(xs[10:])
        i2, st2 = wire_psi_round(client, ep_c, worker=worker,
                                 chunk_size=16)
    finally:
        ep_c.send("psi_stop", {})
        th.join(timeout=10.0)
    ref, _ = psi_round(PSIClient(xs[10:], GROUP),
                       PSIServer(ys, group=GROUP), chunk_size=16)
    assert i2 == ref
    assert st2["delta_used"]
    assert client.ops == ops_mark
    assert st2["modexp_ops"] == 0


def test_full_churn_falls_back_to_full_upload():
    """100% churn: no delta is recorded and the round re-runs the full
    protocol (fresh blind chunks), still exact."""
    xs = [f"id-{i}" for i in range(50)]
    ys = [f"id-{i + 100}" for i in range(100)]
    client = PSIClient(xs, GROUP)
    ep_c, worker, th, captured = _tapped_pair(ys)
    try:
        wire_psi_round(client, ep_c, worker=worker, chunk_size=16)
        mark = len(captured)
        xs2 = [f"id-{i + 120}" for i in range(50)]      # disjoint from xs
        client.update_items(xs2)
        assert client._delta is None
        i2, st2 = wire_psi_round(client, ep_c, worker=worker,
                                 chunk_size=16)
    finally:
        ep_c.send("psi_stop", {})
        th.join(timeout=10.0)
    ref, _ = psi_round(PSIClient(xs2, GROUP),
                       PSIServer(ys, group=GROUP), chunk_size=16)
    assert i2 == ref and len(i2) > 0
    assert not st2["delta_used"] and not st2["upload_skipped"]
    assert "psi_blind_chunk" in [k for k, _ in captured[mark:]]


def test_duplicate_ids_in_delta_keep_multiset_semantics():
    """Churn that raises an existing ID's multiplicity and adds new
    duplicates: the spliced round matches the from-scratch engine with
    exact duplicate multiplicity."""
    xs = [f"id-{i}" for i in range(40)]
    ys = [f"id-{i + 5}" for i in range(40)] + ["dup-x"]
    client = PSIClient(xs, GROUP)
    ep_c, worker, th, _ = _tapped_pair(ys)
    try:
        wire_psi_round(client, ep_c, worker=worker, chunk_size=8)
        xs2 = xs[2:] + ["dup-x", "dup-x", "id-20"]      # id-20 now twice
        client.update_items(xs2)
        assert client._delta is not None
        i2, st2 = wire_psi_round(client, ep_c, worker=worker,
                                 chunk_size=8)
    finally:
        ep_c.send("psi_stop", {})
        th.join(timeout=10.0)
    ref, _ = psi_round(PSIClient(list(client.items), GROUP),
                       PSIServer(ys, group=GROUP), chunk_size=8)
    assert i2 == ref
    assert st2["delta_used"]
    assert i2.count("dup-x") == 2 and i2.count("id-20") == 2


def test_hidden_delta_round_reuses_response_leg():
    """Hidden mode: after ±2 churn the repeat round uses the delta path
    (tiny upload, cached server leg) and the keep-mask stays a correct
    padded superset of the true member positions."""
    import math
    from repro.core.psi import HIDDEN_PAD
    xs = [f"id-{i}" for i in range(100)]
    ys = [f"id-{i + 30}" for i in range(100)]
    client = PSIClient(xs, GROUP, mode="hidden")
    ep_c, worker, th, captured = _tapped_pair(ys)
    try:
        wire_psi_round(client, ep_c, worker=worker, chunk_size=16)
        mark = len(captured)
        xs2 = xs[2:] + ["fresh-0", "fresh-1"]
        client.update_items(xs2)
        keep, st2 = wire_psi_round(client, ep_c, worker=worker,
                                   chunk_size=16)
    finally:
        ep_c.send("psi_stop", {})
        th.join(timeout=10.0)
    assert st2["delta_used"] and st2["server_leg_skipped"]
    assert "psi_blind_chunk" not in [k for k, _ in captured[mark:]]
    members = {i for i, it in enumerate(client.items) if it in set(ys)}
    target = min(len(client.items),
                 math.ceil(max(len(members), 1) / HIDDEN_PAD)
                 * HIDDEN_PAD)
    assert members <= set(keep)
    assert len(keep) == target == st2["hidden_kept"]


# ---------------------------------------------------------------------------
# hidden mode (ISSUE 10): membership hiding on the wire
# ---------------------------------------------------------------------------


def _hidden_round_profile(xs, ys):
    """Run one hidden round; return ({kind: sorted frame lengths},
    stats)."""
    client = PSIClient(xs, GROUP, mode="hidden")
    ep_c, worker, th, captured = _tapped_pair(ys)
    try:
        _, stats = wire_psi_round(client, ep_c, worker=worker,
                                  chunk_size=8)
    finally:
        ep_c.send("psi_stop", {})
        th.join(timeout=10.0)
    profile = {}
    for k, n in captured:
        profile.setdefault(k, []).append(n)
    return {k: sorted(v) for k, v in profile.items()}, stats


def test_hidden_mode_wire_indistinguishable_member_vs_nonmember():
    """Swap one probe ID between member and non-member: every frame kind
    appears the same number of times with the same byte lengths, and the
    padded keep count is identical — a wire observer (or the scientist
    counting frames) cannot tell whether the probe matched."""
    ys = [f"id-{i}" for i in range(30)]
    base = [f"id-{i}" for i in range(10)] + [f"out-{i}" for i in range(9)]
    prof_a, st_a = _hidden_round_profile(base + ["id-20"], ys)   # member
    prof_b, st_b = _hidden_round_profile(base + ["out-99"], ys)  # not
    assert prof_a == prof_b
    assert st_a["hidden_kept"] == st_b["hidden_kept"]
    assert "psi_double_chunk" not in prof_a          # never unblinded back


def test_hidden_mode_ships_no_double_blind_leg():
    """The hidden response is keep positions + rows only: no
    psi_double_chunk and no per-item unblind work on the client."""
    xs = [f"id-{i}" for i in range(64)]
    ys = [f"id-{i + 16}" for i in range(64)]
    client = PSIClient(xs, GROUP, mode="hidden")
    ep_c, worker, th, captured = _tapped_pair(ys)
    try:
        keep, stats = wire_psi_round(client, ep_c, worker=worker,
                                     chunk_size=16)
    finally:
        ep_c.send("psi_stop", {})
        th.join(timeout=10.0)
    kinds = {k for k, _ in captured}
    assert "psi_double_chunk" not in kinds
    assert "psi_keep_mask" in kinds
    assert len(stats["hidden_rows"]) == len(keep)


def test_session_hidden_resolve_bit_stable_direct_vs_queue():
    """mode="hidden" through the session: pseudonymous aligned views are
    bit-identical between the direct and queue backends, and every party
    ends on the same ID list with decoy padding ≤ HIDDEN_PAD - 1."""
    from repro.core.psi import HIDDEN_PAD
    from repro.data import make_vertical_mnist_parties
    from repro.federation import VerticalSession, feature_parties
    views = {}
    for backend in ("direct", "queue"):
        sci, owners = make_vertical_mnist_parties(120, seed=7,
                                                  keep_frac=0.85)
        session = VerticalSession(*feature_parties(sci, owners))
        st = session.resolve(group=GROUP, mode="hidden", backend=backend,
                             chunk_size=16)
        ids = session.scientist.ids
        assert ids and all(i.startswith("anon") for i in ids)
        for o in session.owners:
            assert o.ids == ids
        true_members = set(session.scientist._full.ids)
        for o in session.owners:
            true_members &= set(o._full.ids)
        assert len(true_members) <= len(ids) \
            <= len(true_members) + HIDDEN_PAD - 1
        views[backend] = (list(ids),
                          session.scientist._vd.data.tobytes(),
                          [o._vd.data.tobytes() for o in session.owners])
        assert st["mode"] == "hidden"
    assert views["direct"] == views["queue"]


# ---------------------------------------------------------------------------
# session-level repeat & delta resolution (ISSUE 10 bugfix: response-leg
# cache makes the unchanged repeat round O(hello) wire bytes)
# ---------------------------------------------------------------------------


def test_session_repeat_resolve_is_hello_only_on_queue():
    """Second resolve with unchanged populations: every owner round is
    fully cached — zero modexp, no chunk frames, only the hello/ack/done
    envelope crosses the wire."""
    from repro.data import make_vertical_mnist_parties
    from repro.federation import VerticalSession, feature_parties
    sci, owners = make_vertical_mnist_parties(150, seed=2, keep_frac=0.9)
    session = VerticalSession(*feature_parties(sci, owners))
    st1 = session.resolve(group=GROUP, backend="queue", chunk_size=32)
    ids1 = list(session.scientist.ids)
    st2 = session.resolve(group=GROUP, backend="queue", chunk_size=32)
    assert session.scientist.ids == ids1
    assert st2["global_intersection"] == st1["global_intersection"]
    for r in st2["rounds"]:
        assert r["upload_skipped"] and r["resp_skipped"]
        assert r["server_leg_skipped"]
        assert r["client_modexp_ops"] == 0
        assert r["server_modexp_ops"] == 0
        # O(hello): psi_hello + psi_hello_ack + psi_done + psi_stop only
        assert r["upload_wire_bytes"] < 1024
        assert r["download_wire_bytes"] < 1024
    reuse = [m for m in session.transcript
             if m["kind"] == "psi_resp_reuse"]
    assert len(reuse) >= 0                 # transcript stays parseable


def test_session_delta_resolve_after_churn_is_o_delta_on_queue():
    """±2 churn of the scientist's population between resolves: every
    owner round takes the delta path, total modexp and upload bytes
    collapse to O(Δ), the aligned result is exact, and the transcript
    records the reuse."""
    import numpy as np
    from repro.data import make_vertical_mnist_parties
    from repro.federation import VerticalSession, feature_parties
    sci, owners = make_vertical_mnist_parties(200, seed=3, keep_frac=1.0)
    session = VerticalSession(*feature_parties(sci, owners))
    st1 = session.resolve(group=GROUP, backend="queue", chunk_size=64)
    full_ops = sum(r["client_modexp_ops"] + r["server_modexp_ops"]
                   for r in st1["rounds"])
    full_up = max(r["upload_wire_bytes"] for r in st1["rounds"])
    s = session.scientist
    pop = list(s._full.ids)
    new_ids = pop[2:] + ["fresh-0", "fresh-1"]
    new_data = np.concatenate(
        [s._full.data[2:], np.zeros((2,) + s._full.data.shape[1:],
                                    s._full.data.dtype)])
    s.update_rows(new_ids, new_data)
    st2 = session.resolve(group=GROUP, backend="queue", chunk_size=64)
    for r in st2["rounds"]:
        assert r["delta_used"] and r["server_leg_skipped"]
        assert r["upload_wire_bytes"] < 0.05 * full_up
    delta_ops = sum(r["client_modexp_ops"] + r["server_modexp_ops"]
                    for r in st2["rounds"])
    assert delta_ops <= 0.05 * full_ops
    # exactness: the fresh IDs are unknown to owners, 2 dropped IDs gone
    expect = sorted(set(pop[2:]))
    assert session.scientist.ids == expect
    for o in session.owners:
        assert o.ids == expect
    reuse = [m for m in session.transcript
             if m["kind"] == "psi_delta_reuse"]
    assert [m["to"] for m in reuse] == [o.name for o in session.owners]


# ---------------------------------------------------------------------------
# protocol guards + population-update edge paths (coverage of the loud
# failure modes the desync/validation layer promises)
# ---------------------------------------------------------------------------


def _hello_payload(server, **over):
    from repro.federation.psi_transport import ZERO_TAG, _u8
    pl = {"mode": _u8(b"noinv"), "group": _u8(server.group.encode()),
          "blind_tag": _u8(b"x" * 16), "base_tag": _u8(ZERO_TAG),
          "server_tag": _u8(ZERO_TAG), "have_resp": np.uint8(0),
          "n_items": np.int64(4), "chunk_size": np.int64(2),
          "nb": np.int64(server._nb)}
    pl.update(over)
    return pl


def test_owner_endpoint_rejects_malformed_protocol():
    """Every _on_hello validation arm raises loudly instead of serving a
    desynchronized round; unknown kinds raise; heartbeats are acked."""
    import types

    from repro.federation.psi_transport import _u8

    server = PSIServer([f"s{i}" for i in range(4)], group="modp512")
    ep_c, ep_s = transport.channel_pair("scientist", "owner0",
                                        backend="queue")
    worker = PSIServerEndpoint("owner0", server, ep_s)

    def msg(kind, payload=None, seq=0):
        return types.SimpleNamespace(kind=kind, payload=payload or {},
                                     seq=seq)

    with pytest.raises(RuntimeError, match="unknown message kind"):
        worker.handle(msg("not_a_psi_kind"))
    with pytest.raises(RuntimeError, match="unknown PSI mode"):
        worker.handle(msg("psi_hello",
                          _hello_payload(server, mode=_u8(b"nonsense"))))
    with pytest.raises(RuntimeError, match="element width mismatch"):
        worker.handle(msg("psi_hello",
                          _hello_payload(server, nb=np.int64(1))))
    with pytest.raises(RuntimeError, match="chunk_size must be positive"):
        worker.handle(msg("psi_hello",
                          _hello_payload(server,
                                         chunk_size=np.int64(0))))
    with pytest.raises(RuntimeError, match="delta chunk without"):
        worker.handle(msg("psi_delta_chunk",
                          {"data": _u8(b""),
                           "removed": np.array([], np.int64),
                           "n_retained": np.int64(0)}))
    with pytest.raises(RuntimeError, match="lift chunk outside"):
        worker.handle(msg("psi_lift_chunk",
                          {"data": _u8(b""), "base": np.int64(0)}))
    with pytest.raises(RuntimeError, match="blind chunk outside"):
        worker.handle(msg("psi_blind_chunk",
                          {"data": _u8(b""), "base": np.int64(0)}))
    # heartbeat is acked, not fatal
    assert worker.handle(msg("heartbeat", seq=7))
    ack = ep_c.recv(timeout=5.0)
    assert ack.kind == "heartbeat_ack" and ack.seq == 7


def test_client_mode_and_group_validation():
    with pytest.raises(ValueError, match="unknown PSI mode"):
        PSIClient(["a"], "modp512", mode="nonsense")
    with pytest.raises(ValueError, match="group mismatch"):
        psi_round(PSIClient(["a"], "modp512"),
                  PSIServer(["a"], group="modp2048"))


def test_update_items_before_any_blinding_is_a_plain_swap():
    """Churning a client that never ran a round has no memoized upload
    to splice — the population swaps and no delta is recorded."""
    client = PSIClient(["a", "b"], "modp512")
    client.update_items(["b", "c"])
    assert list(client.items) == ["b", "c"]
    assert client._delta is None
    assert client.ops == 0                  # nothing blinded yet


def test_reorder_only_update_records_no_delta():
    """Same multiset, different order: nothing was added or removed, so
    there is no delta to ship and the blinded upload keeps its canonical
    positional order (peers' caches stay valid)."""
    client = PSIClient([f"c{i}" for i in range(6)], "modp512")
    server = PSIServer([f"c{i}" for i in range(3, 9)], group="modp512")
    psi_round(client, server, chunk_size=4)
    items = list(client.items)
    ops0 = client.ops
    client.update_items(items[::-1])
    assert client._delta is None
    assert list(client.items) == items     # base order preserved
    assert client.ops == ops0              # nothing re-blinded


def test_server_population_update_invalidates_response_leg():
    """PSIServer.update_items: the owner's own set churns between
    rounds — the server leg's content tag changes (so a caching client
    re-downloads it) while only genuinely new items get blinded, and the
    next round resolves the NEW intersection exactly."""
    client = PSIClient([f"c{i}" for i in range(8)], "modp512")
    server = PSIServer([f"c{i}" for i in range(4, 12)], group="modp512")
    i1, _ = psi_round(client, server, chunk_size=4)
    assert sorted(i1) == [f"c{i}" for i in range(4, 8)]
    tag1 = server.server_leg_tag("noinv", None, 4)
    ops0 = server.ops

    server.update_items([f"c{i}" for i in range(2, 10)])
    # no-op update is free
    server.update_items([f"c{i}" for i in range(2, 10)])
    tag2 = server.server_leg_tag("noinv", None, 4)
    assert tag2 != tag1
    i2, _ = psi_round(client, server, chunk_size=4)
    assert sorted(i2) == [f"c{i}" for i in range(2, 8)]
    # round 2 cost: 8 fresh double-blind responses + ONLY the two
    # genuinely-new own items (c2, c3) blinded — the 6 retained own
    # blinds were reused from the element cache
    assert server.ops - ops0 == len(client.items) + 2


def test_blind_cached_but_client_response_lost_reships_doubles():
    """The client loses its transcript cache (fresh process) while the
    owner still holds the blind/response caches: the owner replays the
    double-blind leg from its response cache — zero modexp, zero upload
    bytes, same intersection."""
    xs = [f"x{i}" for i in range(12)]
    ys = [f"x{i}" for i in range(6, 18)]
    client = PSIClient(xs, GROUP)
    server = PSIServer(ys, group=GROUP)
    ep_c, ep_s = transport.channel_pair("scientist", "owner0",
                                        backend="queue")
    worker, th = serve_psi("owner0", server, ep_s)
    try:
        i1, _ = wire_psi_round(client, ep_c, worker=worker, chunk_size=4)
    finally:
        ep_c.send("psi_stop", {})
        th.join(timeout=10.0)

    client.round_cache.clear()
    ep_c, ep_s = transport.channel_pair("scientist", "owner0",
                                        backend="queue")
    w2 = PSIServerEndpoint("owner0", worker.server, ep_s,
                           blind_cache=worker._blind_cache,
                           resp_cache=worker._resp_cache,
                           lift_cache=worker._lift_cache)
    th = threading.Thread(target=w2.run, daemon=True)
    th.start()
    try:
        i2, st2 = wire_psi_round(client, ep_c, worker=w2, chunk_size=4)
    finally:
        ep_c.send("psi_stop", {})
        th.join(timeout=10.0)
    assert sorted(i2) == sorted(i1)
    assert st2["blind_cached"] and st2["upload_skipped"]
    assert not st2["resp_skipped"]
    assert ep_c.recv_stats["by_kind"]["psi_double_chunk"]["count"] > 0
    assert st2["server_modexp_ops"] == 0    # replayed from the cache
