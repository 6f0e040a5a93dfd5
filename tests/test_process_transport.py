"""Process-per-party runtime (ISSUE 6): the multiprocess transport
backend must carry the queue backend's exact frames (bit-identical wire
accounting, same codec/latency/tap semantics), host owner + PSI actors in
spawned worker processes through the full session surface
(``fit``/``resolve``/``serve``), scale to many owners with uneven feature
widths, and survive the straggler/crash/rejoin chaos suite with clean
surfaced errors."""
import dataclasses
import multiprocessing as mp
import queue as _queue
import struct
import threading
import time
import zlib
from importlib.util import find_spec

import numpy as np
import pytest

from repro.configs.base import SplitConfig
from repro.configs.pyvertical_mnist import CONFIG
from repro.core import modexp
from repro.data import make_vertical_mnist_parties
from repro.federation import VerticalSession, transport
from repro.federation.parties import OwnerComputeEndpoint, feature_parties
from repro.federation.process_transport import (HEADER_FMT, POISON_KIND,
                                                ProcessEndpoint,
                                                process_endpoint_pair)
from repro.federation.transport import _pack, _payload_nbytes

GROUP = "modp512"


# ---------------------------------------------------------------------------
# endpoint unit tests (both ends in-process, frames over a real pipe)
# ---------------------------------------------------------------------------


def _pair(**kw):
    return process_endpoint_pair("scientist", "owner0", **kw)


def test_roundtrip_stats_and_stash():
    a, b = _pair()
    try:
        x = np.arange(12, dtype=np.float32).reshape(3, 4)
        a.send("cut_activations", {"cut": x}, seq=3)
        a.send("head_fwd", {"idx": np.arange(5, dtype=np.int32)}, seq=4)
        # recv_kind skips + stashes the earlier-arriving other kind
        m = b.recv_kind("head_fwd", timeout=5.0)
        assert m.seq == 4 and m.sender == "scientist"
        m2 = b.recv_kind("cut_activations", timeout=5.0)
        assert m2.seq == 3
        np.testing.assert_array_equal(m2.payload["cut"], x)
        assert b.empty()
        assert a.sent_stats["messages"] == 2
        assert b.recv_stats["messages"] == 2
        assert (a.sent_stats["by_kind"]["cut_activations"]["wire_bytes"]
                == b.recv_stats["by_kind"]["cut_activations"]["wire_bytes"])
        assert a.sent_stats["payload_bytes"] == x.nbytes + 5 * 4
    finally:
        a.close()
        b.close()


def test_recv_timeout_raises_queue_empty():
    a, b = _pair()
    try:
        t0 = time.monotonic()
        with pytest.raises(_queue.Empty):
            b.recv(timeout=0.3)
        assert 0.2 < time.monotonic() - t0 < 5.0
    finally:
        a.close()
        b.close()


def test_wire_bytes_bit_identical_to_queue_backend():
    """The acceptance invariant at the unit level: the same payload
    crosses both backends with the exact same measured payload and wire
    bytes (the process transport header is uncounted, playing the role
    of the queue backend's uncounted Message envelope)."""
    rng = np.random.default_rng(0)
    payload = {"cut": rng.normal(size=(7, 9)).astype(np.float32),
               "aux": np.float32(1.5).reshape(())}
    qa, qb = transport.channel_pair("a", "b", backend="queue")
    mq = qa.send("cut_activations", payload, seq=0)
    qb.recv_kind("cut_activations")
    pa, pb = _pair()
    try:
        mp_ = pa.send("cut_activations", payload, seq=0)
        got = pb.recv_kind("cut_activations", timeout=5.0)
        assert mp_.wire_bytes == mq.wire_bytes
        assert mp_.payload_bytes == mq.payload_bytes
        assert got.wire_bytes == mq.wire_bytes
        assert (pa.sent_stats["by_kind"]["cut_activations"]
                == qa.sent_stats["by_kind"]["cut_activations"])
    finally:
        pa.close()
        pb.close()


def test_frame_layout_golden():
    """The transport header is frozen:
    [u16 kind_len][kind][i64 seq][f64 not_before][i64 payload_bytes]
    [u32 crc32(blob)] followed by the exact ``transport._pack`` blob."""
    c1, c2 = mp.Pipe(duplex=True)
    ep = ProcessEndpoint("a", "b", c1)
    try:
        payload = {"x": np.arange(3, dtype=np.float32)}
        ep.send("ping", payload, seq=5)
        assert c2.poll(5.0)
        frame = c2.recv_bytes()
        blob = _pack(payload)
        assert frame == (struct.pack("<H", 4) + b"ping"
                         + struct.pack(HEADER_FMT, 5, 0.0,
                                       _payload_nbytes(payload),
                                       zlib.crc32(blob) & 0xFFFFFFFF)
                         + blob)
    finally:
        ep.close()
        c2.close()


def test_latency_injection_delays_delivery():
    a, b = _pair(latency_s=0.2)
    try:
        t0 = time.monotonic()
        a.send("ping", {"x": np.zeros(1, np.float32)})
        b.recv_kind("ping", timeout=5.0)
        assert time.monotonic() - t0 >= 0.15
    finally:
        a.close()
        b.close()


def test_bandwidth_models_transit_time():
    a, b = _pair(bandwidth_bps=64_000.0)
    try:
        t0 = time.monotonic()
        m = a.send("bulk", {"x": np.zeros(4096, np.float32)})
        b.recv_kind("bulk", timeout=30.0)
        expect = m.wire_bytes / 64_000.0
        assert time.monotonic() - t0 >= 0.5 * expect
    finally:
        a.close()
        b.close()


def test_tap_observes_both_directions():
    seen = []
    a, b = _pair(tap=lambda m, blob: seen.append((m.kind, m.sender,
                                                  len(blob))))
    try:
        a.send("ping", {"x": np.zeros(2, np.float32)})
        b.send("pong", {"x": np.zeros(2, np.float32)})
        a.recv_kind("pong", timeout=5.0)
        b.recv_kind("ping", timeout=5.0)
        kinds = {(k, s) for k, s, _ in seen}
        assert ("ping", "scientist") in kinds     # a's send
        assert ("pong", "owner0") in kinds        # a's recv
        assert all(n > 0 for _, _, n in seen)
    finally:
        a.close()
        b.close()


def test_poison_pill_surfaces_peer_error():
    a, b = _pair()
    try:
        try:
            raise ValueError("owner-side kaboom")
        except ValueError as e:
            a.send_error(e, "tb-line-1\ntb-line-2")
        with pytest.raises(RuntimeError,
                           match="died: ValueError: owner-side kaboom"):
            b.recv(timeout=5.0)
        assert b.peer_error is not None
        assert "tb-line-2" in str(b.peer_error)
        # sticky: every subsequent receive re-raises
        with pytest.raises(RuntimeError, match="died"):
            b.recv(timeout=5.0)
    finally:
        a.close()
        b.close()


def test_severed_pipe_raises_clean_runtime_error():
    a, b = _pair()
    a.close()
    try:
        with pytest.raises(RuntimeError, match="connection .* closed"):
            b.recv(timeout=5.0)
    finally:
        b.close()


def test_send_after_close_rejected():
    a, b = _pair()
    a.close()
    b.close()
    with pytest.raises(RuntimeError, match="closed"):
        a.send("ping", {})


# ---------------------------------------------------------------------------
# satellite: spin-wait tunable
# ---------------------------------------------------------------------------


def test_spin_wait_env_override(monkeypatch):
    """``REPRO_SPIN_WAIT_S`` overrides the hybrid-wait spin window;
    garbage or negative values fall back to the core-count default."""
    default = (transport.SPIN_WAIT_S
               if transport._effective_cores() > 1
               else transport.SPIN_WAIT_SINGLE_CORE_S)
    monkeypatch.delenv("REPRO_SPIN_WAIT_S", raising=False)
    assert transport.spin_wait_s() == default
    monkeypatch.setenv("REPRO_SPIN_WAIT_S", "0.0125")
    assert transport.spin_wait_s() == 0.0125
    # endpoints pick the override up at construction
    a, b = _pair()
    try:
        assert a.spin_s == 0.0125
    finally:
        a.close()
        b.close()
    ch_a, _ = transport.channel_pair("a", "b", backend="queue")
    assert ch_a.outbox.spin_s == 0.0125
    monkeypatch.setenv("REPRO_SPIN_WAIT_S", "not-a-float")
    assert transport.spin_wait_s() == default
    monkeypatch.setenv("REPRO_SPIN_WAIT_S", "-3.0")
    assert transport.spin_wait_s() == default


# ---------------------------------------------------------------------------
# satellite: gmpy2 modexp backend selection
# ---------------------------------------------------------------------------


def test_modexp_backend_selection_matches_environment():
    """``HAVE_GMPY2`` must reflect what's actually importable, and the
    live backend must agree with builtin ``pow`` either way (this
    container ships without gmpy2, so CI pins the pure-Python path;
    docs/BENCHMARKS.md records the measured speedup where it exists)."""
    assert modexp.HAVE_GMPY2 == (find_spec("gmpy2") is not None)
    rng = np.random.default_rng(7)
    for _ in range(16):
        base = int(rng.integers(2, 1 << 60))
        exp = int(rng.integers(1, 1 << 60))
        mod = int(rng.integers(3, 1 << 60)) | 1
        assert modexp.powmod(base, exp, mod) == pow(base, exp, mod)


@pytest.mark.skipif(not modexp.HAVE_GMPY2,
                    reason="gmpy2 not installed (optional dev dep)")
def test_gmpy2_powmod_agrees_with_builtin():
    from gmpy2 import powmod as gpowmod
    rng = np.random.default_rng(11)
    for _ in range(32):
        base = int(rng.integers(2, 1 << 61))
        exp = int(rng.integers(1, 1 << 61))
        mod = int(rng.integers(3, 1 << 61)) | 1
        assert int(gpowmod(base, exp, mod)) == pow(base, exp, mod)


# ---------------------------------------------------------------------------
# worker harness (runtime.py) driven on a thread — the exact child code
# path, visible to the coverage tracer
# ---------------------------------------------------------------------------


def _owner_spec(owner_index=0, n_rows=40, seed=0, **kw):
    import jax

    from repro.federation import runtime
    from repro.federation.registry import build_adapter

    adapter = build_adapter(CONFIG)
    params = adapter.init(jax.random.PRNGKey(seed))
    leaves = [np.asarray(l) for l in jax.tree_util.tree_leaves(
        adapter.owner_param_slice(params, owner_index))]
    rng = np.random.default_rng(seed)
    return runtime.OwnerWorkerSpec(
        name=f"owner{owner_index}",
        ids=[f"subject-{i:08d}" for i in range(n_rows)],
        features=rng.normal(size=(n_rows, 392)).astype(np.float32),
        owner_index=owner_index, config=CONFIG, init_seed=seed,
        param_leaves=leaves, **kw), leaves


def test_owner_worker_main_serves_protocol_on_a_thread():
    from repro.federation import runtime

    spec, leaves = _owner_spec()
    parent, child = mp.Pipe(duplex=True)
    th = threading.Thread(target=runtime.owner_worker_main,
                          args=(spec, child), daemon=True)
    th.start()
    ep = ProcessEndpoint("scientist", "owner0", parent)
    try:
        ep.send("barrier", {}, seq=-1)
        assert ep.recv_kind("barrier_ack", timeout=120.0).kind == \
            "barrier_ack"
        # pull_params ships the worker's numbered numpy leaves back
        ep.send("pull_params", {}, seq=-1)
        m = ep.recv_kind("params_dump", timeout=60.0)
        assert len(m.payload) == len(leaves)
        for i, leaf in enumerate(leaves):
            np.testing.assert_array_equal(m.payload[str(i)], leaf)
        ep.send("stop", {})
        th.join(timeout=60.0)
        assert not th.is_alive()
    finally:
        ep.close()


def test_worker_failure_ships_poison_pill():
    from repro.federation import runtime

    spec, leaves = _owner_spec()
    spec.param_leaves = leaves[:1]          # wrong arity: unflatten dies
    parent, child = mp.Pipe(duplex=True)
    th = threading.Thread(target=runtime.owner_worker_main,
                          args=(spec, child), daemon=True)
    th.start()
    ep = ProcessEndpoint("scientist", "owner0", parent)
    try:
        with pytest.raises(RuntimeError, match="party 'owner0' died"):
            ep.recv(timeout=120.0)
        assert ep.peer_error is not None
        th.join(timeout=30.0)
        assert not th.is_alive()
    finally:
        ep.close()


def test_spawned_psi_worker_lifecycle():
    """Spawn, handshake, clean stop: exit code 0 and no surfaced error
    (PSI workers are jax-free, so this round-trips in seconds)."""
    from repro.core.psi import GROUPS
    from repro.federation import runtime
    from repro.federation.parties import DataOwner

    owner = DataOwner("owner0", [f"id-{i}" for i in range(8)],
                      np.zeros((8, 4), np.float32))
    w = runtime.spawn_psi_worker(owner, group=GROUP)
    try:
        w.endpoint.send("psi_hello", {
            "group": np.frombuffer(GROUP.encode(), np.uint8),
            "mode": np.frombuffer(b"noinv", np.uint8),
            "nb": np.int64(GROUPS[GROUP][2]),
            "n_items": np.int64(8), "chunk_size": np.int64(4),
            "blind_tag": np.zeros(16, np.uint8),
            "base_tag": np.zeros(16, np.uint8),
            "server_tag": np.zeros(16, np.uint8),
            "have_resp": np.uint8(0)})
        m = w.endpoint.recv_kind("psi_hello_ack", timeout=60.0)
        assert int(np.asarray(m.payload["n_server_items"]).reshape(-1)[0]) \
            == 8
        assert w.error is None
        assert "alive" in repr(w)
    finally:
        try:
            w.endpoint.send("psi_stop", {})
        except RuntimeError:
            pass
        w.shutdown()
    assert w.proc.exitcode == 0
    assert w.error is None


# ---------------------------------------------------------------------------
# session surface: resolve / fit through spawned workers
# ---------------------------------------------------------------------------


def _mnist_session(n=320, seed=0, keep_frac=0.9, feature_splits=None):
    sci, owners = make_vertical_mnist_parties(
        n, seed=seed, keep_frac=keep_frac, feature_splits=feature_splits)
    return VerticalSession(*feature_parties(sci, owners))


def test_resolve_process_matches_direct():
    s1 = _mnist_session(400, keep_frac=0.8)
    s1.resolve(group=GROUP, backend="direct")
    s2 = _mnist_session(400, keep_frac=0.8)
    st = s2.resolve(group=GROUP, backend="process")
    assert s2.scientist.ids == s1.scientist.ids
    assert st["backend"] == "process"
    assert st["global_intersection"] == len(s1.scientist.ids)
    for name, wire in st["per_party_wire"].items():
        assert wire["sent_wire_bytes"] > 0
        assert wire["recv_wire_bytes"] > 0


def test_resolve_process_broadcasts_aligned_ids():
    """Broadcast fan-out: after a process-backend resolve every owner
    holds the same resolved ID order as the scientist (the invariant
    split training builds on)."""
    s = _mnist_session(200, keep_frac=0.7)
    s.resolve(group=GROUP, backend="process")
    for owner in s.owners:
        assert owner.ids == s.scientist.ids


def _params_equal(p1, p2):
    import jax
    l1 = jax.tree_util.tree_leaves(p1)
    l2 = jax.tree_util.tree_leaves(p2)
    return len(l1) == len(l2) and all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(l1, l2))


@pytest.mark.slow
def test_fit_process_bit_identical_to_queue():
    """The tentpole acceptance property: mode="split" through spawned
    worker processes reproduces the thread-backed queue run bit for bit
    — params, losses, and measured cut/grad wire bytes."""
    def run(backend):
        s = _mnist_session()
        s.resolve(group=GROUP)
        s.build(CONFIG)
        h = s.fit(mode="split", epochs=1, batch_size=64,
                  microbatches=2, backend=backend, verbose=False,
                  timeout=180.0)
        return s, h

    sq, hq = run("queue")
    sp, hp = run("process")
    assert _params_equal(sq.params, sp.params)
    assert hq["train"] == hp["train"]
    wq, wp = (h["transport"]["per_owner"] for h in (hq, hp))
    assert set(wq) == set(wp)
    for name in wq:
        for key in ("cut_payload_bytes", "cut_wire_bytes",
                    "grad_payload_bytes", "grad_wire_bytes"):
            assert wq[name][key] == wp[name][key], (name, key)
    assert hp["transport"]["backend"] == "process"


@pytest.mark.slow
def test_eight_owner_uneven_widths_end_to_end():
    """Many-owner scale-out: 8 spawned workers with uneven feature
    widths resolve + train end-to-end, and each owner's measured cut
    traffic matches the (owner-independent) cut width."""
    splits = (200, 60, 120, 84, 96, 40, 104, 80)       # sums to 784
    cfg = dataclasses.replace(
        CONFIG, feature_splits=splits,
        split=SplitConfig(n_owners=8, cut_layer=1, combine="concat",
                          cut_dim=64, owner_lr=0.01, scientist_lr=0.1))
    s = _mnist_session(256, seed=1, keep_frac=0.95, feature_splits=splits)
    s.resolve(group=GROUP, backend="process")
    s.build(cfg)
    h = s.fit(mode="split", epochs=1, batch_size=64, backend="process",
              verbose=False, timeout=300.0)
    assert [o.feature_shape[0] for o in s.owners] == list(splits)
    per_owner = h["transport"]["per_owner"]
    assert len(per_owner) == 8
    # cut width is owner-independent: every owner ships identical bytes
    assert len({v["cut_wire_bytes"] for v in per_owner.values()}) == 1
    assert h["train"], "training must produce history"


# ---------------------------------------------------------------------------
# chaos: stragglers, crashes, rejoin — process backend
# ---------------------------------------------------------------------------


def test_process_fit_refuses_a_held_accelerator(monkeypatch):
    """On an accelerator backend the parent holds the device, so a
    spawned owner worker could not open it: ``backend="process"`` fails
    at once with an error naming the cause, and spawns nothing."""
    import jax

    from repro.federation import runtime
    s = _mnist_session(200)
    s.resolve(group=GROUP)
    s.build(CONFIG)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(runtime, "_spawn", lambda *a, **k: pytest.fail(
        "spawned a worker on an accelerator backend"))
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="holds the tpu device.*R5"):
        s.fit(mode="split", epochs=1, batch_size=64, backend="process",
              verbose=False)
    assert time.monotonic() - t0 < 30.0


def test_psi_and_modexp_workers_never_load_jax():
    """The spawn targets of the PSI server workers and the modexp pool
    import a jax-free chain, so those children never initialize a JAX
    backend (and cannot contend for an accelerator the parent holds)."""
    import subprocess
    import sys
    code = ("import sys, repro.federation.runtime, repro.core.psi, "
            "repro.federation.psi_transport, repro.core.modexp; "
            "print('jax' in sys.modules)")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, check=True, timeout=120)
    assert out.stdout.strip() == "False"


@pytest.mark.slow
def test_process_fit_owner_crash_surfaces_cleanly(monkeypatch):
    """A worker process that dies mid-step (chaos-injected on its first
    ``head_fwd``) surfaces as an owner-attributed RuntimeError on the
    scientist side — via poison pill or exit code, never a hang."""
    monkeypatch.setenv("REPRO_CHAOS_PARTY", "owner0:crash_fwd")
    s = _mnist_session(200)
    s.resolve(group=GROUP)
    s.build(CONFIG)
    with pytest.raises(RuntimeError, match="owner worker 'owner0'"):
        s.fit(mode="split", epochs=1, batch_size=64, backend="process",
              verbose=False, timeout=60.0)


@pytest.mark.slow
def test_process_fit_wedged_owner_times_out(monkeypatch):
    """A wedged worker (hangs on its first ``head_fwd``, never answers)
    bounds the step by ``timeout`` instead of hanging the scientist;
    teardown escalates to terminate."""
    monkeypatch.setenv("REPRO_CHAOS_PARTY", "owner1:wedge_fwd")
    s = _mnist_session(200)
    s.resolve(group=GROUP)
    s.build(CONFIG)
    with pytest.raises(RuntimeError,
                       match="timed out waiting for 'cut_activations' "
                             "from 'owner1'"):
        s.fit(mode="split", epochs=1, batch_size=64, backend="process",
              verbose=False, timeout=6.0)


def test_queue_fit_wedged_owner_times_out(monkeypatch):
    """The same straggler guarantee on the thread-backed queue backend
    (until this PR only resolve had a wedged-owner timeout test)."""
    orig = OwnerComputeEndpoint.handle

    def wedged(self, msg):
        if msg.kind == "head_fwd" and self.owner.name == "owner0":
            time.sleep(5.0)
        return orig(self, msg)

    monkeypatch.setattr(OwnerComputeEndpoint, "handle", wedged)
    s = _mnist_session(200)
    s.resolve(group=GROUP)
    s.build(CONFIG)
    t0 = time.monotonic()
    with pytest.raises(RuntimeError,
                       match="timed out waiting for 'cut_activations' "
                             "from 'owner0'"):
        s.fit(mode="split", epochs=1, batch_size=64, backend="queue",
              verbose=False, timeout=1.5)
    assert time.monotonic() - t0 < 60.0


def test_process_psi_crash_surfaces_and_owner_rejoins(monkeypatch):
    """A PSI worker crash mid-round surfaces cleanly; clearing the fault
    and re-resolving (the rejoin) succeeds and matches the in-process
    engine."""
    monkeypatch.setenv("REPRO_CHAOS_PARTY", "owner0:crash_psi")
    s = _mnist_session(120, keep_frac=0.8)
    with pytest.raises(RuntimeError, match="owner0"):
        s.resolve(group=GROUP, backend="process", timeout=60.0)
    # fault cleared -> the owner rejoins with a fresh worker
    monkeypatch.delenv("REPRO_CHAOS_PARTY")
    s2 = _mnist_session(120, keep_frac=0.8)
    s2.resolve(group=GROUP, backend="process")
    ref = _mnist_session(120, keep_frac=0.8)
    ref.resolve(group=GROUP, backend="direct")
    assert s2.scientist.ids == ref.scientist.ids


def test_process_psi_wedged_worker_times_out(monkeypatch):
    monkeypatch.setenv("REPRO_CHAOS_PARTY", "owner0:wedge_psi")
    s = _mnist_session(120, keep_frac=0.8)
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="timed out"):
        s.resolve(group=GROUP, backend="process", timeout=4.0)
    assert time.monotonic() - t0 < 60.0


# ---------------------------------------------------------------------------
# serving through the process boundary
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_serving_process_transport_matches_queue():
    from repro.configs import get_config
    from repro.data import make_token_dataset
    from repro.federation.parties import sequence_parties

    cfg = get_config("llama3.2-3b", reduced=True)
    toks = make_token_dataset(4, 16, cfg.vocab, 0)[:, :16]

    def serve(transport_backend):
        session = VerticalSession(*sequence_parties(
            toks, cfg.split.n_owners, with_labels=False))
        session.resolve(group=GROUP)
        session.build(cfg)
        return session.serve_dataset(max_new=3, batch_slots=4,
                                     transport=transport_backend)

    queued, engine_q = serve("queue")
    proc, engine_p = serve("process")
    for rid in queued:
        assert proc[rid].generated == queued[rid].generated
    assert engine_p.stats["cut_wire_bytes"] == \
        engine_q.stats["cut_wire_bytes"]
    assert engine_p.stats["cut_messages"] == \
        engine_q.stats["cut_messages"]


def test_repeat_and_delta_resolve_on_process_backend():
    """ISSUE 10 on spawned workers: round 2 with unchanged populations
    re-ships nothing (caches are mirrored back to the parent parties
    across worker generations), and a ±2 churn round takes the delta
    path — O(hello)/O(Δ) upload bytes, asserted on round wire stats."""
    s = _mnist_session(200, keep_frac=1.0)
    st1 = s.resolve(group=GROUP, backend="process")
    ids1 = list(s.scientist.ids)
    full_up = max(r["upload_wire_bytes"] for r in st1["rounds"])

    st2 = s.resolve(group=GROUP, backend="process")
    assert s.scientist.ids == ids1
    for r in st2["rounds"]:
        assert r["upload_skipped"] and r["resp_skipped"]
        assert r["server_leg_skipped"]
        assert r["upload_wire_bytes"] < 1024
        assert r["download_wire_bytes"] < 1024

    sci = s.scientist
    pop = list(sci._full.ids)
    new_ids = pop[2:] + ["fresh-0", "fresh-1"]
    new_data = np.concatenate(
        [sci._full.data[2:], np.zeros((2,) + sci._full.data.shape[1:],
                                      sci._full.data.dtype)])
    sci.update_rows(new_ids, new_data)
    st3 = s.resolve(group=GROUP, backend="process")
    for r in st3["rounds"]:
        assert r["delta_used"] and r["server_leg_skipped"]
        assert r["upload_wire_bytes"] < 0.05 * full_up
    expect = sorted(set(pop[2:]))
    assert s.scientist.ids == expect
    for o in s.owners:
        assert o.ids == expect


def test_hidden_resolve_process_matches_queue():
    """mode="hidden" through spawned workers is bit-stable with the
    thread-backed queue backend: identical pseudonymous ID order and
    identical aligned feature bytes on every party."""
    sq = _mnist_session(150, seed=4, keep_frac=0.85)
    sq.resolve(group=GROUP, mode="hidden", backend="queue")
    sp = _mnist_session(150, seed=4, keep_frac=0.85)
    st = sp.resolve(group=GROUP, mode="hidden", backend="process")
    assert st["mode"] == "hidden"
    assert sp.scientist.ids == sq.scientist.ids
    assert sp.scientist.ids and \
        all(i.startswith("anon") for i in sp.scientist.ids)
    assert sp.scientist._vd.data.tobytes() == \
        sq.scientist._vd.data.tobytes()
    for oq, op in zip(sq.owners, sp.owners):
        assert op.ids == sp.scientist.ids
        assert op._vd.data.tobytes() == oq._vd.data.tobytes()
