"""True split execution: the transport layer (channels, wire format,
latency model), cut-payload codecs + the Pallas quantize kernel, the
pipelined/sequential split schedules' gradient equivalence against the
joint autodiff oracle, measured-vs-analytic traffic reconciliation, and
transport-backed serving."""
import time

import jax
import numpy as np
import pytest

from repro.testing.hypo import given, settings, strategies as st

from repro.configs import get_config
from repro.configs.pyvertical_mnist import CONFIG as MNIST_CFG
from repro.core.splitnn import cut_layer_traffic
from repro.core.vertical import (partition_features, partition_sequence,
                                 unpartition)
from repro.data import make_token_dataset, make_vertical_mnist_parties
from repro.federation import (VerticalSession, feature_parties,
                              sequence_parties, transport)
from repro.federation.transport import _pack, _unpack

RNG = np.random.default_rng(0)


# ---------------------------------------------------------------------------
# wire format and channels
# ---------------------------------------------------------------------------


def test_wire_format_round_trips_exactly():
    import ml_dtypes
    payload = {
        "f32": RNG.normal(size=(7, 33)).astype(np.float32),
        "i8": RNG.integers(-127, 127, (5, 4, 3)).astype(np.int8),
        "idx": np.arange(11, dtype=np.int32),
        "scalar": np.float32(3.5),
        # LM cut activations are bfloat16 — the wire format must carry
        # ml_dtypes extension types (dtype.name, not dtype.str)
        "bf16": RNG.normal(size=(4, 8)).astype(ml_dtypes.bfloat16),
    }
    back = _unpack(_pack(payload))
    assert set(back) == set(payload)
    for k in payload:
        assert back[k].dtype == np.asarray(payload[k]).dtype
        np.testing.assert_array_equal(
            back[k].astype(np.float32), payload[k].astype(np.float32))


@pytest.mark.parametrize("backend", ["queue", "direct"])
def test_channel_accounting_and_fifo(backend):
    a, b = transport.channel_pair("sci", "owner", backend=backend)
    x = RNG.normal(size=(16, 8)).astype(np.float32)
    a.send("head_fwd", {"idx": np.arange(4, dtype=np.int32)}, seq=0)
    a.send("cut_gradients", {"g": x}, seq=0)
    m0 = b.recv()
    m1 = b.recv()
    assert (m0.kind, m1.kind) == ("head_fwd", "cut_gradients")  # FIFO
    np.testing.assert_array_equal(m1.payload["g"], x)
    # measured bytes: the payload count is exactly the array buffers
    assert m1.payload_bytes == x.nbytes
    if backend == "queue":
        assert m1.wire_bytes > m1.payload_bytes        # + headers
    else:
        assert m1.wire_bytes == m1.payload_bytes
    st_ = a.sent_stats
    assert st_["messages"] == 2
    assert st_["by_kind"]["cut_gradients"]["payload_bytes"] == x.nbytes


def test_recv_kind_stashes_out_of_order_messages():
    a, b = transport.channel_pair("sci", "owner", backend="direct")
    a.send("cut_activations", {"x": np.zeros(3, np.float32)}, seq=7)
    a.send("barrier_ack", {}, seq=-1)
    ack = b.recv_kind("barrier_ack")           # skips past the cut message
    assert ack.seq == -1
    cut = b.recv_kind("cut_activations")       # stashed, not lost
    assert cut.seq == 7


def test_queue_latency_delays_delivery():
    a, b = transport.channel_pair("sci", "owner", backend="queue",
                                  latency_s=0.05)
    t0 = time.monotonic()
    a.send("head_fwd", {"idx": np.arange(2)}, seq=0)
    b.recv()
    assert time.monotonic() - t0 >= 0.045


def test_bandwidth_models_transit_time():
    # 40 KB at 1 MB/s ~= 40 ms of transit
    a, b = transport.channel_pair("sci", "owner", backend="queue",
                                  bandwidth_bps=1e6)
    t0 = time.monotonic()
    a.send("cut_activations",
           {"x": np.zeros((100, 100), np.float32)}, seq=0)
    b.recv()
    assert time.monotonic() - t0 >= 0.03


# ---------------------------------------------------------------------------
# codecs and the Pallas quantize kernel
# ---------------------------------------------------------------------------


def test_codec_round_trips_and_ratios():
    x = RNG.normal(size=(64, 64)).astype(np.float32)
    none = transport.get_codec(None)
    np.testing.assert_array_equal(none.decode(none.encode(x)), x)

    fp16 = transport.get_codec("fp16")
    enc = fp16.encode(x)
    assert sum(a.nbytes for a in enc.values()) == x.nbytes // 2
    assert np.abs(fp16.decode(enc) - x).max() < 2e-3

    int8 = transport.get_codec("int8")
    enc = int8.encode(x)
    nbytes = sum(a.nbytes for a in enc.values())
    assert x.nbytes / nbytes >= 3.0                 # >=3x smaller payload
    # per-row scale bounds the dequantization error
    row_max = np.abs(x).max(-1, keepdims=True)
    assert (np.abs(int8.decode(enc) - x) <= row_max / 127.0 + 1e-7).all()

    with pytest.raises(ValueError, match="unknown compression"):
        transport.get_codec("zstd")


def test_quantize_kernel_matches_ref():
    from repro.kernels.quantize import quantize_int8, quantize_int8_ref
    for shape in ((8, 64), (130, 64), (1, 128)):    # incl. padded grids
        x = RNG.normal(size=shape).astype(np.float32) * 3.0
        q, s = quantize_int8(x, interpret=True)
        qr, sr = quantize_int8_ref(x)
        np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
        np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                                   rtol=1e-6)
        assert np.asarray(q).dtype == np.int8


# ---------------------------------------------------------------------------
# uneven vertical partitions (core/vertical.py)
# ---------------------------------------------------------------------------


@given(st.lists(st.integers(1, 7), min_size=1, max_size=5),
       st.integers(0, 1000))
@settings(max_examples=25)
def test_uneven_partition_round_trips(sizes, seed):
    rng = np.random.default_rng(seed)
    width = sum(sizes)
    x = rng.normal(size=(5, width)).astype(np.float32)
    slices = partition_features(x, sizes)
    assert [s.shape[-1] for s in slices] == list(sizes)
    np.testing.assert_array_equal(unpartition(slices), x)
    t = rng.integers(0, 100, size=(3, width))
    tslices = partition_sequence(t, sizes)
    assert [s.shape[1] for s in tslices] == list(sizes)
    np.testing.assert_array_equal(unpartition(tslices, axis=1), t)


def test_uneven_partition_validation():
    x = np.zeros((2, 10))
    with pytest.raises(ValueError, match="not divisible"):
        partition_features(x, 3)
    with pytest.raises(ValueError, match="sum to"):
        partition_features(x, (4, 4))
    with pytest.raises(ValueError, match="positive"):
        partition_sequence(x, (11, -1))
    # explicit sizes match the equal split
    np.testing.assert_array_equal(
        np.stack(partition_features(x, (5, 5))),
        np.stack(partition_features(x, 2)))


# ---------------------------------------------------------------------------
# split execution: gradient equivalence against the joint oracle
# ---------------------------------------------------------------------------


def _mnist_session(n=400):
    sci, owners = make_vertical_mnist_parties(n, seed=0, keep_frac=0.9)
    session = VerticalSession(*feature_parties(sci, owners))
    session.resolve(group="modp512")
    session.build(MNIST_CFG)
    return session


def _params_equal(p1, p2):
    return all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))


def test_split_matches_joint_bit_for_bit():
    """fit(mode="split") — pipelined AND sequential, uncompressed queue
    transport — reproduces the joint autodiff path's params bit-for-bit
    after K steps (the ISSUE's acceptance bar)."""
    joint = _mnist_session()
    h_joint = joint.fit(epochs=2, batch_size=64, eval_frac=0.1,
                        verbose=False)
    for sched in ("pipelined", "sequential"):
        split = _mnist_session()
        h_split = split.fit(epochs=2, batch_size=64, eval_frac=0.1,
                            verbose=False, mode="split", schedule=sched)
        assert _params_equal(joint.params, split.params), \
            f"{sched} split params diverged from the joint oracle"
        assert (h_split["final"]["val_accuracy"]
                == h_joint["final"]["val_accuracy"])
        steps_per_epoch = (len(split._train_idx) - 64) // 64 + 1
        assert split.transport_stats["steps"] == 2 * steps_per_epoch
        assert split.transport_stats["total_payload_bytes"] > 0


def test_split_fp16_stays_within_tolerance():
    joint = _mnist_session()
    joint.fit(epochs=2, batch_size=64, verbose=False)
    split = _mnist_session()
    split.fit(epochs=2, batch_size=64, verbose=False, mode="split",
              compression="fp16")
    diffs = [np.abs(np.asarray(a) - np.asarray(b)).max()
             for a, b in zip(jax.tree.leaves(joint.params),
                             jax.tree.leaves(split.params))]
    assert 0 < max(diffs) < 5e-2       # lossy but close


def test_int8_compression_cuts_measured_bytes_3x():
    base = _mnist_session()
    base.fit(epochs=1, batch_size=64, verbose=False, mode="split")
    comp = _mnist_session()
    h = comp.fit(epochs=1, batch_size=64, verbose=False, mode="split",
                 compression="int8")
    ratio = (base.transport_stats["total_payload_bytes"]
             / comp.transport_stats["total_payload_bytes"])
    assert ratio >= 3.0
    assert np.isfinite(h["final"]["loss"])


def test_measured_bytes_match_analytic_estimate():
    """The transport backend's measured per-step cut bytes equal the
    ``cut_layer_traffic`` analytic estimate for the MNIST config."""
    session = _mnist_session()
    session.fit(epochs=1, batch_size=64, verbose=False, mode="split")
    steps = session.transport_stats["steps"]
    analytic = cut_layer_traffic(
        n_owners=len(session.owners), batch=64, tokens_per_owner=1,
        cut_dim=session.adapter.model.k, bytes_per_el=4)  # f32 wire
    for owner in session.owners:
        per = session.transport_stats["per_owner"][owner.name]
        assert per["cut_payload_bytes"] == \
            analytic["per_owner_forward_bytes"] * steps
        assert per["grad_payload_bytes"] == \
            analytic["per_owner_backward_bytes"] * steps
    assert session.transport_stats["total_payload_bytes"] == \
        analytic["total_per_step_bytes"] * steps
    # the transcript now records MEASURED traffic for split sessions
    cuts = [m for m in session.transcript
            if m["kind"] == "cut_activations" and m.get("measured")]
    assert len(cuts) == len(session.owners)
    assert all(m["per_step_bytes"]
               == analytic["per_owner_forward_bytes"] for m in cuts)


def test_split_mode_guardrails():
    session = _mnist_session()
    with pytest.raises(ValueError, match="mode"):
        session.fit(epochs=1, batch_size=64, mode="telepathy")
    with pytest.raises(ValueError, match="schedule"):
        session.fit(epochs=1, batch_size=64, mode="split",
                    schedule="warp")
    with pytest.raises(ValueError, match="backend"):
        session.fit(epochs=1, batch_size=64, mode="split",
                    backend="carrier-pigeon")


def test_split_lm_training_smoke():
    """Sequence-split LM trains in split mode over the queue transport;
    loss tracks the joint path within tolerance (per-owner clipping and
    the f32 wire keep it close but not bitwise)."""
    cfg = get_config("llama3.2-3b", reduced=True)
    toks = make_token_dataset(16, 32, cfg.vocab, 0)
    split = VerticalSession(*sequence_parties(toks, cfg.split.n_owners))
    split.resolve(group="modp512")
    split.build(cfg)
    h = split.fit(steps=3, batch_size=4, verbose=False, mode="split")
    assert np.isfinite(h["final"]["loss"])
    joint = VerticalSession(*sequence_parties(toks, cfg.split.n_owners))
    joint.resolve(group="modp512")
    joint.build(cfg)
    hj = joint.fit(steps=3, batch_size=4, verbose=False)
    assert abs(h["final"]["loss"] - hj["final"]["loss"]) < 5e-2
    # the lossless codec ships the model's own cut dtype (bf16): the
    # measured bytes are the bf16 analytic estimate + the 4-byte aux
    # scalar riding along per step
    analytic = cut_layer_traffic(
        n_owners=cfg.split.n_owners, batch=4,
        tokens_per_owner=32 // cfg.split.n_owners,
        cut_dim=split.adapter.model.k, bytes_per_el=2)
    for v in split.transport_stats["per_owner"].values():
        assert v["cut_payload_bytes"] == \
            (analytic["per_owner_forward_bytes"] + 4) * 3


# ---------------------------------------------------------------------------
# transport-backed serving (measured cut bytes, not analytic)
# ---------------------------------------------------------------------------


def test_serving_through_transport_measures_cut_bytes():
    cfg = get_config("llama3.2-3b", reduced=True)
    toks = make_token_dataset(4, 16, cfg.vocab, 0)[:, :16]

    def serve(transport_backend):
        session = VerticalSession(*sequence_parties(
            toks, cfg.split.n_owners, with_labels=False))
        session.resolve(group="modp512")
        session.build(cfg)
        return session.serve_dataset(max_new=3, batch_slots=4,
                                     transport=transport_backend)

    results, engine = serve("direct")
    baseline, engine0 = serve(None)
    queued, _ = serve("queue")         # serialized wire (bf16 cut tensors)
    # identical generations through the channel vs the fused program
    for rid in results:
        assert results[rid].generated == baseline[rid].generated
        assert queued[rid].generated == baseline[rid].generated
    assert engine0.stats["cut_payload_bytes"] == 0
    st_ = engine.stats
    assert st_["cut_payload_bytes"] > 0
    assert st_["cut_wire_bytes"] >= st_["cut_payload_bytes"]
    # one wave: prefill ships P cut slices, then one per decode step
    assert st_["waves"] == 1
    assert st_["cut_messages"] == cfg.split.n_owners + (3 - 1)


# ---------------------------------------------------------------------------
# microbatch pipelining (GPipe): bit-for-bit vs the microbatched oracle
# ---------------------------------------------------------------------------


@given(st.sampled_from([2, 4]), st.integers(0, 3))
@settings(max_examples=3)
def test_microbatched_split_matches_microbatched_oracle(micro, seed):
    """fit(mode="split", microbatches=M) — M GPipe cut exchanges in
    flight per channel — reproduces the microbatched joint oracle
    (fit(mode="joint", microbatches=M)) bit-for-bit: same per-chunk
    programs, grads accumulated in chunk order at step-start params,
    one update per party per step (the ISSUE's acceptance bar)."""
    oracle = _mnist_session(320)
    h_o = oracle.fit(epochs=1, batch_size=64, eval_frac=0.1,
                     verbose=False, microbatches=micro,
                     shuffle_seed=seed)
    split = _mnist_session(320)
    h_s = split.fit(epochs=1, batch_size=64, eval_frac=0.1,
                    verbose=False, mode="split", schedule="pipelined",
                    microbatches=micro, shuffle_seed=seed)
    assert _params_equal(oracle.params, split.params), \
        f"microbatched split diverged from the oracle (M={micro})"
    assert h_s["final"]["loss"] == h_o["final"]["loss"]
    assert h_s["final"]["accuracy"] == h_o["final"]["accuracy"]
    # M chunks per step per direction on the wire
    steps = split.transport_stats["steps"]
    for per in split.transport_stats["per_owner"].values():
        # head_fwd + warmup round + M cut/grad chunks per step
        assert per["cut_payload_bytes"] > 0
    assert split.transport_stats["microbatches"] == micro


def test_microbatched_oracle_tracks_fused_joint():
    """GPipe chunk accumulation is the same math as the one-shot batch
    step — different rounding (chunked reductions), tiny param drift."""
    fused = _mnist_session(320)
    fused.fit(epochs=1, batch_size=64, verbose=False)
    oracle = _mnist_session(320)
    oracle.fit(epochs=1, batch_size=64, verbose=False, microbatches=4)
    diffs = [np.abs(np.asarray(a) - np.asarray(b)).max()
             for a, b in zip(jax.tree.leaves(fused.params),
                             jax.tree.leaves(oracle.params))]
    assert 0 < max(diffs) < 1e-4


def test_microbatch_validation():
    session = _mnist_session(320)
    with pytest.raises(ValueError, match="divide"):
        session.fit(epochs=1, batch_size=64, microbatches=3,
                    verbose=False)
    with pytest.raises(ValueError, match="pipelined"):
        session.fit(epochs=1, batch_size=64, mode="split",
                    schedule="sequential", microbatches=2, verbose=False)
    with pytest.raises(ValueError, match="microbatches"):
        session.fit(epochs=1, batch_size=64, microbatches=0,
                    verbose=False)


def test_int8_microbatched_split_trains():
    """Compression composes with microbatch pipelining: the codec sees
    per-chunk payloads and the run still converges sanely."""
    s = _mnist_session(320)
    h = s.fit(epochs=1, batch_size=64, verbose=False, mode="split",
              microbatches=2, compression="int8")
    assert np.isfinite(h["final"]["loss"])
    ratio = (s.cut_traffic(64)["total_per_step_bytes"]
             / s.transport_stats["total_payload_bytes_per_step"])
    assert ratio >= 3.0


# ---------------------------------------------------------------------------
# transport error paths
# ---------------------------------------------------------------------------


def test_bf16_payload_round_trips_over_queue_backend():
    """LM cut tensors are bf16 — the queue backend's wire frame must
    preserve the extension dtype end to end, payload-accounted at
    2 bytes/el."""
    import ml_dtypes
    a, b = transport.channel_pair("sci", "owner", backend="queue")
    x = RNG.normal(size=(6, 5, 8)).astype(ml_dtypes.bfloat16)
    a.send("cut_activations", {"x": x}, seq=3)
    m = b.recv()
    assert m.payload["x"].dtype == x.dtype
    assert m.payload_bytes == x.size * 2
    np.testing.assert_array_equal(m.payload["x"].astype(np.float32),
                                  x.astype(np.float32))


def test_protocol_desync_raises(monkeypatch):
    """An owner that ships a wrong-sequence cut chunk must fail the fit
    loudly (protocol desync), not silently misalign gradients."""
    from repro.federation.parties import OwnerComputeEndpoint

    real_ship = OwnerComputeEndpoint._ship_cut

    def corrupt(self, out, seq, kind="cut_activations"):
        if kind != "cut_activations":
            return real_ship(self, out, seq, kind)
        return real_ship(self, out, seq + 1 if seq >= 1 else seq)

    monkeypatch.setattr(OwnerComputeEndpoint, "_ship_cut", corrupt)
    session = _mnist_session(320)
    with pytest.raises(RuntimeError, match="desync"):
        session.fit(epochs=1, batch_size=64, verbose=False, mode="split")


def test_owner_thread_exception_surfaces(monkeypatch):
    """A crash on an owner's thread surfaces as the fit's RuntimeError
    (with the owner named), via the recv poll — not a 120 s timeout."""
    from repro.federation.parties import OwnerComputeEndpoint

    def boom(self, step, first_out=None):
        raise ValueError("owner-side kaboom")

    monkeypatch.setattr(OwnerComputeEndpoint, "_run_fwd", boom)
    session = _mnist_session(320)
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="owner worker"):
        session.fit(epochs=1, batch_size=64, verbose=False, mode="split")
    assert time.monotonic() - t0 < 60.0


def test_quantize_pack_kernel_matches_ref():
    """The quantize kernel plus packing emits the exact wire frame of the
    reference (int8 values bit-exact; packed f32 scales within float
    tolerance of the jnp oracle)."""
    from repro.kernels.quantize import (quantize_int8_ref,
                                        quantize_pack_int8,
                                        unpack_int8_ref)
    for shape in ((8, 64), (130, 64), (1, 128)):
        x = RNG.normal(size=shape).astype(np.float32) * 3.0
        packed = np.asarray(quantize_pack_int8(x, interpret=True))
        assert packed.shape == (shape[0], shape[1] + 4)
        assert packed.dtype == np.uint8
        q, s = unpack_int8_ref(packed)
        qr, sr = quantize_int8_ref(x)
        np.testing.assert_array_equal(q, np.asarray(qr))
        np.testing.assert_allclose(s[:, 0], np.asarray(sr)[:, 0],
                                   rtol=1e-6)
