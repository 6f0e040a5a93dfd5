"""The transport layer: what actually crosses the party boundary.

Until this module existed, "communication" in the repro was an analytic
estimate (``core.splitnn.cut_layer_traffic``) layered over one joint
autodiff program.  This module makes the boundary real: parties exchange
:class:`Message` objects over :class:`Channel` s, and everything the
session reports about traffic is *measured* from the wire.

Two backends:

  * ``direct``  — in-process handoff.  Payload pytrees move by reference
    (zero-copy, *zero host sync*: codecs pass device arrays through
    untouched, so nothing forces a device->host round-trip per step).
    This is the fast path for same-process simulation and serving.
  * ``queue``   — a simulated network.  Every payload is serialized to a
    single preallocated wire frame (``_pack``/``_unpack``), byte counts
    are taken from the actual blob, and delivery can be delayed by a
    configurable ``latency_s`` plus ``wire_bytes / bandwidth_bps``.
    Channels are thread-safe: owner compute endpoints run on their own
    threads (``federation/parties.OwnerComputeEndpoint``), so pipelined
    schedules overlap owner and scientist compute in real wall-clock.

The wire frame is one contiguous buffer: a first pass sizes the frame,
the arrays are then copied straight into a per-channel scratch buffer
(reused across sends — no per-array ``tobytes`` allocations), and the
receiver unpacks zero-copy ``np.frombuffer`` views into the immutable
blob.  Delivery deadlines are honored with a hybrid sleep+spin wait
(``SPIN_WAIT_S``): a plain ``time.sleep`` overshoots by 1-3 ms on a
shared box, which is the same order as the per-step budget the pipelined
schedule is trying to protect at LAN latencies.

Cut-payload codecs live here too (``get_codec``): the only bytes that
cross the boundary are cut activations and cut gradients, so shrinking
them is the protocol's one compression lever (Secure Forward Aggregation,
Cai et al. 2022, quantizes the same tensor).  ``fp16`` is a plain
down-cast; ``int8`` is per-row symmetric quantization in a Pallas
kernel (``repro/kernels/quantize``), packed in the same jitted program
into a single ``(rows, K+4)`` byte frame, values + bitcast scale.
"""
from __future__ import annotations

import os
import queue
import struct
import threading
import time
import zlib
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import numpy as np

from repro.federation.spans import HOST_READ, WIRE_PACK, WIRE_UNPACK, span

__all__ = ["Message", "Channel", "Endpoint", "ScopedEndpoint",
           "channel_pair", "Codec", "get_codec", "CODECS", "SPIN_WAIT_S",
           "spin_wait_s", "FrameCorrupt"]


class FrameCorrupt(RuntimeError):
    """A serialized frame failed its CRC32 integrity check.  Raised by
    the receive path of both the queue and process backends; carries the
    frame's protocol ``kind`` and ``seq`` so multiplexed receivers can
    route the failure to the session that owns the frame."""

    def __init__(self, kind: str, seq: int, sender: str, receiver: str):
        super().__init__(
            f"frame corrupt: {kind!r} seq {seq} from {sender!r} to "
            f"{receiver!r} (crc32 mismatch)")
        self.kind, self.seq = kind, seq
        self.sender, self.receiver = sender, receiver

# Hybrid-wait margin: sleep until this close to a delivery deadline, then
# spin on the monotonic clock.  ``time.sleep`` alone overshoots by the
# kernel timer slack (measured 1.5 ms mean / 3 ms p90 here), which would
# put milliseconds of scheduling noise on every simulated-latency hop.
SPIN_WAIT_S = 3e-3

#: single-core default: a long spin can't reclaim precision when the
#: sender needs the same core to make progress — it only burns the GIL
#: quantum the peer was waiting for, so CI boxes pinned to one core get
#: a much shorter spin window by default.
SPIN_WAIT_SINGLE_CORE_S = 5e-4


def _effective_cores() -> int:
    try:
        return len(os.sched_getaffinity(0))
    except (AttributeError, OSError):       # pragma: no cover - non-Linux
        return os.cpu_count() or 1


def spin_wait_s() -> float:
    """The spin-wait margin in effect: the ``REPRO_SPIN_WAIT_S`` env var
    when set to a valid non-negative float, else ``SPIN_WAIT_S``
    (``SPIN_WAIT_SINGLE_CORE_S`` on hosts with one effective core).
    Read at channel construction, so tests and deployments tune it
    without touching code."""
    raw = os.environ.get("REPRO_SPIN_WAIT_S")
    if raw is not None:
        try:
            v = float(raw)
            if v >= 0.0:
                return v
        except ValueError:
            pass
    return (SPIN_WAIT_S if _effective_cores() > 1
            else SPIN_WAIT_SINGLE_CORE_S)


def _wait_until(deadline: float, spin_s: float = SPIN_WAIT_S) -> None:
    """Block until ``time.monotonic() >= deadline`` with sub-0.1 ms
    precision: coarse sleep for the bulk, spin for the last ``spin_s``
    seconds.  The spin yields the GIL every iteration (``sleep(0)``) —
    a bare busy-loop would hold it for the interpreter's full 5 ms
    switch interval and serialize the owner threads against the
    scientist on small hosts."""
    while True:
        rem = deadline - time.monotonic()
        if rem <= 0.0:
            return
        if rem > spin_s:
            time.sleep(rem - spin_s)
        else:
            while time.monotonic() < deadline:
                time.sleep(0)
            return


# ---------------------------------------------------------------------------
# Wire format: one preallocated frame of named arrays
# ---------------------------------------------------------------------------
#
# Frame layout:  [u32 n_entries] then per entry
#   [u16 name_len][name][u16 dtype_len][dtype.name][u8 ndim][i64 dims...]
#   [i64 nbytes][raw buffer]
# ``dtype.name`` (not ``.str``) so the ml_dtypes extension types (bfloat16
# cut activations) round-trip.  The frame is sized in a first pass and the
# array buffers are copied directly into one scratch bytearray — no
# per-array ``tobytes`` allocation, no list-of-parts join.


def _frame_entries(payload: Dict[str, np.ndarray], party: str = ""):
    """Normalize payload values and precompute the exact frame size.  A
    device array is read to the host in a ``vfl.host_read`` span of
    ``party``."""
    entries = []
    size = 4
    for name, arr in payload.items():
        if (isinstance(arr, (np.ndarray, np.generic))
                or not hasattr(arr, "nbytes")):       # already on the host
            arr = np.ascontiguousarray(np.asarray(arr))
        else:                                         # a device array
            with span(HOST_READ, party=party, bytes=arr.nbytes):
                arr = np.ascontiguousarray(np.asarray(arr))
        nb, dt = name.encode(), arr.dtype.name.encode()
        size += 2 + len(nb) + 2 + len(dt) + 1 + 8 * arr.ndim + 8 + arr.nbytes
        entries.append((nb, dt, arr))
    return entries, size


def _pack_into(payload: Dict[str, np.ndarray], buf: bytearray,
               party: str = "") -> int:
    """Pack ``{name: array}`` into ``buf`` (grown as needed), returning
    the number of bytes used.  ``buf`` is reusable scratch: callers
    snapshot the used prefix before the next send.  ``party`` names the
    sender in the spans of its device reads."""
    entries, size = _frame_entries(payload, party)
    if len(buf) < size:
        buf.extend(b"\0" * (size - len(buf)))
    struct.pack_into("<I", buf, 0, len(entries))
    off = 4
    for nb, dt, arr in entries:
        struct.pack_into("<H", buf, off, len(nb))
        off += 2
        buf[off:off + len(nb)] = nb
        off += len(nb)
        struct.pack_into("<H", buf, off, len(dt))
        off += 2
        buf[off:off + len(dt)] = dt
        off += len(dt)
        struct.pack_into("<B", buf, off, arr.ndim)
        off += 1
        struct.pack_into(f"<{arr.ndim}q", buf, off, *arr.shape)
        off += 8 * arr.ndim
        struct.pack_into("<q", buf, off, arr.nbytes)
        off += 8
        # via a flat uint8 view: the ml_dtypes extension types (bf16 cut
        # activations) expose no buffer protocol of their own
        buf[off:off + arr.nbytes] = memoryview(arr.reshape(-1).view(np.uint8))
        off += arr.nbytes
    return off


def _pack(payload: Dict[str, np.ndarray]) -> bytes:
    """Serialize ``{name: array}`` to one immutable blob."""
    buf = bytearray()
    used = _pack_into(payload, buf)
    return bytes(memoryview(buf)[:used])


def _unpack(blob: bytes) -> Dict[str, np.ndarray]:
    """Inverse of ``_pack``.  The returned arrays are zero-copy
    (read-only) views into ``blob`` — the receive buffer is the blob
    itself, shared for the message's lifetime instead of re-sliced into
    per-array copies."""
    out: Dict[str, np.ndarray] = {}
    off = 0
    (n,) = struct.unpack_from("<I", blob, off)
    off += 4
    for _ in range(n):
        (ln,) = struct.unpack_from("<H", blob, off)
        off += 2
        name = blob[off:off + ln].decode()
        off += ln
        (ld,) = struct.unpack_from("<H", blob, off)
        off += 2
        dtype = np.dtype(blob[off:off + ld].decode())
        off += ld
        (ndim,) = struct.unpack_from("<B", blob, off)
        off += 1
        shape = struct.unpack_from(f"<{ndim}q", blob, off)
        off += 8 * ndim
        (nbytes,) = struct.unpack_from("<q", blob, off)
        off += 8
        count = nbytes // dtype.itemsize if dtype.itemsize else 0
        out[name] = np.frombuffer(blob, dtype=dtype, count=count,
                                  offset=off).reshape(shape)
        off += nbytes
    return out


def _payload_nbytes(payload: Dict[str, np.ndarray]) -> int:
    # jax and numpy arrays both expose .nbytes — no materialization
    return sum(getattr(a, "nbytes", None) or np.asarray(a).nbytes
               for a in payload.values())


# ---------------------------------------------------------------------------
# Messages and channels
# ---------------------------------------------------------------------------


@dataclass
class Message:
    sender: str
    receiver: str
    kind: str
    payload: Dict[str, np.ndarray]
    seq: int = 0
    payload_bytes: int = 0         # sum of array buffers (the protocol data)
    wire_bytes: int = 0            # serialized blob incl. headers (queue)
    not_before: float = 0.0        # simulated-network delivery time
    crc: Optional[int] = None      # crc32 of the blob (serialized backends)


class Channel:
    """One direction of a party boundary, with measured byte accounting.

    ``serialize=True`` (the ``queue`` backend) round-trips every payload
    through the wire format and models transit time; ``serialize=False``
    (the ``direct`` backend) hands the pytree over by reference.  Both are
    thread-safe FIFO queues, so message *order* is the protocol's
    happens-before edge (an owner applies the step-``t`` gradient before
    it sees the step-``t+1`` forward request).
    """

    def __init__(self, sender: str, receiver: str, *,
                 serialize: bool = True, latency_s: float = 0.0,
                 bandwidth_bps: Optional[float] = None,
                 spin_s: Optional[float] = None, tap=None):
        self.sender, self.receiver = sender, receiver
        self.serialize = serialize
        self.latency_s = latency_s
        self.bandwidth_bps = bandwidth_bps
        self.spin_s = spin_wait_s() if spin_s is None else spin_s
        # observation hook: tap(msg, blob) per send, with the serialized
        # frame (None on the direct backend).  The privacy-on-the-wire
        # tests capture full transcripts through this without touching
        # the send path's behavior.
        self.tap = tap
        # fault hook: fault_hook(kind, seq) -> (action, delay_s) | None,
        # installed by faults.arm_endpoint (drop/corrupt/delay)
        self.fault_hook = None
        self._q: "queue.Queue[Message]" = queue.Queue()
        self._lock = threading.Lock()
        # serializes access to the shared pack scratch: multiplexed
        # serving sessions send on one channel from several threads
        self._send_lock = threading.Lock()
        self._sendbuf = bytearray()     # reusable pack scratch
        self.stats: Dict[str, object] = {
            "messages": 0, "payload_bytes": 0, "wire_bytes": 0,
            "by_kind": {}}

    def _account(self, kind: str, payload_bytes: int, wire_bytes: int):
        with self._lock:
            st = self.stats
            st["messages"] += 1
            st["payload_bytes"] += payload_bytes
            st["wire_bytes"] += wire_bytes
            k = st["by_kind"].setdefault(
                kind, {"count": 0, "payload_bytes": 0, "wire_bytes": 0})
            k["count"] += 1
            k["payload_bytes"] += payload_bytes
            k["wire_bytes"] += wire_bytes

    def send(self, kind: str, payload: Dict[str, np.ndarray], *,
             seq: int = 0) -> Message:
        pb = _payload_nbytes(payload)
        blob = None
        crc = None
        if self.serialize:
            with span(WIRE_PACK, party=self.sender, peer=self.receiver,
                      kind=kind, seq=seq):
                with self._send_lock:
                    used = _pack_into(payload, self._sendbuf, self.sender)
                    blob = bytes(memoryview(self._sendbuf)[:used])
                crc = zlib.crc32(blob) & 0xFFFFFFFF
            wb = used
            payload = {"__blob__": blob}           # only bytes travel
        else:
            wb = pb                                # by-reference handoff
        msg = Message(self.sender, self.receiver, kind, payload, seq=seq,
                      payload_bytes=pb, wire_bytes=wb, crc=crc)
        if self.tap is not None:
            self.tap(msg, blob)
        fault = (self.fault_hook(kind, seq)
                 if self.fault_hook is not None else None)
        transit = self.latency_s + (wb / self.bandwidth_bps
                                    if self.bandwidth_bps else 0.0)
        if fault is not None and fault[0] == "delay":
            transit += fault[1]
        if transit:
            msg.not_before = time.monotonic() + transit
        self._account(kind, pb, wb)
        if fault is not None:
            action = fault[0]
            if action == "drop_frame":
                with self._lock:
                    self.stats["dropped_frames"] = self.stats.get(
                        "dropped_frames", 0) + 1
                return msg                         # lost on the wire
            if action == "corrupt_frame" and blob is not None:
                # flip one byte AFTER the crc was taken: the receiver's
                # integrity check fails loudly (FrameCorrupt)
                bad = bytearray(blob)
                bad[len(bad) // 2] ^= 0xFF
                msg.payload = {"__blob__": bytes(bad)}
        self._q.put(msg)
        return msg

    def recv(self, timeout: Optional[float] = None) -> Message:
        msg = self._q.get(timeout=timeout)
        if msg.not_before:
            _wait_until(msg.not_before, self.spin_s)
        if self.serialize:
            with span(WIRE_UNPACK, party=self.receiver, peer=self.sender,
                      kind=msg.kind, seq=msg.seq):
                blob = msg.payload["__blob__"]
                if msg.crc is not None and (
                        zlib.crc32(blob) & 0xFFFFFFFF) != msg.crc:
                    raise FrameCorrupt(msg.kind, msg.seq, self.sender,
                                       self.receiver)
                msg.payload = _unpack(blob)
        return msg

    def empty(self) -> bool:
        return self._q.empty()


class Endpoint:
    """A party's end of a duplex boundary: an outbox + an inbox channel.

    ``recv_kind`` stashes messages of other kinds instead of dropping
    them — in a pipelined schedule the next step's cut activations can
    already be in flight when the scientist waits for a barrier ack.
    The stash is lock-protected with a short-poll receive loop, so
    several multiplexed serving sessions can block in ``recv_kind`` on
    one shared endpoint concurrently: whichever thread drains a frame
    either consumes it or stashes it for the session it belongs to."""

    _POLL_S = 0.05

    def __init__(self, name: str, peer: str, outbox: Channel, inbox: Channel):
        self.name, self.peer = name, peer
        self.outbox, self.inbox = outbox, inbox
        self._stash: list = []
        # corrupt frames routed to the kind that owns them: a session
        # draining a shared endpoint must not die on another session's
        # corruption (see recv_kind)
        self._corrupt: Dict[str, FrameCorrupt] = {}
        self._rlock = threading.RLock()

    def send(self, kind: str, payload: Dict[str, np.ndarray], *,
             seq: int = 0) -> Message:
        return self.outbox.send(kind, payload, seq=seq)

    def recv(self, timeout: Optional[float] = None) -> Message:
        with self._rlock:
            if self._stash:
                return self._stash.pop(0)
        return self.inbox.recv(timeout=timeout)

    def recv_kind(self, kind: str, timeout: Optional[float] = None
                  ) -> Message:
        """Receive the next message of protocol kind ``kind``, keeping
        any earlier-arriving messages of other kinds for later.  Raises
        ``queue.Empty`` when ``timeout`` elapses first."""
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            with self._rlock:
                if kind in self._corrupt:
                    raise self._corrupt.pop(kind)
                for i, m in enumerate(self._stash):
                    if m.kind == kind:
                        return self._stash.pop(i)
                try:
                    msg = self.inbox.recv(timeout=self._POLL_S)
                except queue.Empty:
                    msg = None
                except FrameCorrupt as e:
                    if e.kind == kind:
                        raise
                    self._corrupt[e.kind] = e    # another kind's problem
                    continue
                if msg is not None:
                    if msg.kind == kind:
                        return msg
                    self._stash.append(msg)
                    continue
            if deadline is not None and time.monotonic() >= deadline:
                raise queue.Empty

    def flush_pending(self) -> None:
        """Discard every stashed out-of-kind message and routed corrupt
        marker.  The supervised fit's post-rollback drain uses this:
        FIFO order means everything a party sent *before* its
        ``rollback_ack`` is stale, and the ack was just consumed."""
        with self._rlock:
            self._stash.clear()
            self._corrupt.clear()

    @property
    def sent_stats(self) -> Dict[str, object]:
        return self.outbox.stats

    @property
    def recv_stats(self) -> Dict[str, object]:
        return self.inbox.stats


class ScopedEndpoint:
    """A kind-prefixed view of a shared endpoint — session multiplexing.

    Many serving sessions share one owner<->scientist boundary; each
    session's frames ride the same channel with the session scope
    (e.g. ``"s3:"``) prepended to the protocol kind.  Works over both
    :class:`Endpoint` and ``process_transport.ProcessEndpoint`` (the
    kind already travels in the multiplex header on the pipe), and the
    base endpoint's locked stash absorbs cross-session interleaving.
    ``sent_stats``/``recv_stats`` are the prefix-filtered slice of the
    shared totals, with the scope stripped from ``by_kind`` keys — a
    session sees exactly its own traffic."""

    def __init__(self, base, scope: str):
        self.base, self.scope = base, scope
        self.name = getattr(base, "name", "?")
        self.peer = getattr(base, "peer", "?")

    def send(self, kind: str, payload: Dict[str, np.ndarray], *,
             seq: int = 0) -> Message:
        return self.base.send(self.scope + kind, payload, seq=seq)

    def recv_kind(self, kind: str, timeout: Optional[float] = None
                  ) -> Message:
        return self.base.recv_kind(self.scope + kind, timeout)

    def empty(self) -> bool:
        return self.base.empty()

    def _filter(self, stats: Dict[str, object]) -> Dict[str, object]:
        out = {"messages": 0, "payload_bytes": 0, "wire_bytes": 0,
               "by_kind": {}}
        for k, v in stats["by_kind"].items():
            if k.startswith(self.scope):
                out["by_kind"][k[len(self.scope):]] = v
                out["messages"] += v["count"]
                out["payload_bytes"] += v["payload_bytes"]
                out["wire_bytes"] += v["wire_bytes"]
        return out

    @property
    def sent_stats(self) -> Dict[str, object]:
        return self._filter(self.base.sent_stats)

    @property
    def recv_stats(self) -> Dict[str, object]:
        return self._filter(self.base.recv_stats)


def channel_pair(a: str, b: str, *, backend: str = "queue",
                 latency_s: float = 0.0,
                 bandwidth_bps: Optional[float] = None,
                 spin_s: Optional[float] = None, tap=None
                 ) -> Tuple[Endpoint, Endpoint]:
    """Build the duplex boundary between parties ``a`` and ``b``.
    Returns ``(endpoint_a, endpoint_b)``.  ``tap`` observes every send
    on both directions (see :class:`Channel`)."""
    if backend not in ("queue", "direct"):
        raise ValueError(f"unknown transport backend {backend!r}")
    ser = backend == "queue"
    ab = Channel(a, b, serialize=ser, latency_s=latency_s,
                 bandwidth_bps=bandwidth_bps, spin_s=spin_s, tap=tap)
    ba = Channel(b, a, serialize=ser, latency_s=latency_s,
                 bandwidth_bps=bandwidth_bps, spin_s=spin_s, tap=tap)
    return Endpoint(a, b, ab, ba), Endpoint(b, a, ba, ab)


# ---------------------------------------------------------------------------
# Cut-payload codecs
# ---------------------------------------------------------------------------


class Codec:
    """Quantize-dequantize transform for cut payloads.  ``encode`` maps a
    float array to the wire payload dict; ``decode`` inverts it (lossy
    for fp16/int8).  The lossless codec preserves the model's own cut
    dtype on the wire — bf16 LM activations ship as 2 bytes/el, exactly
    what ``cut_layer_traffic`` accounts.  Encode/decode keep device
    arrays as device arrays: on the ``direct`` backend nothing here
    forces a host round-trip (serialization, when it happens, lives in
    ``Channel.send``)."""

    name = "none"

    def encode(self, arr) -> Dict[str, np.ndarray]:
        return {"x": arr}

    def decode(self, payload: Dict[str, np.ndarray]):
        return payload["x"]


class FP16Codec(Codec):
    name = "fp16"

    def encode(self, arr):
        return {"h": arr.astype(np.float16)}

    def decode(self, payload):
        return payload["h"].astype(np.float32)


class Int8Codec(Codec):
    """Per-row symmetric int8 (scale = absmax/127 over the last axis),
    quantized by a Pallas kernel and wire-packed in the same jitted
    program (``repro/kernels/quantize.quantize_pack_int8``): the payload is a
    single ``(rows, K+4)`` uint8 frame — K int8 values plus the
    little-endian f32 scale bitcast into the trailing 4 bytes of each
    row.  Decodes to float32 (consumers cast to their compute dtype)."""

    name = "int8"

    def encode(self, arr):
        from repro.kernels.quantize import quantize_pack_int8
        import jax.numpy as jnp
        a = jnp.asarray(arr).astype(jnp.float32)
        rows = a.reshape(-1, a.shape[-1])
        packed = quantize_pack_int8(rows)
        return {"qp": packed.reshape(a.shape[:-1] + (packed.shape[-1],))}

    def decode(self, payload):
        qp = np.asarray(payload["qp"])
        k = qp.shape[-1] - 4
        q = qp[..., :k].view(np.int8)
        scale = np.ascontiguousarray(qp[..., k:]).view("<f4")
        return q.astype(np.float32) * scale


CODECS = {c.name: c for c in (Codec, FP16Codec, Int8Codec)}


def get_codec(name: Optional[str]) -> Codec:
    key = name or "none"
    if key not in CODECS:
        raise ValueError(f"unknown compression {name!r}; "
                         f"known: {sorted(CODECS)}")
    return CODECS[key]()
