"""Split-inference serving: wave + continuous batching over the party
boundary, session multiplexing, and a repeat-entity cut cache.

A deployer-facing layer over ``SplitModel.prefill``/``decode_step``.
Two schedulers share one engine:

  * ``scheduler="wave"`` — the original drain-by-waves path: requests
    are admitted in waves of ``batch_slots``, prefilled together, then
    decoded in lockstep until every request in the wave hits ``max_new``
    or EOS.  One scalar decode position per wave.
  * ``scheduler="continuous"`` — slot-level admission: when a request
    hits EOS/``max_new`` its slot is freed and refilled from the queue
    on the next tick via a per-slot prefill (full-batch shaped, so the
    engine still compiles exactly two programs), and decode runs with a
    *per-slot* position vector (a ``vmap`` of the single-row decode
    step, bit-identical to the batch program — property-tested).
    Throughput tracks active slots instead of the slowest request in a
    wave; refill prefill ships share the decode ship's latency window.

Serving is the inference analogue of the paper's training protocol:
context slices stay with their owners; only cut activations reach the
scientist, who alone sees the generated text.  With a ``transport``
backend the cut tensors are real wire payloads (measured bytes,
injected latency, optional fp16/int8 codec — ``federation.transport``).

The **repeat-entity cut cache** (:class:`CutCache`) keys a request's
padded context by its sha256 content tag (the PR 5 blind-upload dedup
trick applied to serving): a returning entity's admission restores the
owner-head and trunk KV rows plus first-token logits from the cache —
zero head recompute and zero cut-upload bytes, recorded in the engine
``transcript``.  Cached rows are bitwise what a fresh prefill would
produce (prefill is row-independent), so cache hits preserve the
greedy-decode bit-identity guarantee.

**Session multiplexing** (:class:`ServingService`): many engine
sessions share one owner<->scientist channel pair, each session's
frames kind-scoped through ``transport.ScopedEndpoint`` (``"s3:"`` +
kind), with a service-wide shared cut cache.  Admission control is a
bounded queue per session (``max_queue``): ``submit`` raises
:class:`QueueFull` and counts the rejection in backpressure stats.
"""
from __future__ import annotations

import threading
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.federation import batching, transport as transport_mod
from repro.models.model import SplitModel

__all__ = ["Request", "Result", "ServingEngine", "ServingService",
           "CutCache", "QueueFull", "CUT_DECODE_KIND", "CUT_PREFILL_KIND",
           "ADMIT_KIND"]

#: protocol kinds on the serving boundary (docs/WIRE_PROTOCOL.md)
CUT_DECODE_KIND = "cut_activations"   # per-tick decode cut slices
CUT_PREFILL_KIND = "cut_prefill"      # admission-time context cut rows
ADMIT_KIND = "admit"                  # slot-layout control frame
_CUT_KINDS = (CUT_DECODE_KIND, CUT_PREFILL_KIND)


class QueueFull(RuntimeError):
    """Admission rejected: the bounded request queue is at capacity.

    Carries the backpressure signal the caller needs to do something
    smarter than blind retry: ``queue_depth`` (how deep the queue was at
    rejection) and ``retry_after_s`` (the engine's mean per-request
    service time — a principled retry interval)."""

    def __init__(self, message: str, *, queue_depth: int = 0,
                 retry_after_s: float = 0.0):
        super().__init__(message)
        self.queue_depth = queue_depth
        self.retry_after_s = retry_after_s


@dataclass
class Request:
    rid: int
    tokens: np.ndarray            # (ctx,) int32 — the combined context
    max_new: int = 16
    submit_t: float = 0.0         # wall-clock at submit (latency anchor)
    tag: Optional[str] = None     # content tag of the padded context


@dataclass
class Result:
    rid: int
    generated: List[int] = field(default_factory=list)
    latency_s: float = 0.0        # submit -> finish (queueing + compute)
    error: Optional[str] = None   # set when the request failed (degraded
    #                               service: the engine survives, the
    #                               caller sees a per-request error)


class CutCache:
    """Repeat-entity cut cache: padded-context content tag -> the
    prefill artifacts both parties would otherwise recompute and ship.

    An entry stores the owner-side head KV rows, the scientist-side
    trunk KV rows, and the first-token logits row for one request slot.
    Entries are only valid for the exact engine geometry + codec that
    stored them, so the tag is prefixed with those fields by the engine.
    LRU-bounded (``max_entries``); eviction means a returning entity
    pays one fresh prefill again — correctness is unaffected.
    Thread-safe (shared across a service's sessions)."""

    def __init__(self, max_entries: int = 256):
        self.max_entries = max_entries
        self._d: "OrderedDict[str, dict]" = OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get(self, tag: str) -> Optional[dict]:
        with self._lock:
            entry = self._d.get(tag)
            if entry is not None:
                self._d.move_to_end(tag)
                self.hits += 1
            else:
                self.misses += 1
            return entry

    def put(self, tag: str, entry: dict) -> None:
        with self._lock:
            self._d[tag] = entry
            self._d.move_to_end(tag)
            while len(self._d) > self.max_entries:
                self._d.popitem(last=False)
                self.evictions += 1

    def __len__(self) -> int:
        with self._lock:
            return len(self._d)


class ServingEngine:
    def __init__(self, model: SplitModel, params, *, batch_slots: int = 4,
                 ctx_len: int = 128, max_new: int = 32,
                 eos_token: Optional[int] = None, ring_cache: bool = False,
                 pad_token: int = 0, transport: Optional[str] = None,
                 latency_s: float = 0.0,
                 bandwidth_bps: Optional[float] = None,
                 scheduler: str = "wave",
                 compression: Optional[str] = None,
                 max_queue: Optional[int] = None,
                 cut_cache=None,
                 endpoints: Optional[Tuple] = None):
        """``transport`` ("direct" | "queue" | "process") routes every
        cut activation through a real ``federation.transport`` channel:
        prefill and decode run as separate owner/scientist segment
        programs and ``stats`` reports *measured* cut bytes off the wire
        ("process" carries the frames over a real OS pipe).

        ``scheduler`` picks wave or continuous batching (module doc);
        ``compression`` applies a cut codec ("fp16" | "int8") on the
        wire; ``max_queue`` bounds the admission queue (``submit``
        raises :class:`QueueFull` beyond it); ``cut_cache`` enables the
        repeat-entity cache (``True`` for a private one, or a shared
        :class:`CutCache`); ``endpoints`` injects a pre-built
        (owner, scientist) endpoint pair — how :class:`ServingService`
        multiplexes sessions onto one channel."""
        cfg = model.cfg
        if cfg.modality != "text":
            raise ValueError("ServingEngine drives text archs")
        if scheduler not in ("wave", "continuous"):
            raise ValueError(f"unknown scheduler {scheduler!r}")
        self.model, self.params = model, params
        self.B, self.S, self.max_new = batch_slots, ctx_len, max_new
        self.P = cfg.split.n_owners
        self.eos = eos_token
        self.pad = pad_token
        self.ring = ring_cache
        self.scheduler = scheduler
        self.max_queue = max_queue
        self._codec = transport_mod.get_codec(compression)
        self._cut_dtype = None        # model cut dtype, seen at first ship
        if cut_cache is True:
            cut_cache = CutCache()
        # explicit None-check: an *empty* CutCache is falsy (len 0)
        self.cut_cache: Optional[CutCache] = (
            cut_cache if isinstance(cut_cache, CutCache) else None)
        self._queue: List[Request] = []
        self._next_rid = 0
        self._tick = 0
        #: protocol-event log: (event, rid, detail) tuples — admissions,
        #: refills, cache hits/stores.  The bench and CI smoke assert
        #: against this (e.g. a repeat entity must log "cut_cache_hit").
        self.transcript: List[Tuple] = []
        self._prefill = jax.jit(model.prefill)
        self._decode = jax.jit(model.decode_step)
        self._vdecode = jax.jit(self._vdecode_fn)
        self._ep_owner = self._ep_sci = None
        self._owns_endpoints = False
        if endpoints is not None:
            self._ep_owner, self._ep_sci = endpoints
        elif transport is not None:
            if transport == "process":
                from repro.federation.process_transport import \
                    process_endpoint_pair
                self._ep_owner, self._ep_sci = process_endpoint_pair(
                    "owners", "scientist", latency_s=latency_s,
                    bandwidth_bps=bandwidth_bps)
            else:
                self._ep_owner, self._ep_sci = transport_mod.channel_pair(
                    "owners", "scientist", backend=transport,
                    latency_s=latency_s, bandwidth_bps=bandwidth_bps)
            self._owns_endpoints = True
        if self._ep_owner is not None:
            if cfg.enc_dec:
                raise ValueError("transport-backed serving supports "
                                 "decoder-only text archs")
            self._prefill_heads = jax.jit(model.prefill_heads)
            self._prefill_trunk = jax.jit(model.prefill_trunk)
            self._decode_heads = jax.jit(model.decode_heads)
            self._decode_trunk = jax.jit(model.decode_trunk)
            self._vdec_heads = jax.jit(self._vdec_heads_fn)
            self._vdec_trunk = jax.jit(self._vdec_trunk_fn)
        # cache-row plumbing: masked scatter for refilled slots (one
        # compile — slot choice is data, not shape) and single-row
        # gather/set for cut-cache entries.  Trunk cache leaves are
        # (n_units, B, ...) — batch axis 1; head leaves carry a leading
        # owner dim, (P, n_units, B, ...) — batch axis 2.
        self._scatter_trunk = jax.jit(lambda live, fresh, m: jax.tree.map(
            lambda a, b: jnp.where(
                m.reshape((1, -1) + (1,) * (a.ndim - 2)), b, a),
            live, fresh))
        self._scatter_heads = jax.jit(lambda live, fresh, m: jax.tree.map(
            lambda a, b: jnp.where(
                m.reshape((1, 1, -1) + (1,) * (a.ndim - 3)), b, a),
            live, fresh))
        self._get_trunk_row = jax.jit(
            lambda tc, i: jax.tree.map(lambda a: a[:, i], tc))
        self._get_heads_row = jax.jit(
            lambda hc, i: jax.tree.map(lambda a: a[:, :, i], hc))
        self._set_trunk_row = jax.jit(lambda tc, row, i: jax.tree.map(
            lambda a, r: a.at[:, i].set(r), tc, row))
        self._set_heads_row = jax.jit(lambda hc, row, i: jax.tree.map(
            lambda a, r: a.at[:, :, i].set(r), hc, row))
        self.stats = {"waves": 0, "requests": 0, "tokens_generated": 0,
                      "wall_s": 0.0, "cut_payload_bytes": 0,
                      "cut_wire_bytes": 0, "cut_messages": 0,
                      "ticks": 0, "slot_refills": 0, "prefill_calls": 0,
                      "cut_cache_hits": 0,
                      "submitted": 0, "rejected": 0,
                      "peak_queue_depth": 0, "failed_requests": 0}
        self._cut_seen = (0, 0, 0)    # consumed (payload, wire, count)

    # --------------------------------------------------- vmapped programs
    #
    # Continuous batching needs a per-slot decode position (slots are
    # admitted at different ticks).  Each program below vmaps the B=1
    # decode over the cache batch axis with per-slot position vectors;
    # the mapped axis is re-inserted inside (the transformer's KV update
    # hardcodes a (B, s, nkv, hd) cache).  The result is bit-identical
    # to the scalar-position batch program (tests/test_engine.py).

    def _vdecode_fn(self, params, caches, tok, pos, pos_local):
        def one(tc, hc, tk, p, pl):
            cs = {"heads": jax.tree.map(lambda a: a[:, :, None], hc),
                  "trunk": jax.tree.map(lambda a: a[:, None], tc)}
            l, nc = self.model.decode_step(params, cs, tk[None], p, pl)
            return (l[0],
                    jax.tree.map(lambda a: a[:, 0], nc["trunk"]),
                    jax.tree.map(lambda a: a[:, :, 0], nc["heads"]))
        return jax.vmap(one, in_axes=(1, 2, 0, 0, 0), out_axes=(0, 1, 2))(
            caches["trunk"], caches["heads"], tok, pos, pos_local)

    def _vdec_heads_fn(self, heads, hc, tok, pos_local):
        def one(hc1, tk, pl):
            h2 = jax.tree.map(lambda a: a[:, :, None], hc1)
            z, nhc = self.model.decode_heads(heads, tk[None], h2, pl)
            return z[0], jax.tree.map(lambda a: a[:, :, 0], nhc)
        return jax.vmap(one, in_axes=(2, 0, 0), out_axes=(0, 2))(
            hc, tok, pos_local)

    def _vdec_trunk_fn(self, trunk, z, tc, pos):
        def one(tc1, z1, p):
            t2 = jax.tree.map(lambda a: a[:, None], tc1)
            l, ntc = self.model.decode_trunk(trunk, z1[None], t2, p)
            return l[0], jax.tree.map(lambda a: a[:, 0], ntc)
        return jax.vmap(one, in_axes=(1, 0, 0), out_axes=(0, 1))(
            tc, z, pos)

    # ------------------------------------------------------------ admission

    def _retry_after(self) -> float:
        """Mean per-request service time — the backpressure hint shipped
        inside :class:`QueueFull` (0.05 s floor before any request has
        completed)."""
        done = self.stats["requests"]
        return (self.stats["wall_s"] / done) if done else 0.05

    def submit(self, tokens, max_new: Optional[int] = None, *,
               block: bool = False, timeout: Optional[float] = None) -> int:
        """Queue one request.  When a bounded queue is at capacity:
        ``block=False`` (default) raises :class:`QueueFull` carrying
        ``queue_depth``/``retry_after_s`` and counts the rejection in
        ``stats["rejected"]``; ``block=True`` waits (capped-backoff
        polling, at most ``timeout`` seconds, forever when ``None``) for
        another thread to drain the queue before giving up the same
        way."""
        tokens = np.asarray(tokens, np.int32)
        if len(tokens) > self.S:
            raise ValueError(f"context {len(tokens)} > engine ctx {self.S}")
        if self.max_queue is not None and len(self._queue) >= self.max_queue:
            deadline = (None if timeout is None
                        else time.monotonic() + timeout)
            pause = 0.005
            while block and len(self._queue) >= self.max_queue:
                if deadline is not None and time.monotonic() >= deadline:
                    break
                time.sleep(pause if deadline is None else
                           min(pause, max(0.0,
                                          deadline - time.monotonic())))
                pause = min(pause * 2, 0.25)
            if len(self._queue) >= self.max_queue:
                self.stats["rejected"] += 1
                raise QueueFull(
                    f"admission queue at capacity ({self.max_queue})",
                    queue_depth=len(self._queue),
                    retry_after_s=self._retry_after())
        rid = self._next_rid
        self._next_rid += 1
        self._queue.append(Request(rid, tokens,
                                   min(max_new or self.max_new,
                                       self.max_new),
                                   submit_t=time.time()))
        self.stats["submitted"] += 1
        self.stats["peak_queue_depth"] = max(
            self.stats["peak_queue_depth"], len(self._queue))
        return rid

    # ------------------------------------------------------- cut shipping

    def _encode_cut(self, arr) -> Dict[str, np.ndarray]:
        arr = np.asarray(arr)
        if self._cut_dtype is None:
            self._cut_dtype = arr.dtype
        return self._codec.encode(arr)

    def _decode_cut(self, payload) -> jnp.ndarray:
        x = jnp.asarray(self._codec.decode(payload))
        if self._codec.name != "none" and self._cut_dtype is not None:
            # lossy codecs decode to f32; restore the model's cut dtype
            # so the trunk program signature is codec-independent
            x = x.astype(self._cut_dtype)
        return x

    def _ship_cut(self, cut_arrays, kind: str = CUT_DECODE_KIND
                  ) -> jnp.ndarray:
        """Route cut activations through the owner->scientist channel
        (the measured boundary) and return the scientist-side tensor."""
        for i, c in enumerate(cut_arrays):
            self._ep_owner.send(kind, self._encode_cut(c), seq=i)
        out = [self._decode_cut(self._ep_sci.recv_kind(kind).payload)
               for _ in cut_arrays]
        return jnp.stack(out) if len(out) > 1 else out[0]

    def _drain_cut_stats(self) -> None:
        """Fold the channel's cut-kind totals into ``stats`` as
        *deltas* — the engine's numbers accumulate per-engine work even
        when the endpoint is shared or long-lived (regression-tested
        against ``recv_stats["by_kind"]``)."""
        if self._ep_sci is None:
            return
        bk = self._ep_sci.recv_stats["by_kind"]
        tot = [0, 0, 0]
        for kind in _CUT_KINDS:
            st = bk.get(kind, {})
            tot[0] += st.get("payload_bytes", 0)
            tot[1] += st.get("wire_bytes", 0)
            tot[2] += st.get("count", 0)
        seen = self._cut_seen
        self.stats["cut_payload_bytes"] += tot[0] - seen[0]
        self.stats["cut_wire_bytes"] += tot[1] - seen[1]
        self.stats["cut_messages"] += tot[2] - seen[2]
        self._cut_seen = tuple(tot)

    # ------------------------------------------------------ wave scheduler

    def _split_prefill(self, owner_tokens, caches):
        cut, head_caches = self._prefill_heads(
            self.params["heads"], owner_tokens, caches["heads"])
        self.stats["prefill_calls"] += 1
        cut = self._ship_cut([cut[p] for p in range(self.P)],
                             CUT_DECODE_KIND)
        logits, trunk_caches = self._prefill_trunk(
            self.params["trunk"], cut, caches["trunk"])
        return logits, {"heads": head_caches, "trunk": trunk_caches}

    def _split_decode(self, caches, tok, pos, pos_local):
        z, head_caches = self._decode_heads(
            self.params["heads"], tok, caches["heads"], pos_local)
        z = self._ship_cut([z])          # only the generation owner's slice
        logits, trunk_caches = self._decode_trunk(
            self.params["trunk"], z, caches["trunk"], pos)
        return logits, {"heads": head_caches, "trunk": trunk_caches}

    def _run_wave(self, wave: List[Request]) -> List[Result]:
        t0 = time.time()
        B, S = self.B, self.S
        # serving layout (federation/batching.py): left-pad for recency,
        # then the standard (P, B, S_p) sequence-slice partition
        toks = batching.pad_contexts([r.tokens for r in wave], B, S,
                                     pad=self.pad, pad_side="left")
        caches = self.model.cache_init(B, S, n_new=self.max_new + 1,
                                       ring=self.ring)
        owner_tokens = batching.serving_owner_slices(toks, self.P)
        if self._ep_owner is not None:
            logits, caches = self._split_prefill(owner_tokens, caches)
        else:
            logits, caches = self._prefill(
                self.params, {"owner_tokens": owner_tokens}, caches)
            self.stats["prefill_calls"] += 1
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)

        results = [Result(r.rid) for r in wave]
        done = np.zeros(B, bool)
        done[len(wave):] = True                      # empty slots
        for t in range(self.max_new):
            tk = np.asarray(tok[:, 0])
            appended = 0
            now = time.time()
            for i, r in enumerate(wave):
                if not done[i]:
                    results[i].generated.append(int(tk[i]))
                    appended += 1
                    if (self.eos is not None and tk[i] == self.eos) or \
                            len(results[i].generated) >= r.max_new:
                        done[i] = True
                        results[i].latency_s = now - r.submit_t
            self.stats["tokens_generated"] += appended
            if done.all() or t == self.max_new - 1:
                break
            if self._ep_owner is not None:
                logits, caches = self._split_decode(caches, tok, S + t,
                                                    S // self.P + t)
            else:
                logits, caches = self._decode(self.params, caches, tok,
                                              S + t, S // self.P + t)
            tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        now = time.time()
        for r, res in zip(wave, results):
            if res.latency_s == 0.0:     # hit the max_new ceiling
                res.latency_s = now - r.submit_t
        self.stats["waves"] += 1
        self.stats["requests"] += len(wave)
        self.stats["wall_s"] += now - t0
        self._drain_cut_stats()
        return results

    # ------------------------------------------------ continuous scheduler

    def _entity_tag(self, row: np.ndarray) -> str:
        """Cache key = content tag x everything that changes the stored
        rows bit-for-bit: geometry, codec, and which prefill program
        (fused vs transport-split) produced them."""
        path = "t" if self._ep_owner is not None else "l"
        return (f"{self.B}x{self.S}+{self.max_new}:{int(self.ring)}:"
                f"{path}:{self._codec.name}:{batching.context_tag(row)}")

    def _admit(self, free: List[int]) -> List[Tuple[int, Request, dict]]:
        """Pop up to ``len(free)`` queued requests into free slots.
        Returns [(slot, request, cache_entry_or_None)] and logs the
        admission; the caller runs the prefill/restore."""
        admitted = []
        refill = self._tick > 0
        for slot in free:
            if not self._queue:
                break
            req = self._queue.pop(0)
            row = batching.pad_context_row(req.tokens, self.S,
                                           pad=self.pad)
            req.tag = self._entity_tag(row)
            # "is not None", not truthiness: an EMPTY CutCache is falsy
            # (__len__) but must still count its misses
            entry = (self.cut_cache.get(req.tag)
                     if self.cut_cache is not None else None)
            admitted.append((slot, req, entry, row))
            event = "refill" if refill else "admit"
            self.transcript.append((event, req.rid, slot, self._tick))
            if refill:
                self.stats["slot_refills"] += 1
            if entry is not None:
                self.stats["cut_cache_hits"] += 1
                self.transcript.append(
                    ("cut_cache_hit", req.rid, req.tag[-16:]))
        return admitted

    def _refill_send(self, admitted, caches) -> Optional[dict]:
        """Owner half of an admission: fresh full-batch-shaped head
        prefill with the admitted contexts in their slot rows, cut rows
        for exactly those slots shipped, and the fresh head KV rows
        masked-scattered into the live caches (prefill is
        row-independent, so each admitted row is bitwise what a
        dedicated prefill would produce).  Cache hits skip the prefill
        for their row (all-pad filler; all-cached admissions skip it
        entirely — the control frame is the only thing on the wire).
        Called *after* the tick's decode ship is sent, so both ships
        share one injected-latency window."""
        B, S, P = self.B, self.S, self.P
        fresh_slots = [(s, r) for s, r, e, _ in admitted if e is None]
        if not fresh_slots:
            if self._ep_owner is not None and admitted:
                idx = np.asarray([s for s, _, _, _ in admitted], np.int32)
                self._ep_owner.send(ADMIT_KIND, {
                    "slots": idx, "cached": np.ones(len(idx), np.uint8)})
            return None

        ctx = np.full((B, S), self.pad, np.int32)
        for (slot, req, entry, row) in admitted:
            if entry is None:
                ctx[slot] = row
        fresh = self.model.cache_init(B, S, n_new=self.max_new + 1,
                                      ring=self.ring)
        owner_tokens = batching.serving_owner_slices(ctx, P)
        idx = np.asarray([s for s, _ in fresh_slots], np.int64)
        mask = np.zeros(B, bool)
        mask[idx] = True
        ship = {"fresh": fresh, "idx": idx, "mask": jnp.asarray(mask),
                "fresh_slots": fresh_slots}

        if self._ep_owner is not None:
            cut, fresh_hc = self._prefill_heads(
                self.params["heads"], owner_tokens, fresh["heads"])
            self.stats["prefill_calls"] += 1
            # ship only the admitted rows' cut slices; the scientist
            # scatters them into an all-zero buffer (row independence:
            # filler rows never touch admitted rows' results)
            cut_h = np.asarray(cut)
            self._ep_owner.send(ADMIT_KIND, {
                "slots": idx.astype(np.int32),
                "cached": np.zeros(len(idx), np.uint8)})
            for p in range(P):
                self._ep_owner.send(CUT_PREFILL_KIND,
                                    self._encode_cut(cut_h[p, idx]),
                                    seq=p)
            ship["cut_shape"] = cut_h.shape
            ship["cut_dtype"] = cut_h.dtype
            ship["fresh_hc"] = fresh_hc
            caches["heads"] = self._scatter_heads(
                caches["heads"], fresh_hc, ship["mask"])
        else:
            ship["owner_tokens"] = owner_tokens
        return ship

    def _refill_recv(self, ship, admitted, caches) -> Dict[int, np.ndarray]:
        """Scientist half of an admission: receive the fresh cut rows,
        trunk-prefill them, scatter the fresh trunk KV rows, restore
        cached entries' rows, store new cache entries.  Returns
        {slot: first-token logits row} for every admitted slot."""
        logits_rows: Dict[int, np.ndarray] = {}
        if ship is not None:
            idx = ship["idx"]
            if self._ep_owner is not None:
                self._ep_sci.recv_kind(ADMIT_KIND)
                buf = np.zeros(ship["cut_shape"], ship["cut_dtype"])
                for p in range(self.P):
                    got = self._decode_cut(
                        self._ep_sci.recv_kind(CUT_PREFILL_KIND).payload)
                    buf[p, idx] = np.asarray(got)
                logits, fresh_tc = self._prefill_trunk(
                    self.params["trunk"], jnp.asarray(buf),
                    ship["fresh"]["trunk"])
                fresh_hc = ship["fresh_hc"]
            else:
                logits, fresh_caches = self._prefill(
                    self.params, {"owner_tokens": ship["owner_tokens"]},
                    ship["fresh"])
                self.stats["prefill_calls"] += 1
                fresh_hc, fresh_tc = (fresh_caches["heads"],
                                      fresh_caches["trunk"])
                caches["heads"] = self._scatter_heads(
                    caches["heads"], fresh_hc, ship["mask"])
            caches["trunk"] = self._scatter_trunk(
                caches["trunk"], fresh_tc, ship["mask"])
            logits_np = np.asarray(logits)
            for slot, req in ship["fresh_slots"]:
                logits_rows[slot] = logits_np[slot]
                if self.cut_cache is not None:
                    i = jnp.int32(slot)
                    self.cut_cache.put(req.tag, {
                        "hc_row": self._get_heads_row(fresh_hc, i),
                        "tc_row": self._get_trunk_row(fresh_tc, i),
                        "logits": logits_np[slot]})
                    self.transcript.append(
                        ("cut_cache_store", req.rid, req.tag[-16:]))
        elif admitted and self._ep_owner is not None:
            self._ep_sci.recv_kind(ADMIT_KIND)

        for (slot, req, entry, row) in admitted:
            if entry is not None:
                i = jnp.int32(slot)
                caches["heads"] = self._set_heads_row(
                    caches["heads"], entry["hc_row"], i)
                caches["trunk"] = self._set_trunk_row(
                    caches["trunk"], entry["tc_row"], i)
                logits_rows[slot] = entry["logits"]
        return logits_rows

    def _fail_pending(self, exc: BaseException, out: Dict[int, "Result"],
                      slots: Optional[List[Optional[Request]]] = None,
                      results: Optional[Dict[int, "Result"]] = None
                      ) -> None:
        """Degraded service: the scheduler hit a transport/runtime fault.
        Every in-flight and queued request gets a per-request ``error``
        Result instead of the whole engine call blowing up — a serving
        deployment keeps answering its other sessions."""
        err = f"{type(exc).__name__}: {exc}"
        now = time.time()
        for req in ([r for r in (slots or []) if r is not None]
                    + self._queue):
            res = (results or {}).get(req.rid) or Result(req.rid)
            res.error = err
            res.latency_s = now - req.submit_t
            out[req.rid] = res
            self.stats["failed_requests"] += 1
        if slots is not None:
            slots[:] = [None] * len(slots)
        self._queue.clear()
        self.transcript.append(("degraded", -1, err[:120]))

    def _run_continuous(self) -> Dict[int, Result]:
        out: Dict[int, Result] = {}
        if not self._queue:
            return out
        t0 = time.time()
        B, S, P = self.B, self.S, self.P
        caches = self.model.cache_init(B, S, n_new=self.max_new + 1,
                                       ring=self.ring)
        slots: List[Optional[Request]] = [None] * B
        results: Dict[int, Result] = {}
        gen = np.zeros(B, np.int64)        # tokens appended per slot
        tok_np = np.zeros(B, np.int32)     # next token to append per slot
        self._tick = 0

        try:
            self._continuous_loop(out, caches, slots, results, gen, tok_np)
        except (QueueFull, jax.errors.JaxRuntimeError):
            raise
        except (RuntimeError, OSError) as e:
            self._fail_pending(e, out, slots, results)

        self.stats["wall_s"] += time.time() - t0
        self._drain_cut_stats()
        return out

    def _continuous_loop(self, out, caches, slots, results, gen, tok_np
                         ) -> None:
        B, S, P = self.B, self.S, self.P
        while self._queue or any(s is not None for s in slots):
            continuing = [i for i in range(B) if slots[i] is not None]
            free = [i for i in range(B) if slots[i] is None]
            admitted = self._admit(free) if self._queue else []

            # one decode tick for the continuing slots (input: the token
            # appended last tick, at its per-slot position).  The whole
            # batch decodes — freed rows carry garbage at frozen
            # positions, which row independence keeps harmless.  In
            # transport mode the decode ship and the refill's prefill
            # ship are both *sent* before either recv blocks on its
            # delivery deadline, so a refill tick pays one injected-
            # latency window, not two.
            logits_dec = None
            if continuing:
                tok = jnp.asarray(tok_np[:, None])
                pos = jnp.asarray(S + np.maximum(gen, 1) - 1, jnp.int32)
                pos_l = jnp.asarray(S // P + np.maximum(gen, 1) - 1,
                                    jnp.int32)
                if self._ep_owner is not None:
                    z, hc = self._vdec_heads(self.params["heads"],
                                             caches["heads"], tok, pos_l)
                    caches["heads"] = hc
                    self._ep_owner.send(CUT_DECODE_KIND,
                                        self._encode_cut(z))
                    ship = self._refill_send(admitted, caches) \
                        if admitted else None
                    z = self._decode_cut(
                        self._ep_sci.recv_kind(CUT_DECODE_KIND).payload)
                    logits_dec, tc = self._vdec_trunk(
                        self.params["trunk"], z, caches["trunk"], pos)
                    caches["trunk"] = tc
                else:
                    logits_dec, tc, hc = self._vdecode(
                        self.params, caches, tok, pos, pos_l)
                    caches = {"heads": hc, "trunk": tc}
                    ship = self._refill_send(admitted, caches) \
                        if admitted else None
                logits_rows = self._refill_recv(ship, admitted, caches) \
                    if admitted else {}
                logits_dec = np.asarray(logits_dec)
            else:
                ship = self._refill_send(admitted, caches) \
                    if admitted else None
                logits_rows = self._refill_recv(ship, admitted, caches) \
                    if admitted else {}

            for i in continuing:
                tok_np[i] = int(np.argmax(logits_dec[i]))
            for slot, req, entry, _ in admitted:
                slots[slot] = req
                results[req.rid] = Result(req.rid)
                gen[slot] = 0
                tok_np[slot] = int(np.argmax(logits_rows[slot]))

            # append phase: every active slot banks one token, then
            # EOS/max_new finishes free the slot for next tick's refill
            now = time.time()
            for i in range(B):
                req = slots[i]
                if req is None:
                    continue
                res = results[req.rid]
                res.generated.append(int(tok_np[i]))
                gen[i] += 1
                self.stats["tokens_generated"] += 1
                if (self.eos is not None and tok_np[i] == self.eos) or \
                        len(res.generated) >= req.max_new:
                    res.latency_s = now - req.submit_t
                    self.transcript.append(("finish", req.rid, i,
                                            self._tick))
                    out[req.rid] = res
                    self.stats["requests"] += 1
                    slots[i] = None
            self._tick += 1
            self.stats["ticks"] += 1

    # --------------------------------------------------------------- run

    def run(self) -> Dict[int, Result]:
        """Drain the queue; returns {request_id: Result}.  Requests that
        hit a transport/runtime fault mid-flight come back with
        ``Result.error`` set instead of raising (degraded service).  A
        device fault (``jax.errors.JaxRuntimeError``: out of memory, a
        failed compile) is not a per-request condition and raises."""
        if self.scheduler == "continuous":
            return self._run_continuous()
        out: Dict[int, Result] = {}
        while self._queue:
            wave, self._queue = (self._queue[:self.B], self._queue[self.B:])
            try:
                for res in self._run_wave(wave):
                    out[res.rid] = res
            except (RuntimeError, OSError) as e:
                self._queue = wave + self._queue   # wave died unserved
                if isinstance(e, jax.errors.JaxRuntimeError):
                    raise
                self._fail_pending(e, out)
        return out

    def close(self) -> None:
        """Release engine-owned transport endpoints (process pipes own a
        writer thread each).  Shared/service endpoints are untouched."""
        if self._owns_endpoints:
            for ep in (self._ep_owner, self._ep_sci):
                if ep is not None and hasattr(ep, "close"):
                    ep.close()


class ServingService:
    """One split-serving deployment: a single owner<->scientist channel
    shared by many concurrent engine sessions, plus a service-wide
    repeat-entity :class:`CutCache`.

    Each ``session()`` is a full :class:`ServingEngine` whose frames ride
    the shared channel with a ``"s{sid}:"`` kind prefix
    (``transport.ScopedEndpoint``) — the process-transport multiplex
    header and ``recv_kind``'s stash absorb cross-session interleaving,
    and per-session stats come from the prefix-filtered ``by_kind``
    totals.  Sessions may run on separate threads (channel send/recv are
    locked).  Engine defaults passed here apply to every session; the
    shared cut cache requires sessions to share geometry (the cache tag
    enforces it — mismatched sessions simply never hit)."""

    def __init__(self, model: SplitModel, params, *,
                 transport: str = "queue", latency_s: float = 0.0,
                 bandwidth_bps: Optional[float] = None,
                 cut_cache=True, cache_entries: int = 256,
                 **engine_defaults):
        self.model, self.params = model, params
        self.transport = transport
        if transport == "process":
            from repro.federation.process_transport import \
                process_endpoint_pair
            self._ep_owner, self._ep_sci = process_endpoint_pair(
                "owners", "scientist", latency_s=latency_s,
                bandwidth_bps=bandwidth_bps)
        else:
            self._ep_owner, self._ep_sci = transport_mod.channel_pair(
                "owners", "scientist", backend=transport,
                latency_s=latency_s, bandwidth_bps=bandwidth_bps)
        if cut_cache is True:
            cut_cache = CutCache(cache_entries)
        self.cut_cache = (cut_cache if isinstance(cut_cache, CutCache)
                          else None)
        self._defaults = dict(engine_defaults)
        self._defaults.setdefault("scheduler", "continuous")
        self._sid = 0
        self.sessions: List[ServingEngine] = []

    def session(self, **engine_kw) -> ServingEngine:
        """A new multiplexed serving session on the shared channel."""
        sid = self._sid
        self._sid += 1
        scope = f"s{sid}:"
        kw = {**self._defaults, **engine_kw}
        eng = ServingEngine(
            self.model, self.params, cut_cache=self.cut_cache,
            endpoints=(transport_mod.ScopedEndpoint(self._ep_owner, scope),
                       transport_mod.ScopedEndpoint(self._ep_sci, scope)),
            **kw)
        eng.sid = sid
        self.sessions.append(eng)
        return eng

    @property
    def channel_stats(self) -> Dict[str, object]:
        """The shared channel's raw (un-scoped) receive totals."""
        return self._ep_sci.recv_stats

    def close(self) -> None:
        for ep in (self._ep_owner, self._ep_sci):
            if hasattr(ep, "close"):
                ep.close()
