"""VerticalSession — the single entrypoint for every PyVertical workflow.

The paper's pipeline (Fig. 2) as a facade over the repo's machinery
(this example runs verbatim under ``make docs-check``):

```python
from repro.configs.pyvertical_mnist import CONFIG
from repro.data import make_vertical_mnist_parties
from repro.federation import VerticalSession, feature_parties

sci, owners = feature_parties(*make_vertical_mnist_parties(
    400, seed=0, keep_frac=0.9))
session = VerticalSession(sci, owners)
stats = session.resolve(group="modp512")  # DH-PSI + ID alignment
assert stats["global_intersection"] == len(session.scientist.ids)
session.build(CONFIG)                     # MLPSplitNN | SplitModel
history = session.fit(epochs=3, batch_size=64, eval_frac=0.2,
                      verbose=False)
assert history["train"][-1]["loss"] < history["train"][0]["loss"]
# (LM archs additionally serve: engine = session.serve(...))
```

``resolve`` scales to million-ID sets: ``session.resolve(group=...,
parallelism=4, chunk_size=4096)`` streams the PSI rounds in bounded
chunks through a modexp worker pool and reuses the scientist's blinded
upload across every owner (see ``repro/core/psi.py``).

Party-visibility contract (enforced, see ``tests/test_federation.py``):
owners never see labels, the scientist never receives raw feature arrays.
Every cross-party message the session mediates is appended to
``session.transcript``; during training the only owner->scientist payloads
are PSI responses and cut-layer activations (claim C4), and the only
scientist->owner payloads are blinded PSI sets, the resolved-ID broadcast,
and cut-layer gradients.

Training modes:

  * ``fit(mode="joint")`` — one jitted autodiff program per step.
  * ``fit(mode="joint", microbatches=M)`` — the *microbatched joint
    oracle*: the same GPipe math the pipelined split schedule runs
    (per-chunk grads at step-start params, accumulated in chunk order,
    one update), executed in-process through the same compiled segment
    programs.  Chunked reductions are not bitwise-identical to the
    one-shot program (XLA reduction order differs with row count), so
    this loop — not the fused program — is the bit-for-bit reference
    for microbatched split runs.
  * ``fit(mode="split", microbatches=M)`` — true split execution over
    the transport with M cut exchanges in flight per channel.
"""
from __future__ import annotations

import contextlib
import os
import queue as _queue
import threading
import time
import warnings
from collections import deque
from typing import Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import masking, privacy
from repro.core.modexp import ModexpPool
from repro.core.psi import DEFAULT_CHUNK, DEFAULT_MODE, psi_round
from repro.core.splitnn import (cut_layer_traffic, make_split_train_step,
                                train_state_init)
from repro.federation import batching, faults, spans, transport
from repro.federation.parties import (DataOwner, DataScientist,
                                      OwnerComputeEndpoint, PrivacyError)
from repro.federation.process_transport import ProcessEndpoint
from repro.federation.registry import build_adapter
from repro.federation.spans import SCIENTIST, span
from repro.federation.supervisor import OwnerFailure, Supervisor
from repro.federation.transport import FrameCorrupt


def _scalars(m):
    return {k: float(v) for k, v in m.items()}


def _read_scalars(m):
    """``_scalars`` of the split loop.  On channels that frame their
    sends, the cut gradients' fetch has already brought the metrics to
    the host with them (``_fit_split``), and nothing is read here; the
    device arrays among them (``backend="direct"``) come over in one
    ``jax.device_get``, in one ``vfl.host_read`` span."""
    dev = {k: v for k, v in m.items() if isinstance(v, jax.Array)}
    if dev:
        m = {**m, **_fetch(dev)}
    return _scalars(m)


def _fetch(tree):
    """``tree`` read to the host in one ``jax.device_get``, which starts
    every copy before it waits on any, in one ``vfl.host_read`` span of
    the scientist (``bytes``: each distinct array once)."""
    leaves = {id(a): a.nbytes for a in jax.tree.leaves(tree)}
    with span(spans.HOST_READ, party=SCIENTIST, bytes=sum(leaves.values())):
        return jax.device_get(tree)


def _device_nbytes(tree) -> int:
    """The bytes ``tree``'s host arrays take on the device, in JAX's
    canonical dtypes (int64 labels land as int32)."""
    return sum(a.size * jax.dtypes.canonicalize_dtype(a.dtype).itemsize
               for a in jax.tree.leaves(tree))


def _serializes(ep) -> bool:
    """Whether ``ep`` frames what it sends, reading every device array
    in a payload to the host: the queue backend's channels
    (``Channel.serialize``) and a process pipe do; the direct backend
    hands arrays over by reference."""
    if isinstance(ep, transport.Endpoint):
        return ep.outbox.serialize
    return isinstance(ep, ProcessEndpoint)


def _tree_add(a, b):
    return jax.tree.map(lambda x, y: x + y, a, b)


def _moe_stats(records) -> Optional[dict]:
    """The trunk's share-layer counters (``models.moe.MOE_STATS``) over
    the recorded steps, as the steps' metric parts brought them to the
    host; None where no record holds them.  ``rows_per_routed``: rows
    of the held experts' buffer (``T * min(K, H)`` a layer) over the
    token choices routed to held experts (1 = no padding); ``peak_over_mean``: the
    layers' largest held-expert loads over their mean loads."""
    recs = [r for r in records if "moe_routed" in r]
    if not recs:
        return None
    tot = {k: float(sum(r[f"moe_{k}"] for r in recs))
           for k in ("routed", "rows", "peak", "mean")}
    return {"steps": len(recs), "routed": tot["routed"],
            "rows": tot["rows"],
            "rows_per_routed": tot["rows"] / max(tot["routed"], 1.0),
            "peak_over_mean": tot["peak"] / max(tot["mean"], 1e-9)}


#: leaked-actor accounting: party threads that outlived their join
#: deadline (process-wide — a wedged actor sleeping through its stop is
#: the common producer; tests reset this between cases)
leak_stats = {"leaked_threads": 0}


def _join_or_warn(th, timeout: float, context: str) -> bool:
    """``th.join(timeout)`` that *surfaces* the leak: a party thread
    still alive after its deadline (a wedged actor mid-sleep, a stuck
    receive) gets a loud ``RuntimeWarning`` and a ``leak_stats`` bump
    instead of silently outliving the session."""
    th.join(timeout=timeout)
    if th.is_alive():
        leak_stats["leaked_threads"] += 1
        warnings.warn(
            f"{context}: thread {th.name!r} still alive after "
            f"{timeout:.1f}s join — leaked (wedged actor?)",
            RuntimeWarning, stacklevel=2)
        return False
    return True


class VerticalSession:
    """Orchestrates one scientist + N owners through resolve / build /
    fit / evaluate / serve.  The session itself is the trusted simulation
    runtime; party objects keep their raw data private."""

    def __init__(self, scientist: DataScientist,
                 owners: Union[Sequence[DataOwner], Dict[str, DataOwner]],
                 *, seed: int = 0):
        self.scientist = scientist
        self.owners: List[DataOwner] = (list(owners.values())
                                        if isinstance(owners, dict)
                                        else list(owners))
        if len({o.name for o in self.owners}) != len(self.owners):
            raise ValueError("owner names must be unique")
        if not self.owners:
            raise ValueError("need at least one data owner")
        self.seed = seed
        self.transcript: List[dict] = []
        self.resolve_stats: Optional[dict] = None
        self.transport_stats: Optional[dict] = None
        #: one entry per supervised-fit recovery / PSI round retry
        self.recovery_events: List[dict] = []
        self.adapter = None
        self.config = None
        self._init_seed = seed
        self.params = None
        self.history: Optional[dict] = None
        self._resolved = False
        self._eval_idx = np.arange(0)
        self._train_idx: Optional[np.ndarray] = None
        self._eval_fn = None

    # ------------------------------------------------------------- plumbing

    def _log(self, frm: str, to: str, kind: str, **payload):
        self.transcript.append({"from": frm, "to": to, "kind": kind,
                                **payload})

    def _owner_arrays(self) -> List[np.ndarray]:
        """Owner-side accessor: aligned per-owner feature matrices.  These
        arrays feed the jitted joint step (the simulation of owner-local
        head computation); they are never attached to the scientist."""
        return [o._features for o in self.owners]

    def _require(self, *, resolved=False, built=False, labels=False):
        if resolved and not self._resolved:
            raise RuntimeError("call session.resolve() before training — "
                               "parties are not ID-aligned yet")
        if built and self.adapter is None:
            raise RuntimeError("call session.build(config) first")
        if labels and not self.scientist.has_labels:
            raise PrivacyError("the scientist holds no labels; this "
                               "session supports inference only")

    # ------------------------------------------------------------ 1. resolve

    def resolve(self, *, group: str = "modp2048",
                fp_rate: float = 1e-9, mode: str = DEFAULT_MODE,
                parallelism: int = 0,
                chunk_size: int = DEFAULT_CHUNK,
                backend: str = "direct", latency_s: float = 0.0,
                bandwidth_bps: Optional[float] = None,
                timeout: float = 120.0, retries: int = 0,
                retry_backoff_s: float = 0.05) -> dict:
        """The paper's §3.1 protocol: the scientist runs DH-PSI pairwise
        with each owner (scientist = client, so only the scientist learns
        each intersection), intersects globally, broadcasts the shared IDs,
        and every party filter-and-sorts.  Returns the stats dict.

        ``mode`` selects the protocol variant: ``"noinv"`` (default) and
        ``"bloom"`` reveal each pairwise intersection to the scientist;
        ``"hidden"`` is the membership-hiding variant — matching runs on
        the *owner* side, the scientist receives only a padded keep-mask
        (members + deterministic decoys, indistinguishable in every
        frame), and all parties align on positional pseudonym IDs, so
        training proceeds on aligned row order without the scientist
        ever learning which raw IDs matched.  A repeat resolve after ±Δ
        ID churn (``scientist.update_rows`` / ``owner.update_rows``)
        costs O(Δ) modexp and O(Δ) wire bytes: the memoized blinded
        upload is spliced client-side and shipped as one
        ``psi_delta_chunk``, and unchanged response legs are skipped
        entirely via content tags.

        The scientist blinds its set ONCE and reuses the blinded upload
        for every owner round (logged as a ``psi_blind_reuse`` transcript
        entry from the second round on); each owner's response-side state
        (sharded Bloom or blinded own set, by ``mode``) is likewise
        per-session.  ``parallelism`` forks that many modexp workers
        shared across all owner rounds (0 = the bit-identical serial
        engine); ``chunk_size`` bounds the streamed chunks so million-ID
        sets never materialize one giant blinded batch.

        ``backend`` selects the execution engine:

          * ``"direct"`` (default) — the in-process reference engine
            (``core.psi.psi_round``): party objects exchange chunks by
            direct call, byte counts are protocol-data tallies.
          * ``"queue"`` — *wire-native* resolution: each owner runs a
            ``PSIServerEndpoint`` actor on its own thread behind a
            serialized ``federation.transport`` channel, every protocol
            leg crosses as a framed ``Message`` (pipelined, chunk k+1
            overlapping chunk k's server modexp), and the transcript +
            stats carry **measured** per-party wire bytes.  ``latency_s``
            / ``bandwidth_bps`` inject per-message transit time (wire
            backends only); ``timeout`` bounds each receive so a wedged
            owner fails the resolve instead of hanging it.
          * ``"process"`` — the same wire-native protocol with each
            owner's actor in its own *spawned worker process*
            (``federation/runtime.py``): every leg crosses a real OS
            pipe, the PSI stack's jax-free import chain keeps the
            workers numpy-light, and a crashed worker surfaces through
            its poison-pill frame or exit code.

        The intersection is bit-identical across backends, chunk sizes,
        and parallelism (property-tested).

        ``retries`` re-runs a *failed* owner round (crashed or wedged
        PSI worker) up to that many extra times with exponential backoff
        (``retry_backoff_s`` base), respawning the owner's actor at
        generation ``attempt`` so generation-0 injected faults don't
        re-fire.  The scientist's sha256-memoized blinded upload
        survives the retry, so any chunk the owner already cached ships
        zero repeat bytes (queue backend: the owner-side cache also
        survives actor re-creation)."""
        if backend not in ("direct", "queue", "process"):
            raise ValueError(f"unknown resolve backend {backend!r}")
        if backend == "direct" and (latency_s or bandwidth_bps):
            raise ValueError("latency_s/bandwidth_bps model the wire — "
                             "they require a wire backend "
                             "('queue' or 'process')")
        stats: dict = {"rounds": [], "global_intersection": 0,
                       "mode": mode, "parallelism": parallelism,
                       "chunk_size": chunk_size, "backend": backend}
        if backend != "direct":
            stats["latency_s"] = latency_s
            stats["per_party_wire"] = {}
        hidden = mode == "hidden"
        global_pos: Optional[set] = None        # hidden: keep positions
        row_maps: Dict[str, dict] = {}          # hidden: pos -> owner row
        with ModexpPool(parallelism) as pool:
            # the accessor self-syncs a cached client against the
            # scientist's current population (O(Δ) splice after churn —
            # this is what arms the wire's psi_delta_chunk fast path)
            client = self.scientist.psi_client(group, mode, pool=pool)
            global_ids = set(client.items)
            for owner in self.owners:
                for attempt in range(max(0, retries) + 1):
                    try:
                        if backend != "direct":
                            inter, rstats = self._resolve_owner_wire(
                                client, owner, backend=backend,
                                group=group, fp_rate=fp_rate, pool=pool,
                                chunk_size=chunk_size,
                                latency_s=latency_s,
                                bandwidth_bps=bandwidth_bps,
                                timeout=timeout, stats=stats,
                                generation=attempt)
                        else:
                            inter, rstats = self._resolve_owner_direct(
                                client, owner, group=group,
                                fp_rate=fp_rate, pool=pool,
                                chunk_size=chunk_size)
                        break
                    except RuntimeError as e:
                        # a crashed/wedged PSI round costs one retry:
                        # the client's blinded upload is memoized, so
                        # the rerun re-ships only what the owner never
                        # cached (0 bytes when the round died late)
                        if attempt >= retries:
                            raise
                        self._log("scientist", owner.name,
                                  "psi_round_retry", attempt=attempt + 1,
                                  error=str(e))
                        self.recovery_events.append(
                            {"party": owner.name, "action": "psi_retry",
                             "attempt": attempt + 1, "error": str(e)})
                        time.sleep(retry_backoff_s * (2 ** attempt))
                # the ENGINE's parallelism (0 when the host can't fork),
                # not the requested value — stats must not claim a pool
                # that silently degraded to serial
                stats["parallelism"] = rstats["parallelism"]
                if rstats["blind_cached"] or rstats.get("upload_skipped"):
                    # the memoized-blind reuse is protocol-relevant (it is
                    # why owner rounds 2..N are cheap) — record it
                    self._log("scientist", owner.name, "psi_blind_reuse",
                              reused_upload_bytes=
                              rstats["client_upload_bytes"],
                              recompute_skipped=rstats["blind_cached"],
                              upload_skipped=bool(
                                  rstats.get("upload_skipped", False)))
                if rstats.get("delta_used") or rstats.get("resp_skipped") \
                        or rstats.get("server_leg_skipped"):
                    # the churn fast paths (O(Δ) delta splice / cached
                    # response leg) are likewise protocol-relevant
                    self._log("scientist", owner.name, "psi_delta_reuse",
                              delta_used=bool(rstats.get("delta_used")),
                              resp_skipped=bool(
                                  rstats.get("resp_skipped")),
                              server_leg_skipped=bool(
                                  rstats.get("server_leg_skipped")))
                if hidden:
                    row_maps[owner.name] = dict(
                        zip(inter, rstats["hidden_rows"]))
                    pos = set(inter)
                    global_pos = (pos if global_pos is None
                                  else global_pos & pos)
                else:
                    global_ids &= set(inter)
                stats["rounds"].append({
                    "owner": owner.name, "intersection_size": len(inter),
                    "client_upload_bytes": rstats["client_upload_bytes"],
                    "server_response_bytes":
                        rstats["server_response_bytes"],
                    "n_chunks": rstats["n_chunks"],
                    "blind_cached": rstats["blind_cached"],
                    **({"bloom_bytes": rstats["bloom_bytes"],
                        "bloom_shards": rstats["bloom_shards"]}
                       if mode == "bloom" else
                       {"server_set_bytes": rstats["server_set_bytes"]}),
                    **({k: rstats[k] for k in
                        ("delta_used", "resp_skipped",
                         "server_leg_skipped", "client_modexp_ops",
                         "server_modexp_ops", "hidden_kept")
                        if k in rstats}),
                    **({"upload_skipped": rstats["upload_skipped"],
                        "upload_wire_bytes": rstats["upload_wire_bytes"],
                        "download_wire_bytes":
                            rstats["download_wire_bytes"]}
                       if backend != "direct" else {})})
        if hidden:
            final = sorted(global_pos or set())
            stats["global_intersection"] = len(final)
            # positional pseudonym alignment: every party keeps the
            # same aligned order; the scientist maps keep positions
            # back to its rows via the client's item order and never
            # learns which raw IDs actually matched (decoys are
            # indistinguishable in every frame it saw)
            items = list(client.items)
            for owner in self.owners:
                owner._align_hidden(
                    [row_maps[owner.name][p] for p in final])
                self._log("scientist", owner.name, "resolved_ids",
                          count=len(final))
            self.scientist._align_hidden(final, items)
        else:
            stats["global_intersection"] = len(global_ids)
            self.scientist._align(global_ids)
            for owner in self.owners:
                owner._align(global_ids)
                self._log("scientist", owner.name, "resolved_ids",
                          count=len(global_ids))
        for owner in self.owners:
            # invariant SplitNN training relies on: identical ID order
            assert owner.ids == self.scientist.ids, \
                f"misaligned owner {owner.name}"
        # every owner round succeeded: fold the delta into the new base
        # (the next churn diffs against the state all peers now cache)
        client.rebase_delta()
        self._resolved = True
        self.resolve_stats = stats
        return stats

    def _resolve_owner_direct(self, client, owner, *, group, fp_rate,
                              pool, chunk_size):
        """One in-process PSI round (the PR 4 reference engine), with
        per-kind transcript tallies from the engine's message callback."""
        server = owner.psi_server(group, fp_rate)
        wire: Dict[str, List[int]] = {}

        def tally(kind, n_bytes):
            c = wire.setdefault(kind, [0, 0])
            c[0] += 1
            c[1] += n_bytes

        inter, rstats = psi_round(client, server, pool=pool,
                                  chunk_size=chunk_size, on_message=tally)
        # one transcript entry per wire-message kind, aggregated
        # (per-chunk entries would swamp the transcript at 1e6)
        for kind, (n_msgs, n_bytes) in wire.items():
            frm, to = (("scientist", owner.name)
                       if kind in ("psi_blind_chunk", "psi_delta_chunk",
                                   "psi_lift_chunk")
                       else (owner.name, "scientist"))
            self._log(frm, to, kind, bytes=n_bytes, chunks=n_msgs)
        return inter, rstats

    def _mirror_owner_psi_caches(self, owner, client, group, fp_rate):
        """Copy a finished process-backend round's content-addressed PSI
        artifacts onto the owner, standing in for the persistent caches a
        long-lived owner process would keep (the spawned worker's died
        with it).  Entries are keyed by content tag, so a mirrored value
        can never go stale — at worst it is evicted unused.  The hidden
        response leg (``D``) is the one artifact the client never sees,
        so hidden delta on the process backend degrades to a full upload
        rather than being mirrored here."""
        from repro.core.psi import blind_tag as _btag
        key = (group, fp_rate)
        blob = client._blinded_packed
        if blob is not None:
            owner._psi_blind_caches.setdefault(key, {})[_btag(blob)] = blob
        rc = client.round_cache.get(owner.name)
        if not rc:
            return
        if "d_blob" in rc:
            owner._psi_resp_caches.setdefault(key, {})[rc["tag"]] = \
                rc["d_blob"]
        if client.mode == "hidden" and rc.get("t_blob"):
            owner._psi_lift_caches.setdefault(key, {})[rc["server_tag"]] = \
                rc["t_blob"]

    def _resolve_owner_wire(self, client, owner, *, backend, group,
                            fp_rate, pool, chunk_size, latency_s,
                            bandwidth_bps, timeout, stats,
                            generation=0):
        """One wire-native PSI round: the owner's actor on its own thread
        (``backend="queue"``) or in its own spawned process
        (``backend="process"``, ``federation/runtime.py``) behind a
        serialized channel, every leg a measured Message.  The transcript
        gets one aggregated entry per kind per direction with *measured*
        payload and wire bytes, and ``stats['per_party_wire']`` the
        owner's channel totals."""
        from repro.federation.psi_transport import wire_psi_round

        if backend == "process":
            from repro.federation import runtime
            # spawn-time own-set blinding happens on the owner's
            # persistent server (parent side); fold those ops into the
            # round's server count so backends stay comparable
            srv_parent = owner.psi_server(group, fp_rate)
            spawn_ops0 = srv_parent.ops
            handle = runtime.spawn_psi_worker(
                owner, group=group, fp_rate=fp_rate,
                latency_s=latency_s, bandwidth_bps=bandwidth_bps,
                generation=generation, pool=pool)
            try:
                ep_sci = handle.endpoint
                inter, rstats = wire_psi_round(
                    client, ep_sci, worker=handle, pool=pool,
                    chunk_size=chunk_size, timeout=timeout,
                    peer=owner.name)
            finally:
                try:
                    handle.endpoint.send("psi_stop", {})
                except RuntimeError:        # worker already gone
                    pass
                handle.shutdown()
            for k in ("server_modexp_ops", "modexp_ops"):
                rstats[k] = rstats.get(k, 0) + srv_parent.ops - spawn_ops0
            # the spawned worker's caches died with it; mirror the round's
            # content-addressed artifacts onto the (long-lived) owner so
            # the next spawn rehydrates them and repeat rounds stay O(Δ).
            # Legitimate: the session is the trusted simulation runtime,
            # and every entry is keyed by its own content tag.
            self._mirror_owner_psi_caches(owner, client, group, fp_rate)
        else:
            ep_sci, ep_own = transport.channel_pair(
                "scientist", owner.name, backend="queue",
                latency_s=latency_s, bandwidth_bps=bandwidth_bps)
            worker = owner.psi_endpoint(ep_own, group, fp_rate, pool=pool)
            # same chaos surface as the spawned workers: the env plan's
            # crash/wedge + wire faults land on the in-process actor too
            faults.arm_actor(worker, owner.name, generation=generation)
            faults.arm_endpoint(ep_own, owner.name, generation=generation)
            th = threading.Thread(target=worker.run, daemon=True,
                                  name=f"psi-{owner.name}")
            th.start()
            try:
                inter, rstats = wire_psi_round(
                    client, ep_sci, worker=worker, pool=pool,
                    chunk_size=chunk_size, timeout=timeout,
                    peer=owner.name)
            finally:
                ep_sci.send("psi_stop", {})
                _join_or_warn(th, 10.0, f"resolve({owner.name})")

        sent, rcvd = ep_sci.sent_stats, ep_sci.recv_stats
        for kind, st in sorted(sent["by_kind"].items()):
            if kind == "psi_stop":
                continue
            self._log("scientist", owner.name, kind, measured=True,
                      bytes=st["payload_bytes"],
                      wire_bytes=st["wire_bytes"], chunks=st["count"])
        for kind, st in sorted(rcvd["by_kind"].items()):
            self._log(owner.name, "scientist", kind, measured=True,
                      bytes=st["payload_bytes"],
                      wire_bytes=st["wire_bytes"], chunks=st["count"])
        stats["per_party_wire"][owner.name] = {
            "sent_wire_bytes": sent["wire_bytes"],
            "recv_wire_bytes": rcvd["wire_bytes"],
            "messages": sent["messages"] + rcvd["messages"],
        }
        # the blind upload specifically (zero when the owner had it
        # cached) — hello/stop framing lives in per_party_wire totals
        rstats["upload_wire_bytes"] = sent["by_kind"].get(
            "psi_blind_chunk", {"wire_bytes": 0})["wire_bytes"]
        rstats["download_wire_bytes"] = rcvd["wire_bytes"]
        return inter, rstats

    # -------------------------------------------------------------- 2. build

    def build(self, config, *, seed: Optional[int] = None
              ) -> "VerticalSession":
        """Instantiate the split model for ``config`` via the registry
        (``MLPSplitConfig`` -> MLPSplitNN, ``ArchConfig`` -> SplitModel)
        and initialize per-party parameters."""
        self.adapter = build_adapter(config)
        # the config + init seed are what a spawned owner worker needs to
        # rebuild its adapter/programs (federation/runtime.py)
        self.config = config
        self._init_seed = self.seed if seed is None else seed
        key = jax.random.PRNGKey(self._init_seed)
        self.params = self.adapter.init(key)
        self._eval_fn = jax.jit(
            lambda p, b: self.adapter.loss_fn(p, b)[1])
        return self

    # ---------------------------------------------------------------- 3. fit

    def fit(self, *, epochs: Optional[int] = None,
            steps: Optional[int] = None, batch_size: int = 128,
            eval_frac: float = 0.0, owner_lr: Optional[float] = None,
            scientist_lr: Optional[float] = None,
            log_every: Optional[int] = None, ckpt_dir: Optional[str] = None,
            ckpt_every: int = 0, shuffle_seed: Optional[int] = None,
            verbose: bool = True, mode: str = "joint",
            schedule: str = "pipelined", microbatches: int = 1,
            compression: Optional[str] = None, backend: str = "queue",
            latency_s: float = 0.0,
            bandwidth_bps: Optional[float] = None,
            timeout: float = 120.0, supervise: bool = False,
            max_restarts: int = 2, resync_every: int = 1,
            heartbeat_s: float = 0.5,
            aggregation: Optional[str] = None) -> dict:
        """The SplitNN training loop.

        Exactly one of ``epochs`` (feature workloads) / ``steps`` (LM
        workloads) must be given.  ``eval_frac`` holds out the last
        fraction of aligned rows; per-epoch (or final) eval metrics land
        in ``history["eval"]``.  ``ckpt_dir``+``ckpt_every`` write
        per-party checkpoints through ``repro.checkpoint.save_split``.
        Returns ``{"train": [...], "eval": [...], "final": {...}}``.

        ``mode="joint"`` (default) runs the single jitted autodiff
        program — the gradient-equivalence oracle.  With
        ``microbatches=M > 1`` the joint loop runs the *microbatched*
        oracle instead: per-chunk grads at step-start params, accumulated
        in chunk order through the same compiled segment programs the
        split schedule uses (GPipe semantics).  ``mode="split"`` runs
        *true split execution*: each owner's head segment executes on its
        own thread behind a ``federation.transport`` channel, and the
        only cross-party tensors are cut activations / cut gradients —
        measured wire bytes, not estimates (``self.transport_stats``).
        Split-mode knobs: ``schedule`` ("pipelined" overlaps owner
        compute and wire latency with the scientist's work — with
        ``microbatches=M`` every batch is split into M GPipe chunks and
        up to M cut exchanges ride the channel concurrently;
        "sequential" is the fully synchronous baseline),
        ``compression`` (None | "fp16" | "int8" cut-payload codec),
        ``backend`` ("queue" = serialized simulated network, "direct" =
        in-process reference passing, "process" = each owner in its own
        spawned worker process over a real OS pipe —
        ``federation/runtime.py``), ``latency_s``/``bandwidth_bps``
        (injected per-message transit time), ``timeout`` (seconds each
        steady-state cross-party receive may wait before a wedged or
        dead owner surfaces as a clean error on the scientist side;
        warmup receives use at least 120 s to absorb worker startup +
        compile).

        ``supervise=True`` (split mode, wire backends) turns on the
        crash-recovery protocol: every ``resync_every`` steps the
        scientist ships a ``snapshot`` marker — each owner keeps a host
        copy of its step-start params/optimizer state and acks the
        leaves back — and a ``federation.supervisor.Supervisor`` runs
        heartbeat liveness probes alongside the step loop.  When an
        owner crashes, wedges past ``timeout``, or a frame fails its
        CRC, the session rolls every survivor back to the newest marker
        the failed party acked, respawns the dead owner from its
        snapshotted leaves (bounded exponential backoff, at most
        ``max_restarts`` per party), replays the in-flight steps from
        the cached batch-index log, and continues — the final params
        are bit-identical to the fault-free run (property-tested; the
        zero-grad recovery warmup is a bitwise no-op for SGD-family
        owner optimizers, the paper's case).  Each recovery appends to
        ``session.recovery_events``.

        ``aggregation="masked_sum"`` turns on secure forward
        aggregation (Cai et al., ``core/masking.py``): each owner ships
        its cut quantized + ring-masked with pairwise-cancelling masks
        (root seed over the ``REPRO_MASK_SEED`` env channel), so the
        scientist reconstructs only the owner SUM — no per-owner
        activation ever crosses the wire.  Requires an adapter with
        ``combine="sum"`` and >= 2 owners.  ``mode="joint"`` with
        masked_sum runs the *masked joint oracle* — the identical
        quantize -> ring-sum -> dequantize combine without masks —
        which split masked execution reproduces bit-for-bit (masks
        cancel exactly in the integer ring; property-tested)."""
        self._require(resolved=True, built=True, labels=True)
        if (epochs is None) == (steps is None):
            raise ValueError("pass exactly one of epochs= or steps=")
        if mode not in ("joint", "split"):
            raise ValueError(f"mode must be 'joint' or 'split': {mode!r}")
        microbatches = int(microbatches)
        if microbatches < 1:
            raise ValueError(f"microbatches must be >= 1: {microbatches}")
        if microbatches > 1:
            if batch_size % microbatches:
                raise ValueError(
                    f"microbatches={microbatches} must divide "
                    f"batch_size={batch_size}")
            if not getattr(self.adapter, "supports_microbatch", False):
                raise ValueError(
                    f"{type(self.adapter).__name__} does not support "
                    "microbatched training")
        if aggregation not in (None, "masked_sum"):
            raise ValueError(f"unknown aggregation {aggregation!r} "
                             "(None | 'masked_sum')")
        if aggregation == "masked_sum":
            if not getattr(self.adapter, "supports_masked", False):
                raise ValueError(
                    f"{type(self.adapter).__name__} does not support "
                    "masked_sum aggregation (needs combine='sum')")
            if len(self.owners) < 2:
                raise ValueError(
                    "masked_sum needs >= 2 owners: a single owner's "
                    "masked payload would expose its activations")
        if supervise:
            if mode != "split":
                raise ValueError("supervise=True requires mode='split' "
                                 "(recovery is a wire protocol)")
            if backend == "direct":
                raise ValueError("supervise=True requires a wire "
                                 "backend ('queue' or 'process')")
            if int(resync_every) < 1:
                raise ValueError(
                    f"resync_every must be >= 1: {resync_every}")
        if mode == "split":
            return self._fit_split(
                epochs=epochs, steps=steps, batch_size=batch_size,
                eval_frac=eval_frac, owner_lr=owner_lr,
                scientist_lr=scientist_lr, log_every=log_every,
                ckpt_dir=ckpt_dir, ckpt_every=ckpt_every,
                shuffle_seed=shuffle_seed, verbose=verbose,
                schedule=schedule, microbatches=microbatches,
                compression=compression, backend=backend,
                latency_s=latency_s, bandwidth_bps=bandwidth_bps,
                timeout=timeout, supervise=supervise,
                max_restarts=max_restarts,
                resync_every=int(resync_every),
                heartbeat_s=heartbeat_s, aggregation=aggregation)
        if microbatches > 1 or aggregation is not None:
            # the masked joint oracle runs through the microbatched
            # loop even at M=1: its quantize->ring-sum->dequantize
            # combine is what split masked execution reproduces
            return self._fit_joint_microbatched(
                epochs=epochs, steps=steps, batch_size=batch_size,
                eval_frac=eval_frac, owner_lr=owner_lr,
                scientist_lr=scientist_lr, log_every=log_every,
                ckpt_dir=ckpt_dir, ckpt_every=ckpt_every,
                shuffle_seed=shuffle_seed, verbose=verbose,
                microbatches=microbatches, aggregation=aggregation)

        n = len(self.scientist.ids)
        n_train = n - int(n * eval_frac)
        if n_train < batch_size:
            raise ValueError(f"{n_train} train rows < batch {batch_size}")
        self._train_idx = np.arange(n_train)
        self._eval_idx = np.arange(n_train, n)

        adapter = self.adapter
        opt = adapter.default_optimizer(owner_lr, scientist_lr)
        state = train_state_init(self.params, opt)
        # donate=True: the joint step consumes its param/state buffers in
        # place — the allocation-free hot loop the core API was built for
        step_fn = make_split_train_step(adapter.loss_fn, opt, donate=True)

        # the per-step protocol traffic, recorded once (static shapes)
        for owner in self.owners:
            shape = adapter.cut_shape(batch_size, owner.feature_shape)
            self._log(owner.name, "scientist", "cut_activations",
                      shape=shape, width=shape[-1], per_step=True)
            self._log("scientist", owner.name, "cut_gradients",
                      shape=shape, per_step=True)

        owner_arrays = self._owner_arrays()
        labels = self.scientist.labels
        rng = np.random.default_rng(self.seed if shuffle_seed is None
                                    else shuffle_seed)
        history: dict = {"train": [], "eval": []}
        t0 = time.time()
        metrics = {}

        stream = self._index_stream(rng, n_train, batch_size, epochs, steps)
        if epochs is not None:
            steps_per_epoch = (n_train - batch_size) // batch_size + 1
            global_step = 0
            for ep in range(epochs):
                for _ in range(steps_per_epoch):
                    batch = adapter.make_batch(
                        owner_arrays, labels, next(stream))
                    self.params, state, metrics = step_fn(
                        self.params, state, batch, global_step)
                    global_step += 1
                rec = {"epoch": ep, **_scalars(metrics)}
                history["train"].append(rec)
                if len(self._eval_idx):
                    history["eval"].append(
                        {"epoch": ep, **self.evaluate()})
                if verbose and (ep % (log_every or 1) == 0
                                or ep == epochs - 1):
                    ev = history["eval"][-1] if history["eval"] else {}
                    extra = "".join(f" val_{k}={v:.4f}"
                                    for k, v in ev.items() if k != "epoch")
                    print(f"epoch {ep:3d} " + " ".join(
                        f"{k}={v:.4f}" for k, v in rec.items()
                        if k != "epoch") + extra +
                        f" ({time.time() - t0:.1f}s)")
                if ckpt_dir and ckpt_every and (ep + 1) % ckpt_every == 0:
                    self.checkpoint(ckpt_dir, ep + 1)
        else:
            for i in range(steps):
                batch = adapter.make_batch(owner_arrays, labels,
                                           next(stream))
                self.params, state, metrics = step_fn(
                    self.params, state, batch, i)
                rec = {"step": i, **_scalars(metrics)}
                history["train"].append(rec)
                if verbose and log_every and (i % log_every == 0
                                              or i == steps - 1):
                    print(f"step {i:5d} " + " ".join(
                        f"{k}={v:.4f}" for k, v in rec.items()
                        if k != "step") + f" ({time.time() - t0:.1f}s)")
                if ckpt_dir and ckpt_every and (i + 1) % ckpt_every == 0:
                    self.checkpoint(ckpt_dir, i + 1)
            if len(self._eval_idx):
                history["eval"].append({"step": steps, **self.evaluate()})

        final = dict(history["train"][-1]) if history["train"] else {}
        if history["eval"]:
            final.update({f"val_{k}": v
                          for k, v in history["eval"][-1].items()
                          if k not in ("epoch", "step")})
        history["final"] = final
        self.history = history
        return history

    def _index_stream(self, rng, n_train, batch_size, epochs, steps):
        """The batch-index stream — ONE generator shared by the joint
        and split training loops, so both consume the shuffle rng
        identically (split-mode gradient equivalence is bit-for-bit
        against the joint path and depends on this).  epochs-mode:
        a fresh permutation per epoch, full batches only; steps-mode:
        reshuffle whenever the remaining tail can't fill a batch."""
        if epochs is not None:
            for _ in range(epochs):
                order = rng.permutation(self._train_idx)
                for s in range(0, n_train - batch_size + 1, batch_size):
                    yield order[s:s + batch_size]
        else:
            order = rng.permutation(self._train_idx)
            cursor = 0
            for _ in range(steps):
                if cursor + batch_size > n_train:
                    order = rng.permutation(self._train_idx)
                    cursor = 0
                yield order[cursor:cursor + batch_size]
                cursor += batch_size

    def _train_bookkeeping(self, t, metrics, history, t0, *, epochs,
                           steps, steps_per_epoch, log_every, verbose,
                           ckpt_dir, ckpt_every, sync, scalars=_scalars):
        """Per-step history/eval/print/checkpoint — shared by the
        microbatched joint oracle and the split loop.  ``sync`` makes
        ``self.params`` current (a transport barrier + reassembly for
        the split loop, a local reassembly for the oracle) before any
        eval or checkpoint touches them; ``scalars`` reads the metrics
        to the host.  ``t0`` is a ``time.perf_counter()`` reading."""
        if epochs is not None:
            if (t + 1) % steps_per_epoch:
                return
            ep_i = (t + 1) // steps_per_epoch - 1
            rec = {"epoch": ep_i, **scalars(metrics)}
            history["train"].append(rec)
            if len(self._eval_idx):
                sync()
                history["eval"].append(
                    {"epoch": ep_i, **self.evaluate()})
            if verbose and (ep_i % (log_every or 1) == 0
                            or ep_i == epochs - 1):
                ev = history["eval"][-1] if history["eval"] else {}
                extra = "".join(f" val_{k}={v:.4f}"
                                for k, v in ev.items() if k != "epoch")
                print(f"epoch {ep_i:3d} " + " ".join(
                    f"{k}={v:.4f}" for k, v in rec.items()
                    if k != "epoch") + extra +
                    f" ({time.perf_counter() - t0:.1f}s)")
            if ckpt_dir and ckpt_every and (ep_i + 1) % ckpt_every == 0:
                sync()
                self.checkpoint(ckpt_dir, ep_i + 1)
        else:
            rec = {"step": t, **scalars(metrics)}
            history["train"].append(rec)
            if verbose and log_every and (t % log_every == 0
                                          or t == steps - 1):
                print(f"step {t:5d} " + " ".join(
                    f"{k}={v:.4f}" for k, v in rec.items()
                    if k != "step")
                    + f" ({time.perf_counter() - t0:.1f}s)")
            if ckpt_dir and ckpt_every and (t + 1) % ckpt_every == 0:
                sync()
                self.checkpoint(ckpt_dir, t + 1)

    # ------------------------------------- 3a. microbatched joint oracle

    def _fit_joint_microbatched(self, *, epochs, steps, batch_size,
                                eval_frac, owner_lr, scientist_lr,
                                log_every, ckpt_dir, ckpt_every,
                                shuffle_seed, verbose, microbatches,
                                aggregation=None) -> dict:
        """The GPipe reference loop: per-microbatch segment programs,
        grads accumulated in chunk order at step-start params, one
        optimizer update per party per step.  Runs the SAME compiled
        programs (adapter-cached) as ``fit(mode="split",
        microbatches=M)`` in the same order — the bit-for-bit oracle for
        microbatched split execution.

        With ``aggregation="masked_sum"`` this loop is the *masked
        joint oracle*: cuts are quantized through the adapter's quant
        program, host-ring-summed (``masking.fold_quantized`` — exact
        integer addition, bitwise the wire fold once masks cancel), and
        the masked trunk programs consume the int32 sum; every owner's
        head backward receives the same broadcast ``dL/dz``."""
        adapter = self.adapter
        M = microbatches
        bm = batch_size // M
        n = len(self.scientist.ids)
        n_train = n - int(n * eval_frac)
        if n_train < batch_size:
            raise ValueError(f"{n_train} train rows < batch {batch_size}")
        self._train_idx = np.arange(n_train)
        self._eval_idx = np.arange(n_train, n)

        P = len(self.owners)
        head_progs = [adapter.owner_programs(p) for p in range(P)]
        gather = adapter.gather_program()
        feats = [jnp.asarray(o._features) for o in self.owners]
        owner_opt, owner_update = adapter.owner_update_rule(owner_lr)
        slices = [adapter.owner_param_slice(self.params, p)
                  for p in range(P)]
        ostates = [owner_opt.init(s) for s in slices]
        trunk_opt, trunk_update = adapter.trunk_update_rule(scientist_lr)
        masked = aggregation == "masked_sum"
        if masked:
            quant = adapter.quant_program()
            cutgrad, weightgrad = \
                adapter.masked_trunk_microbatch_programs()
        else:
            cutgrad, weightgrad = adapter.trunk_microbatch_programs()
        tp = self.params["trunk"]
        ts = trunk_opt.init(tp)
        denom = jnp.asarray(float(batch_size), jnp.float32)
        inv_micro = jnp.asarray(1.0 / M, jnp.float32)

        labels = self.scientist.labels
        rng = np.random.default_rng(self.seed if shuffle_seed is None
                                    else shuffle_seed)
        stream = self._index_stream(rng, n_train, batch_size, epochs, steps)
        if epochs is not None:
            steps_per_epoch = (n_train - batch_size) // batch_size + 1
            total_steps = epochs * steps_per_epoch
        else:
            steps_per_epoch = None
            total_steps = steps

        def reassemble():
            self.params = {"heads": adapter.stack_head_params(slices),
                           "trunk": tp}

        history: dict = {"train": [], "eval": []}
        t0 = time.perf_counter()
        metrics: dict = {}

        for t in range(total_steps):
            idx = next(stream)
            lab_full = labels[idx]
            idx_dev = jnp.asarray(np.asarray(idx, np.int32))
            xs = [gather(f, idx_dev) for f in feats]
            chunks = [[x[m * bm:(m + 1) * bm] for m in range(M)]
                      for x in xs]
            parts_list = []
            owner_aux = 0.0
            hg_acc: List[Optional[object]] = [None] * P
            cut_cache = []
            for m in range(M):
                cuts = []
                for p in range(P):
                    out = head_progs[p][0](slices[p], chunks[p][m])
                    cut, aux = (out if isinstance(out, tuple)
                                else (out, None))
                    cuts.append(cut)
                    if aux is not None:
                        # identical f32 round-trip as the wire's aux
                        owner_aux += float(
                            np.float32(np.asarray(aux).sum()))
                lab_m = jnp.asarray(lab_full[m * bm:(m + 1) * bm])
                if masked:
                    # the oracle combine: quantize each owner's cut,
                    # host-ring-sum (no masks — they'd cancel anyway),
                    # feed the masked trunk program the int32 sum.  The
                    # broadcast z-grad is every owner's cut gradient.
                    zsum = jnp.asarray(masking.fold_quantized(
                        [np.asarray(quant(c)) for c in cuts]))
                    zg, parts = cutgrad(tp, zsum, lab_m, denom,
                                        inv_micro)
                    cg = [zg] * P
                    cached = zsum
                else:
                    cached = cuts = tuple(cuts)
                    cg, parts = cutgrad(tp, cuts, lab_m, denom,
                                        inv_micro)
                parts_list.append(parts)
                for p in range(P):
                    hg = head_progs[p][1](slices[p], chunks[p][m], cg[p])
                    hg_acc[p] = hg if hg_acc[p] is None else \
                        _tree_add(hg_acc[p], hg)
                cut_cache.append((cached, lab_m))
            for p in range(P):
                slices[p], ostates[p] = owner_update(
                    slices[p], ostates[p], hg_acc[p], t)
            tg_acc = None
            for cuts, lab_m in cut_cache:
                tg = weightgrad(tp, cuts, lab_m, denom, inv_micro)
                tg_acc = tg if tg_acc is None else _tree_add(tg_acc, tg)
            tp, ts = trunk_update(tp, ts, tg_acc, t)
            parts_acc = parts_list[0]
            for parts in parts_list[1:]:
                parts_acc = {k: parts_acc[k] + parts[k] for k in parts}
            metrics = dict(parts_acc)
            if owner_aux and "aux" in metrics:
                metrics = {**metrics, "aux": metrics["aux"] + owner_aux}

            self._train_bookkeeping(
                t, metrics, history, t0, epochs=epochs, steps=steps,
                steps_per_epoch=steps_per_epoch, log_every=log_every,
                verbose=verbose, ckpt_dir=ckpt_dir,
                ckpt_every=ckpt_every, sync=reassemble)

        reassemble()
        if steps is not None and len(self._eval_idx):
            history["eval"].append({"step": steps, **self.evaluate()})

        final = dict(history["train"][-1]) if history["train"] else {}
        if history["eval"]:
            final.update({f"val_{k}": v
                          for k, v in history["eval"][-1].items()
                          if k not in ("epoch", "step")})
        history["final"] = final
        self.history = history
        return history

    # ------------------------------------------------- 3b. split execution

    def _recv_from_owner(self, ep, worker, kind, timeout: float = 120.0):
        """Receive ``kind`` from one owner, surfacing a dead worker
        immediately (short poll) instead of after the full timeout.
        Process-backed workers can also fail *through* the receive — a
        poison-pill frame or a severed pipe raises out of ``recv_kind``
        — and get wrapped in the same owner-attributed error.  Failures
        raise :class:`~repro.federation.supervisor.OwnerFailure` (a
        ``RuntimeError`` carrying ``.party``), so the supervised fit
        knows whom to restart; message strings are unchanged."""
        deadline = time.monotonic() + timeout
        while True:
            try:
                return ep.recv_kind(kind, timeout=1.0)
            except _queue.Empty:
                if worker.error is not None:
                    raise OwnerFailure(
                        f"owner worker {worker.owner.name!r} failed",
                        party=worker.owner.name) from worker.error
                if time.monotonic() > deadline:
                    raise OwnerFailure(
                        f"timed out waiting for {kind!r} from "
                        f"{worker.owner.name!r}",
                        party=worker.owner.name)
            except Exception:
                if getattr(worker, "error", None) is not None:
                    raise OwnerFailure(
                        f"owner worker {worker.owner.name!r} failed",
                        party=worker.owner.name) from worker.error
                raise

    def _sync_split_params(self, workers, eps, trunk_params,
                           timeout: float = 120.0):
        """Flush every owner's message queue (barrier), then reassemble
        the session-resident param tree from the owners' live segments —
        the trusted-runtime accessor, mirroring ``_owner_arrays``.
        Thread-backed owners expose their params directly; process-backed
        owners answer a ``pull_params`` request with their numbered
        numpy leaves, rebuilt here against the session's tree
        structure."""
        for ep in eps:
            ep.send("barrier", {}, seq=-1)
        for ep, w in zip(eps, workers):
            self._recv_from_owner(ep, w, "barrier_ack", timeout=timeout)
        head_slices = []
        for p, (ep, w) in enumerate(zip(eps, workers)):
            if hasattr(w, "params"):            # in-process actor
                head_slices.append(w.params)
                continue
            ep.send("pull_params", {}, seq=-1)
            m = self._recv_from_owner(ep, w, "params_dump",
                                      timeout=timeout)
            structure = jax.tree_util.tree_structure(
                self.adapter.owner_param_slice(self.params, p))
            head_slices.append(jax.tree_util.tree_unflatten(
                structure, [jnp.asarray(m.payload[str(i)])
                            for i in range(len(m.payload))]))
        self.params = {
            "heads": self.adapter.stack_head_params(head_slices),
            "trunk": trunk_params}

    def _fit_split(self, *, epochs, steps, batch_size, eval_frac, owner_lr,
                   scientist_lr, log_every, ckpt_dir, ckpt_every,
                   shuffle_seed, verbose, schedule, microbatches,
                   compression, backend, latency_s, bandwidth_bps,
                   timeout=120.0, supervise=False, max_restarts=2,
                   resync_every=1, heartbeat_s=0.5,
                   aggregation=None) -> dict:
        """True split execution over the transport layer (paper Fig. 2).

        Per step t the wire carries exactly four message kinds:
        ``head_fwd`` (batch row indices; arrow 4 "compute forward"),
        ``cut_activations`` (arrow 5), ``cut_gradients`` (arrow 7), and
        — in the sequential schedule only — ``step_done`` acks.  The
        pipelined schedule ships the step-t+1 forward request *before*
        step t's gradients and the gradients before the trunk update, so
        the owners' backward+forward for t/t+1 overlap the scientist's
        optimizer step; with ``microbatches=M`` the batch is split into
        M GPipe chunks, each chunk's cut gradient leaves the moment its
        cut activations arrive, and the trunk's weight gradients +
        update run *inside the wire's round-trip window* — only one
        chunk of owner-edge and trunk-cutgrad compute remains on the
        latency-critical path.  FIFO order keeps the math identical
        (owners accumulate every chunk gradient at step-start params and
        update exactly once per step).  An explicit warmup round
        compiles every program on both sides before the timed region.

        Each chunk crosses the scientist's host-device boundary once in
        each direction.  In: right after its cuts arrive, the decoded
        cuts (or the masked path's reconstructed sum) and the chunk's
        labels go to the device in one ``jax.device_put``
        (``vfl.host_stage``); ``cutgrad`` and ``weightgrad`` both take
        those device arrays.  Out: on channels that frame their sends
        (``Channel.serialize``, and process pipes), framing would read
        every cut gradient to the host one by one anyway, so after
        ``cutgrad`` one ``jax.device_get`` fetches the encoded
        cut-gradient payloads together with the chunk's metric parts
        (``_fetch``), and the sends and the bookkeeping read nothing.
        On the direct backend the payloads stay device arrays, handed
        over by reference, and the metrics are read in one batched
        fetch in the bookkeeping (``_read_scalars``).  The transfers
        change only in their grouping and moment: the programs, their
        inputs' bits and the wire's bytes are the same.

        With the lossless codec, both schedules reproduce the joint
        program bit-for-bit whenever the adapter's head optimizer is
        elementwise-separable across owners (the paper's MLP/SGD case —
        property-tested); microbatched runs reproduce the microbatched
        joint oracle (``fit(mode="joint", microbatches=M)``) the same
        way.  The LM adapter clips grads per-owner instead of across all
        heads, so it tracks the joint path within tolerance rather than
        exactly."""
        # ``vfl.fit_start`` runs from here through owner spawn and the
        # warm-up handshake; ``vfl.fit_end`` from the last step through
        # the owners' stop and join
        phase = contextlib.ExitStack()
        phase.enter_context(span(spans.FIT_START, party=SCIENTIST))
        adapter = self.adapter
        if not getattr(adapter, "supports_split", False):
            raise ValueError(f"{type(adapter).__name__} does not support "
                             "split execution")
        if backend not in ("queue", "direct", "process"):
            raise ValueError(f"unknown fit backend {backend!r}")
        if schedule not in ("pipelined", "sequential"):
            raise ValueError(f"unknown schedule {schedule!r}")
        sequential = schedule == "sequential"
        M = microbatches
        if sequential and M > 1:
            raise ValueError("microbatches > 1 requires the pipelined "
                             "schedule (sequential is the synchronous "
                             "baseline)")
        bm = batch_size // M
        codec = transport.get_codec(compression)

        n = len(self.scientist.ids)
        n_train = n - int(n * eval_frac)
        if n_train < batch_size:
            raise ValueError(f"{n_train} train rows < batch {batch_size}")
        self._train_idx = np.arange(n_train)
        self._eval_idx = np.arange(n_train, n)

        trunk_opt, trunk_update = adapter.trunk_update_rule(scientist_lr)
        trunk_params = self.params["trunk"]
        trunk_state = trunk_opt.init(trunk_params)
        # Pipelined: the decomposed trunk programs serve every M (M == 1
        # is a single whole-batch chunk) — cut grads on the
        # latency-critical path, weight grads + update in the wire's
        # shadow.  The decomposition is bitwise-identical to the fused
        # trunk step (property-tested), so the M == 1 joint-oracle
        # equivalence is unchanged.  Sequential: the fused one-pass
        # program — recompute-based decomposition would double trunk
        # work with no wire window to hide it in, overstating the
        # baseline this schedule exists to provide.
        masked = aggregation == "masked_sum"
        if sequential:
            trunk_step = (adapter.masked_trunk_program() if masked
                          else adapter.trunk_program())
            cutgrad = weightgrad = None
        else:
            cutgrad, weightgrad = (
                adapter.masked_trunk_microbatch_programs() if masked
                else adapter.trunk_microbatch_programs())
            trunk_step = None
        denom = jnp.asarray(float(batch_size), jnp.float32)
        inv_micro = jnp.asarray(1.0 / M, jnp.float32)

        # secure aggregation key agreement: the mask root travels the
        # env channel so spawned owner workers (which inherit the
        # parent's environment) and in-process actors derive the same
        # pairwise streams.  Respect a caller-set value (the deployment
        # secret); otherwise publish the session default for the run
        # and restore on exit.
        mask_env_set = False
        if masked and not os.environ.get(masking.MASK_ENV, ""):
            os.environ[masking.MASK_ENV] = str(self._init_seed)
            mask_env_set = True
        mask_root = masking.mask_root_from_env(self._init_seed)

        # gradient-side label-leakage defences (SplitConfig): applied
        # to every cut-gradient chunk before it ships — deterministic
        # per (seed, seq, owner), so supervised replay after a recovery
        # re-derives bitwise-identical defended gradients
        sp_cfg = self.config.split
        defend_on = (sp_cfg.grad_noise_std > 0.0
                     or sp_cfg.grad_norm_mode != "none")

        def defend(g, seq, p):
            if not defend_on:
                return g
            return privacy.obfuscate_cut_gradient(
                np.asarray(g), noise_std=sp_cfg.grad_noise_std,
                norm_mode=sp_cfg.grad_norm_mode, seed=self._init_seed,
                tag=f"g{seq}o{p}")

        owner_opt, owner_update = adapter.owner_update_rule(owner_lr)
        workers, eps, threads = [], [], []

        def spawn_proc(p, *, param_leaves, opt_state_leaves=None,
                       start_step=0, generation=0):
            # one spawned worker process per owner (federation/
            # runtime.py): the spec carries the model config + the
            # owner's param leaves (and, on respawn, its snapshotted
            # optimizer state + resume step), and the worker rebuilds
            # the exact OwnerComputeEndpoint the thread path constructs
            from repro.federation import runtime
            owner = self.owners[p]
            spec = runtime.OwnerWorkerSpec(
                name=owner.name, ids=list(owner.ids),
                features=np.asarray(owner._features),
                owner_index=p, config=self.config,
                init_seed=self._init_seed,
                param_leaves=param_leaves,
                codec=compression, microbatches=M,
                ack_steps=sequential, owner_lr=owner_lr,
                latency_s=latency_s, bandwidth_bps=bandwidth_bps,
                opt_state_leaves=opt_state_leaves,
                start_step=start_step, generation=generation,
                aggregation=aggregation, n_owners=len(self.owners),
                cut_noise_std=sp_cfg.cut_noise_std)
            return runtime.spawn_owner_worker(spec, owner=owner)

        def spawn_thread(p, *, params, opt_state=None, start_step=0,
                         generation=0):
            owner = self.owners[p]
            ep_sci, ep_own = transport.channel_pair(
                "scientist", owner.name, backend=backend,
                latency_s=latency_s, bandwidth_bps=bandwidth_bps)
            head_fwd, head_bwd = adapter.owner_programs(p)
            masker = None
            if masked:
                masker = masking.MaskedAggregator(
                    mask_root, p, len(self.owners),
                    adapter.quant_program(), generation=generation)
            w = OwnerComputeEndpoint(
                owner, ep_own, head_fwd, head_bwd,
                optimizer=owner_opt, params=params,
                codec=codec, ack_steps=sequential, microbatches=M,
                gather=adapter.gather_program(),
                update_program=owner_update,
                tail_program=adapter.owner_tail_rule(owner_lr, p),
                opt_state=opt_state, start_step=start_step,
                masker=masker, cut_noise_std=sp_cfg.cut_noise_std,
                noise_seed=self._init_seed)
            # in-process actors get the same chaos surface as spawned
            # workers: the env plan's crash/wedge wrap + wire faults
            faults.arm_actor(w, owner.name, generation=generation)
            if backend == "queue":
                faults.arm_endpoint(ep_own, owner.name,
                                    generation=generation)
            th = threading.Thread(target=w.run, daemon=True,
                                  name=f"owner-{owner.name}")
            th.start()
            return w, ep_sci, th

        for p in range(len(self.owners)):
            if backend == "process":
                handle = spawn_proc(
                    p, param_leaves=[
                        np.asarray(leaf) for leaf in
                        jax.tree_util.tree_leaves(
                            adapter.owner_param_slice(self.params, p))])
                workers.append(handle)
                eps.append(handle.endpoint)
            else:
                w, ep_sci, th = spawn_thread(
                    p, params=adapter.owner_param_slice(self.params, p))
                workers.append(w)
                eps.append(ep_sci)
                threads.append(th)

        sup = None
        if supervise:
            # heartbeat liveness probes ride the protocol channels on
            # their own thread (send paths are thread-safe; recv_kind's
            # locked stash routes each kind to its consumer).  The step
            # loop never *acts* on a suspicion alone — recovery triggers
            # on in-band failures (OwnerFailure / FrameCorrupt), which
            # are strictly fresher — but the supervisor owns the
            # restart budget and backoff.
            sup = Supervisor(max_restarts=max_restarts,
                             heartbeat_s=heartbeat_s)
            for p, owner in enumerate(self.owners):
                sup.attach(owner.name, eps[p], workers[p])
            sup.start()

        labels = self.scientist.labels
        rng = np.random.default_rng(self.seed if shuffle_seed is None
                                    else shuffle_seed)
        if epochs is not None:
            steps_per_epoch = (n_train - batch_size) // batch_size + 1
            total_steps = epochs * steps_per_epoch
        else:
            steps_per_epoch = None
            total_steps = steps
        # THE batch-index stream — shared with the joint loop.  The
        # replay log caches every batch pulled from the generator so a
        # supervised recovery can re-send step s's exact indices without
        # re-consuming the shuffle rng (bit-identity depends on it).
        gen = self._index_stream(rng, n_train, batch_size, epochs, steps)
        idx_log: list = []

        def get_idx(i):
            while len(idx_log) <= i:
                idx_log.append(next(gen))
            return idx_log[i]

        inflight: deque = deque()

        def send_fwd(idx, seq):
            with span(spans.SEND_FWD, party=SCIENTIST, step=seq):
                for ep in eps:
                    ep.send("head_fwd", {"idx": np.asarray(idx, np.int32)},
                            seq=seq)
            inflight.append(idx)

        def recv_chunk(seq):
            """One microbatch chunk from every owner -> per-owner cut
            tuple + the owners' summed aux scalar, on the host (``stage``
            puts them on the device; stacking happens in-program).
            Masked runs fold the owners' uint32 ring payloads instead:
            the return is the reconstructed int32 SUM — the scientist
            never materializes a per-owner activation."""
            cuts, payloads, aux = [], [], 0.0
            for owner, ep, w in zip(self.owners, eps, workers):
                with span(spans.CUT_EXCHANGE, party=SCIENTIST,
                          peer=owner.name, step=seq // M):
                    m = self._recv_from_owner(ep, w, "cut_activations",
                                              timeout=timeout)
                    if m.seq != seq:
                        raise RuntimeError(f"protocol desync: cut seq "
                                           f"{m.seq} != expected {seq}")
                    if masked:
                        payloads.append(m.payload)
                    else:
                        cuts.append(codec.decode(m.payload))
                    if "aux" in m.payload:
                        aux += float(np.asarray(m.payload["aux"]).sum())
            if masked:
                return masking.reconstruct(payloads), aux
            return tuple(cuts), aux

        def stage(cuts, lab, step):
            """A chunk's cuts and labels on the device, in one put."""
            with span(spans.HOST_STAGE, party=SCIENTIST, step=step,
                      bytes=_device_nbytes((cuts, lab))):
                return jax.device_put((cuts, lab))

        def grad_payloads(cg, parts, seq):
            """Each owner's encoded cut gradient for chunk ``seq``, and
            the chunk's metric parts: fetched to the host together in
            one read where the channels frame their sends — before the
            defences, which run on the host — else left on the
            device."""
            if all(_serializes(ep) for ep in eps):
                if defend_on:
                    cg, parts = _fetch((cg, parts))
                else:
                    return _fetch(
                        ([codec.encode(g) for g in cg], parts))
            return [codec.encode(defend(g, seq, p))
                    for p, g in enumerate(cg)], parts

        # Party threads trade sub-millisecond messages; CPython's default
        # 5 ms GIL switch interval would let one party's pure-Python
        # stretch stall another's dispatch for a whole quantum.
        import sys as _sys
        old_switch = _sys.getswitchinterval()
        _sys.setswitchinterval(5e-4)

        # warmup receives tolerate worker startup + compile (a spawned
        # process imports jax and jits every program before its first
        # cut) — the user's ``timeout`` governs steady-state receives
        warmup_timeout = max(timeout, 120.0)

        # ---------------- warmup: compile both sides before the clock
        try:
            widx = np.zeros(batch_size, np.int32)
            wlab = np.asarray(labels[widx])
            wzero = None        # kept: respawned workers re-warm with it
            for ep in eps:
                ep.send("warmup", {"idx": widx}, seq=-1)
            for m in range(M):
                cuts, payloads = [], []
                for ep, w in zip(eps, workers):
                    mm = self._recv_from_owner(ep, w, "warmup_cuts",
                                               timeout=warmup_timeout)
                    if masked:
                        payloads.append(mm.payload)
                    else:
                        cuts.append(codec.decode(mm.payload))
                # staged as the steps stage theirs: device arrays in
                # every program's warm-up call.  Masked: all owners are
                # generation 0 here, so their warmup masks cancel and the
                # fold is the true zsum — compiles the masked trunk
                # programs at real shapes
                cuts, lab_m = jax.device_put(
                    (masking.reconstruct(payloads) if masked
                     else tuple(cuts), wlab[m * bm:(m + 1) * bm]))
                if sequential:
                    _, _, cg = trunk_step(
                        trunk_params, cuts if masked else jnp.stack(cuts),
                        lab_m)
                else:
                    cg, _ = cutgrad(trunk_params, cuts, lab_m, denom,
                                    inv_micro)
                    weightgrad(trunk_params, cuts, lab_m, denom, inv_micro)
                # masked: the broadcast z-grad
                zero = np.zeros_like(np.asarray(cg if masked else cg[0]))
                wzero = zero
                for ep in eps:
                    ep.send("warmup_grads", codec.encode(zero), seq=m)
            trunk_params, trunk_state = trunk_update(
                trunk_params, trunk_state,
                jax.tree.map(jnp.zeros_like, trunk_params), 0)
            for ep, w in zip(eps, workers):
                self._recv_from_owner(ep, w, "warmup_done",
                                      timeout=warmup_timeout)
            phase.close()

            # ---------------- the timed training region
            history: dict = {"train": [], "eval": []}
            t0 = time.perf_counter()
            t_warm = None     # end of step 0 (steady-state guard band)
            overhead_s = 0.0  # eval/sync/ckpt time, excluded from step cost
            metrics: dict = {}

            def sync():
                self._sync_split_params(workers, eps, trunk_params,
                                        timeout=timeout)

            # -------- supervision state (markers, snapshots, replay)
            trunk_snaps: dict = {}   # marker step -> (np params, np state)
            hist_marks: dict = {}    # marker step -> history lengths
            snap_acks: dict = {p: {} for p in range(len(eps))}
            marker = {"last": None, "pending": False}
            KEEP = 4                 # markers retained (> pipeline lag)

            def collect_acks(s):
                for p, (ep, w) in enumerate(zip(eps, workers)):
                    m = self._recv_from_owner(ep, w, "snapshot_ack",
                                              timeout=timeout)
                    if int(m.seq) != s:
                        raise OwnerFailure(
                            f"snapshot ack desync from "
                            f"{self.owners[p].name!r}: seq {m.seq} != "
                            f"{s}", party=self.owners[p].name)
                    snap_acks[p][s] = {k: np.array(v)
                                       for k, v in m.payload.items()}
                    for old in sorted(snap_acks[p])[:-KEEP]:
                        del snap_acks[p][old]

            def mark(s):
                # collect the previous marker's acks lazily (they have
                # been on the wire since that iteration), then ship
                # marker s: each owner snapshots its step-s-start
                # params/opt state by FIFO order; the trunk's step-s
                # snapshot is taken right here
                if marker["pending"]:
                    collect_acks(marker["last"])
                for ep in eps:
                    ep.send("snapshot", {}, seq=s)
                trunk_snaps[s] = (
                    jax.tree.map(lambda a: np.array(a), trunk_params),
                    jax.tree.map(lambda a: np.array(a), trunk_state))
                hist_marks[s] = (len(history["train"]),
                                 len(history["eval"]))
                for old in sorted(trunk_snaps)[:-KEEP]:
                    del trunk_snaps[old]
                    hist_marks.pop(old, None)
                marker["last"], marker["pending"] = s, True

            def respawn(p, s):
                # rebuild owner p from the marker-s leaves it acked:
                # params + optimizer state + step counter, armed at its
                # next generation so generation-0 faults stay fired
                gen_n = sup.restarts(self.owners[p].name)
                ack = snap_acks[p][s]
                p_leaves = [ack[f"p{i}"] for i in
                            range(sum(k.startswith("p") for k in ack))]
                o_leaves = [ack[f"o{i}"] for i in
                            range(sum(k.startswith("o") for k in ack))]
                if backend == "process":
                    handle = spawn_proc(
                        p, param_leaves=p_leaves,
                        opt_state_leaves=o_leaves, start_step=s,
                        generation=gen_n)
                    workers[p], eps[p] = handle, handle.endpoint
                else:
                    structure = jax.tree_util.tree_structure(
                        adapter.owner_param_slice(self.params, p))
                    params_r = jax.tree_util.tree_unflatten(
                        structure, [jnp.asarray(x) for x in p_leaves])
                    opt_r = jax.tree_util.tree_unflatten(
                        jax.tree_util.tree_structure(
                            owner_opt.init(params_r)),
                        [jnp.asarray(x) for x in o_leaves])
                    w, ep_sci, th = spawn_thread(
                        p, params=params_r, opt_state=opt_r,
                        start_step=s, generation=gen_n)
                    workers[p], eps[p] = w, ep_sci
                    threads.append(th)
                sup.attach(self.owners[p].name, eps[p], workers[p])

            def rewarm(p):
                # compile the respawned worker's programs before it
                # rejoins the timed region; the zero-grad update is a
                # bitwise no-op (SGD-family owner optimizers)
                ep, w = eps[p], workers[p]
                ep.send("warmup", {"idx": widx}, seq=-1)
                for m in range(M):
                    self._recv_from_owner(ep, w, "warmup_cuts",
                                          timeout=warmup_timeout)
                    ep.send("warmup_grads", codec.encode(wzero), seq=m)
                self._recv_from_owner(ep, w, "warmup_done",
                                      timeout=warmup_timeout)

            def recover(exc):
                """Roll every party back to the newest consistent
                marker s*, respawn the dead owner from its acked
                snapshot, and return s* as the step to replay from."""
                nonlocal trunk_params, trunk_state
                crashed = isinstance(exc, OwnerFailure)
                party = exc.party if crashed else exc.sender
                sup.failed.setdefault(party, exc)
                sup.plan_restart(party)     # budget + bounded backoff
                if crashed:
                    p_dead = next(i for i, o in enumerate(self.owners)
                                  if o.name == party)
                    # harvest snapshot acks still in flight from the
                    # dead party (sent before it died), then cut loose
                    try:
                        while True:
                            m = eps[p_dead].recv_kind("snapshot_ack",
                                                      timeout=0.5)
                            snap_acks[p_dead][int(m.seq)] = {
                                k: np.array(v)
                                for k, v in m.payload.items()}
                    except Exception:   # noqa: BLE001 — channel is dead
                        pass
                    shutdown = getattr(workers[p_dead], "shutdown", None)
                    if shutdown is not None:
                        shutdown()
                    acked = sorted(s for s in snap_acks[p_dead]
                                   if s in trunk_snaps)
                    if not acked:
                        raise OwnerFailure(
                            f"party {party!r} failed with no "
                            "recoverable snapshot", party=party) from exc
                    s_star = acked[-1]
                else:
                    # wire fault (FrameCorrupt): the party is alive —
                    # everyone rolls back to the newest marker, which
                    # every owner has processed by FIFO order
                    p_dead = None
                    s_star = marker["last"]
                for i, ep in enumerate(eps):
                    if i != p_dead:
                        ep.send("rollback", {}, seq=s_star)
                for i, (ep, w) in enumerate(zip(eps, workers)):
                    if i == p_dead:
                        continue
                    while int(self._recv_from_owner(
                            ep, w, "rollback_ack",
                            timeout=timeout).seq) != s_star:
                        pass
                    # everything the owner sent before its ack is stale
                    ep.flush_pending()
                    if hasattr(ep, "reset_dedup"):
                        ep.reset_dedup()
                if crashed:
                    respawn(p_dead, s_star)
                    rewarm(p_dead)
                tp_np, ts_np = trunk_snaps[s_star]
                trunk_params = jax.tree.map(jnp.asarray, tp_np)
                trunk_state = jax.tree.map(jnp.asarray, ts_np)
                n_tr, n_ev = hist_marks[s_star]
                del history["train"][n_tr:]
                del history["eval"][n_ev:]
                trunk_snaps.clear()
                hist_marks.clear()
                for p in snap_acks:
                    snap_acks[p].clear()
                marker["last"], marker["pending"] = None, False
                # synchronous re-mark: every owner (respawned included)
                # snapshots its restored step-s*-start state, so a
                # second failure before the next marker stays covered
                mark(s_star)
                collect_acks(s_star)
                marker["pending"] = False
                self.recovery_events.append({
                    "party": party, "step": int(s_star),
                    "action": "respawn" if crashed else "rollback",
                    "error": str(exc)})
                return s_star

            t = 0
            fwd_next = 0        # next head_fwd seq to ship
            while t < total_steps:
              try:
                with span(spans.STEP, party=SCIENTIST, step=t):
                    if supervise and t % resync_every == 0 \
                            and marker["last"] != t:
                        mark(t)
                    if fwd_next == t:
                        # step t's forward request (start or replay
                        # resume)
                        send_fwd(get_idx(t), t)
                        fwd_next = t + 1
                    if (not sequential and t + 1 < total_steps
                            and fwd_next == t + 1):
                        # the t+1 forward request leaves FIRST: it
                        # overlaps the wire and the owners stage (not run)
                        # it until their step-t update lands — FIFO keeps
                        # it exact
                        send_fwd(get_idx(t + 1), t + 1)
                        fwd_next = t + 2
                    idx_t = inflight.popleft()
                    # the labels' gather runs while the cut chunks are on
                    # the wire; each chunk's go to the device with its cuts
                    with span(spans.LABEL_STAGE, party=SCIENTIST, step=t):
                        lab_t = np.asarray(labels[idx_t])
                    if sequential:
                        # synchronous baseline: one whole-batch exchange
                        # through the fused one-pass trunk program; update
                        # strictly before the grads leave, wait for every
                        # owner's step, then request t+1
                        cuts, owner_aux = recv_chunk(t)
                        cuts, lab_d = stage(cuts, lab_t, t)
                        if masked:
                            # recv_chunk already folded the ring sum; the
                            # broadcast z-grad goes back to every owner
                            parts, tg, zg = trunk_step(
                                trunk_params, cuts, lab_d)
                            cg = [zg] * len(eps)
                        else:
                            parts, tg, cg = trunk_step(
                                trunk_params, jnp.stack(cuts), lab_d)
                        trunk_params, trunk_state = trunk_update(
                            trunk_params, trunk_state, tg, t)
                        for p, ep in enumerate(eps):
                            ep.send("cut_gradients",
                                    codec.encode(defend(cg[p], t, p)),
                                    seq=t)
                        for ep, w in zip(eps, workers):
                            self._recv_from_owner(ep, w, "step_done",
                                                  timeout=timeout)
                        if t + 1 < total_steps and fwd_next == t + 1:
                            send_fwd(get_idx(t + 1), t + 1)
                            fwd_next = t + 2
                        parts_list = [parts]
                    else:
                        # pipelined GPipe: each chunk's cut grads ship the
                        # moment its cuts arrive; everything batch-wide —
                        # trunk weight grads, the optimizer update, metric
                        # folds — runs in the wire's shadow afterwards
                        owner_aux = 0.0
                        parts_list = []
                        cut_cache = []
                        for m in range(M):
                            seq = t * M + m
                            cuts, aux_m = recv_chunk(seq)
                            owner_aux += aux_m
                            cuts, lab_m = stage(
                                cuts, lab_t[m * bm:(m + 1) * bm], t)
                            with span(spans.TRUNK_CUTGRAD, party=SCIENTIST,
                                      step=t):
                                cg, parts = cutgrad(trunk_params, cuts,
                                                    lab_m, denom, inv_micro)
                            if masked:
                                # cutgrad returned the broadcast z-grad
                                cg = [cg] * len(eps)
                            with span(spans.CUT_GRAD_SEND, party=SCIENTIST,
                                      step=t):
                                payloads, parts = grad_payloads(cg, parts,
                                                                seq)
                                for ep, payload in zip(eps, payloads):
                                    ep.send("cut_gradients", payload,
                                            seq=seq)
                            # fetched parts add on the host, in chunk
                            # order, in float32, as on the device
                            parts_list.append(parts)
                            cut_cache.append((cuts, lab_m))
                        tg_acc = None
                        with span(spans.TRUNK_WEIGHTGRAD, party=SCIENTIST,
                                  step=t):
                            for cuts, lab_m in cut_cache:
                                tg = weightgrad(trunk_params, cuts, lab_m,
                                                denom, inv_micro)
                                tg_acc = tg if tg_acc is None else \
                                    _tree_add(tg_acc, tg)
                                # the last step's weight grads must not
                                # stay live beside the next weightgrad's
                                del tg
                        with span(spans.TRUNK_UPDATE, party=SCIENTIST,
                                  step=t):
                            trunk_params, trunk_state = trunk_update(
                                trunk_params, trunk_state, tg_acc, t)
                    parts_acc = parts_list[0]
                    for parts in parts_list[1:]:
                        parts_acc = {k: parts_acc[k] + parts[k]
                                     for k in parts}
                    metrics = dict(parts_acc)
                    if owner_aux and "aux" in metrics:
                        # joint-path parity: heads aux + trunk aux
                        metrics = {**metrics,
                                   "aux": metrics["aux"] + owner_aux}
                    if t == 0:
                        t_warm = time.perf_counter()

                    # ----------- bookkeeping (excluded from step timings)
                    tb = time.perf_counter()
                    with span(spans.BOOKKEEPING, party=SCIENTIST, step=t):
                        self._train_bookkeeping(
                            t, metrics, history, t0, epochs=epochs,
                            steps=steps, steps_per_epoch=steps_per_epoch,
                            log_every=log_every, verbose=verbose,
                            ckpt_dir=ckpt_dir, ckpt_every=ckpt_every,
                            sync=sync, scalars=_read_scalars)
                    overhead_s += time.perf_counter() - tb
                    t += 1
              except (OwnerFailure, FrameCorrupt) as e:
                if not supervise:
                    raise
                t = recover(e)
                inflight.clear()
                fwd_next = t

            wall_s = time.perf_counter() - t0
            phase.enter_context(span(spans.FIT_END, party=SCIENTIST))
            self._sync_split_params(workers, eps, trunk_params,
                                    timeout=timeout)
            if steps is not None and len(self._eval_idx):
                history["eval"].append({"step": steps, **self.evaluate()})
        finally:
            _sys.setswitchinterval(old_switch)
            if mask_env_set:
                os.environ.pop(masking.MASK_ENV, None)
            if sup is not None:
                sup.stop()
            for ep in eps:
                try:
                    ep.send("stop", {})
                except RuntimeError:        # worker already gone
                    pass
            for th in threads:
                _join_or_warn(th, 10.0, "fit(split)")
            for w in workers:
                shutdown = getattr(w, "shutdown", None)
                if shutdown is not None:    # process-backed handle
                    shutdown()
            phase.close()

        # ------------------------------------- measured traffic accounting
        per_owner: Dict[str, dict] = {}
        tot_payload = tot_wire = 0
        for owner, ep in zip(self.owners, eps):
            sent, rcvd = ep.sent_stats, ep.recv_stats
            cut_k = rcvd["by_kind"].get("cut_activations",
                                        {"payload_bytes": 0,
                                         "wire_bytes": 0})
            grad_k = sent["by_kind"].get("cut_gradients",
                                         {"payload_bytes": 0,
                                          "wire_bytes": 0})
            per_owner[owner.name] = {
                "cut_payload_bytes": cut_k["payload_bytes"],
                "cut_wire_bytes": cut_k["wire_bytes"],
                "grad_payload_bytes": grad_k["payload_bytes"],
                "grad_wire_bytes": grad_k["wire_bytes"],
                "messages": sent["messages"] + rcvd["messages"],
            }
            tot_payload += cut_k["payload_bytes"] + grad_k["payload_bytes"]
            tot_wire += cut_k["wire_bytes"] + grad_k["wire_bytes"]
            self._log(owner.name, "scientist", "cut_activations",
                      bytes=cut_k["payload_bytes"], measured=True,
                      per_step_bytes=cut_k["payload_bytes"]
                      // max(total_steps, 1),
                      width=self.adapter.cut_shape(
                          batch_size, owner.feature_shape)[-1])
            self._log("scientist", owner.name, "cut_gradients",
                      bytes=grad_k["payload_bytes"], measured=True,
                      per_step_bytes=grad_k["payload_bytes"]
                      // max(total_steps, 1))
        self.transport_stats = {
            "mode": "split", "schedule": schedule,
            "microbatches": M,
            "aggregation": aggregation or "none",
            "compression": compression or "none", "backend": backend,
            "latency_s": latency_s, "bandwidth_bps": bandwidth_bps,
            "steps": total_steps, "wall_s": wall_s,
            # per-step cost excludes eval/sync/ckpt bookkeeping (every
            # compile is pulled out of the timed region by the warmup
            # handshake) ...
            "step_ms": (1e3 * (wall_s - overhead_s)
                        / max(total_steps, 1)),
            # ... and, steady-state, the step-0 pipeline fill too
            "steady_step_ms": (1e3 * (t0 + wall_s - t_warm - overhead_s)
                               / (total_steps - 1)
                               if t_warm is not None and total_steps > 1
                               else 1e3 * (wall_s - overhead_s)
                               / max(total_steps, 1)),
            "per_owner": per_owner,
            "cut_payload_bytes_per_step": sum(
                o["cut_payload_bytes"] for o in per_owner.values())
            // max(total_steps, 1),
            "total_payload_bytes": tot_payload,
            "total_wire_bytes": tot_wire,
            "total_payload_bytes_per_step": tot_payload
            // max(total_steps, 1),
            "recoveries": len(self.recovery_events),
            "supervisor": dict(sup.stats) if sup is not None else None,
            "moe": _moe_stats(history["train"]),
        }

        final = dict(history["train"][-1]) if history["train"] else {}
        if history["eval"]:
            final.update({f"val_{k}": v
                          for k, v in history["eval"][-1].items()
                          if k not in ("epoch", "step")})
        history["final"] = final
        history["transport"] = self.transport_stats
        self.history = history
        return history

    # ------------------------------------------------------------ 4. eval

    def evaluate(self, *, split: str = "eval",
                 batch_size: int = 512) -> Dict[str, float]:
        """Metrics on the held-out (or train) rows, batched and
        length-weighted."""
        self._require(resolved=True, built=True, labels=True)
        idx = self._eval_idx if split == "eval" else self._train_idx
        if idx is None or not len(idx):
            raise ValueError(f"no rows in split {split!r} — "
                             "fit with eval_frac > 0 first")
        owner_arrays = self._owner_arrays()
        labels = self.scientist.labels
        totals: Dict[str, float] = {}
        n_done = 0
        for s in range(0, len(idx), batch_size):
            sub = idx[s:s + batch_size]
            m = self._eval_fn(self.params, self.adapter.make_batch(
                owner_arrays, labels, sub))
            for k, v in m.items():
                totals[k] = totals.get(k, 0.0) + float(v) * len(sub)
            n_done += len(sub)
        return {k: v / n_done for k, v in totals.items()}

    # ------------------------------------------------------------ 5. serve

    def serve(self, **engine_kw):
        """Wrap the resident split model in a ``ServingEngine`` (LM archs).
        Kwargs are forwarded: ``batch_slots, ctx_len, max_new, eos_token,
        ring_cache, pad_token``, plus the transport boundary knobs
        ``transport`` ("direct" | "queue" | "process" routes every cut
        activation through a measured ``federation.transport`` channel),
        ``latency_s``, ``bandwidth_bps``, and ``compression``
        (None | "fp16" | "int8" cut codec), and the serving knobs
        ``scheduler`` ("wave" drains in fixed waves; "continuous"
        refills freed slots per tick), ``max_queue`` (bounded admission
        — ``submit`` raises ``QueueFull`` beyond it), and ``cut_cache``
        (True or a ``CutCache`` — repeat contexts skip head recompute
        and cut upload entirely)."""
        self._require(built=True)
        if not getattr(self.adapter, "supports_serving", False):
            raise ValueError(
                f"{type(self.adapter).__name__} does not support serving")
        return self.adapter.make_engine(self.params, **engine_kw)

    def serve_dataset(self, *, max_new: int = 16, batch_slots: int = 4,
                      n_requests: Optional[int] = None, **engine_kw):
        """Serve the session's own aligned contexts: owners' sequence
        slices are merged (owner-side) into each request's context, queued,
        and decoded in waves.  Returns ({rid: Result}, engine)."""
        self._require(resolved=True, built=True)
        contexts = batching.merge_sequence_slices(
            np.stack(self._owner_arrays()))
        if n_requests is not None:
            contexts = contexts[:n_requests]
        engine = self.serve(batch_slots=batch_slots,
                            ctx_len=contexts.shape[1], max_new=max_new,
                            **engine_kw)
        for row in contexts:
            engine.submit(row)
        return engine.run(), engine

    # ---------------------------------------------------------- accounting

    def checkpoint(self, ckpt_dir: str, step: int = 0) -> str:
        """Per-party checkpoints: heads/owner{i}.npz + trunk.npz."""
        self._require(built=True)
        from repro import checkpoint as ckpt
        return ckpt.save_split(ckpt_dir, self.params, step)

    def restore(self, step_dir: str) -> "VerticalSession":
        """Load per-party checkpoints saved by :meth:`checkpoint` (or
        ``fit(ckpt_every=...)``) back into the resident params, so a
        fresh session resumes training/serving from that step."""
        self._require(built=True)
        from repro import checkpoint as ckpt
        self.params = ckpt.restore_split(step_dir)
        return self

    def cut_traffic(self, batch_size: int,
                    bytes_per_el: int = 4) -> Dict[str, int]:
        """Bytes crossing each owner<->scientist boundary per step (C4)."""
        self._require(built=True)
        shape = self.adapter.cut_shape(
            batch_size, self.owners[0].feature_shape)
        tokens = shape[1] if len(shape) == 3 else 1
        return cut_layer_traffic(len(self.owners), batch_size, tokens,
                                 shape[-1], bytes_per_el)
