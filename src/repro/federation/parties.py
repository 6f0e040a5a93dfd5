"""Party abstractions — the paper's cast of characters as objects.

PyVertical's contribution is an *API*: a data scientist trains on features
vertically partitioned across data owners **without ever touching raw
features**, and owners never see labels.  These classes make that
visibility contract structural:

  * :class:`DataOwner` holds ``(ids, features)``.  It has **no** label
    attribute of any kind, and its ``features`` property raises
    :class:`PrivacyError` — raw features are reachable only through the
    owner-side accessor ``_features`` used by ``federation/batching.py``
    and the session's owner-side assembly (the simulation analogue of code
    running on the owner's device).
  * :class:`DataScientist` holds ``(ids, labels)`` and nothing else: no
    feature array ever lands on the object.
  * Cross-party flows go through :class:`~repro.federation.session.
    VerticalSession`, which records every owner->scientist message in its
    ``transcript`` — tests assert the only payloads are PSI responses and
    cut-layer activations (claim C4).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.psi import DEFAULT_MODE, PSIClient, PSIServer
from repro.core.resolution import VerticalDataset
from repro.core.vertical import make_ids, partition_sequence
from repro.federation.spans import (CUT_ENCODE, OWNER_CUT_GRAD,
                                    OWNER_FWD_REQUEST, span)
from repro.optim import apply_updates


class PrivacyError(RuntimeError):
    """Raised when code crosses the party-visibility boundary."""


class DataOwner:
    """A data owner: a vertical slice of every shared subject's features.

    The owner participates in training by running its head segment and
    shipping only cut-layer activations; raw rows never leave.  ``ids``
    are public to the session for PSI (the protocol itself only reveals
    the intersection to the scientist)."""

    def __init__(self, name: str, ids: Sequence[str], features: np.ndarray):
        self.name = name
        self._vd = VerticalDataset(list(ids), np.asarray(features))
        # the owner's FULL population: ``_vd`` becomes the aligned
        # training view after a resolve, but PSI always runs (and
        # re-runs) against the population — a repeat resolve must not
        # intersect against its own previous output
        self._full = self._vd
        self._psi_servers: Dict[tuple, PSIServer] = {}
        # content-tag caches (client uploads / double-blind responses /
        # hidden-mode lifts) — owned here so the byte and modexp savings
        # survive per-round actor re-creation AND population churn
        self._psi_blind_caches: Dict[tuple, dict] = {}
        self._psi_resp_caches: Dict[tuple, dict] = {}
        self._psi_lift_caches: Dict[tuple, dict] = {}

    # -- public (scientist-visible) surface --------------------------------
    @property
    def ids(self) -> List[str]:
        return self._vd.ids

    @property
    def n_rows(self) -> int:
        return len(self._vd.ids)

    @property
    def feature_shape(self) -> Tuple[int, ...]:
        """Per-row feature shape — metadata, not data."""
        return tuple(self._vd.data.shape[1:])

    @property
    def features(self):
        raise PrivacyError(
            f"raw features of {self.name!r} are private to the owner; "
            "only cut-layer activations cross the party boundary")

    def __repr__(self):
        return (f"DataOwner({self.name!r}, rows={self.n_rows}, "
                f"feature_shape={self.feature_shape})")

    def psi_server(self, group: str, fp_rate: float = 1e-9) -> PSIServer:
        """The owner's PSI endpoint, cached per (group, fp_rate): β and
        the per-element blinded own set are *persistent* state — a
        re-resolve after ±Δ row churn recomputes only the Δ new
        elements' exponentiations (``PSIServer.update_items``), not the
        whole set.  The accessor self-syncs against the owner's current
        rows, so callers never see a stale population."""
        key = (group, fp_rate)
        pop = self._full.ids
        srv = self._psi_servers.get(key)
        if srv is None:
            srv = self._psi_servers[key] = PSIServer(pop, fp_rate, group)
        elif srv.items != pop:
            srv.update_items(pop)
        return srv

    def psi_endpoint(self, endpoint, group: str, fp_rate: float = 1e-9,
                     pool=None):
        """The owner's wire-native PSI actor: wraps the cached
        :meth:`psi_server` state in a
        :class:`~repro.federation.psi_transport.PSIServerEndpoint`
        reacting to protocol messages on ``endpoint``.  The actor object
        is per-channel, but both memoization layers persist on the owner
        (β-side response state on the PSIServer, the client-upload byte
        cache in ``_psi_blind_caches``), so repeat rounds skip the
        blinded re-upload even across actor re-creation.  Invalidated
        when the owner's rows change (``_align``).  ``pool`` feeds the
        actor's own-set chunk kernels (executors are thread-safe, so the
        session shares one resolve pool across all parties)."""
        from repro.federation.psi_transport import PSIServerEndpoint
        key = (group, fp_rate)
        return PSIServerEndpoint(
            self.name, self.psi_server(group, fp_rate), endpoint,
            blind_cache=self._psi_blind_caches.setdefault(key, {}),
            resp_cache=self._psi_resp_caches.setdefault(key, {}),
            lift_cache=self._psi_lift_caches.setdefault(key, {}),
            chunk_kernel_pool=pool)

    def update_rows(self, ids: Sequence[str], features: np.ndarray
                    ) -> None:
        """Streaming-population update: replace the owner's rows in
        place.  PSI state is *kept* — the cached server re-syncs
        incrementally on the next resolve (O(Δ) new exponentiations for
        ±Δ churn), and the content-tag caches stay valid because they
        are keyed by content, never by session."""
        self._full = VerticalDataset(list(ids), np.asarray(features))
        self._vd = self._full

    # -- owner-side surface (runs 'on the owner's device') -----------------
    @property
    def _features(self) -> np.ndarray:
        return self._vd.data

    def _align(self, keep_ids: Sequence[str]) -> None:
        """Derive the aligned training view from the FULL population:
        discard non-shared rows and sort by ID (paper §3.1).  PSI state
        persists: the server accessor self-syncs to the population
        incrementally, and content-tag caches cannot go stale."""
        self._vd = self._full.filter_and_sort(keep_ids)

    def _align_hidden(self, rows: Sequence[int]) -> None:
        """Membership-hiding alignment: keep exactly ``rows`` (row
        indices into the full population, decoys included) in that
        order, and replace raw IDs with positional pseudonyms — the
        aligned order is the only cross-party coordinate system, so no
        party needs to know which raw IDs matched."""
        rows = list(rows)
        self._vd = VerticalDataset(
            [f"anon{k:06d}" for k in range(len(rows))],
            self._full.data[np.asarray(rows, np.int64)]
            if rows else self._full.data[:0])


class DataScientist:
    """The data scientist: subject ids + labels (``None`` for label-free
    workflows such as serving).  Holds no features, ever."""

    def __init__(self, ids: Sequence[str], labels: Optional[np.ndarray]):
        self._vd = VerticalDataset(
            list(ids),
            np.asarray(labels) if labels is not None
            else np.zeros(len(list(ids)), np.int32))
        self.has_labels = labels is not None
        # full population vs aligned view — see DataOwner._full
        self._full = self._vd
        self._psi_clients: Dict[tuple, PSIClient] = {}

    @property
    def ids(self) -> List[str]:
        return self._vd.ids

    @property
    def labels(self) -> Optional[np.ndarray]:
        return self._vd.data if self.has_labels else None

    def __repr__(self):
        return (f"DataScientist(rows={len(self._vd.ids)}, "
                f"labels={self.has_labels})")

    def psi_client(self, group: str, mode: str = DEFAULT_MODE,
                   pool=None) -> PSIClient:
        """The scientist's PSI endpoint, cached per (group, mode): its
        blinded upload is memoized on the client and reused against
        every owner round.  The accessor self-syncs against the
        scientist's current rows via ``PSIClient.update_items`` — after
        ±Δ churn the memoized upload is *spliced*, costing O(Δ) modexp
        and arming the wire delta fast path (``pool`` feeds the spliced
        elements' chunk kernels)."""
        key = (group, mode)
        pop = self._full.ids
        cli = self._psi_clients.get(key)
        if cli is None:
            cli = self._psi_clients[key] = PSIClient(pop, group, mode=mode)
        elif cli.items != pop:
            cli.update_items(pop, pool=pool)
        return cli

    def update_rows(self, ids: Sequence[str],
                    labels: Optional[np.ndarray]) -> None:
        """Streaming-population update: replace the scientist's rows in
        place.  Cached PSI clients re-sync incrementally on the next
        resolve (O(Δ) modexp + a delta upload for ±Δ churn)."""
        self._full = VerticalDataset(
            list(ids),
            np.asarray(labels) if labels is not None
            else np.zeros(len(list(ids)), np.int32))
        self._vd = self._full
        self.has_labels = labels is not None

    def _align(self, keep_ids: Sequence[str]) -> None:
        self._vd = self._full.filter_and_sort(keep_ids)

    def _align_hidden(self, positions: Sequence[int],
                      client_items: Sequence[str]) -> None:
        """Membership-hiding alignment: ``positions`` index the PSI
        client's item order (members + decoys, indistinguishable on the
        wire); map each back to the scientist's full-population row and
        adopt positional pseudonym IDs matching the owners'."""
        row_of = {it: i for i, it in enumerate(self._full.ids)}
        rows = [row_of[client_items[p]] for p in positions]
        self._vd = VerticalDataset(
            [f"anon{k:06d}" for k in range(len(rows))],
            self._full.data[np.asarray(rows, np.int64)]
            if rows else self._full.data[:0])


# ---------------------------------------------------------------------------
# Owner-side compute endpoint (true split execution)
# ---------------------------------------------------------------------------


class OwnerComputeEndpoint:
    """The compute that, in a real deployment, runs on the owner's device.

    Holds the owner's private feature slice (staged on device once — the
    per-step dispatch loop never blocks on a host transfer), its
    head-segment parameters, and its own optimizer state; everything else
    arrives as protocol messages on its
    :class:`~repro.federation.transport.Endpoint`:

      ``head_fwd``       (scientist -> owner): batch row indices, seq t.
                         The owner gathers ITS OWN rows on device, splits
                         them into ``microbatches`` chunks, and — once
                         every update through step t-1 is applied — runs
                         the jitted head forward per chunk, shipping each
                         codec-encoded cut chunk the moment it exists
                         (paper Fig. 2, arrow 5): up to M cut exchanges
                         in flight per channel.
      ``cut_gradients``  (scientist -> owner): the cut gradient for chunk
                         m of step t, seq ``t*M + m`` (arrow 7).  The
                         owner runs its explicit-VJP head backward for
                         that chunk immediately (hidden under the wire
                         for all but the last chunk), accumulates, and on
                         the step's final chunk applies its optimizer
                         update (arrow 8) — grads from every microbatch
                         are accumulated at step-start params before the
                         single update, so the math is the plain
                         full-batch step, GPipe-scheduled.
      ``warmup``         pre-training handshake: runs every jitted
                         program (gather, fwd/bwd per chunk shape, a
                         zero-gradient update, both codec directions) so
                         no XLA compile lands inside the timed training
                         region.  A zero gradient leaves params and
                         optimizer state bitwise unchanged.
      ``barrier``        flush marker; the owner acks once every prior
                         message is processed.
      ``pull_params``    the trusted-runtime param fetch: the owner
                         ships its current head-segment params as
                         numbered numpy leaves (``params_dump``).  The
                         thread backend reads ``self.params`` directly
                         (shared memory); across a process boundary this
                         message is the only way the session's
                         reassembly can see owner state.
      ``stop``           end of training.

    FIFO channel order is the protocol's only synchronization: every
    gradient chunk of step t precedes the forward execution for step
    t+1 (the t+1 ``head_fwd`` may *arrive* early — it is staged, not
    run, until the step-t update lands), so pipelined schedules stay
    mathematically exact.  ``run`` is the thread target; with compute
    released from the GIL (jitted programs), owner threads genuinely
    overlap the scientist's trunk.
    """

    def __init__(self, owner: DataOwner, endpoint, head_fwd, head_bwd, *,
                 optimizer, params, codec, ack_steps: bool = False,
                 microbatches: int = 1, gather=None, update_program=None,
                 tail_program=None, opt_state=None, start_step: int = 0,
                 masker=None, cut_noise_std: float = 0.0,
                 noise_seed: int = 0):
        import jax
        import jax.numpy as jnp

        self.owner = owner
        self.endpoint = endpoint
        self.head_fwd, self.head_bwd = head_fwd, head_bwd
        # secure forward aggregation: when set, every cut that ships is
        # quantized + ring-masked (core/masking.py) instead of
        # codec-encoded — an eavesdropper sees uniform ring elements
        self.masker = masker
        # owner-side Titcombe defence: deterministic Gaussian noise on
        # steady-state cuts BEFORE they ship (the joint path's
        # cut_noise_std analogue, but on the wire)
        self.cut_noise_std = float(cut_noise_std)
        self.noise_seed = int(noise_seed)
        self.opt = optimizer
        self.params = params
        # a respawned worker resumes snapshotted optimizer state and the
        # step counter it rolled back to; fresh endpoints init both
        self.opt_state = (optimizer.init(params) if opt_state is None
                          else opt_state)
        self.codec = codec
        self.ack_steps = ack_steps
        self.micro = int(microbatches)
        self.steps_done = int(start_step)
        self.error: Optional[BaseException] = None
        self._inflight: Dict[int, object] = {}   # seq -> owner-side inputs
        self._plan: Dict[int, list] = {}         # step -> staged fwd chunks
        self._grad_acc = None
        self._grads_seen = 0
        # step -> (np params, np opt_state): host copies (donated device
        # buffers get reused by later updates), kept for the supervised
        # fit's rollback protocol
        self._snaps: Dict[int, tuple] = {}

        if update_program is None:
            # one jitted program per segment op — update+apply compiled
            # together, the same fusion granularity as the joint train
            # step (required for bit-for-bit gradient equivalence);
            # params/state buffers are donated
            def _update(p, s, g, i):
                updates, s = optimizer.update(g, s, p, i)
                return apply_updates(p, updates), s

            update_program = jax.jit(_update, donate_argnums=(0, 1))
        self._update = update_program
        # fused bwd+update+fwd tail (one dispatch on the critical path);
        # None falls back to the separate programs
        self._tail = tail_program
        self._gather = gather or jax.jit(lambda feats, idx: feats[idx])
        self._feats = jnp.asarray(owner._features)   # device-staged, once

    # helpers --------------------------------------------------------------
    def _stage(self, idx) -> list:
        """Gather the step's rows on device and pre-slice the microbatch
        chunks (all off the latency-critical path)."""
        import jax.numpy as jnp
        x = self._gather(self._feats, jnp.asarray(np.asarray(idx)))
        if self.micro == 1:
            return [x]
        bm = x.shape[0] // self.micro
        return [x[m * bm:(m + 1) * bm] for m in range(self.micro)]

    def _ship_cut(self, out, seq: int, kind: str = "cut_activations"
                  ) -> None:
        with span(CUT_ENCODE, party=self.owner.name, seq=seq):
            # segment programs may return (cut, aux): the scalar
            # owner-local aux loss rides along for metric parity
            cut, aux = out if isinstance(out, tuple) else (out, None)
            if self.masker is not None:
                # masked-sum wire format: {"mq": uint32 ring element}.
                # Bypasses the codec — uniform ring bytes are
                # incompressible and already 4 bytes/element, the f32 it
                # replaces.
                tag = (self.masker.step_tag(seq)
                       if kind == "cut_activations"
                       else self.masker.warmup_tag(seq))
                payload = self.masker.encode(cut, tag)
            else:
                if self.cut_noise_std > 0.0 and kind == "cut_activations":
                    from repro.core.privacy import deterministic_cut_noise
                    cut = deterministic_cut_noise(
                        cut, self.cut_noise_std, self.noise_seed, f"s{seq}")
                payload = self.codec.encode(cut)
            if aux is not None:
                payload["aux"] = np.float32(np.asarray(aux).sum())
            self.endpoint.send(kind, payload, seq=seq)

    def _run_fwd(self, step: int, first_out=None) -> None:
        """Run + ship the microbatch forwards of ``step`` (params are
        already at step-start state by FIFO order).  ``first_out``:
        chunk 0's forward output when the fused tail program already
        produced it."""
        chunks = self._plan[step]
        start = 0
        if first_out is not None:
            self._inflight[step * self.micro] = chunks[0]
            self._ship_cut(first_out, step * self.micro)
            start = 1
        for m in range(start, len(chunks)):
            seq = step * self.micro + m
            self._inflight[seq] = chunks[m]
            self._ship_cut(self.head_fwd(self.params, chunks[m]), seq)
        del self._plan[step]

    def _warmup(self, msg) -> None:
        """Compile every program this endpoint will run, leaving params
        and optimizer state bitwise untouched (zero-gradient update)."""
        import jax
        import jax.numpy as jnp

        chunks = self._stage(msg.payload["idx"])
        for m, x in enumerate(chunks):
            self._ship_cut(self.head_fwd(self.params, x), m,
                           kind="warmup_cuts")
        acc = None
        gzero = None
        for m in range(len(chunks)):
            g = jnp.asarray(self.codec.decode(
                self.endpoint.recv_kind("warmup_grads").payload))
            gzero = g * 0.0
            grads = self.head_bwd(self.params, chunks[m], gzero)
            acc = grads if acc is None else jax.tree.map(
                lambda a, b: a + b, acc, grads)
        self.params, self.opt_state = self._update(
            self.params, self.opt_state, acc, 0)
        if self._tail is not None:
            # compile the fused tail too — zero grads leave params and
            # state bitwise unchanged, matching its real call shape
            # (acc=None for single-chunk steps, a grads tree otherwise)
            tail_acc = None if self.micro == 1 else \
                jax.tree.map(lambda a: a * 0.0, acc)
            self.params, self.opt_state, _ = self._tail(
                self.params, self.opt_state, tail_acc, chunks[-1],
                gzero, 0, chunks[0])
        self.endpoint.send("warmup_done", {}, seq=msg.seq)

    # one message ----------------------------------------------------------
    def handle(self, msg) -> bool:
        """Process one protocol message; returns False on ``stop``."""
        if msg.kind == "stop":
            return False
        if msg.kind == "barrier":
            self.endpoint.send("barrier_ack", {}, seq=msg.seq)
            return True
        if msg.kind == "pull_params":
            import jax
            leaves = jax.tree_util.tree_leaves(self.params)
            self.endpoint.send(
                "params_dump",
                {str(i): np.asarray(leaf)
                 for i, leaf in enumerate(leaves)}, seq=msg.seq)
            return True
        if msg.kind == "warmup":
            self._warmup(msg)
            return True
        if msg.kind == "head_fwd":
            step = int(msg.seq)
            with span(OWNER_FWD_REQUEST, party=self.owner.name, seq=step):
                self._plan[step] = self._stage(msg.payload["idx"])
                if step == self.steps_done:
                    # all updates through step-1 applied — run now;
                    # otherwise the staged plan runs when the step-(t-1)
                    # update lands
                    self._run_fwd(step)
            return True
        if msg.kind == "cut_gradients":
            import jax
            import jax.numpy as jnp
            seq = int(msg.seq)
            with span(OWNER_CUT_GRAD, party=self.owner.name, seq=seq):
                g = jnp.asarray(self.codec.decode(msg.payload))
                x = self._inflight.pop(seq)
                # grads accumulate at step-start params; ONE update per
                # step on its last chunk (GPipe semantics — the exact
                # full-batch step; with micro == 1 this degenerates to
                # the one-shot update)
                last = self._grads_seen + 1 == self.micro
                nxt = self.steps_done + 1
                if last and self._tail is not None and nxt in self._plan:
                    # fused fast path: final-chunk bwd + accumulate +
                    # update + next step's first forward, one compiled
                    # dispatch
                    self.params, self.opt_state, out = self._tail(
                        self.params, self.opt_state, self._grad_acc, x, g,
                        self.steps_done, self._plan[nxt][0])
                    self._grad_acc, self._grads_seen = None, 0
                    self.steps_done = nxt
                    self._run_fwd(nxt, out)
                else:
                    grads = self.head_bwd(self.params, x, g)
                    self._grad_acc = grads if self._grad_acc is None \
                        else jax.tree.map(lambda a, b: a + b,
                                          self._grad_acc, grads)
                    self._grads_seen += 1
                    if last:
                        self.params, self.opt_state = self._update(
                            self.params, self.opt_state, self._grad_acc,
                            self.steps_done)
                        self._grad_acc, self._grads_seen = None, 0
                        self.steps_done += 1
                        if self.steps_done in self._plan:
                            self._run_fwd(self.steps_done)
                if self.ack_steps:
                    self.endpoint.send("step_done", {}, seq=seq)
            return True
        if msg.kind == "heartbeat":
            # liveness probe (federation/supervisor.py): answering
            # inline between protocol messages is exactly the signal —
            # a wedged actor stops answering
            self.endpoint.send("heartbeat_ack", {}, seq=msg.seq)
            return True
        if msg.kind == "snapshot":
            # step marker s: params/opt_state are at step-s-start state
            # by FIFO order.  Keep a host copy (device buffers are
            # donated by later updates) and ack it back with the leaves,
            # so the scientist can respawn this owner from step s.
            import jax
            s = int(msg.seq)
            snap = (jax.tree.map(lambda a: np.array(a), self.params),
                    jax.tree.map(lambda a: np.array(a), self.opt_state))
            self._snaps[s] = snap
            # keep the 4 newest markers (NOT a step-distance window:
            # with sparse resync the pipeline's FIFO lag still needs
            # the previous marker around for recovery)
            for old in sorted(self._snaps)[:-4]:
                del self._snaps[old]
            payload = {f"p{i}": leaf for i, leaf in
                       enumerate(jax.tree_util.tree_leaves(snap[0]))}
            payload.update(
                {f"o{i}": leaf for i, leaf in
                 enumerate(jax.tree_util.tree_leaves(snap[1]))})
            self.endpoint.send("snapshot_ack", payload, seq=s)
            return True
        if msg.kind == "rollback":
            # another party failed: restore step-s-start state, discard
            # every staged/in-flight chunk, and let the scientist replay
            # from s.  One update per step still holds — the replayed
            # step's update is the only one applied for it.
            import jax
            import jax.numpy as jnp
            s = int(msg.seq)
            if s not in self._snaps:
                raise RuntimeError(
                    f"owner {self.owner.name}: no snapshot for step {s}")
            p_np, o_np = self._snaps[s]
            self.params = jax.tree.map(jnp.asarray, p_np)
            self.opt_state = jax.tree.map(jnp.asarray, o_np)
            self._plan.clear()
            self._inflight.clear()
            self._grad_acc, self._grads_seen = None, 0
            self.steps_done = s
            self._snaps = {s: (p_np, o_np)}
            if hasattr(self.endpoint, "reset_dedup"):
                self.endpoint.reset_dedup()
            self.endpoint.send("rollback_ack", {}, seq=s)
            return True
        raise RuntimeError(
            f"owner {self.owner.name}: unknown message kind {msg.kind!r}")

    # thread target --------------------------------------------------------
    def run(self):
        try:
            while self.handle(self.endpoint.recv()):
                pass
        except BaseException as e:            # noqa: BLE001 — surfaced by
            self.error = e                    # the session's recv timeout


# ---------------------------------------------------------------------------
# Party constructors for the two standard vertical layouts
# ---------------------------------------------------------------------------


def feature_parties(scientist_ds: VerticalDataset,
                    owner_ds: Dict[str, VerticalDataset]
                    ) -> Tuple[DataScientist, List[DataOwner]]:
    """Wrap ``make_vertical_mnist_parties``-style datasets (scientist
    labels + per-owner feature slices) as party objects."""
    sci = DataScientist(scientist_ds.ids, scientist_ds.data)
    owners = [DataOwner(name, ds.ids, ds.data)
              for name, ds in owner_ds.items()]
    return sci, owners


def sequence_parties(tokens: np.ndarray, n_owners: int,
                     ids: Optional[Sequence[str]] = None,
                     with_labels: bool = True
                     ) -> Tuple[DataScientist, List[DataOwner]]:
    """Vertically partition token streams across sequence-slice owners.

    ``tokens``: (N, S+1) when ``with_labels`` (inputs ``[:, :-1]``, the
    scientist keeps next-token labels ``[:, 1:]``), else (N, S) raw
    contexts (serving: the scientist holds no labels).  Owner p receives
    the contiguous sequence slice [p*S/P, (p+1)*S/P) of every document."""
    tokens = np.asarray(tokens)
    if with_labels:
        inputs, labels = tokens[:, :-1], tokens[:, 1:]
    else:
        inputs, labels = tokens, None
    ids = list(ids) if ids is not None else make_ids(len(tokens), "doc")
    slices = partition_sequence(inputs, n_owners)
    owners = [DataOwner(f"owner{p}", ids, slices[p])
              for p in range(n_owners)]
    return DataScientist(ids, labels), owners
