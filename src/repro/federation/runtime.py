"""Party worker harness: one OS process per data owner.

``process_transport`` provides the boundary; this module provides the
*parties* on its far side.  Each worker is a spawned process (spawn, not
fork: the parent holds live XLA/threading state once jax is loaded, and
spawn re-imports only the target module's dependency chain) that builds
its party actor from a picklable spec and runs the exact same actor loop
the thread backend runs:

  * :func:`owner_worker_main` — rebuilds the owner's
    :class:`~repro.federation.parties.OwnerComputeEndpoint` inside the
    worker: the registry adapter is reconstructed from the (dataclass)
    model config, head programs re-jit in the worker's own XLA runtime,
    and the owner's current head params arrive as numpy leaves.  Only
    cut activations/gradients ever cross back.
  * :func:`psi_worker_main` — a jax-free
    :class:`~repro.federation.psi_transport.PSIServerEndpoint` actor
    (the PSI stack imports no jax, so these workers stay numpy-light).
  * :class:`WorkerHandle` — the parent-side view: the duplex
    :class:`~repro.federation.process_transport.ProcessEndpoint`, the
    ``Process``, and the crash-surfacing ``error`` property the
    session's receive polls check (poison-pill frame, or a nonzero exit
    code for deaths too sudden to send one).

Worker lifecycle (docs/WIRE_PROTOCOL.md §5): spawn -> warmup handshake
(driven by the session over the pipe, compiling every program before the
timed region) -> steady-state protocol -> ``stop`` / ``psi_stop`` ->
drain + exit 0.  A worker that throws ships one final
``__worker_error__`` frame with its traceback and exits 1.

Chaos hooks: ``REPRO_CHAOS_PARTY`` carries a ``federation.faults``
:class:`~repro.federation.faults.FaultPlan` (legacy single tokens like
``"<party>:crash_fwd"``, comma-separated multi-party specs, or a
``json:`` plan) injected inside the named workers.  Spawned children
inherit the parent's environment, so tests set it with
``monkeypatch.setenv`` — the only way to reach inside a spawned process
that a parent-side monkeypatch cannot touch.
"""
from __future__ import annotations

import traceback
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

from repro.federation import faults
from repro.federation.faults import CHAOS_ENV  # noqa: F401 — re-export
from repro.federation.process_transport import ProcessEndpoint

__all__ = ["OwnerWorkerSpec", "PSIWorkerSpec", "WorkerHandle",
           "owner_worker_main", "psi_worker_main",
           "spawn_owner_worker", "spawn_psi_worker", "CHAOS_ENV"]

SCIENTIST = "scientist"


def _chaos_action(name: str) -> Optional[str]:
    """Back-compat view of the env fault plan: the legacy action token
    (``crash_fwd`` / ``wedge_fwd`` / ``crash_psi`` / ``wedge_psi``) for
    ``name``, or ``None``.  Accepts comma-separated multi-party specs —
    the plan *is* the serialization now; this is just its one-token
    projection."""
    for f in faults.plan_from_env().for_party(name):
        key = (f.action, f.kind)
        if key in faults._LEGACY_INV:
            return faults._LEGACY_INV[key]
    return None


def _mp_context():
    import multiprocessing as mp
    return mp.get_context("spawn")


# ---------------------------------------------------------------------------
# Worker specs (picklable: dataclass configs + numpy arrays + scalars)
# ---------------------------------------------------------------------------


@dataclass
class OwnerWorkerSpec:
    """Everything a spawned owner worker needs to reconstruct its party.

    ``config`` is the registry model config (``MLPSplitConfig`` /
    ``ArchConfig`` — frozen dataclasses, cheap pickles); ``param_leaves``
    are the owner's current head-segment params flattened to numpy in
    canonical tree-leaf order (the worker rebuilds the tree against the
    structure of a reference slice from ``adapter.init``, so no treedef
    crosses the boundary)."""

    name: str
    ids: List[str]
    features: np.ndarray
    owner_index: int
    config: object
    init_seed: int
    param_leaves: List[np.ndarray] = field(default_factory=list)
    codec: Optional[str] = None
    microbatches: int = 1
    ack_steps: bool = False
    owner_lr: Optional[float] = None
    latency_s: float = 0.0
    bandwidth_bps: Optional[float] = None
    #: optimizer-state leaves for a respawn resuming mid-run (None: the
    #: worker initializes fresh state from its params, the PR 6 path)
    opt_state_leaves: Optional[List[np.ndarray]] = None
    #: the step counter to resume at (respawned workers must stage the
    #: replayed step's forwards, not step 0's)
    start_step: int = 0
    #: worker generation: 0 for first launch; respawns bump it, so
    #: generation-0 faults (the legacy default) don't re-fire
    generation: int = 0
    #: secure forward aggregation: "masked_sum" builds a
    #: ``core.masking.MaskedAggregator`` in the worker (root seed from
    #: the env channel ``REPRO_MASK_SEED``, default the init seed — the
    #: scientist-side spec never carries the root); None = plain cuts
    aggregation: Optional[str] = None
    #: total owner count — the mask cancellation set (>= 2 for masked)
    n_owners: int = 0
    #: owner-side Titcombe wire defence (deterministic, seeded on
    #: init_seed so replay after recovery re-derives identical noise)
    cut_noise_std: float = 0.0


@dataclass
class PSIWorkerSpec:
    """A PSI server actor's world: the owner's ID set + group geometry.
    Import chain is jax-free end to end.

    ``beta`` and the content-tag cache snapshots rehydrate the owner's
    persistent PSI state into the (otherwise stateless) spawned worker:
    in a real deployment the owner's process is long-lived, so a fresh
    worker per round must reproduce byte-identical response legs (same
    secret, same deterministic shuffle) and honor caches from earlier
    rounds — otherwise repeat resolves re-ship full legs."""

    name: str
    ids: List[str]
    group: str
    fp_rate: float = 1e-9
    latency_s: float = 0.0
    bandwidth_bps: Optional[float] = None
    generation: int = 0
    beta: Optional[int] = None
    blind_cache: Optional[dict] = None
    resp_cache: Optional[dict] = None
    lift_cache: Optional[dict] = None
    # precomputed response-side state (owner-side precompute, performed
    # on the owner's persistent PSIServer at spawn): packed blinded own
    # set, its shuffle->row map, and the per-item element cache
    own_packed: Optional[bytes] = None
    own_rows: Optional[List[int]] = None
    own_elems: Optional[dict] = None


# ---------------------------------------------------------------------------
# Worker mains (top-level functions: spawn pickles them by reference)
# ---------------------------------------------------------------------------


def _run_worker(spec, conn, body) -> None:
    """Shared worker scaffold: endpoint up, body, poison pill + exit 1
    on any failure, clean close + exit 0 otherwise.  (The exit code only
    makes sense process-side; the in-process thread harness just ends
    the thread after the pill ships.)"""
    import threading

    ep = ProcessEndpoint(spec.name, SCIENTIST, conn,
                         latency_s=spec.latency_s,
                         bandwidth_bps=spec.bandwidth_bps)
    # wire faults (drop/corrupt/delay) on everything this worker sends
    faults.arm_endpoint(ep, spec.name,
                        generation=getattr(spec, "generation", 0))
    try:
        body(spec, ep)
    except BaseException as e:              # noqa: BLE001 — shipped to
        ep.send_error(e, traceback.format_exc())   # the parent's poll
        ep.close()
        if threading.current_thread() is threading.main_thread():
            raise SystemExit(1)
        return
    ep.close()


def _owner_body(spec: OwnerWorkerSpec, ep: ProcessEndpoint) -> None:
    import jax

    from repro.federation.parties import DataOwner, OwnerComputeEndpoint
    from repro.federation.registry import build_adapter
    from repro.federation.transport import get_codec

    adapter = build_adapter(spec.config)
    p = spec.owner_index
    # reference slice for the param-tree structure only: init is
    # deterministic per (config, seed), so the structure — and, for a
    # fresh session, the values — match the parent's exactly
    template = adapter.owner_param_slice(
        adapter.init(jax.random.PRNGKey(spec.init_seed)), p)
    params = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(template),
        [jax.numpy.asarray(leaf) for leaf in spec.param_leaves])
    owner = DataOwner(spec.name, spec.ids, spec.features)
    owner_opt, owner_update = adapter.owner_update_rule(spec.owner_lr)
    head_fwd, head_bwd = adapter.owner_programs(p)
    opt_state = None
    if spec.opt_state_leaves is not None:
        # a respawn resumes the snapshotted optimizer state verbatim
        opt_state = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(owner_opt.init(params)),
            [jax.numpy.asarray(leaf) for leaf in spec.opt_state_leaves])
    masker = None
    if spec.aggregation == "masked_sum":
        from repro.core import masking
        masker = masking.MaskedAggregator(
            masking.mask_root_from_env(spec.init_seed), p, spec.n_owners,
            adapter.quant_program(), generation=spec.generation)
    worker = OwnerComputeEndpoint(
        owner, ep, head_fwd, head_bwd, optimizer=owner_opt,
        params=params, codec=get_codec(spec.codec),
        ack_steps=spec.ack_steps, microbatches=spec.microbatches,
        gather=adapter.gather_program(), update_program=owner_update,
        tail_program=adapter.owner_tail_rule(spec.owner_lr, p),
        opt_state=opt_state, start_step=spec.start_step,
        masker=masker, cut_noise_std=spec.cut_noise_std,
        noise_seed=spec.init_seed)
    _arm_chaos(worker, spec.name, generation=spec.generation)
    worker.run()
    if worker.error is not None:
        raise worker.error


def owner_worker_main(spec: OwnerWorkerSpec, conn) -> None:
    """Spawn target for an owner compute worker (also runnable on a
    thread against a pipe end — the in-process harness tests use that to
    exercise this exact code path under the tracer)."""
    _run_worker(spec, conn, _owner_body)


def _psi_body(spec: PSIWorkerSpec, ep: ProcessEndpoint) -> None:
    from repro.core.psi import PSIServer
    from repro.federation.psi_transport import PSIServerEndpoint

    server = PSIServer(spec.ids, spec.fp_rate, spec.group, beta=spec.beta)
    if spec.own_packed is not None:
        server._own_packed = spec.own_packed
        server._own_rows = list(spec.own_rows or [])
        server._own_elems = dict(spec.own_elems or {})
    actor = PSIServerEndpoint(spec.name, server, ep,
                              blind_cache=dict(spec.blind_cache or {}),
                              resp_cache=dict(spec.resp_cache or {}),
                              lift_cache=dict(spec.lift_cache or {}))
    _arm_chaos(actor, spec.name, generation=spec.generation)
    actor.run()
    if actor.error is not None:
        raise actor.error


def psi_worker_main(spec: PSIWorkerSpec, conn) -> None:
    """Spawn target for a PSI server actor (jax-free)."""
    _run_worker(spec, conn, _psi_body)


def _arm_chaos(actor, name: str, *, generation: int = 0) -> None:
    """Wrap ``actor.handle`` with the env fault plan's crash/wedge
    faults for ``name`` (kind targeting lives in the plan — an owner
    actor armed with a ``psi_blind_chunk`` fault simply never sees the
    kind, matching the old suffix dispatch)."""
    faults.arm_actor(actor, name, generation=generation)


# ---------------------------------------------------------------------------
# Parent side
# ---------------------------------------------------------------------------


class WorkerHandle:
    """The scientist's view of one spawned party worker.

    Duck-types the interfaces the session's crash-surfacing polls
    already use: ``error`` (the thread actors' parked-exception slot),
    ``name``, and ``owner`` (the parent-side party object).  ``error``
    reads the poison pill off the endpoint when one arrived, else maps
    an unexpected nonzero/dead exit code to a ``RuntimeError``."""

    def __init__(self, name: str, proc, endpoint: ProcessEndpoint,
                 owner=None):
        self.name = name
        self.proc = proc
        self.endpoint = endpoint
        self.owner = owner

    @property
    def error(self) -> Optional[BaseException]:
        if self.endpoint.peer_error is not None:
            return self.endpoint.peer_error
        code = self.proc.exitcode
        if code not in (None, 0):
            return RuntimeError(
                f"party worker {self.name!r} exited with code {code}")
        return None

    def shutdown(self, timeout: float = 10.0) -> None:
        """Drain + join; escalate to terminate if the worker is stuck.
        Idempotent — safe in ``finally`` blocks."""
        self.proc.join(timeout=timeout)
        if self.proc.is_alive():
            self.proc.terminate()
            self.proc.join(timeout=5.0)
        self.endpoint.close()

    def __repr__(self):
        state = ("alive" if self.proc.is_alive()
                 else f"exit={self.proc.exitcode}")
        return f"WorkerHandle({self.name!r}, {state})"


def _spawn(name: str, main, spec, *, owner=None, tap=None,
           dedup: bool = False) -> WorkerHandle:
    ctx = _mp_context()
    parent_conn, child_conn = ctx.Pipe(duplex=True)
    proc = ctx.Process(target=main, args=(spec, child_conn), daemon=True,
                       name=f"party-{name}")
    proc.start()
    child_conn.close()          # the child owns its end now
    ep = ProcessEndpoint(SCIENTIST, name, parent_conn,
                         latency_s=spec.latency_s,
                         bandwidth_bps=spec.bandwidth_bps, tap=tap,
                         dedup=dedup)
    return WorkerHandle(name, proc, ep, owner=owner)


def spawn_owner_worker(spec: OwnerWorkerSpec, *, owner=None, tap=None,
                       dedup: bool = False) -> WorkerHandle:
    """Spawn one owner compute worker; returns the parent-side handle
    (its ``endpoint`` is the scientist's end of the party boundary).
    ``dedup`` turns on seq-based duplicate drop on the parent's receive
    path — the supervised fit path uses it so a restarted worker's
    replayed frames are idempotent.

    Each worker initializes its own JAX backend, so the workers can only
    share the host's CPU: an accelerator held by this (parent) process
    cannot be opened again by a child, which would fail or hang.  On any
    other backend this raises before spawning."""
    import jax

    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"backend='process' cannot spawn owner {spec.name!r}: this "
            f"process holds the {jax.default_backend()} device, and a "
            "spawned owner worker cannot open it. Use backend='queue' or "
            "'direct' on an accelerator (owners on their own chips is "
            "ROADMAP item R5).")
    return _spawn(spec.name, owner_worker_main, spec, owner=owner,
                  tap=tap, dedup=dedup)


def spawn_psi_worker(owner, *, group: str, fp_rate: float = 1e-9,
                     latency_s: float = 0.0,
                     bandwidth_bps: Optional[float] = None,
                     tap=None, generation: int = 0,
                     pool=None) -> WorkerHandle:
    """Spawn one PSI server actor for ``owner`` (a
    :class:`~repro.federation.parties.DataOwner`).  ``generation``
    increments on retry, so generation-0 faults don't re-fire.

    The spec rehydrates the owner's persistent PSI state (β, blinded
    own set, content-tag caches) into the fresh worker — a stand-in for
    the long-lived owner process of a real deployment, and what keeps
    repeat/churned rounds O(Δ) on the process backend.  The own-set
    blinding runs on the owner's persistent server at spawn (``pool``
    parallelizes it), so respawns and retries never repeat it."""
    key = (group, fp_rate)
    srv = owner.psi_server(group, fp_rate)   # synced to the population
    srv.own_blinded_packed(pool)             # O(Δ new items) after churn
    spec = PSIWorkerSpec(name=owner.name, ids=list(srv.items),
                         group=group, fp_rate=fp_rate,
                         latency_s=latency_s, bandwidth_bps=bandwidth_bps,
                         generation=generation,
                         beta=srv._beta,
                         blind_cache=dict(
                             owner._psi_blind_caches.setdefault(key, {})),
                         resp_cache=dict(
                             owner._psi_resp_caches.setdefault(key, {})),
                         lift_cache=dict(
                             owner._psi_lift_caches.setdefault(key, {})),
                         own_packed=srv._own_packed,
                         own_rows=srv._own_rows,
                         own_elems=srv._own_elems)
    return _spawn(spec.name, psi_worker_main, spec, owner=owner, tap=tap)
