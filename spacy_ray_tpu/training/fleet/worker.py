"""One trainer-fleet process: the asynchronous pull → grad → push →
apply-wait loop (PAPER.md §L3/L4, reference worker.py:117-155).

Each of the N processes:

* computes gradients on ITS OWN corpus shard
  (:func:`~..batcher.shard_stream` by worker id — the per-rank data
  sharding the reference lacked);
* pushes the non-owned shard gradients to their owners, fire-and-forget
  with a bounded :class:`~..resilience.RetryPolicy` (a dead peer costs a
  counted drop, never a stall);
* feeds its OWN shard's gradients to its local :class:`~.peer.OwnerState`,
  which applies the optimizer at quorum and bumps the shard version;
* blocks (apply-wait) until its own shard's version passes the stamp it
  pushed against — bounded by ``quorum_wait_s`` so a lost quorum degrades
  to a counted timeout, not a wedge;
* pulls newer shard bytes from the other owners at the top of the next
  step.

Gradient-clip semantics: with a fusable optimizer (Adam.v1 / RAdam.v1)
the global-norm clip runs WORKER-SIDE over the full gradient tree
(exact global norm of that worker's gradient) and the owner applies a
clip-free fused chain on its slice — the one optimizer stage that needs
the whole tree moves to where the whole tree lives. The owner's state
STRUCTURE still delegates to the reference chain, so fleet part files
reassemble into exactly the canonical state a synchronous run resumes
from (the clip element's state is empty). Non-fusable optimizers run
their full chain per-shard (per-shard clip — documented caveat,
TUNING.md §19).

Per-phase wall time (data / pull / grad / push / apply_wait) is
accounted every step and lands on the per-worker
result file ``fleet-worker-{k}.json`` (which doubles as the CI failure
artifact's discard-counter ledger).
"""

from __future__ import annotations

import http.client
import json
import logging
import socket
import threading
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple
from urllib.parse import urlparse

import numpy as np

from ...registry import registry
from ..batcher import bucket_batch_size, bucket_length, shard_stream
from ..checkpoint import (
    CheckpointCorrupt,
    TrainCheckpoint,
    commit_fleet_generation,
    write_fleet_opt_part,
)
from .. import resilience
from ..resilience import (
    RetryPolicy,
    ShutdownCoordinator,
    Watchdog,
    log_event,
    maybe_fail,
    retry_io,
)
from .membership import (
    LeaseTracker,
    Membership,
    MembershipLedger,
    PeerBackoff,
)
from .ownership import (
    local_opt_from_canonical,
    opt_part_records,
)
from .peer import FleetCounters, OwnerState, PeerServer
from .wire import (
    GradCompressor,
    WireError,
    decode_arrays,
    decode_delta_frame,
    encode_arrays,
    negotiate_push_codec,
    resolve_grad_compression,
)

logger = logging.getLogger("spacy_ray_tpu.training")

DEFAULT_FLEET_BASE_PORT = 47200
PHASES = ("data", "pull", "grad", "push", "apply_wait")

__all__ = [
    "DEFAULT_FLEET_BASE_PORT",
    "PHASES",
    "resolve_quorum",
    "train_fleet_worker",
]


def resolve_quorum(quorum: Optional[int], n_workers: int) -> int:
    """0/None = auto: all-but-one (min 1) — the fleet keeps stepping
    through a single crashed peer (the supervisor restarts it) while
    still averaging nearly every worker's gradient."""
    if not quorum:
        return max(1, int(n_workers) - 1)
    return int(quorum)


class _PeerClient:
    """Minimal persistent HTTP client for one peer (keep-alive, one
    reconnect on a dead socket, every failure surfaced as OSError so
    ``retry_io`` treats the whole family as transient)."""

    def __init__(self, url: str, timeout: float = 10.0) -> None:
        parsed = urlparse(url)
        if parsed.scheme != "http":
            raise ValueError(f"fleet peers speak plain http, got {url!r}")
        self.host = parsed.hostname or "127.0.0.1"
        self.port = int(parsed.port or 80)
        self.timeout = float(timeout)
        self._conn: Optional[http.client.HTTPConnection] = None

    def _connection(self) -> http.client.HTTPConnection:
        if self._conn is None:
            self._conn = http.client.HTTPConnection(
                self.host, self.port, timeout=self.timeout
            )
        return self._conn

    def close(self) -> None:
        if self._conn is not None:
            try:
                self._conn.close()
            except OSError:
                pass
            self._conn = None

    def request(
        self,
        method: str,
        path: str,
        body: Optional[bytes] = None,
        content_type: str = "application/octet-stream",
        headers: Optional[Dict[str, str]] = None,
    ) -> Tuple[int, Dict[str, str], bytes]:
        last: Optional[Exception] = None
        for attempt in (0, 1):  # one transparent reconnect on a dead socket
            conn = self._connection()
            try:
                hdrs = {"Content-Type": content_type} if body else {}
                if headers:
                    hdrs.update(headers)
                conn.request(method, path, body=body, headers=hdrs)
                resp = conn.getresponse()
                payload = resp.read()
                return resp.status, dict(resp.getheaders()), payload
            except (http.client.HTTPException, OSError, socket.timeout) as e:
                last = e
                self.close()
        raise OSError(f"peer {self.host}:{self.port} unreachable: {last}")


def _np_tree(tree: Any) -> Any:
    """Deep host copy: every leaf a fresh mutable np.ndarray."""
    if isinstance(tree, dict):
        return {k: _np_tree(v) for k, v in tree.items()}
    return np.array(np.asarray(tree))


def train_fleet_worker(
    config: Any,
    output_path: Optional[Path] = None,
    *,
    worker_id: int,
    n_workers: int,
    quorum: int = 0,
    max_staleness: int = 1,
    base_port: int = DEFAULT_FLEET_BASE_PORT,
    port: Optional[int] = None,
    peer_urls: Optional[List[str]] = None,
    bind_host: str = "127.0.0.1",
    resume: bool = False,
    stdout_log: bool = True,
    metrics_dir: Optional[Path] = None,
    metrics_port: Optional[int] = None,
    max_steps_override: Optional[int] = None,
    install_signal_handlers: bool = True,
    quorum_wait_s: float = 30.0,
    push_retries: int = 1,
    peer_wait_s: float = 120.0,
    finalize_wait_s: float = 600.0,
    checkpoint_timeout_s: float = 600.0,
    peer_lease_s: float = 60.0,
    lease_miss_threshold: int = 3,
    lease_poll_s: float = 2.0,
    peer_timeout_s: Optional[float] = None,
    probe_timeout_s: Optional[float] = None,
    watch_interval_s: float = 5.0,
    alert_interval_s: float = 5.0,
    grad_compression: str = "auto",
    param_delta_window: int = 4,
    grad_error_feedback: bool = True,
) -> Tuple[Any, Any]:
    """Run ONE fleet worker process; returns ``(nlp, TrainResult)`` like
    :func:`~..loop.train` (``train --fleet-worker-id`` calls this in its
    place).

    ``metrics_port`` is unused (the peer server IS the telemetry
    endpoint — one port per worker, ``base_port + worker_id``); accepted
    so the CLI plumbing stays uniform.

    ``grad_compression`` picks the push codec (``auto`` resolves per
    backend, TUNING.md §20); ``param_delta_window`` is the owner-side K
    for version-delta pulls (0 = PR 14 full pulls). Both degrade to f32
    against peers that don't advertise the codec.
    ``grad_error_feedback=False`` is the ablation control the
    convergence suite uses — never turn it off for real runs (sub-step
    gradient signal then quantizes to zero forever).

    ``peer_lease_s`` arms elastic membership (RESILIENCE.md "Ownership
    failover"): every worker leases its peers off ``/healthz``; the
    acting lead (lowest live active id) evicts a peer whose lease
    expired AND that missed ``lease_miss_threshold`` consecutive
    probes, bumps the fleet-wide membership epoch, and survivors
    re-shard ownership over the remaining ids at their next step
    boundary. Set ``peer_lease_s=0`` to disable eviction entirely
    (PR 14 frozen-membership behavior). ``peer_timeout_s`` /
    ``probe_timeout_s`` override the ``[training]``
    ``fleet_peer_timeout_s`` / ``fleet_probe_timeout_s`` knobs for
    step-traffic and liveness-probe connections respectively.
    """
    import jax
    import jax.numpy as jnp

    from ...parallel import context as pctx
    from ...parallel.mesh import build_mesh
    from ...parallel.step import make_shard_apply
    from ...pipeline.language import Pipeline
    from .. import optimizers as _optimizers
    from ..loop import (
        TrainResult,
        default_pipeline_score_weights,
        resolve_dot_name,
        resolve_training,
        weighted_score,
    )

    if jax.process_count() > 1:
        raise ValueError(
            "the trainer fleet IS the multi-process mode — run it on "
            "single-process jax (one fleet worker per process), not under "
            "jax.distributed"
        )
    worker_id = int(worker_id)
    n_workers = int(n_workers)
    if not (0 <= worker_id < n_workers):
        raise ValueError(
            f"fleet worker id {worker_id} outside [0, {n_workers})"
        )
    quorum_requested = int(quorum or 0)
    quorum = resolve_quorum(quorum, n_workers)
    if not (1 <= quorum <= n_workers):
        raise ValueError(f"quorum {quorum} outside [1, {n_workers}]")

    def _quorum_for(n_active: int) -> int:
        """The effective quorum after a membership change: auto re-auto-
        resolves over the survivor count; an explicit quorum is clamped
        so a shrunken fleet can still reach it."""
        if quorum_requested <= 0:
            return resolve_quorum(0, n_active)
        return max(1, min(quorum_requested, n_active))

    max_staleness = int(max_staleness)
    if max_staleness < 0:
        raise ValueError(f"max_staleness must be >= 0, got {max_staleness}")

    config = config.interpolate()
    T = resolve_training(config)
    if int(T.get("accumulate_gradient") or 1) != 1:
        raise ValueError(
            "fleet mode: accumulate_gradient > 1 is not supported — the "
            "quorum IS the accumulation (the reference folds them "
            "together too; SURVEY.md §2.4)"
        )
    if T.get("annotating_components"):
        raise ValueError(
            "fleet mode does not support annotating_components yet"
        )
    if T.get("frozen_components"):
        raise ValueError(
            "fleet mode does not support frozen_components yet (the "
            "optax.masked mask is built over the full tree and cannot "
            "follow owner-shard slices)"
        )

    seed = int(T.get("seed") or 0)
    import random as _random

    _random.seed(seed)
    np.random.seed(seed)

    resilience.activate_env_fault_plan()
    resilience.drain_events()
    resilience.set_default_retry_policy(
        RetryPolicy(
            max_retries=int(T.get("io_retries", 3) or 0),
            base_delay=float(T.get("io_retry_base_s", 0.5) or 0.5),
        )
    )
    push_policy = RetryPolicy(
        max_retries=max(int(push_retries), 0), base_delay=0.05, max_delay=1.0
    )
    shutdown = ShutdownCoordinator()
    # per-peer connection deadlines: explicit kwargs win, then the
    # [training] knobs, then the historical constants (10s step traffic,
    # 5s liveness probes — same precedence as checkpoint_timeout_s)
    peer_timeout = float(
        peer_timeout_s if peer_timeout_s is not None
        else T.get("fleet_peer_timeout_s") or 10.0
    )
    probe_timeout = float(
        probe_timeout_s if probe_timeout_s is not None
        else T.get("fleet_probe_timeout_s") or 5.0
    )
    if peer_timeout <= 0 or probe_timeout <= 0:
        raise ValueError(
            "fleet_peer_timeout_s / fleet_probe_timeout_s must be > 0"
        )
    peer_lease_s = float(peer_lease_s)
    lease_miss_threshold = max(1, int(lease_miss_threshold))
    lease_poll_s = max(0.2, float(lease_poll_s))

    # ---- telemetry (per-worker sub-directory; the peer server serves it)
    tel = None
    tel_dir = str(metrics_dir) if metrics_dir is not None else str(
        T.get("metrics_dir") or ""
    )
    if tel_dir:
        from ...alerting import default_training_rules
        from ..telemetry import Telemetry

        trace_steps = T.get("trace_steps") or [0, 50]
        tel = Telemetry(
            Path(tel_dir) / f"fleet-worker-{worker_id}",
            trace_steps=(int(trace_steps[0]), int(trace_steps[1])),
            anomaly_detection=bool(T.get("anomaly_detection", True)),
            process_index=worker_id,
            alerting=bool(T.get("alerting", True)),
            alert_rules=default_training_rules(fleet=True),
            alert_interval_s=float(alert_interval_s),
            incident_dir=(
                Path(str(T.get("incident_dir")))
                if T.get("incident_dir") else None
            ),
            process_name=f"fleet-worker-{worker_id}",
        )
        tel.registry.gauge("fleet_worker").set(worker_id)

    # ---- corpora / pipeline -----------------------------------------
    corpora_cfg = config.get("corpora", {})
    resolved_corpora = {
        name: registry.resolve(block) for name, block in corpora_cfg.items()
    }
    train_corpus = resolve_dot_name(config, resolved_corpora, T["train_corpus"])
    dev_corpus = resolve_dot_name(config, resolved_corpora, T["dev_corpus"])
    nlp = Pipeline.from_config(config)
    nlp.initialize(train_corpus, seed=seed)

    mesh = build_mesh(n_data=1)
    tx = registry.resolve(T.get("optimizer") or {"@optimizers": "Adam.v1"})
    use_averages = bool(getattr(tx, "use_averages", False))
    if use_averages:
        raise ValueError(
            "fleet mode does not support use_averages (the running mean "
            "needs every post-apply param tree on one host)"
        )
    meta = getattr(tx, "fusable", None)
    if meta:
        # worker-side exact global-norm clip; owner applies the clip-free
        # fused chain on its slice (state structure delegates to the
        # reference chain, so checkpoints stay canonical)
        worker_clip = float(meta.get("grad_clip") or 0.0)
        from ...ops.fused_update import make_fused_transformation

        fused = make_fused_transformation(
            reference_tx=tx.tx, **{**meta, "grad_clip": 0.0}
        )
        owner_tx = _optimizers.OptimizerWrapper(fused)
        owner_tx.applies_updates = True
    else:
        worker_clip = 0.0
        owner_tx = tx
        log_event(
            "fleet-per-shard-optimizer",
            "optimizer is not fusable: the full chain (including any "
            "global-norm clip) runs PER OWNER SHARD — clip norms are "
            "shard-local, not global (TUNING.md §19)",
        )

    batcher = registry.resolve(
        T.get("batcher")
        or {"@batchers": "spacy.batch_by_words.v1", "size": 1000,
            "tolerance": 0.2}
    )
    dropout = float(T["dropout"])
    loss_fn = nlp.make_loss_fn(dropout=dropout)

    params_host = _np_tree(nlp.params)
    membership = Membership(range(n_workers))
    layout = membership.layout(params_host)

    # ---- state (fresh or resumed) -----------------------------------
    step = 0
    epoch = 0
    best_score = -1.0
    best_step = -1
    version = 0
    rng = jax.random.fold_in(jax.random.PRNGKey(seed), worker_id)
    resumed_from: Optional[int] = None
    ckpt = None
    if resume and output_path is not None:
        try:
            ckpt = TrainCheckpoint.load(Path(output_path) / "last-model")
        except CheckpointCorrupt as e:
            log_event(
                "resume-failed",
                f"--resume found no intact checkpoint generation ({e}); "
                "starting from scratch",
            )
    if ckpt is not None:
        params_host = _np_tree(ckpt["params"])
        step = int(ckpt["step"])
        epoch = int(ckpt["epoch"])
        best_score = float(ckpt["best_score"])
        best_step = int(ckpt["best_step"])
        resumed_from = step
        fleet_extra = (ckpt.get("extra") or {}).get("fleet") or {}
        ck_active = fleet_extra.get("active")
        if ck_active:
            # the checkpoint carries the membership it was committed
            # under — resume into THAT fleet, not the config's nominal
            # one (a pre-elastic checkpoint has no such field: epoch 0,
            # everyone active)
            try:
                membership = Membership(
                    [int(a) for a in ck_active],
                    int(fleet_extra.get("epoch") or 0),
                )
                layout = membership.layout(params_host)
            except (TypeError, ValueError) as e:
                log_event(
                    "fleet-resume-membership-invalid",
                    f"checkpoint extra.fleet.active is malformed ({e}); "
                    "assuming the full nominal fleet at epoch 0",
                    worker=worker_id,
                )
        versions = fleet_extra.get("versions") or []
        if worker_id < len(versions) and versions[worker_id] is not None:
            version = int(versions[worker_id])
        rngs = fleet_extra.get("rngs") or []
        if worker_id < len(rngs) and rngs[worker_id] is not None:
            rng = jnp.asarray(np.array(rngs[worker_id], dtype=np.uint32))
        else:
            rng = jax.random.fold_in(
                jnp.asarray(
                    np.array(
                        np.asarray(jax.device_get(ckpt["rng"])),
                        dtype=np.uint32,
                    )
                ),
                worker_id,
            )
        log_event(
            "fleet-resume",
            f"worker {worker_id} resumed from checkpoint step {step} "
            f"(shard version {version})",
            worker=worker_id, step=step, version=version,
        )

    quorum = _quorum_for(len(membership.active))
    slice_np = layout.slice_tree(params_host, worker_id)
    slice_params = jax.tree_util.tree_map(jnp.asarray, slice_np)
    if ckpt is not None and worker_id in membership:
        opt_local = local_opt_from_canonical(
            owner_tx, layout, ckpt["opt_state"], worker_id, slice_np
        )
    else:
        opt_local = owner_tx.init(slice_params)
    ckpt = None  # drop the loaded canonical trees

    owns_any = bool(layout.owned_keys(worker_id))
    if worker_id not in membership:
        # resumed from a checkpoint committed AFTER our eviction: we are
        # a returning member, not a config error — the join flow below
        # asks the acting lead to admit us at the next epoch boundary;
        # until the admit broadcast lands, every push is epoch-fenced
        # (counted) at the owners
        log_event(
            "fleet-resume-evicted",
            f"worker {worker_id} resumed into membership epoch "
            f"{membership.epoch} which no longer names it (active "
            f"{list(membership.active)}) — requesting rejoin",
            worker=worker_id, epoch=membership.epoch,
            active=list(membership.active),
        )
    elif not owns_any:
        # legal but degenerate (no leaf axis divisible by n_workers
        # beyond worker 0's whole-leaf ownership): this worker
        # contributes gradients to the owners but its own shard is empty
        # — its version never moves, so it must not quorum-wait on it
        log_event(
            "fleet-worker-owns-nothing",
            f"worker {worker_id} owns no parameter slices at "
            f"n_workers={n_workers} (no axis divisible); it will push "
            "gradients but apply nothing — consider fewer workers",
            worker=worker_id, n_workers=n_workers,
        )
    # ---- wire compression (ROADMAP item 3: the bandwidth plane) ------
    # one resolved codec per process; the ACTUAL codec per peer is
    # negotiated at push time against what its /healthz advertises, so
    # a mixed fleet (an old f32-only worker among compressed ones)
    # interoperates — it just gets f32 frames
    wire_codec, wire_reason = resolve_grad_compression(
        grad_compression, jax.default_backend()
    )
    param_delta_window = max(0, int(param_delta_window))
    compressor = GradCompressor(
        wire_codec, error_feedback=bool(grad_error_feedback)
    )
    peer_codecs: Dict[int, Any] = {}
    log_event(
        "fleet-wire-codec",
        f"worker {worker_id}: grad compression {grad_compression} -> "
        f"{wire_codec} ({wire_reason}); param delta window "
        f"{param_delta_window}",
        worker=worker_id, codec=wire_codec, delta_window=param_delta_window,
    )
    counters = FleetCounters(
        registry=tel.registry if tel is not None else None
    )
    version_gauge = (
        tel.registry.gauge("param_version") if tel is not None else None
    )
    epoch_gauge = (
        tel.registry.gauge("membership_epoch") if tel is not None else None
    )
    if epoch_gauge is not None:
        epoch_gauge.set(membership.epoch)
    member_ledger = MembershipLedger(
        Path(output_path) / "fleet-membership.jsonl"
        if output_path is not None else None
    )
    backoff = PeerBackoff(
        base_s=1.0, cap_s=max(1.0, min(30.0, float(quorum_wait_s)))
    )
    # worker-side per-phase dynamics histograms (shared bucket tables —
    # docs/OBSERVABILITY.md "Training fleet"); telemetry off constructs
    # none of them (the zero-calls contract)
    phase_hists: Optional[Dict[str, Any]] = None
    if tel is not None:
        from ..telemetry import FLEET_DYNAMICS_HISTOGRAMS

        phase_hists = {
            p: tel.registry.histogram(
                f"phase_{p}_seconds",
                buckets=FLEET_DYNAMICS_HISTOGRAMS[f"phase_{p}_seconds"],
            )
            for p in PHASES
        }
    owner = OwnerState(
        worker_id=worker_id,
        n_workers=n_workers,
        quorum=quorum,
        max_staleness=max_staleness,
        apply_fn=make_shard_apply(owner_tx),
        slice_params=slice_params,
        opt_state=opt_local,
        counters=counters,
        version=version,
        on_version=(version_gauge.set if version_gauge is not None else None),
        registry=tel.registry if tel is not None else None,
        trace=tel.trace if tel is not None else None,
        delta_window=param_delta_window,
        delta_codec=wire_codec,
    )

    # mutable holders the checkpoint callback (handler thread) reads
    state_holder: Dict[str, Any] = {"step": step, "rng": rng}

    def checkpoint_cb(ckpt_dir: str, stamp: int) -> Dict[str, Any]:
        # snapshot the membership-dependent pieces once: the step loop
        # may swap layout/membership at its next boundary while this
        # handler-thread call is in flight
        lay, member = layout, membership
        rank = lay.rank_of(worker_id)
        if rank is None:
            raise ValueError(
                f"worker {worker_id} is not in membership epoch "
                f"{member.epoch} — cannot contribute a checkpoint part"
            )

        def writer(cur_version, opt_state, host_flat):
            n_leaves, skeleton, records = opt_part_records(
                owner_tx, params_host, lay, opt_state, worker_id
            )
            digest = write_fleet_opt_part(
                ckpt_dir,
                stamp=stamp,
                part=rank,
                parts=len(member.active),
                n_leaves=n_leaves,
                records=records,
                skeleton=skeleton if rank == 0 else None,
            )
            return cur_version, digest, host_flat

        cur_version, digest, host_flat = owner.checkpoint_parts(writer)
        return {
            "meta": {
                "digest": digest,
                "version": cur_version,
                "part": rank,
                "step": int(state_holder["step"]),
                "rng": np.asarray(
                    jax.device_get(state_holder["rng"])
                ).tolist(),
            },
            "params": host_flat,
        }

    server = PeerServer(
        owner,
        worker_id=worker_id,
        layout_signature=layout.signature(),
        counters=counters,
        tel=tel,
        host=bind_host,
        port=int(port) if port is not None else int(base_port) + worker_id,
        checkpoint_cb=checkpoint_cb,
    )
    server.set_membership(membership, layout.signature())
    server.start()
    urls = list(peer_urls) if peer_urls is not None else [
        f"http://127.0.0.1:{int(base_port) + i}" for i in range(n_workers)
    ]
    if len(urls) != n_workers:
        raise ValueError(
            f"peer_urls names {len(urls)} workers, fleet has {n_workers}"
        )
    clients: Dict[int, _PeerClient] = {
        w: _PeerClient(urls[w], timeout=peer_timeout)
        for w in membership.active if w != worker_id
    }
    ckpt_clients: Dict[int, _PeerClient] = {}  # long-deadline, lazy

    # what each peer exchange WOULD cost as a PR 14 f32 frame — the
    # _uncompressed twin counters' source (slice shapes are static, so
    # one encode of the template per peer at startup is exact)
    wire_full_bytes: Dict[int, int] = {}
    for w in clients:
        flat_w = layout.flat_slices(params_host, w)
        if flat_w:
            wire_full_bytes[w] = len(encode_arrays(
                {"worker": worker_id, "stamp": 0},
                {k: np.asarray(v, np.float32) for k, v in flat_w.items()},
            ))

    drifted: set = set()  # peers seen at a different membership epoch

    def wait_for_peers() -> None:
        """Block until every peer answers /healthz with a matching
        layout signature. A COLD start that never sees its peers is a
        misconfiguration (wrong ports/config) and raises loudly; a
        REJOINING worker (supervisor restart with --resume) proceeds
        after a short wait instead — its peers may legitimately have
        finished and exited while it was down (their final state is in
        the checkpoint it just resumed), and every unreachable-peer
        push/pull from here on is a counted drop, not a crash."""
        rejoining = resumed_from is not None
        wait_s = min(float(peer_wait_s), 15.0) if rejoining else float(
            peer_wait_s
        )
        deadline = time.monotonic() + wait_s
        pending = set(clients)
        while pending:
            for w in sorted(pending):
                try:
                    status, _, body = clients[w].request("GET", "/healthz")
                except OSError:
                    continue
                if status != 200:
                    continue
                payload = json.loads(body.decode("utf8"))
                sig = payload.get("layout")
                if sig != layout.signature():
                    peer_epoch = payload.get("epoch")
                    if (
                        isinstance(peer_epoch, int)
                        and not isinstance(peer_epoch, bool)
                        and peer_epoch != membership.epoch
                    ):
                        # not a config error — the peer is at a different
                        # MEMBERSHIP epoch (the fleet re-sharded while we
                        # were down); the join/refresh flow reconciles
                        log_event(
                            "fleet-membership-drift",
                            f"worker {w} is at membership epoch "
                            f"{peer_epoch}, we are at {membership.epoch} "
                            "— syncing membership instead of failing "
                            "the layout check",
                            worker=worker_id, peer=w,
                            peer_epoch=peer_epoch,
                            epoch=membership.epoch,
                        )
                        drifted.add(w)
                        pending.discard(w)
                        continue
                    raise RuntimeError(
                        f"fleet worker {w} runs a different parameter "
                        f"layout ({sig} vs {layout.signature()}) — all "
                        "workers must resolve the same config"
                    )
                # what this peer can DECODE (absent on pre-compression
                # peers: they get f32 pushes)
                peer_codecs[w] = payload.get("codecs")
                pending.discard(w)
            if pending:
                if time.monotonic() > deadline:
                    if rejoining:
                        log_event(
                            "fleet-peers-unreachable",
                            f"rejoined worker {worker_id}: peers "
                            f"{sorted(pending)} unreachable after "
                            f"{wait_s:.0f}s — proceeding (they may have "
                            "finished; lost RPCs are counted)",
                            worker=worker_id, peers=sorted(pending),
                        )
                        return
                    raise RuntimeError(
                        f"fleet peers never became reachable: "
                        f"{sorted(pending)} (waited {wait_s:.0f}s)"
                    )
                time.sleep(0.1)

    # ---- elastic membership: refresh / join / epoch-fenced re-shard --
    _join_throttle = {"t": -(10.0 ** 9)}

    def request_join(m: Membership) -> None:
        """First-class rejoin: ask ``m``'s lead to admit us at the next
        epoch boundary. We keep training meanwhile — our pushes stay
        epoch-fenced (counted) at the owners until the admit broadcast
        lands. Throttled: the pull loop hits a fence every step while
        we are out, and one join request per few seconds is plenty."""
        now = time.monotonic()
        if now - _join_throttle["t"] < 5.0:
            return
        _join_throttle["t"] = now
        lead = m.lead
        if lead == worker_id:
            return
        client = clients.get(lead)
        if client is None:
            client = clients[lead] = _PeerClient(
                urls[lead], timeout=peer_timeout
            )
        try:
            client.request(
                "POST", "/membership/join",
                body=json.dumps({"worker": worker_id}).encode("utf8"),
                content_type="application/json",
            )
        except OSError:
            return
        member_ledger.append(
            "join-requested", worker=worker_id, epoch=m.epoch
        )
        log_event(
            "fleet-join-requested",
            f"worker {worker_id} asked lead {lead} to rejoin the fleet "
            f"(their membership epoch {m.epoch})",
            worker=worker_id, lead=lead, epoch=m.epoch,
        )

    def refresh_membership(w: int) -> None:
        """Sync membership off peer ``w`` after a fence/drift signal:
        adopt its view when newer (queued — the step boundary applies
        it), or request a join when it no longer names us. Step-loop
        thread only (it shares the keep-alive clients)."""
        client = clients.get(w)
        if client is None:
            return
        try:
            status, _, body = client.request("GET", "/membership")
            if status != 200:
                return
            m = Membership.from_wire(json.loads(body.decode("utf8")))
        except (OSError, ValueError, KeyError, UnicodeDecodeError):
            return
        if m.epoch <= membership.epoch:
            return
        if worker_id in m:
            server.queue_membership(m)
        else:
            request_join(m)

    def apply_membership(new_m: Membership) -> None:
        """The epoch-fenced re-shard, at a step boundary only: recompute
        ownership over the new active set (same first-divisible-axis
        rule, survivor-rank addressed), adopt re-owned slices (params
        from this worker's ``params_host`` — the owners' last broadcast
        versions — and optimizer state carved from the last intact fleet
        checkpoint, fresh-init fallback), swap the OwnerState, and stamp
        the new epoch on everything downstream. Handler threads only
        QUEUE memberships; this runs exclusively on the step loop."""
        nonlocal membership, layout, owner, owns_any, quorum
        old_m, old_layout = membership, layout
        was_active = worker_id in old_m
        version_base = owner.version
        if was_active:
            # fold the live owner shard into params_host first: its
            # quorum applies since the last pull must survive the swap
            _, self_flat = owner.current_flat()
            old_layout.merge_flat(params_host, worker_id, self_flat)
        old_index = {
            k: old_layout.key_index(k, worker_id)
            for k in (old_layout.owned_keys(worker_id) if was_active else ())
        }
        membership = new_m
        layout = membership.layout(params_host)
        quorum = _quorum_for(len(membership.active))
        now_active = worker_id in membership
        changed = [
            k for k in layout.owned_keys(worker_id)
            if k not in old_index
            or old_index[k] != layout.key_index(k, worker_id)
        ] if now_active else []
        slice_np = layout.slice_tree(params_host, worker_id)
        new_slice = jax.tree_util.tree_map(jnp.asarray, slice_np)
        new_opt = None
        opt_src = "fresh-init"
        if now_active and not changed:
            # geometry unchanged (pure join/evict of a worker we took
            # nothing from): keep the live optimizer moments
            def _grab(cur_version, opt_state, host_flat):
                return cur_version, opt_state, host_flat

            _, new_opt, _ = owner.checkpoint_parts(_grab)
            opt_src = "live"
        elif now_active and output_path is not None:
            try:
                ck2 = TrainCheckpoint.load(Path(output_path) / "last-model")
                new_opt = local_opt_from_canonical(
                    owner_tx, layout, ck2["opt_state"], worker_id, slice_np
                )
                opt_src = f"checkpoint@{int(ck2['step'])}"
            except (CheckpointCorrupt, OSError, KeyError, ValueError,
                    TypeError):
                new_opt = None
        if new_opt is None:
            new_opt = owner_tx.init(new_slice)
            if changed:
                log_event(
                    "fleet-opt-reinit",
                    f"worker {worker_id}: no intact fleet checkpoint to "
                    f"carve adopted optimizer state from — fresh moments "
                    f"for {len(changed)} re-sharded slices",
                    worker=worker_id, epoch=membership.epoch,
                    resharded=len(changed),
                )
        new_owner = OwnerState(
            worker_id=worker_id,
            n_workers=n_workers,
            quorum=quorum,
            max_staleness=max_staleness,
            apply_fn=make_shard_apply(owner_tx),
            slice_params=new_slice,
            opt_state=new_opt,
            counters=counters,
            version=version_base,
            on_version=(
                version_gauge.set if version_gauge is not None else None
            ),
            registry=tel.registry if tel is not None else None,
            trace=tel.trace if tel is not None else None,
            delta_window=param_delta_window,
            delta_codec=wire_codec,
        )
        owner = new_owner
        server.set_owner(new_owner)
        server.set_membership(membership, layout.signature())
        owns_any = bool(layout.owned_keys(worker_id))
        # clients follow the active set
        for w in [w for w in list(clients) if w not in membership]:
            clients.pop(w).close()
            gone = ckpt_clients.pop(w, None)
            if gone is not None:
                gone.close()
            known.pop(w, None)
            last_stamp.pop(w, None)
            wire_full_bytes.pop(w, None)
            peer_codecs.pop(w, None)
        for w in membership.active:
            if w == worker_id or w in clients:
                continue
            clients[w] = _PeerClient(urls[w], timeout=peer_timeout)
            try:
                status, _, body = clients[w].request("GET", "/healthz")
                if status == 200:
                    peer_codecs[w] = json.loads(
                        body.decode("utf8")
                    ).get("codecs")
            except (OSError, ValueError):
                pass
        # the old epoch's version bookkeeping and delta chains are void
        # under the new slice geometry: force full re-pulls
        for w in clients:
            known[w] = -1
            last_stamp[w] = -(10 ** 9)
            flat_w = layout.flat_slices(params_host, w)
            if flat_w:
                wire_full_bytes[w] = len(encode_arrays(
                    {"worker": worker_id, "stamp": 0},
                    {k: np.asarray(v, np.float32)
                     for k, v in flat_w.items()},
                ))
            else:
                wire_full_bytes.pop(w, None)
        # grad-push error-feedback residuals telescope against slices
        # of the dead geometry — carrying them would corrupt
        compressor.reset()
        if changed:
            counters.inc("shards_adopted", len(changed))
        if epoch_gauge is not None:
            epoch_gauge.set(membership.epoch)
        member_ledger.append(
            "apply", worker=worker_id, epoch=membership.epoch,
            active=list(membership.active), resharded=len(changed),
            opt_source=opt_src,
        )
        log_event(
            "fleet-membership-applied",
            f"worker {worker_id}: membership epoch {membership.epoch} "
            f"applied (active {list(membership.active)}, "
            f"{len(changed)} slices re-sharded, optimizer {opt_src})",
            worker=worker_id, epoch=membership.epoch,
            active=list(membership.active), resharded=len(changed),
        )
        if was_active and not now_active:
            # the fleet moved on without us (a heal after a partition,
            # say): request readmission — our pushes are fenced until it
            log_event(
                "fleet-self-evicted",
                f"worker {worker_id}: membership epoch "
                f"{membership.epoch} no longer names this worker — "
                "requesting rejoin",
                worker=worker_id, epoch=membership.epoch,
            )
            request_join(membership)

    # ---- jitted gradient step ---------------------------------------
    def gstep(params, tokens, targets, rng_key):
        import optax

        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(params, tokens, targets, rng_key)
        gnorm = optax.global_norm(grads)
        if worker_clip > 0:
            scale = jnp.minimum(
                1.0, worker_clip / jnp.maximum(gnorm, 1e-16)
            )
            grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
        return loss, metrics, grads, gnorm

    gstep_jit = jax.jit(gstep)

    def run_gstep(*args):
        with pctx.use_mesh(mesh):
            return gstep_jit(*args)

    # ---- logger / eval scaffolding (worker 0 reports) ----------------
    log_step: Callable[[Optional[Dict[str, Any]]], None]
    log_finalize: Callable[[], None]
    if worker_id == 0:
        import io as _io
        import sys as _sys

        logger_cfg = T.get("logger") or {
            "@loggers": "spacy_ray_tpu.ConsoleLogger.v1"
        }
        logger_setup = registry.resolve(logger_cfg)
        out_stream = _sys.stdout if stdout_log else _io.StringIO()
        log_step, log_finalize = logger_setup(nlp, out_stream, _sys.stderr)
        dev_examples = list(dev_corpus())
        score_weights = dict(T.get("score_weights") or {})
        if not score_weights:
            score_weights = default_pipeline_score_weights(nlp)
    else:
        log_step, log_finalize = (lambda info: None), (lambda: None)
        dev_examples = []
        score_weights = {}

    max_steps = int(max_steps_override or T["max_steps"] or 0)
    max_epochs = int(T["max_epochs"] or 0)
    eval_frequency = int(T["eval_frequency"] or 200)
    patience = int(T["patience"] or 0)
    keep_checkpoints = int(T.get("keep_checkpoints", 2) or 1)
    n_data = 1

    result = TrainResult()
    phases: Dict[str, float] = {p: 0.0 for p in PHASES}
    loss_accum: Dict[str, float] = {}
    known: Dict[int, int] = {w: -1 for w in clients}
    last_saved_step = -1 if resumed_from is None else resumed_from
    stop = False
    clean_exit = False  # set at normal loop exit; a crash leaves it False
    steps_run = 0
    words_since_log = 0
    start_time = time.perf_counter()
    last_log_time = start_time

    # ---- data stream (this worker's corpus shard) --------------------
    def batches():
        nonlocal epoch
        while True:
            stream = train_corpus()
            if n_workers > 1:
                stream = shard_stream(stream, worker_id, n_workers)
            got_any = False
            for b in batcher(stream):
                got_any = True
                yield b
            if not got_any:
                raise ValueError(
                    f"Training corpus is empty on worker {worker_id}'s "
                    "shard"
                )
            epoch += 1
            if max_epochs and epoch >= max_epochs:
                return

    last_stamp: Dict[int, int] = {w: -(10 ** 9) for w in clients}

    def pull_peers() -> Dict[int, int]:
        """Refresh non-owned shards; returns the version stamps the next
        push will carry (per owner).

        The staleness gate: a worker may run at most ``max_staleness``
        rounds ahead of any owner — it blocks (bounded by
        ``quorum_wait_s``) until owner ``w``'s version has passed
        ``last_stamp[w] - S``, i.e. until the round it last contributed
        to has closed, S rounds of slack allowed. At S=0 this is what
        makes quorum=N synchronous-equivalent: without it a fast worker
        re-pulls an owner mid-round, stamps the OLD version, and its
        push is discarded — wedging the round it was needed for."""
        stamps: Dict[int, int] = {}
        self_version, self_flat = owner.current_flat()
        if worker_id in membership:
            layout.merge_flat(params_host, worker_id, self_flat)
        stamps[worker_id] = self_version
        deadline = time.monotonic() + float(quorum_wait_s)
        # ask for delta frames only when we track a window ourselves; an
        # owner that can't serve one (old peer ignores the header, new
        # peer outside the window) replies with a full frame — degrade,
        # never stall (RESILIENCE.md). Every pull carries our membership
        # epoch: a re-sharded owner 409s a stale one (the fence), which
        # is our cue to sync membership instead of merging wrong-geometry
        # bytes.
        accept_hdrs: Dict[str, str] = {
            "X-SRT-Epoch": str(membership.epoch)
        }
        if param_delta_window > 0:
            accept_hdrs["X-SRT-Accept"] = "delta"
        fenced_by: Optional[int] = None
        for w, client in list(clients.items()):
            if backoff.skip(w):
                # mid-outage: zero wait spent on this owner (the
                # dead-owner pull-spin fix) — push against what we know
                stamps[w] = known.get(w, -1)
                continue
            timed_out = False
            unreachable = False
            while True:
                try:
                    if resilience.partitioned(w):
                        raise OSError(f"peer {w} partitioned (fault plan)")
                    maybe_fail("param-pull")
                    act = resilience.consume_wire_fault("param-pull")
                    if act is not None and act[0] == "delay":
                        time.sleep(float(act[1] or 1.0))
                    status, headers, body = client.request(
                        "GET", f"/params?known={known[w]}",
                        headers=accept_hdrs,
                    )
                    if act is not None and act[0] == "dup":
                        # duplicated request: idempotent GET, the second
                        # reply wins — proves re-reads are harmless
                        status, headers, body = client.request(
                            "GET", f"/params?known={known[w]}",
                            headers=accept_hdrs,
                        )
                    if act is not None and act[0] == "corrupt":
                        body = resilience.corrupt_bytes(body)
                except (OSError, resilience.FaultInjected):
                    counters.inc("pull_failed")
                    unreachable = True
                    break
                if status == 204:
                    v = int(headers.get("X-SRT-Version", known[w]))
                elif status == 409:
                    # epoch fence: the fleet re-sharded past us
                    fenced_by = w
                    break
                elif status == 200:
                    try:
                        meta_w, arrays = decode_arrays(body)
                        v = int(meta_w["version"])
                        is_delta = str(meta_w.get("codec") or "") == "delta"
                        deltas = None
                        if is_delta:
                            base = int(meta_w.get("base", -1))
                            if base != known[w]:
                                raise WireError(
                                    f"delta frame base {base} does not "
                                    f"match known version {known[w]}"
                                )
                            deltas = decode_delta_frame(meta_w, arrays)
                    except Exception:
                        counters.inc("pull_failed")
                        break
                    if is_delta:
                        layout.merge_flat(
                            params_host, w, deltas, add=True
                        )
                    else:
                        layout.merge_flat(params_host, w, arrays)
                    counters.inc("wire_pull_bytes", len(body))
                    counters.inc(
                        "wire_pull_bytes_uncompressed",
                        wire_full_bytes.get(w, len(body))
                        if is_delta else len(body),
                    )
                    if v < known[w]:
                        # a restarted owner legitimately REGRESSES to its
                        # checkpointed version: our round bookkeeping
                        # against the pre-crash lineage is void — reset it
                        # or the staleness gate below would block a full
                        # timeout every step waiting for versions that no
                        # longer exist
                        last_stamp[w] = -(10 ** 9)
                        log_event(
                            "fleet-owner-regressed",
                            f"owner {w} regressed to version {v} (knew "
                            f"{known[w]}) — it restarted from its "
                            "checkpoint; resyncing",
                            owner=w, version=v, known=known[w],
                        )
                    known[w] = v
                else:
                    counters.inc("pull_failed")
                    break
                if v > last_stamp[w] - max_staleness or timed_out:
                    stamps[w] = v
                    break
                if time.monotonic() > deadline:
                    timed_out = True  # one final fetch, then proceed
                    counters.inc("pull_wait_timeouts")
                    continue
                time.sleep(0.01)
            if unreachable or timed_out:
                # ONE structured event per outage, then capped backoff —
                # not a quorum_wait_s burn plus a counter tick every step
                if backoff.record_failure(w):
                    log_event(
                        "fleet-peer-unreachable",
                        f"worker {worker_id}: owner {w} "
                        f"{'unreachable' if unreachable else 'missing its staleness deadline'}"
                        f" — pulls back off (cap {backoff.cap_s:.0f}s) "
                        "until it answers again",
                        worker=worker_id, owner=w,
                        reason=(
                            "unreachable" if unreachable else "deadline"
                        ),
                    )
            elif fenced_by != w and backoff.record_success(w):
                log_event(
                    "fleet-peer-recovered",
                    f"worker {worker_id}: owner {w} answering again — "
                    "backoff cleared",
                    worker=worker_id, owner=w,
                )
            stamps.setdefault(w, known.get(w, -1))
        if fenced_by is not None:
            refresh_membership(fenced_by)
        return stamps

    def push_grads(grads: Any, stamps: Dict[int, int]) -> None:
        fenced_peer: Dict[str, Optional[int]] = {"w": None}
        for w in list(membership.active):
            flat = layout.flat_slices(grads, w)
            if not flat:
                continue  # nothing shardable lands on this owner
            if w == worker_id:
                # self-delivery is NOT counted as a push: grad_pushed is
                # the fleet-health signal (the push-stalled AbsenceRule
                # watches it), and an always-succeeding local submit
                # would keep it moving exactly when every peer is gone
                owner.submit(worker_id, stamps[worker_id], flat)
                continue
            if w not in clients:
                continue
            # per-peer negotiated codec: the error-feedback residual for
            # peer w absorbs THIS frame's quantization error and rides
            # into the next round's gradient for w (f32 keeps none)
            codec_w = negotiate_push_codec(wire_codec, peer_codecs.get(w))
            body = compressor.encode(
                w,
                {
                    "worker": worker_id,
                    "stamp": int(stamps.get(w, -1)),
                    "epoch": int(membership.epoch),
                },
                flat,
                codec_w,
            )
            # wire chaos (the drill matrix): one queued fault covers one
            # frame — a corrupted body stays corrupted across retries
            # (the owner 400s it every time: a counted, typed discard)
            act = resilience.consume_wire_fault("grad-push")
            dup = False
            if act is not None:
                if act[0] == "corrupt":
                    body = resilience.corrupt_bytes(body)
                elif act[0] == "delay":
                    time.sleep(float(act[1] or 1.0))
                elif act[0] == "dup":
                    dup = True

            def send(w=w, body=body, dup=dup):
                maybe_fail("grad-push")
                if resilience.partitioned(w):
                    raise OSError(f"peer {w} partitioned (fault plan)")
                status, _, reply = clients[w].request(
                    "POST", "/grad", body=body
                )
                if status != 200:
                    raise OSError(
                        f"peer {w} rejected grad push: HTTP {status}"
                    )
                if dup:
                    # duplicated frame: the owner's round bookkeeping
                    # takes one contribution per (worker, stamp) — the
                    # twin is a counted discard, never a double-apply
                    clients[w].request("POST", "/grad", body=body)
                try:
                    if json.loads(reply.decode("utf8")).get("fenced"):
                        fenced_peer["w"] = w
                except (ValueError, UnicodeDecodeError, AttributeError):
                    pass

            t_send = time.perf_counter()
            delivered = False
            try:
                retry_io("grad-push", send, policy=push_policy)
                counters.inc("grad_pushed")
                counters.inc("wire_push_bytes", len(body))
                counters.inc(
                    "wire_push_bytes_uncompressed",
                    wire_full_bytes.get(w, len(body)),
                )
                delivered = True
            except (OSError, resilience.FaultInjected):
                # fire-and-forget: a dead/unreachable owner costs a
                # counted drop, never a stalled fleet
                counters.inc("push_failed")
            if tel is not None:
                # the sender-side half of the cross-worker hop the merged
                # fleet timeline shows (owner-side twin: grad_apply)
                tel.trace.add_span(
                    "grad_push",
                    t_send,
                    time.perf_counter() - t_send,
                    cat="fleet",
                    args={
                        "to": w,
                        "stamp": int(stamps.get(w, -1)),
                        "delivered": delivered,
                        "codec": codec_w,
                        "bytes": len(body),
                    },
                )
            last_stamp[w] = int(stamps.get(w, -1))
        if fenced_peer["w"] is not None:
            # an owner fenced our frame: we are at a stale epoch — sync
            refresh_membership(fenced_peer["w"])

    def fleet_checkpoint() -> None:
        """Worker 0 coordinates one generation: every owner writes its
        own part (this process directly, peers via POST /checkpoint,
        which also returns an atomically-consistent copy of their param
        slices), then worker 0 assembles params and commits meta. Any
        unreachable peer aborts the generation (a committed meta naming
        a missing part would poison load()'s fallback walk) — the
        previous generation stays current."""
        nonlocal last_saved_step
        if output_path is None or step == last_saved_step:
            return
        if worker_id not in membership:
            return  # a fenced-out worker must not commit generations
        stamp = int(step)
        ckpt_dir = Path(output_path) / "last-model"
        my = checkpoint_cb(str(ckpt_dir), stamp)
        # part digests are keyed by survivor RANK: a post-failover
        # generation is a normal len(active)-shard v2 generation
        digests: Dict[int, str] = {
            int(my["meta"]["part"]): my["meta"]["digest"]
        }
        versions: List[Optional[int]] = [None] * n_workers
        rngs: List[Optional[List[int]]] = [None] * n_workers
        versions[worker_id] = int(my["meta"]["version"])
        rngs[worker_id] = list(my["meta"]["rng"])
        assembled = _np_tree(params_host)
        layout.merge_flat(assembled, worker_id, my["params"])
        req = json.dumps({
            "dir": str(ckpt_dir), "stamp": stamp,
            "epoch": int(membership.epoch),
        }).encode("utf8")
        for w in sorted(clients):
            try:
                maybe_fail("checkpoint-wire")
                if resilience.partitioned(w):
                    raise OSError(f"peer {w} partitioned (fault plan)")
                act = resilience.consume_wire_fault("checkpoint-wire")
                if act is not None and act[0] == "delay":
                    time.sleep(float(act[1] or 1.0))
                # a /checkpoint reply arrives only after the peer's whole
                # owner-shard part file is hashed and written — the 10s
                # step-traffic timeout would abort every generation on a
                # big model, so checkpoint coordination gets its own
                # long-deadline connections
                client = ckpt_clients.get(w)
                if client is None:
                    client = ckpt_clients[w] = _PeerClient(
                        urls[w], timeout=float(checkpoint_timeout_s)
                    )
                status, _, body = client.request(
                    "POST", "/checkpoint", body=req,
                    content_type="application/json",
                )
                if status != 200:
                    raise OSError(f"peer {w} checkpoint: HTTP {status}")
                if act is not None and act[0] == "dup":
                    # re-sent coordination request: same stamp, same
                    # part file — idempotent by construction
                    status, _, body = client.request(
                        "POST", "/checkpoint", body=req,
                        content_type="application/json",
                    )
                    if status != 200:
                        raise OSError(
                            f"peer {w} checkpoint: HTTP {status}"
                        )
                if act is not None and act[0] == "corrupt":
                    body = resilience.corrupt_bytes(body)
                meta_w, arrays = decode_arrays(body)
                digests[int(meta_w["part"])] = str(meta_w["digest"])
                versions[w] = int(meta_w["version"])
                rngs[w] = list(meta_w["rng"])
                layout.merge_flat(assembled, w, arrays)
            except (OSError, WireError, KeyError, ValueError, TypeError,
                    resilience.FaultInjected) as e:
                # unreachable, wire-malformed, meta-incomplete, or
                # structurally mismatched reply — ALL of them abort the
                # generation (the docstring's promise); a partial commit
                # naming a bad part would poison load()'s fallback walk,
                # and an exception here must not crash the lead's loop
                log_event(
                    "fleet-checkpoint-aborted",
                    f"worker {w} failed the checkpoint exchange at step "
                    f"{stamp} ({type(e).__name__}: {e}); keeping the "
                    "previous generation",
                    worker=w, step=stamp,
                )
                return
        commit_fleet_generation(
            ckpt_dir,
            params=assembled,
            step=stamp,
            epoch=epoch,
            rng=np.asarray(jax.device_get(rng)),
            best_score=best_score,
            best_step=best_step,
            opt_shards=len(membership.active),
            opt_digests=digests,
            extra={
                "fleet": {
                    "n_workers": n_workers,
                    "quorum": quorum,
                    "max_staleness": max_staleness,
                    "epoch": int(membership.epoch),
                    "active": list(membership.active),
                    "versions": versions,
                    "rngs": rngs,
                },
                "mesh": {"n_data": n_data, "update_sharding": "fleet"},
            },
            keep=keep_checkpoints,
        )
        last_saved_step = stamp

    # ---- convergence watch (lead-side, docs/OBSERVABILITY.md) --------
    # worker 0 polls every peer's /metrics on a slow daemon thread and
    # feeds the cross-worker divergence detector: a worker whose recent
    # loss median is an outlier vs its PEERS (or that is training on
    # NaNs, or whose arriving gradients keep being discarded) emits
    # through the anomaly chain — metrics row + trace instant + flight-
    # recorder bundle naming the worker — and bumps divergence_flags,
    # which the fleet-worker-diverging alert rule pages on. Telemetry
    # off constructs neither the detector nor the thread.
    watch_stop = threading.Event()
    watch_thread: Optional[threading.Thread] = None
    if tel is not None and worker_id == 0 and n_workers > 1:
        from ..telemetry import FleetDivergenceDetector

        div_counter = tel.registry.counter("divergence_flags")

        def _emit_divergence(event: str, message: str, **fields: Any) -> None:
            div_counter.inc()
            tel._emit_anomaly(event, message, **fields)

        divergence = FleetDivergenceDetector(_emit_divergence)

        def _watch_stats(payload: Dict[str, Any]) -> Dict[str, Any]:
            counters_p = payload.get("counters") or {}
            loss_h = (payload.get("histograms") or {}).get("loss") or {}
            return {
                "loss": loss_h.get("p50"),
                "steps": counters_p.get("steps"),
                "received": counters_p.get("grad_received"),
                "discarded": counters_p.get("grad_discarded"),
                "loss_nonfinite": counters_p.get("loss_nonfinite"),
            }

        def _watch_loop() -> None:
            # the step loop's keep-alive peer connections are NOT
            # thread-safe; the watch owns its own clients
            watch_clients = {
                w: _PeerClient(urls[w], timeout=probe_timeout)
                for w in clients
            }
            try:
                while not watch_stop.wait(float(watch_interval_s)):
                    stats = {
                        worker_id: _watch_stats(tel.registry.snapshot())
                    }
                    for w, client in watch_clients.items():
                        try:
                            status, _, body = client.request(
                                "GET", "/metrics"
                            )
                            if status != 200:
                                continue
                            stats[w] = _watch_stats(
                                json.loads(body.decode("utf8"))
                            )
                        except (OSError, ValueError):
                            continue  # an exiting peer: no-signal, no crash
                    try:
                        divergence.observe(stats)
                    except Exception:
                        logger.exception("fleet divergence watch failed")
            finally:
                for client in watch_clients.values():
                    client.close()

        watch_thread = threading.Thread(
            target=_watch_loop, name="fleet-watch", daemon=True
        )

    # ---- lease-based liveness + the eviction verdict -----------------
    # EVERY worker runs the tracker; only the ACTING LEAD — the lowest
    # active id it still believes live — issues verdicts. Lead death
    # therefore falls through to the next survivor deterministically,
    # no election. Verdicts and admits are queued/broadcast here but
    # APPLIED only at step boundaries (apply_membership), so handler
    # threads and this thread never touch the layout.
    member_stop = threading.Event()
    member_thread: Optional[threading.Thread] = None
    if n_workers > 1 and peer_lease_s > 0:
        def _membership_loop() -> None:
            # own clients: the step loop's keep-alive connections are
            # not thread-safe (same rule as the watch loop)
            probes = {
                w: _PeerClient(urls[w], timeout=probe_timeout)
                for w in range(n_workers) if w != worker_id
            }
            tracker = LeaseTracker(
                [w for w in membership.active if w != worker_id],
                lease_s=peer_lease_s,
                miss_threshold=lease_miss_threshold,
            )
            # epoch of our own last QUEUED verdict: a verdict applies
            # only at the step loop's next boundary, so without this the
            # lead would re-evict (and re-count, and re-log) the same
            # peer every poll round until the apply lands
            verdict_epoch = 0
            try:
                while not member_stop.wait(lease_poll_s):
                    m = membership  # one snapshot per round
                    if worker_id not in m:
                        continue  # fenced-out: no verdicts while stale
                    if m.epoch < verdict_epoch:
                        continue  # our verdict is still pending apply
                    for w in list(tracker.peers()):
                        if w not in m:
                            tracker.remove(w)
                    for w in m.active:
                        if w != worker_id:
                            tracker.add(w)
                    drift_from: Optional[int] = None
                    for w in m.active:
                        if w == worker_id:
                            continue
                        ok = False
                        try:
                            status, _, body = probes[w].request(
                                "GET", "/healthz"
                            )
                            if status == 200:
                                ok = True
                                pe = json.loads(
                                    body.decode("utf8")
                                ).get("epoch")
                                if (
                                    isinstance(pe, int)
                                    and not isinstance(pe, bool)
                                    and pe > m.epoch
                                ):
                                    drift_from = w
                        except (OSError, ValueError):
                            ok = False
                        tracker.observe(w, ok)
                    if drift_from is not None:
                        # a peer is ahead of us — we missed a broadcast;
                        # pull its membership and queue it
                        try:
                            status, _, body = probes[drift_from].request(
                                "GET", "/membership"
                            )
                            if status == 200:
                                mm = Membership.from_wire(
                                    json.loads(body.decode("utf8"))
                                )
                                if mm.epoch > m.epoch and worker_id in mm:
                                    server.queue_membership(mm)
                        except (OSError, ValueError, KeyError,
                                UnicodeDecodeError):
                            pass
                        continue  # re-probe under the new membership
                    live = [
                        w for w in m.active
                        if w == worker_id or not tracker.dead(w)
                    ]
                    if not live or min(live) != worker_id:
                        continue  # not the acting lead this round
                    new_m = m
                    dead = [w for w in m.active if w not in live]
                    for w in dead:
                        new_m = new_m.evict(w)
                    joiners = sorted(
                        int(j) for j in server.drain_join_requests()
                        if isinstance(j, int)
                        and 0 <= int(j) < n_workers
                        and int(j) not in new_m
                    )
                    for j in joiners:
                        new_m = new_m.admit(j)
                    if new_m.epoch == m.epoch:
                        continue
                    if dead:
                        counters.inc("evictions", len(dead))
                        member_ledger.append(
                            "evict", lead=worker_id, evicted=dead,
                            epoch=new_m.epoch,
                            active=list(new_m.active),
                        )
                        log_event(
                            "fleet-owner-evicted",
                            f"acting lead {worker_id}: evicting {dead} "
                            f"(lease {peer_lease_s:.0f}s and "
                            f"{lease_miss_threshold} consecutive misses "
                            f"both expired) — membership epoch "
                            f"{new_m.epoch}, survivors "
                            f"{list(new_m.active)}",
                            lead=worker_id, evicted=dead,
                            epoch=new_m.epoch,
                            active=list(new_m.active),
                        )
                    if joiners:
                        member_ledger.append(
                            "admit", lead=worker_id, admitted=joiners,
                            epoch=new_m.epoch,
                            active=list(new_m.active),
                        )
                        log_event(
                            "fleet-worker-admitted",
                            f"acting lead {worker_id}: admitting "
                            f"{joiners} at membership epoch "
                            f"{new_m.epoch}",
                            lead=worker_id, admitted=joiners,
                            epoch=new_m.epoch,
                        )
                    verdict_epoch = new_m.epoch
                    wire_m = json.dumps(new_m.to_wire()).encode("utf8")
                    for w in new_m.active:
                        if w == worker_id:
                            continue
                        try:
                            probes[w].request(
                                "POST", "/membership", body=wire_m,
                                content_type="application/json",
                            )
                        except OSError:
                            pass  # it will drift-sync off /healthz
                    server.queue_membership(new_m)
            finally:
                for c in probes.values():
                    c.close()

        member_thread = threading.Thread(
            target=_membership_loop, name="fleet-membership", daemon=True
        )

    # ---- resilience arming ------------------------------------------
    watchdog: Optional[Watchdog] = None
    watchdog_timeout = float(T.get("watchdog_timeout_s", 0) or 0)
    if watchdog_timeout > 0:
        def watchdog_stats():
            if tel is not None:
                tel.emergency_flush()
            return {
                "fleet_worker": worker_id,
                "version": owner.version,
                **counters.snapshot(),
            }

        watchdog = Watchdog(watchdog_timeout, stats_fn=watchdog_stats)
    if install_signal_handlers:
        shutdown.install()
    if watchdog is not None:
        watchdog.start()
    wait_for_peers()
    for w in sorted(drifted):
        refresh_membership(w)
    if n_workers > 1 and worker_id not in membership:
        request_join(membership)
    if tel is not None:
        tel.loop_start()
    if watch_thread is not None:
        watch_thread.start()
    if member_thread is not None:
        member_thread.start()

    def note_phase(name: str, t0: float, t1: float) -> None:
        """One phase's wall time: the ledger accumulator, the shared-
        bucket histogram, and (inside the trace window) a span on this
        worker's track — one stamp pair feeds all three surfaces."""
        d = t1 - t0
        phases[name] += d
        if phase_hists is not None:
            phase_hists[name].observe(d)
            tel.trace.add_span(
                f"phase_{name}", t0, d, cat="fleet",
                args={"step": step + 1},
            )

    try:
        batch_iter = batches()
        while not stop:
            # step boundary: adopt any queued membership (a lead
            # broadcast, our own verdict, or a drift-sync) before any
            # frame of this step is stamped
            pending_m = server.take_pending_membership()
            if pending_m is not None and pending_m.epoch > membership.epoch:
                apply_membership(pending_m)
            t_data = time.perf_counter()
            try:
                b = next(batch_iter)
            except StopIteration:
                break
            max_len = max(len(eg) for eg in b)
            T_pad = bucket_length(max_len, nlp.length_buckets)
            B_pad = bucket_batch_size(len(b))
            collated = nlp.collate(
                b, pad_batch_to=B_pad, pad_len_to=T_pad, host=True
            )
            tokens, targets = collated["tokens"], collated["targets"]
            n_words = int(collated["n_words"])
            now = time.perf_counter()
            note_phase("data", t_data, now)

            t_pull = now
            stamps = pull_peers()
            now = time.perf_counter()
            note_phase("pull", t_pull, now)

            maybe_fail("step")
            poisoned = resilience.consume_poison("step")
            t_grad = now
            rng, sub = jax.random.split(rng)
            state_holder["rng"] = rng
            loss, metrics, grads, gnorm = run_gstep(
                params_host, tokens, targets, sub
            )
            grads = jax.tree_util.tree_map(
                lambda g: np.asarray(jax.device_get(g)), grads
            )
            now = time.perf_counter()
            note_phase("grad", t_grad, now)

            t_push = now
            push_grads(grads, stamps)
            now = time.perf_counter()
            note_phase("push", t_push, now)

            t_wait = now
            if owns_any:
                wait_deadline = time.monotonic() + float(quorum_wait_s)
                reached = False
                wait_fenced = False
                while True:
                    remaining = wait_deadline - time.monotonic()
                    if remaining <= 0:
                        break
                    if owner.wait_version_above(
                        stamps[worker_id], min(0.25, remaining)
                    ):
                        reached = True
                        break
                    pending_epoch = server.pending_membership_epoch()
                    if (
                        pending_epoch is not None
                        and pending_epoch > membership.epoch
                    ):
                        # an eviction verdict is queued: survivors
                        # already stamp the NEW epoch, so this epoch's
                        # quorum can never complete — yield to the
                        # apply at the top of the next iteration
                        wait_fenced = True
                        break
                if not reached and not wait_fenced:
                    counters.inc("apply_wait_timeouts")
                    log_event(
                        "fleet-quorum-timeout",
                        f"worker {worker_id}: own shard stuck at version "
                        f"{owner.version} for {quorum_wait_s:.0f}s (quorum "
                        f"{quorum} not reached) — proceeding",
                        worker=worker_id, version=owner.version,
                    )
            note_phase("apply_wait", t_wait, time.perf_counter())

            step += 1
            steps_run += 1
            state_holder["step"] = step
            result.words_seen += n_words
            words_since_log += n_words
            loss_val = float("nan") if poisoned else float(loss)
            for key, value in jax.device_get(metrics).items():
                if key.startswith("loss_"):
                    v = float("nan") if poisoned else float(value)
                    loss_accum[key[5:]] = loss_accum.get(key[5:], 0.0) + v
            if tel is not None:
                # per-step loss streaming: the row lands in metrics.jsonl
                # (the run report's loss trajectories) and the recent-
                # median ring is what the lead's convergence watch polls
                tel.step_boundary(
                    step=step, epoch=epoch, n_words=n_words,
                    steps_run=steps_run, loss=loss_val,
                )

            info: Optional[Dict[str, Any]] = None
            if worker_id == 0 and step % eval_frequency == 0:
                eval_t0 = time.perf_counter()
                scores = nlp.evaluate(dev_examples, params_host, mesh=mesh)
                eval_seconds = time.perf_counter() - eval_t0
                score = weighted_score(scores, score_weights)
                now2 = time.perf_counter()
                wps = words_since_log / max(now2 - last_log_time, 1e-9)
                last_log_time = now2
                words_since_log = 0
                info = {
                    "epoch": epoch,
                    "step": step,
                    "words": result.words_seen,
                    "losses": dict(loss_accum),
                    "other_scores": scores,
                    "score": score,
                    "wps": wps,
                    "eval_seconds": eval_seconds,
                    "fleet": {
                        "worker": worker_id,
                        "version": owner.version,
                        **counters.snapshot(),
                    },
                }
                result.history.append(info)
                loss_accum = {}
                if score > best_score:
                    best_score = score
                    best_step = step
                    if output_path is not None:
                        nlp.params = params_host
                        nlp.to_disk(Path(output_path) / "best-model")
                fleet_checkpoint()
                if tel is not None:
                    tel.rearm_step_clock()
            elif (
                worker_id != 0
                and worker_id == membership.lead
                and step % eval_frequency == 0
            ):
                # lead failover: the acting lead inherits CHECKPOINT
                # duty (scores pause — the dev corpus and logger live on
                # worker 0 — but the lineage keeps committing;
                # RESILIENCE.md "Ownership failover")
                fleet_checkpoint()
                if tel is not None:
                    tel.rearm_step_clock()
            log_step(info)
            if watchdog is not None:
                watchdog.beat()

            if max_steps and step >= max_steps:
                stop = True
            if (
                worker_id == 0
                and patience
                and best_step >= 0
                and (step - best_step) >= patience
            ):
                stop = True
            if (
                not stop
                and worker_id != membership.lead
                and server.finalize_event.is_set()
            ):
                # the lead finished (patience, max_steps, preemption) and
                # committed its final generation: follow it instead of
                # training headless to our own max_steps — progress past
                # this point could never be checkpointed (worker 0 owns
                # the commit) and every push to it would be a dead letter
                log_event(
                    "fleet-finalized",
                    f"worker {worker_id}: lead worker finalized the "
                    f"fleet at our step {step} — stopping",
                    worker=worker_id, step=step,
                )
                stop = True
            if not stop and shutdown.coordinated_stop(1):
                if worker_id == membership.lead:
                    fleet_checkpoint()
                result.interrupted = True
                log_event(
                    "preempted",
                    f"fleet worker {worker_id}: shutdown signal at step "
                    f"{step}; resume with --resume",
                    step=step, worker=worker_id,
                )
                stop = True
        clean_exit = True
    finally:
        if watchdog is not None:
            watchdog.stop()
        watch_stop.set()
        member_stop.set()
        if watch_thread is not None:
            watch_thread.join(timeout=5.0)
        if member_thread is not None and member_thread.is_alive():
            member_thread.join(timeout=5.0)
        if install_signal_handlers:
            shutdown.restore()
        try:
            if worker_id == membership.lead:
                # finalize ONLY on a clean exit (max_steps / patience /
                # preemption): a CRASHED lead is about to be relaunched
                # with --resume by its supervisor, and broadcasting
                # /finalize here would shut down the very peers it needs
                # to rejoin — the survivors-keep-stepping contract.
                # membership.lead, not literal 0: after a lead failover
                # the acting lead owns the final commit and broadcast
                if clean_exit:
                    if not result.interrupted:
                        fleet_checkpoint()
                    for w, client in clients.items():
                        try:
                            client.request(
                                "POST", "/finalize", body=b"{}",
                                content_type="application/json",
                            )
                        except OSError:
                            pass
            elif clean_exit:
                # keep serving /grad, /params and /checkpoint until the
                # lead finishes its final generation: with quorum < N a
                # non-evaluating peer finishes max_steps well BEFORE the
                # lead (eval/checkpoint overhead is lead-only), and
                # shutting this server early would abort the lead's
                # final commit. Patience is bounded two ways: the long
                # finalize_wait_s deadline, and a lead-liveness probe —
                # a DEAD lead (past its restart cap) will never post
                # /finalize, and waiting the full deadline for it would
                # just delay this worker's own ledger
                lead = clients.get(membership.lead)
                deadline = time.monotonic() + float(finalize_wait_s)
                lead_misses = 0
                while not server.finalize_event.wait(timeout=5.0):
                    if time.monotonic() > deadline:
                        break
                    if lead is None:
                        continue
                    try:
                        lead.request("GET", "/healthz")
                        lead_misses = 0
                    except OSError:
                        lead_misses += 1
                        if lead_misses >= 2:
                            log_event(
                                "fleet-lead-gone",
                                f"worker {worker_id}: lead unreachable "
                                "while awaiting finalize — exiting",
                                worker=worker_id,
                            )
                            break
        finally:
            result.seconds = time.perf_counter() - start_time
            result.best_score = best_score
            result.best_step = best_step
            result.final_step = step
            result.epoch = epoch
            result.fleet = {
                "worker": worker_id,
                "n_workers": n_workers,
                "quorum": quorum,
                "max_staleness": max_staleness,
                "version": owner.version,
                "membership_epoch": int(membership.epoch),
                "active": list(membership.active),
                "grad_compression": wire_codec,
                "param_delta_window": param_delta_window,
                "counters": counters.snapshot(),
                "phases": {p: round(v, 6) for p, v in phases.items()},
                "owner_apply_seconds": round(owner.apply_seconds, 6),
            }
            if output_path is not None:
                out = Path(output_path)
                out.mkdir(parents=True, exist_ok=True)
                ledger = {
                    "worker": worker_id,
                    "steps": step,
                    "words_seen": result.words_seen,
                    "seconds": round(result.seconds, 6),
                    "interrupted": result.interrupted,
                    "resumed_from": resumed_from,
                    **result.fleet,
                }
                (out / f"fleet-worker-{worker_id}.json").write_text(
                    json.dumps(ledger, indent=2), encoding="utf8"
                )
            if tel is not None:
                # the kind:"fleet" exit row: the dynamics histograms'
                # final snapshots ride into metrics.jsonl so the run
                # report and `telemetry summarize` can digest them
                # offline (the in-memory registry dies with the process)
                snap_h = tel.registry.snapshot().get("histograms") or {}
                tel.append_row({
                    "kind": "fleet",
                    "worker": worker_id,
                    "n_workers": n_workers,
                    "quorum": quorum,
                    "max_staleness": max_staleness,
                    "version": owner.version,
                    "membership_epoch": int(membership.epoch),
                    "active": list(membership.active),
                    "grad_compression": wire_codec,
                    "param_delta_window": param_delta_window,
                    "counters": counters.snapshot(),
                    "phases": {p: round(v, 6) for p, v in phases.items()},
                    "histograms": {
                        k: v for k, v in snap_h.items()
                        if k in ("staleness", "quorum_wait_seconds",
                                 "apply_seconds", "loss")
                        or k.startswith("phase_")
                    },
                })
            for client in clients.values():
                client.close()
            for client in ckpt_clients.values():
                client.close()
            server.stop()
            if tel is not None:
                tel.finalize()
    nlp.params = params_host
    if worker_id == membership.lead and output_path is not None:
        nlp.to_disk(Path(output_path) / "last-model")
    log_finalize()
    return nlp, result
