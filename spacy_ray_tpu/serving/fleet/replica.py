"""Replica supervision for the serving fleet: spawn one ``serve``
process per replica, track its lifecycle, restart crashes with backoff.

The process-per-replica idiom is the repo's answer to Ray's actor pool
(Moritz et al., arXiv:1712.05889): each replica is a whole ``serve``
process with its own interpreter (no shared GIL), its own jit cache, and
its own device assignment — the horizontal unit the router balances
over. Supervision reuses the resilience primitives the trainer already
trusts: :class:`~...training.resilience.RetryPolicy` paces crash
restarts (exponential backoff + jitter — a crash-looping replica must
not spin the host), and :func:`~...training.resilience.terminate_with_grace`
performs the SIGTERM → SIGKILL escalation on shutdown, which on a
healthy replica triggers its own graceful drain (finish in-flight,
exit 0).

A replica's lifecycle::

    SPAWNED -- banner parsed --> ADDRESSED -- /healthz 200 --> (router: ready)
       |                             |
       +--- process exit (crash) ----+--> RESTARTING (backoff) --> SPAWNED
       |
       +--- stop()/drain --> STOPPING --> STOPPED   (never restarted)

The supervisor owns processes and restarts; READINESS is the router's
judgement (it probes ``/healthz`` — the supervisor only knows whether
the process is alive and where it listens).
"""

from __future__ import annotations

import http.client
import logging
import os
import re
import subprocess
import sys
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from ...training.resilience import (
    RetryPolicy,
    log_event,
    terminate_with_grace,
)

__all__ = ["ReplicaHandle", "ReplicaSupervisor", "BANNER_RE"]

logger = logging.getLogger("spacy_ray_tpu.serving")

# the exact line server.py prints; the supervisor learns each replica's
# ephemeral port from it (one parseable contract, shared with operators)
BANNER_RE = re.compile(r"serving on http://([^:\s]+):(\d+)")


class ReplicaHandle:
    """One replica process as the fleet sees it: the subprocess, its
    parsed address, router-side accounting (outstanding requests, ready
    flag), and restart history. All mutable state is guarded by
    ``lock``; the router and the supervisor share the handle."""

    def __init__(self, replica_id: int, slot: Optional[int] = None) -> None:
        self.replica_id = int(replica_id)
        # resource slot: which device/core mask and base-port offset this
        # replica occupies. Ids grow monotonically forever (logs stay
        # unambiguous across scale cycles) but slots are RECYCLED — the
        # supervisor hands a new replica the lowest slot no live handle
        # holds, so after a scale-down/scale-up cycle two replicas can
        # never share a core/device mask while another mask sits idle.
        self.slot = self.replica_id if slot is None else int(slot)
        self.lock = threading.Lock()
        self.proc: Optional[subprocess.Popen] = None
        self.host: Optional[str] = None
        self.port: Optional[int] = None
        # router-maintained: a replica is ready only after ITS /healthz
        # answered 200 (warmup complete, not draining)
        self.ready = False
        # router-maintained from the /healthz body: which checkpoint
        # generation this replica's engine is serving (None = the model
        # as loaded from disk) and its flip count — the canary split and
        # the live fleet controller key on these
        self.generation: Optional[int] = None
        self.swap_count = 0
        # router-maintained from /healthz (multi-model serving only):
        # which models this replica currently hosts — model name →
        # {"generation", "swap_count", "warmed"} — and its configured
        # default. The router's pick() routes a named model WITHIN the
        # replicas hosting it, and the placement policy reads the same
        # facts; the probe loop keeps both fresh for free.
        self.resident_models: Dict[str, Dict[str, Any]] = {}
        self.default_model: Optional[str] = None
        # router-maintained: requests currently forwarded to this replica
        self.outstanding = 0
        self.restarts = 0
        self.stopping = False
        self.tail: "deque[str]" = deque(maxlen=40)  # crash diagnostics
        # router-maintained: the last few /healthz payloads this replica
        # answered — a crash postmortem's "what did the fleet last know"
        self.health_history: "deque[Dict[str, Any]]" = deque(maxlen=8)
        # supervisor-maintained: when the CURRENT process incarnation
        # was spawned (unix time; None for externally-managed handles)
        self.spawned_at_unix: Optional[float] = None
        # router-side pool of idle keep-alive connections to THIS replica.
        # A TCP handshake + thread spawn per forwarded request costs more
        # than small parses themselves; reuse makes the router hop cheap.
        # Guarded by its own lock: checkout happens on the hot path and
        # must not contend with the ready/outstanding bookkeeping above.
        self._pool_lock = threading.Lock()
        self._pool: List[http.client.HTTPConnection] = []
        self.pool_cap = 16
        # control-plane pool (health probes, metrics/exemplar scrapes):
        # SEPARATE from the hot-path pool because the two dial with
        # different timeouts — a probe reusing a forward's 60s-timeout
        # socket would take 60s to notice a hung replica, and a forward
        # reusing a probe's 5s socket would time out long parses. Small
        # cap: one prober + a couple of concurrent scrape passes.
        self._aux_pool: List[http.client.HTTPConnection] = []
        self.aux_pool_cap = 4

    def checkout_conn(self) -> Optional[http.client.HTTPConnection]:
        """Pop an idle keep-alive connection, or None (caller dials)."""
        with self._pool_lock:
            if self._pool:
                return self._pool.pop()
        return None

    def checkin_conn(self, conn: http.client.HTTPConnection) -> None:
        """Return a healthy connection for reuse; over-cap or stopping
        replicas just close it."""
        with self._pool_lock:
            if not self.stopping and len(self._pool) < self.pool_cap:
                self._pool.append(conn)
                return
        conn.close()

    def checkout_aux_conn(self) -> Optional[http.client.HTTPConnection]:
        """Pop an idle control-plane connection, or None (caller dials)."""
        with self._pool_lock:
            if self._aux_pool:
                return self._aux_pool.pop()
        return None

    def checkin_aux_conn(self, conn: http.client.HTTPConnection) -> None:
        with self._pool_lock:
            if not self.stopping and len(self._aux_pool) < self.aux_pool_cap:
                self._aux_pool.append(conn)
                return
        conn.close()

    def close_conns(self) -> None:
        """Drop every pooled connection — hot path and control plane
        (replica died, left rotation, or the fleet is draining — the
        replica-side handler threads see EOF instead of waiting on an
        idle socket)."""
        with self._pool_lock:
            pool, self._pool = self._pool, []
            aux, self._aux_pool = self._aux_pool, []
        for conn in pool + aux:
            try:
                conn.close()
            except OSError:
                pass

    @property
    def address(self) -> Optional[Tuple[str, int]]:
        with self.lock:
            if self.host is None or self.port is None:
                return None
            return self.host, self.port

    def set_address(self, host: str, port: int) -> None:
        with self.lock:
            self.host, self.port = host, int(port)

    def clear_address(self) -> None:
        with self.lock:
            self.host = self.port = None
            self.ready = False
            # a restarted replica boots from the on-disk model again —
            # its generation identity is re-learned from /healthz
            self.generation = None
            self.swap_count = 0
            # residency is re-learned too: the restarted process hosts
            # only its pinned default until traffic/placement reloads
            self.resident_models = {}
            self.default_model = None
        self.close_conns()

    @property
    def alive(self) -> bool:
        p = self.proc
        if p is None:
            # externally-managed handle (static replica sets in tests,
            # pre-registered remote endpoints): liveness is whatever the
            # health probe says, so "alive" just means "addressed"
            return self.host is not None
        return p.poll() is None

    def describe(self) -> Dict[str, Any]:
        proc = self.proc
        with self.lock:
            return {
                "id": self.replica_id,
                "slot": self.slot,
                "alive": self.alive,
                "ready": self.ready,
                "host": self.host,
                "port": self.port,
                "pid": proc.pid if proc is not None else None,
                "outstanding": self.outstanding,
                "restarts": self.restarts,
                "generation": self.generation,
                "swap_count": self.swap_count,
                "resident_models": sorted(self.resident_models),
                "default_model": self.default_model,
            }


class ReplicaSupervisor:
    """Spawn/monitor/restart/scale the replica processes.

    ``build_cmd(slot)`` returns the argv for one replica (the fleet
    config builds a ``python -m spacy_ray_tpu serve`` line; tests inject
    tiny stub scripts). ``build_env(slot)`` lets the config pin a
    device per replica (e.g. round-robin visible-device masks) without
    the supervisor knowing platform details. Both receive the replica's
    resource SLOT, not its id: slots are recycled across scale cycles
    (see :class:`ReplicaHandle`), so masks and base-port offsets stay
    within the configured layout no matter how many replicas have ever
    existed.

    Crash policy: an exit while not ``stopping`` is a crash. Restarts are
    paced by ``restart_policy`` (RetryPolicy backoff keyed on the
    replica's own restart count) and capped by ``max_restarts_per_replica``
    — a replica that keeps dying is removed from the active set (logged
    loudly) rather than crash-looping the host: the router stops routing
    to it, its slot frees up, and a later scale-up (autoscaler or
    operator) spawns a FRESH replica with its own restart budget instead
    of silently no-op'ing against a zombie handle.
    """

    def __init__(
        self,
        build_cmd: Callable[[int], List[str]],
        *,
        build_env: Optional[Callable[[int], Dict[str, str]]] = None,
        max_restarts_per_replica: int = 3,
        restart_policy: Optional[RetryPolicy] = None,
        grace_s: float = 30.0,
        popen: Callable[..., "subprocess.Popen"] = subprocess.Popen,
        clock: Callable[[], float] = time.monotonic,
        monitor_poll_s: float = 0.2,
        on_crash: Optional[Callable[[ReplicaHandle, int], None]] = None,
    ) -> None:
        self.build_cmd = build_cmd
        self.build_env = build_env
        self.max_restarts_per_replica = int(max_restarts_per_replica)
        self.restart_policy = restart_policy or RetryPolicy(
            max_retries=max_restarts_per_replica, base_delay=0.5, max_delay=15.0
        )
        self.grace_s = float(grace_s)
        self.popen = popen
        self.clock = clock
        self.monitor_poll_s = float(monitor_poll_s)
        # crash-postmortem hook (docs/OBSERVABILITY.md "Alerting &
        # incidents"): called once per observed crash, BEFORE the handle
        # is wiped for restart — the callback still sees the generation,
        # output tail, and health history the dead process had
        self.on_crash = on_crash
        self._lock = threading.Lock()
        self._handles: List[ReplicaHandle] = []
        self._next_id = 0
        self._draining = False
        self._stop = threading.Event()
        self._monitor: Optional[threading.Thread] = None
        # restart sleeps happen on the monitor thread; an Event-based wait
        # (not time.sleep) lets shutdown interrupt a pending backoff
        self._restart_at: Dict[int, float] = {}

    # -- spawn / address parsing ---------------------------------------
    def _spawn(self, handle: ReplicaHandle) -> None:
        cmd = self.build_cmd(handle.slot)
        env = dict(os.environ)
        if self.build_env is not None:
            env.update(self.build_env(handle.slot))
        handle.clear_address()
        proc = self.popen(
            cmd,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=env,
        )
        handle.proc = proc
        # wall-clock birth of THIS incarnation: the crash-bundle writer
        # compares it against the black box's written_unix so a
        # crash-looping successor can't inherit its predecessor's final
        # state as its own forensics
        handle.spawned_at_unix = time.time()
        log_event(
            "replica-spawn",
            f"replica {handle.replica_id} spawned (pid {proc.pid})",
            level=logging.INFO,
            replica=handle.replica_id,
            pid=proc.pid,
        )
        threading.Thread(
            target=self._read_stdout,
            args=(handle, proc),
            daemon=True,
            name=f"replica-{handle.replica_id}-stdout",
        ).start()

    def _read_stdout(
        self, handle: ReplicaHandle, proc: "subprocess.Popen"
    ) -> None:
        """Drain the replica's stdout forever (an unread PIPE would block
        the child), parsing the serving banner for the bound address and
        keeping a short tail for crash diagnostics."""
        try:
            assert proc.stdout is not None
            for line in proc.stdout:
                handle.tail.append(line.rstrip("\n"))
                m = BANNER_RE.search(line)
                if m and handle.proc is proc:
                    handle.set_address(m.group(1), int(m.group(2)))
                logger.debug("[replica %d] %s", handle.replica_id,
                             line.rstrip("\n"))
        except (ValueError, OSError):  # pipe closed mid-read
            pass

    def _alloc_slot(self) -> int:
        """Lowest slot no ACTIVE handle holds (caller holds ``_lock``).
        A stopping replica's slot is reusable immediately: its successor
        may briefly share the core/device while the drain finishes — a
        bounded handover — whereas waiting for the exit would wrap new
        replicas past the configured mask layout, pinning two LIVE
        replicas to one mask permanently."""
        used = {h.slot for h in self._handles if not h.stopping}
        slot = 0
        while slot in used:
            slot += 1
        return slot

    # -- lifecycle ------------------------------------------------------
    def start(self, n_replicas: int) -> List[ReplicaHandle]:
        with self._lock:
            for _ in range(int(n_replicas)):
                handle = ReplicaHandle(self._next_id, slot=self._alloc_slot())
                self._next_id += 1
                self._handles.append(handle)
                self._spawn(handle)
        self._monitor = threading.Thread(
            target=self._monitor_loop, daemon=True, name="fleet-monitor"
        )
        self._monitor.start()
        return self.handles()

    def handles(self) -> List[ReplicaHandle]:
        with self._lock:
            return [h for h in self._handles if not h.stopping]

    def all_handles(self) -> List[ReplicaHandle]:
        with self._lock:
            return list(self._handles)

    @property
    def replica_count(self) -> int:
        return len(self.handles())

    # -- crash monitoring / restart ------------------------------------
    def _monitor_loop(self) -> None:
        while not self._stop.is_set():
            now = self.clock()
            for handle in self.handles():
                if self._draining or handle.stopping:
                    continue
                proc = handle.proc
                if proc is None or proc.poll() is None:
                    continue
                due = self._restart_at.get(handle.replica_id)
                if due is None:
                    # fresh crash: schedule the restart after backoff
                    rc = proc.returncode
                    if self.on_crash is not None:
                        # forensics FIRST: clear_address() below wipes
                        # the generation; the bundle writer needs the
                        # handle as the dead process left it
                        try:
                            self.on_crash(handle, rc)
                        except Exception:
                            logger.exception(
                                "crash-incident hook failed for replica %d",
                                handle.replica_id,
                            )
                    handle.clear_address()
                    handle.restarts += 1
                    if handle.restarts > self.max_restarts_per_replica:
                        log_event(
                            "replica-giving-up",
                            f"replica {handle.replica_id} exited rc={rc} "
                            f"after {handle.restarts - 1} restart(s) — "
                            "removing it from the fleet",
                            replica=handle.replica_id,
                            rc=rc,
                        )
                        # terminal: leave the active set entirely, so
                        # replica_count is honest, scale_to can spawn a
                        # replacement (a zombie handle would make the
                        # autoscaler's scale-up a silent no-op while it
                        # keeps consuming decisions and cooldown), and
                        # the slot frees for that replacement
                        handle.stopping = True
                        with self._lock:
                            if handle in self._handles:
                                self._handles.remove(handle)
                        continue
                    delay = self.restart_policy.delay(handle.restarts)
                    tail = " | ".join(list(handle.tail)[-3:])
                    log_event(
                        "replica-crash",
                        f"replica {handle.replica_id} exited rc={rc} — "
                        f"restart {handle.restarts}/"
                        f"{self.max_restarts_per_replica} in {delay:.2f}s"
                        + (f" (last output: {tail})" if tail else ""),
                        replica=handle.replica_id,
                        rc=rc,
                        restart=handle.restarts,
                        delay_s=round(delay, 3),
                    )
                    self._restart_at[handle.replica_id] = now + delay
                elif now >= due:
                    del self._restart_at[handle.replica_id]
                    self._spawn(handle)
            self._stop.wait(self.monitor_poll_s)

    # -- scaling --------------------------------------------------------
    def scale_to(self, n: int) -> int:
        """Grow or shrink the fleet to ``n`` replicas. Growth spawns
        fresh processes (they join the router once their /healthz goes
        200); shrink SIGTERMs the highest-id replicas — each drains its
        in-flight work and exits 0 — without blocking this caller.
        Returns the new target count."""
        n = int(n)
        with self._lock:
            active = [h for h in self._handles if not h.stopping]
            delta = n - len(active)
            if delta > 0:
                for _ in range(delta):
                    handle = ReplicaHandle(
                        self._next_id, slot=self._alloc_slot()
                    )
                    self._next_id += 1
                    self._handles.append(handle)
                    self._spawn(handle)
            elif delta < 0:
                # stop the youngest first: oldest replicas have the
                # longest-warmed caches and proven stability
                for handle in sorted(
                    active, key=lambda h: h.replica_id, reverse=True
                )[: -delta]:
                    handle.stopping = True
                    handle.ready = False
                    threading.Thread(
                        target=self._stop_one,
                        args=(handle,),
                        daemon=True,
                        name=f"replica-{handle.replica_id}-stop",
                    ).start()
        return n

    def _stop_one(self, handle: ReplicaHandle) -> Optional[int]:
        proc = handle.proc
        if proc is None:
            return None
        rc = terminate_with_grace(proc, grace_s=self.grace_s)
        log_event(
            "replica-stopped",
            f"replica {handle.replica_id} stopped (rc={rc})",
            level=logging.INFO,
            replica=handle.replica_id,
            rc=rc,
        )
        with self._lock:
            if handle in self._handles:
                self._handles.remove(handle)
        return rc

    # -- fleet shutdown -------------------------------------------------
    def begin_drain(self) -> None:
        """Stop restarting crashed replicas; the fleet is going down."""
        self._draining = True

    def stop_all(self) -> bool:
        """SIGTERM every replica (their own graceful drain finishes
        admitted work), escalate stragglers, join the monitor. Returns
        True when every replica exited 0 — the fleet's clean-drain bit."""
        self._draining = True
        self._stop.set()
        handles = self.all_handles()
        for h in handles:
            h.stopping = True
            h.ready = False
        # parallel SIGTERM: replicas drain concurrently, so the fleet's
        # drain time is the slowest replica's, not the sum
        results: Dict[int, Optional[int]] = {}
        threads = []
        for h in handles:
            if h.proc is None:
                continue

            def stop(h: ReplicaHandle = h) -> None:
                results[h.replica_id] = terminate_with_grace(
                    h.proc, grace_s=self.grace_s
                )

            t = threading.Thread(target=stop, daemon=True)
            t.start()
            threads.append(t)
        for t in threads:
            t.join(timeout=self.grace_s + 10.0)
        if self._monitor is not None:
            self._monitor.join(timeout=5.0)
            self._monitor = None
        clean = all(rc == 0 for rc in results.values())
        log_event(
            "fleet-replicas-stopped",
            f"{len(results)} replica(s) stopped "
            f"({'all clean' if clean else 'NON-ZERO exits: ' + str(results)})",
            level=logging.INFO if clean else logging.WARNING,
            exits={str(k): v for k, v in results.items()},
        )
        return clean


def build_serve_cmd(
    model_path: str,
    *,
    device: str = "cpu",
    port: int = 0,
    host: str = "127.0.0.1",
    max_batch: Optional[int] = None,
    max_wait_ms: Optional[float] = None,
    queue_size: Optional[int] = None,
    timeout_ms: Optional[float] = None,
    max_doc_len: Optional[int] = None,
    drain_timeout_s: Optional[float] = None,
    batching: Optional[str] = None,
    precision: Optional[str] = None,
    swap_dir: Optional[str] = None,
    incidents_dir: Optional[str] = None,
    blackbox: Optional[str] = None,
    observe_interval_s: Optional[float] = None,
    no_telemetry: bool = False,
    model_manifest: Optional[str] = None,
    resident_models: Optional[int] = None,
    extra_args: Sequence[str] = (),
) -> List[str]:
    """The canonical replica argv: one place building the ``serve`` line
    so the CLI and the tests can't drift on flag names."""
    cmd = [
        sys.executable, "-m", "spacy_ray_tpu", "serve", str(model_path),
        "--host", host, "--port", str(int(port)), "--device", device,
    ]
    if max_batch is not None:
        cmd += ["--max-batch", str(int(max_batch))]
    if max_wait_ms is not None:
        cmd += ["--max-wait-ms", str(float(max_wait_ms))]
    if queue_size is not None:
        cmd += ["--queue-size", str(int(queue_size))]
    if timeout_ms is not None:
        cmd += ["--timeout-ms", str(float(timeout_ms))]
    if max_doc_len is not None:
        cmd += ["--max-doc-len", str(int(max_doc_len))]
    if drain_timeout_s is not None:
        cmd += ["--drain-timeout-s", str(float(drain_timeout_s))]
    if batching is not None:
        cmd += ["--batching", str(batching)]
    if precision is not None:
        cmd += ["--precision", str(precision)]
    if swap_dir is not None:
        # the ONE directory this replica's /admin/swap may load from —
        # the fleet controller's rollouts; anything else is 403
        cmd += ["--swap-dir", str(swap_dir)]
    if incidents_dir is not None:
        # the replica's own alert firings dump flight-recorder bundles
        # into the fleet-shared incidents directory
        cmd += ["--incidents-dir", str(incidents_dir)]
    if blackbox is not None:
        # SIGKILL-survivable state: the replica persists its span ring +
        # metric snapshots here every observer tick; the supervisor
        # copies it into the crash bundle when this process dies
        cmd += ["--blackbox", str(blackbox)]
    if observe_interval_s is not None:
        cmd += ["--observe-interval-s", str(float(observe_interval_s))]
    if model_manifest is not None:
        # multi-model serving: the replica builds its own registry /
        # residency / admission stack from the shared manifest
        cmd += ["--model-manifest", str(model_manifest)]
    if resident_models is not None:
        cmd += ["--resident-models", str(int(resident_models))]
    if no_telemetry:
        cmd.append("--no-telemetry")
    cmd += list(extra_args)
    return cmd
