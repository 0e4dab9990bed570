"""Fleet orchestration: wire supervisor + router + autoscaler into one
process with one lifecycle.

Topology (one fleet process, N replica processes)::

            clients
               |
        RouterHTTPServer (:port)         <- this process
         /v1/parse  /healthz  /metrics[?format=prometheus]
         /trace  /admin/exemplars
               |
        Router (least-outstanding, health-probed, retry-on-crash)
          |         |          |
       serve #0  serve #1 ... serve #N-1  <- subprocesses (one engine each)
          ^---- ReplicaSupervisor (spawn / backoff-restart / scale)
                      ^---- AutoscalerPolicy (SLO telemetry -> scale_to)

Shutdown is the trainer's drain discipline applied at fleet scope, via
the same ``ShutdownCoordinator.add_callback`` hook the single-replica
server uses: SIGTERM →

1. the router stops admitting (``/v1/parse`` and ``/healthz`` go 503);
2. in-flight forwarded requests complete (router-side wait);
3. every replica gets SIGTERM and runs its OWN graceful drain
   (finish queued + in-flight batches, exit 0) — in parallel, so the
   fleet drains in max(replica drain), not sum;
4. the fleet exits 0 iff the router went quiet AND every replica
   exited 0 — the honest-failure contract everywhere else in the repo.
"""

from __future__ import annotations

import logging
import shutil
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

from ...training.resilience import ShutdownCoordinator, log_event
from .autoscaler import AutoscalerPolicy, observation_from_snapshots
from .replica import ReplicaSupervisor, build_serve_cmd
from .router import Router, RouterHTTPServer, RouterTelemetry

__all__ = ["FleetConfig", "Fleet"]

logger = logging.getLogger("spacy_ray_tpu.serving")


@dataclass
class FleetConfig:
    """Everything a fleet needs; CLI flags and tests both build
    one of these (one knob surface, no drift)."""

    model_path: str
    host: str = "127.0.0.1"
    port: int = 8090
    device: str = "cpu"
    replicas: int = 2                 # initial size
    min_replicas: int = 1
    max_replicas: int = 4
    # per-replica serving knobs (None = the serve command's defaults)
    max_batch: Optional[int] = None
    max_wait_ms: Optional[float] = None
    queue_size: Optional[int] = None
    timeout_ms: Optional[float] = None
    max_doc_len: Optional[int] = None
    # admission discipline + precision overlay policy, passed through to
    # every replica (None = the serve command's defaults: continuous
    # admission, precision "auto" — bf16 overlay on accelerators only)
    batching: Optional[str] = None
    precision: Optional[str] = None
    # multi-model serving (docs/SERVING.md "Multi-model fleet"): a model
    # manifest turns every replica into a multi-model host (registry +
    # residency + admission built per replica from the same file) and
    # teaches the router to resolve/route per model; resident_models
    # caps each replica's LRU hot set. None = single-model, bit-identical
    # to before the subsystem existed.
    model_manifest: Optional[str] = None
    resident_models: Optional[int] = None
    replica_drain_timeout_s: float = 30.0
    # replica port assignment: 0 = ephemeral (parsed from each banner);
    # nonzero = base_port + slot (fixed layouts for firewalls — slots
    # are recycled across scale cycles, so ports never drift)
    base_port: int = 0
    # per-replica device pinning: visible-device masks cycled by the
    # replica's SLOT, e.g. ["0", "1"] -> slot 0 sees device 0, slot 1
    # device 1 (slots recycle, so a scale cycle can't double-book one)
    visible_devices: Optional[List[str]] = None
    visible_devices_env: str = "CUDA_VISIBLE_DEVICES"
    # the CPU value of the same idea: ``taskset -c`` core masks cycled by
    # slot, e.g. ["0", "1"] -> slot 0 owns core 0. On CPU the
    # "device" a replica must not share IS its core set — co-scheduled
    # unmasked replicas each spawn an nproc-wide XLA pool and thrash
    # (measured NEGATIVE scaling on this container without masks).
    # "auto" in the CLI resolves to one core per replica round-robin.
    cpu_cores: Optional[List[str]] = None
    # router
    # router response cache: ARMED by default since PR 13 (generation
    # correctness landed in PR 11 — stamped entries, mixed-generation
    # bypass, promotion flush — and the Zipfian open-loop record proves
    # the hit-rate x p99 win on skewed traffic; 0 = off)
    cache_mb: float = 32.0
    probe_interval_s: float = 0.5
    # length-bucket affinity routing (docs/SERVING.md "Data plane"):
    # steer similar doc lengths to the same replica so device batches
    # fill one bucket shape instead of padding to the longest straggler.
    # Off by default — it pays on skewed length mixtures with >1
    # replica (docs/TUNING.md §24), and is a no-op otherwise.
    length_routing: bool = False
    # live continuous learning (docs/SERVING.md "Continuous learning"):
    # watch_dir = a TrainCheckpoint directory a training run writes into;
    # new intact generations are canaried onto canary_fraction of the
    # replicas (traffic split by generation), then promoted fleet-wide or
    # auto-rolled-back by the guard (error rate / window-p99 regression)
    watch_dir: Optional[str] = None
    watch_interval_s: float = 2.0
    canary_fraction: float = 0.25
    guard_p99_frac: float = 1.5
    guard_error_rate: float = 0.02
    guard_min_samples: int = 20
    guard_bad_consecutive: int = 2
    guard_good_consecutive: int = 3
    guard_verdict_timeout_s: float = 120.0
    # autoscaler (disabled unless autoscale=True)
    autoscale: bool = False
    p99_target_ms: float = 500.0
    autoscale_interval_s: float = 2.0
    up_consecutive: int = 3
    down_consecutive: int = 10
    cooldown_s: float = 30.0
    # diagnosis layer (docs/OBSERVABILITY.md "Alerting & incidents"):
    # incidents_dir arms the flight recorder fleet-wide — the router
    # keeps a snapshot ring and dumps it when an alert fires; every
    # replica gets --incidents-dir (its own alert-triggered dumps) and
    # --blackbox <incidents_dir>/blackbox/slot-N.json (the
    # SIGKILL-survivable copy the crash postmortem reads; rewrites are
    # rate-limited to ~10s, so it may lag the crash by that much); a dead
    # replica produces a crash bundle with exit status, stderr tail,
    # config, generation, health history, and both processes' span
    # rings. None = recorder off; the AlertEngine itself runs whenever
    # telemetry is on (alert state costs nothing to keep).
    incidents_dir: Optional[str] = None
    observe_interval_s: float = 2.0
    alert_slo: float = 0.99
    # lifecycle
    drain_timeout_s: float = 60.0
    ready_timeout_s: float = 300.0
    telemetry: bool = True
    extra_replica_args: List[str] = field(default_factory=list)

    def build_cmd(self, slot: int) -> List[str]:
        # keyed on the replica's recycled resource SLOT, not its
        # monotonically-growing id: after scale-down/scale-up cycles the
        # mask and port layout stay within the configured set instead of
        # drifting (two live replicas sharing one core while another
        # sits idle is exactly the co-scheduling collapse masking exists
        # to prevent)
        port = 0 if self.base_port == 0 else self.base_port + slot
        prefix: List[str] = []
        if self.cpu_cores and self.device == "cpu":
            taskset = shutil.which("taskset")
            if taskset is None:
                logger.warning(
                    "cpu_cores set but taskset is unavailable; replica "
                    "slot %d spawns unpinned", slot,
                )
            else:
                mask = self.cpu_cores[slot % len(self.cpu_cores)]
                prefix = [taskset, "-c", mask]
        incidents = (
            self.incidents_dir if self.telemetry else None
        )
        return prefix + build_serve_cmd(
            self.model_path,
            device=self.device,
            port=port,
            host="127.0.0.1",
            max_batch=self.max_batch,
            max_wait_ms=self.max_wait_ms,
            queue_size=self.queue_size,
            timeout_ms=self.timeout_ms,
            max_doc_len=self.max_doc_len,
            drain_timeout_s=self.replica_drain_timeout_s,
            batching=self.batching,
            precision=self.precision,
            swap_dir=self.watch_dir,
            incidents_dir=incidents,
            blackbox=(
                self.blackbox_path(slot) if incidents is not None else None
            ),
            observe_interval_s=(
                self.observe_interval_s if incidents is not None else None
            ),
            no_telemetry=not self.telemetry,
            model_manifest=self.model_manifest,
            resident_models=self.resident_models,
            extra_args=self.extra_replica_args,
        )

    def blackbox_path(self, slot: int) -> str:
        """One black-box file per resource SLOT (slots recycle with the
        core/port layout, so a successor's recorder takes over exactly
        the file its predecessor's crash bundle was copied from)."""
        from pathlib import Path

        return str(
            Path(self.incidents_dir) / "blackbox" / f"slot-{int(slot)}.json"
        )

    def build_env(self, slot: int) -> Dict[str, str]:
        env: Dict[str, str] = {}
        if self.device == "cpu":
            # pin the platform in the child's env too, so it holds for
            # whatever the replica itself starts
            env["JAX_PLATFORMS"] = "cpu"
        if self.visible_devices:
            mask = self.visible_devices[slot % len(self.visible_devices)]
            env[self.visible_devices_env] = mask
        return env


class Fleet:
    """One fleet lifecycle: ``run()`` for the CLI (signal handlers +
    banner), ``start()``/``request_shutdown()``/``wait()`` for tests —
    the same drain code either way, mirroring ``Server``."""

    def __init__(self, config: FleetConfig) -> None:
        self.config = config
        self.tel = RouterTelemetry() if config.telemetry else None
        # diagnosis layer: alert engine whenever telemetry is on, flight
        # recorder + crash postmortems only with an incidents_dir. With
        # telemetry OFF neither exists — zero rule evaluations, zero
        # ring writes, zero incident I/O, even if incidents_dir is set
        # (guard-tested).
        self.alerts = None
        self.recorder = None
        on_crash = None
        if config.telemetry:
            from pathlib import Path

            from ...alerting import AlertEngine, default_router_rules
            from ...incidents import FlightRecorder

            inc_dir = (
                Path(config.incidents_dir)
                if config.incidents_dir else None
            )
            if inc_dir is not None:
                self.recorder = FlightRecorder(
                    incident_dir=inc_dir,
                    process_name="router",
                )
            self.alerts = AlertEngine(
                default_router_rules(
                    p99_target_s=config.p99_target_ms / 1e3,
                    slo=config.alert_slo,
                ),
                sink_path=(
                    inc_dir / "alerts.jsonl" if inc_dir is not None else None
                ),
                on_firing=(
                    self.recorder.alert_hook()
                    if self.recorder is not None
                    else None
                ),
                source="router",
            )
            if self.recorder is not None:
                self.recorder.attach(
                    trace=self.tel.trace,
                    alerts_fn=self.alerts.states,
                )
                on_crash = self._on_replica_crash
        self.supervisor = ReplicaSupervisor(
            config.build_cmd,
            build_env=config.build_env,
            grace_s=config.replica_drain_timeout_s + 15.0,
            on_crash=on_crash,
        )
        # multi-model: one registry parse in the fleet process (each
        # replica re-parses the same manifest itself) — the router's
        # model resolution and the placement policy both read it
        self.registry = None
        if config.model_manifest:
            from ..multimodel import ModelRegistry

            self.registry = ModelRegistry.from_manifest(
                config.model_manifest
            )
        self.router = Router(
            self.supervisor.handles,
            telemetry=self.tel,
            cache_bytes=int(config.cache_mb * 1024 * 1024),
            probe_interval_s=config.probe_interval_s,
            length_routing=config.length_routing,
            # the split only activates while ready replicas actually
            # straddle two generations, i.e. during a controller rollout
            canary_fraction=(
                config.canary_fraction if config.watch_dir else 0.0
            ),
            registry=self.registry,
        )
        self.controller = None
        if config.watch_dir:
            from ..live import CanaryGuard, LiveFleetController

            self.controller = LiveFleetController(
                config.watch_dir,
                self.router,
                canary_fraction=config.canary_fraction,
                interval_s=config.watch_interval_s,
                guard=CanaryGuard(
                    p99_frac=config.guard_p99_frac,
                    error_rate_high=config.guard_error_rate,
                    min_window_samples=config.guard_min_samples,
                    min_canary_requests=config.guard_min_samples,
                    bad_consecutive=config.guard_bad_consecutive,
                    good_consecutive=config.guard_good_consecutive,
                ),
                verdict_timeout_s=config.guard_verdict_timeout_s,
            )
        self.policy: Optional[AutoscalerPolicy] = None
        if config.autoscale:
            self.policy = AutoscalerPolicy(
                min_replicas=config.min_replicas,
                max_replicas=config.max_replicas,
                p99_target_s=config.p99_target_ms / 1e3,
                up_consecutive=config.up_consecutive,
                down_consecutive=config.down_consecutive,
                cooldown_s=config.cooldown_s,
            )
        # placement-aware extension of the autoscaler: with a manifest
        # AND autoscaling on, each tick also decides WHICH models need
        # another host (per-model window p99 vs the tightest class
        # target), applied via POST /admin/models/load and appended to
        # the placement ledger (a CI failure artifact)
        self.placement_policy = None
        self._placement_ledger: Optional[str] = None
        if self.registry is not None and config.autoscale:
            from ..multimodel import PlacementPolicy

            self.placement_policy = PlacementPolicy(
                self.registry,
                default_p99_target_ms=config.p99_target_ms,
                breach_consecutive=config.up_consecutive,
                cooldown_s=config.cooldown_s,
            )
            if config.incidents_dir:
                from pathlib import Path

                inc = Path(config.incidents_dir)
                inc.mkdir(parents=True, exist_ok=True)
                self._placement_ledger = str(inc / "placement.jsonl")
        self.router.alerts = self.alerts
        self.router.recorder = self.recorder
        self.httpd = RouterHTTPServer((config.host, config.port), self.router)
        self._stop = threading.Event()
        self._serve_thread: Optional[threading.Thread] = None
        self._autoscale_thread: Optional[threading.Thread] = None
        self._observer_thread: Optional[threading.Thread] = None

    # -- diagnosis layer -------------------------------------------------
    def _on_replica_crash(self, handle: Any, rc: int) -> None:
        """Supervisor crash hook: one bundle per dead replica — exit
        status + signal, output tail, effective argv, generation, the
        router's last health payloads, the replica's black box (its
        pre-crash span ring), and the router's own flight payload so
        the postmortem timeline crosses the process boundary."""
        from ...incidents import write_crash_bundle

        write_crash_bundle(
            self.config.incidents_dir,
            process_name=f"replica-{handle.replica_id}",
            rc=rc,
            argv=self.config.build_cmd(handle.slot),
            output_tail=list(handle.tail),
            generation=handle.generation,
            health_history=list(handle.health_history),
            blackbox_path=self.config.blackbox_path(handle.slot),
            process_started_unix=handle.spawned_at_unix,
            extra_flights={"router": self.recorder.payload()},
            replica_id=handle.replica_id,
            slot=handle.slot,
        )

    def observe_tick(self) -> None:
        """One diagnosis tick (callable directly by tests): feed the
        router-side flight ring and evaluate the router rule set over a
        composite snapshot — router telemetry plus the replica roster.
        No replica scrapes here: everything these rules read, the
        router already knows."""
        snap = {
            "router": self.tel.snapshot(),
            "replicas": [h.describe() for h in self.supervisor.handles()],
            "scrape_failures": self.router.scrape_failure_stats(),
            # router-process host truth: what the process.* alert rules
            # (rss-growth, fd-leak) and the flight ring read
            "process": self.tel.hoststats.sample(),
        }
        if self.recorder is not None:
            self.recorder.record(snap)
        if self.alerts is not None:
            self.alerts.evaluate(snap)

    def _observe_loop(self) -> None:
        while True:
            try:
                self.observe_tick()
            except Exception:  # the diagnosis loop must survive anything
                logger.exception("fleet observer tick failed")
            if self._stop.wait(self.config.observe_interval_s):
                return

    @property
    def address(self) -> Tuple[str, int]:
        host, port = self.httpd.server_address[:2]
        return str(host), int(port)

    # -- lifecycle ------------------------------------------------------
    def start(self) -> Tuple[str, int]:
        self.supervisor.start(self.config.replicas)
        self.router.start()
        self._serve_thread = threading.Thread(
            target=self.httpd.serve_forever,
            kwargs={"poll_interval": 0.1},
            name="fleet-http",
            daemon=True,
        )
        self._serve_thread.start()
        if self.policy is not None:
            self._autoscale_thread = threading.Thread(
                target=self._autoscale_loop,
                name="fleet-autoscaler",
                daemon=True,
            )
            self._autoscale_thread.start()
        if self.alerts is not None or self.recorder is not None:
            self._observer_thread = threading.Thread(
                target=self._observe_loop,
                name="fleet-observer",
                daemon=True,
            )
            self._observer_thread.start()
        if self.controller is not None:
            self.controller.start()
        return self.address

    def wait_ready(
        self, n: Optional[int] = None, timeout_s: Optional[float] = None
    ) -> bool:
        """Block until ``n`` replicas (default: all initial) are ready —
        warmup done, /healthz 200. The prober runs on its own cadence;
        this just polls its verdict."""
        want = self.config.replicas if n is None else int(n)
        deadline = time.monotonic() + (
            self.config.ready_timeout_s if timeout_s is None else timeout_s
        )
        while time.monotonic() < deadline:
            if len(self.router.ready_handles()) >= want:
                return True
            if self._stop.is_set():
                return False
            time.sleep(0.1)
        return False

    # -- autoscaling ----------------------------------------------------
    def _autoscale_loop(self) -> None:
        interval = self.config.autoscale_interval_s
        while not self._stop.wait(interval):
            if self.router.draining:
                return
            try:
                self.autoscale_tick()
            except Exception:  # the control loop must survive anything
                logger.exception("autoscaler tick failed")

    def autoscale_tick(self) -> Optional[int]:
        """One observe-decide-act cycle (callable directly by tests)."""
        assert self.policy is not None
        snaps = self.router.scrape_replica_metrics()
        obs = observation_from_snapshots(
            snaps, ready=len(self.router.ready_handles())
        )
        desired = self.policy.observe(obs)
        if desired is not None:
            if self.tel is not None:
                self.tel.trace.add_instant(
                    "autoscale", cat="fleet",
                    args={"from": obs.ready, "to": desired},
                )
                self.tel.registry.counter("autoscale_decisions").inc()
            self.supervisor.scale_to(desired)
        if self.placement_policy is not None:
            self.placement_tick(snaps)
        return desired

    def placement_tick(self, snaps: Optional[List[Dict[str, Any]]] = None):
        """Placement half of the scaling loop: per-model window p99 from
        the merged ``by_model`` view → which models need another host →
        apply via ``/admin/models/load`` + append to the ledger. Returns
        the decisions applied (callable directly by tests)."""
        assert self.placement_policy is not None
        from ...training.telemetry import merge_serving_snapshots

        if snaps is None:
            snaps = self.router.scrape_replica_metrics()
        merged = merge_serving_snapshots(snaps)
        by_model: Dict[str, Dict[str, Any]] = {}
        for name, sub in (merged.get("by_model") or {}).items():
            win = (sub or {}).get("slo_window") or {}
            by_model[name] = {
                "p99": win.get("request_latency_p99"),
                "samples": win.get("samples"),
            }
        decisions = self.placement_policy.observe(
            by_model,
            self.router.placement(),
            [h.replica_id for h in self.router.ready_handles()],
        )
        for d in decisions:
            try:
                status, _ = self.router.load_model(d.replica_id, d.model)
            except Exception as exc:
                status = None
                logger.warning(
                    "placement: load %r onto replica %d failed: %r",
                    d.model, d.replica_id, exc,
                )
            log_event(
                "placement-move",
                f"model {d.model!r} -> replica {d.replica_id} "
                f"(status {status}): {d.reason}",
                level=logging.INFO,
                model=d.model, replica=d.replica_id, status=status,
            )
            if self.tel is not None:
                self.tel.trace.add_instant(
                    "placement", cat="fleet",
                    args={"model": d.model, "replica": d.replica_id},
                )
                self.tel.registry.counter("placement_decisions").inc()
            if self._placement_ledger is not None:
                import json

                try:
                    with open(self._placement_ledger, "a") as fh:
                        fh.write(json.dumps({
                            "unix_time": round(time.time(), 3),
                            "model": d.model,
                            "replica_id": d.replica_id,
                            "status": status,
                            "reason": d.reason,
                        }) + "\n")
                except OSError:
                    logger.exception("placement ledger append failed")
        return decisions

    # -- shutdown -------------------------------------------------------
    def request_shutdown(self, signum: Optional[int] = None) -> None:
        """Signal-handler-safe (flag writes + Event set only, like
        Server.request_shutdown): the admission gate flips instantly;
        the waiting thread performs the actual drain."""
        self.router.draining = True
        self._stop.set()

    def wait(self) -> int:
        self._stop.wait()
        self.router.begin_drain()
        self.supervisor.begin_drain()  # a crash during drain stays down
        if self.controller is not None:
            self.controller.stop()  # no swaps into a draining fleet
        log_event(
            "fleet-drain",
            "shutdown requested — draining router, then "
            f"{self.supervisor.replica_count} replica(s)",
            level=logging.INFO,
        )
        router_quiet = self.router.wait_inflight(self.config.drain_timeout_s)
        self.router.stop()
        replicas_clean = self.supervisor.stop_all()
        self.httpd.shutdown()
        self.httpd.server_close()
        clean = router_quiet and replicas_clean
        if not clean:
            log_event(
                "fleet-drain-failed",
                f"router_quiet={router_quiet} replicas_clean={replicas_clean}",
            )
        return 0 if clean else 1

    def run(self, *, banner: bool = True) -> int:
        coordinator = ShutdownCoordinator()
        coordinator.add_callback(self.request_shutdown)
        coordinator.install()
        try:
            host, port = self.start()
            if banner:
                # parseable, like the single-replica banner: tests and
                # operator scripts read the router address from it
                print(
                    f"fleet serving on http://{host}:{port} "
                    f"({self.config.replicas} replica(s), device "
                    f"{self.config.device})",
                    flush=True,
                )
            if self.wait_ready():
                if banner:
                    print(
                        f"fleet ready: {len(self.router.ready_handles())} "
                        "replica(s) warmed", flush=True,
                    )
            elif not self._stop.is_set():
                print(
                    "fleet NOT ready within "
                    f"{self.config.ready_timeout_s:.0f}s — serving with "
                    f"{len(self.router.ready_handles())} ready replica(s)",
                    flush=True,
                )
            return self.wait()
        finally:
            coordinator.restore()
