"""Multi-replica serving fleet: router, replica supervisor, SLO-driven
autoscaling.

One :class:`~.fleet.Fleet` process runs the HTTP router
(least-outstanding-requests over health-probed replicas, typed 503 when
none is ready, optional byte-capped response cache, aggregated fleet
``/metrics``), the :class:`~.replica.ReplicaSupervisor` (one ``serve``
subprocess per replica, backoff restarts on crash, drain-aware stops),
and the :class:`~.autoscaler.AutoscalerPolicy` (hysteresis scaling
between min/max replicas driven by the engines' own SLO telemetry).

Entry point: ``spacy-ray-tpu serve-fleet <model_dir>`` (cli.py).
"""

from .autoscaler import (
    AutoscalerPolicy,
    FleetObservation,
    observation_from_snapshots,
)
from .fleet import Fleet, FleetConfig
from .replica import ReplicaHandle, ReplicaSupervisor, build_serve_cmd
from .router import (
    GENERATION_MIXED,
    NoReplicaAvailable,
    ResponseCache,
    Router,
    RouterHTTPServer,
    RouterTelemetry,
)

__all__ = [
    "AutoscalerPolicy",
    "FleetObservation",
    "observation_from_snapshots",
    "Fleet",
    "FleetConfig",
    "ReplicaHandle",
    "ReplicaSupervisor",
    "build_serve_cmd",
    "NoReplicaAvailable",
    "ResponseCache",
    "GENERATION_MIXED",
    "Router",
    "RouterHTTPServer",
    "RouterTelemetry",
]
