"""The training loop: config-driven train-while-improving on a device mesh.

Capability parity with the reference's L4/L5 training path (reference
worker.py:157-204 ``Worker.train`` driving spacy's
``train_while_improving``; SURVEY.md §3.1/3.2 call stacks), redesigned
synchronous-SPMD:

* one process per host, all hosts execute the same loop (no driver/actor
  split; the reference's is_running polling at train_cli.py:88-91 and the
  Evaluator score-exchange actor at worker.py:281-300 disappear — eval
  scores are replicated by SPMD symmetry, SURVEY.md §5.8);
* the data stream is sharded by host (fixing SURVEY.md §2.4 "No data
  sharding by rank"), and the global batch is sharded over the mesh's
  ``data`` axis inside the compiled step;
* patience / best-model selection / eval_frequency semantics match the
  reference's loop contract (worker.py:176-189);
* checkpointing is wired (best-model + last-model + full resume), unlike
  the reference's unreachable save path (SURVEY.md §2.4).
"""

from __future__ import annotations

import math
import random
import time
from functools import partial
from pathlib import Path
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import names
from ..config import Config
from ..pipeline.doc import Example
from ..pipeline.language import Pipeline
from ..registry import registry
from ..parallel.mesh import build_mesh
from ..parallel.step import (
    make_train_step,
    place_batch,
    place_replicated,
    resolve_update_sharding,
    shard_opt_state,
    update_sharding_status,
)
from .batcher import bucket_batch_size, bucket_length, shard_stream
from . import resilience
from .checkpoint import CheckpointCorrupt, TrainCheckpoint
from .resilience import ShutdownCoordinator, Watchdog, log_event, maybe_fail
from . import corpus as _corpus  # noqa: F401  (registers readers)
from . import optimizers as _optimizers  # noqa: F401  (registers optimizers)
from . import loggers as _loggers  # noqa: F401  (registers loggers)


DEFAULT_TRAINING = {
    "seed": 0,
    "dropout": 0.1,
    "accumulate_gradient": 1,
    "patience": 1600,
    "max_epochs": 0,
    "max_steps": 20000,
    "eval_frequency": 200,
    "frozen_components": [],
    "annotating_components": [],
    "dev_corpus": "corpora.dev",
    "train_corpus": "corpora.train",
    "score_weights": {},
    "zero1": False,
    # update-phase sharding over the data axis (parallel/step.py):
    # "replicated" = every replica applies the full optimizer update;
    # "zero1" = optimizer STATE sharded (the old zero1=true, which stays
    # as an accepted alias); "full" = the update COMPUTATION is sharded —
    # each replica updates only its owned param shard and the result is
    # allgathered (arXiv 2004.13336). "auto" = honor the zero1 alias,
    # else arm "full" on accelerators with >1 data rank and stay
    # "replicated" on CPU/single-replica (same gating discipline as
    # fused_update). full == replicated bit-exactly (tested), so the knob
    # can be flipped mid-lineage; checkpoints are mesh-shape portable
    # either way. See TUNING.md §15 for when full loses.
    "update_sharding": "auto",
    "mesh": {},  # {"n_model": .., "n_context": .., "n_pipe": ..} axis sizes
    # batches collated + transferred ahead on a background thread (single-
    # process only; 0/1 disables). Overlaps host work with the device step.
    "prefetch_batches": 2,
    # host-side collation fanned out over N worker threads (single-process,
    # non-annotating runs only; 0/1 keeps the inline path). Batch ORDER is
    # preserved and device_put stays on one thread — see collate_pool.py.
    "collate_workers": 0,
    # byte budget (in MB) for the epoch-level collation cache; 0 disables.
    # Auto-bypassed when augmentation is active (fresh Example copies every
    # epoch can never hit an identity-keyed cache) and in annotating mode
    # (targets depend on per-step predictions).
    "collate_cache_mb": 0,
    # checkpoint generations retained under last-model/ — load() falls back
    # generation-by-generation to the newest INTACT one when a file is
    # torn/truncated/missing (training/checkpoint.py)
    "keep_checkpoints": 2,
    # hung-step watchdog: no completed step/eval within this many seconds
    # dumps all thread stacks + pipeline stats and hard-exits RC_WATCHDOG
    # (a desynced multi-host collective wedges forever otherwise). 0 = off;
    # must comfortably exceed first-step compile time when enabled.
    "watchdog_timeout_s": 0,
    # transient-I/O retry (corpus/DocBin opens, checkpoint writes):
    # attempts beyond the first, and the backoff base (doubles per retry,
    # jittered — training/resilience.py)
    "io_retries": 3,
    "io_retry_base_s": 0.5,
    # jax.profiler capture window [start, stop) in steps RUN THIS PROCESS
    # (steps_run, not global step — resume-safe), active only when
    # train --profile / profile_dir is given
    "profile_window": [5, 15],
    # telemetry (training/telemetry.py): directory for metrics.jsonl +
    # trace.json; "" disables the whole subsystem (the hot loop then
    # makes zero telemetry calls). Written by process 0 only.
    "metrics_dir": "",
    # Chrome-trace span window [start, stop) in steps_run: host-stage and
    # step spans are recorded only inside it (eval/checkpoint/anomaly
    # spans always record) — bounds trace size on long runs
    "trace_steps": [0, 50],
    # trainer-side telemetry HTTP endpoint (training/telemetry_http.py):
    # /metrics (JSON or ?format=prometheus), /healthz (trace clock
    # anchor), /trace — the trainer's leg of the cross-process
    # observability plane (`telemetry top`, `telemetry collect-trace`,
    # any Prometheus scraper). 0 (default) = no listener; requires
    # metrics_dir (the endpoint serves the telemetry objects). Process 0
    # only, like the telemetry files.
    "metrics_port": 0,
    # bind address for the metrics_port listener. The loopback default
    # is the safe posture for a laptop run; a pod trainer scraped by an
    # off-host Prometheus/`telemetry top` sets "0.0.0.0" (or the pod
    # interface) — without this the endpoint only ever answers same-host
    # scrapers.
    "metrics_host": "127.0.0.1",
    # NaN/Inf-loss, loss-spike, step-time-regression, recompile-storm
    # detectors (only active when telemetry is on); they emit through
    # log_event so anomalies land in jsonl logger rows too
    "anomaly_detection": True,
    # in-process alert engine (spacy_ray_tpu/alerting.py, only active
    # when telemetry is on): the default training rule set —
    # training-stalled (step counter unchanged for 300s, the watchdog's
    # signal visible BEFORE the watchdog's hard exit) and anomaly-burst —
    # evaluated on a rate-limited boundary hook PLUS a slow wall-clock
    # ticker thread (a wedged loop stops reaching boundaries; the ticker
    # is what lets the stall rule still fire); transitions land in
    # <metrics_dir>/alerts.jsonl and the /metrics endpoint's alert state
    "alerting": True,
    # flight recorder (spacy_ray_tpu/incidents.py): directory for
    # incident bundles — when an anomaly detector trips or an alert
    # fires, the recent metric-snapshot ring + the live span ring are
    # dumped to <incident_dir>/<utc-stamp>-<source>/ for `telemetry
    # postmortem`. "" (default) = recorder off; requires metrics_dir.
    "incident_dir": "",
    # fused optimizer update (ops/fused_update.py): the whole Adam/RAdam
    # chain + apply_updates as ONE traversal (pallas kernel on TPU when
    # the startup probe passes). "auto" = fuse on accelerators when the
    # optimizer is fusable (Adam.v1/RAdam.v1, no frozen components) and
    # keep the reference chain on CPU (measured parity there — PERF.md
    # round 7); "on" = require it anywhere, "off" = never. State
    # structure is identical either way — checkpoints survive knob flips.
    "fused_update": "auto",
    # bf16 parameter shadow: keep a persistently maintained bfloat16 copy
    # of the transformer trunk's matmul weights next to the f32 masters,
    # refreshed inside the jitted update — the per-step (and per-remat-
    # backward) 124M-weight cast disappears. "auto" = on when the trunk's
    # compute dtype resolves to bfloat16 (accelerators; compute_dtype
    # semantics unchanged), "on" = require that, "off" = never.
    "bf16_shadow": "auto",
    # run K train steps per host round-trip (lax.scan over K pre-staged
    # device batches). Default 1 = exactly the old behavior; raised, the
    # dispatch is capped so eval/max_steps boundaries still land exactly,
    # and results are bit-identical to K=1 (tested). Auto-bypassed (K=1)
    # for annotating runs, before_update callbacks, and use_averages —
    # each needs the host between consecutive steps. See TUNING.md §11
    # for when NOT to raise it (watchdog granularity, preemption latency).
    "steps_per_dispatch": 1,
    # trainer-fleet peer connection deadlines (fleet mode only; plain
    # runs ignore them). fleet_peer_timeout_s bounds every step-traffic
    # exchange (grad push, param pull); fleet_probe_timeout_s bounds the
    # liveness/membership/watch probes — probes must fail FAST so the
    # lease verdict reflects reality, while step traffic gets room for a
    # big frame on a loaded box. The /checkpoint exchange has its own
    # (much longer) checkpoint_timeout_s on the worker entry point.
    "fleet_peer_timeout_s": 10.0,
    "fleet_probe_timeout_s": 5.0,
}

# Sub-blocks resolved through the registry rather than read as plain values.
# Together with DEFAULT_TRAINING these are the FULL key surface of
# [training] — anything else is rejected (the role of the reference's
# pydantic ConfigSchemaTraining validation, reference worker.py:93
# registry.resolve(config["training"], schema=ConfigSchemaTraining)).
_TRAINING_BLOCK_KEYS = {"optimizer", "batcher", "logger", "before_update"}

# What each registry sub-block resolves to when the config omits it — the
# single source for fill-config (writes them out) and debug-diff-config
# (classifies against them).
DEFAULT_TRAINING_BLOCKS = {
    "optimizer": {"@optimizers": "Adam.v1", "learn_rate": 0.001},
    "batcher": {"@batchers": "spacy.batch_by_words.v1", "size": 1000,
                "tolerance": 0.2},
    "logger": {"@loggers": "spacy_ray_tpu.ConsoleLogger.v1"},
}

# value validators: (predicate, description) — intentionally permissive
# (ints where floats are fine etc.), strict on category errors
_TRAINING_TYPES: Dict[str, Tuple[Callable[[Any], bool], str]] = {
    "seed": (lambda v: isinstance(v, int) and not isinstance(v, bool), "an int"),
    "dropout": (
        lambda v: isinstance(v, (int, float))
        and not isinstance(v, bool)
        and 0.0 <= float(v) < 1.0,
        "a float in [0, 1)",
    ),
    "accumulate_gradient": (
        lambda v: isinstance(v, int) and not isinstance(v, bool) and v >= 1,
        "an int >= 1",
    ),
    "patience": (lambda v: isinstance(v, int) and not isinstance(v, bool) and v >= 0, "an int >= 0"),
    "max_epochs": (
        lambda v: isinstance(v, int) and not isinstance(v, bool) and v >= -1,
        "an int >= -1",
    ),
    "max_steps": (lambda v: isinstance(v, int) and not isinstance(v, bool) and v >= 0, "an int >= 0"),
    "eval_frequency": (
        lambda v: isinstance(v, int) and not isinstance(v, bool) and v >= 1,
        "an int >= 1",
    ),
    "frozen_components": (
        lambda v: isinstance(v, (list, tuple)) and all(isinstance(x, str) for x in v),
        "a list of component names",
    ),
    "annotating_components": (
        lambda v: isinstance(v, (list, tuple)) and all(isinstance(x, str) for x in v),
        "a list of component names",
    ),
    "dev_corpus": (lambda v: isinstance(v, str), "a dotted corpus name"),
    "train_corpus": (lambda v: isinstance(v, str), "a dotted corpus name"),
    "score_weights": (lambda v: isinstance(v, dict), "a mapping of score -> weight"),
    "zero1": (lambda v: isinstance(v, bool), "a bool"),
    "update_sharding": (
        lambda v: v in ("auto", "replicated", "zero1", "full"),
        'one of "auto", "replicated", "zero1", "full"',
    ),
    "mesh": (lambda v: isinstance(v, dict), "a mapping of mesh axis sizes"),
    "prefetch_batches": (
        lambda v: isinstance(v, int) and not isinstance(v, bool) and v >= 0,
        "an int >= 0",
    ),
    "collate_workers": (
        lambda v: isinstance(v, int) and not isinstance(v, bool) and v >= 0,
        "an int >= 0",
    ),
    "collate_cache_mb": (
        lambda v: isinstance(v, int) and not isinstance(v, bool) and v >= 0,
        "an int >= 0",
    ),
    "keep_checkpoints": (
        lambda v: isinstance(v, int) and not isinstance(v, bool) and v >= 1,
        "an int >= 1",
    ),
    "watchdog_timeout_s": (
        lambda v: isinstance(v, (int, float)) and not isinstance(v, bool) and v >= 0,
        "a number of seconds >= 0 (0 disables the watchdog)",
    ),
    "io_retries": (
        lambda v: isinstance(v, int) and not isinstance(v, bool) and v >= 0,
        "an int >= 0",
    ),
    "io_retry_base_s": (
        lambda v: isinstance(v, (int, float)) and not isinstance(v, bool) and v > 0,
        "a number of seconds > 0",
    ),
    "profile_window": (
        lambda v: _is_step_window(v),
        "a [start, stop] pair of ints with 0 <= start <= stop",
    ),
    "metrics_dir": (
        lambda v: isinstance(v, str),
        "a directory path string (empty string disables telemetry)",
    ),
    "trace_steps": (
        lambda v: _is_step_window(v),
        "a [start, stop] pair of ints with 0 <= start <= stop",
    ),
    "anomaly_detection": (lambda v: isinstance(v, bool), "a bool"),
    "alerting": (lambda v: isinstance(v, bool), "a bool"),
    "incident_dir": (
        lambda v: isinstance(v, str),
        "a directory path string (empty string disables the flight "
        "recorder)",
    ),
    "metrics_port": (
        lambda v: isinstance(v, int) and not isinstance(v, bool)
        and 0 <= v <= 65535,
        "a TCP port int in [0, 65535] (0 disables the endpoint)",
    ),
    "metrics_host": (
        lambda v: isinstance(v, str) and bool(v),
        "a non-empty bind address string",
    ),
    "fused_update": (
        lambda v: v in ("auto", "on", "off"),
        'one of "auto", "on", "off"',
    ),
    "bf16_shadow": (
        lambda v: v in ("auto", "on", "off"),
        'one of "auto", "on", "off"',
    ),
    "steps_per_dispatch": (
        lambda v: isinstance(v, int) and not isinstance(v, bool) and v >= 1,
        "an int >= 1",
    ),
    "fleet_peer_timeout_s": (
        lambda v: isinstance(v, (int, float)) and not isinstance(v, bool)
        and v > 0,
        "a number of seconds > 0",
    ),
    "fleet_probe_timeout_s": (
        lambda v: isinstance(v, (int, float)) and not isinstance(v, bool)
        and v > 0,
        "a number of seconds > 0",
    ),
}


def _is_step_window(v: Any) -> bool:
    return (
        isinstance(v, (list, tuple))
        and len(v) == 2
        and all(isinstance(x, int) and not isinstance(x, bool) for x in v)
        and 0 <= v[0] <= v[1]
    )


def _ms(seconds: Optional[float]) -> Optional[float]:
    """Seconds -> rounded milliseconds (None passes through)."""
    return round(seconds * 1000.0, 3) if seconds is not None else None


def _group_shape_sig(group: Dict[str, Any]) -> Tuple:
    """Shape/dtype signature of one staged batch group — steps_per_dispatch
    stacks only groups in the SAME padding bucket (a lax.scan needs
    homogeneous xs); a bucket change flushes the run and the odd group
    leads the next dispatch."""
    return tuple(
        (x.shape, str(x.dtype))
        for x in jax.tree_util.tree_leaves((group["tokens"], group["targets"]))
    )


@partial(jax.jit, donate_argnums=(0,))
def _avg_step(avg, params, t):
    """One running-mean step for use_averages. The ``avg`` accumulator is
    DONATED: before this fix every eval-window step allocated a fresh
    full-size param tree here — a second silent O(n_params) traversal's
    worth of memory churn per step (donation-audit test pins this)."""
    t = jnp.float32(t)
    return jax.tree_util.tree_map(lambda a, p: a + (p - a) / t, avg, params)


def _unknown_name_error(what: str, name: str, allowed) -> ValueError:
    """Uniform unknown-name error with a did-you-mean hint."""
    import difflib

    allowed = sorted(allowed)
    close = difflib.get_close_matches(name, allowed, n=1)
    hint = f" — did you mean {close[0]!r}?" if close else ""
    return ValueError(
        f"{what} {name!r}{hint} (known: {', '.join(allowed)})"
    )


def validate_training(raw: Dict[str, Any]) -> None:
    """Reject unknown / mistyped [training] keys loudly, with a
    did-you-mean hint — a typo'd ``patiance`` silently training with the
    default patience is a silent-wrong-training bug (the reference
    validates via pydantic at worker.py:93; VERDICT r2 weak #4)."""
    allowed = set(DEFAULT_TRAINING) | _TRAINING_BLOCK_KEYS
    for key, value in raw.items():
        if key not in allowed:
            raise _unknown_name_error("[training] has unknown key", key, allowed)
        if key in _TRAINING_BLOCK_KEYS:
            if not isinstance(value, dict):
                raise ValueError(
                    f"[training.{key}] must be a registry block "
                    f"(a [training.{key}] section), got {type(value).__name__}"
                )
            continue
        pred, desc = _TRAINING_TYPES[key]
        if not pred(value):
            raise ValueError(
                f"[training] {key} must be {desc}, got {value!r} "
                f"({type(value).__name__})"
            )


def resolve_training(config: Config) -> Dict[str, Any]:
    raw = config.get("training", {})
    validate_training(raw)
    t = dict(DEFAULT_TRAINING)
    t.update(raw)
    return t


def resolve_dot_name(config: Config, resolved_corpora: Dict[str, Any], dot_name: str):
    """'corpora.train' -> resolved reader (reference worker.py:94-95
    ``resolve_dot_names``)."""
    parts = dot_name.split(".")
    if parts[0] != "corpora" or len(parts) != 2:
        raise ValueError(f"Unsupported dot name {dot_name!r}")
    if parts[1] not in resolved_corpora:
        raise ValueError(f"No [corpora.{parts[1]}] block in config")
    return resolved_corpora[parts[1]]


class TrainResult:
    def __init__(self):
        self.best_score: float = -1.0
        self.best_step: int = -1
        self.final_step: int = 0
        self.epoch: int = 0
        self.history: List[Dict[str, Any]] = []
        self.words_seen: int = 0
        self.seconds: float = 0.0
        # True when the run stopped on a shutdown signal (preemption):
        # a step-boundary checkpoint was written and the CLI exits with
        # resilience.RC_PREEMPTED so supervisors can tell "resume me"
        # from "done"
        self.interrupted: bool = False
        # what the platform-dependent [training] switches resolved to on
        # this run's mesh and backend (honest labels, by name)
        self.resolved: Dict[str, str] = {}

    @property
    def wps(self) -> float:
        return self.words_seen / self.seconds if self.seconds > 0 else 0.0


def default_pipeline_score_weights(nlp: Pipeline) -> Dict[str, float]:
    """Combine the pipeline components' declared ``default_score_weights``
    and normalize the positive weights to sum 1 — spaCy's
    ``util.combine_score_weights`` semantics for the default [training]
    score_weights (each factory declares its metadata; the reference
    inherits this through spaCy's init_nlp, reference worker.py:91)."""
    combined: Dict[str, float] = {}
    for name in nlp.pipe_names:
        comp_weights = getattr(nlp.components[name], "default_score_weights", None)
        for key, value in (comp_weights or {}).items():
            combined[key] = float(value)  # later components override
    total = sum(v for v in combined.values() if v > 0)
    if total > 0:
        combined = {k: (v / total if v > 0 else 0.0) for k, v in combined.items()}
    return combined


def weighted_score(scores: Dict[str, float], weights: Dict[str, float]) -> float:
    """spaCy final-score semantics: None scores (no gold annotation for
    that metric) are EXCLUDED rather than counted as 0."""
    if not weights:
        # last-resort fallback (pipeline declared NO score metadata at
        # all): mean of all numeric scores (None / nested excluded)
        vals = [
            v
            for v in scores.values()
            if isinstance(v, (int, float)) and not isinstance(v, bool)
        ]
        return float(np.mean(vals)) if vals else 0.0
    total = 0.0
    for key, weight in weights.items():
        if weight in (None, 0.0):
            continue
        value = scores.get(key)
        if value is None:
            continue
        total += float(value) * float(weight)
    return total


def train(
    config: Config,
    output_path: Optional[Path] = None,
    *,
    n_workers: Optional[int] = None,
    resume: bool = False,
    max_steps_override: Optional[int] = None,
    stdout_log: bool = True,
    profile_dir: Optional[Path] = None,
    metrics_dir: Optional[Path] = None,
    metrics_port: Optional[int] = None,
) -> Tuple[Pipeline, TrainResult]:
    """Run config-driven training. Returns (pipeline, result).

    ``n_workers`` maps to the mesh's data-axis size (the reference's
    ``--n-workers`` actor count, train_cli.py:27); default = all devices.

    ``profile_dir``: capture a jax.profiler trace of the
    ``[training] profile_window`` steps (default 5-15; first-class
    tracing — the reference's Timer scaffolding is unwired, SURVEY.md §5.1).

    ``metrics_dir``: override for ``[training] metrics_dir`` — enables the
    telemetry subsystem (metrics.jsonl + Chrome trace + anomaly
    detectors, training/telemetry.py).
    """
    config = config.interpolate()
    T = resolve_training(config)
    seed = int(T.get("seed") or 0)
    random.seed(seed)
    np.random.seed(seed)

    # ---- resilience setup ----
    # fault plan from the environment (a supervisor-relaunched child reads
    # its own copy), transient-I/O retry policy from the config, and the
    # SIGTERM/SIGINT flag the loop polls at step boundaries
    resilience.activate_env_fault_plan()
    # a previous run in this process may have queued events no logger
    # drained (console logger path) — they must not leak into THIS run's
    # first jsonl row
    resilience.drain_events()
    resilience.set_default_retry_policy(
        resilience.RetryPolicy(
            max_retries=int(T.get("io_retries", 3) or 0),
            base_delay=float(T.get("io_retry_base_s", 0.5) or 0.5),
        )
    )
    # created now, installed right before the main loop (whose finally is
    # the only place that restores handlers — a setup-phase failure must
    # not leak a handler pointing at an abandoned run)
    shutdown = ShutdownCoordinator()

    # ---- telemetry (training/telemetry.py) ----
    # Process 0 owns the files (every rank's loop is replica-identical, so
    # rank 0's timeline IS the pod's); disabled = `tel is None` and the
    # hot loop makes ZERO telemetry calls — every use below is guarded.
    tel = None
    tel_http = None
    tel_dir = str(metrics_dir) if metrics_dir is not None else str(
        T.get("metrics_dir") or ""
    )
    if not tel_dir and (
        metrics_port or T.get("metrics_port")
    ) and jax.process_index() == 0:
        # the endpoint serves the telemetry objects — with telemetry off
        # there is nothing to serve, and silently dropping an explicit
        # --metrics-port would leave the operator's scraper getting
        # connection-refused with no hint why
        log_event(
            "telemetry-endpoint-skipped",
            "--metrics-port/[training] metrics_port is set but telemetry "
            "is disabled (no metrics_dir) — no endpoint started; set "
            "--metrics-dir/[training] metrics_dir to enable it",
        )
    if tel_dir and jax.process_index() == 0:
        from .telemetry import Telemetry

        trace_steps = T.get("trace_steps") or [0, 50]
        tel = Telemetry(
            Path(tel_dir),
            trace_steps=(int(trace_steps[0]), int(trace_steps[1])),
            anomaly_detection=bool(T.get("anomaly_detection", True)),
            process_index=jax.process_index(),
            alerting=bool(T.get("alerting", True)),
            incident_dir=(
                Path(str(T.get("incident_dir")))
                if T.get("incident_dir") else None
            ),
        )
        # trainer-side scrape endpoint ([training] metrics_port /
        # train --metrics-port): /metrics (+?format=prometheus),
        # /healthz clock anchor, /trace — the trainer's leg of the
        # cross-process observability plane
        tel_port = int(
            metrics_port if metrics_port is not None
            else T.get("metrics_port") or 0
        )
        if tel_port > 0:
            import logging as _logging

            from .telemetry_http import TelemetryHTTPServer

            tel_http = TelemetryHTTPServer(
                tel,
                host=str(T.get("metrics_host") or "127.0.0.1"),
                port=tel_port,
            )
            host, bound = tel_http.start()
            log_event(
                "telemetry-endpoint",
                f"trainer telemetry on http://{host}:{bound} "
                "(/metrics, /healthz, /trace)",
                level=_logging.INFO,
                port=bound,
            )

    def _tspan(name: str, **args: Any):
        """Span context when telemetry is on, else the bare profiler
        annotation (a flag check unless a profiler trace is running)."""
        if tel is None:
            return jax.profiler.TraceAnnotation(names.SPAN_PREFIX + name)
        return tel.trace.span(name, cat="loop", **args)

    # ---- corpora ----
    corpora_cfg = config.get("corpora", {})
    resolved_corpora = {name: registry.resolve(block) for name, block in corpora_cfg.items()}
    train_corpus = resolve_dot_name(config, resolved_corpora, T["train_corpus"])
    dev_corpus = resolve_dot_name(config, resolved_corpora, T["dev_corpus"])

    # ---- pipeline ----
    nlp = Pipeline.from_config(config)
    nlp.initialize(train_corpus, seed=seed)

    # Multi-host startup assertion: every host must have built the IDENTICAL
    # param tree (same paths, same label sets) — the SPMD-era replacement for
    # the reference's unchecked reliance on identical model construction
    # order (SURVEY.md §2.4 "Key identity is fragile", §5.2 race detection).
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        from ..models.core import param_paths
        from ..ops.hashing import hash_string_u64

        signature = "|".join(param_paths(nlp.params)) + "||" + "|".join(
            f"{n}:{','.join(nlp.components[n].labels)}" for n in nlp.pipe_names
        )
        digest = np.array([hash_string_u64(signature) % (2 ** 31)], np.int32)
        digests = multihost_utils.process_allgather(digest)
        if int(np.min(digests)) != int(np.max(digests)):
            raise RuntimeError(
                "Parameter-tree/label mismatch across hosts: all processes "
                "must resolve the same config over the same training data "
                f"(digests: {digests.tolist()})"
            )

    # ---- mesh / optimizer / step ----
    mesh_cfg = dict(T.get("mesh") or {})
    mesh = build_mesh(
        n_data=n_workers if n_workers is not None else mesh_cfg.get("n_data"),
        n_model=int(mesh_cfg.get("n_model", 1)),
        n_context=int(mesh_cfg.get("n_context", 1)),
        n_pipe=int(mesh_cfg.get("n_pipe", 1)),
    )
    n_data = mesh.shape["data"]
    # [training] update_sharding, resolved against THIS run's mesh/backend
    # (the zero1 bool stays as an accepted alias — parallel/step.py)
    zero1 = bool(T.get("zero1"))
    update_sharding = resolve_update_sharding(
        str(T.get("update_sharding", "auto")), zero1=zero1, n_data=int(n_data)
    )
    if update_sharding != "replicated":
        import logging as _logging

        log_event(
            "update-sharding",
            f"update phase: {update_sharding_status(update_sharding, mesh)}",
            level=_logging.INFO,
            mode=update_sharding,
            n_data=int(n_data),
        )
    tx = registry.resolve(T.get("optimizer") or {"@optimizers": "Adam.v1"})
    tx = _optimizers.mask_frozen(tx, nlp.params)  # skip frozen_ leaves entirely
    # [training] fused_update: rebuild a fusable chain as one traversal
    # (ops/fused_update.py). State structure is identical, so resume works
    # across knob flips; "auto" silently keeps the reference chain for
    # unfusable optimizers (masked/frozen, custom registrations) AND on
    # CPU, where the round-7 A/B measured the mega-fusion at parity-to-
    # slightly-slower vs XLA's own chain fusion (PERF.md "Fixed-cost
    # floor"; the same platform-gating precedent as compute_dtype="auto").
    fused_mode = str(T.get("fused_update", "auto"))
    if fused_mode == "on" or (
        fused_mode == "auto"
        and (
            jax.default_backend() != "cpu"
            # full update-sharding prefers the fused transformation even on
            # CPU: its partitioner-proof global norm (stable_global_norm)
            # is what guarantees full == replicated to EQUALITY; the optax
            # chain's in-chain clip norm is at the partitioner's mercy
            or (update_sharding == "full" and int(n_data) > 1)
        )
    ):
        fused_tx = _optimizers.fuse_optimizer(tx)
        if fused_tx is not None:
            tx = fused_tx
        elif fused_mode == "on":
            raise ValueError(
                '[training] fused_update = "on" needs a fusable optimizer '
                "(Adam.v1 / RAdam.v1 with no frozen_ param leaves); use "
                '"auto" to fall back to the reference chain silently'
            )
    batcher = registry.resolve(
        T.get("batcher")
        or {"@batchers": "spacy.batch_by_words.v1", "size": 1000, "tolerance": 0.2}
    )
    accum = max(int(T.get("accumulate_gradient") or 1), 1)

    params = place_replicated(nlp.params, mesh)
    opt_state = tx.init(params)
    opt_state = shard_opt_state(opt_state, mesh, update_sharding)

    rng = jax.random.PRNGKey(seed)
    step = 0
    epoch = 0
    best_score = -1.0
    best_step = -1

    # ---- resume ----
    resume_skip = 0  # batches already consumed in the checkpointed epoch
    if resume and output_path is not None:
        try:
            with _tspan("checkpoint_load"):
                ckpt = TrainCheckpoint.load(Path(output_path) / "last-model")
        except CheckpointCorrupt as e:
            # every retained generation is torn: warn and train from
            # scratch rather than crash — the data survives, the run
            # restarts (and log_event lands the anomaly in jsonl logs)
            log_event(
                "resume-failed",
                f"--resume found no intact checkpoint generation ({e}); "
                "starting from scratch",
            )
            ckpt = None
        if jax.process_count() > 1:
            # generation fallback is a PER-RANK decision over possibly-flaky
            # shared storage: if one rank fell back to an older generation
            # (or to scratch) while the others resumed the newest, the ranks
            # hold different step counters and every later collective
            # desyncs — fail loudly at startup instead of wedging the pod
            from jax.experimental import multihost_utils

            steps = multihost_utils.process_allgather(
                np.array([ckpt["step"] if ckpt is not None else -1], np.int64)
            )
            if int(np.min(steps)) != int(np.max(steps)):
                raise RuntimeError(
                    "--resume loaded different checkpoint generations across "
                    f"hosts (per-rank steps: {steps.ravel().tolist()}); fix or "
                    "remove the torn generation so every rank resumes the "
                    "same state"
                )
        if ckpt is not None:
            # elastic resume: the checkpoint's canonical unsharded state is
            # re-sharded under THIS run's mesh — the save-time mesh shape
            # (recorded in extra) does not constrain the resume shape
            saved_mesh = (ckpt.get("extra") or {}).get("mesh") or {}
            saved_n_data = saved_mesh.get("n_data")
            if saved_n_data is not None and int(saved_n_data) != int(n_data):
                log_event(
                    "elastic-resume",
                    f"checkpoint was written on a {saved_n_data}-replica "
                    f"data axis; re-sharding to this run's {int(n_data)} "
                    f"(update_sharding={update_sharding})",
                    saved_n_data=int(saved_n_data),
                    n_data=int(n_data),
                )
            params = place_replicated(ckpt["params"], mesh)
            opt_state = shard_opt_state(ckpt["opt_state"], mesh, update_sharding)
            step = ckpt["step"]
            epoch = ckpt["epoch"]
            rng = ckpt["rng"]
            best_score = ckpt["best_score"]
            best_step = ckpt["best_step"]
            # exact data-position resume: reproduce the checkpointed epoch's
            # shuffle order (restore the corpus's own epoch counter — it may
            # be offset from the loop's epoch by initialize() passes), then
            # fast-forward past the batches already consumed. On multi-host,
            # the checkpoint carries EVERY rank's (epoch, batches_in_epoch,
            # corpus_epoch) — per-host epoch boundaries drift when shards
            # are unequal, so each rank fast-forwards to its OWN position
            # (VERDICT r3 next #4; rank-0 scalars kept for old checkpoints).
            resume_skip = int(ckpt["extra"].get("batches_in_epoch", 0))
            corpus_epoch = ckpt["extra"].get("corpus_epoch")
            per_rank = ckpt["extra"].get("per_rank_positions")
            if per_rank is not None:
                if len(per_rank) == jax.process_count():
                    my_epoch, my_skip, my_corpus_epoch = per_rank[jax.process_index()]
                    epoch = int(my_epoch)
                    resume_skip = int(my_skip)
                    corpus_epoch = int(my_corpus_epoch)
                else:
                    log_event(
                        "resume-rank-mismatch",
                        f"checkpoint was written by {len(per_rank)} "
                        f"processes but this run has {jax.process_count()}; "
                        "data position restored from rank 0's scalars "
                        "(approximate — the stream sharding changed)",
                        checkpoint_processes=len(per_rank),
                        run_processes=jax.process_count(),
                    )
            if corpus_epoch is not None and hasattr(train_corpus, "_epoch"):
                train_corpus._epoch = int(corpus_epoch)
            import logging as _logging

            log_event(
                "resume",
                f"resumed from checkpoint step {step} (epoch {epoch}, "
                f"best {best_score:.4f} @ step {best_step})",
                level=_logging.INFO,
                step=step,
                epoch=epoch,
            )
        else:
            log_event(
                "resume-empty",
                f"--resume requested but {Path(output_path) / 'last-model'} "
                "holds no checkpoint; starting from scratch",
            )

    # [training] annotating_components: validated against the pipeline, then
    # each batch is annotated with the CURRENT model's predictions before
    # collation so downstream components train on upstream predictions
    # (reference worker.py:187 threads the list into train_while_improving)
    annotating = list(T.get("annotating_components") or [])
    for comp_name in annotating:
        if comp_name not in nlp.pipe_names:
            raise _unknown_name_error(
                "[training] annotating_components names", comp_name, nlp.pipe_names
            )
    for comp_name in T.get("frozen_components") or []:
        if comp_name not in nlp.pipe_names:
            raise _unknown_name_error(
                "[training] frozen_components names", comp_name, nlp.pipe_names
            )
    # Multi-host annotation runs HOST-LOCALLY (see device_groups): each host
    # device_gets the replicated trunk + annotating-head params once per
    # update group and predicts on its local devices with no mesh, so
    # per-host batch divergence can't launch mismatched global programs.
    # (The reference supports annotating_components at N worker processes
    # trivially — each Ray worker threads the list into its own loop,
    # reference worker.py:187; VERDICT r3 next #2.)
    # A component that trains on predicted upstream annotations
    # (use_gold_ents = false) learns NOTHING unless some annotating
    # component actually writes those annotations — catch the silent
    # zero-mention configuration here rather than training a no-op.
    for comp_name in nlp.pipe_names:
        comp = nlp.components[comp_name]
        if getattr(comp, "use_gold_ents", True):
            continue
        writers = [n for n in annotating if nlp.components[n].sets_ents]
        if not writers:
            raise ValueError(
                f"[components.{comp_name}] sets use_gold_ents = false, so its "
                "training mentions come from predicted doc.ents — but no "
                "[training] annotating_components entry writes entities. Add "
                "an entity-setting component (ner / entity_ruler) to "
                "annotating_components, or set use_gold_ents = true"
            )

    # [training.before_update] callback slot (spaCy semantics: called with
    # (nlp, {"step": ..., "epoch": ...}) before every optimizer update —
    # reference worker.py:188 passes it into train_while_improving)
    before_update: Optional[Callable] = None
    if T.get("before_update"):
        before_update = registry.resolve(T["before_update"])
        if not callable(before_update):
            raise ValueError(
                "[training.before_update] must resolve to a callable — add "
                "an @callbacks line to the block (got "
                f"{type(before_update).__name__})"
            )

    # Parameter averaging (thinc Adam use_averages semantics): running mean
    # of params, used for eval + best-model checkpoints.
    use_averages = bool(getattr(tx, "use_averages", False))
    # copy: params buffers are donated to the jitted update, so an alias
    # would dereference deleted buffers at the first _avg_step on TPU
    avg_params = (
        jax.tree_util.tree_map(jnp.copy, params) if use_averages else None
    )
    avg_count = 0

    # [training] bf16_shadow: persistent bf16 copies of the trunk's matmul
    # weights, built AFTER resume (from the final params) and maintained
    # incrementally inside the jitted update. "auto" resolves through the
    # trunk's compute dtype so CPU runs (f32 compute) change nothing.
    shadow_mode = str(T.get("bf16_shadow", "auto"))
    shadow = None
    if shadow_mode in ("auto", "on"):
        from ..models.shadow import build_param_shadow, pipeline_shadow_dtype

        shadow_dtype = pipeline_shadow_dtype(nlp)
        if shadow_dtype is not None:
            shadow = build_param_shadow(params, shadow_dtype)
        if shadow is None and shadow_mode == "on":
            raise ValueError(
                '[training] bf16_shadow = "on" needs a transformer trunk '
                "whose compute dtype resolves to bfloat16 (compute_dtype = "
                '"bfloat16", or "auto" on an accelerator); use "auto" to '
                "disable the shadow silently where it cannot help"
            )

    # [training] steps_per_dispatch: K compiled steps per host round-trip.
    # Modes that need the host between consecutive steps bypass to 1.
    steps_per_dispatch = max(int(T.get("steps_per_dispatch", 1) or 1), 1)
    if steps_per_dispatch > 1 and (
        annotating or before_update is not None or use_averages
    ):
        log_event(
            "steps-per-dispatch-bypass",
            "steps_per_dispatch > 1 needs the host between steps for "
            "annotating_components / before_update / use_averages; "
            "running with K=1",
        )
        steps_per_dispatch = 1

    loss_fn = nlp.make_loss_fn(dropout=float(T["dropout"]))
    update = make_train_step(
        loss_fn, tx, mesh, accumulate_gradient=accum,
        update_sharding=update_sharding,
        opt_state_template=opt_state, shadow=shadow is not None,
    )
    update_multi = (
        make_train_step(
            loss_fn, tx, mesh, accumulate_gradient=accum,
            update_sharding=update_sharding,
            opt_state_template=opt_state, shadow=shadow is not None,
            multi_dispatch=True,
        )
        if steps_per_dispatch > 1
        else None
    )

    # ---- logger ----
    logger_cfg = T.get("logger") or {"@loggers": "spacy_ray_tpu.ConsoleLogger.v1"}
    logger_setup = registry.resolve(logger_cfg)
    import io as _io
    import sys as _sys

    log_stdout = _sys.stdout if stdout_log else _io.StringIO()
    log_step, log_finalize = logger_setup(nlp, log_stdout, _sys.stderr)

    # ---- dev set (materialized once) ----
    dev_examples = list(dev_corpus())

    # empty [training.score_weights] falls back to the components' declared
    # defaults (normalized), NOT a blind mean over every numeric score —
    # mixing accuracies with AUCs silently was VERDICT r3 weak #6
    score_weights = dict(T.get("score_weights") or {})
    if not score_weights:
        score_weights = default_pipeline_score_weights(nlp)

    max_steps = int(max_steps_override or T["max_steps"] or 0)
    max_epochs = int(T["max_epochs"] or 0)
    eval_frequency = int(T["eval_frequency"] or 200)
    patience = int(T["patience"] or 0)

    result = TrainResult()
    process_rank = jax.process_index()
    process_count = jax.process_count()

    batches_in_epoch = 0  # data position within the current epoch
    stream_corpus_epoch = 0  # corpus._epoch as of the current stream

    def batches_forever() -> Iterator[Tuple[int, List[Example]]]:
        nonlocal epoch, batches_in_epoch, stream_corpus_epoch
        skip = resume_skip
        while True:
            stream_corpus_epoch = getattr(train_corpus, "_epoch", 0)
            stream = train_corpus()
            if process_count > 1:
                stream = shard_stream(stream, process_rank, process_count)
            got_any = False
            for b in batcher(stream):
                got_any = True
                # batches_in_epoch is the position from the EPOCH START, so
                # fast-forwarded batches count too — otherwise a checkpoint
                # written after a resume would record a position relative to
                # the resume point and a second resume would be inexact
                batches_in_epoch += 1
                if skip > 0:  # resume fast-forward within the first epoch
                    skip -= 1
                    continue
                yield epoch, b
            if not got_any:
                raise ValueError("Training corpus is empty")
            skip = 0
            epoch += 1
            batches_in_epoch = 0
            if max_epochs and epoch >= max_epochs:
                return

    start_time = time.perf_counter()
    loss_accum: Dict[str, float] = {}
    pending_metrics: List[Tuple[Dict[str, Any], bool]] = []
    counter_totals: Dict[str, int] = {}  # device counters (names.COUNTER_PREFIX), summed over the run
    words_since_log = 0
    last_log_time = start_time
    stop = False
    steps_run = 0  # steps executed THIS run (profiling window is resume-safe)
    profile_active = False
    # configurable jax.profiler window (was hardcoded 5-15): counted in
    # steps_run, not global step, so a resumed run still profiles its own
    # warm steps rather than an arbitrary slice of the step counter
    profile_window = T.get("profile_window") or [5, 15]
    profile_start, profile_stop = int(profile_window[0]), int(profile_window[1])

    def drain_metrics() -> None:
        """Materialize queued device metrics into loss_accum (sync point).

        A step poisoned by a ``nan`` fault rule gets its loss overwritten
        HERE, on the host — poisoning on device would dispatch fresh XLA
        ops whose compile the recompile-storm detector would (correctly,
        but spuriously for the drill) flag."""
        for m, poisoned in pending_metrics:
            host = jax.device_get(m)
            for key, value in host.items():
                if key.startswith("loss_"):
                    v = float("nan") if poisoned else float(value)
                    loss_accum[key[5:]] = loss_accum.get(key[5:], 0.0) + v
                elif key.startswith(names.COUNTER_PREFIX):
                    counter_totals[key] = counter_totals.get(key, 0) + int(value)
        pending_metrics.clear()

    # ---- staged input pipeline (read -> collate -> transfer) ----
    # Stage split exists so collation can fan out over worker threads while
    # the read stage (corpus/batcher state) and the transfer stage
    # (device_put + all multi-host collectives) each stay on ONE thread —
    # the ordering constraint documented in prefetch.py / collate_pool.py.
    from .collate_pool import (
        CollateCache,
        PipelineStats,
        cached_collate,
        ordered_map,
    )

    pipe_stats = PipelineStats()
    if tel is not None:
        # stage timings double as Chrome-trace spans — emitted identically
        # whether collation runs inline or on pool workers (each worker
        # thread gets its own trace track)
        pipe_stats.attach_trace(tel.trace)
    collate_workers = int(T.get("collate_workers", 0) or 0)
    collate_cache_mb = int(T.get("collate_cache_mb", 0) or 0)
    # the pool runs only where the prefetch thread may: single-process,
    # non-annotating (annotation must predict with the step's own params)
    use_pool = collate_workers >= 2 and process_count == 1 and not annotating
    pipe_stats.workers = collate_workers if use_pool else 1
    # identity-keyed cache: only meaningful when epochs re-yield the SAME
    # Example objects in the SAME batches. Auto-bypass when the corpus says
    # batches can't recur (augmentation = fresh copies per epoch; shuffle =
    # different batch membership per epoch; Corpus.stable_identity) and in
    # annotating mode (targets depend on per-step predictions). Readers
    # that don't declare either flag get the cache as configured — the
    # byte-capped LRU bounds the damage if their batches never recur.
    corpus_augmented = bool(getattr(train_corpus, "augmented", False))
    cache_stable = bool(
        getattr(train_corpus, "stable_identity", not corpus_augmented)
    )
    collate_cache: Optional[CollateCache] = None
    if collate_cache_mb > 0 and not annotating and cache_stable:
        collate_cache = CollateCache(collate_cache_mb * 1024 * 1024)
        pipe_stats.cache_enabled = True

    def gather_groups() -> Iterator[Dict[str, Any]]:
        """Read stage: one update's worth of RAW batches + position tags.

        Each record carries its own data-position tags (batches_in_epoch /
        corpus_epoch snapshots) so the consumer checkpoints the position of
        the group it actually trained on — exact resume stays exact even
        when this generator runs ahead on the prefetch thread or the
        collation pool. Multi-host shape/termination allgathers live here,
        on the one thread that iterates this generator (the pool never
        wraps the multi-host path).
        """
        batch_iter = batches_forever()
        while True:
            # gather `accum` raw batches (stacked microbatches per update)
            raw_batches: List[List[Example]] = []
            cur_epoch = epoch
            with pipe_stats.timer(names.READ):
                try:
                    for _ in range(accum):
                        cur_epoch, b = next(batch_iter)
                        raw_batches.append(b)
                    have_group = True
                except StopIteration:
                    # end of data: an incomplete accumulation group would
                    # under-scale the mean gradient (scan still divides by
                    # `accum`)
                    have_group = False
            if process_count > 1:
                # loop termination must be COLLECTIVE: if any host ran out
                # of data, all hosts stop this step, else the continuing
                # hosts enter the update collectives alone and deadlock
                from jax.experimental import multihost_utils

                flags = multihost_utils.process_allgather(
                    np.array([1 if have_group else 0], np.int32)
                )
                if int(np.min(flags)) == 0:
                    return
            elif not have_group:
                return
            if annotating:
                # annotate each batch with the CURRENT model before target
                # construction, so downstream components (e.g. an
                # entity_linker with use_gold_ents = false) train on
                # upstream predictions — spaCy's annotating_components
                # semantics (reference worker.py:187). Runs inline (this
                # mode disables the prefetch thread): the predictions come
                # from the same pre-update params spaCy would use.
                current = params_cell["params"]
                if process_count > 1:
                    # host-local annotation: restrict to the trunk + the
                    # annotating heads (the only subtrees the annotation
                    # forward reads) and predict with no mesh — a purely
                    # local program per host. Replicated leaves stay ON
                    # DEVICE: the local shard of a fully-replicated array
                    # IS the full value, so handing it to the host-local
                    # jit program costs zero transfers (round-4 advisor:
                    # the previous device_get here was a full trunk
                    # host round-trip per accumulation group — material
                    # for a flagship-size trf trunk on a real pod).
                    needed = set(annotating)
                    if nlp.tok2vec_name is not None:
                        needed.add(nlp.tok2vec_name)

                    def _local_view(a):
                        if (
                            isinstance(a, jax.Array)
                            and a.sharding.is_fully_replicated
                        ):
                            return a.addressable_data(0)
                        return jax.device_get(a)  # sharded: host assemble

                    current = {
                        name: jax.tree_util.tree_map(_local_view, current[name])
                        for name in needed
                        if name in current
                    }
                    ann_mesh = None
                else:
                    ann_mesh = mesh
                for b in raw_batches:
                    shells = [eg.reference.copy_shell() for eg in b]
                    nlp.predict_docs(
                        shells, params=current, mesh=ann_mesh, annotate=annotating
                    )
                    for eg, shell in zip(b, shells):
                        eg.predicted = shell
            # bucketed padded shapes, computed in the read stage: the
            # multi-host shape sync below is a collective and must stay on
            # this (single) thread, never inside a pool worker
            max_len = max(max(len(eg) for eg in b) for b in raw_batches)
            max_b = max(len(b) for b in raw_batches)
            T_pad = bucket_length(max_len, nlp.length_buckets)
            # B must divide evenly over the mesh data axis for P("data")
            B_pad = max(bucket_batch_size(max_b), n_data)
            B_pad = ((B_pad + n_data - 1) // n_data) * n_data
            n_words: Optional[int] = None  # single-process: counted at collate
            if process_count > 1:
                # multi-controller SPMD: every host must launch the same
                # program — sync padded shapes to the all-host max. The same
                # allgather carries each host's word count: the global batch
                # is the concatenation of all hosts' rows (place_batch), so
                # the words consumed this step are the sum over hosts, not
                # local × P.
                from jax.experimental import multihost_utils

                local_words = sum(len(eg) for b in raw_batches for eg in b)
                dims = multihost_utils.process_allgather(
                    np.array([T_pad, B_pad, local_words], np.int32)
                ).reshape(-1, 3)
                T_pad = int(dims[:, 0].max())
                B_pad = int(dims[:, 1].max())
                n_words = int(dims[:, 2].sum())
            yield {
                "raw_batches": raw_batches,
                "B_pad": B_pad,
                "T_pad": T_pad,
                "n_words": n_words,
                "cur_epoch": cur_epoch,
                "batches_in_epoch": batches_in_epoch,
                "corpus_epoch": stream_corpus_epoch,
            }

    def collate_group(item: Dict[str, Any]) -> Dict[str, Any]:
        """Tokenize+hash+collate stage: raw batches -> stacked HOST arrays.

        Pure host work (no device_put, no collectives) so the pool may run
        it on any worker thread. Collated host batches are cached per
        (batch identity, bucket shape) when the cache is enabled — a
        steady-state epoch then reduces to cache lookups + device_put.
        """
        raw_batches = item["raw_batches"]
        B_pad, T_pad = item["B_pad"], item["T_pad"]
        with pipe_stats.timer(names.COLLATE):
            # a cache hit opens no child span: it is this span's self time
            collated = [
                cached_collate(
                    collate_cache,
                    b,
                    B_pad,
                    T_pad,
                    lambda b_, B_, T_: nlp.collate(
                        b_, pad_batch_to=B_, pad_len_to=T_, host=True,
                        stats=pipe_stats,
                    ),
                    pipe_stats,
                )
                for b in raw_batches
            ]
            n_words = item["n_words"]
            if n_words is None:  # single-process: no dims allgather happened
                n_words = sum(c["n_words"] for c in collated)
            if accum == 1:
                tokens, targets = collated[0]["tokens"], collated[0]["targets"]
            else:
                # host-side stack: one contiguous array per leaf so the
                # transfer stage pays a single device_put (multi-host
                # place_batch re-assembles on the host anyway)
                with pipe_stats.timer(names.COLLATE_STACK):
                    tokens = jax.tree_util.tree_map(
                        lambda *xs: np.stack(xs),
                        *[c["tokens"] for c in collated],
                    )
                    targets = jax.tree_util.tree_map(
                        lambda *xs: np.stack(xs),
                        *[c["targets"] for c in collated],
                    )
        return {
            "tokens": tokens,
            "targets": targets,
            "n_words": n_words,
            "cur_epoch": item["cur_epoch"],
            "batches_in_epoch": item["batches_in_epoch"],
            "corpus_epoch": item["corpus_epoch"],
        }

    def device_groups() -> Iterator[Dict[str, Any]]:
        """Consumer composition: read -> (pooled) collate -> transfer.

        Whatever single thread iterates THIS generator (the main loop, or
        the prefetch producer) is the only thread that calls device_put —
        pool workers stop at host arrays.
        """
        collated_iter = ordered_map(
            gather_groups(),
            collate_group,
            workers=collate_workers if use_pool else 1,
        )
        try:
            for group in collated_iter:
                with pipe_stats.timer(names.TRANSFER):
                    group["tokens"] = place_batch(
                        group["tokens"], mesh, accum=accum > 1
                    )
                    group["targets"] = place_batch(
                        group["targets"], mesh, accum=accum > 1
                    )
                yield group
        finally:
            close = getattr(collated_iter, "close", None)
            if close is not None:
                close()

    # ---- resilience wiring: watchdog + step-boundary checkpoint ----
    watchdog_timeout = float(T.get("watchdog_timeout_s", 0) or 0)
    watchdog: Optional[Watchdog] = None
    if watchdog_timeout > 0:
        watchdog_stats = pipe_stats.snapshot
        if tel is not None:
            def watchdog_stats():
                # the watchdog hard-exits (os._exit) right after the dump:
                # flush the metric rows + trace buffer NOW so the wedged
                # run's timeline survives for the post-mortem
                tel.emergency_flush()
                return pipe_stats.snapshot()
        watchdog = Watchdog(watchdog_timeout, stats_fn=watchdog_stats)
    keep_checkpoints = int(T.get("keep_checkpoints", 2) or 1)
    last_saved_step = -1

    def save_last(group: Dict[str, Any]) -> None:
        """Write the full-resume checkpoint for the CONSUMED group's step.

        Shared by the eval path and the preemption path so both write the
        identical state shape. The opt-state gather and the data-position
        allgather are COLLECTIVES on multi-host — every rank runs them at
        the same step boundary (rank 0 then writes the files), which is
        why the shutdown flag itself is allgathered first.
        """
        nonlocal last_saved_step
        if output_path is None or step == last_saved_step:
            return
        # every rank's data position, gathered on EVERY process (a
        # collective — all ranks reach this in lockstep); saved by rank 0
        # so each rank can fast-forward to its own exact position on resume
        per_rank_pos = None
        if process_count > 1:
            from jax.experimental import multihost_utils

            per_rank_pos = (
                multihost_utils.process_allgather(
                    np.array(
                        [
                            group["cur_epoch"],
                            group["batches_in_epoch"],
                            group["corpus_epoch"],
                        ],
                        np.int64,
                    )
                )
                .reshape(-1, 3)
                .tolist()
            )
        # called on EVERY rank: with a sharded opt state each rank writes
        # its OWN owner-shard part files (no allgather of the full state
        # through any host — checkpoint.py format v2); rank gating for the
        # params/meta/pointer writes is internal to save()
        TrainCheckpoint.save(
            Path(output_path) / "last-model",
            params=params,  # raw (not averaged): resume state
            opt_state=opt_state,
            step=step,
            epoch=group["cur_epoch"],
            # post-split rng, NOT this step's subkey: resume must
            # continue the exact rng chain the uninterrupted run
            # would have used
            rng=rng,
            best_score=best_score,
            best_step=best_step,
            extra={
                # the CONSUMED group's position tags, not the (possibly
                # prefetched-ahead) producer counters
                "batches_in_epoch": group["batches_in_epoch"],
                "corpus_epoch": group["corpus_epoch"],
                # save-time mesh shape + resolved sharding mode: purely
                # informational (elastic resume re-shards to the CURRENT
                # mesh), logged when the shapes differ
                "mesh": {
                    "n_data": int(n_data),
                    "update_sharding": update_sharding,
                },
                **(
                    {"per_rank_positions": per_rank_pos}
                    if per_rank_pos is not None
                    else {}
                ),
            },
            keep=keep_checkpoints,
        )
        last_saved_step = step  # on every rank: the skip must stay aligned

    last_consumed_epoch = epoch
    dispatch_pushback: Optional[Dict[str, Any]] = None  # bucket-change carry
    # the open `loop_host` span: everything this thread does per dispatch
    # that is not waiting for input, evaluating or checkpointing. It is
    # closed round each of those and re-opened after, so its seconds are
    # the loop's own host work and nothing else.
    host_span: Optional[Any] = None

    def host_work(on: bool) -> None:
        nonlocal host_span
        if host_span is not None:
            host_span.__exit__(None, None, None)
            host_span = None
        if on:
            host_span = pipe_stats.timer(names.LOOP_HOST).__enter__()

    params_cell = {"params": params}  # read by the annotation pass
    groups: Iterator[Dict[str, Any]] = device_groups()
    prefetch_n = int(T.get("prefetch_batches", 2) or 0)
    if process_count == 1 and not annotating:
        # overlap collation + host->device transfer with the running step
        # (multi-host keeps the inline path: the producer's allgathers must
        # stay ordered with the update collectives — see prefetch.py).
        # Annotating mode stays inline too: the producer must predict with
        # the params of the step it feeds (and the update donates the old
        # param buffers, so a run-ahead producer would read freed memory).
        from .prefetch import prefetch_iter

        groups = prefetch_iter(groups, prefetch_n)

    # armed HERE, torn down in the finally below — the watchdog's first
    # window covers the first step's compile, so its timeout must exceed
    # compile time (documented at the knob)
    shutdown.install()
    if watchdog is not None:
        watchdog.start()
    if tel is not None:
        tel.loop_start()
    try:
        while not stop:
            # queue-wait: how long the consumer stalled for its next group.
            # With prefetch/pool active this is the residual the input
            # pipeline failed to hide; inline it equals the whole host-side
            # pipeline time (read+collate+transfer happen in this call).
            if dispatch_pushback is not None:
                # bucket-change leftover from the previous gather leads
                # this dispatch (no queue wait — it is already staged)
                group = dispatch_pushback
                dispatch_pushback = None
            else:
                try:
                    with pipe_stats.timer(names.QUEUE_WAIT):
                        group = next(groups)
                except StopIteration:
                    break
            host_work(True)
            # multi-step dispatch: pull up to K groups, CAPPED so the
            # dispatch lands exactly on the next eval/max_steps/patience
            # boundary — those paths then run identically to K=1 (the
            # "force K=1 at the boundary step" contract)
            k_this = 1
            if update_multi is not None:
                k_this = min(
                    steps_per_dispatch,
                    eval_frequency - (step % eval_frequency),
                )
                if max_steps:
                    k_this = min(k_this, max_steps - step)
                if patience and best_step >= 0:
                    k_this = min(k_this, max(patience - (step - best_step), 1))
                if profile_dir is not None and profile_start < profile_stop:
                    # land a dispatch exactly on each window edge, else a
                    # window strictly inside one K-stride is never seen
                    # (start is only checked at dispatch boundaries) and an
                    # active trace would overshoot the stop by up to K-1
                    if steps_run < profile_start:
                        k_this = min(k_this, profile_start - steps_run)
                    elif steps_run < profile_stop:
                        k_this = min(k_this, profile_stop - steps_run)
                k_this = max(k_this, 1)
            dispatch_groups = [group]
            if k_this > 1:
                # stack only groups in the SAME padding bucket (the scan
                # needs homogeneous shapes): a bucket change flushes this
                # dispatch and the odd group leads the next one
                sig0 = _group_shape_sig(group)
                while len(dispatch_groups) < k_this:
                    host_work(False)
                    try:
                        with pipe_stats.timer(names.QUEUE_WAIT):
                            g = next(groups)
                    except StopIteration:
                        # stream ran dry mid-gather: dispatch what we have
                        break
                    finally:
                        host_work(True)
                    if _group_shape_sig(g) != sig0:
                        dispatch_pushback = g
                        break
                    dispatch_groups.append(g)
            k_this = len(dispatch_groups)
            # the LAST group's data-position tags are the consumed position
            # (save_last checkpoints the boundary after all k inner steps)
            group = dispatch_groups[-1]
            tokens, targets = group["tokens"], group["targets"]
            n_words = sum(g["n_words"] for g in dispatch_groups)
            cur_epoch = last_consumed_epoch = group["cur_epoch"]
            if (
                profile_dir is not None
                and not profile_active
                and profile_start < profile_stop  # [start, stop): empty = off
                and profile_start <= steps_run < profile_stop
            ):
                # a span that was open when the trace starts is not in it:
                # re-open the loop's, so the first profiled step has one
                host_work(False)
                jax.profiler.start_trace(str(profile_dir))
                profile_active = True
                host_work(True)
            if before_update is not None:
                before_update(nlp, {"step": step, "epoch": cur_epoch})
            # fault-injection site "step": a `sigterm` rule here exercises
            # the preemption path at an exact step; an error rule, the
            # supervisor's crash/restart path; a `nan` rule poisons this
            # step's reported loss (telemetry NaN-detector drill). One
            # probe per INNER step so rule call-counts stay step-aligned
            # when steps_per_dispatch > 1.
            poisons = []
            for _ in range(k_this):
                maybe_fail("step")
                poisons.append(resilience.consume_poison("step"))
            with pipe_stats.timer(names.LOOP_DISPATCH):
                if k_this == 1:
                    rng, sub = jax.random.split(rng)
                    if shadow is not None:
                        params, opt_state, shadow, loss, metrics = update(
                            params, opt_state, shadow, tokens, targets, sub
                        )
                    else:
                        params, opt_state, loss, metrics = update(
                            params, opt_state, tokens, targets, sub
                        )
                    step_metrics = [(metrics, poisons[0])]
                else:
                    # ONE host round-trip for k_this steps: stack the staged
                    # device batches with a leading [k] dim and scan the
                    # update over them (bit-identical to k singles — the rng
                    # split chain continues inside the program)
                    def _stack(groups_, key):
                        return jax.tree_util.tree_map(
                            lambda *xs: jnp.stack(xs), *[g[key] for g in groups_]
                        )

                    s_tokens = _stack(dispatch_groups, "tokens")
                    s_targets = _stack(dispatch_groups, "targets")
                    if shadow is not None:
                        params, opt_state, shadow, rng, losses, metricses = (
                            update_multi(
                                params, opt_state, shadow, s_tokens, s_targets, rng
                            )
                        )
                    else:
                        params, opt_state, rng, losses, metricses = update_multi(
                            params, opt_state, s_tokens, s_targets, rng
                        )
                    loss = losses[-1]

                    def _inner(tree, i):
                        return jax.tree_util.tree_map(lambda x: x[i], tree)

                    step_metrics = [
                        (_inner(metricses, i), poisons[i]) for i in range(k_this)
                    ]
            params_cell["params"] = params
            step += k_this
            steps_run += k_this
            if profile_active and steps_run >= profile_stop:
                # waiting for the device and writing the trace are not
                # the loop's work per step
                host_work(False)
                jax.block_until_ready(loss)
                jax.profiler.stop_trace()
                profile_active = False
                host_work(True)
            if use_averages:
                # steps_per_dispatch is bypassed to 1 under use_averages,
                # so the running mean still sees every step's params
                avg_count += 1
                avg_params = _avg_step(avg_params, params, avg_count)
            result.words_seen += n_words
            words_since_log += n_words

            # keep metrics as device arrays — float() here would synchronize the
            # host with the device EVERY step and kill host/device overlap; the
            # accumulated scalars are only materialized at eval/log time
            # (tagged with each step's nan-poison flag for drain_metrics)
            pending_metrics.extend(step_metrics)
            if tel is not None:
                # ONE clock stamp per dispatch: the boundary fans out into
                # k_this per-inner-step histogram observations / rows /
                # spans (elapsed/k each), so detectors and percentiles
                # still see every step
                tel.step_boundary(
                    step=step, epoch=cur_epoch, n_words=n_words,
                    steps_run=steps_run, inner_steps=k_this,
                    words_each=(
                        [g["n_words"] for g in dispatch_groups]
                        if k_this > 1
                        else None
                    ),
                )

            info: Optional[Dict[str, Any]] = None
            if step % eval_frequency == 0:
                host_work(False)
                drain_metrics()
                # eval (and best-model save) uses averaged params when enabled.
                # Params stay ON DEVICE through prediction — gathering the full
                # tree to host every eval (then re-uploading it per dev chunk)
                # costs two full-model transfers for nothing.
                eval_src = avg_params if use_averages else params
                eval_t0 = time.perf_counter()
                with _tspan("eval", step=step):
                    scores = nlp.evaluate(dev_examples, eval_src, mesh=mesh)
                eval_seconds = time.perf_counter() - eval_t0
                score = weighted_score(scores, score_weights)
                now = time.perf_counter()
                wps = words_since_log / max(now - last_log_time, 1e-9)
                last_log_time = now
                words_since_log = 0
                info = {
                    "epoch": cur_epoch,
                    "step": step,
                    "words": result.words_seen,
                    "losses": dict(loss_accum),
                    "other_scores": scores,
                    "score": score,
                    "wps": wps,
                    "eval_seconds": eval_seconds,
                    # cumulative per-stage input-pipeline seconds + cache
                    # counters (read / tokenize+collate / transfer /
                    # queue-wait) — the host-side account of where batch
                    # preparation time went (collate_pool.py)
                    "input_pipeline": pipe_stats.snapshot(),
                }
                if tel is not None:
                    info["telemetry"] = tel.eval_boundary(
                        step=step,
                        epoch=cur_epoch,
                        steps_run=steps_run,
                        losses=dict(loss_accum),
                        score=score,
                        eval_seconds=eval_seconds,
                        input_pipeline=info["input_pipeline"],
                        wps=wps,
                    )
                    info["step_ms_p50"] = _ms(
                        info["telemetry"]["step_seconds_p50"]
                    )
                    info["step_ms_p95"] = _ms(
                        info["telemetry"]["step_seconds_p95"]
                    )
                result.history.append(info)
                loss_accum = {}
                if score > best_score:
                    best_score = score
                    best_step = step
                    if output_path is not None and jax.process_index() == 0:
                        nlp.params = jax.device_get(eval_src)
                        with _tspan("checkpoint_save", kind="best", step=step):
                            nlp.to_disk(Path(output_path) / "best-model")
                with _tspan("checkpoint_save", kind="last", step=step):
                    save_last(group)
                if tel is not None:
                    # eval + checkpoint time must not count against the
                    # NEXT step's measured step time
                    tel.rearm_step_clock()
                host_work(True)
            log_step(info)
            if watchdog is not None:
                watchdog.beat()

            if max_steps and step >= max_steps:
                stop = True
            if patience and best_step >= 0 and (step - best_step) >= patience:
                stop = True
            # preemption poll, AFTER the step completed: on multi-host the
            # flag is allgathered so every rank agrees to checkpoint THIS
            # step (stop conditions above are replica-identical, so the
            # poll itself stays collective-aligned)
            if not stop and shutdown.coordinated_stop(process_count):
                host_work(False)
                with _tspan("preemption_drain", step=step):
                    drain_metrics()
                    save_last(group)
                result.interrupted = True
                log_event(
                    "preempted",
                    f"shutdown signal at step {step} — checkpoint written at "
                    "the step boundary; resume with --resume",
                    step=step,
                )
                stop = True
            host_work(False)

    finally:
        host_work(False)  # a step/eval that raised left the span open
        # stop the prefetch producer and drop its buffered (on-device)
        # batches even when a step/eval raises — train() may be called
        # again in the same process
        if hasattr(groups, "close"):
            groups.close()
        if watchdog is not None:
            watchdog.stop()
        shutdown.restore()
        if tel_http is not None:
            tel_http.stop()
        if tel is not None:
            # flush metric rows + trace even when a step/eval raised — a
            # crashed run's timeline is exactly the one worth reading
            tel.finalize()
    if profile_active:  # loop ended inside the window: still write the trace
        jax.profiler.stop_trace()
        profile_active = False
    result.seconds = time.perf_counter() - start_time
    result.best_score = best_score
    result.best_step = best_step
    result.final_step = step
    # the producer may have run ahead under prefetch: report the epoch count
    # as of the last CONSUMED group (matching the no-prefetch behavior of
    # "completed epochs" when the stream ran dry, else the current epoch)
    result.epoch = epoch if not stop else last_consumed_epoch
    from ..ops.fused_update import fused_status, in_place_status

    result.resolved = {
        "update_sharding": update_sharding_status(update_sharding, mesh),
        # read after the steps ran: the kernel probe fires in the first trace
        "fused_update": fused_status(tx, mesh),
        "bf16_shadow": "on" if shadow is not None else "off",
    }
    in_place = in_place_status(tx)
    if in_place is not None:
        # which leaves the kernel updated where they lay, from their shapes
        result.resolved["fused_update_in_place"] = in_place
    oracles = [
        comp.oracle_report() for comp in nlp.components.values()
        if hasattr(comp, "oracle_report")
    ]
    if oracles:
        # native, or python and why, with the documents each worked out
        result.resolved["parser_oracle"] = {
            "path": oracles[0]["path"],
            **{key: sum(o[key] for o in oracles) for key in ("native", "python")},
        }
    if pending_metrics:
        drain_metrics()  # the steps since the last evaluation
    if counter_totals:
        # whichever model made the counters says what they come to
        for comp in nlp.components.values():
            for m in (comp.model.walk() if getattr(comp, "model", None) is not None else ()):
                if names.SUMMARISE_COUNTERS in m.meta:
                    result.resolved.update(m.meta[names.SUMMARISE_COUNTERS](counter_totals))
    nlp.params = jax.device_get(params)
    if output_path is not None and jax.process_index() == 0:
        nlp.to_disk(Path(output_path) / "last-model")
    log_finalize()
    return nlp, result
