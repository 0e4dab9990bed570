# Convenience targets; the canonical commands live in README.md / PERF.md.

.PHONY: test test-fast test-slow resilience telemetry observability serving fleet multi-model live train-fleet train-fleet-obs train-fleet-chaos serve-perf3 update-shard dryrun

test:
	python -m pytest tests/ -q

test-fast:
	python -m pytest tests/ -q -m "not slow"

test-slow:
	python -m pytest tests/ -q -m slow

# fault-injection / checkpoint-fallback / watchdog suite (docs/RESILIENCE.md)
resilience:
	python -m pytest tests/test_resilience.py tests/test_checkpoint_fallback.py -q

# telemetry suite: trace validity, registry thread-safety, anomaly
# detectors, the telemetry-enabled smoke train (docs/OBSERVABILITY.md)
telemetry:
	python -m pytest tests/test_telemetry.py -q

# cross-process observability plane (docs/OBSERVABILITY.md): Prometheus
# exposition golden-format + bucket merge, request-id propagation +
# concurrent-load header equality, trace-collector clock-anchor merge,
# slow-request exemplars, trainer /metrics endpoint, `telemetry top`,
# serving-row summarize — plus the PR 12 diagnosis layer (alert engine
# burn-rate/threshold/absence matrix under a fake clock, flight
# recorder, crash bundles, `telemetry postmortem`) — then the real-fleet
# acceptance pair: the sigterm test (one request's spans across router +
# replica tracks in one merged Perfetto file) and the sigkill test (a
# killed replica's crash postmortem bundle)
observability:
	JAX_PLATFORMS=cpu python -m pytest tests/test_observability.py tests/test_telemetry.py tests/test_alerting.py tests/test_incidents.py -q -m "not slow"
	JAX_PLATFORMS=cpu python -m pytest tests/test_fleet.py -q -k "sigterm or sigkill"

# online-serving suite: batcher/engine/HTTP correctness under load,
# SIGTERM graceful drain, SLO telemetry (docs/SERVING.md)
serving:
	JAX_PLATFORMS=cpu python -m pytest tests/test_serving.py -q -m "not slow"

# multi-replica fleet suite: router balancing/health/retry, response
# cache, metrics aggregation, supervisor restarts, autoscaler hysteresis,
# whole-fleet SIGTERM drain (docs/SERVING.md "Fleet"); the real-load
# crash-recovery variants are slow-marked and excluded
fleet:
	JAX_PLATFORMS=cpu python -m pytest tests/test_fleet.py -q -m "not slow"

# multi-tenant multi-model suite (docs/SERVING.md "Multi-model fleet",
# docs/TUNING.md §23): manifest registry + path/header routing matrix,
# WFQ weight-ratio convergence + per-class expiry, token-bucket quotas
# under a fake clock + the typed-429 matrix, residency LRU (pinned
# default, leader-election cold load, zero post-load compiles),
# placement hysteresis, per-model cache/merge/top surfaces, the
# zero-telemetry guard, and the 2-model HTTP end-to-end
multi-model:
	JAX_PLATFORMS=cpu python -m pytest tests/test_multimodel.py -q -m "not slow"

# live continuous-learning suite (docs/SERVING.md "Continuous learning"):
# Checkpoints reader API + writer-protocol contract, watcher torn-skip,
# swap-at-dispatch-boundary bit-exactness, rollback, canary guard +
# fleet rollout controller (incl. forced-regression auto-rollback), the
# train+fleet integration and train-and-serve SIGTERM drain
live:
	JAX_PLATFORMS=cpu python -m pytest tests/test_live.py -q -m "not slow"

# asynchronous trainer fleet (docs/TUNING.md §19–20, RESILIENCE.md
# "Trainer fleet crash semantics"): ownership/wire/quorum/staleness
# units + the wire-compression suite (int8/bf16 codecs, error-feedback
# telescoping + ablation, delta-pull chain, mixed-codec interop) + the
# thread-driven 2-worker integration and v2 owner-part round trip, then
# the subprocess drills — the real CLI fleet, the SIGKILL
# crash-and-rejoin recovery, and the bounded-staleness convergence
# acceptance (S∈{0,1,2} vs the synchronous loop, compression ON)
train-fleet:
	JAX_PLATFORMS=cpu python -m pytest tests/test_training_fleet.py tests/test_fleet_wire.py -q -m "not slow"
	JAX_PLATFORMS=cpu python -m pytest tests/test_training_fleet.py -q -m slow

# trainer-fleet observability plane (docs/OBSERVABILITY.md "Training
# fleet"): srt_training_* dynamics-histogram golden grammar +
# exactly-summing buckets across fake workers, the fake-clock fleet
# divergence-detector matrix, fleet-aware `telemetry summarize` /
# `report`, collect-trace --fleet-base-port expansion, the top columns,
# the zero-telemetry fleet guard — then the real 2-worker acceptance
# pair from tests/test_training_fleet.py (subprocess fleet → ONE merged
# Perfetto timeline + markdown run report; thread-fleet forced-
# divergence drill → alert + incident bundle naming the worker)
train-fleet-obs:
	JAX_PLATFORMS=cpu python -m pytest tests/test_fleet_obs.py -q -m "not slow"
	JAX_PLATFORMS=cpu python -m pytest tests/test_training_fleet.py -q -m "not slow" -k "obs_acceptance or divergence"

# elastic-membership chaos drills (docs/RESILIENCE.md "Ownership
# failover", docs/TUNING.md §21): the fake-clock lease matrix (a
# merely-slow worker is provably never evicted), re-shard / epoch-fence
# / rejoin units, PeerServer malformed-input fuzz (typed 400s, never a
# traceback), then the slow subprocess drills — owner SIGKILL past its
# restart budget → lease eviction → epoch-fenced re-shard → the
# survivors keep training (zero NaN, zero lost lineage, degraded-success
# rc=0) and the wire-chaos matrix (corrupt/delay/dup/partition at every
# wire site; a healed zombie's stale-epoch pushes all fenced)
train-fleet-chaos:
	JAX_PLATFORMS=cpu python -m pytest tests/test_fleet_membership.py -q -m "not slow"
	JAX_PLATFORMS=cpu python -m pytest tests/test_fleet_membership.py -q -m slow

# serving data plane (PR 20, docs/SERVING.md "Data plane"): the fast-tier
# data-plane tests (conditional 304s + ETag/generation interaction,
# length-affinity policy, pooled-connection stale-retry)
serve-perf3:
	JAX_PLATFORMS=cpu python -m pytest tests/test_fleet.py -q -m 'not slow' \
		-k "conditional or suppressed or passthrough or length_ or stale_pooled or aux_conns"
	JAX_PLATFORMS=cpu python -m pytest tests/test_serving.py -q -m 'not slow' \
		-k "etag or conditional or pad or batch_span"

# cross-replica update sharding (docs/TUNING.md §15):
# the full==replicated equality suite + v2 owner-shard checkpoint format +
# elastic (8->4->1) resume bit-exactness
update-shard:
	python -m pytest tests/test_update_sharding.py -q

dryrun:
	XLA_FLAGS="--xla_force_host_platform_device_count=8" python __graft_entry__.py
