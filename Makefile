# Convenience targets; the canonical commands live in README.md / PERF.md.

.PHONY: test test-fast test-slow resilience telemetry observability serving fleet multi-model live train-fleet train-fleet-obs train-fleet-chaos bench bench-gate baseline profile step-perf serve-perf serve-perf3 update-shard dryrun

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
# SIGTERM graceful drain, SLO telemetry, bench records (docs/SERVING.md);
# the heavy open-loop load variant is slow-marked and excluded here
serving:
	JAX_PLATFORMS=cpu python -m pytest tests/test_serving.py -q -m "not slow"

# multi-replica fleet suite: router balancing/health/retry, response
# cache, metrics aggregation, supervisor restarts, autoscaler hysteresis,
# whole-fleet SIGTERM drain (docs/SERVING.md "Fleet"); the real-load
# crash-recovery and bench-record variants are slow-marked and excluded
fleet:
	JAX_PLATFORMS=cpu python -m pytest tests/test_fleet.py -q -m "not slow"

# multi-tenant multi-model suite (docs/SERVING.md "Multi-model fleet",
# docs/TUNING.md §23): manifest registry + path/header routing matrix,
# WFQ weight-ratio convergence + per-class expiry, token-bucket quotas
# under a fake clock + the typed-429 matrix, residency LRU (pinned
# default, leader-election cold load, zero post-load compiles),
# placement hysteresis, per-model cache/merge/top surfaces, the
# zero-telemetry guard, and the 2-model HTTP end-to-end — then the
# isolation bench: a saturating quota-metered burst on model alpha must
# not move model beta's gold-class window p99 past target (zero 5xx;
# the committed record names per-model p99 / cache hit rate / quota
# rejects / residency swaps)
multi-model:
	JAX_PLATFORMS=cpu python -m pytest tests/test_multimodel.py -q -m "not slow"
	JAX_PLATFORMS=cpu python bench.py --cpu --serving --multi-model

# live continuous-learning suite (docs/SERVING.md "Continuous learning"):
# Checkpoints reader API + writer-protocol contract, watcher torn-skip,
# swap-at-dispatch-boundary bit-exactness, rollback, canary guard +
# fleet rollout controller (incl. forced-regression auto-rollback), the
# train+fleet integration and train-and-serve SIGTERM drain — then the
# hot-swap tail-latency bench at the committed offered rate
live:
	JAX_PLATFORMS=cpu python -m pytest tests/test_live.py -q -m "not slow"
	JAX_PLATFORMS=cpu python bench.py --cpu --serving --swap

# asynchronous trainer fleet (docs/TUNING.md §19–20, RESILIENCE.md
# "Trainer fleet crash semantics"): ownership/wire/quorum/staleness
# units + the wire-compression suite (int8/bf16 codecs, error-feedback
# telescoping + ablation, delta-pull chain, mixed-codec interop) + the
# thread-driven 2-worker integration and v2 owner-part round trip, then
# the subprocess drills — the real CLI fleet, the SIGKILL
# crash-and-rejoin recovery, and the bounded-staleness convergence
# acceptance (S∈{0,1,2} vs the synchronous loop, compression ON) — then
# the 1/2/4-worker pinned scaling spec and the f32-vs-compressed wire
# A/B (records land in BENCH_SESSION.jsonl with the per-phase
# breakdown, the discard-counter ledger, and the wire-byte columns)
train-fleet:
	JAX_PLATFORMS=cpu python -m pytest tests/test_training_fleet.py tests/test_fleet_wire.py -q -m "not slow"
	JAX_PLATFORMS=cpu python -m pytest tests/test_training_fleet.py -q -m slow
	JAX_PLATFORMS=cpu python bench.py --cpu --training-fleet
	JAX_PLATFORMS=cpu python bench.py --cpu --fleet-wire-ab

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

bench:
	python bench.py

# regression sentry (docs/OBSERVABILITY.md "Host resources & the run
# ledger"): one fast bench smoke appends its fresh record to a scratch
# session (SRT_BENCH_SESSION keeps throwaway runs OUT of committed
# history), then `telemetry ledger regress` judges it against the latest
# clean committed record for the same (spec, shape, platform, labels)
# key. Exits 1 only on a confirmed clean-vs-clean regression beyond the
# measurement's own noise band; a contended host makes the verdict
# "untrusted", never red. The JSON verdict is the CI artifact.
bench-gate:
	rm -f .bench-gate-fresh.jsonl
	SRT_BENCH_SESSION=.bench-gate-fresh.jsonl JAX_PLATFORMS=cpu python bench.py --cpu --configs cnn_tagger
	JAX_PLATFORMS=cpu python -m spacy_ray_tpu telemetry ledger regress \
		--record .bench-gate-fresh.jsonl --session BENCH_SESSION.jsonl \
		--json-out bench-gate-verdict.json

baseline:
	python bench.py --measure-baseline

profile:
	python bin/profile_trf.py --sweep

# per-step fixed-cost floor (PERF.md round 7): optimizer-update-only bench
# (naive vs fused) + the MFU-vs-shape profile sweep. Compare two --trace
# runs with: python bin/profile_trf.py --compare before.json after.json
step-perf:
	JAX_PLATFORMS=cpu python bench.py --cpu --update-only
	JAX_PLATFORMS=cpu python bin/profile_trf.py --sweep

# per-replica serving speed A/Bs (PERF.md rounds 9 + 13): window vs
# continuous admission, and the f32 vs bf16 vs int8 precision-overlay
# arms (the int8 arm self-forces SRT_PALLAS_INT8=1 on CPU so the pallas
# kernel runs interpret-mode with an honest "forced" label), each
# open-loop at FIXED offered rates (committed baseline + saturation
# points) — then the Zipfian edge-cache spec through the real fleet at
# the armed cache default (hit-rate x window p99, zero rejects/5xx).
# Records append to BENCH_SESSION.jsonl with honest batching/precision
# labels. The tier-1 smoke of the same harness lives in
# tests/test_serving.py; interpret-mode int8 kernel tests run in tier-1
# (tests/test_int8.py, CPU-only, fast) like the other pallas suites.
serve-perf:
	JAX_PLATFORMS=cpu python bench.py --cpu --serving-ab
	JAX_PLATFORMS=cpu python bench.py --cpu --serving
	JAX_PLATFORMS=cpu python bench.py --cpu --serving --zipfian

# serving data plane (PR 20, docs/SERVING.md "Data plane"): the fast-tier
# data-plane tests (conditional 304s + ETag/generation interaction,
# length-affinity policy, pooled-connection stale-retry), then the
# length-routing A/B through the real 2-replica fleet (pad share must
# strictly drop), the Zipfian spec whose conditional arm commits 304
# share + bytes saved, and the router-ceiling spec (pooled vs fresh-dial
# arms against stub replicas, naming which side bounds the fleet)
serve-perf3:
	JAX_PLATFORMS=cpu python -m pytest tests/test_fleet.py -q -m 'not slow' \
		-k "conditional or suppressed or passthrough or length_ or stale_pooled or aux_conns"
	JAX_PLATFORMS=cpu python -m pytest tests/test_serving.py -q -m 'not slow' \
		-k "etag or conditional or pad or batch_span"
	JAX_PLATFORMS=cpu python bench.py --cpu --serving --length-mix
	JAX_PLATFORMS=cpu python bench.py --cpu --serving --zipfian
	JAX_PLATFORMS=cpu python bench.py --cpu --serving --router-ceiling

# cross-replica update sharding (PERF.md "Update sharding (round 11)"):
# the full==replicated equality suite + v2 owner-shard checkpoint format +
# elastic (8->4->1) resume bit-exactness, then the sharded update-only A/B
# (replicated vs zero1 vs full at 1/2/4/8 virtual devices, with the
# grad-reduce/apply/allgather phase split on every record)
update-shard:
	python -m pytest tests/test_update_sharding.py -q
	python bench.py --update-only --sharded

dryrun:
	XLA_FLAGS="--xla_force_host_platform_device_count=8" python __graft_entry__.py
