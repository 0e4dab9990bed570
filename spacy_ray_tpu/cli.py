"""CLI: ``python -m spacy_ray_tpu train config.cfg [overrides]``.

Capability parity with the reference CLI (reference train_cli.py:23-53:
``spacy ray train <config> --n-workers --address --gpu-id --code --output
--verbose`` + dotted config overrides). Mapping:

* ``--n-workers N`` -> mesh data-axis size (actor count at reference
  train_cli.py:72-82);
* ``--address`` -> ``--coordinator`` (jax.distributed coordinator address;
  Ray cluster address at train_cli.py:28);
* ``--gpu-id`` -> ``--device`` (tpu/cpu; reference train_cli.py:29 + GPU
  setup at :43);
* ``--code`` -> same semantics: imported before config resolution in every
  process (reference train_cli.py:30, worker.py:87);
* ``--output`` -> WIRED to best/last checkpoints (the reference accepts and
  drops it, TODO at train_cli.py:41 — SURVEY.md §2.4);
* ``--verbose`` -> log level (train_cli.py:42).
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional

logger = logging.getLogger("spacy_ray_tpu")


def _setup_device(device: str) -> None:
    """Select the compute platform (the reference's setup_gpu/--gpu-id path,
    train_cli.py:29,43) and join the run's shared compile cache. ``tpu`` and
    ``gpu`` are requirements, not hints: where JAX finds another platform
    this exits with the reason (devices.select_device) — ``--device cpu``
    is the explicit way to run on the CPU."""
    from .devices import enable_compile_cache, select_device

    enable_compile_cache()
    select_device(device)


def _init_distributed(coordinator: Optional[str], num_processes: Optional[int], process_id: Optional[int]) -> None:
    """Multi-host init (the reference's ray.init(address=...) equivalent,
    train_cli.py:66-71): jax.distributed over ICI/DCN (SURVEY.md §5.8)."""
    if coordinator:
        import jax

        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
        )


# grace period before a forwarded shutdown escalates SIGTERM → SIGKILL
SHUTDOWN_GRACE_S = 10.0


def _supervise_train(argv: List[str], max_restarts: int) -> int:
    """``train --max-restarts N``: run training as a child process and
    relaunch it on nonzero exit (crash, watchdog kill, injected fault),
    resuming from the last intact checkpoint generation. Signals to the
    supervisor are forwarded to the child with SIGTERM → SIGKILL
    escalation after a grace period."""
    from .training.resilience import Supervisor

    child_args = _strip_flags(argv, ["--max-restarts"])

    def build_cmd(attempt: int) -> List[str]:
        cmd = [sys.executable, "-m", "spacy_ray_tpu", "train"] + child_args
        if attempt > 0 and "--resume" not in cmd:
            cmd.append("--resume")  # recover from the last intact checkpoint
        return cmd

    return Supervisor(build_cmd, max_restarts, grace_s=SHUTDOWN_GRACE_S).run()


def _strip_flags(argv: List[str], flags: List[str]) -> List[str]:
    """Remove ``--flag value`` / ``--flag=value`` pairs from an argv."""
    out: List[str] = []
    skip_next = False
    for a in argv:
        if skip_next:
            skip_next = False
            continue
        if a in flags:
            skip_next = True
            continue
        if any(a.startswith(f + "=") for f in flags):
            continue
        out.append(a)
    return out


def _run_fleet_coordinator(argv: List[str], args) -> int:
    """``train --fleet-workers N`` (no worker id): this process never
    touches jax — it spawns N pinned worker subprocesses (each rerunning
    this argv plus ``--fleet-worker-id k``) and supervises restarts with
    ``--resume`` (training/fleet/coordinator.py)."""
    from .devices import refuse_shared_chip
    from .training.fleet.coordinator import run_fleet

    refuse_shared_chip(
        args.device, args.fleet_workers, "train --fleet-workers"
    )
    # coordinator-only flags must not reach the children: --max-restarts
    # would nest a per-child supervisor chain, --cpu-cores is resolved
    # HERE into per-worker taskset masks
    child_argv = _strip_flags(argv, ["--max-restarts", "--cpu-cores"])
    cpu_cores: Optional[List[str]] = None
    if args.cpu_cores and args.device == "cpu":
        if args.cpu_cores.strip().lower() == "auto":
            cpu_cores = [str(c) for c in sorted(os.sched_getaffinity(0))]
        else:
            cpu_cores = [
                m.strip() for m in args.cpu_cores.split(",") if m.strip()
            ]
    return run_fleet(
        child_argv,
        n_workers=args.fleet_workers,
        max_restarts=args.max_restarts,
        cpu_cores=cpu_cores,
        # fleet default, NOT the 10s serving grace: a preemption must
        # outlive worker 0's distributed checkpoint commit
    )


def train_command(argv: List[str]) -> int:
    # allow_abbrev=False: an abbreviated --max-restart would parse as
    # supervisor mode yet escape the exact-spelling strip in
    # _supervise_train, so every child would re-supervise a grandchild
    # with the same argv — an unbounded supervisor chain
    parser = argparse.ArgumentParser(
        prog="spacy_ray_tpu train", description="Train a pipeline from a config.",
        allow_abbrev=False,
    )
    parser.add_argument("config_path", type=Path)
    parser.add_argument("--n-workers", type=int, default=None, dest="n_workers")
    parser.add_argument("--coordinator", type=str, default=None,
                        help="jax.distributed coordinator address (multi-host)")
    parser.add_argument("--num-processes", type=int, default=None)
    parser.add_argument("--process-id", type=int, default=None)
    parser.add_argument("--device", type=str, default="tpu", choices=["tpu", "cpu", "gpu"])
    parser.add_argument("--code", type=Path, default=None)
    parser.add_argument("--output", "-o", type=Path, default=None)
    parser.add_argument("--resume", action="store_true")
    parser.add_argument("--max-restarts", type=int, default=0, dest="max_restarts",
                        help="supervisor mode: relaunch the training child up "
                        "to N times on nonzero exit, resuming from the last "
                        "intact checkpoint (0 = train in-process)")
    parser.add_argument("--profile", type=Path, default=None,
                        help="write a jax.profiler trace of the [training] "
                        "profile_window steps (default 5-15) here")
    parser.add_argument("--metrics-dir", type=Path, default=None,
                        dest="metrics_dir",
                        help="enable telemetry: metrics.jsonl + Chrome trace "
                        "+ anomaly detectors land here (overrides "
                        "[training] metrics_dir; see docs/OBSERVABILITY.md)")
    parser.add_argument("--metrics-port", type=int, default=None,
                        dest="metrics_port",
                        help="serve the trainer's telemetry over HTTP on "
                        "this port (/metrics JSON or ?format=prometheus, "
                        "/healthz clock anchor, /trace) — requires "
                        "telemetry on via --metrics-dir/[training] "
                        "metrics_dir; overrides [training] metrics_port. "
                        "Binds 127.0.0.1 unless [training] metrics_host "
                        "(or --training.metrics_host) says otherwise")
    parser.add_argument("--fleet-workers", type=int, default=0,
                        dest="fleet_workers",
                        help="asynchronous trainer fleet: spawn N worker "
                        "PROCESSES exchanging gradients/params over HTTP "
                        "with parameter ownership, quorum apply, and "
                        "staleness discard (training/fleet/; TUNING.md "
                        "§19). 0 = the in-mesh synchronous loop")
    parser.add_argument("--quorum", type=int, default=0,
                        help="fleet: gradients from this many distinct "
                        "workers trigger an owner's optimizer apply "
                        "(0 = auto: all-but-one, min 1 — one crashed "
                        "peer cannot stall the fleet)")
    parser.add_argument("--max-staleness", type=int, default=1,
                        dest="max_staleness",
                        help="fleet: accept gradients stamped up to S "
                        "shard versions behind the owner's current; "
                        "staler pushes are discarded and counted "
                        "(srt_training_grad_discarded_total)")
    parser.add_argument("--fleet-base-port", type=int, default=None,
                        dest="fleet_base_port",
                        help="fleet: worker k's peer+telemetry endpoint "
                        "binds base+k (default 47200)")
    parser.add_argument("--fleet-worker-id", type=int, default=None,
                        dest="fleet_worker_id",
                        help="(internal) run as fleet worker K — the "
                        "coordinator appends this; setting it by hand "
                        "runs one worker of a hand-assembled fleet")
    parser.add_argument("--cpu-cores", type=str, default="auto",
                        dest="cpu_cores",
                        help="fleet coordinator on --device cpu: taskset "
                        "-c core masks cycled per worker ('auto' = "
                        "round-robin over this process's affinity set, "
                        "'' = unpinned)")
    parser.add_argument("--grad-compression", type=str, default="auto",
                        dest="grad_compression",
                        choices=("auto", "f32", "bf16", "int8"),
                        help="fleet: wire codec for gradient pushes "
                        "(TUNING.md §20). auto = int8 with error "
                        "feedback where the convergence suite has run, "
                        "bf16 elsewhere; per-peer negotiated, so mixed "
                        "fleets degrade to f32 instead of erroring")
    parser.add_argument("--param-delta-window", type=int, default=4,
                        dest="param_delta_window",
                        help="fleet: owners retain K versions of "
                        "compressed param deltas so a puller at most K "
                        "versions behind ships a delta frame instead of "
                        "its full slice; 0 = full pulls only. Window "
                        "misses degrade to full pulls (RESILIENCE.md)")
    parser.add_argument("--peer-lease-s", type=float, default=60.0,
                        dest="peer_lease_s",
                        help="fleet: elastic-membership lease — a peer "
                        "silent on /healthz for this long AND missing 3 "
                        "consecutive probes is evicted by the acting "
                        "lead; survivors re-shard its parameters at the "
                        "next membership epoch (RESILIENCE.md "
                        "'Ownership failover'). 0 disables eviction "
                        "(frozen membership)")
    parser.add_argument("--verbose", "-V", action="store_true")
    args, extra = parser.parse_known_args(argv)

    logging.basicConfig(level=logging.DEBUG if args.verbose else logging.ERROR)
    # resilience events (resume anomalies, retries, preemption, checkpoint
    # fallback) must reach the operator even without -V — they used to be
    # bare prints; now they flow through this logger (+ the jsonl logger)
    logging.getLogger("spacy_ray_tpu.training").setLevel(
        logging.INFO if args.verbose else logging.WARNING
    )

    if args.fleet_workers > 0 and args.fleet_worker_id is None:
        # fleet coordinator mode: jax-free parent spawning N pinned
        # worker subprocesses, each rerunning this argv with its own
        # --fleet-worker-id; --max-restarts becomes the PER-WORKER
        # restart cap (crashed workers rejoin with --resume)
        return _run_fleet_coordinator(argv, args)

    if args.max_restarts > 0:
        # supervisor mode: this process never touches jax — it only spawns,
        # forwards signals to, and relaunches the training child
        return _supervise_train(argv, args.max_restarts)

    _setup_device(args.device)
    _init_distributed(args.coordinator, args.num_processes, args.process_id)

    from .config import load_config, parse_cli_overrides
    from .registry import import_code

    import_code(str(args.code) if args.code else None)
    overrides = parse_cli_overrides(extra)
    config = load_config(args.config_path, overrides, interpolate=False)

    fleet_kwargs = None
    if args.fleet_worker_id is not None:
        if args.fleet_workers <= 0:
            parser.error("--fleet-worker-id requires --fleet-workers N")
        if args.profile is not None:
            raise ValueError(
                "fleet mode does not support --profile (profile one "
                "worker via its own telemetry trace instead)"
            )
        from .training.fleet.worker import (
            DEFAULT_FLEET_BASE_PORT,
            train_fleet_worker,
        )

        fleet_kwargs = {
            "worker_id": args.fleet_worker_id,
            "n_workers": args.fleet_workers,
            "quorum": args.quorum,
            "max_staleness": args.max_staleness,
            "base_port": (
                args.fleet_base_port
                if args.fleet_base_port is not None
                else DEFAULT_FLEET_BASE_PORT
            ),
            "grad_compression": args.grad_compression,
            "param_delta_window": args.param_delta_window,
            "peer_lease_s": args.peer_lease_s,
        }

    if fleet_kwargs is not None:
        # this process is ONE worker of the asynchronous trainer fleet
        # (training/fleet/), not the in-mesh synchronous loop
        nlp, result = train_fleet_worker(
            config,
            args.output,
            resume=args.resume,
            metrics_dir=args.metrics_dir,
            metrics_port=args.metrics_port,
            **fleet_kwargs,
        )
    else:
        from .training.loop import train

        nlp, result = train(
            config,
            output_path=args.output,
            n_workers=args.n_workers,
            resume=args.resume,
            profile_dir=args.profile,
            metrics_dir=args.metrics_dir,
            metrics_port=args.metrics_port,
        )
    if result.interrupted:
        from .training.resilience import RC_PREEMPTED

        if args.output is not None:
            print(
                f"Interrupted at step {result.final_step} — checkpoint "
                f"written; rerun with --resume to continue (exit {RC_PREEMPTED})"
            )
        else:
            print(
                f"Interrupted at step {result.final_step} — NO checkpoint "
                f"(no --output given); progress is lost (exit {RC_PREEMPTED})"
            )
        return RC_PREEMPTED
    if fleet_kwargs is not None and fleet_kwargs["worker_id"] != 0:
        # non-lead fleet workers don't evaluate — a best_score of -1
        # here would read as a failed run
        fl = getattr(result, "fleet", {}) or {}
        print(
            f"Done. fleet worker {fleet_kwargs['worker_id']}: "
            f"steps={result.final_step} shard version={fl.get('version')} "
            f"words/sec={result.wps:,.0f}"
        )
    else:
        print(
            f"Done. steps={result.final_step} best_score={result.best_score:.4f} "
            f"(step {result.best_step}) words/sec={result.wps:,.0f}"
        )
    for comp_name in nlp.pipe_names:
        stats = getattr(nlp.components[comp_name], "oracle_stats", None)
        if stats and (stats["projectivized"] or stats["skipped"]):
            print(
                f"[{comp_name}] collation: {stats['docs']} doc-passes, "
                f"{stats['projectivized']} pseudo-projectivized, "
                f"{stats['skipped']} skipped (unusable trees)"
            )
    _print_runtime(nlp, **getattr(result, "resolved", {}))
    return 0


def _print_runtime(nlp: Any, **extra: Any) -> None:
    """One ``runtime {...}`` line, after the work: the device this process
    ran on and what each platform-dependent switch resolved to for the
    pipeline it ran (devices.runtime_report)."""
    from .devices import runtime_report

    print("runtime " + json.dumps({**runtime_report(nlp), **extra}), flush=True)


def evaluate_command(argv: List[str]) -> int:
    parser = argparse.ArgumentParser(prog="spacy_ray_tpu evaluate")
    parser.add_argument("model_path", type=Path)
    parser.add_argument("data_path", type=Path)
    parser.add_argument("--device", type=str, default="tpu", choices=["tpu", "cpu", "gpu"])
    parser.add_argument(
        "--output", type=Path, default=None,
        help="write the metrics as JSON (spaCy's `evaluate --output` surface)",
    )
    args = parser.parse_args(argv)
    _setup_device(args.device)

    from .pipeline.language import Pipeline
    from .training.corpus import Corpus

    nlp = Pipeline.from_disk(args.model_path)
    examples = list(Corpus(args.data_path)())
    scores = nlp.evaluate(examples)
    for key, value in sorted(scores.items()):
        if isinstance(value, dict):
            # per-type tables (ents_per_type, cats_f_per_type, ...)
            for sub, prf in sorted(value.items()):
                line = "  ".join(f"{m}={prf[m]:.4f}" for m in ("p", "r", "f"))
                print(f"{key:24s} {sub:14s} {line}")
        elif value is None:
            print(f"{key:24s} -")  # no gold annotation for this metric
        else:
            print(f"{key:24s} {value:.4f}")
    if args.output is not None:
        import json

        args.output.parent.mkdir(parents=True, exist_ok=True)
        args.output.write_text(
            json.dumps(scores, indent=2, sort_keys=True, default=float) + "\n",
            encoding="utf8",
        )
        print(f"metrics written to {args.output}")
    _print_runtime(nlp)
    return 0


def convert_command(argv: List[str]) -> int:
    """Convert jsonl/conllu corpora into the binary corpus format (the
    reference's data path runs `spacy convert`, bin/get-data.sh:8-12)."""
    parser = argparse.ArgumentParser(prog="spacy_ray_tpu convert")
    parser.add_argument("input_path", type=Path)
    parser.add_argument("output_path", type=Path)
    args = parser.parse_args(argv)

    from .training.corpus import DocBin, _iter_path

    try:
        docs = list(_iter_path(args.input_path))
    except Exception as e:  # corrupt inputs raise zlib/msgpack/Key errors too
        print(f"Could not read {args.input_path}: {e}", file=sys.stderr)
        return 1
    if args.output_path.suffix == ".spacy":
        # the real spaCy DocBin byte format (readable by spaCy itself)
        from .training.spacy_docbin import write_docbin

        write_docbin(args.output_path, docs)
    else:
        DocBin(docs).to_disk(args.output_path)
    print(f"Wrote {len(docs)} docs to {args.output_path}")
    return 0


def init_config_command(argv: List[str]) -> int:
    """Write a ready-to-train config (spacy's `init config` role): either a
    named preset, or an arbitrary `--pipeline` component list composed over
    a shared trunk (spacy's `init config --pipeline` surface)."""
    from .presets import INIT_PRESETS, compose_pipeline_config

    parser = argparse.ArgumentParser(prog="spacy_ray_tpu init-config")
    parser.add_argument("output_path", type=Path)
    parser.add_argument(
        "--preset",
        default=None,
        choices=sorted(INIT_PRESETS),
        help="cnn: tagger-only CNN tok2vec; sm: tagger+parser+ner shared CNN; "
        "trf: RoBERTa-base-shape transformer pipeline; spancat: spancat+textcat",
    )
    parser.add_argument(
        "--pipeline", default=None,
        help="comma-separated component list composed over one shared trunk "
        "(e.g. tagger,parser,ner,entity_ruler); mutually exclusive with "
        "--preset",
    )
    parser.add_argument(
        "--trunk", default="cnn", choices=["cnn", "trf"],
        help="shared trunk for --pipeline: CNN tok2vec or transformer",
    )
    parser.add_argument(
        "--width", type=int, default=0,
        help="trunk width for --pipeline (default: 96 cnn / 768 trf)",
    )
    args = parser.parse_args(argv)
    if args.preset and args.pipeline:
        print("--preset and --pipeline are mutually exclusive", file=sys.stderr)
        return 1
    from .config import Config

    if args.pipeline:
        try:
            text = compose_pipeline_config(
                [c.strip() for c in args.pipeline.split(",") if c.strip()],
                trunk=args.trunk,
                width=args.width,
            )
        except ValueError as e:
            print(str(e), file=sys.stderr)
            return 1
        label = f"pipeline [{args.pipeline}] over {args.trunk} trunk"
    else:
        text = INIT_PRESETS[args.preset or "cnn"]
        label = f"{args.preset or 'cnn'!r} preset"
    cfg = Config.from_str(text)  # parse = validate
    args.output_path.write_text(cfg.to_str(), encoding="utf8")
    print(f"Wrote {label} to {args.output_path}")
    return 0


def assemble_command(argv: List[str]) -> int:
    """`assemble` — build a pipeline from a config WITHOUT training and save
    it (spaCy's `spacy assemble`): the path for rule/lookup-only pipelines
    (entity_ruler, attribute_ruler, lemmatizer) and for materializing
    sourced-component combinations."""
    parser = argparse.ArgumentParser(
        prog="spacy_ray_tpu assemble",
        description="Build a pipeline from a config without training; "
        "initializes components (labels from [initialize] data when "
        "present, else empty) and writes the pipeline to output.",
    )
    parser.add_argument("config_path", type=Path)
    parser.add_argument("output_path", type=Path)
    parser.add_argument("--device", type=str, default="cpu", choices=["tpu", "cpu", "gpu"])
    parser.add_argument("--code", type=Path, default=None)
    args, extra = parser.parse_known_args(argv)
    _setup_device(args.device)

    from .config import load_config, parse_cli_overrides
    from .pipeline.language import Pipeline
    from .registry import import_code, registry

    import_code(str(args.code) if args.code else None)
    overrides = parse_cli_overrides(extra)
    config = load_config(args.config_path, overrides, interpolate=False).interpolate()
    nlp = Pipeline.from_config(config)

    get_examples = None
    corpora_cfg = config.get("corpora", {})
    train_name = (config.get("training") or {}).get("train_corpus", "corpora.train")
    parts = str(train_name).split(".")
    block = (
        corpora_cfg.get(parts[1])
        if len(parts) == 2 and parts[0] == "corpora"
        else None
    )
    if block is not None:
        try:
            corpus = registry.resolve(dict(block))
            get_examples = lambda: iter(corpus())  # noqa: E731
        except Exception as e:
            print(
                f"note: train corpus unavailable ({e}); assembling without "
                "initialize data — trainable components get empty label sets",
                file=sys.stderr,
            )
    nlp.initialize(get_examples, seed=0)
    nlp.to_disk(args.output_path)
    print(f"Assembled pipeline ({', '.join(nlp.pipe_names)}) -> {args.output_path}")
    return 0


def _check_arch_names(block, registry, where: str) -> None:
    """Recursively verify @-references resolve to registered callables and
    that non-@ keys are accepted argument names — without calling anything."""
    if not isinstance(block, dict):
        return
    ref_keys = [k for k in block if k.startswith("@")]
    for k in ref_keys:
        namespace = k[1:]
        func = registry.get(namespace, block[k])  # raises if unknown
        # the SAME name/arity validation resolve applies at train time —
        # one implementation, so debug-config can't drift from it
        args = {a: v for a, v in block.items() if not a.startswith("@")}
        registry._validate_args(func, args, namespace, block[k])
    for key, sub in block.items():
        if isinstance(sub, dict):
            _check_arch_names(sub, registry, f"{where}.{key}")


def debug_config_command(argv: List[str]) -> int:
    """`debug config` — resolve every block of a config and report what's
    wrong (or print the resolved summary), without touching any data."""
    parser = argparse.ArgumentParser(prog="spacy_ray_tpu debug-config")
    parser.add_argument("config_path", type=Path)
    parser.add_argument("--code", type=Path, default=None)
    args, extra = parser.parse_known_args(argv)

    from .config import load_config, parse_cli_overrides
    from .registry import import_code, registry

    import_code(str(args.code) if args.code else None)
    overrides = parse_cli_overrides(extra)
    try:
        config = load_config(args.config_path, overrides, interpolate=False)
        config = config.interpolate()
    except Exception as e:
        print(f"[config] INVALID: {e}", file=sys.stderr)
        return 1
    problems = 0
    nlp_block = config.get("nlp") or {}
    pipeline = list(nlp_block.get("pipeline") or [])
    comps = config.get("components") or {}
    for name in pipeline:
        block = comps.get(name)
        if block is None:
            print(f"[components.{name}] MISSING (listed in nlp.pipeline)",
                  file=sys.stderr)
            problems += 1
            continue
        if "source" in block:
            print(f"[components.{name}] sourced from {block['source']!r}")
            continue
        try:
            factory = block.get("factory")
            registry.get("factories", factory)
            # validate architecture names + argument names WITHOUT invoking
            # the factories: eager construction would run model-building
            # code that legitimately needs runtime context (loaded vectors,
            # devices) and must not decide config validity
            _check_arch_names(block.get("model"), registry, f"components.{name}.model")
            print(f"[components.{name}] ok (factory={factory})")
        except Exception as e:
            print(f"[components.{name}] INVALID: {e}", file=sys.stderr)
            problems += 1
    for section in ("corpora", "training", "pretraining", "initialize"):
        if section in config and config[section]:
            print(f"[{section}] present ({len(dict(config[section]))} keys)")
    extra_comps = sorted(set(comps) - set(pipeline))
    if extra_comps:
        print(f"note: components defined but not in nlp.pipeline: {extra_comps}")
    if problems:
        print(f"{problems} problem(s) found", file=sys.stderr)
        return 1
    print("Config OK")
    return 0


def debug_data_command(argv: List[str]) -> int:
    """Corpus sanity report (spaCy's `debug data` role): doc/token counts,
    annotation coverage, label distributions, length histogram, and
    parser-specific warnings (non-projective trees are skipped by the
    arc-eager oracle)."""
    parser = argparse.ArgumentParser(prog="spacy_ray_tpu debug-data")
    parser.add_argument("data_path", type=Path)
    parser.add_argument("--limit", type=int, default=0)
    args = parser.parse_args(argv)

    from collections import Counter

    from .pipeline.nonproj import is_projective, projectivize
    from .training.corpus import Corpus

    examples = list(Corpus(args.data_path, limit=args.limit)())
    n_docs = len(examples)
    n_tokens = sum(len(eg) for eg in examples)
    lengths = sorted(len(eg) for eg in examples)
    have = Counter()
    tag_labels, dep_labels, ent_labels, cat_labels = Counter(), Counter(), Counter(), Counter()
    nonproj = 0
    parsed_trees = []
    for eg in examples:
        ref = eg.reference
        if ref.tags:
            have["tags"] += 1
            tag_labels.update(t for t in ref.tags if t)
        if ref.heads and ref.deps:
            have["deps"] += 1
            dep_labels.update(d for d in ref.deps if d)
            parsed_trees.append((ref.heads, ref.deps))
            if not is_projective(ref.heads):
                nonproj += 1
        if ref.ents:
            have["ents"] += 1
            ent_labels.update(s.label for s in ref.ents)
        if ref.cats:
            have["cats"] += 1
            cat_labels.update(ref.cats)
        if ref.spans:
            have["spans"] += 1
        if ref.sent_starts:
            have["sent_starts"] += 1
        if ref.morphs:
            have["morphs"] += 1

    def pct(n):
        return f"{100 * n / n_docs:.1f}%" if n_docs else "0%"

    print(f"docs: {n_docs}   tokens: {n_tokens}")
    if lengths:
        print(
            f"doc length: min={lengths[0]} p50={lengths[len(lengths) // 2]} "
            f"p95={lengths[int(len(lengths) * 0.95)]} max={lengths[-1]}"
        )
    print("annotation coverage:", {k: pct(v) for k, v in sorted(have.items())})
    for name, counter in [
        ("tags", tag_labels), ("deps", dep_labels), ("ents", ent_labels), ("cats", cat_labels)
    ]:
        if counter:
            top = ", ".join(f"{l}({c})" for l, c in counter.most_common(12))
            print(f"{name} labels ({len(counter)}): {top}")
    if parsed_trees:
        # the EXACT check training collation applies: projectivize, then the
        # arc-eager oracle (a doc can pass the crossing test yet still be
        # oracle-unreachable, e.g. cyclic heads from bad annotation)
        from .pipeline.nonproj import is_decorated
        from .pipeline.transition import gold_oracle

        base_ids = {l: i for i, l in enumerate(sorted(dep_labels))}
        lifted = unusable = 0
        for heads, deps in parsed_trees:
            res = projectivize(heads, deps)
            if res is None:
                unusable += 1
                continue
            proj_heads, deco, n_lifted = res
            extra = sorted(
                {d for d in deco if is_decorated(d) and d not in base_ids}
            )
            if extra:
                ids_map = dict(base_ids)
                for d in extra:
                    ids_map[d] = len(ids_map)
            else:
                ids_map = base_ids
            ids = [ids_map.get(d, 0) for d in deco]
            if gold_oracle(proj_heads, ids, len(ids_map)) is None:
                unusable += 1
            elif n_lifted:
                lifted += 1
        if nonproj or unusable:
            print(
                f"non-projective trees: {nonproj}/{len(parsed_trees)} parsed "
                f"docs — {lifted} trainable via pseudo-projective lifting "
                f"(label decoration); unusable trees (skipped at training): "
                f"{unusable}"
            )
    if n_docs == 0:
        print("WARNING: corpus is empty")
        return 1
    return 0


def _load_plugins() -> None:
    """Import packages registered under the `spacy_ray_tpu_plugins` entry
    point so their @registry decorators run (the reference's setuptools
    plugin mechanism, setup.cfg:35-41)."""
    try:
        from importlib.metadata import entry_points

        for ep in entry_points(group="spacy_ray_tpu_plugins"):
            try:
                ep.load()
            except Exception as e:  # a broken plugin must not kill the CLI
                print(f"warning: plugin {ep.name!r} failed to load: {e}", file=sys.stderr)
    except Exception:
        pass


def pretrain_command(argv: List[str]) -> int:
    """`pretrain` — tok2vec pretraining from the config's [pretraining]
    block (spaCy's `spacy pretrain` surface); weights go to --output and
    load back via [initialize] init_tok2vec."""
    parser = argparse.ArgumentParser(
        prog="spacy_ray_tpu pretrain",
        description="Pretrain the tok2vec/transformer trunk on raw text "
        "([pretraining] config block); load results with "
        "[initialize] init_tok2vec.",
    )
    parser.add_argument("config_path", type=Path)
    parser.add_argument("output_dir", type=Path)
    parser.add_argument("--n-workers", type=int, default=None, dest="n_workers")
    parser.add_argument("--device", type=str, default="tpu", choices=["tpu", "cpu", "gpu"])
    parser.add_argument("--code", type=Path, default=None)
    parser.add_argument("--verbose", "-V", action="store_true")
    args, extra = parser.parse_known_args(argv)

    logging.basicConfig(level=logging.DEBUG if args.verbose else logging.ERROR)
    _setup_device(args.device)

    from .config import load_config, parse_cli_overrides
    from .registry import import_code

    import_code(str(args.code) if args.code else None)
    overrides = parse_cli_overrides(extra)
    config = load_config(args.config_path, overrides, interpolate=False)

    from .training.pretrain import pretrain

    stats = pretrain(config, args.output_dir, n_workers=args.n_workers)
    print(
        f"Pretraining done. steps={stats['steps']} loss={stats['loss']:.4f} "
        f"words={stats['words']:,} -> {stats['output']}"
    )
    return 0


def package_command(argv: List[str]) -> int:
    """`package` — wrap a trained pipeline directory into an installable
    Python package (spaCy's `spacy package` surface)."""
    parser = argparse.ArgumentParser(
        prog="spacy_ray_tpu package",
        description="Package a saved pipeline as an installable Python "
        "project; load it back with spacy_ray_tpu.load(name).",
    )
    parser.add_argument("model_dir", type=Path)
    parser.add_argument("output_dir", type=Path)
    parser.add_argument("--name", type=str, default="pipeline")
    parser.add_argument("--version", type=str, default="0.0.0")
    parser.add_argument(
        "--build", type=str, default="none", choices=["none", "sdist", "wheel"]
    )
    parser.add_argument("--force", "-f", action="store_true",
                        help="overwrite an existing package directory")
    args = parser.parse_args(argv)

    from .packaging import package

    project = package(
        args.model_dir,
        args.output_dir,
        name=args.name,
        version=args.version,
        build=args.build,
        force=args.force,
    )
    print(f"Package written to {project}")
    if args.build != "none":
        dist = project / "dist"
        for f in sorted(dist.iterdir()):
            print(f"  built: {f}")
    return 0


def init_vectors_command(argv: List[str]) -> int:
    """`init-vectors` — convert word2vec-text / glove-text / .npz embeddings
    into the vectors.npz format `[initialize] vectors` loads (spaCy's
    `spacy init vectors` surface)."""
    parser = argparse.ArgumentParser(
        prog="spacy_ray_tpu init-vectors",
        description="Convert word embeddings (word2vec/glove text, optionally "
        ".gz, or an npz with words+vectors) for [initialize] vectors.",
    )
    parser.add_argument("input_path", type=Path)
    parser.add_argument("output_path", type=Path)
    parser.add_argument("--truncate", type=int, default=0,
                        help="keep only the first N rows (0 = all)")
    args = parser.parse_args(argv)

    import gzip

    import numpy as np

    from .pipeline.vectors import Vectors

    if args.input_path.suffix == ".npz":
        vec = Vectors.from_disk(args.input_path)
        words, table = list(vec.key_to_row), vec.table
        if args.truncate:
            words, table = words[: args.truncate], table[: args.truncate]
    else:
        opener = gzip.open if args.input_path.suffix == ".gz" else open
        words, rows = [], []
        with opener(args.input_path, "rt", encoding="utf8") as f:
            first = f.readline()
            parts = first.split()
            if len(parts) == 2 and all(p.isdigit() for p in parts):
                pass  # word2vec "N D" header line
            elif len(parts) >= 2:
                # glove-style: no header; first line is already a row
                words.append(parts[0])
                rows.append(np.asarray(parts[1:], dtype=np.float32))
            # else: empty/blank first line -> fall through; the "No vectors
            # found" check below reports cleanly
            for line in f:
                if args.truncate and len(words) >= args.truncate:
                    break
                parts = line.split()
                if len(parts) < 2:
                    continue
                words.append(parts[0])
                rows.append(np.asarray(parts[1:], dtype=np.float32))
        if not rows:
            print("No vectors found in input", file=sys.stderr)
            return 1
        widths = {r.shape[0] for r in rows}
        if len(widths) != 1:
            print(f"Inconsistent vector widths in input: {sorted(widths)}",
                  file=sys.stderr)
            return 1
        table = np.stack(rows)
    Vectors(words, table).to_disk(args.output_path)
    print(
        f"Wrote {len(words)} vectors (dim {table.shape[1]}) to "
        f"{args.output_path}; use via [initialize] vectors = "
        f"\"{args.output_path}\""
    )
    return 0


def parse_command(argv: List[str], prog: str = "parse") -> int:
    """Bulk parallel inference: annotate a corpus with a trained pipeline —
    the ``spacy ray parse`` command the reference advertises as planned
    (reference README.md:15 "we expect to add `spacy ray pretrain` and
    `spacy ray parse` as well"); also exposed as ``apply`` (spaCy's name
    for the same operation). Prediction batches shard over the mesh's
    ``data`` axis (every local device busy); under multi-host each process
    parses a round-robin shard of the input and writes its own output
    part, so throughput scales with hosts like the training loop does."""
    import time

    parser = argparse.ArgumentParser(prog=f"spacy_ray_tpu {prog}")
    parser.add_argument("model_path", type=Path)
    parser.add_argument("input_path", type=Path,
                        help=".jsonl/.conllu/.msgdoc/.spacy corpus, or .txt "
                        "with one raw text per line")
    parser.add_argument("output_path", type=Path,
                        help=".spacy (DocBin) or .jsonl output; multi-host "
                        "runs write one .partN per process")
    parser.add_argument("--device", type=str, default="tpu",
                        choices=["tpu", "cpu", "gpu"])
    parser.add_argument("--batch-size", type=int, default=128)
    parser.add_argument("--n-workers", type=int, default=None,
                        help="data-axis size for sharded prediction "
                        "(default: all local devices)")
    parser.add_argument("--coordinator", type=str, default=None,
                        help="jax.distributed coordinator address (multi-host)")
    parser.add_argument("--num-processes", type=int, default=None)
    parser.add_argument("--process-id", type=int, default=None)
    args = parser.parse_args(argv)
    _setup_device(args.device)
    _init_distributed(args.coordinator, args.num_processes, args.process_id)

    import jax

    from .parallel.mesh import build_mesh
    from .pipeline.language import Pipeline

    nlp = Pipeline.from_disk(args.model_path)

    # ---- stream input as bare (unannotated) docs ----
    # read/predict/write chunk-by-chunk: a genuinely bulk corpus — the
    # command's whole purpose — must not be materialized doc-by-doc on the
    # host (round-4 advisor finding). Only the .spacy writer keeps state
    # across chunks, and that is packed attribute rows, not Doc objects.
    import itertools
    import os as _os

    if args.input_path.suffix == ".txt":

        def _txt_docs():
            with open(args.input_path, encoding="utf8") as f:
                for line in f:
                    if line.strip():
                        yield nlp.tokenizer(line.rstrip("\n"))

        doc_iter = _txt_docs()
    else:
        from .training.corpus import _iter_path

        # strip any gold annotation: parse writes the MODEL's predictions
        doc_iter = (d.copy_shell() for d in _iter_path(args.input_path))

    # count docs BEFORE rank sharding: an empty round-robin slice on a
    # non-empty corpus (world > n_docs) is a legitimate empty part file,
    # not the corpus-empty error
    seen = {"total": 0}

    def _counted(it):
        for d in it:
            seen["total"] += 1
            yield d

    doc_iter = _counted(doc_iter)
    rank, world = jax.process_index(), jax.process_count()
    if world > 1:
        doc_iter = itertools.islice(doc_iter, rank, None, world)

    out = args.output_path
    if world > 1:
        out = out.with_name(f"{out.stem}.part{rank}{out.suffix}")
    out.parent.mkdir(parents=True, exist_ok=True)
    out_tmp = out.with_name(out.name + ".tmp")

    # one streaming writer per output family: text formats share a handle
    # (.jsonl plain, .msgdoc gzip lines), .spacy goes through the
    # incremental DocBinWriter. Everything lands in a .tmp first and is
    # promoted on success — a mid-corpus failure must not leave a
    # well-formed-looking truncated artifact at the final path.
    text_f = docbin_writer = None
    if out.suffix == ".spacy":
        from .training.spacy_docbin import DocBinWriter

        docbin_writer = DocBinWriter()
    elif out.suffix == ".jsonl":
        text_f = open(out_tmp, "w", encoding="utf8")
    else:
        import gzip

        text_f = gzip.open(out_tmp, "wt", encoding="utf8")

    mesh = build_mesh(n_data=args.n_workers) if jax.process_count() == 1 else None
    n_docs = n_words = 0
    seconds = 0.0
    try:
        while True:
            chunk = list(itertools.islice(doc_iter, args.batch_size))
            if not chunk:
                break
            t0 = time.perf_counter()
            nlp.predict_docs(chunk, batch_size=args.batch_size, mesh=mesh)
            seconds += time.perf_counter() - t0
            n_docs += len(chunk)
            n_words += sum(len(d) for d in chunk)
            if text_f is not None:
                import json

                from .training.corpus import _doc_to_json

                for d in chunk:
                    text_f.write(json.dumps(_doc_to_json(d)) + "\n")
            else:
                for d in chunk:
                    docbin_writer.add(d)
    except BaseException:
        if text_f is not None:
            text_f.close()
            out_tmp.unlink(missing_ok=True)
        raise
    if text_f is not None:
        text_f.close()
    if seen["total"] == 0:
        out_tmp.unlink(missing_ok=True)
        print(f"No documents in {args.input_path}", file=sys.stderr)
        return 1
    if docbin_writer is not None:
        docbin_writer.finalize(out_tmp)
    _os.replace(out_tmp, out)
    print(
        f"Parsed {n_docs} docs ({n_words} words) in {seconds:.1f}s "
        f"({n_words / max(seconds, 1e-9):,.0f} words/s) -> {out}"
    )
    return 0


def find_threshold_command(argv: List[str]) -> int:
    """Sweep a component's decision threshold against dev data and report
    the best value — spaCy's `find-threshold` surface for spancat /
    textcat_multilabel / entity_linker-style thresholded components."""
    parser = argparse.ArgumentParser(prog="spacy_ray_tpu find-threshold")
    parser.add_argument("model_path", type=Path)
    parser.add_argument("data_path", type=Path)
    parser.add_argument("pipe_name", type=str)
    parser.add_argument("--threshold-key", type=str, default="threshold",
                        help="component attribute to sweep")
    parser.add_argument("--scores-key", type=str, default=None,
                        help="score metric to maximize (default: the "
                        "component's positively-weighted default score)")
    parser.add_argument("--n-trials", type=int, default=11)
    parser.add_argument("--device", type=str, default="tpu",
                        choices=["tpu", "cpu", "gpu"])
    args = parser.parse_args(argv)
    _setup_device(args.device)

    from .pipeline.language import Pipeline
    from .training.corpus import Corpus

    nlp = Pipeline.from_disk(args.model_path)
    if args.pipe_name not in nlp.pipe_names:
        print(
            f"No component {args.pipe_name!r} in pipeline "
            f"(have: {', '.join(nlp.pipe_names)})", file=sys.stderr,
        )
        return 1
    comp = nlp.components[args.pipe_name]
    current = getattr(comp, args.threshold_key, None)
    if not isinstance(current, (int, float)) or isinstance(current, bool):
        print(
            f"[components.{args.pipe_name}] has no numeric attribute "
            f"{args.threshold_key!r} to sweep "
            f"(found: {type(current).__name__})", file=sys.stderr,
        )
        return 1
    scores_key = args.scores_key
    if scores_key is None:
        positive = [
            k for k, v in (getattr(comp, "default_score_weights", None) or {}).items()
            if v and v > 0
        ]
        if not positive:
            print(
                f"--scores-key required: [components.{args.pipe_name}] "
                "declares no default score weights", file=sys.stderr,
            )
            return 1
        scores_key = positive[0]

    examples = list(Corpus(args.data_path)())
    if not examples:
        print(f"No documents in {args.data_path}", file=sys.stderr)
        return 1

    # forward ONCE: the swept attribute is consumed host-side in
    # set_annotations/score, so device outputs are identical across
    # trials — only re-annotate + re-score per threshold. Consequence:
    # scores_key must be produced by the swept component itself.
    docs = [eg.reference.copy_shell() for eg in examples]
    chunks = list(
        nlp.predict_chunks(docs, batch_size=128, only=[args.pipe_name])
    )
    for eg, doc in zip(examples, docs):
        eg.predicted = doc

    n = max(int(args.n_trials), 2)
    best = (None, -1.0)
    try:
        for i in range(n):
            t = i / (n - 1)
            setattr(comp, args.threshold_key, t)
            for chunk, lengths, outputs in chunks:
                comp.set_annotations(chunk, outputs.get(args.pipe_name), lengths)
            scores = comp.score(examples)
            value = scores.get(scores_key)
            if value is None and i == 0 and scores_key not in scores:
                print(
                    f"{scores_key!r} is not produced by "
                    f"[components.{args.pipe_name}] (its scores: "
                    f"{', '.join(sorted(scores))}) — find-threshold sweeps one "
                    "component's own metric", file=sys.stderr,
                )
                return 1
            shown = f"{value:.4f}" if value is not None else "-"
            print(f"threshold={t:.3f}  {scores_key}={shown}")
            if value is not None and value > best[1]:
                best = (t, float(value))
    finally:
        # the sweep must not leave the component at its last trial value
        # (t=1.0): an in-process save after this call would persist an
        # arbitrary threshold (round-4 advisor finding)
        setattr(comp, args.threshold_key, current)
    if best[0] is None:
        print(f"{scores_key} was None at every threshold (no gold "
              "annotation for this metric in the dev data?)", file=sys.stderr)
        return 1
    print(
        f"Best: {args.threshold_key}={best[0]:.3f} ({scores_key}={best[1]:.4f}) "
        f"— set [components.{args.pipe_name}] {args.threshold_key} = {best[0]:.3f}"
    )
    return 0


def info_command(argv: List[str]) -> int:
    """Environment + install diagnostics (spacy's `info` role). Does not
    initialize the jax backend unless asked: `--probe` does, IN THIS
    PROCESS (a chip belongs to one process at a time, so a parent that
    imported jax must not hand the probing to children), and reports what
    every platform-dependent switch resolves to on the device it finds."""
    import os
    import platform as _platform

    parser = argparse.ArgumentParser(prog="spacy_ray_tpu info")
    parser.add_argument(
        "--probe", action="store_true",
        help="initialise the default backend in this process and report "
        "the device plus what each \"auto\" switch and kernel probe "
        "resolves to on it (compiles four small kernels on a TPU)",
    )
    parser.add_argument(
        "--markdown", action="store_true",
        help="print the environment block as a markdown table "
        "(spaCy's issue-report format)",
    )
    parser.add_argument("model_path", nargs="?", type=Path, default=None,
                        help="optional: show a saved pipeline's metadata")
    args = parser.parse_args(argv)

    from . import __version__

    import jax

    rows = [
        ("spacy-ray-tpu", __version__),
        ("python", f"{_platform.python_version()} ({_platform.system()})"),
        ("jax", jax.__version__),
        ("JAX_PLATFORMS", os.environ.get("JAX_PLATFORMS", "(unset)")),
        ("XLA_FLAGS", os.environ.get("XLA_FLAGS", "(unset)")),
    ]
    if args.probe:
        rows += _probe_rows()
    if args.markdown:
        print("| field | value |")
        print("|---|---|")
        for key, value in rows:
            print(f"| {key} | {value} |")
    else:
        for key, value in rows:
            print(f"{key:16s} {value}")
    if args.model_path is not None:
        import json

        meta_path = args.model_path / "meta.json"
        if not meta_path.exists():
            print(f"\nNo pipeline at {args.model_path} (missing meta.json)",
                  file=sys.stderr)
            return 1
        meta = json.loads(meta_path.read_text(encoding="utf8"))
        print(f"\npipeline         {meta.get('lang', '?')}/{meta.get('name', '?')}")
        print(f"version          {meta.get('version', '?')}")
        print(f"components       {', '.join(meta.get('pipeline', []))}")
    return 0


def _probe_rows() -> List[tuple]:
    """`info --probe`: initialise the backend here and resolve every
    platform-dependent switch on it, the same honest-label discipline as
    the records: what each knob would ACTUALLY do on this device. A kernel
    whose probe fails on a TPU is reported in its own words, not raised —
    this command exists to show the state of the installation."""
    import jax

    from .devices import enable_compile_cache, runtime_report
    from .models.transformer import _resolve_compute_dtype
    from .ops import flash_attention, fused_update, pallas_kernels
    from .ops.probe import KernelProbeError
    from .parallel.mesh import build_mesh
    from .parallel.step import resolve_update_sharding, update_sharding_status
    from .serving.overlay import resolve_precision
    from .training.fleet.wire import resolve_grad_compression

    enable_compile_cache()
    try:
        devs = jax.devices()
    except Exception as e:  # backend start-up fails with several types
        return [("accelerator", f"UNREACHABLE ({type(e).__name__}: {e})")]
    platform_name = devs[0].platform

    # run the kernel probes eagerly: each compiles and checks its kernel,
    # and a failure stays in its status ("FAILED (...)", ops/probe.Gate)
    for enabled in (
        flash_attention.flash_attention_enabled,
        pallas_kernels.pallas_enabled,
        fused_update.fused_kernel_enabled,
    ):
        try:
            enabled()
        except KernelProbeError:
            pass
    try:
        precision = "{} ({})".format(*resolve_precision("int8", platform_name))
    except KernelProbeError as e:
        precision = f"FAILED ({e})"
    report = runtime_report()
    gc = resolve_grad_compression("auto", platform_name)
    return [
        ("accelerator", f"reachable: {platform_name} x{len(devs)} "
                        f"({devs[0].device_kind})"),
        ("update_sharding", "auto -> " + update_sharding_status(
            resolve_update_sharding(
                "auto", n_data=len(devs), backend=platform_name
            ),
            build_mesh(n_data=len(devs)),
        )),
        ("grad_compression", f"auto -> {gc[0]} ({gc[1]})"),
        ("compute_dtype",
         f"auto -> {_resolve_compute_dtype('auto').__name__}"),
        ("flash_attention", report["flash_attention"]),
        ("hash_embed", report["hash_embed_kernel"]),
        ("fused_kernel", fused_update.fused_kernel_status()),
        ("precision", "int8 -> " + precision),
        ("native_hash", report["native_hash"]),
        ("compile_cache", "{dir} ({entries} entries)".format(
            **report["compile_cache"]
        )),
    ]


def debug_model_command(argv: List[str]) -> int:
    """Inspect a config's resolved model shapes (spacy's `debug model`
    role): initialize the pipeline from the training corpus (labels need
    gold data) and print every parameter path, shape, dtype, and
    per-component totals."""
    parser = argparse.ArgumentParser(prog="spacy_ray_tpu debug-model")
    parser.add_argument("config_path", type=Path)
    parser.add_argument("component", nargs="?", default=None,
                        help="restrict output to one component")
    parser.add_argument("--device", type=str, default="cpu",
                        choices=["tpu", "cpu", "gpu"],
                        help="default cpu: shape inspection needs no accelerator")
    parser.add_argument("--code", type=Path, default=None)
    # split dotted overrides out BEFORE argparse: the optional positional
    # `component` would otherwise swallow an override's value
    override_args: List[str] = []
    rest: List[str] = []
    i = 0
    while i < len(argv):
        a = argv[i]
        if a.startswith("--") and "." in a.split("=", 1)[0]:
            override_args.append(a)
            if "=" not in a and i + 1 < len(argv):
                override_args.append(argv[i + 1])
                i += 1
        else:
            rest.append(a)
        i += 1
    args = parser.parse_args(rest)
    extra = override_args
    _setup_device(args.device)

    import numpy as np

    from .config import load_config, parse_cli_overrides
    from .pipeline.language import Pipeline
    from .registry import import_code, registry
    from .training.loop import resolve_dot_name, resolve_training

    import_code(str(args.code) if args.code else None)
    config = load_config(args.config_path, parse_cli_overrides(extra),
                         interpolate=False).interpolate()
    T = resolve_training(config)
    resolved_corpora = {
        name: registry.resolve(block)
        for name, block in config.get("corpora", {}).items()
    }
    train_corpus = resolve_dot_name(config, resolved_corpora, T["train_corpus"])
    nlp = Pipeline.from_config(config)
    nlp.initialize(train_corpus, seed=int(T.get("seed") or 0))

    if args.component is not None and args.component not in nlp.pipe_names:
        print(
            f"No component {args.component!r} (have: {', '.join(nlp.pipe_names)})",
            file=sys.stderr,
        )
        return 1

    from .models.core import param_paths

    grand_total = 0
    for name in nlp.pipe_names:
        if args.component and name != args.component:
            continue
        comp_params = nlp.params.get(name)
        comp = nlp.components[name]
        if comp_params is None:
            print(f"[{name}] (host-side component, no device parameters)")
            continue
        print(f"[{name}] labels={len(comp.labels)}")
        total = 0
        import jax

        flat = {
            path: leaf
            for path, leaf in zip(
                param_paths(comp_params), jax.tree_util.tree_leaves(comp_params)
            )
        }
        for path, leaf in sorted(flat.items()):
            n = int(np.prod(leaf.shape)) if leaf.shape else 1
            total += n
            print(f"  {path:48s} {str(tuple(leaf.shape)):20s} {leaf.dtype} {n:,}")
        grand_total += total
        print(f"  [{name}] total: {total:,} params")
    print(f"TOTAL: {grand_total:,} params")
    return 0


def fill_config_command(argv: List[str]) -> int:
    """Complete a partial config with every [training] default and validate
    the result (spacy's `init fill-config` role): the written file shows
    explicitly what a bare config would train with — seed, dropout,
    patience, eval_frequency, batcher, optimizer, logger — instead of
    relying on invisible defaults."""
    parser = argparse.ArgumentParser(prog="spacy_ray_tpu fill-config")
    parser.add_argument("base_path", type=Path, help="partial config")
    parser.add_argument("output_path", type=Path, help="filled config")
    args, extra = parser.parse_known_args(argv)

    from .config import Config, load_config, parse_cli_overrides
    from .training.loop import (
        DEFAULT_TRAINING,
        DEFAULT_TRAINING_BLOCKS,
        resolve_training,
    )

    config = load_config(args.base_path, parse_cli_overrides(extra),
                         interpolate=False)
    raw_training = dict(config.get("training", {}))
    if "paths" not in config:
        # a partial config may interpolate ${paths.*} without declaring
        # the section; fill it before validation like `train` overrides do
        config = config.merge({"paths": {"train": None, "dev": None}})
    resolve_training(config.interpolate())  # validates keys/types loudly
    filled_training = dict(DEFAULT_TRAINING)
    filled_training.update(raw_training)
    # registry sub-blocks every run resolves implicitly when absent
    for key, block in DEFAULT_TRAINING_BLOCKS.items():
        filled_training.setdefault(key, dict(block))
    merged = dict(config)
    merged["training"] = filled_training
    merged.setdefault("paths", {"train": None, "dev": None})
    out_cfg = Config(merged)
    Config.from_str(out_cfg.to_str())  # round-trip = validate serialization
    args.output_path.write_text(out_cfg.to_str(), encoding="utf8")
    added = sorted(set(filled_training) - set(raw_training))
    print(f"Filled {args.base_path} -> {args.output_path} "
          f"(added: {', '.join(added) if added else 'nothing'})")
    return 0


def debug_diff_command(argv: List[str]) -> int:
    """spaCy's `debug diff-config` role: classify every [training] key of
    a config against the defaults a bare config trains with (the same
    table fill-config writes) — customized / redundant restatement of a
    default / implicit default — so a reviewer sees at a glance what a
    config actually changes."""
    parser = argparse.ArgumentParser(prog="spacy_ray_tpu debug-diff-config")
    parser.add_argument("config_path", type=Path)
    args, extra = parser.parse_known_args(argv)

    from .config import load_config, parse_cli_overrides
    from .training.loop import (
        DEFAULT_TRAINING,
        DEFAULT_TRAINING_BLOCKS,
        resolve_training,
    )

    config = load_config(args.config_path, parse_cli_overrides(extra),
                         interpolate=False)
    if "paths" not in config:
        config = config.merge({"paths": {"train": None, "dev": None}})
    interpolated = config.interpolate()
    resolve_training(interpolated)  # loud validation first
    # classify INTERPOLATED values: `dropout = ${vars.drop}` must compare
    # by what it resolves to, not the template string
    raw = dict(interpolated.get("training", {}))
    defaults: Dict[str, Any] = {**DEFAULT_TRAINING, **DEFAULT_TRAINING_BLOCKS}
    rows = []
    for key in sorted(set(raw) | set(defaults)):
        if key in raw and key not in defaults:
            rows.append((key, "customized", raw[key], "-"))
        elif key in raw and raw[key] != defaults[key]:
            rows.append((key, "customized", raw[key], defaults[key]))
        elif key in raw:
            rows.append((key, "redundant (= default)", raw[key], defaults[key]))
        else:
            rows.append((key, "implicit default", "-", defaults[key]))
    width = max(len(r[0]) for r in rows)
    print(f"{'[training] key':{width}s}  {'status':22s} value (default)")
    for key, status, value, default in rows:
        shown = value if value != "-" else default
        suffix = f" (default: {default})" if status == "customized" and default != "-" else ""
        print(f"{key:{width}s}  {status:22s} {shown}{suffix}")
    n_custom = sum(1 for r in rows if r[1] == "customized")
    n_redund = sum(1 for r in rows if r[1].startswith("redundant"))
    print(f"\n{n_custom} customized, {n_redund} redundant, "
          f"{len(rows) - n_custom - n_redund} implicit defaults")
    return 0


def init_labels_command(argv: List[str]) -> int:
    """spaCy's `init labels` surface: collect every trainable component's
    label set from the training corpus ONCE and write one JSON file per
    component. Point the config at them via
    ``[initialize.components.<name>] labels = "<dir>/<name>.json"`` —
    later runs skip corpus label collection and the class ORDER is frozen
    (a grown corpus can no longer silently renumber classes between
    train/resume)."""
    import json

    parser = argparse.ArgumentParser(prog="spacy_ray_tpu init-labels")
    parser.add_argument("config_path", type=Path)
    parser.add_argument("output_dir", type=Path)
    parser.add_argument("--code", type=Path, default=None)
    parser.add_argument("--device", type=str, default="cpu",
                        choices=["tpu", "cpu", "gpu"],
                        help="label collection is host-side; cpu default")
    args, extra = parser.parse_known_args(argv)
    _setup_device(args.device)

    from .config import load_config, parse_cli_overrides
    from .registry import import_code, registry
    from .training.loop import resolve_dot_name, resolve_training

    import_code(str(args.code) if args.code else None)
    config = load_config(args.config_path, parse_cli_overrides(extra),
                         interpolate=False).interpolate()
    T = resolve_training(config)
    corpora_cfg = config.get("corpora", {})
    resolved = {n: registry.resolve(b) for n, b in corpora_cfg.items()}
    train_corpus = resolve_dot_name(config, resolved, T["train_corpus"])

    from .pipeline.language import LABEL_SAMPLE_LIMIT, Pipeline

    nlp = Pipeline.from_config(config)
    sample = []
    for i, eg in enumerate(train_corpus()):
        if i >= LABEL_SAMPLE_LIMIT:  # Pipeline.initialize's cap, shared
            break
        sample.append(eg)
    if not sample:
        print("Training corpus is empty — no labels to collect",
              file=sys.stderr)
        return 1
    args.output_dir.mkdir(parents=True, exist_ok=True)
    written = []
    for name in nlp.pipe_names:
        if name in nlp.sourced_components:
            # initialize ignores a labels override for sourced components
            # (their labels came with the saved model) — writing a file
            # here would advertise a pin that can never take effect
            print(f"[components.{name}] sourced: labels come with the "
                  "saved component; skipped")
            continue
        comp = nlp.components[name]
        comp.add_labels_from(sample)
        comp.finish_labels()
        if not comp.labels:
            continue  # host-only / label-free components have nothing to pin
        out = args.output_dir / f"{name}.json"
        out.write_text(json.dumps(comp.labels, indent=2) + "\n",
                       encoding="utf8")
        print(f"[components.{name}] {len(comp.labels)} labels -> {out}")
        written.append(name)
    if written:
        print(
            "Use in the config:\n"
            + "".join(
                f'[initialize.components.{name}]\nlabels = '
                f'"{args.output_dir / (name + ".json")}"\n'
                for name in written
            )
        )
    else:
        print("No component produced labels from this corpus")
    return 0


def debug_profile_command(argv: List[str]) -> int:
    """spaCy's `debug profile` surface: cProfile bulk inference over a
    corpus and print the hottest host-side functions. Device compute shows
    up as opaque `block_until_ready`/execute frames — use
    `train --profile` (jax.profiler) for the device-side picture; this
    command is for finding HOST bottlenecks (tokenization, collation,
    decode, annotation)."""
    import cProfile
    import pstats

    parser = argparse.ArgumentParser(prog="spacy_ray_tpu debug-profile")
    parser.add_argument("model_path", type=Path)
    parser.add_argument("data_path", type=Path)
    parser.add_argument("--batch-size", type=int, default=128)
    parser.add_argument("--n-rows", type=int, default=25,
                        help="how many rows of the cumtime table to print")
    parser.add_argument("--device", type=str, default="tpu",
                        choices=["tpu", "cpu", "gpu"])
    args = parser.parse_args(argv)
    _setup_device(args.device)

    from .pipeline.language import Pipeline
    from .training.corpus import Corpus

    nlp = Pipeline.from_disk(args.model_path)
    examples = list(Corpus(args.data_path)())
    if not examples:
        print(f"No documents in {args.data_path}", file=sys.stderr)
        return 1
    docs = [eg.reference.copy_shell() for eg in examples]
    # un-profiled warmup pass: compile time would otherwise dominate the
    # table and hide the steady-state host cost
    nlp.predict_docs([d.copy_shell() for d in docs], batch_size=args.batch_size)

    profiler = cProfile.Profile()
    profiler.enable()
    try:
        nlp.predict_docs(docs, batch_size=args.batch_size)
    finally:
        # a raised predict must not leave the process-wide C profiling
        # hook installed (in-process callers: every later call runs
        # profiled and a second Profile().enable() raises)
        profiler.disable()
    stats = pstats.Stats(profiler, stream=sys.stdout)
    stats.strip_dirs().sort_stats("cumulative").print_stats(args.n_rows)
    return 0


def benchmark_command(argv: List[str]) -> int:
    """``benchmark speed`` / ``benchmark accuracy`` — spaCy's `spacy
    benchmark` surface. `speed` times bulk inference on a corpus with
    warmup, reporting median words/s over N repetitions with min/max;
    `accuracy` is `evaluate` under its spaCy-CLI name."""
    import time

    if argv and argv[0] == "accuracy":
        return evaluate_command(argv[1:])
    if not argv or argv[0] != "speed":
        print("Usage: spacy_ray_tpu benchmark {speed,accuracy} "
              "<model> <data> ...", file=sys.stderr)
        return 1
    parser = argparse.ArgumentParser(prog="spacy_ray_tpu benchmark speed")
    parser.add_argument("model_path", type=Path)
    parser.add_argument("data_path", type=Path)
    parser.add_argument("--batch-size", type=int, default=128)
    parser.add_argument("--n-reps", type=int, default=3)
    parser.add_argument("--warmup", type=int, default=1,
                        help="un-timed full passes first (compile + cache)")
    parser.add_argument("--device", type=str, default="tpu",
                        choices=["tpu", "cpu", "gpu"])
    args = parser.parse_args(argv[1:])
    _setup_device(args.device)

    from .pipeline.language import Pipeline
    from .training.corpus import Corpus

    nlp = Pipeline.from_disk(args.model_path)
    examples = list(Corpus(args.data_path)())
    if not examples:
        print(f"No documents in {args.data_path}", file=sys.stderr)
        return 1
    n_words = sum(len(eg.reference) for eg in examples)

    def one_pass():
        docs = [eg.reference.copy_shell() for eg in examples]
        t0 = time.perf_counter()
        nlp.predict_docs(docs, batch_size=args.batch_size)
        return time.perf_counter() - t0

    import statistics

    for _ in range(max(args.warmup, 0)):
        one_pass()
    rates = sorted(n_words / one_pass() for _ in range(max(args.n_reps, 1)))
    median = statistics.median(rates)
    print(
        f"Benchmark: {len(examples)} docs, {n_words} words, "
        f"batch_size={args.batch_size}, reps={len(rates)}"
    )
    print(
        f"words/s: median {median:,.0f}  min {rates[0]:,.0f}  "
        f"max {rates[-1]:,.0f}"
    )
    return 0


def telemetry_command(argv: List[str]) -> int:
    """``telemetry`` — offline and live observability tools, all jax-free
    (safe on any host):

    * ``summarize <metrics.jsonl | run-dir>`` — digest a telemetry file
      (training rows: step-time percentiles, device gauges, per-stage
      breakdown; serving rows: SLO window, rejects, by-generation split;
      trainer-fleet rows: counters, phase share, staleness digest;
      anomaly digest) or a whole fleet run directory;
    * ``top <url>...`` — live terminal dashboard polling ``/metrics`` on
      replica / router / trainer endpoints (req/s, window p50/p99,
      occupancy, queue depth, generation, swap count, anomalies);
    * ``collect-trace <url>... --out FILE`` — merge the Perfetto trace
      buffers of router, replicas (auto-discovered from a router URL),
      and trainer into ONE timeline file via their /healthz clock
      anchors (docs/OBSERVABILITY.md "Distributed tracing").
    * ``postmortem <dir>`` — render an incident bundle (an alert-fired
      flight-recorder dump or a crash postmortem) as a human-readable
      report: exit status/signal, config, stderr tail, alert states,
      metric digest, and a merged cross-process timeline built with the
      same clock-anchor merge collect-trace uses. Given the incidents
      ROOT, renders the newest bundle.
    * ``report <run-dir>`` — digest a training run directory (the
      trainer fleet's per-worker ledgers + metrics.jsonl files, or a
      single-process run's metrics.jsonl) into ONE markdown report:
      per-worker loss trajectories, the phase-share table,
      staleness/discard histograms, quorum-wait/apply timing, and the
      alert/anomaly timeline (docs/OBSERVABILITY.md "Training fleet").
    """
    usage = ("Usage: spacy_ray_tpu telemetry "
             "{summarize <metrics.jsonl-or-run-dir> | top <url>... | "
             "collect-trace [<url>...] [--fleet-base-port N --workers K] "
             "--out FILE | "
             "postmortem <bundle-or-incidents-dir> | "
             "report <run-dir> [--out FILE]}")
    if not argv or argv[0] not in (
        "summarize", "top", "collect-trace", "postmortem", "report",
    ):
        print(usage, file=sys.stderr)
        return 1
    sub, rest = argv[0], argv[1:]
    if sub == "report":
        parser = argparse.ArgumentParser(
            prog="spacy_ray_tpu telemetry report"
        )
        parser.add_argument("run_dir", type=Path,
                            help="a training run's output directory "
                            "(fleet-worker-*.json ledgers + metrics/, "
                            "or a plain metrics.jsonl run)")
        parser.add_argument("--metrics-dir", type=Path, default=None,
                            dest="metrics_dir",
                            help="where the run's telemetry landed "
                            "(default: <run-dir>/metrics)")
        parser.add_argument("--out", type=Path, default=None,
                            help="also write the markdown report here")
        args = parser.parse_args(rest)

        from .training.report import build_run_report

        try:
            report = build_run_report(args.run_dir, args.metrics_dir)
        except ValueError as e:
            print(str(e), file=sys.stderr)
            return 1
        except OSError as e:
            print(f"Cannot read {args.run_dir}: {e}", file=sys.stderr)
            return 1
        print(report)
        if args.out is not None:
            try:
                args.out.parent.mkdir(parents=True, exist_ok=True)
                args.out.write_text(report, encoding="utf8")
            except OSError as e:
                print(f"Cannot write {args.out}: {e}", file=sys.stderr)
                return 1
            print(f"run report written to {args.out}", file=sys.stderr)
        return 0
    if sub == "postmortem":
        parser = argparse.ArgumentParser(
            prog="spacy_ray_tpu telemetry postmortem"
        )
        parser.add_argument("bundle", type=Path,
                            help="an incident bundle directory "
                            "(incidents/<stamp>-<source>/) or the "
                            "incidents root (newest bundle is rendered)")
        parser.add_argument("--trace-out", type=Path, default=None,
                            help="also write the bundle's merged "
                            "cross-process Chrome trace here (open in "
                            "ui.perfetto.dev)")
        args = parser.parse_args(rest)

        from .incidents import (
            find_bundle,
            load_bundle,
            merged_bundle_trace,
            render_bundle,
        )

        try:
            # load ONCE: the report and the optional --trace-out merge
            # share the same loaded bundle (flight files can be MBs)
            bundle = load_bundle(find_bundle(args.bundle))
            print(render_bundle(bundle))
        except FileNotFoundError as e:
            print(str(e), file=sys.stderr)
            return 1
        except (OSError, ValueError) as e:
            print(f"Cannot render {args.bundle}: {e}", file=sys.stderr)
            return 1
        if args.trace_out is not None:
            from .serving.tracecollect import write_merged_trace

            try:
                merged = merged_bundle_trace(bundle)
                path = write_merged_trace(merged, args.trace_out)
            except OSError as e:
                print(
                    f"Cannot write {args.trace_out}: {e}", file=sys.stderr
                )
                return 1
            print(f"merged bundle trace written to {path}")
        return 0
    if sub == "summarize":
        parser = argparse.ArgumentParser(
            prog="spacy_ray_tpu telemetry summarize"
        )
        parser.add_argument("metrics_path", type=Path,
                            help="metrics.jsonl written by a [training] "
                            "metrics_dir / train --metrics-dir run or a "
                            "serve --metrics-dir run — or a trainer-fleet "
                            "RUN DIRECTORY (fleet-worker-*.json ledgers "
                            "+ metrics/fleet-worker-*/metrics.jsonl)")
        args = parser.parse_args(rest)

        from .training.telemetry import summarize_metrics

        try:
            print(summarize_metrics(args.metrics_path))
        except OSError as e:
            # FileNotFound, IsADirectory (the metrics DIR), permissions
            print(f"Cannot read {args.metrics_path}: {e}", file=sys.stderr)
            return 1
        except ValueError as e:
            print(str(e), file=sys.stderr)
            return 1
        return 0
    if sub == "top":
        parser = argparse.ArgumentParser(prog="spacy_ray_tpu telemetry top")
        parser.add_argument("urls", nargs="+", metavar="URL",
                            help="endpoint base URLs (router, replica, or "
                            "trainer --metrics-port), e.g. "
                            "http://127.0.0.1:8090")
        parser.add_argument("--interval-s", type=float, default=2.0)
        parser.add_argument("--iterations", type=int, default=None,
                            help="stop after N refreshes (default: until "
                            "Ctrl-C)")
        args = parser.parse_args(rest)

        from .top import run_top

        return run_top(
            args.urls, interval_s=args.interval_s,
            iterations=args.iterations,
        )
    parser = argparse.ArgumentParser(
        prog="spacy_ray_tpu telemetry collect-trace"
    )
    parser.add_argument("urls", nargs="*", metavar="URL",
                        help="endpoint base URLs; a fleet router URL "
                        "auto-discovers its replicas")
    parser.add_argument("--out", type=Path, required=True,
                        help="merged Chrome-trace JSON output path "
                        "(open in ui.perfetto.dev)")
    parser.add_argument("--no-discover", action="store_true",
                        help="do not expand a router URL into its "
                        "replicas")
    parser.add_argument("--fleet-base-port", type=int, default=None,
                        dest="fleet_base_port",
                        help="TRAINER fleet: scrape worker k's endpoint "
                        "at <fleet-host>:base+k for k in 0..workers-1 "
                        "(a trainer fleet has no router to discover "
                        "through; matches train --fleet-base-port)")
    parser.add_argument("--workers", type=int, default=None,
                        help="trainer fleet worker count (with "
                        "--fleet-base-port)")
    parser.add_argument("--fleet-host", default="127.0.0.1",
                        dest="fleet_host",
                        help="trainer fleet host (default 127.0.0.1)")
    args = parser.parse_args(rest)

    from .serving.tracecollect import (
        collect_fleet_traces,
        fleet_worker_urls,
        write_merged_trace,
    )

    urls = list(args.urls)
    if (args.fleet_base_port is None) != (args.workers is None):
        parser.error("--fleet-base-port and --workers go together")
    if args.workers is not None and args.workers <= 0:
        parser.error(f"--workers must be positive, got {args.workers}")
    if args.fleet_base_port is not None:
        urls.extend(
            fleet_worker_urls(
                args.fleet_base_port, args.workers, host=args.fleet_host
            )
        )
    if not urls:
        parser.error(
            "give endpoint URLs, or --fleet-base-port N --workers K "
            "for a trainer fleet"
        )
    merged = collect_fleet_traces(urls, discover=not args.no_discover)
    info = merged.get("otherData") or {}
    if not info.get("merged_from"):
        print(
            "no traces collected "
            f"(skipped: {info.get('skipped')}) — are the endpoints up "
            "with telemetry enabled?",
            file=sys.stderr,
        )
        return 1
    path = write_merged_trace(merged, args.out)
    n = sum(
        1 for e in merged["traceEvents"] if e.get("ph") != "M"
    )
    print(
        f"merged {n} event(s) from {len(info['merged_from'])} process(es) "
        f"into {path}"
        + (f" (skipped: {info['skipped']})" if info.get("skipped") else "")
    )
    return 0


def serve_command(argv: List[str]) -> int:
    """``serve`` — online inference over HTTP with dynamic micro-batching
    (docs/SERVING.md): load a saved pipeline, warm the (B, T) bucket
    programs, then serve ``/v1/parse`` until SIGTERM, which triggers a
    graceful drain (stop admitting, finish in-flight batches, exit 0)."""
    from .serving.engine import SERVING_DEFAULTS

    parser = argparse.ArgumentParser(
        prog="spacy_ray_tpu serve",
        description="Serve a saved pipeline as a JSON HTTP API "
        "(/v1/parse, /healthz, /metrics) with dynamic micro-batching.",
    )
    parser.add_argument("model_path", type=Path)
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--port", type=int, default=8080,
                        help="0 = ephemeral; the bound port is printed in "
                        "the 'serving on http://...' banner")
    parser.add_argument("--device", type=str, default="tpu",
                        choices=["tpu", "cpu", "gpu"])
    parser.add_argument("--max-batch", type=int,
                        default=SERVING_DEFAULTS["max_batch_docs"],
                        help="max docs coalesced into one device batch")
    parser.add_argument("--batching",
                        choices=["continuous", "window"],
                        default=SERVING_DEFAULTS["batching"],
                        help="admission discipline: 'continuous' (default) "
                        "admits queued requests into the next dispatch's "
                        "free slots immediately — the in-flight batch is "
                        "the coalescing window; 'window' is the classic "
                        "size-or-deadline rule bounded by --window-ms")
    parser.add_argument("--continuous", action="store_const",
                        const="continuous", dest="batching",
                        help="alias for --batching continuous")
    parser.add_argument("--max-wait-ms", "--window-ms", type=float,
                        dest="max_wait_ms",
                        default=SERVING_DEFAULTS["max_wait_s"] * 1e3,
                        help="window mode only: coalescing window from the "
                        "first queued request (added latency bound); "
                        "ignored under continuous admission")
    parser.add_argument("--precision",
                        choices=["auto", "f32", "bf16", "int8"],
                        default=SERVING_DEFAULTS["precision"],
                        help="serving precision overlay (docs/SERVING.md): "
                        "'auto' arms a bf16 trunk overlay on accelerators "
                        "and resolves f32 on CPU (emulated bf16 is a "
                        "measured pessimization there); 'bf16' forces the "
                        "overlay; 'int8' arms the weight-only pallas "
                        "dequant-in-kernel overlay where the probe "
                        "passes (TPU; CPU only under SRT_PALLAS_INT8=1, "
                        "interpret-mode) and serves f32 with an honest "
                        "refusal label everywhere else")
    parser.add_argument("--queue-size", type=int,
                        default=SERVING_DEFAULTS["max_queue_docs"],
                        help="bounded admission queue (docs); beyond it "
                        "requests are rejected 429")
    parser.add_argument("--timeout-ms", type=float,
                        default=SERVING_DEFAULTS["timeout_s"] * 1e3,
                        help="default per-request deadline (clients may "
                        "lower it per call via timeout_ms)")
    parser.add_argument("--max-doc-len", type=int,
                        default=SERVING_DEFAULTS["max_doc_len"],
                        help="longest admissible doc in tokens (the warmed "
                        "shape cap; longer docs are rejected 413)")
    parser.add_argument("--drain-timeout-s", type=float, default=30.0)
    parser.add_argument("--watch", type=Path, default=None, metavar="CKPT_DIR",
                        help="live continuous learning (docs/SERVING.md): "
                        "poll this TrainCheckpoint directory (a training "
                        "run's <output>/last-model) and hot-swap each new "
                        "digest-verified generation at a dispatch boundary "
                        "— zero dropped requests, torn generations "
                        "skipped, instant rollback via POST /admin/rollback")
    parser.add_argument("--watch-interval-s", type=float, default=2.0,
                        help="checkpoint-directory poll interval")
    parser.add_argument("--swap-dir", type=Path, action="append",
                        default=[], dest="swap_dirs", metavar="CKPT_DIR",
                        help="checkpoint directory POST /admin/swap may "
                        "load generations from (repeatable; --watch is "
                        "allowed implicitly). With neither, admin swaps "
                        "are refused 403 — an open port must not accept "
                        "arbitrary client-supplied weight paths")
    parser.add_argument("--no-warmup", action="store_true",
                        help="skip the bucket compile sweep (first requests "
                        "then pay compiles — testing only)")
    parser.add_argument("--model-manifest", type=Path, default=None,
                        help="multi-model serving (docs/SERVING.md "
                        "'Multi-model fleet'): a JSON manifest of model "
                        "name -> pipeline dir (plus SLO classes and tenant "
                        "quotas). Requests route by /v1/models/<name>/parse "
                        "or the X-SRT-Model header; /v1/parse keeps serving "
                        "the manifest's default model. The positional "
                        "model_path is ignored — the manifest's default "
                        "model path is authoritative")
    parser.add_argument("--resident-models", type=int, default=2,
                        help="multi-model only: how many warmed engines "
                        "this replica keeps resident at once (LRU eviction "
                        "past this; the default model is pinned)")
    parser.add_argument("--no-telemetry", action="store_true",
                        help="disable the SLO metrics/trace surface "
                        "entirely (zero telemetry calls; /metrics reports "
                        "disabled)")
    parser.add_argument("--metrics-dir", type=Path, default=None,
                        help="write serving_trace.json + a final metrics "
                        "snapshot here on shutdown")
    parser.add_argument("--incidents-dir", type=Path, default=None,
                        help="arm the flight recorder (docs/OBSERVABILITY.md "
                        "'Alerting & incidents'): when an alert fires, the "
                        "recent metric-snapshot ring + span ring are dumped "
                        "to <dir>/<utc-stamp>-<source>/ for `telemetry "
                        "postmortem`; alert transitions append to "
                        "<dir>/alerts.jsonl")
    parser.add_argument("--blackbox", type=Path, default=None,
                        help="persist the flight-recorder payload to this "
                        "file (atomic replace, rate-limited to ~10s between "
                        "rewrites — crash evidence may lag by up to that) — "
                        "the SIGKILL-survivable copy a fleet supervisor "
                        "folds into the crash postmortem bundle")
    parser.add_argument("--alert-p99-ms", type=float, default=500.0,
                        help="sliding-window p99 target the default "
                        "'serving-latency-slo' alert rule fires against "
                        "(the error-budget burn-rate rule is independent "
                        "of it)")
    parser.add_argument("--observe-interval-s", type=float, default=2.0,
                        help="cadence of the diagnosis tick (alert rule "
                        "evaluation, flight-recorder ring feed, black-box "
                        "persistence)")
    parser.add_argument("--verbose", "-V", action="store_true")
    args = parser.parse_args(argv)

    logging.basicConfig(level=logging.DEBUG if args.verbose else logging.ERROR)
    logging.getLogger("spacy_ray_tpu.training").setLevel(
        logging.INFO if args.verbose else logging.WARNING
    )
    _setup_device(args.device)

    from .pipeline.language import Pipeline
    from .serving.engine import InferenceEngine, ServingTelemetry
    from .serving.server import Server

    # multi-model serving: registry + admission from the manifest; the
    # residency manager owns every engine beyond the pinned default
    registry = None
    residency = None
    admission = None
    if args.model_manifest is not None:
        from .serving.multimodel import (
            AdmissionController,
            ModelRegistry,
            ResidencyManager,
        )

        registry = ModelRegistry.from_manifest(args.model_manifest)
        admission = AdmissionController(registry)

    class_weights = (
        registry.class_weights() if registry is not None else None
    )

    def _build_engine(path: Path, mtel) -> "InferenceEngine":
        return InferenceEngine(
            Pipeline.from_disk(path),
            max_batch_docs=args.max_batch,
            max_wait_s=max(args.max_wait_ms, 0.0) / 1e3,
            max_queue_docs=args.queue_size,
            timeout_s=max(args.timeout_ms, 1.0) / 1e3,
            max_doc_len=args.max_doc_len,
            batching=args.batching,
            precision=args.precision,
            telemetry=mtel,
            class_weights=class_weights,
        )

    default_path = args.model_path
    if registry is not None:
        default_path = Path(registry.spec(registry.default_model).path)
    tel = None if args.no_telemetry else ServingTelemetry()
    engine = _build_engine(default_path, tel)
    if registry is not None:

        def _engine_factory(spec) -> "InferenceEngine":
            # each resident model gets its OWN telemetry (per-model
            # /metrics blocks) and its own warmed bucket programs —
            # loads happen on a request thread, never the dispatch one
            mtel = None if args.no_telemetry else ServingTelemetry()
            e = _build_engine(Path(spec.path), mtel)
            if not args.no_warmup:
                e.warmup()
            e.start()
            return e

        residency = ResidencyManager(
            registry,
            _engine_factory,
            capacity=max(args.resident_models, 1),
            evict_drain_s=min(args.drain_timeout_s, 10.0),
            pinned={registry.default_model},
        )
        # the default engine is adopted, not factory-loaded: the server
        # lifecycle warms and starts it (listener-first banner intact)
        residency.adopt(registry.default_model, engine)
    print(f"serving batching={engine.batching} "
          f"precision={engine.overlay.label}"
          + (
              f" models={','.join(registry.names())} "
              f"default={registry.default_model}"
              if registry is not None else ""
          ),
          flush=True)
    watcher = None
    if args.watch is not None:
        from .serving.live import CheckpointWatcher

        def _swap(stamp: int, state: dict, _engine=engine) -> None:
            _engine.swap_params(state["params"], stamp, source="watch")

        watcher = CheckpointWatcher(
            args.watch, _swap, interval_s=args.watch_interval_s
        )
    # diagnosis layer: AlertEngine always rides along with telemetry
    # (alert state is a handful of floats); the FlightRecorder only when
    # an incidents dir / black box is configured. With --no-telemetry
    # NEITHER is constructed — zero rule evaluations, zero ring writes,
    # zero incident I/O (guard-tested).
    alerts = None
    recorder = None
    if tel is not None:
        from .alerting import AlertEngine, default_serving_rules
        from .incidents import FlightRecorder

        if args.incidents_dir is not None or args.blackbox is not None:
            recorder = FlightRecorder(
                incident_dir=args.incidents_dir,
                blackbox_path=args.blackbox,
                process_name=f"replica-pid{os.getpid()}",
            )
        alerts = AlertEngine(
            default_serving_rules(p99_target_s=args.alert_p99_ms / 1e3),
            sink_path=(
                args.incidents_dir / "alerts.jsonl"
                if args.incidents_dir is not None else None
            ),
            on_firing=(
                recorder.alert_hook() if recorder is not None else None
            ),
            source="replica",
        )
        if recorder is not None:
            recorder.attach(
                trace=tel.trace,
                alerts_fn=alerts.states,
                exemplars_fn=tel.exemplars,
            )
    server = Server(
        engine, args.host, args.port,
        telemetry=tel, drain_timeout_s=args.drain_timeout_s,
        watcher=watcher, swap_dirs=[str(d) for d in args.swap_dirs],
        alerts=alerts, recorder=recorder,
        observe_interval_s=args.observe_interval_s,
        registry=registry, residency=residency, admission=admission,
    )
    # listener-first: the banner (and thus the bound port) appears before
    # the warmup sweep, so a fleet supervisor can probe /healthz — which
    # reports 503 "warming" until every bucket program is compiled
    rc = server.run(warmup_engine=not args.no_warmup)
    if tel is not None and args.metrics_dir is not None:
        import json
        import time as _time

        args.metrics_dir.mkdir(parents=True, exist_ok=True)
        tel.trace.flush(args.metrics_dir / "serving_trace.json")
        from .training.telemetry import sanitize_json

        snap = tel.snapshot()
        snap["generation"] = engine.serving_generation
        snap["swap_count"] = engine.swap_count
        (args.metrics_dir / "serving_metrics.json").write_text(
            json.dumps(sanitize_json(snap), indent=2) + "\n",
            encoding="utf8",
        )
        # the same snapshot as a `kind: "serving"` row in metrics.jsonl,
        # so `telemetry summarize` digests serving runs with the exact
        # file contract training runs use
        with open(
            args.metrics_dir / "metrics.jsonl", "a", encoding="utf8"
        ) as f:
            f.write(json.dumps(sanitize_json(
                {"kind": "serving", "unix_time": _time.time(), **snap}
            )) + "\n")
        print(f"serving telemetry written to {args.metrics_dir}", flush=True)
    _print_runtime(engine.nlp, precision=engine.overlay.label)
    if rc == 0:
        print("drained; exiting 0", flush=True)
    else:
        # the failure path must not carry the success word: in-flight
        # work was abandoned at the drain timeout
        print(f"drain timed out after {args.drain_timeout_s:.0f}s — "
              f"in-flight work abandoned; exiting {rc}", flush=True)
    return rc


def serve_fleet_command(argv: List[str]) -> int:
    """``serve-fleet`` — horizontally-scaled serving (docs/SERVING.md
    "Fleet"): a router process load-balancing ``/v1/parse`` over N
    ``serve`` replica subprocesses with health-probed rotation, crash
    restarts with backoff, optional SLO-driven autoscaling, and a
    fleet-wide SIGTERM drain (router stops admitting, replicas finish
    in-flight work, exit 0).

    This process never imports jax — it only spawns, probes, and proxies;
    every device interaction lives in the replicas."""
    parser = argparse.ArgumentParser(
        prog="spacy_ray_tpu serve-fleet",
        description="Serve a saved pipeline from N engine replicas behind "
        "one load-balancing router (/v1/parse, /healthz, /metrics).",
    )
    parser.add_argument("model_path", type=Path)
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--port", type=int, default=8090,
                        help="router port (0 = ephemeral; printed in the "
                        "'fleet serving on http://...' banner)")
    parser.add_argument("--device", type=str, default="tpu",
                        choices=["tpu", "cpu", "gpu"],
                        help="device each replica pins (replicas are "
                        "separate processes; see --visible-devices)")
    parser.add_argument("--replicas", type=int, default=2,
                        help="initial replica count")
    parser.add_argument("--min-replicas", type=int, default=1)
    parser.add_argument("--max-replicas", type=int, default=4)
    parser.add_argument("--base-port", type=int, default=0,
                        help="replica ports: 0 = ephemeral (parsed from "
                        "each replica's banner), N = N + replica_id")
    parser.add_argument("--visible-devices", type=str, default=None,
                        help="comma-separated visible-device masks cycled "
                        "per replica (sets CUDA_VISIBLE_DEVICES or "
                        "--visible-devices-env in each replica's env)")
    parser.add_argument("--visible-devices-env", type=str,
                        default="CUDA_VISIBLE_DEVICES")
    parser.add_argument("--cpu-cores", type=str, default=None,
                        help="--device cpu only: 'auto' or comma-separated "
                        "taskset -c core masks cycled per replica (e.g. "
                        "'0-3,4-7' gives replica 0 cores 0-3). The CPU "
                        "value of --visible-devices: without masks, "
                        "co-scheduled replicas each spawn an nproc-wide "
                        "XLA pool and thrash (measured NEGATIVE scaling); "
                        "'auto' resolves to one core per replica, "
                        "round-robin over this process's affinity set")
    # per-replica serving knobs, passed through to each `serve` child
    parser.add_argument("--max-batch", type=int, default=None)
    parser.add_argument("--max-wait-ms", "--window-ms", type=float,
                        dest="max_wait_ms", default=None)
    parser.add_argument("--queue-size", type=int, default=None)
    parser.add_argument("--timeout-ms", type=float, default=None)
    parser.add_argument("--max-doc-len", type=int, default=None)
    parser.add_argument("--batching",
                        choices=["continuous", "window"], default=None,
                        help="replica admission discipline (None = the "
                        "serve default, continuous)")
    parser.add_argument("--continuous", action="store_const",
                        const="continuous", dest="batching",
                        help="alias for --batching continuous")
    parser.add_argument("--precision",
                        choices=["auto", "f32", "bf16", "int8"], default=None,
                        help="replica serving precision overlay (None = "
                        "the serve default, auto — bf16 on accelerators, "
                        "f32 on CPU)")
    parser.add_argument("--model-manifest", type=Path, default=None,
                        help="multi-model fleet (docs/SERVING.md "
                        "'Multi-model fleet'): every replica serves the "
                        "models in this JSON manifest (name -> pipeline "
                        "dir, SLO classes, tenant quotas); the router "
                        "resolves /v1/models/<name>/parse and X-SRT-Model "
                        "and routes within the replicas hosting the model. "
                        "The positional model_path is ignored by replicas "
                        "— the manifest is authoritative")
    parser.add_argument("--resident-models", type=int, default=None,
                        help="multi-model only: per-replica warmed-engine "
                        "hot-set size (LRU eviction past it; the default "
                        "model is pinned)")
    # router knobs
    parser.add_argument("--cache-mb", type=float, default=32.0,
                        help="router response cache budget in MB, keyed by "
                        "input-text hash and stamped with the serving "
                        "generation (default ON at 32MB — heavy real "
                        "traffic is Zipfian; 0 = off); hit/miss/stale/"
                        "bypass counters in /metrics")
    parser.add_argument("--probe-interval-s", type=float, default=0.5,
                        help="how often the router re-probes each "
                        "replica's /healthz")
    parser.add_argument("--length-routing", action="store_true",
                        help="length-bucket affinity routing: steer "
                        "similar doc lengths to the same replica (within "
                        "the least-outstanding/model-hosting candidates) "
                        "so device batches fill their bucket instead of "
                        "padding to the longest straggler; pays on skewed "
                        "length mixtures with >1 replica (TUNING.md §24); "
                        "pad share lands in /metrics as "
                        "srt_serving_pad_tokens_total / "
                        "srt_serving_real_tokens_total")
    # live continuous learning (docs/SERVING.md "Continuous learning",
    # TUNING.md §14)
    parser.add_argument("--watch", type=Path, default=None,
                        metavar="CKPT_DIR",
                        help="poll this TrainCheckpoint directory (a "
                        "training run's <output>/last-model); each new "
                        "digest-verified generation is canaried onto "
                        "--canary-fraction of the replicas (router splits "
                        "traffic by generation), then promoted fleet-wide "
                        "or auto-rolled-back by the guard")
    parser.add_argument("--watch-interval-s", type=float, default=2.0)
    parser.add_argument("--canary-fraction", type=float, default=0.25,
                        help="fraction of replicas (and of traffic) a new "
                        "generation canaries on before promote/rollback; "
                        "<=0 or >=1 disables the canary phase (direct "
                        "rollout to every replica)")
    parser.add_argument("--guard-p99-frac", type=float, default=1.5,
                        help="rollback when canary window p99 exceeds this "
                        "multiple of the baseline's")
    parser.add_argument("--guard-error-rate", type=float, default=0.02,
                        help="rollback when the canary's error rate "
                        "exceeds this (and the baseline's)")
    parser.add_argument("--guard-min-samples", type=int, default=20,
                        help="minimum canary requests / window samples "
                        "before any verdict")
    parser.add_argument("--guard-verdict-timeout-s", type=float,
                        default=120.0,
                        help="a canary with no verdict after this long is "
                        "rolled back (ship on evidence, not silence)")
    # autoscaler knobs (TUNING.md §12)
    parser.add_argument("--autoscale", action="store_true",
                        help="enable the SLO-driven autoscaler (scale "
                        "between --min/--max-replicas on p99 vs "
                        "--p99-target-ms and queue pressure)")
    parser.add_argument("--p99-target-ms", type=float, default=500.0)
    parser.add_argument("--autoscale-interval-s", type=float, default=2.0)
    parser.add_argument("--up-consecutive", type=int, default=3,
                        help="breaching observations required to scale up")
    parser.add_argument("--down-consecutive", type=int, default=10,
                        help="idle observations required to scale down")
    parser.add_argument("--cooldown-s", type=float, default=30.0,
                        help="minimum seconds between scaling decisions")
    parser.add_argument("--drain-timeout-s", type=float, default=60.0,
                        help="fleet drain budget: router in-flight wait + "
                        "per-replica graceful stop")
    parser.add_argument("--ready-timeout-s", type=float, default=300.0)
    parser.add_argument("--incidents-dir", type=Path, default=None,
                        help="arm the fleet-wide flight recorder "
                        "(docs/OBSERVABILITY.md 'Alerting & incidents'): "
                        "alert firings dump router/replica flight bundles "
                        "here, every replica persists a SIGKILL-survivable "
                        "black box under <dir>/blackbox/, and a crashed "
                        "replica leaves a crash postmortem bundle (exit "
                        "signal, stderr tail, config, generation, pre-crash "
                        "span ring) readable via `telemetry postmortem`")
    parser.add_argument("--observe-interval-s", type=float, default=2.0,
                        help="cadence of the diagnosis tick (alert rule "
                        "evaluation + flight-recorder ring feed)")
    parser.add_argument("--no-telemetry", action="store_true",
                        help="disable router + replica telemetry (zero "
                        "telemetry calls fleet-wide)")
    parser.add_argument("--verbose", "-V", action="store_true")
    args = parser.parse_args(argv)

    logging.basicConfig(level=logging.DEBUG if args.verbose else logging.ERROR)
    for name in ("spacy_ray_tpu.training", "spacy_ray_tpu.serving"):
        logging.getLogger(name).setLevel(
            logging.INFO if args.verbose else logging.WARNING
        )
    if args.min_replicas < 1 or args.replicas < 1:
        print("--replicas/--min-replicas must be >= 1", file=sys.stderr)
        return 2
    if not (args.min_replicas <= args.replicas <= args.max_replicas):
        print(
            f"--replicas {args.replicas} must lie within --min-replicas "
            f"{args.min_replicas} .. --max-replicas {args.max_replicas}",
            file=sys.stderr,
        )
        return 2

    from .devices import refuse_shared_chip
    from .serving.fleet import Fleet, FleetConfig

    visible_devices = (
        [m.strip() for m in args.visible_devices.split(",") if m.strip()]
        if args.visible_devices else None
    )
    refuse_shared_chip(
        args.device,
        args.max_replicas if args.autoscale else args.replicas,
        "serve-fleet --replicas",
        # the default mask variable is CUDA's: it keeps no two TPU
        # processes apart
        n_masks=(
            len(set(visible_devices or ()))
            if args.visible_devices_env != "CUDA_VISIBLE_DEVICES" else 0
        ),
    )
    cpu_cores: Optional[List[str]] = None
    if args.cpu_cores:
        if args.device != "cpu":
            print("--cpu-cores only applies to --device cpu; ignoring",
                  file=sys.stderr)
        elif args.cpu_cores.strip().lower() == "auto":
            cpu_cores = [str(c) for c in sorted(os.sched_getaffinity(0))]
        else:
            cpu_cores = [m.strip() for m in args.cpu_cores.split(",")
                         if m.strip()]

    config = FleetConfig(
        model_path=str(args.model_path),
        host=args.host,
        port=args.port,
        device=args.device,
        replicas=args.replicas,
        min_replicas=args.min_replicas,
        max_replicas=args.max_replicas,
        max_batch=args.max_batch,
        max_wait_ms=args.max_wait_ms,
        queue_size=args.queue_size,
        timeout_ms=args.timeout_ms,
        max_doc_len=args.max_doc_len,
        batching=args.batching,
        precision=args.precision,
        model_manifest=(
            str(args.model_manifest)
            if args.model_manifest is not None else None
        ),
        resident_models=args.resident_models,
        base_port=args.base_port,
        visible_devices=visible_devices,
        visible_devices_env=args.visible_devices_env,
        cpu_cores=cpu_cores,
        cache_mb=args.cache_mb,
        probe_interval_s=args.probe_interval_s,
        length_routing=args.length_routing,
        watch_dir=str(args.watch) if args.watch is not None else None,
        watch_interval_s=args.watch_interval_s,
        canary_fraction=args.canary_fraction,
        guard_p99_frac=args.guard_p99_frac,
        guard_error_rate=args.guard_error_rate,
        guard_min_samples=args.guard_min_samples,
        guard_verdict_timeout_s=args.guard_verdict_timeout_s,
        autoscale=args.autoscale,
        p99_target_ms=args.p99_target_ms,
        autoscale_interval_s=args.autoscale_interval_s,
        up_consecutive=args.up_consecutive,
        down_consecutive=args.down_consecutive,
        cooldown_s=args.cooldown_s,
        drain_timeout_s=args.drain_timeout_s,
        ready_timeout_s=args.ready_timeout_s,
        incidents_dir=(
            str(args.incidents_dir)
            if args.incidents_dir is not None else None
        ),
        observe_interval_s=args.observe_interval_s,
        telemetry=not args.no_telemetry,
    )
    rc = Fleet(config).run()
    if rc == 0:
        print("fleet drained; exiting 0", flush=True)
    else:
        print("fleet drain incomplete (router timeout or nonzero replica "
              f"exit) — exiting {rc}", flush=True)
    return rc


def train_and_serve_command(argv: List[str]) -> int:
    """``train-and-serve`` — the continuous-learning loop as one command
    (docs/SERVING.md "Continuous learning"): spawn a ``train`` subprocess
    writing checkpoint generations into ``<output>/last-model``, and a
    serving fleet that watches that directory and hot-swaps each new
    digest-verified generation (canary + guard when replicas > 1)
    without dropping a request. SIGTERM drains BOTH: the trainer
    checkpoints out (exit 75 = preempted-clean), the fleet finishes
    in-flight work — exit 0 iff both were clean."""
    parser = argparse.ArgumentParser(
        prog="spacy_ray_tpu train-and-serve",
        description="Run training and a hot-swapping serving fleet "
        "against one checkpoint directory, under one lifecycle.",
    )
    parser.add_argument("config_path", type=Path)
    parser.add_argument("--output", "-o", type=Path, required=True,
                        help="training output dir; the fleet watches "
                        "<output>/last-model for generations")
    parser.add_argument("--model", type=Path, default=None,
                        help="serve this model dir from t=0 (e.g. the "
                        "previous run's best-model). Default: wait for "
                        "this run's first best-model save and bootstrap "
                        "from a snapshot of it")
    parser.add_argument("--bootstrap-timeout-s", type=float, default=600.0,
                        help="--model unset: how long to wait for the "
                        "first best-model save before giving up")
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--port", type=int, default=8090)
    parser.add_argument("--device", type=str, default="tpu",
                        choices=["tpu", "cpu", "gpu"],
                        help="device for the trainer AND each serving "
                        "replica (separate processes; on one-device "
                        "hosts run --device cpu serving next to an "
                        "accelerator trainer via --serve-device)")
    parser.add_argument("--serve-device", type=str, default=None,
                        choices=["tpu", "cpu", "gpu"],
                        help="override the replicas' device (default: "
                        "--device)")
    parser.add_argument("--replicas", type=int, default=1)
    parser.add_argument("--base-port", type=int, default=0)
    parser.add_argument("--cpu-cores", type=str, default=None,
                        help="serve-fleet's --cpu-cores, applied to the "
                        "replicas ('auto' = one core per replica)")
    parser.add_argument("--max-batch", type=int, default=None)
    parser.add_argument("--max-doc-len", type=int, default=None)
    parser.add_argument("--batching",
                        choices=["continuous", "window"], default=None)
    parser.add_argument("--precision",
                        choices=["auto", "f32", "bf16", "int8"], default=None)
    parser.add_argument("--watch-interval-s", type=float, default=2.0)
    parser.add_argument("--canary-fraction", type=float, default=0.25)
    parser.add_argument("--guard-p99-frac", type=float, default=1.5)
    parser.add_argument("--guard-error-rate", type=float, default=0.02)
    parser.add_argument("--guard-min-samples", type=int, default=20)
    parser.add_argument("--guard-verdict-timeout-s", type=float,
                        default=120.0)
    parser.add_argument("--drain-timeout-s", type=float, default=60.0)
    parser.add_argument("--no-telemetry", action="store_true")
    parser.add_argument("--train-arg", action="append", default=[],
                        dest="train_args", metavar="ARG",
                        help="extra argument appended to the train "
                        "subprocess command (repeatable), e.g. "
                        "--train-arg=--max-restarts --train-arg=2")
    parser.add_argument("--verbose", "-V", action="store_true")
    args = parser.parse_args(argv)

    logging.basicConfig(level=logging.DEBUG if args.verbose else logging.ERROR)
    for name in ("spacy_ray_tpu.training", "spacy_ray_tpu.serving"):
        logging.getLogger(name).setLevel(
            logging.INFO if args.verbose else logging.WARNING
        )
    serve_device = args.serve_device or args.device

    from .devices import refuse_shared_chip
    from .serving.fleet import FleetConfig
    from .serving.live import TrainAndServe

    refuse_shared_chip(
        "tpu",
        (args.device == "tpu") + args.replicas * (serve_device == "tpu"),
        "train-and-serve (one trainer + --replicas)",
    )

    cpu_cores: Optional[List[str]] = None
    if args.cpu_cores and serve_device == "cpu":
        if args.cpu_cores.strip().lower() == "auto":
            cpu_cores = [str(c) for c in sorted(os.sched_getaffinity(0))]
        else:
            cpu_cores = [m.strip() for m in args.cpu_cores.split(",")
                         if m.strip()]

    output = args.output
    train_cmd = [
        sys.executable, "-m", "spacy_ray_tpu", "train",
        str(args.config_path), "--output", str(output),
        "--device", args.device,
    ] + list(args.train_args)
    train_env = {"JAX_PLATFORMS": "cpu"} if args.device == "cpu" else None

    config = FleetConfig(
        model_path=str(args.model) if args.model is not None else "",
        host=args.host,
        port=args.port,
        device=serve_device,
        replicas=args.replicas,
        min_replicas=1,
        max_replicas=max(args.replicas, 1),
        max_batch=args.max_batch,
        max_doc_len=args.max_doc_len,
        batching=args.batching,
        precision=args.precision,
        base_port=args.base_port,
        cpu_cores=cpu_cores,
        watch_dir=str(output / "last-model"),
        watch_interval_s=args.watch_interval_s,
        canary_fraction=args.canary_fraction,
        guard_p99_frac=args.guard_p99_frac,
        guard_error_rate=args.guard_error_rate,
        guard_min_samples=args.guard_min_samples,
        guard_verdict_timeout_s=args.guard_verdict_timeout_s,
        drain_timeout_s=args.drain_timeout_s,
        telemetry=not args.no_telemetry,
    )
    rc = TrainAndServe(
        train_cmd,
        config,
        output_dir=output,
        train_env=train_env,
        bootstrap_timeout_s=args.bootstrap_timeout_s,
    ).run()
    if rc == 0:
        print("train-and-serve: exiting 0", flush=True)
    else:
        print(f"train-and-serve: incomplete drain or trainer failure — "
              f"exiting {rc}", flush=True)
    return rc


def _project_command(argv: List[str]) -> int:
    """spaCy-projects-style workflow runner (`project run` / `project
    document`); implementation in project.py."""
    from .project import main as project_main

    return project_main(argv)


COMMANDS = {
    "train": train_command,
    "pretrain": pretrain_command,
    "parse": parse_command,
    # spaCy's name for bulk annotation; same command, correctly-named help
    "apply": lambda argv: parse_command(argv, prog="apply"),
    "debug-profile": debug_profile_command,
    "serve": serve_command,
    "serve-fleet": serve_fleet_command,
    "train-and-serve": train_and_serve_command,
    "telemetry": telemetry_command,
    "find-threshold": find_threshold_command,
    "info": info_command,
    "debug-model": debug_model_command,
    "fill-config": fill_config_command,
    "evaluate": evaluate_command,
    "benchmark": benchmark_command,
    "convert": convert_command,
    "init-config": init_config_command,
    "init-labels": init_labels_command,
    "init-vectors": init_vectors_command,
    "assemble": assemble_command,
    "debug-data": debug_data_command,
    "debug-config": debug_config_command,
    "debug-diff-config": debug_diff_command,
    "project": _project_command,
    "package": package_command,
}


def main(argv: Optional[List[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        print(f"Usage: python -m spacy_ray_tpu {{{','.join(COMMANDS)}}} ...")
        return 0
    command = argv[0]
    if command not in COMMANDS:
        print(f"Unknown command {command!r}. Available: {', '.join(COMMANDS)}", file=sys.stderr)
        return 1
    _load_plugins()
    return COMMANDS[command](argv[1:])


if __name__ == "__main__":
    raise SystemExit(main())
