#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

``python chip_smoke.py`` (one TPU chip) drives the main path once, through
the entry points a user calls, at the full width of ``configs/trf.cfg``
(RoBERTa-base: 768 wide, 12 layers, 12 heads, tagger + parser + NER), on
data made from ``--seed``:

    info --probe -> train trf -> train sm -> evaluate -> serve

Each phase is its own ``python -m spacy_ray_tpu ...`` child, run strictly
one after the other: a chip belongs to one process at a time, so THIS
process never imports JAX. Every line on stdout is one JSON object; the
last one is ``{"ok": ..., "device": {"platform", "kind", "count"}}`` with
the device as the working phases reported it. Any phase failing, a device
that is not a TPU, or a main-path kernel that is off without the run
saying so by name and reason makes the script exit non-zero.

``python chip_smoke.py --chips 4`` (one four-chip host) runs ONLY the path
across chips and what it is compared with, in one process: the same trf
config for the same few steps on a 4-device data mesh (``train
--n-workers 4``, ``update_sharding = "full"``) and on one device, same
seed and batches, losses compared step by step.

``--rehearse-cpu`` is the rehearsal without a chip (on-chip-measurement
guide section 2): the same phases at a tiny width with ``--device cpu``.
Its last line names the cpu, so it can never be read as a chip run.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import signal
import subprocess
import sys
import tempfile
import time
import urllib.request
from pathlib import Path
from typing import Any, Dict, List, Optional

ROOT = Path(__file__).resolve().parent
PHASE_TIMEOUT_S = 900.0

# max_steps = 2 windows of K optimizer steps (trf: each 3 accumulated
# microbatches of ~2000 words); the loss must fall from the first window's
# sum to the last's — a window is steady under dropout where one step is
# not. trf at lr 1e-3 with no warm-up first climbs: on the v5e its 6-step
# windows ran 115, 64, 25, 11 (3-step windows: 43, then 72)
TRF_WINDOW, SM_WINDOW = 6, 10
# --chips 4: optimizer steps compared, and the stated tolerance on each
# step's loss between the 4-device and the 1-device run (same seed, same
# batches; the gradient all-reduce and the sharded update sum in another
# order than one device does, and the trunk computes in bfloat16). On the
# four v5e chips the steps differed by 2e-7, 1.8e-4, 2.4e-4 and 5.6e-5
MESH_STEPS = 4
MESH_LOSS_RTOL = 5e-3

# --rehearse-cpu: every width cut, nothing else changed
TINY_TRF = {
    "components.transformer.model.width": 64,
    "components.transformer.model.depth": 2,
    "components.transformer.model.n_heads": 4,
    "components.transformer.model.embed_size": 500,
    "components.tagger.model.tok2vec.width": 64,
    "components.parser.model.tok2vec.width": 64,
    "components.ner.model.tok2vec.width": 64,
}


# children run with deprecation warnings shown, and the run lists what the
# main path raised on this installation (it should be nothing)
SHOW_DEPRECATIONS = ["-W", "default::DeprecationWarning"]
DEPRECATIONS: List[str] = []


def deprecations(stderr: str) -> List[str]:
    return [l.strip()[:300] for l in stderr.splitlines() if "DeprecationWarning" in l]


def emit(**obj: Any) -> None:
    print(json.dumps(obj, default=str), flush=True)


class PhaseFailed(Exception):
    pass


def cache_dir() -> Path:
    """Where the children keep their compile cache — the rule of
    spacy_ray_tpu/devices.enable_compile_cache, restated because this
    process must not import the package."""
    return Path(os.environ.get("JAX_COMPILATION_CACHE_DIR") or ROOT / ".xla_cache")


def cache_entries() -> int:
    d = cache_dir()
    if not d.is_dir():
        return 0
    return sum(1 for p in d.iterdir() if not p.name.endswith("-atime"))


def run_child(
    phase: str, argv: List[str], timeout: float = PHASE_TIMEOUT_S
) -> Dict[str, Any]:
    """Run one phase as a child to its end; raise PhaseFailed with the tail
    of its output unless it exits 0."""
    t0 = time.monotonic()
    before = cache_entries()
    try:
        p = subprocess.run(
            [sys.executable] + SHOW_DEPRECATIONS + argv, cwd=ROOT,
            capture_output=True, text=True, timeout=timeout,
        )
        rc, out, err = p.returncode, p.stdout, p.stderr
    except subprocess.TimeoutExpired as e:
        rc, out, err = 124, str(e.stdout or ""), f"timed out after {timeout:.0f}s"
    info = {
        "phase": phase, "rc": rc, "wall_s": round(time.monotonic() - t0, 2),
        "cache_entries_before": before, "cache_entries_after": cache_entries(),
        "stdout": out, "stderr": err,
    }
    DEPRECATIONS.extend(deprecations(err))
    if rc != 0:
        raise PhaseFailed(
            f"{phase}: child exited {rc}: " + (err or out).strip()[-1500:]
        )
    return info


def runtime_line(stdout: str) -> Dict[str, Any]:
    """The ``runtime {...}`` line train/evaluate/serve print: the device the
    process ran on and what each switch resolved to there."""
    for line in reversed(stdout.splitlines()):
        if line.startswith("runtime {"):
            return json.loads(line[len("runtime "):])
    raise PhaseFailed("child printed no runtime line")


def require(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


def require_active(
    runtime: Dict[str, Any], key: str, rehearsal: bool,
    how: str = "active (pallas)",
) -> None:
    """A main-path kernel must be ``active`` in compiled mode on the chip.
    The status is the path(s) the child's programs took, so a kernel that
    gave way to XLA on some shape fails here with its reason. (On the CPU
    rehearsal it must be ``off`` — with its reason.)"""
    status = str(runtime.get(key, ""))
    if rehearsal:
        require(status.startswith(("off (", "active (xla")), f"{key}: {status!r}")
    else:
        require(
            status == how,
            f"{key} is not {how!r} in compiled mode on the chip: {status!r}",
        )


def trunk_dtype(rehearsal: bool) -> str:
    """What trf.cfg's ``compute_dtype = "auto"`` must resolve to."""
    return "float32" if rehearsal else "bfloat16"


def overrides(extra: Dict[str, Any]) -> List[str]:
    out: List[str] = []
    for k, v in extra.items():
        out += [f"--{k}", str(v)]
    return out


# ----------------------------------------------------------------------
# phases (one chip)
# ----------------------------------------------------------------------


def phase_probe(device: str, rehearsal: bool) -> Dict[str, str]:
    """The device check, before any training: ``info --probe`` initialises
    the backend in ITS process, compiles the four kernels and compares each
    with its reference. Returns its rows; raises where there is no chip."""
    info = run_child("probe", ["-m", "spacy_ray_tpu", "info", "--probe"])
    rows = dict(
        re.split(r"\s+", line.strip(), maxsplit=1)
        for line in info["stdout"].splitlines() if re.match(r"^\w+\s+\S", line)
    )
    m = re.match(r"reachable: (\w+) x(\d+) \((.*)\)", rows.get("accelerator", ""))
    require(m is not None, f"no device: {rows.get('accelerator')!r}")
    emit(phase="probe", wall_s=info["wall_s"],
         device={"platform": m.group(1), "kind": m.group(3), "count": int(m.group(2))},
         switches={k: rows.get(k) for k in (
             "compute_dtype", "update_sharding", "flash_attention",
             "hash_embed", "fused_kernel", "precision", "native_hash",
             "compile_cache")})
    require(
        m.group(1) == device,
        f"JAX found no {device}: the platform is {m.group(1)!r} "
        f"({m.group(3)}). Nothing was run.",
    )
    return rows


def check_probed_kernels(rows: Dict[str, str], rehearsal: bool) -> None:
    """All four kernels, int8 included (it is off the main path: serving
    resolves bf16 under ``auto``), compiled and compared on the chip."""
    for key in ("flash_attention", "hash_embed", "fused_kernel", "precision"):
        require("FAILED" not in rows.get(key, "FAILED"), f"{key}: {rows.get(key)}")
    if not rehearsal:
        for key in ("flash_attention", "hash_embed", "fused_kernel"):
            require(rows[key] == "active (pallas)", f"{key}: {rows[key]}")
        require("int8 kernel active (pallas)" in rows["precision"], rows["precision"])


def phase_data(work: Path, seed: int, n_dev: int = 128) -> None:
    """Corpora from the seed (the chip machine has no network), written by a
    child: parser docs carry tags + heads/deps, ner docs carry entities."""
    code = (
        "import sys; from pathlib import Path\n"
        "from spacy_ray_tpu.util import write_synth_jsonl as w\n"
        "work, seed, n_dev = Path(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])\n"
        "for split, n, s in (('train', 3000, seed), ('dev', n_dev, seed + 100)):\n"
        "    (work / split).mkdir()\n"
        "    w(work / split / 'parser.jsonl', n, kind='parser', seed=s)\n"
        "    w(work / split / 'ner.jsonl', n, kind='ner', seed=s + 1)\n"
    )
    info = run_child("data", ["-c", code, str(work), str(seed), str(n_dev)])
    emit(phase="data", wall_s=info["wall_s"], seed=seed,
         train_docs=6000, dev_docs=2 * n_dev)


def read_metrics(metrics_dir: Path) -> Dict[str, List[Dict[str, Any]]]:
    rows: Dict[str, List[Dict[str, Any]]] = {"step": [], "eval": [], "anomaly": []}
    for line in (metrics_dir / "metrics.jsonl").read_text().splitlines():
        row = json.loads(line)
        rows.setdefault(row.get("kind"), []).append(row)
    return rows


def phase_train(
    name: str, cfg: str, window: int, work: Path, device: str,
    rehearsal: bool, extra: Dict[str, Any], kernels: List[str],
    compute_dtype: str,
) -> Dict[str, Any]:
    """``train`` for 2*window optimizer steps with an evaluation and a
    checkpoint at each window's end; every loss finite, the last window's
    below the first's."""
    out = work / name
    steps = 2 * window
    info = run_child(name, [
        "-m", "spacy_ray_tpu", "train", cfg, "--device", device,
        "--output", str(out), "--metrics-dir", str(out / "metrics"),
        "--paths.train", str(work / "train"), "--paths.dev", str(work / "dev"),
        "--training.max_steps", str(steps),
        "--training.eval_frequency", str(window),
    ] + overrides(extra))
    runtime = runtime_line(info["stdout"])
    rows = read_metrics(out / "metrics")
    losses = [r["loss_total"] for r in rows["eval"]]
    step_s = [r["step_seconds"] for r in rows["step"]]
    done = re.search(r"Done\. steps=(\d+) best_score=([-\d.]+)", info["stdout"])
    emit(
        phase=name, config=cfg, wall_s=info["wall_s"],
        steps=int(done.group(1)) if done else None,
        loss_first_window=losses[0] if losses else None,
        loss_last_window=losses[-1] if losses else None,
        losses_by_component=[r["losses"] for r in rows["eval"]],
        scores=[r["score"] for r in rows["eval"]],
        first_step_s=step_s[0] if step_s else None,
        steady_step_s=sorted(step_s[1:])[len(step_s[1:]) // 2] if len(step_s) > 1 else None,
        compile_count=rows["eval"][-1]["compile_count"] if rows["eval"] else None,
        hbm_peak_bytes=rows["eval"][-1]["hbm_peak_bytes"] if rows["eval"] else None,
        cache_entries=[info["cache_entries_before"], info["cache_entries_after"]],
        runtime=runtime,
    )
    require(done is not None and int(done.group(1)) == steps,
            f"{name}: expected {steps} steps: {info['stdout'][-300:]!r}")
    require(len(losses) == 2, f"{name}: expected 2 evaluations, got {len(losses)}")
    require(all(l is not None and math.isfinite(l) for l in losses),
            f"{name}: non-finite loss {losses}")
    require(not rows["anomaly"], f"{name}: anomalies {rows['anomaly']}")
    require(losses[-1] < losses[0], f"{name}: loss did not fall: {losses}")
    require((out / "best-model" / "meta.json").exists(), f"{name}: no best-model")
    require(any((out / "last-model").iterdir()), f"{name}: no checkpoint")
    require(runtime["device"]["platform"] == device,
            f"{name} ran on {runtime['device']}")
    for key in kernels:
        require_active(runtime, key, rehearsal)
    require(runtime["compute_dtype"] == compute_dtype,
            f"{name}: compute_dtype {runtime['compute_dtype']!r}")
    return runtime


def phase_evaluate(work: Path, device: str, rehearsal: bool) -> Dict[str, Any]:
    scores_path = work / "scores.json"
    info = run_child("evaluate", [
        "-m", "spacy_ray_tpu", "evaluate", str(work / "trf" / "best-model"),
        str(work / "dev"), "--device", device, "--output", str(scores_path),
    ])
    runtime = runtime_line(info["stdout"])
    scores = json.loads(scores_path.read_text())
    flat = {k: v for k, v in scores.items() if isinstance(v, (int, float))}
    emit(phase="evaluate", wall_s=info["wall_s"], scores=flat,
         cache_entries=[info["cache_entries_before"], info["cache_entries_after"]],
         runtime=runtime)
    require({"tag_acc", "dep_las", "ents_f"} <= set(flat), f"scores: {sorted(flat)}")
    require(all(math.isfinite(v) and 0.0 <= v <= 1.0 for v in flat.values()),
            f"scores out of range: {flat}")
    require(runtime["device"]["platform"] == device, str(runtime["device"]))
    require_active(runtime, "flash_attention", rehearsal)
    require(runtime["compute_dtype"] == trunk_dtype(rehearsal),
            f"evaluate: compute_dtype {runtime['compute_dtype']!r}")
    return runtime


def http_json(url: str, body: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    data = json.dumps(body).encode("utf8") if body is not None else None
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"}
    )
    with urllib.request.urlopen(req, timeout=120) as resp:
        require(resp.status == 200, f"{url}: HTTP {resp.status}")
        return json.loads(resp.read())


def phase_serve(work: Path, device: str, rehearsal: bool) -> Dict[str, Any]:
    """``serve`` the trained trf model: banner, a few /v1/parse requests of
    different lengths, /healthz, /metrics, SIGTERM, a clean drain."""
    t0 = time.monotonic()
    before = cache_entries()
    log = open(work / "serve.log", "w+", encoding="utf8")
    p = subprocess.Popen(
        [sys.executable] + SHOW_DEPRECATIONS + ["-m", "spacy_ray_tpu", "serve",
         str(work / "trf" / "best-model"), "--device", device, "--port", "0",
         "--max-batch", "4", "--max-doc-len", "128"],
        cwd=ROOT, stdout=log, stderr=subprocess.STDOUT, text=True,
    )

    def logged() -> str:
        log.flush()
        return (work / "serve.log").read_text(encoding="utf8")

    try:
        url = warmed = None
        while time.monotonic() - t0 < PHASE_TIMEOUT_S and p.poll() is None:
            text = logged()
            m = re.search(r"serving on (http://\S+)", text)
            w = re.search(r"warmed (\d+) \(B, T\) bucket", text)
            if m and w:
                url, warmed = m.group(1), int(w.group(1))
                break
            time.sleep(0.5)
        require(url is not None, "serve: no banner: " + logged()[-1500:])
        warmup_s = round(time.monotonic() - t0, 2)
        health = http_json(url + "/healthz")
        words = "the green cat quickly sees a tiny tensor in Tokyo with Alice Smith .".split()
        answered = []
        for n_words in (4, 30, 100):
            text = " ".join(words[i % len(words)] for i in range(n_words))
            t1 = time.monotonic()
            reply = http_json(url + "/v1/parse", {"texts": [text, "Bob Jones runs ."]})
            docs = reply["docs"]
            require(len(docs) == 2, f"serve: {len(docs)} docs for 2 texts")
            require(len(docs[0]["tokens"]) == n_words,
                    f"serve: {len(docs[0]['tokens'])} tokens for {n_words} words")
            answered.append({"words": n_words, "B": reply["batch"].get("B"),
                             "T": reply["batch"].get("T"),
                             "latency_s": round(time.monotonic() - t1, 4)})
        metrics = http_json(url + "/metrics")
        p.send_signal(signal.SIGTERM)
        rc = p.wait(timeout=120)
        text = logged()
        DEPRECATIONS.extend(deprecations(text))
        runtime = runtime_line(text)
        emit(phase="serve", wall_s=round(time.monotonic() - t0, 2),
             warmup_s=warmup_s, warmed_programs=warmed, health=health,
             requests=answered, requests_ok=len(answered),
             metrics_keys=sorted(metrics)[:12], rc=rc,
             cache_entries=[before, cache_entries()], runtime=runtime)
        require(rc == 0, f"serve: exit {rc}: {text[-800:]}")
        require("drained; exiting 0" in text, "serve: no clean drain: " + text[-800:])
        require(health.get("status") == "ok", f"healthz: {health}")
        require(runtime["device"]["platform"] == device, str(runtime["device"]))
        require_active(runtime, "flash_attention", rehearsal)
        require(runtime["compute_dtype"] == trunk_dtype(rehearsal),
                f"serve: compute_dtype {runtime['compute_dtype']!r}")
        if not rehearsal:
            require(str(runtime["precision"]).startswith("bf16"), runtime["precision"])
        return runtime
    finally:
        if p.poll() is None:  # stop every process this script starts
            p.kill()
            p.wait()
        log.close()


def one_chip(args: argparse.Namespace) -> int:
    rehearsal = args.rehearse_cpu
    device = "cpu" if rehearsal else "tpu"
    reported: List[Dict[str, Any]] = []
    errors: List[str] = []

    def attempt(phase, *a: Any) -> Any:
        """Run one phase and return what it returned (True for nothing),
        or False when it failed: the failure is recorded and the later
        phases that do not need its output still run (one chip call, every
        finding)."""
        try:
            out = phase(*a)
        except (PhaseFailed, OSError, KeyError, ValueError,
                subprocess.SubprocessError) as e:
            errors.append(f"{type(e).__name__}: {e}")
            emit(phase="failed", error=errors[-1])
            return False
        if isinstance(out, dict) and "device" in out:
            reported.append(out)
        return True if out is None else out

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        work = Path(tmp)
        emit(phase="start", mode="rehearse-cpu" if rehearsal else "chip",
             seed=args.seed, compile_cache={"dir": str(cache_dir()),
                                            "entries": cache_entries()})
        # no device, no data: nothing else can run
        rows = attempt(phase_probe, device, rehearsal)
        if rows and attempt(phase_data, work, args.seed):
            attempt(check_probed_kernels, rows, rehearsal)
            attempt(phase_train, "trf", "configs/trf.cfg", TRF_WINDOW, work,
                    device, rehearsal, TINY_TRF if rehearsal else {},
                    ["flash_attention", "fused_update"], trunk_dtype(rehearsal))
            attempt(phase_train, "sm", "configs/sm.cfg", SM_WINDOW, work,
                    device, rehearsal, {}, ["hash_embed_kernel", "fused_update"],
                    "n/a (no transformer trunk)")
            if (work / "trf" / "best-model" / "meta.json").exists():
                attempt(phase_evaluate, work, device, rehearsal)
                attempt(phase_serve, work, device, rehearsal)
    devices = [r["device"] for r in reported]
    if not errors and len(devices) != 4:
        errors.append(f"{len(devices)} of 4 working phases reported a device")
    if any(d != devices[0] for d in devices):
        errors.append(f"phases disagree on the device: {devices}")
    emit(phase="end", compile_cache={"dir": str(cache_dir()),
                                     "entries": cache_entries()},
         deprecation_warnings=sorted(set(DEPRECATIONS)))
    return finish(not errors, devices[0] if devices else None,
                  "; ".join(errors) or None, rehearsal)


def finish(
    ok: bool, device: Optional[Dict[str, Any]], error: Optional[str],
    rehearsal: bool,
) -> int:
    """The last line, and the exit code that goes with it."""
    if not rehearsal and (device or {}).get("platform") != "tpu":
        ok = False
    last: Dict[str, Any] = {"ok": ok, "device": device}
    if rehearsal:
        last["rehearsal"] = "cpu"
    if error:
        last["error"] = error
    print(json.dumps(last), flush=True)
    return 0 if ok else 1


# ----------------------------------------------------------------------
# --chips 4: the path across chips, in ONE process
# ----------------------------------------------------------------------


def four_chips(args: argparse.Namespace) -> int:
    rehearsal = args.rehearse_cpu
    if rehearsal:  # four virtual CPU devices, set before jax starts
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4"
        ).strip()
    sys.path.insert(0, str(ROOT))
    try:
        from spacy_ray_tpu.devices import enable_compile_cache, select_device
    except ImportError as e:
        return finish(False, None, f"not a checkout of the repo: {e}", rehearsal)
    enable_compile_cache()
    try:
        platform, kind, count = select_device("cpu" if rehearsal else "tpu")
    except SystemExit as e:
        return finish(False, None, str(e), rehearsal)
    device = {"platform": platform, "kind": kind, "count": count}
    if count != 4:
        return finish(False, device, f"--chips 4 needs 4 devices, JAX found {count}",
                      rehearsal)
    try:
        error = mesh_comparison(args, rehearsal)
    except Exception as e:  # the last line must still parse
        import traceback

        traceback.print_exc()
        error = f"{type(e).__name__}: {e}"
    return finish(error is None, device, error, rehearsal)


def mesh_comparison(args: argparse.Namespace, rehearsal: bool) -> Optional[str]:
    """trf for MESH_STEPS optimizer steps through ``train(n_workers=4)`` and
    ``train(n_workers=1)``: the real loop, observed at its step boundary."""
    import gc

    import jax
    import numpy as np

    import spacy_ray_tpu.ops.flash_attention as fa
    import spacy_ray_tpu.training.loop as loop
    from spacy_ray_tpu.config import load_config
    from spacy_ray_tpu.devices import runtime_report

    ids = [d.id for d in jax.devices()]
    emit(phase="devices", ids=ids, coords=[
        list(getattr(d, "coords", ())) for d in jax.devices()])

    real_step = loop.make_train_step
    seen: Dict[str, Any] = {}

    def bytes_by_device(tree: Any) -> Dict[int, int]:
        out = {i: 0 for i in ids}
        for leaf in jax.tree_util.tree_leaves(tree):
            for shard in leaf.addressable_shards:
                out[shard.device.id] += shard.data.nbytes
        return out

    def spying_step(*a: Any, **k: Any):
        update = real_step(*a, **k)

        def run(*step_args: Any):
            if "resident" not in seen:
                names = ["params", "opt_state"] + (
                    ["bf16_shadow"] if update.takes_shadow else []
                ) + ["tokens", "targets"]
                seen["resident"] = {
                    n: bytes_by_device(x) for n, x in zip(names, step_args)
                }
            out = update(*step_args)
            seen.setdefault("losses", []).append(out[-2])
            return out

        run.__dict__.update(update.__dict__)
        return run

    runs: Dict[int, Dict[str, Any]] = {}
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        work = Path(tmp)
        phase_data(work, args.seed, n_dev=64)
        over = {
            "paths.train": str(work / "train"), "paths.dev": str(work / "dev"),
            "training.max_steps": MESH_STEPS,
            # one evaluation, at the end: the dev batch sharded over the
            # mesh goes through the same kernels under the eval program
            "training.eval_frequency": MESH_STEPS,
            **(TINY_TRF if rehearsal else {}),
        }
        loop.make_train_step = spying_step
        try:
            for n_workers in (4, 1):
                seen.clear()
                fa.GATE.reset()  # each run's attention status is its own
                t0 = time.monotonic()
                config = load_config(ROOT / "configs" / "trf.cfg", over,
                                     interpolate=False)
                nlp, result = loop.train(config, n_workers=n_workers,
                                         stdout_log=False)
                # what `train` prints as its runtime line
                report = {**runtime_report(nlp), **result.resolved}
                runs[n_workers] = {
                    "losses": [float(x) for x in seen["losses"]],
                    "resident": seen["resident"],
                    "runtime": {k: report[k] for k in (
                        "compute_dtype", "flash_attention", "fused_update",
                        "update_sharding", "bf16_shadow")},
                    "eval_score": result.history[-1]["score"],
                    "eval_seconds": round(result.history[-1]["eval_seconds"], 2),
                    "wall_s": round(time.monotonic() - t0, 2),
                }
                del nlp
                gc.collect()
        finally:
            loop.make_train_step = real_step

    for n_workers, run in runs.items():
        emit(phase=f"mesh_{n_workers}", wall_s=run["wall_s"], steps=len(run["losses"]),
             losses=run["losses"], eval_score=run["eval_score"],
             eval_s=run["eval_seconds"],
             resident_bytes_by_device=run["resident"], **run["runtime"])
    l4, l1 = np.array(runs[4]["losses"]), np.array(runs[1]["losses"])
    rel = np.abs(l4 - l1) / np.abs(l1)
    emit(phase="mesh_compare", loss_rel_diff_by_step=[float(x) for x in rel],
         tolerance=MESH_LOSS_RTOL)

    res = runs[4]["resident"]
    full = runs[1]["resident"]
    one = ids[0]
    problems = []
    if len(l4) != MESH_STEPS or len(l1) != MESH_STEPS:
        problems.append(f"steps taken: {len(l4)} and {len(l1)}, wanted {MESH_STEPS}")
    elif not (np.all(np.isfinite(l4)) and np.all(rel <= MESH_LOSS_RTOL)):
        problems.append(f"losses differ beyond {MESH_LOSS_RTOL}: {rel.tolist()}")
    if not all(math.isfinite(runs[n]["eval_score"]) for n in runs):
        problems.append("evaluation under the mesh gave a non-finite score")
    # the batch split four ways, the Adam moments sharded, the params
    # replicated — "everything landed on device 0" must not pass
    for name in ("tokens", "targets"):
        per = set(res[name].values())
        if len(per) != 1 or 4 * per.pop() != full[name][one]:
            problems.append(f"{name} not split four ways: {res[name]}")
    if set(res["params"].values()) != {full["params"][one]}:
        problems.append(f"params not replicated: {res['params']}")
    if max(res["opt_state"].values()) > 0.5 * full["opt_state"][one]:
        problems.append(f"optimizer state not sharded: {res['opt_state']}")
    if "full (state + apply sharded 4-way" not in runs[4]["runtime"]["update_sharding"]:
        problems.append(f"update_sharding: {runs[4]['runtime']['update_sharding']}")
    # the step and the evaluation both kept the kernel, one shard a chip
    try:
        require_active(runs[4]["runtime"], "flash_attention", rehearsal,
                       "active (pallas, per shard in a shard_map)")
        require_active(runs[1]["runtime"], "flash_attention", rehearsal)
    except PhaseFailed as e:
        problems.append(str(e))
    return "; ".join(problems) or None


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = run only the 4-device-mesh vs 1-device "
                    "training comparison (needs a four-chip host)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated corpora")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="rehearsal without a chip: tiny widths, --device cpu")
    args = ap.parse_args()
    return four_chips(args) if args.chips == 4 else one_chip(args)


if __name__ == "__main__":
    sys.exit(main())
