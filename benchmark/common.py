"""What every cell of the benchmark shares: finding a cell's files by the
names in ``BENCHMARK.json``, the device's description and memory peaks, and
the table of peaks."""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import tempfile
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class BenchError(Exception):
    """The run cannot give a result; ``run.py`` exits non-zero without one."""


def load_json(path: Path) -> Any:
    if not path.is_file():
        raise BenchError(f"missing file: {path.relative_to(ROOT)}")
    return json.loads(path.read_text(encoding="utf8"))


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module, found by name."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"missing file: {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(workload: str) -> Dict[str, Any]:
    """The cell's entry with its configuration and traffic files read in,
    and the metrics that are the cell's own."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no cell {workload!r} in BENCHMARK.json; have {sorted(cells)}")
    cell = dict(cells[workload])
    configs = {c["name"]: c for c in bench["configs"]}
    if cell["config"] not in configs:
        raise BenchError(f"cell {workload!r} names config {cell['config']!r}, "
                         "which BENCHMARK.json does not list")
    cell["config_file"] = load_json(ROOT / configs[cell["config"]]["file"])
    cell["traffic_file"] = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")

    def mine(metrics: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
        return [m for m in metrics if workload in m.get("workloads", [workload])]

    cell["end_to_end"] = mine(bench["end_to_end"])
    e2e = {m["name"] for m in cell["end_to_end"]}
    # a per-layer metric is reported only where the metric it moves is
    cell["per_layer"] = [m for m in mine(bench["per_layer"]) if m["moves"] in e2e]
    return cell


def peaks_for(device_kind: str) -> Dict[str, Any]:
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise BenchError(f"device kind {device_kind!r} is not in benchmark/peaks.json; "
                         f"known: {sorted(table)}")
    return table[device_kind]


def median(values: Sequence[float]) -> Optional[float]:
    if not values:
        return None
    ordered = sorted(values)
    mid = len(ordered) // 2
    return ordered[mid] if len(ordered) % 2 else 0.5 * (ordered[mid - 1] + ordered[mid])


class WorkDir:
    """A directory for what one run writes (corpus, model, requests, trace),
    under ``TMPDIR`` and removed when the run ends."""

    def __enter__(self) -> Path:
        self.path = Path(tempfile.mkdtemp(prefix="srt_bench_"))
        return self.path

    def __exit__(self, *exc: Any) -> None:
        shutil.rmtree(self.path, ignore_errors=True)


def write_jsonl(path: Path, rows: Sequence[Dict[str, Any]]) -> None:
    with open(path, "w", encoding="utf8") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")


def device_description(peaks: Dict[str, int]) -> Dict[str, Any]:
    """The device as JAX reports it. ``memory_peak_bytes`` is the peak of the
    chip's memory, whoever held it: the larger of the two peaks of
    ``memory_peaks`` (they need not fall together, so their sum would only be
    an upper bound)."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs),
            "memory_peak_bytes": max(peaks.values()) or None}


def memory_peaks() -> Dict[str, int]:
    """The runtime's two peaks after the window, each on the fullest chip.

    ``in_use`` (``peak_bytes_in_use``) is the buffers JAX holds: parameters,
    optimizer state, batches. ``reserved`` (``peak_bytes_reserved``) is what the
    runtime set aside while a program ran, its temporaries included. They are
    counted apart on the TPU: the sm step at 128x256 peaked at 0.31 GB in use
    and 1.663 GB reserved (chip, PR 22), and the TPU compiler's own memory
    analysis of that step gives 1.678 GB of temporaries (compiled here for a
    described v5e, PR 22). A traced run prints the compiler's analysis of the
    step it ran beside both (``train_cell.step_memory``)."""
    import jax

    peaks = {"in_use": 0, "reserved": 0}
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peaks["in_use"] = max(peaks["in_use"], int(stats.get("peak_bytes_in_use", 0)))
        peaks["reserved"] = max(peaks["reserved"], int(stats.get("peak_bytes_reserved", 0)))
    return peaks


def start_jax(chips: int, rehearse_cpu: bool) -> None:
    """Bring JAX up on the TPU (or, rehearsing, on ``chips`` virtual CPU
    devices) with the program's compile cache. Without the TPU and the chips the
    cell asks for there is no result: ``select_device`` exits non-zero naming
    what JAX found. Call before anything imports JAX."""
    # keep every program in the persistent cache, the sub-second ones too
    # (JAX's default floor is 1 s of compile time)
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    if rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + f" --xla_force_host_platform_device_count={chips}").strip()
    try:
        from spacy_ray_tpu.devices import enable_compile_cache, select_device
    except ImportError as e:
        raise BenchError(f"the program is not in this checkout: {e}")
    enable_compile_cache()
    _, _, count = select_device("cpu" if rehearse_cpu else "tpu")
    if count < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found {count}")
