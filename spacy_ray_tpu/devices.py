"""Platform selection, the compile cache, and the one-process-per-chip rule.

This module imports nothing of the package at import time, so callers that
must run before anything else — test conftests, the multichip dryrun,
``chip_smoke.py`` children — can import it without pulling the full
package.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Any, Dict, Tuple

# the persistent XLA compile cache when JAX_COMPILATION_CACHE_DIR does not
# place it: a fixed path, because the path is part of the cache's key — a
# directory named after a pid, a time or a temp dir never hits
XLA_CACHE_DIR = Path(__file__).resolve().parent.parent / ".xla_cache"

_PLATFORMS = {"cpu": ("cpu",), "tpu": ("tpu",), "gpu": ("gpu", "cuda")}


def force_cpu(n_devices: int = 8) -> None:
    """Select the CPU platform with at least ``n_devices`` virtual devices.

    Safe to call multiple times and after another caller already forced CPU.
    Raises (instead of silently proceeding on an accelerator backend) if the
    jax backend was already initialized on a non-CPU platform — proceeding
    there would mean running a CPU-only check on real hardware.

    Mutates no environment variables, so nothing leaks into subprocesses
    spawned later (a child that inherited ``JAX_PLATFORMS=cpu`` would
    silently run its real-hardware work on CPU).
    """
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", n_devices)
    except RuntimeError:
        pass  # backend already initialized; verified below

    backend = jax.default_backend()  # initializes the backend if needed
    if backend != "cpu":
        raise RuntimeError(
            f"force_cpu(): backend is {backend!r}, not 'cpu' — the jax "
            "backend was already initialized on another platform before "
            "force_cpu() ran. Call it before any jax device use."
        )
    have = len(jax.devices())
    if have < n_devices:
        raise RuntimeError(
            f"force_cpu(): need {n_devices} CPU devices, have {have}. "
            "The device count was locked in before force_cpu() ran; start "
            "a fresh process, or set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n_devices}."
        )


def select_device(device: str) -> Tuple[str, str, int]:
    """Initialise the backend ``--device`` names and return what JAX found
    there as ``(platform, device_kind, count)``. ``cpu`` pins the CPU
    platform; ``tpu`` and ``gpu`` are REQUIREMENTS: where JAX comes up on
    anything else this raises ``SystemExit`` with the platform it found —
    a run that asked for the chip never trains, evaluates or serves on the
    CPU instead and exits 0."""
    import jax

    if device not in _PLATFORMS:
        raise SystemExit(f"--device must be one of {sorted(_PLATFORMS)}")
    prev = jax.config.jax_platforms
    if device == "cpu":
        jax.config.update("jax_platforms", "cpu")
    elif device == "gpu":
        # pin the platform: device selection WITHIN it stays with JAX
        # (CUDA_VISIBLE_DEVICES for pinning)
        jax.config.update("jax_platforms", "cuda")
    try:
        # init now: a missing backend raises opaquely later. In a process
        # whose backends are ALREADY initialized, jax returns the cached
        # platform instead of raising — check what we got.
        devs = jax.devices()
        found = devs[0].platform
    except Exception as e:  # jax fails here with several types, one bare
        devs, found = [], f"no backend ({type(e).__name__}: {e})"
    if not devs or found not in _PLATFORMS[device]:
        # restore: the CLI exits anyway, but an embedding process (or the
        # test suite) must not be left pinned to a dead platform
        jax.config.update("jax_platforms", prev)
        raise SystemExit(
            f"--device {device}: JAX found no {device} here — the platform "
            f"it initialised is {found!r} (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '(unset)')}). Nothing was "
            "run. Pass --device cpu to run on the CPU on purpose."
        )
    return found, devs[0].device_kind, len(devs)


def enable_compile_cache() -> str:
    """Switch on JAX's persistent compilation cache and return its
    directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads
    it, and no directory is set in code at all; otherwise the cache lives
    at the fixed ``<checkout>/.xla_cache``. Every process of a run — CLI
    commands, fleet workers, replicas, ``chip_smoke.py`` children —
    calls this, so they share one cache."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", str(XLA_CACHE_DIR))
    return str(XLA_CACHE_DIR)


def compile_cache_entries() -> int:
    """Programs in the persistent compile cache this process points at."""
    import jax

    cache = jax.config.jax_compilation_cache_dir
    if not cache or not os.path.isdir(cache):
        return 0
    # JAX keeps an access-time stamp next to each entry
    return sum(1 for name in os.listdir(cache) if not name.endswith("-atime"))


def runtime_report(nlp: Any = None) -> Dict[str, Any]:
    """What this process ran on, and what every platform-dependent switch
    resolved to here — by name, with the reason wherever one is off. The
    commands that do device work print it, so a record never has to infer
    the path from the flags that were passed. Read it AFTER the work: the
    kernel lines are the paths the traced programs took (a kernel that is
    armed but gave way to XLA on this mesh or shape says so), and
    ``compute_dtype`` is what the trunks of ``nlp`` computed in."""
    import jax

    from . import native
    from .models.shadow import pipeline_compute_dtype
    from .ops.flash_attention import flash_attention_status
    from .ops.pallas_kernels import hash_embed_status

    devs = jax.devices()
    report = {
        "device": {
            "platform": devs[0].platform,
            "kind": devs[0].device_kind,
            "count": len(devs),
        },
        "flash_attention": flash_attention_status(),
        "hash_embed_kernel": hash_embed_status(),
        "native_hash": (
            "libsrt_native.so" if native.available()
            else "python fallback (native build failed)"
        ),
        "compile_cache": {
            "dir": jax.config.jax_compilation_cache_dir,
            "entries": compile_cache_entries(),
        },
    }
    if nlp is not None:
        report["compute_dtype"] = pipeline_compute_dtype(nlp)
    return report


def refuse_shared_chip(
    device: str, n_processes: int, what: str, n_masks: int = 0
) -> None:
    """One process for each chip. A TPU chip belongs to one process at a
    time, and a JAX process claims every chip it can see, so a launcher
    about to start ``n_processes`` children that each want ``--device tpu``
    would leave all but the first failing or hanging at backend start-up.
    Refuse at once instead. ``n_masks`` is how many distinct per-process
    visible-device masks the caller will hand out (0 = none)."""
    if device != "tpu" or n_processes <= max(n_masks, 1):
        return
    raise SystemExit(
        f"{what}: --device tpu would start {n_processes} processes that "
        f"each claim the TPU, with {n_masks} per-process device mask(s) to "
        "keep them apart — a chip belongs to one process at a time, so the "
        "others would fail or hang at start-up. Nothing was started. Run "
        "one process across the chips (train --n-workers N), give every "
        "process its own chip mask where the command takes one, or pass "
        "--device cpu."
    )
