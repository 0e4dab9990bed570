"""No fallback that hides the device: ``--device tpu`` is a requirement,
kernel probes do not swallow a failure on a TPU, the compile cache can be
placed from outside, launchers refuse to share a chip between processes —
and ``chip_smoke.py`` stops at the device check where there is no chip.
All on the CPU; nothing here runs a model."""

import json
import logging
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from spacy_ray_tpu import devices
from spacy_ray_tpu.cli import main as cli_main
from spacy_ray_tpu.ops import flash_attention as fa
from spacy_ray_tpu.ops import fused_update as fu
from spacy_ray_tpu.ops import int8_matmul as i8
from spacy_ray_tpu.ops import pallas_kernels as pk
from spacy_ray_tpu.ops.probe import KernelProbeError

REPO = Path(__file__).parent.parent


# ----------------------------------------------------------------------
# --device tpu with no TPU
# ----------------------------------------------------------------------


@pytest.mark.parametrize(
    "argv",
    [
        ["train", "configs/cnn.cfg", "--device", "tpu"],
        ["train", "configs/cnn.cfg"],  # tpu is the default
        ["evaluate", "no-such-model", "no-such-data"],
        ["serve", "no-such-model", "--port", "0"],
        ["parse", "no-such-model", "no-such-input", "no-such-output"],
    ],
    ids=["train", "train-default", "evaluate", "serve", "parse"],
)
def test_device_tpu_without_a_tpu_exits_and_names_the_platform(argv, capsys):
    """Where JAX finds no chip the command exits non-zero and says which
    platform it found — before it loads a model, reads data or trains
    (it used to fall through to JAX's default and train on the CPU)."""
    with pytest.raises(SystemExit) as exc:
        cli_main(argv)
    message = str(exc.value.code)
    assert "JAX found no tpu" in message and "'cpu'" in message
    assert "--device cpu" in message  # the way out is named
    assert "Done." not in capsys.readouterr().out


def test_select_device_cpu_reports_what_it_found():
    assert devices.select_device("cpu") == ("cpu", "cpu", len(jax.devices()))


# ----------------------------------------------------------------------
# the compile cache
# ----------------------------------------------------------------------


def test_compile_cache_is_left_alone_when_placed_from_outside(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    before = jax.config.jax_compilation_cache_dir
    assert devices.enable_compile_cache() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == before  # set nothing


def test_compile_cache_default_is_the_fixed_checkout_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = devices.enable_compile_cache()
        assert first == devices.enable_compile_cache() == str(REPO / ".xla_cache")
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    # another process computes the same path: no pid, time or temp name in it
    other = subprocess.run(
        [sys.executable, "-c",
         "from spacy_ray_tpu.devices import enable_compile_cache as e; print(e())"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
        env={k: v for k, v in os.environ.items()
             if k != "JAX_COMPILATION_CACHE_DIR"},
    )
    assert other.stdout.strip().splitlines()[-1] == first, other.stderr


# ----------------------------------------------------------------------
# kernel probes on a (faked) TPU backend
# ----------------------------------------------------------------------


def _refuse(*a, **k):
    raise NotImplementedError("Mosaic says no: block shape (1, 256)")


KERNELS = {
    "flash": (fa, "_fwd_raw", lambda: fa.flash_attention_enabled(), "SRT_PALLAS_ATTN"),
    "hash_embed": (pk, "_pallas_lookup_raw", lambda: pk.pallas_enabled(), "SRT_PALLAS"),
    "fused": (fu, "_kernel_leaf", lambda: fu.fused_kernel_enabled(), "SRT_PALLAS_FUSED"),
    "int8": (i8, "_int8_matmul_raw", lambda: i8.int8_probe(), "SRT_PALLAS_INT8"),
}


@pytest.fixture
def fake_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for mod in (fa, pk, fu):
        monkeypatch.setattr(mod, "_PROBED", None)
    monkeypatch.setattr(i8, "_PROBE_CACHE", {})
    for _, _, _, env in KERNELS.values():
        monkeypatch.delenv(env, raising=False)


@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_probe_that_cannot_compile_on_tpu_raises(fake_tpu, monkeypatch, kernel):
    """A compile error under a tpu backend is raised with the compiler's
    own words — never turned into a silent False that leaves the run on
    the reference path while its records name the kernel."""
    mod, raw, enabled, _ = KERNELS[kernel]
    monkeypatch.setattr(mod, raw, _refuse)
    with pytest.raises(KernelProbeError, match="Mosaic says no"):
        enabled()


def test_probe_that_disagrees_with_its_reference_on_tpu_raises(fake_tpu, monkeypatch):
    real = pk._pallas_lookup_raw
    monkeypatch.setattr(
        pk, "_pallas_lookup_raw",
        lambda table, ids, interpret=False: real(table, ids, interpret=True) + 1.0,
    )
    with pytest.raises(KernelProbeError, match="disagrees.*forward.*= 1"):
        pk.pallas_enabled()


def test_probe_off_tpu_is_off_with_its_reason(monkeypatch):
    """Off a TPU nothing changes: auto-off, and the status says why."""
    monkeypatch.setattr(fa, "_PROBED", None)
    monkeypatch.setattr(fa, "_STATUS", fa._STATUS)  # restored afterwards
    monkeypatch.delenv("SRT_PALLAS_ATTN", raising=False)
    assert fa.flash_attention_enabled() is False
    assert fa.flash_attention_status() == (
        "off (auto-off on cpu; SRT_PALLAS_ATTN=1 forces it)"
    )


def test_probe_runs_eagerly_from_inside_a_trace(monkeypatch):
    """The first caller is the train step's trace. The probe must step out
    of it: run inside, its arrays are tracers, its comparison cannot be
    read, and (before this was fixed) every probe failed there — silently."""
    monkeypatch.setattr(fa, "_PROBED", None)
    monkeypatch.setattr(fa, "_STATUS", fa._STATUS)  # restored afterwards
    monkeypatch.setattr(fa, "_INTERPRET", True)
    monkeypatch.setenv("SRT_PALLAS_ATTN", "1")
    import jax.numpy as jnp

    q = jnp.ones((1, 128, 1, 64), jnp.float32)
    mask = jnp.ones((1, 128), bool)
    jax.jit(lambda q, m: fa.attention(q, q, q, m))(q, mask)
    assert fa._PROBED is True, fa.flash_attention_status()
    assert fa.flash_attention_status() == "active (pallas interpret-mode)"


# ----------------------------------------------------------------------
# one process for each chip
# ----------------------------------------------------------------------


@pytest.mark.parametrize(
    "argv,what",
    [
        (["train", "configs/cnn.cfg", "--fleet-workers", "2"], "train --fleet-workers"),
        (["serve-fleet", "no-such-model", "--replicas", "2"], "serve-fleet --replicas"),
        (["serve-fleet", "no-such-model", "--replicas", "2",
          "--visible-devices", "0,1"], "serve-fleet --replicas"),  # CUDA's variable
        (["train-and-serve", "configs/cnn.cfg", "-o", "out"], "train-and-serve"),
    ],
    ids=["train-fleet", "serve-fleet", "serve-fleet-cuda-masks", "train-and-serve"],
)
def test_launchers_refuse_to_share_the_chip(argv, what):
    """More processes that want the TPU than chip masks to keep them apart:
    refused at once, with the reason, before any child starts."""
    with pytest.raises(SystemExit) as exc:
        cli_main(argv)
    message = str(exc.value.code)
    assert what in message and "one process at a time" in message
    assert "Nothing was started" in message


def test_refuse_shared_chip_lets_the_workable_layouts_through():
    devices.refuse_shared_chip("cpu", 8, "x")  # the CPU is shareable
    devices.refuse_shared_chip("tpu", 1, "x")  # one process, every chip
    devices.refuse_shared_chip("tpu", 4, "x", n_masks=4)  # a chip each
    with pytest.raises(SystemExit):
        devices.refuse_shared_chip("tpu", 4, "x", n_masks=2)


# ----------------------------------------------------------------------
# peak table, native build
# ----------------------------------------------------------------------


@pytest.mark.parametrize(
    "kind,peak",
    [("TPU v5 lite", 197e12), ("TPU v5", 459e12), ("TPU v5 lite pod", None),
     ("TPU v7x", None)],
)
def test_peak_flops_matches_the_exact_device_kind(monkeypatch, kind, peak):
    """No substring match: a kind that merely contains "v5" gets no peak."""
    from types import SimpleNamespace

    from spacy_ray_tpu.training import telemetry

    fake = SimpleNamespace(platform="tpu", device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    got, why = telemetry.device_peak_flops()
    assert got == peak
    assert kind in why


def test_failed_native_build_is_reported_and_leaves_no_half_written_file(
    tmp_path, monkeypatch, caplog
):
    from spacy_ray_tpu import native

    (tmp_path / "bad.cpp").write_text("this is not C++\n")
    monkeypatch.setattr(native, "_HERE", tmp_path)
    monkeypatch.setattr(native, "_SRC", tmp_path / "bad.cpp")
    monkeypatch.setattr(native, "_SO", tmp_path / "libsrt_native.so")
    with caplog.at_level(logging.WARNING, logger="spacy_ray_tpu.native"):
        assert native._build() is False
    assert "did not build" in caplog.text and "error" in caplog.text  # g++'s words
    assert sorted(p.name for p in tmp_path.iterdir()) == ["bad.cpp"]
    # and a good build lands by rename, whole
    monkeypatch.setattr(native, "_SRC", REPO / "spacy_ray_tpu" / "native" / "murmur.cpp")
    assert native._build() is True
    assert sorted(p.name for p in tmp_path.iterdir()) == ["bad.cpp", "libsrt_native.so"]


# ----------------------------------------------------------------------
# chip_smoke.py where there is no chip
# ----------------------------------------------------------------------


def test_chip_smoke_stops_at_the_device_check_without_a_chip():
    p = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=REPO, capture_output=True,
        text=True, timeout=300,
    )
    assert p.returncode != 0
    lines = [json.loads(line) for line in p.stdout.strip().splitlines()]
    assert lines[-1]["ok"] is False and lines[-1]["device"] is None
    assert "JAX found no tpu" in lines[-1]["error"]
    phases = [line.get("phase") for line in lines[:-1]]
    assert "probe" in phases  # it looked, in a child
    assert not {"data", "trf", "sm", "evaluate", "serve"} & set(phases)
