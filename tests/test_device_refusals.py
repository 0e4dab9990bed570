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


def _fresh_gates(monkeypatch):
    """Every kernel unprobed, nothing traced — put back afterwards."""
    for mod in (fa, pk, fu):
        monkeypatch.setattr(mod.GATE, "armed", None)
        monkeypatch.setattr(mod.GATE, "verdict", mod.GATE.verdict)
        monkeypatch.setattr(mod.GATE, "paths", [])
    monkeypatch.setattr(i8, "_PROBE_CACHE", {})
    for _, _, _, env in KERNELS.values():
        monkeypatch.delenv(env, raising=False)


@pytest.fixture
def fake_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    _fresh_gates(monkeypatch)


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


def test_info_probe_reports_a_failed_kernel_in_the_compilers_words(
    fake_tpu, monkeypatch
):
    """``info --probe`` catches the probe's error to show the whole
    installation — and then must print the failure, not "not probed". On
    this CPU no kernel compiles for the faked tpu. (The int8 row is asked
    about the device JAX found, the cpu; its raise is tested above.)"""
    from spacy_ray_tpu import cli

    monkeypatch.setattr(fa, "_fwd_raw", _refuse)
    monkeypatch.setattr(devices, "enable_compile_cache", lambda: None)
    rows = dict(cli._probe_rows())
    for key in ("flash_attention", "hash_embed", "fused_kernel"):
        assert "FAILED (" in rows[key] and "did not compile on tpu" in rows[key], rows
        assert "not probed" not in rows[key]
    assert "Mosaic says no: block shape (1, 256)" in rows["flash_attention"]


def test_probe_off_tpu_is_off_with_its_reason(monkeypatch):
    """Off a TPU nothing changes: auto-off, and the status says why."""
    _fresh_gates(monkeypatch)
    assert fa.flash_attention_enabled() is False
    assert fa.flash_attention_status() == (
        "off (auto-off on cpu; SRT_PALLAS_ATTN=1 forces it)"
    )


def test_probe_runs_eagerly_from_inside_a_trace(monkeypatch):
    """The first caller is the train step's trace. The probe must step out
    of it: run inside, its arrays are tracers, its comparison cannot be
    read, and (before this was fixed) every probe failed there — silently."""
    _fresh_gates(monkeypatch)
    monkeypatch.setattr(fa, "_INTERPRET", True)
    monkeypatch.setenv("SRT_PALLAS_ATTN", "1")
    import jax.numpy as jnp

    q = jnp.ones((1, 128, 1, 64), jnp.float32)
    mask = jnp.ones((1, 128), bool)
    jax.jit(lambda q, m: fa.attention(q, q, q, m))(q, mask)
    assert fa.GATE.armed is True, fa.flash_attention_status()
    assert fa.flash_attention_status() == "active (pallas interpret-mode)"


# ----------------------------------------------------------------------
# the status is the path the program took, not only the probe's verdict
# ----------------------------------------------------------------------


@pytest.fixture
def armed(monkeypatch):
    """Kernels as a TPU run has them after a passed probe."""
    _fresh_gates(monkeypatch)
    for mod in (fa, pk):
        monkeypatch.setattr(mod.GATE, "armed", True)
        monkeypatch.setattr(mod.GATE, "verdict", "active (pallas)")
    monkeypatch.setattr(fa, "_INTERPRET", True)


def _attend(B=2, T=128, H=2):
    import jax.numpy as jnp

    q = jax.ShapeDtypeStruct((B, T, H, 64), jnp.bfloat16)
    jax.eval_shape(fa.attention, q, q, q, jax.ShapeDtypeStruct((B, T), bool))


def test_flash_status_names_the_kernel_where_it_ran(armed):
    assert fa.flash_attention_status() == "active (pallas)"  # the verdict
    _attend()
    assert fa.flash_attention_status() == "active (pallas interpret-mode)"


def test_flash_status_says_so_when_an_armed_kernel_gives_way(armed):
    """Past the VMEM gate, and on a mesh the layout does not divide,
    attention() falls back to XLA: the run's record must not go on saying
    "active (pallas)"."""
    from spacy_ray_tpu.parallel import context as pctx
    from spacy_ray_tpu.parallel.mesh import build_mesh

    _attend(T=8192)
    assert fa.flash_attention_status() == (
        "xla (T=8192 is past the kernel's VMEM budget)"
    )
    with pctx.use_mesh(build_mesh(n_data=4)):
        _attend(B=4)
        _attend(B=3)
    assert fa.flash_attention_status().split("; ")[1:] == [
        "active (pallas interpret-mode, per shard in a shard_map)",
        "xla (batch 3 x heads 2 does not divide the mesh {'data': 4})",
    ]


def test_hash_embed_status_knows_the_mesh(armed, monkeypatch):
    """``train sm.cfg --n-workers 4``: the kernel is gated off a
    multi-device mesh, and the status used to say "not probed" or
    "active (pallas)" all the same."""
    import jax.numpy as jnp

    from spacy_ray_tpu.parallel import context as pctx
    from spacy_ray_tpu.parallel.mesh import build_mesh

    table = jax.ShapeDtypeStruct((2000, 96), jnp.float32)
    ids = jax.ShapeDtypeStruct((64, 4), jnp.int32)
    with pctx.use_mesh(build_mesh(n_data=4)):
        jax.eval_shape(pk.hash_embed_lookup, table, ids)
    assert pk.hash_embed_status() == "xla (kernel gated off a multi-device mesh)"
    big = jax.ShapeDtypeStruct((50000, 96), jnp.float32)
    jax.eval_shape(pk.hash_embed_lookup, big, ids)
    assert pk.hash_embed_status().split("; ")[1] == (
        "xla (table 50000x96 float32 is outside the kernel's f32 VMEM budget)"
    )


def test_runtime_report_gives_the_pipelines_own_compute_dtype():
    """Not what "auto" would resolve to: what this pipeline's trunk was
    configured with, resolved — and nothing at all for a CNN pipeline."""
    from types import SimpleNamespace

    from spacy_ray_tpu.models.transformer import TransformerEncoder

    def nlp_with(*models):
        return SimpleNamespace(components={
            str(i): SimpleNamespace(model=m) for i, m in enumerate(models)
        })

    trunk = lambda dtype: TransformerEncoder(  # noqa: E731
        width=32, depth=1, n_heads=2, embed_size=50, compute_dtype=dtype
    )
    report = devices.runtime_report(nlp_with(trunk("bfloat16")))
    assert report["compute_dtype"] == "bfloat16"  # "auto" is float32 here
    assert devices.runtime_report(nlp_with(trunk("auto")))["compute_dtype"] == "float32"
    assert devices.runtime_report(nlp_with())["compute_dtype"] == (
        "n/a (no transformer trunk)"
    )
    assert "compute_dtype" not in devices.runtime_report()


def test_verbose_still_reaches_the_packages_loggers():
    """The native build's warning is let through by ITS logger's level;
    the package logger keeps none of its own, so ``--verbose`` (root at
    DEBUG) still shows the serving, fleet and residency loggers' INFO."""
    assert cli_main(["info"]) == 0
    assert logging.getLogger("spacy_ray_tpu").level == logging.NOTSET
    assert logging.getLogger("spacy_ray_tpu.native").level == logging.WARNING
    assert logging.getLogger("spacy_ray_tpu.serving").level == logging.NOTSET


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


NATIVE_SOURCES = tuple(
    REPO / "spacy_ray_tpu" / "native" / name for name in ("murmur.cpp", "oracle.cpp"))


def test_failed_native_build_is_reported_and_leaves_no_half_written_file(
    tmp_path, monkeypatch, caplog
):
    from spacy_ray_tpu import native

    (tmp_path / "bad.cpp").write_text("this is not C++\n")
    monkeypatch.setattr(native, "_HERE", tmp_path)
    monkeypatch.setattr(native, "_SOURCES", (tmp_path / "bad.cpp",))
    monkeypatch.setattr(native, "_SO", tmp_path / "libsrt_native.so")
    monkeypatch.setattr(native, "_WHY_MISSING", "")
    with caplog.at_level(logging.WARNING, logger="spacy_ray_tpu.native"):
        assert native._build() is False
    assert "did not build" in caplog.text and "error" in caplog.text  # g++'s words
    assert native.why_missing() == "g++ failed: CalledProcessError"
    assert sorted(p.name for p in tmp_path.iterdir()) == ["bad.cpp"]
    # and a good build lands by rename, whole
    monkeypatch.setattr(native, "_SOURCES", NATIVE_SOURCES)
    assert native._build() is True
    assert sorted(p.name for p in tmp_path.iterdir()) == ["bad.cpp", "libsrt_native.so"]


@pytest.fixture
def native_in(tmp_path, monkeypatch):
    """The loader over a copy of the two sources in ``tmp_path``, not yet
    tried, with its builds counted; the process's own library comes back
    when the test ends."""
    from spacy_ray_tpu import native

    sources = tuple(tmp_path / src.name for src in NATIVE_SOURCES)
    for src, copy in zip(NATIVE_SOURCES, sources):
        copy.write_bytes(src.read_bytes())
    monkeypatch.setattr(native, "_HERE", tmp_path)
    monkeypatch.setattr(native, "_SOURCES", sources)
    monkeypatch.setattr(native, "_SO", tmp_path / "libsrt_native.so")
    monkeypatch.setattr(native, "_LIB", None)
    monkeypatch.setattr(native, "_TRIED", False)
    monkeypatch.setattr(native, "_WHY_MISSING", "")
    builds = []
    real_build = native._build
    monkeypatch.setattr(native, "_build", lambda: builds.append(1) or real_build())
    return native, builds


@pytest.mark.parametrize("newer", ["murmur.cpp", "oracle.cpp"])
def test_a_library_older_than_either_source_is_rebuilt(native_in, newer):
    native, builds = native_in
    assert native._build() is True
    built = native._SO.stat().st_mtime
    for src in native._SOURCES:
        os.utime(src, (built - 10, built - 10))
    assert not native._stale()
    os.utime(native._HERE / newer, (built + 10, built + 10))
    assert native._stale()
    inode = native._SO.stat().st_ino
    del builds[:]
    assert native.load() is not None and builds == [1]
    assert native._SO.stat().st_ino != inode  # a new file, renamed onto the path
    assert native.load() is not None and builds == [1]  # and not again


def test_a_library_without_the_oracles_symbol_is_rebuilt_once_and_then_used(native_in):
    """An ignored .so of an older tree, copied with the tree and so newer
    than both sources: neither an AttributeError nor a silent fallback."""
    from spacy_ray_tpu.pipeline import transition

    native, builds = native_in
    subprocess.run(
        ["g++", "-O3", "-shared", "-fPIC", "-o", str(native._SO), str(native._SOURCES[0])],
        check=True, capture_output=True, timeout=120,
    )
    newest = max(src.stat().st_mtime for src in native._SOURCES)
    assert native._SO.stat().st_mtime >= newest and native._stale()
    lib = native.load()
    assert builds == [1] and lib is not None and hasattr(lib, "arc_eager_gold_oracle")
    assert transition.oracle_path() == "native"
    got = transition.gold_oracle([1, 1, 1], [0, 0, 1], 2)
    want = transition.gold_oracle_python([1, 1, 1], [0, 0, 1], 2)
    assert all((g == w).all() for g, w in zip(got, want))
    from spacy_ray_tpu.ops.hashing import hash_string_u64

    assert native.hash_strings_u64(["norm=the"])[0] == hash_string_u64("norm=the")
    assert native.load() is lib and builds == [1]


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
