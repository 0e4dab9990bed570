"""Pallas int8 weight-only matmul for the serving precision overlay.

The serving overlay's ``--precision int8`` knob existed since PR 7 with
an honestly-refusing probe (``serving/overlay.py:_probe_int8`` — "no
int8 serving kernel on <backend>"). This module is that kernel: the
weights of the transformer trunk's dense matmuls are quantized ONCE at
overlay build time to int8 with per-output-channel symmetric scales
(``quantize_int8``), and the forward consumes them through this
pallas_call — the int8 block is dequantized IN-KERNEL (int8 -> f32 on
the VPU, one multiply by the channel scale after the dot) so HBM streams
the weights at 1/4 of their f32 byte volume while the MXU still
accumulates in f32. Activations stay in the compute dtype (weight-only
quantization: the activation distribution is input-dependent and NOT
quantized — SURVEY.md's serving-precision ladder, and the standard
weight-only serving recipe).

Why the memory shape matters: serving batches are small (continuous
admission dispatches at occupancy 2-8 on the committed records), so the
trunk matmuls are BANDWIDTH-bound — every dispatched batch re-streams
the whole weight matrix from HBM. Quartering the weight bytes is the
per-replica multiplier ROADMAP item 3a names; the arithmetic itself was
never the bottleneck at these occupancies.

Honesty rules (the gate every kernel goes through, ops/probe.py):

* enabled ONLY by :func:`int8_probe` — compile + numeric validation vs
  the f32-dequant reference on the current backend; ``SRT_PALLAS_INT8=1``
  forces on (interpret-mode on non-TPU backends, so CPU tests run the
  REAL kernel body, interpreted), ``=0`` forces
  off; default auto-enables on TPU only, where a failed probe raises.
* the probe's reason string is the overlay label's source of truth:
  "active (pallas)" only when the compiled kernel runs, "active (pallas
  interpret-mode)" when interpreted, a typed refusal otherwise.
* activations keep the precision they arrive in: bfloat16 (the trunk's
  compute dtype on a TPU) goes to the MXU as it is, exactly; float32 is
  contracted at full f32 precision, not rounded to bfloat16 on the way.
* shapes whose per-block VMEM working set exceeds the budget fall back
  to the jnp dequant matmul (same numbers, no kernel) — the same
  host-side guard as ``flash_attention.attention_vmem_ok``.
"""

from __future__ import annotations

import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..names import KERNEL_INT8_MATMUL
from . import probe as _probe

__all__ = [
    "quantize_int8",
    "dequantize_int8",
    "quantize_int8_np",
    "dequantize_int8_np",
    "reference_int8_matmul",
    "int8_matmul",
    "int8_matmul_enabled",
    "int8_probe",
]

BM = 128   # activation rows per grid step (MXU-aligned)
BN = 128   # output-channel block (lane-aligned)
KP = 128   # contraction dim padded to a lane multiple
# The TPU compiler's scoped-VMEM limit for one kernel ("limit 16.00M" in
# its refusal, v5e, libtpu 0.0.34). K stays fully resident per grid step
# (encoder trunk K <= ~4k).
VMEM_INT8_BUDGET = 16 * 1024 * 1024


# ------------------------------------------------------------ quantization


def quantize_int8(w: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-output-channel symmetric int8 quantization of a weight array
    whose LAST axis is the output channel: returns ``(q8, scale)`` with
    ``q8`` int8 in [-127, 127] and ``scale`` f32 per channel, such that
    ``q8 * scale ~= w`` with per-element error bounded by ``scale / 2``
    (round-to-nearest; test-enforced). Symmetric (no zero point): the
    dequant epilogue stays one multiply, and trunk weight distributions
    are zero-centered (glorot/normal init, weight decay)."""
    w = jnp.asarray(w, jnp.float32)
    reduce_axes = tuple(range(w.ndim - 1))
    absmax = jnp.max(jnp.abs(w), axis=reduce_axes)
    scale = jnp.maximum(absmax / 127.0, 1e-12).astype(jnp.float32)
    q = jnp.clip(jnp.round(w / scale), -127.0, 127.0).astype(jnp.int8)
    return q, scale


def dequantize_int8(q8: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """``q8 [..., N] int8, scale [N] f32 -> f32`` — the reference
    reconstruction the kernel's in-VMEM dequant must match."""
    return q8.astype(jnp.float32) * scale


def quantize_int8_np(arr) -> Tuple["np.ndarray", "np.ndarray"]:
    """Grad-shaped host-side twin of :func:`quantize_int8` for the
    trainer fleet's wire compression (training/fleet/wire.py): pure
    numpy (gradients are already host arrays on the push path — no
    device round trip), same symmetric per-channel semantics and the
    same test-pinned bound (per-element error <= scale / 2).

    Shape policy: rank >= 2 quantizes per-channel over the LAST axis
    (``scale`` shape ``(N,)``, exactly :func:`quantize_int8`); rank <= 1
    uses ONE per-tensor scale (``scale`` shape ``()``) — a per-element
    scale on a vector would cost 5 bytes/element against the 4 it
    replaces. Gradient leaves are any rank, weight matrices rank 2+."""
    import numpy as np

    a = np.ascontiguousarray(np.asarray(arr, dtype=np.float32))
    if a.ndim >= 2:
        reduce_axes = tuple(range(a.ndim - 1))
        absmax = np.max(np.abs(a), axis=reduce_axes) if a.size else np.zeros(
            a.shape[-1], np.float32
        )
    else:
        absmax = np.max(np.abs(a)) if a.size else np.float32(0.0)
    scale = np.maximum(
        np.asarray(absmax, np.float32) / np.float32(127.0), np.float32(1e-12)
    ).astype(np.float32)
    q = np.clip(np.rint(a / scale), -127.0, 127.0).astype(np.int8)
    return q, scale


def dequantize_int8_np(q8, scale) -> "np.ndarray":
    """Host-side reconstruction twin of :func:`dequantize_int8` —
    broadcasting covers both the per-channel (rank >= 2) and per-tensor
    (rank <= 1) scale shapes :func:`quantize_int8_np` emits."""
    import numpy as np

    return q8.astype(np.float32) * np.asarray(scale, np.float32)


def reference_int8_matmul(
    x: jnp.ndarray, q8: jnp.ndarray, scale: jnp.ndarray
) -> jnp.ndarray:
    """jnp fallback/reference: ``x [..., K] @ dequant(q8 [K, N]) -> [..., N]``
    in f32 — what the pallas kernel is validated against. HIGHEST precision:
    at the default a TPU rounds f32 operands to bfloat16 before the MXU
    (measured on a v5e: 4.7e-3 from the f32 product), which is not what
    the kernel does (see ``_kernel``) and no reference to hold it to."""
    return jnp.matmul(
        x.astype(jnp.float32), dequantize_int8(q8, scale),
        precision=jax.lax.Precision.HIGHEST,
    )


# ----------------------------------------------------------------- kernel


def _kernel(x_ref, wq_ref, s_ref, o_ref):
    # x [BM, K] bf16|f32, wq [K, BN] int8, s [1, BN] f32 -> o [BM, BN] f32.
    # Dequantize-in-kernel: the int8 block upcasts on the VPU to the
    # activations' dtype (|q| <= 127 is exact in bfloat16 too); the scale
    # multiply lands on the f32 accumulator AFTER the dot (exact: scale
    # is constant per output column, so (x @ q) * s == x @ (q * s)).
    # bf16 x bf16 is one exact MXU pass. An f32 dot at the default
    # precision would be rounded to that same one pass (3.2e-3 from the
    # f32 product, measured on a v5e), so f32 activations ask for HIGHEST.
    x = x_ref[...]
    w = wq_ref[...].astype(x.dtype)
    acc = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        precision=(
            jax.lax.Precision.HIGHEST if x.dtype == jnp.float32 else None
        ),
        preferred_element_type=jnp.float32,
    )
    o_ref[...] = acc * s_ref[...]


_INTERPRET = False  # tests flip this to run the kernel body on CPU


def _pad_axis(a: jnp.ndarray, axis: int, mult: int, value=0) -> jnp.ndarray:
    pad = (-a.shape[axis]) % mult
    if not pad:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths, constant_values=value)


def _int8_matmul_raw(
    x2: jnp.ndarray, q8: jnp.ndarray, scale: jnp.ndarray,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """[M, K] bf16|f32, [K, N] int8, [N] f32 -> [M, N] f32. Pads M/N/K to
    the block grid (zero rows/columns contribute nothing; padded scale
    columns are sliced away with their outputs)."""
    if interpret is None:
        # forced-on non-TPU backends (CPU tests)
        # run the same kernel body through the pallas interpreter — the
        # numbers are the kernel's, only the execution engine differs
        interpret = _INTERPRET or jax.default_backend() != "tpu"
    M, K = x2.shape
    N = q8.shape[1]
    xp = _pad_axis(_pad_axis(x2, 0, BM), 1, KP)
    wp = _pad_axis(_pad_axis(q8, 0, KP), 1, BN)
    sp = _pad_axis(scale.reshape(1, -1), 1, BN, value=1.0)
    Mp, Kp = xp.shape
    Np = wp.shape[1]
    out = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
        grid=(Mp // BM, Np // BN),
        in_specs=[
            pl.BlockSpec((BM, Kp), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((Kp, BN), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, BN), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((BM, BN), lambda i, j: (i, j),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
        name=KERNEL_INT8_MATMUL,
    )(xp, wp, sp)
    return out[:M, :N]


def int8_vmem_ok(K: int) -> bool:
    """Whether one grid step's windows (x block + w block int8 + out
    block f32 + scale row, each double-buffered by the pipeline) fit the
    compiler's scoped VMEM for contraction dim ``K`` (kept fully resident
    per step). Sized for f32 activations, the larger of the two dtypes:
    compiled for v5e that kernel is accepted at K=12928, where this
    arithmetic stops, and refused at K=16384 (bf16 activations are still
    accepted at K=20480)."""
    Kp = ((K + KP - 1) // KP) * KP
    need = 2 * (BM * Kp * 4 + Kp * BN * 1 + BM * BN * 4 + BN * 4)
    return need <= VMEM_INT8_BUDGET


def int8_matmul(
    x: jnp.ndarray, q8: jnp.ndarray, scale: jnp.ndarray
) -> jnp.ndarray:
    """Weight-only int8 matmul: ``x [..., K]`` times a quantized weight
    ``q8 [K, N] int8`` with per-channel ``scale [N]``; returns f32
    ``[..., N]``. bfloat16 activations stay bfloat16 into the kernel (no
    f32 copy of them is made); any other float dtype is taken as float32
    and contracted at full f32 precision. Uses the pallas kernel (compiled
    on TPU, interpreted where the probe armed it that way); contraction
    dims past the VMEM budget fall back to the jnp dequant matmul —
    identical numbers, no kernel."""
    lead = x.shape[:-1]
    K = x.shape[-1]
    x2 = x.reshape(-1, K)
    if x2.dtype != jnp.bfloat16:
        x2 = x2.astype(jnp.float32)
    if not int8_vmem_ok(K):
        return reference_int8_matmul(x2, q8, scale).reshape(*lead, q8.shape[1])
    out = _int8_matmul_raw(x2, q8, scale)
    return out.reshape(*lead, q8.shape[1])


# ------------------------------------------------------------------ probe


# (env value, backend) -> (ok, reason); the env is part of the key so a
# test that flips SRT_PALLAS_INT8 re-probes instead of reading a stale
# verdict, the backend because the overlay can ask about another one
_PROBE_CACHE: dict = {}


def _numeric_probe(interpret: bool) -> Optional[str]:
    """Compile (interpret=False) or interpret (True) + validate the
    kernel against the dequant reference; None when they agree. The flag
    is EXPLICIT: the unforced TPU gate must prove the COMPILED kernel —
    letting the interpret fallback answer for it would pass the probe on
    hosts where the real kernel cannot lower. Both activation dtypes the
    kernel takes are held to the f32 product: bfloat16 (what a trunk hands
    over under ``compute_dtype = "auto"`` on a TPU) and float32."""
    r = jax.random.split(jax.random.PRNGKey(0), 2)
    w = jax.random.normal(r[0], (96, 160), jnp.float32) * 0.05
    q8, scale = quantize_int8(w)
    for dtype in (jnp.bfloat16, jnp.float32):
        x = jax.random.normal(r[1], (33, 96), dtype)
        got = jax.jit(
            lambda x_, q_, s_: _int8_matmul_raw(x_, q_, s_, interpret=interpret)
        )(x, q8, scale)
        bad = _probe.mismatch(
            f"{jnp.dtype(dtype).name} x @ dequant(w)", got,
            reference_int8_matmul(x, q8, scale), atol=1e-4, rtol=1e-4,
        )
        if bad:
            return bad
    return None


def int8_probe(backend: Optional[str] = None) -> Tuple[bool, str]:
    """The serving overlay's int8 gate: ``(ok, reason)`` where the
    reason string is exactly what the overlay label carries. The policy
    is the one every kernel shares (``ops/probe.probe``): ``=0`` refuses
    everywhere, ``=1`` probes anywhere (interpret-mode off a TPU, and the
    label says so), unset arms on a TPU only, where the compiled kernel
    must validate or the probe raises. ``backend`` names the backend the
    answer is for; only the process that holds it can prove a kernel."""
    backend = backend or jax.default_backend()
    key = (os.environ.get("SRT_PALLAS_INT8"), backend)
    if key not in _PROBE_CACHE:
        interpret = _INTERPRET or jax.default_backend() != "tpu"
        ok, status = _probe.probe(
            "int8 matmul", "SRT_PALLAS_INT8",
            lambda: _numeric_probe(interpret), interpret, backend,
        )
        _PROBE_CACHE[key] = ok, (
            f"int8 kernel {status} on {backend}" if ok
            else f"int8 kernel {status} — probe refused"
        )
    return _PROBE_CACHE[key]


def int8_matmul_enabled() -> bool:
    """Convenience view of :func:`int8_probe` on the default backend."""
    return int8_probe()[0]
