"""Precision-overlay serving: reduced-precision device-resident copies
of the f32 parameter tree, applied once at engine startup.

PR 5 built the mechanism for the TRAINING update path — persistent bf16
copies of the transformer trunk's matmul weights
(``models/transformer.py build_param_shadow``), overlaid onto the f32
masters for the forward pass (``parallel/step.py overlay_shadow``) and
refreshed inside the donated update. Serving has no update: the params
never change, so the overlay is built ONCE and the f32 masters can even
be dropped from the device. This module generalizes the trunk-shadow
extraction out of the training step into that serving shape — the
phase-specific precision placement the adaptive-placement line of work
describes (PAPERS.md: different precision per workload phase, one param
source).

Honesty rules (the same discipline as every pallas kernel claim):

* ``auto`` arms the bf16 overlay ONLY on accelerators. On CPU it
  resolves OFF (f32): XLA CPU *emulates* bf16 by upcasting around every
  elementwise op — PR 5 measured the "saved" casts reappearing as
  emulation converts (PERF.md "Fixed-cost floor", front 2) — so a CPU
  auto-overlay would be a silent pessimization wearing a speedup label.
* An explicit ``bf16`` is honored anywhere (tests and drills need it on
  CPU) but the label says it was forced.
* The overlay is REFUSED — f32 served, refusal in the label — when the
  model has no shadow-eligible trunk leaves, or when a trunk layer
  carries leaves the shadow scheme does not know
  (``shadow_coverage``): a half-covered tree must not ship under a
  "bf16" label.
* ``int8`` is probe-gated like the pallas kernels: it resolves to an
  int8 weight-only overlay only where the pallas dequant-in-kernel
  matmul (ops/int8_matmul.py) compiles AND validates on the current
  backend — auto-armed on TPU, OFF on CPU unless ``SRT_PALLAS_INT8=1``
  forces the interpret-mode kernel (tests, drills), the same auto
  policy shape as bf16. The overlay quantizes the
  trunk's dense matmul weights per-output-channel
  (``models/transformer.py build_int8_overlay``) and REFUSES — f32
  served, refusal in the label — on unknown trunk leaves, trunk-less
  models, or MoE trunks (expert weights are outside the kernel's
  coverage; an "int8" label over mostly-f32 weight mass would lie).

Every refusal/downgrade is also a structured ``log_event`` row, and the
resolved label travels into ``/healthz`` — a reply can never claim a
precision the device is not actually using.
"""

from __future__ import annotations

import logging
from dataclasses import dataclass
from typing import Any, Optional, Tuple

from ..training.resilience import log_event

__all__ = [
    "PRECISION_CHOICES",
    "OverlayResult",
    "resolve_precision",
    "build_params_overlay",
    "build_serving_overlay",
]

logger = logging.getLogger("spacy_ray_tpu.serving")

PRECISION_CHOICES = ("auto", "f32", "bf16", "int8")


@dataclass(frozen=True)
class OverlayResult:
    """What the engine actually serves, with the paper trail attached."""

    requested: str       # the knob as given ("auto" | "f32" | "bf16" | "int8")
    resolved: str        # what the device runs: "f32" | "bf16" | "int8"
    label: str           # honest record label, e.g. "bf16 (overlay: 16 leaves)"
    reason: str          # why resolved != requested, or the auto decision
    params: Any          # the tree predict_docs should consume
    n_overlaid: int      # leaves replaced by reduced-precision copies


def _probe_int8(backend: str) -> Tuple[bool, str]:
    """Int8 serving-kernel probe: defers to ``ops/int8_matmul.int8_probe``
    — compile (or interpret, when forced) + numeric validation of the
    pallas dequant-in-kernel matmul on the current backend, with the
    CPU-auto-OFF / SRT_PALLAS_INT8 force policy. The reason string is
    the label's source of truth: "active (pallas)" only when the kernel
    actually runs."""
    from ..ops.int8_matmul import int8_probe

    return int8_probe(backend)


def resolve_precision(
    requested: str, backend: Optional[str] = None
) -> Tuple[str, str]:
    """Map the requested precision knob to what this backend will run.
    Returns ``(resolved, reason)`` where resolved is "f32", "bf16", or
    "int8" (the last only when the kernel probe passed).

    The auto policy is PR 5's, verbatim: accelerators arm the overlay,
    CPU resolves OFF (emulated bf16 is a measured pessimization there —
    PERF.md). Parity with ``[training] bf16_shadow = "auto"`` is
    test-enforced."""
    if requested not in PRECISION_CHOICES:
        raise ValueError(
            f"precision must be one of {list(PRECISION_CHOICES)}, "
            f"got {requested!r}"
        )
    if backend is None:
        import jax

        backend = jax.default_backend()
    if requested == "f32":
        return "f32", "explicit f32"
    if requested == "bf16":
        if backend == "cpu":
            return "bf16", "forced on cpu (auto would resolve f32 there)"
        return "bf16", f"explicit bf16 on {backend}"
    if requested == "int8":
        ok, why = _probe_int8(backend)
        if not ok:
            return "f32", why
        return "int8", why
    # auto
    if backend == "cpu":
        return "f32", (
            "auto resolves f32 on cpu — XLA CPU emulates bf16 "
            "(measured pessimization, PERF.md fixed-cost floor)"
        )
    return "bf16", f"auto arms bf16 on {backend}"


def build_serving_overlay(nlp, precision: str = "auto") -> OverlayResult:
    """Resolve the precision policy and build the param tree the serving
    engine dispatches with. f32 resolutions return ``nlp.params``
    untouched; bf16 builds the trunk overlay via the training shadow
    extraction (one mechanism, two phases) — or refuses with an honest
    f32 fallback when coverage would be partial."""
    assert nlp.params is not None, "serving overlay needs initialized params"
    return build_params_overlay(nlp.params, precision)


def build_params_overlay(params: Any, precision: str = "auto") -> OverlayResult:
    """The param-tree core of :func:`build_serving_overlay`, callable on
    a bare tree: the engine's hot-swap path re-runs the SAME overlay
    resolution on every incoming checkpoint generation (same requested
    knob, fresh coverage check, honest label preserved), so a swapped-in
    tree can never silently ship at a different precision than the one
    the engine advertised at startup."""
    resolved, reason = resolve_precision(precision)
    if resolved == "f32":
        return OverlayResult(
            requested=precision, resolved="f32",
            label=f"f32 ({reason})" if precision != "f32" else "f32",
            reason=reason, params=params, n_overlaid=0,
        )

    from ..models.shadow import (
        build_param_shadow,
        int8_unsupported_leaves,
        shadow_coverage,
    )
    from ..models.transformer import build_int8_overlay
    from ..parallel.step import overlay_shadow

    def _refuse(reason: str, level: int = logging.INFO, **extra):
        log_event("serving-overlay-refused", reason, level=level, **extra)
        return OverlayResult(
            requested=precision, resolved="f32", label=f"f32 ({reason})",
            reason=reason, params=params, n_overlaid=0,
        )

    eligible, unknown = shadow_coverage(params)
    if unknown:
        return _refuse(
            f"overlay refused: {len(unknown)} trunk leaf(s) unknown to the "
            f"shadow scheme ({', '.join(unknown[:4])}"
            + (", ..." if len(unknown) > 4 else "") + ")",
            level=logging.WARNING,
            unknown=unknown[:16],
        )
    if eligible == 0:
        return _refuse(
            "overlay refused: no shadow-eligible trunk leaves "
            "(no transformer trunk in the pipeline)"
        )
    if resolved == "int8":
        # the int8 kernel covers the dense matmul weights only: a trunk
        # whose FFNs are MoE experts would ship its weight mass f32
        # under an "int8" label — refuse instead (the probe passing is
        # necessary, not sufficient; coverage is per MODEL)
        moe = int8_unsupported_leaves(params)
        if moe:
            return _refuse(
                f"overlay refused: {len(moe)} trunk weight leaf(s) "
                "outside int8 coverage "
                f"({', '.join(moe[:4])}"
                + (", ..." if len(moe) > 4 else "") + ")"
            )
        served, n_q = build_int8_overlay(params)
        label = (
            f"int8 (overlay: {n_q} trunk weights quantized per-channel; "
            f"{reason})"
        )
        log_event(
            "serving-overlay-armed",
            f"serving params carry an int8 weight-only overlay of {n_q} "
            f"trunk weight(s) ({reason})",
            level=logging.INFO,
            leaves=n_q,
            requested=precision,
        )
        return OverlayResult(
            requested=precision, resolved="int8", label=label,
            reason=reason, params=served, n_overlaid=n_q,
        )
    shadow = build_param_shadow(params)
    assert shadow is not None  # eligible > 0 guarantees it
    served = overlay_shadow(params, shadow)
    label = f"bf16 (overlay: {eligible} trunk leaves; {reason})"
    log_event(
        "serving-overlay-armed",
        f"serving params carry a bf16 overlay of {eligible} trunk "
        f"leaf(s) ({reason})",
        level=logging.INFO,
        leaves=eligible,
        requested=precision,
    )
    return OverlayResult(
        requested=precision, resolved="bf16", label=label, reason=reason,
        params=served, n_overlaid=eligible,
    )
