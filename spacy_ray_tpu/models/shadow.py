"""Which trunk leaves the bf16 parameter shadow covers: one definition.

A trunk stores its layers under ``layer_<i>`` dicts. Each trunk module
registers the names of its layer leaves here (:func:`register_trunk_leaves`,
at import), and everything that has to know them reads this module: the
train loop's ``bf16_shadow`` (``training/loop.py``), the serving precision
overlay (``serving/overlay.py``) and the tests.

* ``SHADOW_LEAF_NAMES``: every weight or bias the layer stack casts to the
  compute dtype each step (matmul operands, the biases added to matmul
  outputs). The shadow holds a bf16 copy of each.
* ``TRUNK_F32_LEAF_NAMES``: layer leaves that stay float32 BY DESIGN (they
  feed float32 ops): norm gains and biases, a router and its selection bias.
* ``INT8_UNSUPPORTED_LEAF_NAMES``: matmul weights the int8 serving overlay
  cannot quantize (they do not flow through its kernel); a tree that holds
  one is refused rather than served under an "int8" label.

A layer leaf in neither of the first two sets is UNKNOWN to the scheme: a
serving overlay refuses the tree rather than ship one it half understands.
"""

from __future__ import annotations

from typing import Any, Callable, Iterable, List, Optional, Tuple

import jax.numpy as jnp

SHADOW_LEAF_NAMES: set = set()
TRUNK_F32_LEAF_NAMES: set = set()
INT8_UNSUPPORTED_LEAF_NAMES: set = set()


def register_trunk_leaves(
    *, shadow: Iterable[str] = (), f32: Iterable[str] = (),
    int8_unsupported: Iterable[str] = (),
) -> None:
    """Add one trunk's layer-leaf names. A name may not be claimed for both
    the shadow and float32: the two trunks share the sets, so a clash would
    silently change the other's coverage."""
    shadow, f32 = set(shadow), set(f32)
    clash = (shadow & (f32 | TRUNK_F32_LEAF_NAMES)) | (f32 & SHADOW_LEAF_NAMES)
    if clash:
        raise ValueError(f"leaf names claimed as both shadow and float32: {sorted(clash)}")
    SHADOW_LEAF_NAMES.update(shadow)
    TRUNK_F32_LEAF_NAMES.update(f32)
    INT8_UNSUPPORTED_LEAF_NAMES.update(int8_unsupported)


def walk_layer_leaves(params: Any, visit: Callable[[str, Any, Tuple[str, ...]], None]) -> None:
    """``visit(name, leaf, path)`` for every leaf under a ``layer_<i>`` dict."""

    def rec(node, in_layer, path):
        for k, v in node.items():
            if isinstance(v, dict):
                rec(v, in_layer or str(k).startswith("layer_"), path + (str(k),))
            elif in_layer:
                visit(str(k), v, path + (str(k),))

    rec(params, False, ())


def shadow_coverage(params) -> "Tuple[int, List[str]]":
    """Audit a param tree against the shadow scheme: returns
    ``(n_eligible, unknown)`` where ``n_eligible`` counts f32 trunk
    leaves :func:`build_param_shadow` would overlay and ``unknown``
    lists the paths of ``layer_i`` leaves in neither SHADOW_LEAF_NAMES
    nor TRUNK_F32_LEAF_NAMES. Non-empty ``unknown`` means the overlay's
    coverage claim would be false for this model — callers fall back to
    f32 with an honest label instead of serving a partial overlay."""
    eligible = 0
    unknown: List[str] = []

    def visit(name, leaf, path):
        nonlocal eligible
        if name in SHADOW_LEAF_NAMES:
            if jnp.asarray(leaf).dtype == jnp.float32:
                eligible += 1
        elif name not in TRUNK_F32_LEAF_NAMES:
            unknown.append("/".join(path))

    walk_layer_leaves(params, visit)
    return eligible, unknown


def build_param_shadow(params, dtype=jnp.bfloat16):
    """Nested sub-tree of ``params`` holding ``dtype`` copies of every
    trunk matmul weight (SHADOW_LEAF_NAMES under a ``layer_i`` dict).

    The train step overlays this shadow onto the f32 master params for the
    forward/backward pass: the layer stack's per-step (and, under remat,
    per-backward) ``astype(compute_dtype)`` of the whole trunk becomes a
    no-op, replaced by ONE incremental refresh of the shadow inside the
    same jitted update (parallel/step.py). Returns None when nothing
    qualifies (no such trunk in the tree)."""

    def rec(node, in_layer):
        out = {}
        for k, v in node.items():
            if isinstance(v, dict):
                sub = rec(v, in_layer or str(k).startswith("layer_"))
                if sub:
                    out[k] = sub
            elif (
                in_layer
                and k in SHADOW_LEAF_NAMES
                and jnp.asarray(v).dtype == jnp.float32
            ):
                out[k] = v.astype(dtype)
        return out

    return rec(params, False) or None


def int8_unsupported_leaves(params) -> "List[str]":
    """Paths of trunk leaves the int8 overlay cannot cover (expert weights,
    the weights of a trunk that does not go through its kernel). Non-empty
    means ``build_int8_overlay`` must not run: the overlay would quantize
    the dense shell of a model whose weight mass lives elsewhere, and the
    label would lie."""
    out: List[str] = []

    def visit(name, leaf, path):
        if name in INT8_UNSUPPORTED_LEAF_NAMES:
            out.append("/".join(path))

    walk_layer_leaves(params, visit)
    return out


# ---- what "auto" compute dtype and the shadow resolve to for a pipeline ----


def _resolve_compute_dtype(name: str):
    """Matmul compute dtype: "auto" picks bfloat16 on accelerators (native
    MXU dtype) and float32 on CPU, where bf16 buys nothing (the matmul
    microbench runs at identical GFLOP/s in both dtypes) and the
    activation/weight casts cost real time (profile_trf.py measured the
    f32 path 15% faster at B=8/T=64 — PERF.md §MFU)."""
    import jax

    if name == "auto":
        return (
            jnp.float32 if jax.default_backend() == "cpu" else jnp.bfloat16
        )
    table = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    if name not in table:
        raise ValueError(
            "compute_dtype must be one of ['auto', 'bfloat16', 'float32'], "
            f"got {name!r}"
        )
    return table[name]


def _trunk_compute_dtypes(nlp) -> List[Any]:
    """The resolved compute dtype of every transformer trunk in the
    pipeline ("auto" depends on the backend), in pipeline order. A trunk
    declares itself by ``meta["compute_dtype_name"]``."""
    out = []
    for comp in nlp.components.values():
        model = getattr(comp, "model", None)
        if model is None:
            continue
        for m in model.walk():
            name = m.meta.get("compute_dtype_name")
            if name:
                out.append(_resolve_compute_dtype(name))
    return out


def pipeline_shadow_dtype(nlp) -> Optional[Any]:
    """bfloat16 when some transformer trunk in the pipeline resolves its
    compute dtype to bf16 (the only case a bf16 shadow is numerics-
    preserving), else None — the ``[training] bf16_shadow = "auto"``
    decision point."""
    return jnp.bfloat16 if jnp.bfloat16 in _trunk_compute_dtypes(nlp) else None


def pipeline_compute_dtype(nlp) -> str:
    """What ``compute_dtype`` resolved to for THIS pipeline's trunks on
    this backend, for the run's records — not what "auto" would give."""
    names = sorted({jnp.dtype(d).name for d in _trunk_compute_dtypes(nlp)})
    return " + ".join(names) or "n/a (no transformer trunk)"
