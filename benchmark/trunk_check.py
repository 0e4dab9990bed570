"""Trunk agreement at the widths the cell runs: the program's own trunk
forward with the cell's parameters on a few seeded sequences, against the
configuration's plain reference (``benchmark/reference/<config>.py``). A few
seconds of set-up-side work, outside the timed window.

The reference owns its inputs. Where it defines ``make_inputs(nlp, master,
tokens)``, what that returns is handed to ``reference.forward`` after the
trunk's float32 tree: ``forward(master, *make_inputs(...))``. Where it does
not, the inputs are MultiHashEmbed's row ids, the mask and the number of heads.

Where the reference declares ``GRAD_TOLERANCE`` (``GRAD_TOLERANCE_F32`` on the
CPU), the gradients are compared too: of ``sum(mask * X * R)``, R normal from
the run's seed, with respect to the trunk's float32 tree; through the
program's own forward under ``jax.grad`` (its kernels' backward, its remat,
its scan) against ``jax.grad`` of ``reference.forward``. The error of a leaf
is max |difference| over max |reference| of that leaf or of the median leaf,
whichever is larger (a leaf whose gradient is all but nought would otherwise
be judged on its rounding); the worst leaf has to meet the tolerance. R is an
ARGUMENT of both losses, never a constant they close over: the gradient
program's text is then the same for every seed, one entry of the compile
cache for all runs of a cell (closed over, each run compiled a new program
of 40 MiB that nothing read again; PERF.md section 6, PR 32).
"""

from __future__ import annotations

from contextlib import nullcontext
from typing import Any, Dict, List, Tuple

from common import load_module

N_SEQUENCES = 8


def hash_inputs(nlp: Any, master: Any, tokens: Any) -> Tuple[Any, ...]:
    """The program's hashing gives the row ids; the reference starts there."""
    import jax.numpy as jnp

    from spacy_ray_tpu.models.tok2vec import ATTRS
    from spacy_ray_tpu.ops import hashing
    from spacy_ray_tpu.ops.hashing import hash_string_u64

    embeds = (master.get("embed") or master["0_multi_hash_embed"])["0_embeds"]
    ids: List[Any] = []
    for i, table in enumerate(sorted(embeds)):
        attr = table.split("_")[-1].upper()
        table_seed = hash_string_u64(f"hashembed-{attr}-{i}") & 0x7FFFFFFF
        rows = embeds[table]["E"].shape[0]
        ids.append(hashing.hash_embed_ids(
            jnp.asarray(tokens.attr_keys)[..., ATTRS.index(attr), :], table_seed, rows))
    trunk = nlp.components[nlp.tok2vec_name]
    return ids, jnp.asarray(tokens.mask), trunk.model.dims.get("n_heads")


def gradient_errors(got: Any, want: Any) -> Dict[str, Any]:
    """Leaf by leaf, max |got - want| over max |want| of that leaf or of the
    median leaf, whichever is larger; the worst leaf and its path. Beside it,
    reported and held to nothing, the worst gap between the two norms of a
    leaf over the reference's norm of that leaf or of the median leaf: a hard
    max below a leaf (a maxout) flips under bfloat16 operands and moves single
    entries far and the norm hardly (PERF.md, section 6, PR 26)."""
    import jax
    import numpy as np

    wants = jax.tree_util.tree_leaves_with_path(want)
    gots = [np.asarray(g, np.float32) for g in jax.tree_util.tree_leaves(got)]
    if len(gots) != len(wants):
        raise ValueError(f"the gradients have {len(gots)} and {len(wants)} leaves")
    sizes = [float(np.max(np.abs(w))) for _, w in wants]
    norms = [float(np.linalg.norm(w)) for _, w in wants]
    floor, norm_floor = float(np.median(sizes)), float(np.median(norms))
    worst = {"grad_rel_err": 0.0, "grad_worst_leaf": None}
    norm_gap = 0.0
    for (path, w), g, size, norm in zip(wants, gots, sizes, norms):
        err = float(np.max(np.abs(g - w))) / max(size, floor)
        if err >= worst["grad_rel_err"]:
            worst = {"grad_rel_err": err, "grad_worst_leaf": jax.tree_util.keystr(path)}
        norm_gap = max(norm_gap, abs(float(np.linalg.norm(g)) - norm) / max(norm, norm_floor))
    return {**worst, "grad_norm_gap": norm_gap, "grad_leaves": len(wants),
            "grad_median_leaf_max": floor}


def check(nlp: Any, params: Any, config_name: str,
          docs: List[Dict[str, Any]], seed: int = 0) -> Dict[str, Any]:
    """``params``: the float32 tree both sides compute with; ``docs``: the
    seeded sequences, as the cell's generator makes them; ``seed``: the run's,
    for the cotangent of the gradient comparison."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from spacy_ray_tpu.models.core import Context
    from spacy_ray_tpu.pipeline.doc import Example
    from spacy_ray_tpu.training.corpus import _doc_from_json

    reference = load_module("reference", config_name)
    name = nlp.tok2vec_name
    trunk = nlp.components[name]
    examples = [Example.from_gold(_doc_from_json(d)) for d in docs]
    tokens = nlp.collate(examples, with_targets=False)["tokens"]
    mask = np.asarray(tokens.mask)
    real = mask[..., None]

    def trunk_forward(p: Any, t: Any) -> Any:
        return trunk.forward(p, t, Context(train=False)).X

    def system(fn: Any, precision: Any, *more: Any) -> Any:
        # a new jit for each precision: the context is read when it traces
        scope = jax.default_matmul_precision(precision) if precision else nullcontext()
        with scope:
            return jax.tree_util.tree_map(lambda a: np.asarray(a, np.float32),
                                          jax.jit(fn)(params[name], tokens, *more))

    master = jax.tree_util.tree_map(np.asarray, params[name])
    inputs = getattr(reference, "make_inputs", hash_inputs)(nlp, master, tokens)
    want = np.asarray(reference.forward(master, *inputs), np.float32)

    def rel_err(got: Any) -> float:
        return float(np.max(np.abs(got - want) * real) / np.max(np.abs(want) * real))

    on_cpu = jax.default_backend() == "cpu"
    precision = reference.SYSTEM_MATMUL_PRECISION
    err = rel_err(system(trunk_forward, precision))
    tolerance = reference.TOLERANCE_F32 if on_cpu else reference.TOLERANCE
    out = {"rel_err": err, "tolerance": tolerance, "ok": bool(err <= tolerance),
           "sequences": len(docs), "tokens": int(mask.sum()),
           "compute": "float32" if on_cpu else reference.COMPUTE_DTYPE_ON_TPU}
    if precision is not None:  # and as the program trains it, at the default precision
        out["rel_err_as_trained"] = rel_err(system(trunk_forward, None))
        out["ok"] = bool(out["ok"] and out["rel_err_as_trained"] <= reference.TOLERANCE_AS_TRAINED)

    grad_tolerance = getattr(reference, "GRAD_TOLERANCE_F32" if on_cpu else "GRAD_TOLERANCE", None)
    if grad_tolerance is not None:
        cotangent = jnp.asarray(
            np.random.default_rng(seed).standard_normal(want.shape).astype(np.float32) * real)

        def system_loss(p: Any, t: Any, r: Any) -> Any:
            return jnp.sum(trunk_forward(p, t).astype(jnp.float32) * r)

        def reference_loss(p: Any, r: Any) -> Any:
            return jnp.sum(reference.forward(p, *inputs) * r)

        got = system(jax.grad(system_loss), precision, cotangent)
        wanted = jax.tree_util.tree_map(
            lambda a: np.asarray(a, np.float32),
            jax.grad(reference_loss)(jax.tree_util.tree_map(jnp.asarray, master), cotangent))
        out.update(gradient_errors(got, wanted), grad_tolerance=grad_tolerance)
        out["ok"] = bool(out["ok"] and out["grad_rel_err"] <= grad_tolerance)
    return out


def compared(trunk: Dict[str, Any], config_name: str) -> Dict[str, List[Any]]:
    """Each number ``check`` held to a limit, beside that limit."""
    out = {"trunk_rel_err": [trunk["rel_err"], trunk["tolerance"]]}
    if "rel_err_as_trained" in trunk:
        limit = load_module("reference", config_name).TOLERANCE_AS_TRAINED
        out["trunk_rel_err_as_trained"] = [trunk["rel_err_as_trained"], limit]
    if "grad_rel_err" in trunk:
        out["trunk_grad_rel_err"] = [trunk["grad_rel_err"], trunk["grad_tolerance"]]
    return out
