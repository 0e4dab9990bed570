"""Trunk agreement at the widths the cell runs: the program's own trunk
forward with the cell's parameters on a few seeded sequences, against the
configuration's plain reference (``benchmark/reference/<config>.py``). A few
seconds of set-up-side work, outside the timed window."""

from __future__ import annotations

from contextlib import nullcontext
from typing import Any, Dict, List

from common import load_module

N_SEQUENCES = 8


def check(nlp: Any, params: Any, config_name: str,
          docs: List[Dict[str, Any]]) -> Dict[str, Any]:
    """``params``: the float32 tree both sides compute with; ``docs``: the
    seeded sequences, as the cell's generator makes them."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from spacy_ray_tpu.models.core import Context
    from spacy_ray_tpu.models.tok2vec import ATTRS
    from spacy_ray_tpu.ops import hashing
    from spacy_ray_tpu.ops.hashing import hash_string_u64
    from spacy_ray_tpu.pipeline.doc import Example
    from spacy_ray_tpu.training.corpus import _doc_from_json

    reference = load_module("reference", config_name)
    name = nlp.tok2vec_name
    trunk = nlp.components[name]
    examples = [Example.from_gold(_doc_from_json(d)) for d in docs]
    tokens = nlp.collate(examples, with_targets=False)["tokens"]
    mask = np.asarray(tokens.mask)

    def system(precision: Any) -> Any:
        # a new jit for each precision: the context is read when it traces
        forward = jax.jit(lambda p, t: trunk.forward(p, t, Context(train=False)).X)
        scope = jax.default_matmul_precision(precision) if precision else nullcontext()
        with scope:
            return np.asarray(forward(params[name], tokens), np.float32)

    # the program's hashing gives the row ids; the reference starts there
    master = jax.tree_util.tree_map(np.asarray, params[name])
    embeds = (master.get("embed") or master["0_multi_hash_embed"])["0_embeds"]
    ids: List[Any] = []
    for i, table in enumerate(sorted(embeds)):
        attr = table.split("_")[-1].upper()
        table_seed = hash_string_u64(f"hashembed-{attr}-{i}") & 0x7FFFFFFF
        rows = embeds[table]["E"].shape[0]
        ids.append(hashing.hash_embed_ids(
            jnp.asarray(tokens.attr_keys)[..., ATTRS.index(attr), :], table_seed, rows))
    want = np.asarray(reference.forward(
        master, ids, jnp.asarray(mask), trunk.model.dims.get("n_heads")), np.float32)

    real = mask[..., None]

    def rel_err(got: Any) -> float:
        return float(np.max(np.abs(got - want) * real) / np.max(np.abs(want) * real))

    on_cpu = jax.default_backend() == "cpu"
    precision = reference.SYSTEM_MATMUL_PRECISION
    err = rel_err(system(precision))
    tolerance = reference.TOLERANCE_F32 if on_cpu else reference.TOLERANCE
    out = {"rel_err": err, "tolerance": tolerance, "ok": bool(err <= tolerance),
           "sequences": len(docs), "tokens": int(mask.sum()),
           "compute": "float32" if on_cpu else reference.COMPUTE_DTYPE_ON_TPU}
    if precision is not None:  # and as the program trains it, at the default precision
        out["rel_err_as_trained"] = rel_err(system(None))
        out["ok"] = bool(out["ok"] and out["rel_err_as_trained"] <= reference.TOLERANCE_AS_TRAINED)
    return out
