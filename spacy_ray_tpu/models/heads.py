"""Per-component head architectures: tagger, textcat, morphologizer-style.

Registered under the canonical ``spacy.*`` architecture names used by the
configs the reference trains (reference worker.py:91 resolves these via
spacy's registry; SURVEY.md §5.6). Heads consume the tok2vec output
(:class:`Padded`) either from an inline tok2vec sublayer or from the shared
upstream component via ``spacy.Tok2VecListener.v1`` (the listener/upstream
sharing pattern — SURVEY.md §7 "Transformer sharing across components").
"""

from __future__ import annotations

from typing import Any, Optional

import jax.numpy as jnp

from ..registry import registry
from ..ops import ops as O
from ..types import Padded, TokenBatch
from .core import Context, Model, chain, glorot_uniform
from .layers import Linear


@registry.architectures("spacy.Tok2VecListener.v1")
def Tok2VecListener(width: int, upstream: str = "*") -> Model:
    """Placeholder layer standing in for the shared tok2vec component.

    The pipeline feeds the upstream component's Padded output directly into
    any head whose model tree contains a listener (pipeline/language.py wires
    this; gradient flows back into the shared trunk because the whole
    pipeline loss is one jitted function — the functional equivalent of
    spaCy's listener backprop hand-off).
    """

    def init_fn(rng):
        return {}

    def apply_fn(params, x: Padded, ctx: Context) -> Padded:
        if not isinstance(x, Padded):
            raise TypeError(
                "Tok2VecListener expected the upstream tok2vec output (Padded); "
                "did the pipeline forget to run the shared tok2vec?"
            )
        return x

    return Model(
        "tok2vec_listener",
        init_fn,
        apply_fn,
        dims={"nO": width},
        meta={"listener": True, "upstream": upstream},
    )


def _has_listener(model: Model) -> bool:
    return any(m.meta.get("listener") for m in model.walk())


@registry.architectures("spacy.Tagger.v1")
@registry.architectures("spacy.Tagger.v2")
def Tagger(tok2vec: Model, nO: Optional[int] = None, normalize: bool = False) -> Model:
    """Softmax tagger head: tok2vec → linear(nO). Loss/decode live in the
    component (pipeline/components/tagger.py)."""
    width = tok2vec.dims.get("nO")
    if nO is None:
        # Resolution happens again at Pipeline.initialize() with label count
        # injected; constructing with nO=1 placeholder is never trained.
        nO = 1
    head = chain(tok2vec, Linear(width, nO, name="output"), name="tagger_model")
    head.dims.update({"nO": nO, "width": width})
    head.meta["has_listener"] = _has_listener(tok2vec)
    return head


@registry.architectures("spacy.TextCatReduce.v1")
def TextCatReduce(
    tok2vec: Model,
    nO: Optional[int] = None,
    exclusive_classes: bool = False,
    use_reduce_first: bool = False,
    use_reduce_last: bool = False,
    use_reduce_max: bool = True,
    use_reduce_mean: bool = True,
) -> Model:
    """Doc classifier: tok2vec → masked pooling (mean/max/first/last concat)
    → linear(nO). Sigmoid vs softmax is applied by the component depending on
    ``exclusive_classes``."""
    width = tok2vec.dims.get("nO")
    n_pools = sum([use_reduce_first, use_reduce_last, use_reduce_max, use_reduce_mean])
    if n_pools == 0:
        raise ValueError("TextCatReduce: enable at least one reduction")
    if nO is None:
        nO = 1

    def init_fn(rng):
        import jax

        r1, r2 = jax.random.split(rng)
        return {
            "tok2vec": tok2vec.init(r1),
            "W": glorot_uniform(r2, (width * n_pools, nO)),
            "b": jnp.zeros((nO,)),
        }

    def apply_fn(params, x: Any, ctx: Context) -> jnp.ndarray:
        # .get: a listener tok2vec has no params and is pruned from the tree
        h: Padded = tok2vec.apply(params.get("tok2vec", {}), x, ctx)
        pools = []
        mask = h.mask
        if use_reduce_first:
            first = h.X[:, 0, :]
            pools.append(first)
        if use_reduce_last:
            lengths = jnp.maximum(jnp.sum(mask.astype(jnp.int32), axis=1) - 1, 0)
            last = jnp.take_along_axis(h.X, lengths[:, None, None], axis=1)[:, 0, :]
            pools.append(last)
        if use_reduce_max:
            pools.append(O.max_pool(h.X, mask))
        if use_reduce_mean:
            pools.append(O.mean_pool(h.X, mask))
        feats = jnp.concatenate(pools, axis=-1)
        return feats @ params["W"] + params["b"]

    m = Model(
        "textcat_model",
        init_fn,
        apply_fn,
        dims={"nO": nO, "width": width},
        layers=[tok2vec],
        meta={
            "has_listener": _has_listener(tok2vec),
            "exclusive_classes": exclusive_classes,
        },
    )
    return m


@registry.architectures("spacy.TextCatBOW.v2")
@registry.architectures("spacy.TextCatBOW.v3")
def TextCatBOW(
    exclusive_classes: bool = False,
    ngram_size: int = 1,
    no_output_layer: bool = False,
    nO: Optional[int] = None,
    length: int = 262144,
) -> Model:
    """Hashed n-gram bag-of-words classifier (spaCy's sparse linear
    textcat, the default fast architecture). No tok2vec: consumes the
    TokenBatch directly — each unigram (and bigram, for ngram_size >= 2)
    hashes to a row of a [length, nO] weight table; the doc score is the
    mean of its n-gram rows. TPU-shaped as a masked gather + segment sum
    (no sparse ops needed).

    ``nO`` may be left unset (the stock spaCy config shape): the output
    dim is read from ``dims`` at INIT time, so a wrapping TextCatEnsemble
    or the owning component fills it in before params exist — spaCy's
    dim-inference, without a second resolution pass."""
    n = max(int(ngram_size), 1)
    dims = {"nO": nO}  # None until a parent fills it; read lazily below

    def init_fn(rng):
        out = dims.get("nO") or 1
        # sparse-linear convention: start at zero so untouched rows stay
        # exactly neutral (a random init would inject noise per rare ngram)
        return {"W": jnp.zeros((length, out)), "b": jnp.zeros((out,))}

    def apply_fn(params, tokens: TokenBatch, ctx: Context) -> jnp.ndarray:
        # NORM hash halves (collate attr order: NORM first)
        lo = tokens.attr_keys[:, :, 0, 0].astype(jnp.uint32)  # [B, T]
        hi = tokens.attr_keys[:, :, 0, 1].astype(jnp.uint32)
        mask = tokens.mask
        L = jnp.uint32(length)
        nO_now = params["W"].shape[-1]
        scores = jnp.zeros((lo.shape[0], nO_now), jnp.float32)
        count = jnp.zeros((lo.shape[0], 1), jnp.float32)
        prev = (lo ^ (hi >> jnp.uint32(1)))
        gram_mask = mask
        for k in range(n):
            if k > 0:
                # roll in the next token's hash for (k+1)-grams
                nxt_lo = jnp.roll(lo, -k, axis=1)
                prev = prev * jnp.uint32(2654435761) + nxt_lo
                gram_mask = gram_mask & jnp.roll(mask, -k, axis=1)
                gram_mask = gram_mask.at[:, -k:].set(False)
            idx = (prev % L).astype(jnp.int32)  # [B, T]
            rows = params["W"][idx]  # [B, T, nO]
            m = gram_mask.astype(jnp.float32)[..., None]
            scores = scores + jnp.sum(rows * m, axis=1)
            count = count + jnp.sum(m, axis=1)
        return scores / jnp.maximum(count, 1.0) + params["b"]

    return Model(
        "textcat_bow",
        init_fn,
        apply_fn,
        dims=dims,
        meta={"has_listener": False, "exclusive_classes": exclusive_classes},
    )


@registry.architectures("spacy.TextCatEnsemble.v2")
def TextCatEnsemble(
    tok2vec: Model,
    linear_model: Model,
    nO: Optional[int] = None,
) -> Model:
    """spaCy's default textcat: a neural (tok2vec + pooling) classifier
    summed with a sparse linear (BOW) classifier."""
    if _has_listener(tok2vec):
        raise ValueError(
            "spacy.TextCatEnsemble.v2 needs an INLINE tok2vec here: its "
            "linear_model reads raw token features, which a listener-fed "
            "head never receives. Put a full tok2vec block under "
            "[components.textcat.model.tok2vec] instead of a listener."
        )
    neural = TextCatReduce(tok2vec, nO=nO)
    if nO is None:
        nO = neural.dims["nO"]
    lm_nO = linear_model.dims.get("nO")
    if lm_nO is None:
        # stock spaCy config shape: the linear block omits nO — fill the
        # label count in before init creates its params
        linear_model.dims["nO"] = nO
    elif lm_nO != nO:
        raise ValueError(
            f"TextCatEnsemble: linear_model nO={lm_nO} != {nO} labels — "
            "omit nO in the [linear_model] block to inherit the label count"
        )

    def init_fn(rng):
        import jax

        r1, r2 = jax.random.split(rng)
        return {"neural": neural.init(r1), "linear": linear_model.init(r2)}

    def apply_fn(params, x: Any, ctx: Context) -> jnp.ndarray:
        c1, c2 = ctx.split()
        a = neural.apply(params.get("neural", {}), x, c1)
        b = linear_model.apply(params.get("linear", {}), x, c2)
        return a + b

    return Model(
        "textcat_ensemble",
        init_fn,
        apply_fn,
        dims={"nO": nO},
        layers=[neural, linear_model],
        meta={
            # listener tok2vecs are rejected above, so never a listener
            "has_listener": False,
            "exclusive_classes": neural.meta.get("exclusive_classes", False),
        },
    )


@registry.architectures("spacy.TextCatCNN.v2")
def TextCatCNN(
    tok2vec: Model,
    exclusive_classes: bool = False,
    nO: Optional[int] = None,
) -> Model:
    """CNN tok2vec + mean pooling + linear — spaCy's TextCatCNN surface,
    expressed through TextCatReduce."""
    return TextCatReduce(
        tok2vec,
        nO=nO,
        exclusive_classes=exclusive_classes,
        use_reduce_max=False,
        use_reduce_mean=True,
    )


@registry.architectures("spacy.EntityLinker.v1")
@registry.architectures("spacy.EntityLinker.v2")
def EntityLinker(tok2vec: Model, nO: Optional[int] = None) -> Model:
    """Entity-linking encoder: tok2vec → linear projection into the KB's
    entity-vector space. Mention pooling, candidate scoring, and decode live
    in the component (pipeline/components/nel.py) — the projection is the
    only dense compute, so it is all that runs on device."""
    width = tok2vec.dims.get("nO")
    if nO is None:
        # Re-resolved at Pipeline.initialize() with the KB's
        # entity_vector_length injected; nO=1 placeholder is never trained.
        nO = 1
    head = chain(tok2vec, Linear(width, nO, name="project"), name="entity_linker_model")
    head.dims.update({"nO": nO, "width": width})
    head.meta["has_listener"] = _has_listener(tok2vec)
    return head
