"""Token-to-vector architectures: MultiHashEmbed + CNN window encoder.

These are the registered ``@architectures`` the config files reference — the
same names the reference's configs use for its pipeline models (trained by
reference worker.py:91 ``init_nlp`` → thinc layers; SURVEY.md §2.3 row
"Thinc ops"). Registered under the canonical ``spacy.*`` names so a config
written for the reference resolves unchanged.

TPU notes: the embedding is 4-row murmur gather-sum fused by XLA; the encoder
is depth× [seq2col → maxout → layernorm → residual] where seq2col lowers to
pad+shift slices (no gather), keeping the hot path as three large MXU
matmuls per layer.
"""

from __future__ import annotations

from typing import List, Optional

from ..names import SCOPE_EMBED, SCOPE_TRUNK
from ..registry import registry
from ..ops.hashing import hash_string_u64
from .core import Model, chain, residual, scoped
from .layers import (
    ConcatPadded,
    Dropout,
    HashEmbed,
    LayerNorm,
    Maxout,
    Seq2Col,
)

# Canonical ordering of lexical attributes in TokenBatch.attr_keys
# (pipeline/vocab.py featurizes in this order).
ATTRS = ("NORM", "PREFIX", "SUFFIX", "SHAPE")


def attr_index(attr: str) -> int:
    try:
        return ATTRS.index(attr.upper())
    except ValueError:
        raise ValueError(f"Unknown attr {attr!r}; supported: {ATTRS}")


@registry.architectures("spacy.MultiHashEmbed.v2")
def MultiHashEmbed(
    width: int,
    attrs: Optional[List[str]] = None,
    rows: Optional[List[int]] = None,
    include_static_vectors: bool = False,
) -> Model:
    """Embed tokens by hashing multiple lexical attributes into tables.

    Per attr: HashEmbed(width, rows[i]); concatenated and mixed by a Maxout
    projection to `width` + LayerNorm, matching the capability of the
    reference's embedding stack.
    """
    if attrs is None:
        attrs = list(ATTRS)
    if rows is None:
        rows = [5000] + [2500] * (len(attrs) - 1)
    if len(rows) != len(attrs):
        raise ValueError(f"len(rows) != len(attrs): {rows} vs {attrs}")
    embeds = [
        HashEmbed(
            width,
            int(r),
            seed=hash_string_u64(f"hashembed-{a}-{i}") & 0x7FFFFFFF,
            attr_index=attr_index(a),
            name=f"embed_{a.lower()}",
        )
        for i, (a, r) in enumerate(zip(attrs, rows))
    ]
    n_inputs = len(attrs)
    if include_static_vectors:
        from .layers import StaticVectors

        embeds.append(StaticVectors(width))
        n_inputs += 1
    concat = ConcatPadded(*embeds, name="embeds")
    mix = chain(
        concat,
        Maxout(width * n_inputs, width, nP=3, name="mix"),
        LayerNorm(width),
        name="multi_hash_embed",
    )
    mix.dims.update({"nO": width})
    return scoped(mix, SCOPE_EMBED)


@registry.architectures("spacy.MultiHashEmbed.v1")
def MultiHashEmbedV1(
    width: int,
    rows: int = 7000,
    also_embed_subwords: bool = True,
    also_use_static_vectors: bool = False,
) -> Model:
    """v1 signature adapter: a single row count + subword flag maps onto
    the v2 attr/rows form (NORM at full rows; PREFIX/SUFFIX/SHAPE at half
    when subwords are embedded)."""
    if also_embed_subwords:
        attrs = ["NORM", "PREFIX", "SUFFIX", "SHAPE"]
        row_list = [rows, rows // 2, rows // 2, rows // 2]
    else:
        attrs = ["NORM"]
        row_list = [rows]
    return MultiHashEmbed(
        width,
        attrs=attrs,
        rows=row_list,
        include_static_vectors=also_use_static_vectors,
    )


@registry.architectures("spacy.MaxoutWindowEncoder.v1")
@registry.architectures("spacy.MaxoutWindowEncoder.v2")
def MaxoutWindowEncoder(
    width: int,
    window_size: int = 1,
    maxout_pieces: int = 3,
    depth: int = 4,
) -> Model:
    """depth × residual[seq2col(window) → maxout → layernorm]."""

    def block(i: int) -> Model:
        return residual(
            chain(
                Seq2Col(window_size, width),
                Maxout(width * (2 * window_size + 1), width, nP=maxout_pieces),
                LayerNorm(width),
                name=f"cnn_{i}",
            ),
            name=f"res_{i}",
        )

    layers = [block(i) for i in range(depth)]
    enc = chain(*layers, name="maxout_window_encoder")
    enc.dims.update({"nI": width, "nO": width})
    return scoped(enc, SCOPE_TRUNK)


@registry.architectures("spacy.TorchBiLSTMEncoder.v1")
def TorchBiLSTMEncoder(width: int, depth: int = 2, dropout: float = 0.0) -> Model:
    raise NotImplementedError(
        "BiLSTM encoder is not provided on TPU; use spacy.MaxoutWindowEncoder.v2 "
        "or the transformer backbone (data-dependent recurrence maps poorly to XLA)."
    )


@registry.architectures("spacy.Tok2Vec.v1")
@registry.architectures("spacy.Tok2Vec.v2")
def Tok2Vec(embed: Model, encode: Model) -> Model:
    t2v = chain(embed, encode, name="tok2vec")
    t2v.dims.update({"nO": encode.dims.get("nO", embed.dims.get("nO", 0))})
    return t2v


@registry.architectures("spacy.HashEmbedCNN.v1")
@registry.architectures("spacy.HashEmbedCNN.v2")
def HashEmbedCNN(
    width: int,
    depth: int,
    embed_size: int,
    window_size: int = 1,
    maxout_pieces: int = 3,
    subword_features: bool = True,
    pretrained_vectors: Optional[str] = None,
    dropout: Optional[float] = None,
) -> Model:
    """The standard CNN tok2vec (BASELINE.json config #1's backbone)."""
    attrs = list(ATTRS) if subword_features else ["NORM"]
    rows = [embed_size] + [embed_size // 2] * (len(attrs) - 1)
    embed = MultiHashEmbed(
        width=width, attrs=attrs, rows=rows,
        include_static_vectors=bool(pretrained_vectors),
    )
    layers = [embed]
    if dropout:
        layers.append(Dropout(dropout))
    encode = MaxoutWindowEncoder(
        width=width,
        window_size=window_size,
        maxout_pieces=maxout_pieces,
        depth=depth,
    )
    layers.append(encode)
    t2v = chain(*layers, name="hash_embed_cnn")
    t2v.dims.update({"nO": width})
    return t2v
