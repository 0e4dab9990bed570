"""Span categorizer: ngram span suggester + multilabel span scorer.

Capability parity with spaCy's ``spancat`` pipe (BASELINE.json config #5).
TPU-first: the ngram span grid is STATIC given the padded length — for
sizes (1..k) the candidate set is k slices of the token axis — so span
representations are shifted-slice stacks (mean+max pooled), one batched
matmul scores every candidate, and validity is a mask. No ragged span
lists ever reach the device.

Spans may overlap (multilabel sigmoid, like the reference's spancat).
Scores: ``spans_{key}_f/p/r`` (exact span+label match).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ...registry import registry
from ...models.core import Context, Model, Params, glorot_uniform
from ...ops import ops as O
from ...pipeline.doc import Doc, Example, Span
from ...types import Padded
from .base import Component


@registry.misc("spacy.ngram_suggester.v1")
def ngram_suggester(sizes: List[int]):
    return {"sizes": [int(s) for s in sizes]}


@registry.misc("spacy.ngram_range_suggester.v1")
def ngram_range_suggester(min_size: int = 1, max_size: int = 3):
    """spaCy's range form: all ngram sizes in [min_size, max_size]."""
    if min_size < 1:
        raise ValueError(f"min_size must be >= 1, got {min_size}")
    if max_size < min_size:
        raise ValueError(f"max_size {max_size} < min_size {min_size}")
    return {"sizes": list(range(int(min_size), int(max_size) + 1))}


def span_grid(Tlen: int, sizes: List[int]) -> List[Tuple[int, int]]:
    """Static candidate list [(start, size)] for a padded length."""
    out = []
    for s in sizes:
        for start in range(Tlen - s + 1):
            out.append((start, s))
    return out


def span_reprs(X: jnp.ndarray, sizes: List[int]) -> jnp.ndarray:
    """X [B, T, D] -> [B, n_spans, 2D]: [mean; max] over each ngram span.

    Built from shifted slices (static shapes, no gathers).
    """
    B, Tlen, D = X.shape
    reprs = []
    for s in sizes:
        n = Tlen - s + 1
        if n <= 0:
            continue
        stack = jnp.stack([X[:, k : k + n, :] for k in range(s)], axis=2)
        # [B, n, s, D]
        mean = jnp.mean(stack, axis=2)
        mx = jnp.max(stack, axis=2)
        reprs.append(jnp.concatenate([mean, mx], axis=-1))
    return jnp.concatenate(reprs, axis=1)  # [B, n_spans, 2D]


@registry.architectures("spacy.SpanCategorizer.v1")
def SpanCategorizer(
    tok2vec: Model,
    reducer: Optional[Dict] = None,
    scorer: Optional[Dict] = None,
    suggester: Optional[Dict] = None,
    hidden_size: int = 128,
    nO: Optional[int] = None,
) -> Model:
    width = tok2vec.dims.get("nO")
    n_labels = nO if nO else 1
    sizes = (suggester or {}).get("sizes", [1, 2, 3])

    def init_fn(rng):
        r1, r2, r3 = jax.random.split(rng, 3)
        return {
            "tok2vec": tok2vec.init(r1),
            "hidden_W": glorot_uniform(r2, (2 * width, hidden_size)),
            "hidden_b": jnp.zeros((hidden_size,)),
            "out_W": glorot_uniform(r3, (hidden_size, n_labels)),
            "out_b": jnp.zeros((n_labels,)),
        }

    def apply_fn(params, x, ctx: Context) -> jnp.ndarray:
        t2v: Padded = tok2vec.apply(params.get("tok2vec", {}), x, ctx)
        reprs = span_reprs(t2v.X, sizes)  # [B, n_spans, 2D]
        h = O.gelu(reprs @ params["hidden_W"] + params["hidden_b"])
        return h @ params["out_W"] + params["out_b"]  # [B, n_spans, n_labels]

    has_listener = any(m.meta.get("listener") for m in tok2vec.walk())
    return Model(
        "spancat_model",
        init_fn,
        apply_fn,
        dims={"nO": n_labels, "width": width},
        layers=[tok2vec],
        meta={"has_listener": has_listener, "sizes": sizes},
    )


class SpanCatComponent(Component):
    def __init__(
        self,
        name: str,
        model_cfg: Dict[str, Any],
        spans_key: str = "sc",
        threshold: float = 0.5,
        max_positive: Optional[int] = None,
    ):
        super().__init__(name, model_cfg)
        self.spans_key = spans_key
        self.threshold = threshold
        self.max_positive = max_positive
        # per-instance: the score keys carry the configured spans_key
        self.default_score_weights = {
            f"spans_{spans_key}_f": 1.0,
            f"spans_{spans_key}_p": 0.0,
            f"spans_{spans_key}_r": 0.0,
        }

    def add_labels_from(self, examples) -> None:
        labels = set(self.labels)
        for eg in examples:
            for span in eg.reference.spans.get(self.spans_key, []):
                labels.add(span.label)
        self.labels = list(labels)

    @property
    def sizes(self) -> List[int]:
        assert self.model is not None
        return self.model.meta["sizes"]

    def make_targets(
        self, examples: List[Example], B: int, Tlen: int, span: Any = None
    ) -> Dict[str, np.ndarray]:
        label_ids = {label: i for i, label in enumerate(self.labels)}
        sizes = self.sizes if self.model else [1, 2, 3]
        grid = span_grid(Tlen, sizes)
        grid_index = {sp: i for i, sp in enumerate(grid)}
        n_spans = len(grid)
        n_labels = max(len(self.labels), 1)
        target = np.zeros((B, n_spans, n_labels), dtype=np.float32)
        mask = np.zeros((B, n_spans), dtype=bool)
        for i, eg in enumerate(examples):
            ref = eg.reference
            n = min(len(ref), Tlen)
            for j, (start, size) in enumerate(grid):
                if start + size <= n:
                    mask[i, j] = True
            for span in ref.spans.get(self.spans_key, []):
                size = span.end - span.start
                j = grid_index.get((span.start, size))
                li = label_ids.get(span.label)
                if j is not None and li is not None:
                    target[i, j, li] = 1.0
        return {"span_target": target, "span_mask": mask}

    def loss(self, params: Params, inputs: Any, targets: Dict[str, Any], ctx: Context):
        logits = self.model.apply(params, inputs, ctx)  # [B, n_spans, n_labels]
        loss = O.masked_sigmoid_bce(logits, targets["span_target"], targets["span_mask"])
        return loss, {}

    def forward(self, params: Params, inputs: Any, ctx: Context):
        logits = self.model.apply(params, inputs, ctx)
        return {"probs": jax.nn.sigmoid(logits.astype(jnp.float32))}

    def set_annotations(self, docs: List[Doc], outputs, lengths: List[int]) -> None:
        probs = np.asarray(outputs["probs"])  # [B, n_spans, n_labels]
        grid = span_grid(self._grid_T(probs.shape[1]), self.sizes)
        for i, doc in enumerate(docs):
            n = lengths[i]
            found: List[Span] = []
            for j, (start, size) in enumerate(grid):
                if start + size > n:
                    continue
                # labels over threshold for THIS span, best-first;
                # max_positive limits labels per span (spaCy semantics)
                over = [
                    (float(probs[i, j, li]), label)
                    for li, label in enumerate(self.labels)
                    if probs[i, j, li] >= self.threshold
                ]
                over.sort(reverse=True)
                if self.max_positive:
                    over = over[: self.max_positive]
                for _, label in over:
                    found.append(Span(start, start + size, label))
            doc.spans[self.spans_key] = found

    def _grid_T(self, n_spans: int) -> int:
        """Invert len(span_grid(T, sizes)) = sum(T - s + 1) for T."""
        sizes = self.sizes
        k = len(sizes)
        # n_spans = k*T - sum(sizes) + k  =>  T = (n_spans + sum(sizes) - k) / k
        return (n_spans + sum(sizes) - k) // k

    def score(self, examples: List[Example]) -> Dict[str, float]:
        from ..scoring import score_spans

        key = self.spans_key
        # spaCy semantics: docs without the spans key are skipped (their
        # predictions aren't false positives); key-present-but-empty counts
        return score_spans(
            examples,
            f"spans_{key}",
            lambda d: d.spans.get(key, []),
            has_annotation=lambda d: key in d.spans,
        )


@registry.factories("spancat")
def make_spancat(
    name: str,
    model: Dict[str, Any],
    spans_key: str = "sc",
    threshold: float = 0.5,
    max_positive: Optional[int] = None,
    suggester: Optional[Dict] = None,
) -> SpanCatComponent:
    if suggester is not None:
        # thread the suggester's sizes into the model config block
        model = dict(model)
        model.setdefault("suggester", suggester)
    return SpanCatComponent(
        name, model, spans_key=spans_key, threshold=threshold, max_positive=max_positive
    )
