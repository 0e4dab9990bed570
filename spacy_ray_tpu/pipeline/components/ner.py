"""Named entity recognizer: BILUO transition system (push-down automaton).

Capability parity with spaCy's ``ner`` pipe (BiluoPushDown transition
system over the same nn_parser machinery, SURVEY.md §2.3) as trained by the
reference. TPU-first: the BILUO action at each token depends only on the
token position and the open-entity automaton state, so

* training is one batched window-feature classification over [B, T]
  (teacher-forced gold actions = the BILUO tags — no scan);
* decode precomputes all logits in one matmul and runs only the constraint
  automaton under ``lax.scan`` (models/parser.py ``decode_biluo``).

Action encoding: O=0, B-i=1+4i, I-i=2+4i, L-i=3+4i, U-i=4+4i.
Scores: ``ents_p``/``ents_r``/``ents_f`` (exact-span match, spaCy scorer
semantics) + per-type F.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np
import jax.numpy as jnp

from ...registry import registry
from ...models.core import Context, Params
from ...models.parser import NER_N_FEATURES, decode_biluo, decode_biluo_viterbi, ner_window_features
from ...ops import ops as O
from ...pipeline.doc import Doc, Example, Span
from ...types import Padded
from .base import Component


def n_ner_actions(n_labels: int) -> int:
    return 1 + 4 * n_labels


def biluo_action_id(tag: str, label_ids: Dict[str, int]) -> int:
    if tag == "O" or tag == "-":
        return 0
    prefix, _, label = tag.partition("-")
    i = label_ids.get(label)
    if i is None:  # label outside the initialize()-sampled set: treat as O
        return 0
    return {"B": 1, "I": 2, "L": 3, "U": 4}[prefix] + 4 * i


def action_to_biluo(action: int, labels: List[str]) -> str:
    if action == 0:
        return "O"
    prefix = ["B", "I", "L", "U"][(action - 1) % 4]
    return f"{prefix}-{labels[(action - 1) // 4]}"




class NERComponent(Component):

    default_score_weights = {"ents_f": 1.0, "ents_p": 0.0, "ents_r": 0.0}

    sets_ents = True
    def __init__(self, name, model_cfg, decode: str = "viterbi"):
        super().__init__(name, model_cfg)
        if decode not in ("viterbi", "greedy"):
            raise ValueError(f"ner decode must be viterbi|greedy, got {decode!r}")
        self.decode = decode

    def add_labels_from(self, examples) -> None:
        labels = set(self.labels)
        for eg in examples:
            for span in eg.reference.ents:
                labels.add(span.label)
        self.labels = list(labels)

    def build_model(self):
        cfg = dict(self.model_cfg)
        cfg["nO"] = n_ner_actions(len(self.labels))
        model = registry.resolve(cfg)
        self.model = model
        self.listens = bool(model.meta.get("has_listener"))
        return model

    def make_targets(
        self, examples: List[Example], B: int, Tlen: int, span: Any = None
    ) -> Dict[str, np.ndarray]:
        label_ids = {label: i for i, label in enumerate(self.labels)}
        actions = np.zeros((B, Tlen), dtype=np.int32)
        mask = np.zeros((B, Tlen), dtype=bool)
        lengths = np.zeros(B, dtype=np.int32)  # the padded rows stay empty
        for i, eg in enumerate(examples):
            ref = eg.reference
            n = lengths[i] = min(len(ref), Tlen)
            tags = ref.ents_biluo()
            for t in range(n):
                actions[i, t] = biluo_action_id(tags[t], label_ids)
                mask[i, t] = True
        # a numpy.ndarray in: NumPy out, on this thread, nothing dispatched
        feats = ner_window_features(Tlen, lengths)
        return {"actions": actions, "feats": feats, "ner_mask": mask}

    def loss(self, params: Params, inputs: Any, targets: Dict[str, Any], ctx: Context):
        logits = self.model.apply(params, (inputs, targets["feats"]), ctx)
        loss = O.masked_softmax_cross_entropy(
            logits, targets["actions"], targets["ner_mask"]
        )
        acc = O.masked_accuracy(logits, targets["actions"], targets["ner_mask"])
        return loss, {"ner_action_acc": acc}

    def forward(self, params: Params, inputs: Any, ctx: Context):
        if isinstance(inputs, Padded):
            t2v = inputs
        else:
            tok2vec = self.model.layers[0]
            t2v = tok2vec.apply(params.get("tok2vec", {}), inputs, ctx)
        B, Tlen, _ = t2v.X.shape
        lengths_arr = jnp.sum(t2v.mask.astype(jnp.int32), axis=1)
        feats = ner_window_features(Tlen, lengths_arr)
        fns = self.model.meta["fns"]
        logits = fns.step_logits(params["upper"], t2v.X, feats)
        decode_fn = (
            decode_biluo_viterbi if self.decode == "viterbi" else decode_biluo
        )
        actions = decode_fn(logits, lengths_arr, len(self.labels))
        return {"actions": actions}

    def set_annotations(self, docs: List[Doc], outputs, lengths: List[int]) -> None:
        actions = np.asarray(outputs["actions"])
        for i, doc in enumerate(docs):
            n = lengths[i]
            tags = [action_to_biluo(int(a), self.labels) for a in actions[i, :n]]
            model_ents = Doc.spans_from_biluo(tags)
            if doc.ents:
                # respect entities preset by earlier components (e.g. an
                # entity_ruler placed before ner, spaCy semantics): keep
                # them and add only non-overlapping model entities
                claimed = {j for e in doc.ents for j in range(e.start, e.end)}
                model_ents = [
                    m
                    for m in model_ents
                    if not (set(range(m.start, m.end)) & claimed)
                ]
                doc.ents = sorted(doc.ents + model_ents, key=lambda s: s.start)
            else:
                doc.ents = model_ents

    def score(self, examples: List[Example]) -> Dict[str, float]:
        from ..scoring import score_spans

        # spaCy Scorer.score_spans semantics: docs without gold entity
        # annotation are skipped entirely (predictions there are NOT false
        # positives — Doc.has_ents_annotation carries the DocBin 0-vs-2
        # missing/O distinction); per-type PRF beside the micro scores;
        # None when no gold doc is annotated
        return score_spans(
            examples,
            "ents",
            lambda d: d.ents,
            has_annotation=lambda d: d.has_ents_annotation,
        )


@registry.factories("ner")
def make_ner(name: str, model: Dict[str, Any], decode: str = "viterbi") -> NERComponent:
    return NERComponent(name, model, decode=decode)
