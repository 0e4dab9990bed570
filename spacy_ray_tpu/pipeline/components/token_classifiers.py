"""Additional per-token classifier components: morphologizer + senter.

Capability parity with spaCy's ``morphologizer`` and ``senter`` pipes (part
of the pipeline family the reference trains through its config-driven loop;
both are per-token classification heads over the shared tok2vec, like the
tagger). They reuse the tagger machinery with different gold attributes:

* morphologizer: label = "POS|FEATS" combination string (spaCy semantics);
  sets doc.pos and doc.morphs. Score: ``pos_acc``, ``morph_acc``.
* senter: binary sentence-start decisions; sets doc.sent_starts.
  Score: ``sents_f`` (boundary P/R/F over start positions, excluding
  token 0 which is trivially a start).
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np
import jax.numpy as jnp

from ...registry import registry
from ...pipeline.doc import Doc, Example
from .base import Component
from .tagger import TaggerComponent


class MorphologizerComponent(TaggerComponent):

    default_score_weights = {"pos_acc": 0.5, "morph_acc": 0.5}
    @staticmethod
    def _gold_label(doc: Doc, i: int) -> str:
        pos = doc.pos[i] if doc.pos else ""
        morph = doc.morphs[i] if doc.morphs else ""
        if not pos and not morph:
            return ""
        return f"{pos}|{morph}" if morph else pos

    def add_labels_from(self, examples) -> None:
        labels = set(self.labels)
        for eg in examples:
            ref = eg.reference
            if ref.pos or ref.morphs:
                for i in range(len(ref)):
                    label = self._gold_label(ref, i)
                    if label:
                        labels.add(label)
        self.labels = list(labels)

    def make_targets(
        self, examples: List[Example], B: int, T: int, span: Any = None
    ) -> Dict[str, np.ndarray]:
        label_ids = {label: i for i, label in enumerate(self.labels)}
        tags = np.zeros((B, T), dtype=np.int32)
        mask = np.zeros((B, T), dtype=bool)
        for i, eg in enumerate(examples):
            ref = eg.reference
            if not (ref.pos or ref.morphs):
                continue
            for j in range(min(len(ref), T)):
                label = self._gold_label(ref, j)
                if label in label_ids:
                    tags[i, j] = label_ids[label]
                    mask[i, j] = True
        return {"tags": tags, "tag_mask": mask}

    def set_annotations(self, docs: List[Doc], outputs, lengths: List[int]) -> None:
        pred = np.asarray(jnp.argmax(outputs.X, axis=-1))
        for i, doc in enumerate(docs):
            n = lengths[i]
            pos, morphs = [], []
            for t in pred[i, :n]:
                label = self.labels[t] if self.labels else ""
                p, _, m = label.partition("|")
                pos.append(p)
                morphs.append(m)
            doc.pos = pos
            doc.morphs = morphs

    def score(self, examples: List[Example]) -> Dict[str, float]:
        from ..scoring import score_morph_per_feat, score_token_acc

        # spaCy morphologizer surface: pos_acc + morph_acc (exact FEATS
        # string) + morph_per_feat (independent PRF per UD feature); each
        # None when that gold layer is absent everywhere
        out: Dict[str, Any] = {}
        out.update(score_token_acc(examples, "pos_acc", lambda d: d.pos))
        out.update(score_token_acc(examples, "morph_acc", lambda d: d.morphs))
        out.update(score_morph_per_feat(examples))
        return out


class SenterComponent(TaggerComponent):
    """Binary sentence-start classifier. Labels fixed: ["I", "S"]."""

    default_score_weights = {"sents_f": 1.0, "sents_p": 0.0, "sents_r": 0.0}

    def add_labels_from(self, examples) -> None:
        self.labels = ["I", "S"]

    def finish_labels(self) -> None:
        self.labels = ["I", "S"]

    def make_targets(
        self, examples: List[Example], B: int, T: int, span: Any = None
    ) -> Dict[str, np.ndarray]:
        tags = np.zeros((B, T), dtype=np.int32)
        mask = np.zeros((B, T), dtype=bool)
        for i, eg in enumerate(examples):
            ref = eg.reference
            if not ref.sent_starts:
                continue
            for j, s in enumerate(ref.sent_starts[:T]):
                tags[i, j] = 1 if s == 1 else 0
                mask[i, j] = s != 0  # 0 = unannotated
        return {"tags": tags, "tag_mask": mask}

    def set_annotations(self, docs: List[Doc], outputs, lengths: List[int]) -> None:
        pred = np.asarray(jnp.argmax(outputs.X, axis=-1))
        for i, doc in enumerate(docs):
            n = lengths[i]
            starts = [1 if t == 1 else -1 for t in pred[i, :n]]
            if starts:
                starts[0] = 1  # first token always starts a sentence
            doc.sent_starts = starts

    def score(self, examples: List[Example]) -> Dict[str, float]:
        from ..scoring import score_sents

        # spaCy scores sentences as SPANS (both boundaries must match),
        # not per boundary token — Scorer.score_spans over doc.sents
        return score_sents(examples)


@registry.factories("morphologizer")
def make_morphologizer(name: str, model: Dict[str, Any]) -> MorphologizerComponent:
    return MorphologizerComponent(name, model)


@registry.factories("senter")
def make_senter(name: str, model: Dict[str, Any]) -> SenterComponent:
    return SenterComponent(name, model)
