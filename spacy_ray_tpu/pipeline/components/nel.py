"""``entity_linker``: disambiguate entity mentions against a knowledge base.

Capability parity with spaCy's ``entity_linker`` pipe (spaCy core surface,
SURVEY.md §2.3; the reference trains whatever components the config names,
reference worker.py:91). The split is TPU-first:

* DEVICE: the only dense math — project tok2vec states into the KB's
  entity-vector space ([B, T, D], models/heads.py EntityLinker arch), and
  at training time pool mention encodings with a cumulative-sum gather
  (O(1) per mention, no ragged loops) and score K padded candidates per
  mention with one einsum. Statically shaped [B, M, K, D] throughout; the
  mention axis M buckets to powers of two to keep recompiles bounded.
* HOST: candidate lookup (a dict hit in pipeline/kb.py, at collation and
  decode), argmax + NIL-threshold decode over a handful of candidates per
  mention, and scoring.

Training uses gold mention spans whose gold KB id appears among the top-K
prior-ranked candidates (spaCy's EL trains the same way); prediction links
whatever ``doc.ents`` an upstream ``ner``/``entity_ruler`` produced earlier
in the same pipeline pass.
"""

from __future__ import annotations

from pathlib import Path
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...models.core import Context, Params
from ...registry import registry
from ...types import Padded
from ..doc import Doc, Example
from ..kb import KnowledgeBase
from .base import Component

NEG = -1e30


def _mention_text(doc: Doc, start: int, end: int) -> str:
    """Canonical surface form for KB alias lookup: space-joined words (the
    same form on the training and decode paths, so priors line up)."""
    return " ".join(doc.words[start:end])


def _bucket_mentions(n: int) -> int:
    m = 2
    while m < n:
        m *= 2
    return m


class EntityLinkerComponent(Component):

    default_score_weights = {"nel_micro_f": 1.0, "nel_micro_p": 0.0, "nel_micro_r": 0.0}

    def __init__(
        self,
        name: str,
        model_cfg: Dict[str, Any],
        *,
        n_candidates: int = 8,
        threshold: float = 0.0,
        use_prior: bool = True,
        use_gold_ents: bool = True,
        kb_path: Optional[str] = None,
    ):
        super().__init__(name, model_cfg)
        self.n_candidates = int(n_candidates)
        self.threshold = float(threshold)
        self.use_prior = bool(use_prior)
        # evaluation seeds prediction shells with gold mention boundaries
        # (spaCy's use_gold_ents) so a linker-only pipeline is evaluable;
        # turn off when an upstream ner should supply the mentions
        self.use_gold_ents = bool(use_gold_ents)
        self.kb_path = kb_path
        self.kb: Optional[KnowledgeBase] = None

    # ------------------------------------------------------------- setup
    def set_kb(self, kb: KnowledgeBase) -> None:
        self.kb = kb

    def add_labels_from(self, examples) -> None:
        # EL has no label set; this initialize hook is where the KB loads
        if self.kb is None and self.kb_path:
            self.kb = KnowledgeBase.from_disk(self.kb_path)

    def build_model(self):
        if self.kb is None and self.kb_path:
            self.kb = KnowledgeBase.from_disk(self.kb_path)
        if self.kb is None:
            raise ValueError(
                f"entity_linker {self.name!r} has no knowledge base: set "
                "kb_path in [components." + self.name + "] or call set_kb() "
                "before initialize"
            )
        self.model_cfg = dict(self.model_cfg)
        self.model_cfg["nO"] = self.kb.entity_vector_length
        return super().build_model()

    # ----------------------------------------------------------- collate
    def _training_mentions(self, eg: Example) -> List[tuple]:
        """(start, end, gold_kb_id) spans to supervise. Gold ents by
        default; with ``use_gold_ents = false`` the mentions an upstream
        ``[training] annotating_components`` ner predicted onto
        ``eg.predicted`` (spaCy's EL-under-annotating-ner training setup;
        reference worker.py:187 threads the list into
        ``train_while_improving``), each supervised by boundary-matching
        against gold — predicted spans with no gold match are skipped. A doc
        with no predicted ents contributes no mentions (spaCy semantics: EL
        with use_gold_ents = false trains on doc.ents as-is)."""
        if self.use_gold_ents:
            return [(s.start, s.end, s.kb_id) for s in eg.reference.ents]
        gold = {(s.start, s.end): s.kb_id for s in eg.reference.ents if s.kb_id}
        return [
            (s.start, s.end, gold.get((s.start, s.end), ""))
            for s in eg.predicted.ents
        ]

    def make_targets(
        self, examples: List[Example], B: int, T: int, span: Any = None
    ) -> Dict[str, np.ndarray]:
        assert self.kb is not None
        K = self.n_candidates
        D = self.kb.entity_vector_length
        per_doc: List[List[tuple]] = []
        m_max = 1
        for eg in examples[:B]:
            rows = []
            for start, end, kb_id in self._training_mentions(eg):
                if not kb_id or end > T or end <= start:
                    continue
                cands = self.kb.candidates(
                    _mention_text(eg.reference, start, end)
                )[:K]
                gold = next(
                    (i for i, c in enumerate(cands) if c.entity == kb_id), None
                )
                if gold is None:
                    continue  # gold entity not reachable through top-K priors
                rows.append((start, end, gold, cands))
            per_doc.append(rows)
            m_max = max(m_max, len(rows))
        M = _bucket_mentions(m_max)
        m_start = np.zeros((B, M), np.int32)
        m_end = np.ones((B, M), np.int32)
        m_mask = np.zeros((B, M), bool)
        gold_idx = np.zeros((B, M), np.int32)
        cand_vecs = np.zeros((B, M, K, D), np.float32)
        cand_mask = np.zeros((B, M, K), bool)
        for i, rows in enumerate(per_doc):
            for j, (s, e, gold, cands) in enumerate(rows[:M]):
                m_start[i, j] = s
                m_end[i, j] = e
                m_mask[i, j] = True
                gold_idx[i, j] = gold
                for k, c in enumerate(cands):
                    cand_vecs[i, j, k] = c.vector
                    cand_mask[i, j, k] = True
        return {
            "nel_start": m_start,
            "nel_end": m_end,
            "nel_mask": m_mask,
            "nel_gold": gold_idx,
            "nel_cand_vecs": cand_vecs,
            "nel_cand_mask": cand_mask,
        }

    # ------------------------------------------------------------ device
    @staticmethod
    def _pool_mentions(X: jnp.ndarray, start: jnp.ndarray, end: jnp.ndarray) -> jnp.ndarray:
        """Mean of X[b, s:e] per mention via a cumulative-sum gather:
        X [B, T, D], start/end [B, M] -> [B, M, D]. No dynamic shapes."""
        B, T, D = X.shape
        csz = jnp.concatenate(
            [jnp.zeros((B, 1, D), X.dtype), jnp.cumsum(X, axis=1)], axis=1
        )  # [B, T+1, D]
        take = lambda idx: jnp.take_along_axis(  # noqa: E731
            csz, idx[..., None].astype(jnp.int32), axis=1
        )
        total = take(end) - take(start)  # [B, M, D]
        length = jnp.maximum((end - start)[..., None], 1).astype(X.dtype)
        return total / length

    def loss(self, params: Params, inputs: Any, targets: Dict[str, Any], ctx: Context):
        proj: Padded = self.model.apply(params, inputs, ctx)
        X = proj.X.astype(jnp.float32)
        enc = self._pool_mentions(X, targets["nel_start"], targets["nel_end"])
        scores = jnp.einsum(
            "bmd,bmkd->bmk", enc, targets["nel_cand_vecs"].astype(jnp.float32)
        )
        scores = jnp.where(targets["nel_cand_mask"], scores, NEG)
        logp = jax.nn.log_softmax(scores, axis=-1)
        nll = -jnp.take_along_axis(logp, targets["nel_gold"][..., None], axis=-1)[..., 0]
        mask = targets["nel_mask"].astype(jnp.float32)
        denom = jnp.maximum(jnp.sum(mask), 1.0)
        loss = jnp.sum(nll * mask) / denom
        acc = jnp.sum((jnp.argmax(logp, -1) == targets["nel_gold"]) * mask) / denom
        return loss, {"nel_acc": acc}

    # ------------------------------------------------------------- host
    def set_annotations(self, docs: List[Doc], outputs: Any, lengths: List[int]) -> None:
        assert self.kb is not None
        X = np.asarray(outputs.X, dtype=np.float32)  # [B, T, D]
        for i, doc in enumerate(docs):
            L = lengths[i]
            for span in doc.ents:
                span.kb_id = ""
                if span.end > L or span.end <= span.start:
                    continue
                cands = self.kb.candidates(
                    _mention_text(doc, span.start, span.end)
                )[: self.n_candidates]
                if not cands:
                    continue
                enc = X[i, span.start : span.end].mean(axis=0)
                scores = np.array([float(enc @ c.vector) for c in cands])
                if self.use_prior:
                    scores = scores + np.log(
                        np.array([c.prior for c in cands]) + 1e-8
                    )
                probs = np.exp(scores - scores.max())
                probs = probs / probs.sum()
                best = int(np.argmax(probs))
                if probs[best] >= self.threshold:
                    span.kb_id = cands[best].entity

    # ------------------------------------------------------- serialization
    # settings travel in components.json; the KB itself is a binary npz
    # sidecar ({name}.kb.npz next to params.npz) — JSON-encoding dense
    # entity vectors would bloat every best-model save
    def table_data(self) -> Dict[str, Any]:
        return {
            "n_candidates": self.n_candidates,
            "threshold": self.threshold,
            "use_prior": self.use_prior,
            "use_gold_ents": self.use_gold_ents,
        }

    def load_table_data(self, data: Dict[str, Any]) -> None:
        self.n_candidates = int(data.get("n_candidates", self.n_candidates))
        self.threshold = float(data.get("threshold", self.threshold))
        self.use_prior = bool(data.get("use_prior", self.use_prior))
        self.use_gold_ents = bool(data.get("use_gold_ents", self.use_gold_ents))

    def save_binary(self, path, name: str) -> None:
        assert self.kb is not None
        self.kb.to_disk(Path(path) / f"{name}.kb.npz")

    def load_binary(self, path, name: str) -> None:
        kb_file = Path(path) / f"{name}.kb.npz"
        if kb_file.exists():
            self.kb = KnowledgeBase.from_disk(kb_file)

    def score(self, examples: List[Example]) -> Dict[str, float]:
        """Micro P/R/F over non-NIL links (spaCy's nel_micro_* semantics:
        a link is correct when a predicted span with the same boundaries
        carries the same KB id)."""
        tp = fp = fn = 0
        for eg in examples:
            gold = {
                (s.start, s.end): s.kb_id for s in eg.reference.ents if s.kb_id
            }
            pred = {
                (s.start, s.end): s.kb_id for s in eg.predicted.ents if s.kb_id
            }
            for key, kb_id in pred.items():
                if gold.get(key) == kb_id:
                    tp += 1
                else:
                    fp += 1
            for key, kb_id in gold.items():
                if pred.get(key) != kb_id:
                    fn += 1
        p = tp / (tp + fp) if tp + fp else 0.0
        r = tp / (tp + fn) if tp + fn else 0.0
        f = 2 * p * r / (p + r) if p + r else 0.0
        return {
            "nel_micro_p": p,
            "nel_micro_r": r,
            "nel_micro_f": f,
            "nel_score": f,
        }


@registry.factories("entity_linker")
def make_entity_linker(
    name: str,
    model: Dict[str, Any],
    n_candidates: int = 8,
    threshold: float = 0.0,
    use_prior: bool = True,
    use_gold_ents: bool = True,
    kb_path: Optional[str] = None,
) -> EntityLinkerComponent:
    return EntityLinkerComponent(
        name,
        model,
        n_candidates=n_candidates,
        threshold=threshold,
        use_prior=use_prior,
        use_gold_ents=use_gold_ents,
        kb_path=kb_path,
    )
