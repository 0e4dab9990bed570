"""Dependency parser component (arc-eager, teacher-forced training).

Capability parity with spaCy's ``parser`` pipe trained by the reference
(reference worker.py:91/176-189; SURVEY.md §2.3 "spaCy core", §7 hard part
#1). Training lowers each gold tree to a precomputed (actions, state
features, valid masks) grid host-side (pipeline/transition.py) — the device
loss is one batched classification over the doc×step grid. Decode runs the
arc-eager machine under ``lax.scan`` on device (models/parser.py).

Scores: UAS/LAS (``dep_uas``/``dep_las``), matching spaCy's scorer keys for
the parity targets in BASELINE.md.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np
import jax
import jax.numpy as jnp

from ... import native
from ...registry import registry
from ...models.core import Context, Params
from ...models.parser import decode_parser, decode_parser_beam
from ...pipeline import nonproj
from ...pipeline import transition as T
from ...pipeline.doc import Doc, Example
from ...types import Padded, TokenBatch
from .base import Component


class ParserComponent(Component):

    default_score_weights = {"dep_uas": 0.5, "dep_las": 0.5}

    def __init__(self, name, model_cfg, beam_width: int = 1):
        super().__init__(name, model_cfg)
        self.beam_width = int(beam_width)
        # collation-time oracle accounting (reported by debug-data and the
        # CLI train summary; the reference's spaCy stack handles these docs
        # via pseudo-projective lifting, nonproj.pyx — silent drops capped
        # LAS with no diagnostic, VERDICT r1 #5)
        # "native" / "python": the documents whose targets each form of
        # transition.gold_oracle worked out (a memo hit counts as neither)
        self.oracle_stats = {
            "docs": 0, "projectivized": 0, "skipped": 0, "native": 0, "python": 0,
        }
        # the library is built and loaded here, while the pipeline is set
        # up, not by the first batch of a timed window
        native.load()
        # make_targets may run concurrently on collation-pool workers
        # ([training] collate_workers): counter merges must be atomic
        import threading

        self._stats_lock = threading.Lock()
        self._warned_skip = False

    def add_labels_from(self, examples) -> None:
        labels = set(self.labels)
        for eg in examples:
            ref = eg.reference
            if ref.deps:
                labels.update(d for d in ref.deps if d)
                if ref.heads:
                    # decorated labels produced by pseudo-projective lifting
                    # must be in the inventory before the action space is
                    # sized (they are real LEFT/RIGHT-ARC labels at train
                    # and decode time)
                    res = nonproj.projectivize(ref.heads, ref.deps)
                    if res is not None and res[2] > 0:
                        labels.update(
                            l for l in res[1] if nonproj.is_decorated(l)
                        )
        self.labels = list(labels)

    def build_model(self):
        cfg = dict(self.model_cfg)
        cfg["nO"] = T.n_actions(len(self.labels))
        model = registry.resolve(cfg)
        self.model = model
        self.listens = bool(model.meta.get("has_listener"))
        return model

    # ------------------------------------------------------------------
    def make_targets(
        self, examples: List[Example], B: int, Tlen: int, span: Any = None
    ) -> Dict[str, np.ndarray]:
        label_ids = {label: i for i, label in enumerate(self.labels)}
        n_act = T.n_actions(len(self.labels))
        S = 2 * Tlen + 2
        actions = np.zeros((B, S), dtype=np.int32)
        feats = np.full((B, S, T.N_FEATURES), -1, dtype=np.int32)
        valid = np.zeros((B, S, n_act), dtype=bool)
        step_mask = np.zeros((B, S), dtype=bool)
        # per-call counters, merged under the lock at the end: this method
        # runs concurrently on collation-pool worker threads
        batch_stats = dict.fromkeys(self.oracle_stats, 0)
        oracle_ran = "python" if native.load() is None else "native"
        labels_sig = tuple(self.labels)
        for i, eg in enumerate(examples):
            ref = eg.reference
            if not ref.heads or not ref.deps or len(ref) > Tlen:
                continue
            # oracle simulation is the collation hot path: memoize per
            # Example (the corpus reuses Example objects across epochs).
            # The key hashes the gold annotations so an augmenter mutating
            # reference heads/deps in place can never serve a stale oracle.
            memo_key = (labels_sig, hash((tuple(ref.heads), tuple(ref.deps))))
            cached = getattr(eg, "_oracle_cache", None)
            if cached is not None and cached[0] == memo_key:
                kept, lifted = cached[1]
                out = kept
                if isinstance(kept, np.ndarray):
                    out = (kept, *T.replay(kept, len(ref), len(self.labels)))
            else:
                res = nonproj.projectivize(ref.heads, ref.deps)
                if res is None:  # malformed tree (cycle / bad head index)
                    out, lifted = None, 0
                else:
                    proj_heads, deco_deps, lifted = res
                    ids = [label_ids.get(d) for d in deco_deps]
                    if None in ids:
                        # a decorated combo outside the label-sample window
                        # falls back to its undecorated base label (still
                        # supervises the arc; the decoration just isn't
                        # recoverable) rather than training against an
                        # arbitrary id
                        ids = [
                            label_ids.get(nonproj.decompose_label(d)[0], 0)
                            if label_id is None else label_id
                            for label_id, d in zip(ids, deco_deps)
                        ]
                    out = T.gold_oracle(proj_heads, ids, len(self.labels))
                    batch_stats[oracle_ran] += 1
                # of the native oracle's answer a memo keeps the actions
                # (1.3 kB a document) and replays the rows they determine
                # (44 kB) on a hit, natively: a corpus's worth of rows is
                # gigabytes that the first epoch has to find, which costs
                # more than working them out again. The Python's answer is
                # kept whole: its replay would be the Python state machine
                kept = out
                if out is not None and oracle_ran == "native":
                    kept = out[0].astype(np.int32)
                try:
                    eg._oracle_cache = (memo_key, (kept, lifted))
                except AttributeError:
                    pass
            batch_stats["docs"] += 1
            if lifted:
                batch_stats["projectivized"] += 1
            if out is None:  # oracle-unreachable even after lifting: skip
                batch_stats["skipped"] += 1
                if not self._warned_skip:
                    import sys

                    print(
                        f"[{self.name}] warning: dropped a doc whose gold tree "
                        "is unusable even after pseudo-projective lifting; "
                        "run debug-data for corpus-wide counts",
                        file=sys.stderr,
                    )
                    self._warned_skip = True
                continue
            acts, f, v = out
            s = min(len(acts), S)
            actions[i, :s] = acts[:s]
            feats[i, :s] = f[:s]
            valid[i, :s] = v[:s]
            step_mask[i, :s] = True
        with self._stats_lock:
            for key, count in batch_stats.items():
                self.oracle_stats[key] += count
        return {
            "actions": actions,
            "feats": feats,
            "valid": valid,
            "step_mask": step_mask,
        }

    def oracle_report(self) -> Dict[str, Any]:
        """Which form of the oracle this process runs, and how many
        documents' targets each has worked out so far (the loop's
        ``resolved["parser_oracle"]``, docs/OBSERVABILITY.md)."""
        with self._stats_lock:
            counts = {key: self.oracle_stats[key] for key in ("native", "python")}
        return {"path": T.oracle_path(), **counts}

    # ------------------------------------------------------------------
    def loss(self, params: Params, inputs: Any, targets: Dict[str, Any], ctx: Context):
        logits = self.model.apply(params, (inputs, targets["feats"]), ctx)
        NEG = jnp.float32(-1e9)
        masked_logits = jnp.where(targets["valid"], logits, NEG)
        logp = jax.nn.log_softmax(masked_logits.astype(jnp.float32), axis=-1)
        gold = jax.nn.one_hot(targets["actions"], logits.shape[-1], dtype=jnp.float32)
        ce = -jnp.sum(gold * logp, axis=-1)
        mask_f = targets["step_mask"].astype(jnp.float32)
        loss = jnp.sum(ce * mask_f) / jnp.maximum(jnp.sum(mask_f), 1.0)
        pred = jnp.argmax(masked_logits, axis=-1)
        acc = jnp.sum((pred == targets["actions"]) * mask_f) / jnp.maximum(
            jnp.sum(mask_f), 1.0
        )
        return loss, {"parse_action_acc": acc}

    # ------------------------------------------------------------------
    def forward(self, params: Params, inputs: Any, ctx: Context):
        fns = self.model.meta["fns"]
        if isinstance(inputs, Padded):
            t2v = inputs
            if not self.listens:
                raise TypeError("parser got Padded input but has its own tok2vec")
        else:
            tok2vec = self.model.layers[0]
            t2v = tok2vec.apply(params.get("tok2vec", {}), inputs, ctx)
        lengths = jnp.sum(t2v.mask.astype(jnp.int32), axis=1)
        if self.beam_width > 1:
            heads, labels = decode_parser_beam(
                fns, params["upper"], t2v.X, lengths, len(self.labels),
                self.beam_width,
            )
        else:
            heads, labels = decode_parser(
                fns, params["upper"], t2v.X, lengths, len(self.labels)
            )
        return {"heads": heads, "labels": labels}

    def set_annotations(self, docs: List[Doc], outputs, lengths: List[int]) -> None:
        heads = np.asarray(outputs["heads"])
        labels = np.asarray(outputs["labels"])
        for i, doc in enumerate(docs):
            n = lengths[i]
            doc.heads = [int(h) for h in heads[i, :n]]
            doc.deps = [
                self.labels[l] if self.labels else "dep" for l in labels[i, :n]
            ]
            # undo pseudo-projective lifting: decorated labels point back to
            # the original attachment site (must run BEFORE the ROOT rewrite,
            # which would erase the decoration)
            if any(nonproj.is_decorated(d) for d in doc.deps):
                doc.heads, doc.deps = nonproj.deprojectivize(doc.heads, doc.deps)
            # ROOT-attached tokens (head == self) get the root label
            for j in range(n):
                if doc.heads[j] == j:
                    doc.deps[j] = "ROOT"

    def score(self, examples: List[Example]) -> Dict[str, float]:
        from ..scoring import score_deps

        # spaCy Scorer.score_deps semantics: gold-punct tokens excluded
        # from UAS/LAS, labels compared lowercased, None when no gold parse
        return score_deps(examples)


@registry.factories("parser")
def make_parser(
    name: str, model: Dict[str, Any], beam_width: int = 1
) -> ParserComponent:
    return ParserComponent(name, model, beam_width=beam_width)
