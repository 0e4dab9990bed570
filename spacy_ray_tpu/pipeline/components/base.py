"""Pipeline component protocol.

The functional counterpart of spaCy's ``TrainablePipe`` components that the
reference trains (reference worker.py:91 ``init_nlp`` builds them;
worker.py:176-189 ``nlp.update`` runs them; SURVEY.md §2.3 row "spaCy
core"). Split cleanly across the host/device boundary:

* host: label collection at initialize, target collation to padded arrays,
  annotation decode, scoring;
* device: a pure ``loss(params, inputs, targets, ctx)`` and pure
  ``forward(params, inputs, ctx)``, both jit-traceable.

Components are created from config blocks by ``@registry.factories``
factories (the ``factory = "tagger"`` key in ``[components.tagger]``).
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional, Tuple

import jax
import numpy as np

from ...models.core import Context, Model, Params
from ...pipeline.doc import Doc, Example
from ...registry import registry


class Component:
    """Base class; subclasses override the protocol methods."""

    #: does this component's model contain a Tok2VecListener?
    listens: bool = False
    #: does this component WRITE doc.ents at prediction time? (gates
    #: use_gold_ents seeding in evaluate: gold mention boundaries are only
    #: safe to seed when nothing upstream produces mentions itself)
    sets_ents: bool = False
    #: does this component produce a trainable loss?
    trainable: bool = True
    #: default [training] score weights contributed by this component when
    #: the config declares none — spaCy's per-factory default_score_weights
    #: metadata (combined and normalized in the loop, spacy
    #: util.combine_score_weights semantics). Keys are OUR emitted score
    #: keys; 0.0 marks a score that's reported but unweighted.
    default_score_weights: Dict[str, float] = {}

    def __init__(self, name: str, model_cfg: Dict[str, Any]):
        self.name = name
        self.model_cfg = dict(model_cfg)
        self.model: Optional[Model] = None
        self.labels: List[str] = []

    # -------------------------- initialize ---------------------------
    def add_labels_from(self, examples: Iterable[Example]) -> None:
        """Collect the label set from gold data (host, once)."""

    def finish_labels(self) -> None:
        self.labels = sorted(set(self.labels))

    def build_model(self) -> Model:
        """Resolve the model config block (with nO injected) into a Model."""
        cfg = dict(self.model_cfg)
        if self.labels and "nO" in self._label_dim_keys():
            cfg["nO"] = len(self.labels)
            # any direct sub-block that explicitly declares `nO = null`
            # shares the component's output dim (spaCy fills these by dim
            # inference at init — e.g. TextCatEnsemble's linear_model);
            # here the label count is known before resolution
            for key, sub in list(cfg.items()):
                if (
                    isinstance(sub, dict)
                    and "@architectures" in sub
                    and "nO" in sub
                    and sub["nO"] is None
                ):
                    sub = dict(sub)
                    sub["nO"] = len(self.labels)
                    cfg[key] = sub
        model = registry.resolve(cfg)
        if not isinstance(model, Model):
            raise TypeError(f"[components.{self.name}.model] did not resolve to a Model")
        self.model = model
        self.listens = bool(model.meta.get("has_listener"))
        return model

    def _label_dim_keys(self) -> Tuple[str, ...]:
        return ("nO",)

    def init_params(self, rng: jax.Array) -> Params:
        assert self.model is not None, "build_model() first"
        from ...models.core import prune_empty

        return prune_empty(self.model.init(rng))

    # --------------------------- collate -----------------------------
    def make_targets(
        self, examples: List[Example], B: int, T: int, span: Any = None
    ) -> Dict[str, np.ndarray]:
        """Lower gold annotations to padded arrays for the device loss.

        ``span`` is the caller's open span of this head
        (``PipelineStats.timer``; ``NO_SPAN`` where nothing is recorded).
        An implementation that leaves the host (an eager ``jnp`` call, a
        copy back from the device) wraps that call in
        ``span.child(names.DEVICE_CALL)``: on the chip such a call queues
        behind the running step, and the span is how that wait is seen.
        Pure host work ignores it, and no shipped head opens it (NER's
        window features are NumPy: ``models/parser.ner_window_features``)."""
        return {}

    # ---------------------------- device -----------------------------
    def loss(
        self,
        params: Params,
        inputs: Any,
        targets: Dict[str, Any],
        ctx: Context,
    ):
        """Pure loss: returns (scalar loss, metrics dict). jit-traced."""
        raise NotImplementedError

    def forward(self, params: Params, inputs: Any, ctx: Context):
        """Pure forward for prediction. jit-traced."""
        assert self.model is not None
        return self.model.apply(params, inputs, ctx)

    # ----------------------------- host ------------------------------
    def set_annotations(self, docs: List[Doc], outputs: Any, lengths: List[int]) -> None:
        """Decode device outputs into doc annotations."""

    def score(self, examples: List[Example]) -> Dict[str, float]:
        return {}
