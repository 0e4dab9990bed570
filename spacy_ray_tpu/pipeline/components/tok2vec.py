"""The shared tok2vec trunk component.

Capability parity with spaCy's ``tok2vec`` pipe: one trunk feeding every
listener-equipped head, gradients summed into the trunk because the whole
pipeline loss is a single differentiable function (the functional version of
the listener backprop hand-off; SURVEY.md §7 "Transformer sharing across
components" — the same wiring serves the transformer trunk).
"""

from __future__ import annotations

from typing import Any, Dict

from ...registry import registry
from ...models.core import Context, Params
from ...types import TokenBatch
from .base import Component


class Tok2VecComponent(Component):
    trainable = False  # no loss of its own; trained via listeners

    def loss(self, params, inputs, targets, ctx):
        raise RuntimeError("tok2vec has no standalone loss")

    def forward(self, params: Params, inputs: TokenBatch, ctx: Context):
        assert self.model is not None
        return self.model.apply(params, inputs, ctx)


@registry.factories("tok2vec")
def make_tok2vec(name: str, model: Dict[str, Any]) -> Tok2VecComponent:
    return Tok2VecComponent(name, model)


@registry.factories("transformer")
def make_transformer(
    name: str, model: Dict[str, Any], max_batch_items: int = 4096
) -> Tok2VecComponent:
    """The shared transformer trunk is a tok2vec-protocol component: heads
    listen to it exactly like the CNN trunk (spacy's `transformer` pipe +
    TransformerListener collapse to the same listener wiring here)."""
    return Tok2VecComponent(name, model)
