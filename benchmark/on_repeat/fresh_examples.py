"""What a mix's documents do when the run passes its corpus's end: the first
pass is a run's with no augmenter, and every later pass works out again what
the first did.

The loop keeps what it worked out for a document ON its ``Example`` (the
parser's oracle, the tagger's targets, the word features), and a cached
``Corpus`` hands the same ``Example`` objects out again each epoch, so a second
pass over a corpus collates many times faster than the first and is another
workload. ``fresh_examples`` is an augmenter for ``[corpora.train.augmenter]``
that changes no word. While nothing has been kept on a document's ``Example``
it hands out that very object, as ``Corpus`` does when no augmenter is set and
as the augmenters the program ships (``spacy.orth_variants.v1``,
``spacy.lower_case.v1``) do for every document they leave alone: the first
pass is the parent's to the object, and what it keeps it keeps for the run.
Once the loop has kept anything on it (any attribute beside the dataclass's
own fields: the rule names no memo), the document comes out as a fresh
``Example`` round the same gold document, as those augmenters hand out for
every document they rewrite; its targets are worked out again and go when its
batch is done. The corpus then reports ``augmented``, which ``correct`` holds
it to, and ``correct`` holds every later pass's rate against the first's
(``train_cell.corpus_rule``): a memo that moved somewhere this rule does not
see would show there.

A traffic mix asks for it by this file's name (``"docs": {"on_repeat":
"fresh_examples"}``). ``train_cell.py`` calls ``register`` with the program's
registry before ``train`` and lays ``overrides`` over the configuration.
Another way to treat a repeated document is another file here.
"""

import dataclasses
from typing import Any, Callable, Dict, Iterator

AUGMENTER = "bench.fresh_examples.v1"


def fresh_examples() -> Callable[[Any], Iterator[Any]]:
    from spacy_ray_tpu.pipeline.doc import Example

    own = {field.name for field in dataclasses.fields(Example)}

    def augment(eg: Any) -> Iterator[Any]:
        yield eg if vars(eg).keys() <= own else Example.from_gold(eg.reference)

    return augment


def register(registry: Any) -> None:
    registry.augmenters(AUGMENTER, fresh_examples)


def overrides(config: Dict[str, Any]) -> Dict[str, Any]:
    """``config``: the program's configuration with the mix's own overrides
    applied."""
    return {"corpora.train.augmenter": {"@augmenters": AUGMENTER}}
