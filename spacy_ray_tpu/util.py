"""Synthetic data generation.

The synthetic corpus generator backs tests and chip_smoke.py (the
reference pulls a fashion-brands NER corpus in bin/get-data.sh; tests
here must run hermetically with zero egress).
"""

from __future__ import annotations

import random
from typing import List

from .pipeline.doc import Doc, Example, Span


# ----------------------------------------------------------------------
# Synthetic corpora
# ----------------------------------------------------------------------

_POS_VOCAB = {
    "DET": ["the", "a", "an", "this", "that"],
    "NOUN": ["cat", "dog", "tree", "market", "chip", "tensor", "mesh", "house"],
    "VERB": ["runs", "jumps", "compiles", "shards", "eats", "sees", "builds"],
    "ADJ": ["green", "fast", "large", "tiny", "sharded", "parallel"],
    "ADV": ["quickly", "slowly", "very", "almost"],
    "PROPN": ["Alice", "Bob", "Jax", "Pallas", "Austin", "Tokyo"],
    "ADP": ["in", "on", "under", "over", "with"],
    "PRON": ["he", "she", "it", "they", "we"],
}

_ENT_LABELS = {
    "PERSON": ["Alice Smith", "Bob Jones", "Carol White"],
    "ORG": ["Acme Corp", "Globex Inc", "Initech LLC"],
    "GPE": ["Austin", "Tokyo", "Berlin", "Paris"],
}


def synth_tagged_doc(rng: random.Random, min_len: int = 4, max_len: int = 24) -> Doc:
    """A doc whose tags are recoverable from word identity (learnable)."""
    n = rng.randint(min_len, max_len)
    words: List[str] = []
    tags: List[str] = []
    pos_names = list(_POS_VOCAB)
    for _ in range(n):
        pos = rng.choice(pos_names)
        words.append(rng.choice(_POS_VOCAB[pos]))
        tags.append(pos)
    return Doc(words=words, tags=tags, pos=list(tags))


def synth_ner_doc(rng: random.Random, min_len: int = 5, max_len: int = 24) -> Doc:
    words: List[str] = []
    ents: List[Span] = []
    n_chunks = rng.randint(2, 6)
    for _ in range(n_chunks):
        if rng.random() < 0.4:
            label = rng.choice(list(_ENT_LABELS))
            ent_words = rng.choice(_ENT_LABELS[label]).split()
            start = len(words)
            words.extend(ent_words)
            ents.append(Span(start, len(words), label))
        else:
            for _ in range(rng.randint(1, 4)):
                pos = rng.choice(list(_POS_VOCAB))
                words.append(rng.choice(_POS_VOCAB[pos]))
    doc = Doc(words=words)
    doc.ents = ents
    return doc


def synth_parsed_doc(rng: random.Random) -> Doc:
    """Template-grammar sentence with a gold projective dependency tree.

    S -> NP VP [PUNCT]; NP -> DET ADJ* NOUN; VP -> VERB [NP] [ADV].
    Heads: DET/ADJ->NOUN, subj NOUN->VERB, obj NOUN->VERB, ADV->VERB,
    VERB=root. Always projective; structure recoverable from word identity.
    """

    words: List[str] = []
    tags: List[str] = []
    heads: List[int] = []
    deps: List[str] = []

    def emit(pos: str, dep: str, head: int = -100) -> int:
        words.append(rng.choice(_POS_VOCAB[pos]))
        tags.append(pos)
        heads.append(head)
        deps.append(dep)
        return len(words) - 1

    def np_() -> int:
        """Append an NP; returns noun index; dependents head to the noun."""
        start = len(words)
        if rng.random() < 0.7:
            emit("DET", "det")
        for _ in range(rng.randint(0, 2)):
            emit("ADJ", "amod")
        noun_i = emit("NOUN", "dep", -200)
        for k in range(start, noun_i):
            heads[k] = noun_i
        return noun_i

    subj = np_()
    verb_i = emit("VERB", "ROOT")
    heads[verb_i] = verb_i  # root: head = self
    heads[subj] = verb_i
    deps[subj] = "nsubj"
    if rng.random() < 0.7:
        obj = np_()
        heads[obj] = verb_i
        deps[obj] = "obj"
    if rng.random() < 0.5:
        i = emit("ADV", "advmod")
        heads[i] = verb_i
    if rng.random() < 0.6:
        words.append(".")
        tags.append("PUNCT")
        heads.append(verb_i)
        deps.append("punct")
    morphs = [f"Cat={t.title()}" for t in tags]
    sent_starts = [1 if i == 0 else -1 for i in range(len(words))]
    return Doc(
        words=words, tags=tags, pos=list(tags), heads=heads, deps=deps,
        morphs=morphs, sent_starts=sent_starts,
    )


def synth_textcat_doc(rng: random.Random) -> Doc:
    label = rng.choice(["SPORTS", "TECH", "FOOD"])
    topical = {
        "SPORTS": ["game", "team", "score", "win", "league", "ball"],
        "TECH": ["chip", "tensor", "compile", "code", "mesh", "kernel"],
        "FOOD": ["eat", "ham", "eggs", "bake", "sauce", "dish"],
    }
    words = [rng.choice(topical[label]) for _ in range(rng.randint(5, 15))]
    rng.shuffle(words)
    doc = Doc(words=words)
    doc.cats = {k: (1.0 if k == label else 0.0) for k in topical}
    return doc


def synth_spancat_doc(rng: random.Random) -> Doc:
    """NER-style doc whose entity spans live in doc.spans["sc"] (spancat
    gold: overlapping/nested spans allowed)."""
    doc = synth_ner_doc(rng)
    doc.spans["sc"] = list(doc.ents)
    doc.ents = []
    return doc


def synth_corpus(
    n_docs: int, kind: str = "tagger", seed: int = 0
) -> List[Example]:
    rng = random.Random(seed)
    makers = {
        "tagger": synth_tagged_doc,
        "ner": synth_ner_doc,
        "textcat": synth_textcat_doc,
        "parser": synth_parsed_doc,
        "spancat": synth_spancat_doc,
    }
    maker = makers[kind]
    return [Example.from_gold(maker(rng)) for _ in range(n_docs)]


def write_synth_jsonl(path, n_docs: int, kind: str = "tagger", seed: int = 0) -> None:
    import json

    from .training.corpus import _doc_to_json

    with open(path, "w", encoding="utf8") as f:
        for eg in synth_corpus(n_docs, kind, seed):
            f.write(json.dumps(_doc_to_json(eg.reference)) + "\n")
