"""Pseudo-UD documents: the benchmark's own copy of the program's
``udgen.synth_ud_corpus`` (original listed in PERF.md, Open questions),
emitting plain dicts in the corpus JSONL format instead of the program's
``Doc`` objects. Two things differ from the original, both so that a traffic
file can fit the documents to a treebank as people convert it for training:
a document has a fixed number of sentences (``spacy convert --n-sents``), and
a sentence is one clause plus a geometric number of coordinated ones, which
sets its mean length.

One document carries tags, heads/deps, sentence starts and entities over a
Zipfian lexicon of ~2,400 word types. The lexicon is fixed; the documents
come from the seed. Parameters (the traffic file's ``docs`` object):

    sents_per_doc    sentences in every document
    more_clauses_p   after each clause, the chance of "and" + one more: a
                     clause has 9.2 words in the mean, a sentence
                     9.2 + 10.2 * p / (1 - p)
    max_tokens       whole sentences are dropped past this length
"""

from __future__ import annotations

import bisect
import random
from typing import Any, Dict, List, Tuple

_CONS = ["b", "d", "f", "g", "k", "l", "m", "n", "p", "r", "s", "t", "v", "z"]
_VOW = ["a", "e", "i", "o", "u"]


def _make_stem(type_id: int, n_syll: int) -> str:
    rng = random.Random(0xC0FFEE ^ type_id)
    return "".join(rng.choice(_CONS) + rng.choice(_VOW) for _ in range(n_syll))


class Lexicon:
    """Per-POS Zipfian lexicons, the same for every seed."""

    def __init__(self) -> None:
        rng = random.Random(1234)

        def types(n: int, n_syll: int, prefix: int) -> List[str]:
            return [_make_stem(prefix * 100000 + i, n_syll) for i in range(n)]

        self.nouns = types(800, 2, 1)
        self.verbs = types(600, 2, 2)
        self.adjs = types(400, 2, 3)
        self.advs = types(200, 3, 4)
        self.dets = ["the", "a", "this", "that", "every"]
        self.adps = ["in", "on", "under", "near", "with", "from"]
        self.cconjs = ["and", "and", "and", "but", "or"]
        self.propn: List[Tuple[List[str], str]] = []
        for i in range(120):
            first = _make_stem(500000 + i, 2).capitalize()
            second = _make_stem(600000 + i, 2).capitalize()
            if i == 7 or rng.random() < 0.02:
                label = "WORK_OF_ART"
            else:
                label = rng.choice(["PERSON", "ORG", "GPE"])
            self.propn.append(([first, second], label))
        self._cums: Dict[int, List[float]] = {}

    def zipf(self, rng: random.Random, items: list) -> Any:
        n = len(items)
        cum = self._cums.get(n)
        if cum is None:
            total, cum = 0.0, []
            for r in range(n):
                total += 1.0 / (r + 1)
                cum.append(total)
            self._cums[n] = cum
        return items[bisect.bisect_left(cum, rng.random() * cum[-1])]


class _Sent:
    def __init__(self) -> None:
        self.words: List[str] = []
        self.tags: List[str] = []
        self.pos: List[str] = []
        self.heads: List[int] = []
        self.deps: List[str] = []
        self.ents: List[Tuple[int, int, str]] = []

    def emit(self, word: str, tag: str, pos: str, dep: str, head: int = -1) -> int:
        self.words.append(word)
        self.tags.append(tag)
        self.pos.append(pos)
        self.heads.append(head)
        self.deps.append(dep)
        return len(self.words) - 1


def _noun(rng: random.Random, lex: Lexicon, s: _Sent, head_slot: int, dep: str) -> Tuple[int, bool]:
    """det + adjs + noun, or a two-word PROPN entity mention. Returns the
    head's index and whether it is singular."""
    if rng.random() < 0.18:
        mention, label = lex.zipf(rng, lex.propn)
        start = len(s.words)
        idxs = [
            s.emit(w, "NNP", "PROPN", "compound" if k < len(mention) - 1 else dep)
            for k, w in enumerate(mention)
        ]
        for k in idxs[:-1]:
            s.heads[k] = idxs[-1]
        s.heads[idxs[-1]] = head_slot
        s.ents.append((start, len(s.words), label))
        return idxs[-1], True
    di = s.emit(rng.choice(lex.dets), "DT", "DET", "det")
    adj_idx = [
        s.emit(lex.zipf(rng, lex.adjs), "JJ", "ADJ", "amod")
        for _ in range(rng.choice([0, 0, 0, 1, 1, 2]))
    ]
    plural = rng.random() < 0.35
    stem = lex.zipf(rng, lex.nouns)
    ni = s.emit(stem + ("s" if plural else ""), "NNS" if plural else "NN",
                "NOUN", dep, head=head_slot)
    for k in [di] + adj_idx:
        s.heads[k] = ni
    return ni, not plural


def _pp(rng: random.Random, lex: Lexicon, s: _Sent, attach_to: int) -> None:
    ci = s.emit(rng.choice(lex.adps), "IN", "ADP", "case")
    ni, _ = _noun(rng, lex, s, attach_to, "nmod")
    s.heads[ci] = ni


def _clause(rng: random.Random, lex: Lexicon, s: _Sent, base: int) -> int:
    """Subject, verb, object and their modifiers; whatever since ``base``
    waits for a head (-2) gets the verb. Returns the verb's index, its own
    head still to be set."""
    extrapose = rng.random() < 0.07  # ~7% non-projective
    subj, third_sg = _noun(rng, lex, s, -2, "nsubj")
    if not extrapose and rng.random() < 0.25:
        _pp(rng, lex, s, subj)
    stem = lex.zipf(rng, lex.verbs)
    if rng.random() < 0.5:
        form, tag = stem + "ed", "VBD"
    elif third_sg:
        form, tag = stem + "s", "VBZ"
    else:
        form, tag = stem, "VBP"
    verb = s.emit(form, tag, "VERB", "ROOT")
    for i in range(base, verb):
        if s.heads[i] == -2:
            s.heads[i] = verb
    if rng.random() < 0.3:
        s.heads[s.emit(lex.zipf(rng, lex.advs), "RB", "ADV", "advmod")] = verb
    _noun(rng, lex, s, verb, "obj")
    if extrapose:
        _pp(rng, lex, s, subj)
    elif rng.random() < 0.2:
        _pp(rng, lex, s, verb)
    return verb


def _sentence(rng: random.Random, lex: Lexicon, s: _Sent, more_clauses_p: float) -> None:
    base = len(s.words)
    if rng.random() < 0.007:  # rare vocative opener
        mention, _ = lex.propn[rng.randrange(len(lex.propn))]
        vi = s.emit(mention[0], "NNP", "PROPN", "vocative", head=-2)
        s.emit(",", ",", "PUNCT", "punct", head=vi)
    root = _clause(rng, lex, s, base)
    s.heads[root] = root
    while rng.random() < more_clauses_p:  # "... and <clause>": cc and conj, UD v2
        base = len(s.words)
        s.emit(rng.choice(lex.cconjs), "CC", "CCONJ", "cc", head=-2)
        verb = _clause(rng, lex, s, base)
        s.heads[verb], s.deps[verb] = root, "conj"
    s.heads[s.emit(".", ".", "PUNCT", "punct")] = root


def one_doc(rng: random.Random, lex: Lexicon, params: Dict[str, Any]) -> Dict[str, Any]:
    n_sents = int(params["sents_per_doc"])
    more_clauses_p = float(params["more_clauses_p"])
    max_tokens = int(params["max_tokens"])
    s = _Sent()
    bounds: List[int] = []
    for _ in range(n_sents):
        mark = len(s.words), len(s.ents)
        _sentence(rng, lex, s, more_clauses_p)
        if len(s.words) > max_tokens and bounds:
            # drop the whole sentence that crossed the cap, and stop
            for name in ("words", "tags", "pos", "heads", "deps"):
                del getattr(s, name)[mark[0]:]
            del s.ents[mark[1]:]
            break
        bounds.append(mark[0])
    sent_starts = [-1] * len(s.words)
    for b in bounds:
        sent_starts[b] = 1
    return {
        "tokens": s.words, "tags": s.tags, "pos": s.pos, "heads": s.heads,
        "deps": s.deps, "sent_starts": sent_starts,
        "ents": [[a, b, label] for a, b, label in s.ents],
    }


def generate(n_docs: int, seed: int, params: Dict[str, Any]) -> List[Dict[str, Any]]:
    """``n_docs`` documents from ``seed``; the same seed gives the same docs."""
    rng = random.Random(seed)
    lex = Lexicon()
    return [one_doc(rng, lex, params) for _ in range(n_docs)]
