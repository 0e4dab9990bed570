"""A run may pass its corpus's end where its mix says what its documents do
``on_repeat``: ``benchmark/on_repeat/fresh_examples.py`` through the program's
own ``Corpus``, registry and ``collate`` (the first pass hands out the corpus's
own ``Example`` objects, as a run with no augmenter does; every later pass works
out again what the first did), and the rule of ``correct`` that goes with it
(``train_cell.corpus_rule``). CPU, rehearsal widths. Runs on
a CPU: ``pytest benchmark/tests``."""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))

import common  # noqa: E402
import train_cell  # noqa: E402

N_DOCS = 24
MEMOS = ("_oracle_cache", "_tag_target_cache", "_feat_cache")
ASKING = ["ewt10_b28k", "ewt10_b3k5", "ewt10_b64k", "ewt10_b7k"]


@pytest.fixture(scope="module")
def fresh():
    from spacy_ray_tpu.registry import registry

    module = common.load_module("on_repeat", "fresh_examples")
    module.register(registry)
    return module


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """sm at the rehearsal's widths over ``N_DOCS`` seeded documents in a file,
    as a cell's run has them."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from spacy_ray_tpu.config import load_config
    from spacy_ray_tpu.pipeline.language import Pipeline
    from spacy_ray_tpu.training.corpus import Corpus

    config_file = json.loads((BENCH / "configs" / "sm.json").read_text())
    spec = json.loads((BENCH / "traffic" / "ewt10_b64k.json").read_text())["docs"]
    path = tmp_path_factory.mktemp("corpus") / "train.jsonl"
    common.write_jsonl(path, common.load_module("generators", spec["generator"]).generate(
        N_DOCS, 2147400001, spec))
    config = load_config(BENCH.parent / config_file["program_config"],
                         config_file.get("rehearse_overrides", {}), interpolate=False)
    nlp = Pipeline.from_config(config)
    nlp.initialize(Corpus(path), seed=5)
    return nlp, path


def three_passes(nlp, corpus, monkeypatch):
    """What each of three epochs had to work out: calls of the parser's
    oracle, and documents that came without each memo."""
    from spacy_ray_tpu.pipeline import transition

    calls = []
    real_oracle = transition.gold_oracle
    monkeypatch.setattr(transition, "gold_oracle",
                        lambda *a, **k: calls.append(1) or real_oracle(*a, **k))
    passes = []
    for _ in range(3):
        before = len(calls)
        examples = list(corpus())
        missing = {memo: sum(getattr(eg, memo, None) is None for eg in examples) for memo in MEMOS}
        for i in range(0, len(examples), 8):
            nlp.collate(examples[i:i + 8])
        assert all(getattr(eg, memo, None) is not None for eg in examples for memo in MEMOS)
        passes.append(dict(missing, oracle_calls=len(calls) - before, docs=len(examples)))
    return passes


def test_every_pass_with_fresh_examples_works_out_what_the_first_did(rehearsal, fresh, monkeypatch):
    from spacy_ray_tpu.registry import registry

    nlp, path = rehearsal
    corpus = registry.resolve({"@readers": "spacy.Corpus.v1", "path": str(path), "shuffle": True,
                               "augmenter": {"@augmenters": fresh.AUGMENTER}})
    assert corpus.augmented and corpus.cache
    passes = three_passes(nlp, corpus, monkeypatch)
    everything = {"docs": N_DOCS, "oracle_calls": N_DOCS, **{memo: N_DOCS for memo in MEMOS}}
    assert passes == [everything] * 3
    assert sum(p["oracle_calls"] for p in passes) == 3 * N_DOCS


def test_without_it_the_second_pass_finds_the_first_ones_targets_kept(rehearsal, monkeypatch):
    from spacy_ray_tpu.training.corpus import Corpus

    nlp, path = rehearsal
    corpus = Corpus(path, shuffle=True)
    assert not corpus.augmented
    first, second, third = three_passes(nlp, corpus, monkeypatch)
    assert first["oracle_calls"] == N_DOCS and first["_feat_cache"] == N_DOCS
    nothing = {"docs": N_DOCS, "oracle_calls": 0, **{memo: 0 for memo in MEMOS}}
    assert second == third == nothing


def test_the_first_pass_hands_out_the_corpus_own_examples_and_a_repeated_one_fresh(rehearsal, fresh):
    """While nothing is kept on a document's ``Example`` it comes out as that
    very object (the parent's first pass, to the object); once the loop has
    kept anything on it, as a new shell round the same gold document, no word
    changed, and the corpus's own keeps what the first pass kept."""
    from spacy_ray_tpu.training.corpus import Corpus

    nlp, path = rehearsal
    kept = Corpus(path)
    corpus = Corpus(path, augmenter=fresh.fresh_examples())
    own = list(corpus())
    assert all(a is b for a, b in zip(own, corpus()))  # nothing kept yet: the same objects again
    assert [eg.reference.words for eg in own] == [eg.reference.words for eg in kept()]
    nlp.collate(own[:8])  # the loop keeps the first eight documents' targets
    one, two = list(corpus()), list(corpus())
    assert [a is b for a, b in zip(own, one)] == [False] * 8 + [True] * (N_DOCS - 8)
    for eg, a, b in zip(own[:8], one, two):
        assert a is not b and a.reference is b.reference is eg.reference
        assert a.predicted is not b.predicted and a.predicted.words == eg.reference.words
        assert all(getattr(eg, memo, None) is not None for memo in MEMOS)
        assert all(getattr(x, memo, None) is None for x in (a, b) for memo in MEMOS)


def test_an_attribute_the_rule_has_never_heard_of_makes_the_next_one_fresh(fresh):
    """The rule names no memo: anything beside the dataclass's own fields."""
    from spacy_ray_tpu.pipeline.doc import Doc, Example

    augment = fresh.fresh_examples()
    eg = Example.from_gold(Doc(words=["a", "b"], spaces=[True, False]))
    assert next(augment(eg)) is eg
    eg._kept_by_a_later_pr = ()
    again = next(augment(eg))
    assert again is not eg and again.reference is eg.reference and vars(again).keys() == {
        "predicted", "reference"}


def test_the_overrides_set_the_augmenter_and_leave_the_batcher_as_configured(fresh):
    from spacy_ray_tpu.config import load_config
    from spacy_ray_tpu.registry import registry

    config_file = json.loads((BENCH / "configs" / "sm.json").read_text())
    config = load_config(BENCH.parent / config_file["program_config"],
                         {"training.batcher.size": 64000}, interpolate=False)
    batcher = dict(config["training"]["batcher"])
    assert set(fresh.overrides(config)) == {"corpora.train.augmenter"}
    config = config.apply_overrides(fresh.overrides(config))
    assert config["corpora"]["train"]["augmenter"] == {"@augmenters": "bench.fresh_examples.v1"}
    assert config["training"]["batcher"] == batcher and batcher["size"] == 64000
    assert callable(registry.resolve(config["corpora"]["train"]["augmenter"]))


@pytest.mark.parametrize("mix", ASKING)
def test_the_ewt10_mixes_ask_for_fresh_examples(mix):
    docs = json.loads((BENCH / "traffic" / f"{mix}.json").read_text())["docs"]
    assert docs["on_repeat"] == "fresh_examples"


def test_a_mix_that_says_on_repeat_names_a_file():
    for path in sorted((BENCH / "traffic").glob("*.json")):
        on_repeat = json.loads(path.read_text())["docs"].get("on_repeat")
        if on_repeat is not None:  # a mix that does not say may not pass its corpus's end
            module = common.load_module("on_repeat", on_repeat)
            assert callable(module.register) and callable(module.overrides), path.name


class StubCorpus:
    def __init__(self, augmented):
        self.augmented = augmented


@pytest.mark.parametrize("counted,on_repeat,corpus,refused", [
    (900, None, StubCorpus(False), None),
    (1000, None, StubCorpus(False), None),
    (1001, None, StubCorpus(False), "an epoch repeated"),
    (1001, None, StubCorpus(True), "an epoch repeated"),  # it did not ask: the rule stands
    (2500, "fresh_examples", StubCorpus(True), None),
    (900, "fresh_examples", StubCorpus(True), None),
    (900, "fresh_examples", StubCorpus(False), "does not report `augmented`"),
    (2500, "fresh_examples", StubCorpus(False), "does not report `augmented`"),
    (2500, "fresh_examples", None, "does not report `augmented`"),  # the loop resolved none
], ids=["short", "whole", "repeated", "repeated_unasked", "passes", "asked_short",
        "asked_not_augmented", "passes_not_augmented", "no_corpus"])
def test_correct_and_the_corpus_end(counted, on_repeat, corpus, refused):
    problems, compared = train_cell.corpus_rule(counted, 1000, on_repeat, corpus, {})
    if refused is None:
        assert problems == []
    else:
        assert len(problems) == 1 and refused in problems[0]
    assert compared["words_taken_of_corpus"] == [counted, 1000]
    assert compared["corpus_passes"] == [counted / 1000, None if on_repeat else 1.0]
    assert compared["corpus_hands_out_fresh_examples"] == [
        bool(getattr(corpus, "augmented", False)), on_repeat is not None]
    assert compared["later_pass_rate_over_first"] == [None, train_cell.LATER_PASS_OVER_FIRST]


def passes_at(*rates, steps=40):
    return {n: {"steps": steps, "words": 1000 * steps, "seconds": 1000 * steps / rate,
                "words_per_s": rate} for n, rate in enumerate(rates, start=1)}


@pytest.mark.parametrize("by_pass,on_repeat,over_first,refused", [
    (passes_at(29093.9, 29665.1), "fresh_examples", 29665.1 / 29093.9, False),  # the chip's +2.0%
    (passes_at(26500.0, 390000.0), "fresh_examples", 390000.0 / 26500.0, True),  # the memo's pace
    (passes_at(26500.0, 27000.0, 41000.0), "fresh_examples", 41000.0 / 26500.0, True),  # the worst counts
    (passes_at(26500.0, 39000.0), "fresh_examples", 39000.0 / 26500.0, False),
    (passes_at(26500.0, 24000.0), "fresh_examples", 24000.0 / 26500.0, False),  # slower is not this rule's
    (passes_at(26500.0), "fresh_examples", None, False),
    ({2: passes_at(1.0, 90000.0)[2], 3: passes_at(1.0, 1.0, 95000.0)[3]}, "fresh_examples", None, False),
    ({**passes_at(26500.0), 2: passes_at(1.0, 390000.0, steps=2)[2]}, "fresh_examples", None, False),
    ({**passes_at(1.0, 390000.0), 1: passes_at(26500.0, steps=2)[1]}, "fresh_examples", None, False),
    (passes_at(26500.0, 390000.0), None, 390000.0 / 26500.0, False),  # words-against-corpus is that mix's rule
], ids=["chip_fresh", "memo", "worst_of_two", "under_the_limit", "slower", "one_pass", "no_first_pass",
        "short_later_pass", "short_first_pass", "not_asked"])
def test_correct_holds_a_later_pass_to_the_first_ones_rate(by_pass, on_repeat, over_first, refused):
    problems, compared = train_cell.corpus_rule(900, 1000, on_repeat, StubCorpus(True), by_pass)
    assert compared["later_pass_rate_over_first"] == [over_first, train_cell.LATER_PASS_OVER_FIRST]
    if refused:
        assert len(problems) == 1 and "found targets kept" in problems[0]
        assert f"{over_first:.3f}" in problems[0]
    else:
        assert problems == []


def test_rates_by_pass_by_hand():
    # 100 words a step, a step a second; from the fifth on, two steps a second
    calls = [0, 1, 2, 3, 4, 4.5, 5.0, 5.5]
    steps = [{"words": 100, "t_call": t} for t in calls]
    out = train_cell.rates_by_pass(steps, 1, 8, 450)
    # steps 2, 3, 4 begin in the first pass (200, 300, 400 words taken), 5-7 in the second
    assert out[1] == {"steps": 3, "words": 300, "seconds": 3.0, "words_per_s": 100.0}
    assert out[2] == {"steps": 3, "words": 300, "seconds": 1.5, "words_per_s": 200.0}
    steps[4]["cut"] = True  # that call compiled and was blocked on: the interval after it goes
    out = train_cell.rates_by_pass(steps, 1, 8, 450)
    assert out[2]["steps"] == 2 and out[2]["seconds"] == 1.0 and out[1]["steps"] == 3


def test_the_first_five_blocked_steps_are_left_out_of_the_step_time():
    assert train_cell.QUEUED_AHEAD == 5
    spy = train_cell.StepSpy(seconds=30, warm_steps=5, warm_seconds=5, trace_dir=None,
                             trace_seconds=10, words_fifo=None, compile_count=None,
                             loop_thread_compiles=None, stats_handles=[])
    spy.blocked_done_at = [10.0, 10.7, 11.4, 12.1, 12.8, 13.5, 14.4, 15.3, 16.2]
    assert spy.paced_done_at() == [13.5, 14.4, 15.3, 16.2]
