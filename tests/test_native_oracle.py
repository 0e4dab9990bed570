"""The native arc-eager oracle (native/oracle.cpp) against the Python state
machine (``transition.gold_oracle_python``), element for element and dtype
for dtype: the Python is the statement of the semantics, the native code is
what ``transition.gold_oracle`` runs where the library loaded."""

import logging
import random
import sys
import threading

import numpy as np
import pytest

from spacy_ray_tpu import native
from spacy_ray_tpu.pipeline import nonproj
from spacy_ray_tpu.pipeline import transition as T
from spacy_ray_tpu.udgen import synth_ud_corpus


def random_forest(rng, n, p_root):
    """A strictly projective forest over ``n`` words from a random run of the
    machine itself; what is left on the stack at the end is a root with
    probability ``p_root`` and hangs off the token below it otherwise."""
    heads = list(range(n))
    stack = []
    for i in range(n):
        while stack and rng.random() < 0.45:
            if len(stack) >= 2 and rng.random() < 0.5:
                dep = stack.pop()
                heads[dep] = stack[-1]
            elif rng.random() < 0.7:
                heads[stack.pop()] = i
            else:
                break
        stack.append(i)
    while len(stack) >= 2:
        dep = stack.pop()
        if rng.random() >= p_root:
            heads[dep] = stack[-1]
    return heads


def assert_same(got, want):
    if want is None or got is None:
        assert got is None and want is None, (got is None, want is None)
        return
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g, w)


def both(heads, labels, n_labels):
    """The entry (native here) and the reference, on one document."""
    assert native.load() is not None and T.oracle_path() == "native"
    got = T.gold_oracle(heads, labels, n_labels)
    want = T.gold_oracle_python(heads, labels, n_labels)
    assert_same(got, want)
    return got


@pytest.mark.parametrize("seed", range(10))
def test_native_oracle_equals_the_python_one_on_random_trees(seed):
    """250 trees a seed, 2,500 in all, of 1-256 words: forests of one and of
    several roots, the same with a few heads moved anywhere (crossing arcs,
    covered roots, cycles), and heads drawn at random."""
    rng = random.Random(1000 + seed)
    usable = unusable = several_roots = 0
    for t in range(250):
        n = rng.randint(1, 256) if t % 5 else rng.randint(1, 10)
        n_labels = rng.choice([1, 3, 14, 60])
        heads = random_forest(rng, n, rng.choice([0.0, 0.15, 0.6]))
        if t % 5 == 3:
            for _ in range(rng.randint(1, 3)):
                heads[rng.randrange(n)] = rng.randrange(n)
        elif t % 5 == 4:
            heads = [rng.randrange(n) for _ in range(n)]
        labels = [rng.randrange(n_labels) for _ in range(n)]
        out = both(heads, labels, n_labels)
        if out is None:
            unusable += 1
            continue
        usable += 1
        several_roots += sum(h == i for i, h in enumerate(heads)) > 1
        # the rows are a function of the actions: what a memo replays
        kept = out[0].astype(np.int32)
        assert_same((kept, *T.replay(kept, n, n_labels)), (kept, *out[1:]))
        if t % 10 == 0:
            assert_same(T.replay_python(kept, n, n_labels), out[1:])
        assert out[0].dtype == out[1].dtype == np.int64 and out[2].dtype == np.bool_
        assert out[0].shape == (2 * n,) and out[1].shape == (2 * n, T.N_FEATURES)
        assert out[2].shape == (2 * n, T.n_actions(n_labels))
    assert usable >= 140 and unusable >= 40 and several_roots >= 40


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_native_oracle_equals_the_python_one_on_a_generated_ud_corpus(seed):
    """``udgen``'s documents of up to ten sentences (a root each), as
    ``make_targets`` hands them over: after ``projectivize``, the lifted ones
    with their decorated labels in the inventory."""
    examples = synth_ud_corpus(120, seed=seed, max_sents=10)
    lifted = []
    inventory = {}
    for eg in examples:
        heads, deco, n_lifted = nonproj.projectivize(eg.reference.heads, eg.reference.deps)
        for label in deco:
            inventory.setdefault(label, len(inventory))
        lifted.append((heads, deco, n_lifted, eg.reference.heads))
    assert any(nonproj.is_decorated(label) for label in inventory)
    n_lifted_docs = 0
    for heads, deco, n_lifted, raw in lifted:
        ids = [inventory[label] for label in deco]
        assert both(heads, ids, len(inventory)) is not None
        if n_lifted:
            n_lifted_docs += 1
            both(raw, ids, len(inventory))  # before lifting: unusable, in both
            assert T.gold_oracle(raw, ids, len(inventory)) is None
    assert n_lifted_docs >= 10


@pytest.mark.parametrize("heads,why", [
    ([2, 3, 1, 1], "crossing arcs"),
    ([2, 1, 2], "a root under another arc's span"),
    ([1, 0], "a cycle of two"),
    ([1, 2, 0, 3], "a cycle of three beside a root"),
    ([1, 5, 1], "a head past the end"),
    ([1, -1, 1], "a head before the start"),
    ([], "an empty document"),
], ids=lambda v: v.replace(" ", "_") if isinstance(v, str) else None)
@pytest.mark.parametrize("n_labels", [1, 60])
def test_unusable_trees_give_none_from_both(heads, why, n_labels):
    labels = [n_labels - 1] * len(heads)
    assert both(heads, labels, n_labels) is None, why


@pytest.mark.parametrize("n_labels", [1, 60])
def test_the_valid_rows_take_the_state_machines_patterns(n_labels):
    """One small tree read by hand: a left arc, the root, a right arc."""
    actions, feats, valid = both([1, 1, 1], [0, 0, n_labels - 1], n_labels)
    last = n_labels - 1
    assert actions.tolist() == [
        T.SHIFT, T.left_arc(0), T.SHIFT, T.right_arc(last), T.REDUCE, T.REDUCE]
    assert feats[3].tolist() == [1, -1, -1, 2, -1, -1, 0, -1, -1, -1, -1, -1]
    assert feats[4].tolist() == [2, 1, -1, -1, -1, -1, -1, -1, 0, 2, -1, -1]
    only_shift = [True] + [False] * (2 * n_labels + 1)
    assert valid[0].tolist() == only_shift  # an empty stack
    assert valid[1].tolist() == [True, False] + [True] * (2 * n_labels)  # s0 headless
    assert valid[4].tolist() == [False, True] + [False] * (2 * n_labels)  # no buffer


@pytest.mark.parametrize("actions,n_words,why", [
    ([0, 2, 0, 3, 1], 3, "a step short"),
    ([0, 2, 0, 3, 1, 1, 1], 3, "a step past the end"),
    ([0, 1, 0, 3, 1, 1], 3, "REDUCE of a token without a head while the buffer holds words"),
    ([2, 0, 0, 3, 1, 1], 3, "LEFT-ARC on an empty stack"),
    ([0, 2, 0, 9, 1, 1], 3, "an action past the inventory"),
    ([0, 2, 0, -1, 1, 1], 3, "a negative action"),
    ([0, 2, 0, 3, 1, 1], 4, "another document's length"),
    ([], 0, "no document"),
], ids=lambda v: v.replace(" ", "_") if isinstance(v, str) else None)
def test_replay_refuses_what_is_not_a_run_of_the_machine(actions, n_words, why):
    kept = np.asarray(actions, dtype=np.int32)
    assert T.replay_python(kept, n_words, 1) is None, why
    assert native.arc_eager_replay(native.load(), kept, n_words, 1) is None, why
    with pytest.raises(ValueError, match="not a run of the arc-eager machine"):
        T.replay(kept, n_words, 1)


def test_numpy_arguments_and_spare_labels_are_taken_as_lists_are():
    heads = np.array([1, 1, 3, 1], dtype=np.int32)
    labels = np.array([2, 0, 1, 2, 0, 0], dtype=np.int64)  # longer than the document
    assert_same(T.gold_oracle(heads, labels, 3),
                T.gold_oracle_python(heads.tolist(), labels.tolist(), 3))


def test_a_label_outside_the_inventory_is_left_to_the_python_to_refuse():
    """The native code reads no row it was not given: it declines, and the
    entry raises what the Python state machine raises."""
    lib = native.load()
    assert native.arc_eager_oracle(lib, [1, 1], [5, 0], 2) is native.DECLINED
    with pytest.raises(IndexError):
        T.gold_oracle_python([1, 1], [5, 0], 2)
    with pytest.raises(IndexError):
        T.gold_oracle([1, 1], [5, 0], 2)
    # a root's label is never read, by either
    assert both([0, 0], [5, 1], 2) is not None


def test_two_threads_at_once_agree_with_the_serial_answers():
    rng = random.Random(5)
    docs = []
    for _ in range(300):
        n = rng.randint(1, 200)
        docs.append((random_forest(rng, n, 0.2), [rng.randrange(7) for _ in range(n)]))
    serial = [T.gold_oracle_python(h, l, 7) for h, l in docs]
    got = [[None] * len(docs) for _ in range(3)]
    errors = []

    def work(slot):
        try:
            for _ in range(2):
                for i, (h, l) in enumerate(docs):
                    got[slot][i] = T.gold_oracle(h, l, 7)
        except BaseException as e:  # noqa: BLE001  (re-raised on the test's thread)
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(k,)) for k in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    if errors:
        raise errors[0]
    for answers in got:
        for a, want in zip(answers, serial):
            assert_same(a, want)


# ----------------------------------------------------------------------
# where the library is absent
# ----------------------------------------------------------------------


@pytest.fixture
def no_library(monkeypatch):
    """A machine without g++: the loader has tried, and has nothing."""
    monkeypatch.setattr(native, "_LIB", None)
    monkeypatch.setattr(native, "_TRIED", True)
    monkeypatch.setattr(native, "_WHY_MISSING", "no g++")


def parser_pipeline():
    from spacy_ray_tpu.config import Config
    from spacy_ray_tpu.pipeline.language import Pipeline

    from test_parser import PARSER_CFG

    examples = synth_ud_corpus(16, seed=11, max_sents=4)
    nlp = Pipeline.from_config(Config.from_str(PARSER_CFG))
    nlp.initialize(lambda: examples, seed=0)
    return nlp, examples


def fresh(examples):
    from spacy_ray_tpu.pipeline.doc import Example

    return [Example.from_gold(eg.reference) for eg in examples]


def test_make_targets_counts_the_documents_each_oracle_worked_out():
    nlp, examples = parser_pipeline()
    parser = nlp.components["parser"]
    before = dict(parser.oracle_stats)
    batch = fresh(examples)
    parser.make_targets(batch, 16, 64)
    parser.make_targets(batch, 16, 64)  # every document from its memo
    after = parser.oracle_stats
    assert after["native"] - before["native"] == 16 and after["python"] == before["python"]
    assert after["docs"] - before["docs"] == 32
    assert parser.oracle_report() == {
        "path": "native", "native": after["native"], "python": after["python"]}


def test_the_memo_keeps_the_actions_and_a_hit_replays_the_same_targets():
    """Of the native oracle's answer an ``Example`` keeps 4 bytes a step, and
    the second collation of it gives the first's four arrays without a call
    of the oracle; of the Python's answer it keeps the whole."""
    nlp, examples = parser_pipeline()
    parser = nlp.components["parser"]
    batch = fresh(examples)
    first = parser.make_targets(batch, 16, 64)
    for eg in batch:
        kept, _ = eg._oracle_cache[1]
        assert kept.dtype == np.int32 and kept.shape == (2 * len(eg.reference),)
    counted = dict(parser.oracle_stats)
    again = parser.make_targets(batch, 16, 64)
    assert parser.oracle_stats["native"] == counted["native"]  # every document a hit
    for key in first:
        assert again[key].dtype == first[key].dtype
        np.testing.assert_array_equal(again[key], first[key])
    assert first["step_mask"].sum() == 2 * sum(len(eg.reference) for eg in batch)


def test_without_the_library_the_python_runs_says_so_and_gives_the_same_targets(
    monkeypatch, caplog
):
    nlp, examples = parser_pipeline()
    parser = nlp.components["parser"]
    with_native = parser.make_targets(fresh(examples), 16, 64)
    counted = dict(parser.oracle_stats)

    monkeypatch.setattr(native, "_LIB", None)
    monkeypatch.setattr(native, "_TRIED", False)
    monkeypatch.setattr(native, "_WHY_MISSING", "")
    monkeypatch.setattr(native, "_stale", lambda: True)
    monkeypatch.setenv("PATH", "")  # no g++ to find
    with caplog.at_level(logging.WARNING, logger="spacy_ray_tpu.native"):
        assert native.load() is None
        assert T.oracle_path() == "python (native library missing: no g++)"
        without = parser.make_targets(fresh(examples), 16, 64)
        parser.make_targets(fresh(examples), 16, 64)
    assert caplog.text.count("did not build") == 1  # said once, not a batch
    assert "parser oracle" in caplog.text
    assert set(without) == set(with_native) == {"actions", "feats", "valid", "step_mask"}
    for key in with_native:
        assert without[key].dtype == with_native[key].dtype
        np.testing.assert_array_equal(without[key], with_native[key])
    assert with_native["step_mask"].any()
    assert parser.oracle_stats["python"] - counted["python"] == 32
    kept_whole = fresh(examples)
    parser.make_targets(kept_whole, 16, 64)
    assert all(isinstance(eg._oracle_cache[1][0], tuple) for eg in kept_whole)
    hit = parser.make_targets(kept_whole, 16, 64)
    np.testing.assert_array_equal(hit["feats"], with_native["feats"])
    assert parser.oracle_stats["python"] - counted["python"] == 48
    assert parser.oracle_stats["native"] == counted["native"]
    assert parser.oracle_report()["path"].startswith("python (")


def test_the_entry_falls_back_where_the_library_is_absent(no_library):
    assert T.oracle_path() == "python (native library missing: no g++)"
    heads, labels = [1, 1, 1, 2], [0, 1, 2, 0]
    assert_same(T.gold_oracle(heads, labels, 3), T.gold_oracle_python(heads, labels, 3))
    assert T.gold_oracle([2, 3, 1, 1], [0] * 4, 1) is None


# ----------------------------------------------------------------------
# what a run says of it
# ----------------------------------------------------------------------


def test_train_reports_the_oracle_that_ran_with_every_document_native(tmp_path):
    """``train`` over the benchmark's documents at its rehearsal widths of
    ``sm``: ``resolved`` carries ``parser_oracle`` beside ``fused_update``,
    and no document's targets came from the Python state machine."""
    from pathlib import Path

    root = Path(__file__).resolve().parent.parent
    sys.path.insert(0, str(root / "benchmark"))
    import common
    from spacy_ray_tpu.config import load_config
    from spacy_ray_tpu.training.loop import train

    config_file = common.load_json(root / "benchmark" / "configs" / "sm.json")
    docs = common.load_json(root / "benchmark" / "traffic" / "ewt10_b64k.json")["docs"]
    generate = common.load_module("generators", docs["generator"]).generate
    common.write_jsonl(tmp_path / "train.jsonl", generate(24, 2147400035, docs))
    common.write_jsonl(tmp_path / "dev.jsonl", generate(4, 6, docs))
    cfg = load_config(root / config_file["program_config"], {
        **config_file["overrides"], **config_file["rehearse_overrides"],
        "paths.train": str(tmp_path / "train.jsonl"),
        "paths.dev": str(tmp_path / "dev.jsonl"),
        "training.batcher.size": 600, "training.max_steps": 3,
        "training.eval_frequency": 10 ** 9,
    }, interpolate=False)
    nlp, result = train(cfg, n_workers=1, stdout_log=False)
    report = result.resolved["parser_oracle"]
    assert set(report) == {"path", "native", "python"}
    assert report["path"] == "native" and report["python"] == 0
    stats = nlp.components["parser"].oracle_stats
    assert report["native"] == stats["native"] > 0
    # a run this short never meets a document twice: each was worked out once
    assert stats["native"] == stats["docs"] and stats["skipped"] == 0
