"""The program's own spans and names (``spacy_ray_tpu/names.py``): host
spans on the profiler's clock, a fixed number of them a step, nothing
recorded without a stats handle, and scope / kernel / program names in the
lowered train step."""

import json
import re
import threading

import jax
import jax.numpy as jnp
import pytest

from spacy_ray_tpu import names
from spacy_ray_tpu.config import Config
from spacy_ray_tpu.training import collate_pool
from spacy_ray_tpu.training.corpus import _doc_to_json
from spacy_ray_tpu.training.loop import train
from spacy_ray_tpu.util import synth_corpus

# the sm shape (tagger + parser + NER over one shared CNN trunk) and the trf
# shape (the same heads over a transformer trunk), at test widths
SM_CFG = """
[paths]
train = null
dev = null

[nlp]
lang = "en"
pipeline = ["tok2vec","tagger","parser","ner"]

[components.tok2vec]
factory = "tok2vec"

[components.tok2vec.model]
@architectures = "spacy.HashEmbedCNN.v2"
width = 32
depth = 2
embed_size = 256

[components.tagger]
factory = "tagger"

[components.tagger.model]
@architectures = "spacy.Tagger.v2"

[components.tagger.model.tok2vec]
@architectures = "spacy.Tok2VecListener.v1"
width = 32

[components.parser]
factory = "parser"

[components.parser.model]
@architectures = "spacy.TransitionBasedParser.v2"
state_type = "parser"
hidden_width = 32
maxout_pieces = 2

[components.parser.model.tok2vec]
@architectures = "spacy.Tok2VecListener.v1"
width = 32

[components.ner]
factory = "ner"

[components.ner.model]
@architectures = "spacy.TransitionBasedParser.v2"
state_type = "ner"
hidden_width = 32
maxout_pieces = 2

[components.ner.model.tok2vec]
@architectures = "spacy.Tok2VecListener.v1"
width = 32

[corpora.train]
@readers = "spacy.JsonlCorpus.v1"
path = ${paths.train}

[corpora.dev]
@readers = "spacy.JsonlCorpus.v1"
path = ${paths.dev}

[training]
seed = 0
max_steps = 6
eval_frequency = 1000
patience = 0

[training.optimizer]
@optimizers = "Adam.v1"
learn_rate = 0.005

[training.batcher]
@batchers = "spacy.batch_by_words.v1"
size = 200

[training.score_weights]
tag_acc = 0.34
dep_las = 0.33
ents_f = 0.33
"""

TRF_TRUNK = """[components.tok2vec]
factory = "transformer"

[components.tok2vec.model]
@architectures = "spacy_ray_tpu.TransformerEncoder.v1"
width = 32
depth = 2
n_heads = 2
ffn_mult = 2
max_len = 64
embed_size = 256
"""

TRF_CFG = re.sub(
    r"\[components\.tok2vec\]\n.*?embed_size = 256\n", TRF_TRUNK, SM_CFG,
    flags=re.S,
)


def _write_mixed(path, n, seed):
    egs = synth_corpus(n // 2, "parser", seed=seed) + synth_corpus(
        n // 2, "ner", seed=seed + 1
    )
    with open(path, "w", encoding="utf8") as f:
        for eg in egs:
            f.write(json.dumps(_doc_to_json(eg.reference)) + "\n")


@pytest.fixture(scope="module")
def mixed_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("spans_data")
    _write_mixed(d / "train.jsonl", 240, seed=0)
    _write_mixed(d / "dev.jsonl", 20, seed=7)
    return d


def _config(text, data_dir, **overrides):
    return Config.from_str(text).apply_overrides(
        {
            "paths.train": str(data_dir / "train.jsonl"),
            "paths.dev": str(data_dir / "dev.jsonl"),
            **overrides,
        }
    )


class RecordingStats(collate_pool.PipelineStats):
    """The loop's stage clocks, keeping every span they are given (the
    handle the benchmark takes: a subclass that registers itself)."""

    made = []

    def __init__(self):
        super().__init__()
        self.calls = []
        self.spans = []  # (stage, seconds, start on time.perf_counter)
        RecordingStats.made.append(self)

    def _add_all(self, spans, n=1):
        self.calls.extend((stage, seconds) for stage, seconds, _ in spans)
        self.spans.extend(spans)
        super()._add_all(spans, n)


@pytest.fixture
def recorded(monkeypatch):
    RecordingStats.made.clear()
    monkeypatch.setattr(collate_pool, "PipelineStats", RecordingStats)
    return RecordingStats.made


def _host_spans(profile_dir):
    """``{thread line: [(start_ns, end_ns, name)]}`` of the program's spans
    on the host plane of the one trace under ``profile_dir`` (a thread's
    line is known by its place: every Python thread's is named alike)."""
    from jax.profiler import ProfileData

    (path,) = list(profile_dir.rglob("*.xplane.pb"))
    lines = {}
    for p, plane in enumerate(ProfileData.from_file(str(path)).planes):
        if not plane.name.startswith("/host:"):
            continue
        for n, line in enumerate(plane.lines):
            spans = [
                (int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
                for e in line.events
                if e.name.startswith(names.SPAN_PREFIX)
            ]
            if spans:
                lines[(p, n)] = spans
    return lines


def test_spans_are_on_the_profilers_clock(mixed_dir, tmp_path, recorded):
    """One trace holds the program's spans beside the device's operations:
    ``train --profile`` writes the collate thread's and the loop's spans
    as host events, each child inside its parent on its thread's line, and
    what the profiler's clock says of ``collate`` is what ``PipelineStats``
    counted for the same calls."""
    cfg = _config(
        SM_CFG, mixed_dir,
        **{"training.max_steps": 8, "training.profile_window": [1, 7]},
    )
    train(cfg, n_workers=1, stdout_log=False, profile_dir=tmp_path / "prof")
    lines = _host_spans(tmp_path / "prof")
    by_name = {}
    for line_name, spans in lines.items():
        for a, b, name in spans:
            by_name.setdefault(name, []).append((line_name, a, b))
    for key in (
        names.COLLATE,
        names.COLLATE_FEATURES,
        names.COLLATE_TARGETS,
        names.collate_head("parser"),
        names.collate_head("ner"),
        names.QUEUE_WAIT,
        names.TRANSFER,
        names.LOOP_HOST,
        names.LOOP_DISPATCH,
    ):
        assert names.SPAN_PREFIX + key in by_name, (key, sorted(by_name))
    # no shipped head leaves the host inside make_targets
    assert not [n for n in by_name if n.endswith("/" + names.DEVICE_CALL)]

    # a child lies inside a span of its parent, on the same thread line
    # (a span that was open when the trace began is not in it: its children
    # are the ones that start before the first of their parent's name)
    checked = 0
    for name, found in by_name.items():
        key = name[len(names.SPAN_PREFIX):]
        if "/" not in key:
            continue
        parents = by_name[names.SPAN_PREFIX + key.rsplit("/", 1)[0]]
        for line, a, b in found:
            on_line = [(pa, pb) for pl, pa, pb in parents if pl == line]
            assert on_line, f"{name} on a line without its parent"
            if a < min(pa for pa, _ in on_line):
                continue
            assert any(pa <= a and b <= pb for pa, pb in on_line), (
                f"{name} [{a}, {b}] lies in no parent span"
            )
            checked += 1
    assert checked >= 30

    # the collate thread is not the loop's thread
    collate_lines = {l for l, _, _ in by_name[names.SPAN_PREFIX + names.COLLATE]}
    loop_lines = {l for l, _, _ in by_name[names.SPAN_PREFIX + names.LOOP_DISPATCH]}
    assert collate_lines.isdisjoint(loop_lines)

    # the same calls on two clocks: the trace's collate spans are a run of
    # consecutive calls among those PipelineStats was given
    (stats,) = recorded
    counted = [s for stage, s in stats.calls if stage == names.COLLATE]
    traced = [
        (b - a) / 1e9
        for _, a, b in sorted(by_name[names.SPAN_PREFIX + names.COLLATE], key=lambda x: x[1])
    ]
    assert 3 <= len(traced) <= len(counted)
    gaps = [
        abs(sum(traced) - sum(counted[i:i + len(traced)]))
        for i in range(len(counted) - len(traced) + 1)
    ]
    assert min(gaps) <= 0.05 * sum(traced) + 1e-3, (traced, counted)
    assert stats.seconds[names.COLLATE] == pytest.approx(sum(counted))


def test_a_part_is_counted_together_with_its_whole():
    """The benchmark reads ``seconds`` at two instants and takes ratios of
    what lies between: a child's seconds must not arrive before its
    parent's, or a window edge inside the parent skews every share."""
    stats = collate_pool.PipelineStats()
    with stats.timer(names.COLLATE) as whole:
        with stats.timer(names.COLLATE_TARGETS):
            with whole.child("targets/ner"):
                pass
        assert names.COLLATE_TARGETS not in stats.seconds
        assert stats.counts[names.COLLATE] == 0
        # another thread's spans are its own
        def read():
            with stats.timer(names.READ):
                pass

        other = threading.Thread(target=read)
        other.start()
        other.join(10)
        assert stats.counts[names.READ] == 1
    assert stats.counts[names.COLLATE] == 1
    assert stats.counts[names.collate_head("ner")] == 1
    assert 0 < stats.seconds[names.COLLATE_TARGETS] <= stats.seconds[names.COLLATE]
    stats.add(names.TRANSFER, 0.5)  # a caller that only counts: at once
    assert stats.seconds[names.TRANSFER] == 0.5


def test_a_head_that_leaves_the_host_times_the_call(mixed_dir):
    """The ``make_targets`` contract (``components/base.py``): a head that
    must leave the host wraps that call in ``span.child(DEVICE_CALL)``. No
    shipped head does, so a stub keeps the contract under test: the key is
    ``collate/targets/<head>/device_call``, it arrives together with its
    whole, and it lies inside its parent on the clock."""
    from spacy_ray_tpu.pipeline.components.base import Component
    from spacy_ray_tpu.pipeline.language import Pipeline

    key = names.collate_head("stub") + "/" + names.DEVICE_CALL
    stats = RecordingStats()
    early = []  # was the child's key there before its whole had closed?

    class LeavesTheHost(Component):
        def make_targets(self, examples, B, T, span=None):
            with span.child(names.DEVICE_CALL):
                rows = jax.device_get(jnp.zeros((B, T), jnp.int32))
            early.append(key in stats.seconds)
            return {"rows": rows}

    nlp = Pipeline.from_config(_config(SM_CFG, mixed_dir))
    examples = synth_corpus(8, "parser", seed=3)
    nlp.initialize(lambda: iter(examples), seed=0)
    nlp.components["stub"] = LeavesTheHost("stub", {})
    nlp.pipe_names.append("stub")

    batch = nlp.collate(examples, host=True, stats=stats)
    assert batch["targets"]["stub"]["rows"].shape == batch["tokens"].mask.shape
    assert early == [False]  # a part is added together with its whole
    assert stats.counts[key] == 1
    assert [k for k in stats.counts if k.endswith("/" + names.DEVICE_CALL)] == [key]
    spans = {stage: (t0, t0 + seconds) for stage, seconds, t0 in stats.spans}
    child, parent = spans[key], spans[names.collate_head("stub")]
    assert parent[0] <= child[0] <= child[1] <= parent[1]
    assert 0 < stats.seconds[key] <= stats.seconds[names.collate_head("stub")]
    # nothing recorded, nothing opened: the same stub under NO_SPAN
    bare = nlp.collate(examples, host=True)
    assert bare["targets"]["stub"]["rows"].shape == batch["tokens"].mask.shape


@pytest.mark.parametrize("accumulate", [1, 2])
def test_spans_a_step_do_not_grow_with_the_batch(mixed_dir, recorded, accumulate):
    """Every span is per batch or per micro-batch, never per document: a
    batch of four times the words records the same spans a step."""
    per_step = []
    for size in (100, 400):
        cfg = _config(
            SM_CFG, mixed_dir,
            **{
                "training.max_steps": 3,
                "training.batcher.size": size,
                "training.accumulate_gradient": accumulate,
                "training.prefetch_batches": 0,  # inline: 3 steps, 3 collates
            },
        )
        train(cfg, n_workers=1, stdout_log=False)
        stats = recorded[-1]
        assert stats.counts[names.COLLATE] in (3, 4)  # the end of data or not
        per_step.append({
            stage: round(n / stats.counts[names.COLLATE], 3)
            for stage, n in stats.counts.items()
            if stage.startswith(names.COLLATE)
        })
    assert per_step[0] == per_step[1]
    assert per_step[0][names.COLLATE_TARGETS] == accumulate
    assert per_step[0].get(names.COLLATE_STACK, 0) == (1 if accumulate > 1 else 0)
    stats = recorded[-1]
    assert stats.counts[names.LOOP_DISPATCH] == 3
    assert stats.counts[names.LOOP_HOST] == 3
    # set-up's stations are once a run, whatever the steps
    assert {key: stats.counts[key] for key in names.SETUP_STATIONS} == dict.fromkeys(
        names.SETUP_STATIONS, 1
    )
    # the snapshot (eval rows, watchdog dump) carries every key it holds
    snap = stats.snapshot()
    assert set(snap["stage_seconds"]) == set(stats.seconds)
    assert snap["stage_counts"][names.collate_head("parser")] == 3 * accumulate


def test_set_up_is_timed_station_by_station(mixed_dir, tmp_path, recorded, monkeypatch):
    """``train`` times its set-up with the run's one ``PipelineStats``: every
    station of ``names.SETUP_STATIONS`` is an ``srt:`` span on the loop's
    thread, ``resolved["setup"]`` has their seconds and ``program_s`` (entry
    to the first update call's return), and ``resolved["compile"]["setup"]``
    the train step's trace, lower and compile. Only the first update call is
    timed: the loop calls the step itself from the second on."""
    import sys

    import spacy_ray_tpu.training.loop as loop

    callers = []
    real = loop.make_train_step

    def noting(*a, **k):
        update = real(*a, **k)

        def run(*args):
            callers.append(sys._getframe(1).f_code.co_name)
            return update(*args)

        run.__dict__.update(update.__dict__)
        return run

    monkeypatch.setattr(loop, "make_train_step", noting)
    cfg = _config(SM_CFG, mixed_dir, **{"training.max_steps": 1})
    jax.profiler.start_trace(str(tmp_path / "prof"))
    try:
        _, result = train(cfg, n_workers=1, stdout_log=False)
    finally:
        jax.profiler.stop_trace()
    setup = result.resolved["setup"]
    stations = [key.split("/", 1)[1] for key in names.SETUP_STATIONS]
    assert sorted(setup) == sorted(stations + ["program_s"])
    assert all(setup[name] >= 0 for name in stations)
    assert sum(setup[name] for name in stations) <= setup["program_s"]
    assert setup["first_update"] > 0 and setup["pipeline"] > 0
    (stats,) = recorded
    assert setup["first_update"] == stats.seconds[names.SETUP_FIRST_UPDATE]

    ledger = result.resolved["compile"]
    step = ledger["by_program"][names.PROGRAM_TRAIN_STEP]
    assert (step["traces"], step["lowers"], step["compiles"] + step["cache_loads"]) == (1, 1, 1)
    assert names.PROGRAM_TRAIN_STEP not in ledger["after"]  # one step: all of it was set-up's
    assert ledger["setup"]["trace_s"] >= step["trace_s"] > 0
    assert ledger["setup"]["lower_s"] >= step["lower_s"] > 0
    assert ledger["setup"]["compile_s"] >= step["compile_s"] > 0

    lines = _host_spans(tmp_path / "prof")
    where = {name: line for line, spans in lines.items() for _, _, name in spans}
    loop_line = where[names.SPAN_PREFIX + names.LOOP_DISPATCH]
    for key in names.SETUP_STATIONS:
        assert where.get(names.SPAN_PREFIX + key) == loop_line, key
    # the step is called through the timing once, then by the loop itself
    assert callers == ["once"]
    _, result = train(_config(SM_CFG, mixed_dir, **{"training.max_steps": 3}),
                      n_workers=1, stdout_log=False)
    assert callers == ["once", "once", "_train", "_train"]
    assert result.resolved["setup"]["program_s"] > 0


def test_collate_without_a_stats_handle_records_nothing(mixed_dir, monkeypatch):
    """Serving, ``evaluate`` and tests call ``collate`` with no handle: no
    clock is read, no annotation is made, and the batch is the same."""
    import numpy as np

    nlp, _ = train(
        _config(SM_CFG, mixed_dir, **{"training.max_steps": 1}),
        n_workers=1, stdout_log=False,
    )
    examples = synth_corpus(8, "parser", seed=3)
    stats = collate_pool.PipelineStats()
    timed = nlp.collate(examples, host=True, stats=stats)
    assert stats.counts[names.COLLATE_FEATURES] == 1
    assert stats.counts[names.collate_head("ner")] == 1
    assert not [k for k in stats.counts if k.endswith("/" + names.DEVICE_CALL)]

    def boom(*a, **k):
        raise AssertionError("a span was made without a stats handle")

    monkeypatch.setattr(collate_pool.PipelineStats, "timer", boom)
    monkeypatch.setattr(collate_pool, "TraceAnnotation", boom)
    monkeypatch.setattr(collate_pool.time, "perf_counter", boom)
    bare = nlp.collate(examples, host=True)
    for a, b in zip(jax.tree_util.tree_leaves(timed["targets"]),
                    jax.tree_util.tree_leaves(bare["targets"])):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(
        timed["tokens"].attr_keys, bare["tokens"].attr_keys
    )


def _lowered_step(text, data_dir, monkeypatch):
    """The train step of ``text``'s pipeline, lowered from its first call's
    arguments (a trace, no compile), and the name of its function."""
    import spacy_ray_tpu.training.loop as loop

    made = []
    real = loop.make_train_step

    def keeping(*a, **k):
        update = real(*a, **k)
        made.append(update)

        def run(*args):
            if not hasattr(run, "lowered"):
                run.lowered = update.lower(*args).as_text(debug_info=True)
            return update(*args)

        run.__dict__.update(update.__dict__)
        made.append(run)
        return run

    monkeypatch.setattr(loop, "make_train_step", keeping)
    train(_config(text, data_dir, **{"training.max_steps": 1}),
          n_workers=1, stdout_log=False)
    return made[1].lowered


@pytest.mark.parametrize("shape", ["sm", "trf"])
def test_the_lowered_step_carries_the_scope_names(mixed_dir, monkeypatch, shape):
    """Device time is read by name: every operation of the step lies in one
    of the scopes of ``names.py`` (its transposes too), and the program is
    ``jit_srt_train_step``. Metadata only: nothing is added to the program."""
    text = _lowered_step(SM_CFG if shape == "sm" else TRF_CFG, mixed_dir, monkeypatch)
    assert f"module @jit_{names.PROGRAM_TRAIN_STEP} " in text
    for scope in (
        names.SCOPE_EMBED,
        names.SCOPE_TRUNK,
        names.head_scope("tagger"),
        names.head_scope("parser"),
        names.head_scope("ner"),
        names.SCOPE_LOSS,
        names.SCOPE_UPDATE,
    ):
        assert re.search(rf'loc\("[^"]*\b{re.escape(scope)}[)/]', text), scope
    # the backward pass keeps the forward's name
    for scope in (names.SCOPE_TRUNK, names.head_scope("parser")):
        assert re.search(
            rf'loc\("[^"]*transpose\(jvp\({re.escape(scope)}\)\)/', text
        ), f"no transposed operation under {scope}"
    if shape == "trf":
        # the layers are rematerialised: the forward's and the backward's
        # copies of the scanned stack are both called from under the trunk
        for side in ("jvp", "transpose\\(jvp"):
            assert re.search(
                rf'loc\("[^"]*{side}\({names.SCOPE_TRUNK}\)+/while/body/closed_call"', text
            ), side


def test_accumulated_steps_are_scoped_and_named(mixed_dir, monkeypatch):
    """``accumulate_gradient`` > 1: the scan over micro-batches is the
    ``grad_accum`` scope, and the model's scopes are inside its body."""
    text = _lowered_step(
        SM_CFG.replace("patience = 0", "patience = 0\naccumulate_gradient = 2"),
        mixed_dir, monkeypatch,
    )
    assert f"module @jit_{names.PROGRAM_TRAIN_STEP} " in text
    assert re.search(rf'loc\("[^"]*/{names.SCOPE_GRAD_ACCUM}/while/body/', text)
    assert re.search(rf'loc\("[^"]*transpose\(jvp\({names.SCOPE_TRUNK}\)\)/', text)


# the trf shape over the trunk built from a layer pattern (models/hybrid_ssm.py):
# one layer of each kind and a second state-space one
HYBRID_TRUNK = """[components.transformer]
factory = "transformer"

[components.transformer.model]
@architectures = "spacy_ray_tpu.HybridSSMTrunk.v1"
pattern = "ME*M"
width = 32
ssm_heads = 4
ssm_head_dim = 8
ssm_groups = 2
ssm_state = 8
chunk = 8
n_heads = 4
n_kv_heads = 2
head_dim = 8
expert_ffn = 16
shared_ffn = 24
n_experts = 8
experts_held = 4
top_k = 2
vocab_rows = 97
"""
HYBRID_CFG = re.sub(
    r"\[components\.tok2vec\]\n.*?embed_size = 256\n", HYBRID_TRUNK, SM_CFG, flags=re.S,
).replace('pipeline = ["tok2vec",', 'pipeline = ["transformer",')


def test_a_pattern_trunk_splits_the_step_by_kind_of_layer(mixed_dir, monkeypatch):
    """Inside ``trunk`` every layer's operations lie under its kind
    (``mamba``, the scan alone under ``mamba/scan``, ``attention``, ``moe``
    and its parts), forward and backward, each layer rematerialised."""
    text = _lowered_step(HYBRID_CFG, mixed_dir, monkeypatch)
    assert f"module @jit_{names.PROGRAM_TRAIN_STEP} " in text
    trunk = re.escape(names.SCOPE_TRUNK)
    for scope in (names.SCOPE_MAMBA, names.SCOPE_MAMBA_SCAN, names.SCOPE_ATTENTION, names.SCOPE_MOE,
                  names.SCOPE_MOE_ROUTER, names.SCOPE_MOE_DISPATCH, names.SCOPE_MOE_EXPERTS,
                  names.SCOPE_MOE_COMBINE, names.SCOPE_MOE_SHARED):
        for side in (rf"jvp\({trunk}\)", rf"transpose\(jvp\({trunk}\)\)"):
            assert re.search(rf'loc\("[^"]*{side}/[^"]*\b{re.escape(scope)}[)/"]', text), (scope, side)
    # a part's name starts with its whole's, so a reader by prefix finds both
    assert names.SCOPE_MAMBA_SCAN.startswith(names.SCOPE_MAMBA + "/")
    assert names.SCOPE_MOE_ROUTER.startswith(names.SCOPE_MOE + "/")


def test_the_scans_counters_leave_the_device_and_reach_the_report(mixed_dir):
    """``count_ssm_chunks`` / ``count_ssm_live_chunks`` are device counters
    like the routed trunk's eight: summed over the run, and the trunk's own
    summary turns them into the ``ssm`` block of the ``runtime`` report."""
    for key in (names.SSM_CHUNKS, names.SSM_LIVE_CHUNKS, names.MOE_ASSIGNMENTS,
                names.MOE_BOUNDED_CALLS, names.MOE_BUFFER_ROWS, names.MOE_TIER_CALLS):
        assert key.startswith(names.COUNTER_PREFIX)
    _, result = train(_config(HYBRID_CFG, mixed_dir, **{"training.max_steps": 3}),
                      n_workers=1, stdout_log=False)
    resolved = result.resolved
    assert resolved["layer_pattern"] == "ME*M" and resolved["ssm_scan"] == "chunked 8, xla"
    ssm = resolved["ssm"]
    assert ssm["layers"] == 2 and ssm["chunk"] == 8
    assert 0 < ssm["live_chunks"] <= ssm["chunks"] and ssm["chunks"] % 2 == 0
    assert resolved["moe"]["layer_calls"] == 3 and resolved["moe_dropped"] == "0"
    moe = resolved["moe"]  # a call takes the tier, the bound or every pair: at least one row
    assert moe["tier_calls"] <= moe["bounded_calls"] <= 3 <= moe["buffer_rows"]
    assert resolved["moe_dispatch"].startswith("sorted, ragged_dot, 4 of 8 held")


# the same shape over the other family's two mixers (one published period cut to a
# softmax layer and one linear layer, each with its expert block), the heads shared
DELTA_TRUNK = """[components.transformer]
factory = "transformer"

[components.transformer.model]
@architectures = "spacy_ray_tpu.HybridSSMTrunk.v1"
pattern = "GEKE"
width = 32
chunk = 8
n_heads = 4
n_kv_heads = 2
head_dim = 8
heads_held = 2
kda_heads = 4
kda_head_dim = 8
kda_gate_rank = 8
kda_heads_held = 2
expert_form = "gated_silu"
route_bias = false
expert_ffn = 16
shared_ffn = 16
n_experts = 8
experts_held = 4
top_k = 2
vocab_rows = 97
"""
DELTA_CFG = re.sub(
    r"\[components\.tok2vec\]\n.*?embed_size = 256\n", DELTA_TRUNK, SM_CFG, flags=re.S,
).replace('pipeline = ["tok2vec",', 'pipeline = ["transformer",')


@pytest.fixture(scope="module")
def delta_step(mixed_dir):
    with pytest.MonkeyPatch.context() as patch:  # one lowering for the six cases
        return _lowered_step(DELTA_CFG, mixed_dir, patch)


@pytest.mark.parametrize("scope", [
    names.SCOPE_KDA, names.SCOPE_KDA_SCAN, names.SCOPE_GATED_ATTENTION, names.SCOPE_MOE,
    names.SCOPE_MOE_EXPERTS, names.SCOPE_MOE_SHARED])
def test_the_delta_rule_and_gated_layers_have_their_scopes_forward_and_backward(delta_step, scope):
    """``kda`` (projections, convolutions, gates, norm), the chunked
    recurrence alone under ``kda/scan`` and ``gated_attention`` lie inside
    ``trunk`` in the lowered step, forward and backward, beside the expert
    blocks' own."""
    text = delta_step
    trunk = re.escape(names.SCOPE_TRUNK)
    for side in (rf"jvp\({trunk}\)", rf"transpose\(jvp\({trunk}\)\)"):
        assert re.search(rf'loc\("[^"]*{side}/[^"]*\b{re.escape(scope)}[)/"]', text), (scope, side)
    assert names.SCOPE_KDA_SCAN.startswith(names.SCOPE_KDA + "/")
    # no operation of this trunk is named for a layer it does not have
    assert not re.search(rf'loc\("[^"]*/{names.SCOPE_MAMBA}[)/"]', text)


def test_the_delta_rules_counters_leave_the_device_and_reach_the_report(mixed_dir):
    """``count_kda_chunks`` / ``count_kda_live_chunks`` are device counters
    like the scan's two: summed over the run, and the trunk's own summary
    turns them into the ``kda`` block of the ``runtime`` report, beside the
    share of the heads."""
    for key in (names.KDA_CHUNKS, names.KDA_LIVE_CHUNKS):
        assert key.startswith(names.COUNTER_PREFIX)
    _, result = train(_config(DELTA_CFG, mixed_dir, **{"training.max_steps": 3}),
                      n_workers=1, stdout_log=False)
    resolved = result.resolved
    assert resolved["layer_pattern"] == "GEKE" and resolved["kda_scan"] == "chunked 8, xla"
    assert resolved["head_share"] == "2 of 4 query, 1 of 2 key, 2 of 4 linear heads, rank 0"
    kda = resolved["kda"]
    assert kda["layers"] == 1 and kda["chunk"] == 8 and 0 < kda["live_chunks"] <= kda["chunks"]
    assert "ssm" not in resolved
    assert resolved["moe"]["layer_calls"] == 6 and resolved["moe_dropped"] == "0"
    assert resolved["moe_dispatch"].startswith("sorted, ragged_dot, 4 of 8 held")


def _pallas_names(fn, *args):
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn.params["name"])
            for v in eqn.params.values():
                for sub in v if isinstance(v, (list, tuple)) else [v]:
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def _flash():
    from spacy_ray_tpu.ops import flash_attention as fa

    q = jnp.ones((1, 2, 128, 128), jnp.float32)
    bias = jnp.zeros((1, 128), jnp.float32)
    flash = fa._make_flash(0.125)
    return jax.grad(lambda q, k, v: flash(q, k, v, bias).sum(), argnums=(0, 1, 2)), (q, q, q)


def _fused_adam():
    from spacy_ray_tpu.ops import fused_update as fu

    p = jnp.ones((300, 7), jnp.float32)
    hyper = fu.FusedHyper("adam", 0.9, 0.999, 1e-8, 1.0, 0.0, 0.01)
    scal = jnp.ones((8,), jnp.float32)
    return (lambda p, g, m, v: fu._kernel_leaf(p, g, m, v, scal, hyper)), (p, p, p, p)


def _hash_embed():
    from spacy_ray_tpu.ops import pallas_kernels as pk

    return pk._pallas_lookup_raw, (
        jnp.ones((512, 128), jnp.float32), jnp.zeros((256, 4), jnp.int32))


def _int8():
    from spacy_ray_tpu.ops import int8_matmul as im

    return im._int8_matmul_raw, (
        jnp.ones((8, 256), jnp.float32), jnp.ones((256, 128), jnp.int8),
        jnp.ones((128,), jnp.float32))


@pytest.mark.parametrize(
    "build,expected",
    [
        (_flash, {names.KERNEL_FLASH_FWD, names.KERNEL_FLASH_BWD}),
        (_fused_adam, {names.KERNEL_FUSED_ADAM}),
        (_hash_embed, {names.KERNEL_HASH_EMBED}),
        (_int8, {names.KERNEL_INT8_MATMUL}),
    ],
    ids=["flash", "fused_adam", "hash_embed", "int8_matmul"],
)
def test_each_pallas_call_carries_its_name(build, expected):
    fn, args = build()
    assert set(_pallas_names(fn, *args)) == expected
