"""Child process for the real 2-process multi-host test.

Spawned (not imported) by tests/test_multihost.py: each instance is one
"host" of a 2-process jax.distributed group with 4 local CPU devices
(8 global). Exercises the multi-host-only paths of the training loop —
the startup digest assertion, per-step shape sync, collective loop
termination (training/loop.py) — and place_batch's global-batch assembly
(parallel/step.py), none of which run under the single-process test
harness. The reference shipped an untested sync protocol and a silent
quorum bug with it (SURVEY.md §2.4, §4); this is the guard against
repeating that one level up.

Usage: python multihost_child.py <rank> <port> <data_dir>
Prints "CHILD_OK rank=R words=W step=S score=F" on success.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


CFG_TEMPLATE = """
[paths]
train = "{data_dir}/train.jsonl"
dev = "{data_dir}/dev.jsonl"

[nlp]
lang = "en"
pipeline = ["tok2vec","tagger"]

[components]
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

[corpora]
[corpora.train]
@readers = "spacy.JsonlCorpus.v1"
path = ${{paths.train}}
[corpora.dev]
@readers = "spacy.JsonlCorpus.v1"
path = ${{paths.dev}}

[training]
seed = 0
dropout = 0.1
accumulate_gradient = 2
patience = 0
max_epochs = 3
max_steps = 0
eval_frequency = 2

[training.optimizer]
@optimizers = "Adam.v1"
learn_rate = 0.01

[training.batcher]
@batchers = "spacy.batch_by_words.v1"
size = 300
tolerance = 0.2

[training.score_weights]
tag_acc = 1.0
"""


# Consuming-annotation config (VERDICT r4 next #4): the NER (a TRAINED
# annotator, so the host-local annotation pass must transfer real trunk +
# head params) predicts mentions, and the entity_linker with
# use_gold_ents = false builds its training targets from those PREDICTED
# mentions. Unlike the tagger-annotates-tagger no-op above, a bug in
# loop.py's `needed`-subtree handoff that produced wrong annotations
# starves/corrupts the linker's targets and collapses nel_micro_f — this
# config CONSUMES what the annotation pass produces.
CONSUMING_CFG_TEMPLATE = """
[nlp]
lang = "en"
pipeline = ["tok2vec","ner","entity_linker"]

[components.tok2vec]
factory = "tok2vec"

[components.tok2vec.model]
@architectures = "spacy.HashEmbedCNN.v2"
width = 32
depth = 2
embed_size = 256

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

[components.entity_linker]
factory = "entity_linker"
n_candidates = 4
use_gold_ents = false
kb_path = "{data_dir}/kb.npz"

[components.entity_linker.model]
@architectures = "spacy.EntityLinker.v2"

[components.entity_linker.model.tok2vec]
@architectures = "spacy.Tok2VecListener.v1"
width = 32

[corpora]

[corpora.train]
@readers = "mh.linker_docs.v1"
n = 96

[corpora.dev]
@readers = "mh.linker_docs.v1"
n = 24
seed = 1

[training]
seed = 0
dropout = 0.1
accumulate_gradient = 2
patience = 0
max_epochs = 0
max_steps = 80
eval_frequency = 20
annotating_components = ["ner"]

[training.optimizer]
@optimizers = "Adam.v1"
learn_rate = 0.05

[training.batcher]
@batchers = "spacy.batch_by_words.v1"
size = 300
tolerance = 0.2

[training.score_weights]
nel_micro_f = 1.0
"""

VEC_D = 16


def linker_docs(n, seed=0):
    """Deterministic context-split linking corpus: 'Python' at (3, 4) is
    Q_python_lang after 'code in', Q_python_snake after 'bite from'."""
    import numpy as np

    from spacy_ray_tpu.pipeline.doc import Doc, Span

    rng = np.random.RandomState(seed)
    docs = []
    contexts = [
        (["code", "in"], "Q_python_lang"),
        (["bite", "from"], "Q_python_snake"),
    ]
    for _ in range(n):
        pre, ent = contexts[rng.randint(len(contexts))]
        words = ["I", *pre, "Python", "today"]
        doc = Doc(words=words)
        doc.ents.append(Span(3, 4, "TOPIC", kb_id=ent))
        docs.append(doc)
    return docs


def make_linker_kb():
    import numpy as np

    from spacy_ray_tpu.pipeline.kb import KnowledgeBase

    rng = np.random.RandomState(0)
    kb = KnowledgeBase(VEC_D)
    for ent in ("Q_python_lang", "Q_python_snake"):
        kb.add_entity(ent, freq=10.0, vector=rng.normal(size=VEC_D))
    kb.add_alias("Python", ["Q_python_lang", "Q_python_snake"], [0.5, 0.5])
    return kb


def register_linker_reader():
    """Idempotent (registration overwrites): callable from both the child
    and the parent test process."""
    from spacy_ray_tpu.pipeline.doc import Example
    from spacy_ray_tpu.registry import registry

    @registry.readers("mh.linker_docs.v1")
    def linker_docs_reader(n: int, seed: int = 0):
        def read():
            return iter(
                [Example.from_gold(d) for d in linker_docs(n, seed=seed)]
            )

        return read


def main() -> int:
    rank = int(sys.argv[1])
    port = sys.argv[2]
    data_dir = sys.argv[3]

    import jax

    # CPU platform and device count must be selected before the backend
    # initializes
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 4)
    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{port}", num_processes=2, process_id=rank
    )
    assert jax.process_count() == 2, jax.process_count()
    assert jax.process_index() == rank
    assert len(jax.devices()) == 8, len(jax.devices())
    assert len(jax.local_devices()) == 4

    import numpy as np

    # --- place_batch: the global batch must contain EVERY host's rows, in
    # host order — not each host's rows sliced at that host's global shard
    # offsets (the device_put bug this guards against yields
    # [0..3, 104..107] here instead of [0..3, 100..103]).
    from spacy_ray_tpu.parallel.mesh import build_mesh
    from spacy_ray_tpu.parallel.step import place_batch

    mesh = build_mesh(n_data=8)
    local = (np.arange(4, dtype=np.float32) + 100.0 * rank)[:, None] * np.ones(
        (1, 3), np.float32
    )
    g = place_batch(local, mesh)
    assert g.shape == (8, 3), g.shape
    from jax.sharding import NamedSharding, PartitionSpec as P

    col = jax.jit(
        lambda x: x[:, 0], out_shardings=NamedSharding(mesh, P())
    )(g)
    got = np.asarray(jax.device_get(col))
    want = np.array([0, 1, 2, 3, 100, 101, 102, 103], np.float32)
    assert np.array_equal(got, want), f"global batch rows wrong: {got}"

    # --- end-to-end train() across 2 processes ---
    from spacy_ray_tpu.config import Config
    from spacy_ray_tpu.training.loop import train

    cfg_text = CFG_TEMPLATE.format(data_dir=data_dir)
    nlp, result = train(Config.from_str(cfg_text), stdout_log=False)
    assert result.final_step > 0
    assert result.best_score >= 0, "eval never ran (too few steps for eval_frequency)"

    # SPMD symmetry: every process must have computed identical scores and
    # word counts (words are a global sum now, not a local count).
    from jax.experimental import multihost_utils

    stats = multihost_utils.process_allgather(
        np.array([result.best_score, float(result.words_seen)], np.float64)
    ).reshape(-1, 2)
    assert np.allclose(stats[0], stats[1]), f"rank-divergent results: {stats}"

    # Global words/epoch must be ~ the FULL corpus, not the ~half this host
    # saw locally (the pre-fix accounting), and not x2 (the reference's
    # estimated scaling, worker.py:310). With accumulate_gradient=2 and
    # unequal shards, up to a few batches per host are dropped when the
    # shorter stream ends mid-group, hence the loose lower bound — the
    # pre-fix failure modes land far outside [0.65, 1.0]x.
    import json

    with open(f"{data_dir}/train.jsonl") as f:
        corpus_words = sum(
            len(json.loads(line)["tokens"]) for line in f if line.strip()
        )
    expect = 3 * corpus_words  # max_epochs=3
    assert 0.65 * expect <= result.words_seen <= expect, (
        f"words_seen={result.words_seen} expected ~{expect} "
        f"(global sum over hosts, 2 epochs)"
    )

    # --- annotating_components under multi-host (VERDICT r3 next #2) ---
    # Tagger-annotating a tagger pipeline is a gradient NO-OP (targets come
    # from the reference docs), so this run must reproduce the plain run
    # bit-for-bit — while exercising the whole host-local annotation path:
    # per-group device_get of the replicated trunk+head params and a
    # mesh-free local predict on every host. Deadlock or divergence here
    # means the multi-host annotation machinery is broken.
    cfg_ann = cfg_text.replace(
        "[training]\n", '[training]\nannotating_components = ["tagger"]\n', 1
    )
    assert "annotating_components" in cfg_ann
    nlp_ann, res_ann = train(Config.from_str(cfg_ann), stdout_log=False)
    assert res_ann.final_step == result.final_step, (
        res_ann.final_step, result.final_step
    )
    assert res_ann.words_seen == result.words_seen, (
        res_ann.words_seen, result.words_seen
    )
    assert abs(res_ann.best_score - result.best_score) < 1e-9, (
        f"annotating run diverged from plain run: "
        f"{res_ann.best_score} vs {result.best_score}"
    )

    # --- CONSUMING annotation under multi-host (VERDICT r4 next #4) ---
    # The no-op check above proves the machinery doesn't crash or diverge,
    # but its annotations are never read. Here the linker trains on the
    # NER's PREDICTED mentions (use_gold_ents = false): if the host-local
    # `needed`-subtree handoff in loop.py fed the annotation forward wrong
    # trunk/head params, the mentions would be wrong or absent, the
    # linker's targets would collapse, and nel_micro_f would not reach the
    # single-process quality band (the parent test asserts proximity).
    register_linker_reader()
    res_cons = train(
        Config.from_str(CONSUMING_CFG_TEMPLATE.format(data_dir=data_dir)),
        stdout_log=False,
    )[1]
    assert res_cons.best_score > 0.9, (
        f"consuming-annotation run failed to learn from predicted mentions "
        f"(nel_micro_f={res_cons.best_score}, "
        f"history={[h['score'] for h in res_cons.history]})"
    )
    cons_stats = multihost_utils.process_allgather(
        np.array([res_cons.best_score], np.float64)
    )
    assert np.allclose(cons_stats[0], cons_stats[1]), (
        f"rank-divergent consuming scores: {cons_stats}"
    )

    # --- exact per-rank resume (VERDICT r3 next #4) ---
    # resume_train.jsonl: 9 same-length docs -> 5 vs 4 docs/epoch per rank
    # -> 3 vs 2 batches/epoch (size=40 packs two 20-token docs) -> the
    # ranks' (epoch, batches_in_epoch) drift apart after the first epoch
    # rollover. The interrupted-and-resumed run must reproduce the
    # uninterrupted run BIT-FOR-BIT on both ranks; pre-fix, rank 1 resumed
    # from rank 0's saved position and silently trained on the wrong
    # batch sequence.
    from pathlib import Path

    from spacy_ray_tpu.training.checkpoint import TrainCheckpoint

    def resume_cfg():
        text = (
            CFG_TEMPLATE.format(data_dir=data_dir)
            .replace(f"{data_dir}/train.jsonl", f"{data_dir}/resume_train.jsonl")
            .replace("max_epochs = 3", "max_epochs = 0")
            .replace("accumulate_gradient = 2", "accumulate_gradient = 1")
            .replace("size = 300", "size = 40")
        )
        return Config.from_str(text)

    out_dir = Path(data_dir) / "resume_out"
    nlp_a, _ = train(resume_cfg(), max_steps_override=8, stdout_log=False)
    nlp_b, _ = train(
        resume_cfg(), output_path=out_dir, max_steps_override=4, stdout_log=False
    )
    # barrier: rank 1 must not read the checkpoint before rank 0's writes
    # (which happen inside its train()) are all flushed
    multihost_utils.sync_global_devices("resume_checkpoint_written")
    ck = TrainCheckpoint.load(out_dir / "last-model")
    pos = ck["extra"].get("per_rank_positions")
    assert pos is not None and len(pos) == 2, f"per-rank positions missing: {pos}"
    assert pos[0] != pos[1], (
        f"per-rank positions did not drift — test corpus no longer "
        f"discriminates: {pos}"
    )
    nlp_c, _ = train(
        resume_cfg(), output_path=out_dir, resume=True, max_steps_override=8,
        stdout_log=False,
    )
    leaves_a = jax.tree_util.tree_leaves(nlp_a.params)
    leaves_c = jax.tree_util.tree_leaves(nlp_c.params)
    assert len(leaves_a) == len(leaves_c)
    for la, lc in zip(leaves_a, leaves_c):
        assert np.array_equal(np.asarray(la), np.asarray(lc)), (
            "resumed run diverged from uninterrupted run"
        )

    # --- multi-host parse: each process annotates a round-robin shard of
    # the input and writes its own output part (cli.py parse_command) ---
    from spacy_ray_tpu.cli import main as cli_main

    parse_out = Path(data_dir) / "parsed.jsonl"
    rc = cli_main([
        "parse", str(out_dir / "last-model"), f"{data_dir}/dev.jsonl",
        str(parse_out), "--device", "cpu",
    ])
    assert rc == 0
    my_part = parse_out.with_name(f"{parse_out.stem}.part{rank}{parse_out.suffix}")
    assert my_part.exists(), f"missing per-rank parse output {my_part}"
    import json as _json

    rows = [_json.loads(l) for l in my_part.read_text().splitlines()]
    assert len(rows) == 15, len(rows)  # 30 dev docs round-robin over 2 hosts
    assert all(r.get("tags") for r in rows)

    print(
        f"CHILD_OK rank={rank} words={result.words_seen} "
        f"step={result.final_step} score={result.best_score:.4f} "
        f"ann_score={res_ann.best_score:.4f} "
        f"cons_score={res_cons.best_score:.4f}",
        flush=True,
    )
    jax.distributed.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
