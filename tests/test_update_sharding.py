"""Cross-replica weight-update sharding + mesh-shape-portable resume.

The two claims this suite pins, both to EQUALITY (the fused==optax
discipline of tests/test_fused_update.py):

* ``update_sharding = "full"`` — each replica applies the optimizer only
  to its owned param shard, updated params allgathered back (arXiv
  2004.13336) — produces BIT-IDENTICAL params, opt state, and losses to
  ``"replicated"`` on the same batch stream, with and without the fused
  transformation, gradient accumulation, and the bf16 shadow.
* Checkpoints are mesh-shape portable: the v2 owner-shard part files
  reassemble into the canonical unsharded layout exactly, re-shard under
  any mesh bit-exactly, fall back on a torn part, and v1 single-pickle
  generations remain loadable (format regression).
"""

import json
import pickle

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spacy_ray_tpu.config import Config
from spacy_ray_tpu.parallel.mesh import build_mesh
from spacy_ray_tpu.parallel.step import (
    make_train_step,
    place_batch,
    place_replicated,
    resolve_update_sharding,
    shard_opt_state,
    update_sharding_status,
)
from spacy_ray_tpu.pipeline.language import Pipeline
from spacy_ray_tpu.registry import registry
from spacy_ray_tpu.training.checkpoint import (
    CheckpointCorrupt,
    TrainCheckpoint,
    save_params,
)
from spacy_ray_tpu.training.optimizers import fuse_optimizer
from spacy_ray_tpu.util import synth_corpus

_leaves = jax.tree_util.tree_leaves


def _assert_tree_equal(a, b, what="trees"):
    la, lb = _leaves(a), _leaves(b)
    assert len(la) == len(lb), f"{what}: leaf count {len(la)} != {len(lb)}"
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y), err_msg=what)


# ----------------------------------------------------------- knob resolution


def test_resolve_update_sharding_matrix():
    r = resolve_update_sharding
    # explicit modes pass through untouched, whatever the context
    for mode in ("replicated", "zero1", "full"):
        assert r(mode, zero1=True, n_data=8, backend="tpu") == mode
    # auto honors the legacy zero1 alias exactly
    assert r("auto", zero1=True, n_data=8, backend="tpu") == "zero1"
    assert r("auto", zero1=True, n_data=1, backend="cpu") == "zero1"
    # auto arms full ONLY on accelerator meshes with >1 data rank
    assert r("auto", n_data=8, backend="tpu") == "full"
    assert r("auto", n_data=8, backend="gpu") == "full"
    assert r("auto", n_data=8, backend="cpu") == "replicated"
    assert r("auto", n_data=1, backend="tpu") == "replicated"
    with pytest.raises(ValueError, match="update_sharding"):
        r("sharded", n_data=8)


def test_update_sharding_status_labels(mesh8):
    # honest labeling: a 1-rank mesh must not claim a sharded update
    mesh1 = build_mesh(n_data=1, devices=jax.devices()[:1])
    assert update_sharding_status("replicated", mesh8) == "replicated"
    assert update_sharding_status("full", mesh1).startswith(
        "replicated (full degenerates"
    )
    assert "8-way" in update_sharding_status("full", mesh8)
    assert "8-way" in update_sharding_status("zero1", mesh8)


def test_training_knob_validation(tagger_config_text):
    from spacy_ray_tpu.training.loop import resolve_training

    cfg = Config.from_str(tagger_config_text)
    raw = dict(cfg.get("training") or {})
    raw["update_sharding"] = "fully"
    cfg["training"] = raw
    with pytest.raises(ValueError, match="update_sharding"):
        resolve_training(cfg)
    raw["update_sharding"] = "full"
    cfg["training"] = raw
    assert resolve_training(cfg)["update_sharding"] == "full"


# ------------------------------------------------- full == replicated (exact)


CNN_CFG = """
[nlp]
lang = "en"
pipeline = ["tok2vec","tagger"]
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
"""


@pytest.fixture(scope="module")
def cnn_setup():
    nlp = Pipeline.from_config(Config.from_str(CNN_CFG))
    egs = synth_corpus(64, "tagger", seed=0)
    nlp.initialize(lambda: iter(egs), seed=0)
    return nlp, egs


def _run_mode(nlp, egs, mode, *, fused=False, accum=1, steps=3, B=16):
    mesh = build_mesh(n_data=8)
    tx = registry.get("optimizers", "Adam.v1")(learn_rate=0.01)
    if fused:
        tx = fuse_optimizer(tx)
        assert tx is not None
    params = place_replicated(
        jax.tree_util.tree_map(jnp.asarray, nlp.params), mesh
    )
    opt_state = shard_opt_state(tx.init(params), mesh, mode)
    update = make_train_step(
        nlp.make_loss_fn(dropout=0.1), tx, mesh, update_sharding=mode,
        accumulate_gradient=accum, opt_state_template=opt_state, donate=False,
    )
    rng = jax.random.PRNGKey(42)
    losses = []
    for s in range(steps):
        group = egs[s * B:(s + 1) * B]
        if accum == 1:
            c = nlp.collate(group, pad_batch_to=B, pad_len_to=16)
            tokens = place_batch(c["tokens"], mesh)
            targets = place_batch(c["targets"], mesh)
        else:
            half = B // accum
            cs = [
                nlp.collate(
                    group[i * half:(i + 1) * half],
                    pad_batch_to=half, pad_len_to=16,
                )
                for i in range(accum)
            ]
            stack = lambda key: jax.tree_util.tree_map(  # noqa: E731
                lambda *xs: jnp.stack(xs), *[c[key] for c in cs]
            )
            tokens = place_batch(stack("tokens"), mesh, accum=True)
            targets = place_batch(stack("targets"), mesh, accum=True)
        params, opt_state, loss, metrics = update(
            params, opt_state, tokens, targets, jax.random.fold_in(rng, s)
        )
        losses.append(float(loss))
    return (
        jax.device_get(params),
        jax.device_get(opt_state),
        losses,
        float(metrics["grad_norm"]),
    )


@pytest.mark.parametrize("fused", [False, True], ids=["optax-chain", "fused"])
def test_full_matches_replicated_to_equality(cnn_setup, fused):
    """THE tentpole equality: the full-sharded update — grads pinned
    behind the barrier, owner-shard apply, params allgathered — must be
    bit-identical to the replicated update on the same batch stream,
    optimizer state included. Tolerances would hide real resharding bugs
    (a desynced shard is a silent wrong-training bug, cf. 2004.13336)."""
    nlp, egs = cnn_setup
    p_r, o_r, l_r, g_r = _run_mode(nlp, egs, "replicated", fused=fused)
    p_f, o_f, l_f, g_f = _run_mode(nlp, egs, "full", fused=fused)
    assert l_f == l_r
    assert g_f == g_r  # stable_global_norm: same value in both programs
    _assert_tree_equal(p_f, p_r, "params full vs replicated")
    _assert_tree_equal(o_f, o_r, "opt_state full vs replicated")


def test_full_matches_replicated_with_accumulation(cnn_setup):
    nlp, egs = cnn_setup
    p_r, o_r, l_r, _ = _run_mode(nlp, egs, "replicated", fused=True, accum=2)
    p_f, o_f, l_f, _ = _run_mode(nlp, egs, "full", fused=True, accum=2)
    assert l_f == l_r
    _assert_tree_equal(p_f, p_r, "params (accum=2)")
    _assert_tree_equal(o_f, o_r, "opt_state (accum=2)")


def test_zero1_program_is_unpinned_but_close(cnn_setup):
    """zero1 keeps its legacy (pre-knob) program — no grad pin — so it is
    only rtol-close to replicated, never asserted bitwise; this pins that
    the mode string routes to the same layout the old bool produced."""
    nlp, egs = cnn_setup
    p_r, _, l_r, _ = _run_mode(nlp, egs, "replicated")
    p_z, _, l_z, _ = _run_mode(nlp, egs, "zero1")
    np.testing.assert_allclose(l_r, l_z, rtol=2e-4)
    for a, b in zip(_leaves(p_r), _leaves(p_z)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-2, atol=1e-5
        )


def test_full_update_donates_state(cnn_setup):
    """Donation audit for the full mode: the constraint/allgather chain
    must not cost an undonated second copy of the tree (the same contract
    the round-7 donation test pins for the replicated update)."""
    nlp, egs = cnn_setup
    mesh = build_mesh(n_data=8)
    tx = fuse_optimizer(registry.get("optimizers", "Adam.v1")(learn_rate=0.01))
    params = place_replicated(
        jax.tree_util.tree_map(jnp.asarray, nlp.params), mesh
    )
    opt_state = shard_opt_state(tx.init(params), mesh, "full")
    update = make_train_step(
        nlp.make_loss_fn(dropout=0.0), tx, mesh, update_sharding="full",
        opt_state_template=opt_state,
    )
    c = nlp.collate(egs[:16], pad_batch_to=16, pad_len_to=16)
    tokens = place_batch(c["tokens"], mesh)
    targets = place_batch(c["targets"], mesh)
    p2, o2, _loss, _m = update(
        params, opt_state, tokens, targets, jax.random.PRNGKey(0)
    )
    assert all(leaf.is_deleted() for leaf in _leaves(params))
    assert all(leaf.is_deleted() for leaf in _leaves(opt_state))
    jax.block_until_ready(p2)


# --------------------------------------------------- full + bf16 shadow

TRF_CFG = """
[nlp]
lang = "en"
pipeline = ["transformer","tagger"]
[components.transformer]
factory = "transformer"
[components.transformer.model]
@architectures = "spacy_ray_tpu.TransformerEncoder.v1"
width = 32
depth = 2
n_heads = 2
embed_size = 500
compute_dtype = "bfloat16"
[components.tagger]
factory = "tagger"
[components.tagger.model]
@architectures = "spacy.Tagger.v2"
[components.tagger.model.tok2vec]
@architectures = "spacy.Tok2VecListener.v1"
width = 32
"""


def test_full_with_shadow_matches_replicated_with_shadow():
    """full + bf16 shadow == replicated + bf16 shadow, bitwise — the
    shard-local shadow refresh (cast before the allgather) changes where
    the cast runs, never its values; the shadow stays exactly
    cast(masters) in both modes."""
    from spacy_ray_tpu.models.transformer import build_param_shadow
    from spacy_ray_tpu.parallel.step import refresh_shadow

    nlp = Pipeline.from_config(Config.from_str(TRF_CFG))
    egs = synth_corpus(32, "tagger", seed=0)
    nlp.initialize(lambda: iter(egs), seed=0)
    mesh = build_mesh(n_data=8)
    c = nlp.collate(egs[:8], pad_batch_to=8, pad_len_to=16)
    tokens = place_batch(c["tokens"], mesh)
    targets = place_batch(c["targets"], mesh)
    loss_fn = nlp.make_loss_fn(dropout=0.0)
    results = {}
    for mode in ("replicated", "full"):
        tx = fuse_optimizer(
            registry.get("optimizers", "Adam.v1")(learn_rate=0.01)
        )
        p = place_replicated(
            jax.tree_util.tree_map(jnp.asarray, nlp.params), mesh
        )
        s = shard_opt_state(tx.init(p), mesh, mode)
        sh = build_param_shadow(p)
        upd = make_train_step(
            loss_fn, tx, mesh, update_sharding=mode,
            opt_state_template=s, shadow=True, donate=False,
        )
        rng = jax.random.PRNGKey(5)
        for i in range(3):
            p, s, sh, loss, _m = upd(
                p, s, sh, tokens, targets, jax.random.fold_in(rng, i)
            )
        results[mode] = (
            jax.device_get(p), jax.device_get(s), jax.device_get(sh),
            float(loss),
        )
    p_f, s_f, sh_f, l_f = results["full"]
    p_r, s_r, sh_r, l_r = results["replicated"]
    assert l_f == l_r
    _assert_tree_equal(p_f, p_r, "params (shadow)")
    _assert_tree_equal(s_f, s_r, "opt_state (shadow)")
    _assert_tree_equal(sh_f, sh_r, "shadow tree")
    # the refreshed shadow is exactly the cast of the final masters
    ref = refresh_shadow(
        jax.tree_util.tree_map(jnp.asarray, p_f), build_param_shadow(p_f)
    )
    _assert_tree_equal(sh_f, jax.device_get(ref), "shadow == cast(masters)")


# --------------------------------------------- checkpoint format v2 (shards)


def _toy_state(mesh, mode="full"):
    import optax

    params = {
        "a": {"w": np.arange(256 * 4, dtype=np.float32).reshape(256, 4)},
        "b": np.arange(7, dtype=np.float32),  # no divisible axis: replicated
    }
    tx = optax.chain(
        optax.clip_by_global_norm(1.0), optax.scale_by_adam(),
        optax.scale_by_learning_rate(lambda c: 0.01),
    )
    opt = tx.init(jax.tree_util.tree_map(jnp.asarray, params))
    return params, shard_opt_state(opt, mesh, mode)


def _save_gen(tmp_path, mesh, step, mode="full"):
    params, opt_sharded = _toy_state(mesh, mode)
    TrainCheckpoint.save(
        tmp_path, params=place_replicated(params, mesh),
        opt_state=opt_sharded, step=step, epoch=0,
        rng=jax.random.PRNGKey(0), best_score=0.1 * step, best_step=step,
        keep=2,
    )
    return params, jax.device_get(opt_sharded)


def test_v2_save_writes_owner_shard_parts(tmp_path, mesh8):
    _save_gen(tmp_path, mesh8, 3)
    names = {p.name for p in tmp_path.iterdir()}
    parts = {f"opt_state-3.part{k}of8.pkl" for k in range(8)}
    assert parts <= names
    assert "opt_state-3.pkl" not in names
    meta = json.loads((tmp_path / "train_meta-3.json").read_text())
    assert meta["format"] == 2 and meta["opt_shards"] == 8
    # every part is individually digest-stamped
    assert parts <= set(meta["digests"])


def test_v2_roundtrip_and_reshard_bit_exact(tmp_path, mesh8):
    """Owner-shard parts reassemble into the canonical unsharded layout
    EXACTLY, and re-shard bit-exactly under 4-, 2-, and 1-device meshes —
    the mesh-shape-portability contract."""
    _, host_opt = _save_gen(tmp_path, mesh8, 3)
    ck = TrainCheckpoint.load(tmp_path)
    _assert_tree_equal(ck["opt_state"], host_opt, "v2 roundtrip")
    assert jax.tree_util.tree_structure(
        ck["opt_state"]
    ) == jax.tree_util.tree_structure(host_opt)
    for n in (4, 2, 1):
        mesh_n = build_mesh(n_data=n, devices=jax.devices()[:n])
        re = shard_opt_state(ck["opt_state"], mesh_n, "full")
        _assert_tree_equal(jax.device_get(re), host_opt, f"reshard@{n}")


def test_v2_torn_part_falls_back_generation(tmp_path, mesh8):
    torn = tmp_path / "torn"
    _save_gen(torn, mesh8, 1)
    _save_gen(torn, mesh8, 2)
    victim = torn / "opt_state-2.part5of8.pkl"
    victim.write_bytes(victim.read_bytes()[:20])
    assert TrainCheckpoint.load(torn)["step"] == 1
    # a DELETED part is equally fatal for that generation
    gone = tmp_path / "gone"
    _save_gen(gone, mesh8, 1)
    _save_gen(gone, mesh8, 2)
    (gone / "opt_state-2.part0of8.pkl").unlink()
    assert TrainCheckpoint.load(gone)["step"] == 1


def test_v2_all_generations_torn_raises_typed(tmp_path, mesh8):
    _save_gen(tmp_path, mesh8, 1)
    for f in tmp_path.glob("opt_state-*.pkl"):
        f.write_bytes(b"torn")
    with pytest.raises(CheckpointCorrupt):
        TrainCheckpoint.load(tmp_path)


def test_v2_retention_cleans_part_files(tmp_path, mesh8):
    for step in (1, 2, 3):
        _save_gen(tmp_path, mesh8, step)
    names = {p.name for p in tmp_path.iterdir()}
    assert not any(n.startswith("opt_state-1.") for n in names), names
    assert any(n.startswith("opt_state-2.part") for n in names)
    assert any(n.startswith("opt_state-3.part") for n in names)


def test_v1_generation_regression_still_loads(tmp_path):
    """A generation written by the pre-v2 single-pickle writer (format key
    absent) must keep loading forever — existing fleets resume across the
    upgrade."""
    import hashlib

    params = {"c": {"w": np.full((2, 2), 1.5, np.float32)}}
    opt = {"m": np.full((2, 2), 15.0, np.float32)}
    save_params(tmp_path / "params-7.npz", params)
    with open(tmp_path / "opt_state-7.pkl", "wb") as f:
        pickle.dump(opt, f)
    digests = {
        name: hashlib.sha256((tmp_path / name).read_bytes()).hexdigest()
        for name in ("params-7.npz", "opt_state-7.pkl")
    }
    meta = {
        "step": 7, "epoch": 0, "rng": [0, 7], "best_score": 0.5,
        "best_step": 7, "extra": {}, "stamp": 7, "digests": digests,
    }
    (tmp_path / "train_meta-7.json").write_text(json.dumps(meta))
    (tmp_path / "train_meta.json").write_text(json.dumps(meta))
    ck = TrainCheckpoint.load(tmp_path)
    assert ck["step"] == 7
    np.testing.assert_array_equal(
        np.asarray(ck["opt_state"]["m"]), opt["m"]
    )
    # and the serving-side reader agrees the generation is intact
    from spacy_ray_tpu.training.checkpoint import Checkpoints

    assert Checkpoints(tmp_path).latest_intact_generation() == 7


def test_v2_serving_reader_and_stdlib_twin_verify_parts(tmp_path, mesh8):
    """Checkpoints.verify_generation and the jax-free watcher twin both
    walk the v2 part list from the meta (not a hardcoded single-pickle
    name) — a torn part must fail verification in both."""
    from spacy_ray_tpu.serving.live.watcher import scan_intact_generations
    from spacy_ray_tpu.training.checkpoint import Checkpoints

    _save_gen(tmp_path, mesh8, 3)
    reader = Checkpoints(tmp_path)
    reader.verify_generation(3)
    assert scan_intact_generations(tmp_path) == [3]
    victim = tmp_path / "opt_state-3.part2of8.pkl"
    victim.write_bytes(b"torn")
    with pytest.raises(CheckpointCorrupt):
        reader.verify_generation(3)
    assert scan_intact_generations(tmp_path) == []
    # params-only scope never touches the opt parts (the swap path)
    reader.verify_generation(3, params_only=True)
    assert scan_intact_generations(tmp_path, params_only=True) == [3]


# ------------------------------------------------------ elastic resume


@pytest.mark.slow
def test_elastic_resume_bit_exact_8_4_1():
    """The acceptance matrix: an 8 -> 4 -> 1 resharded-resume run (state
    round-tripped through owner-shard checkpoints at every mesh change)
    is bit-identical to the same shape schedule run uninterrupted in
    memory — the checkpoint machinery adds nothing beyond the unavoidable
    re-shard. Runs the driver's own dryrun entry."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).parent.parent))
    from __graft_entry__ import dryrun_elastic_resume

    dryrun_elastic_resume(8)


@pytest.mark.slow
def test_train_loop_elastic_resume_across_worker_counts(
    tagger_config_text, tmp_path
):
    """Loop-level elastic resume: train at 8 data ranks with full update
    sharding (checkpoint written as owner-shard parts), then --resume the
    SAME directory at 2 ranks — the run continues from the checkpointed
    step and the resumed checkpoint reshards cleanly."""
    from spacy_ray_tpu.training.loop import train
    from spacy_ray_tpu.util import write_synth_jsonl

    write_synth_jsonl(tmp_path / "train.jsonl", 160, kind="tagger", seed=0)
    write_synth_jsonl(tmp_path / "dev.jsonl", 24, kind="tagger", seed=1)
    cfg = Config.from_str(tagger_config_text).apply_overrides(
        {
            "paths.train": str(tmp_path / "train.jsonl"),
            "paths.dev": str(tmp_path / "dev.jsonl"),
            "training.update_sharding": "full",
            "training.eval_frequency": 4,
        }
    )
    out = tmp_path / "out"
    _nlp, res = train(
        cfg, out, n_workers=8, max_steps_override=8, stdout_log=False
    )
    assert res.final_step == 8
    names = {p.name for p in (out / "last-model").iterdir()}
    assert any(".part0of8." in n for n in names), names
    meta = json.loads((out / "last-model" / "train_meta.json").read_text())
    assert meta["extra"]["mesh"] == {"n_data": 8, "update_sharding": "full"}
    # resume on a QUARTER of the mesh: 8 -> 2 data ranks
    _nlp2, res2 = train(
        cfg, out, n_workers=2, resume=True, max_steps_override=12,
        stdout_log=False,
    )
    assert res2.final_step == 12
    meta2 = json.loads((out / "last-model" / "train_meta.json").read_text())
    assert meta2["extra"]["mesh"]["n_data"] == 2
    assert meta2["opt_shards"] == 2
