"""``trunk_check.py`` at rehearsal widths on the CPU, with a reference that is
added as a file in a temporary directory: it owns its inputs (``make_inputs``)
and declares a gradient tolerance, and no file under ``benchmark/`` is edited
for it. The comparison passes on the program as it is and fails when one leaf
of the program's gradient is 1% off. Runs on a CPU: ``pytest benchmark/tests``.
"""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))

import common  # noqa: E402
import trunk_check  # noqa: E402

# the plain reference of trf, as shipped, and what a later PR's reference adds
STUB_ADDS = '''

GRAD_TOLERANCE = 5e-2
GRAD_TOLERANCE_F32 = 1e-3
N_HEADS = 4


def make_inputs(nlp, master, tokens):
    """The reference owns its inputs: here the hashed row ids and the mask,
    and the number of heads from its own constant."""
    import trunk_check

    ids, mask, _ = trunk_check.hash_inputs(nlp, master, tokens)
    return ids, mask, N_HEADS
'''


@pytest.fixture(scope="module")
def pipeline():
    """trf at the rehearsal's widths, initialised from seeded documents."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from spacy_ray_tpu.config import load_config
    from spacy_ray_tpu.pipeline.doc import Example
    from spacy_ray_tpu.pipeline.language import Pipeline
    from spacy_ray_tpu.training.corpus import _doc_from_json

    config_file = json.loads((BENCH / "configs" / "trf.json").read_text())
    spec = json.loads((BENCH / "traffic" / "ewt10_b7k.json").read_text())["docs"]
    generator = common.load_module("generators", spec["generator"])
    config = load_config(BENCH.parent / config_file["program_config"],
                         config_file["rehearse_overrides"], interpolate=False)
    nlp = Pipeline.from_config(config)
    examples = [Example.from_gold(_doc_from_json(d)) for d in generator.generate(40, 5, spec)]
    nlp.initialize(lambda: examples, seed=5)
    return nlp, generator.generate(4, 11, spec)


@pytest.fixture()
def stub_reference(tmp_path, monkeypatch):
    reference = tmp_path / "benchmark" / "reference"
    reference.mkdir(parents=True)
    (reference / "stub.py").write_text((BENCH / "reference" / "trf.py").read_text() + STUB_ADDS)
    monkeypatch.setattr(common, "ROOT", tmp_path)
    monkeypatch.setattr(common, "BENCH", tmp_path / "benchmark")


def test_shipped_reference_compares_no_gradient(pipeline):
    nlp, docs = pipeline
    out = trunk_check.check(nlp, nlp.params, "trf", docs, seed=3)
    assert out["ok"] and out["rel_err"] <= out["tolerance"] == 2e-5
    assert sorted(out) == ["compute", "ok", "rel_err", "sequences", "tokens", "tolerance"]
    assert trunk_check.compared(out, "trf") == {"trunk_rel_err": [out["rel_err"], 2e-5]}


def test_added_reference_with_its_own_inputs_and_a_gradient_tolerance(pipeline, stub_reference):
    nlp, docs = pipeline
    out = trunk_check.check(nlp, nlp.params, "stub", docs, seed=3)
    assert out["ok"], out
    assert out["rel_err"] <= 2e-5
    assert out["grad_tolerance"] == 1e-3 and 0.0 < out["grad_rel_err"] <= 1e-4
    assert out["grad_leaves"] > 20 and out["grad_worst_leaf"] and out["grad_norm_gap"] <= 1e-5
    assert trunk_check.compared(out, "stub")["trunk_grad_rel_err"] == [out["grad_rel_err"], 1e-3]


def test_one_leaf_of_the_gradient_off_by_a_hundredth_fails(pipeline, stub_reference, monkeypatch):
    import jax

    nlp, docs = pipeline
    trunk = nlp.components[nlp.tok2vec_name]
    real_forward = trunk.forward

    @jax.custom_vjp
    def off_by_a_hundredth(x):
        return x

    off_by_a_hundredth.defvjp(lambda x: (x, None), lambda _, g: (1.01 * g,))

    def forward(params, tokens, ctx):
        layer = dict(params["layer_1"], ffn_W2=off_by_a_hundredth(params["layer_1"]["ffn_W2"]))
        return real_forward(dict(params, layer_1=layer), tokens, ctx)

    monkeypatch.setattr(trunk, "forward", forward)
    out = trunk_check.check(nlp, nlp.params, "stub", docs, seed=3)
    assert out["rel_err"] <= 2e-5  # the forward pass is the same
    assert not out["ok"]
    assert out["grad_worst_leaf"] == "['layer_1']['ffn_W2']"
    assert out["grad_rel_err"] == pytest.approx(0.01, rel=0.05)


def test_the_gradient_program_is_one_cache_entry_for_every_seed(pipeline, stub_reference, tmp_path):
    """The cotangent is an argument of the gradient program, not a constant
    in its text: a second seed finds every program of the check in the
    persistent compile cache and writes none (closed over, each seed was a new
    program of the cotangent's size and more, that nothing read again: PERF.md
    section 6, PR 32). Every run of a cell is a new process, so the persistent
    cache's entries are what counts; the program's compile hook counts requests,
    hits included."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    nlp, docs = pipeline
    cache = tmp_path / "xla_cache"
    settings = {"jax_compilation_cache_dir": str(cache), "jax_enable_compilation_cache": True,
                "jax_persistent_cache_min_compile_time_secs": 0.0,
                "jax_persistent_cache_min_entry_size_bytes": -1}
    before = {key: getattr(jax.config, key) for key in settings}

    def entries():
        return sorted(p.name for p in cache.iterdir() if not p.name.endswith("-atime"))

    try:
        for key, value in settings.items():
            jax.config.update(key, value)
        compilation_cache.reset_cache()
        first = trunk_check.check(nlp, nlp.params, "stub", docs, seed=3)
        after_first = entries()
        second = trunk_check.check(nlp, nlp.params, "stub", docs, seed=4)
        after_second = entries()
    finally:
        for key, value in before.items():
            jax.config.update(key, value)
        compilation_cache.reset_cache()
    assert first["ok"] and second["ok"]
    assert first["grad_rel_err"] != second["grad_rel_err"]  # another cotangent
    assert first["rel_err"] == second["rel_err"]  # the same forward
    assert len([name for name in after_first if "system_loss" in name]) == 1
    assert after_second == after_first


def test_gradient_errors_by_hand():
    import numpy as np

    want = {"a": np.array([1.0, -4.0]), "b": np.array([1e-9, 0.0]), "c": np.array([[2.0]])}
    got = {"a": np.array([1.0, -3.0]), "b": np.array([2e-9, 0.0]), "c": np.array([[2.0]])}
    out = trunk_check.gradient_errors(got, want)
    # a: 1 / 4; b is all but nought and is held against the median leaf's 2.0
    assert out["grad_rel_err"] == pytest.approx(0.25) and out["grad_worst_leaf"] == "['a']"
    assert out["grad_median_leaf_max"] == 2.0 and out["grad_leaves"] == 3
    # norms: a 4.123 -> 3.162, held against its own 4.123
    assert out["grad_norm_gap"] == pytest.approx((17 ** 0.5 - 10 ** 0.5) / 17 ** 0.5)
    with pytest.raises(ValueError):
        trunk_check.gradient_errors({"a": got["a"]}, want)
