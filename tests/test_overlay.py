"""Precision-overlay serving (spacy_ray_tpu/serving/overlay.py): the
resolve policy (CPU auto OFF — PR 5 parity), bf16-overlay output within
documented tolerance of f32, coverage refusal on unknown trunk leaves,
no-trunk refusal, int8 probe gating, and the honest labels every
resolution carries."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spacy_ray_tpu.config import Config
from spacy_ray_tpu.models.transformer import (
    SHADOW_LEAF_NAMES,
    pipeline_shadow_dtype,
    shadow_coverage,
)
from spacy_ray_tpu.pipeline.language import Pipeline
from spacy_ray_tpu.presets import TINY_TRF_TAGGER_CFG
from spacy_ray_tpu.serving.overlay import (
    PRECISION_CHOICES,
    build_serving_overlay,
    resolve_precision,
)
from spacy_ray_tpu.util import synth_corpus

CNN_CFG = """
[nlp]
lang = "en"
pipeline = ["tok2vec","tagger"]

[components]

[components.tok2vec]
factory = "tok2vec"

[components.tok2vec.model]
@architectures = "spacy.HashEmbedCNN.v2"
width = 64
depth = 2
embed_size = 512

[components.tagger]
factory = "tagger"

[components.tagger.model]
@architectures = "spacy.Tagger.v2"

[components.tagger.model.tok2vec]
@architectures = "spacy.Tok2VecListener.v1"
width = 64
"""


@pytest.fixture(scope="module")
def trf_nlp():
    nlp = Pipeline.from_config(Config.from_str(TINY_TRF_TAGGER_CFG))
    egs = synth_corpus(32, "tagger", seed=0)
    nlp.initialize(lambda: iter(egs), seed=0)
    return nlp


@pytest.fixture(scope="module")
def cnn_nlp():
    nlp = Pipeline.from_config(Config.from_str(CNN_CFG))
    egs = synth_corpus(32, "tagger", seed=0)
    nlp.initialize(lambda: iter(egs), seed=0)
    return nlp


# ----------------------------------------------------------------------
# resolve policy
# ----------------------------------------------------------------------


def test_auto_resolves_off_on_cpu_pr5_policy_parity(trf_nlp):
    """The PR 5 policy, verbatim: "auto" arms reduced precision only on
    accelerators. CPU must resolve f32 — the same decision
    ``[training] bf16_shadow = "auto"`` makes through
    ``pipeline_shadow_dtype`` (this pipeline's compute dtype resolves
    f32 on CPU, so the TRAINING shadow is off there too — the two knobs
    may never diverge)."""
    resolved, reason = resolve_precision("auto", "cpu")
    assert resolved == "f32"
    assert "cpu" in reason
    assert jax.default_backend() == "cpu"
    ov = build_serving_overlay(trf_nlp, "auto")
    assert ov.resolved == "f32" and ov.n_overlaid == 0
    assert ov.params is trf_nlp.params  # untouched tree, not a copy
    # training-side parity: auto shadow is off on CPU for the same model
    assert pipeline_shadow_dtype(trf_nlp) is None


def test_auto_arms_bf16_on_accelerators():
    for backend in ("tpu", "gpu"):
        resolved, _ = resolve_precision("auto", backend)
        assert resolved == "bf16"


def test_int8_cpu_auto_off_unless_forced(monkeypatch):
    """The int8 auto-resolution policy mirrors bf16's shape: OFF on CPU
    (typed refusal, f32 served) unless SRT_PALLAS_INT8=1 forces the
    interpret-mode kernel. Enforced here like the bf16 policy above."""
    from spacy_ray_tpu.ops.int8_matmul import _PROBE_CACHE

    monkeypatch.delenv("SRT_PALLAS_INT8", raising=False)
    _PROBE_CACHE.clear()
    resolved, reason = resolve_precision("int8", "cpu")
    assert resolved == "f32"
    assert "probe refused" in reason and "auto-off on cpu" in reason
    # requesting the tpu resolution from a CPU host must fail the
    # COMPILED-kernel probe, never pass via the interpret fallback
    resolved, reason = resolve_precision("int8", "tpu")
    assert resolved == "f32" and "probe refused" in reason
    monkeypatch.setenv("SRT_PALLAS_INT8", "1")
    _PROBE_CACHE.clear()
    resolved, reason = resolve_precision("int8", "cpu")
    assert resolved == "int8"
    assert "active (pallas interpret-mode)" in reason
    _PROBE_CACHE.clear()


def test_unknown_precision_rejected():
    with pytest.raises(ValueError):
        resolve_precision("fp8", "cpu")
    assert set(PRECISION_CHOICES) == {"auto", "f32", "bf16", "int8"}


# ----------------------------------------------------------------------
# overlay correctness
# ----------------------------------------------------------------------


def test_bf16_overlay_output_within_tolerance(trf_nlp):
    """Forced-bf16 overlay forward stays within documented tolerance of
    the f32 forward on fixture docs. Tolerance: bf16 has an 8-bit
    mantissa, so per-matmul relative error is ~2^-8; through a 2-layer
    trunk the logits are pinned at |Δ| <= 0.15 absolute / 2% of the
    logit range — and the argmax decisions (the served tags) must not
    flip on these fixtures."""
    egs = synth_corpus(16, "tagger", seed=3)
    batch = trf_nlp.collate(egs[:8], pad_batch_to=8, pad_len_to=16)
    fwd = jax.jit(trf_nlp.make_forward_fn())
    out_f32 = fwd(trf_nlp.params, batch["tokens"])
    ov = build_serving_overlay(trf_nlp, "bf16")
    assert ov.resolved == "bf16" and ov.n_overlaid == 16  # 2 layers x 8
    assert "forced" in ov.label  # honest: auto would not have armed this
    out_bf16 = fwd(ov.params, batch["tokens"])
    logits_f32 = np.asarray(out_f32["tagger"].X)
    logits_bf16 = np.asarray(out_bf16["tagger"].X)
    span = float(logits_f32.max() - logits_f32.min())
    max_abs = float(np.max(np.abs(logits_f32 - logits_bf16)))
    assert max_abs <= max(0.15, 0.02 * span), (
        f"bf16 overlay drifted {max_abs} from f32 (range {span})"
    )
    assert np.array_equal(
        logits_f32.argmax(-1), logits_bf16.argmax(-1)
    ), "served tags flipped under the bf16 overlay on fixture docs"


def test_overlay_leaves_are_bf16_and_masters_untouched(trf_nlp):
    ov = build_serving_overlay(trf_nlp, "bf16")
    layer = ov.params["transformer"]["layer_0"]
    for k in layer:
        if k in SHADOW_LEAF_NAMES:
            assert layer[k].dtype == jnp.bfloat16
        else:
            assert layer[k].dtype == jnp.float32  # LN/router stay f32
    # the pipeline's master tree is not mutated
    assert (
        trf_nlp.params["transformer"]["layer_0"]["qkv_W"].dtype
        == jnp.float32
    )


def test_overlay_refused_on_unknown_trunk_leaf(trf_nlp):
    """A trunk layer carrying a leaf the shadow scheme does not know
    must refuse the whole overlay (f32 fallback, refusal in the label)
    — a half-covered tree shipping under a "bf16" label would be a
    false claim."""
    saved = trf_nlp.params
    doctored = dict(saved)
    doctored["transformer"] = dict(saved["transformer"])
    doctored["transformer"]["layer_0"] = dict(
        saved["transformer"]["layer_0"]
    )
    doctored["transformer"]["layer_0"]["mystery_W"] = jnp.ones(
        (4, 4), jnp.float32
    )
    trf_nlp.params = doctored
    try:
        eligible, unknown = shadow_coverage(trf_nlp.params)
        assert unknown == ["transformer/layer_0/mystery_W"]
        assert eligible > 0  # refusal is about coverage, not eligibility
        ov = build_serving_overlay(trf_nlp, "bf16")
        assert ov.resolved == "f32" and ov.n_overlaid == 0
        assert "refused" in ov.label and "mystery_W" in ov.label
        assert ov.params is doctored  # serves the untouched f32 tree
    finally:
        trf_nlp.params = saved


def test_overlay_refused_without_trunk(cnn_nlp):
    """No transformer trunk (the CNN serving flagship) = nothing the
    shadow scheme covers: honest f32 fallback, never a bf16 label."""
    eligible, unknown = shadow_coverage(cnn_nlp.params)
    assert eligible == 0 and unknown == []
    ov = build_serving_overlay(cnn_nlp, "bf16")
    assert ov.resolved == "f32" and ov.n_overlaid == 0
    assert "refused" in ov.label


@pytest.fixture
def forced_int8(monkeypatch):
    from spacy_ray_tpu.ops.int8_matmul import _PROBE_CACHE

    monkeypatch.setenv("SRT_PALLAS_INT8", "1")
    _PROBE_CACHE.clear()
    yield
    _PROBE_CACHE.clear()


def test_int8_overlay_output_within_tolerance(trf_nlp, forced_int8):
    """Forced-int8 overlay forward stays within the SAME documented
    envelope as the bf16 suite above on fixture docs. Tolerance
    rationale: per-channel symmetric int8 bounds each weight element's
    error by scale/2 = absmax(channel)/254; through a K-dim contraction
    the logit error concentrates well under the bf16 bound (measured
    ~4e-4 on these fixtures vs bf16's ~1e-1 envelope) — so int8 reuses
    the bf16 envelope rather than inventing a looser one. And the
    argmax decisions (the served tags) must not flip."""
    egs = synth_corpus(16, "tagger", seed=3)
    batch = trf_nlp.collate(egs[:8], pad_batch_to=8, pad_len_to=16)
    fwd = jax.jit(trf_nlp.make_forward_fn())
    out_f32 = fwd(trf_nlp.params, batch["tokens"])
    ov = build_serving_overlay(trf_nlp, "int8")
    assert ov.resolved == "int8"
    assert ov.n_overlaid == 8  # 2 layers x 4 dense matmul weights
    assert "active (pallas interpret-mode)" in ov.label
    out_i8 = fwd(ov.params, batch["tokens"])
    logits_f32 = np.asarray(out_f32["tagger"].X)
    logits_i8 = np.asarray(out_i8["tagger"].X)
    span = float(logits_f32.max() - logits_f32.min())
    max_abs = float(np.max(np.abs(logits_f32 - logits_i8)))
    assert max_abs <= max(0.15, 0.02 * span), (
        f"int8 overlay drifted {max_abs} from f32 (range {span})"
    )
    assert np.array_equal(
        logits_f32.argmax(-1), logits_i8.argmax(-1)
    ), "served tags flipped under the int8 overlay on fixture docs"


def test_int8_engine_reports_honest_labels(trf_nlp, forced_int8):
    """The engine path: serve_params carry the quantized dicts and the
    /healthz-bound label says exactly how the kernel runs."""
    from spacy_ray_tpu.serving import InferenceEngine

    engine = InferenceEngine(
        trf_nlp, max_batch_docs=2, max_doc_len=8, precision="int8"
    )
    try:
        assert engine.overlay.resolved == "int8"
        layer = engine.serve_params["transformer"]["layer_0"]
        assert layer["qkv_W"]["q8"].dtype == jnp.int8
        assert layer["qkv_b"].dtype == jnp.float32  # weight-only
        engine.start(warmup=True)
        req = engine.submit_texts(["the cat runs fast"])
        assert req.docs[0].tags
    finally:
        engine.stop()


def test_int8_engine_auto_refuses_on_cpu_unforced(trf_nlp, monkeypatch):
    from spacy_ray_tpu.ops.int8_matmul import _PROBE_CACHE
    from spacy_ray_tpu.serving import InferenceEngine

    monkeypatch.delenv("SRT_PALLAS_INT8", raising=False)
    _PROBE_CACHE.clear()
    engine = InferenceEngine(
        trf_nlp, max_batch_docs=2, max_doc_len=8, precision="int8"
    )
    assert engine.overlay.resolved == "f32"
    assert "probe refused" in engine.overlay.label
    assert engine.serve_params is trf_nlp.params


# ----------------------------------------------------------------------
# engine integration: the labels the record surfaces carry
# ----------------------------------------------------------------------


def test_engine_serves_overlay_params_and_reports_labels(trf_nlp):
    from spacy_ray_tpu.serving import InferenceEngine

    engine = InferenceEngine(
        trf_nlp, max_batch_docs=4, max_doc_len=16, precision="bf16"
    )
    try:
        assert engine.overlay.resolved == "bf16"
        assert engine.serve_params is engine.overlay.params
        assert (
            engine.serve_params["transformer"]["layer_0"]["qkv_W"].dtype
            == jnp.bfloat16
        )
        engine.start(warmup=True)
        req = engine.submit_texts(["the cat runs fast"])
        assert req.docs[0].tags
    finally:
        engine.stop()


def test_engine_auto_is_f32_on_cpu(trf_nlp):
    from spacy_ray_tpu.serving import InferenceEngine

    engine = InferenceEngine(
        trf_nlp, max_batch_docs=4, max_doc_len=16, precision="auto"
    )
    assert engine.overlay.resolved == "f32"
    assert engine.serve_params is trf_nlp.params
