"""Int8 weight-only serving path (ops/int8_matmul.py + the overlay's
int8 resolution): interpret-mode kernel numerics on CPU (the real-TPU
path is the same kernel body, compiled — the flash-attention testing
discipline), quantize→dequantize round-trip bounds, the probe policy
matrix (CPU auto-OFF unless forced, honest labels), the refusal matrix
(unknown trunk leaves / trunk-less / MoE trunks), and the hot-swap
contract: re-quantization on swap with ZERO post-swap compiles and
rollback restoring the exact previous overlay."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from spacy_ray_tpu.config import Config
from spacy_ray_tpu.models.transformer import (
    INT8_LEAF_NAMES,
    build_int8_overlay,
    int8_unsupported_leaves,
    transformer_layer_params,
)
from spacy_ray_tpu.ops.int8_matmul import (
    _PROBE_CACHE,
    _int8_matmul_raw,
    dequantize_int8,
    int8_matmul,
    int8_probe,
    int8_vmem_ok,
    quantize_int8,
    reference_int8_matmul,
)
from spacy_ray_tpu.pipeline.language import Pipeline
from spacy_ray_tpu.presets import TINY_TRF_TAGGER_CFG
from spacy_ray_tpu.util import synth_corpus


@pytest.fixture
def forced_int8(monkeypatch):
    """SRT_PALLAS_INT8=1 with a clean probe cache on both sides — the
    force knob's verdict is env-dependent and must not leak."""
    monkeypatch.setenv("SRT_PALLAS_INT8", "1")
    _PROBE_CACHE.clear()
    yield
    _PROBE_CACHE.clear()


def _trf_nlp(seed=0):
    nlp = Pipeline.from_config(Config.from_str(TINY_TRF_TAGGER_CFG))
    egs = synth_corpus(32, "tagger", seed=0)
    nlp.initialize(lambda: iter(egs), seed=seed)
    return nlp


# ----------------------------------------------------------------------
# quantization math
# ----------------------------------------------------------------------


def test_quantize_roundtrip_error_bounded_by_half_scale():
    """Round-to-nearest symmetric quantization: per-element
    reconstruction error <= scale/2 for that element's OUTPUT CHANNEL
    (the per-channel scale is the whole point — a single tensor scale
    would bound every column by the worst column's range)."""
    rng = np.random.default_rng(0)
    # per-column ranges spanning 3 orders of magnitude
    w = rng.normal(size=(64, 48)).astype(np.float32)
    w *= np.logspace(-2, 1, 48, dtype=np.float32)[None, :]
    q8, scale = quantize_int8(jnp.asarray(w))
    assert q8.dtype == jnp.int8 and scale.dtype == jnp.float32
    assert scale.shape == (48,)
    assert int(jnp.max(jnp.abs(q8.astype(jnp.int32)))) <= 127
    err = np.abs(np.asarray(dequantize_int8(q8, scale)) - w)
    bound = np.asarray(scale)[None, :] / 2 + 1e-8
    assert (err <= bound).all(), float((err - bound).max())
    # and the scale really is per-channel absmax/127
    np.testing.assert_allclose(
        np.asarray(scale), np.abs(w).max(axis=0) / 127.0, rtol=1e-6
    )


def test_zero_and_constant_channels_do_not_blow_up():
    w = jnp.zeros((16, 4), jnp.float32)
    q8, scale = quantize_int8(w)
    out = int8_matmul(jnp.ones((3, 16)), q8, scale)
    assert not bool(jnp.any(jnp.isnan(out)))
    np.testing.assert_allclose(np.asarray(out), 0.0)


# ----------------------------------------------------------------------
# kernel numerics (interpret mode on CPU — the tier-1 proof)
# ----------------------------------------------------------------------


def test_kernel_matches_reference_interpret():
    """The pallas kernel body (dequantize-in-kernel, f32 accumulation)
    vs the jnp dequant reference, on unaligned shapes that exercise the
    M/K/N padding paths."""
    rng = np.random.default_rng(1)
    for M, K, N in [(33, 96, 160), (128, 128, 128), (1, 7, 3)]:
        w = jnp.asarray(rng.normal(size=(K, N)).astype(np.float32) * 0.1)
        x = jnp.asarray(rng.normal(size=(M, K)).astype(np.float32))
        q8, scale = quantize_int8(w)
        got = _int8_matmul_raw(x, q8, scale, interpret=True)
        want = reference_int8_matmul(x, q8, scale)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-4
        )


def test_entry_point_handles_lead_dims_and_bf16_activations():
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.normal(size=(32, 24)).astype(np.float32) * 0.1)
    q8, scale = quantize_int8(w)
    x = jnp.asarray(rng.normal(size=(2, 5, 32)).astype(np.float32)).astype(
        jnp.bfloat16
    )
    out = int8_matmul(x, q8, scale)
    assert out.shape == (2, 5, 24) and out.dtype == jnp.float32
    want = reference_int8_matmul(x.astype(jnp.float32), q8, scale)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(want), atol=1e-4, rtol=1e-4
    )


def test_vmem_fallback_is_numerically_identical():
    """Contraction dims past the VMEM budget take the jnp dequant path —
    same numbers, no kernel (the flash-attention fallback discipline)."""
    K = 20_000
    assert not int8_vmem_ok(K)
    rng = np.random.default_rng(3)
    w = jnp.asarray(rng.normal(size=(K, 4)).astype(np.float32) * 0.01)
    q8, scale = quantize_int8(w)
    x = jnp.asarray(rng.normal(size=(2, K)).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(int8_matmul(x, q8, scale)),
        np.asarray(reference_int8_matmul(x, q8, scale)),
        rtol=1e-6,
    )
    assert int8_vmem_ok(4096)  # encoder-trunk Ks stay on the kernel


# ----------------------------------------------------------------------
# probe policy matrix
# ----------------------------------------------------------------------


def test_probe_cpu_auto_off_unless_forced(monkeypatch):
    """The CPU auto-resolution policy, test-enforced like bf16's: OFF
    (typed refusal) without the force knob."""
    monkeypatch.delenv("SRT_PALLAS_INT8", raising=False)
    _PROBE_CACHE.clear()
    ok, why = int8_probe("cpu")
    assert not ok
    assert "probe refused" in why and "auto-off on cpu" in why
    _PROBE_CACHE.clear()


def test_probe_forced_off_refuses_everywhere(monkeypatch):
    monkeypatch.setenv("SRT_PALLAS_INT8", "0")
    _PROBE_CACHE.clear()
    for backend in ("cpu", "tpu"):
        ok, why = int8_probe(backend)
        assert not ok and "SRT_PALLAS_INT8=0" in why
    _PROBE_CACHE.clear()


def test_probe_forced_on_cpu_runs_interpret_with_honest_label(forced_int8):
    ok, why = int8_probe("cpu")
    assert ok
    assert "active (pallas interpret-mode)" in why
    # never the bare compiled-kernel claim on an interpreted backend
    assert "active (pallas) on" not in why


# ----------------------------------------------------------------------
# overlay build + refusal matrix
# ----------------------------------------------------------------------


def test_build_int8_overlay_structure_and_master_isolation():
    nlp = _trf_nlp()
    tree, n_q = build_int8_overlay(nlp.params)
    assert n_q == 8  # 2 layers x {qkv_W, o_W, ffn_W1, ffn_W2}
    layer = tree["transformer"]["layer_0"]
    for k in INT8_LEAF_NAMES:
        assert set(layer[k]) == {"q8", "scale"}
        assert layer[k]["q8"].dtype == jnp.int8
        assert layer[k]["scale"].dtype == jnp.float32
    # biases/LNs stay f32 and are the SAME objects as the master tree
    assert layer["qkv_b"] is nlp.params["transformer"]["layer_0"]["qkv_b"]
    assert layer["ln1_g"].dtype == jnp.float32
    # masters untouched
    assert nlp.params["transformer"]["layer_0"]["qkv_W"].dtype == jnp.float32


def test_moe_trunk_refused(forced_int8):
    """Expert weights are outside the kernel's coverage: the overlay
    must refuse the whole model, never ship an "int8" label over a
    trunk whose weight mass stays f32."""
    from spacy_ray_tpu.serving.overlay import build_params_overlay

    layer = transformer_layer_params(
        jax.random.PRNGKey(0), 32, 64, n_experts=2
    )
    params = {"transformer": {"layer_0": layer}}
    moe = int8_unsupported_leaves(params)
    assert sorted(moe) == [
        "transformer/layer_0/e_W1", "transformer/layer_0/e_W2",
    ]
    ov = build_params_overlay(params, "int8")
    assert ov.resolved == "f32" and ov.n_overlaid == 0
    assert "refused" in ov.label and "e_W1" in ov.label
    assert ov.params is params


def test_unknown_trunk_leaf_and_trunkless_still_refuse(forced_int8):
    from spacy_ray_tpu.serving.overlay import build_params_overlay

    nlp = _trf_nlp()
    doctored = dict(nlp.params)
    doctored["transformer"] = dict(doctored["transformer"])
    doctored["transformer"]["layer_0"] = dict(
        doctored["transformer"]["layer_0"]
    )
    doctored["transformer"]["layer_0"]["mystery_W"] = jnp.ones(
        (4, 4), jnp.float32
    )
    ov = build_params_overlay(doctored, "int8")
    assert ov.resolved == "f32" and "mystery_W" in ov.label

    # trunk-less tree (no layer_i dicts): nothing to quantize — refuse
    ov2 = build_params_overlay({"tok2vec": {"W": jnp.ones((4, 4))}}, "int8")
    assert ov2.resolved == "f32" and "refused" in ov2.label


# ----------------------------------------------------------------------
# hot-swap: re-quantize, zero post-swap compiles, rollback identity
# ----------------------------------------------------------------------


def test_hot_swap_requantizes_with_zero_compiles_and_rollback(forced_int8):
    """swap_params on an int8 engine re-runs the SAME overlay
    resolution (fresh quantization of the candidate masters); the
    re-quantized tree has identical structure/dtypes/shapes so every
    warmed program is reused — zero post-swap compiles — and rollback
    re-seats the previous overlay object, restoring identical outputs."""
    from spacy_ray_tpu.serving.engine import InferenceEngine

    nlp = _trf_nlp(seed=0)
    params_b = _trf_nlp(seed=1).params
    engine = InferenceEngine(
        nlp, max_batch_docs=2, max_doc_len=8, timeout_s=30.0,
        precision="int8",
    )
    assert engine.overlay.resolved == "int8"
    assert "active (pallas interpret-mode)" in engine.overlay.label
    engine.start(warmup=True)
    try:
        text = "the cat runs"
        tags_before = list(engine.submit_texts([text]).docs[0].tags)
        n_compiled_before = sum(
            f._cache_size() for f in nlp._jit_forward.values()
        )
        overlay_before = engine.overlay

        out = engine.swap_params(params_b, 5, source="test")
        assert "int8 (overlay:" in out["precision_label"]
        tags_swapped = list(engine.submit_texts([text]).docs[0].tags)

        n_compiled_after = sum(
            f._cache_size() for f in nlp._jit_forward.values()
        )
        assert n_compiled_after == n_compiled_before, (
            "hot-swap re-quantization triggered a post-swap compile"
        )

        rb = engine.rollback()
        assert rb["generation"] is None
        # the displaced overlay never left staging: the exact object is
        # re-seated, so the served tree is bit-identical, not re-built
        assert engine.overlay is overlay_before
        tags_after = list(engine.submit_texts([text]).docs[0].tags)
        assert tags_after == tags_before
        assert sum(
            f._cache_size() for f in nlp._jit_forward.values()
        ) == n_compiled_before
        if tags_swapped != tags_before:
            pass  # seed-1 params usually differ; either way identity held
    finally:
        engine.stop()
