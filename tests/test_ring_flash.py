"""Ring attention with pallas flash blocks: each ring step runs the flash
kernel on its current K/V block (interpret mode on the CPU harness) and the
per-block (output, logsumexp) pairs merge associatively — forward AND
gradients must match dense attention exactly like the jnp ring path does."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import spacy_ray_tpu.ops.flash_attention as fa
import spacy_ray_tpu.parallel.ring_attention as ra
from spacy_ray_tpu.parallel import context as pctx
from spacy_ray_tpu.parallel.mesh import build_mesh


@pytest.fixture(autouse=True)
def _force_flash(monkeypatch):
    monkeypatch.setattr(fa, "_INTERPRET", True)
    monkeypatch.setattr(fa.GATE, "armed", True)  # pretend the probe passed


def _mk(B=2, T=128, H=2, Dh=32, seed=0):
    r = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(r[0], (B, T, H, Dh), jnp.float32)
    k = jax.random.normal(r[1], (B, T, H, Dh), jnp.float32)
    v = jax.random.normal(r[2], (B, T, H, Dh), jnp.float32)
    lens = jnp.array([T, T - 41, T - 7, 5, T - 13, 9, T - 3, T // 2])[:B]
    mask = jnp.arange(T)[None, :] < lens[:, None]
    return q, k, v, mask


def test_ring_flash_path_is_taken():
    # the gate must be on for the shapes used below, else the tests silently
    # exercise the jnp path
    assert ra._use_flash_blocks(64, 32)


def test_ring_flash_matches_dense():
    q, k, v, mask = _mk()
    want = np.asarray(fa.reference_attention(q, k, v, mask))
    mesh = build_mesh(n_context=4)
    with pctx.use_mesh(mesh):
        got = jax.jit(ra.ring_attention)(q, k, v, mask)
    m = np.asarray(mask)[:, :, None, None]
    np.testing.assert_allclose(
        np.where(m, np.asarray(got), 0), np.where(m, want, 0), atol=2e-4
    )


def test_ring_flash_grads_match_dense():
    q, k, v, mask = _mk(T=64)
    m = mask[:, :, None, None]

    def loss(fn, q, k, v):
        out = fn(q, k, v, mask).astype(jnp.float32)
        return jnp.sum(jnp.where(m, out, 0.0) ** 2)

    mesh = build_mesh(n_context=4)
    with pctx.use_mesh(mesh):
        g_ring = jax.jit(
            jax.grad(functools.partial(loss, ra.ring_attention), (0, 1, 2))
        )(q, k, v)
    g_dense = jax.grad(
        functools.partial(loss, fa.reference_attention), (0, 1, 2)
    )(q, k, v)
    for a, b in zip(g_ring, g_dense):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-3, rtol=2e-3
        )


def test_ring_flash_matches_dense_dp_cp():
    # data axis > 1: the flash region must go manual over data too (a
    # pallas_call can't live under an automatic GSPMD axis); exactness
    # must hold on the composed DP x CP mesh
    q, k, v, mask = _mk(B=4, T=128)
    want = np.asarray(fa.reference_attention(q, k, v, mask))
    mesh = build_mesh(n_data=2, n_context=4)
    with pctx.use_mesh(mesh):
        got = jax.jit(ra.ring_attention)(q, k, v, mask)
    m = np.asarray(mask)[:, :, None, None]
    np.testing.assert_allclose(
        np.where(m, np.asarray(got), 0), np.where(m, want, 0), atol=2e-4
    )


def test_ring_flash_indivisible_batch_falls_back():
    # B=3 does not divide data=2: the gate must drop to the dense path (and
    # still be exact) instead of mis-sharding the kernel
    q, k, v, mask = _mk(B=3, T=128)
    want = np.asarray(fa.reference_attention(q, k, v, mask))
    mesh = build_mesh(n_data=2, n_context=4)
    with pctx.use_mesh(mesh):
        got = jax.jit(ra.ring_attention)(q, k, v, mask)
    m = np.asarray(mask)[:, :, None, None]
    np.testing.assert_allclose(
        np.where(m, np.asarray(got), 0), np.where(m, want, 0), atol=2e-4
    )


def test_ring_flash_all_masked_rows_finite():
    q, k, v, _ = _mk()
    mask = jnp.zeros(q.shape[:2], bool).at[0].set(True)  # row 1 fully padded
    mesh = build_mesh(n_context=4)
    with pctx.use_mesh(mesh):
        out = jax.jit(ra.ring_attention)(q, k, v, mask)
    assert bool(jnp.all(jnp.isfinite(out)))
