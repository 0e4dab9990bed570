"""Pallas flash attention under a multi-device mesh: the kernel runs
per-shard inside a partial-manual shard_map over data/model (exact — no
cross-shard interaction in attention), interpret mode on the CPU harness."""

import jax
import numpy as np
import pytest

import spacy_ray_tpu.ops.flash_attention as fa
from spacy_ray_tpu.parallel import context as pctx
from spacy_ray_tpu.parallel.mesh import build_mesh


@pytest.fixture(autouse=True)
def _force_flash(monkeypatch):
    monkeypatch.setattr(fa, "_INTERPRET", True)
    monkeypatch.setattr(fa.GATE, "armed", True)  # pretend the probe passed


def _mk(B=4, T=128, H=4, Dh=32, seed=0):
    import jax.numpy as jnp

    r = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(r[0], (B, T, H, Dh), jnp.float32)
    k = jax.random.normal(r[1], (B, T, H, Dh), jnp.float32)
    v = jax.random.normal(r[2], (B, T, H, Dh), jnp.float32)
    lens = jnp.array([T, T - 9, T - 31, 5])
    mask = jnp.arange(T)[None, :] < lens[:, None]
    return q, k, v, mask


def test_sharded_attention_matches_dense():
    q, k, v, mask = _mk()
    want = np.asarray(fa.reference_attention(q, k, v, mask))
    mesh = build_mesh(n_data=2, n_model=2)
    with pctx.use_mesh(mesh):
        got = jax.jit(fa.attention)(q, k, v, mask)
    m = np.asarray(mask)[:, :, None, None]
    np.testing.assert_allclose(
        np.where(m, np.asarray(got), 0), np.where(m, want, 0), atol=1e-4
    )


def test_sharded_attention_falls_back_on_indivisible_layout():
    # H=3 does not divide over model=2: attention() must fall back to the
    # XLA path rather than produce wrong shards
    q, k, v, mask = _mk(B=4, T=128, H=3, Dh=32)
    want = np.asarray(fa.reference_attention(q, k, v, mask))
    mesh = build_mesh(n_data=2, n_model=2)
    with pctx.use_mesh(mesh):
        got = jax.jit(fa.attention)(q, k, v, mask)
    m = np.asarray(mask)[:, :, None, None]
    np.testing.assert_allclose(
        np.where(m, np.asarray(got), 0), np.where(m, want, 0), atol=1e-4
    )
