"""Pallas kernel tests (interpret mode on CPU — the real-TPU path is
enabled by the runtime probe in ops/pallas_kernels.py)."""

import numpy as np
import jax
import jax.numpy as jnp

from spacy_ray_tpu.ops.pallas_kernels import (
    TOKEN_BLOCK,
    _pallas_lookup_raw,
    _reference_lookup,
    _table_grad,
    hash_embed_lookup,
    pallas_enabled,
)


def test_pallas_lookup_matches_reference_interpret():
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.normal(size=(500, 96)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, 500, size=(2 * TOKEN_BLOCK, 4)).astype(np.int32))
    got = _pallas_lookup_raw(table, ids, interpret=True)
    want = _reference_lookup(table, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_lookup_entry_point_cpu_fallback():
    # on CPU the probe must auto-disable (no SRT_PALLAS=1 set in tests)
    assert pallas_enabled() is False or jax.default_backend() == "tpu"
    rng = np.random.default_rng(1)
    table = jnp.asarray(rng.normal(size=(100, 32)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, 100, size=(3, 7, 4)).astype(np.int32))
    out = hash_embed_lookup(table, ids)
    assert out.shape == (3, 7, 32)
    want = _reference_lookup(table, ids.reshape(-1, 4)).reshape(3, 7, 32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


def test_lookup_grad_flows():
    """HashEmbed training depends on d(lookup)/d(table) — scatter-add."""
    table = jnp.ones((50, 8), jnp.float32)
    ids = jnp.asarray([[0, 1, 2, 3], [0, 0, 0, 0]], jnp.int32)

    def loss(tbl):
        return jnp.sum(hash_embed_lookup(tbl, ids))

    g = jax.grad(loss)(table)
    assert float(g[0].sum()) == 8 * 5  # row 0 used 1 + 4 times, 8 dims
    assert float(g[4].sum()) == 0.0


def test_custom_vjp_backward_matches_reference():
    """The pallas path's hand-written backward (scatter-add) must equal the
    autodiff gradient of the jnp reference."""
    rng = np.random.default_rng(3)
    table = jnp.asarray(rng.normal(size=(50, 16)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, 50, size=(20, 4)).astype(np.int32))
    ct = jnp.asarray(rng.normal(size=(20, 16)).astype(np.float32))

    # reference gradient via autodiff with the same cotangent
    def ref_loss(tbl):
        return jnp.sum(_reference_lookup(tbl, ids) * ct)

    g_ref = jax.grad(ref_loss)(table)
    g_ours = _table_grad(ids, ct, 50)
    np.testing.assert_allclose(np.asarray(g_ours), np.asarray(g_ref), atol=1e-5)


def test_onehot_lookup_matches_gather(monkeypatch):
    """The TPU one-hot fallback (probe off, small table) must equal the
    reference gather-sum, including repeated ids (multiplicity counts)."""
    import jax as _jax
    import jax.numpy as jnp
    import numpy as np

    import spacy_ray_tpu.ops.pallas_kernels as PK

    table = _jax.random.normal(_jax.random.PRNGKey(0), (64, 16))
    ids = _jax.random.randint(_jax.random.PRNGKey(1), (10, 3, 4), 0, 64)
    ids = ids.at[0, 0].set(jnp.array([5, 5, 5, 9]))  # repeats

    monkeypatch.setattr(PK.GATE, "armed", False)
    monkeypatch.setattr(PK.jax, "default_backend", lambda: "tpu")
    got = PK.hash_embed_lookup(table, ids)
    want = PK._reference_lookup(table, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
