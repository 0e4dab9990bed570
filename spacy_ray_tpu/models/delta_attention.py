"""The delta rule with a decay for every channel (Kimi Delta Attention,
arXiv:2510.26692, section 3), in chunks: the recurrence of the ``K`` layers of
``models/hybrid_ssm.py``, and nothing of the layer round it.

A head keeps a MATRIX ``S`` (key width x value width). Each position first
lets every key channel of it decay by its own ``alpha_t = exp(g_t)``, then
corrects what the state holds under the position's key towards the position's
value (a rank-one step of size ``beta_t``), then is read by the query::

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_(t-1) + beta_t k_t v_t^T
    o_t = S_t^T q_t

``S`` is nought before a row's first position. With ``beta`` in (0, 2) a
transition's eigenvalue along ``k_t`` lies in (-1, 1) (arXiv:2411.12537); the
caller decides the range, this module takes ``beta`` as it comes.

**In chunks** (the WY form). Write ``u_t = beta_t (v_t - (Diag(alpha_t)
S_(t-1))^T k_t)``, so that ``S_t = Diag(alpha_t) S_(t-1) + k_t u_t^T``, and
``G_t`` for the running sum of ``g`` from the chunk's first position to ``t``
inclusive. Inside a chunk that starts from the state ``S_0``::

    (I + A) U = beta * (V - (K * exp(G)) S_0),   A[i, j] = beta_i P_k[i, j], j < i
    O = (Q * exp(G)) S_0 + P_q U,                P_x[i, j] = sum_c x_i[c] k_j[c] exp(G_i[c] - G_j[c]), j <= i
    S_end = exp(G_last) * S_0 + (K * exp(G_last - G))^T U

``A`` is strictly lower triangular, so ``(I + A)^-1`` is ONE triangular solve
a chunk, made once for both right-hand sides (``beta V`` and ``beta K
exp(G)``) before the state is known; the chunks are then a ``lax.scan`` over
the carried state with three products each.

**The decay between two positions is the exponential of a DIFFERENCE**
``G_i - G_j`` (``i >= j``, so never positive), never ``exp(G_i) *
exp(-G_j)``: under strong decay the second factor overflows float32 within a
chunk. A chunk is cut into blocks of ``SUB`` positions. Within a block the
differences are formed for every pair and channel (elementwise, float32).
Across blocks the pair goes through the first position ``r`` of the later
block: ``exp(G_i - G_r) * exp(G_r - G_j)``, both exponents at most nought, and
the sum over channels is a matrix product of the two scaled factors.

Float32: the running sums, every exponential, the within-block products, the
solve, the carried state. The compute dtype (bfloat16 on a TPU): the operands
of the across-block products and of the products with the state, accumulated
in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

SUB = 16  # positions a block; the public kernels' own


def l2norm(x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    """``x / sqrt(sum(x^2) + eps)`` over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _decayed_pairs(x, k, G, sub: int, cd) -> jnp.ndarray:
    """``P_x[i, j] = sum_c x_i[c] k_j[c] exp(G_i[c] - G_j[c])`` for ``j <= i``,
    nought past the diagonal. x [..., X, Q, K] (X stacked operands, each
    paired with the one k), k / G [..., Q, K] float32 -> [..., X, Q, Q]
    float32."""
    Q, K = k.shape[-2:]
    ns = Q // sub
    lead = k.shape[:-2]
    x5 = x.reshape(x.shape[:-2] + (ns, sub, K))
    k5, G5 = k.reshape(lead + (ns, sub, K)), G.reshape(lead + (ns, sub, K))
    first = G5[..., 0, :]  # [..., ns, K]: the running sum at each block's first position
    # across blocks: (x_i exp(G_i - G_r)) . (k_j exp(G_r - G_j)), j before block a
    left = x5 * jnp.exp(G5 - first[..., None, :])[..., None, :, :, :]
    earlier = (jnp.arange(Q)[None, :] < sub * jnp.arange(ns)[:, None])[..., None]  # [ns, Q, 1]
    right = k[..., None, :, :] * jnp.exp(
        jnp.where(earlier, first[..., None, :] - G[..., None, :, :], -jnp.inf))
    across = jnp.einsum("...xarc,...ajc->...xarj", left.astype(cd), right.astype(cd),
                        preferred_element_type=jnp.float32)
    # within a block: the differences themselves, pair by pair and channel by
    # channel. The exponent is masked, not the result: past the diagonal it is
    # positive and may overflow, and nought times infinity is no number
    upto = jnp.tril(jnp.ones((sub, sub), bool))[..., None]
    between = jnp.exp(jnp.where(upto, G5[..., :, None, :] - G5[..., None, :, :], -jnp.inf))
    within = jnp.sum(x5[..., :, None, :] * (k5[..., None, :, :] * between)[..., None, :, :, :, :],
                     axis=-1)  # [..., X, ns, sub, sub]
    blocks = within[..., None, :] * jnp.eye(ns, dtype=within.dtype)[:, None, :, None]
    return (across.reshape(across.shape[:-3] + (ns, sub, ns, sub)) + blocks).reshape(
        across.shape[:-3] + (Q, Q))


def _solve(A: jnp.ndarray, rhs: jnp.ndarray) -> jnp.ndarray:
    """``(I + A)^-1 rhs`` for a strictly lower triangular ``A`` [..., Q, Q]:
    one triangular solve (forward substitution), float32."""
    return jax.lax.linalg.triangular_solve(
        A + jnp.eye(A.shape[-1], dtype=A.dtype), rhs, left_side=True, lower=True,
        unit_diagonal=True)


def chunked_delta_rule(q, k, v, g, beta, chunk: int, cd, sub: int = SUB) -> jnp.ndarray:
    """``o_t = S_t^T q_t`` of the recurrence above, in chunks of ``chunk``
    positions. q / k [B, T, H, K] float32 (as the layer hands them: normed,
    ``q`` scaled), v [B, T, H, V], g [B, T, H, K] float32 (the log of the
    decay, never positive), beta [B, T, H] float32. Returns [B, T, H, V]
    float32."""
    Bn, T, H, K = k.shape
    V = v.shape[-1]
    Q = chunk
    if Q % sub:
        sub = Q  # a chunk that no block divides is one block
    pad = -T % Q
    if pad:  # positions past the row's end: behind every real one, and inert (beta, k, v nought)
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                            for a in (q, k, v, g, beta))
    nc = (T + pad) // Q
    f32 = jnp.float32

    def by_chunk(a):  # [B, T, H, ...] -> [B, nc, H, Q, ...]: time next to the channels
        return jnp.moveaxis(a.reshape((Bn, nc, Q) + a.shape[2:]), 2, 3)

    q, k, v, g = (by_chunk(a.astype(f32)) for a in (q, k, v, g))
    beta = by_chunk(beta.astype(f32))[..., None]  # [B, nc, H, Q, 1]
    G = jnp.cumsum(g, axis=-2)
    last = G[..., -1:, :]  # across the whole chunk [B, nc, H, 1, K]
    pairs = _decayed_pairs(jnp.stack([q, k], axis=3), k, G, sub, cd)
    P_q, P_k = pairs[..., 0, :, :], pairs[..., 1, :, :]
    A = beta * P_k * jnp.tril(jnp.ones((Q, Q), f32), -1)
    since = jnp.exp(G)  # the decay from the chunk's start to each position
    W = _solve(A, beta * jnp.concatenate([v, k * since], axis=-1))
    W_v, W_k = W[..., :V], W[..., V:]
    q_in = (q * since).astype(cd)  # what the state carried in gives each query
    k_out = (k * jnp.exp(last - G)).astype(cd)  # what each key leaves at the chunk's end

    def carry_over(S, chunk_):
        W_v_, W_k_, q_in_, P_q_, k_out_, decay_ = chunk_
        S16 = S.astype(cd)
        U = W_v_ - jnp.einsum("bhqk,bhkv->bhqv", W_k_.astype(cd), S16, preferred_element_type=f32)
        U16 = U.astype(cd)
        o = (jnp.einsum("bhqk,bhkv->bhqv", q_in_, S16, preferred_element_type=f32)
             + jnp.einsum("bhqs,bhsv->bhqv", P_q_.astype(cd), U16, preferred_element_type=f32))
        S = decay_[..., None] * S + jnp.einsum(
            "bhqk,bhqv->bhkv", k_out_, U16, preferred_element_type=f32)
        return S, o

    _, o = jax.lax.scan(
        carry_over, jnp.zeros((Bn, H, K, V), f32),
        tuple(jnp.moveaxis(a, 1, 0) for a in (W_v, W_k, q_in, P_q, k_out, jnp.exp(last[..., 0, :]))))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)  # [B, nc, Q, H, V]
    return o.reshape(Bn, nc * Q, H, V)[:, :T]
