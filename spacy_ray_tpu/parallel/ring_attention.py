"""Ring attention: exact attention over sequences sharded on the ``context``
mesh axis.

Long-context sequence parallelism — absent from the reference (SURVEY.md
§5.7: sequence scaling there is document segmentation only) but first-class
here: each device holds a [B, T/n] slice of the sequence; key/value blocks
rotate around the ring via ``lax.ppermute`` over ICI while queries stay
put, with an online-softmax accumulator so the result is EXACT attention
(numerically identical to the dense computation), memory O(T/n) per device,
and communication overlapped block-by-block.

Implemented with ``shard_map`` over the mesh (per-device code + explicit
collectives), the idiomatic JAX pattern for collective-permute pipelines.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import context as pctx
from .smap import manual_region, shard_map

AXIS = "context"


def _use_flash_blocks(t_block: int, head_dim: int) -> bool:
    """Static host-side gate: run each ring block through the pallas flash
    kernel (ops/flash_attention.py) instead of the dense jnp score block.
    Exact either way; flash keeps the per-block [B, H, Tq, Tk] score tensor
    out of HBM, which matters once the per-device sequence slice is long —
    the whole point of the context axis."""
    from ..ops import flash_attention as fa

    return fa.flash_attention_enabled() and fa.attention_vmem_ok(
        t_block, fa._dp(head_dim)
    )


def _ring_flash(q, k, v, kmask, *, scale, n_shards, out_dtype):
    """Per-device ring loop with pallas flash blocks: q is laid out for the
    kernel once; the RAW k/v/kmask rotate around the ring (padding them per
    step is a fused VPU op, while rotating padded tensors would inflate
    per-step ppermute ICI traffic by the pad ratio). Each block's (output,
    logsumexp) pair merges associatively into a running pair — the flash
    merge, differentiable end-to-end because the block kernel's VJP accepts
    an lse cotangent (_make_flash_lse)."""
    from ..ops import flash_attention as fa

    B, T, H, Dh = q.shape
    qk = fa._to_kernel_layout(q)
    fl = fa._make_flash_lse(scale)

    _, _, Tp, DP = qk.shape
    o_acc = jnp.zeros((B, H, Tp, DP), jnp.float32)
    lse_acc = jnp.full((B, H, Tp), fa.NEG, jnp.float32)
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]

    def body(carry, _):
        k, v, kmask, o_acc, lse_acc = carry
        o_b, lse_b = fl(
            qk, fa._to_kernel_layout(k), fa._to_kernel_layout(v),
            fa._mask_to_bias(kmask),
        )
        m = jnp.maximum(lse_acc, lse_b)
        w_acc = jnp.exp(lse_acc - m)
        w_b = jnp.exp(lse_b - m)
        den = w_acc + w_b
        o_acc = (
            o_acc * (w_acc / den)[..., None]
            + o_b.astype(jnp.float32) * (w_b / den)[..., None]
        )
        lse_acc = m + jnp.log(den)
        k = jax.lax.ppermute(k, AXIS, perm)
        v = jax.lax.ppermute(v, AXIS, perm)
        kmask = jax.lax.ppermute(kmask, AXIS, perm)
        return (k, v, kmask, o_acc, lse_acc), None

    (_, _, _, o_acc, _), _ = jax.lax.scan(
        body, (k, v, kmask, o_acc, lse_acc), None, length=n_shards
    )
    return o_acc[:, :, :T, :Dh].transpose(0, 2, 1, 3).astype(out_dtype)


def _ring_body(carry, _, *, q, scale, axis_name, n_shards):
    k, v, kmask, m, num, den = carry
    # scores over the current key block: [B, H, Tq, Tk]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    neg = jnp.float32(-1e30)
    scores = jnp.where(kmask[:, None, None, :], scores, neg)
    block_max = jnp.max(scores, axis=-1)  # [B, H, Tq]
    new_m = jnp.maximum(m, block_max)
    correction = jnp.exp(m - new_m)
    p = jnp.exp(scores - new_m[..., None])  # [B, H, Tq, Tk]
    p = jnp.where(kmask[:, None, None, :], p, 0.0)
    corr_q = correction.transpose(0, 2, 1)[..., None]  # [B, Tq, H, 1]
    num = num * corr_q + jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    den = den * correction + jnp.sum(p, axis=-1)
    # rotate k/v/mask to the next ring position
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]
    k = jax.lax.ppermute(k, axis_name, perm)
    v = jax.lax.ppermute(v, axis_name, perm)
    kmask = jax.lax.ppermute(kmask, axis_name, perm)
    return (k, v, kmask, new_m, num, den), None


def ring_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, mask: jnp.ndarray
) -> jnp.ndarray:
    """q/k/v [B, T, H, Dh] (T logically sharded over 'context'), mask [B, T].

    Returns [B, T, H, Dh] in q.dtype. Must be called under jit with the
    active mesh (parallel/context.py) carrying a 'context' axis.
    """
    mesh = pctx.current_mesh()
    assert mesh is not None and AXIS in mesh.shape, "ring_attention needs a context axis"
    n_shards = int(mesh.shape[AXIS])
    B_g, T_g, H_g, Dh = q.shape
    scale = 1.0 / (Dh ** 0.5)
    out_dtype = q.dtype
    n_data = int(mesh.shape.get("data", 1))
    n_model = int(mesh.shape.get("model", 1))
    # flash blocks run a pallas_call per device shard; the gate is decided
    # HERE because the region's manual axis set depends on it (see
    # flash_attention._sharded_flash_attention for the single-chip analogue)
    flash = _use_flash_blocks(T_g // n_shards, Dh)

    # manual over `context` ONLY by default: data/model dims keep their
    # automatic (GSPMD) semantics, so the dense body's einsums still
    # partition over them — and the whole region can nest inside another
    # partial-manual shard_map (the pipeline's `pipe` region). The flash
    # path instead goes manual over EVERY axis: its kernel covers the whole
    # per-device computation, nothing is left to partition, and the TPU
    # lowering refuses a kernel while any mesh axis is still automatic. It
    # falls back to dense when the layout doesn't divide.
    if flash and (B_g % n_data or H_g % n_model):
        flash = False  # indivisible layout: dense partitions cleanly
    from ..ops import flash_attention as fa
    from ..ops.probe import active

    if fa.flash_attention_enabled():  # armed: the status says what ran
        fa.GATE.took(
            active(fa._INTERPRET, "ring attention blocks") if flash else
            "xla (ring attention: dense blocks — a block past the kernel's "
            "VMEM budget, or a layout that does not divide the mesh)"
        )
    sm_mesh, manual = manual_region(
        mesh, mesh.axis_names if flash else (AXIS,)
    )
    data_ax = "data" if flash and n_data > 1 else None
    model_ax = "model" if flash and n_model > 1 else None
    qkv_spec = P(data_ax, AXIS, model_ax, None)
    mask_spec = P(data_ax, AXIS)

    @partial(
        shard_map,
        mesh=sm_mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, mask_spec),
        out_specs=qkv_spec,
        axis_names=manual,
        check_vma=False,
    )
    def inner(q, k, v, kmask):
        B, Tq, H, _ = q.shape
        if flash:
            return _ring_flash(
                q, k, v, kmask,
                scale=scale, n_shards=n_shards, out_dtype=out_dtype,
            )
        m = jnp.full((B, H, Tq), -1e30, jnp.float32)
        num = jnp.zeros((B, Tq, H, Dh), jnp.float32)
        den = jnp.zeros((B, H, Tq), jnp.float32)
        body = partial(
            _ring_body, q=q, scale=scale, axis_name=AXIS, n_shards=n_shards
        )
        (k, v, kmask, m, num, den), _ = jax.lax.scan(
            body, (k, v, kmask, m, num, den), None, length=n_shards
        )
        den_t = den.transpose(0, 2, 1)[..., None]  # [B, Tq, H, 1]
        return (num / jnp.maximum(den_t, 1e-9)).astype(out_dtype)

    return inner(q, k, v, mask)
