"""Pallas TPU flash attention for the transformer trunk.

The trunk's single-chip attention path was ``jax.nn.dot_product_attention``
(models/transformer.py), whose XLA lowering materializes the [B, H, T, T]
score tensor in HBM. This kernel computes exact attention without ever
writing scores to HBM: per (batch, head, query-block) grid step it keeps the
whole K/V for that head resident in VMEM, forms a [BQ, T] score block
in-register, softmaxes, and contracts straight into the output block — the
standard flash-attention memory shape (O(T) HBM traffic instead of O(T²)),
sized for encoder sequence lengths (VMEM budget checked host-side, jnp
fallback beyond it).

Backward is a second pallas kernel via ``jax.custom_vjp`` (pallas_call has
no automatic VJP): it recomputes the probability block from the saved
logsumexp and accumulates dK/dV across query-block grid steps (TPU grids
execute sequentially, so revisiting an output block is the idiomatic
accumulation pattern).

Like the hash-embed kernel (ops/pallas_kernels.py), a one-time startup
probe compiles and numerically validates forward AND gradients on the
current backend before enabling; force with SRT_PALLAS_ATTN=1/0. The
capability matched is the reference ecosystem's fused attention (torch SDPA
inside its transformer dependency); the implementation is TPU-first.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..names import KERNEL_FLASH_BWD, KERNEL_FLASH_FWD
from . import probe as _probe

BQ = 128  # query block (MXU-aligned)
NEG = -1e30
# The TPU compiler's scoped-VMEM limit for one kernel ("limit 16.00M" in
# its refusal, v5e, libtpu 0.0.34) — what attention_vmem_ok budgets against
VMEM_ATTN_BUDGET = 16 * 1024 * 1024


def reference_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, mask: jnp.ndarray
) -> jnp.ndarray:
    """Dense reference: q/k/v [B, T, H, Dh], mask [B, T] bool -> [B, T, H, Dh]."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    scores = jnp.where(mask[:, None, None, :], scores, NEG)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v)


# ---------------------------------------------------------------- kernels


def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref, *, scale):
    # q [1,1,BQ,DP]  k/v [1,1,T,DP]  bias [1,1,T]  -> o [1,1,BQ,DP],
    # lse [1,1,1,BQ]. Per-row statistics are [BQ, 1] columns (keepdims) in
    # the kernel — the TPU lowering has no layout for a rank-1 vector — and
    # cross HBM as lane-dense [1, BQ] rows.
    q = q_ref[0, 0]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [BQ, T]
    s = s * scale + bias_ref[0]
    m = jnp.max(s, axis=-1, keepdims=True)  # [BQ, 1]
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)  # [BQ, 1]
    o = jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32
    ) / l
    o_ref[0, 0] = o.astype(o_ref.dtype)
    lse_ref[0, 0] = (m + jnp.log(l)).T


def _bwd_kernel(
    q_ref, k_ref, v_ref, bias_ref, do_ref, o_ref, lse_ref, dlse_ref,
    dq_ref, dk_ref, dv_ref, *, scale,
):
    # grid (B, H, nq); dk/dv blocks are revisited across the q-block axis
    @pl.when(pl.program_id(2) == 0)
    def _init():
        dk_ref[0, 0] = jnp.zeros_like(dk_ref[0, 0])
        dv_ref[0, 0] = jnp.zeros_like(dv_ref[0, 0])

    q = q_ref[0, 0]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    do = do_ref[0, 0].astype(jnp.float32)
    o = o_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0].T  # [BQ, 1]
    dlse = dlse_ref[0, 0].T  # [BQ, 1] cotangent of the logsumexp output

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    s = s * scale + bias_ref[0]
    p = jnp.exp(s - lse)  # [BQ, T] softmax probs (recomputed)

    delta = jnp.sum(do * o, axis=-1, keepdims=True)  # [BQ, 1]
    dp = jax.lax.dot_general(
        do.astype(v.dtype), v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [BQ, T]
    # d(lse)/d(s_j) = p_j, so the lse cotangent folds straight into ds —
    # this is what lets the ring-attention block merge differentiate
    # through each block's logsumexp
    ds = p * (dp - delta + dlse) * scale  # [BQ, T] fp32
    ds16 = ds.astype(q.dtype)

    dq_ref[0, 0] = jnp.dot(
        ds16, k, preferred_element_type=jnp.float32
    ).astype(dq_ref.dtype)
    dk_ref[0, 0] += jax.lax.dot_general(
        ds16, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ).astype(dk_ref.dtype)
    dv_ref[0, 0] += jax.lax.dot_general(
        p.astype(do_ref.dtype), do.astype(do_ref.dtype),
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    ).astype(dv_ref.dtype)


# ------------------------------------------------------- pallas_call wrappers


_INTERPRET = False  # tests flip this to run the kernels on CPU


def _block_specs(T: int, DP: int):
    """(q, k/v, bias, lse) BlockSpecs for grid (B, H, nq). The TPU lowering
    wants each block's last two dims to equal the array's or be multiples
    of (8, 128), so the bias rides as [B, 1, T] and the per-query logsumexp
    as [B, H, 1, T] — a unit second-to-last axis under each vector."""
    vmem = pltpu.VMEM
    return (
        pl.BlockSpec((1, 1, BQ, DP), lambda b, h, i: (b, h, i, 0),
                     memory_space=vmem),
        pl.BlockSpec((1, 1, T, DP), lambda b, h, i: (b, h, 0, 0),
                     memory_space=vmem),
        pl.BlockSpec((1, 1, T), lambda b, h, i: (b, 0, 0), memory_space=vmem),
        pl.BlockSpec((1, 1, 1, BQ), lambda b, h, i: (b, h, 0, i),
                     memory_space=vmem),
    )


def _fwd_raw(q, k, v, bias, *, scale, interpret=None):
    # q/k/v [B, H, T, DP], bias [B, T]; T % BQ == 0, DP % 128 == 0
    # -> o [B, H, T, DP], lse [B, H, T]
    interpret = _INTERPRET if interpret is None else interpret
    B, H, T, DP = q.shape
    qspec, kvspec, bspec, lspec = _block_specs(T, DP)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale),
        out_shape=(
            jax.ShapeDtypeStruct((B, H, T, DP), q.dtype),
            jax.ShapeDtypeStruct((B, H, 1, T), jnp.float32),
        ),
        grid=(B, H, T // BQ),
        in_specs=[qspec, kvspec, kvspec, bspec],
        out_specs=(qspec, lspec),
        interpret=interpret,
        name=KERNEL_FLASH_FWD,
    )(q, k, v, bias[:, None, :])
    return o, lse[:, :, 0, :]


def _bwd_raw(q, k, v, bias, do, o, lse, dlse, *, scale, interpret=None):
    interpret = _INTERPRET if interpret is None else interpret
    B, H, T, DP = q.shape
    qspec, kvspec, bspec, lspec = _block_specs(T, DP)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale),
        out_shape=(
            jax.ShapeDtypeStruct((B, H, T, DP), q.dtype),   # dq
            jax.ShapeDtypeStruct((B, H, T, DP), jnp.float32),  # dk (accum)
            jax.ShapeDtypeStruct((B, H, T, DP), jnp.float32),  # dv (accum)
        ),
        grid=(B, H, T // BQ),
        in_specs=[qspec, kvspec, kvspec, bspec, qspec, qspec, lspec, lspec],
        out_specs=(qspec, kvspec, kvspec),
        interpret=interpret,
        name=KERNEL_FLASH_BWD,
    )(q, k, v, bias[:, None, :], do, o, lse[:, :, None, :], dlse[:, :, None, :])


def _make_flash(scale: float):
    """Differentiable flash attention for one (static) softmax scale — the
    scale must come from the REAL head dim, not the zero-padded kernel DP,
    so the host wrapper passes it down explicitly. Output-only view of
    :func:`_make_flash_lse`; JAX supplies a zero cotangent for the dropped
    lse output, which the shared backward folds in at no cost."""
    fl = _make_flash_lse(scale)
    return lambda q, k, v, bias: fl(q, k, v, bias)[0]


@functools.lru_cache(maxsize=None)
def _make_flash_lse(scale: float):
    """Like :func:`_make_flash` but also RETURNS the per-query logsumexp, with
    a VJP that accepts its cotangent — the building block for ring attention,
    whose online merge of per-ring-block partial results is a differentiable
    function of each block's (output, logsumexp) pair."""

    @jax.custom_vjp
    def fl(q, k, v, bias):
        return _fwd_raw(q, k, v, bias, scale=scale)

    def fl_fwd(q, k, v, bias):
        o, lse = _fwd_raw(q, k, v, bias, scale=scale)
        return (o, lse), (q, k, v, bias, o, lse)

    def fl_bwd(res, cts):
        q, k, v, bias, o, lse = res
        do, dlse = cts
        dq, dk, dv = _bwd_raw(q, k, v, bias, do, o, lse, dlse, scale=scale)
        return dq, dk.astype(k.dtype), dv.astype(v.dtype), None

    fl.defvjp(fl_fwd, fl_bwd)
    return fl


# ------------------------------------------------------------- host wrapper


def _pad_to(x: jnp.ndarray, axis: int, mult: int, value=0.0) -> jnp.ndarray:
    pad = (-x.shape[axis]) % mult
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _dp(head_dim: int) -> int:
    """Kernel head dim: the real head dim zero-padded up to a lane multiple."""
    return max(((head_dim + 127) // 128) * 128, 128)


def _to_kernel_layout(x: jnp.ndarray) -> jnp.ndarray:
    """[B, T, H, Dh] trunk layout -> [B, H, Tp, DP] kernel layout; zero
    head-dim padding leaves scores and output columns exact."""
    return _pad_to(_pad_to(x.transpose(0, 2, 1, 3), 3, _dp(x.shape[-1])), 2, BQ)


def _mask_to_bias(mask: jnp.ndarray) -> jnp.ndarray:
    """[B, T] bool key-padding mask -> [B, Tp] additive fp32 bias."""
    bias = jnp.where(mask, 0.0, NEG).astype(jnp.float32)
    return _pad_to(bias, 1, BQ, value=NEG)


def flash_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, mask: jnp.ndarray
) -> jnp.ndarray:
    """Exact masked attention, pallas-fused. q/k/v [B, T, H, Dh] (the trunk's
    layout), mask [B, T] bool (key padding). Returns [B, T, H, Dh] in q.dtype.
    """
    B, T, H, Dh = q.shape
    o = _make_flash(1.0 / (Dh ** 0.5))(
        _to_kernel_layout(q), _to_kernel_layout(k), _to_kernel_layout(v),
        _mask_to_bias(mask),
    )
    return o[:, :, :T, :Dh].transpose(0, 2, 1, 3)


def attention_vmem_ok(T: int, DP: int, dtype_bytes: int = 2) -> bool:
    """Whether one (b, h) grid step fits the compiler's scoped VMEM. Sized
    for the BACKWARD kernel, the larger of the two, because one gate serves
    training and inference: K/V and the f32 dK/dV accumulators span the
    whole sequence and are double-buffered as pipelined windows, next to
    two live f32 [BQ, T] score blocks and the q/do/o/dq blocks. Compiled
    for v5e the backward is accepted at T=4096 and refused at T=4608
    (16.84M against the 16.00M limit); this arithmetic stops at T=3968."""
    Tp = ((T + BQ - 1) // BQ) * BQ
    kv = 2 * 2 * Tp * DP * dtype_bytes
    dkv = 2 * 2 * Tp * DP * 4
    scores = 2 * BQ * Tp * 4
    qblocks = 2 * 4 * BQ * DP * dtype_bytes
    return kv + dkv + scores + qblocks <= VMEM_ATTN_BUDGET


def _probe_check() -> Optional[str]:
    """Forward AND gradients against the dense reference, on a shape with
    a ragged key mask and a T that needs padding to the query block."""
    r = jax.random.split(jax.random.PRNGKey(0), 4)
    B, T, H, Dh = 2, 192, 2, 64
    q = jax.random.normal(r[0], (B, T, H, Dh), jnp.bfloat16)
    k = jax.random.normal(r[1], (B, T, H, Dh), jnp.bfloat16)
    v = jax.random.normal(r[2], (B, T, H, Dh), jnp.bfloat16)
    mask = jnp.arange(T)[None, :] < jnp.array([T, T - 57])[:, None]
    m = mask[:, :, None, None]

    got = jax.jit(flash_attention)(q, k, v, mask)
    want = reference_attention(q, k, v, mask)
    bad = _probe.mismatch(
        "forward", jnp.where(m, got, 0), jnp.where(m, want, 0), atol=2e-2
    )
    if bad:
        return bad

    def loss(fn, q, k, v):
        out = fn(q, k, v, mask).astype(jnp.float32)
        return jnp.sum(jnp.where(m, out, 0.0) ** 2)

    g_got = jax.grad(functools.partial(loss, flash_attention), (0, 1, 2))(q, k, v)
    g_want = jax.grad(functools.partial(loss, reference_attention), (0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), g_got, g_want):
        bad = _probe.mismatch(name, a, b, atol=5e-2, rtol=5e-2)
        if bad:
            return bad
    return None


GATE = _probe.Gate(
    "flash attention", "SRT_PALLAS_ATTN", _probe_check,
    unprobed="no attention ran in this process",
)


def flash_attention_enabled() -> bool:
    """One-time probe (ops/probe.py): compile + validate forward AND
    gradients vs the dense reference on the current backend; cache the
    verdict. SRT_PALLAS_ATTN=1 forces the probe on any backend, =0 forces
    off; default arms on TPU only, where a failed probe raises."""
    return GATE.enabled(_INTERPRET)


def flash_attention_status() -> str:
    """What attention did in this process, in words: the paths
    :func:`attention` (and ring attention) took in the programs traced so
    far, else the probe's verdict."""
    return GATE.status()


def _sharded_flash_attention(q, k, v, mask, mesh):
    """Run the pallas kernel per device shard inside a shard_map.

    A pallas_call has no GSPMD partitioning rule, so under an automatically-
    partitioned jit it would force replication of the global q/k/v. But
    attention is INDEPENDENT per (batch row, head): each device runs the
    kernel on its own [B/d, T, H/m, Dh] shard with zero communication —
    exact. The region is manual over EVERY mesh axis, size-1 ones included:
    the TPU lowering refuses a kernel while any axis of the mesh is still
    automatic ("Mosaic kernels cannot be automatically partitioned").
    Returns None when the layout doesn't divide (caller falls back to XLA
    attention)."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.smap import manual_region, shard_map

    B, T, H, _ = q.shape
    d = int(mesh.shape.get("data", 1))
    m = int(mesh.shape.get("model", 1))
    if d * m == 1 or B % d or H % m:
        return None
    data_ax = "data" if d > 1 else None
    model_ax = "model" if m > 1 else None
    qkv_spec = P(data_ax, None, model_ax, None)
    mask_spec = P(data_ax, None)
    sm_mesh, axis_names = manual_region(mesh, mesh.axis_names)
    fn = shard_map(
        flash_attention,
        mesh=sm_mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, mask_spec),
        out_specs=qkv_spec,
        axis_names=axis_names,
        check_vma=False,
    )
    return fn(q, k, v, mask)


def xla_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, mask: jnp.ndarray,
    causal: bool = False,
) -> jnp.ndarray:
    """Attention as plain XLA operations: q/k [B, T, H, Dqk], v [B, T, H, Dv]
    (the two widths may differ), mask [B, T] bool (key padding), ``causal``
    adds the lower triangle. Grouped keys: k and v may have fewer heads than
    q, a whole number of query heads to each (query head j reads key head
    ``j // (H / Hkv)``); no key is repeated in memory. Scores and softmax in
    float32; a query with no visible key (a padded row) gets a finite,
    uniform row. Returns [B, T, H, Dv] in v.dtype."""
    B, T, H, _ = q.shape
    scale = 1.0 / (q.shape[-1] ** 0.5)
    kv_heads = k.shape[2]
    if kv_heads == H:
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    else:
        grouped = q.reshape(B, T, kv_heads, H // kv_heads, q.shape[-1])
        scores = jnp.einsum(
            "bqhgd,bkhd->bhgqk", grouped, k, preferred_element_type=jnp.float32) * scale
    keep = mask[:, None, None, :]
    if causal:
        keep = keep & jnp.tril(jnp.ones((T, T), bool))[None, None]
    if kv_heads == H:
        p = jax.nn.softmax(jnp.where(keep, scores, NEG), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
    p = jax.nn.softmax(jnp.where(keep[:, :, None], scores, NEG), axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)
    return out.reshape(B, T, H, v.shape[-1])


def attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, mask: jnp.ndarray,
    causal: bool = False,
) -> jnp.ndarray:
    """Attention entry point for the trunk: pallas flash kernel when the
    probe enabled it and the shape fits VMEM, else XLA's fused
    ``jax.nn.dot_product_attention``. Under a multi-device mesh the kernel
    runs per-shard inside a shard_map (_sharded_flash_attention); layouts
    that don't divide fall back to XLA attention, which partitions cleanly.
    An armed kernel that gives way says so: each branch notes its path on
    ``GATE``, and ``flash_attention_status`` reports what was taken.

    ``causal``, a ``v`` whose width differs from ``q``'s (latent
    attention: 192 against 128) and grouped keys (``k`` and ``v`` with fewer
    heads than ``q``: 32 query heads on 2) are outside what the kernels
    compute (one head width, one key head a query head, a key-padding bias):
    such a call goes through :func:`xla_attention` and says so on ``GATE``."""
    from ..parallel import context as pctx

    B, T, H, Dh = q.shape
    kv_heads = k.shape[2]
    if kv_heads != H and (H % kv_heads or v.shape[2] != kv_heads):
        raise ValueError(f"{H} query heads cannot share {kv_heads} key and "
                         f"{v.shape[2]} value heads evenly")
    if causal or v.shape[-1] != Dh or kv_heads != H:
        GATE.took(
            f"xla (causal={causal}, q/k width {Dh}, v width {v.shape[-1]}"
            + (f", {H} query heads on {kv_heads} key heads" if kv_heads != H else "")
            + ": outside the flash kernels)")
        return xla_attention(q, k, v, mask, causal)
    out = None
    if not flash_attention_enabled():
        pass  # the probe's verdict says why
    elif not attention_vmem_ok(T, _dp(Dh), q.dtype.itemsize):
        GATE.took(f"xla (T={T} is past the kernel's VMEM budget)")
    elif pctx.single_device():
        GATE.took(_probe.active(_INTERPRET))
        out = flash_attention(q, k, v, mask)
    else:
        mesh = pctx.current_mesh()
        out = _sharded_flash_attention(q, k, v, mask, mesh)
        axes = {name: n for name, n in mesh.shape.items() if n > 1}
        GATE.took(
            _probe.active(_INTERPRET, "per shard in a shard_map")
            if out is not None else
            f"xla (batch {B} x heads {H} does not divide the mesh {axes})"
        )
    if out is None:
        out = jax.nn.dot_product_attention(
            q, k, v, mask=mask[:, None, None, :]
        )
    return out
