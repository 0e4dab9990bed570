"""Fused Adam/RAdam optimizer update: one traversal, probe-gated pallas kernel.

PERF.md Finding 1 (round 5) measured a ~3.3 s O(n_params) per-step floor on
the trf config, 44.6% of it the optimizer's elementwise fusions. The naive
path compiles optax's link-by-link chain (clip -> scale_by_adam -> decay ->
lr) into the step; this module provides the same math as ONE update:

* ``make_fused_transformation``: an optax-compatible transformation whose
  ``update`` computes the whole chain in a single pass per leaf and applies
  the update to the params directly (``applies_updates = True`` — the train
  step then skips its separate ``optax.apply_updates`` traversal). The
  state STRUCTURE is byte-identical to the reference chain's (init
  delegates to it), so checkpoints, ZeRO-1 shardings, and the
  ``fused_update`` knob can be flipped without invalidating resume state.
* a pallas TPU kernel for the per-leaf elementwise update (params, grads,
  mu, nu in; params', mu', nu' and, where the leaf has one, its bf16
  shadow out). It takes each leaf WHERE IT LIES: the leaf is viewed as
  [product of the leading dimensions, last dimension], which keeps the
  device's (8, 128) tiles (a bitcast, never a copy), the grid walks row
  blocks of the whole width, and input_output_aliases updates the donated
  params and moments themselves. A leaf that cannot be walked without a
  copy goes through the same math under XLA (``leaf_blocking`` says
  which, and why; ``FusedTransformation.in_place`` keeps the tally)
  — probe-gated exactly like the flash-attention kernel: compiled and
  numerically validated against the XLA math at startup, forced with
  SRT_PALLAS_FUSED=1/0, auto-enabled on TPU only. CPU tests run it in
  interpret mode. Its perf claim is only as good as a ``runtime`` line that
  says ``"fused_update": "active (pallas)"``.

Numerical contract: the fused math mirrors the installed optax's exact
expressions (optax 0.2.3: ``scale_by_adam``/``scale_by_radam`` moment and
bias-correction forms, ``clip_by_global_norm``'s ``(g / gnorm) * clip``
select, ``add_decayed_weights``, ``scale_by_schedule``'s pre-increment
count, ``apply_updates``' ``p + u``) so per-leaf results agree with the
reference chain to 1 ulp — asserted by tests/test_fused_update.py.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..names import KERNEL_FUSED_ADAM
from . import probe as _probe

# kernel block: whole rows of a leaf, about this many bytes of f32 per
# operand and grid step (4 inputs + 4 outputs, double-buffered, stay under
# the 16 MiB of VMEM a kernel may use)
BLOCK_BYTES = 512 * 1024
# block rows come in multiples of a bf16 tile's 16 (f32's 8 divides it)
ROW_ALIGN = 16
# leaves smaller than this skip the pallas path: a kernel launch per tiny
# bias buys nothing (the XLA fallback fuses those fine)
MIN_KERNEL_SIZE = 16 * 1024


class FusedHyper(NamedTuple):
    """Static hyperparameters of one fused update (python floats — they
    specialize the compiled program, exactly like the optax chain)."""

    kind: str  # "adam" | "radam"
    b1: float
    b2: float
    eps: float
    grad_clip: float  # 0 = no clipping link
    l2_grad: float  # classic L2 added to grads BEFORE adam (0 = absent)
    l2_decay: float  # decoupled weight decay AFTER adam (0 = absent)
    radam_threshold: float = 5.0


# ---------------------------------------------------------------- leaf math


def _leaf_math(
    p: jnp.ndarray,
    g: jnp.ndarray,
    m: jnp.ndarray,
    v: jnp.ndarray,
    gnorm: jnp.ndarray,
    bc1: jnp.ndarray,
    bc2: jnp.ndarray,
    step_size: jnp.ndarray,
    ro: jnp.ndarray,
    rect: jnp.ndarray,
    hyper: FusedHyper,
    in_kernel: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One leaf's whole chain: clip -> (classic L2) -> moments -> bias
    correction -> (radam rectification) -> (decoupled decay) -> lr ->
    apply. Shared by the pallas kernel (on block refs) and the XLA
    fallback (on whole leaves) so the two paths cannot drift; the one
    divergence is the clip select form (below), asserted value-equal by
    the kernel probe."""
    if hyper.grad_clip > 0:
        # optax clip_by_global_norm, verbatim: SCALAR-predicate lax.select
        # (jnp.where would broadcast the predicate into a full elementwise
        # mask — a measurable extra pass at 134M params on CPU). Inside
        # the pallas kernel the block-local jnp.where lowers fine and the
        # scalar-pred select may not; values are identical either way.
        if in_kernel:
            g = jnp.where(
                gnorm < hyper.grad_clip, g, (g / gnorm) * hyper.grad_clip
            )
        else:
            g = jax.lax.select(
                gnorm < hyper.grad_clip, g, (g / gnorm) * hyper.grad_clip
            )
    if hyper.l2_grad:
        g = g + hyper.l2_grad * p
    m2 = (1 - hyper.b1) * g + hyper.b1 * m
    v2 = (1 - hyper.b2) * (g**2) + hyper.b2 * v
    mu_hat = m2 / bc1
    nu_hat = v2 / bc2
    if hyper.kind == "radam":
        # optax scale_by_radam: rectified update where ro >= threshold,
        # plain bias-corrected momentum otherwise (rect is NaN for
        # ro < 4 — jnp.where selects it away, mirroring optax)
        u = jnp.where(
            ro >= hyper.radam_threshold,
            rect * mu_hat / (jnp.sqrt(nu_hat) + hyper.eps),
            mu_hat,
        )
    else:
        u = mu_hat / (jnp.sqrt(nu_hat) + hyper.eps)
    if hyper.l2_decay:
        u = u + hyper.l2_decay * p
    u = step_size * u
    return p + u, m2, v2


# ------------------------------------------------------------ pallas kernel


def _update_kernel(scal_ref, p_ref, g_ref, m_ref, v_ref, op_ref, om_ref,
                   ov_ref, *shadow_ref, hyper: FusedHyper):
    # scal [6] SMEM: gnorm, bc1, bc2, step_size, ro, rect
    p2, m2, v2 = _leaf_math(
        p_ref[...],
        # a shadowed leaf's cotangent arrives in the shadow's dtype: widened
        # here, in VMEM (exact), instead of as a float32 copy in HBM
        g_ref[...].astype(p_ref.dtype),
        m_ref[...],
        v_ref[...],
        scal_ref[0],
        scal_ref[1],
        scal_ref[2],
        scal_ref[3],
        scal_ref[4],
        scal_ref[5],
        hyper,
        in_kernel=True,
    )
    op_ref[...] = p2
    om_ref[...] = m2
    ov_ref[...] = v2
    if shadow_ref:
        # the leaf's bf16 shadow, refreshed from the block already in VMEM
        shadow_ref[0][...] = p2.astype(shadow_ref[0].dtype)


_INTERPRET = False  # tests flip this to run the kernel on CPU


def leaf_blocking(
    shape: Tuple[int, ...], dtype: Any, block_bytes: int = BLOCK_BYTES
) -> Tuple[Optional[Tuple[int, int, int]], str]:
    """How the kernel walks a leaf without copying it, decided from what the
    leaf shows: ``((rows, width, block_rows), "")``, or ``(None, why)`` for
    a leaf it cannot walk so, whose update goes through XLA (which fuses it
    in place; so does a leaf under ``MIN_KERNEL_SIZE``, the caller's rule).

    The view ``[rows, width]`` merges the leading dimensions; the device
    keeps an array in (8, 128) tiles of its last two ((16, 128) for a bf16
    gradient or shadow), so the merge is a bitcast only while the
    second-to-last is a whole number of ``ROW_ALIGN``-row tiles.
    A block is ``block_rows`` whole rows (a width that is no multiple of 128
    lowers as a full-width block) of about ``block_bytes`` of float32; the
    grid masks a last block that is not full."""
    if dtype != jnp.float32:
        return None, f"dtype {jnp.dtype(dtype).name}"
    if len(shape) < 2:
        return None, "one dimension"
    if len(shape) > 2 and shape[-2] % ROW_ALIGN:
        return None, f"rows {shape[-2]} (merging the leading dimensions would copy)"
    width = int(shape[-1])
    rows = math.prod(int(d) for d in shape[:-1])
    row_bytes = 4 * (-(-width // 128) * 128)  # a row in VMEM, lanes padded
    if ROW_ALIGN * row_bytes > 2 * block_bytes:
        return None, f"width {width}"
    block_rows = max(block_bytes // row_bytes // ROW_ALIGN, 1) * ROW_ALIGN
    return (rows, width, min(block_rows, rows)), ""


def _kernel_leaf(p, g, m, v, scal, hyper: FusedHyper, shadow_dtype=None,
                 interpret=None, block_bytes: int = BLOCK_BYTES):
    """Run one leaf through the pallas kernel where it lies (the caller has
    asked ``leaf_blocking``): ``(p', m', v')`` and, given ``shadow_dtype``,
    the cast of ``p'`` to it as a fourth output."""
    interpret = _INTERPRET if interpret is None else interpret
    blocking, why = leaf_blocking(p.shape, p.dtype, block_bytes)
    if blocking is None:
        raise ValueError(f"leaf {p.shape} cannot take the kernel in place: {why}")
    rows, width, block_rows = blocking
    kernel = functools.partial(_update_kernel, hyper=hyper)
    bspec = pl.BlockSpec((block_rows, width), lambda i: (i, 0),
                         memory_space=pltpu.VMEM)
    sspec = pl.BlockSpec(memory_space=pltpu.SMEM)
    out = jax.ShapeDtypeStruct((rows, width), p.dtype)
    outs = (out, out, out)
    if shadow_dtype is not None:
        outs += (jax.ShapeDtypeStruct((rows, width), shadow_dtype),)
    res = pl.pallas_call(
        kernel,
        out_shape=outs,
        grid=(pl.cdiv(rows, block_rows),),
        in_specs=[sspec, bspec, bspec, bspec, bspec],
        out_specs=(bspec,) * len(outs),
        # alias p/m/v buffers into the outputs: the update is in-place in
        # HBM, the same no-new-allocation contract the donated XLA path has
        input_output_aliases={1: 0, 3: 1, 4: 2},
        interpret=interpret,
        name=KERNEL_FUSED_ADAM,
    )(scal, *(x.reshape(rows, width) for x in (p, g, m, v)))
    return tuple(x.reshape(p.shape) for x in res)


# ------------------------------------------------------------------- probe


def _probe_kernel(interpret=None) -> Optional[str]:
    """None when the kernel matches the XLA leaf math, else the mismatch."""
    hyper = FusedHyper(
        kind="adam", b1=0.9, b2=0.999, eps=1e-8, grad_clip=1.0,
        l2_grad=0.0, l2_decay=0.01,
    )
    r = jax.random.split(jax.random.PRNGKey(7), 4)
    # three dimensions (merged), a width that is no multiple of 128, and
    # more rows than whole blocks hold (32 + 16: the last block is ragged);
    # small blocks, so that the probe's arrays (30 KB each) are nothing
    # beside a small model's own on the device
    shape, block_bytes = (3, 16, 160), 32 * 1024
    p = jax.random.normal(r[0], shape, jnp.float32)
    g = jax.random.normal(r[1], shape, jnp.float32) * 0.1
    m = jax.random.normal(r[2], shape, jnp.float32) * 0.01
    v = jnp.abs(jax.random.normal(r[3], shape, jnp.float32)) * 0.01
    scal = jnp.asarray([2.3, 0.1, 0.001, -0.001, 6.0, 0.8], jnp.float32)
    # both forms the step uses: a float32 gradient, and a shadowed leaf's
    # (bf16 gradient in, bf16 shadow out beside the three)
    for g_in, shadow_dtype in ((g, None), (g.astype(jnp.bfloat16), jnp.bfloat16)):
        got = jax.jit(
            lambda *a: _kernel_leaf(*a, hyper=hyper, shadow_dtype=shadow_dtype,
                                    interpret=interpret, block_bytes=block_bytes)
        )(p, g_in, m, v, scal)
        want = _leaf_math(p, g_in.astype(p.dtype), m, v, *scal, hyper)
        for name, a, b in zip(("params", "mu", "nu"), got, want):
            bad = _probe.mismatch(name, a, b, atol=1e-6, rtol=1e-6)
            if bad:
                return bad
        if shadow_dtype is not None:
            # the shadow IS the cast of the params the kernel wrote
            bad = _probe.mismatch(
                "shadow", got[3], got[0].astype(shadow_dtype), atol=0.0
            )
            if bad:
                return bad
    return None


GATE = _probe.Gate(
    "fused update", "SRT_PALLAS_FUSED", _probe_kernel,
    unprobed="no fused update ran in this process",
)


def fused_kernel_enabled() -> bool:
    """One-time probe (ops/probe.py): compile the kernel and validate it
    against the XLA leaf math on the current backend; cache the verdict.
    SRT_PALLAS_FUSED=1 forces the probe on any backend, =0 forces off;
    default arms on TPU only, where a failed probe raises — the same
    discipline as the flash-attention probe."""
    return GATE.enabled(_INTERPRET)


def fused_kernel_status() -> str:
    """What the fused-update kernel's probe resolved to, in words."""
    return GATE.verdict


def fused_status(tx: Any, mesh: Any = None) -> str:
    """Honest-labeling string for the ``runtime`` line: what the optimizer update
    path ACTUALLY is (a CPU fallback must not masquerade as the kernel).

    ``mesh`` is the mesh the update was compiled under: the kernel gate
    (``context.single_device``) keeps pallas off multi-device meshes, and the
    label must agree with the gate — the record's mesh, not the contextvar
    at record time (unset outside the traced update)."""
    if not getattr(tx, "applies_updates", False):
        return "off (optax chain)"
    multi = mesh is not None and int(mesh.size) > 1
    if GATE.armed is True and not multi:
        return _probe.active(_INTERPRET)
    if multi:
        return "active (xla; kernel gated off a multi-device mesh)"
    return f"active (xla; kernel {GATE.verdict})"


# ------------------------------------------------- fused transformation


def stable_global_norm(tree: Any) -> "jnp.ndarray":
    """Global L2 norm with a partitioner-proof computation.

    Under a multi-device mesh the SPMD partitioner is free to split a
    full-tree norm reduction into per-shard partial sums + psum, and it
    makes that choice per-program: the same norm compiles to different
    accumulation orders in the replicated vs full-update-sharding
    programs (and at different mesh shapes), drifting the grad-clip
    scale by an ulp and with it every updated parameter. Here every
    device instead computes the WHOLE reduction locally over its
    replicated copy inside ``shard_map`` (manual mode — GSPMD cannot
    re-partition the body), so the value is identical across
    ``update_sharding`` modes and across mesh shapes. Off-mesh (or on a
    single device) this is exactly ``optax.global_norm``, which keeps
    the fused==optax single-device bitwise tests intact.

    Callers must hand in grads that are logically replicated (the train
    step pins them with a ``with_sharding_constraint`` + barrier before
    the optimizer runs — parallel/step.py).
    """
    from ..parallel import context as pctx

    mesh = pctx.current_mesh()
    if mesh is None or int(mesh.size) == 1:
        return optax.global_norm(tree)
    from jax.sharding import PartitionSpec as P

    from ..parallel.smap import shard_map

    leaves = jax.tree_util.tree_leaves(tree)
    fn = shard_map(
        lambda *ls: optax.global_norm(ls),
        mesh=mesh,
        in_specs=tuple(P() for _ in leaves),
        out_specs=P(),
        check_vma=False,
    )
    return fn(*leaves)


class FusedTransformation:
    """optax-shaped transformation computing the whole chain in one pass.

    ``update(grads, state, params)`` returns ``(new_params, new_state)`` —
    NOT (updates, state): ``applies_updates`` tells the train step the
    ``optax.apply_updates`` traversal is already folded in. ``init`` and
    the state pytree structure delegate to the reference chain, so
    flipping the knob never invalidates checkpointed optimizer state.
    """

    applies_updates = True

    def __init__(
        self,
        reference_tx: optax.GradientTransformation,
        hyper: FusedHyper,
        lr_fn: Callable[[Any], Any],
        adam_idx: int,
        sched_idx: int,
    ):
        self.reference_tx = reference_tx
        self.hyper = hyper
        self.lr_fn = lr_fn
        self.adam_idx = adam_idx
        self.sched_idx = sched_idx
        self.in_place: Optional[dict] = None

    def init(self, params):
        return self.reference_tx.init(params)

    def update(self, grads, state, params=None, shadow=None):
        """``(new_params, new_state)``; given ``shadow`` (the bf16 copies of
        some of the leaves, a sub-tree of ``params``: parallel/step.py),
        ``(new_params, new_state, new_shadow)`` with each shadow leaf the
        cast of its new params. A gradient leaf may arrive in a narrower
        float dtype than its parameter (a shadowed leaf's cotangent): it is
        widened where it is read, the norm over the same values."""
        if params is None:
            raise ValueError("fused update needs params (applies in place)")
        from optax._src import numerics

        hyper = self.hyper
        adam_state = state[self.adam_idx]
        sched_state = state[self.sched_idx]
        count_inc = numerics.safe_int32_increment(adam_state.count)
        # optax scale_by_schedule reads its count BEFORE incrementing
        step_size = jnp.float32(-1.0) * self.lr_fn(sched_state.count)
        bc1 = 1 - hyper.b1**count_inc
        bc2 = 1 - hyper.b2**count_inc
        # partitioner-proof norm: the clip scale must be the same VALUE in
        # every update-sharding mode and at every mesh shape, or the fused
        # update can never be bit-compared across them (see the function's
        # docstring; single-device this IS optax.global_norm). The widening
        # of a narrow leaf fuses into its reduction.
        gnorm = (
            stable_global_norm(
                jax.tree_util.tree_map(
                    lambda g, p: g.astype(p.dtype), grads, params
                )
            )
            if hyper.grad_clip > 0
            else jnp.float32(0.0)
        )
        if hyper.kind == "radam":
            ro_inf = 2.0 / (1 - hyper.b2) - 1
            b2t = hyper.b2**count_inc
            ro = ro_inf - 2 * count_inc * b2t / (1 - b2t)
            rect = jnp.sqrt(
                (ro - 4)
                * (ro - 2)
                * ro_inf
                / ((ro_inf - 4) * (ro_inf - 2) * ro)
            )
        else:
            ro = jnp.float32(0.0)
            rect = jnp.float32(0.0)

        # kernel gate: under a multi-device mesh (replicated params /
        # ZeRO-1 sharded moments) the update stays on the XLA path, which
        # GSPMD partitions cleanly
        from ..parallel import context as pctx

        use_kernel = pctx.single_device() and fused_kernel_enabled()
        scal = None
        if use_kernel:
            scal = jnp.stack(
                [
                    jnp.asarray(gnorm, jnp.float32),
                    jnp.asarray(bc1, jnp.float32),
                    jnp.asarray(bc2, jnp.float32),
                    jnp.asarray(step_size, jnp.float32),
                    jnp.asarray(ro, jnp.float32),
                    jnp.asarray(rect, jnp.float32),
                ]
            )

        leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
        shadow_leaves, shadow_def = (
            jax.tree_util.tree_flatten_with_path(shadow)
            if shadow is not None else ([], None)
        )
        shadow_at = dict(shadow_leaves)
        new_p, new_m, new_v, new_shadow = [], [], [], {}
        in_kernel, fell = [], {}
        for (path, p), g, m, v in zip(
            leaves,
            treedef.flatten_up_to(grads),
            treedef.flatten_up_to(adam_state.mu),
            treedef.flatten_up_to(adam_state.nu),
        ):
            old_shadow = shadow_at.get(path)
            blocking, why = (
                (None, "small") if p.size < MIN_KERNEL_SIZE
                else leaf_blocking(p.shape, p.dtype)
            )
            if use_kernel and blocking is not None:
                in_kernel.append(p.size)
                out = _kernel_leaf(
                    p, g, m, v, scal, hyper,
                    shadow_dtype=None if old_shadow is None else old_shadow.dtype,
                )
            else:
                name = jax.tree_util.keystr(path, simple=True, separator="/")
                fell[name] = (p.size, why)
                out = _leaf_math(
                    p, g.astype(p.dtype), m, v, gnorm, bc1, bc2, step_size,
                    ro, rect, hyper,
                )
                if old_shadow is not None:
                    out += (out[0].astype(old_shadow.dtype),)
            new_p.append(out[0])
            new_m.append(out[1])
            new_v.append(out[2])
            if old_shadow is not None:
                new_shadow[path] = out[3]
        if use_kernel:
            # what the traced update did, for the ``runtime`` report (the
            # shapes decide it, so every trace of one model agrees)
            self.in_place = _in_place_record(in_kernel, fell)

        from optax._src.transform import ScaleByAdamState, ScaleByScheduleState

        new_state = list(state)
        new_state[self.adam_idx] = ScaleByAdamState(
            count=count_inc,
            mu=treedef.unflatten(new_m),
            nu=treedef.unflatten(new_v),
        )
        new_state[self.sched_idx] = ScaleByScheduleState(
            count=numerics.safe_int32_increment(sched_state.count)
        )
        if shadow is None:
            return treedef.unflatten(new_p), tuple(new_state)
        return (
            treedef.unflatten(new_p),
            tuple(new_state),
            shadow_def.unflatten([new_shadow[path] for path, _ in shadow_leaves]),
        )


def _in_place_record(in_kernel, fell) -> dict:
    """The tally ``FusedTransformation.in_place`` keeps: the share of the
    parameters' elements whose leaf the kernel updated where it lay, how
    many leaves that was, how many fell to XLA for being small, and the
    others that fell, by name and reason (the first nine)."""
    total = sum(in_kernel) + sum(n for n, _ in fell.values())
    other = {k: why for k, (_, why) in fell.items() if why != "small"}
    record = {
        "share": sum(in_kernel) / max(total, 1),
        "leaves": len(in_kernel),
        "small": len(fell) - len(other),
        "xla": dict(list(other.items())[:9]),
    }
    if len(other) > 9:
        record["xla"]["..."] = f"{len(other) - 9} more"
    return record


def in_place_status(tx: Any) -> Optional[dict]:
    """``FusedTransformation.in_place`` of a (wrapped) transformation: None
    where no update was traced through the kernel (off a TPU, on a mesh)."""
    return getattr(getattr(tx, "tx", tx), "in_place", None)


def make_fused_transformation(
    *,
    kind: str,
    lr_fn: Callable[[Any], Any],
    b1: float,
    b2: float,
    eps: float,
    grad_clip: float = 0.0,
    l2_grad: float = 0.0,
    l2_decay: float = 0.0,
    adam_idx: int,
    sched_idx: int,
    reference_tx: optax.GradientTransformation,
) -> FusedTransformation:
    if kind not in ("adam", "radam"):
        raise ValueError(f"unknown fused optimizer kind {kind!r}")
    hyper = FusedHyper(
        kind=kind, b1=float(b1), b2=float(b2), eps=float(eps),
        grad_clip=float(grad_clip or 0.0), l2_grad=float(l2_grad or 0.0),
        l2_decay=float(l2_decay or 0.0),
    )
    return FusedTransformation(
        reference_tx, hyper, lr_fn, int(adam_idx), int(sched_idx)
    )
