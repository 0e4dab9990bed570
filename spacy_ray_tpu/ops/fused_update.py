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
  mu, nu in; params', mu', nu' out, HBM-aliased via input_output_aliases)
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
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..names import KERNEL_FUSED_ADAM
from . import probe as _probe

# kernel block: BR rows x 128 lanes of f32 per grid step (1 MB/operand —
# well under VMEM with 5 inputs + 3 outputs resident)
LANES = 128
BLOCK_ROWS = 2048
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
                   ov_ref, *, hyper: FusedHyper):
    # scal [6] SMEM: gnorm, bc1, bc2, step_size, ro, rect
    p2, m2, v2 = _leaf_math(
        p_ref[...],
        g_ref[...],
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


_INTERPRET = False  # tests flip this to run the kernel on CPU


def _kernel_leaf(p, g, m, v, scal, hyper: FusedHyper, interpret=None):
    """Run one leaf through the pallas kernel: ravel, zero-pad to a whole
    number of (BLOCK_ROWS, 128) blocks, grid over row blocks, un-pad."""
    interpret = _INTERPRET if interpret is None else interpret
    n = p.size
    shape = p.shape
    tile = BLOCK_ROWS * LANES
    padded = ((n + tile - 1) // tile) * tile
    rows = padded // LANES

    def prep(x):
        x = jnp.ravel(x)
        if padded != n:
            x = jnp.pad(x, (0, padded - n))
        return x.reshape(rows, LANES)

    kernel = functools.partial(_update_kernel, hyper=hyper)
    bspec = pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM)
    sspec = pl.BlockSpec(memory_space=pltpu.SMEM)
    out = jax.ShapeDtypeStruct((rows, LANES), p.dtype)
    p2, m2, v2 = pl.pallas_call(
        kernel,
        out_shape=(out, out, out),
        grid=(rows // BLOCK_ROWS,),
        in_specs=[sspec, bspec, bspec, bspec, bspec],
        out_specs=(bspec, bspec, bspec),
        # alias p/m/v buffers into the outputs: the update is in-place in
        # HBM, the same no-new-allocation contract the donated XLA path has
        input_output_aliases={1: 0, 3: 1, 4: 2},
        interpret=interpret,
        name=KERNEL_FUSED_ADAM,
    )(scal, prep(p), prep(g), prep(m), prep(v))

    def unprep(x):
        return jnp.ravel(x)[:n].reshape(shape)

    return unprep(p2), unprep(m2), unprep(v2)


# ------------------------------------------------------------------- probe


def _probe_kernel(interpret=None) -> Optional[str]:
    """None when the kernel matches the XLA leaf math, else the mismatch."""
    hyper = FusedHyper(
        kind="adam", b1=0.9, b2=0.999, eps=1e-8, grad_clip=1.0,
        l2_grad=0.0, l2_decay=0.01,
    )
    r = jax.random.split(jax.random.PRNGKey(7), 4)
    n = 4321  # deliberately not a tile multiple: exercises the padding
    p = jax.random.normal(r[0], (n,), jnp.float32)
    g = jax.random.normal(r[1], (n,), jnp.float32) * 0.1
    m = jax.random.normal(r[2], (n,), jnp.float32) * 0.01
    v = jnp.abs(jax.random.normal(r[3], (n,), jnp.float32)) * 0.01
    scal = jnp.asarray([2.3, 0.1, 0.001, -0.001, 6.0, 0.8], jnp.float32)
    got = jax.jit(
        lambda *a: _kernel_leaf(*a, hyper=hyper, interpret=interpret)
    )(p, g, m, v, scal)
    want = _leaf_math(p, g, m, v, *scal, hyper)
    for name, a, b in zip(("params", "mu", "nu"), got, want):
        bad = _probe.mismatch(name, a, b, atol=1e-6, rtol=1e-6)
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

    def init(self, params):
        return self.reference_tx.init(params)

    def update(self, grads, state, params=None):
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
        # docstring; single-device this IS optax.global_norm)
        gnorm = (
            stable_global_norm(grads)
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

        def leaf(p, g, m, v):
            if (
                use_kernel
                and p.dtype == jnp.float32
                and p.size >= MIN_KERNEL_SIZE
            ):
                return _kernel_leaf(p, g, m, v, scal, hyper)
            return _leaf_math(
                p, g, m, v, gnorm, bc1, bc2, step_size, ro, rect, hyper
            )

        out = jax.tree_util.tree_map(leaf, params, grads, adam_state.mu,
                                     adam_state.nu)
        is_triple = lambda x: isinstance(x, tuple)  # noqa: E731
        new_params = jax.tree_util.tree_map(
            lambda t: t[0], out, is_leaf=is_triple
        )
        new_mu = jax.tree_util.tree_map(lambda t: t[1], out, is_leaf=is_triple)
        new_nu = jax.tree_util.tree_map(lambda t: t[2], out, is_leaf=is_triple)

        from optax._src.transform import ScaleByAdamState, ScaleByScheduleState

        new_state = list(state)
        new_state[self.adam_idx] = ScaleByAdamState(
            count=count_inc, mu=new_mu, nu=new_nu
        )
        new_state[self.sched_idx] = ScaleByScheduleState(
            count=numerics.safe_int32_increment(sched_state.count)
        )
        return new_params, tuple(new_state)


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
