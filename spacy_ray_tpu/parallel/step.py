"""The sharded train step: one compiled XLA program per (B, T) bucket.

This single function replaces the reference's entire L3/L4 communication
machinery (SURVEY.md §1): forward, backward, gradient all-reduce over ICI,
optimizer update, and a sharded update phase — where the reference does
per-parameter RPC push/broadcast with version gates and quorums (reference
proxies.py:54-133, worker.py:117-132), here GSPMD insert collectives from
sharding annotations and the whole exchange compiles into the step
(SURVEY.md §2.2: "synchronous allreduce is strictly better on TPU ICI").

Update-phase sharding (``[training] update_sharding``, subsuming the old
``zero1`` bool):

* ``"replicated"`` — every replica holds the full optimizer state and
  applies the full update (the original layout).
* ``"zero1"`` — optimizer STATE is sharded over the data axis
  (:func:`~..mesh.zero1_spec`); where the update math runs is left to
  GSPMD's placement inference.
* ``"full"`` — the update COMPUTATION itself is sharded (arXiv
  2004.13336 "Automatic Cross-Replica Sharding of Weight Update in
  Data-Parallel Training", the TPU-native completion of the reference's
  owner-applies-the-update scheme): each replica applies the optimizer
  chain only to its owned param shard and the updated params are
  allgathered back to the replicated data-parallel layout. Bit-exactness
  with ``"replicated"`` is engineered, not hoped for: the all-reduced
  gradients are pinned replicated behind an ``optimization_barrier``
  (XLA must not rewrite the all-reduce into a reduce-scatter, whose
  different accumulation order changes last-ulp values) so any global
  reduction inside the optimizer (grad-clip global norm) sees the same
  full arrays in the same order, and everything downstream is elementwise
  — identical per element whether computed on a shard or the whole leaf.
  tests/test_update_sharding.py asserts full == replicated to EQUALITY,
  the same discipline as the fused==optax tests.

Gradient accumulation: the reference folds ``accumulate_gradient`` into its
distributed quorum (reference worker.py:151-155,182 — with the dead-code bug
noted in SURVEY.md §2.4); here it is an explicit ``lax.scan`` over stacked
microbatches, numerically equivalent to a quorum of exactly
``num_workers × accumulate_gradient`` with zero staleness.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import names
from . import context as pctx
from .mesh import replicated, zero1_spec

# the full [training] update_sharding knob surface; "auto" resolves via
# resolve_update_sharding before any of the functions below see it
UPDATE_SHARDING_MODES = ("auto", "replicated", "zero1", "full")


def resolve_update_sharding(
    mode: str,
    *,
    zero1: bool = False,
    n_data: int = 1,
    backend: Optional[str] = None,
) -> str:
    """Resolve the ``[training] update_sharding`` knob to a concrete mode.

    ``zero1`` is the legacy bool knob, kept as an accepted alias:
    ``zero1 = true`` under ``update_sharding = "auto"`` resolves to
    ``"zero1"`` (existing configs keep their exact behavior). An explicit
    non-auto ``update_sharding`` wins over the alias. ``"auto"`` without
    the alias arms ``"full"`` on accelerator backends with more than one
    data rank — the same platform-gating discipline as ``fused_update`` /
    ``bf16_shadow`` (PERF.md round 7: CPU measures the mega-rewrites at
    parity-to-worse; accelerators are where the bandwidth/compute ratios
    pay) — and stays ``"replicated"`` on CPU or single-replica meshes.
    """
    if mode not in UPDATE_SHARDING_MODES:
        raise ValueError(
            f"update_sharding must be one of {UPDATE_SHARDING_MODES}, "
            f"got {mode!r}"
        )
    if mode != "auto":
        return mode
    if zero1:
        return "zero1"
    if backend is None:
        backend = jax.default_backend()
    if backend != "cpu" and n_data > 1:
        return "full"
    return "replicated"


def update_sharding_status(mode: str, mesh: Optional[Mesh] = None) -> str:
    """Honest-labeling string for the ``runtime`` line / ``info --probe``: what
    the update phase ACTUALLY does, the same discipline as
    ``fused_update``'s label — a single-replica mesh must not masquerade
    as a sharded update."""
    n_data = int(mesh.shape["data"]) if mesh is not None else 1
    if mode == "replicated" or n_data <= 1:
        degenerate = mode != "replicated" and n_data <= 1
        return "replicated" + (
            f" ({mode} degenerates: 1 data rank)" if degenerate else ""
        )
    if mode == "zero1":
        return f"zero1 (state sharded {n_data}-way, apply placement free)"
    return (
        f"full (state + apply sharded {n_data}-way, params allgathered)"
    )


def _mode_of(zero1_or_mode: Any) -> str:
    """Accept the legacy bool OR a resolved mode string."""
    if isinstance(zero1_or_mode, str):
        if zero1_or_mode == "auto":
            raise ValueError(
                "update_sharding 'auto' must be resolved before use "
                "(resolve_update_sharding)"
            )
        if zero1_or_mode not in UPDATE_SHARDING_MODES:
            raise ValueError(
                f"unknown update_sharding mode {zero1_or_mode!r}"
            )
        return zero1_or_mode
    return "zero1" if zero1_or_mode else "replicated"


def _constrain_owner_shards(tree: Any, mesh: Mesh) -> Any:
    """with_sharding_constraint every leaf to its owner-shard spec."""
    return jax.tree_util.tree_map(
        lambda x: jax.lax.with_sharding_constraint(x, zero1_spec(x, mesh)),
        tree,
    )


def _constrain_replicated(tree: Any, mesh: Mesh) -> Any:
    repl_sh = replicated(mesh)
    return jax.tree_util.tree_map(
        lambda x: jax.lax.with_sharding_constraint(x, repl_sh), tree
    )


def shard_opt_state(opt_state: Any, mesh: Mesh, zero1: Any) -> Any:
    """Place optimizer state per mode (bool = legacy ZeRO-1 alias):
    sharded over the data axis for ``"zero1"``/``"full"``, replicated
    otherwise. Input leaves may be host arrays from ANY saved mesh shape
    (the checkpoint's canonical unsharded layout) — placement here is
    what re-shards a resumed state to the CURRENT mesh."""
    if _mode_of(zero1) == "replicated":
        return jax.device_put(opt_state, replicated(mesh))
    return jax.tree_util.tree_map(
        lambda leaf: jax.device_put(leaf, zero1_spec(leaf, mesh)), opt_state
    )


def opt_state_shardings(opt_state: Any, mesh: Mesh, zero1: Any) -> Any:
    if _mode_of(zero1) == "replicated":
        return jax.tree_util.tree_map(lambda _: replicated(mesh), opt_state)
    return jax.tree_util.tree_map(lambda leaf: zero1_spec(leaf, mesh), opt_state)


def overlay_shadow(params: Any, shadow: Any) -> Any:
    """Overlay a (sub-structure) shadow tree onto params: positions present
    in ``shadow`` are taken from it (bf16 copies of the trunk's matmul
    weights — models/transformer.py build_param_shadow), the rest from
    ``params``. The forward then consumes the shadow leaves directly, so
    the layer stack's per-step ``astype(compute_dtype)`` is a no-op."""
    if not isinstance(shadow, dict):
        return shadow
    out = dict(params)
    for k, v in shadow.items():
        out[k] = overlay_shadow(params[k], v)
    return out


def refresh_shadow(new_params: Any, shadow: Any) -> Any:
    """Re-derive the shadow from freshly updated master params — ONE cast
    per shadowed leaf, fused into the same jitted update (the donated old
    shadow buffer is reused; no second host-visible traversal). The fused
    update on one device writes the shadow itself, beside the params
    (ops/fused_update.py): this is the optax chain's and the sharded
    update's."""
    if not isinstance(shadow, dict):
        return new_params.astype(shadow.dtype)
    return {k: refresh_shadow(new_params[k], v) for k, v in shadow.items()}


def _cast_like(tree: Any, like: Any) -> Any:
    """Cast shadow-leaf cotangents (bf16) back to the master dtype. The
    VALUES match the cast-per-step path up to one bf16 rounding: the
    baseline program's backward may elide the f32->bf16->f32 double
    rounding inside the weight-grad matmul, so the two trajectories agree
    to ~1e-8/step rather than bitwise (forward IS bit-exact — asserted by
    tests/test_fused_update.py)."""
    return jax.tree_util.tree_map(
        lambda x, ref: x.astype(ref.dtype), tree, like
    )


def make_train_step(
    loss_fn: Callable,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    *,
    accumulate_gradient: int = 1,
    zero1: Any = False,
    update_sharding: Optional[str] = None,
    opt_state_template: Any = None,
    donate: bool = True,
    shadow: bool = False,
    multi_dispatch: bool = False,
) -> Callable:
    """Build the jitted sharded update.

    loss_fn(params, tokens, targets, rng) -> (loss, metrics).

    Returns update(params, opt_state, tokens, targets, rng) ->
    (params, opt_state, loss, metrics). When accumulate_gradient > 1,
    tokens/targets leaves carry a leading [A] microbatch dim and the batch
    dim is sharded at position 1; otherwise position 0.

    ``shadow=True``: the update takes (params, opt_state, shadow, tokens,
    targets, rng) and returns (params, opt_state, shadow, loss, metrics).
    The forward runs on ``overlay_shadow(params, shadow)`` (bf16 trunk
    weights read directly — no per-step cast), gradients are cast back to
    the master dtype before accumulation/optimizer, and the shadow is
    refreshed from the new params inside the same program (all three
    state arguments donated).

    ``multi_dispatch=True``: tokens/targets leaves carry a leading [K]
    per-dispatch dim; the update runs K full train steps as one
    ``lax.scan`` (ONE host round-trip) and returns (params, opt_state,
    [shadow,] rng, losses[K], metrics[K]) — ``rng`` is carried through
    the scan with the same ``jax.random.split`` chain the host performs
    at K=1, so K steps are bit-identical to K single dispatches. K is
    read from the input shape: each distinct K compiles once.

    ``update_sharding``: a RESOLVED mode ("replicated" | "zero1" |
    "full"); when None the legacy ``zero1`` bool decides. "full" shards
    the optimizer apply itself across the data axis and allgathers the
    updated params (module docstring) — with ``shadow=True`` the bf16
    shadow is refreshed SHARD-LOCAL from the still-sharded new params
    before its own allgather, so the refresh cast costs 1/n_data of the
    work and the gather moves bf16 bytes.
    """
    accum = max(int(accumulate_gradient), 1)
    mode = _mode_of(update_sharding if update_sharding is not None else zero1)
    # a 1-rank data axis makes every owner-shard spec replicated: skip the
    # constraint/barrier scaffolding entirely (bit-identical either way)
    multi_replica = int(mesh.shape["data"]) > 1
    full_sharded = mode == "full" and multi_replica
    # Gradients are pinned fully replicated behind an optimization_barrier
    # in BOTH "replicated" and "full" modes: the two programs then share an
    # identical region up to the barrier (same all-reduce, same
    # accumulation order), which is what makes full == replicated hold to
    # EQUALITY rather than tolerance. "zero1" deliberately keeps its
    # pre-knob unpinned program byte-for-byte (GSPMD placement freedom —
    # it was never bit-compared against replicated, only rtol-tested).
    pin_grads = multi_replica and mode in ("replicated", "full")

    def _to_owner_shards(tree):
        return _constrain_owner_shards(tree, mesh)

    def _to_replicated(tree):
        return _constrain_replicated(tree, mesh)

    def grads_of(params, tokens, targets, rng):
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, tokens, targets, rng
        )
        return loss, metrics, grads

    applies_updates = bool(getattr(tx, "applies_updates", False))
    # the fused update on ONE device takes the shadow with the params: it
    # reads a shadowed leaf's bf16 cotangent as it is (widened where it is
    # read: the same values, no float32 copy) and writes the new shadow
    # beside the new params, so neither cast is a pass of its own
    update_owns_shadow = applies_updates and int(mesh.size) == 1

    def step_once(params, opt_state, shadow_t, tokens, targets, rng):
        fwd_params = (
            overlay_shadow(params, shadow_t) if shadow_t is not None else params
        )
        shadow_in_update = update_owns_shadow and shadow_t is not None
        if accum == 1:
            loss, metrics, grads = grads_of(fwd_params, tokens, targets, rng)
            if shadow_t is not None and not shadow_in_update:
                # bf16 cotangents at shadow leaves -> f32 master grads (the
                # same values the cast-per-step path produces via the
                # cast's transpose)
                grads = _cast_like(grads, params)
        else:
            def body(carry, micro):
                acc_grads, rng = carry
                rng, sub = jax.random.split(rng)
                m_tokens, m_targets = micro
                loss, metrics, grads = grads_of(fwd_params, m_tokens, m_targets, sub)
                if shadow_t is not None:
                    grads = _cast_like(grads, acc_grads)
                acc_grads = jax.tree_util.tree_map(jnp.add, acc_grads, grads)
                return (acc_grads, rng), (loss, metrics)

            with jax.named_scope(names.SCOPE_GRAD_ACCUM):
                zero_grads = jax.tree_util.tree_map(jnp.zeros_like, params)
                (grads, _), (losses, metricses) = jax.lax.scan(
                    body, (zero_grads, rng), (tokens, targets)
                )
                grads = jax.tree_util.tree_map(lambda g: g / accum, grads)
                loss = jnp.mean(losses)
                # losses and accuracies are means over the micro-batches;
                # device counters (names.py) add up
                metrics = {
                    k: (jnp.sum(metricses[k], axis=0)
                        if k.startswith(names.COUNTER_PREFIX)
                        else jax.tree_util.tree_map(jnp.mean, metricses[k]))
                    for k in sorted(metricses)
                }
        with jax.named_scope(names.SCOPE_UPDATE):
            if pin_grads:
                # pin the all-reduced grads REPLICATED and fence them: XLA must
                # not rewrite the gradient all-reduce into a reduce-scatter
                # (a different accumulation order drifts last-ulp values), and
                # any global reduction inside the optimizer (grad-clip norm)
                # then sees the identical full arrays — the two properties the
                # full==replicated equality test stands on
                grads = jax.lax.optimization_barrier(_to_replicated(grads))
            upd_params = _to_owner_shards(params) if full_sharded else params
            new_shadow = None
            if shadow_in_update:
                new_params, new_opt_state, new_shadow = tx.update(
                    grads, opt_state, upd_params, shadow=shadow_t
                )
            elif applies_updates:
                # fused path (ops/fused_update.py): the whole optimizer chain
                # plus apply_updates in one traversal
                new_params, new_opt_state = tx.update(grads, opt_state, upd_params)
            else:
                updates, new_opt_state = tx.update(grads, opt_state, upd_params)
                if full_sharded:
                    updates = _to_owner_shards(updates)
                new_params = optax.apply_updates(upd_params, updates)
            if full_sharded:
                # shard-local results; the shadow refresh happens PRE-allgather
                # (each rank casts only its owned shard, and the gather moves
                # bf16 bytes); then the ONE allgather returns the updated
                # params to the replicated data-parallel layout
                new_params = _to_owner_shards(new_params)
                if shadow_t is not None:
                    new_shadow = _to_replicated(
                        _to_owner_shards(refresh_shadow(new_params, shadow_t))
                    )
                new_params = _to_replicated(new_params)
            elif shadow_t is not None and not shadow_in_update:
                new_shadow = refresh_shadow(new_params, shadow_t)
            if pin_grads:
                # same partitioner-proof reduction the fused clip uses, so the
                # reported norm is identical across modes and mesh shapes (the
                # free-floating optax.global_norm compiles to a different
                # accumulation order per program — ops/fused_update.py)
                from ..ops.fused_update import stable_global_norm

                grad_norm = stable_global_norm(grads)
            else:
                # (a leaf the update took narrow is widened inside the sum)
                grad_norm = optax.global_norm(_cast_like(grads, params))
        metrics = dict(metrics)
        metrics["grad_norm"] = grad_norm
        return new_params, new_opt_state, new_shadow, loss, metrics

    if multi_dispatch:
        def multi_core(params, opt_state, shadow_t, tokens, targets, rng):
            def body(carry, batch):
                params, opt_state, shadow_t, rng = carry
                rng, sub = jax.random.split(rng)
                b_tokens, b_targets = batch
                params, opt_state, shadow_t, loss, metrics = step_once(
                    params, opt_state, shadow_t, b_tokens, b_targets, sub
                )
                return (params, opt_state, shadow_t, rng), (loss, metrics)

            (params, opt_state, shadow_t, rng), (losses, metricses) = (
                jax.lax.scan(
                    body, (params, opt_state, shadow_t, rng), (tokens, targets)
                )
            )
            return params, opt_state, shadow_t, rng, losses, metricses

        if shadow:
            update = multi_core
        else:
            def update(params, opt_state, tokens, targets, rng):
                p, o, _, rng, losses, metricses = multi_core(
                    params, opt_state, None, tokens, targets, rng
                )
                return p, o, rng, losses, metricses
    elif shadow:
        def update(params, opt_state, shadow_t, tokens, targets, rng):
            p, o, s, loss, metrics = step_once(
                params, opt_state, shadow_t, tokens, targets, rng
            )
            return p, o, s, loss, metrics
    else:
        def update(params, opt_state, tokens, targets, rng):
            p, o, _, loss, metrics = step_once(
                params, opt_state, None, tokens, targets, rng
            )
            return p, o, loss, metrics

    # Sharding layout, DECLARED to jit (not left to placement inference):
    # params replicated; batch sharded over `data`; opt state replicated or
    # ZeRO-1 per-leaf. out_shardings pin the updated opt state to the same
    # layout so a ZeRO-1 state stays sharded across steps instead of being
    # replicated back by GSPMD.
    repl = replicated(mesh)
    batch_dims = (1 if multi_dispatch else 0) + (1 if accum > 1 else 0)
    batch_shard = NamedSharding(mesh, P(*([None] * batch_dims), "data"))
    if opt_state_template is not None:
        opt_sh: Any = opt_state_shardings(opt_state_template, mesh, mode)
    else:
        opt_sh = repl  # prefix: whole subtree replicated

    in_sh: Tuple[Any, ...] = (repl, opt_sh)
    out_sh: Tuple[Any, ...] = (repl, opt_sh)
    donate_argnums: Tuple[int, ...] = (0, 1)
    if shadow:
        in_sh += (repl,)
        out_sh += (repl,)
        donate_argnums += (2,)  # the old shadow buffer backs the refresh
    in_sh += (batch_shard, batch_shard, repl)
    if multi_dispatch:
        out_sh += (repl, repl, repl)  # rng, losses [K], metrics [K]
    else:
        out_sh += (repl, repl)  # loss, metrics

    jit_kwargs: Dict[str, Any] = {
        "in_shardings": in_sh,
        "out_shardings": out_sh,
    }
    if donate:
        jit_kwargs["donate_argnums"] = donate_argnums

    # a fixed function name is a fixed XLA module name (jit_<name>): a trace
    # tells the programs apart, and a refactor does not rename them
    update.__name__ = (
        names.PROGRAM_TRAIN_STEP_MULTI if multi_dispatch
        else names.PROGRAM_TRAIN_STEP
    )
    jitted = jax.jit(update, **jit_kwargs)

    def run(*args):
        # install the mesh so model code (transformer TP/CP constraints,
        # ring attention) can consult it at trace time
        with pctx.use_mesh(mesh):
            return jitted(*args)

    def lower(*args):
        # same mesh install as ``run``: model code consults the mesh at
        # trace time, and lowering traces without executing
        with pctx.use_mesh(mesh):
            return jitted.lower(*args)

    run.mesh = mesh
    run.batch_shard = batch_shard
    run.replicated = repl
    run.opt_shardings = opt_sh
    run.lower = lower
    run.takes_shadow = shadow
    run.multi_dispatch = multi_dispatch
    run.update_sharding = mode
    return run


def make_shard_apply(tx: Any, *, donate: bool = True) -> Callable:
    """Jitted single-shard optimizer apply: ``(params, opt_state, grads)
    -> (params, opt_state)`` over ONE owner's slice tree, no mesh.

    This is the trainer fleet's apply entry point (training/fleet/): the
    cross-process analogue of :func:`make_train_step`'s update section,
    where the "shard" is the nested slice tree a fleet worker owns
    (ownership.py) rather than a mesh-sharded leaf — the owner applies
    the optimizer to exactly the parameters it owns, at quorum, and
    nothing else (PAPER.md §L3 owner-applies-the-update). ``tx`` may be
    the fused transformation (``applies_updates`` — ops/fused_update.py
    on the owned slice, as in the in-mesh "full" mode) or a plain optax
    chain. State and params are donated: the owner holds exactly one
    live copy of its shard.

    Wire compression is invisible here: compressed gradient pushes are
    dequantized to f32 at the wire boundary (fleet/wire.decode_grads)
    BEFORE the quorum buffer, so this apply always consumes plain f32
    grad trees — same numerics whatever codec carried them.
    """
    applies_updates = bool(getattr(tx, "applies_updates", False))

    def update(params, opt_state, grads):
        if applies_updates:
            new_params, new_opt_state = tx.update(grads, opt_state, params)
        else:
            import optax as _optax

            updates, new_opt_state = tx.update(grads, opt_state, params)
            new_params = _optax.apply_updates(params, updates)
        return new_params, new_opt_state

    jit_kwargs: Dict[str, Any] = {}
    if donate:
        jit_kwargs["donate_argnums"] = (0, 1)
    update.__name__ = names.PROGRAM_SHARD_APPLY
    jitted = jax.jit(update, **jit_kwargs)

    def run(params, opt_state, grads):
        return jitted(params, opt_state, grads)

    run.update_sharding = "fleet-owner-shard"
    return run


def place_batch(batch_tree: Any, mesh: Mesh, accum: bool = False) -> Any:
    """Place batch leaves with the batch dim sharded over the ``data`` axis.

    Pads are already in the arrays; B must be divisible by the data-axis
    size (the batcher guarantees it via bucket_batch_size + mesh multiple).

    Single-process: a plain sharded device_put (the local array IS the
    global batch). Multi-process: every host collated a DIFFERENT local
    batch (the stream is sharded by host in the loop), so device_put with a
    global sharding would treat each host's array as the same global value
    and silently drop every row outside that host's global shard slice —
    most of the corpus. Instead the global batch is assembled with
    ``jax.make_array_from_process_local_data``: global B = per-host B ×
    process_count, each host contributing all of its local rows.
    """
    sh = NamedSharding(mesh, P(None, "data") if accum else P("data"))
    if jax.process_count() == 1:
        return jax.tree_util.tree_map(lambda x: jax.device_put(x, sh), batch_tree)

    bdim = 1 if accum else 0

    def make_global(x):
        x = np.asarray(x)
        global_shape = (
            x.shape[:bdim]
            + (x.shape[bdim] * jax.process_count(),)
            + x.shape[bdim + 1 :]
        )
        return jax.make_array_from_process_local_data(sh, x, global_shape)

    return jax.tree_util.tree_map(make_global, batch_tree)


def place_replicated(tree: Any, mesh: Mesh) -> Any:
    sh = replicated(mesh)
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sh), tree)
