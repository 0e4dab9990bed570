"""Pipeline parallelism: GPipe-style SPMD schedule over the ``pipe`` axis.

Absent from the reference (SURVEY.md §2.2 row PP: "NO"); here it is a
first-class mesh axis for deep trunks whose layer stack exceeds one
device's HBM. TPU-idiomatic formulation — no per-stage processes, no
send/recv runtime: ALL devices run the same compiled program
(``shard_map``), each holding ``depth/S`` of the stacked layer parameters
(leading dim sharded over ``pipe``), and activations hop stage→stage+1
via ``lax.ppermute`` over ICI inside a ``lax.scan`` of ``M + S - 1``
ticks for M microbatches:

    tick t: stage s processes microbatch (t - s); stage 0 feeds microbatch
    t in; stage S-1 writes microbatch (t - S + 1) out.

The bubble fraction is (S-1)/(M+S-1) — pick M >= S. Everything is
differentiable (ppermute/psum transpose), so the same schedule runs the
backward pass in reverse. The region is partial-manual (``axis_names``:
manual over ``pipe`` only), so it composes with the ``data`` axis, with
the ``model`` axis (the stage body stays automatic over data/model, so TP
sharding constraints inside the layers apply) AND with the ``context``
axis: ring attention nests inside the stage body as a second
partial-manual region, manual over ``context`` only
(parallel/ring_attention.py).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from . import context as pctx

from .smap import shard_map

AXIS = "pipe"


def spmd_pipeline(
    stage_fn: Callable,
    stacked_params: Any,
    microbatches: jnp.ndarray,
    masks: jnp.ndarray,
    rng: jax.Array,
) -> "tuple[jnp.ndarray, jnp.ndarray]":
    """Run the pipelined layer stack.

    stage_fn(local_params, x, mask, rng) -> (y, aux) applies ONE STAGE's
    layers to one microbatch (local_params leaves have leading dim
    depth/S); aux is a scalar auxiliary loss for that stage+microbatch
    (e.g. the MoE router's load-balancing term; 0.0 when unused).

    stacked_params: pytree, leaves [depth, ...] (sharded over 'pipe' here).
    microbatches:   [M, mb, T, D] activations (embedding+positions done).
    masks:          [M, mb, T].
    Returns ([M, mb, T, D] replicated over the pipe axis, aux) where aux
    is the MEAN over microbatches of the per-microbatch aux sums across
    all stages. Drain ticks (a stage holding stale data) are masked out
    of the accumulation. NOTE: for a nonlinear aux (the MoE router's
    load-balance term) mean-of-per-microbatch values is the standard
    pipelined formulation (Switch/GShard practice) but is NOT numerically
    identical to the dense loop's full-batch aux — activations ARE
    dense-equal, the regularizer differs at O(1/M).
    """
    mesh = pctx.current_mesh()
    assert mesh is not None and AXIS in mesh.shape, "spmd_pipeline needs a pipe axis"
    S = int(mesh.shape[AXIS])
    M = int(microbatches.shape[0])
    param_spec = P(AXIS)  # leading (stacked-depth) dim -> stages

    # manual over `pipe` only: activations keep their global (auto) batch
    # semantics, so data/model constraints inside stage_fn apply, and aux
    # is global under automatic data semantics
    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(param_spec, P(), P(), P()),
        out_specs=(P(), P()),
        axis_names=frozenset({AXIS}),
        check_vma=False,
    )
    def run(local_params, xs, ms, key):
        stage = jax.lax.axis_index(AXIS)
        state = jnp.zeros_like(xs[0])
        outputs = jnp.zeros_like(xs)
        aux_acc = jnp.float32(0.0)
        perm = [(i, (i + 1) % S) for i in range(S)]

        def body(carry, t):
            state, outputs, aux_acc = carry
            # stage 0 ingests microbatch t (clipped: harmless compute on
            # stale data during drain ticks, results never written)
            feed = xs[jnp.clip(t, 0, M - 1)]
            x = jnp.where(stage == 0, feed, state)
            # the microbatch THIS stage processes at tick t is (t - stage)
            mb_idx = t - stage
            mask = ms[jnp.clip(mb_idx, 0, M - 1)]
            y, aux = stage_fn(local_params, x, mask, jax.random.fold_in(key, t))
            # drain ticks run on stale data: their aux must not count
            valid = (mb_idx >= 0) & (mb_idx < M)
            aux_acc = aux_acc + jnp.where(valid, aux, 0.0)
            out_idx = t - (S - 1)
            write = (stage == S - 1) & (out_idx >= 0)
            updated = jax.lax.dynamic_update_index_in_dim(
                outputs, y, jnp.clip(out_idx, 0, M - 1), 0
            )
            outputs = jnp.where(write, updated, outputs)
            state = jax.lax.ppermute(y, AXIS, perm)
            return (state, outputs, aux_acc), None

        (state, outputs, aux_acc), _ = jax.lax.scan(
            body, (state, outputs, aux_acc), jnp.arange(M + S - 1)
        )
        # finished microbatches live on the last stage; broadcast so the
        # (pipe-replicated) heads downstream see them everywhere
        outputs = jax.lax.psum(
            jnp.where(stage == S - 1, outputs, jnp.zeros_like(outputs)), AXIS
        )
        # every stage contributed its own layers' aux: sum over the ring,
        # mean over microbatches (the dense loop computes each layer's aux
        # once over the full batch)
        aux_total = jax.lax.psum(aux_acc, AXIS) / jnp.float32(M)
        return outputs, aux_total.reshape(1)

    outputs, aux_vec = run(stacked_params, microbatches, masks, rng)
    return outputs, aux_vec[0]
