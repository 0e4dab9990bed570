"""Optimizers + LR schedules: registered ``@optimizers`` / ``@schedules``.

Capability parity with the thinc Optimizer surface the reference drives
(reference proxies.py:128 ``optimizer(key, param, grad)``;
``step_schedules`` at worker.py/proxies via thinc; FakeOptimizer no-op at
reference worker.py:265-278). Here the optimizer is an optax
GradientTransformation compiled INTO the train step — there is no per-key
optimizer call and no proxy, so the reference's whole stale-gradient /
quorum machinery (proxies.py:111-133) has no equivalent to need.

``Adam.v1`` matches the config-surface of thinc's Adam (learn_rate, betas,
eps, L2, grad_clip, L2_is_weight_decay, use_averages).
"""

from __future__ import annotations

from typing import Any, Callable, Iterable, Optional, Union

import jax
import optax

from ..registry import registry

ScheduleLike = Union[float, Callable[[int], float], Iterable[float]]


class Schedule:
    """A LR schedule usable both as an optax step->value callable and as an
    iterator (thinc schedules are generators; optax wants step->value).

    ``fn`` MUST be jnp-traceable: inside the jitted train step the optax
    step count is a tracer, so python control flow on it would crash.
    """

    def __init__(self, fn: Callable[[Any], Any]):
        self.fn = fn
        self._step = 0

    def __call__(self, step):
        return self.fn(step)

    def __iter__(self):
        return self

    def __next__(self) -> float:
        val = float(self.fn(self._step))
        self._step += 1
        return val


def as_schedule_fn(value: ScheduleLike) -> Callable[[Any], Any]:
    """Normalize a learn_rate config value to a traceable step->rate fn."""
    import jax.numpy as jnp

    if isinstance(value, Schedule):
        return value.fn
    if isinstance(value, (int, float)):
        return lambda step: jnp.float32(value)
    if callable(value):
        return value
    # A generator/iterable (e.g. compounding.v1 used as LR): materialize a
    # long prefix into a device array and index it — python iteration can't
    # run under jit.
    import itertools

    table = jnp.asarray(
        [float(v) for v in itertools.islice(iter(value), 100_000)], dtype=jnp.float32
    )
    if table.size == 0:
        return lambda step: jnp.float32(0.0)

    def fn(step):
        idx = jnp.minimum(step, table.size - 1)
        return jnp.take(table, idx)

    return fn


@registry.schedules("warmup_linear.v1")
def warmup_linear(initial_rate: float, warmup_steps: int, total_steps: int) -> Schedule:
    """Linear warmup then linear decay — jnp-traceable (runs inside jit)."""
    import jax.numpy as jnp

    warmup = max(int(warmup_steps), 0)
    decay_span = max(int(total_steps) - warmup, 1)

    def fn(step):
        step = jnp.asarray(step, jnp.float32)
        warm = initial_rate * (step + 1.0) / max(warmup, 1)
        frac = (step - warmup) / decay_span
        decayed = jnp.maximum(initial_rate * (1.0 - frac), 0.0)
        if warmup == 0:
            return decayed
        return jnp.where(step < warmup, warm, decayed)

    return Schedule(fn)


@registry.schedules("linear.v1")
def linear(initial_rate: float, final_rate: float, total_steps: int) -> Schedule:
    import jax.numpy as jnp

    span = max(int(total_steps), 1)

    def fn(step):
        frac = jnp.minimum(jnp.asarray(step, jnp.float32) / span, 1.0)
        return initial_rate + (final_rate - initial_rate) * frac

    return Schedule(fn)


@registry.schedules("cosine.v1")
def cosine(initial_rate: float, total_steps: int, final_scale: float = 0.0) -> Schedule:
    import jax.numpy as jnp

    span = max(int(total_steps), 1)

    def fn(step):
        frac = jnp.minimum(jnp.asarray(step, jnp.float32) / span, 1.0)
        return initial_rate * (
            final_scale + (1 - final_scale) * 0.5 * (1 + jnp.cos(jnp.pi * frac))
        )

    return Schedule(fn)


class OptimizerWrapper:
    """optax transformation + framework metadata.

    ``use_averages`` signals the loop to keep a running mean of params and
    evaluate/checkpoint with it (thinc Adam's averages semantics — the
    reference's optimizer is constructed from config with use_averages and
    spacy evaluates under ``use_params(optimizer.averages)``).

    ``fusable`` (set by the Adam.v1 / RAdam.v1 factories) records the
    chain's hyperparameters so :func:`fuse_optimizer` can rebuild it as a
    single fused traversal (ops/fused_update.py — the ``[training]
    fused_update`` knob). ``applies_updates`` marks a wrapper whose
    ``update`` returns NEW PARAMS directly (apply folded in); the train
    step checks it before running its own ``optax.apply_updates``.
    """

    def __init__(self, tx: optax.GradientTransformation, use_averages: bool = False):
        self.tx = tx
        self.use_averages = use_averages
        self.fusable: Optional[dict] = None
        self.applies_updates = False

    def init(self, params):
        return self.tx.init(params)

    def update(self, grads, state, params=None, **extra):
        # ``extra``: the fused transformation's ``shadow`` (parallel/step.py)
        return self.tx.update(grads, state, params, **extra)


def fuse_optimizer(tx) -> Optional["OptimizerWrapper"]:
    """Rebuild a fusable optimizer as a single-traversal fused update.

    Returns None when ``tx`` is not fusable — an optimizer other than
    Adam.v1/RAdam.v1, or one wrapped by ``optax.masked`` for frozen
    components (``mask_frozen`` drops the metadata, so frozen runs keep
    the reference chain). The fused state structure is identical to the
    chain's (init delegates), so checkpoints survive knob flips.
    """
    meta = getattr(tx, "fusable", None)
    if not meta:
        return None
    from ..ops import fused_update as _fu

    fused = _fu.make_fused_transformation(reference_tx=tx.tx, **meta)
    out = OptimizerWrapper(fused, use_averages=tx.use_averages)
    out.applies_updates = True
    return out


def mask_frozen(tx, params):
    """Wrap a transformation with optax.masked so leaves under a dict key
    starting with "frozen_" (e.g. static-vector tables) get NO updates, NO
    weight decay, and NO optimizer-state moments."""

    def trainable_tree(tree):
        def rec(node, frozen):
            if isinstance(node, dict):
                return {
                    k: rec(v, frozen or str(k).startswith("frozen_"))
                    for k, v in node.items()
                }
            return not frozen

        return rec(tree, False)

    mask = trainable_tree(params)
    if all(jax.tree_util.tree_leaves(mask)):
        return tx  # nothing frozen: keep the plain transformation
    inner = tx.tx if isinstance(tx, OptimizerWrapper) else tx
    masked = optax.masked(inner, mask)
    if isinstance(tx, OptimizerWrapper):
        return OptimizerWrapper(masked, use_averages=tx.use_averages)
    return masked


@registry.optimizers("Adam.v1")
def Adam(
    learn_rate: ScheduleLike = 0.001,
    beta1: float = 0.9,
    beta2: float = 0.999,
    eps: float = 1e-8,
    L2: float = 0.0,
    grad_clip: float = 1.0,
    L2_is_weight_decay: bool = True,
    use_averages: bool = False,
) -> OptimizerWrapper:
    lr_fn = as_schedule_fn(learn_rate)
    chain = []
    if grad_clip and grad_clip > 0:
        chain.append(optax.clip_by_global_norm(grad_clip))
    if L2 and not L2_is_weight_decay:
        chain.append(optax.add_decayed_weights(L2))  # classic L2 into grads
    adam_idx = len(chain)
    chain.append(optax.scale_by_adam(b1=beta1, b2=beta2, eps=eps))
    if L2 and L2_is_weight_decay:
        chain.append(optax.add_decayed_weights(L2))
    chain.append(optax.scale_by_learning_rate(lr_fn))
    out = OptimizerWrapper(optax.chain(*chain), use_averages=use_averages)
    out.fusable = dict(
        kind="adam", lr_fn=lr_fn, b1=beta1, b2=beta2, eps=eps,
        grad_clip=grad_clip if grad_clip and grad_clip > 0 else 0.0,
        l2_grad=L2 if (L2 and not L2_is_weight_decay) else 0.0,
        l2_decay=L2 if (L2 and L2_is_weight_decay) else 0.0,
        adam_idx=adam_idx, sched_idx=len(chain) - 1,
    )
    return out


@registry.optimizers("SGD.v1")
def SGD(
    learn_rate: ScheduleLike = 0.001, L2: float = 0.0, grad_clip: float = 1.0
) -> optax.GradientTransformation:
    lr_fn = as_schedule_fn(learn_rate)
    chain = []
    if grad_clip and grad_clip > 0:
        chain.append(optax.clip_by_global_norm(grad_clip))
    if L2:
        chain.append(optax.add_decayed_weights(L2))
    chain.append(optax.scale_by_learning_rate(lr_fn))
    return optax.chain(*chain)


@registry.optimizers("RAdam.v1")
def RAdam(
    learn_rate: ScheduleLike = 0.001,
    beta1: float = 0.9,
    beta2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 0.0,
    grad_clip: float = 1.0,
) -> OptimizerWrapper:
    lr_fn = as_schedule_fn(learn_rate)
    chain = []
    if grad_clip and grad_clip > 0:
        chain.append(optax.clip_by_global_norm(grad_clip))
    adam_idx = len(chain)
    chain.append(optax.scale_by_radam(b1=beta1, b2=beta2, eps=eps))
    if weight_decay:
        chain.append(optax.add_decayed_weights(weight_decay))
    chain.append(optax.scale_by_learning_rate(lr_fn))
    out = OptimizerWrapper(optax.chain(*chain))
    out.fusable = dict(
        kind="radam", lr_fn=lr_fn, b1=beta1, b2=beta2, eps=eps,
        grad_clip=grad_clip if grad_clip and grad_clip > 0 else 0.0,
        l2_grad=0.0, l2_decay=weight_decay or 0.0,
        adam_idx=adam_idx, sched_idx=len(chain) - 1,
    )
    return out
