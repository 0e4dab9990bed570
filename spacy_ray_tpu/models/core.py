"""Functional model core: named layers over jnp pytrees.

Capability parity with the thinc ``Model`` tree the reference's param
plumbing walks (reference util.py:41-75 ``set_params_proxy`` /
``divide_params`` over ``model.walk()``; SURVEY.md §2.1). Design differences,
deliberately TPU-first:

* A model is a pair of pure functions ``init(rng) -> params`` and
  ``apply(params, x, ctx) -> y``; params are nested dicts of jnp arrays.
* Parameter identity is the **path string** in the params pytree
  ("embed/norm/b"), stable across processes — fixing the fragile per-process
  ``(node.id, name)`` identity of the reference (reference util.py:6,53-54;
  SURVEY.md §2.4 "Key identity is fragile").
* There is no mutable parameter server / proxy hook: distribution happens by
  sharding the params pytree under GSPMD, not by intercepting get_param
  (reference proxies.py:86-109 becomes a sharding annotation).
* Initialization takes explicit dimensions from the config (no lazy shape
  inference), so every shape is static under jit.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp


Params = Dict[str, Any]


@dataclass
class Context:
    """Per-call context threaded through apply: dropout rng, train flag,
    and an optional auxiliary-loss sink (``aux_losses``) that layers with
    regularizer terms (e.g. the MoE router's load-balancing loss) append
    to during tracing; the loss builder sums it into the total.

    ``dropout`` is the global training-time dropout override: when set,
    every dropout site uses it in place of its architecture-configured
    rate — the equivalent of spaCy's ``set_dropout_rate(model, drop)``
    call with ``[training] dropout`` before each update (reference
    worker.py:181 passes it into ``train_while_improving``). ``None``
    (the predict path and direct ``apply`` calls) keeps each layer's own
    configured rate.

    ``metrics`` is an optional sink for device counters a layer makes while
    it is traced (the routed trunk's assignments and loads); the loss
    builder hands them on in the step's ``metrics``, so they leave the
    device with the losses and cost no synchronisation of their own."""

    train: bool = False
    rng: Optional[jax.Array] = None
    aux_losses: Optional[list] = None
    dropout: Optional[float] = None
    metrics: Optional[dict] = None

    def split(self) -> Tuple["Context", "Context"]:
        if self.rng is None:
            return self, self
        r1, r2 = jax.random.split(self.rng)
        return (
            Context(self.train, r1, self.aux_losses, self.dropout, self.metrics),
            Context(self.train, r2, self.aux_losses, self.dropout, self.metrics),
        )

    def dropout_rate(self, configured: float) -> float:
        """The effective dropout rate at a site whose architecture default
        is ``configured`` (static Python float — resolved at trace time)."""
        return self.dropout if self.dropout is not None else configured

    def add_aux_loss(self, value) -> None:
        if self.aux_losses is not None:
            self.aux_losses.append(value)

    def add_metrics(self, values: dict) -> None:
        """Counters to add up: a key met twice is summed."""
        if self.metrics is not None:
            for key, value in values.items():
                self.metrics[key] = self.metrics[key] + value if key in self.metrics else value


@dataclass
class Model:
    """A named pure-function layer.

    ``init(rng) -> params``; ``apply(params, x, ctx) -> y``.
    ``dims`` records static dimensions ("nI", "nO", ...) for introspection
    and head wiring. ``layers`` are the children (for walk()).
    """

    name: str
    init_fn: Callable[[jax.Array], Params]
    apply_fn: Callable[[Params, Any, Context], Any]
    dims: Dict[str, int] = field(default_factory=dict)
    layers: List["Model"] = field(default_factory=list)
    meta: Dict[str, Any] = field(default_factory=dict)

    def init(self, rng: jax.Array) -> Params:
        return self.init_fn(rng)

    def apply(self, params: Params, x: Any, ctx: Optional[Context] = None) -> Any:
        return self.apply_fn(params, x, ctx or Context())

    def __call__(self, params: Params, x: Any, ctx: Optional[Context] = None) -> Any:
        return self.apply(params, x, ctx)

    def walk(self) -> Iterator["Model"]:
        """DFS over the model tree, like thinc's ``Model.walk()``
        (reference util.py:44, 62)."""
        yield self
        for layer in self.layers:
            yield from layer.walk()

    def get_dim(self, name: str) -> int:
        if name not in self.dims:
            raise KeyError(f"Model {self.name} has no dim {name!r}; has {self.dims}")
        return self.dims[name]


def prune_empty(params: Params) -> Params:
    """Drop empty sub-dicts (param-less layers) for a canonical pytree
    structure — save/load (npz) can't represent empty dicts, and optax
    states must structurally match params, so the canonical form never
    contains them. ``apply`` tolerates the missing keys via .get()."""
    if not isinstance(params, dict):
        return params
    out = {}
    for k, v in params.items():
        if isinstance(v, dict):
            pruned = prune_empty(v)
            if pruned:
                out[k] = pruned
        else:
            out[k] = v
    return out


def param_paths(params: Params, prefix: str = "") -> List[str]:
    """Flatten a params pytree into stable '/'-joined path strings."""
    out: List[str] = []
    if isinstance(params, dict):
        for k in sorted(params):
            sub = prefix + ("/" if prefix else "") + str(k)
            out.extend(param_paths(params[k], sub))
    else:
        out.append(prefix)
    return out


def count_params(params: Params) -> int:
    leaves = jax.tree_util.tree_leaves(params)
    return int(sum(x.size for x in leaves if hasattr(x, "size")))


# ----------------------------------------------------------------------
# Initializers
# ----------------------------------------------------------------------


def glorot_uniform(rng: jax.Array, shape: Tuple[int, ...], dtype=jnp.float32) -> jnp.ndarray:
    fan_in, fan_out = shape[0], shape[-1]
    limit = (6.0 / (fan_in + fan_out)) ** 0.5
    return jax.random.uniform(rng, shape, dtype, -limit, limit)


def normal_init(rng: jax.Array, shape: Tuple[int, ...], stddev: float, dtype=jnp.float32) -> jnp.ndarray:
    return jax.random.normal(rng, shape, dtype) * stddev


def zeros(shape: Tuple[int, ...], dtype=jnp.float32) -> jnp.ndarray:
    return jnp.zeros(shape, dtype)


def ones(shape: Tuple[int, ...], dtype=jnp.float32) -> jnp.ndarray:
    return jnp.ones(shape, dtype)


# ----------------------------------------------------------------------
# Combinators
# ----------------------------------------------------------------------


def _child_key(i: int, layer: Model) -> str:
    return f"{i}_{layer.name}"


def chain(*layers: Model, name: str = "chain") -> Model:
    """Feed-forward composition. Params keyed '{i}_{childname}'."""

    def init_fn(rng: jax.Array) -> Params:
        rngs = jax.random.split(rng, len(layers))
        return {
            _child_key(i, layer): layer.init(rngs[i]) for i, layer in enumerate(layers)
        }

    def apply_fn(params: Params, x: Any, ctx: Context) -> Any:
        for i, layer in enumerate(layers):
            ctx, sub = ctx.split()
            x = layer.apply(params.get(_child_key(i, layer), {}), x, sub)
        return x

    dims = {}
    if layers and "nI" in layers[0].dims:
        dims["nI"] = layers[0].dims["nI"]
    if layers and "nO" in layers[-1].dims:
        dims["nO"] = layers[-1].dims["nO"]
    return Model(name, init_fn, apply_fn, dims=dims, layers=list(layers))


def scoped(model: Model, scope: str) -> Model:
    """``model`` with ``jax.named_scope(scope)`` round its apply: the
    operations it traces (and their transposes in the backward pass) carry
    ``scope`` in their metadata, which is how a device trace is read by
    layer (``spacy_ray_tpu/names.py``). No operation is added."""
    inner = model.apply_fn

    def apply_fn(params: Params, x: Any, ctx: Context) -> Any:
        with jax.named_scope(scope):
            return inner(params, x, ctx)

    model.apply_fn = apply_fn
    return model


def residual(layer: Model, name: str = "residual") -> Model:
    def init_fn(rng: jax.Array) -> Params:
        return {"inner": layer.init(rng)}

    def apply_fn(params: Params, x: Any, ctx: Context) -> Any:
        out = layer.apply(params.get("inner", {}), x, ctx)
        # generic over raw arrays and Padded-style containers with .X
        if hasattr(out, "X") and hasattr(x, "X"):
            return type(out)(X=x.X + out.X, mask=out.mask)
        return x + out

    return Model(name, init_fn, apply_fn, dims=dict(layer.dims), layers=[layer])


def clone(layer_factory: Callable[[int], Model], n: int, name: str = "clone") -> Model:
    """n independent copies (distinct params), chained."""
    layers = [layer_factory(i) for i in range(n)]
    return chain(*layers, name=name)
