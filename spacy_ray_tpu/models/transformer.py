"""Transformer trunk: RoBERTa-base-shape encoder, TPU-first.

Capability parity with the reference ecosystem's shared transformer backbone
(en_core_web_trf: RoBERTa-base feeding tagger/parser/NER via listeners —
BASELINE.json config #4; the reference trains it through the same loop,
worker.py:91/176-189). Differences, deliberate and TPU-native:

* Pretrained HF checkpoint loading is gated (zero-egress environment);
  the trunk trains from scratch. Sub-word information comes from the
  MultiHashEmbed featurizer (NORM/PREFIX/SUFFIX/SHAPE) instead of BPE
  wordpieces, so there is no wordpiece↔token alignment problem at all —
  one vector per token throughout.
* bfloat16 matmuls on the MXU, fp32 layernorm/softmax accumulation,
  fp32 params.
* Attention on a single chip uses the pallas flash kernel
  (ops/flash_attention.py, probe-gated; ``jax.nn.dot_product_attention``
  fallback); with a ``context`` mesh axis the same layer switches to ring
  attention over ICI (parallel/ring_attention.py, SURVEY.md §5.7 —
  first-class here although the reference has none).
* Tensor parallelism: head and FFN dims carry sharding constraints over
  the ``model`` mesh axis when TP is enabled (parallel/context.py).
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..names import SCOPE_TRUNK
from ..registry import registry
from ..ops import ops as O
from ..types import Padded, TokenBatch
from ..parallel import context as pctx
from .core import Context, Model, glorot_uniform, normal_init
from .shadow import (  # noqa: F401  (re-exported: the names' old home)
    INT8_UNSUPPORTED_LEAF_NAMES,
    SHADOW_LEAF_NAMES,
    TRUNK_F32_LEAF_NAMES,
    _resolve_compute_dtype,
    build_param_shadow,
    int8_unsupported_leaves,
    pipeline_compute_dtype,
    pipeline_shadow_dtype,
    register_trunk_leaves,
    shadow_coverage,
)
from .tok2vec import MultiHashEmbed, ATTRS


def _maybe_shard(x: jnp.ndarray, spec: P) -> jnp.ndarray:
    """Apply a sharding constraint when a mesh is active (no-op otherwise;
    axes of size 1 in the mesh make the constraint a no-op too)."""
    mesh = pctx.current_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, spec)
    )


def transformer_layer_params(rng, width: int, ffn: int, n_experts: int = 0):
    r = jax.random.split(rng, 6)
    scale = 0.02
    params = {
        "qkv_W": normal_init(r[0], (width, 3 * width), scale),
        "qkv_b": jnp.zeros((3 * width,)),
        "o_W": normal_init(r[1], (width, width), scale),
        "o_b": jnp.zeros((width,)),
        "ln1_g": jnp.ones((width,)),
        "ln1_b": jnp.zeros((width,)),
        "ln2_g": jnp.ones((width,)),
        "ln2_b": jnp.zeros((width,)),
    }
    if n_experts > 0:
        # mixture-of-experts FFN (switch-style): E expert FFNs + a router
        params.update(
            router_W=normal_init(r[4], (width, n_experts), scale),
            e_W1=normal_init(r[2], (n_experts, width, ffn), scale),
            e_b1=jnp.zeros((n_experts, ffn)),
            e_W2=normal_init(r[3], (n_experts, ffn, width), scale),
            e_b2=jnp.zeros((n_experts, width)),
        )
    else:
        params.update(
            ffn_W1=normal_init(r[2], (width, ffn), scale),
            ffn_b1=jnp.zeros((ffn,)),
            ffn_W2=normal_init(r[3], (ffn, width), scale),
            ffn_b2=jnp.zeros((width,)),
        )
    return params


def _moe_ffn(p, h: jnp.ndarray, token_mask: jnp.ndarray, *,
             capacity_factor: float, compute_dtype):
    """Switch-transformer top-1 MoE FFN over flattened tokens.

    h [N, D] (post-LN), token_mask [N] bool. Experts are EXPERT-PARALLEL:
    the leading E dim of the dispatched activations carries a sharding
    constraint over the ``model`` mesh axis, so GSPMD places each expert's
    FFN on its own device group and inserts the all_to_alls (SURVEY.md
    §2.2 row EP — absent from the reference, first-class here).

    Returns (out [N, D] fp32, aux load-balancing loss scalar). Tokens
    routed past an expert's capacity are dropped (contribute zero), the
    standard switch behavior.
    """
    N, D = h.shape
    E = p["e_W1"].shape[0]
    F = p["e_W1"].shape[2]
    maskf = token_mask.astype(jnp.float32)

    logits = (h @ p["router_W"]).astype(jnp.float32)  # [N, E] fp32 routing
    probs = jax.nn.softmax(logits, axis=-1)
    idx = jnp.argmax(probs, axis=-1)  # [N]
    gate = jnp.take_along_axis(probs, idx[:, None], axis=-1)[:, 0]  # [N]

    onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32) * maskf[:, None]
    capacity = max(int(capacity_factor * N / max(E, 1)), 1)
    # arrival position of each token in its expert's queue
    pos = jnp.cumsum(onehot, axis=0) - onehot  # [N, E]
    pos_tok = jnp.sum(pos * onehot, axis=-1)  # [N]
    keep = (pos_tok < capacity) & token_mask
    disp = onehot * keep.astype(jnp.float32)[:, None]  # [N, E]
    pos_oh = jax.nn.one_hot(pos_tok.astype(jnp.int32), capacity, dtype=jnp.float32)
    dispatch = (disp[:, :, None] * pos_oh[:, None, :]).astype(compute_dtype)  # [N, E, C]

    h16 = h.astype(compute_dtype)
    x_e = jnp.einsum("nec,nd->ecd", dispatch, h16)  # [E, C, D]
    x_e = _maybe_shard(x_e, P("model", None, None))
    inner = jnp.einsum("ecd,edf->ecf", x_e, p["e_W1"].astype(compute_dtype))
    inner = inner + p["e_b1"].astype(compute_dtype)[:, None, :]
    inner = _maybe_shard(inner, P("model", None, None))
    inner = O.gelu(inner)
    y_e = jnp.einsum("ecf,efd->ecd", inner, p["e_W2"].astype(compute_dtype))
    y_e = y_e + p["e_b2"].astype(compute_dtype)[:, None, :]
    y = jnp.einsum("nec,ecd->nd", dispatch, y_e).astype(jnp.float32)
    y = y * gate[:, None]

    # switch load-balancing loss: E * sum_e fraction_routed_e * mean_prob_e
    denom = jnp.maximum(jnp.sum(maskf), 1.0)
    frac = jnp.sum(onehot, axis=0) / denom  # [E]
    mean_prob = jnp.sum(probs * maskf[:, None], axis=0) / denom  # [E]
    aux = jnp.float32(E) * jnp.sum(frac * mean_prob)
    return y, aux


# The leaf-name sets of the bf16 shadow live in models/shadow.py (one
# definition for every trunk); this trunk registers its own there. Shadow:
# every weight/bias the layer stack casts to the compute dtype each step
# (matmul operands + the biases added to matmul outputs). f32 BY DESIGN: LN
# params and the router (they feed fp32 ops); embeddings/positions are
# consumed in f32 by the embed path and are no layer leaves.
register_trunk_leaves(
    shadow=(
        "qkv_W", "qkv_b", "o_W", "o_b",
        "ffn_W1", "ffn_b1", "ffn_W2", "ffn_b2",
        "e_W1", "e_b1", "e_W2", "e_b2",
    ),
    f32=("ln1_g", "ln1_b", "ln2_g", "ln2_b", "router_W"),
    # the MoE expert weights flow through einsum contractions the int8
    # kernel does not implement, and an "int8" label over a trunk whose
    # parameter mass stays f32 would be a false claim (the overlay REFUSES
    # MoE trunks instead; test-enforced)
    int8_unsupported=("e_W1", "e_W2"),
)

# Leaves the int8 weight-only serving overlay quantizes: the DENSE 2-D
# matmul weights (the bandwidth-bound operands a small serving batch
# re-streams from HBM every dispatch). Biases stay f32 (weight-only).
INT8_LEAF_NAMES = frozenset({"qkv_W", "o_W", "ffn_W1", "ffn_W2"})


def build_int8_overlay(params) -> "Tuple[Any, int]":
    """The int8 weight-only serving overlay: a copy of ``params`` where
    every f32 INT8_LEAF_NAMES leaf under a ``layer_i`` dict is replaced
    by ``{"q8": int8 [K, N], "scale": f32 [N]}`` (per-output-channel
    symmetric quantization, ops/int8_matmul.py). Everything else — LNs,
    biases, embeddings, heads — is the SAME array object as the master
    tree (no copies). Returns ``(tree, n_quantized)``.

    The layer forward consumes these dict leaves through ``_wdot``; the
    dict structure is part of the jit trace, so a hot-swap that
    re-quantizes a new generation (same structure, same dtypes) reuses
    every warmed program — zero post-swap compiles, test-enforced."""
    from ..ops.int8_matmul import quantize_int8

    n = 0

    def rec(node, in_layer):
        nonlocal n
        out = {}
        for k, v in node.items():
            if isinstance(v, dict):
                out[k] = rec(v, in_layer or str(k).startswith("layer_"))
            elif (
                in_layer
                and k in INT8_LEAF_NAMES
                and jnp.asarray(v).dtype == jnp.float32
            ):
                q8, scale = quantize_int8(v)
                out[k] = {"q8": q8, "scale": scale}
                n += 1
            else:
                out[k] = v
        return out

    return rec(params, False), n


def _wdot(h: jnp.ndarray, leaf, compute_dtype) -> jnp.ndarray:
    """Trunk weight matmul that understands the two leaf encodings: a
    plain array (cast to the compute dtype — the training/bf16 path) or
    an int8 serving-overlay dict (``{"q8", "scale"}`` — dequantize-in-
    kernel pallas matmul, f32 accumulation, downcast to the compute
    dtype so the surrounding arithmetic is dtype-identical either way).
    The isinstance check runs at trace time: each param-tree structure
    compiles once, exactly like a dtype change would."""
    if isinstance(leaf, dict):
        from ..ops.int8_matmul import int8_matmul

        return int8_matmul(h, leaf["q8"], leaf["scale"]).astype(compute_dtype)
    return h @ leaf.astype(compute_dtype)


def apply_transformer_layer(
    p,
    X: jnp.ndarray,
    mask: jnp.ndarray,
    rng: Optional[jax.Array],
    *,
    n_heads: int,
    dropout: float,
    train: bool,
    n_experts: int = 0,
    capacity_factor: float = 1.25,
    compute_dtype=jnp.bfloat16,
):
    """Pre-LN encoder layer. X [B, T, D] fp32, mask [B, T] bool.

    Returns (X, aux) — aux is the MoE router's load-balancing loss (0.0
    for the dense FFN). Keyword args are static (bound with
    functools.partial before jax.checkpoint, so the checkpointed callable
    takes only pytrees).
    """
    B, T, D = X.shape
    H = n_heads
    Dh = D // H
    use_dropout = train and rng is not None and dropout > 0
    if use_dropout:
        rng1, rng2 = jax.random.split(rng)

    # ---- attention ----
    h = O.layer_norm(X, p["ln1_g"], p["ln1_b"])
    h16 = h.astype(compute_dtype)
    qkv = _wdot(h16, p["qkv_W"], compute_dtype) + p["qkv_b"].astype(compute_dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)

    def heads(x):
        return x.reshape(B, T, H, Dh)

    q, k, v = heads(q), heads(k), heads(v)
    # full-layout constraints (batch over data, seq over context, heads over
    # model) — partial specs make the partitioner re-materialize
    qkv_spec = P("data", "context", "model", None)
    q = _maybe_shard(q, qkv_spec)
    k = _maybe_shard(k, qkv_spec)
    v = _maybe_shard(v, qkv_spec)

    if pctx.context_parallel_active():
        from ..parallel.ring_attention import ring_attention

        attn = ring_attention(q, k, v, mask)
    else:
        # pallas flash kernel when the startup probe enabled it (TPU),
        # XLA's fused dot_product_attention otherwise
        from ..ops.flash_attention import attention

        attn = attention(q, k, v, mask)
    attn = attn.reshape(B, T, D)
    out = _wdot(attn, p["o_W"], compute_dtype) + p["o_b"].astype(compute_dtype)
    out = out.astype(jnp.float32)
    if use_dropout:
        out = O.dropout(rng1, out, dropout, True)
    X = X + out

    # ---- ffn (dense or mixture-of-experts) ----
    h = O.layer_norm(X, p["ln2_g"], p["ln2_b"])
    aux = jnp.float32(0.0)
    if n_experts > 0:
        out2d, aux = _moe_ffn(
            p,
            h.reshape(B * T, D),
            mask.reshape(B * T),
            capacity_factor=capacity_factor,
            compute_dtype=compute_dtype,
        )
        out = out2d.reshape(B, T, D)
    else:
        h16 = h.astype(compute_dtype)
        inner = _wdot(h16, p["ffn_W1"], compute_dtype) + p["ffn_b1"].astype(compute_dtype)
        inner = _maybe_shard(inner, P("data", "context", "model"))
        inner = O.gelu(inner)
        out = _wdot(inner, p["ffn_W2"], compute_dtype) + p["ffn_b2"].astype(compute_dtype)
        out = out.astype(jnp.float32)
    if use_dropout:
        out = O.dropout(rng2, out, dropout, True)
    return X + out, aux


def _stack_layer_params(params, depth: int):
    """Stack the per-layer param dicts into leaves with a leading [depth]
    dim. Storage stays per-layer ("layer_i" keys — the checkpoint and
    pretrained-loader schema); stacking happens at apply time, costing one
    HBM copy of the trunk per step (~0.3 ms for RoBERTa-base at HBM
    bandwidth — noise next to the step) in exchange for a compiled program
    with ONE layer body instead of `depth` copies."""
    import jax.tree_util as jtu

    return jtu.tree_map(
        lambda *xs: jnp.stack(xs), *[params[f"layer_{i}"] for i in range(depth)]
    )


def _scan_layer_stack(layer_fn, stacked, X, mask, key, depth: int):
    """Run the stacked layers as one lax.scan, accumulating the aux loss.
    Per-layer rng = fold_in(key, layer_index) — the SAME derivation the
    pipelined stage body uses, so the two paths stay in lockstep."""

    def body(carry, inp):
        x, aux_sum = carry
        lp, li = inp
        y, aux = layer_fn(lp, x, mask, jax.random.fold_in(key, li))
        return (y, aux_sum + aux), None

    (X, aux_total), _ = jax.lax.scan(
        body, (X, jnp.float32(0.0)), (stacked, jnp.arange(depth))
    )
    return X, aux_total


def _pipelined_layers(
    params, X, mask, ctx, layer_fn, *, depth: int, n_microbatches: int
):
    """Run the layer stack under GPipe pipeline parallelism
    (parallel/pipeline.py). Stacks the per-layer param dicts into leaves
    with a leading [depth] dim (sharded over 'pipe' by the pipeline), and
    splits the batch into microbatches along dim 0.

    The pipeline region is partial-manual (manual over `pipe` only), so the
    stage body keeps its automatic axes and TP constraints compose with PP
    — and ring attention nests as a second partial-manual region (manual
    over `context` only, parallel/ring_attention.py), so PP x CP works too.
    """
    from ..parallel import pipeline as ppl

    mesh = pctx.current_mesh()
    S = int(mesh.shape["pipe"])
    if depth % S != 0:
        raise ValueError(f"depth {depth} not divisible by {S} pipeline stages")
    B = X.shape[0]
    d = int(mesh.shape.get("data", 1))
    # each microbatch is sharded over the data axis, so M must divide B/d
    # (keeping every microbatch's size a multiple of d)
    per_data = max(B // d, 1)
    requested = n_microbatches or 2 * S
    M = min(requested, per_data)
    while M > 1 and per_data % M != 0:
        M -= 1
    if n_microbatches and M != n_microbatches:
        import warnings

        warnings.warn(
            f"pp_microbatches={n_microbatches} cannot divide the per-data-"
            f"shard batch ({per_data}); using {M} microbatches instead "
            f"(pipeline bubble {(S - 1) / (M + S - 1):.0%})",
            stacklevel=2,
        )
    stacked = _stack_layer_params(params, depth)
    mb = X.reshape(M, B // M, *X.shape[1:])
    mb_mask = mask.reshape(M, B // M, mask.shape[1])
    ctx, sub = ctx.split()
    rng = sub.rng if sub.rng is not None else jax.random.PRNGKey(0)
    layers_per_stage = depth // S

    def stage_fn(local_params, x, m, key):
        # this stage's layers, sequentially. Fold the stage index into the
        # key: without it every stage would reuse the same per-tick
        # dropout masks on different microbatches
        key = jax.random.fold_in(key, jax.lax.axis_index("pipe"))
        # the body keeps automatic data/model axes, so TP constraints
        # inside the layers still apply: the mesh stays active
        return _scan_layer_stack(
            layer_fn, local_params, x, m, key, layers_per_stage
        )

    out, aux_total = ppl.spmd_pipeline(stage_fn, stacked, mb, mb_mask, rng)
    return out.reshape(B, *X.shape[1:]), aux_total


@registry.architectures("spacy_ray_tpu.TransformerEncoder.v1")
def TransformerEncoder(
    width: int = 768,
    depth: int = 12,
    n_heads: int = 12,
    ffn_mult: int = 4,
    dropout: float = 0.1,
    max_len: int = 512,
    embed_size: int = 10000,
    remat: bool = True,
    remat_policy: str = "dots",
    compute_dtype: str = "auto",
    init_weights: Optional[str] = None,
    pp_microbatches: int = 0,
    n_experts: int = 0,
    expert_capacity_factor: float = 1.25,
    router_aux_weight: float = 0.01,
    scan_layers: bool = True,
) -> Model:
    """Hash-embed featurized transformer trunk (tok2vec-compatible output).

    ``n_experts > 0`` replaces each layer's dense FFN with a switch-style
    top-1 mixture of experts (expert-parallel over the ``model`` mesh
    axis); ``router_aux_weight`` scales the load-balancing loss added to
    training via the Context aux sink.

    ``compute_dtype``: matmul dtype for the attention/FFN blocks —
    "auto" (default) = bfloat16 on accelerators, float32 on CPU (bf16 is
    a cast-overhead-only cost there; see _resolve_compute_dtype);
    layernorm/softmax always accumulate in fp32 either way.

    ``remat=True`` wraps each layer in jax.checkpoint — rematerialize
    activations in backward to trade FLOPs for HBM (the standard TPU
    memory/bandwidth tradeoff for deep trunks). ``remat_policy`` picks
    WHAT is saved: "dots" (default) saves weight-matmul outputs and
    recomputes only cheap elementwise/norm/attention-score work — ~25%
    fewer backward FLOPs than full recompute for a modest HBM cost;
    "all_dots" additionally saves batched (attention) matmuls; "nothing"
    is full recompute (the pre-round-4 behavior, minimum memory).

    ``pp_microbatches``: microbatch count for pipeline parallelism; used
    only when the active mesh has a ``pipe`` axis > 1 (0 = auto: 2x the
    stage count, a reasonable bubble/memory tradeoff).

    ``init_weights``: path to a local .npz (native schema) or .safetensors
    (native or HuggingFace-encoder keys, remapped) checkpoint to start the
    trunk from — see models/pretrained.py for the key schema. Every tensor
    is shape-checked; keys absent from the file keep their random init.

    ``scan_layers=True`` runs the (homogeneous) layer stack as ONE
    ``lax.scan`` over stacked per-layer params instead of an unrolled
    Python loop: the compiled program contains one layer body instead of
    ``depth`` copies (~8x smaller HLO for RoBERTa-base — compile time and
    compile-server memory scale with program size). Per-layer dropout rng
    derives from fold_in(key, layer_index) on both paths.
    """
    if width % n_heads != 0:
        raise ValueError(f"width {width} not divisible by n_heads {n_heads}")
    ffn = width * ffn_mult
    embed = MultiHashEmbed(width=width, attrs=list(ATTRS),
                           rows=[embed_size] + [embed_size // 2] * 3)

    def init_fn(rng):
        rngs = jax.random.split(rng, depth + 2)
        params = {
            "embed": embed.init(rngs[0]),
            "pos": normal_init(rngs[1], (max_len, width), 0.02),
            "ln_f_g": jnp.ones((width,)),
            "ln_f_b": jnp.zeros((width,)),
        }
        for i in range(depth):
            params[f"layer_{i}"] = transformer_layer_params(
                rngs[i + 2], width, ffn, n_experts=n_experts
            )
        if init_weights:
            from .pretrained import load_trunk_weights

            params = load_trunk_weights(params, init_weights)
        return params

    def apply_fn(params, batch: TokenBatch, ctx: Context) -> Padded:
        emb: Padded = embed.apply(params["embed"], batch, ctx)
        with jax.named_scope(SCOPE_TRUNK):
            return encode(params, emb, ctx)

    def encode(params, emb: Padded, ctx: Context) -> Padded:
        T = emb.X.shape[1]
        if T > max_len:
            import warnings

            warnings.warn(
                f"sequence length {T} exceeds transformer max_len {max_len}; "
                "positions beyond max_len reuse the last positional embedding "
                "(set a larger max_len or bound doc length via corpus "
                "max_length)",
                stacklevel=2,
            )
        pos_idx = jnp.minimum(jnp.arange(T), params["pos"].shape[0] - 1)
        X = emb.X + params["pos"][pos_idx][None, :, :]
        mask = emb.mask
        if pctx.context_parallel_active():
            # sequence-parallel layout: T sharded over the context axis
            X = _maybe_shard(X, P("data", "context", None))
            mask = _maybe_shard(mask, P("data", "context"))

        from functools import partial as _partial

        layer_fn = _partial(
            apply_transformer_layer,
            n_heads=n_heads,
            dropout=ctx.dropout_rate(dropout),
            train=ctx.train,
            n_experts=n_experts,
            capacity_factor=expert_capacity_factor,
            compute_dtype=_resolve_compute_dtype(compute_dtype),
        )
        if remat:
            # checkpointed callable takes only pytree args (p, X, mask, rng)
            policies = {
                "nothing": None,  # full recompute
                "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                "all_dots": jax.checkpoint_policies.dots_saveable,
            }
            if remat_policy not in policies:
                raise ValueError(
                    f"remat_policy must be one of {sorted(policies)}, "
                    f"got {remat_policy!r}"
                )
            policy = policies[remat_policy]
            layer_fn = (
                jax.checkpoint(layer_fn, policy=policy)
                if policy is not None
                else jax.checkpoint(layer_fn)
            )
        if pctx.pipeline_active():
            X, aux_total = _pipelined_layers(
                params, X, mask, ctx, layer_fn, depth=depth,
                n_microbatches=pp_microbatches,
            )
        elif scan_layers and depth > 1:
            # one scanned layer body instead of `depth` unrolled copies —
            # same math, ~depth-x smaller compiled program
            ctx, sub = ctx.split()
            key = sub.rng if sub.rng is not None else jax.random.PRNGKey(0)
            X, aux_total = _scan_layer_stack(
                layer_fn, _stack_layer_params(params, depth), X, mask, key,
                depth,
            )
        else:
            aux_total = jnp.float32(0.0)
            for i in range(depth):
                ctx, sub = ctx.split()
                X, aux = layer_fn(params[f"layer_{i}"], X, mask, sub.rng)
                aux_total = aux_total + aux
        if n_experts > 0:
            ctx.add_aux_loss(jnp.float32(router_aux_weight) * aux_total)
        X = O.layer_norm(X, params["ln_f_g"], params["ln_f_b"])
        return Padded(X=X * mask[..., None].astype(X.dtype), mask=mask)

    return Model(
        "transformer_encoder",
        init_fn,
        apply_fn,
        dims={"nO": width, "depth": depth, "n_heads": n_heads},
        layers=[embed],
        # the bf16-shadow decision point (pipeline_shadow_dtype) resolves
        # this at loop-setup time — "auto" depends on the backend
        meta={"compute_dtype_name": compute_dtype},
    )


@registry.architectures("spacy-transformers.TransformerModel.v3")
def HFTransformerModel(
    name: str = "roberta-base",
    get_spans=None,
    tokenizer_config: Optional[dict] = None,
    transformer_config: Optional[dict] = None,
) -> Model:
    """Reference-ecosystem config compatibility (spacy-transformers'
    registered name). ``name`` must be a LOCAL path to a .safetensors or
    .npz checkpoint (this environment is zero-egress — hub names can't be
    downloaded); the encoder weights are remapped into the native RoBERTa-
    base-shape trunk via models/pretrained.py. A bare hub name raises with
    that guidance."""
    from pathlib import Path

    if not Path(name).exists():
        raise NotImplementedError(
            f"{name!r} is not a local file, and downloading HuggingFace "
            "checkpoints is impossible in this zero-egress environment. "
            "Point `name` at a local .safetensors/.npz checkpoint, or use "
            '@architectures "spacy_ray_tpu.TransformerEncoder.v1" with '
            "init_weights=<path> (same RoBERTa-base shape)."
        )
    cfg = dict(transformer_config or {})
    return TransformerEncoder(
        width=int(cfg.get("width", 768)),
        depth=int(cfg.get("depth", 12)),
        n_heads=int(cfg.get("n_heads", 12)),
        max_len=int(cfg.get("max_len", 512)),
        init_weights=name,
    )
