"""Plain reference of the ``sm`` trunk: float32 ``jax.numpy`` under
``default_matmul_precision("highest")``, no kernels.

Written from the architecture, not from ``spacy_ray_tpu/models``: spaCy's
HashEmbedCNN.v2 as its component defaults give it: MultiHashEmbed (four hashed
tables, four rows summed per token and table, concatenated, a 3-piece maxout
to the width, LN), then depth x residual[window-1 concatenation of the
neighbours (zeros past the ends and over padding) -> 3-piece maxout -> LN]. It
starts at the table lookup: the program's hashing gives the row ids.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# max over real positions of |system - reference|, relative to the largest
# |reference| entry. The configuration stores and computes in float32, but on
# a TPU XLA's default precision rounds a float32 matmul's operands to bfloat16
# (PR 21, finding 5): as trained, five matmuls deep, the trunk is 2.5e-3 to
# 4.2e-3 from this reference (chip, PR 22), which is what bfloat16 compute
# gives, so no bound on THAT number can tell float32 from bfloat16. The
# comparison that decides is therefore made with the system's forward under
# ``default_matmul_precision("highest")`` as well: the program's own code, its
# kernel included, then has to be float32 throughout (2.5e-7 to 3.5e-7 on the
# chip, PR 22; 4e-7 on the CPU), and a cast to bfloat16 anywhere in it (1e-3
# and more) fails TOLERANCE, which is the CPU's. The forward at the default
# precision is run too and held to TOLERANCE_AS_TRAINED, 2.4 times the largest
# measured: the platform's rounding, reported, not a fault of the program.
TOLERANCE = 2e-5
TOLERANCE_F32 = 2e-5
TOLERANCE_AS_TRAINED = 1e-2
SYSTEM_MATMUL_PRECISION = "highest"
COMPUTE_DTYPE_ON_TPU = "float32 (held at matmul precision highest; as trained, XLA's default rounds operands to bfloat16)"


def _layer_norm(x, g, b, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _maxout(x, p):
    n_out, pieces = p["b"].shape
    h = (x @ p["W"]).reshape(x.shape[:-1] + (n_out, pieces)) + p["b"]
    return h.max(-1)


def forward(params, ids, mask, n_heads=None):
    """``params``: the trunk's float32 tree; ``ids``: per table ``[B, T, 4]``
    row ids; ``mask``: ``[B, T]`` bool. Returns ``[B, T, D]`` float32."""
    params = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)
    m = mask[..., None].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        emb = params["0_multi_hash_embed"]
        tables = sorted(emb["0_embeds"])
        x = jnp.concatenate(
            [emb["0_embeds"][name]["E"][ids[i]].sum(-2) * m
             for i, name in enumerate(tables)], -1)
        x = _layer_norm(_maxout(x, emb["1_mix"]), emb["2_norm"]["g"], emb["2_norm"]["b"])
        enc = params["1_maxout_window_encoder"]
        for name in sorted(enc):  # "0_res_0" .. "3_res_3"
            p = enc[name]["inner"]
            z = x * m
            left = jnp.pad(z[:, :-1], ((0, 0), (1, 0), (0, 0)))
            right = jnp.pad(z[:, 1:], ((0, 0), (0, 1), (0, 0)))
            h = _maxout(jnp.concatenate([left, z, right], -1), p["1_maxout"])
            x = x + _layer_norm(h, p["2_norm"]["g"], p["2_norm"]["b"])
    return x * m
