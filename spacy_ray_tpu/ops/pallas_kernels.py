"""Pallas TPU kernels for ops XLA fuses poorly (SURVEY.md §7.1: "pallas only
where profiling shows XLA fusion fails (likely: ragged gather for hash
embeds)").

``hash_embed_lookup``: the HashEmbed inner op — gather 4 rows per token from
the embedding table and sum them. The XLA lowering materializes a
[tokens, 4, width] gather intermediate in HBM; this kernel keeps the table
resident in VMEM (typical tables: 2000 x 96 fp32 = 768KB, well under the
~16MB budget), streams id blocks through SMEM (bounded at TOKEN_BLOCK*16B
regardless of batch shape), and accumulates rows in-register.

Differentiation: pallas_call has no automatic VJP, so the kernel carries a
``jax.custom_vjp`` whose backward is the standard scatter-add of the output
cotangent into the table rows (a jnp ``.at[ids].add`` — XLA lowers this
well); the probe validates BOTH forward and gradient numerics before
enabling.

Safety: enabled only by a one-time startup probe (ops/probe.py: compile +
numeric check on the current backend). Off a TPU the jnp path is the
default; on a TPU a probe that fails raises. Force with SRT_PALLAS=1/0.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..names import KERNEL_HASH_EMBED
from . import probe as _probe

TOKEN_BLOCK = 256
# bytes of VMEM we allow the resident table. Compiled for v5e (libtpu
# 0.0.34) the kernel is accepted with 8 MiB tables at widths 64/96/128/256
# (lane padding included) and well beyond; the cap keeps room for the rest
VMEM_TABLE_BUDGET = 8 * 1024 * 1024


def _reference_lookup(table: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """jnp fallback: [rows, D], [N, 4] -> [N, D]."""
    return jnp.sum(jnp.take(table, ids, axis=0), axis=-2)


def _table_grad(ids: jnp.ndarray, ct: jnp.ndarray, rows: int) -> jnp.ndarray:
    """Backward of the gather-sum: scatter-add cotangent into table rows.

    ids [N, 4], ct [N, D] -> [rows, D].
    """
    updates = jnp.broadcast_to(ct[:, None, :], (ct.shape[0], 4, ct.shape[1]))
    zeros = jnp.zeros((rows, ct.shape[1]), ct.dtype)
    return zeros.at[ids].add(updates)


def _kernel(ids_ref, table_ref, out_ref):
    """One grid step: TOKEN_BLOCK tokens; ids block lives in SMEM."""
    import jax.lax as lax

    def body(t, _):
        r0 = ids_ref[t, 0]
        r1 = ids_ref[t, 1]
        r2 = ids_ref[t, 2]
        r3 = ids_ref[t, 3]
        out_ref[t, :] = (
            table_ref[r0, :] + table_ref[r1, :] + table_ref[r2, :] + table_ref[r3, :]
        )
        return 0

    lax.fori_loop(0, TOKEN_BLOCK, body, 0)


def _pallas_lookup_raw(
    table: jnp.ndarray, ids: jnp.ndarray, interpret: bool = False
) -> jnp.ndarray:
    """[rows, D] fp32, [N, 4] int32 -> [N, D]. N must be a TOKEN_BLOCK multiple."""
    n = ids.shape[0]
    D = table.shape[1]
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((n, D), table.dtype),
        grid=(n // TOKEN_BLOCK,),
        in_specs=[
            # per-step id block in SMEM: bounded regardless of batch shape
            pl.BlockSpec((TOKEN_BLOCK, 4), lambda i: (i, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),  # whole table resident
        ],
        out_specs=pl.BlockSpec(
            (TOKEN_BLOCK, D), lambda i: (i, 0), memory_space=pltpu.VMEM
        ),
        interpret=interpret,
        name=KERNEL_HASH_EMBED,
    )(ids, table)


@jax.custom_vjp
def _pallas_lookup(table: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    return _pallas_lookup_raw(table, ids)


def _pallas_lookup_fwd(table, ids):
    return _pallas_lookup_raw(table, ids), (ids, table.shape[0])


def _pallas_lookup_bwd(res, ct):
    ids, rows = res
    return _table_grad(ids, ct, rows), None


_pallas_lookup.defvjp(_pallas_lookup_fwd, _pallas_lookup_bwd)


def _probe_check() -> Optional[str]:
    """Forward and table gradient against the jnp gather-sum, at the sm
    pipeline's table shape (2000 x 96) with repeated ids in play."""
    table = jax.random.normal(jax.random.PRNGKey(0), (2000, 96), jnp.float32)
    ids = jax.random.randint(
        jax.random.PRNGKey(1), (2 * TOKEN_BLOCK, 4), 0, 2000
    ).astype(jnp.int32)
    got = jax.jit(_pallas_lookup)(table, ids)
    bad = _probe.mismatch(
        "forward", got, _reference_lookup(table, ids), atol=1e-5
    )
    if bad:
        return bad
    g_got = jax.grad(lambda t: jnp.sum(jnp.sin(_pallas_lookup(t, ids))))(table)
    g_want = jax.grad(lambda t: jnp.sum(jnp.sin(_reference_lookup(t, ids))))(table)
    return _probe.mismatch("table grad", g_got, g_want, atol=1e-4)


GATE = _probe.Gate(
    "hash-embed lookup", "SRT_PALLAS", _probe_check,
    unprobed="no hash-embed lookup ran in this process",
)


def pallas_enabled() -> bool:
    """One-time probe: compile + numerically validate forward AND gradient
    on the default backend; cache the verdict."""
    return GATE.enabled()


def hash_embed_status() -> str:
    """What the hash-embed lookup did in this process, in words: the paths
    :func:`hash_embed_lookup` took in the programs traced so far, else the
    probe's verdict."""
    return GATE.status()


# HBM budget for the one-hot counts operand ([tokens, rows] elements) —
# beyond it the plain gather's [tokens, 4, D] intermediate is cheaper
ONEHOT_LOOKUP_MAX_BYTES = 64 * 1024 * 1024


def hash_embed_lookup(table: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """Gather-sum 4 rows per key: table [rows, D], ids [..., 4] -> [..., D].

    Uses the pallas kernel when the startup probe enabled it, the table
    fits the VMEM budget and the program runs on one device (a kernel has
    no partitioning rule: under a multi-device mesh the jnp paths below
    partition cleanly); an armed kernel that gives way notes why on
    ``GATE``. On TPU without the kernel, small tables use a
    one-hot count-matrix matmul instead of the gather (TPU gathers
    serialize; summing the 4 one-hots gives a count row, and counts @
    table == the multiplicity-weighted row sum). Plain jnp gather
    otherwise (CPU, big tables).
    """
    from ..parallel import context as pctx

    lead_shape = ids.shape[:-1]
    fits = table.dtype == jnp.float32 and table.nbytes <= VMEM_TABLE_BUDGET
    if not pallas_enabled():
        pass  # the probe's verdict says why
    elif not pctx.single_device():
        GATE.took("xla (kernel gated off a multi-device mesh)")
    elif not fits:
        GATE.took(
            f"xla (table {table.shape[0]}x{table.shape[1]} {table.dtype} "
            "is outside the kernel's f32 VMEM budget)"
        )
    else:
        GATE.took(_probe.active())
        flat_ids = ids.reshape(-1, 4).astype(jnp.int32)
        n = flat_ids.shape[0]
        pad = (-n) % TOKEN_BLOCK
        if pad:
            flat_ids = jnp.pad(flat_ids, ((0, pad), (0, 0)))
        out = _pallas_lookup(table, flat_ids)
        if pad:
            out = out[:n]
        return out.reshape(*lead_shape, table.shape[1])
    counts_bytes = (ids.size // 4) * table.shape[0] * table.dtype.itemsize
    if (
        jax.default_backend() == "tpu"
        and counts_bytes <= ONEHOT_LOOKUP_MAX_BYTES
    ):
        counts = jnp.sum(
            jax.nn.one_hot(ids.astype(jnp.int32), table.shape[0],
                           dtype=table.dtype),
            axis=-2,
        )  # [..., rows]
        return counts @ table
    return _reference_lookup(table, ids.astype(jnp.int32))
