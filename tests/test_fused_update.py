"""Round-7 fixed-cost-floor contracts (ISSUE 5): fused optimizer update ==
optax reference, bf16 parameter shadow == cast-per-step forward,
steps_per_dispatch == K single dispatches, and the donation audit.

The equality discipline mirrors PERF.md's honesty rules: everything that
CAN be bitwise is asserted bitwise (fused-vs-optax under jit, the shadow
forward, multi-dispatch vs singles); the one thing that can't — the shadow
TRAJECTORY, where the baseline program elides a bf16 double-rounding in
its weight-grad matmuls — is pinned at a 1e-6 tolerance with the forward
still exact.
"""

import json
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import spacy_ray_tpu.ops.fused_update as fu
from spacy_ray_tpu.config import Config
from spacy_ray_tpu.models.transformer import (
    build_param_shadow,
    pipeline_shadow_dtype,
)
from spacy_ray_tpu.parallel.mesh import build_mesh
from spacy_ray_tpu.parallel.step import (
    make_train_step,
    overlay_shadow,
    place_batch,
    place_replicated,
    refresh_shadow,
    shard_opt_state,
)
from spacy_ray_tpu.pipeline.language import Pipeline
from spacy_ray_tpu.registry import registry
from spacy_ray_tpu.training import optimizers as O
from spacy_ray_tpu.training.loop import train, validate_training
from spacy_ray_tpu.util import synth_corpus, write_synth_jsonl


ROOT = Path(__file__).resolve().parent.parent


def _tree(seed=0):
    r = np.random.default_rng(seed)
    return {
        "a": jnp.asarray(r.standard_normal((64, 32)), jnp.float32),
        "b": {"w": jnp.asarray(r.standard_normal((128,)), jnp.float32),
              "c": jnp.asarray(r.standard_normal((8, 8)), jnp.float32)},
    }


def _leaves(t):
    return jax.tree_util.tree_leaves(t)


# ---------------------------------------------------------------- fused tx


@pytest.mark.parametrize(
    "factory,kw",
    [
        (O.Adam, dict(learn_rate=0.001)),  # default grad_clip=1.0, wd
        (O.Adam, dict(learn_rate=0.01, L2=0.02, grad_clip=0.5)),
        (O.Adam, dict(learn_rate=0.01, L2=0.02, L2_is_weight_decay=False,
                      grad_clip=0.0)),
        (O.RAdam, dict(learn_rate=0.003, weight_decay=0.01)),
    ],
)
def test_fused_matches_optax_bitwise(factory, kw):
    """The fused single-traversal update equals the reference optax chain
    BITWISE under jit (same expressions, same order — ops/fused_update.py
    mirrors the installed optax's formulas), params and state both."""
    tx = factory(**kw)
    fused = O.fuse_optimizer(tx)
    assert fused is not None and fused.applies_updates
    params = _tree()

    @jax.jit
    def step_ref(p, s, g):
        u, s = tx.update(g, s, p)
        return optax.apply_updates(p, u), s

    step_fused = jax.jit(lambda p, s, g: fused.update(g, s, p))
    s_ref, s_f = tx.init(params), fused.init(params)
    # identical state STRUCTURE: checkpoints survive knob flips
    assert jax.tree_util.tree_structure(s_ref) == jax.tree_util.tree_structure(s_f)
    p_ref, p_f = params, params
    for i in range(6):
        grads = jax.tree_util.tree_map(lambda p: p * 0.1 + 0.01 * i, params)
        p_ref, s_ref = step_ref(p_ref, s_ref, grads)
        p_f, s_f = step_fused(p_f, s_f, grads)
    for a, b in zip(_leaves((p_ref, s_ref)), _leaves((p_f, s_f))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fused_matches_optax_with_schedule():
    """Schedule counts live in the chain's ScaleByScheduleState: the fused
    update must read the PRE-increment count like optax does."""
    sched = registry.get("schedules", "warmup_linear.v1")(
        initial_rate=0.01, warmup_steps=3, total_steps=20
    )
    tx = O.Adam(learn_rate=sched)
    fused = O.fuse_optimizer(tx)
    params = _tree(1)

    @jax.jit
    def step_ref(p, s, g):
        u, s = tx.update(g, s, p)
        return optax.apply_updates(p, u), s

    step_fused = jax.jit(lambda p, s, g: fused.update(g, s, p))
    s_ref, s_f = tx.init(params), fused.init(params)
    p_ref, p_f = params, params
    for i in range(6):  # crosses the warmup boundary
        grads = jax.tree_util.tree_map(lambda p: p * 0.05, params)
        p_ref, s_ref = step_ref(p_ref, s_ref, grads)
        p_f, s_f = step_fused(p_f, s_f, grads)
    for a, b in zip(_leaves((p_ref, s_ref)), _leaves((p_f, s_f))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_frozen_masked_optimizer_is_not_fusable():
    """mask_frozen (frozen_ leaves) drops the fusable metadata — the loop's
    "auto" mode keeps the reference chain there."""
    tx = O.Adam(learn_rate=0.01)
    params = {"frozen_vectors": jnp.ones((4,)), "w": jnp.ones((4,))}
    masked = O.mask_frozen(tx, params)
    assert O.fuse_optimizer(masked) is None
    # nothing frozen: metadata survives
    assert O.fuse_optimizer(O.mask_frozen(tx, {"w": jnp.ones((4,))})) is not None


def test_pallas_kernel_matches_xla_math_interpret():
    """The pallas kernel (CPU interpret mode) reproduces the XLA leaf math
    — the same probe that gates the kernel on TPU at startup. Its leaf has
    three dimensions, a width that is no multiple of 128 and a last block
    that is not full."""
    assert fu._probe_kernel(interpret=True) is None
    blocking, why = fu.leaf_blocking((3, 16, 160), jnp.float32, 32 * 1024)
    assert (blocking, why) == ((48, 160, 32), "")  # 32 rows, then 16


_HYPER = fu.FusedHyper(
    kind="adam", b1=0.9, b2=0.999, eps=1e-8, grad_clip=1.0, l2_grad=0.0,
    l2_decay=0.01,
)
_SCAL = (2.3, 0.1, 0.001, -0.001, 6.0, 0.8)  # gnorm bc1 bc2 step ro rect


def _leaf_operands(shape, g_dtype, seed=3):
    r = jax.random.split(jax.random.PRNGKey(seed), 4)
    p = jax.random.normal(r[0], shape, jnp.float32)
    g = (jax.random.normal(r[1], shape, jnp.float32) * 0.1).astype(g_dtype)
    m = jax.random.normal(r[2], shape, jnp.float32) * 0.01
    v = jnp.abs(jax.random.normal(r[3], shape, jnp.float32)) * 0.01
    return p, g, m, v


@pytest.mark.parametrize(
    "shape,g_dtype,shadow_dtype,ragged",
    [
        ((16, 64, 384), jnp.float32, None, True),  # three dimensions, merged
        ((1000, 256), jnp.float32, None, True),  # 512 rows a block: 488 left
        ((300, 96), jnp.float32, None, False),  # sm's width: a full-width block
        ((96, 96), jnp.float32, None, False),  # under the floor, but walkable
        ((700, 576), jnp.float32, jnp.bfloat16, True),  # 4.5 x 128 lanes
        ((16, 64, 384), jnp.bfloat16, jnp.bfloat16, True),  # a shadowed leaf
        ((1000, 256), jnp.bfloat16, jnp.bfloat16, True),
        ((1000, 256), jnp.bfloat16, None, True),  # narrow gradient, no shadow
    ],
    ids=lambda x: "x".join(map(str, x)) if isinstance(x, tuple) else getattr(
        x, "__name__", str(x)),
)
def test_kernel_takes_a_leaf_where_it_lies(shape, g_dtype, shadow_dtype, ragged):
    """The kernel on a leaf in its own shape and dtypes (interpret mode)
    against ``_leaf_math`` on the same leaf, at the probe's tolerances; the
    shadow it writes is exactly the cast of the params it wrote."""
    (rows, _, block_rows), _ = fu.leaf_blocking(shape, jnp.float32)
    assert bool(rows % block_rows) == ragged
    p, g, m, v = _leaf_operands(shape, g_dtype)
    scal = jnp.asarray(_SCAL, jnp.float32)
    got = jax.jit(
        lambda *a: fu._kernel_leaf(
            *a, hyper=_HYPER, shadow_dtype=shadow_dtype, interpret=True
        )
    )(p, g, m, v, scal)
    want = fu._leaf_math(p, g.astype(jnp.float32), m, v, *scal, _HYPER)
    assert len(got) == (4 if shadow_dtype else 3)
    for a, b in zip(got, want):
        assert a.shape == shape and a.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6, rtol=1e-6)
    if shadow_dtype:
        assert got[3].dtype == shadow_dtype and got[3].shape == shape
        np.testing.assert_array_equal(
            np.asarray(got[3], np.float32),
            np.asarray(got[0].astype(shadow_dtype), np.float32),
        )


@pytest.mark.parametrize(
    "shape,dtype,why",
    [
        ((4321,), jnp.float32, "one dimension"),
        ((40000,), jnp.float32, "one dimension"),
        ((3, 12, 1024), jnp.float32, "rows 12"),  # 12 rows: no whole tiles
        ((2, 1 << 20), jnp.float32, "width 1048576"),  # no 16 rows in VMEM
        ((256, 256), jnp.bfloat16, "dtype bfloat16"),
    ],
    ids=["small_flat", "flat", "odd_rows", "too_wide", "not_f32"],
)
def test_a_leaf_the_kernel_cannot_walk_in_place_says_why(shape, dtype, why):
    blocking, reason = fu.leaf_blocking(shape, dtype)
    assert blocking is None and reason.startswith(why)
    with pytest.raises(ValueError, match="cannot take the kernel in place"):
        fu._kernel_leaf(*[jnp.zeros(shape, dtype)] * 4, jnp.zeros(6), _HYPER)


@pytest.fixture
def kernel_armed(monkeypatch):
    """The kernel on the CPU, in the interpreter, as the probe would arm it."""
    monkeypatch.setattr(fu, "_INTERPRET", True)
    monkeypatch.setattr(fu.GATE, "armed", True)


_MIXED = {  # one tree, every way a leaf can go
    "merged": (16, 64, 384), "ragged": (1000, 256), "narrow": (300, 96),
    "tiny": (96, 96), "flat_small": (4321,), "flat": (40000,),
    "odd_rows": (3, 12, 1024),
}
_SHADOWED = ("merged", "ragged", "tiny")


def _mixed_tree():
    params = {"trunk": {}, "head": {}}
    grads = {"trunk": {}, "head": {}}
    for i, (name, shape) in enumerate(_MIXED.items()):
        where = "trunk" if name in _SHADOWED else "head"
        p, g, _, _ = _leaf_operands(
            shape, jnp.bfloat16 if name in _SHADOWED else jnp.float32, seed=i
        )
        params[where][name], grads[where][name] = p, g
    shadow = {"trunk": {k: v.astype(jnp.bfloat16) for k, v in params["trunk"].items()}}
    return params, grads, shadow


def test_update_sends_each_leaf_by_its_shape(kernel_armed, monkeypatch):
    """One tree through ``FusedTransformation.update`` with the kernel armed
    and with it off: the same params, moments and shadow (the probe's
    tolerances; the shadow to one bf16 step), and a tally that names what
    fell to XLA and why. bf16 gradients at the shadowed leaves."""
    fused = O.fuse_optimizer(O.Adam(learn_rate=0.01, L2=0.01)).tx
    params, grads, shadow = _mixed_tree()
    state = fused.init(params)
    step = lambda: jax.jit(  # noqa: E731
        lambda g, s, p, sh: fused.update(g, s, p, shadow=sh)
    )(grads, state, params, shadow)
    p_k, s_k, sh_k = step()
    tally = fused.in_place
    sizes = {k: int(np.prod(v)) for k, v in _MIXED.items()}
    taken = sizes["merged"] + sizes["ragged"] + sizes["narrow"]
    assert tally == {
        "share": taken / sum(sizes.values()), "leaves": 3, "small": 2,
        "xla": {
            "head/flat": "one dimension",
            "head/odd_rows": "rows 12 (merging the leading dimensions would copy)",
        },
    }
    monkeypatch.setattr(fu.GATE, "armed", False)
    fused.in_place = None
    p_x, s_x, sh_x = step()
    assert fused.in_place is None  # nothing went through the kernel
    for a, b in zip(_leaves((p_k, s_k)), _leaves((p_x, s_x))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6, rtol=1e-6)
    for a, b, p in zip(_leaves(sh_k), _leaves(sh_x), _leaves(p_k["trunk"])):
        assert a.dtype == b.dtype == jnp.bfloat16
        np.testing.assert_array_equal(  # the kernel's shadow is ITS params' cast
            np.asarray(a, np.float32), np.asarray(p.astype(jnp.bfloat16), np.float32)
        )
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), rtol=2 ** -7
        )
    # without a shadow the call keeps optax's shape
    assert len(jax.jit(lambda g, s, p: fused.update(g, s, p))(grads, state, params)) == 2


def test_kernel_operands_are_the_leaves_themselves(kernel_armed):
    """No ``pad`` anywhere in the update, and whatever reaches the kernel is
    a leaf as it arrived or that leaf with its LEADING dimensions merged
    (rows in whole tiles: a bitcast on the device); what comes back is put
    in the leaf's shape the same way. The old wrapper ravelled, padded and
    re-cut every operand to 128 lanes: seven copies a leaf on the chip."""
    fused = O.fuse_optimizer(O.Adam(learn_rate=0.01)).tx
    params, grads, shadow = _mixed_tree()
    jaxpr = jax.make_jaxpr(lambda g, s, p, sh: fused.update(g, s, p, shadow=sh))(
        grads, fused.init(params), params, shadow
    ).jaxpr
    made_by = {v: e for e in jaxpr.eqns for v in e.outvars}
    assert not [e for e in jaxpr.eqns if e.primitive.name == "pad"]
    calls = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 3
    inputs = set(jaxpr.invars)
    for call in calls:
        for var in call.invars[1:]:  # [0] is the six scalars
            eqn = made_by.get(var)
            if eqn is None:
                assert var in inputs
                continue
            assert eqn.primitive.name == "reshape" and eqn.invars[0] in inputs
            before, after = eqn.invars[0].aval.shape, var.aval.shape
            assert after == (int(np.prod(before[:-1])), before[-1])
            assert before[-2] % fu.ROW_ALIGN == 0
    reshapes = [e for e in jaxpr.eqns if e.primitive.name == "reshape"]
    # 4 in + 4 out for the one three-dimensional leaf; the scalars' stack
    assert len([e for e in reshapes if e.outvars[0].aval.size > 6]) == 8



def test_fused_status_labels():
    tx = O.Adam(learn_rate=0.01)
    assert fu.fused_status(tx) == "off (optax chain)"
    fused = O.fuse_optimizer(tx)
    # CPU: the kernel probe is off -> the label must say the path is XLA
    assert fu.fused_status(fused).startswith("active (")
    assert "pallas" not in fu.fused_status(fused) or fu.GATE.armed is True
    # multi-device mesh: the kernel gate (single_device) keeps pallas off,
    # so the label must downgrade even when the probe passed — a multi-chip
    # run must never claim "active (pallas)" (honest labeling)
    import jax

    from spacy_ray_tpu.parallel.mesh import build_mesh

    mesh = build_mesh(n_data=len(jax.devices()))
    old = fu.GATE.armed
    fu.GATE.armed = True
    try:
        if int(mesh.size) > 1:
            assert "pallas" not in fu.fused_status(fused, mesh)
        assert fu.fused_status(fused, None) == "active (pallas)"
    finally:
        fu.GATE.armed = old


# ------------------------------------------------------------------ shadow


TRF_CFG = """
[nlp]
lang = "en"
pipeline = ["transformer","tagger"]
[components.transformer]
factory = "transformer"
[components.transformer.model]
@architectures = "spacy_ray_tpu.TransformerEncoder.v1"
width = 32
depth = 2
n_heads = 2
embed_size = 500
compute_dtype = "bfloat16"
[components.tagger]
factory = "tagger"
[components.tagger.model]
@architectures = "spacy.Tagger.v2"
[components.tagger.model.tok2vec]
@architectures = "spacy.Tok2VecListener.v1"
width = 32
"""


@pytest.fixture(scope="module")
def trf_setup():
    nlp = Pipeline.from_config(Config.from_str(TRF_CFG))
    egs = synth_corpus(32, "tagger", seed=0)
    nlp.initialize(lambda: iter(egs), seed=0)
    host_params = jax.tree_util.tree_map(np.asarray, nlp.params)
    mesh = build_mesh(n_data=1)
    batch = nlp.collate(egs[:4], pad_batch_to=4, pad_len_to=16)
    tokens = place_batch(batch["tokens"], mesh)
    targets = place_batch(batch["targets"], mesh)
    return nlp, host_params, mesh, tokens, targets


def _fresh(host_params, mesh, tx):
    p = place_replicated(
        jax.tree_util.tree_map(jnp.asarray, host_params), mesh
    )
    s = shard_opt_state(tx.init(p), mesh, False)
    return p, s


def test_shadow_selects_trunk_matmul_weights(trf_setup):
    nlp, host_params, mesh, _, _ = trf_setup
    assert pipeline_shadow_dtype(nlp) == jnp.bfloat16
    sh = build_param_shadow(nlp.params)
    leaves = jax.tree_util.tree_leaves(sh)
    assert leaves and all(x.dtype == jnp.bfloat16 for x in leaves)
    # 2 layers x 8 dense-layer tensors; LN params must NOT be shadowed
    assert len(leaves) == 16
    flat = sh["transformer"]["layer_0"]
    assert "ln1_g" not in flat and "qkv_W" in flat
    # a CPU-auto (f32) trunk yields no shadow: "auto" is a no-op there
    cpu_cfg = TRF_CFG.replace('compute_dtype = "bfloat16"', "")
    cpu_nlp = Pipeline.from_config(Config.from_str(cpu_cfg))
    assert pipeline_shadow_dtype(cpu_nlp) is None


def test_shadow_forward_bit_exact(trf_setup):
    """overlay_shadow(params, cast(params)) through the loss == the
    cast-per-step loss, bitwise (the astype the layer stack applies to an
    already-bf16 leaf is the identity)."""
    nlp, host_params, mesh, tokens, targets = trf_setup
    loss_fn = nlp.make_loss_fn(dropout=0.0)
    p = place_replicated(
        jax.tree_util.tree_map(jnp.asarray, host_params), mesh
    )
    rng = jax.random.PRNGKey(0)
    l_base, _ = jax.jit(loss_fn)(p, tokens, targets, rng)
    l_shadow, _ = jax.jit(
        lambda p_, sh_, t, g, r: loss_fn(overlay_shadow(p_, sh_), t, g, r)
    )(p, build_param_shadow(p), tokens, targets, rng)
    assert float(l_base) == float(l_shadow)


def test_shadow_training_trajectory_and_sync(trf_setup):
    """Shadow-enabled training stays within 1e-6 of the cast-per-step
    trajectory over several steps (exactness bound: the baseline backward
    may skip one bf16 rounding in weight-grad matmuls), and the shadow is
    ALWAYS exactly cast(master params) — it never drifts."""
    nlp, host_params, mesh, tokens, targets = trf_setup
    tx = registry.get("optimizers", "Adam.v1")(learn_rate=0.01)
    loss_fn = nlp.make_loss_fn(dropout=0.0)
    p0, s0 = _fresh(host_params, mesh, tx)
    upd = make_train_step(loss_fn, tx, mesh, opt_state_template=s0)
    p1, s1 = _fresh(host_params, mesh, tx)
    sh = build_param_shadow(p1)
    upd_s = make_train_step(
        loss_fn, tx, mesh, opt_state_template=s1, shadow=True
    )
    rng = jax.random.PRNGKey(0)
    for i in range(4):
        rng, sub = jax.random.split(rng)
        p0, s0, l0, _ = upd(p0, s0, tokens, targets, sub)
        p1, s1, sh, l1, _ = upd_s(p1, s1, sh, tokens, targets, sub)
    for a, b in zip(_leaves(p0), _leaves(p1)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-6, rtol=1e-5
        )
    # shadow integrity: exactly the bf16 cast of the current masters
    ref = refresh_shadow(p1, build_param_shadow(p1))
    for a, b in zip(_leaves(sh), _leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ----------------------------------------------------- multi-step dispatch


def test_multi_dispatch_bit_exact_vs_singles(trf_setup):
    """K stacked steps through the scan == K host-dispatched singles:
    params, opt state, rng chain, and per-step losses all bitwise equal
    (the scan continues the identical jax.random.split chain)."""
    nlp, host_params, mesh, tokens, targets = trf_setup
    tx = registry.get("optimizers", "Adam.v1")(learn_rate=0.01)
    loss_fn = nlp.make_loss_fn(dropout=0.0)
    p0, s0 = _fresh(host_params, mesh, tx)
    upd = make_train_step(loss_fn, tx, mesh, opt_state_template=s0)
    rng = jax.random.PRNGKey(7)
    r = rng
    losses = []
    for _ in range(3):
        r, sub = jax.random.split(r)
        p0, s0, loss, _ = upd(p0, s0, tokens, targets, sub)
        losses.append(float(loss))
    p1, s1 = _fresh(host_params, mesh, tx)
    upd_m = make_train_step(
        loss_fn, tx, mesh, opt_state_template=s1, multi_dispatch=True
    )
    stack = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda x: jnp.stack([x, x, x]), t
    )
    p1, s1, r_out, losses_m, metrics_m = upd_m(
        p1, s1, stack(tokens), stack(targets), rng
    )
    for a, b in zip(_leaves((p0, s0)), _leaves((p1, s1))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(r_out), np.asarray(r))
    np.testing.assert_array_equal(
        np.asarray(losses_m), np.asarray(losses, np.float32)
    )
    # per-step metrics keep the leading [K] dim for telemetry fan-out
    assert all(v.shape[0] == 3 for v in metrics_m.values())


# ---------------------------------------------------------- donation audit


def test_update_donates_params_opt_state_and_shadow(trf_setup):
    """The jitted update must DONATE its state buffers: a stray copy would
    silently reintroduce the O(n_params) traversal the round-7 tentpole
    removes. Donated jax arrays report is_deleted() after the call."""
    nlp, host_params, mesh, tokens, targets = trf_setup
    tx = O.fuse_optimizer(registry.get("optimizers", "Adam.v1")(learn_rate=0.01))
    loss_fn = nlp.make_loss_fn(dropout=0.0)
    p, s = _fresh(host_params, mesh, tx)
    sh = build_param_shadow(p)
    upd = make_train_step(loss_fn, tx, mesh, opt_state_template=s, shadow=True)
    out = upd(p, s, sh, tokens, targets, jax.random.PRNGKey(0))
    jax.block_until_ready(out[0])
    for leaf in _leaves((p, sh)):
        assert leaf.is_deleted(), "params/shadow buffer was not donated"
    # float opt-state moments must donate too (tiny int counts may not
    # alias across dtypes on all backends — the bytes that matter do)
    for leaf in _leaves(s):
        if leaf.dtype == jnp.float32 and leaf.size > 1:
            assert leaf.is_deleted(), "opt-state moment buffer not donated"
    # ... and the compiled step writes each of them where it lay: every
    # parameter, both moments and the shadow are aliased to an output (a
    # copy round the optimizer's kernel broke exactly this on the chip)
    p, s = _fresh(host_params, mesh, tx)
    sh = build_param_shadow(p)
    compiled = upd.lower(p, s, sh, tokens, targets, jax.random.PRNGKey(0)).compile()
    aliased = {
        int(n) for n in re.findall(
            r"\(\s*(\d+), \{\}, (?:may|must)-alias\)", compiled.as_text()
        )
    }
    state_leaves = _leaves((p, s, sh))  # the order jit flattens its arguments in
    wanted = {
        i for i, leaf in enumerate(state_leaves)
        if leaf.dtype in (jnp.float32, jnp.bfloat16) and leaf.size > 1
    }
    assert len(wanted) == 3 * len(_leaves(p)) + len(_leaves(sh))
    assert wanted <= aliased, sorted(wanted - aliased)
    assert compiled.memory_analysis().alias_size_in_bytes >= sum(
        state_leaves[i].nbytes for i in wanted
    )


@pytest.mark.parametrize("config,taken,fell,floor", [
    ("trf", 18, {}, 0.98),  # with 27 small ones: 99.4% of the elements
    ("kanana2_a3b", 29, {}, 0.98),  # with 23 small ones: 98.2%
    # the pattern trunk: the convolution's taps [4, channels], the 64-element
    # A_log / D / dt_bias and the gains are small here (and, but for the taps,
    # at the published widths too: 39 leaves taken, 42 small, 99.99%); with 52
    # small ones the rehearsal widths come to 97.9%, so this case has its own floor
    ("nemotron3_nano_a3b", 33, {}, 0.975),
])
def test_train_reports_which_leaves_the_kernel_took_in_place(
    config, taken, fell, floor, tmp_path, monkeypatch
):
    """``train`` at the benchmark's rehearsal widths with the kernel armed
    (interpreter): ``resolved`` carries ``fused_update_in_place`` beside
    ``fused_update``, worked out from the leaves' shapes. The widths are
    tiny, so the floor under which a leaf is left to XLA is lowered with
    them; nothing else of the rule moves."""
    sys.path.insert(0, str(ROOT / "benchmark"))
    import common
    from spacy_ray_tpu.config import load_config

    monkeypatch.setenv("SRT_PALLAS_FUSED", "1")
    monkeypatch.setattr(fu, "_INTERPRET", True)
    monkeypatch.setattr(fu, "MIN_KERNEL_SIZE", 2048)
    monkeypatch.setattr(fu.GATE, "armed", None)
    config_file = common.load_json(ROOT / "benchmark" / "configs" / f"{config}.json")
    docs = common.load_json(ROOT / "benchmark" / "traffic" / "ewt10_b3k5.json")["docs"]
    generate = common.load_module("generators", docs["generator"]).generate
    common.write_jsonl(tmp_path / "train.jsonl", generate(24, 5, docs))
    common.write_jsonl(tmp_path / "dev.jsonl", generate(4, 6, docs))
    cfg = load_config(ROOT / config_file["program_config"], {
        **config_file["overrides"], **config_file["rehearse_overrides"],
        "paths.train": str(tmp_path / "train.jsonl"),
        "paths.dev": str(tmp_path / "dev.jsonl"),
        "training.batcher.size": 600, "training.accumulate_gradient": 1,
        "training.max_steps": 2, "training.eval_frequency": 10 ** 9,
        # what "auto" resolves to on the chip
        "training.fused_update": "on", "training.bf16_shadow": "on",
        "components.transformer.model.compute_dtype": "bfloat16",
    }, interpolate=False)
    _, result = train(cfg, n_workers=1, stdout_log=False)
    assert result.resolved["fused_update"] == "active (pallas interpret-mode)"
    tally = result.resolved["fused_update_in_place"]
    assert set(tally) == {"share", "leaves", "small", "xla"}
    assert (tally["leaves"], tally["xla"]) == (taken, fell), tally
    assert floor < tally["share"] < 1.0 and tally["small"] > 0


def test_avg_step_donates_accumulator():
    """loop._avg_step must donate its running-mean accumulator instead of
    allocating a fresh full-size tree every step (ISSUE 5 satellite)."""
    from spacy_ray_tpu.training.loop import _avg_step

    avg = {"w": jnp.ones((256, 256))}
    params = {"w": jnp.full((256, 256), 2.0)}
    out = _avg_step(avg, params, 2)
    jax.block_until_ready(out["w"])
    assert avg["w"].is_deleted(), "avg accumulator was not donated"
    np.testing.assert_allclose(np.asarray(out["w"]), 1.5)


# ------------------------------------------------------------- loop knobs


def test_training_knob_validation():
    validate_training({"fused_update": "auto", "bf16_shadow": "off",
                       "steps_per_dispatch": 4})
    with pytest.raises(ValueError, match="fused_update"):
        validate_training({"fused_update": True})
    with pytest.raises(ValueError, match="bf16_shadow"):
        validate_training({"bf16_shadow": "always"})
    with pytest.raises(ValueError, match="steps_per_dispatch"):
        validate_training({"steps_per_dispatch": 0})


@pytest.mark.slow
def test_train_loop_steps_per_dispatch_equivalence(tmp_path):
    """train() with steps_per_dispatch=3 reproduces the K=1 run exactly:
    same eval history (scores + losses), and the telemetry metrics file
    still carries one step row PER INNER STEP."""
    write_synth_jsonl(tmp_path / "train.jsonl", 200, kind="tagger", seed=0)
    write_synth_jsonl(tmp_path / "dev.jsonl", 40, kind="tagger", seed=1)
    base = """
[nlp]
lang = "en"
pipeline = ["tok2vec","tagger"]
[components.tok2vec]
factory = "tok2vec"
[components.tok2vec.model]
@architectures = "spacy.HashEmbedCNN.v2"
width = 32
depth = 1
embed_size = 300
[components.tagger]
factory = "tagger"
[components.tagger.model]
@architectures = "spacy.Tagger.v2"
[components.tagger.model.tok2vec]
@architectures = "spacy.Tok2VecListener.v1"
width = 32
[corpora.train]
@readers = "spacy.JsonlCorpus.v1"
path = "{train}"
[corpora.dev]
@readers = "spacy.JsonlCorpus.v1"
path = "{dev}"
[training]
seed = 1
max_steps = 8
eval_frequency = 4
dropout = 0.0
prefetch_batches = 0
steps_per_dispatch = {K}
[training.batcher]
@batchers = "spacy.batch_by_words.v1"
size = 200
tolerance = 0.2
"""
    hist = {}
    for K in (1, 3):
        cfg = Config.from_str(base.format(
            train=tmp_path / "train.jsonl", dev=tmp_path / "dev.jsonl", K=K
        ))
        out = tmp_path / f"out{K}"
        _, res = train(cfg, out, stdout_log=False, metrics_dir=out / "m")
        rows = [json.loads(line)
                for line in (out / "m" / "metrics.jsonl").read_text().splitlines()]
        step_rows = [r["step"] for r in rows if r["kind"] == "step"]
        assert step_rows == list(range(1, res.final_step + 1))
        hist[K] = [(h["step"], h["score"], h["losses"]) for h in res.history]
    assert hist[1] == hist[3]
