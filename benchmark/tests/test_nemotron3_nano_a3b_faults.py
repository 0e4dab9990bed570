"""The comparison that decides ``correct`` for ``nemotron3_nano_a3b`` bites:
five faults planted in the PROGRAM from outside (the reference untouched), and
a control (the plain reference itself with every matrix product's operands
rounded to fewer significant bits) in the program's place, each put through
``trunk_check.check``, the harness's own comparison, and each has to come out
NOT ok; the program as it is has to come out ok.

As a test (``pytest benchmark/tests``, and the tier-1 suite imports it): CPU,
float32, the configuration's rehearsal widths, seeded weights; the control
rounds to bfloat16's 8 significant bits (one precision under the float32 the
CPU computes in).

As a script, on the chip at the PUBLISHED widths, after ``--steps`` steps of the
cell's own training (an untrained router's top-k lies inside bfloat16's reach:
``reference/nemotron3_nano_a3b.py``), it prints each reading beside its limit;
there the control rounds to float8's 4 significant bits (one precision under
the bfloat16 the chip computes in), and the bfloat16 control is read beside it
for what rounding alone gives::

    python3 benchmark/tests/test_nemotron3_nano_a3b_faults.py --seed 13 --steps 60

A fault upstream of a router moves the router's choices, and the reference's
tie rule then refuses the run (NaN: not ok, by the forward limit). To read how
LARGE a fault is beside ``TOLERANCE`` and ``GRAD_TOLERANCE``
(``GRAD_TOLERANCE_F32`` on the CPU), each case is read a second time with the
reference made to FOLLOW the system's routing everywhere (``following``). The
4-bit control on the chip is refused by the tie rule itself (NaN, no number
beside a limit); its two readings, 3.9e-2 and 4.8, exist only under
``following``. PERF.md section 6 (PR 34) has the chip's readings.
"""

from __future__ import annotations

import contextlib
import functools
import json
import sys
import tempfile
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))

import pytest  # noqa: E402

import common  # noqa: E402
import trunk_check  # noqa: E402

CONFIG = "nemotron3_nano_a3b"
MIX = "ewt10_16x256"
BF16_BITS = 7  # explicit significand bits of bfloat16
FLOAT8_BITS = 3  # of float8_e4m3


def build(seed: int, published: bool, steps: int):
    """The pipeline and the float32 tree both sides compute with: seeded at
    the rehearsal widths, or trained ``steps`` steps by the cell's own loop at
    the published ones; and the 8 seeded sequences the cell compares on."""
    from spacy_ray_tpu.config import load_config
    from spacy_ray_tpu.pipeline.doc import Example
    from spacy_ray_tpu.pipeline.language import Pipeline
    from spacy_ray_tpu.training.corpus import _doc_from_json

    config_file = common.load_json(BENCH / "configs" / f"{CONFIG}.json")
    traffic = common.load_json(BENCH / "traffic" / f"{MIX}.json")
    generator = common.load_module("generators", traffic["docs"]["generator"])
    docs = generator.generate(trunk_check.N_SEQUENCES, seed + 7919, traffic["docs"])
    program_config = BENCH.parent / config_file["program_config"]
    if not published:
        config = load_config(program_config, config_file["rehearse_overrides"], interpolate=False)
        nlp = Pipeline.from_config(config)
        examples = [Example.from_gold(_doc_from_json(d))
                    for d in generator.generate(40, seed, traffic["docs"])]
        nlp.initialize(lambda: examples, seed=seed)
        return nlp, nlp.params, docs
    from spacy_ray_tpu.training import loop

    work = Path(tempfile.mkdtemp(prefix=f"{CONFIG}_faults_"))
    common.write_jsonl(work / "train.jsonl", generator.generate(20 * steps, seed, traffic["docs"]))
    common.write_jsonl(work / "dev.jsonl", generator.generate(16, seed + 1, traffic["docs"]))
    overrides = {**traffic["overrides"], "paths.train": str(work / "train.jsonl"),
                 "paths.dev": str(work / "dev.jsonl"), "training.seed": seed,
                 "training.eval_frequency": 10 ** 9, "training.max_steps": steps}
    nlp, result = loop.train(load_config(program_config, overrides, interpolate=False),
                             n_workers=1, stdout_log=False)
    print("trained", result.final_step, "steps;", {k: result.resolved.get(k)
                                                   for k in ("moe", "moe_dropped", "ssm")}, flush=True)
    return nlp, nlp.params, docs


# ---- the faults: each patches the program, and is undone on the way out ----------------


@contextlib.contextmanager
def _patched(module, name, value):
    real = getattr(module, name)
    setattr(module, name, value)
    try:
        yield real
    finally:
        setattr(module, name, real)


@contextlib.contextmanager
def convolution_tap_dropped():
    """The convolution sees t-2..t: its oldest tap is multiplied by nought."""
    from spacy_ray_tpu.models import hybrid_ssm

    real = hybrid_ssm.causal_conv
    with _patched(hybrid_ssm, "causal_conv", lambda x, w, b: real(x, w.at[0].set(0.0), b)):
        yield


@contextlib.contextmanager
def state_not_carried_over_chunk_edges():
    """Every chunk starts from a state of nought: the scan run chunk by chunk."""
    import jax.numpy as jnp

    from spacy_ray_tpu.models import hybrid_ssm

    real = hybrid_ssm.chunked_scan

    def each_chunk_alone(x, B_, C_, dt, A, chunk, cd):
        cuts = range(0, x.shape[1], chunk)
        return jnp.concatenate([real(*(a[:, c:c + chunk] for a in (x, B_, C_, dt)), A, chunk, cd)
                                for c in cuts], axis=1)

    with _patched(hybrid_ssm, "chunked_scan", each_chunk_alone):
        yield


@contextlib.contextmanager
def dt_bias_left_out():
    """``dt = softplus(dt)``: the step's learned bias is not added."""
    import jax.numpy as jnp

    from spacy_ray_tpu.models import hybrid_ssm

    real = hybrid_ssm.mamba_mixer
    with _patched(hybrid_ssm, "mamba_mixer", lambda p, h, s, cd: real(
            dict(p, dt_bias=jnp.zeros_like(p["dt_bias"])), h, s, cd)):
        yield


@contextlib.contextmanager
def query_heads_on_the_wrong_key_head():
    """The first half of the query heads reads the LAST key/value head."""
    from spacy_ray_tpu.ops import flash_attention

    real = flash_attention.attention
    with _patched(flash_attention, "attention", lambda q, k, v, mask, causal=False: real(
            q, k[:, :, ::-1], v[:, :, ::-1], mask, causal=causal)):
        yield


@contextlib.contextmanager
def relu_for_relu2():
    """An expert's activation is relu, not its square: routed and shared."""
    import jax
    import jax.numpy as jnp

    from spacy_ray_tpu.models import hybrid_ssm, latent_moe

    def shared(h16, w_up, w_down, cd):
        up = (h16 @ w_up.astype(cd)).astype(jnp.float32)
        return (jax.nn.relu(up).astype(cd) @ w_down.astype(cd)).astype(jnp.float32)

    def routed(form, rows, live, group_sizes, experts):
        eu, ed = experts
        grouped = functools.partial(jax.lax.ragged_dot, group_sizes=group_sizes)
        inner = jax.nn.relu(grouped(rows, eu).astype(jnp.float32))
        return jnp.where(live, grouped(jnp.where(live, inner, 0).astype(rows.dtype), ed), 0), None

    with _patched(hybrid_ssm, "relu2_ffn", shared), _patched(latent_moe, "_expert_products", routed):
        yield


# ---- the control: the reference itself, its products' operands rounded -----------------


def round_significand(x, bits: int):
    """float32 rounded (to nearest, ties to even) to ``bits`` explicit
    significand bits, with float32's exponent range: float8_e4m3's precision
    (bits = 3) or bfloat16's (7) without their narrower ranges."""
    import jax
    import jax.numpy as jnp

    drop = 23 - bits
    x = x.astype(jnp.float32)
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    u = (u + jnp.uint32((1 << (drop - 1)) - 1) + ((u >> drop) & 1)) & jnp.uint32(
        ~((1 << drop) - 1) & 0xFFFFFFFF)
    # the gradient passes straight through the rounding
    return x + jax.lax.stop_gradient(jax.lax.bitcast_convert_type(u, jnp.float32) - x)


def eval_rounded(jaxpr, consts, args, bits: int, router_shape):
    """Evaluate a jaxpr with the operands of every ``dot_general`` rounded,
    the router's product (float32 in the program too) apart; a ``scan`` (the
    recurrence, whose state the program keeps in float32 as well) is bound as
    it stands."""
    from jax.extend import core as jcore

    env = dict(zip(jaxpr.constvars, consts))
    env.update(zip(jaxpr.invars, args))

    def read(v):
        return v.val if isinstance(v, jcore.Literal) else env[v]

    for eqn in jaxpr.eqns:
        vals = [read(v) for v in eqn.invars]
        inner = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
        if eqn.primitive.name in ("pjit", "jit", "custom_jvp_call") and inner is not None:
            outs = eval_rounded(inner.jaxpr, inner.consts, vals, bits, router_shape)
        else:
            if eqn.primitive.name == "dot_general" and tuple(vals[1].shape) != tuple(router_shape):
                vals = [round_significand(v, bits) for v in vals]
            outs = eqn.primitive.bind(*vals, **eqn.params)
            outs = outs if eqn.primitive.multiple_results else [outs]
        env.update(zip(eqn.outvars, outs))
    return [read(v) for v in jaxpr.outvars]


@contextlib.contextmanager
def reference_in_the_programs_place(nlp, bits: int):
    """``trunk_check`` asks the pipeline's trunk component for its forward:
    for the control that component answers with the plain reference, operands
    rounded, fed what ``make_inputs`` hands the reference (the system's
    routing with it)."""
    import jax
    import jax.numpy as jnp

    from spacy_ray_tpu.types import Padded

    trunk = nlp.components[nlp.tok2vec_name]
    shape = trunk.model.meta["shape"]

    def forward(params, tokens, ctx):
        reference = trunk_check.load_module("reference", CONFIG)  # as ``check`` loads it
        inputs = reference.make_inputs(nlp, jax.lax.stop_gradient(params), tokens)
        closed = jax.make_jaxpr(lambda p: reference.forward(p, *inputs))(params)
        out, = eval_rounded(closed.jaxpr, closed.consts, jax.tree_util.tree_leaves(params),
                            bits, (shape.width, shape.n_experts))
        return Padded(X=out, mask=jnp.asarray(tokens.mask))

    trunk.forward = forward  # the instance's attribute shadows the method
    try:
        yield
    finally:
        del trunk.forward


@contextlib.contextmanager
def following():
    """The reference takes the system's routing everywhere (no score is too
    far from the cut, no share of ties too large): a fault's SIZE, which the
    tie rule would otherwise answer with NaN. ``load_module`` executes the
    reference's file anew on every call, so the constants are patched where
    ``trunk_check`` loads it."""
    real = common.load_module

    def load(kind, name):
        module = real(kind, name)
        if (kind, name) == ("reference", CONFIG):
            module.ROUTE_TIE = module.ROUTE_TIE_F32 = module.MAX_TIE_SHARE = 1.0
        return module

    with _patched(trunk_check, "load_module", load):
        yield


def cases(nlp, control_bits: int):
    """name -> a context manager under which ``trunk_check.check`` has to fail."""
    return {
        f"control: the reference, operands of {control_bits + 1} significant bits":
            lambda: reference_in_the_programs_place(nlp, control_bits),
        "a convolution tap dropped": convolution_tap_dropped,
        "the carried state zeroed at chunk edges": state_not_carried_over_chunk_edges,
        "dt_bias left out": dt_bias_left_out,
        "query heads on the wrong key head": query_heads_on_the_wrong_key_head,
        "relu for relu2": relu_for_relu2,
    }


def reading(nlp, params, docs, seed, planted=contextlib.nullcontext, follow=False):
    with planted(), (following() if follow else contextlib.nullcontext()):
        try:
            out = trunk_check.check(nlp, params, CONFIG, docs, seed)
        except ZeroDivisionError:
            # the tie rule refused every word: the reference's gradients are NaN throughout,
            # and ``gradient_errors`` divides a leaf of noughts by the larger of 0 and NaN
            out = {"ok": False, "rel_err": float("nan")}
    return {k: out.get(k) for k in ("ok", "rel_err", "tolerance", "grad_rel_err",
                                    "grad_tolerance", "grad_worst_leaf", "grad_norm_gap")}


# ---- as a test -------------------------------------------------------------------------
# (no fixture: the tier-1 suite imports the ``test_`` names of this file by path)

SEED = 5
NAMES = ["a convolution tap dropped", "the carried state zeroed at chunk edges", "dt_bias left out",
         "query heads on the wrong key head", "relu for relu2", "control"]


@functools.lru_cache(maxsize=1)
def _built():
    nlp, params, docs = build(SEED, published=False, steps=0)
    return nlp, params, docs, cases(nlp, BF16_BITS)


def test_the_state_space_program_as_it_is_passes():
    nlp, params, docs, _ = _built()
    got = reading(nlp, params, docs, SEED)
    assert got["ok"] and got["rel_err"] <= got["tolerance"]
    assert got["grad_rel_err"] <= got["grad_tolerance"]


@pytest.mark.parametrize("name", NAMES)
def test_a_fault_planted_in_the_state_space_trunk_and_the_control_fail_the_comparison(name):
    """Each fault's SIZE, the tie rule out of the way (every one of them lies
    upstream of a router, whose choices it moves; whether the rule then answers
    NaN depends on whether ``routing_choices``, one jitted function for the
    pipeline's life, was first traced with the fault in place): over a limit,
    not NaN."""
    nlp, params, docs, planted = _built()
    key = next(k for k in planted if k.startswith(name))
    sized = reading(nlp, params, docs, SEED, planted[key], follow=True)
    assert not sized["ok"] and sized["rel_err"] == sized["rel_err"]
    assert (sized["rel_err"] > sized["tolerance"]
            or sized["grad_rel_err"] > sized["grad_tolerance"])
    assert "forward" not in vars(nlp.components[nlp.tok2vec_name])  # the patch is gone


if __name__ == "__main__":
    import argparse

    parser = argparse.ArgumentParser()
    parser.add_argument("--seed", type=int, default=13)
    parser.add_argument("--steps", type=int, default=60)
    parser.add_argument("--rehearse-cpu", action="store_true")
    args = parser.parse_args()
    common.start_jax(1, args.rehearse_cpu)
    nlp, params, docs = build(args.seed, published=not args.rehearse_cpu, steps=args.steps)

    def show(what, **got):
        print("READING " + json.dumps({"what": what, "seed": args.seed, **got}), flush=True)

    show("the program as it is", **reading(nlp, params, docs, args.seed))
    planted = cases(nlp, BF16_BITS if args.rehearse_cpu else FLOAT8_BITS)
    if not args.rehearse_cpu:  # what bfloat16's rounding alone gives, beside the sound readings
        planted["the reference, operands of 8 significant bits (bfloat16: no control on the chip)"] = (
            lambda: reference_in_the_programs_place(nlp, BF16_BITS))
    for name, plant in planted.items():
        got = reading(nlp, params, docs, args.seed, plant)
        show(name, **got)
        if got["rel_err"] != got["rel_err"]:  # NaN: the tie rule refused; read the size too
            show(name + ", the reference following its routing",
                 **reading(nlp, params, docs, args.seed, plant, follow=True))
    print("done", flush=True)
