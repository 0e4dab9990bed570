"""The comparison that decides ``correct`` for ``solar_open2_250b`` bites:
five faults planted in the PROGRAM from outside (the reference untouched), and
a control (the plain reference itself with every matrix product's operands
rounded to fewer significant bits) in the program's place, each put through
``trunk_check.check``, the harness's own comparison, and each has to come out
NOT ok; the program as it is has to come out ok.

The machinery is ``test_nemotron3_nano_a3b_faults.py``'s, unedited (``build``,
the rounding of a jaxpr's products, the reference in the program's place,
``following``, ``reading``): that file is loaded here a second time, by path,
as a module of this file's own, and told this configuration's name and mix.
What is this file's: the five faults.

As a test (``pytest benchmark/tests``, and the tier-1 suite imports it): CPU,
float32, the configuration's rehearsal widths, seeded weights; the control
rounds to bfloat16's 8 significant bits (one precision under the float32 the
CPU computes in).

As a script, on the chip at the PUBLISHED widths, after ``--steps`` steps of
the cell's own training (an untrained router's top-k lies inside bfloat16's
reach), it prints each reading beside its limit; there the control rounds to
float8's 4 significant bits (one precision under the bfloat16 the chip
computes in), and the bfloat16 control is read beside it for what rounding
alone gives::

    python3 benchmark/tests/test_solar_open2_250b_faults.py --seed 13 --steps 60

PERF.md section 6 (PR 36) has the chip's readings.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import importlib.util
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))

import pytest  # noqa: E402

import common  # noqa: E402

CONFIG = "solar_open2_250b"
MIX = "ewt10_8x256"


def _machinery():
    path = Path(__file__).with_name("test_nemotron3_nano_a3b_faults.py")
    spec = importlib.util.spec_from_file_location("solar_open2_250b_faults_machinery", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    module.CONFIG, module.MIX = CONFIG, MIX
    return module


BASE = _machinery()
_patched = BASE._patched


# ---- the faults: each patches the program, and is undone on the way out ----------------


@contextlib.contextmanager
def rank_one_correction_left_out():
    """``S_t = Diag(alpha_t) S_(t-1) + beta_t k_t v_t^T``: nothing is taken
    from what the state holds under the key (gated linear attention, no delta
    rule). In the chunked form: no solve, and no correction from the state
    carried in (the right-hand side's second half, ``beta K exp(G)``)."""
    from spacy_ray_tpu.models import delta_attention

    def no_correction(A, rhs):
        return rhs.at[..., rhs.shape[-1] // 2:].set(0.0)

    with _patched(delta_attention, "_solve", no_correction):
        yield


@contextlib.contextmanager
def beta_not_doubled():
    """``beta = sigmoid(h W_beta)``: a transition's eigenvalues stay in (0, 1)."""
    from spacy_ray_tpu.models import hybrid_ssm

    real = hybrid_ssm.kda_mixer
    with _patched(hybrid_ssm, "kda_mixer", lambda p, h, s, cd: real(
            p, h, dataclasses.replace(s, kda_neg_eigval=False), cd)):
        yield


@contextlib.contextmanager
def decay_a_scalar_a_head():
    """Every key channel of a head decays by the head's MEAN log-decay (the
    Mamba-2 / gated-delta-net form, not a decay for every channel)."""
    import jax.numpy as jnp

    from spacy_ray_tpu.models import hybrid_ssm

    real = hybrid_ssm.chunked_delta_rule
    with _patched(hybrid_ssm, "chunked_delta_rule", lambda q, k, v, g, beta, chunk, cd: real(
            q, k, v, jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape), beta,
            chunk, cd)):
        yield


@contextlib.contextmanager
def gate_left_off_the_softmax_layer():
    """The ``G`` layer without its output gate: plain grouped attention."""
    from spacy_ray_tpu.models import hybrid_ssm

    real = hybrid_ssm.grouped_attention
    with _patched(hybrid_ssm, "grouped_attention", lambda p, h, mask, s, cd, kind=None: real(
            p, h, mask, s, cd, hybrid_ssm.ATTENTION)):
        yield


@contextlib.contextmanager
def an_expert_skipped():
    """The second of the held experts answers nothing: its pairs' weights are nought."""
    import jax.numpy as jnp

    from spacy_ray_tpu.models import latent_moe

    real = latent_moe.routed_experts

    def skipping(p, h, token_mask, idx, weights, s, cd, form=latent_moe.GATED_SILU):
        return real(p, h, token_mask, idx, jnp.where(idx == s.held_from + 1, 0.0, weights),
                    s, cd, form=form)

    with _patched(latent_moe, "routed_experts", skipping):
        yield


# ---- the control: the reference's products rounded on the way BACK as well --------------


def eval_rounded_both_ways(jaxpr, consts, args, bits: int, router_shape):
    """``eval_rounded`` of the machinery (the operands of every ``dot_general``
    rounded, the router's product apart, a ``scan`` bound as it stands), and
    the COTANGENT that comes back into each such product rounded too. The
    machinery's rounding passes the gradient straight through, so its
    backward products run on exact cotangents: half of what a lower precision
    does to a gradient. A step computed in a lower precision rounds what it
    multiplies in both passes."""
    import jax
    from jax.extend import core as jcore

    @functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
    def cotangent_rounded(x, bits_):
        return x

    cotangent_rounded.defvjp(lambda x, bits_: (x, None),
                             lambda bits_, _, g: (BASE.round_significand(g, bits_),))

    env = dict(zip(jaxpr.constvars, consts))
    env.update(zip(jaxpr.invars, args))

    def read(v):
        return v.val if isinstance(v, jcore.Literal) else env[v]

    for eqn in jaxpr.eqns:
        vals = [read(v) for v in eqn.invars]
        inner = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
        if eqn.primitive.name in ("pjit", "jit", "custom_jvp_call") and inner is not None:
            outs = eval_rounded_both_ways(inner.jaxpr, inner.consts, vals, bits, router_shape)
        elif eqn.primitive.name == "dot_general" and tuple(vals[1].shape) != tuple(router_shape):
            vals = [BASE.round_significand(v, bits) for v in vals]
            outs = [cotangent_rounded(eqn.primitive.bind(*vals, **eqn.params), bits)]
        else:
            outs = eqn.primitive.bind(*vals, **eqn.params)
            outs = outs if eqn.primitive.multiple_results else [outs]
        env.update(zip(eqn.outvars, outs))
    return [read(v) for v in jaxpr.outvars]


@contextlib.contextmanager
def reference_rounded_both_ways(nlp, bits: int):
    with _patched(BASE, "eval_rounded", eval_rounded_both_ways), \
            BASE.reference_in_the_programs_place(nlp, bits):
        yield


def cases(nlp, control_bits: int):
    """name -> a context manager under which ``trunk_check.check`` has to fail."""
    return {
        f"control: the reference, operands and cotangents of {control_bits + 1} significant bits":
            lambda: reference_rounded_both_ways(nlp, control_bits),
        "the rank-one correction left out": rank_one_correction_left_out,
        "beta not doubled": beta_not_doubled,
        "the decay a scalar a head": decay_a_scalar_a_head,
        "the gate left off G": gate_left_off_the_softmax_layer,
        "an expert skipped": an_expert_skipped,
    }


# ---- as a test -------------------------------------------------------------------------
# (no fixture: the tier-1 suite imports the ``test_`` names of this file by path)

SEED = 5
NAMES = ["the rank-one correction left out", "beta not doubled", "the decay a scalar a head",
         "the gate left off G", "an expert skipped", "control"]


@functools.lru_cache(maxsize=1)
def _built():
    nlp, params, docs = BASE.build(SEED, published=False, steps=0)
    return nlp, params, docs, cases(nlp, BASE.BF16_BITS)


def test_the_delta_rule_program_as_it_is_passes():
    nlp, params, docs, _ = _built()
    got = BASE.reading(nlp, params, docs, SEED)
    assert got["ok"] and got["rel_err"] <= got["tolerance"]
    assert got["grad_rel_err"] <= got["grad_tolerance"]


@pytest.mark.parametrize("name", NAMES)
def test_a_fault_planted_in_the_delta_rule_trunk_and_the_control_fail_the_comparison(name):
    """Each fault's SIZE, the tie rule out of the way (each lies upstream of a
    router, whose choices it moves): over a limit, not NaN."""
    nlp, params, docs, planted = _built()
    key = next(k for k in planted if k.startswith(name))
    sized = BASE.reading(nlp, params, docs, SEED, planted[key], follow=True)
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
    parser.add_argument("--only", default="", help="read only the cases whose name holds this")
    args = parser.parse_args()
    common.start_jax(1, args.rehearse_cpu)
    nlp, params, docs = BASE.build(args.seed, published=not args.rehearse_cpu, steps=args.steps)

    def show(what, **got):
        print("READING " + json.dumps({"what": what, "seed": args.seed, **got}), flush=True)

    show("the program as it is", **BASE.reading(nlp, params, docs, args.seed))
    planted = cases(nlp, BASE.BF16_BITS if args.rehearse_cpu else BASE.FLOAT8_BITS)
    if not args.rehearse_cpu:
        # PR 34's control, whose backward runs on exact cotangents, beside this file's
        planted["control, forward alone: the reference, operands of 4 significant bits"] = (
            lambda: BASE.reference_in_the_programs_place(nlp, BASE.FLOAT8_BITS))
        # what bfloat16's rounding alone gives, beside the sound readings
        planted["the reference, operands and cotangents of 8 significant bits (bfloat16: no "
                "control on the chip)"] = lambda: reference_rounded_both_ways(nlp, BASE.BF16_BITS)
    for name, plant in planted.items():
        if args.only not in name:
            continue
        got = BASE.reading(nlp, params, docs, args.seed, plant)
        show(name, **got)
        if got["rel_err"] != got["rel_err"]:  # NaN: the tie rule refused; read the size too
            show(name + ", the reference following its routing",
                 **BASE.reading(nlp, params, docs, args.seed, plant, follow=True))
    print("done", flush=True)
