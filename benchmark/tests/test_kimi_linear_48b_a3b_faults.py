"""The comparison that decides ``correct`` for ``kimi_linear_48b_a3b`` bites:
four faults planted in the PROGRAM from outside (the reference untouched),
and a control (the plain reference itself with every matrix product's
operands and the cotangents that come back into them rounded to fewer
significant bits) in the program's place, each put through
``trunk_check.check``, the harness's own comparison, and each has to come out
NOT ok; the program as it is has to come out ok.

The machinery is ``test_nemotron3_nano_a3b_faults.py``'s (``build``,
``reading``, ``following``, the rounding, the reference in the program's
place), unedited: that file is loaded here by path as a module of this
file's own and told this configuration's name and mix, as
``test_solar_open2_250b_faults.py`` does. What is this file's: the four
faults, and the control's walk (``eval_rounded_through_layers``: solar's
control, which rounds products in the cotangents' direction as well, walking
into a ``jax.checkpoint`` as into a call, because this reference puts every
layer under one).

As a test (``pytest benchmark/tests``, and the tier-1 suite imports it): CPU,
float32, the configuration's rehearsal widths, seeded weights; the control
rounds to bfloat16's 8 significant bits (one precision under the float32 the
CPU computes in).

As a script, on the chip at the PUBLISHED widths, after ``--steps`` steps of
the cell's own training (an untrained router's top-k lies inside bfloat16's
reach), it prints each reading beside its limit; there the control rounds to
float8's 4 significant bits (one precision under the bfloat16 the chip
computes in), and the bfloat16 rounding is read beside it for what rounding
alone gives::

    python3 benchmark/tests/test_kimi_linear_48b_a3b_faults.py --seed 13 --steps 60

(``--only PART`` reads only the readings whose name holds PART;
``--size-only PART`` reads the cases whose name holds PART with the
reference following the routing alone.)

PERF.md section 6 has the chip's readings.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import importlib.util
import json
import sys
import types
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))

import pytest  # noqa: E402

import common  # noqa: E402

CONFIG = "kimi_linear_48b_a3b"
MIX = "ewt10_16x256"
ROPE_THETA = 10000.0  # config.json's rope_theta, which the published attention does not read


def _machinery():
    path = Path(__file__).with_name("test_nemotron3_nano_a3b_faults.py")
    spec = importlib.util.spec_from_file_location("kimi_linear_48b_a3b_faults_machinery", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    module.CONFIG, module.MIX = CONFIG, MIX
    return module


BASE = _machinery()
_patched = BASE._patched


# ---- the faults: each patches the program, and is undone on the way out ----------------


@contextlib.contextmanager
def rotary_left_on():
    """The ``L`` layer rotates the query's and the shared key's 64 channels
    by each word's position (``kanana2_a3b``'s rotary path, theta 10,000):
    ``mla_use_nope`` not honoured."""
    import jax.numpy as jnp

    from spacy_ray_tpu.models import hybrid_ssm

    real = hybrid_ssm.latent_attention

    def rotating(p, h, mask, positions, s, cd, rotary=False):
        B, T = mask.shape
        at = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], (B, T))
        with_theta = types.SimpleNamespace(**dataclasses.asdict(s), rope_theta=ROPE_THETA)
        return real(p, h, mask, at, with_theta, cd, rotary=True)

    with _patched(hybrid_ssm, "latent_attention", rotating):
        yield


@contextlib.contextmanager
def beta_doubled():
    """``beta = 2 sigmoid(h W_beta)``: a transition's eigenvalues reach -1,
    which this configuration does not ask for."""
    from spacy_ray_tpu.models import hybrid_ssm

    real = hybrid_ssm.kda_mixer
    with _patched(hybrid_ssm, "kda_mixer", lambda p, h, s, cd: real(
            p, h, dataclasses.replace(s, kda_neg_eigval=True), cd)):
        yield


@contextlib.contextmanager
def dense_layer_routed():
    """The leading dense layer taken for a routed one: its FFN goes through
    the expert dispatch as the one expert every word chooses, so it is
    weighted by ``routed_scaling_factor`` (2.446) and padding gets none."""
    import jax.numpy as jnp

    from spacy_ray_tpu.models import hybrid_ssm, latent_moe

    real = hybrid_ssm.apply_layer

    def routing(p, x, mask, *, kind, s, cd):
        if kind != hybrid_ssm.DENSE:
            return real(p, x, mask, kind=kind, s=s, cd=cd)
        B, T, D = x.shape
        h = latent_moe.rms_norm(x, p["norm_g"], s.rms_eps).reshape(B * T, D)
        one = dataclasses.replace(s, n_experts=1, experts_held=1, expert_rank=0, top_k=1)
        experts = {"eg_W": p["g_W"][None], "eu_W": p["u_W"][None], "ed_W": p["d_W"][None]}
        y, _ = latent_moe.routed_experts(
            experts, h, mask.reshape(B * T), jnp.zeros((B * T, 1), jnp.int32),
            jnp.full((B * T, 1), s.route_scale, jnp.float32), one, cd)
        return x + y.reshape(B, T, D), None, None

    with _patched(hybrid_ssm, "apply_layer", routing):
        yield


@contextlib.contextmanager
def latent_norm_skipped():
    """The latent ``c`` goes to ``W_kvb`` as it comes out of ``W_kva``, times
    its gain: no RMSNorm over its 512 channels."""
    import jax.numpy as jnp

    from spacy_ray_tpu.models import hybrid_ssm, latent_moe

    real = hybrid_ssm.latent_attention

    def unnormed(*args, **kwargs):
        # latent_attention's only norm is the latent's
        with _patched(latent_moe, "rms_norm", lambda x, g, eps: x.astype(jnp.float32) * g):
            return real(*args, **kwargs)

    with _patched(hybrid_ssm, "latent_attention", unnormed):
        yield


# ---- the control: solar's, walking into the reference's checkpointed layers too -----------


def _called(eqn):
    """(jaxpr, consts) of an equation the control walks into: a call (as the
    machinery walks) or a ``jax.checkpoint`` (the primitive ``remat2`` in this
    JAX, ``checkpoint`` in others), under which this reference puts every
    layer; None for any other."""
    inner = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
    if eqn.primitive.name in ("pjit", "jit", "custom_jvp_call") and inner is not None:
        return inner.jaxpr, inner.consts
    if eqn.primitive.name in ("checkpoint", "remat2"):
        return inner, ()
    return None


def eval_rounded_through_layers(jaxpr, consts, args, bits: int, router_shape):
    """``test_solar_open2_250b_faults.eval_rounded_both_ways`` (the operands of
    every ``dot_general`` and the cotangent that comes back into it rounded,
    the router's product apart, a ``scan`` bound as it stands), walking into a
    checkpointed layer as into a call: otherwise the layers of this reference,
    each under ``jax.checkpoint``, would be bound as they stand and nothing
    in them rounded."""
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
        called = _called(eqn)
        if called is not None:
            outs = eval_rounded_through_layers(*called, vals, bits, router_shape)
        elif eqn.primitive.name == "dot_general" and tuple(vals[1].shape) != tuple(router_shape):
            vals = [BASE.round_significand(v, bits) for v in vals]
            outs = [cotangent_rounded(eqn.primitive.bind(*vals, **eqn.params), bits)]
        else:
            outs = eqn.primitive.bind(*vals, **eqn.params)
            outs = outs if eqn.primitive.multiple_results else [outs]
        env.update(zip(eqn.outvars, outs))
    return [read(v) for v in jaxpr.outvars]


@contextlib.contextmanager
def reference_rounded(nlp, bits: int):
    with _patched(BASE, "eval_rounded", eval_rounded_through_layers), \
            BASE.reference_in_the_programs_place(nlp, bits):
        yield


def cases(nlp, control_bits: int):
    """name -> a context manager under which ``trunk_check.check`` has to fail."""
    return {
        f"control: the reference, operands and cotangents of {control_bits + 1} significant bits":
            lambda: reference_rounded(nlp, control_bits),
        "rotary left on in L": rotary_left_on,
        "beta doubled": beta_doubled,
        "the dense layer routed": dense_layer_routed,
        "the latent norm skipped": latent_norm_skipped,
    }


# ---- as a test -------------------------------------------------------------------------
# (no fixture: the tier-1 suite imports the ``test_`` names of this file by path)

SEED = 5
NAMES = ["rotary left on in L", "beta doubled", "the dense layer routed", "the latent norm skipped",
         "control"]


@functools.lru_cache(maxsize=1)
def _built():
    nlp, params, docs = BASE.build(SEED, published=False, steps=0)
    return nlp, params, docs, cases(nlp, BASE.BF16_BITS)


def test_the_latent_attention_program_as_it_is_passes():
    nlp, params, docs, _ = _built()
    got = BASE.reading(nlp, params, docs, SEED)
    assert got["ok"] and got["rel_err"] <= got["tolerance"]
    assert got["grad_rel_err"] <= got["grad_tolerance"]


@contextlib.contextmanager
def forward_alone():
    """The reference as ``trunk_check`` loads it, without its gradient limits
    and handed the forward program's choices alone: the check then compares
    the forward alone. On the CPU every case below fails the forward limit by
    orders of magnitude, and the gradient programs are most of a reading's
    compile time; the program as it is is read with both."""
    real = common.load_module

    def load(kind, name):
        module = real(kind, name)
        if (kind, name) == ("reference", CONFIG):
            for limit in ("GRAD_TOLERANCE", "GRAD_TOLERANCE_F32"):
                vars(module).pop(limit, None)
            module.make_inputs = functools.partial(module.make_inputs, gradient_choices=False)
        return module

    with _patched(common, "load_module", load):
        yield


@pytest.mark.parametrize("name", NAMES)
def test_a_fault_planted_in_the_latent_attention_trunk_and_the_control_fail_the_comparison(name):
    """Each fault's SIZE, the tie rule out of the way (each lies upstream of a
    router, whose choices it moves): over the forward limit, not NaN."""
    nlp, params, docs, planted = _built()
    key = next(k for k in planted if k.startswith(name))
    with forward_alone():
        sized = BASE.reading(nlp, params, docs, SEED, planted[key], follow=True)
    assert not sized["ok"] and sized["rel_err"] == sized["rel_err"]
    assert sized["rel_err"] > sized["tolerance"] and sized["grad_rel_err"] is None
    assert "forward" not in vars(nlp.components[nlp.tok2vec_name])  # the patch is gone


if __name__ == "__main__":
    import argparse

    parser = argparse.ArgumentParser()
    parser.add_argument("--seed", type=int, default=13)
    parser.add_argument("--steps", type=int, default=60)
    parser.add_argument("--rehearse-cpu", action="store_true")
    parser.add_argument("--only", default="", help="read only the readings whose name holds this")
    parser.add_argument("--size-only", default=None, metavar="PART",
                        help="read the cases whose name holds this with the reference following "
                             "the routing alone (their reading as the cell runs it already known)")
    args = parser.parse_args()
    common.start_jax(1, args.rehearse_cpu)
    nlp, params, docs = BASE.build(args.seed, published=not args.rehearse_cpu, steps=args.steps)

    def show(what, **got):
        print("READING " + json.dumps({"what": what, "seed": args.seed, **got}), flush=True)

    if args.only in "the program as it is":
        show("the program as it is", **BASE.reading(nlp, params, docs, args.seed))
    planted = cases(nlp, BASE.BF16_BITS if args.rehearse_cpu else BASE.FLOAT8_BITS)
    if not args.rehearse_cpu:  # what bfloat16's rounding alone gives, beside the sound readings
        planted["the reference, operands and cotangents of 8 significant bits (bfloat16: no "
                "control on the chip)"] = lambda: reference_rounded(nlp, BASE.BF16_BITS)
    for name, plant in planted.items():
        if args.only not in name:
            continue
        sized_alone = args.size_only is not None and args.size_only in name
        got = {"rel_err": float("nan")} if sized_alone else BASE.reading(
            nlp, params, docs, args.seed, plant)
        if not sized_alone:
            show(name, **got)
        if got["rel_err"] != got["rel_err"]:  # NaN: the tie rule refused; read the size too
            show(name + ", the reference following its routing",
                 **BASE.reading(nlp, params, docs, args.seed, plant, follow=True))
    print("done", flush=True)
