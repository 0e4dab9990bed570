"""The comparison that decides ``correct`` for ``kanana2_a3b`` bites: four
faults planted in the PROGRAM from outside (the reference untouched), and a
control (the plain reference itself with every matrix product's operands
rounded to float8's four significant bits) in the program's place, each put
through ``trunk_check.check``, the harness's own comparison, and each has to
come out NOT ok; the program as it is has to come out ok.

As a test (``pytest benchmark/tests``, and the tier-1 suite imports it): CPU,
float32, the configuration's rehearsal widths, seeded weights.

As a script, on the chip at the PUBLISHED widths, after ``--steps`` steps of the
cell's own training (an untrained router's top-k lies inside bfloat16's reach:
``reference/kanana2_a3b.py``), it prints each reading beside its limit::

    python3 benchmark/tests/test_kanana2_a3b_faults.py --seed 13 --steps 60

A fault upstream of a router moves the router's choices, and the reference's
tie rule then refuses the run (NaN: not ok, by the forward limit). To read how
LARGE a fault is beside ``TOLERANCE`` and ``GRAD_TOLERANCE``, each case is read
a second time with the reference made to FOLLOW the system's routing
everywhere (``following``). PERF.md section 6 (PR 27) has the chip's readings.
"""

from __future__ import annotations

import contextlib
import functools
import json
import sys
import tempfile
from dataclasses import replace
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))

import pytest  # noqa: E402

import common  # noqa: E402
import trunk_check  # noqa: E402

CONFIG = "kanana2_a3b"
MIX = "ewt10_b3k5"
CONTROL_BITS = 3  # float8_e4m3's mantissa; bfloat16 has 7


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

    work = Path(tempfile.mkdtemp(prefix="kanana2_a3b_faults_"))
    common.write_jsonl(work / "train.jsonl", generator.generate(40 * steps, seed, traffic["docs"]))
    common.write_jsonl(work / "dev.jsonl", generator.generate(16, seed + 1, traffic["docs"]))
    overrides = {**traffic["overrides"], "paths.train": str(work / "train.jsonl"),
                 "paths.dev": str(work / "dev.jsonl"), "training.seed": seed,
                 "training.eval_frequency": 10 ** 9, "training.max_steps": steps}
    nlp, result = loop.train(load_config(program_config, overrides, interpolate=False),
                             n_workers=1, stdout_log=False)
    print("trained", result.final_step, "steps;", {k: result.resolved.get(k)
                                                   for k in ("moe", "moe_dropped")}, flush=True)
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
def expert_dropped(victim: int):
    """"Hold 15": the term of one held expert is left out of every word's sum."""
    import jax.numpy as jnp

    from spacy_ray_tpu.models import latent_moe

    real = latent_moe.routed_experts

    def fifteen(p, h, token_mask, idx, weights, s, cd):
        return real(p, h, token_mask, idx, jnp.where(idx == victim, 0.0, weights), s, cd)

    with _patched(latent_moe, "routed_experts", fifteen):
        yield


@contextlib.contextmanager
def scaling_left_out():
    """The routed scaling factor (2.448) taken for 1."""
    from spacy_ray_tpu.models import latent_moe

    real = latent_moe.route
    with _patched(latent_moe, "route", lambda p, h, s: real(p, h, replace(s, route_scale=1.0))):
        yield


@contextlib.contextmanager
def causal_mask_left_out():
    from spacy_ray_tpu.ops import flash_attention

    real = flash_attention.attention
    with _patched(flash_attention, "attention",
                  lambda q, k, v, mask, causal=False: real(q, k, v, mask, causal=False)):
        yield


@contextlib.contextmanager
def shared_key_not_rotated():
    """The ONE rotary key all heads share ([B, T, 1, d]) goes in unrotated."""
    import jax.numpy as jnp

    from spacy_ray_tpu.models import latent_moe

    real = latent_moe.rope

    def rope(x, positions, theta):
        return x.astype(jnp.float32) if x.shape[2] == 1 else real(x, positions, theta)

    with _patched(latent_moe, "rope", rope):
        yield


def most_used_held_expert(nlp, params, docs) -> int:
    """A fault in an expert no word is sent to cannot be seen in any output:
    drop the held expert these sequences use most."""
    import jax
    import numpy as np

    from spacy_ray_tpu.pipeline.doc import Example
    from spacy_ray_tpu.training.corpus import _doc_from_json

    name = nlp.tok2vec_name
    trunk = nlp.components[name].model
    shape = trunk.meta["shape"]
    tokens = nlp.collate([Example.from_gold(_doc_from_json(d)) for d in docs],
                         with_targets=False)["tokens"]
    choices = np.asarray(jax.jit(trunk.meta["routing_choices"])(params[name], tokens))
    loads = np.bincount(choices[:, np.asarray(tokens.mask)].reshape(-1), minlength=shape.n_experts)
    held = loads[shape.held_from:shape.held_from + shape.experts_held]
    print("held experts' loads on these sequences", held.tolist(), flush=True)
    return shape.held_from + int(np.argmax(held))


# ---- the control: the reference itself, its products' operands rounded -----------------


def round_significand(x, bits: int):
    """float32 rounded (to nearest, ties to even) to ``bits`` explicit
    significand bits, with float32's exponent range: float8_e4m3's precision
    (bits = 3) or bfloat16's (7) without e4m3's overflow at 448."""
    import jax
    import jax.numpy as jnp

    drop = 23 - bits
    x = x.astype(jnp.float32)
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    u = (u + jnp.uint32((1 << (drop - 1)) - 1) + ((u >> drop) & 1)) & jnp.uint32(
        ~((1 << drop) - 1) & 0xFFFFFFFF)
    # the gradient passes straight through the rounding
    return x + jax.lax.stop_gradient(jax.lax.bitcast_convert_type(u, jnp.float32) - x)


def eval_rounded(jaxpr, consts, args, bits: int, router_width: int):
    """Evaluate a jaxpr with the operands of every ``dot_general`` rounded,
    the router's product (``[., n_experts]``, float32 in the program too) apart."""
    from jax.extend import core as jcore

    env = dict(zip(jaxpr.constvars, consts))
    env.update(zip(jaxpr.invars, args))

    def read(v):
        return v.val if isinstance(v, jcore.Literal) else env[v]

    for eqn in jaxpr.eqns:
        vals = [read(v) for v in eqn.invars]
        inner = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
        if eqn.primitive.name in ("pjit", "jit", "custom_jvp_call") and inner is not None:
            outs = eval_rounded(inner.jaxpr, inner.consts, vals, bits, router_width)
        else:
            if eqn.primitive.name == "dot_general" and not (
                    vals[1].ndim == 2 and vals[1].shape[-1] == router_width):
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
    width = trunk.model.meta["shape"].n_experts

    def forward(params, tokens, ctx):
        reference = trunk_check.load_module("reference", CONFIG)  # as ``check`` loads it
        inputs = reference.make_inputs(nlp, jax.lax.stop_gradient(params), tokens)
        closed = jax.make_jaxpr(lambda p: reference.forward(p, *inputs))(params)
        out, = eval_rounded(closed.jaxpr, closed.consts,
                            jax.tree_util.tree_leaves(params), bits, width)
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


def cases(nlp, params, docs):
    """name -> a context manager under which ``trunk_check.check`` has to fail."""
    victim = most_used_held_expert(nlp, params, docs)
    return {
        f"control: the reference, operands of {CONTROL_BITS + 1} significant bits":
            lambda: reference_in_the_programs_place(nlp, CONTROL_BITS),
        "a held expert dropped": lambda: expert_dropped(victim),
        "the routed scaling left out": scaling_left_out,
        "the shared rotary key not rotated": shared_key_not_rotated,
        "no causal mask": causal_mask_left_out,
    }


def reading(nlp, params, docs, seed, planted=contextlib.nullcontext, follow=False):
    with planted(), (following() if follow else contextlib.nullcontext()):
        out = trunk_check.check(nlp, params, CONFIG, docs, seed)
    return {k: out.get(k) for k in ("ok", "rel_err", "tolerance", "grad_rel_err",
                                    "grad_tolerance", "grad_worst_leaf", "grad_norm_gap")}


# ---- as a test -------------------------------------------------------------------------
# (no fixture: the tier-1 suite imports the ``test_`` names of this file by path)

SEED = 5
NAMES = ["a held expert dropped", "the routed scaling left out", "no causal mask",
         "the shared rotary key not rotated", "control"]


@functools.lru_cache(maxsize=1)
def _built():
    nlp, params, docs = build(SEED, published=False, steps=0)
    return nlp, params, docs, cases(nlp, params, docs)


def test_the_program_as_it_is_passes():
    nlp, params, docs, _ = _built()
    got = reading(nlp, params, docs, SEED)
    assert got["ok"] and got["rel_err"] <= got["tolerance"]
    assert got["grad_rel_err"] <= got["grad_tolerance"]


@pytest.mark.parametrize("name", NAMES)
def test_a_planted_fault_and_the_control_fail_the_comparison(name):
    nlp, params, docs, planted = _built()
    key = next(k for k in planted if k.startswith(name))
    assert not reading(nlp, params, docs, SEED, planted[key])["ok"]
    # and its size, the tie rule out of the way: over a limit, not NaN
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
    for name, planted in cases(nlp, params, docs).items():
        got = reading(nlp, params, docs, args.seed, planted)
        show(name, **got)
        if got["rel_err"] != got["rel_err"]:  # NaN: the tie rule refused; read the size too
            show(name + ", the reference following its routing",
                 **reading(nlp, params, docs, args.seed, planted, follow=True))
    print("done", flush=True)
