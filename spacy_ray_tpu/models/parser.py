"""TransitionBasedParser architecture: state2vec MLP + on-device greedy decode.

Capability parity with spaCy's ``TransitionBasedParser.v2`` architecture
(the model of the reference's parser/NER pipes, trained via reference
worker.py:91/176-189; native Cython ``nn_parser.pyx`` machinery per
SURVEY.md §2.3). TPU-first design per SURVEY.md §7 option (a):

* TRAINING: zero dynamic control flow. The host precomputes teacher-forced
  state features (pipeline/transition.py); the model is
  ``gather token vectors at [B, S, F] indices → maxout hidden → linear
  actions`` — two large batched MXU matmuls over the whole doc×step grid.
* DECODE (parser): fixed-length ``lax.scan`` arc-eager state machine with
  masked-action argmax — stacks/buffers/heads as dense int arrays, jnp ops
  only, vectorized over the batch.
* DECODE (NER): BILUO logits are position-only, so they're one batched
  matmul; the scan only walks the constraint automaton (open-entity state)
  over precomputed logits.

Action encodings follow pipeline/transition.py (parser) and
pipeline/components/ner.py (BILUO).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..registry import registry
from ..pipeline import transition as T
from ..types import Padded
from .core import Context, Model, glorot_uniform
from ..ops import ops as O

PARSER_N_FEATURES = T.N_FEATURES
NER_N_FEATURES = 5  # token window [t-2, t-1, t, t+1, t+2]


def ner_window_features(Tlen: int, lengths):
    """[B, T, 5] int32 window indices [t-2 .. t+2], -1 outside [0, length).

    Single source of truth for the NER feature layout. The type of
    ``lengths`` picks the array module: a ``numpy.ndarray`` (the training
    targets, ``NERComponent.make_targets`` on the collate thread) is
    answered in NumPy on the host and dispatches nothing to the device;
    anything else (the tracer of ``NERComponent.forward`` under jit: decode,
    ``evaluate``, serving; a ``jax.Array``) is answered in ``jax.numpy`` as
    part of the caller's program. Same values either way (tested).
    """
    xp = np if isinstance(lengths, np.ndarray) else jnp
    grid = (
        xp.arange(Tlen, dtype=xp.int32)[None, :, None]
        + xp.array([-2, -1, 0, 1, 2], dtype=xp.int32)[None, None, :]
    )
    lengths = xp.asarray(lengths)
    return xp.where((grid >= 0) & (grid < lengths[:, None, None]), grid, -1)


# HBM budget for the one-hot operand ([*feats.shape, T] elements, live
# across fwd+bwd as an einsum residual) — beyond it the vmap gather wins
ONEHOT_GATHER_MAX_BYTES = 128 * 1024 * 1024


def _gather(X: jnp.ndarray, feats: jnp.ndarray) -> jnp.ndarray:
    """X [B, T, D], feats [B, S, F] -> [B, S, F, D], -1 slots zeroed.

    On TPU a batched row gather lowers to serialized dynamic-slices; for
    the doc-length Ts this model sees, re-expressing it as a one-hot
    einsum puts the work on the MXU instead (the standard TPU gather
    rewrite: B*S*F*T*D MACs, trivially saturating the systolic array,
    and -1 slots fall out as all-zero one-hot rows — no separate mask).
    """
    Tlen = X.shape[1]
    onehot_bytes = feats.size * Tlen * X.dtype.itemsize
    if onehot_bytes <= ONEHOT_GATHER_MAX_BYTES and jax.default_backend() == "tpu":
        # one_hot(-1) == all zeros, so invalid slots zero themselves.
        # feats may be [B, S, F] (training grid) or [B, F] (decode step):
        # the ellipsis spans whatever lies between batch and the T axis.
        onehot = jax.nn.one_hot(feats, Tlen, dtype=X.dtype)  # [B, ..., T]
        return jnp.einsum("b...t,btd->b...d", onehot, X)
    safe = jnp.clip(feats, 0, Tlen - 1).astype(jnp.int32)

    def per_row(Xrow, frow):  # [T, D], [S, F]
        return Xrow[frow]  # [S, F, D]

    out = jax.vmap(per_row)(X, safe)
    mask = (feats >= 0)[..., None].astype(X.dtype)
    return out * mask


class ParserModelFns:
    """Pure functions bound to static dims; stored in Model.meta."""

    def __init__(self, n_feats: int, width: int, hidden: int, pieces: int, n_actions: int):
        self.n_feats = n_feats
        self.width = width
        self.hidden = hidden
        self.pieces = pieces
        self.n_actions = n_actions

    def init(self, rng: jax.Array) -> Dict[str, Any]:
        r1, r2, r3 = jax.random.split(rng, 3)
        return {
            "hidden_W": glorot_uniform(r1, (self.n_feats * self.width, self.hidden * self.pieces)),
            "hidden_b": jnp.zeros((self.hidden, self.pieces)),
            "out_W": glorot_uniform(r2, (self.hidden, self.n_actions)),
            "out_b": jnp.zeros((self.n_actions,)),
        }

    def logits(self, params: Dict[str, Any], state_vecs: jnp.ndarray) -> jnp.ndarray:
        """state_vecs [..., F*D] -> [..., n_actions]."""
        h = O.maxout(state_vecs, params["hidden_W"], params["hidden_b"])
        return h @ params["out_W"] + params["out_b"]

    def step_logits(self, params, X, feats):
        """X [B,T,D], feats [B,S,F] -> [B,S,nA] (training path, fully batched)."""
        vecs = _gather(X, feats)  # [B, S, F, D]
        B, S = vecs.shape[:2]
        flat = vecs.reshape(B, S, self.n_feats * self.width)
        return self.logits(params, flat)


@registry.architectures("spacy.TransitionBasedParser.v1")
@registry.architectures("spacy.TransitionBasedParser.v2")
def TransitionBasedParser(
    tok2vec: Model,
    state_type: str = "parser",
    extra_state_tokens: bool = False,
    hidden_width: int = 64,
    maxout_pieces: int = 2,
    use_upper: bool = True,
    nO: Optional[int] = None,
) -> Model:
    """nO = number of actions (injected at Pipeline.initialize from labels)."""
    width = tok2vec.dims.get("nO")
    n_feats = PARSER_N_FEATURES if state_type == "parser" else NER_N_FEATURES
    n_act = nO if nO else 3
    fns = ParserModelFns(n_feats, width, hidden_width, maxout_pieces, n_act)

    def init_fn(rng):
        r1, r2 = jax.random.split(rng)
        return {"tok2vec": tok2vec.init(r1), "upper": fns.init(r2)}

    def apply_fn(params, x, ctx: Context):
        """x = (inputs_for_tok2vec, feats [B,S,F]) -> [B,S,nA] logits."""
        inputs, feats = x
        t2v: Padded = tok2vec.apply(params.get("tok2vec", {}), inputs, ctx)
        return fns.step_logits(params["upper"], t2v.X, feats)

    has_listener = any(m.meta.get("listener") for m in tok2vec.walk())
    m = Model(
        f"transition_model_{state_type}",
        init_fn,
        apply_fn,
        dims={"nO": n_act, "width": width, "hidden": hidden_width, "n_feats": n_feats},
        layers=[tok2vec],
        meta={
            "has_listener": has_listener,
            "state_type": state_type,
            "fns": fns,
        },
    )
    return m


# ----------------------------------------------------------------------
# Device decode: arc-eager greedy under lax.scan
# ----------------------------------------------------------------------


def _arc_eager_machine(Tlen: int, lengths_n: jnp.ndarray, n_labels: int, n_act: int):
    """Vectorized arc-eager state machine over a leading dim N (= batch for
    greedy decode, batch*beam for beam decode). Returns the state ops as a
    dict of pure functions."""
    N = lengths_n.shape[0]
    nidx = jnp.arange(N)

    def init_state():
        return {
            "stack": jnp.full((N, Tlen + 1), -1, jnp.int32),
            "sp": jnp.zeros((N,), jnp.int32),
            "buf": jnp.zeros((N,), jnp.int32),
            "heads": jnp.full((N, Tlen), -2, jnp.int32),
            "labels": jnp.zeros((N, Tlen), jnp.int32),
            "lc0": jnp.full((N, Tlen), -1, jnp.int32),
            "lc1": jnp.full((N, Tlen), -1, jnp.int32),
            "rc0": jnp.full((N, Tlen), -1, jnp.int32),
            "rc1": jnp.full((N, Tlen), -1, jnp.int32),
        }

    def peek(st, depth):
        idx = st["sp"] - depth
        ok = idx >= 1
        return jnp.where(ok, st["stack"][nidx, jnp.clip(idx - 1, 0, Tlen)], -1)

    def features(st):
        s0 = peek(st, 0)
        s1 = peek(st, 1)
        s2 = peek(st, 2)
        b = st["buf"]
        b0 = jnp.where(b < lengths_n, b, -1)
        b1 = jnp.where(b + 1 < lengths_n, b + 1, -1)
        b2 = jnp.where(b + 2 < lengths_n, b + 2, -1)
        s0c = jnp.clip(s0, 0, Tlen - 1)
        s1c = jnp.clip(s1, 0, Tlen - 1)
        s0l = jnp.where(s0 >= 0, st["lc0"][nidx, s0c], -1)
        s0r = jnp.where(s0 >= 0, st["rc0"][nidx, s0c], -1)
        s1l = jnp.where(s1 >= 0, st["lc0"][nidx, s1c], -1)
        s1r = jnp.where(s1 >= 0, st["rc0"][nidx, s1c], -1)
        s0l2 = jnp.where(s0 >= 0, st["lc1"][nidx, s0c], -1)
        s0r2 = jnp.where(s0 >= 0, st["rc1"][nidx, s0c], -1)
        return jnp.stack(
            [s0, s1, s2, b0, b1, b2, s0l, s0r, s1l, s1r, s0l2, s0r2], axis=1
        )  # [N, 12]

    def valid_mask(st):
        has_b0 = st["buf"] < lengths_n
        has_s0 = st["sp"] >= 1
        s0 = peek(st, 0)
        s0c = jnp.clip(s0, 0, Tlen - 1)
        s0_has_head = has_s0 & (st["heads"][nidx, s0c] != -2)
        shift_ok = has_b0
        # cleanup: when buffer is empty, REDUCE pops anything (ROOT-escape)
        reduce_ok = (has_s0 & s0_has_head) | (has_s0 & ~has_b0)
        la_ok = has_s0 & has_b0 & ~s0_has_head
        ra_ok = has_s0 & has_b0
        mask = jnp.zeros((N, n_act), bool)
        mask = mask.at[:, T.SHIFT].set(shift_ok)
        mask = mask.at[:, T.REDUCE].set(reduce_ok)
        la_cols = 2 + 2 * jnp.arange(n_labels)
        ra_cols = 3 + 2 * jnp.arange(n_labels)
        mask = mask.at[:, la_cols].set(la_ok[:, None])
        mask = mask.at[:, ra_cols].set(ra_ok[:, None])
        return mask

    def apply_action(st, action, active):
        is_shift = (action == T.SHIFT) & active
        is_reduce = (action == T.REDUCE) & active
        arc = action >= 2
        is_la = arc & ((action - 2) % 2 == 0) & active
        is_ra = arc & ((action - 2) % 2 == 1) & active
        label = jnp.where(arc, (action - 2) // 2, 0).astype(jnp.int32)
        s0 = peek(st, 0)
        s0c = jnp.clip(s0, 0, Tlen - 1)
        b0 = st["buf"]
        b0c = jnp.clip(b0, 0, Tlen - 1)

        push = is_shift | is_ra
        pop = is_reduce | is_la

        # ROOT-escape on REDUCE of a headless token
        s0_headless = st["heads"][nidx, s0c] == -2
        heads = st["heads"]
        heads = heads.at[nidx, s0c].set(
            jnp.where(
                is_reduce & s0_headless & (s0 >= 0), -1, heads[nidx, s0c]
            )
        )
        # LEFT-ARC: head(s0) = b0
        heads = heads.at[nidx, s0c].set(
            jnp.where(is_la & (s0 >= 0), b0, heads[nidx, s0c])
        )
        labels_arr = st["labels"]
        labels_arr = labels_arr.at[nidx, s0c].set(
            jnp.where(is_la & (s0 >= 0), label, labels_arr[nidx, s0c])
        )
        # RIGHT-ARC: head(b0) = s0 (or ROOT if stack empty — masked anyway)
        ra_head = jnp.where(st["sp"] >= 1, s0, -1)
        heads = heads.at[nidx, b0c].set(
            jnp.where(is_ra, ra_head, heads[nidx, b0c])
        )
        labels_arr = labels_arr.at[nidx, b0c].set(
            jnp.where(is_ra, label, labels_arr[nidx, b0c])
        )

        # child bookkeeping (dep < head -> left chain, else right chain)
        def upd_children(lc0, lc1, rc0, rc1, head, dep, on):
            hc = jnp.clip(head, 0, Tlen - 1)
            left = dep < head
            old_l0 = lc0[nidx, hc]
            new_l0 = jnp.where(on & left & ((old_l0 == -1) | (dep < old_l0)), dep, old_l0)
            new_l1 = jnp.where(
                on & left & ((old_l0 == -1) | (dep < old_l0)), old_l0, lc1[nidx, hc]
            )
            new_l1 = jnp.where(
                on & left & ~((old_l0 == -1) | (dep < old_l0))
                & ((lc1[nidx, hc] == -1) | (dep < lc1[nidx, hc])),
                dep,
                new_l1,
            )
            old_r0 = rc0[nidx, hc]
            new_r0 = jnp.where(on & ~left & ((old_r0 == -1) | (dep > old_r0)), dep, old_r0)
            new_r1 = jnp.where(
                on & ~left & ((old_r0 == -1) | (dep > old_r0)), old_r0, rc1[nidx, hc]
            )
            new_r1 = jnp.where(
                on & ~left & ~((old_r0 == -1) | (dep > old_r0))
                & ((rc1[nidx, hc] == -1) | (dep > rc1[nidx, hc])),
                dep,
                new_r1,
            )
            on_h = on & (head >= 0)
            lc0 = lc0.at[nidx, hc].set(jnp.where(on_h, new_l0, lc0[nidx, hc]))
            lc1 = lc1.at[nidx, hc].set(jnp.where(on_h, new_l1, lc1[nidx, hc]))
            rc0 = rc0.at[nidx, hc].set(jnp.where(on_h, new_r0, rc0[nidx, hc]))
            rc1 = rc1.at[nidx, hc].set(jnp.where(on_h, new_r1, rc1[nidx, hc]))
            return lc0, lc1, rc0, rc1

        lc0, lc1, rc0, rc1 = st["lc0"], st["lc1"], st["rc0"], st["rc1"]
        lc0, lc1, rc0, rc1 = upd_children(lc0, lc1, rc0, rc1, b0, s0, is_la & (s0 >= 0))
        lc0, lc1, rc0, rc1 = upd_children(lc0, lc1, rc0, rc1, ra_head, b0, is_ra)

        sp = st["sp"]
        stack = st["stack"]
        # pop then (maybe) push
        sp_after_pop = jnp.where(pop, sp - 1, sp)
        stack = stack.at[nidx, jnp.clip(sp_after_pop, 0, Tlen)].set(
            jnp.where(push, b0, stack[nidx, jnp.clip(sp_after_pop, 0, Tlen)])
        )
        sp_new = jnp.where(push, sp_after_pop + 1, sp_after_pop)
        buf_new = jnp.where(is_shift | is_ra, st["buf"] + 1, st["buf"])
        return {
            "stack": stack,
            "sp": sp_new,
            "buf": buf_new,
            "heads": heads,
            "labels": labels_arr,
            "lc0": lc0,
            "lc1": lc1,
            "rc0": rc0,
            "rc1": rc1,
        }

    return {
        "init": init_state,
        "features": features,
        "valid_mask": valid_mask,
        "apply_action": apply_action,
    }


def decode_parser(
    fns: ParserModelFns,
    upper_params: Dict[str, Any],
    X: jnp.ndarray,
    lengths: jnp.ndarray,
    n_labels: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Greedy arc-eager decode on device.

    X [B, T, D] tok2vec output; lengths [B] true lengths.
    Returns (heads [B, T] int32 with ROOT as self-index, labels [B, T]).
    """
    B, Tlen, D = X.shape
    n_act = fns.n_actions
    NEG = jnp.float32(-1e9)
    m = _arc_eager_machine(Tlen, lengths, n_labels, n_act)

    def body(st, _):
        done = (st["buf"] >= lengths) & (st["sp"] == 0)
        feats = m["features"](st)  # [B, 12]
        vecs = _gather(X, feats[:, None, :])  # [B, 1, F, D]
        flat = vecs.reshape(B, fns.n_feats * fns.width)
        logits = fns.logits(upper_params, flat)  # [B, nA]
        mask = m["valid_mask"](st)
        masked = jnp.where(mask, logits, NEG)
        action = jnp.argmax(masked, axis=-1).astype(jnp.int32)
        st = m["apply_action"](st, action, ~done)
        return st, None

    n_steps = 2 * Tlen + 2
    final, _ = jax.lax.scan(body, m["init"](), None, length=n_steps)
    heads = final["heads"]
    # ROOT (-1) and never-attached (-2) -> self (Doc convention)
    self_idx = jnp.arange(Tlen)[None, :].repeat(B, axis=0)
    heads = jnp.where(heads < 0, self_idx, heads)
    return heads, final["labels"]


def decode_parser_beam(
    fns: ParserModelFns,
    upper_params: Dict[str, Any],
    X: jnp.ndarray,
    lengths: jnp.ndarray,
    n_labels: int,
    beam_width: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Beam-search arc-eager decode (scored by summed action log-probs).

    The reference ecosystem's parser offers beam alongside greedy; here the
    beam lives as an extra leading dim on the same vectorized state machine
    — states flattened to [B*K], top-k re-selection per step, all under one
    ``lax.scan``.
    """
    K = int(beam_width)
    if K <= 1:
        return decode_parser(fns, upper_params, X, lengths, n_labels)
    B, Tlen, D = X.shape
    n_act = fns.n_actions
    NEG = jnp.float32(-1e9)
    lengths_n = jnp.repeat(lengths, K)  # [B*K]
    m = _arc_eager_machine(Tlen, lengths_n, n_labels, n_act)
    bidx = jnp.arange(B)

    def gather_beams(st, beam_idx):
        """beam_idx [B, K] source-beam per new slot -> reindexed state."""
        flat_src = (bidx[:, None] * K + beam_idx).reshape(-1)  # [B*K]

        return jax.tree_util.tree_map(lambda a: a[flat_src], st)

    def body(carry, _):
        st, scores = carry  # scores [B, K]
        done = ((st["buf"] >= lengths_n) & (st["sp"] == 0)).reshape(B, K)
        feats = m["features"](st)  # [B*K, F]
        # gather against the UN-replicated X: beams of one sentence share it,
        # so fold the beam dim into the feature dim instead of copying X K
        # times ([B, K*F] gather -> [B*K, F, D])
        vecs = _gather(X, feats.reshape(B, K * fns.n_feats))
        flat = vecs.reshape(B * K, fns.n_feats * fns.width)
        logits = fns.logits(upper_params, flat)
        mask = m["valid_mask"](st)
        masked = jnp.where(mask, logits.astype(jnp.float32), NEG)
        logp = jax.nn.log_softmax(masked, axis=-1).reshape(B, K, n_act)
        logp = jnp.where(mask.reshape(B, K, n_act), logp, NEG)
        cand = scores[:, :, None] + logp  # [B, K, nA]
        # finished beams contribute exactly ONE candidate (no-op, action 0)
        # carrying their score forward
        noop = jnp.full((B, K, n_act), NEG)
        noop = noop.at[:, :, 0].set(scores)
        cand = jnp.where(done[:, :, None], noop, cand)
        flat_cand = cand.reshape(B, K * n_act)
        new_scores, top = jax.lax.top_k(flat_cand, K)  # [B, K]
        src_beam = (top // n_act).astype(jnp.int32)
        action = (top % n_act).astype(jnp.int32)
        st = gather_beams(st, src_beam)
        done_sel = jnp.take_along_axis(done, src_beam, axis=1).reshape(-1)
        st = m["apply_action"](st, action.reshape(-1), ~done_sel)
        return (st, new_scores), None

    init_scores = jnp.full((B, K), NEG).at[:, 0].set(0.0)  # identical-beam fix
    n_steps = 2 * Tlen + 2
    (final, scores), _ = jax.lax.scan(
        body, (m["init"](), init_scores), None, length=n_steps
    )
    best = jnp.argmax(scores, axis=1)  # [B]
    flat_best = bidx * K + best
    heads = final["heads"][flat_best]
    labels = final["labels"][flat_best]
    self_idx = jnp.arange(Tlen)[None, :].repeat(B, axis=0)
    heads = jnp.where(heads < 0, self_idx, heads)
    return heads, labels


def decode_biluo_viterbi(
    logits: jnp.ndarray, lengths: jnp.ndarray, n_labels: int
) -> jnp.ndarray:
    """EXACT max-sum decode over the BILUO constraint automaton.

    The automaton has 1 + n_labels states (outside, inside-label-i); the
    chain structure makes exact Viterbi an O(T * n_labels) ``lax.scan`` —
    strictly better than greedy (which can open an entity it later regrets).
    Returns action ids [B, T] (same encoding as ``decode_biluo``).
    """
    B, Tlen, nA = logits.shape
    if n_labels == 0:
        return jnp.zeros((B, Tlen), jnp.int32)
    NEG = jnp.float32(-1e30)
    lab = jnp.arange(n_labels)
    B_cols = 1 + 4 * lab
    I_cols = 2 + 4 * lab
    L_cols = 3 + 4 * lab
    U_cols = 4 + 4 * lab
    lg = logits.astype(jnp.float32)

    def fwd(carry, t):
        dp_out, dp_in = carry  # [B], [B, L]
        sc = lg[:, t, :]  # [B, nA]
        is_last = (t + 1) >= lengths  # [B]
        # entering "outside": stay-O / U-i from outside, or L-i closing i
        stay_o = dp_out + sc[:, 0]
        u_best = dp_out[:, None] + sc[:, U_cols]  # [B, L]
        u_max = jnp.max(u_best, axis=1)
        u_arg = jnp.argmax(u_best, axis=1)
        close = dp_in + sc[:, L_cols]  # [B, L]
        close_max = jnp.max(close, axis=1)
        close_arg = jnp.argmax(close, axis=1)
        out_cands = jnp.stack([stay_o, u_max, close_max], axis=1)
        new_out = jnp.max(out_cands, axis=1)
        out_choice = jnp.argmax(out_cands, axis=1)  # 0=O, 1=U, 2=L
        out_action = jnp.where(
            out_choice == 0,
            0,
            jnp.where(out_choice == 1, U_cols[u_arg], L_cols[close_arg]),
        ).astype(jnp.int32)
        # entering "inside i": B-i from outside (not at last token) or I-i
        # continuing (not at last token — an entity must close by doc end)
        open_i = dp_out[:, None] + sc[:, B_cols]  # [B, L]
        cont_i = dp_in + sc[:, I_cols]
        not_last = ~is_last[:, None]
        open_i = jnp.where(not_last, open_i, NEG)
        cont_i = jnp.where(not_last, cont_i, NEG)
        new_in = jnp.maximum(open_i, cont_i)
        in_action = jnp.where(open_i >= cont_i, B_cols[None, :], I_cols[None, :]).astype(
            jnp.int32
        )
        # inactive (padded) positions carry state through unchanged
        active = (t < lengths)[:, None]
        new_in = jnp.where(active, new_in, dp_in)
        new_out = jnp.where(active[:, 0], new_out, dp_out)
        return (new_out, new_in), (out_action, in_action)

    init = (jnp.zeros((B,), jnp.float32), jnp.full((B, n_labels), NEG))
    (final_out, _), (out_actions, in_actions) = jax.lax.scan(
        fwd, init, jnp.arange(Tlen)
    )
    # out_actions [T, B], in_actions [T, B, L]

    def bwd(state, t):
        # state: current automaton state entering position t from the right
        # (-1 = outside, i = inside label i); emit the action taken AT t
        act_out = out_actions[t]  # [B]
        act_in = jnp.take_along_axis(
            in_actions[t], jnp.clip(state, 0, n_labels - 1)[:, None], axis=1
        )[:, 0]
        outside = state < 0
        action = jnp.where(outside, act_out, act_in)
        active = t < lengths
        action = jnp.where(active, action, 0)
        # previous state (entering position t): determined by the action type
        arc = action >= 1
        kind = jnp.where(arc, (action - 1) % 4, -1)  # 0=B,1=I,2=L,3=U
        label = jnp.where(arc, (action - 1) // 4, 0)
        # B: prev outside; I: prev inside(label); L: prev inside(label);
        # U/O: prev outside
        prev = jnp.where((kind == 1) | (kind == 2), label, -1).astype(jnp.int32)
        prev = jnp.where(active, prev, state)
        return prev, action

    start = jnp.full((B,), -1, jnp.int32)  # sequences must END outside
    _, actions_rev = jax.lax.scan(
        bwd, start, jnp.arange(Tlen - 1, -1, -1)
    )
    return actions_rev[::-1].T  # [B, T]


def decode_biluo(
    logits: jnp.ndarray, lengths: jnp.ndarray, n_labels: int
) -> jnp.ndarray:
    """Constrained greedy BILUO decode over precomputed logits.

    logits [B, T, nA] with action encoding O=0, B=1+4i, I=2+4i, L=3+4i,
    U=4+4i. Returns action ids [B, T]. The scan carries only the
    open-entity automaton state (-1 = outside).
    """
    B, Tlen, nA = logits.shape
    if n_labels == 0:  # no entity labels seen in training data: all-O
        return jnp.zeros((B, Tlen), jnp.int32)
    NEG = jnp.float32(-1e9)
    lab = jnp.arange(n_labels)
    B_cols = 1 + 4 * lab
    I_cols = 2 + 4 * lab
    L_cols = 3 + 4 * lab
    U_cols = 4 + 4 * lab

    bidx = jnp.arange(B)

    def body(open_lab, t):
        lg = logits[:, t, :]  # [B, nA]
        outside = open_lab < 0
        inside = ~outside
        is_last = (t + 1) >= lengths
        mask = jnp.zeros((B, nA), bool)
        # outside: O, U-i always; B-i only if not last token (needs an L)
        mask = mask.at[:, 0].set(outside)
        mask = mask.at[:, U_cols].set(outside[:, None])
        mask = mask.at[:, B_cols].set((outside & ~is_last)[:, None])
        # inside open label k: only I-k (if not last) or L-k
        open_c = jnp.clip(open_lab, 0, n_labels - 1)
        mask = mask.at[bidx, I_cols[open_c]].max(inside & ~is_last)
        mask = mask.at[bidx, L_cols[open_c]].max(inside)
        masked = jnp.where(mask, lg, NEG)
        act = jnp.argmax(masked, axis=-1).astype(jnp.int32)
        # new automaton state
        opens = (act >= 1) & ((act - 1) % 4 == 0)  # B-i
        conts = (act >= 2) & ((act - 2) % 4 == 0)  # I-i
        new_open = jnp.where(opens, (act - 1) // 4, jnp.where(conts, open_lab, -1))
        return new_open, act

    _, actions = jax.lax.scan(body, jnp.full((B,), -1, jnp.int32), jnp.arange(Tlen))
    return actions.T  # [B, T]