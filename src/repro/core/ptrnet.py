"""LSTM pointer network (paper §III-B, Fig. 1b, Alg. 1) in pure JAX.

Encoder: an LSTM digests the embedded node queue ``q`` and produces the
context matrix ``C`` (one d-dim context per node) plus its final latent state,
which seeds the decoder.  Decoder: at each step the LSTM consumes the
embedding of the previously picked node (a trainable ``dec0`` vector at step
0), a *glimpse* attention refines the query against ``C``, and a *pointer*
head scores every node; visited nodes get ``-inf`` logits (Alg. 1), and —
optionally, ``mask_infeasible`` — so do nodes whose parents are not all
scheduled, which makes every emitted sequence a topological order.

Everything is a plain parameter pytree + functional apply, so the whole
decode loop jits and vmaps; the pointer/glimpse inner product is also
implemented as a Pallas TPU kernel (``repro.kernels.ptr``) selected via
``impl=`` for deployment-time inference.

Padded batching: every entry point accepts ``n_valid`` so graphs of
different sizes can share one compiled (bucketed) shape.  The encoder
freezes its latent state after ``n_valid`` rows, the pointer mask excludes
padded slots during the first ``n_valid`` decode steps, and padded steps
contribute exactly zero log-prob/entropy — so the valid prefix of a padded
greedy decode emits the same order as the unpadded decode of the same
graph (log-probs agree up to float-reduction rounding).  The stochastic
decode is pad-invariant too: per-step keys come from ``fold_in`` (not a
length-dependent ``split``) and the categorical draw is an inverse-CDF
pick from one scalar uniform, so a padded sampled decode emits the same
sequence as its unpadded self — which is what lets mixed-size padded RL
training steps reproduce the per-size path exactly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .costmodel import SYS_FEAT_DIM

__all__ = [
    "init_params",
    "encode",
    "decode",
    "greedy_order",
    "sample_order",
    "inverse_cdf_pick",
    "policy_precision",
    "NEG_INF",
]

NEG_INF = -1.0e9


def _glorot(key, shape):
    fan_in, fan_out = shape[0], shape[-1]
    scale = jnp.sqrt(6.0 / (fan_in + fan_out))
    return jax.random.uniform(key, shape, jnp.float32, -scale, scale)


def init_params(key, feat_dim: int, hidden: int = 256,
                sys_feat_dim: int = SYS_FEAT_DIM) -> dict:
    """Parameter pytree for the LSTM-PtrNet (paper: 256-cell LSTMs).

    ``w_sys`` projects the hardware-profile vector
    (:meth:`repro.core.costmodel.PipelineSystem.profile_features`) onto the
    decoder start token — drawn from ``ks[10]``, which earlier revisions
    split off but never consumed, so every pre-existing leaf is
    bit-identical to what the same key produced before the leaf existed.
    Checkpoints saved without ``w_sys`` still load: conditioning is skipped
    when the leaf (or the profile) is absent.
    """
    ks = jax.random.split(key, 12)
    def lstm(k):
        k1, k2 = jax.random.split(k)
        return {
            "wx": _glorot(k1, (hidden, 4 * hidden)),
            "wh": _glorot(k2, (hidden, 4 * hidden)),
            "b": jnp.zeros((4 * hidden,)),
        }
    return {
        "w_in": _glorot(ks[0], (feat_dim, hidden)),
        "b_in": jnp.zeros((hidden,)),
        "enc": lstm(ks[1]),
        "dec": lstm(ks[2]),
        "glimpse": {
            "w_ref": _glorot(ks[3], (hidden, hidden)),
            "w_q": _glorot(ks[4], (hidden, hidden)),
            "v": _glorot(ks[5], (hidden, 1))[:, 0],
        },
        "pointer": {
            "w_ref": _glorot(ks[6], (hidden, hidden)),
            "w_q": _glorot(ks[7], (hidden, hidden)),
            "v": _glorot(ks[8], (hidden, 1))[:, 0],
        },
        "dec0": jax.random.normal(ks[9], (hidden,)) * 0.1,
        "w_sys": _glorot(ks[10], (sys_feat_dim, hidden)),
    }


def _lstm_step(p, x, state):
    h, c = state
    gates = x @ p["wx"] + h @ p["wh"] + p["b"]
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    h = jax.nn.sigmoid(o) * jnp.tanh(c)
    return h, c


@jax.named_scope("encode")
def encode(params, feats, n_valid=None, unroll: int = 1):
    """feats (n, F) -> contexts C (n, H), final (h, c), projected emb (n, H).

    With ``n_valid`` the LSTM state stops updating after the first
    ``n_valid`` rows, so the final state (the decoder seed) equals the one
    an unpadded encode of ``feats[:n_valid]`` would produce.

    ``unroll`` is forwarded to ``lax.scan`` — the per-step math is
    unchanged (identical results), but unrolling slashes the loop
    dispatch overhead that dominates small-``H`` steps on CPU hosts.
    """
    emb = feats @ params["w_in"] + params["b_in"]
    hidden = params["enc"]["wh"].shape[0]
    init = (jnp.zeros(hidden), jnp.zeros(hidden))

    if n_valid is None:

        def step(state, x):
            state = _lstm_step(params["enc"], x, state)
            return state, state[0]

        final, contexts = jax.lax.scan(step, init, emb, unroll=unroll)
    else:
        idx = jnp.arange(emb.shape[0])

        def step(state, xi):
            x, i = xi
            new = _lstm_step(params["enc"], x, state)
            live = i < n_valid
            new = jax.tree.map(
                lambda a, b: jnp.where(live, a, b), new, state)
            return new, new[0]

        final, contexts = jax.lax.scan(step, init, (emb, idx),
                                       unroll=unroll)
    return contexts, final, emb


def _attention_scores(head, C, query):
    """v . tanh(C @ W_ref + query @ W_q) per node — the glimpse/pointer op."""
    return jnp.tanh(C @ head["w_ref"] + query @ head["w_q"]) @ head["v"]


def pointer_logits(params, C, h, mask):
    """One decode step's glimpse + pointer (Alg. 1 lines 3-5); mask True =
    selectable.  Pure-jnp reference shared by the Pallas kernel tests."""
    g_scores = jnp.where(mask, _attention_scores(params["glimpse"], C, h), NEG_INF)
    attn = jax.nn.softmax(g_scores)
    glimpse = attn @ C
    logits = _attention_scores(params["pointer"], C, glimpse)
    return jnp.where(mask, logits, NEG_INF)


def _pointer_logits_hoisted(params, ref_g, ref_p, C, h, mask):
    """`pointer_logits` with the step-invariant ``C @ W_ref`` projections
    precomputed (``ref_g``/``ref_p``).  The projections are the dominant
    matmuls of a decode step and don't depend on the query, so the decode
    scan hoists them — same floating-point ops, same results."""
    g_scores = jnp.where(
        mask, jnp.tanh(ref_g + h @ params["glimpse"]["w_q"])
        @ params["glimpse"]["v"], NEG_INF)
    attn = jax.nn.softmax(g_scores)
    glimpse = attn @ C
    logits = jnp.tanh(ref_p + glimpse @ params["pointer"]["w_q"]) \
        @ params["pointer"]["v"]
    return jnp.where(mask, logits, NEG_INF)


def inverse_cdf_pick(probs, u):
    """Inverse-CDF categorical pick: the first index whose CDF prefix
    exceeds ``u * total``, else the last index with nonzero probability.

    probs: (n, 1) f32 column; u: scalar uniform.  Returns the index as an
    f32 scalar (exact for any realistic n).  The prefix sums are a
    compare-and-sum against a triangular mask rather than ``cumsum``: the
    TPU kernel compiler has no ``cumsum`` lowering, and the scan decode and
    the whole-decode kernel share this one expression so that their
    sampled picks stay bit-identical.
    """
    n = probs.shape[0]
    f32 = jnp.float32
    rows = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    cdf = jnp.sum(jnp.where(rows <= cols, probs, 0.0), axis=0,
                  keepdims=True)                              # (1, n)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1).astype(f32)
    row = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0).astype(f32)
    total = jnp.sum(jnp.where(col == n - 1.0, cdf, 0.0))
    draw = u * total
    idx = jnp.min(jnp.where(cdf > draw, col, f32(n)))
    last_live = jnp.max(jnp.where(probs > 0, row, -1.0))
    return jnp.where(total > draw, idx, last_live)


@jax.named_scope("decode")
def decode(
    params,
    C,
    emb,
    enc_state,
    parent_mat,
    *,
    sample_key=None,
    mask_infeasible: bool = True,
    logits_fn=None,
    n_valid=None,
    unroll: int = 1,
    sys_feat=None,
):
    """Run the full pointing decode (Alg. 1).

    Args:
      C: (n, H) contexts.  emb: (n, H) projected node embeddings.
      enc_state: final encoder (h, c) — initial decoder latent state.
      parent_mat: (n, max_deg) int32 parent indices, -1 padded.
      sample_key: PRNG key -> stochastic decode; None -> greedy (argmax).
      mask_infeasible: additionally mask nodes with unscheduled parents.
      logits_fn: override for the glimpse+pointer op (e.g. Pallas kernel).
      sys_feat: optional hardware-profile vector; when given (and the
        params carry a ``w_sys`` leaf) its projection is added to the
        decoder start token ``dec0``.  None — or a release without
        ``w_sys`` — leaves the decode bit-identical to the unconditioned
        program (uniform systems pass None, not the zero vector, so no
        extra ops enter the trace).
      n_valid: number of real (non-padded) nodes; the first ``n_valid``
        steps only point at real nodes, the remaining steps consume the
        padded slots with zero log-prob/entropy, so ``order[:n_valid]`` is
        a permutation of the real nodes.
      unroll: ``lax.scan`` unroll factor (identical math, fewer loop
        dispatches — the serving engine's CPU fast path).

    Returns: order (n,) int32, logp (n,) per-step log-probs, entropy (n,).
    """
    n = C.shape[0]
    if logits_fn is None:
        ref_g = C @ params["glimpse"]["w_ref"]
        ref_p = C @ params["pointer"]["w_ref"]
        logits_fn = functools.partial(
            _pointer_logits_hoisted, params, ref_g, ref_p)
    # per-step keys via fold_in (NOT split(key, n)): the key of decode step
    # i is independent of the padded length, which is what makes a padded
    # stochastic decode emit the same sequence as its unpadded self.
    keys = (
        jax.vmap(lambda i: jax.random.fold_in(sample_key, i))(jnp.arange(n))
        if sample_key is not None
        else jnp.zeros((n, 2), jnp.uint32)
    )
    valid = None if n_valid is None else jnp.arange(n) < n_valid

    def step(carry, key):
        state, d, visited = carry
        state = _lstm_step(params["dec"], d, state)
        h = state[0]
        mask = ~visited
        if valid is not None:
            mask &= valid
        if mask_infeasible:
            pvisited = jnp.where(parent_mat >= 0, visited[parent_mat.clip(0)], True)
            mask &= pvisited.all(axis=-1)
        if valid is None:
            logits = logits_fn(C, h, mask)
            live = True
        else:
            # once every real node is visited only padded slots remain:
            # drain them (arbitrary unvisited pick) at zero logp/entropy.
            live = mask.any()
            mask = jnp.where(live, mask, ~visited)
            logits = logits_fn(C, h, mask)
        logprobs = jax.nn.log_softmax(logits)
        if sample_key is not None:
            # inverse-CDF categorical draw from ONE scalar uniform.  Masked
            # slots carry exactly-zero probability, so the CDF prefix —
            # and hence the sampled index — is identical for the padded and
            # unpadded decode of the same graph (gumbel-based sampling is
            # not: its noise vector depends on the padded length).
            idx = inverse_cdf_pick(
                jnp.exp(logprobs)[:, None],
                jax.random.uniform(key, ())).astype(jnp.int32)
        else:
            idx = jnp.argmax(logits)
        probs = jnp.exp(logprobs)
        ent = -jnp.sum(jnp.where(probs > 0, probs * logprobs, 0.0))
        lp = logprobs[idx]
        if valid is not None:
            lp = jnp.where(live, lp, 0.0)
            ent = jnp.where(live, ent, 0.0)
        visited = visited.at[idx].set(True)
        return (state, emb[idx], visited), (idx, lp, ent)

    d0 = params["dec0"]
    if sys_feat is not None and "w_sys" in params:
        d0 = d0 + sys_feat @ params["w_sys"]
    init = (enc_state, d0, jnp.zeros(n, bool))
    _, (order, logp, ent) = jax.lax.scan(step, init, keys, unroll=unroll)
    return order.astype(jnp.int32), logp, ent


def policy_precision():
    """Context under which the policy is traced: full-f32 matmuls on every
    backend.  A TPU's default f32 matmul is one bf16 pass, which moves the
    logits of the f32-trained release enough to change greedy picks; dots
    traced under this context carry ``Precision.HIGHEST``, inside the
    Pallas kernels too.  CPU results are unchanged."""
    return jax.default_matmul_precision("highest")


def _run(params, feats, parent_mat, sample_key, mask_infeasible, n_valid,
         logits_builder=None, decode_builder=None, unroll: int = 1,
         sys_feat=None):
    if decode_builder is not None and sys_feat is not None:
        raise ValueError(
            "decode_builder kernels do not take a system profile; "
            "select the scan decode for heterogeneous systems")
    with policy_precision():
        C, enc_state, emb = encode(params, feats, n_valid=n_valid,
                                   unroll=unroll)
        if decode_builder is not None:
            # whole-decode hook: the builder's decode_fn replaces the entire
            # per-step scan (e.g. the persistent Pallas kernel,
            # repro.kernels.ptr.decode.make_decode_fn) — it owns masking,
            # argmax/sampling and the drain semantics end to end.
            return decode_builder(params)(
                params, C, emb, enc_state, parent_mat,
                sample_key=sample_key, mask_infeasible=mask_infeasible,
                n_valid=n_valid)
        logits_fn = (None if logits_builder is None
                     else logits_builder(params, C))
        return decode(
            params, C, emb, enc_state, parent_mat,
            sample_key=sample_key, mask_infeasible=mask_infeasible,
            logits_fn=logits_fn, n_valid=n_valid, unroll=unroll,
            sys_feat=sys_feat,
        )


def greedy_order(params, feats, parent_mat, mask_infeasible=True,
                 n_valid=None, logits_builder=None, decode_builder=None,
                 unroll: int = 1, sys_feat=None):
    """``logits_builder(params, C) -> logits_fn`` overrides the pointer/
    glimpse op after encoding (e.g. the Pallas kernel via
    :func:`repro.kernels.ptr.ops.make_logits_fn`); None keeps the hoisted
    pure-jnp path.  ``decode_builder(params) -> decode_fn`` replaces the
    WHOLE decode loop instead (the persistent kernel,
    :func:`repro.kernels.ptr.decode.make_decode_fn`); it wins over
    ``logits_builder`` when both are given.  ``sys_feat`` conditions the
    decode on a hardware profile (see :func:`decode`)."""
    return _run(params, feats, parent_mat, None, mask_infeasible, n_valid,
                logits_builder, decode_builder, unroll, sys_feat=sys_feat)


def sample_order(params, feats, parent_mat, key, mask_infeasible=True,
                 n_valid=None, logits_builder=None, decode_builder=None,
                 unroll: int = 1, sys_feat=None):
    return _run(params, feats, parent_mat, key, mask_infeasible, n_valid,
                logits_builder, decode_builder, unroll, sys_feat=sys_feat)
