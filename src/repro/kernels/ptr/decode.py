"""Persistent whole-decode pointer kernel: the full greedy/sampled loop
on-chip.

:mod:`.kernel` fused ONE decode step (glimpse + pointer scores) into a
Pallas call, but the serving loop still launched ``n`` of them from an
``lax.scan``, re-reading the context matrix from HBM every step.  This
module moves the ENTIRE decode loop (paper Alg. 1) into a single kernel:
the encoder context ``C``, the hoisted projections ``C @ W_ref_g`` /
``C @ W_ref_p`` and the node embeddings stay VMEM-resident across all
``n`` steps, and each grid step (grid = (B,), one per batched graph) runs
the whole pointing episode — decoder LSTM update, visited/validity/
infeasibility masking, glimpse attention, pointer logits, argmax or
inverse-CDF sample, log-prob/entropy bookkeeping — without touching HBM.

TPU-friendly formulation (no gathers, no 1D iota, everything 2D; iotas
are built in int32 and converted, since Mosaic has no float iota):

* node-indexed vectors live on sublanes as ``(n, 1)`` columns (visited,
  mask, scores, per-step outputs); latent rows are ``(1, H)``;
* ``emb[idx]`` / ``logprobs[idx]`` / ``visited[idx] = True`` become
  one-hot reductions against ``iota == idx``;
* first-occurrence argmax (the scan's ``jnp.argmax`` tie-break) is
  ``min(where(x == max(x), iota, n))``;
* parent feasibility (``all parents visited``) is a dense adjacency
  matvec: node ``i`` is feasible iff ``(padj @ visited)[i]`` reaches its
  parent count — exact in f32 for any realistic in-degree.

The sampled variant consumes ONE precomputed uniform per step
(:func:`step_uniforms`), drawn from exactly the per-step ``fold_in`` key
stream the scan decode uses, and picks through the scan's own
:func:`repro.core.ptrnet.inverse_cdf_pick` — so the padded/unpadded
sampling contract carries over unchanged.

``bf16=True`` stores the four big per-graph operands (``C``, the two
projections, ``emb``) in bfloat16 — halving their VMEM footprint — while
every score accumulation stays f32 (blocks are upcast on read).  Off by
default; order agreement is tested, bit-identity is not guaranteed.

``interpret=True`` runs the same kernel through the Pallas interpreter
(pure XLA ops), which is what makes the whole-decode path testable on
CPU CI; the compiled path targets TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.ptrnet import inverse_cdf_pick, policy_precision
from . import ops as _ops

__all__ = [
    "parent_adjacency",
    "step_uniforms",
    "decode_batch",
    "decode_pack",
    "make_decode_fn",
]

NEG_INF = -1.0e9


def parent_adjacency(parent_mat, n: int):
    """(..., n, D) int32 parent indices (-1 padded) -> (..., n, n) f32
    counts: ``adj[i, j]`` = how many parent slots of node ``i`` point at
    ``j``.  Feasibility inside the kernel is then one matvec:
    ``(adj @ visited) >= adj.sum(-1)``."""
    oh = jax.nn.one_hot(jnp.clip(parent_mat, 0, n - 1), n,
                        dtype=jnp.float32)
    oh = oh * (parent_mat >= 0).astype(jnp.float32)[..., None]
    return oh.sum(axis=-2)


def step_uniforms(sample_key, n: int):
    """The scan decode's per-step uniforms, precomputed: step ``i`` draws
    ``uniform(fold_in(key, i), ())`` — the identical bit stream, so the
    kernel's inverse-CDF pick sees the same draws as the scan's, and the
    pad-invariance of the fold_in stream is preserved."""
    keys = jax.vmap(
        lambda i: jax.random.fold_in(sample_key, i))(jnp.arange(n))
    return jax.vmap(lambda k: jax.random.uniform(k, ()))(keys)


def _decode_kernel(C_ref, CWg_ref, CWp_ref, emb_ref, padj_ref, valid_ref,
                   unif_ref, h0_ref, c0_ref, dec0_ref, wx_ref, wh_ref,
                   b_ref, wqg_ref, vg_ref, wqp_ref, vp_ref,
                   order_ref, logp_ref, ent_ref,
                   *, sampled: bool, mask_infeasible: bool):
    f32 = jnp.float32
    C = C_ref[0].astype(f32)          # (n, H)
    CWg = CWg_ref[0].astype(f32)      # (n, H)
    CWp = CWp_ref[0].astype(f32)      # (n, H)
    emb = emb_ref[0].astype(f32)      # (n, H)
    padj = padj_ref[0]                # (n, n) f32 parent counts
    valid = valid_ref[0]              # (n, 1) f32 {0, 1}
    unif = unif_ref[0]                # (n, 1) f32 per-step uniforms
    wx = wx_ref[...].astype(f32)      # (H, 4H)
    wh = wh_ref[...].astype(f32)      # (H, 4H)
    bias = b_ref[...].astype(f32)     # (1, 4H)
    wqg = wqg_ref[...].astype(f32)    # (H, H)
    vg = vg_ref[...].astype(f32)      # (H, 1)
    wqp = wqp_ref[...].astype(f32)    # (H, H)
    vp = vp_ref[...].astype(f32)      # (H, 1)

    n, hidden = C.shape
    iota = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0).astype(f32)
    n_parents = jnp.sum(padj, axis=1, keepdims=True)          # (n, 1)
    dot = functools.partial(jnp.dot, preferred_element_type=f32)

    def step(t, carry):
        h, c, d, visited, ord_a, lp_a, ent_a = carry
        # decoder LSTM cell (same gate layout as ptrnet._lstm_step)
        gates = dot(d, wx) + dot(h, wh) + bias                # (1, 4H)
        gi = gates[:, :hidden]
        gf = gates[:, hidden:2 * hidden]
        gg = gates[:, 2 * hidden:3 * hidden]
        go = gates[:, 3 * hidden:]
        c = jax.nn.sigmoid(gf + 1.0) * c + jax.nn.sigmoid(gi) * jnp.tanh(gg)
        h = jax.nn.sigmoid(go) * jnp.tanh(c)

        # selectable mask: unvisited & real & (parents all visited)
        mask = (1.0 - visited) * valid                        # (n, 1)
        if mask_infeasible:
            feasible = dot(padj, visited) >= n_parents
            mask = mask * feasible.astype(f32)
        live = jnp.max(mask) > 0.0
        # drain: once every real node is visited only pads remain — pick
        # any unvisited slot at (forced-zero) logp/entropy, like the scan.
        mask = jnp.where(live, mask, 1.0 - visited)
        sel = mask > 0.0

        # glimpse attention then pointer scores (Alg. 1 lines 3-5)
        qg = dot(h, wqg)                                      # (1, H)
        g_scores = dot(jnp.tanh(CWg + qg), vg)                # (n, 1)
        g_scores = jnp.where(sel, g_scores, NEG_INF)
        g_max = jnp.max(g_scores)
        g_exp = jnp.exp(g_scores - g_max)
        attn = g_exp / jnp.sum(g_exp)
        glimpse = jnp.sum(attn * C, axis=0, keepdims=True)    # (1, H)
        qp = dot(glimpse, wqp)
        logits = dot(jnp.tanh(CWp + qp), vp)                  # (n, 1)
        logits = jnp.where(sel, logits, NEG_INF)

        l_max = jnp.max(logits)
        lse = l_max + jnp.log(jnp.sum(jnp.exp(logits - l_max)))
        logprobs = logits - lse
        probs = jnp.exp(logprobs)

        if sampled:
            u = jnp.sum(jnp.where(iota == t.astype(f32), unif, 0.0))
            idx = inverse_cdf_pick(probs, u)
        else:
            # first-occurrence argmax — the scan's jnp.argmax tie-break
            idx = jnp.min(jnp.where(logits == l_max, iota, f32(n)))

        onehot = (iota == idx).astype(f32)                    # (n, 1)
        lp = jnp.sum(onehot * logprobs)
        ent = -jnp.sum(jnp.where(probs > 0, probs * logprobs, 0.0))
        lp = jnp.where(live, lp, 0.0)
        ent = jnp.where(live, ent, 0.0)

        visited = visited + onehot
        d = jnp.sum(onehot * emb, axis=0, keepdims=True)      # (1, H)
        step_oh = (iota == t.astype(f32)).astype(f32)
        ord_a = ord_a + step_oh * idx
        lp_a = lp_a + step_oh * lp
        ent_a = ent_a + step_oh * ent
        return h, c, d, visited, ord_a, lp_a, ent_a

    h0 = h0_ref[0].astype(f32)        # (1, H)
    c0 = c0_ref[0].astype(f32)
    d0 = dec0_ref[...].astype(f32)    # (1, H)
    zeros_n = jnp.zeros((n, 1), f32)
    carry = (h0, c0, d0, zeros_n, zeros_n, zeros_n, zeros_n)
    _, _, _, _, ord_a, lp_a, ent_a = jax.lax.fori_loop(0, n, step, carry)
    order_ref[0] = ord_a
    logp_ref[0] = lp_a
    ent_ref[0] = ent_a


@functools.partial(
    jax.jit,
    static_argnames=("sampled", "mask_infeasible", "interpret", "bf16"))
def decode_batch(params, C, emb, h0, c0, parent_mat, n_valid,
                 uniforms=None, *, sampled: bool = False,
                 mask_infeasible: bool = True, interpret: bool = False,
                 bf16: bool = False):
    """Whole-decode kernel over a padded batch of encoded graphs.

    C/emb: (B, n, H) contexts and projected embeddings; h0/c0: (B, H)
    final encoder state; parent_mat: (B, n, D) int32 (-1 padded);
    n_valid: (B,) int32; uniforms: (B, n) per-step draws (sampled only).

    Returns (order (B, n) int32, logp (B, n) f32, ent (B, n) f32) with
    the scan decode's exact semantics (drained pads at zero logp/ent).
    """
    with policy_precision():
        B, n, hidden = C.shape
        if sampled and uniforms is None:
            raise ValueError("sampled decode needs per-step uniforms")
        CWg, CWp = _ops.precompute_refs(params, C)
        padj = parent_adjacency(parent_mat, n)
        valid = (jnp.arange(n)[None, :] < n_valid[:, None]) \
            .astype(jnp.float32)[..., None]                       # (B, n, 1)
        unif = (jnp.zeros((B, n, 1), jnp.float32) if uniforms is None
                else uniforms.astype(jnp.float32)[..., None])
        store = jnp.bfloat16 if bf16 else jnp.float32
        big = [x.astype(store) for x in (C, CWg, CWp, emb)]
        dec = params["dec"]
        weights = [
            params["dec0"].reshape(1, hidden).astype(store),
            dec["wx"].astype(store), dec["wh"].astype(store),
            dec["b"].reshape(1, -1).astype(jnp.float32),
            params["glimpse"]["w_q"].astype(store),
            params["glimpse"]["v"].reshape(hidden, 1).astype(store),
            params["pointer"]["w_q"].astype(store),
            params["pointer"]["v"].reshape(hidden, 1).astype(store),
        ]
        per_graph_3d = lambda shape: pl.BlockSpec(shape, lambda b: (b, 0, 0))
        shared = lambda shape: pl.BlockSpec(
            shape, (lambda b: (0, 0)) if len(shape) == 2 else (lambda b: (0,)))
        kernel = functools.partial(
            _decode_kernel, sampled=sampled, mask_infeasible=mask_infeasible)
        out = pl.pallas_call(
            kernel,
            grid=(B,),
            in_specs=[
                per_graph_3d((1, n, hidden)),   # C
                per_graph_3d((1, n, hidden)),   # CWg
                per_graph_3d((1, n, hidden)),   # CWp
                per_graph_3d((1, n, hidden)),   # emb
                per_graph_3d((1, n, n)),        # padj
                per_graph_3d((1, n, 1)),        # valid
                per_graph_3d((1, n, 1)),        # uniforms
                per_graph_3d((1, 1, hidden)),   # h0
                per_graph_3d((1, 1, hidden)),   # c0
                shared((1, hidden)),            # dec0
                shared((hidden, 4 * hidden)),   # wx
                shared((hidden, 4 * hidden)),   # wh
                shared((1, 4 * hidden)),        # b
                shared((hidden, hidden)),       # w_q glimpse
                shared((hidden, 1)),            # v glimpse
                shared((hidden, hidden)),       # w_q pointer
                shared((hidden, 1)),            # v pointer
            ],
            out_specs=[per_graph_3d((1, n, 1))] * 3,
            out_shape=[jax.ShapeDtypeStruct((B, n, 1), jnp.float32)] * 3,
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_ops.decode_kernel_vmem_bytes(
                    n, hidden, sampled=sampled, bf16=bf16)),
            interpret=interpret,
        )(*big, padj, valid, unif,
          h0[:, None, :], c0[:, None, :], *weights)
        order_f, logp, ent = (o[..., 0] for o in out)
        return order_f.astype(jnp.int32), logp, ent


@functools.partial(
    jax.jit,
    static_argnames=("sampled", "mask_infeasible", "interpret", "bf16"))
def decode_pack(params, feats, parent_mat, n_valid, sample_keys=None, *,
                sampled: bool = False, mask_infeasible: bool = True,
                interpret: bool = False, bf16: bool = False):
    """Encode (vmapped pad-aware scan) + whole-decode kernel for a padded
    pack: the batched building block `BucketedDecoder` and the RL rollout
    select when ``decode_impl`` is a kernel path.

    feats: (B, n, F); parent_mat: (B, n, D); n_valid: (B,) int32;
    sample_keys: (B, 2) per-graph PRNG keys (sampled only).
    Returns (order, logp, ent), each (B, n).
    """
    from ...core import ptrnet
    n = feats.shape[1]
    with policy_precision():
        C, state, emb = jax.vmap(
            lambda f, nv: ptrnet.encode(params, f, n_valid=nv))(
                feats, n_valid)
    h0, c0 = state
    uniforms = None
    if sampled:
        if sample_keys is None:
            raise ValueError("sampled decode needs per-graph sample_keys")
        uniforms = jax.vmap(lambda k: step_uniforms(k, n))(sample_keys)
    return decode_batch(
        params, C, emb, h0, c0, parent_mat, n_valid, uniforms,
        sampled=sampled, mask_infeasible=mask_infeasible,
        interpret=interpret, bf16=bf16)


def make_decode_fn(*, interpret: bool = False, bf16: bool = False):
    """Whole-decode builder for :func:`repro.core.ptrnet.greedy_order` /
    ``sample_order`` (``decode_builder=``): replaces the per-graph decode
    scan with a batch-of-one persistent kernel call.  The returned
    callable matches the hook signature
    ``(params, C, emb, enc_state, parent_mat, *, sample_key,
    mask_infeasible, n_valid) -> (order, logp, ent)``.
    """

    def decode_fn(params, C, emb, enc_state, parent_mat, *,
                  sample_key=None, mask_infeasible=True, n_valid=None):
        n = C.shape[0]
        nv = jnp.asarray(
            n if n_valid is None else n_valid, jnp.int32)[None]
        h0, c0 = enc_state
        uniforms = (None if sample_key is None
                    else step_uniforms(sample_key, n)[None])
        order, logp, ent = decode_batch(
            params, C[None], emb[None], h0[None], c0[None],
            parent_mat[None], nv, uniforms,
            sampled=sample_key is not None,
            mask_infeasible=mask_infeasible, interpret=interpret,
            bf16=bf16)
        return order[0], logp[0], ent[0]

    return decode_fn
