"""Fused pointer/glimpse decode step as a Pallas TPU kernel.

This is RESPECT's deployment hot loop: scheduling a graph runs |V| decode
steps, each of which reads the full context matrix three times in the naive
formulation (glimpse scores, glimpse reduction, pointer scores).  The fusion
story on TPU:

* the loop-invariant projections ``C @ W_ref_g`` / ``C @ W_ref_p`` are
  hoisted out of the decode loop entirely (done by the wrapper, once per
  graph);
* the remaining per-step work — two (H,H) matvecs, two tanh-activated
  reductions against the context, one masked softmax and the glimpse
  contraction — becomes ONE kernel launch touching VMEM-resident tiles,
  instead of ~7 HBM round-trips of (n, H) intermediates;
* one grid step per batched graph (grid = (B,)); per-step VMEM =
  3 x (n, H) fp32 tiles + weights = ~3 MB at n=782, H=256 (InceptionResNetv2,
  the largest Table-I graph) — comfortably VMEM-resident, MXU-aligned H.

Callers pad n to a size bucket; padded rows carry mask=False and are
provably inert (masked to -1e9 before the softmax).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["pointer_step_pallas"]

NEG_INF = -1.0e9


def _ptr_kernel(C_ref, CWg_ref, CWp_ref, h_ref, wqg_ref, vg_ref, wqp_ref,
                vp_ref, mask_ref, out_ref):
    f32 = jnp.float32
    dot = functools.partial(jnp.dot, preferred_element_type=f32)
    C = C_ref[0].astype(f32)                  # (n, H)
    CWg = CWg_ref[0].astype(f32)
    CWp = CWp_ref[0].astype(f32)
    h = h_ref[0].astype(f32)                  # (1, H) row
    sel = mask_ref[0] == 1                    # (n, 1) column

    qg = dot(h, wqg_ref[...].astype(f32))                          # (1, H)
    sg = dot(jnp.tanh(CWg + qg), vg_ref[...].astype(f32))          # (n, 1)
    sg = jnp.where(sel, sg, NEG_INF)
    e = jnp.exp(sg - jnp.max(sg))
    attn = e / jnp.sum(e)
    glimpse = jnp.sum(attn * C, axis=0, keepdims=True)             # (1, H)
    qp = dot(glimpse, wqp_ref[...].astype(f32))                    # (1, H)
    logits = dot(jnp.tanh(CWp + qp), vp_ref[...].astype(f32))      # (n, 1)
    out_ref[0] = jnp.where(sel, logits, NEG_INF).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pointer_step_pallas(C, CWg, CWp, h, w_q_g, v_g, w_q_p, v_p, mask,
                        *, interpret: bool = False):
    """Batched fused decode step.

    C/CWg/CWp: (B, n, H); h: (B, H); weights shared: (H, H)/(H,);
    mask: (B, n) bool.  Returns logits (B, n) float32.

    Every block's last two dims equal the array's (the TPU tiling rule):
    ``h`` rides as (B, 1, H) rows, ``mask`` and the logits as (B, n, 1)
    columns, and the ``v`` heads as (H, 1) so the scores are 2-D dots.
    """
    bsz, n, hidden = C.shape
    per_graph = lambda shape: pl.BlockSpec(shape, lambda b: (b, 0, 0))
    shared = lambda shape: pl.BlockSpec(shape, lambda b: (0, 0))
    out = pl.pallas_call(
        _ptr_kernel,
        grid=(bsz,),
        in_specs=[
            per_graph((1, n, hidden)),      # C
            per_graph((1, n, hidden)),      # CWg
            per_graph((1, n, hidden)),      # CWp
            per_graph((1, 1, hidden)),      # h
            shared((hidden, hidden)),       # w_q glimpse
            shared((hidden, 1)),            # v glimpse
            shared((hidden, hidden)),       # w_q pointer
            shared((hidden, 1)),            # v pointer
            per_graph((1, n, 1)),           # mask
        ],
        out_specs=per_graph((1, n, 1)),
        out_shape=jax.ShapeDtypeStruct((bsz, n, 1), jnp.float32),
        interpret=interpret,
    )(C, CWg, CWp, h[:, None, :], w_q_g, v_g.reshape(hidden, 1), w_q_p,
      v_p.reshape(hidden, 1), mask.astype(jnp.int32)[..., None])
    return out[..., 0]
