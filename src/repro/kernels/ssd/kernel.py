"""Mamba-2 SSD chunked scan as a Pallas TPU kernel.

Grid: (batch, heads, S/Q) — the chunk axis is last, hence sequential on TPU,
and the inter-chunk state (N, P fp32) lives in VMEM scratch carried across
chunk iterations; one kernel launch covers the whole sequence with zero HBM
state traffic.

Per-chunk VMEM working set at Q=128, N=64, P=64:

    x (Q,P) + B,C (Q,N) + decay L (Q,Q fp32) + state (N,P fp32)  ~= 130 KB

MXU work per chunk: C@B^T (Q,Q,N-contraction), the (Q,Q)@(Q,P) intra matmul,
the (Q,N)^T@(Q,P) state update and the (Q,N)@(N,P) inter term — all dims
padded to lane multiples by the wrapper.  This is the TPU-native shape of
the SSD "matrix-form" algorithm (Dao & Gu 2024), adapted from the CUDA
warp-level version: instead of warp shuffles for the running state, the
sequential-grid + VMEM-scratch idiom expresses the same carry.

The B/C group broadcast (GQA-style ``G`` state groups shared by H/G heads)
happens in the index_map — head h reads group h // (H/G) — so grouped
layouts never materialize repeated tensors in HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd_scan_pallas"]


def _ssd_kernel(x_ref, loga_ref, sc_ref, B_ref, C_ref, y_ref, hout_ref,
                h_ref, *, chunk: int, nchunks: int):
    c = pl.program_id(2)
    f32 = jnp.float32

    @pl.when(c == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0].astype(f32)                   # (Q, P)
    loga = loga_ref[0, 0]                         # (Q, 1) f32 log-decay
    sc = sc_ref[0, 0].astype(f32)                 # (Q, 1) input gate
    Bm = B_ref[0, 0].astype(f32)                  # (Q, N)
    Cm = C_ref[0, 0].astype(f32)                  # (Q, N)

    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # inclusive prefix sums as a compare-and-sum (Mosaic has no cumsum),
    # as a row, then moved onto sublanes through the diagonal
    la_row = jnp.sum(jnp.where(rows <= cols, loga, 0.0), axis=0,
                     keepdims=True)               # (1, Q)
    la = jnp.sum(jnp.where(rows == cols, la_row, 0.0), axis=1,
                 keepdims=True)                   # (Q, 1)
    la_last = jnp.sum(jnp.where(cols[:1] == chunk - 1, la_row, 0.0))
    L = jnp.where(rows >= cols, jnp.exp(la - la_row), 0.0)   # (Q, Q)

    scores = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())), preferred_element_type=f32)
    scores = scores * L                           # (Q, Q)
    dx = sc * x                                   # (Q, P)
    y_intra = jnp.dot(scores, dx, preferred_element_type=f32)

    hstate = h_ref[...]                           # (N, P)
    y_inter = jnp.exp(la) * jnp.dot(Cm, hstate, preferred_element_type=f32)

    w = jnp.exp(la_last - la)                     # (Q, 1)
    h_new = jnp.exp(la_last) * hstate + jax.lax.dot_general(
        Bm * w, dx, (((0,), (0,)), ((), ())), preferred_element_type=f32)
    h_ref[...] = h_new

    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    @pl.when(c == nchunks - 1)
    def _final():
        hout_ref[0, 0] = h_new.astype(hout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_pallas(x, dt, A, B, C, *, chunk: int = 64, interpret: bool = False,
                    in_scale=None):
    """x: (Bt, S, H, P); dt: (Bt, S, H); A: (H,); B, C: (Bt, S, G, N).

    Returns (y (Bt, S, H, P), h_final (Bt, H, N, P) fp32).

    The wrapper lays heads out ahead of the sequence, so every block is
    (1, 1, chunk, ·) and its last two dims tile on TPU; the per-step
    scalars (log-decay ``-A * dt`` and the input gate) ride as (chunk, 1)
    columns.
    """
    if in_scale is None:
        in_scale = dt
    bt, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    if s % chunk:
        raise ValueError("S must divide chunk")
    if h % g:
        raise ValueError("H must divide G")
    hpg = h // g
    nc = s // chunk
    grid = (bt, h, nc)

    heads_first = lambda a: jnp.swapaxes(a, 1, 2)
    loga = -A.astype(jnp.float32)[None, None, :] * dt.astype(jnp.float32)
    per_head = lambda d: pl.BlockSpec(
        (1, 1, chunk, d), lambda b, hh, c: (b, hh, c, 0))
    per_group = pl.BlockSpec(
        (1, 1, chunk, n), lambda b, hh, c, q=hpg: (b, hh // q, c, 0))
    kernel = functools.partial(_ssd_kernel, chunk=chunk, nchunks=nc)
    y, hout = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[per_head(p), per_head(1), per_head(1), per_group,
                  per_group],
        out_specs=[
            per_head(p),
            pl.BlockSpec((1, 1, n, p), lambda b, hh, c: (b, hh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bt, h, s, p), x.dtype),
            jax.ShapeDtypeStruct((bt, h, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
    )(heads_first(x), heads_first(loga)[..., None],
      heads_first(in_scale)[..., None], heads_first(B), heads_first(C))
    return heads_first(y), hout
