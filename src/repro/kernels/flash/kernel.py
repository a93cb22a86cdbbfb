"""Causal GQA flash-attention forward as a Pallas TPU kernel.

Tiling (BlockSpec): the grid is (batch, q_heads, Sq/block_q, Sk/block_k); the
last grid axis is sequential on TPU, so the online-softmax state — running
max ``m``, normalizer ``l`` and the fp32 accumulator — lives in VMEM scratch
and is carried across key blocks.  Per-step VMEM working set:

    q tile  (block_q, d)   +  k,v tiles (block_k, d)  +  acc (block_q, d) f32

with block_q = block_k = 128 and d <= 256 this is < 0.5 MB — far inside the
~16 MB v5e VMEM, leaving room for double buffering; all matmul dims are
multiples of 128, MXU-aligned.  GQA is handled in the k/v index_map
(q head h reads kv head h // group), so no repeated-KV materialization ever
happens.  Numerics: scores and accumulation in fp32 regardless of input
dtype, one division at the end — identical to the oracle in ref.py.

Causality: key blocks strictly above the diagonal contribute nothing; the
kernel skips their compute with ``pl.when`` (the iteration still runs — grid
shapes are static — but does no FLOPs, halving effective work vs the dense
loop; the q-block-local mask handles the diagonal block).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_pallas"]

NEG_INF = float("-inf")


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, scale: float, causal: bool, block_q: int, block_k: int,
                  num_k_blocks: int, sk: int, sq: int):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # queries sit at the end of the kv sequence (sq == sk in prefill)
    q_start = qi * block_q + (sk - sq)
    k_start = kj * block_k
    needed = (not causal) or (k_start <= q_start + block_q - 1)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (block_q, d)
        k = k_ref[0, 0].astype(jnp.float32)          # (block_k, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(cols <= rows, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        # guard fully-masked rows: exp(-inf - -inf) -> use large finite shift
        shift = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - shift[:, None])
        alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - shift), 0.0)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kj == num_k_blocks - 1)
    def _finalize():
        l = l_ref[...]
        denom = jnp.where(l > 0, l, 1.0)
        o_ref[0, 0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


# NOTE: value head dim dv may differ from the qk head dim d (MLA: qk 96 / v
# 64); the accumulator and output tiles are sized by dv.


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "block_q", "block_k", "interpret"))
def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           scale: float | None = None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False):
    """q: (B, Hq, Sq, D); k: (B, Hkv, Sk, D); v: (B, Hkv, Sk, Dv)."""
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    dv = v.shape[-1]
    if hq % hkv:
        raise ValueError("Hq must be a multiple of Hkv")
    group = hq // hkv
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError("sequence lengths must divide the block sizes")
    nq, nk = sq // block_q, sk // block_k
    scale = scale if scale is not None else 1.0 / (d ** 0.5)

    grid = (b, hq, nq, nk)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, num_k_blocks=nk, sk=sk, sq=sq)

    scratch = [
        pltpu.VMEM((block_q,), jnp.float32),     # running max m
        pltpu.VMEM((block_q,), jnp.float32),     # normalizer l
        pltpu.VMEM((block_q, dv), jnp.float32),  # fp32 accumulator
    ]

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bb, h, i, j: (bb, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bb, h, i, j, g=group: (bb, h // g, j, 0)),
            pl.BlockSpec((1, 1, block_k, dv),
                         lambda bb, h, i, j, g=group: (bb, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dv), lambda bb, h, i, j: (bb, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, dv), q.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(q, k, v)
