"""Microbatch pipeline parallelism over a ``pipe`` mesh axis (shard_map).

Execution model (GPipe schedule, autodiff-transparent):

* the stage's blocks run as a scan over ``L_stage`` stacked block slots with
  a validity mask, so every stage executes the SAME program (SPMD
  requirement) even when RESPECT assigns unequal layer counts — shorter
  stages no-op the padded slots (the select keeps x);
* each clock tick every stage (a) computes its resident microbatch and
  (b) hands its output to the next stage over ``jax.lax.ppermute`` — the
  ICI-ring analogue of the paper's USB chain;
* total ticks = n_micro + n_stages - 1; bubble fraction =
  (n_stages - 1) / ticks, the classic GPipe bound — RESPECT minimizes the
  *bottleneck stage time*, the other factor of pipeline throughput;
* training: `jax.grad` straight through the pipelined forward — the VJP of
  ppermute is the reversed ppermute, so the backward pass is automatically
  the reverse pipeline (all-forward-then-all-backward GPipe memory
  profile; 1F1B interleaving is a scheduling refinement left on the
  roadmap and does not change the communication volume).

Embedding lookup and the LM head run OUTSIDE the pipe (replicated over the
pipe axis; sharded over data/model as usual) — hidden states are the only
tensors that transit stages, matching the partitioner's cost model.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..models import blocks as blocks_mod

__all__ = ["PipelineRunner"]


class PipelineRunner:
    """Uniform-block ("a"*L patterns) pipeline executor.

    stages: list of per-stage block-index lists (from the partitioner);
    only contiguous assignments are valid (monotone schedules are).
    """

    def __init__(self, cfg, mesh, stages: list[list[int]], n_micro: int,
                 remat: bool = True):
        if cfg.block_pattern not in (None, "a"):
            raise NotImplementedError("pipeline runner covers uniform-attn "
                                      "patterns; hybrids use the pjit path")
        self.cfg = cfg
        self.mesh = mesh
        self.stages = stages
        self.n_stages = len(stages)
        self.n_micro = n_micro
        self.remat = remat
        self.l_max = max(len(s) for s in stages)
        flat = [b for s in stages for b in s]
        if flat != sorted(flat) or len(flat) != cfg.n_layers:
            raise ValueError("stage assignment must be a contiguous cover")

    # ------------------------------------------------------------------ #
    # parameters: (n_stages, l_max, ...) stacked block params + validity
    # ------------------------------------------------------------------ #
    def init_params(self, key):
        keys = jax.random.split(key, self.n_stages * self.l_max)

        def one(k):
            return blocks_mod.init_block(k, self.cfg, "a")

        stacked = jax.vmap(one)(keys)
        stacked = jax.tree.map(
            lambda l: l.reshape(self.n_stages, self.l_max, *l.shape[1:]),
            stacked)
        valid = np.zeros((self.n_stages, self.l_max), np.bool_)
        for s, blks in enumerate(self.stages):
            valid[s, : len(blks)] = True
        return {"blocks": stacked, "valid": jnp.asarray(valid)}

    # ------------------------------------------------------------------ #
    def _stage_fn(self, stage_params, valid, x, positions):
        """Run this stage's (masked) block slots over x."""
        def body(x, inp):
            p, ok = inp
            y, _ = blocks_mod.block_forward(p, self.cfg, "a", x, positions,
                                            mode="train")
            return jnp.where(ok, y, x), None

        body_fn = jax.checkpoint(body) if self.remat else body
        x, _ = jax.lax.scan(body_fn, x, (stage_params, valid))
        return x

    # ------------------------------------------------------------------ #
    def forward(self, params, x_embedded):
        """x_embedded: (n_micro, B_mb, S, d) hidden states post-embedding.
        Returns (n_micro, B_mb, S, d) after all stages."""
        cfg = self.cfg
        n_stages, n_micro = self.n_stages, self.n_micro
        s_len = x_embedded.shape[2]
        positions = jnp.arange(s_len)
        perm = [(i, i + 1) for i in range(n_stages - 1)]

        @functools.partial(
            jax.shard_map, mesh=self.mesh,
            in_specs=(P("pipe"), P("pipe"), P(None, "data")),
            out_specs=P(None, "data"),
            check_vma=False,
        )
        def run(stage_params, valid, mbs):
            stage_params = jax.tree.map(lambda l: l[0], stage_params)
            valid = valid[0]
            stage_id = jax.lax.axis_index("pipe")
            ticks = n_micro + n_stages - 1
            buf = jnp.zeros_like(mbs[0])          # inter-stage register
            outs = jnp.zeros_like(mbs)

            def tick(carry, t):
                buf, outs = carry
                # stage 0 ingests microbatch t (while available)
                mb_idx = jnp.clip(t, 0, n_micro - 1)
                x_in = jnp.where(stage_id == 0, mbs[mb_idx], buf)
                y = self._stage_fn(stage_params, valid, x_in, positions)
                # last stage retires microbatch t - (n_stages - 1)
                out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
                take = (t - (n_stages - 1) >= 0) & (stage_id == n_stages - 1)
                outs = jax.lax.dynamic_update_index_in_dim(
                    outs,
                    jnp.where(take, y, outs[out_idx]),
                    out_idx, 0)
                buf = jax.lax.ppermute(y, "pipe", perm)
                return (buf, outs), None

            (buf, outs), _ = jax.lax.scan(
                tick, (buf, outs), jnp.arange(ticks))
            # every stage holds `outs`; only the last stage's is real —
            # broadcast it (psum of masked copies) so out_specs can drop pipe
            mask = (stage_id == n_stages - 1).astype(outs.dtype)
            return jax.lax.psum(outs * mask, "pipe")

        return run(params["blocks"], params["valid"], x_embedded)

    # ------------------------------------------------------------------ #
    def sequential_forward(self, params, x_embedded):
        """Reference path: same params, no pipeline (for equivalence tests)."""
        positions = jnp.arange(x_embedded.shape[2])

        def per_mb(x):
            for s in range(self.n_stages):
                sp = jax.tree.map(lambda l: l[s], params["blocks"])
                x = self._stage_fn(sp, params["valid"][s], x, positions)
            return x

        return jax.vmap(per_mb)(x_embedded)
