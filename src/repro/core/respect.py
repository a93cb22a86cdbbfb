"""RespectScheduler — the deployable facade (paper Fig. 1a, steps 1-4).

``schedule_many(graphs, n_stages)`` is the serving path: graphs are grouped
into power-of-two size buckets (:mod:`repro.core.batching`) and every
cache-miss bucket runs ONE jitted, vmapped, pad-aware XLA program that
fuses the whole pipeline —

  step 1  graph is already a :class:`CompGraph` (DAG extraction happens in
          :mod:`repro.core.dnn_graphs` for the Table-I models and in
          :mod:`repro.core.partitioner` for pod-scale LMs);
  step 2  embed (:func:`repro.core.embedding.embed_graph`);
  step 3  LSTM-PtrNet greedy decode -> node sequence pi;
  step 4  rho(pi) -> stage assignment (:func:`repro.core.segment.rho_dp_jax`)
          + post-inference repair (:func:`repro.core.segment.repair_jax`),
          ready for deployment —

so the host only packs inputs, slices outputs and runs the cache.  A
content-hash LRU cache short-circuits repeated graphs (multi-tenant traffic
re-submits the same model DAGs constantly); ``schedule(graph, ...)`` is the
single-graph convenience wrapper over the same engine and the same cache.

The fused device pipeline is property-tested to match the host reference
``repair(rho(order))`` exactly (:mod:`repro.core.rho`,
:mod:`repro.core.postprocess`).

Checkpoints use the :mod:`repro.checkpoint.manager` directory format
(manifest + one raw buffer per leaf — atomic, dtype-exact); legacy ``.npz``
parameter dumps from older agents still load.  A pretrained agent trained
by ``examples/train_respect.py`` ships with the benchmarks.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from . import ptrnet
from .batching import BucketedDecoder
from .costmodel import PipelineSystem
from .embedding import embed_dim
from .graph import CompGraph

__all__ = ["RespectScheduler", "ScheduleResult"]


class ScheduleResult(dict):
    """assignment + provenance; behaves like a dict for serialization."""

    @property
    def assignment(self) -> np.ndarray:
        return self["assignment"]


class RespectScheduler:
    def __init__(self, params, mask_infeasible: bool = True, max_deg: int = 6,
                 cache_size: int = 1024, logits_impl: str | None = None,
                 max_compiled: int = 16, decode_impl: str | None = None,
                 decode_bf16: bool = False):
        self.params = params
        #: release manifest dict when the params came from a verified
        #: trained release checkpoint (see :meth:`from_release`), else None
        self.release: dict | None = None
        self.mask_infeasible = mask_infeasible
        self.max_deg = max_deg
        # decode_impl/decode_bf16 select how the pointing loop runs (the
        # scan, or the persistent whole-decode Pallas kernel — see
        # BucketedDecoder); None auto-picks per backend and bucket shape.
        self._decoder = BucketedDecoder(
            mask_infeasible=mask_infeasible, max_deg=max_deg,
            logits_impl=logits_impl, max_compiled=max_compiled,
            decode_impl=decode_impl, decode_bf16=decode_bf16)
        self._cache: OrderedDict = OrderedDict()   # content hash -> result
        self._cache_size = cache_size
        # One lock guards the schedule cache AND the stat counters, so the
        # scheduler can be hammered from many threads (the serving front
        # end's worker plus direct callers).  Device compute runs OUTSIDE
        # the lock; only the hit-scan and the fill hold it.
        self._cache_lock = threading.Lock()
        self.cache_hits = 0
        self.cache_misses = 0
        # lazily-built seeded weights for the degraded serving rung
        # (:meth:`fallback_schedule_many`); never mixed with self.params
        self._fallback_params = None

    # ------------------------------------------------------------------ #
    @classmethod
    def init(cls, seed: int = 0, hidden: int = 256, max_deg: int = 6,
             mask_infeasible: bool = True, **kw) -> "RespectScheduler":
        params = ptrnet.init_params(
            jax.random.PRNGKey(seed), embed_dim(max_deg), hidden)
        return cls(params, mask_infeasible=mask_infeasible, max_deg=max_deg,
                   **kw)

    def save(self, path: str | Path) -> None:
        """Write the agent checkpoint in the repo-wide
        :func:`repro.checkpoint.manager.save_pytree` directory format
        (manifest.json + raw leaf buffers; atomic tmp+rename)."""
        from ..checkpoint import save_pytree
        save_pytree(self.params, path)

    @classmethod
    def load(cls, path: str | Path, **kw) -> "RespectScheduler":
        """Load a checkpoint — the manager directory format, or (back-
        compat) the legacy flat ``.npz`` with ``["enc"]["wx"]``-style keys
        that pre-refactor agents shipped."""
        from ..checkpoint import is_checkpoint_dir, load_pytree_dict
        path = Path(path)
        if is_checkpoint_dir(path):
            return cls(load_pytree_dict(path), **kw)
        data = np.load(path)
        params: dict = {}
        for key in data.files:
            # legacy keystr keys look like ["enc"]["wx"]
            parts = [p.strip("'\"") for p in key.strip("[]").split("][")]
            d = params
            for p in parts[:-1]:
                d = d.setdefault(p, {})
            d[parts[-1]] = jnp.asarray(data[key])
        return cls(params, **kw)

    @classmethod
    def from_release(cls, path: str | Path | None = None,
                     fallback_seed: int = 0, **kw) -> "RespectScheduler":
        """The DEFAULT deployment constructor: load the trained release
        checkpoint (``checkpoints/respect-v*``, integrity-verified — see
        :mod:`repro.checkpoint.release`) when one exists, else warn and
        fall back to seeded untrained weights.

        ``path``: a specific release directory (then it MUST verify —
        corruption raises instead of silently downgrading quality).
        ``sched.release`` carries the manifest when trained, else None.
        """
        from ..checkpoint.release import load_release_params, warn_no_release
        params, manifest = load_release_params(path)
        if params is None:
            warn_no_release("RespectScheduler.from_release")
            return cls.init(seed=fallback_seed, **kw)
        cfg = manifest.get("config", {})
        kw.setdefault("mask_infeasible", cfg.get("mask_infeasible", True))
        kw.setdefault("max_deg", cfg.get("max_deg", 6))
        sched = cls(params, **kw)
        sched.release = manifest
        return sched

    # ------------------------------------------------------------------ #
    def order(self, graph: CompGraph) -> np.ndarray:
        """Raw greedy decode of one graph (no rho/repair, no cache).

        Routed through the shared :class:`BucketedDecoder`, so the Pallas
        ``logits_builder`` path and the bucketed compile cache apply here
        exactly as on the serving path (no per-size legacy programs)."""
        return self._decoder.greedy_orders(self.params, [graph])[0]

    def schedule(
        self,
        graph: CompGraph,
        n_stages: int,
        system: PipelineSystem | None = None,
        use_cache: bool = True,
    ) -> ScheduleResult:
        """Schedule one graph: a batch-of-one through the serving engine,
        sharing the fused per-bucket programs AND the content-hash LRU
        schedule cache with :meth:`schedule_many`."""
        return self.schedule_many([graph], n_stages, system,
                                  use_cache=use_cache)[0]

    def schedule_model(
        self,
        arch: str,
        n_stages: int = 4,
        *,
        n_nodes: int = 32,
        smoke: bool = True,
        kind: str = "prefill",
        system: PipelineSystem | None = None,
        use_cache: bool = True,
    ) -> ScheduleResult:
        """Schedule a REAL registry model end-to-end: trace it under
        ``jax.jit``, parse the compiled HLO into per-instruction cost
        records, coarsen to at most ``n_nodes`` super-nodes
        (:mod:`repro.ingest`), then run the resulting CompGraph through
        the standard :meth:`schedule` path — same fused engine, same
        cache.  The ingest report (timing split, parse warnings, graph
        stats) rides along under ``result["ingest"]``."""
        from ..ingest import ingest_model   # deferred: pulls in models/
        res = ingest_model(arch, n_nodes=n_nodes, smoke=smoke, kind=kind,
                           max_deg=self.max_deg)
        out = self.schedule(res.graph, n_stages, system,
                            use_cache=use_cache)
        out["ingest"] = dict(res.report)
        return out

    # ------------------------------------------------------------------ #
    # degraded-path entry points (the serving ladder's middle rung)
    # ------------------------------------------------------------------ #
    @property
    def hidden(self) -> int:
        """Hidden width of the loaded policy (from the decoder-seed leaf)."""
        return int(np.asarray(self.params["dec0"]).shape[0])

    def fallback_schedule_many(
        self,
        graphs: list[CompGraph],
        n_stages: int,
        system: PipelineSystem | None = None,
        fallback_seed: int = 0,
    ) -> list[ScheduleResult]:
        """Schedule with the SEEDED-fallback policy instead of the loaded
        one: same fused per-bucket programs, same decoder compile cache
        (parameters are traced arguments, so no recompile at equal
        hidden width), but freshly initialized weights.

        This is the degradation ladder's middle rung
        (:mod:`repro.serving.degrade`): when the trained-policy path
        raises — corrupted release params, a poisoned cache entry, a
        kernel bug tripped by one input — the service retries here before
        dropping all the way to the host ``list`` heuristic.  Results
        NEVER touch the schedule cache (different weights produce
        different schedules; mixing them would poison policy-path hits)
        and are stamped ``served_by="fallback"``.
        """
        system = (system or PipelineSystem(n_stages)).with_stages(n_stages)
        if self._fallback_params is None:
            self._fallback_params = ptrnet.init_params(
                jax.random.PRNGKey(fallback_seed),
                embed_dim(self.max_deg), self.hidden)
        fused = self._decoder.fused_schedules(
            self._fallback_params, graphs, n_stages, system)
        out = []
        for g, (order, assignment) in zip(graphs, fused):
            res = self._result_from(
                {"assignment": assignment, "order": order},
                n_stages, g.model_name, cache_hit=False)
            res["served_by"] = "fallback"
            out.append(res)
        return out

    # ------------------------------------------------------------------ #
    # batch serving API
    # ------------------------------------------------------------------ #
    def _cache_key(self, graph: CompGraph, n_stages: int,
                   system: PipelineSystem) -> tuple:
        return (graph.content_hash(), n_stages, system)

    def clear_cache(self) -> None:
        """Empty the schedule cache and reset the stat counters.

        Safe to call while other threads are mid-``schedule_many``: an
        in-progress fill simply re-inserts its freshly computed entries
        into the emptied cache (results are never lost, and the counters
        restart from the clear point)."""
        with self._cache_lock:
            self._cache.clear()
            self.cache_hits = 0
            self.cache_misses = 0

    def cache_stats(self) -> dict:
        """Snapshot of the schedule-cache counters (one lock hold), and of
        the fused programs the decoder built and evicted: a built program
        compiles on its first call, so a rise in a window is a recompile."""
        with self._cache_lock:
            stats = {
                "hits": self.cache_hits,
                "misses": self.cache_misses,
                "size": len(self._cache),
            }
        stats["programs_built"] = self._decoder.programs_built
        stats["programs_evicted"] = self._decoder.programs_evicted
        return stats

    def _result_from(self, entry: dict, n_stages: int, model: str,
                     cache_hit: bool) -> ScheduleResult:
        """Materialize a result as COPIES of the cache entry's arrays, so
        no two results — and never the cache itself — share storage; a
        caller mutating its result cannot poison later hits."""
        return ScheduleResult(
            assignment=entry["assignment"].copy(),
            order=entry["order"].copy(),
            n_stages=n_stages,
            model=model,
            cache_hit=cache_hit,
            served_by="policy",
        )

    def schedule_many(
        self,
        graphs: list[CompGraph],
        n_stages: int,
        system: PipelineSystem | None = None,
        use_cache: bool = True,
    ) -> list[ScheduleResult]:
        """Schedule a batch of graphs through the fused bucketed engine.

        Results are positionally aligned with ``graphs``.  Cache misses run
        decode -> rho -> repair as one vmapped device program per size
        bucket; repeated graphs — by content hash, within this call or
        across calls — are served from an LRU schedule cache.
        """
        system = (system or PipelineSystem(n_stages)).with_stages(n_stages)
        results: list[ScheduleResult | None] = [None] * len(graphs)
        misses: list[int] = []
        seen: dict[tuple, list[int]] = {}   # key -> positions awaiting fill
        # cache entries are immutable once inserted (the cache owns them;
        # results are always fresh copies), so the lock only needs to
        # cover the dict operations — entry refs are snapshotted under
        # the lock and the numpy copies happen outside it.
        hit_fills: list[tuple[int, dict]] = []
        with TraceAnnotation("respect.lookup"):
            # content hashing is pure per-graph work — outside the lock
            keys = ([self._cache_key(g, n_stages, system) for g in graphs]
                    if use_cache else [None] * len(graphs))
            with self._cache_lock:
                for i in range(len(graphs)):
                    key = keys[i]
                    if use_cache and key in self._cache:
                        self._cache.move_to_end(key)
                        self.cache_hits += 1
                        hit_fills.append((i, self._cache[key]))
                    elif use_cache and key in seen:
                        seen[key].append(i)     # duplicate within this batch
                    else:
                        if use_cache:
                            seen[key] = [i]
                        misses.append(i)

        entries: dict[int, dict] = {}
        if misses:
            # device compute runs UNLOCKED — concurrent callers missing on
            # different graphs overlap here; two callers racing on the SAME
            # graph both compute (deterministically identical) entries and
            # the second insert below harmlessly replaces the first.
            fused = self._decoder.fused_schedules(
                self.params, [graphs[i] for i in misses], n_stages, system)
            entries = {i: {"assignment": assignment, "order": order}
                       for i, (order, assignment) in zip(misses, fused)}
        with TraceAnnotation("respect.results"):
            if entries and use_cache:
                with self._cache_lock:
                    # counters track cache LOOKUPS: hits + misses == the
                    # number of cached-path requests.  use_cache=False
                    # traffic (warmup, benchmarks) never consults the
                    # cache, so it moves neither counter.
                    self.cache_misses += len(misses)
                    for i, entry in entries.items():
                        # the cache OWNS entry's arrays; every result
                        # (miss, in-batch duplicate, later hit) gets fresh
                        # copies.  A clear_cache() racing with this fill
                        # just means the entry lands in the emptied cache.
                        self._cache[keys[i]] = entry
                        for j in seen.get(keys[i], [])[1:]:
                            self.cache_hits += 1
                            hit_fills.append((j, entry))
                        while len(self._cache) > self._cache_size:
                            self._cache.popitem(last=False)
            for i, entry in entries.items():
                results[i] = self._result_from(
                    entry, n_stages, graphs[i].model_name, cache_hit=False)
            for j, entry in hit_fills:
                results[j] = self._result_from(
                    entry, n_stages, graphs[j].model_name, cache_hit=True)
        return results
