"""Serving cells: the program's ``SchedulerService`` under the traffic
file's arrival process and request pool, the harness's own spans around
it, and the comparison of what it served with the reference.

The traffic file names its parts, each a module found by name:

* ``arrivals``: ``bench/arrivals/<name>.py``.  An open loop gives each
  request a due time, and latency runs from that due time to the result,
  so a late generator shows as latency; a closed loop keeps ``clients``
  requests outstanding.
* ``pool``: ``bench/pools/<name>.py``, which graph of the pool each
  request asks for (all distinct, or repeats with a popularity).
* ``generator``: ``bench/graphs/<name>.py``, which makes the pool's
  graphs from the seed, with ``generator_args``.

Set-up makes the pool, loads the release, and warms the programs the
pool can reach: on the pool's first chunk while worker processes make the
rest, then on whatever the rest adds.  The window then drives
``SchedulerService.submit`` for ``seconds`` seconds.  The service's
scheduler is wrapped in :class:`FlushRecorder`, which times every
``schedule_many`` flush: the seam ``repro.serving.faults`` also uses.
"""

from __future__ import annotations

import contextlib
import gc
import queue
import threading
import time
from collections import Counter
from pathlib import Path

import numpy as np

from bench.lib import flops as F
from bench.lib import reference as ref
from bench.lib.graphspec import to_program
from bench.lib.hostwatch import HostWatch
from bench.lib.pool import PoolMaker, default_workers, rng_for
from bench.lib.spec import BENCH, ROOT, check_keys, load_module
from bench.lib.warm import Warmer, bucket_for

RESULT_WAIT_S = 60.0     # an answer may come this long after the window
TRACE_S = 1.0            # a traced run traces this much of its window:
#                          writing out 4 s of table1's trace took 5-6 minutes
POOL_WORKERS_FROM = 2048  # pools larger than this are made by workers

# the traffic keys this runner reads itself
KEYS = {"runner", "arrivals", "pool", "generator", "generator_args",
        "check_sample", "assign_sample"}


def resolve(traffic: dict, where: str) -> dict:
    """The arrival process and pool kind that ``traffic`` names; refuses a
    key that none of the runner, the two and the generator reads."""
    arrivals = load_module("arrivals", traffic.get("arrivals", ""))
    pool = load_module("pools", traffic.get("pool", ""))
    load_module("graphs", traffic.get("generator", ""))
    check_keys(where, traffic, KEYS | arrivals.KEYS | pool.KEYS)
    return {"arrivals": arrivals, "pool": pool}


def _span(on: bool):
    if on:
        import jax
        return lambda name: jax.profiler.TraceAnnotation(name)
    return lambda name: contextlib.nullcontext()


class SubWindowTrace:
    """Profiles the first ``seconds`` of the window: starts the trace and
    marks its start, then a timer thread marks the end and stops it."""

    def __init__(self, trace_dir: Path, seconds: float):
        import jax
        from bench.lib.trace import WINDOW_START
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # host spans: bench.* only
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        self.t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(WINDOW_START):
            pass
        self.t1 = None
        self.stop_s = None      # how long stopping and writing the trace took
        self._lock = threading.Lock()
        self._timer = threading.Timer(seconds, self.stop)
        self._timer.start()

    def stop(self) -> None:
        import jax
        from bench.lib.trace import WINDOW_END
        with self._lock:
            if self.t1 is not None:
                return
            with jax.profiler.TraceAnnotation(WINDOW_END):
                pass
            self.t1 = time.perf_counter()
            jax.profiler.stop_trace()
            self.stop_s = time.perf_counter() - self.t1

    def finish(self) -> None:
        self._timer.cancel()
        self.stop()
        self._timer.join()


class FlushRecorder:
    """Wraps the scheduler the service drives; records each flush as
    (start, end, graphs, results) and delegates everything else."""

    def __init__(self, inner, span):
        self._inner = inner
        self._span = span
        self.flushes: list[tuple[float, float, list, list]] = []

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def schedule_many(self, graphs, *args, **kw):
        t0 = time.perf_counter()
        with self._span("bench.flush"):
            out = self._inner.schedule_many(graphs, *args, **kw)
        self.flushes.append((t0, time.perf_counter(), list(graphs), out))
        return out


def load_scheduler(cfg: dict, **kw):
    """The program's scheduler over the configuration's release."""
    from repro.core import RespectScheduler
    svc = cfg["service"]
    sched = RespectScheduler.from_release(
        ROOT / cfg["release"], max_compiled=svc["max_compiled"],
        cache_size=svc["cache_size"], **kw)
    if sched.release is None or sched.hidden != int(cfg["hidden"]):
        raise RuntimeError("release not loaded at the stated width")
    return sched


# --------------------------------------------------------------------- #
# the run
# --------------------------------------------------------------------- #
class ServeRun:
    """One serving cell: ``setup``, ``window``, then the readings and the
    comparison (``check``).  ``wrap``, for the harness's own tests, wraps
    the program's scheduler under the timed path."""

    def __init__(self, parts: dict, seed: int, seconds: float, trace: bool,
                 t_start: float, log=print, wrap=None):
        self.parts = parts
        self.cfg = parts["config"]
        self.traffic = parts["traffic"]
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.t_start = t_start
        self.log = log
        self.wrap = wrap
        self.span = _span(trace)

    # ---------------------------------------------------------------- #
    def setup(self, counter, sched=None) -> None:
        """Make the pool, load the release (or take ``sched``, already
        warm for this traffic) and warm the pool's programs."""
        from repro.core import PipelineSystem

        n_req, self.dues = self.parts["arrivals"].requests(
            self.traffic, self.seed, self.seconds)
        n_pool, self.pick = self.parts["pool"].plan(self.traffic, self.seed,
                                                    n_req)
        gen_path = str(BENCH / "graphs" / f"{self.traffic['generator']}.py")
        workers = default_workers() if n_pool > POOL_WORKERS_FROM else 0
        maker = PoolMaker(gen_path, self.traffic["generator_args"],
                          self.seed, n_pool, workers)
        svc = self.cfg["service"]
        self.system = PipelineSystem(**self.cfg["system"])
        self.k = int(self.cfg["system"]["n_stages"])
        self.specs, self.graphs = [], []
        c0 = counter.snapshot()
        t0 = time.perf_counter()
        try:
            self.sched = sched or load_scheduler(self.cfg)
            t_load = time.perf_counter() - t0
            warmer = Warmer(self.sched, svc["max_batch"], self.k, self.system)
            calls = 0
            for chunk in maker.chunks():
                self.specs += chunk
                self.graphs += [to_program(s) for s in chunk]
                if sched is None and len(self.specs) == len(chunk):
                    calls += warmer.warm(self.graphs)   # the first chunk
        finally:
            maker.close()
        if sched is None:
            calls += warmer.warm(self.graphs)           # what the rest adds
        self.sched.clear_cache()
        if self.wrap is not None:
            self.sched = self.wrap(self.sched)
        # The pool stands in for requests that a deployment receives one by
        # one; frozen, its millions of objects are no longer walked by every
        # full collection inside the window (0.2 s for table1's pool, about
        # 1.5 s for a closed loop's, on a CPU)
        gc.collect()
        gc.freeze()
        c1 = counter.snapshot()
        self.log(f"setup: release {t_load:.2f} s, pool {len(self.specs)} "
                 f"graphs for {n_req} requests, pool and warm "
                 f"{time.perf_counter() - t0 - t_load:.2f} s ({calls} warm "
                 f"calls), compiles {c1['compiles'] - c0['compiles']}, "
                 f"persistent cache hits "
                 f"{c1['cache_hits'] - c0['cache_hits']} misses "
                 f"{c1['cache_misses'] - c0['cache_misses']}")

    def graph_of(self, i: int):
        return self.graphs[self.pick[i]]

    def spec_of(self, i: int):
        return self.specs[self.pick[i]]

    # ---------------------------------------------------------------- #
    def window(self, counter, trace_dir: Path | None):
        from repro.serving import SchedulerService

        svc = self.cfg["service"]
        self.rec = FlushRecorder(self.sched, self.span)
        service = SchedulerService(
            self.rec, max_batch=svc["max_batch"],
            max_wait_ms=svc["max_wait_ms"], max_queue=svc["max_queue"])
        n = len(self.pick)
        self.t_due = np.full(n, np.nan)
        self.t_sub = np.full(n, np.nan)
        self.t_done = np.full(n, np.nan)
        self.futs: list = [None] * n
        c0 = counter.snapshot()
        self.setup_s = time.perf_counter() - self.t_start
        self.tracer = None
        if trace_dir is not None:
            self.tracer = SubWindowTrace(trace_dir, min(TRACE_S, self.seconds))
        self.host = HostWatch()
        self.host.start()
        try:
            if self.parts["arrivals"].LOOP == "open":
                self._open(service)
            else:
                self._closed(service)
            self._await()
            service.close(timeout=RESULT_WAIT_S)
        finally:
            self.host.stop()
            if self.tracer is not None:
                self.tracer.finish()
                self.log(f"trace: written in {self.tracer.stop_s:.1f} s")
        self.window_compiles = counter.snapshot()["compiles"] - c0["compiles"]
        self.stats = service.stats()

    def _submit(self, service, i: int) -> None:
        def done(_f, i=i):
            self.t_done[i] = time.perf_counter()
        with self.span("bench.submit"):
            self.t_sub[i] = time.perf_counter()
            f = service.submit(self.graph_of(i), self.k, self.system)
            f.add_done_callback(done)
        self.futs[i] = f

    def _open(self, service) -> None:
        self.t0 = time.perf_counter() + 0.01
        self.t_due[:] = self.t0 + self.dues
        for i, due in enumerate(self.t_due):
            wait = due - time.perf_counter()
            if wait > 0:
                with self.span("bench.wait"):
                    time.sleep(wait)
            self._submit(service, i)
        self.t1 = self.t0 + self.seconds

    def _closed(self, service) -> None:
        freed: queue.SimpleQueue = queue.SimpleQueue()
        n = len(self.t_sub)
        nxt = 0

        def submit_next():
            nonlocal nxt
            if nxt >= n:
                raise RuntimeError(
                    f"{n} requests offered before the window closed: the "
                    f"traffic's max_per_s is too low for this program")
            self._submit(service, nxt)
            self.futs[nxt].add_done_callback(lambda f: freed.put(1))
            nxt += 1

        self.t0 = time.perf_counter()
        self.t1 = self.t0 + self.seconds
        for _ in range(int(self.traffic["clients"])):
            submit_next()
        while True:
            left = self.t1 - time.perf_counter()
            if left <= 0:
                break
            with self.span("bench.wait"):
                try:
                    freed.get(timeout=left)
                except queue.Empty:
                    break
            if time.perf_counter() < self.t1:
                submit_next()
        self.t_due[:nxt] = self.t_sub[:nxt]

    def _await(self) -> None:
        limit = time.perf_counter() + RESULT_WAIT_S
        for f in self.futs:
            if f is None:
                continue
            try:
                f.exception(timeout=max(0.0, limit - time.perf_counter()))
            except TimeoutError:
                pass

    # ---------------------------------------------------------------- #
    def outcomes(self) -> dict:
        """Per-request outcome over the requests the window offered."""
        asked = [i for i, f in enumerate(self.futs) if f is not None]
        ok, failed, unanswered = [], 0, 0
        for i in asked:
            f = self.futs[i]
            if not f.done():
                unanswered += 1
            elif f.exception() is not None:
                failed += 1
            elif f.result()["served_by"] != "policy":
                failed += 1
            else:
                ok.append(i)
        return {"asked": asked, "ok": ok, "failed": failed + unanswered,
                "unanswered": unanswered}

    def end_to_end(self, out: dict) -> dict:
        lat = np.full(len(out["asked"]), np.inf)
        pos = {i: j for j, i in enumerate(out["asked"])}
        for i in out["ok"]:
            lat[pos[i]] = self.t_done[i] - self.t_due[i]
        done_in = [i for i in out["ok"] if self.t_done[i] <= self.t1]
        return {
            "latency_p95_ms": float(np.percentile(lat, 95) * 1e3),
            "schedules_per_s": len(done_in) / self.seconds,
            "setup_s": self.setup_s,
        }

    def layer_record(self, out: dict, red: dict | None, peaks: dict) -> dict:
        """What the per-layer readers read."""
        # queue wait of the requests whose graph no other request asks
        # for: only those name the flush that served them
        flush_start = {}
        for t0, _, graphs, _ in self.rec.flushes:
            for g in graphs:
                flush_start.setdefault(id(g), t0)
        asks = Counter(int(self.pick[i]) for i in out["asked"])
        qwait = [flush_start[id(self.graph_of(i))] - self.t_due[i]
                 for i in out["ok"] if asks[int(self.pick[i])] == 1
                 and id(self.graph_of(i)) in flush_start]
        hidden = int(self.cfg["hidden"])
        # policy and kernel work of the flushes inside the window, and of
        # those wholly inside the traced part of it
        tr0, tr1 = ((self.tracer.t0, self.tracer.t1) if self.tracer
                    else (None, None))
        pol_window = pol_traced = 0.0
        k_flops = k_bytes = 0.0
        for t0, t1, graphs, results in self.rec.flushes:
            traced = tr0 is not None and t0 >= tr0 and t1 <= tr1
            misses: dict[int, int] = {}
            for g, r in zip(graphs, results):
                if r.get("served_by") == "policy" and not r["cache_hit"]:
                    f = F.policy_forward_flops(g.n, hidden)
                    pol_window += f if t1 <= self.t1 else 0.0
                    pol_traced += f if traced else 0.0
                    b = bucket_for(g.n)
                    misses[b] = misses.get(b, 0) + 1
            for b, cnt in misses.items():
                if not traced:
                    continue
                fl, by = F.decode_kernel_cost(b, 1 << (cnt - 1).bit_length(),
                                              hidden)
                k_flops += fl
                k_bytes += by
        st = self.stats
        return {
            "window_s": self.t1 - self.t0,
            "requests": len(out["asked"]),
            "queue_wait_s": np.asarray(qwait),
            "generator_lag_s": np.asarray(
                [self.t_sub[i] - self.t_due[i] for i in out["asked"]]),
            "flushes": st.batches,
            "served_requests": st.completed,
            "cache_hits": st.cache_hits,
            "dedup_hits": st.dedup_hits,
            "policy_flops_traced": pol_traced,
            "policy_flops_window": pol_window,
            "kernel_flops": k_flops,
            "kernel_bytes": k_bytes,
            "peaks": peaks,
            "trace": red,
        }

    # ---------------------------------------------------------------- #
    def release_program(self) -> None:
        """Drop the program's state before the reference runs."""
        self.sched = None
        self.rec = None
        gc.unfreeze()
        gc.collect()

    def sample(self, out: dict) -> tuple[list[int], int]:
        """Requests to compare, drawn from the seed, the one with the most
        nodes first: up to ``check_sample`` for the logit gaps, of which
        the first ``assign_sample`` also for the assignment."""
        ok = list(out["ok"])
        if not ok:
            return [], 0
        longest = max(ok, key=lambda i: self.spec_of(i).n)
        rest = [i for i in ok if i != longest]
        rest = [rest[j] for j in rng_for(self.seed, 4).permutation(len(rest))]
        idx = ([longest] + rest)[: int(self.traffic["check_sample"])]
        return idx, min(len(idx), int(self.traffic["assign_sample"]))

    def served(self, idx: list[int]):
        specs = [self.spec_of(i) for i in idx]
        res = [self.futs[i].result() for i in idx]
        return specs, [np.asarray(r["order"]) for r in res], \
            [np.asarray(r["assignment"]) for r in res]

    def describe(self, out: dict) -> str:
        st = self.stats
        asked = np.asarray(out["asked"], dtype=int)
        lat = (self.t_done - self.t_due)[asked] * 1e3
        fifths = [np.nanpercentile(part, 95) if np.isfinite(part).any()
                  else np.nan for part in np.array_split(lat, 5)]
        lag = (self.t_sub - self.t_due)[asked] * 1e3
        backlog = int(np.sum(self.t_due <= self.t1)
                      - np.sum(self.t_done <= self.t1))
        flush_ms = [(t1 - t0) * 1e3 for t0, t1, _, _ in self.rec.flushes]
        return (f"window: {len(out['asked'])} requests, {len(out['ok'])} "
                f"served by the policy, {out['failed']} failed "
                f"({out['unanswered']} unanswered), {st.batches} flushes, "
                f"cache hits {st.cache_hits}, dedup {st.dedup_hits}, "
                f"compiles inside the window {self.window_compiles}\n"
                f"window detail: p95 ms by fifth of the requests "
                f"{[round(float(v), 1) for v in fifths]}, generator lag max "
                f"{np.max(lag, initial=0.0):.1f} ms, backlog at close "
                f"{backlog}, flush ms mean "
                f"{np.mean(flush_ms) if flush_ms else 0.0:.1f} max "
                f"{max(flush_ms, default=0.0):.1f}\n{self.host.describe()}")

    def check(self, out: dict) -> tuple[dict | None, str]:
        """The numbers ``correct`` compares (None if nothing was served),
        and what was compared.  Call after :meth:`release_program`."""
        idx, n_assign = self.sample(out)
        if not idx:
            return None, "nothing served to compare"
        specs, orders, assigns = self.served(idx)
        params = ref.load_params(ROOT / self.cfg["release"])
        numbers = compare(params, specs, orders, assigns, self.cfg["system"],
                          n_assign)
        numbers["unanswered"] = out["unanswered"]
        return numbers, (f"{len(idx)} schedules compared ({n_assign} "
                         f"assignments)")


def compare(params: dict, specs, orders, assigns, system: dict,
            n_assign: int | None = None) -> dict:
    """The numbers ``correct`` compares for a list of served schedules:

    * ``logit_gap_max``: how far below the reference's best logit a served
      node lies, widest over every step of every schedule, the decoder
      conditioned on ``system`` as the program's is;
    * ``invalid_schedules``: schedules that break a dependency, leave the
      stage range, or whose order is not one the reference could decode;
    * ``bottleneck_excess``: for the first ``n_assign``, the most by which
      a served schedule's pipeline bottleneck exceeds that of the
      reference's ``repair(rho(order))``, relative to it."""
    readings = ref.pointer_readings(params, specs, orders, system=system)
    k = int(system["n_stages"])
    gap = excess = 0.0
    invalid = 0
    n_assign = len(specs) if n_assign is None else n_assign
    for j, (s, o, a, rd) in enumerate(zip(specs, orders, assigns, readings)):
        g = ref.logit_gaps(rd, o, s.n)
        gap = max(gap, float(np.max(g)))
        if not np.isfinite(g).all() or not ref.valid_schedule(s, a, k):
            invalid += 1
        elif j < n_assign:
            b_ref, _ = ref.objective(s, ref.schedule(s, o, system), system)
            b_got, _ = ref.objective(s, a, system)
            excess = max(excess, (b_got - b_ref) / b_ref)
    return {"logit_gap_max": gap, "invalid_schedules": invalid,
            "bottleneck_excess": excess}


def control_excess(specs, orders, system: dict, n_assign: int,
                   cost_dtype) -> float:
    """``bottleneck_excess`` of the reference's own schedules with the
    rho cost table rounded to ``cost_dtype``, on the served orders."""
    worst = 0.0
    for s, o in list(zip(specs, orders))[:n_assign]:
        b_ref, _ = ref.objective(s, ref.schedule(s, o, system), system)
        b_low, _ = ref.objective(s, ref.schedule(s, o, system, cost_dtype),
                                 system)
        worst = max(worst, (b_low - b_ref) / b_ref)
    return worst


def control_gap(params: dict, specs, orders, precision: str,
                system: dict | None = None) -> float:
    """Widest gap, under the reference, of the node that the reference at
    ``precision`` puts first, at each position of the served orders, both
    decoders conditioned on ``system``."""
    low = ref.pointer_readings(params, specs, orders, precision=precision,
                               system=system)
    hi = ref.pointer_readings(params, specs, orders,
                              probes=[r["argmax"] for r in low],
                              system=system)
    return max(float(np.max(h["best"].astype(np.float64) - h["probe"]))
               for h in hi)


Run = ServeRun
