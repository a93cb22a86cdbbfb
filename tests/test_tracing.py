"""The serving path's own spans, named device scopes and counters.

Spans are ``jax.profiler.TraceAnnotation`` events named ``respect.*``,
written into the profiler's trace on the device operations' clock; the
device stages carry ``jax.named_scope`` names that change no compiled
program; the service stamps each result with its queue time; the decoder
counts the programs it builds and evicts.
"""

import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import RespectScheduler, ptrnet, sample_dag, segment
from repro.core.batching import BucketedDecoder, pack_padded
from repro.core.costmodel import PipelineSystem
from repro.core.embedding import embed_dim
from repro.serving import SchedulerService

HIDDEN = 16
N_STAGES = 4

WORKER_SPANS = {
    "respect.wait_request", "respect.collect", "respect.flush",
    "respect.lookup", "respect.pack", "respect.pack.embed",
    "respect.pack.closure", "respect.pack.h2d", "respect.run",
    "respect.dispatch", "respect.fetch", "respect.unpack",
    "respect.results", "respect.resolve"}


def _program_lines(path):
    """Host line (plane, line index) -> its respect.* events as
    (name, start_ns, end_ns, stats)."""
    pd = jax.profiler.ProfileData.from_file(path)
    lines = {}
    for p, plane in enumerate(pd.planes):
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("respect."):
                    lines.setdefault((p, i), []).append(
                        (ev.name, ev.start_ns, ev.end_ns, dict(ev.stats)))
    return lines


def test_service_spans_nest_on_the_worker_line(tmp_path):
    sched = RespectScheduler.init(seed=0, hidden=HIDDEN)
    rng = np.random.default_rng(0)
    graphs = [sample_dag(rng, n=10) for _ in range(3)]
    sched.schedule_many(graphs[:1], N_STAGES, use_cache=False)   # compile
    wide = [sample_dag(rng, n=30) for _ in range(16)]
    with jax.profiler.trace(str(tmp_path)):
        with SchedulerService(sched, max_batch=3, max_wait_ms=5e3) as svc:
            futs = [svc.submit(g, N_STAGES) for g in graphs]
            for f in futs:
                f.result(timeout=60)
        list(BucketedDecoder()._packed_buckets(wide))   # pack only
    (path,) = tmp_path.glob("**/*.xplane.pb")
    lines = _program_lines(str(path))
    worker = next(k for k, evs in lines.items()
                  if any(n == "respect.flush" for n, *_ in evs))
    evs = lines[worker]
    assert WORKER_SPANS <= {n for n, *_ in evs}
    assert "respect.submit" not in {n for n, *_ in evs}
    submits = [n for k, v in lines.items() if k != worker
               for n, *_ in v if n == "respect.submit"]
    assert len(submits) == len(graphs)
    # one full flush of the three requests; every span of the serving
    # path from the lookup to the resolve lies inside it
    (flush,) = [e for e in evs if e[0] == "respect.flush"]
    assert (flush[3]["size"], flush[3]["reason"]) == (len(graphs), "full")
    inside = [e for e in evs if e[0] not in
              ("respect.flush", "respect.wait_request", "respect.collect")]
    assert all(flush[1] <= s and e <= flush[2] for _, s, e, _ in inside)
    # packing's children lie inside the pack span, the run's inside it
    for parent, kids in (("respect.pack", ("respect.pack.embed",
                                           "respect.pack.closure",
                                           "respect.pack.h2d")),
                         ("respect.run", ("respect.dispatch",
                                          "respect.fetch",
                                          "respect.unpack"))):
        (p,) = [e for e in evs if e[0] == parent]
        for kid in kids:
            (k,) = [e for e in evs if e[0] == kid]
            assert p[1] <= k[1] and k[2] <= p[2]
    (pack,) = [e for e in evs if e[0] == "respect.pack"]
    assert (pack[3]["bucket_n"], pack[3]["batch"]) == (16, 3)
    assert pack[3]["path"] == "per_graph"
    (wide_pack,) = [e for k, v in lines.items() if k != worker for e in v
                    if e[0] == "respect.pack"]
    assert (wide_pack[3]["bucket_n"], wide_pack[3]["batch"]) == (32, 16)
    assert wide_pack[3]["path"] == "batched"
    (run,) = [e for e in evs if e[0] == "respect.run"]
    assert (run[3]["bucket_n"], run[3]["bucket_b"]) == (16, 4)
    assert run[3]["impl"] == "scan"
    assert str(run[3]["new_program"]) in ("True", "1")   # batch of 4: new


def test_queued_s_is_the_wait_before_the_serving_flush():
    sched = RespectScheduler.init(seed=1, hidden=HIDDEN)
    rng = np.random.default_rng(1)
    graphs = [sample_dag(rng, n=int(rng.integers(8, 14))) for _ in range(6)]
    sched.schedule_many(graphs, N_STAGES, use_cache=False)        # compile
    sent, done = {}, {}
    with SchedulerService(sched, max_batch=4, max_wait_ms=5) as svc:
        futs = []
        # the same graph twice: the second coalesces onto the first
        for j, g in enumerate(graphs + graphs[:1]):
            sent[j] = time.perf_counter()
            f = svc.submit(g, N_STAGES)
            f.add_done_callback(
                lambda _f, j=j: done.__setitem__(j, time.perf_counter()))
            futs.append(f)
        results = [f.result(timeout=60) for f in futs]
    assert svc.stats().dedup_hits + svc.stats().cache_hits >= 1
    for j, r in enumerate(results):
        assert r["served_by"] == "policy"
        assert 0.0 <= r["queued_s"] <= done[j] - sent[j]


@pytest.mark.parametrize("impl, scopes", [
    ("scan", ("encode", "decode", "rho_dp", "repair")),
    ("kernel-interpret", ("encode", "decode_batch", "rho_dp", "repair")),
])
def test_fused_program_carries_the_named_scopes(impl, scopes):
    rng = np.random.default_rng(2)
    graphs = [sample_dag(rng, n=12) for _ in range(2)]
    params = ptrnet.init_params(jax.random.PRNGKey(0), embed_dim(), HIDDEN)
    batch = pack_padded(graphs, 16)
    fn = BucketedDecoder()._fused_fn(16, 2, batch.child_width, N_STAGES,
                                     PipelineSystem(N_STAGES), impl)
    text = fn.lower(params, batch).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]+)"', text))
    for scope in scopes:
        assert any(re.search(rf"(^|/|\()({scope})(\)|/|$)", n)
                   for n in names), scope


def _compiled(fn, *args):
    """Compiled HLO text without its debug metadata and source tables."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
    return [ln for ln in text.splitlines()
            if not re.match(r"^(FileNames|FunctionNames|FileLocations|"
                            r"StackFrames|\d+ )", ln)]


def _scoped_calls():
    n, k = 12, N_STAGES
    rng = np.random.default_rng(3)
    g = sample_dag(rng, n=n)
    params = ptrnet.init_params(jax.random.PRNGKey(0), embed_dim(), HIDDEN)
    b = pack_padded([g], 16)
    feats, pmat = b.feats[0], b.parent_mat[0]
    order = jnp.asarray(np.arange(16, dtype=np.int32))
    system = PipelineSystem(k)
    C, state, emb = ptrnet.encode(params, feats)
    return {
        "encode": (ptrnet.encode, lambda f: f(params, feats)),
        "decode": (ptrnet.decode,
                   lambda f: f(params, C, emb, state, pmat)[0]),
        "rho_dp": (segment.rho_dp_jax,
                   lambda f: f(order, b.flops[0], b.param_bytes[0],
                               b.out_bytes[0], pmat, k, system)[0]),
        "repair": (segment.repair_jax,
                   lambda f: f(pmat, b.child_mat[0], b.ancestor_mat[0],
                               jnp.zeros(16, jnp.int32), k)),
    }


@pytest.mark.parametrize("scope", ["encode", "decode", "rho_dp", "repair"])
def test_named_scope_changes_no_compiled_program(scope):
    fn, call = _scoped_calls()[scope]
    plain = fn.__wrapped__          # the function without its scope
    assert _compiled(lambda: call(fn)) == _compiled(lambda: call(plain))


def test_program_lru_counts_builds_and_evictions():
    sched = RespectScheduler.init(seed=0, hidden=HIDDEN, max_compiled=2)
    rng = np.random.default_rng(4)
    by_bucket = [sample_dag(rng, n=n) for n in (6, 12, 20)]   # 8, 16, 32
    for g in by_bucket:
        sched.schedule_many([g], N_STAGES, use_cache=False)
    stats = sched.cache_stats()
    assert (stats["programs_built"], stats["programs_evicted"]) == (3, 1)
    sched.schedule_many(by_bucket[2:], N_STAGES, use_cache=False)  # warm
    assert sched.cache_stats()["programs_built"] == 3
    sched.schedule_many(by_bucket[:1], N_STAGES, use_cache=False)  # evicted
    stats = sched.cache_stats()
    assert (stats["programs_built"], stats["programs_evicted"]) == (4, 2)
