#!/usr/bin/env python3
"""Smoke run of the scheduler's main path on one TPU chip.

    python chip_smoke.py               # one chip: serve, oracle, train, ingest
    python chip_smoke.py --four-chips  # data-parallel training, 4 chips vs 1

One process does everything (a chip belongs to one process).  The default
run loads the shipped release (``checkpoints/respect-v1``, hidden 128),
serves a few hundred requests through ``SchedulerService`` — uniform,
heterogeneous and memory-capped systems, plus the ten Table-I graphs at
k = 4 — runs the batched exact oracle on a small grid, takes a few
REINFORCE steps of the release recipe and schedules whisper-tiny and
xlstm-350m through ``schedule_model``.  Every result is checked on the
chip against the host references and the golden digests; any mismatch,
any request not served by the policy, any compile inside the served
window and any ``RuntimeWarning`` fails the run.

``--four-chips`` runs only the data-parallel REINFORCE step on the four
chips of a host and the single-device step at equal global batch it is
compared with.

Without a TPU, or outside a checkout of the repository, the script exits
non-zero and prints no result.  On success the last line of standard
output is ``{"ok": true, "device": {...}}``.  Times printed on earlier
lines are a first chip reading, not a benchmark.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import sys
import time
import warnings
from pathlib import Path

ROOT = Path(__file__).resolve().parent
N_STAGES = 4
WAVE = 16              # requests per service flush (= max_batch)
TRAIN_STEPS = 5
FOUR_CHIP_STEPS = 3
FOUR_CHIP_TOL = 1e-5   # tests/test_train_engine.py, same check on CPU


class Smoke:
    """Collects findings (printed as they come) and failures."""

    def __init__(self):
        self.failures: list[str] = []

    def say(self, msg: str) -> None:
        print(msg, flush=True)

    def check(self, ok: bool, msg: str) -> bool:
        if not ok:
            self.failures.append(msg)
            print(f"FAIL: {msg}", flush=True)
        return ok


class CompileCounter:
    """Counts backend compiles through JAX's monitoring events."""

    def __init__(self):
        from jax import monitoring
        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, duration, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def _digest(arr) -> str:
    import numpy as np
    return hashlib.sha256(np.asarray(arr, np.int64).tobytes()).hexdigest()


def _host_schedule(graph, order, n_stages, system):
    """The host reference: repair(rho(order)) under the same system."""
    from repro.core import repair, rho
    assign = rho(graph, order, n_stages, system)
    return repair(graph, assign, n_stages,
                  mem_capacity=system.capacity_vector())


def _synthetic_wave(rng, bucket: int, count: int) -> list:
    """``count`` graphs of the scenario families, sizes inside one bucket."""
    from repro.eval.scenarios import SYNTH_FAMILIES, synthetic_dag
    lo = max(5, bucket // 2 + 1)
    return [synthetic_dag(SYNTH_FAMILIES[i % len(SYNTH_FAMILIES)], rng,
                          int(rng.integers(lo, bucket + 1)))
            for i in range(count)]


def build_traffic(seed: int = 0):
    """Waves of ``WAVE`` distinct requests, each one service flush:
    (label, graphs, system).  Uniform k = 4 waves cover buckets 8-64,
    one heterogeneous and one memory-capped system take two waves each,
    and the ten Table-I graphs ride in one wave with six fillers."""
    import numpy as np
    from repro.core import PipelineSystem, all_model_graphs
    from repro.eval.scenarios import hetero_grid

    rng = np.random.default_rng(seed)
    uniform = PipelineSystem(N_STAGES)
    cells = {sc.name: sc for sc in hetero_grid()}
    waves = []
    for bucket in (8, 16, 32, 64):
        for _ in range(4):
            waves.append(("uniform", _synthetic_wave(rng, bucket, WAVE),
                          uniform))
    hetero = cells[f"hetero/k{N_STAGES}"].resolve_system([])
    for bucket in (16, 32):
        waves.append(("hetero", _synthetic_wave(rng, bucket, WAVE), hetero))
    memcap_cell = cells[f"memcap/k{N_STAGES}"]
    memcap_graphs = [_synthetic_wave(rng, b, WAVE) for b in (16, 32)]
    memcap = memcap_cell.resolve_system(sum(memcap_graphs, []))
    for graphs in memcap_graphs:
        waves.append(("memcap", graphs, memcap))
    table1 = list(all_model_graphs().values())
    waves.append(("table1", table1 + _synthetic_wave(
        rng, 32, WAVE - len(table1)), uniform))
    return waves


def phase_serve(smoke: Smoke, sched, counter: CompileCounter) -> None:
    import numpy as np
    from repro.core import evaluate_schedule, validate_monotone
    from repro.serving import SchedulerService

    waves = build_traffic()
    n_req = sum(len(g) for _, g, _ in waves)

    # warmup: every served wave once through the engine (use_cache=False,
    # as SchedulerService.warmup does), so the window reuses its programs
    c0, t0 = counter.count, time.perf_counter()
    for _, graphs, system in waves:
        sched.schedule_many(graphs, N_STAGES, system, use_cache=False)
    t_warm = time.perf_counter() - t0
    n_warm = counter.count - c0
    smoke.say(f"serve: warmup {len(waves)} waves, {n_warm} compiles, "
              f"{t_warm:.1f} s (compile included)")

    impls = {}
    for bucket_n, bucket_b, _, k, system, impl in \
            sched._decoder.compiled_shapes:
        kind = ("conditioned" if system.profile_features().any()
                else "uniform")
        impls.setdefault((kind, bucket_n), set()).add(impl)
    for (kind, bucket_n), found in sorted(impls.items()):
        smoke.say(f"serve: decode impl {kind:11s} bucket {bucket_n:5d} -> "
                  f"{','.join(sorted(found))}")
        want = "scan" if kind == "conditioned" else "kernel"
        smoke.check(found == {want},
                    f"{kind} bucket {bucket_n} ran {sorted(found)}, "
                    f"expected {want}")

    service = SchedulerService(sched, max_batch=WAVE, max_wait_ms=200.0,
                               max_queue=4 * n_req)
    served = []
    c0, t0 = counter.count, time.perf_counter()
    try:
        for label, graphs, system in waves:
            futs = [service.submit(g, N_STAGES, system) for g in graphs]
            served += [(label, g, system, f.result(timeout=600))
                       for g, f in zip(graphs, futs)]
        t_window = time.perf_counter() - t0
        n_window = counter.count - c0
        stats = service.stats()
    finally:
        service.close(timeout=60)
    smoke.say(f"serve: {n_req} requests in {t_window:.3f} s = "
              f"{n_req / t_window:.1f} requests/s, p50 {stats.p50_ms:.2f} ms"
              f", p99 {stats.p99_ms:.2f} ms (first chip reading, not a "
              f"benchmark)")
    smoke.check(n_window == 0,
                f"{n_window} compiles inside the served window")
    smoke.check(stats.completed == n_req and stats.failed == 0,
                f"completed {stats.completed}/{n_req}, failed {stats.failed}")
    smoke.check(stats.degraded == 0 and stats.worker_restarts == 0,
                f"degraded {stats.degraded}, worker restarts "
                f"{stats.worker_restarts}")

    golden = json.loads(
        (ROOT / "tests" / "golden" / "dnn_schedules.json").read_text())
    by_label = {}
    for label, g, system, res in served:
        by_label[label] = by_label.get(label, 0) + 1
        name = f"{label}:{g.model_name or g.n}"
        if not smoke.check(res["served_by"] == "policy",
                           f"{name} served by {res['served_by']}"):
            continue
        assign = np.asarray(res.assignment)
        smoke.check(validate_monotone(g, assign, N_STAGES),
                    f"{name}: schedule breaks a dependency")
        host = _host_schedule(g, res["order"], N_STAGES, system)
        smoke.check(np.array_equal(host, assign),
                    f"{name}: device rho/repair differs from host")
        if label == "memcap":
            smoke.check(evaluate_schedule(g, assign, system).capacity_ok,
                        f"{name}: over a stage's memory capacity")
        snap = golden["models"].get(g.model_name)
        if label == "table1" and snap is not None:
            smoke.check(_digest(res["order"]) == snap["order_sha256"]
                        and _digest(assign) == snap["assign_sha256"],
                        f"{g.model_name}: k=4 schedule differs from the "
                        f"golden digest")
    n_table1 = sum(1 for label, g, _, _ in served
                   if label == "table1" and g.model_name in golden["models"])
    smoke.check(n_table1 == len(golden["models"]),
                f"{n_table1} Table-I graphs served, expected "
                f"{len(golden['models'])}")
    smoke.say(f"serve: checked {len(served)} results {by_label}, "
              f"{n_table1} Table-I golden digests")


def phase_oracle(smoke: Smoke) -> None:
    import numpy as np
    from repro.core import PipelineSystem
    from repro.eval import ExactOracle
    from repro.eval.scenarios import SYNTH_FAMILIES, hetero_system, \
        synthetic_dag

    rng = np.random.default_rng(1)
    graphs = [synthetic_dag(fam, rng, n) for fam in SYNTH_FAMILIES
              for n in (6, 10, 14, 20, 30) for _ in range(2)]
    oracle = ExactOracle()
    cells = [(k, PipelineSystem(k)) for k in (2, 4, 6)]
    cells.append((4, hetero_system(4, seed=7)))
    t0 = time.perf_counter()
    n_bad = 0
    for k, system in cells:
        dev = oracle.solve_many(graphs, k, system)
        host = ExactOracle.solve_many_host(graphs, k, system)
        n_bad += sum(not np.array_equal(d.assignment, h.assignment)
                     for d, h in zip(dev, host))
    smoke.say(f"oracle: {len(graphs)} graphs x {len(cells)} systems, "
              f"{time.perf_counter() - t0:.1f} s (compile included)")
    smoke.check(n_bad == 0,
                f"oracle: {n_bad} device assignments differ from host "
                f"exact_dp")


def _train_batch(rng, k: int, bucket_n: int = 64, batch: int = 64):
    from repro.core import PipelineSystem
    from repro.core.rl import pack_graphs
    from repro.eval.scenarios import SYNTH_FAMILIES, synthetic_dag
    graphs = [synthetic_dag(SYNTH_FAMILIES[i % len(SYNTH_FAMILIES)], rng,
                            int(rng.integers(5, 51)))
              for i in range(batch)]
    return pack_graphs(graphs, k, PipelineSystem(k), bucket_n=bucket_n)


def phase_train(smoke: Smoke) -> None:
    import jax
    import numpy as np
    from repro.core import PipelineSystem
    from repro.core.rl import RLTrainer

    stage_counts = (2, 3, 4, 6, 8)
    trainer = RLTrainer(system=PipelineSystem(stage_counts[0]), hidden=128,
                        lr=3e-4, seed=0, stage_counts=stage_counts)
    rng = np.random.default_rng(2)
    key = jax.random.PRNGKey(0)
    for step in range(TRAIN_STEPS):
        k = stage_counts[step % len(stage_counts)]
        batch = _train_batch(rng, k)
        t0 = time.perf_counter()
        m = trainer.train_step(batch, jax.random.fold_in(key, step),
                               n_stages=k)
        loss, reward = float(m["loss"]), float(m["reward_sample"])
        smoke.say(f"train: step {step} k={k} loss {loss:.5f} reward "
                  f"{reward:.4f} {time.perf_counter() - t0:.2f} s")
        smoke.check(np.isfinite(loss) and np.isfinite(reward),
                    f"train step {step}: loss {loss}, reward {reward}")


def phase_ingest(smoke: Smoke, sched) -> None:
    import numpy as np
    from repro.core import PipelineSystem, validate_monotone
    from repro.ingest import ingest_model

    for arch in ("whisper-tiny", "xlstm-350m"):
        t0 = time.perf_counter()
        res = sched.schedule_model(arch, n_stages=N_STAGES, n_nodes=64,
                                   smoke=False)
        g = ingest_model(arch, n_nodes=64, smoke=False).graph
        rep = res["ingest"]
        smoke.say(f"ingest: {arch} |V|={g.n} warnings {rep['n_warnings']} "
                  f"lower+compile {rep['timing']['lower_s'] + rep['timing']['compile_s']:.1f} s "
                  f"coarsen {rep['timing']['coarsen_s']:.1f} s, total "
                  f"{time.perf_counter() - t0:.1f} s")
        assign = np.asarray(res.assignment)
        smoke.check(res["served_by"] == "policy"
                    and validate_monotone(g, assign, N_STAGES),
                    f"{arch}: schedule not from the policy or invalid")
        host = _host_schedule(g, res["order"], N_STAGES,
                              PipelineSystem(N_STAGES))
        smoke.check(np.array_equal(host, assign),
                    f"{arch}: device rho/repair differs from host")


def run_one_chip(smoke: Smoke, counter: CompileCounter) -> None:
    from repro.core import RespectScheduler

    t0 = time.perf_counter()
    sched = RespectScheduler.from_release(max_compiled=64)
    if not smoke.check(sched.release is not None and sched.hidden == 128,
                       "release checkpoint not loaded at hidden 128"):
        return
    smoke.say(f"release: {sched.release.get('version')} hidden "
              f"{sched.hidden}, loaded in {time.perf_counter() - t0:.2f} s")
    phase_serve(smoke, sched, counter)
    phase_oracle(smoke)
    phase_train(smoke)
    phase_ingest(smoke, sched)


def _data_parallel_diff(smoke: Smoke, label: str, batch, gate: bool,
                        **trainer_kw) -> None:
    """Sharded REINFORCE step on 4 chips vs the single-device step at equal
    global batch, ``FOUR_CHIP_STEPS`` steps; max parameter difference."""
    import jax
    import jax.numpy as jnp
    from repro.core import PipelineSystem
    from repro.core.rl import RLTrainer

    system = PipelineSystem(N_STAGES)
    single = RLTrainer(n_stages=N_STAGES, system=system, seed=0,
                       **trainer_kw)
    sharded = RLTrainer(n_stages=N_STAGES, system=system, seed=0,
                        n_devices=4, **trainer_kw)
    key = jax.random.PRNGKey(0)
    for step in range(FOUR_CHIP_STEPS):
        key, k = jax.random.split(key)
        t0 = time.perf_counter()
        r1 = float(single.train_step(batch, k)["reward_sample"])
        t1 = time.perf_counter()
        r4 = float(sharded.train_step(batch, k)["reward_sample"])
        t4 = time.perf_counter()
        smoke.say(f"four-chips: {label} step {step} reward 1-chip {r1:.6f} "
                  f"4-chip {r4:.6f} ({t1 - t0:.2f} s / {t4 - t1:.2f} s)")
    diff = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree.leaves(single.params), jax.tree.leaves(sharded.params)))
    smoke.say(f"four-chips: {label} max |param diff| after "
              f"{FOUR_CHIP_STEPS} steps {diff:.3e} (tolerance "
              f"{FOUR_CHIP_TOL:.0e}{'' if gate else ', reported only'})")
    if gate:
        smoke.check(diff < FOUR_CHIP_TOL,
                    f"{label}: data-parallel params differ by {diff}")


def run_four_chips(smoke: Smoke) -> None:
    """The comparison of tests/test_train_engine.py on four chips — its
    graphs, width and learning rate, whose tolerance it holds — then the
    release recipe's width, reported beside it."""
    import jax
    import numpy as np
    from repro.core import PipelineSystem, sample_dag
    from repro.core.rl import pack_graphs

    if not smoke.check(len(jax.devices()) == 4,
                       f"--four-chips needs 4 devices, found "
                       f"{len(jax.devices())}"):
        return
    rng = np.random.default_rng(0)
    graphs = [sample_dag(rng, n=int(rng.integers(10, 25)), deg=3)
              for _ in range(8)]
    batch = pack_graphs(graphs, N_STAGES, PipelineSystem(N_STAGES),
                        label_method="dp")
    _data_parallel_diff(smoke, "test config (hidden 16)", batch, True,
                        hidden=16, lr=3e-3)
    _data_parallel_diff(smoke, "release width (hidden 128)",
                        _train_batch(np.random.default_rng(3), N_STAGES),
                        False, hidden=128, lr=3e-4)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the data-parallel training comparison "
                         "on the 4 chips of a host")
    args = ap.parse_args()

    if not (ROOT / "src" / "repro").is_dir():
        print(f"no repository checkout around {ROOT}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache(ROOT)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {dev.platform}", file=sys.stderr)
        return 2
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    smoke = Smoke()
    smoke.say(f"device: {json.dumps(device)}, compile cache {cache_dir}")
    counter = CompileCounter()
    t0 = time.perf_counter()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        try:
            if args.four_chips:
                run_four_chips(smoke)
            else:
                run_one_chip(smoke, counter)
        except Exception as exc:    # noqa: BLE001 — report, then fail
            import traceback
            traceback.print_exc()
            smoke.check(False, f"{type(exc).__name__}: {exc}")
    for w in caught:
        if issubclass(w.category, RuntimeWarning):
            smoke.check(False, f"RuntimeWarning: {w.message}")
    stats = dev.memory_stats() or {}
    smoke.say(f"memory: peak {stats.get('peak_bytes_in_use')} bytes in use "
              f"on device 0; {counter.count} compiles; "
              f"{time.perf_counter() - t0:.1f} s")
    if smoke.failures:
        print(f"{len(smoke.failures)} check(s) failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
