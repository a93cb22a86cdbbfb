#!/usr/bin/env python3
"""Readings that set the benchmark's fixed numbers, made on the chip and
kept apart from the benchmark's own runs.

    python3 bench/probe.py sweep --workload <open cell> --rates 20,30,40 --seconds 20
    python3 bench/probe.py readings --workload <cell> --seeds 1,2,3 --seconds 20
    python3 bench/probe.py trace-summary

``sweep`` serves the cell's traffic at each offered rate, with one
scheduler warmed once, and prints per rate the latency percentiles, the
backlog when the window closes and how the latency grows across the
window: the knee is the highest rate whose backlog does not grow.

``readings`` serves one window per seed and prints the numbers ``correct``
compares for the program (the lower readings) beside those of the
controls on the same requests: the reference computed in a lower matmul
precision (``high`` and ``default``) for the logit gap, the reference's
rho with its cost table in bfloat16 for the bottleneck excess, and the
program with its own bfloat16 decode path switched on (``decode_bf16``).

``trace-summary`` prints the planes, lines and busiest events of the
newest trace of ``bench/.cache/trace`` (a ``--trace 1`` run leaves it).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def _setup(workload: str):
    from bench.lib import device
    from bench.lib.spec import load_cell
    parts = load_cell(workload)
    device.enable_compile_cache(ROOT)
    dev = device.require_chips(int(parts["cell"]["chips"]))
    return parts, dev, device.CompileCounter()


def _emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def sweep(args) -> None:
    import numpy as np
    from bench.runners.serve import ServeRun
    parts, dev, counter = _setup(args.workload)
    sched = None
    for j, rate in enumerate(float(r) for r in args.rates.split(",")):
        parts["traffic"]["rate_per_s"] = rate
        run = ServeRun(parts, args.seed + j, args.seconds, False,
                       time.perf_counter(), log=lambda m: print(m, file=sys.stderr))
        run.setup(counter, sched=sched)
        sched = run.sched
        run.window(counter, None)
        out = run.outcomes()
        lat = run.t_done - run.t_due
        third = len(lat) // 3
        backlog = int(np.sum(run.t_due <= run.t1) - np.sum(run.t_done <= run.t1))
        _emit({"rate_per_s": rate, "requests": len(out["asked"]),
               "failed": out["failed"],
               "p50_ms": float(np.nanpercentile(lat, 50) * 1e3),
               "p95_ms": float(np.nanpercentile(lat, 95) * 1e3),
               "first_third_mean_ms": float(np.nanmean(lat[:third]) * 1e3),
               "last_third_mean_ms": float(np.nanmean(lat[-third:]) * 1e3),
               "backlog_at_close": backlog,
               "drain_s": float(np.nanmax(run.t_done) - run.t1),
               "flushes": run.stats.batches,
               "compiles_in_window": run.window_compiles})


def readings(args) -> None:
    import numpy as np
    from bench.lib import reference as ref
    import ml_dtypes
    from bench.runners.serve import (ServeRun, compare, control_excess,
                                 control_gap, load_scheduler)
    from bench.lib.spec import ROOT as root
    parts, dev, counter = _setup(args.workload)
    cfg = parts["config"]
    params = ref.load_params(root / cfg["release"])
    bf16 = load_scheduler(cfg, decode_bf16=True) if args.bf16 else None
    sched = None
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        run = ServeRun(parts, seed, args.seconds, False, time.perf_counter(),
                       log=lambda m: print(m, file=sys.stderr))
        run.setup(counter, sched=sched)
        sched = run.sched
        run.window(counter, None)
        out = run.outcomes()
        idx, n_assign = run.sample(out)
        specs, orders, assigns = run.served(idx)
        row = {"seed": seed, "compared": len(idx), "failed": out["failed"],
               "program": compare(params, specs, orders, assigns,
                                  cfg["system"], n_assign)}
        for prec in ("high", "default"):
            row[f"reference_{prec}"] = control_gap(params, specs, orders, prec,
                                                   cfg["system"])
        row["reference_rho_bf16"] = control_excess(
            specs, orders, cfg["system"], n_assign, ml_dtypes.bfloat16)
        if bf16 is not None:
            res = []
            mb = int(cfg["service"]["max_batch"])
            for lo in range(0, len(idx), mb):
                gs = [run.graph_of(i) for i in idx[lo: lo + mb]]
                res += bf16.schedule_many(gs, run.k, run.system,
                                          use_cache=False)
            row["program_decode_bf16"] = compare(
                params, specs, [r["order"] for r in res],
                [np.asarray(r["assignment"]) for r in res], cfg["system"],
                n_assign)
        row["seconds"] = time.perf_counter() - t0
        _emit(row)


def trace_summary(args) -> None:
    import glob
    import jax
    files = sorted(glob.glob(str(ROOT / "bench" / ".cache" / "trace" / "**"
                                 / "*.xplane.pb"), recursive=True))
    pd = jax.profiler.ProfileData.from_file(files[-1])
    for plane in pd.planes:
        for line in plane.lines:
            tot: dict[str, float] = {}
            first = None
            n = 0
            for ev in line.events:
                n += 1
                tot[ev.name] = tot.get(ev.name, 0.0) + ev.duration_ns * 1e-9
                if first is None:
                    first = (ev.start_ns, dict(ev.stats))
            top = sorted(tot.items(), key=lambda kv: -kv[1])[:12]
            _emit({"plane": plane.name, "line": line.name, "events": n,
                   "first": str(first)[:300], "top": top})


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    s = sub.add_parser("sweep")
    s.add_argument("--workload", required=True)
    s.add_argument("--rates", required=True)
    s.add_argument("--seconds", type=float, default=20.0)
    s.add_argument("--seed", type=int, default=1000)
    r = sub.add_parser("readings")
    r.add_argument("--workload", required=True)
    r.add_argument("--seeds", required=True)
    r.add_argument("--seconds", type=float, default=20.0)
    r.add_argument("--bf16", type=int, default=1)
    sub.add_parser("trace-summary")
    args = ap.parse_args()
    {"sweep": sweep, "readings": readings,
     "trace-summary": trace_summary}[args.cmd](args)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
