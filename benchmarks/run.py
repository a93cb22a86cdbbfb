"""Benchmark harness entry point: one module per paper table/figure plus the
beyond-paper pod-scale benches.  Prints ``name,us_per_call,derived`` CSV.

    PYTHONPATH=src python -m benchmarks.run [--only fig3,fig4,...]
    PYTHONPATH=src python -m benchmarks.run --smoke        # CI: fast subset
                                                          # + BENCH_smoke.json
"""

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

BENCHES = ["table1", "fig3", "fig4", "fig5", "partitioner", "kernels",
           "decode", "roofline", "batched", "train", "traffic", "eval",
           "ingest"]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: " + ",".join(BENCHES))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced batched-engine bench; writes the per-PR "
                         "perf-trajectory artifact (see --out-json)")
    ap.add_argument("--out-json", default=None,
                    help="summary artifact path (--smoke default: "
                         "BENCH_smoke.json).  Full runs use a different "
                         "config (batch 64), so they never overwrite the "
                         "checked-in smoke baselines unless pointed at them "
                         "explicitly.")
    ap.add_argument("--out-serve-json", default=None,
                    help="serving-split artifact path (decode vs rho+repair "
                         "vs fused; --smoke default: BENCH_serve.json)")
    args = ap.parse_args()

    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache(Path(__file__).resolve().parent.parent)
    from . import (batched_schedule_bench, decode_kernel_bench, eval_grid,
                   fig3_solving_time, fig4_inference_runtime,
                   fig5_gap_to_optimal, ingest_bench, kernels_bench,
                   partitioner_bench, roofline_table, serve_traffic_bench,
                   table1_graphs, train_bench)
    mods = {
        "table1": table1_graphs, "fig3": fig3_solving_time,
        "fig4": fig4_inference_runtime, "fig5": fig5_gap_to_optimal,
        "partitioner": partitioner_bench, "kernels": kernels_bench,
        "decode": decode_kernel_bench, "roofline": roofline_table,
        "batched": batched_schedule_bench, "train": train_bench,
        "traffic": serve_traffic_bench, "eval": eval_grid,
        "ingest": ingest_bench,
    }
    if args.smoke and args.only:
        ap.error("--smoke runs the fixed CI subset; drop --only or --smoke")
    print("name,us_per_call,derived")
    t0 = time.perf_counter()
    if args.smoke:
        batched_schedule_bench.run(
            smoke=True, out_json=args.out_json or "BENCH_smoke.json",
            out_serve_json=args.out_serve_json or "BENCH_serve.json")
    else:
        want = args.only.split(",") if args.only else BENCHES
        unknown = [n for n in want if n not in mods]
        if unknown:
            ap.error(f"unknown bench(es) {','.join(unknown)}; "
                     f"choose from: {','.join(BENCHES)}")
        for name in want:
            if name == "batched":
                mods[name].run(out_json=args.out_json,
                               out_serve_json=args.out_serve_json)
            else:
                mods[name].run()
    print(f"# total {time.perf_counter()-t0:.1f}s")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
