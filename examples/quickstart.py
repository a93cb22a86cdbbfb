#!/usr/bin/env python
"""Quickstart: schedule ResNet50 onto a 4-stage pipelined Edge TPU system.

Runs the full Fig. 1a flow — graph extraction, embedding, PtrNet decode, rho,
post-inference repair — with the three scheduler backends (RESPECT / exact /
commercial-compiler emulation) and reports simulated on-chip inference
runtime for each.

    PYTHONPATH=src python examples/quickstart.py [--model ResNet50] [--stages 4]
"""

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import numpy as np  # noqa: E402

from repro.core import (EDGETPU, RespectScheduler, build_model_graph,  # noqa: E402
                        compiler_partition, evaluate_schedule, exact_dp,
                        validate_monotone)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="ResNet50")
    ap.add_argument("--stages", type=int, default=4)
    ap.add_argument("--agent", default="artifacts/respect_agent.npz")
    args = ap.parse_args()

    g = build_model_graph(args.model)
    sys_ = EDGETPU.with_stages(args.stages)
    print(f"model {args.model}: |V|={g.n} deg={g.max_in_degree} "
          f"depth={g.depth} params={g.param_bytes.sum()/2**20:.1f} MiB")

    # --- RESPECT -------------------------------------------------------- #
    agent_path = Path(args.agent)
    if agent_path.exists():
        sched = RespectScheduler.load(agent_path)
        print(f"[agent] loaded {agent_path}")
    else:
        sched = RespectScheduler.init(seed=0)
        print("[agent] untrained weights (run examples/train_respect.py "
              "for the trained agent)")
    t0 = time.perf_counter()
    res = sched.schedule(g, args.stages, sys_)
    t_rl = time.perf_counter() - t0
    assert validate_monotone(g, res.assignment, args.stages)
    ev_rl = evaluate_schedule(g, res.assignment, sys_)

    # --- exact + compiler baselines ------------------------------------- #
    t0 = time.perf_counter()
    a_exact, _ = exact_dp(g, args.stages, sys_)
    t_exact = time.perf_counter() - t0
    ev_exact = evaluate_schedule(g, a_exact, sys_)

    t0 = time.perf_counter()
    a_comp = compiler_partition(g, args.stages, sys_)
    t_comp = time.perf_counter() - t0
    ev_comp = evaluate_schedule(g, a_comp, sys_)

    print(f"\n{'scheduler':12s} {'solve (ms)':>10s} {'runtime (ms)':>13s} "
          f"{'vs compiler':>12s}")
    base = ev_comp.bottleneck_s
    for name, t, ev in (("compiler", t_comp, ev_comp),
                        ("exact", t_exact, ev_exact),
                        ("RESPECT", t_rl, ev_rl)):
        print(f"{name:12s} {t*1e3:10.2f} {ev.bottleneck_s*1e3:13.3f} "
              f"{base/ev.bottleneck_s:11.2f}x")

    print("\nper-stage parameter placement (RESPECT):")
    for s in range(args.stages):
        mb = ev_rl.stage_params[s] / 2**20
        flag = " (over 8 MiB SRAM!)" if ev_rl.off_cache_bytes[s] > 0 else ""
        print(f"  stage {s}: {int((res.assignment == s).sum()):4d} ops, "
              f"{mb:6.2f} MiB params{flag}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
