#!/usr/bin/env python3
"""Run one benchmark cell once on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic and per-layer metrics are found by
name from ``BENCHMARK.json`` and the files under ``bench/``.  With
``--trace 0`` the last line of standard output is the result with the
cell's end-to-end metrics; with ``--trace 1`` a profiler trace of the
window gives its per-layer metrics.  The numbers that decide ``correct``
are printed with their limits as the last lines of standard error and
under ``checks`` in the result.  Without a TPU, or with fewer chips than
the cell asks for, the run exits 3 and prints no result.
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


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from bench.lib import device
    from bench.lib.cell import log, run_cell
    from bench.lib.spec import load_cell

    if not (ROOT / "src" / "repro").is_dir():
        log(f"no program checkout around {ROOT}")
        return 2
    parts = load_cell(args.workload)
    cache = device.enable_compile_cache(ROOT)
    log(f"compile cache: {cache}")
    try:
        line = run_cell(parts, args.seed, args.seconds, bool(args.trace),
                        T_START)
    except device.NoChipError as exc:
        log(str(exc))
        return 3
    for name, c in line["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
