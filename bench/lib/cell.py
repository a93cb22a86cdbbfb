"""One run of one cell: set-up, the measured window, the comparison with
the reference, and the result line."""

from __future__ import annotations

import json
import shutil
import sys
import time

from bench.lib import device
from bench.lib.spec import BENCH


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def checks_of(numbers: dict, limits: dict) -> dict:
    return {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}


def run_cell(parts: dict, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True,
             scheduler_wrap=None) -> dict:
    """Run the cell and return the result line as a dict.

    The traffic file's runner (``bench/runners/<name>.py``) does the work.
    ``require_tpu=False`` and ``scheduler_wrap`` exist for the harness's
    own tests: the first skips the look for a chip, the second wraps the
    program's scheduler (for example with a fault) under the timed path.
    Raises :class:`device.NoChipError` when the chip is missing."""
    import jax

    chips = int(parts["cell"]["chips"])
    if require_tpu:
        dev = device.require_chips(chips)
        peaks = device.peaks_for(dev["kind"])
    else:
        d0 = jax.devices()[0]
        dev = {"platform": d0.platform, "kind": d0.device_kind,
               "count": chips}
        peaks = device.PEAKS.get(d0.device_kind)
    counter = device.CompileCounter()

    cfg = parts["config"]
    run = parts["runner"].Run(parts, seed, seconds, trace, t_start, log=log,
                              wrap=scheduler_wrap)
    run.setup(counter)
    trace_dir = None
    if trace:
        trace_dir = BENCH / ".cache" / "trace"
        shutil.rmtree(trace_dir, ignore_errors=True)
    run.window(counter, trace_dir)
    out = run.outcomes()
    e2e = run.end_to_end(out)
    dev["memory_peak_bytes"] = device.memory_peak_bytes(chips)
    log(run.describe(out))

    red = None
    if trace:
        from bench.lib.trace import read_trace
        t0 = time.perf_counter()
        red = read_trace(trace_dir, chips)
        log(f"trace: read in {time.perf_counter() - t0:.1f} s")
    rec = run.layer_record(out, red, peaks)

    # the comparison: the program's state goes first, then the reference
    run.release_program()
    t0 = time.perf_counter()
    numbers, what = run.check(out)
    limits = cfg["correct"]
    correct = numbers is not None and all(v <= limits[k]
                                          for k, v in numbers.items())
    log(f"reference: {what} in {time.perf_counter() - t0:.1f} s")

    line: dict = {"correct": correct, "attempted": len(out["asked"]),
                  "failed": out["failed"], "device": dev}
    if trace:
        metrics = {}
        for m, reader in parts["per_layer"]:
            v = reader.read(rec)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        line["metrics"] = metrics
        if red is not None:
            from bench.lib.trace import breakdown
            dev["busy_s"] = red["busy_s"]
            dev["window_s"] = red["window_s"]
            line["breakdown"] = breakdown(red)
            log("idle by host span: " + json.dumps(red["idle_by_host"]))
    else:
        line["metrics"] = {m["name"]: {"value": float(e2e[m["name"]]),
                                       "unit": m["unit"]}
                           for m in parts["end_to_end"]}
    line["checks"] = checks_of(numbers or {}, limits)
    return line
