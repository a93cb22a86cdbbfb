"""The harness: it refuses to run without a chip or outside a program
checkout, a run whose served answers are altered reads not correct, and a
cell on a heterogeneous chain runs and reads correct."""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

ARGS = ["--workload", "synth30-k4.closed64", "--seed", "3000000001",
        "--seconds", "1", "--trace", "0"]


def _run(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _no_result(stdout: str) -> bool:
    return not any(line.startswith("{") for line in stdout.splitlines())


def test_refuses_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode == 3, p.stderr[-2000:]
    assert "no TPU" in p.stderr
    assert _no_result(p.stdout)


def test_refuses_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)


class _AlterOneToken:
    """Wraps the program's scheduler and swaps two served nodes of the
    first result of every flush: a token altered where it is produced."""

    def __init__(self, inner):
        self._inner = inner

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def schedule_many(self, graphs, *args, **kw):
        out = self._inner.schedule_many(graphs, *args, **kw)
        order = np.asarray(out[0]["order"]).copy()
        order[[-1, -2]] = order[[-2, -1]]
        out[0]["order"] = order
        return out


def _small_cell(name: str):
    from bench.lib.spec import load_cell
    parts = load_cell(name)
    parts["config"]["service"]["max_batch"] = 4
    parts["traffic"]["max_per_s"] = 3000
    parts["traffic"]["check_sample"] = 10_000
    parts["traffic"]["assign_sample"] = 10_000
    return parts


def test_altered_answer_reads_not_correct():
    from bench.lib.cell import run_cell
    parts = _small_cell("synth30-k4.closed64")
    clean = run_cell(parts, 3000000001, 1.0, False, time.perf_counter(),
                     require_tpu=False)
    assert clean["correct"], json.dumps(clean["checks"])
    broken = run_cell(parts, 3000000001, 1.0, False, time.perf_counter(),
                      require_tpu=False, scheduler_wrap=_AlterOneToken)
    assert not broken["correct"]
    assert broken["checks"]["invalid_schedules"]["value"] > 0 or \
        broken["checks"]["logit_gap_max"]["value"] > \
        broken["checks"]["logit_gap_max"]["limit"]
    assert list(broken)[-1] == "checks"


#: a 4-stage Coral chain whose last two stages sit behind USB 2.0 (about
#: 40 MB/s effective) and the first two behind USB 3.0
USB2_CHAIN = {"compute_rate": [4.0e12] * 4, "compute_eff": [0.25] * 4,
              "link_bw": [320.0e6, 320.0e6, 40.0e6, 40.0e6],
              "cache_bytes": [8388608.0] * 4}


def test_heterogeneous_cell_reads_correct():
    from bench.lib.cell import run_cell
    from bench.lib.spec import load_cell
    parts = load_cell("table1-k4.open80")
    parts["config"]["system"] = dict(parts["config"]["system"], **USB2_CHAIN)
    parts["config"]["service"]["max_batch"] = 2
    parts["traffic"]["rate_per_s"] = 6.0
    line = run_cell(parts, 3000000002, 1.0, False, time.perf_counter(),
                    require_tpu=False)
    assert line["correct"], json.dumps(line["checks"])
    assert set(line["checks"]) == {"logit_gap_max", "invalid_schedules",
                                   "bottleneck_excess", "unanswered"}
    assert line["attempted"] > 0 and line["failed"] == 0
