"""The controls: the reference in a lower precision, put in the program's
place, reads not correct where the program reads correct: the pointer
network's products for the logit gap, rho's cost table (bfloat16) for the
bottleneck excess, on the configurations' uniform chain and on a
heterogeneous one.

On the chip the control runs at the cell's own size through
``bench/probe.py readings`` (the TPU's own ``high`` and ``default``
precisions).  Here, on the CPU, which runs every float32 product in full,
the lower precision is emulated with bfloat16 passes; at a size a test
run holds, the one-pass product flips a served node where the three-pass
one does not yet (it needs the cell's whole window, see PERF.md).
"""

import dataclasses
import json
import sys
from pathlib import Path

import ml_dtypes
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench.graphs import table1_variants  # noqa: E402
from bench.lib import reference as ref  # noqa: E402
from bench.lib.graphspec import to_program  # noqa: E402
from bench.runners.serve import compare, control_excess, control_gap  # noqa: E402


def _system(chain: str, cfg: dict) -> dict:
    """The configuration's uniform chain, or a seeded heterogeneous one
    (``eval.scenarios.hetero_system``): the bottleneck excess limit holds
    for both."""
    if chain == "uniform":
        return cfg["system"]
    from repro.eval.scenarios import hetero_system
    return {k: (list(v) if isinstance(v, tuple) else v)
            for k, v in dataclasses.asdict(hetero_system(4, 16)).items()}


@pytest.mark.parametrize("chain", ["uniform", "hetero"])
def test_lower_precision_reference_fails_the_limit(chain):
    from repro.core import PipelineSystem, RespectScheduler
    cfg = json.loads((ROOT / "bench/configs/coral-table1-k4.json").read_text())
    traffic = json.loads(
        (ROOT / "bench/traffic/table1-open80.json").read_text())
    system = _system(chain, cfg)
    specs = table1_variants.make(np.random.default_rng([5, 1]), 10,
                                 traffic["generator_args"])
    sched = RespectScheduler.from_release(ROOT / cfg["release"])
    res = sched.schedule_many([to_program(s) for s in specs], 4,
                              PipelineSystem(**system), use_cache=False)
    orders = [r["order"] for r in res]
    params = ref.load_params(ROOT / cfg["release"])
    limit = cfg["correct"]["logit_gap_max"]
    program = compare(params, specs, orders, [r["assignment"] for r in res],
                      system, n_assign=2)
    assert program["logit_gap_max"] <= limit
    assert program["invalid_schedules"] == 0
    assert program["bottleneck_excess"] <= cfg["correct"]["bottleneck_excess"]
    assert control_gap(params, specs, orders, "bf16x1", system) > limit
    assert control_excess(specs, orders, system, len(specs),
                          ml_dtypes.bfloat16) > \
        cfg["correct"]["bottleneck_excess"]


def test_emulated_products_are_ordered_by_precision():
    rng = np.random.default_rng(0)
    a = rng.normal(size=(8, 128)).astype(np.float32)
    b = rng.normal(size=(128, 16)).astype(np.float32)
    exact = a.astype(np.float64) @ b.astype(np.float64)
    errs = [float(np.max(np.abs(np.asarray(ref.DOTS[p](a, b)) - exact)))
            for p in ("highest", "bf16x3", "bf16x1")]
    assert errs[0] < errs[1] < errs[2]
    assert errs[2] > 1e-3 and errs[1] < 1e-3
