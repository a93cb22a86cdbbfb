"""Finds a cell's parts by name: ``BENCHMARK.json`` at the checkout root,
``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json``, the
runner ``bench/runners/<runner>.py`` that the traffic file names, and
the per-layer metric readers ``bench/metrics/<metric>.py``.  The runner
finds the rest of what the traffic file names the same way (for serving:
``bench/arrivals/``, ``bench/pools/``, ``bench/graphs/``) and refuses a
key that none of them reads.  Adding a cell, a mix, a generator, an
arrival process, a pool kind or a metric adds files; nothing here names
one."""

from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
ROOT = BENCH.parent


class SpecError(ValueError):
    """The benchmark's files do not describe the requested cell."""


def load_benchmark(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"no BENCHMARK.json in {root}")
    return json.loads(path.read_text())


def _json(kind: str, name: str) -> dict:
    path = BENCH / kind / f"{name}.json"
    if not path.is_file():
        raise SpecError(f"no {kind} file {path}")
    return json.loads(path.read_text())


def load_module(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no {kind} module {path}")
    mod_name = f"bench.{kind}.{name.replace('.', '_').replace('-', '_')}"
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    mod_spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    sys.modules[mod_name] = mod
    mod_spec.loader.exec_module(mod)
    return mod


def check_keys(where: str, have, need: set) -> None:
    """Refuses a file whose keys are not exactly ``need``."""
    missing, unknown = need - set(have), set(have) - need
    if missing or unknown:
        raise SpecError(f"{where}: missing keys {sorted(missing)}, keys no "
                        f"part reads {sorted(unknown)}")


def load_cell(name: str, root: Path = ROOT) -> dict:
    """The cell ``name`` with its config, traffic, runner and metrics:
    ``{"cell", "config", "traffic", "runner", "end_to_end", "per_layer"}``
    and whatever the runner resolves from the traffic file; the metric
    lists hold this cell's entries."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    config = _json("configs", cell["config"])
    traffic = _json("traffic", cell["traffic"])
    runner = load_module("runners", traffic.get("runner", ""))

    def applies(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if applies(m) and m["moves"] in e2e_names]
    return {
        "cell": cell,
        "config": config,
        "traffic": traffic,
        "runner": runner,
        **runner.resolve(traffic, f"traffic {cell['traffic']}"),
        "end_to_end": e2e,
        "per_layer": [(m, load_module("metrics", m["name"]))
                      for m in per_layer],
    }
