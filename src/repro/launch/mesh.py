"""Production mesh construction.

Kept as FUNCTIONS (never module-level constants): importing this module must
not touch jax device state, so smoke tests see 1 CPU device while
``dryrun.py`` — which sets ``--xla_force_host_platform_device_count=512``
before any jax import — sees the full placeholder fleet.

Mesh layout:

* single-pod: (16, 16) over ("data", "model") — 256 chips (v5e pod);
* multi-pod:  (2, 16, 16) over ("pod", "data", "model") — 512 chips; the
  ``pod`` axis is pure data parallelism whose gradient all-reduce crosses
  the inter-pod DCI once per step (and is the int8-compression target);
* pipeline:   optional ("pipe", "data", "model") mesh for the
  RESPECT-partitioned pipeline runner (beyond-paper feature).
"""

from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_pipeline_mesh", "small_test_mesh"]


def _mk(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_pipeline_mesh(n_stages: int, data: int = 8, model: int = 4):
    """Mesh for the shard_map pipeline runner (pipe axis outermost)."""
    return _mk((n_stages, data, model), ("pipe", "data", "model"))


def small_test_mesh(data: int = 2, model: int = 4):
    """CI-sized mesh for subprocess tests (8 host devices)."""
    return _mk((data, model), ("data", "model"))
