"""Pin the JAX APIs the repo calls directly (``jax.make_mesh`` with
``axis_types``, ``jax.set_mesh``, ``jax.shard_map``,
``compiled.cost_analysis()``).

These run on the fast tier with ONE device — they exercise the API
contracts, not multi-device semantics (that's tests/test_distributed.py's
subprocess job).  A toolchain bump that renames one of them must fail
HERE, by name, instead of as an AttributeError buried in a subprocess
stderr dump.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import small_test_mesh


def _auto_mesh(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def test_make_mesh_auto_single_device():
    mesh = _auto_mesh((1,), ("data",))
    assert mesh.shape == {"data": 1}
    assert all(t == jax.sharding.AxisType.Auto for t in mesh.axis_types)


def test_small_test_mesh_uses_shim():
    # the production mesh constructors build Auto-axis meshes; on this
    # box a (1, 1) mesh is constructible
    mesh = small_test_mesh(data=1, model=1)
    assert mesh.size == 1
    assert all(t == jax.sharding.AxisType.Auto for t in mesh.axis_types)


def test_set_mesh_context_resolves_ambient_mesh():
    from repro.parallel.sharding import _current_mesh
    mesh = _auto_mesh((1,), ("data",))
    with jax.set_mesh(mesh):
        seen = _current_mesh()
        assert seen is not None and not seen.empty
        assert tuple(seen.axis_names) == ("data",)
        # the ambient mesh stays visible inside jit, where constrain runs
        traced = []
        jax.jit(lambda x: traced.append(_current_mesh()) or x)(1.0)
        assert traced[0] is seen
    # context exit restores "no ambient mesh"
    assert _current_mesh() is None


def test_shard_map_direct_and_partial_styles():
    mesh = _auto_mesh((1,), ("data",))
    x = jnp.asarray(np.arange(8.0).reshape(4, 2))

    def double(v):
        return v * 2.0

    direct = jax.shard_map(double, mesh=mesh, in_specs=P("data"),
                           out_specs=P("data"), check_vma=False)
    deco = functools.partial(jax.shard_map, mesh=mesh, in_specs=P("data"),
                             out_specs=P("data"), check_vma=False)(double)
    np.testing.assert_array_equal(np.asarray(direct(x)), np.asarray(x) * 2)
    np.testing.assert_array_equal(np.asarray(deco(x)), np.asarray(x) * 2)


def test_cost_analysis_returns_flat_dict():
    compiled = jax.jit(lambda a, b: a @ b).lower(
        jnp.ones((8, 8)), jnp.ones((8, 8))).compile()
    ca = compiled.cost_analysis()
    assert isinstance(ca, dict)
    assert float(ca.get("flops", 0.0)) > 0.0


def test_shard_map_psum_single_device():
    from repro.parallel.sharding import _inside_manual_context
    mesh = _auto_mesh((1,), ("data",))
    x = jnp.ones((2, 3))
    seen = []

    def f(v):
        seen.append(_inside_manual_context())
        return jax.lax.psum(v, "data")

    out = jax.shard_map(f, mesh=mesh, in_specs=P("data"),
                        out_specs=P("data"), check_vma=False)(x)
    np.testing.assert_array_equal(np.asarray(out), np.ones((2, 3)))
    assert seen == [True] and not _inside_manual_context()
