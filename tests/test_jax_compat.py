"""The sharding API the model/runtime stack calls resolves on installed JAX.

The stack calls current JAX directly (``jax.make_mesh`` with axis types,
``jax.set_mesh``, ``jax.shard_map``, ``jax.lax.pcast``); the two helpers it
still needs on top — `current_mesh` (None outside a mesh context) and
`tree_as_shardings` — live in `repro.parallel.sharding`.  These tests pin
that surface: mesh construction and activation, and `shard` / `logical`
resolving PartitionSpecs against the active mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.parallel.sharding import current_mesh, tree_as_shardings


def test_axis_type_and_make_mesh_resolve():
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    assert mesh.axis_names == ("data", "model")
    assert dict(mesh.shape) == {"data": 1, "model": 1}


def test_get_abstract_mesh_tracks_mesh_context():
    assert current_mesh() is None
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    with jax.set_mesh(mesh):
        m = current_mesh()
        assert m is not None
        assert tuple(m.axis_names) == ("data", "model")
    assert current_mesh() is None


def test_shard_and_logical_work_without_new_api_symbols():
    from repro.parallel.sharding import logical, shard

    x = jnp.ones((4, 8))
    # no mesh in scope: shard is the identity (the arch-smoke path)
    np.testing.assert_array_equal(np.asarray(shard(x, "batch", None)),
                                  np.asarray(x))
    # Auto axes, as the production mesh builds them: on Explicit axes (the
    # make_mesh default) a sharding constraint asserts instead of resharding
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    with jax.set_mesh(mesh):
        spec = logical("batch", "mlp")
        # the ("pod", "data") batch rule prunes to the in-mesh axes; JAX
        # normalises the one-axis tuple ("data",) to "data"
        assert spec == P(("data",), "model")
        assert tuple(spec) == ("data", "model")
        y = jax.jit(lambda t: shard(t, "batch", "mlp"))(x)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


def test_mesh_module_imports_and_builds_host_mesh():
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh()
    assert "data" in mesh.axis_names and "model" in mesh.axis_names


def test_tree_as_shardings_wraps_specs_for_jit():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    tree = {"w": P("data", None), "b": None, "n": (P("model"), None)}
    out = tree_as_shardings(mesh, tree)
    assert isinstance(out["w"], NamedSharding)
    assert out["b"] is None
    assert isinstance(out["n"][0], NamedSharding) and out["n"][1] is None
    # the wrapped shardings are jit-accepted with no mesh context
    f = jax.jit(lambda x: x + 1, in_shardings=out["w"], out_shardings=out["w"])
    np.testing.assert_array_equal(np.asarray(f(jnp.zeros((2, 2)))),
                                  np.ones((2, 2)))


def test_pcast_and_shard_map_resolve():
    mesh = jax.make_mesh((1,), ("stage",))

    def body(x):
        return jax.lax.pcast(jnp.ones(3), ("stage",), to="varying").sum() * x

    f = jax.shard_map(body, mesh=mesh, in_specs=(P("stage"),),
                      out_specs=P("stage"))
    np.testing.assert_array_equal(np.asarray(f(jnp.arange(4.0))),
                                  np.arange(4.0) * 3)


def test_set_mesh_usable_as_context_manager():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    with jax.set_mesh(mesh):
        pass
    with pytest.raises(ValueError):
        jax.make_mesh((7, 3), ("a", "b"))  # device count mismatch still raises
