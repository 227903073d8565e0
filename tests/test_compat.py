"""The compat wrappers over the installed JAX's shard_map and mesh APIs."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import compat


def test_abstract_mesh_both_generations():
    m = compat.abstract_mesh((16, 16), ("data", "model"))
    assert compat.mesh_axis_sizes(m) == {"data": 16, "model": 16}
    m3 = compat.abstract_mesh((2, 16, 16), ("pod", "data", "model"))
    assert compat.mesh_axis_sizes(m3) == {"pod": 2, "data": 16, "model": 16}


def test_make_mesh_drops_axis_types_when_unsupported():
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    assert compat.mesh_axis_sizes(mesh) == {"data": 1, "model": 1}


def test_mesh_axis_sizes_concrete_mesh():
    mesh = compat.make_mesh((1,), ("data",))
    assert compat.mesh_axis_sizes(mesh) == {"data": 1}


def test_shard_map_wrapper_full_manual():
    mesh = compat.make_mesh((1,), ("data",))
    f = compat.shard_map(lambda x: x * 2, mesh, in_specs=P(), out_specs=P())
    np.testing.assert_array_equal(f(jnp.arange(3.0)), 2 * jnp.arange(3.0))


def test_shard_map_wrapper_partial_manual_under_jit():
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    f = compat.shard_map(lambda x: x + jax.lax.axis_index("data"),
                         mesh, in_specs=P(), out_specs=P(),
                         axis_names={"data"})
    np.testing.assert_array_equal(jax.jit(f)(jnp.zeros(2)), jnp.zeros(2))
