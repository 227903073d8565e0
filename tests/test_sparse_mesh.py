"""Production (shard_map + ppermute) sparse combine == dense combine.

Runs in a subprocess with 8 forced host devices (the main test process owns
a single-device jax runtime)."""
import os
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"   # never probe accelerator plugins
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import compat
    from repro.core import diffusion, topology

    mesh = compat.make_mesh((4, 2), ("data", "model"))
    K = 4
    A = topology.combination_matrix(K, "ring")
    phi = {
        "w": jax.random.normal(jax.random.key(0), (K, 8, 6)),
        "b": jax.random.normal(jax.random.key(1), (K, 10)),
    }
    with mesh:
        phi_sh = {
            "w": jax.device_put(phi["w"], NamedSharding(mesh, P("data", None, "model"))),
            "b": jax.device_put(phi["b"], NamedSharding(mesh, P("data", None))),
        }
        specs = {"w": P("data", None, "model"), "b": P("data", None)}
        sparse = diffusion.make_combine("mesh_sparse_dynamic", A=A,
                                        mesh=mesh, axis_name="data",
                                        in_specs=specs)
        out = jax.jit(sparse)(phi_sh)
        ref = diffusion.dense_combine(jnp.asarray(A), phi)
        for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)

        # dynamic schedule: the shard_mapped ppermute rounds with
        # step-gathered weights match the dense stacked einsum at every step
        topo = topology.build_topology("ring", K)
        sched = topology.make_schedule("link_failure", topo, p=0.3,
                                       period=5, seed=1)
        dyn = jax.jit(diffusion.make_combine(
            "mesh_sparse_dynamic", A=sched.matrices, mesh=mesh,
            axis_name="data", in_specs=specs))
        for step in [0, 3, 7]:
            out = dyn(phi_sh, jnp.int32(step))
            ref = diffusion.dense_combine(
                jnp.asarray(sched.matrix_at(step)), phi)
            for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=1e-5)
    print("SPARSE_MESH_OK")
""")


def test_mesh_sparse_combine_equals_dense():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", SCRIPT],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.join(os.path.dirname(__file__), ".."),
                         timeout=300)
    assert "SPARSE_MESH_OK" in out.stdout, out.stderr[-2000:]


SCRIPT_2D = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import compat
    from repro.core import diffusion, topology
    from repro.launch.mesh import make_host_mesh

    K = 4
    A = topology.combination_matrix(K, "ring")
    phi = {
        "w": jax.random.normal(jax.random.key(0), (K, 8, 6)),
        "b": jax.random.normal(jax.random.key(1), (K, 10)),
    }
    ref = diffusion.dense_combine(jnp.asarray(A), phi)

    # --- 2D (agent, model) mesh: TP-sharded leaves ride the permute ------
    mesh2d = make_host_mesh(model=2, agents=K)
    assert mesh2d.axis_names == ("agent", "model"), mesh2d.axis_names
    specs = {"w": P("agent", None, "model"), "b": P("agent", None)}
    with mesh2d:
        phi_sh = {
            k: jax.device_put(v, NamedSharding(mesh2d, specs[k]))
            for k, v in phi.items()
        }
        # select_backend must detect the agent axis on its own: a ring on
        # a 2D (agent, model) mesh routes to the shard_mapped backend
        # without the caller passing axis_name
        assert (diffusion.select_backend(A, mesh=mesh2d)
                == "mesh_sparse_dynamic")
        sparse = diffusion.make_combine("mesh_sparse_dynamic", A=A,
                                        mesh=mesh2d, axis_name="agent",
                                        in_specs=specs)
        out2d = jax.jit(sparse)(phi_sh)
        for a, b in zip(jax.tree.leaves(out2d), jax.tree.leaves(ref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)

        topo = topology.build_topology("ring", K)
        sched = topology.make_schedule("link_failure", topo, p=0.3,
                                       period=5, seed=1)
        dyn = jax.jit(diffusion.make_combine(
            "mesh_sparse_dynamic", A=sched.matrices, mesh=mesh2d,
            axis_name="agent", in_specs=specs))
        for step in [0, 3, 7]:
            outd = dyn(phi_sh, jnp.int32(step))
            refd = diffusion.dense_combine(
                jnp.asarray(sched.matrix_at(step)), phi)
            for a, b in zip(jax.tree.leaves(outd), jax.tree.leaves(refd)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=1e-5)

    # --- 1D-vs-2D bit-identity: adding the model axis must not change ----
    # the combine math (same ppermute rounds, same per-element reduction
    # order; TP only splits the trailing dim's storage)
    mesh1d = compat.make_mesh((K,), ("agent",))
    specs1d = {"w": P("agent"), "b": P("agent")}
    with mesh1d:
        phi_1d = {
            k: jax.device_put(v, NamedSharding(mesh1d, specs1d[k]))
            for k, v in phi.items()
        }
        sparse1d = diffusion.make_combine("mesh_sparse_dynamic", A=A,
                                          mesh=mesh1d, axis_name="agent",
                                          in_specs=specs1d)
        out1d = jax.jit(sparse1d)(phi_1d)
    for a, b in zip(jax.tree.leaves(out1d), jax.tree.leaves(out2d)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("SPARSE_MESH_2D_OK")
""")


def test_mesh_sparse_combine_2d_agent_mesh():
    """Agent-axis 2D mesh: parity with dense + bit-identity with the 1D
    agent-only mesh (the TP axis must be transparent to the combine)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", SCRIPT_2D],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.join(os.path.dirname(__file__), ".."),
                         timeout=300)
    assert "SPARSE_MESH_2D_OK" in out.stdout, out.stderr[-2000:]
