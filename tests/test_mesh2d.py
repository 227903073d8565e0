"""Agent-axis mesh composition: factories, full-stack compile, wire budget.

The factory-validation tests run in-process (single device).  The
end-to-end test compiles a real reduced train step on an 8-forced-host-
device (agent=4, model=2) mesh in a subprocess and runs the same
``agent_combine_check`` budget the production dry-run asserts: the ring
combine's collective-permute bytes must be deg·(per-agent f32 shard) —
NOT K·shard — with TP composing underneath.
"""
import os
import subprocess
import sys
import textwrap

import pytest

from repro.launch.mesh import make_host_mesh, make_production_mesh


def test_make_production_mesh_rejects_non_factoring():
    with pytest.raises(ValueError, match="agents=3"):
        make_production_mesh(agents=3)
    with pytest.raises(ValueError, match="512"):
        make_production_mesh(agents=3, multi_pod=True)
    with pytest.raises(ValueError):
        make_production_mesh(agents=0)


def test_make_host_mesh_agent_rejects_non_factoring():
    # the single-device test runtime cannot hold 2 agents
    with pytest.raises(ValueError, match="agents=2"):
        make_host_mesh(agents=2)


def test_make_host_mesh_agent_trivial_extent():
    mesh = make_host_mesh(agents=1, model=1)
    assert mesh.axis_names == ("agent", "model")
    assert mesh.devices.shape == (1, 1)


def test_make_host_mesh_legacy_clamp_warns():
    # no clamp: a legacy request that does not factor raises with both
    # numbers instead of shrinking the data extent under the caller
    with pytest.raises(ValueError, match=r"data=4 .* 1 available device"):
        make_host_mesh(data=4)
    assert make_host_mesh(data=1).devices.shape == (1, 1)


SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import sys
    sys.path.insert(0, "src")
    import dataclasses
    import jax
    import numpy as np
    from repro.compat import mesh_axis_sizes
    from repro.configs import INPUT_SHAPES, get_config
    from repro.configs.base import InputShape
    from repro.core import diffusion
    from repro.launch import steps as S
    from repro.launch.mesh import make_host_mesh
    from repro.launch.hlo_cost import agent_combine_check, tree_shard_bytes

    mesh = make_host_mesh(model=2, agents=4)
    assert mesh.axis_names == ("agent", "model"), mesh.axis_names
    cfg = get_config("qwen2-7b").reduced()
    INPUT_SHAPES["t_2d"] = InputShape("t_2d", 32, 8, "train")
    with mesh:
        bundle = S.build_train(cfg, mesh, "t_2d",
                               combine_override="mesh_sparse_dynamic")
        assert bundle.K == 4
        jitted = jax.jit(bundle.step_fn,
                         in_shardings=(bundle.state_shardings,
                                       bundle.batch_shardings),
                         out_shardings=(bundle.state_shardings, None),
                         donate_argnums=(0,))
        hlo = jitted.lower(bundle.state_specs,
                           S.input_specs(cfg, "t_2d")).compile().as_text()
    # the combine permutes the wire dtype (bf16 payloads ride as 2-byte
    # u16), so the budget window is sized at wire_elem_bytes — half of
    # what the old hard-coded f32 sizing would demand
    assert bundle.combine_dtype == "bfloat16", bundle.combine_dtype
    shard = tree_shard_bytes(
        bundle.state_shardings.params, bundle.state_specs.params,
        mesh_axis_sizes(mesh),
        elem_bytes=diffusion.wire_elem_bytes(bundle.combine_dtype))
    deg = bundle.schedule.ir().degree
    assert deg == 2, deg                     # ring: offsets ±1
    budget = agent_combine_check(hlo, 8, degree=deg, shard_bytes=shard,
                                 wire_dtype=bundle.combine_dtype)
    assert budget["ok"], budget
    # the discriminating claims: K·shard would blow the window open, and
    # an f32 wire would overshoot the halved ceiling
    assert budget["permute_bytes"] < bundle.K * shard, budget
    assert budget["permute_bytes"] < deg * 2 * shard, budget
    print("MESH2D_BUDGET_OK", budget["permute_bytes"], budget["degree"])
""")


SCRIPT_3D = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import sys
    sys.path.insert(0, "src")
    import jax
    from repro.compat import mesh_axis_sizes
    from repro.configs import INPUT_SHAPES, get_config
    from repro.configs.base import InputShape
    from repro.core import diffusion
    from repro.launch import steps as S
    from repro.launch.mesh import make_host_mesh
    from repro.launch.hlo_cost import agent_combine_check, tree_shard_bytes

    # 3D (agent, data, model): intra-agent data parallelism underneath the
    # diffusion axis, TP underneath that — the production (8, 2, 16) shape
    # collapsed onto 8 host devices
    mesh = make_host_mesh(data=2, model=2, agents=2)
    assert mesh.axis_names == ("agent", "data", "model"), mesh.axis_names
    cfg = get_config("qwen2-7b").reduced()
    INPUT_SHAPES["t_3d"] = InputShape("t_3d", 32, 8, "train")
    with mesh:
        bundle = S.build_train(cfg, mesh, "t_3d",
                               combine_override="mesh_sparse_dynamic")
        assert bundle.K == 2
        jitted = jax.jit(bundle.step_fn,
                         in_shardings=(bundle.state_shardings,
                                       bundle.batch_shardings),
                         out_shardings=(bundle.state_shardings, None),
                         donate_argnums=(0,))
        hlo = jitted.lower(bundle.state_specs,
                           S.input_specs(cfg, "t_3d")).compile().as_text()
    shard = tree_shard_bytes(
        bundle.state_shardings.params, bundle.state_specs.params,
        mesh_axis_sizes(mesh),
        elem_bytes=diffusion.wire_elem_bytes(bundle.combine_dtype))
    deg = bundle.schedule.ir().degree
    budget = agent_combine_check(hlo, 8, degree=deg, shard_bytes=shard,
                                 wire_dtype=bundle.combine_dtype)
    assert budget["ok"], budget
    print("MESH3D_BUDGET_OK", budget["permute_bytes"], budget["degree"])
""")


def _run_subprocess_budget(script, marker):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.join(os.path.dirname(__file__), ".."),
                         timeout=600)
    assert marker in out.stdout, out.stderr[-2000:]


def test_train_step_2d_mesh_combine_budget():
    _run_subprocess_budget(SCRIPT, "MESH2D_BUDGET_OK")


def test_train_step_3d_mesh_combine_budget():
    _run_subprocess_budget(SCRIPT_3D, "MESH3D_BUDGET_OK")
