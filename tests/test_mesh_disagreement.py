"""The disagreement metric on agent meshes: one all-to-all, same number.

``diffusion.make_mesh_disagreement`` computes the metric with one tiled
all-to-all a leaf over the agent axis and one scalar psum, where GSPMD's
lowering of ``diffusion.disagreement`` all-reduces every leaf in f32.  The
checks run in subprocesses on forced CPU devices (jax fixes the device
count at its first use).  The reference is ``diffusion.disagreement`` under
``jit``, as the meta step computes it: on the CPU, XLA may keep a bf16
intermediate in f32 inside a jitted program, so an eager call rounds
differently from either.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.launch.mesh import make_host_mesh

ROOT = os.path.join(os.path.dirname(__file__), "..")

EQUALITY = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import sys
    sys.path.insert(0, "src")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import compat
    from repro.core import diffusion

    setup = sys.argv[1]
    bf, f32 = jnp.bfloat16, jnp.float32
    if setup == "1d":
        # (3, 5) has no dimension K divides: it is flattened and padded
        extents, names = (4,), ("agent",)
        leaves = {"odd": ((3, 5), bf, P("agent")),
                  "even": ((8, 12), bf, P("agent")),
                  "f32": ((6, 4), f32, P("agent"))}
    elif setup == "2d":
        extents, names = (4, 2), ("agent", "model")
        leaves = {"tp": ((6, 8), bf, P("agent", None, "model")),
                  "replicated": ((7, 3), bf, P("agent"))}
    else:
        extents, names = (2, 2, 2), ("agent", "data", "model")
        leaves = {"fsdp_tp": ((6, 8), bf, P("agent", "data", "model")),
                  "tp": ((5, 4), bf, P("agent", None, "model")),
                  "replicated": ((9,), bf, P("agent"))}
    mesh = compat.make_mesh(extents, names,
                            devices=jax.devices()[:int(np.prod(extents))])
    K = extents[0]
    params, specs = {}, {}
    for i, (name, (shape, dtype, spec)) in enumerate(leaves.items()):
        x = jax.random.normal(jax.random.key(i), (K,) + shape, f32)
        params[name] = jax.device_put(x.astype(dtype),
                                      NamedSharding(mesh, spec))
        specs[name] = spec
    mesh_fn = jax.jit(diffusion.make_mesh_disagreement(mesh, "agent", specs))
    with mesh:
        got = float(mesh_fn(params))
        hlo = mesh_fn.lower(params).compile().as_text()
    want = float(jax.jit(diffusion.disagreement)(params))
    assert want > 0, want
    assert "all-to-all" in hlo
    print("GAP", abs(got - want) / want, got, want)
""")


def _run(script, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", script, *args],
                         capture_output=True, text=True, env=env, cwd=ROOT,
                         timeout=600)
    return out


@pytest.mark.parametrize("setup", ["1d", "2d", "3d"])
def test_mesh_disagreement_equals_the_stacked_metric(setup):
    out = _run(EQUALITY, setup)
    lines = [line for line in out.stdout.splitlines()
             if line.startswith("GAP")]
    assert lines, out.stderr[-2000:]
    gap = float(lines[-1].split()[1])
    assert gap < 1e-4, lines[-1]


STEP = textwrap.dedent(r"""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import sys
    sys.path.insert(0, "src")
    import json
    import re
    import jax
    from repro.configs import get_config
    from repro.configs.base import InputShape
    from repro.launch import steps as S
    from repro.launch.mesh import make_host_mesh

    # the mesh cell's layout: one agent a device, model extent 1
    mesh = make_host_mesh(model=1, agents=4)
    cfg = get_config("mamba2-130m").reduced()
    shape = InputShape("t", 32, 8, "train")
    with mesh:
        bundle = S.build_train(cfg, mesh, shape,
                               combine_override="mesh_sparse_dynamic",
                               agents=4)
        batch = {k: jax.ShapeDtypeStruct((8, 32), "int32")
                 for k in ("tokens", "labels")}
        hlo = jax.jit(bundle.step_fn).lower(bundle.state_specs,
                                            batch).compile().as_text()
    # each collective's result shapes (XLA:CPU returns a tuple for a
    # combined one), in bytes by element type
    size = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "u16": 2,
            "s16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8}
    op = re.compile(r"= (.*?) (all-reduce|all-to-all)(?:-start)?\(")
    found = {"all-reduce": [], "all-to-all": []}
    for line in hlo.splitlines():
        m = op.search(line)
        if m:
            for dtype, dims in re.findall(r"(\w+)\[([\d,]*)\]", m.group(1)):
                n = 1
                for d in filter(None, dims.split(",")):
                    n *= int(d)
                found[m.group(2)].append((dtype, n * size[dtype]))
    print("STEP", json.dumps({"backend": bundle.disagreement_backend,
                              **found}))
""")


def test_agent_mesh_step_takes_the_all_to_all():
    out = _run(STEP)
    lines = [line for line in out.stdout.splitlines()
             if line.startswith("STEP")]
    assert lines, out.stderr[-2000:]
    got = json.loads(lines[-1][len("STEP"):])
    assert got["backend"] == "mesh_all_to_all", got
    # no all-reduce of a parameter's size is left: the loss mean and the
    # metric's psum are scalars
    assert got["all-reduce"], got
    assert max(nbytes for _, nbytes in got["all-reduce"]) <= 4096, got
    # the agents' values cross as 2-byte elements
    assert got["all-to-all"], got
    assert {dtype for dtype, _ in got["all-to-all"]} == {"u16"}, got


def test_stacked_build_keeps_the_stacked_disagreement():
    from repro.configs import get_config
    from repro.configs.base import InputShape
    from repro.launch import steps as S

    mesh = make_host_mesh()
    cfg = get_config("mamba2-130m").reduced()
    with mesh:
        bundle = S.build_train(cfg, mesh, InputShape("t", 32, 8, "train"),
                               agents=4)
    assert bundle.disagreement_backend == "stacked"
