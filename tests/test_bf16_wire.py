"""bf16 combine wire: dtype resolution rules and the u16 wire contract.

The resolution tests run in-process.  The end-to-end test builds the
mesh_sparse_dynamic combine on a 4-forced-host-device agent mesh in a
subprocess and checks the module-docstring contract in optimized HLO: the
bf16 wire ships as 2-byte u16 collective-permutes (XLA:CPU's float
normalization would silently re-widen raw bf16 permutes to f32 — the
bitcast is what makes the halving real on every backend), totals exactly
deg · bf16-shard bytes, and the mix stays within one bf16 rounding of the
f64 reference.
"""
import os
import subprocess
import sys
import textwrap

import pytest

from repro.core import diffusion


def test_resolve_combine_dtype_follows_outer_dtype():
    assert diffusion.resolve_combine_dtype("bfloat16") == "bfloat16"
    assert diffusion.resolve_combine_dtype("float32") == "float32"


def test_resolve_combine_dtype_override_wins():
    assert diffusion.resolve_combine_dtype(
        "bfloat16", "float32") == "float32"
    assert diffusion.resolve_combine_dtype(
        "float32", "bfloat16") == "bfloat16"


def test_resolve_combine_dtype_rejects_unknown():
    with pytest.raises(ValueError, match="wire format"):
        diffusion.resolve_combine_dtype("bfloat16", "float16")


def test_wire_elem_bytes():
    assert diffusion.wire_elem_bytes("bfloat16") == 2
    assert diffusion.wire_elem_bytes("float32") == 4


def test_make_combine_rejects_unknown_wire():
    import numpy as np
    with pytest.raises(ValueError, match="wire format"):
        diffusion.make_combine("dense", np.eye(2), combine_dtype="f16")


SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import sys
    sys.path.insert(0, "src")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import compat
    from repro.core import diffusion, topology
    from repro.launch.hlo_cost import HloCost

    K = 4
    topo = topology.build_topology("ring", K)
    mesh = compat.make_mesh((K,), ("agent",))
    sh = NamedSharding(mesh, P("agent"))
    rng = np.random.default_rng(0)
    phi = {"w": jax.device_put(
        rng.standard_normal((K, 256)).astype(np.float32), sh)}
    phi = jax.tree.map(lambda x: x.astype(jnp.bfloat16), phi)
    deg = topology.schedule_ir(topo.matrix).degree
    shard = 256 * 2                       # one agent's bf16 leaf block

    fn = jax.jit(diffusion.make_combine(
        "mesh_sparse_dynamic", topo.matrix, "agent", mesh=mesh,
        combine_dtype="bfloat16"))
    hlo = fn.lower(phi).compile().as_text()
    cp = HloCost(hlo, n_dev=K).collectives()["per_op"]["collective-permute"]
    u16 = cp["by_dtype"].get("u16", 0)
    assert u16 == deg * shard, (u16, deg * shard, cp)
    assert "f32" not in cp["by_dtype"], cp   # normalization didn't re-widen

    out = fn(phi)
    ref = topo.matrix.T @ np.asarray(phi["w"], np.float64)
    err = float(np.max(np.abs(np.asarray(out["w"], np.float64) - ref)))
    assert err < 2 ** -7, err             # one bf16 rounding of O(1) values
    print("BF16_WIRE_OK", u16, err)
""")


def test_mesh_sparse_bf16_wire_is_u16():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", SCRIPT],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.join(os.path.dirname(__file__), ".."),
                         timeout=600)
    assert "BF16_WIRE_OK" in out.stdout, out.stderr[-2000:]
