"""The outer-update kernels compile for one described TPU v5e chip.

The Pallas interpreter that runs the kernel tests on the CPU accepts block
shapes the TPU's compiler refuses (a ``(1, bm)`` row block of a ``(K, M)``
array breaks the (8, 128) tiling rule).  These tests hand the chip's
compiler shapes only, so they need no chip: the topology is described, not
attached.  M is the packed size of the largest ``mamba2-130m`` dtype group,
the reference job of ``chip_smoke.py``, at K=4 agents.  The meta step of
a reduced model compiles there too, with its named scopes on the ops the
chip runs.

Only one process may load the TPU library at a time, so the topology is
described inside a fixture of this one file, never while a module is
imported.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import InputShape
from repro.core.diffusion import pack_pytree
from repro.kernels.dif_combine.dif_combine import (dif_combine,
                                                   fused_combine_update)
from repro.launch import steps as S
from repro.models.init import abstract, with_agent_axis
from repro.models.transformer import build_model

K = 4
BLOCK_M = 512
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def group_m():
    """Packed width of the largest dtype group of K-stacked mamba2-130m
    parameters (what ``pack_pytree`` hands ``dif_combine``)."""
    model = build_model(get_config("mamba2-130m"))
    stacked = abstract(with_agent_axis(model.specs(), K), jnp.bfloat16)
    bufs = jax.eval_shape(lambda p: pack_pytree(p, block_m=BLOCK_M)[0],
                          stacked)
    M = max(b.shape[1] for b in bufs)
    assert M % BLOCK_M == 0 and M > 100_000_000
    return M


def _compile(fn, *args, donate=()):
    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: jnp.dtype(d).name)
def test_dif_combine_compiles_for_v5e(one_chip, group_m, dtype):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    _compile(lambda A, phi: dif_combine(A, phi, block_m=BLOCK_M),
             sds((K, K), jnp.float32), sds((K, group_m), dtype))


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: jnp.dtype(d).name)
def test_fused_combine_update_compiles_for_v5e(one_chip, group_m, dtype):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    table = np.ones((1, K, K), np.float32) / K

    def step(w, g, mu, nu, sel, ctl, scale):
        return fused_combine_update(jnp.asarray(table), sel, ctl, scale, w, g,
                                    mu, nu, mode="atc", kind="adam", lr=1e-3,
                                    block_m=BLOCK_M)

    # params and moments are donated, as the trainer's step donates them
    _compile(step, sds((K, group_m), dtype), sds((K, group_m), dtype),
             sds((K, group_m), jnp.float32), sds((K, group_m), jnp.float32),
             sds((1, 1), jnp.int32), sds((1, 3), jnp.float32),
             sds((K, 1), jnp.float32), donate=(0, 2, 3))


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "mamba2-130m"])
def test_meta_step_scopes_cover_the_chips_ops(topo, arch):
    """At least 90 % of the fusions, dots and convolutions that the chip's
    compiler leaves an ``op_name`` sit under a ``dif.step.*`` scope (the
    ``disagreement`` metric has its own); the rest is the step's other
    metrics and what the compiler hoists out of them."""
    cfg = dataclasses.replace(get_config(arch).reduced(), remat=True)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    with mesh:
        b = S.build_train(cfg, mesh, InputShape("scopes", 64, 8, "train"),
                          combine_override="dense", agents=K)
        state = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            b.state_specs, b.state_shardings)
        batch = {k: jax.ShapeDtypeStruct((8, 64), jnp.int32,
                                         sharding=b.batch_shardings[k])
                 for k in ("tokens", "labels")}
        text = jax.jit(b.step_fn, donate_argnums=(0,)).lower(
            state, batch).compile().as_text()
    named = [m.group(1) for m in re.finditer(
        r" (?:fusion|dot|convolution)\(.*op_name=\"([^\"]*)\"", text)]
    phases = {p for n in named for p in re.findall(r"dif\.step\.\w+", n)}
    assert phases == {"dif.step.inner_adapt", "dif.step.outer_grad",
                      "dif.step.hvp", "dif.step.outer_update",
                      "dif.step.combine", "dif.step.disagreement"}
    assert sum("dif.step." in n for n in named) >= 0.9 * len(named)
