"""Launch-layer logic that runs without the 512-device dry-run env."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.compat import abstract_mesh
from repro.configs import INPUT_SHAPES, get_config
from repro.launch import steps as S
from repro.launch.mesh import make_host_mesh

MESH1 = abstract_mesh((16, 16), ("data", "model"))
MESH2 = abstract_mesh((2, 16, 16), ("pod", "data", "model"))
AMESH2 = abstract_mesh((16, 16), ("agent", "model"))
AMESH3 = abstract_mesh((8, 2, 16), ("agent", "data", "model"))


def test_agent_count_placements():
    qw = get_config("qwen2-7b")          # placement=data
    mx = get_config("mixtral-8x22b")     # placement=pod
    assert S.agent_count(qw, MESH1) == 16
    assert S.agent_count(qw, MESH2) == 32
    assert S.agent_count(mx, MESH1) == 1
    assert S.agent_count(mx, MESH2) == 2


def test_agent_count_agent_axis_wins():
    # a first-class agent axis overrides placement for every config
    qw = get_config("qwen2-7b")          # placement=data
    mx = get_config("mixtral-8x22b")     # placement=pod
    for cfg in (qw, mx):
        assert S.agent_count(cfg, AMESH2) == 16
        assert S.agent_count(cfg, AMESH3) == 8


def test_batch_geometry_divides_exactly():
    # (prefill shapes lower a plain forward — no meta geometry needed)
    shape = INPUT_SHAPES["train_4k"]
    for arch in ["qwen2-7b", "mixtral-8x22b"]:
        cfg = get_config(arch)
        for mesh in (MESH1, MESH2):
            K = S.agent_count(cfg, mesh)
            T, tb = S.batch_geometry(cfg, shape, K)
            assert K * T * tb * 2 == shape.global_batch


def test_batch_geometry_rejects_indivisible_batch():
    """K ∤ B (or an odd per-agent batch) must fail loudly with the numbers,
    not vanish rows in the (K, T, 2·tb) fold."""
    import dataclasses
    from repro.configs.base import InputShape
    cfg = get_config("qwen2-7b")
    with pytest.raises(ValueError) as ei:
        S.batch_geometry(cfg, InputShape("x", 16, 10, "train"), K=4)
    msg = str(ei.value)
    assert "global_batch=10" in msg and "K=4" in msg and "8" in msg
    # per-agent batch below the support+query minimum
    with pytest.raises(ValueError, match="minimum 8"):
        S.batch_geometry(cfg, InputShape("x", 16, 4, "train"), K=4)
    # odd per-agent batch cannot split into support+query halves
    with pytest.raises(ValueError):
        S.batch_geometry(cfg, InputShape("x", 16, 12, "train"), K=4)


def test_batch_geometry_T_falls_back():
    """T retreats from cfg.meta_tasks toward 1 until it divides the
    per-agent half batch — and WARNS with the requested and effective T
    (silent degradation erased the eq. 4 multi-task average)."""
    import dataclasses
    from repro.configs.base import InputShape
    cfg = dataclasses.replace(get_config("qwen2-7b"), meta_tasks=4)
    # half = 6: 6 % 4 != 0, 6 % 3 == 0 -> T=3, tb=2
    with pytest.warns(RuntimeWarning, match=r"meta_tasks=4.*T=3"):
        assert S.batch_geometry(cfg, InputShape("x", 16, 24, "train"),
                                K=2) == (3, 2)
    # half = 5: falls all the way back to T=1, tb=5
    with pytest.warns(RuntimeWarning, match=r"meta_tasks=4.*T=1"):
        assert S.batch_geometry(cfg, InputShape("x", 16, 20, "train"),
                                K=2) == (1, 5)
    # exact fit keeps meta_tasks — and stays silent
    import warnings as W
    with W.catch_warnings():
        W.simplefilter("error")
        assert S.batch_geometry(cfg, InputShape("x", 16, 16, "train"),
                                K=2) == (4, 1)


def test_split_meta_batch_layout():
    cfg = get_config("qwen2-7b")
    B, Sq = 32, 8
    batch = {"tokens": jnp.arange(B * Sq).reshape(B, Sq)}
    sup, qry = S.split_meta_batch(cfg, batch, K=4, T=2, tb=2)
    assert sup["tokens"].shape == (4, 2, 2, Sq)
    assert qry["tokens"].shape == (4, 2, 2, Sq)
    # support/query are disjoint halves of each task's rows
    joined = jnp.concatenate([sup["tokens"], qry["tokens"]], axis=2)
    np.testing.assert_array_equal(joined.reshape(B, Sq), batch["tokens"])


def test_input_specs_train_shapes():
    specs = S.input_specs(get_config("qwen2-7b"), "train_4k")
    assert specs["tokens"].shape == (256, 4096)
    assert specs["labels"].dtype == jnp.int32
    w = S.input_specs(get_config("whisper-large-v3"), "train_4k")
    assert w["encoder_frames"].shape == (256, 1500, 1280)
    v = S.input_specs(get_config("llama-3.2-vision-90b"), "train_4k")
    assert v["image_patches"].shape == (256, 576, 8192)


def test_input_specs_decode_cache():
    specs = S.input_specs(get_config("command-r-35b"), "decode_32k")
    assert specs["token"].shape == (128, 1)
    assert specs["pos"].shape == (128,)
    leaves = jax.tree.leaves(specs["cache"])
    # 40 layers of K + V at (B, S, KV, hd)
    assert any(l.shape == (40, 128, 32768, 8, 128) for l in leaves)


def test_decode_cache_swa_is_window_bounded():
    specs = S.input_specs(get_config("mixtral-8x22b"), "long_500k")
    for l in jax.tree.leaves(specs["cache"]):
        assert l.shape[2] <= 4096   # ring buffer, not 524288


def test_mamba_long_context_cache_constant():
    specs = S.input_specs(get_config("mamba2-130m"), "long_500k")
    total = sum(np.prod(l.shape) for l in jax.tree.leaves(specs["cache"]))
    assert total < 50e6             # O(1) state, not O(seq)


def test_train_bundle_builds_on_host_mesh():
    """Full bundle construction + one real step on the host mesh."""
    from repro.configs.base import InputShape
    cfg = get_config("qwen2-1.5b").reduced()
    INPUT_SHAPES["t_test"] = InputShape("t_test", 16, 8, "train")
    mesh = make_host_mesh()
    with mesh:
        bundle = S.build_train(cfg, mesh, "t_test")
        state = bundle.init_state(seed=0)
        batch = {
            "tokens": jnp.zeros((8, 16), jnp.int32),
            "labels": jnp.zeros((8, 16), jnp.int32),
        }
        state2, metrics = jax.jit(bundle.step_fn)(state, batch)
        assert bool(jnp.isfinite(metrics["loss"]))
        assert int(state2.step) == 1
    del INPUT_SHAPES["t_test"]


def test_register_input_shape_idempotent_and_conflict():
    """The registry helper (replaces raw INPUT_SHAPES mutation): same
    value re-registers silently, a different geometry under the same name
    fails loudly unless override=True."""
    from repro.configs import register_input_shape
    from repro.configs.base import InputShape
    shape = InputShape("reg_test", 16, 8, "train")
    try:
        register_input_shape(shape)
        assert INPUT_SHAPES["reg_test"] is shape
        register_input_shape(InputShape("reg_test", 16, 8, "train"))  # no-op
        clash = InputShape("reg_test", 32, 8, "train")
        with pytest.raises(ValueError, match="already registered"):
            register_input_shape(clash)
        register_input_shape(clash, override=True)
        assert INPUT_SHAPES["reg_test"].seq_len == 32
    finally:
        del INPUT_SHAPES["reg_test"]


def test_register_input_shape_protects_builtins():
    from repro.configs import register_input_shape
    from repro.configs.base import InputShape
    with pytest.raises(ValueError, match="built in"):
        register_input_shape(InputShape("train_4k", 16, 8, "train"),
                             override=True)


def test_input_shape_scope_restores_registry():
    from repro.configs import input_shape_scope
    from repro.configs.base import InputShape
    before = dict(INPUT_SHAPES)
    with input_shape_scope(InputShape("scoped_a", 16, 8, "train")) as sh:
        assert INPUT_SHAPES["scoped_a"] is sh
        # shadow a non-builtin name, restore the prior entry on exit
        with input_shape_scope(InputShape("scoped_a", 32, 8, "train")):
            assert INPUT_SHAPES["scoped_a"].seq_len == 32
        assert INPUT_SHAPES["scoped_a"] is sh
    assert dict(INPUT_SHAPES) == before


def test_meta_config_for_uses_arch_fields():
    cfg = get_config("deepseek-v2-lite-16b")
    mcfg = S.meta_config_for(cfg, K=16, T=2)
    assert mcfg.mode == "fomaml"
    assert mcfg.num_agents == 16
    assert mcfg.outer_optimizer == "momentum"
    mcfg1 = S.meta_config_for(cfg, K=1, T=2)
    assert mcfg1.combine == "none"   # degenerate single-agent case


def test_opt_state_axes_match_structures():
    p_axes = {"w": ("agent", "embed", "ffn")}
    assert S.opt_state_axes("sgd", p_axes) == ()
    mom = S.opt_state_axes("momentum", p_axes)
    assert mom.velocity == p_axes
    ad = S.opt_state_axes("adam", p_axes)
    assert ad.mu == p_axes and ad.nu == p_axes and ad.step == ()


def test_agent_count_keeps_callers_k():
    """A caller's K is returned as given or refused with both numbers —
    never replaced by what the mesh happens to hold."""
    qw = get_config("qwen2-7b")          # placement=data: agents tile data
    assert S.agent_count(qw, MESH1, agents=32) == 32   # 2 per data slice
    with pytest.raises(ValueError, match=r"K=8 .*16 agent slice"):
        S.agent_count(qw, MESH1, agents=8)
    assert S.agent_count(qw, AMESH2, agents=16) == 16
    with pytest.raises(ValueError, match=r"K=8 .*extent K"):
        S.agent_count(qw, AMESH2, agents=8)


# --- the trainer's entry point: K agents on whatever devices exist ----------

# mamba2-130m (the chip smoke's reference arch) cut to its smoke widths
TRAIN_ARGV = ["--arch", "mamba2-130m", "--reduced", "--seq", "32",
              "--global-batch", "8", "--steps", "3", "--seed", "0"]

MESH_AGENTS_SCRIPT = """
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, "src")
from repro.launch import train
out = train.main(json.loads(sys.argv[1]))
print("RESULT " + json.dumps({k: out[k] for k in ("K", "loss",
                                                  "disagreement")}))
"""


@pytest.fixture(scope="module")
def stacked_run(tmp_path_factory):
    from repro.launch import train
    log = tmp_path_factory.mktemp("train") / "stacked.jsonl"
    return train.main(TRAIN_ARGV + ["--agents", "4", "--run-log", str(log)])


def test_train_main_stacks_k_agents_on_one_device(stacked_run):
    """--agents 4 on the one CPU device runs K=4 (the agent copies stack on
    the device) with the diffusion step live: disagreement > 0, falling."""
    assert len(jax.devices()) == 1
    assert stacked_run["K"] == 4
    dis = stacked_run["disagreement"]
    assert len(dis) == 3 and all(d > 0 for d in dis)
    assert dis[2] < dis[1] < dis[0]
    assert all(np.isfinite(stacked_run["loss"]))


def test_mesh_agents_run_matches_stacked_run(stacked_run, tmp_path):
    """One agent per (virtual) device with the ppermute combine on a bf16
    wire follows the one-device stacked run of the same seed and data."""
    import json
    import os
    import subprocess
    import sys
    argv = TRAIN_ARGV + ["--mesh-agents", "4",
                         "--combine", "mesh_sparse_dynamic",
                         "--run-log", str(tmp_path / "mesh.jsonl")]
    proc = subprocess.run(
        [sys.executable, "-c", MESH_AGENTS_SCRIPT, json.dumps(argv)],
        capture_output=True, text=True, timeout=600,
        cwd=os.path.join(os.path.dirname(__file__), ".."),
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")]
    assert line, proc.stderr[-3000:]
    mesh = json.loads(line[-1][len("RESULT "):])
    assert mesh["K"] == 4
    # the dense combine rounds the K×K weights to bf16, the ppermute
    # combine keeps them f32: the runs agree to bf16 rounding, not bitwise
    np.testing.assert_allclose(mesh["loss"], stacked_run["loss"], rtol=1e-3)
    np.testing.assert_allclose(mesh["disagreement"],
                               stacked_run["disagreement"], rtol=5e-2)


@pytest.mark.parametrize("argv, match", [
    (["--mesh-agents", "4"], r"agents=4 .* 1 available device"),
    (["--agents", "4", "--mesh-agents", "2"], None),
    (["--devices", "2"], None),
])
def test_train_main_rejects_geometry_that_does_not_factor(argv, match,
                                                          tmp_path):
    from repro.launch import train
    argv = TRAIN_ARGV + argv + ["--run-log", str(tmp_path / "x.jsonl")]
    if match is None:       # argparse refuses the request outright
        with pytest.raises(SystemExit):
            train.main(argv)
    else:
        with pytest.raises(ValueError, match=match):
            train.main(argv)
