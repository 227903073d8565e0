"""Cross-backend combine parity + backend registry behavior.

Acceptance: dense == sparse == pallas to <= 1e-5 on ring/torus/full, with
the pallas path serving parameter pytrees whose flattened size is NOT a
multiple of block_m (ragged-M), via the pack/unpack layer.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import diffusion as D
from repro.core import topology as T


def _ragged_phi(K, seed=0):
    """Leaf sizes 35 + 3 + 17 = 55 floats — nothing lane- or block-aligned."""
    k1, k2, k3 = jax.random.split(jax.random.key(seed), 3)
    return {"w": jax.random.normal(k1, (K, 7, 5)),
            "b": jax.random.normal(k2, (K, 3)),
            "scale": jax.random.normal(k3, (K, 17))}


@pytest.mark.parametrize("topo", ["ring", "torus", "full"])
@pytest.mark.parametrize("K", [4, 8])
def test_dense_sparse_pallas_parity(topo, K):
    A = T.combination_matrix(K, topo)
    phi = _ragged_phi(K, seed=K)
    dense = D.make_combine("dense", A=A)(phi)
    sparse = D.make_combine("sparse_host_dynamic", A=A)(phi)
    pallas = D.make_combine("pallas", A=A, interpret=True)(phi)
    for a, b, c in zip(jax.tree.leaves(dense), jax.tree.leaves(sparse),
                       jax.tree.leaves(pallas)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=1e-5)


@pytest.mark.parametrize("block_m", [128, 512])
def test_pallas_handles_ragged_m(block_m):
    """Total flattened M = 55 is far from any block multiple; the packed
    path must pad, combine, and slice back exactly."""
    K = 6
    A = T.combination_matrix(K, "ring")
    phi = _ragged_phi(K, seed=1)
    total = sum(int(np.prod(x.shape[1:])) for x in jax.tree.leaves(phi))
    assert total % block_m != 0
    out = D.make_combine("pallas", A=A, block_m=block_m, interpret=True)(phi)
    ref = D.make_combine("dense", A=A)(phi)
    assert jax.tree.structure(out) == jax.tree.structure(phi)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_pallas_mixed_dtype_pytree():
    K = 4
    A = T.combination_matrix(K, "ring")
    k1, k2 = jax.random.split(jax.random.key(0))
    phi = {"f32": jax.random.normal(k1, (K, 9)),
           "bf16": jax.random.normal(k2, (K, 5)).astype(jnp.bfloat16)}
    out = D.make_combine("pallas", A=A, interpret=True)(phi)
    assert out["f32"].dtype == jnp.float32
    assert out["bf16"].dtype == jnp.bfloat16
    ref = D.dense_combine(jnp.asarray(A), phi)
    np.testing.assert_allclose(np.asarray(out["f32"]),
                               np.asarray(ref["f32"]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(out["bf16"], np.float32),
                               np.asarray(ref["bf16"], np.float32), atol=2e-2)


def test_pack_pytree_roundtrip_and_alignment():
    K = 5
    phi = _ragged_phi(K)
    bufs, unpack = D.pack_pytree(phi, block_m=512)
    assert len(bufs) == 1                      # single dtype group
    assert bufs[0].shape == (K, 512)           # padded to one block
    assert bufs[0].shape[1] % D.LANE == 0      # lane-aligned
    back = unpack(bufs)
    for a, b in zip(jax.tree.leaves(phi), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_pallas_combine_inside_jit():
    K = 4
    A = T.combination_matrix(K, "full")
    phi = _ragged_phi(K, seed=3)
    fn = jax.jit(D.make_combine("pallas", A=A, interpret=True))
    ref = D.dense_combine(jnp.asarray(A), phi)
    for a, b in zip(jax.tree.leaves(fn(phi)), jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


# ---------------------------------------------------------------------------
# Registry / selection
# ---------------------------------------------------------------------------

def test_registry_contains_all_backends():
    assert D.combine_backends() == (
        "dense", "sparse_host_dynamic", "sparse_dynamic",
        "mesh_sparse_dynamic", "pallas", "centralized", "none")


def test_make_combine_rejects_unknown():
    with pytest.raises(ValueError, match="registered"):
        D.make_combine("bogus", A=np.eye(2))


def test_select_backend_rules():
    assert D.select_backend(np.ones((1, 1))) == "none"
    ring = T.combination_matrix(8, "ring")
    assert D.select_backend(ring) == "sparse_host_dynamic"
    full = T.combination_matrix(8, "full")          # degree K-1: dense wins
    assert D.select_backend(full) in ("dense", "pallas")
    # a live mesh whose agent axis matches K takes the shard_mapped rounds
    from repro.compat import abstract_mesh
    mesh = abstract_mesh((8, 2), ("data", "model"))
    assert (D.select_backend(ring, mesh=mesh, axis_name="data")
            == "mesh_sparse_dynamic")
    # mismatched extent falls back to the host roll
    assert (D.select_backend(ring, mesh=mesh, axis_name="model")
            == "sparse_host_dynamic")
    # 'sparse' resolves by the mesh: host rolls off a mesh and on a legacy
    # mesh with the agents on data, the shard_mapped rounds on an agent mesh
    assert D.select_backend(ring, request="sparse") == "sparse_host_dynamic"
    assert (D.select_backend(ring, mesh=mesh, axis_name="data",
                             request="sparse") == "sparse_host_dynamic")
    agent_mesh = abstract_mesh((8, 2), ("agent", "model"))
    assert (D.select_backend(ring, mesh=agent_mesh, request="sparse")
            == "mesh_sparse_dynamic")
    # a registered name is itself
    assert D.select_backend(ring, request="dense") == "dense"


def test_auto_strategy_through_make_combine():
    K = 6
    A = T.combination_matrix(K, "ring")
    phi = _ragged_phi(K)
    out = D.make_combine("auto", A=A)(phi)
    ref = D.dense_combine(jnp.asarray(A), phi)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_combine_wire_bytes_model():
    K = 8
    ring = T.combination_matrix(K, "ring")
    mb = 1000
    assert D.combine_wire_bytes(ring, "none", mb) == 0
    assert D.combine_wire_bytes(ring, "sparse_host_dynamic", mb) == 2 * mb  # deg 2
    assert D.combine_wire_bytes(ring, "dense", mb) == (K - 1) * mb
    assert D.combine_wire_bytes(ring, "centralized", mb) == 2 * (K - 1) * mb // K


# ---------------------------------------------------------------------------
# Trainer integration: pallas backend trains identically to dense
# ---------------------------------------------------------------------------

def test_trainer_pallas_matches_dense_and_disagreement_decays():
    from repro.configs import get_config
    from repro.core import MetaConfig, init_state, make_meta_step, diffusion
    from repro.data.sine import agent_sine_distributions, stacked_agent_batch
    from repro.models.simple import SineMLP

    cfg = get_config("sine_mlp")
    model = SineMLP(cfg)
    common = dict(num_agents=6, tasks_per_agent=2, inner_lr=0.01,
                  mode="maml", topology="ring", outer_optimizer="sgd",
                  outer_lr=5e-3)

    def run(combine, steps=8, interpret=True):
        mcfg = MetaConfig(combine=combine, **common)
        state = init_state(jax.random.key(0), model.init, mcfg,
                           identical_init=False)
        A = T.combination_matrix(6, "ring")
        combine_fn = (D.make_combine("pallas", A=A, interpret=True)
                      if combine == "pallas" else None)
        step = jax.jit(make_meta_step(model.loss_fn, mcfg,
                                      combine_fn=combine_fn))
        dists = agent_sine_distributions(6)
        ds = [float(diffusion.disagreement(state.params))]
        for _ in range(steps):
            sup, qry = stacked_agent_batch(dists, 2, 10)
            state, metrics = step(state, jax.tree.map(jnp.asarray, sup),
                                  jax.tree.map(jnp.asarray, qry))
            ds.append(float(metrics["disagreement"]))
        return state, ds

    sa, _ = run("dense")
    sb, ds = run("pallas")
    for a, b in zip(jax.tree.leaves(sa.params), jax.tree.leaves(sb.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    # disagreement-decay smoke (Thm 1): combine contracts the network
    assert ds[-1] < ds[0]
