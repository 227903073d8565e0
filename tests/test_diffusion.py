"""Diffusion combine invariants (paper eq. 6b + Thm 1).

Former hypothesis property tests run as seeded parametrize grids so tier-1
collects with no optional dependencies.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import diffusion as D
from repro.core import topology as T


def _phi(K, seed=0):
    k1, k2 = jax.random.split(jax.random.key(seed))
    return {"w": jax.random.normal(k1, (K, 7, 5)),
            "b": jax.random.normal(k2, (K, 3))}


@pytest.mark.parametrize("K", [2, 3, 7, 16])
@pytest.mark.parametrize("topo", ["ring", "full", "erdos"])
@pytest.mark.parametrize("seed", [0, 11])
def test_combine_preserves_centroid(K, topo, seed):
    """Doubly-stochastic A leaves the network centroid invariant — the
    mechanism behind Thm 2 (the centroid performs unperturbed descent)."""
    A = T.combination_matrix(K, topo, seed=seed) if topo == "erdos" \
        else T.combination_matrix(K, topo)
    phi = _phi(K, seed)
    out = D.dense_combine(jnp.asarray(A), phi)
    for a, b in zip(jax.tree.leaves(D.centroid(phi)),
                    jax.tree.leaves(D.centroid(out))):
        np.testing.assert_allclose(a, b, atol=1e-5)


@pytest.mark.parametrize("K", [4, 8, 16])
@pytest.mark.parametrize("topo", ["ring", "full"])
def test_sparse_host_equals_dense(K, topo):
    A = T.combination_matrix(K, topo)
    phi = _phi(K, K)
    dense = D.dense_combine(jnp.asarray(A), phi)
    sparse = D.make_combine("sparse_host_dynamic", A=A)(phi)
    for a, b in zip(jax.tree.leaves(dense), jax.tree.leaves(sparse)):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_centralized_equals_full_graph():
    K = 6
    A = T.combination_matrix(K, "full")
    phi = _phi(K, 1)
    a = D.dense_combine(jnp.asarray(A), phi)
    b = D.centralized_combine(phi)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(x, y, atol=1e-5)


def test_no_combine_identity():
    phi = _phi(5)
    out = D.no_combine(phi)
    for x, y in zip(jax.tree.leaves(phi), jax.tree.leaves(out)):
        np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("K", [2, 4, 7, 12])
@pytest.mark.parametrize("seed", [0, 3, 9])
def test_combine_contracts_disagreement(K, seed):
    """One combine shrinks (1/K)Σ‖w_k − w_c‖² by at least λ₂² (Thm 1)."""
    A = T.combination_matrix(K, "ring")
    lam2 = T.mixing_rate(A)
    phi = _phi(K, seed)
    before = float(D.disagreement(phi))
    after = float(D.disagreement(D.dense_combine(jnp.asarray(A), phi)))
    # f32 slack: near-1 λ₂ (large ring K) puts `after` within float error
    # of the bound itself
    assert after <= lam2 ** 2 * before * (1 + 1e-5) + 1e-5


def test_atc_vs_cta_differ_but_share_centroid_update():
    K = 4
    A = jnp.asarray(T.combination_matrix(K, "ring"))
    params = _phi(K, 2)
    updates = jax.tree.map(lambda x: 0.1 * jnp.ones_like(x), params)
    combine = lambda p: D.dense_combine(A, p)
    atc = D.atc_step(params, updates, combine)
    cta = D.cta_step(params, updates, combine)
    c_atc = D.centroid(atc)
    c_cta = D.centroid(cta)
    for a, b in zip(jax.tree.leaves(c_atc), jax.tree.leaves(c_cta)):
        np.testing.assert_allclose(a, b, atol=1e-5)   # same centroid motion
    diff = sum(float(jnp.sum(jnp.abs(a - b)))
               for a, b in zip(jax.tree.leaves(atc), jax.tree.leaves(cta)))
    assert diff > 1e-6                                 # but different iterates


def test_disagreement_zero_for_identical_agents():
    phi = jax.tree.map(lambda x: jnp.broadcast_to(x[:1], x.shape), _phi(6))
    assert float(D.disagreement(phi)) < 1e-10


def test_make_combine_factory():
    K = 4
    A = T.combination_matrix(K, "ring")
    for name in ["dense", "sparse_host_dynamic", "sparse", "auto",
                 "centralized", "none"]:
        fn = D.make_combine(name, A=A)
        out = fn(_phi(K))
        assert jax.tree.structure(out) == jax.tree.structure(_phi(K))
    with pytest.raises(ValueError):
        D.make_combine("bogus", A=A)
