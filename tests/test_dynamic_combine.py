"""ScheduleIR lowering + dynamic sparse combine backends.

Acceptance: the sparse_dynamic family reproduces the dense step-indexed
einsum for every dynamic schedule kind on ring/full with ragged mixed-dtype
pytrees, every matrix backend takes a static matrix and a stacked schedule
as given, and the selection rules prefer the sparse lowering over the dense
stacked fallback whenever the offset union is sparse.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import diffusion as D
from repro.core import topology as T

K = 8

SCHED_KW = {"link_failure": dict(p=0.3, period=7, seed=1),
            "gossip": dict(period=5, seed=2),
            "round_robin": {}}


def _schedule(kind, topo_name, K=K):
    topo = T.build_topology(topo_name, K)
    return T.make_schedule(kind, topo, **SCHED_KW.get(kind, {}))


def _ragged_phi(K, seed=0):
    """Ragged sizes, mixed dtype — nothing lane-aligned."""
    k1, k2, k3 = jax.random.split(jax.random.key(seed), 3)
    return {"w": jax.random.normal(k1, (K, 7, 5)),
            "b": jax.random.normal(k2, (K, 3)).astype(jnp.bfloat16),
            "scale": jax.random.normal(k3, (K, 17))}


def _assert_tree_close(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        tol = 2e-2 if x.dtype == jnp.bfloat16 else 1e-6
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32), atol=tol)


# ---------------------------------------------------------------------------
# ScheduleIR: exact decomposition
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["static", "link_failure", "gossip",
                                  "round_robin"])
@pytest.mark.parametrize("topo", ["ring", "full"])
def test_ir_reconstructs_stack_exactly(kind, topo):
    sched = _schedule(kind, topo)
    ir = sched.ir()
    np.testing.assert_array_equal(ir.stacked(), sched.matrices)
    assert ir.period == sched.period
    assert ir.K == K


def test_ir_offsets_are_the_static_graphs_union():
    """Dynamic kinds never activate an edge outside the static graph, so
    the offset union (= the fixed ppermute rounds) is the static set:
    deg 2 on the ring regardless of the schedule's randomness."""
    for kind in ["link_failure", "gossip", "round_robin"]:
        ir = _schedule(kind, "ring").ir()
        assert set(ir.offsets) <= {1, K - 1}
        assert ir.degree <= 2
    assert _schedule("round_robin", "full").ir().degree == K - 1


def test_ir_keeps_offsets_with_negative_weights():
    """Negative off-diagonal weights (legal in e.g. accelerated consensus
    matrices) must keep their offset — dropping them would make the sparse
    lowering silently diverge from the dense einsum."""
    A = np.eye(4)
    for k in range(4):
        A[(k - 1) % 4, k] = -0.1          # offset 1, all-negative weights
        A[k, k] = 1.1
    ir = T.schedule_ir(A)
    assert 1 in ir.offsets
    np.testing.assert_array_equal(ir.matrix_at(0), A)
    phi = _ragged_phi(4)
    _assert_tree_close(D.make_combine("sparse_host_dynamic", A=A)(phi),
                       D.dense_combine(jnp.asarray(A), phi))


def test_schedule_ir_accepts_single_matrix():
    A = T.combination_matrix(K, "ring")
    ir = T.schedule_ir(A)
    assert ir.period == 1 and ir.degree == 2
    np.testing.assert_array_equal(ir.matrix_at(0), A)


# ---------------------------------------------------------------------------
# Parity: sparse_host_dynamic == dense stacked, every kind × topology
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["link_failure", "gossip", "round_robin"])
@pytest.mark.parametrize("topo", ["ring", "full"])
def test_sparse_host_dynamic_matches_dense_stacked(kind, topo):
    sched = _schedule(kind, topo)
    stack = sched.matrices
    phi = _ragged_phi(K, seed=3)
    dense = D.make_combine("dense", A=stack)
    dyn = jax.jit(D.make_combine("sparse_host_dynamic", A=stack))
    for step in [0, 2, sched.period, 2 * sched.period + 1]:   # incl. wraps
        _assert_tree_close(dense(phi, jnp.int32(step)),
                           dyn(phi, jnp.int32(step)))


def test_sparse_host_dynamic_accepts_ir_and_static_matrix():
    sched = _schedule("round_robin", "ring")
    phi = _ragged_phi(K, seed=4)
    via_ir = D.make_combine("sparse_host_dynamic", A=sched.ir())
    via_stack = D.make_combine("sparse_host_dynamic", A=sched.matrices)
    _assert_tree_close(via_ir(phi, jnp.int32(1)),
                       via_stack(phi, jnp.int32(1)))
    # a static (K, K) matrix is the S=1 degenerate: step optional
    A = T.combination_matrix(K, "ring")
    static = D.make_combine("sparse_host_dynamic", A=A)
    _assert_tree_close(static(phi), D.dense_combine(jnp.asarray(A), phi))


def test_dynamic_combine_requires_step_when_periodic():
    sched = _schedule("gossip", "ring")
    fn = D.make_combine("sparse_host_dynamic", A=sched.matrices)
    with pytest.raises(ValueError, match="step"):
        fn(_ragged_phi(K))


# ---------------------------------------------------------------------------
# Every matrix backend takes a static matrix and a stacked schedule as given
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["dense", "sparse_host_dynamic", "pallas",
                                     "centralized", "none"])
@pytest.mark.parametrize("kind", ["static", "gossip"])
def test_backend_takes_static_matrix_and_stacked_schedule(kind, backend):
    """A static ring matrix and a stacked gossip schedule (and the same
    schedule as its ScheduleIR) go to the backend with no upgrade step:
    matrix backends match the dense einsum at the same step, centralized
    gives the centroid and none the identity."""
    sched = _schedule(kind, "ring")
    phi = _ragged_phi(K, seed=5)
    step = 3
    if backend == "centralized":
        want = jax.tree.map(lambda x: jnp.broadcast_to(
            jnp.mean(x.astype(jnp.float32), axis=0), x.shape), phi)
    elif backend == "none":
        want = phi
    else:
        want = D.dense_combine(jnp.asarray(sched.matrix_at(step)), phi)
    for A in (sched.stacked(), sched.ir()):
        got = D.make_combine(backend, A=A, interpret=True)(phi,
                                                           jnp.int32(step))
        _assert_tree_close(got, want)


# ---------------------------------------------------------------------------
# Selection rules
# ---------------------------------------------------------------------------

def test_select_backend_prefers_sparse_dynamic_for_stacked():
    ring = _schedule("link_failure", "ring").matrices
    assert D.select_backend(ring) == "sparse_host_dynamic"
    # a stack of one static matrix is a schedule too
    stack = np.stack([T.combination_matrix(K, "ring")] * 3)
    assert D.select_backend(stack) == "sparse_host_dynamic"
    # dense offset union (full graph): the step-indexed einsum stays
    full = _schedule("link_failure", "full").matrices
    assert D.select_backend(full) == "dense"
    # live mesh with one agent per shard upgrades to the mesh backend
    from repro.compat import abstract_mesh
    mesh = abstract_mesh((K, 2), ("data", "model"))
    assert D.select_backend(ring, mesh=mesh,
                            axis_name="data") == "mesh_sparse_dynamic"
    assert D.select_backend(ring, mesh=mesh,
                            axis_name="model") == "sparse_host_dynamic"


def test_combine_wire_bytes_dynamic():
    stack = _schedule("link_failure", "ring").matrices
    mb = 1000
    assert D.combine_wire_bytes(stack, "sparse_host_dynamic", mb) == 2 * mb
    assert D.combine_wire_bytes(stack, "mesh_sparse_dynamic", mb) == 2 * mb
    assert D.combine_wire_bytes(stack, "dense", mb) == (K - 1) * mb


def test_mesh_sparse_dynamic_validates_agent_extent():
    from repro.compat import abstract_mesh
    stack = _schedule("gossip", "ring").matrices
    mesh = abstract_mesh((4, 2), ("data", "model"))   # extent 4 != K=8
    with pytest.raises(ValueError, match="one agent per shard"):
        D.make_combine("mesh_sparse_dynamic", A=stack, mesh=mesh,
                       axis_name="data")


# ---------------------------------------------------------------------------
# Trainer integration: dynamic sparse backend == dense backend, end to end
# ---------------------------------------------------------------------------

def test_trainer_sparse_dynamic_matches_dense_backend():
    from repro.configs import get_config
    from repro.core import (MetaConfig, TopologyConfig, UpdateConfig,
                            init_state, make_meta_step)
    from repro.data.sine import agent_sine_distributions, stacked_agent_batch
    from repro.models.simple import SineMLP

    cfg = get_config("sine_mlp")
    model = SineMLP(cfg)
    Ka = 6

    def run(backend, steps=6):
        mcfg = MetaConfig(
            num_agents=Ka, tasks_per_agent=2, inner_lr=0.01,
            outer_optimizer="sgd", outer_lr=5e-3,
            update_config=UpdateConfig(strategy="atc", backend=backend),
            topology_config=TopologyConfig(graph="ring",
                                           schedule="link_failure",
                                           link_failure_p=0.3, seed=0))
        state = init_state(jax.random.key(0), model.init, mcfg,
                           identical_init=False)
        step = jax.jit(make_meta_step(model.loss_fn, mcfg))
        dists = agent_sine_distributions(Ka)
        for _ in range(steps):
            sup, qry = stacked_agent_batch(dists, 2, 10)
            state, metrics = step(state, jax.tree.map(jnp.asarray, sup),
                                  jax.tree.map(jnp.asarray, qry))
        return state

    # off a mesh, 'sparse' resolves to 'sparse_host_dynamic'
    sa = run("dense")
    sb = run("sparse")
    for a, b in zip(jax.tree.leaves(sa.params), jax.tree.leaves(sb.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
