"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (us_per_call = wall time of one
jitted training/eval step on this host; derived = the figure's headline
quantity).  Detailed curves are written to results/benchmarks/*.json.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import (MetaConfig, TopologyConfig, UpdateConfig, init_state,
                        make_eval_fn, make_meta_step, diffusion, topology)
from repro.data import (Episode, FewShotTaskSource, MetaBatchPipeline,
                        SineTaskSource)
from repro.models.simple import FewShotCNN, SineMLP

_DEVICE_EP = Episode.to_device

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results", "benchmarks")
ROWS: list[tuple[str, float, str]] = []


def emit(name: str, us: float, derived: str, detail: dict | None = None):
    ROWS.append((name, us, derived))
    print(f"{name},{us:.1f},{derived}")
    if detail is not None:
        os.makedirs(RESULTS, exist_ok=True)
        with open(os.path.join(RESULTS, f"{name}.json"), "w") as f:
            json.dump(detail, f, indent=1)


def _timed(fn, *args, reps=5):
    """Median-of-reps wall time (us) with a per-rep ``block_until_ready``.

    The old mean-with-one-trailing-block protocol had two failure modes on
    2-vCPU CI: async dispatch let reps overlap (the loop timed enqueue, not
    execution, for all but the last rep) and a single noisy rep skewed the
    mean.  Blocking each rep and taking the median fixes both."""
    jax.block_until_ready(fn(*args))            # compile + warm caches
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) * 1e6


# ---------------------------------------------------------------------------
# Shared sine harness (paper §4.1 setup: K=6, Fig 2a graph, Adam mu=1e-3)
# ---------------------------------------------------------------------------

def _sine_train(strategy: str, steps: int, seed: int = 0, mode: str = "maml",
                outer: str = "adam", lr: float = 1e-3, eval_every: int = 50,
                source: SineTaskSource | None = None, param_dtype=None):
    cfg = get_config("sine_mlp")
    model = SineMLP(cfg)
    K = 6
    combine = {"dif": "dense", "centralized": "centralized",
               "noncoop": "none"}[strategy]
    mcfg = MetaConfig(num_agents=K, tasks_per_agent=5, inner_lr=cfg.inner_lr,
                      mode=mode, combine=combine, topology="paper",
                      outer_optimizer=outer, outer_lr=lr)
    init_fn = (model.init if param_dtype is None
               else lambda k: model.init(k, param_dtype))
    state = init_state(jax.random.key(seed), init_fn, mcfg,
                       identical_init=True)
    step = jax.jit(make_meta_step(model.loss_fn, mcfg))
    if source is None:
        source = SineTaskSource(K=K, tasks_per_agent=5, shots=10, seed=seed)
    evaln = make_eval_fn(model.loss_fn, inner_lr=cfg.inner_lr, inner_steps=1)
    ev = source.eval_sample(200, seed=999)      # full-range eval (paper)
    esup = jax.tree.map(jnp.asarray, ev.support)
    eqry = jax.tree.map(jnp.asarray, ev.query)
    curve, step_us = [], None
    with MetaBatchPipeline(source, depth=2, prepare=_DEVICE_EP) as pipe:
        for i in range(steps):
            support, query = next(pipe)
            t0 = time.perf_counter()
            state, metrics = step(state, support, query)
            if i == steps - 1:
                jax.block_until_ready(metrics["loss"])
                step_us = (time.perf_counter() - t0) * 1e6
            if i % eval_every == 0 or i == steps - 1:
                if strategy == "noncoop":
                    # paper protocol: average of per-agent test losses
                    losses = []
                    for k in range(K):
                        pk = jax.tree.map(lambda x: x[k], state.params)
                        losses.append(float(np.mean(np.asarray(
                            evaln(pk, esup, eqry))[:, 1])))
                    curve.append((i, float(np.mean(losses))))
                else:
                    # eval the centroid in f32 so bf16-storage runs measure
                    # training drift, not eval-precision noise (no-op at f32)
                    c = jax.tree.map(lambda x: x.astype(jnp.float32),
                                     diffusion.centroid(state.params))
                    l = float(np.mean(np.asarray(evaln(c, esup, eqry))[:, 1]))
                    curve.append((i, l))
    return state, model, curve, step_us


def bench_fig2b_sine_regression(quick: bool):
    """Fig 2b: test loss during training — centralized vs Dif vs non-coop."""
    steps = 200 if quick else 1000
    out = {}
    for strat in ["centralized", "dif", "noncoop"]:
        _, _, curve, us = _sine_train(strat, steps)
        out[strat] = curve
        emit(f"fig2b_sine_{strat}", us,
             f"final_test_loss={curve[-1][1]:.4f}")
    gap_cd = out["dif"][-1][1] - out["centralized"][-1][1]
    gap_nd = out["noncoop"][-1][1] - out["dif"][-1][1]
    emit("fig2b_summary", 0.0,
         f"dif_minus_centralized={gap_cd:.4f};noncoop_minus_dif={gap_nd:.4f}",
         detail=out)


def bench_fig2c_adaptation_steps(quick: bool):
    """Fig 2c: post-training test loss vs number of adaptation steps."""
    steps = 200 if quick else 1000
    n_adapt = 10
    ep = SineTaskSource(K=6).eval_sample(200, seed=777)
    esup = jax.tree.map(jnp.asarray, ep.support)
    eqry = jax.tree.map(jnp.asarray, ep.query)
    out = {}
    for strat in ["centralized", "dif", "noncoop"]:
        state, model, _, us = _sine_train(strat, steps)
        ev = make_eval_fn(model.loss_fn, inner_lr=0.01, inner_steps=n_adapt)
        if strat == "noncoop":
            curves = []
            for k in range(6):
                pk = jax.tree.map(lambda x: x[k], state.params)
                curves.append(np.asarray(ev(pk, esup, eqry)).mean(0))
            curve = np.mean(curves, axis=0)
        else:
            c = diffusion.centroid(state.params)
            curve = np.asarray(ev(c, esup, eqry)).mean(0)
        out[strat] = curve.tolist()
        emit(f"fig2c_adapt_{strat}", us,
             f"loss_step1={curve[1]:.4f};loss_step10={curve[10]:.4f}")
    emit("fig2c_summary", 0.0,
         "ordering_preserved=%s" % (out["dif"][10] < out["noncoop"][10]),
         detail=out)


def bench_fig3_fewshot_classification(quick: bool):
    """Fig 3 analogue: few-shot classification (synthetic Omniglot
    surrogate), centralized vs Dif vs non-coop, 5-way 1-shot."""
    steps = 60 if quick else 300
    cfg = get_config("omniglot_cnn")
    source = FewShotTaskSource(K=6, tasks_per_agent=2, n_classes=80,
                               n_way=cfg.vocab_size, k_shot=1, n_query=5,
                               seed=0)
    model = FewShotCNN(cfg, image_hw=source.image_hw)
    test_ep = source.eval_sample(50, seed=4242)       # meta-test classes
    tsup = jax.tree.map(jnp.asarray, test_ep.support)
    tqry = jax.tree.map(jnp.asarray, test_ep.query)
    out = {}
    for strat in ["centralized", "dif", "noncoop"]:
        combine = {"dif": "dense", "centralized": "centralized",
                   "noncoop": "none"}[strat]
        mcfg = MetaConfig(num_agents=6, tasks_per_agent=2, inner_lr=cfg.inner_lr,
                          mode="maml", combine=combine, topology="paper",
                          outer_optimizer="adam", outer_lr=1e-3)
        state = init_state(jax.random.key(0), model.init, mcfg,
                           identical_init=True)
        step = jax.jit(make_meta_step(model.loss_fn, mcfg))
        us = None
        accs = []
        with MetaBatchPipeline(source, depth=2, prepare=_DEVICE_EP) as pipe:
            for i in range(steps):
                sup, qry = next(pipe)
                t0 = time.perf_counter()
                state, m = step(state, sup, qry)
                if i == steps - 1:
                    jax.block_until_ready(m["loss"])
                    us = (time.perf_counter() - t0) * 1e6
                if i % max(1, steps // 5) == 0 or i == steps - 1:
                    c = diffusion.centroid(state.params)
                    accs_k = []
                    agents = range(6) if strat == "noncoop" else [None]
                    for k in agents:
                        p = c if k is None else jax.tree.map(lambda x: x[k],
                                                             state.params)
                        def adapted_acc(sx_, sy_, qx_, qy_):
                            g = jax.grad(model.loss_fn)(p, (sx_, sy_))
                            pa = jax.tree.map(
                                lambda a, b: a - cfg.inner_lr * b, p, g)
                            return model.accuracy(pa, (qx_, qy_))
                        acc = jnp.mean(jax.vmap(adapted_acc)(
                            tsup[0], tsup[1], tqry[0], tqry[1]))
                        accs_k.append(float(acc))
                    accs.append((i, float(np.mean(accs_k))))
        out[strat] = accs
        emit(f"fig3_fewshot_{strat}", us, f"final_test_acc={accs[-1][1]:.4f}")
    emit("fig3_summary", 0.0,
         "dif_ge_noncoop=%s" % (out["dif"][-1][1] >= out["noncoop"][-1][1] - 0.02),
         detail=out)


def bench_thm1_agreement(quick: bool):
    """Thm 1: network disagreement decays linearly at rate lambda_2, then
    plateaus at O(mu^2)."""
    cfg = get_config("sine_mlp")
    model = SineMLP(cfg)
    rows = {}
    for mu in [5e-3, 1e-3]:
        mcfg = MetaConfig(num_agents=6, tasks_per_agent=3, inner_lr=0.01,
                          mode="maml", combine="dense", topology="ring",
                          outer_optimizer="sgd", outer_lr=mu)
        state = init_state(jax.random.key(1), model.init, mcfg,
                           identical_init=False)
        step = jax.jit(make_meta_step(model.loss_fn, mcfg))
        source = SineTaskSource(K=6, tasks_per_agent=3, shots=10)
        ds = [float(diffusion.disagreement(state.params))]
        with MetaBatchPipeline(source, depth=2, prepare=_DEVICE_EP) as pipe:
            for i in range(80 if quick else 300):
                sup, qry = next(pipe)
                state, m = step(state, sup, qry)
                ds.append(float(m["disagreement"]))
        rows[f"mu={mu}"] = ds
        plateau = float(np.mean(ds[-20:]))
        emit(f"thm1_agreement_mu{mu}", 0.0,
             f"plateau={plateau:.3e};decay10={ds[10]/ds[0]:.3e}")
    lam2 = topology.mixing_rate(topology.combination_matrix(6, "ring"))
    p1 = np.mean(rows["mu=0.005"][-20:])
    p2 = np.mean(rows["mu=0.001"][-20:])
    emit("thm1_summary", 0.0,
         f"lambda2={lam2:.3f};plateau_ratio={(p1 / p2):.1f};mu_ratio_sq=25.0",
         detail=rows)


def bench_thm2_stationarity(quick: bool):
    """Thm 2/Cor 1: ||grad J(centroid)||^2 reaches an O(mu) ball."""
    from repro.core import maml
    cfg = get_config("sine_mlp")
    model = SineMLP(cfg)
    source = SineTaskSource(K=6, tasks_per_agent=5, shots=10)
    out = {}
    for mu in [2e-3, 5e-4]:
        mcfg = MetaConfig(num_agents=6, tasks_per_agent=5, inner_lr=0.01,
                          mode="maml", combine="dense", topology="paper",
                          outer_optimizer="sgd", outer_lr=mu)
        state = init_state(jax.random.key(0), model.init, mcfg,
                           identical_init=True)
        step = jax.jit(make_meta_step(model.loss_fn, mcfg))

        @jax.jit
        def grad_norm_sq(params_c, sup, qry):
            def one_agent(s, q):
                _, g = maml.multi_task_meta_grad(model.loss_fn, params_c,
                                                 s, q, alpha=0.01)
                return g
            gs = jax.vmap(one_agent)(sup, qry)
            g_mean = jax.tree.map(lambda x: jnp.mean(x, 0), gs)
            return sum(jnp.sum(x ** 2) for x in jax.tree.leaves(g_mean))

        norms = []
        with MetaBatchPipeline(source, depth=2, prepare=_DEVICE_EP) as pipe:
            for i in range(100 if quick else 400):
                sup, qry = next(pipe)
                state, _ = step(state, sup, qry)
                if i % 20 == 0:
                    c = diffusion.centroid(state.params)
                    norms.append(float(grad_norm_sq(c, sup, qry)))
        out[f"mu={mu}"] = norms
        emit(f"thm2_stationarity_mu{mu}", 0.0,
             f"grad_norm_sq_final={norms[-1]:.3e};initial={norms[0]:.3e}")
    emit("thm2_summary", 0.0, "smaller_mu_smaller_ball=%s"
         % (np.min(out["mu=0.0005"]) <= np.min(out["mu=0.002"]) * 2),
         detail=out)


def bench_combine_strategies(quick: bool):
    """Combine backend shoot-out through the unified registry entry point:
    wall time + modeled collective bytes/step per backend, on a 1M-param
    launch model, K=16 ring.  The pallas backend runs compiled on TPU and
    in interpreter mode elsewhere (correctness row, not a perf row)."""
    K = 16
    A = topology.combination_matrix(K, "ring")
    lam2 = topology.mixing_rate(A)
    phi = {"w": jax.random.normal(jax.random.key(0), (K, 1024, 1024)),
           "b": jax.random.normal(jax.random.key(1), (K, 4096))}
    nbytes = sum(x.nbytes // K for x in jax.tree.leaves(phi))
    on_tpu = jax.default_backend() == "tpu"
    backends = ["dense", "sparse_host_dynamic", "centralized", "pallas"]
    outs = {}
    for name in backends:
        # interpreter-mode pallas: bigger blocks keep the grid (and the
        # python-loop interpret overhead) small
        bm = 8192 if (name == "pallas" and not on_tpu) else 512
        fn = jax.jit(diffusion.make_combine(name, A=A, block_m=bm))
        us = _timed(fn, phi)
        outs[name] = fn(phi)
        wire = diffusion.combine_wire_bytes(A, name, nbytes)
        # centralized replaces A with (1/K)11^T, whose mixing rate is 0
        lam = 0.0 if name == "centralized" else lam2
        emit(f"combine_{name}", us,
             f"combine_bytes_step={wire};lambda2={lam:.3f}"
             + ("" if name != "pallas" or on_tpu else ";interpret=1"))
    auto = diffusion.select_backend(A)
    emit("combine_auto_selects", 0.0, f"backend={auto}")
    ref = jax.tree.leaves(outs["dense"])
    for name in ["sparse_host_dynamic", "pallas"]:
        err = max(float(jnp.max(jnp.abs(a - b)))
                  for a, b in zip(ref, jax.tree.leaves(outs[name])))
        emit(f"combine_{name}_equals_dense", 0.0, f"max_err={err:.2e}")


# Runs under 8 forced host devices in a subprocess (the parent process owns
# a single-device jax runtime): lowers the dense-stacked and the
# mesh_sparse_dynamic combine for each dynamic schedule × topology, reads
# collective wire bytes off the optimized HLO (launch/hlo_cost.py), and
# times both with the median-of-reps protocol.
_DYNAMIC_COMBINE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import json, sys
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import compat
from repro.core import diffusion, topology
from repro.launch.hlo_cost import HloCost
from benchmarks.run import _timed as timed   # ONE timing protocol

K = 8
M = int(sys.argv[1])
mesh = compat.make_mesh((K,), ("data",))
phi = {"w": jax.random.normal(jax.random.key(0), (K, M), jnp.float32)}
sh = NamedSharding(mesh, P("data", None))
phi_sh = {"w": jax.device_put(phi["w"], sh)}
step0 = jnp.zeros((), jnp.int32)

out = {}
with mesh:
    for topo_name in ["ring", "full"]:
        topo = topology.build_topology(topo_name, K)
        for kind, kw in [("link_failure", dict(p=0.3, period=16, seed=0)),
                         ("gossip", dict(period=16, seed=0)),
                         ("round_robin", {})]:
            sched = topology.make_schedule(kind, topo, **kw)
            stack = sched.matrices           # always (S, K, K)
            dense = jax.jit(diffusion.make_combine("dense", A=stack))
            dyn = jax.jit(diffusion.make_combine(
                "mesh_sparse_dynamic", A=stack, mesh=mesh,
                axis_name="data", in_specs={"w": P("data", None)}))
            rec = {"period": sched.period, "deg": sched.ir().degree}
            for name, fn in [("dense", dense), ("sparse_dynamic", dyn)]:
                txt = fn.lower(phi_sh, step0).compile().as_text()
                coll = HloCost(txt, n_dev=K).collectives()
                rec[name] = {"wire_bytes": coll["total_bytes"],
                             "collectives": coll["total_count"],
                             "us": timed(fn, phi_sh, step0)}
            s = jnp.int32(3)
            err = jnp.max(jnp.abs(dense(phi_sh, s)["w"] - dyn(phi_sh, s)["w"]))
            rec["max_err"] = float(err)
            out[kind + "_" + topo_name] = rec

    # bf16 wire vs the f32 escape hatch on the K=8 ring: same bf16 phi,
    # same backend, only the wire format differs.  Permute bytes come off
    # the optimized HLO — the bf16 payload rides as u16 (2 B/elem; see the
    # wire-format contract in core/diffusion.py), so the halving is real
    # on-wire, not a trace-level fiction the CPU backend re-widens.
    ring = topology.build_topology("ring", K)
    rr = topology.make_schedule("round_robin", ring)
    phi_bf = jax.tree.map(lambda x: x.astype(jnp.bfloat16), phi_sh)
    for label, Amat, extra in [
            ("static", ring.matrix, ()),
            ("round_robin", rr.stacked(), (step0,))]:
        rec, lint = {}, {}
        for wire in ["float32", "bfloat16"]:
            fn = jax.jit(diffusion.make_combine(
                "mesh_sparse_dynamic", A=Amat, mesh=mesh, axis_name="data",
                in_specs={"w": P("data", None)}, combine_dtype=wire))
            txt = fn.lower(phi_bf, *extra).compile().as_text()
            cp = HloCost(txt, n_dev=K).collectives()["per_op"].get(
                "collective-permute", {"wire_bytes": 0, "by_dtype": {}})
            rec[wire] = {"permute_bytes": cp["wire_bytes"],
                         "by_dtype": cp["by_dtype"],
                         "us": timed(fn, phi_bf, *extra),
                         "out": fn(phi_bf, *extra)}
            if wire == "bfloat16":
                # the u16-wire invariant now lives in the lint registry:
                # deg=2 on the K=8 ring, shard = M bf16 elems = 2M wire B
                from repro.analysis.rules import LintContext, run_rules
                ctx = LintContext(hlo=txt, n_dev=K, K=K, degree=2,
                                  shard_bytes=M * 2, wire_dtype="bfloat16")
                rep = run_rules(ctx, only=["collective-budget",
                                           "wire-dtype-leak"])
                lint = {"ok": rep.to_json()["ok"],
                        "checked": rep.checked,
                        "findings": [f.message for f in rep.findings]}
        err = float(jnp.max(jnp.abs(
            rec["bfloat16"]["out"]["w"].astype(jnp.float32)
            - rec["float32"]["out"]["w"].astype(jnp.float32))))
        out["wire_" + label] = {
            "wire_bytes_bf16": rec["bfloat16"]["permute_bytes"],
            "wire_bytes_f32": rec["float32"]["permute_bytes"],
            "by_dtype_bf16": rec["bfloat16"]["by_dtype"],
            "us_bf16": rec["bfloat16"]["us"],
            "us_f32": rec["float32"]["us"],
            "max_err_vs_f32_wire": err,
            "lint": lint}
print("BENCH_JSON:" + json.dumps(out))
"""


def bench_combine_dynamic(quick: bool):
    """Dynamic-schedule combine: collective wire bytes (HLO-verified) and
    wall time, dense-stacked step-indexed einsum vs the sparse_dynamic
    ppermute lowering, per schedule × {ring, full} at K=8 on an 8-shard
    agent mesh.  On ring (deg 2) the sparse path must move ≤ (deg+1)/K of
    the dense bytes per combine — the acceptance row CI records."""
    import subprocess
    M = 1 << 13 if quick else 1 << 15
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, "-c", _DYNAMIC_COMBINE_SCRIPT, str(M)],
        capture_output=True, text=True, env=env,
        cwd=os.path.join(os.path.dirname(__file__), ".."), timeout=1200)
    lines = [l for l in res.stdout.splitlines()
             if l.startswith("BENCH_JSON:")]
    if not lines:
        raise RuntimeError(
            f"combine_dynamic subprocess failed:\n{res.stderr[-2000:]}")
    data = json.loads(lines[0][len("BENCH_JSON:"):])
    for name, rec in data.items():
        if name.startswith("wire_"):
            # bf16 wire vs f32 escape hatch, same backend and bf16 phi:
            # the acceptance row — ratio ≤ 0.55 (HLO-verified; exactly 0.5
            # up to rounding since the payload rides as 2-byte u16)
            ratio = rec["wire_bytes_bf16"] / max(rec["wire_bytes_f32"], 1)
            emit(f"combine_{name}_bf16", rec["us_bf16"],
                 f"f32_us={rec['us_f32']:.1f};"
                 f"wire_bf16={rec['wire_bytes_bf16']};"
                 f"wire_f32={rec['wire_bytes_f32']};"
                 f"bytes_ratio={ratio:.3f};"
                 f"within_055={ratio <= 0.55};K=8;"
                 f"lint_clean={rec['lint'].get('ok', False)};"
                 f"max_err_vs_f32_wire={rec['max_err_vs_f32_wire']:.2e}")
            continue
        dense, sp = rec["dense"], rec["sparse_dynamic"]
        ratio = sp["wire_bytes"] / max(dense["wire_bytes"], 1)
        emit(f"combine_dynamic_{name}", sp["us"],
             f"dense_us={dense['us']:.1f};"
             f"wire_sparse={sp['wire_bytes']};wire_dense={dense['wire_bytes']};"
             f"bytes_ratio={ratio:.3f};deg={rec['deg']};K=8;"
             f"max_err={rec['max_err']:.2e}")
    ring = data["link_failure_ring"]
    ring_ratio = (ring["sparse_dynamic"]["wire_bytes"]
                  / max(ring["dense"]["wire_bytes"], 1))
    emit("combine_dynamic_summary", 0.0,
         f"ring_bytes_ratio={ring_ratio:.3f};"
         f"bound_deg_plus_1_over_K={(ring['deg'] + 1) / 8:.3f};"
         f"ring_within_bound={ring_ratio <= (ring['deg'] + 1) / 8}",
         detail=data)


def bench_superstep(quick: bool):
    """Dispatch-free training loop: steps/sec of the superstep driver at
    C=1 (one jitted dispatch + one host metric fetch per step — the legacy
    loop's behavior) vs C=8 (one per 8 steps).  On dispatch-bound hardware
    the win is the Python/sync overhead times (C−1)/C."""
    from repro.configs.base import ArchConfig, InputShape
    from repro.data import LMTaskSource
    from repro.launch.mesh import make_host_mesh
    from repro.launch import steps as S

    seq, gb = 32, 8
    cfg = ArchConfig(name="superstep-bench", arch_type="dense", num_layers=1,
                     d_model=32, num_heads=2, num_kv_heads=2, head_dim=16,
                     d_ff=64, vocab_size=256, meta_mode="fomaml",
                     topology="ring", outer_optimizer="adam",
                     dtype="float32", remat=False, attn_q_chunk=None,
                     meta_tasks=2)
    shape = InputShape("superstep_bench", seq, gb, "train")
    mesh = make_host_mesh(data=min(4, len(jax.devices())))
    with mesh:
        bundle = S.build_train(cfg, mesh, shape)
        source = LMTaskSource(vocab_size=cfg.padded_vocab, seq_len=seq,
                              K=bundle.K, tasks_per_agent=bundle.T,
                              task_batch=bundle.tb, n_domains=8, seed=0)
        superstep = S.make_superstep(bundle.step_fn)
        fns = {C: jax.jit(superstep, donate_argnums=(0,))
               for C in (1, 8)}
        n_steps = 32 if quick else 64

        def run(C):
            fn = fns[C]
            st = bundle.init_state(seed=0)
            with bundle.make_pipeline(source, depth=2, stack=C) as pipe:
                for _ in range(2):               # compile + warm caches
                    st, m = fn(st, next(pipe))
                jax.device_get(m)
                t0 = time.perf_counter()
                for _ in range(n_steps // C):
                    st, m = fn(st, next(pipe))
                    jax.device_get(m)            # per-dispatch host sync
                return (n_steps // C) / (time.perf_counter() - t0) * C

        run(1)                                   # process burn-in
        r = {1: [], 8: []}
        for _ in range(3 if quick else 5):       # alternate reps (2-vCPU
            for C in (1, 8):                     # clock drift protocol)
                r[C].append(run(C))
        sps = {C: float(np.median(v)) for C, v in r.items()}
        emit("superstep", 1e6 / sps[8],
             f"steps_per_s_c8={sps[8]:.1f};steps_per_s_c1={sps[1]:.1f};"
             f"speedup={sps[8] / sps[1]:.2f}x",
             detail={"steps_per_s": {str(C): v for C, v in r.items()}})


def bench_serve(quick: bool):
    """Serving tier (adaptation-as-a-service): (1) N=8 concurrent user
    episodes adapted in ONE vmapped dispatch vs 8 sequential serve.py-style
    adapts (fresh per-request jit — the legacy path); (2) adapted-state
    cache: recurring-task hit (low-rank delta reconstruction) vs
    re-adaptation, plus the delta fidelity (|Δ adapted query loss|) and
    compression ratio; (3) scanned two-phase decode vs the legacy
    per-token python loop; (4) adapt p50/p99 + adapted-decodes/sec vs
    concurrent users × recurring fraction.  The two CI-pinned thresholds
    (batched ≥3× cold-sequential, cache hit ≥5× faster than re-adapt)
    raise on violation."""
    from repro.configs.base import ArchConfig
    from repro.core import maml
    from repro.launch.serve import make_support_source
    from repro.models.transformer import build_model
    from repro.serve import AdaptRequest, ServeEngine
    from repro.serve.cache import AdaptedStateCache

    cfg = ArchConfig(name="serve-bench", arch_type="dense", num_layers=1,
                     d_model=32, num_heads=2, num_kv_heads=2, head_dim=16,
                     d_ff=64, vocab_size=256, dtype="float32", remat=False,
                     attn_q_chunk=None, inner_lr=1e-2, inner_steps=1)
    P, G, B, N = 8, 16, 4, 8
    steps = 2
    reps = 3 if quick else 8
    engine = ServeEngine(cfg, prompt_len=P, gen=G, batch=B,
                         adapt_steps=steps, buckets=(1, 2, 4, 8))
    model = build_model(cfg)
    params = model.init(jax.random.key(0), jnp.float32)
    engine.load_params(params)
    source = make_support_source(cfg, P + G, B)
    ep = source.eval_sample(N, seed=3, split="full")
    sup = [{k: v[i] for k, v in ep.support.items()} for i in range(N)]
    qry = [{k: v[i] for k, v in ep.query.items()} for i in range(N)]
    # forced-distinct keys: eval_sample may repeat domains, and a shared
    # key would make two users alias one cache entry
    keyed = [AdaptRequest(sup[i], engine.signature(source, 1000 + i))
             for i in range(N)]
    keyless = [AdaptRequest(s) for s in sup]

    # --- (1) batched vmapped adapt vs sequential ------------------------
    engine.adapt(keyless)                        # compile bucket-8
    batched_s = []
    for _ in range(reps):
        t0 = time.perf_counter()
        engine.adapt(keyless)
        batched_s.append(time.perf_counter() - t0)
    batched = float(np.median(batched_s))

    def adapt_one_fn():
        return jax.jit(lambda p, b: maml.inner_adapt(
            model.loss_fn, p, b, alpha=cfg.inner_lr, steps=steps,
            first_order=True))

    warm_fn = adapt_one_fn()
    dev_sup = [{k: jnp.asarray(v) for k, v in s.items()} for s in sup]
    jax.block_until_ready(warm_fn(params, dev_sup[0]))
    t0 = time.perf_counter()
    for s in dev_sup:
        jax.block_until_ready(warm_fn(params, s))
    warm_seq = time.perf_counter() - t0
    t0 = time.perf_counter()
    for s in dev_sup:                            # serve.py-style: a fresh
        f = adapt_one_fn()                       # jit per request, so every
        jax.block_until_ready(f(params, s))      # request retraces
    cold_seq = time.perf_counter() - t0
    x_cold, x_warm = cold_seq / batched, warm_seq / batched
    emit("serve_adapt_batched", batched * 1e6 / N,
         f"n={N};batched_s={batched:.4f};cold_seq_s={cold_seq:.2f};"
         f"warm_seq_s={warm_seq:.4f};throughput_x_cold={x_cold:.1f};"
         f"throughput_x_warm={x_warm:.2f};meets_3x={x_cold >= 3.0}",
         detail={"batched_s": batched_s, "cold_seq_s": cold_seq,
                 "warm_seq_s": warm_seq})

    # --- (2) cache hit vs re-adaptation + delta fidelity ----------------
    full_adapted, _ = engine.adapt(keyed)        # misses: fill the cache
    engine.adapt(keyed)                          # compile the hit path
    miss_s, hit_s = [], []
    for _ in range(reps):
        engine.cache._store.clear()              # force misses (warm fns)
        t0 = time.perf_counter()
        engine.adapt(keyed)
        miss_s.append((time.perf_counter() - t0) / N)
        t0 = time.perf_counter()
        rec_adapted, _ = engine.adapt(keyed)
        hit_s.append((time.perf_counter() - t0) / N)
    miss_us, hit_us = np.median(miss_s) * 1e6, np.median(hit_s) * 1e6
    speedup = miss_us / hit_us
    l_full = engine.adapted_loss(full_adapted, qry)
    l_rec = engine.adapted_loss(rec_adapted, qry)
    drift = float(np.max(np.abs(l_full - l_rec)))
    stats = engine.cache.stats()
    emit("serve_cache_hit", hit_us,
         f"readapt_us={miss_us:.0f};speedup={speedup:.1f}x;"
         f"meets_5x={speedup >= 5.0};loss_drift={drift:.5f};"
         f"drift_ok={drift <= 1e-2};compression={stats['compression']:.2f}x",
         detail={"miss_s": miss_s, "hit_s": hit_s, "cache": stats,
                 "loss_full": l_full.tolist(), "loss_rec": l_rec.tolist()})

    # --- (3) scanned decode vs per-token python loop --------------------
    prompt = np.asarray(ep.query["tokens"][0])[:, :P]
    a0 = full_adapted[0]
    engine.decode(a0, prompt)                    # compile both scans
    dm = None
    for _ in range(reps):
        _, dm = engine.decode(a0, prompt)
    step = jax.jit(engine.bundle.step_fn)        # legacy loop baseline

    def py_loop():
        cache = model.init_cache(B, P + G, jnp.float32, params=a0)
        tok = jnp.asarray(prompt[:, :1])
        for t in range(P + G - 1):
            logits, cache = step(a0, cache, tok, jnp.full((B,), t, jnp.int32))
            if t + 1 < P:
                tok = jnp.asarray(prompt[:, t + 1: t + 2])
            else:
                tok = jnp.argmax(logits[:, 0], axis=-1)[:, None].astype(
                    jnp.int32)
                np.asarray(tok)                  # the per-token host sync
        return tok

    loop_us = _timed(py_loop, reps=reps)
    scan_us = (dm["prefill_s"] + dm["decode_s"]) * 1e6
    emit("serve_decode", scan_us,
         f"prompt_tok_s={dm['prompt_tok_s']:.0f};"
         f"decode_tok_s={dm['decode_tok_s']:.0f};"
         f"pyloop_us={loop_us:.0f};speedup_vs_pyloop={loop_us / scan_us:.1f}x",
         detail={"scan": dm, "pyloop_us": loop_us})

    # --- (4) adapt latency + adapted-decodes/sec vs users × recurring ---
    sweep: dict[str, dict] = {}
    for users in (1, 2, 4, 8):
        u_sup = sup[:users]
        u_keyed = keyed[:users]
        engine.adapt([AdaptRequest(s) for s in u_sup])   # compile bucket
        row = {}
        for frac_name, frac in [("cold", 0.0), ("mixed", 0.5),
                                ("recurring", 1.0)]:
            n_rec = int(users * frac)
            # recurring users resolve from the cache; the rest opt out of
            # caching so every rep re-measures a genuine miss
            requests = u_keyed[:n_rec] + [AdaptRequest(s)
                                          for s in u_sup[n_rec:]]
            engine.cache = AdaptedStateCache(capacity=64)
            if n_rec:
                engine.adapt(u_keyed[:n_rec])    # residents + compile
            lat, thru = [], []
            for _ in range(reps):
                t0 = time.perf_counter()
                adapted, _ = engine.adapt(requests)
                adapt_s = time.perf_counter() - t0
                t0 = time.perf_counter()
                for a in adapted:
                    engine.decode(a, prompt)
                dec_s = time.perf_counter() - t0
                lat.append(adapt_s / users)
                thru.append(users * B * G / (adapt_s + dec_s))
            row[frac_name] = {
                "adapt_p50_us": float(np.percentile(lat, 50) * 1e6),
                "adapt_p99_us": float(np.percentile(lat, 99) * 1e6),
                "adapted_decodes_per_s": float(np.median(thru)),
            }
        sweep[str(users)] = row
        emit(f"serve_users_{users}", row["cold"]["adapt_p50_us"],
             f"cold_p50_us={row['cold']['adapt_p50_us']:.0f};"
             f"cold_p99_us={row['cold']['adapt_p99_us']:.0f};"
             f"recurring_p50_us={row['recurring']['adapt_p50_us']:.0f};"
             f"decodes_per_s_cold={row['cold']['adapted_decodes_per_s']:.1f};"
             f"decodes_per_s_recurring="
             f"{row['recurring']['adapted_decodes_per_s']:.1f}")
    emit("serve_summary", batched * 1e6 / N,
         f"batched_x_cold={x_cold:.1f};cache_hit_x={speedup:.1f};"
         f"drift={drift:.5f};compression={stats['compression']:.2f}x",
         detail={"sweep": sweep})

    if x_cold < 3.0:
        raise RuntimeError(
            f"serve acceptance: batched adapt {x_cold:.2f}x vs "
            f"cold-sequential, pinned >= 3x")
    if speedup < 5.0:
        raise RuntimeError(
            f"serve acceptance: cache hit {speedup:.2f}x vs re-adapt, "
            f"pinned >= 5x")
    if drift > 1e-2:
        raise RuntimeError(
            f"serve acceptance: delta-reconstruction loss drift "
            f"{drift:.4f}, pinned <= 1e-2")


def bench_kernels(quick: bool):
    """Pallas kernels (interpret mode) vs jnp oracles: correctness +
    oracle wall time (kernels target TPU; interpret timing is not a perf
    number, the oracle timing is the CPU reference)."""
    from repro.kernels.dif_combine.dif_combine import dif_combine
    from repro.kernels.dif_combine.ref import dif_combine_ref
    from repro.kernels.flash_attention.flash_attention import flash_attention_fwd
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.ssd_scan.ssd_scan import ssd_scan_pallas
    from repro.kernels.ssd_scan.ref import ssd_scan_ref

    K, M = 16, 1 << 16
    A = jnp.asarray(topology.combination_matrix(K, "ring"), jnp.float32)
    phi = jax.random.normal(jax.random.key(0), (K, M))
    out = dif_combine(A, phi, block_m=512, interpret=True)
    err = float(jnp.max(jnp.abs(out - dif_combine_ref(A, phi))))
    us = _timed(jax.jit(lambda a, p: dif_combine_ref(a, p)), A, phi)
    emit("kernel_dif_combine", us, f"allclose_err={err:.2e};shape={K}x{M}")

    B, H, S, d = 1, 2, 256, 64
    q, k, v = [jax.random.normal(jax.random.key(i), (B, H, S, d))
               for i in range(3)]
    o = flash_attention_fwd(q, k, v, causal=True, block_q=128, block_k=128,
                            interpret=True)
    err = float(jnp.max(jnp.abs(o - attention_ref(q, k, v, causal=True))))
    us = _timed(jax.jit(lambda q, k, v: attention_ref(q, k, v, causal=True)),
                q, k, v)
    emit("kernel_flash_attention", us, f"allclose_err={err:.2e};S={S}")

    Bb, L, Hh, P, N = 1, 256, 2, 32, 64
    ks = jax.random.split(jax.random.key(7), 5)
    x = jax.random.normal(ks[0], (Bb, L, Hh, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bb, L, Hh))) * 0.5
    Aa = -jnp.exp(jax.random.normal(ks[2], (Hh,)) * 0.3)
    Bm = jax.random.normal(ks[3], (Bb, L, Hh, N)) * 0.3
    Cm = jax.random.normal(ks[4], (Bb, L, Hh, N)) * 0.3
    y, _ = ssd_scan_pallas(x, dt, Aa, Bm, Cm, chunk=64, interpret=True)
    yr, _ = ssd_scan_ref(x, dt, Aa, Bm, Cm)
    err = float(jnp.max(jnp.abs(y - yr)))
    us = _timed(jax.jit(lambda *a: ssd_scan_ref(*a)[0]), x, dt, Aa, Bm, Cm)
    emit("kernel_ssd_scan", us, f"allclose_err={err:.2e};L={L}")


class _LoopLMSource:
    """Legacy python-triple-loop LM sampler adapted to the TaskSource
    surface — the pre-vectorization baseline the pipeline rows measure
    against (also a stand-in for host-bound real-corpus sources)."""

    def __init__(self, sampler, K, T, tb):
        self.sampler = sampler
        self.K, self.tasks_per_agent, self.task_batch = K, T, tb
        self.n_domains = sampler.n_domains
        self.heterogeneity = "domain-shards(loop)"

    def sample(self, step):
        sup, qry = self.sampler.sample_agents(
            self.K, self.tasks_per_agent, self.task_batch, step=step)
        return Episode(sup, qry, step=step)


def bench_pipeline(quick: bool):
    """Tentpole rows: (1) vectorized LM episode generation (one batched
    Markov pass over all K·T·2·tb rows) vs the legacy per-task python
    loop; (2) train-step wall time with synchronous sampling vs the
    background prefetcher, for both the loop and vectorized sources —
    overlap_recovered = fraction of the sync step time the pipeline wins
    back by sampling episode i+1 while the device runs step i."""
    from repro.configs.base import ArchConfig, InputShape
    from repro.data import LMTaskSampler, LMTaskSource
    from repro.launch.mesh import make_host_mesh
    from repro.launch import steps as S

    # Rich Markov domains (4096-bucket × 256-branch transition tables, 8
    # tasks/agent) put episode generation squarely on the host critical
    # path — the regime the pipeline exists for.  The legacy loop rebuilds
    # every table per task; the vectorized source builds each once, caches
    # it, and advances all rows of the step in one generator pass.
    seq, gb = 256, 64
    cfg = ArchConfig(name="lm-pipe-bench", arch_type="dense", num_layers=1,
                     d_model=32, num_heads=2, num_kv_heads=2, head_dim=16,
                     d_ff=64, vocab_size=256, meta_mode="fomaml",
                     topology="ring", outer_optimizer="adam",
                     dtype="float32", remat=False, attn_q_chunk=None,
                     meta_tasks=8)
    shape = InputShape("lm_pipe_bench", seq, gb, "train")
    mesh = make_host_mesh(data=min(4, len(jax.devices())))
    with mesh:
        bundle = S.build_train(cfg, mesh, shape)
        K, T, tb = bundle.K, bundle.T, bundle.tb
        dom_kw = dict(n_domains=8 * max(1, K), branching=256,
                      n_buckets=4096, seed=0)
        vec = LMTaskSource(vocab_size=cfg.padded_vocab, seq_len=seq,
                           K=K, tasks_per_agent=T, task_batch=tb,
                           **dom_kw)
        loop = _LoopLMSource(
            LMTaskSampler(cfg.padded_vocab, seq, **dom_kw), K, T, tb)

        # --- (1) episode generation: vectorized vs python loop -------
        reps = 3 if quick else 10
        vec.sample(0); loop.sample(0)            # warm table caches
        t0 = time.perf_counter()
        for i in range(reps):
            vec.sample(i)
        vec_s = (time.perf_counter() - t0) / reps
        t0 = time.perf_counter()
        for i in range(reps):
            loop.sample(i)
        loop_s = (time.perf_counter() - t0) / reps
        emit("pipeline_lm_vectorized", vec_s * 1e6,
             f"speedup_vs_loop={loop_s / vec_s:.1f}x;"
             f"episodes_per_s={1.0 / vec_s:.1f};"
             f"rows={K * T * 2 * tb};seq={seq}")

        # --- (2) sync vs prefetched trainer input --------------------
        # Two readings per (source, depth):
        #   wall  — end-to-end step wall time (the loop reads the loss
        #           every step, as the production trainer does for
        #           logging; without that read jax's async dispatch
        #           hides sampling in BOTH modes);
        #   stall — time the step loop spends blocked in next(pipe),
        #           i.e. the input path's share of the critical path.
        # The stall is the mechanism metric (prefetch drives it to ~0
        # regardless of machine noise); the wall delta additionally
        # depends on spare host cores, so alternating repetitions are
        # taken and the MEDIAN reported (shared-vCPU clocks drift).
        step = jax.jit(bundle.step_fn, donate_argnums=(0,))
        n_steps = 5 if quick else 8
        n_reps = 3 if quick else 5

        def run(source, depth):
            st = bundle.init_state(seed=0)
            with bundle.make_pipeline(source, depth=depth) as pipe:
                for _ in range(3):               # compile + warm caches
                    st, m = step(st, next(pipe))
                jax.block_until_ready(m["loss"])
                stall = 0.0
                t0 = time.perf_counter()
                for _ in range(n_steps):
                    t1 = time.perf_counter()
                    batch = next(pipe)
                    stall += time.perf_counter() - t1
                    st, m = step(st, batch)
                    float(m["loss"])
                wall = time.perf_counter() - t0
                return wall / n_steps, stall / n_steps

        run(vec, 0)                              # burn-in (first jit run
        # of a fresh process is systematically slower on 2-core CI)

        out = {"sample_us": {"vec": vec_s * 1e6, "loop": loop_s * 1e6},
               "loop": {"sync": [], "prefetch": []},
               "vec": {"sync": [], "prefetch": []}}
        for _ in range(n_reps):
            for label, source in [("loop", loop), ("vec", vec)]:
                out[label]["sync"].append(run(source, 0))
                out[label]["prefetch"].append(run(source, 2))
        med = lambda xs, i: float(np.median([x[i] for x in xs]))
        for label in ["loop", "vec"]:
            raw = out[label]
            out[label] = {
                "sync_us": med(raw["sync"], 0) * 1e6,
                "prefetch_us": med(raw["prefetch"], 0) * 1e6,
                "stall_sync_us": med(raw["sync"], 1) * 1e6,
                "stall_prefetch_us": med(raw["prefetch"], 1) * 1e6,
                "raw": raw,
            }
            o = out[label]
            emit(f"pipeline_overlap_lm_{label}", o["prefetch_us"],
                 f"sync_us={o['sync_us']:.0f};"
                 f"overlap_recovered="
                 f"{(o['sync_us'] - o['prefetch_us']) / o['sync_us']:.3f};"
                 f"input_stall_sync_us={o['stall_sync_us']:.0f};"
                 f"input_stall_prefetch_us={o['stall_prefetch_us']:.0f}")
        emit("pipeline_summary", 0.0,
             "prefetch_faster_than_sync=%s;input_stall_hidden=%.3f;"
             "vectorized_speedup=%.1fx"
             % (out["loop"]["prefetch_us"] < out["loop"]["sync_us"],
                1.0 - out["loop"]["stall_prefetch_us"]
                / max(out["loop"]["stall_sync_us"], 1e-9),
                loop_s / vec_s),
             detail=out)


def bench_generalization_gap(quick: bool):
    """Recurring-vs-unseen generalization (Fallah et al. 2021): meta-train
    Dif-MAML on a sine universe whose top amplitude bands are held out of
    every agent's shard, then report adaptation-loss curves on both splits
    through the same :class:`EvalHarness` the trainer's in-training eval
    hook uses.  ``us_per_call`` = MEDIAN-of-reps wall time of one jitted
    batched adapt-and-measure pass (2-vCPU noise protocol: never trust a
    single timed window)."""
    from repro.eval import EvalHarness

    steps = 150 if quick else 600
    n_tasks = 100 if quick else 200
    source = SineTaskSource(K=6, tasks_per_agent=5, shots=10, n_domains=60,
                            holdout_domains=12, seed=0)
    state, model, _, _ = _sine_train("dif", steps, source=source)
    harness = EvalHarness(model.loss_fn, inner_lr=0.01, inner_steps=5)
    report = harness.evaluate(state, source, n_tasks, seed=1234)

    c = diffusion.centroid(state.params)
    ep = source.eval_sample(n_tasks, seed=1234, split="recurring")
    esup = jax.tree.map(jnp.asarray, ep.support)
    eqry = jax.tree.map(jnp.asarray, ep.query)
    jax.block_until_ready(harness.curves(c, esup, eqry))    # compile
    times = []
    for _ in range(3 if quick else 7):
        t0 = time.perf_counter()
        jax.block_until_ready(harness.curves(c, esup, eqry))
        times.append(time.perf_counter() - t0)
    us = float(np.median(times)) * 1e6

    rec = report.to_record()
    r = rec["splits"]["recurring"]["centroid_curve"]
    u = rec["splits"]["unseen"]["centroid_curve"]
    emit("generalization_gap", us,
         f"recurring_final={r[-1]:.4f};unseen_final={u[-1]:.4f};"
         f"gap={rec['generalization_gap']:.4f};"
         f"disagreement={rec['disagreement']:.2e}",
         detail=rec)


def bench_meta_modes(quick: bool):
    """Exact MAML vs FOMAML vs Reptile on the sine benchmark (paper uses
    exact; the frontier configs use FOMAML — quantify the gap)."""
    steps = 150 if quick else 600
    for mode in ["maml", "fomaml", "reptile"]:
        _, model, curve, us = _sine_train("dif", steps, mode=mode,
                                          lr=1e-3 if mode != "reptile" else 2e-2)
        emit(f"meta_mode_{mode}", us, f"final_test_loss={curve[-1][1]:.4f}")




def bench_mixing(quick: bool):
    """Mixing family: disagreement-decay rate per DiffusionStrategy ×
    TopologySchedule vs the theoretical linear rate of Thm 1.

    For each (topology ∈ {ring, full}) × (schedule ∈ {static,
    link_failure}) × (strategy ∈ {atc, cta, consensus}) the network starts
    from independent inits and the per-step geometric decay of the network
    disagreement over the transient is fitted and compared against λ₂² of
    the (mean) combination matrix — the contraction constant Thm 1
    predicts for one combine.  ``us_per_call`` = MEDIAN wall time of the
    last jitted steps (2-vCPU noise protocol — strategy overhead shows up
    here: cta pays its pre-mix)."""
    from repro.core.meta_trainer import schedule_for

    cfg = get_config("sine_mlp")
    model = SineMLP(cfg)
    K = 6
    steps = 40 if quick else 150
    fit_n = 8                     # early-transient window for the rate fit
    source = SineTaskSource(K=K, tasks_per_agent=3, shots=10, seed=0)
    out = {}
    for topo in ["ring", "full"]:
        for sched in ["static", "link_failure"]:
            for strat in ["atc", "cta", "consensus"]:
                mcfg = MetaConfig(
                    num_agents=K, tasks_per_agent=3, inner_lr=0.01,
                    outer_optimizer="sgd", outer_lr=1e-3,
                    update_config=UpdateConfig(strategy=strat),
                    topology_config=TopologyConfig(
                        graph=topo, schedule=sched, link_failure_p=0.3,
                        seed=0))
                schedule = schedule_for(mcfg)
                lam2 = schedule.mean_mixing_rate
                state = init_state(jax.random.key(1), model.init, mcfg,
                                   identical_init=False)
                step = jax.jit(make_meta_step(model.loss_fn, mcfg))
                ds = [float(diffusion.disagreement(state.params))]
                times = []
                with MetaBatchPipeline(source, depth=2,
                                       prepare=_DEVICE_EP) as pipe:
                    for i in range(steps):
                        sup, qry = next(pipe)
                        t0 = time.perf_counter()
                        state, m = step(state, sup, qry)
                        if i >= steps - 5:
                            jax.block_until_ready(m["loss"])
                            times.append(time.perf_counter() - t0)
                        ds.append(float(m["disagreement"]))
                us = float(np.median(times)) * 1e6
                rate = float((ds[fit_n] / ds[0]) ** (1.0 / fit_n))
                plateau = float(np.mean(ds[-10:]))
                name = f"mixing_{topo}_{strat}_{sched}"
                out[name] = {"lambda2": lam2, "theory_rate": lam2 ** 2,
                             "decay_rate": rate, "plateau": plateau,
                             "curve": ds}
                emit(name, us,
                     f"decay_rate={rate:.3f};theory_rate={lam2 ** 2:.3f};"
                     f"plateau={plateau:.3e}")
    ring = {s: out[f"mixing_ring_{s}_static"]["decay_rate"]
            for s in ["atc", "cta", "consensus"]}
    lf_slows = (out["mixing_ring_atc_link_failure"]["decay_rate"]
                >= out["mixing_ring_atc_static"]["decay_rate"] - 0.05)
    emit("mixing_summary", 0.0,
         "ring_static_rates=atc:%.3f,cta:%.3f,consensus:%.3f;"
         "link_failure_slows_or_matches=%s" %
         (ring["atc"], ring["cta"], ring["consensus"], lf_slows),
         detail=out)


def bench_topology_ablation(quick: bool):
    """Beyond-paper: Thm 1 makes λ₂ (the mixing rate) the contraction
    constant of the network — sweep topologies at K=16 and relate λ₂ to
    post-training performance and disagreement."""
    from repro.core import init_state, make_meta_step
    steps = 120 if quick else 500
    cfg = get_config("sine_mlp")
    model = SineMLP(cfg)
    K = 16
    source = SineTaskSource(K=K, tasks_per_agent=3, shots=10, n_domains=64)
    evaln = make_eval_fn(model.loss_fn, inner_lr=0.01, inner_steps=1)
    ep = source.eval_sample(200, seed=999)
    esup = jax.tree.map(jnp.asarray, ep.support)
    eqry = jax.tree.map(jnp.asarray, ep.query)
    out = {}
    for topo in ["full", "torus", "erdos", "ring", "star"]:
        A = topology.combination_matrix(K, topo)
        lam2 = topology.mixing_rate(A)
        mcfg = MetaConfig(num_agents=K, tasks_per_agent=3, inner_lr=0.01,
                          mode="maml", combine="dense", topology=topo,
                          outer_optimizer="adam", outer_lr=1e-3)
        state = init_state(jax.random.key(0), model.init, mcfg,
                           identical_init=False)
        step = jax.jit(make_meta_step(model.loss_fn, mcfg))
        with MetaBatchPipeline(source, depth=2, prepare=_DEVICE_EP) as pipe:
            for i in range(steps):
                sup, qry = next(pipe)
                state, m = step(state, sup, qry)
        c = diffusion.centroid(state.params)
        loss = float(np.mean(np.asarray(evaln(c, esup, eqry))[:, 1]))
        dis = float(m["disagreement"])
        deg = int((A[:, 0] > 0).sum() - 1) if topo != "erdos" else             int(np.mean((A > 0).sum(0) - 1))
        out[topo] = {"lambda2": lam2, "loss": loss, "disagreement": dis,
                     "avg_degree": deg}
        emit(f"topology_{topo}", 0.0,
             f"lambda2={lam2:.3f};final_loss={loss:.4f};"
             f"disagreement={dis:.2e};avg_degree={deg}")
    # Thm 1 prediction: plateau disagreement grows with λ₂²/(1−λ₂)²
    ordered = sorted(out, key=lambda t: out[t]["lambda2"])
    mono = all(out[a]["disagreement"] <= out[b]["disagreement"] * 50
               for a, b in zip(ordered, ordered[1:]))
    emit("topology_summary", 0.0,
         f"lambda2_order={'<'.join(ordered)};disagreement_tracks_lambda2={mono}",
         detail=out)


def bench_outer_update(quick: bool):
    """Fused combine-then-update vs the unfused clip→adam→ATC chain:
    HBM bytes/step and wall time at K=8, per param dtype.

    Unfused bytes come from :class:`HloCost` over the compiled step (the
    same trip-count-aware parser the roofline uses); fused bytes are the
    kernel's analytic one-pass contract
    (:func:`repro.launch.hlo_cost.fused_outer_update_bytes`) — interpret-
    mode pallas HLO is emulation scaffolding, not a traffic model, and on
    CPU CI its wall time is emulation-bound too, so the headline derived
    quantity is the bytes ratio with parity pinned by ``max_err``.  The
    acceptance row: bf16 params/grads with fp32 moments — the production
    wire format — must come in at ≤ 0.5× the unfused traffic (f32 lands at
    ≈0.53×: its unfused chain moves relatively less, every buffer already
    being 4-byte)."""
    from repro.core import update
    from repro.core.fused import make_fused_outer
    from repro.launch.hlo_cost import HloCost, fused_outer_update_bytes
    from repro.optim import adam, optimizers as om

    K, S = 8, 1
    M = (1 << 12) if quick else (1 << 15)
    A = jnp.asarray(topology.build_topology("ring", K).matrix)
    lr, b1, b2, eps, clip = 1e-3, 0.9, 0.999, 1e-8, 1.0
    out = {}
    for dtype in (jnp.float32, jnp.bfloat16):
        name = jnp.dtype(dtype).name
        rng = np.random.default_rng(0)
        w = jnp.asarray(rng.normal(size=(K, M)), dtype)
        g = jnp.asarray(rng.normal(size=(K, M)), dtype)
        mu = jnp.zeros((K, M), jnp.float32)
        nu = jnp.zeros((K, M), jnp.float32)

        @jax.jit
        def unfused(w, g, mu, nu, t):
            scale = jax.vmap(lambda gk: om.global_norm_scale(gk, clip))(g)
            g = (g * scale[:, None]).astype(g.dtype)
            g32 = g.astype(jnp.float32)
            mu = om.adam_mu(mu, g32, b1)
            nu = om.adam_nu(nu, g32, b2)
            u = om.adam_direction(mu, nu, 1 - b1 ** t, 1 - b2 ** t,
                                  lr=lr, eps=eps)
            phi = w.astype(jnp.float32) + u
            return (jnp.einsum("lk,lm->km", A, phi).astype(w.dtype),
                    mu, nu)

        t1 = jnp.ones((), jnp.float32)
        hlo = unfused.lower(w, g, mu, nu, t1).compile().as_text()
        unfused_bytes = int(HloCost(hlo).bytes_accessed())
        unfused_us = _timed(unfused, w, g, mu, nu, t1)

        outer = make_fused_outer(adam(lr, b1=b1, b2=b2, eps=eps), "atc",
                                 update.CommSchedule(1), np.asarray(A),
                                 grad_clip=clip, num_agents=K)
        st = om.AdamState(jnp.zeros((), jnp.int32), mu, nu)
        step0 = jnp.zeros((), jnp.int32)
        fused = jax.jit(lambda w, g, st, s: outer(w, g, st, s))
        fused_bytes = fused_outer_update_bytes(
            K * M, jnp.dtype(dtype).itemsize, optimizer="adam",
            grad_clip=True)
        fused_us = _timed(fused, w, g, st, step0)

        w_u, mu_u, nu_u = unfused(w, g, mu, nu, t1)
        w_f, st_f = fused(w, g, st, step0)
        max_err = float(jnp.max(jnp.abs(w_f.astype(jnp.float32)
                                        - w_u.astype(jnp.float32))))
        ratio = fused_bytes / unfused_bytes
        out[name] = {"unfused_us": unfused_us, "fused_us": fused_us,
                     "unfused_bytes": unfused_bytes,
                     "fused_bytes": fused_bytes, "ratio": ratio,
                     "max_err": max_err, "K": K, "M": M}
        emit(f"outer_update_{name}", fused_us,
             f"unfused_us={unfused_us:.1f};"
             f"bytes_fused={fused_bytes};bytes_unfused={unfused_bytes};"
             f"bytes_ratio={ratio:.3f};max_err={max_err:.2e};K={K}")
    bf = out["bfloat16"]
    emit("outer_update_summary", 0.0,
         f"bf16_bytes_ratio={bf['ratio']:.3f};"
         f"bf16_within_half={bf['ratio'] <= 0.5};"
         f"f32_bytes_ratio={out['float32']['ratio']:.3f}",
         detail=out)

    # bf16 vs f32 outer storage, end-to-end: 100 sine meta-steps (paper
    # §4.1 harness, same seed and episode stream), meta-loss measured on
    # the f32-cast centroid.  The acceptance row: |drift| ≤ 1e-2 — the
    # parity evidence that bf16 params/grads (with fp32 Adam moments) are
    # safe as the production outer format.
    steps = 100
    curves = {}
    for name, pdt in [("float32", None), ("bfloat16", jnp.bfloat16)]:
        _, _, curve, us = _sine_train("dif", steps, param_dtype=pdt)
        curves[name] = {"curve": curve, "us": us}
    drift = abs(curves["bfloat16"]["curve"][-1][1]
                - curves["float32"]["curve"][-1][1])
    emit("outer_update_bf16_drift", curves["bfloat16"]["us"],
         f"meta_loss_bf16={curves['bfloat16']['curve'][-1][1]:.4f};"
         f"meta_loss_f32={curves['float32']['curve'][-1][1]:.4f};"
         f"drift={drift:.4f};within_tol={drift <= 1e-2};"
         f"steps={steps};K=6", detail=curves)


BENCHES = {
    "fig2b": bench_fig2b_sine_regression,
    "fig2c": bench_fig2c_adaptation_steps,
    "fig3": bench_fig3_fewshot_classification,
    "thm1": bench_thm1_agreement,
    "thm2": bench_thm2_stationarity,
    "combine": bench_combine_strategies,
    "combine_dynamic": bench_combine_dynamic,
    "outer_update": bench_outer_update,
    "superstep": bench_superstep,
    "serve": bench_serve,
    "kernels": bench_kernels,
    "generalization": bench_generalization_gap,
    "modes": bench_meta_modes,
    "pipeline": bench_pipeline,
    "mixing": bench_mixing,
    "topology": bench_topology_ablation,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    print("name,us_per_call,derived")
    for name, fn in BENCHES.items():
        if args.only and name != args.only:
            continue
        fn(args.quick)
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, "summary.csv"), "w") as f:
        f.write("name,us_per_call,derived\n")
        for n, u, d in ROWS:
            f.write(f"{n},{u:.1f},{d}\n")


if __name__ == "__main__":
    main()
