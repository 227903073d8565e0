"""Dif-MAML training driver.

Runs the decentralized meta-training loop for any registered architecture
on the devices that are present.  ``--agents K`` always means K agents: on
fewer devices the K agent copies stack on the leading ``agent`` dimension
of each device (dense or host-roll combine); ``--mesh-agents K`` puts one
agent on each slice of an ``(agent[, data], model)`` mesh (ppermute
combine).  ``--reduced`` cuts widths only; ``--seq``/``--global-batch`` (or
``--shape``) set the geometry either way.  The pod-scale production mesh is
the dry run's (``launch/dryrun.py``).

Every run emits a JSONL run log (``--run-log``, default
``results/train_<arch>_seed<seed>.jsonl``): one ``{"kind": "train", ...}``
record per logged step and — with ``--eval-every`` — one
``{"kind": "eval", ...}`` record per :class:`~repro.eval.EvalHarness` pass,
carrying the recurring-vs-unseen adaptation-loss curves, the generalization
gap, and disagreement-at-eval.  Benchmarks and plots consume the log
instead of scraping stdout.  Train records carry ``step_time_s`` (per-step
train-compute wall of the dispatch that produced them, excluding eval/
checkpoint/log time) next to the cumulative wall-clock ``time_s``.

The hot loop is a *superstep* driver: ``--steps-per-dispatch C`` runs C
meta-steps inside one jitted, buffer-donated ``lax.scan`` call
(:func:`repro.launch.steps.make_superstep`) with the pipeline stacking C
meta-batches per dispatch and metrics accumulated on device — one Python
dispatch and one host fetch per C steps, so fast hardware is no longer
dispatch-bound.  Log/eval/checkpoint cadences align to dispatch
boundaries; C=1 reproduces the legacy per-step loop step-for-step.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --steps 20 \\
      --reduced --seq 64 --global-batch 16 --agents 4 --seed 1 \\
      --eval-every 10 --eval-tasks 8

``main(argv)`` runs in the caller's process and returns a summary of the
run, so a script that already holds the accelerator can drive it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax

from repro.checkpoint import save_checkpoint, restore_checkpoint, latest_step
from repro.configs import INPUT_SHAPES, get_config
from repro.configs.base import InputShape
from repro.core import diffusion, topology, update
from repro.data.lm_tasks import LMTaskSource
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch import steps as S


def make_train_source(cfg, shape, K: int, T: int, tb: int, seed: int = 0,
                      holdout_domains: int | None = None) -> LMTaskSource:
    """The production trainer's task stream: per-agent heterogeneous LM
    domain shards (the paper's π_k).  Replaces the old ``make_batch``,
    which sampled ONE domain for the entire global batch — every agent was
    secretly training on the same distribution.

    On top of the trained universe, ``holdout_domains`` extra domains
    (default ``max(2, K // 2)``) are appended and held out of every agent's
    shard — the unseen split the in-training EvalHarness measures against.
    """
    n_train = max(8, 4 * K)
    holdout = max(2, K // 2) if holdout_domains is None else holdout_domains
    return LMTaskSource(
        vocab_size=cfg.padded_vocab, seq_len=shape.seq_len,
        K=K, tasks_per_agent=T, task_batch=tb,
        n_domains=n_train + holdout, holdout_domains=holdout, seed=seed)


class RunLog:
    """JSONL writer, one flushed record per line.  ``resume=True`` appends
    (a checkpoint-resumed run continues its existing log); otherwise the
    file restarts with the run."""

    def __init__(self, path: str, resume: bool = False):
        self.path = path
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self._f = open(path, "a" if resume else "w")

    def write(self, **record) -> None:
        self._f.write(json.dumps(record) + "\n")
        self._f.flush()

    def close(self) -> None:
        self._f.close()


def main(argv: list[str] | None = None) -> dict:
    """Run one training job; ``argv`` defaults to ``sys.argv[1:]``.

    Returns ``{"K", "loss", "disagreement", "step_s", "compile_s",
    "tpu_custom_calls", "run_log"}``: per-step loss and disagreement,
    per-step seconds of each dispatch (compilation excluded), the seconds
    spent compiling, and how many Pallas TPU kernels the compiled step
    holds."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default=None,
                    help="registered input shape (e.g. train_4k); "
                         "overrides --seq/--global-batch")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0,
                    help="run seed: threads through launch-model init, the "
                         "task source, and checkpoint naming (ckpt-dir/"
                         "seed<N>/) so independent runs never collide")
    ap.add_argument("--reduced", action="store_true",
                    help="cut widths and depth to the smoke-test variant "
                         "(ArchConfig.reduced); the geometry still comes "
                         "from --seq/--global-batch or --shape")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--agents", type=int, default=None,
                    help="K agents (default 4, or --mesh-agents), whatever "
                         "the device count: agents that outnumber the "
                         "devices stack on each device's leading agent "
                         "dimension")
    ap.add_argument("--devices", type=int, default=None,
                    help="run on the first N devices (default: all)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--log-every", type=int, default=1)
    ap.add_argument("--eval-every", type=int, default=0,
                    help="run the recurring-vs-unseen EvalHarness every n "
                         "steps (0 = off); results go to the run log")
    ap.add_argument("--eval-tasks", type=int, default=8,
                    help="eval tasks drawn per split per harness pass")
    ap.add_argument("--eval-inner-steps", type=int, default=3,
                    help="adaptation steps measured by the eval harness "
                         "(curves have this + 1 entries; index 0 = 0-shot)")
    ap.add_argument("--run-log", default=None,
                    help="JSONL run log path (default results/"
                         "train_<arch>_seed<seed>.jsonl)")
    ap.add_argument("--mesh-agents", type=int, default=None,
                    help="K agents, one per slice of an (agent, model) mesh "
                         "over the devices; the leftover device factor is "
                         "tensor parallelism (the device count must be a "
                         "multiple of K)")
    ap.add_argument("--prefetch", type=int, default=2,
                    help="meta-batch pipeline depth (0 = sample "
                         "synchronously on the step loop)")
    ap.add_argument("--steps-per-dispatch", type=int, default=1,
                    help="meta-steps per jitted dispatch (lax.scan "
                         "superstep): one Python dispatch + one host "
                         "metric fetch per C steps; log/eval/ckpt "
                         "cadences align to dispatch boundaries. Pick "
                         "--steps divisible by C to avoid one extra "
                         "compile for the final partial dispatch")
    ap.add_argument("--combine", default=None,
                    help="combine backend override: 'auto' or any "
                         "diffusion.combine_backends() name")
    ap.add_argument("--strategy", default=None,
                    choices=sorted(update.update_strategies()),
                    help="outer-update composition (default atc, paper "
                         "Algorithm 1): how the combine composes with the "
                         "local meta-update")
    ap.add_argument("--topology-schedule", default="static",
                    choices=sorted(topology.SCHEDULES),
                    help="per-step communication-graph schedule over the "
                         "arch's topology")
    ap.add_argument("--link-failure-p", type=float, default=0.2,
                    help="i.i.d. per-edge drop probability for "
                         "--topology-schedule link_failure")
    ap.add_argument("--fused-outer", action="store_true",
                    help="run the one-pass combine-then-update outer step "
                         "(shorthand for --combine fused): clip scale, "
                         "optimizer moments and launch-model mix in a "
                         "single kernel sweep over the parameter bytes")
    ap.add_argument("--outer-dtype", default=None,
                    choices=sorted(S.DTYPES),
                    help="params/grads storage dtype for the outer loop "
                         "(Adam moments stay fp32); defaults to the arch's "
                         "dtype")
    ap.add_argument("--combine-dtype", default=None,
                    choices=sorted(diffusion.WIRE_DTYPES),
                    help="combine wire format for the ppermute backends; "
                         "defaults to bfloat16 when the outer dtype is "
                         "bfloat16 (f32 escape hatch: --combine-dtype "
                         "float32)")
    args = ap.parse_args(argv)
    if args.fused_outer:
        if args.combine not in (None, "fused"):
            ap.error(f"--fused-outer conflicts with --combine "
                     f"{args.combine}: the fused outer step IS the combine "
                     f"backend")
        args.combine = "fused"
    if args.mesh_agents and args.agents not in (None, args.mesh_agents):
        ap.error(f"--agents {args.agents} conflicts with --mesh-agents "
                 f"{args.mesh_agents}")
    K = args.mesh_agents or args.agents or 4
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.outer_dtype or args.combine_dtype:
        cfg = dataclasses.replace(
            cfg, outer_dtype=args.outer_dtype or cfg.outer_dtype,
            combine_dtype=args.combine_dtype or cfg.combine_dtype)
    if args.reduced:
        cfg = cfg.reduced()
    shape = (INPUT_SHAPES[args.shape] if args.shape else
             InputShape("custom", args.seq, args.global_batch, "train"))
    devices = jax.devices()
    if args.devices is not None:
        if not 1 <= args.devices <= len(devices):
            ap.error(f"--devices {args.devices}: {len(devices)} present")
        devices = devices[:args.devices]
    if args.mesh_agents:
        mesh = make_host_mesh(model=max(1, len(devices) // K), agents=K,
                              devices=devices)
    else:
        mesh = make_host_mesh(data=len(devices), devices=devices)

    ckpt_dir = (os.path.join(args.ckpt_dir, f"seed{args.seed}")
                if args.ckpt_dir else None)
    resuming = ckpt_dir is not None and latest_step(ckpt_dir) is not None
    log_path = args.run_log or os.path.join(
        "results", f"train_{cfg.name}_seed{args.seed}.jsonl")
    run_log = RunLog(log_path, resume=resuming)

    with mesh:
        bundle = S.build_train(cfg, mesh, shape,
                               combine_override=args.combine,
                               strategy=args.strategy,
                               schedule=args.topology_schedule,
                               link_failure_p=args.link_failure_p,
                               schedule_seed=args.seed, agents=K)
        ucfg = bundle.mcfg.update_config
        sched = bundle.schedule
        print(f"[train] {cfg.name}: K={bundle.K} agents, "
              f"T={bundle.T} tasks × {bundle.tb} examples, "
              f"mode={ucfg.inner}, seed={args.seed}, mesh "
              f"{dict(zip(mesh.axis_names, mesh.devices.shape))} on "
              f"{devices[0].platform}")
        if sched is not None:
            print(f"[train] outer update: strategy={ucfg.strategy} over "
                  f"'{sched.topology.name}' ({sched.kind} schedule, "
                  f"period {sched.period}, "
                  f"mean λ₂={sched.mean_mixing_rate:.3f}), "
                  f"combine_every={ucfg.combine_every}")
        # The state is created (or restored) straight into its steady-state
        # shardings, and the step output is pinned to the same layout, so
        # one compiled program serves every dispatch.  Built under jit, no
        # device ever holds more than its own shards of the K-agent state.
        if resuming:
            state = restore_checkpoint(ckpt_dir, bundle.state_specs,
                                       shardings=bundle.state_shardings)
            print(f"[train] restored step {int(state.step)}")
        else:
            state = jax.jit(bundle.init_state,
                            out_shardings=bundle.state_shardings)(args.seed)
        C = max(1, args.steps_per_dispatch)
        superstep_fn = jax.jit(S.make_superstep(bundle.step_fn),
                               donate_argnums=(0,),
                               out_shardings=(bundle.state_shardings, None))
        executables = {}       # dispatch length -> compiled superstep
        compile_s = 0.0
        source = make_train_source(cfg, shape, bundle.K, bundle.T, bundle.tb,
                                   seed=args.seed)
        print(f"[train] task source: {source.n_train_domains} domains "
              f"(+{source.holdout_domains} held out), "
              f"{source.heterogeneity} over K={bundle.K} agents, "
              f"prefetch depth {args.prefetch}")
        harness = prepare = None
        if args.eval_every:
            harness = bundle.make_eval_harness(args.eval_inner_steps)
            prepare = bundle.eval_prepare()
            print(f"[train] eval hook: recurring-vs-unseen, "
                  f"{args.eval_tasks} tasks × {args.eval_inner_steps} "
                  f"adaptation steps every {args.eval_every} steps "
                  f"-> {log_path}")
        run_log.write(kind="config", arch=cfg.name, seed=args.seed,
                      mesh_axes={n: int(s) for n, s in
                                 zip(mesh.axis_names, mesh.devices.shape)},
                      K=bundle.K, T=bundle.T, tb=bundle.tb,
                      mode=ucfg.inner, strategy=ucfg.strategy,
                      combine_backend=ucfg.backend,
                      fused_outer=ucfg.backend == "fused",
                      outer_dtype=bundle.outer_dtype,
                      combine_dtype=bundle.combine_dtype,
                      topology_schedule=args.topology_schedule,
                      link_failure_p=(args.link_failure_p
                                      if args.topology_schedule
                                      == "link_failure" else None),
                      steps=args.steps, steps_per_dispatch=C,
                      n_domains=source.n_domains,
                      holdout_domains=source.holdout_domains)
        t0 = time.time()
        train_wall = 0.0       # train-compute only: excludes eval/ckpt/log
        done = 0
        losses, disagreements, step_s = [], [], []
        with bundle.make_pipeline(source, depth=args.prefetch,
                                  start_step=int(state.step),
                                  stack=C) as pipe:
            while done < args.steps:
                n = min(C, args.steps - done)
                batch = next(pipe)
                if n < C:      # final partial dispatch (one extra compile)
                    batch = {k: v[:n] for k, v in batch.items()}
                if n not in executables:
                    tc = time.perf_counter()
                    executables[n] = superstep_fn.lower(state, batch).compile()
                    compile_s += time.perf_counter() - tc
                    print(f"[train] compiled the {n}-step dispatch in "
                          f"{time.perf_counter() - tc:.1f}s")
                td = time.perf_counter()
                state, metrics = executables[n](state, batch)
                # ONE host sync per dispatch: the (n,)-shaped step-resolved
                # metric arrays come back in a single fetch
                m = jax.device_get(metrics)
                dispatch_s = time.perf_counter() - td
                train_wall += dispatch_s
                losses += [float(x) for x in m["loss"]]
                disagreements += [float(x) for x in m["disagreement"]]
                step_s += [dispatch_s / n] * n
                base, done = done, done + n
                last_step = int(state.step)       # one fetch per dispatch
                for j in range(n):
                    if (base + j) % args.log_every == 0:
                        step_no = last_step - n + j + 1
                        loss = float(m["loss"][j])
                        dis = float(m["disagreement"][j])
                        print(f"step {step_no:5d} "
                              f"loss {loss:.4f} "
                              f"disagreement {dis:.3e} "
                              f"({time.time() - t0:.1f}s)")
                        run_log.write(kind="train", step=step_no,
                                      loss=loss, disagreement=dis,
                                      time_s=round(time.time() - t0, 3),
                                      step_time_s=round(dispatch_s / n, 6),
                                      train_time_s=round(train_wall, 3))
                if harness is not None and (
                        base // args.eval_every < done // args.eval_every
                        or done >= args.steps):
                    report = harness.evaluate(state, source, args.eval_tasks,
                                              prepare=prepare)
                    rec = report.to_record()
                    run_log.write(kind="eval", **rec)
                    rc = rec["splits"]["recurring"]["centroid_curve"]
                    uc = rec["splits"]["unseen"]["centroid_curve"]
                    print(f"[eval] step {int(state.step)} "
                          f"recurring {rc[0]:.3f}->{rc[-1]:.3f} "
                          f"unseen {uc[0]:.3f}->{uc[-1]:.3f} "
                          f"gap {rec['generalization_gap']:.4f}")
                if ckpt_dir and (base // args.ckpt_every
                                 < done // args.ckpt_every):
                    save_checkpoint(ckpt_dir, int(state.step), state)
        if ckpt_dir:
            save_checkpoint(ckpt_dir, int(state.step), state)
        # Post-run compiled-program lint (repro.analysis): retrace-guard
        # checks the traced step for weak-type python scalars and host
        # callbacks, and that the trainer compiled exactly one program per
        # batch shape — 1, plus 1 more only when a final partial dispatch
        # (steps % C != 0) forced a second shape.  The record lands in the
        # run log for check_run_log.py --expect-analysis.
        from repro.analysis.rules import run_rules
        from repro.analysis.run import context_for_bundle
        dispatches = -(-args.steps // C)
        expected_compiles = 1 + (1 if args.steps % C else 0)
        compiles = len(executables)
        kernels = sum(ex.as_text().count('custom_call_target="tpu_custom_call"')
                      for ex in executables.values())
        jaxpr = jax.make_jaxpr(bundle.step_fn)(
            bundle.state_specs, S.input_specs(cfg, shape))
        ctx = context_for_bundle(
            bundle, jaxpr=jaxpr,
            compile_counts={"superstep": {"compiles": compiles,
                                          "expected": expected_compiles,
                                          "dispatches": dispatches}})
        report = run_rules(ctx, only=["retrace-guard"])
        run_log.write(kind="analysis", **report.to_json(),
                      jit_compiles=compiles,
                      expected_compiles=expected_compiles,
                      dispatches=dispatches, compile_s=round(compile_s, 3),
                      tpu_custom_calls=kernels)
        if not report.ok:
            for f in report.findings:
                print(f"[analysis] FINDING[{f.rule}] {f.message}")
    run_log.close()
    print(f"[train] done (run log: {log_path})")
    return {"K": bundle.K, "loss": losses, "disagreement": disagreements,
            "step_s": step_s, "compile_s": compile_s,
            "tpu_custom_calls": kernels, "run_log": log_path}


if __name__ == "__main__":
    main()
