import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run (deliverable e).

Lowers + compiles every (architecture × input-shape) combination against the
production meshes — 16×16 single-pod, 2×16×16 two-pod, and the agent-axis
meshes of ``make_production_mesh(agents=K)`` — and records memory analysis,
HLO FLOPs/bytes, and the per-device collective schedule (parsed from the
post-SPMD HLO) for the roofline analysis.

The two lines above MUST stay the first statements in this module: jax locks
the device count at first initialization, and only the dry-run wants 512
placeholder host devices.

With ``--agents K`` the train step is validated on the 2D/3D agent mesh:
the per-device parameter-shard size and the schedule degree give the exact
wire budget the sparse combine must hit — deg·shard collective-permute
bytes, NOT K·shard — and ``--assert-budgets`` enforces it plus the pinned
per-config total-collective ceilings in :data:`AGENT_MESH_BUDGETS` (the
production-scale sibling of tests/test_hlo_cost.py's deg-not-K pin).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all --mesh both
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k \\
      --agents 16 --combine mesh_sparse_dynamic --assert-budgets
"""
import argparse
import json
import re
import time
import traceback

import jax
import numpy as np

from repro.configs import INPUT_SHAPES, get_config, list_archs
from repro.launch.mesh import make_production_mesh
from repro.launch import steps as S

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")
_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}
_SHAPE_RE = re.compile(r"\b(f64|f32|bf16|f16|f8e4m3fn|f8e5m2|s64|u64|s32|u32|s16|u16|s8|u8|pred)\[([0-9,]*)\]")


def _shape_bytes(m: re.Match) -> int:
    dt, dims = m.group(1), m.group(2)
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _group_size(ls: str, n_dev: int) -> int:
    m = _GROUPS_IOTA_RE.search(ls)
    if m:  # [n_groups, group_size]<=[...]
        return max(1, int(m.group(2)))
    m = _GROUPS_LIST_RE.search(ls)
    if m:
        return max(1, len(m.group(1).split(",")))
    return n_dev


def parse_collectives(hlo: str, n_dev: int) -> dict:
    """Per-device wire bytes for every collective in post-SPMD HLO.

    Result shapes in the HLO are per-device shards.  Wire-byte model per op
    (ring algorithms, group size K):
      all-gather          result · (K−1)/K
      reduce-scatter      result · (K−1)          (operand = result·K)
      all-reduce          result · 2(K−1)/K       (RS + AG)
      all-to-all          result · (K−1)/K
      collective-permute  result                  (point-to-point)
    """
    per_op: dict[str, dict] = {}
    biggest: list[tuple[int, str]] = []
    for line in hlo.splitlines():
        ls = line.strip()
        m_op = re.search(r"= [^ ]+ ([a-z\-]+)(?:-start)?\(", ls)
        if not m_op:
            continue
        op = m_op.group(1).removesuffix("-start")
        if op not in COLLECTIVE_OPS or "-done(" in ls:
            continue
        head = ls.split("=", 1)[1]
        head = head[: head.index("(")]
        result_bytes = sum(_shape_bytes(m) for m in _SHAPE_RE.finditer(head))
        K = _group_size(ls, n_dev)
        if op == "all-gather":
            wire = result_bytes * (K - 1) // K
        elif op == "reduce-scatter":
            wire = result_bytes * (K - 1)
        elif op == "all-reduce":
            wire = result_bytes * 2 * (K - 1) // K
        elif op == "all-to-all":
            wire = result_bytes * (K - 1) // K
        else:  # collective-permute
            wire = result_bytes
        d = per_op.setdefault(op, {"count": 0, "bytes": 0, "wire_bytes": 0})
        d["count"] += 1
        d["bytes"] += result_bytes
        d["wire_bytes"] += wire
        biggest.append((wire, ls[:200]))
    biggest.sort(key=lambda t: -t[0])
    return {"per_op": per_op,
            "total_bytes": sum(d["wire_bytes"] for d in per_op.values()),
            "total_count": sum(d["count"] for d in per_op.values()),
            "top": [{"bytes": b, "op": s} for b, s in biggest[:8]]}


def _mem_dict(mem) -> dict:
    fields = ("generated_code_size_in_bytes", "argument_size_in_bytes",
              "output_size_in_bytes", "alias_size_in_bytes",
              "temp_size_in_bytes")
    return {f: getattr(mem, f) for f in fields}


# The pinned agent-mesh budgets moved to repro.analysis.run (the lint
# driver owns every compiled-program invariant); re-exported here for the
# existing consumers of this module's surface.
from repro.analysis.run import AGENT_MESH_BUDGETS  # noqa: E402,F401


def _mesh_tag(mesh, multi_pod: bool, agents: int | None) -> str:
    if agents is None:
        return "2x16x16" if multi_pod else "16x16"
    return "x".join(f"{name[0]}{size}" for name, size in
                    zip(mesh.axis_names, mesh.devices.shape))


def run_one(arch: str, shape_name: str, multi_pod: bool,
            combine: str | None = None, save_hlo: str | None = None,
            overrides: dict | None = None, agents: int | None = None,
            assert_budgets: bool = False) -> dict:
    import dataclasses
    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod, agents=agents)
    t0 = time.time()
    with mesh:
        if shape.kind == "train":
            bundle = S.build_train(cfg, mesh, shape_name,
                                   combine_override=combine)
            # out_shardings pins the NEW state to the same layout as the
            # input state — without it XLA may emit a step whose output
            # sharding differs (hiding the combine's data movement from
            # this step and pushing it into the next one)
            jitted = jax.jit(bundle.step_fn,
                             in_shardings=(bundle.state_shardings,
                                           bundle.batch_shardings),
                             out_shardings=(bundle.state_shardings, None),
                             donate_argnums=(0,))
            lowered = jitted.lower(bundle.state_specs,
                                   S.input_specs(cfg, shape_name))
            extra = {"num_agents": bundle.K, "tasks_per_agent": bundle.T,
                     "task_batch": bundle.tb}
        elif shape.kind == "prefill":
            bundle = S.build_prefill(cfg, mesh, shape_name)
            jitted = jax.jit(bundle.step_fn,
                             in_shardings=(bundle.params_shardings,
                                           bundle.batch_shardings))
            lowered = jitted.lower(bundle.params_specs,
                                   S.input_specs(cfg, shape_name))
            extra = {}
        else:  # decode
            bundle = S.build_serve(cfg, mesh, shape_name)
            ins = S.input_specs(cfg, shape_name)
            jitted = jax.jit(
                bundle.step_fn,
                in_shardings=(bundle.params_shardings,
                              bundle.input_shardings["cache"],
                              bundle.input_shardings["token"],
                              bundle.input_shardings["pos"]),
                donate_argnums=(1,))
            lowered = jitted.lower(bundle.params_specs, ins["cache"],
                                   ins["token"], ins["pos"])
            extra = {}
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    from repro.compat import cost_analysis as _cost_analysis
    cost = _cost_analysis(compiled)
    hlo = compiled.as_text()
    n_dev = int(np.prod(mesh.devices.shape))
    # cost_analysis() counts while-loop bodies once (ignores trip counts) —
    # fatal for layer-scanned models, including their in-scan collectives.
    # hlo_cost re-derives flops/bytes/collectives with known_trip_count
    # applied (see launch/hlo_cost.py).
    from repro.launch.hlo_cost import corrected_costs
    corr = corrected_costs(hlo, n_dev=n_dev)
    coll = corr["collectives"]
    coll["top_level_only"] = parse_collectives(hlo, n_dev)["per_op"]
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(hlo)
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": _mesh_tag(mesh, multi_pod, agents),
        "devices": n_dev,
        "kind": shape.kind,
        "combine": combine or cfg.combine,
        "flops_per_device": corr["flops"],
        "bytes_per_device": corr["bytes"],
        "flops_raw_cost_analysis": cost.get("flops", 0.0),
        "bytes_raw_cost_analysis": cost.get("bytes accessed", 0.0),
        "collectives": coll,
        "memory": _mem_dict(mem),
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        **extra,
    }
    if agents is not None and shape.kind == "train":
        # Delegate every compiled-program invariant to the lint registry
        # (repro.analysis) — the deg·shard permute window, the bf16→u16
        # wire check, and the pinned per-config collective ceiling all
        # live there now; this block only reports and (under
        # --assert-budgets) raises on findings.
        from repro.analysis.rules import run_rules
        from repro.analysis.run import context_for_bundle
        ceiling = AGENT_MESH_BUDGETS.get((arch, shape_name, agents))
        ctx = context_for_bundle(bundle, hlo, ceiling=ceiling)
        report = run_rules(ctx,
                           only=["collective-budget", "wire-dtype-leak"])
        budget = report.records["collective-budget"]
        rec["combine_budget"] = budget
        rec["lint"] = report.to_json()
        wire, deg = bundle.combine_dtype, budget["degree"]
        print(f"  combine_budget: deg={deg} × shard "
              f"{budget['param_shard_bytes']:.3e} B "
              f"({wire} wire) → permute {budget['permute_bytes']:.3e} B "
              f"({'ok' if report.ok else 'VIOLATION'}), "
              f"total coll {budget['total_collective_bytes']:.3e} B")
        if assert_budgets and not report.ok:
            raise AssertionError(
                f"{arch} × {shape_name} × {rec['mesh']}: " +
                "; ".join(f.message for f in report.findings))
    print(f"[dryrun] {arch} × {shape_name} × {rec['mesh']}"
          f" ok: {rec['flops_per_device']:.3e} flops/dev,"
          f" {rec['bytes_per_device']:.3e} B/dev,"
          f" coll {coll['total_bytes']:.3e} B/dev ({coll['total_count']} ops),"
          f" temp {mem.temp_size_in_bytes/2**30:.2f} GiB/dev,"
          f" args {mem.argument_size_in_bytes/2**30:.2f} GiB/dev,"
          f" compile {rec['compile_s']:.0f}s")
    print("  memory_analysis:", _mem_dict(mem))
    print("  cost_analysis: flops=%.4g bytes=%.4g" %
          (rec["flops_per_device"], rec["bytes_per_device"]))
    return rec


def shapes_for(arch: str) -> list[str]:
    """long_500k only for sub-quadratic archs (DESIGN.md §long_500k skips);
    decode skipped for encoder-only archs (none assigned)."""
    cfg = get_config(arch)
    sub_quadratic = (cfg.arch_type in ("ssm", "hybrid")
                     or cfg.sliding_window is not None)
    out = ["train_4k", "prefill_32k", "decode_32k"]
    if sub_quadratic:
        out.append("long_500k")
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--agents", type=int, default=None,
                    help="build the agent-axis production mesh "
                         "make_production_mesh(agents=K) — (agent, data, "
                         "model), collapsing to 2D (agent, model) — instead "
                         "of the legacy placement-driven meshes")
    ap.add_argument("--combine", default=None,
                    help="combine request, resolved by "
                         "repro.core.diffusion.select_backend: 'auto', "
                         "'sparse' or any combine_backends() name (dense | "
                         "sparse_host_dynamic | sparse_dynamic | "
                         "mesh_sparse_dynamic | pallas | centralized | none)")
    ap.add_argument("--assert-budgets", action="store_true",
                    help="fail if the agent-mesh combine leaves the "
                         "deg·shard collective-permute window or a config "
                         "exceeds its pinned AGENT_MESH_BUDGETS ceiling")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--save-hlo", default=None)
    ap.add_argument("--hvp-subsample", type=float, default=None)
    ap.add_argument("--attn-q-chunk", type=int, default=None)
    ap.add_argument("--inner-freeze", default=None)
    ap.add_argument("--attn-shard", default=None)
    ap.add_argument("--inner-steps", type=int, default=None)
    ap.add_argument("--tag", default=None, help="suffix for output json")
    args = ap.parse_args()
    overrides = {}
    if args.hvp_subsample is not None:
        overrides["hvp_subsample"] = args.hvp_subsample
    if args.attn_q_chunk is not None:
        overrides["attn_q_chunk"] = args.attn_q_chunk
    if args.inner_freeze is not None:
        overrides["inner_freeze"] = args.inner_freeze
    if args.attn_shard is not None:
        overrides["attn_shard"] = args.attn_shard
    if args.inner_steps is not None:
        overrides["inner_steps"] = args.inner_steps

    os.makedirs(args.out, exist_ok=True)
    archs = list_archs() if args.arch == "all" else [args.arch]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    failures = []
    for arch in archs:
        shapes = shapes_for(arch) if args.shape == "all" else [args.shape]
        for shape in shapes:
            for mp in meshes:
                mesh_part = (f"agent{args.agents}" if args.agents
                             else ("multi" if mp else "single"))
                tag = f"{arch.replace('-', '_').replace('.', '_')}__{shape}__{mesh_part}"
                if args.combine:
                    tag += f"__{args.combine}"
                if args.tag:
                    tag += f"__{args.tag}"
                path = os.path.join(args.out, tag + ".json")
                try:
                    rec = run_one(arch, shape, mp, combine=args.combine,
                                  save_hlo=args.save_hlo, overrides=overrides,
                                  agents=args.agents,
                                  assert_budgets=args.assert_budgets)
                    with open(path, "w") as f:
                        json.dump(rec, f, indent=1)
                except Exception as e:  # record and continue
                    failures.append((tag, repr(e)))
                    print(f"[dryrun] FAIL {tag}: {e}")
                    traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for t, e in failures:
            print(" ", t, e)
        raise SystemExit(1)
    print("\nall dry-runs passed")


if __name__ == "__main__":
    main()
