"""Readings that the limits of ``correct`` are set from, for one cell.

  python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \\
      [--control-seeds ...] [--fault half_batch --fault-seeds ...] \\
      [--out FILE] [--write-limits]

In one process: for every seed of ``--seeds`` the program's first steps
against the plain reference (the lower readings); for every seed of
``--control-seeds`` the control, the reference one precision below
(``reference/ops.py``), against the reference (the upper readings); for
each ``--fault``, the program with that fault planted (``faults.py``)
against the reference.  Prints one JSON line per reading and writes them
all to ``--out``.  The benchmark's own runs never run this.

From the readings it proposes each number's limit (:func:`propose`) and,
with ``--write-limits``, writes them to ``bench/limits/<cell>.json``.
"""
import argparse
import json
import pathlib
import statistics
import sys
import time

CHECKOUT = pathlib.Path(__file__).resolve().parents[1]


# A step that returns its state unchanged reads 1 on these, with no run:
# its optimizer moments and its parameters never move.
UNCHANGED = {"grad_gap": 1.0, "change_gap": 1.0}


def _seeds(text: str) -> list:
    return [int(x) for x in text.split(",") if x]


def propose(rows: list) -> dict:
    """Per number: the lower reading (the largest of the program's sound
    runs) and the upper one (the least of the control's, where it is three
    times the lower or more, and of each fault's, where it is ten times the
    lower or more; a state left unchanged counts at three times).  A number
    with no upper reading is left out.  The limit lies two thirds of the
    way from the lower to the upper reading on a log scale, so that fresh
    seeds have more room than the control has."""
    def readings(kind, n):
        return [r[n] for r in rows if r["kind"] == kind]

    faults = sorted({r["kind"] for r in rows if r["kind"].startswith("fault:")})
    out = {}
    for n in ("loss_gap", "first_loss_gap", "grad_gap", "change_gap"):
        lower = max(readings("program", n))
        uppers = [min(v) for v, k in
                  [(readings("control", n), 3)] +
                  [(readings(f, n), 10) for f in faults] +
                  [([UNCHANGED[n]] if n in UNCHANGED else [], 3)]
                  if v and min(v) >= k * lower and min(v) > lower]
        if uppers:
            upper = min(uppers)
            floor = max(lower, 1e-9)
            limit = float(f"{floor * (upper / floor) ** (2 / 3):.2g}")
            out[n] = {"lower": lower, "upper": upper, "limit": limit}
    for kind in ["control", *faults]:
        missed = [r["seed"] for r in rows if r["kind"] == kind
                  and all(r[n] <= v["limit"] for n, v in out.items())]
        if missed:
            print(f"calibrate: {kind} passes every limit on seeds {missed}",
                  file=sys.stderr)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault", action="append", default=[])
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--out", default=None)
    ap.add_argument("--write-limits", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(CHECKOUT))
    import jax
    from bench import faults, harness

    harness._program()[4]()
    cell = harness.load_cell(args.workload)
    devices = jax.devices()
    rows = []

    def worst(got, ref, n=4):
        """The leaves that set ``grad_gap``, with both readings."""
        med = statistics.median(ref["grad"].values())
        gap = {k: abs(got["grad"][k] - r) / max(r, med)
               for k, r in ref["grad"].items()}
        return [[k, gap[k], got["grad"][k], ref["grad"][k]]
                for k in sorted(gap, key=gap.get, reverse=True)[:n]]

    def emit(kind, seed, gaps, **extra):
        row = {"kind": kind, "seed": seed, **gaps, **extra}
        rows.append(row)
        print(json.dumps(row), flush=True)

    def program_gaps(prog, seed):
        tr = harness.traffic_for(prog, seed)
        t = time.perf_counter()
        with harness.pipeline(prog, tr) as pipe:
            state, prog_r, step_s = harness.first_steps(prog, pipe, seed)
        del state
        t_ref = time.perf_counter()
        ref_r = harness.reference_readings(prog, tr, seed)
        return harness.compare(prog_r, ref_r), {
            "program_s": t_ref - t, "reference_s": time.perf_counter() - t_ref,
            "loss": prog_r["loss"], "ref_loss": ref_r["loss"],
            "worst_grad": worst(prog_r, ref_r)}

    prog = harness.build_program(cell, devices)
    for seed in _seeds(args.seeds):
        gaps, extra = program_gaps(prog, seed)
        emit("program", seed, gaps, **extra)
    for seed in _seeds(args.control_seeds):
        tr = harness.traffic_for(prog, seed)
        t = time.perf_counter()
        ref_r = harness.reference_readings(prog, tr, seed)
        ctl_r = harness.reference_readings(prog, tr, seed, quant=True)
        emit("control", seed, harness.compare(ctl_r, ref_r),
             seconds=time.perf_counter() - t, loss=ctl_r["loss"],
             ref_loss=ref_r["loss"], worst_grad=worst(ctl_r, ref_r))
    del prog
    for fault in args.fault:
        with faults.planted(fault):
            prog = harness.build_program(cell, devices)
        for seed in _seeds(args.fault_seeds):
            gaps, extra = program_gaps(prog, seed)
            emit(f"fault:{fault}", seed, gaps, **extra)
        del prog
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    if args.seeds:
        proposed = propose(rows)
        print(json.dumps({"proposed": proposed}), flush=True)
        if args.write_limits:
            path = CHECKOUT / "bench" / "limits" / f"{args.workload}.json"
            path.write_text(json.dumps(
                {n: v["limit"] for n, v in proposed.items()}, indent=2) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
