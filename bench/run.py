"""Run one cell of the on-chip benchmark and print its result line.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is a ``workloads`` entry of ``BENCHMARK.json``.  The run takes the
chips the cell asks for, measures ``--seconds`` of meta-training, checks the
first steps against the plain reference, and prints, as the last line of
standard output, one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer ones), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``: each number compared with its limit, also printed as the last
lines of standard error.

It exits non-zero and prints no result when JAX finds no TPU, fewer chips
than the cell asks for, or a device kind missing from ``bench/peaks.json``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

CHECKOUT = pathlib.Path(__file__).resolve().parents[1]


def fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr)
    return 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (CHECKOUT / "src" / "repro").is_dir():
        return fail(f"the program is not in this checkout ({CHECKOUT})")
    # The program keeps its compile cache where this variable says: give it
    # a fixed directory inside the checkout, so that only a checkout's first
    # run of a cell compiles and two checkouts share nothing.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CHECKOUT / ".jax_cache")
    sys.path.insert(0, str(CHECKOUT))
    from bench import harness
    try:
        cell = harness.load_cell(args.workload, trace=bool(args.trace))
    except (KeyError, FileNotFoundError) as e:
        return fail(str(e))

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < cell.chips:
        return fail(f"{args.workload} needs {cell.chips} chips, JAX found "
                    f"{len(devices)}")
    try:
        peaks = harness.load_peaks(devices[0].device_kind)
    except KeyError as e:
        return fail(str(e))

    out = harness.run_cell(cell, devices, seed=args.seed,
                           seconds=args.seconds, trace=bool(args.trace),
                           t_start=T_START, peaks=peaks)
    print(f"correct: {out['correct']} (failed steps {out['failed']})",
          file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stdout.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
