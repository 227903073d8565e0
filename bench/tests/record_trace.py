"""Record the small trace that ``test_bench.py`` reduces.

  python3 bench/tests/record_trace.py [OUT]

On one chip: a jitted matmul and a reduction, dispatched four times under
the harness's own host spans (``bench.window``, ``bench.input_wait``,
``bench.dispatch``, ``bench.drain``), traced by ``jax.profiler``.  Writes
the ``.xplane.pb`` to ``OUT`` (default ``bench/tests/data/small.xplane.pb``).
"""
import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                   "small.xplane.pb")


def main(out: str = OUT) -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 1
    step = jax.jit(lambda x: jnp.tanh(x @ x).sum(axis=0))
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    step(x).block_until_ready()
    tdir = tempfile.mkdtemp(prefix="bench_record_")
    jax.profiler.start_trace(tdir)
    with jax.profiler.TraceAnnotation("bench.window"):
        ys = []
        for _ in range(4):
            with jax.profiler.TraceAnnotation("bench.input_wait"):
                time.sleep(0.002)
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                ys.append(step(x))
        with jax.profiler.TraceAnnotation("bench.drain"):
            jax.block_until_ready(ys)
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True)
    os.makedirs(os.path.dirname(out), exist_ok=True)
    shutil.copy(found[0], out)
    shutil.rmtree(tdir, ignore_errors=True)
    print(f"record_trace: {out} ({os.path.getsize(out)} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
