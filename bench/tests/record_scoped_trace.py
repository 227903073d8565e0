"""Record the small scoped trace that ``test_scopes.py`` reduces.

  python3 bench/tests/record_scoped_trace.py [OUT_DIR]

On one chip: a jitted step whose work sits under two of the program's
phase scopes, one of them also under a model scope, with a loop of matmuls
on the chip (a ``while`` op that encloses its body's ops) and an unscoped
reduction, dispatched four times under the harness's own host spans, while
another thread marks each of three batches it makes with the pipeline's
``dif.pipeline.produce`` span.  Writes ``small_scoped.xplane.pb`` and the
compiled step's ``small_scoped.hlo.txt`` to ``OUT_DIR`` (default
``bench/tests/data``).
"""
import glob
import os
import shutil
import sys
import tempfile
import threading
import time

import jax
import jax.numpy as jnp

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def step(x, w, n):
    with jax.named_scope("dif.step.inner_adapt"):
        with jax.named_scope("dif.model.mixer"):
            h = jnp.tanh(x @ w)
    with jax.named_scope("dif.step.hvp"):
        h = jax.lax.fori_loop(0, n, lambda i, h: jnp.tanh(h @ w) + x, h)
    return jnp.sum(h * h, axis=0)


def produce(batches: int) -> None:
    for _ in range(batches):
        with jax.profiler.TraceAnnotation("dif.pipeline.produce"):
            time.sleep(0.001)


def main(out: str = OUT) -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_scoped_trace: no TPU", file=sys.stderr)
        return 1
    # source paths from the checkout down, not the recording machine's
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      r".*/(?=bench/)")
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    w = jnp.full((1024, 1024), 1e-3, jnp.bfloat16)
    n = jnp.int32(3)
    compiled = jax.jit(step).lower(x, w, n).compile()
    compiled(x, w, n).block_until_ready()
    tdir = tempfile.mkdtemp(prefix="bench_record_")
    jax.profiler.start_trace(tdir)
    with jax.profiler.TraceAnnotation("bench.window"):
        producer = threading.Thread(target=produce, args=(3,))
        producer.start()
        ys = []
        for _ in range(4):
            with jax.profiler.TraceAnnotation("bench.input_wait"):
                time.sleep(0.002)
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                ys.append(compiled(x, w, n))
        with jax.profiler.TraceAnnotation("bench.drain"):
            jax.block_until_ready(ys)
            producer.join()
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True)
    os.makedirs(out, exist_ok=True)
    shutil.copy(found[0], os.path.join(out, "small_scoped.xplane.pb"))
    with open(os.path.join(out, "small_scoped.hlo.txt"), "w") as f:
        f.write(compiled.as_text())
    shutil.rmtree(tdir, ignore_errors=True)
    print(f"record_scoped_trace: {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
