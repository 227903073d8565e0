"""Milliseconds per step the window's loop spent blocked on the input
pipeline, from the harness's ``bench.input_wait`` spans around each
``next()`` of the program's prefetching pipeline, over the traced steps."""


def read(run):
    if run.trace is None or not run.trace.span_n.get("bench.input_wait"):
        return None
    return 1e3 * run.trace.span_s["bench.input_wait"] / run.traced_steps
