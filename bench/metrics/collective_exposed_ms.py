"""Milliseconds a step in which a collective ran on a chip and no other op
of that chip covered it: the exposed part of the combine's exchange over
the links, over the traced steps, mean over the chips
(``bench/trace.py``).  Nothing to read where no collective ran."""


def read(run):
    if run.trace is None or not run.trace.collective_s:
        return None
    return 1e3 * run.trace.collective_exposed_s / run.traced_steps
