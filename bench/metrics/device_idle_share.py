"""Percent of the traced window in which the device ran no op, averaged
over the chips: one minus the union of the op intervals over the window
(``bench/trace.py``)."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
