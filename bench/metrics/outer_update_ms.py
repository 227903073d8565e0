"""Device milliseconds a step under ``dif.step.outer_update``: clipping and
the outer optimizer's update, self time of its ops over the traced steps,
mean over the chips (``bench/scopes.py``)."""

from bench import scopes


def read(run):
    return scopes.ms_under(run, "dif.step", "outer_update")
