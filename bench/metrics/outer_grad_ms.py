"""Device milliseconds a step under ``dif.step.outer_grad``: the query
loss and its gradient at the adapted parameters, self time of its ops over
the traced steps, mean over the chips (``bench/scopes.py``)."""

from bench import scopes


def read(run):
    return scopes.ms_under(run, "dif.step", "outer_grad")
