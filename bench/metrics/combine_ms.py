"""Device milliseconds a step under ``dif.step.combine``: the combine of the
agents' parameters with their neighbours', whichever backend runs it
(collectives included), self time of its ops over the traced steps, mean
over the chips (``bench/scopes.py``)."""

from bench import scopes


def read(run):
    return scopes.ms_under(run, "dif.step", "combine")
