"""Device milliseconds a step under ``dif.model.head``: the final norm, the
vocabulary head's product and the loss's log-sum-exp, in every phase, self
time of its ops over the traced steps, mean over the chips
(``bench/scopes.py``)."""

from bench import scopes


def read(run):
    return scopes.ms_under(run, "dif.model", "head")
