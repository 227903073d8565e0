"""Device milliseconds a step under ``dif.model.ffn``: each block's norm,
MLP or expert layer and residual, in every phase, self time of its ops over
the traced steps, mean over the chips (``bench/scopes.py``)."""

from bench import scopes


def read(run):
    return scopes.ms_under(run, "dif.model", "ffn")
