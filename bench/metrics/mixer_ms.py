"""Device milliseconds a step under the model's ``dif.model.mixer`` scope:
each block's norm, sequence mixer (attention or SSD) and residual, in
every phase, self time of its ops over the traced steps, mean over the
chips (``bench/scopes.py``)."""

from bench import scopes


def read(run):
    return scopes.ms_under(run, "dif.model", "mixer")
