"""Device milliseconds a step under the meta-step's ``dif.step.inner_adapt``
scope: the inner adaptation on the support rows (the ``maml`` trajectory
loop), self time of its ops over the traced steps, mean over the chips
(``bench/scopes.py``)."""

from bench import scopes


def read(run):
    return scopes.ms_under(run, "dif.step", "inner_adapt")
