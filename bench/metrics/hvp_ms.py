"""Device milliseconds a step under ``dif.step.hvp``: the Hessian-vector
product of the ``maml`` meta-gradient (the reversed ``jvp`` loop, with the
inner backward it recomputes), self time of its ops over the traced steps,
mean over the chips (``bench/scopes.py``)."""

from bench import scopes


def read(run):
    return scopes.ms_under(run, "dif.step", "hvp")
