"""Faults planted under the timed path, to show that ``correct`` catches them.

Each is a context manager that patches the program while the harness
builds its step; nothing of the harness changes.  Used by the benchmark's
tests at a small size and by ``calibrate.py`` at the cells' own sizes.

* ``unchanged``: the step returns its state unchanged (its counter aside);
* ``half_batch``: the second half of the global batch is left out and the
  first half takes its place, so every mean is over the rest;
* ``no_exchange``: the combine returns each agent's own parameters, as if
  nothing went between the agents (or the chips).
"""
from __future__ import annotations

import contextlib
import dataclasses
from unittest import mock

FAULTS = ("unchanged", "half_batch", "no_exchange")


def _wrap_step(wrap):
    from repro.launch import steps
    build = steps.build_train

    def build_train(*a, **kw):
        bundle = build(*a, **kw)
        return dataclasses.replace(bundle, step_fn=wrap(bundle.step_fn))

    return mock.patch.object(steps, "build_train", build_train)


def _unchanged(step_fn):
    def step(state, batch):
        new, metrics = step_fn(state, batch)
        return state._replace(step=new.step), metrics
    return step


def _half_batch(step_fn):
    def step(state, batch):
        def halve(x):
            h = x.shape[0] // 2
            return x.at[h:].set(x[:h])
        return step_fn(state, {k: halve(v) for k, v in batch.items()})
    return step


@contextlib.contextmanager
def planted(fault: str):
    if fault == "unchanged":
        with _wrap_step(_unchanged):
            yield
    elif fault == "half_batch":
        with _wrap_step(_half_batch):
            yield
    elif fault == "no_exchange":
        from repro.core import diffusion
        with mock.patch.object(diffusion, "make_combine",
                               lambda *a, **kw: lambda phi, step=None: phi):
            yield
    else:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
