"""First-class decentralized outer-update composition.

The paper's central experimental axis is the *outer* update structure: who
mixes with whom (:mod:`repro.core.topology`), **how** the mix composes with
the local gradient step (this module's :class:`DiffusionStrategy`), and
**when** communication happens (:class:`CommSchedule`).  The trainer
(:func:`repro.core.meta_trainer.make_meta_step`) is a thin assembly of

    InnerAlgo × DiffusionStrategy × CommSchedule

with each factor an independently pluggable registry entry.

DiffusionStrategy registry
==========================

A strategy composes the per-agent optimizer update ``u_k`` (already produced
by InnerAlgo + outer optimizer) with the combine step.  ``apply`` is a pure
``(params, updates, combine_fn, step) -> params`` function; ``combine_fn``
is a :data:`repro.core.diffusion.CombineFn` (``combine(phi, step)``), and
``step`` threads the traced counter so stacked topology schedules stay
jit-compatible.

``atc``          Adapt-then-Combine (paper Algorithm 1, eq. 6a/6b):
                 ``w' = A (w + u)``.  The paper's headline strategy — the
                 combine sees the freshest local information.
``cta``          Combine-then-Adapt (Sayed 2014 diffusion variant): the
                 iterate is mixed **before** the meta-gradient is taken, so
                 the inner adaptation, meta-gradient, and optimizer update
                 are all evaluated at the mixed point ``ψ = A w``:
                 ``w' = ψ + u(ψ)``.  Declared via ``pre_combine=True`` —
                 the trainer mixes ahead of the gradient computation and
                 ``apply`` is the plain local update.
``consensus``    consensus / DGD composition: mix the previous iterates,
                 apply the update evaluated at the **own** previous iterate
                 — ``w' = A w + u(w)`` (this is exactly
                 :func:`repro.core.diffusion.cta_step`, revived from dead
                 code).
``none``         non-cooperative baseline: ``w' = w + u`` (A = I).
``centralized``  every agent receives the centroid of the adapted iterates
                 (A = (1/K)·11ᵀ), the paper's centralized reference;
                 ignores the topology entirely.

InnerAlgo registry
==================

Names the inner meta-gradient algorithm.  The math lives unchanged in
:mod:`repro.core.maml`; the registry only validates the name and carries
the mode string the trainer passes through (``maml`` exact second-order,
``fomaml`` first-order, ``reptile`` update-direction, ``maml_naive``
cross-validation form).

CommSchedule
============

When to communicate: ``every=n`` runs the combine only on steps where
``step ≡ n−1 (mod n)`` (the legacy ``combine_every`` semantics).  The
trainer folds the decision into ``lax.cond`` so skipped steps execute *no*
combine matmul or collective — unlike the old ``jnp.where`` path, which
paid the full communication cost every step and discarded the result.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax

from repro.core import diffusion

PyTree = Any

__all__ = [
    "DiffusionStrategy",
    "register_strategy",
    "update_strategies",
    "get_strategy",
    "InnerAlgo",
    "inner_algos",
    "get_inner_algo",
    "CommSchedule",
    "local_update",
]


# ---------------------------------------------------------------------------
# DiffusionStrategy registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DiffusionStrategy:
    """One registered outer-update composition.

    ``apply(params, updates, combine_fn, step) -> params`` is the pure
    composition; ``params`` arrive already mixed when ``pre_combine`` is
    set (the trainer runs ``combine_fn`` *before* the meta-gradient).

    ``communicates``    whether the strategy moves bytes between agents at
                        all (gates the :class:`CommSchedule`); ``none`` and
                        the K=1 degenerate case don't.
    ``needs_combine_fn`` whether ``apply`` consumes the topology's combine
                        (``centralized`` averages regardless of the graph).
    ``pre_combine``     mix the iterate before the gradient step (``cta``).
    """

    name: str
    apply: Callable[[PyTree, PyTree, diffusion.CombineFn | None, Any], PyTree]
    communicates: bool = True
    needs_combine_fn: bool = True
    pre_combine: bool = False


_STRATEGIES: dict[str, DiffusionStrategy] = {}


def register_strategy(name: str, **flags: bool):
    """Decorator: register an ``apply`` composition under ``name``."""

    def deco(apply):
        _STRATEGIES[name] = DiffusionStrategy(name, apply, **flags)
        return apply

    return deco


def update_strategies() -> tuple[str, ...]:
    return tuple(_STRATEGIES)


def get_strategy(name: str) -> DiffusionStrategy:
    s = _STRATEGIES.get(name)
    if s is None:
        raise ValueError(f"unknown diffusion strategy {name!r}; "
                         f"registered: {update_strategies()}")
    return s


def local_update(params: PyTree, updates: PyTree) -> PyTree:
    """The communication-free outer update w' = w + u — the 'none' strategy
    and the skip branch of the CommSchedule gate, by construction the same
    function."""
    return jax.tree.map(lambda p, u: p + u, params, updates)


@register_strategy("atc")
def _atc(params, updates, combine_fn, step):
    """w' = A (w + u): paper Algorithm 1 (eq. 6a adapt, 6b combine)."""
    return diffusion.atc_step(params, updates, lambda p: combine_fn(p, step))


@register_strategy("cta", pre_combine=True)
def _cta(params, updates, combine_fn, step):
    """w' = ψ + u(ψ) with ψ = A w: the mix happened before the gradient
    (``pre_combine``), so the remaining composition is the local update."""
    return local_update(params, updates)


@register_strategy("consensus")
def _consensus(params, updates, combine_fn, step):
    """w' = A w + u(w): consensus/DGD — gradient at the own previous
    iterate, mix of the previous iterates (diffusion.cta_step revived)."""
    return diffusion.cta_step(params, updates, lambda p: combine_fn(p, step))


@register_strategy("none", communicates=False, needs_combine_fn=False)
def _none(params, updates, combine_fn, step):
    """w' = w + u: non-cooperative baseline (A = I)."""
    return local_update(params, updates)


@register_strategy("centralized", needs_combine_fn=False)
def _centralized(params, updates, combine_fn, step):
    """Every agent receives the centroid of the adapted iterates — the
    paper's centralized reference (A = (1/K)·11ᵀ, graph-independent)."""
    adapted = local_update(params, updates)
    with jax.named_scope("dif.step.combine"):
        return diffusion.centralized_combine(adapted)


# ---------------------------------------------------------------------------
# InnerAlgo registry (names only — math unchanged in core/maml.py)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class InnerAlgo:
    """A named inner meta-gradient algorithm; ``mode`` is the string
    :func:`repro.core.maml.multi_task_meta_grad` dispatches on."""

    name: str
    mode: str
    order: int                 # derivative order of the meta-gradient
    doc: str = ""


_INNER: dict[str, InnerAlgo] = {
    "maml": InnerAlgo("maml", "maml", 2,
                      "exact second-order meta-gradient (paper eq. 4)"),
    "fomaml": InnerAlgo("fomaml", "fomaml", 1,
                        "first-order: curvature term dropped"),
    "reptile": InnerAlgo("reptile", "reptile", 1,
                         "update direction = (w_adapted - w)"),
    "maml_naive": InnerAlgo("maml_naive", "maml_naive", 2,
                            "differentiate-through-the-update "
                            "cross-validation form"),
}


def inner_algos() -> tuple[str, ...]:
    return tuple(_INNER)


def get_inner_algo(name: str) -> InnerAlgo:
    a = _INNER.get(name)
    if a is None:
        raise ValueError(f"unknown inner algorithm {name!r}; "
                         f"registered: {inner_algos()}")
    return a


# ---------------------------------------------------------------------------
# CommSchedule
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CommSchedule:
    """Communicate every ``every``-th step (legacy ``combine_every``
    phase: the combine runs when ``step % every == every - 1``, so a fresh
    run's first communication lands on step ``every - 1``)."""

    every: int = 1

    def __post_init__(self):
        if self.every < 1:
            raise ValueError(f"CommSchedule.every must be >= 1, "
                             f"got {self.every}")

    @property
    def always(self) -> bool:
        return self.every == 1

    def is_comm_step(self, step) -> Any:
        """Predicate usable on a traced step index."""
        return (step % self.every) == self.every - 1
