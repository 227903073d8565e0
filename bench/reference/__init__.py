"""Plain reference of the Dif-MAML meta-step (Kayaalp et al., arXiv:2010.02870).

It imports nothing of the program.  Each step, agent k, with parameters w_k:

1. per task t of its T tasks, one inner SGD step on the support rows,
   ``w' = w_k - inner_lr * grad L(w_k; support)``, then the query loss
   ``L(w'; query)`` and its exact gradient with respect to ``w_k``, by the
   paper's eq. 4: ``(I - inner_lr * H) v`` with ``v = grad L(w'; query)``
   and ``H`` the Hessian of ``L(.; support)`` at ``w_k``, applied to ``v``
   as the product of ``v`` with the derivative of the support gradient
   (H is symmetric);
2. the meta-gradient is the mean over the T tasks, and so is the loss;
3. Adam (Kingma & Ba: b1 0.9, b2 0.999, eps 1e-8, bias-corrected) turns it
   into an update u_k;
4. adapt-then-combine: ``w_k <- sum_l A[l, k] (w_l + u_l)``.

Parameters are kept in the dtype the benchmark made them in, the one the
configuration states: the sum ``w + u`` and the combine are rounded to it,
so that an update smaller than half a unit of its last place is lost, as it
is in any model stored in that dtype.  Everything else is float32 at full
matmul precision.  The control (``quant``) is the same step one precision
below: every product of the forward, backward and second-order passes on
float8 operands (:class:`ops.Ops`), and the parameters an agent takes from
its neighbours in the combine rounded to float8, one scale per row; Adam's
moments stay float32, as the program keeps them.  Agents run one at a time,
so that one agent's second-order pass is live at once, and the moments of
the others wait on the host.

:func:`readings` gives what the benchmark compares: the query loss of every
agent at every step, the norm of every parameter leaf's first gradient,
and the norm of every leaf's change over the steps run.
"""
from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np

from .ops import Ops, fp8_round

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


def family(name: str):
    """The module of the model family ``name``: ``<name>.py`` beside this
    file.  It gives ``loss`` and ``forward_flops_per_token``, and may give
    ``fan_in`` (``bench/weights.py``).  An unknown family is an error."""
    module = f"{__name__}.{name}"
    try:
        return importlib.import_module(module)
    except ModuleNotFoundError as e:
        if e.name != module:
            raise
        raise ValueError(f"no model family {name!r} in bench/reference/"
                         ) from None


def model_loss(name: str):
    """``loss(ops, params, tokens, labels, cfg)`` of a model family."""
    return family(name).loss


def ring_metropolis(K: int) -> np.ndarray:
    """Combination matrix of a K-ring under the Metropolis rule: each agent
    weighs itself and its two neighbours 1/3 (K > 2)."""
    A = np.zeros((K, K))
    for k in range(K):
        for l in (k - 1, k + 1):
            A[k, l % K] = 1.0 / 3.0
    A[np.arange(K), np.arange(K)] = 1.0 - A.sum(axis=1)
    return A


def leaf_norms(tree) -> dict:
    """{leaf path: float32 norm} of a tree (device values)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                     for p in path): jnp.sqrt(jnp.sum(jnp.square(
                         x.astype(jnp.float32))))
            for path, x in flat}


def meta_grad(family: str, cfg: dict, *, inner_lr: float,
              quant: bool = False):
    """Jitted ``(w, support, query) -> (loss, grad)`` of one agent: its
    query loss after the inner step and the meta-gradient of eq. 4, each
    the mean over the agent's T tasks."""
    loss_fn = functools.partial(model_loss(family), Ops(quant), cfg=cfg)

    @jax.jit
    def agent_grad(w, support, query):
        w = jax.tree.map(lambda x: x.astype(jnp.float32), w)

        def task(s_tok, s_lab, q_tok, q_lab):
            support_grad = jax.grad(lambda w: loss_fn(w, s_tok, s_lab))
            g, hvp = jax.vjp(support_grad, w)
            adapted = jax.tree.map(lambda p, g: p - inner_lr * g, w, g)
            loss, v = jax.value_and_grad(
                lambda w: loss_fn(w, q_tok, q_lab))(adapted)
            (hv,) = hvp(v)          # H is symmetric: v^T H = H v
            return loss, jax.tree.map(lambda v, hv: v - inner_lr * hv, v, hv)

        T = support["tokens"].shape[0]
        out = [task(support["tokens"][t], support["labels"][t],
                    query["tokens"][t], query["labels"][t]) for t in range(T)]
        loss = sum(o[0] for o in out) / T
        grad = jax.tree.map(lambda *g: sum(g) / T, *(o[1] for o in out))
        return loss, grad

    return agent_grad


def readings(family: str, cfg: dict, params0, batches, *, A: np.ndarray,
             inner_lr: float, outer_lr: float, quant: bool = False) -> dict:
    """Run ``len(batches)`` reference meta-steps.

    ``params0``: callable returning the stacked initial parameters (leading
    agent axis K), as the benchmark made them, in the dtype they are kept
    in.  ``batches``: per step,
    ``(support, query)`` with ``{"tokens", "labels"}`` of shape
    ``(K, T, tb, seq)``.  Returns ``{"loss": [[per agent] per step],
    "grad": {leaf: norm}, "change": {leaf: norm}}`` as Python floats."""
    agent_grad = meta_grad(family, cfg, inner_lr=inner_lr, quant=quant)
    K = A.shape[0]
    links = [[l for l in range(K) if A[l, k] != 0] for k in range(K)]
    A = jnp.asarray(A, jnp.float32)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def adapt(w, m, v, g, t):
        m = jax.tree.map(lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g, m, g)
        v = jax.tree.map(lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * g * g,
                         v, g)
        bc1, bc2 = 1 - ADAM_B1 ** t, 1 - ADAM_B2 ** t
        w = jax.tree.map(
            lambda w, m, v: (w.astype(jnp.float32) - outer_lr * (m / bc1)
                             / (jnp.sqrt(v / bc2) + ADAM_EPS)).astype(w.dtype),
            w, m, v)
        return w, m, v

    def sent(x, mine):
        """What agent k takes from a neighbour: with ``quant``, float8."""
        x = x.astype(jnp.float32)
        if quant and not mine:
            x = fp8_round(x, (x.ndim - 1,))
        return x

    @functools.partial(jax.jit, donate_argnums=(0,))
    def combine(phi):
        return [jax.tree.map(
            lambda *xs: sum(A[l, k] * sent(xs[l], l == k) for l in links[k]
                            ).astype(xs[0].dtype), *phi)
            for k in range(K)]

    stacked = params0()
    w = [jax.tree.map(lambda x: x[k], stacked) for k in range(K)]
    del stacked
    # Adam's moments wait on the host while the other agents run, so that
    # one agent's moments and second-order pass share the device at a time.
    zeros = lambda wk: jax.tree.map(
        lambda x: np.zeros(x.shape, np.float32), wk)
    m = [zeros(wk) for wk in w]
    v = [zeros(wk) for wk in w]
    losses, grad = [], None
    for step, (support, query) in enumerate(batches):
        step_loss = []
        for k in range(K):
            sk = jax.tree.map(lambda a: jnp.asarray(a[k]), support)
            qk = jax.tree.map(lambda a: jnp.asarray(a[k]), query)
            loss, g = agent_grad(w[k], sk, qk)
            step_loss.append(loss)
            if step == 0:
                norms = leaf_norms(g)
                grad = norms if grad is None else {
                    n: jnp.sqrt(grad[n] ** 2 + norms[n] ** 2) for n in norms}
            w[k], mk, vk = adapt(w[k], *jax.device_put((m[k], v[k])), g,
                                 jnp.float32(step + 1))
            del g
            m[k], v[k] = jax.device_get((mk, vk))
            del mk, vk
        w = combine(w)
        losses.append([float(x) for x in step_loss])
    del m, v
    stacked = params0()
    change = {}
    for k in range(K):
        d = leaf_norms(jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b[k].astype(jnp.float32),
            w[k], stacked))
        change = d if not change else {
            n: jnp.sqrt(change[n] ** 2 + d[n] ** 2) for n in d}
    return {"loss": losses,
            "grad": {n: float(x) for n, x in grad.items()},
            "change": {n: float(x) for n, x in change.items()}}
