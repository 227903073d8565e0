"""Seeded random weights, made by the benchmark and never by the program.

The benchmark takes only the layout of the program's parameters (the
abstract tree of shapes), fills it from ``--seed`` in one jitted call on the
device, in the dtype the program trains in, and gives the same values to
the plain reference.  Every agent's copy is drawn independently, as the
paper's launch models are.

The rules go by leaf name, after the leading agent axis (and, under
``segments``, the stacked layer axis) are set aside:

* ``scale`` (norms), ``D`` (SSD skip): ones;
* ``A_log``: log of U(1, 16), Mamba-2's range for the decay rate;
* ``dt_bias``: softplus^-1 of a step drawn log-uniformly in [1e-3, 1e-1];
* ``bq``/``bk``/``bv``: N(0, 0.02);
* ``embed``: N(0, 0.02);
* the Mamba-2 mixer's projections and convolutions (``w_x``, ``w_z``,
  ``w_B``, ``w_C``, ``w_dt``, ``conv_*``): U(-1/sqrt(fan_in), 1/sqrt(fan_in)),
  and its output projection ``w_out`` the same over sqrt(layers), as the
  published Mamba-2 initializes them (PyTorch's default for linear and
  convolution layers; the output projection rescaled for the residual
  stream);
* every other leaf is a weight: N(0, 1/fan_in).

fan_in is the product of a weight's contracted dims: the first for all but
``wo``/``w_out``, whose first two (heads x head dim) are contracted; a
depthwise convolution contracts its width alone.

A model family (``bench/reference/<family>.py``, the configuration's
``reference``) may override the fan-in alone, by a function
``fan_in(name, core_shape) -> int | None`` of the leaf's name and its shape
without the agent and layer axes: a number is that leaf's fan-in, ``None``
leaves it to the rule above.  The rules of the distribution stay as they
are.  A stacked experts axis is never part of a fan-in: a family whose
weights stack experts, ``(experts, d, f)``, gives ``d`` for them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference

_TWO_DIM_FAN_IN = ("wo", "w_out")
_MAMBA_UNIFORM = ("w_x", "w_z", "w_B", "w_C", "w_dt", "w_out",
                  "conv_x", "conv_B", "conv_C")


def seed_data(seed: int) -> np.ndarray:
    """Threefry key data that keeps every bit of a seed wider than 32 bits;
    passed to :func:`make_params` as an argument, so that one compiled
    program serves every seed."""
    return np.random.SeedSequence(int(seed)).generate_state(2)


def leaf_name(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def _core_shape(path, shape):
    """Shape without the agent axis (and the layer axis under segments)."""
    lead = 2 if leaf_name(path).startswith("segments/") else 1
    return shape[lead:]


def _fan_in(rule, name: str, core) -> int:
    found = rule(name, core) if rule is not None else None
    if found is not None:
        return found
    return int(np.prod(core[:2 if name in _TWO_DIM_FAN_IN else 1]))


def _leaf(key, path, spec, dtype, rule):
    name = leaf_name(path).rsplit("/", 1)[-1]
    shape = spec.shape
    fan_in = _fan_in(rule, name, _core_shape(path, shape))
    normal = jax.random.normal(key, shape, jnp.float32)
    if name in ("scale", "D"):
        x = jnp.ones(shape, jnp.float32)
    elif name == "A_log":
        x = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    elif name == "dt_bias":
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (np.log(1e-1) - np.log(1e-3)) + np.log(1e-3))
        x = dt + jnp.log(-jnp.expm1(-dt))
    elif name in ("bq", "bk", "bv", "embed"):
        x = 0.02 * normal
    elif name in _MAMBA_UNIFORM:
        bound = 1.0 / np.sqrt(float(fan_in))
        if name == "w_out":                 # over sqrt(layers): shape[1]
            bound /= np.sqrt(float(shape[1]))
        x = jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    else:
        x = normal / np.sqrt(float(fan_in))
    return x.astype(dtype)


def make_params(abstract_params, key_data, dtype=None,
                family: str | None = None):
    """Parameters for ``abstract_params`` (a tree of ShapeDtypeStructs with a
    leading agent axis) from :func:`seed_data`, in ``dtype`` or each leaf's
    own dtype, with the ``fan_in`` rule of the model family ``family`` where
    it gives one.  Call under ``jax.jit`` to make them on the device in one
    program."""
    rule = getattr(reference.family(family), "fan_in", None) \
        if family is not None else None
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract_params)
    key = jax.random.wrap_key_data(jnp.asarray(key_data, jnp.uint32))
    keys = jax.random.split(key, len(flat))
    leaves = [_leaf(k, path, spec, dtype or spec.dtype, rule)
              for k, (path, spec) in zip(keys, flat)]
    return jax.tree_util.tree_unflatten(treedef, leaves)
