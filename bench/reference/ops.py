"""Matrix products of the plain reference, in float32 at full precision.

``Ops(quant=True)`` is the control: every product of the step, in the
forward pass, the backward pass and the second-order pass alike, takes both
operands rounded to float8 first, each scaled so that its largest value
over the contracted dims meets the format's largest, as fp8 matmuls are fed
(:func:`fp8_einsum`).  Values (weights, activations) are e4m3 and gradients
e5m2, the float8 training recipe of Micikevicius et al. (arXiv:2209.05433).
It stands for the step below the bfloat16 the configurations state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


E4M3 = jnp.float8_e4m3fn      # values
E5M2 = jnp.float8_e5m2        # gradients


def fp8_round(x: jax.Array, contracted: tuple[int, ...], fmt=E4M3
              ) -> jax.Array:
    """``x`` rounded to the float8 format ``fmt``, with one scale per slice
    over ``contracted``."""
    scale = jnp.max(jnp.abs(x), axis=contracted, keepdims=True) \
        / float(jnp.finfo(fmt).max)
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(fmt).astype(jnp.float32) * scale


def _operands(eq: str) -> tuple[str, str, str]:
    ins, out = eq.split("->")
    sa, sb = ins.split(",")
    return sa, sb, out


def fp8_einsum(eq: str, a: jax.Array, b: jax.Array, fa=E4M3, fb=E4M3
               ) -> jax.Array:
    """``einsum(eq, a, b)`` on float8 operands (``a`` in format ``fa``,
    ``b`` in ``fb``), accumulated in float32.

    Its derivative is the pair of products of the cotangent, in e5m2, with
    the other operand in its own format, each again an :func:`fp8_einsum`,
    so that the backward pass and the derivative of the backward pass (the
    Hessian-vector product) are float8 products too.  Every index of an
    operand must appear in the other operand or in the output."""
    return _fp8_product(eq, fa, fb)(a, b)


@functools.lru_cache(maxsize=None)
def _fp8_product(eq: str, fa, fb):
    sa, sb, out = _operands(eq)
    ca = tuple(i for i, c in enumerate(sa) if c not in out)
    cb = tuple(i for i, c in enumerate(sb) if c not in out)

    @jax.custom_vjp
    def product(a, b):
        return jnp.einsum(eq, fp8_round(a, ca, fa), fp8_round(b, cb, fb),
                          precision=jax.lax.Precision.HIGHEST)

    def fwd(a, b):
        # Residuals that are new values, not the inputs themselves: the
        # second-order pass through a scan mis-shapes forwarded inputs.
        return product(a, b), (a + 0.0, b + 0.0)

    def bwd(res, ct):
        a, b = res
        return (fp8_einsum(f"{out},{sb}->{sa}", ct, b, E5M2, fb),
                fp8_einsum(f"{out},{sa}->{sb}", ct, a, E5M2, fa))

    product.defvjp(fwd, bwd)
    return product


class Ops:
    def __init__(self, quant: bool = False):
        self.quant = quant

    def einsum(self, eq: str, a: jax.Array, b: jax.Array) -> jax.Array:
        a = a.astype(jnp.float32)
        b = b.astype(jnp.float32)
        if self.quant:
            return fp8_einsum(eq, a, b)
        return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def lm_loss(ops: Ops, x: jax.Array, params: dict, labels: jax.Array
            ) -> jax.Array:
    """Final norm, vocabulary head and mean next-token cross-entropy."""
    h = rms_norm(x, params["final_norm"]["scale"])
    logits = ops.einsum("rsd,dv->rsv", h, params["head"])
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def scan_layers(block, x: jax.Array, layers: dict) -> jax.Array:
    """Apply ``block(layer_params, x)`` over the stacked layer axis, each
    layer recomputed in the backward pass so that one layer's activations
    are live at a time."""
    body = jax.checkpoint(lambda h, p: (block(p, h), None))
    x, _ = jax.lax.scan(body, x, layers)
    return x
