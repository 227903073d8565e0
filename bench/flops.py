"""Model FLOPs that a Dif-MAML meta-step requires, from the shapes alone.

These are the operations the algorithm needs, never more: a product of an
(m x k) by a (k x n) matrix is 2mkn; elementwise work, norms, softmax and
the optimizer are left out; causal products count only the lower triangle
with its diagonal; recomputation for memory (remat) does not count.  The
vocabulary is the configuration's own, not the program's padded one.

The forward FLOPs a token are the model family's: each family module
(``bench/reference/<family>.py``, the configuration's ``reference``) gives
``forward_flops_per_token(arch, seq)`` by these conventions, so a family
joins with its own file.

A ``maml`` meta-step with one inner step, per task of tb support and tb
query rows (F = forward FLOPs of tb rows):

* support forward + backward: 3F (the backward is twice the forward);
* query forward + backward at the adapted parameters: 3F;
* the Hessian-vector product through the inner gradient: 6F, the tangent
  of the support forward + backward, where every product of two varying
  operands doubles;

12F in all, twice the 6 N D of a plain training step over the same tokens.
``fomaml`` drops the Hessian-vector product: 6F.
"""
from __future__ import annotations

from bench import reference

PASSES = {"maml": 12, "fomaml": 6}


def forward_flops_per_token(family: str, arch: dict, seq: int) -> float:
    """Forward FLOPs a token of the model family ``family``."""
    return reference.family(family).forward_flops_per_token(arch, seq)


def meta_step_flops(family: str, arch: dict, *, K: int, T: int, tb: int,
                    seq: int) -> float:
    """FLOPs of one meta-step of K agents, T tasks each, tb rows per side."""
    try:
        passes = PASSES[arch["meta_mode"]]
    except KeyError:
        raise ValueError(f"no FLOP count for meta_mode "
                         f"{arch['meta_mode']!r}") from None
    return passes * K * T * tb * seq * forward_flops_per_token(family, arch,
                                                               seq)
