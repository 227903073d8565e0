"""Model FLOPs that a Dif-MAML meta-step requires, from the shapes alone.

These are the operations the algorithm needs, never more: a product of an
(m x k) by a (k x n) matrix is 2mkn; elementwise work, norms, softmax and
the optimizer are left out; causal products count only the lower triangle
with its diagonal; recomputation for memory (remat) does not count.  The
vocabulary is the configuration's own, not the program's padded one.

Forward, per token of a sequence of length S:

* every projection and the head: 2 x (weights of the product);
* the Mamba-2 depthwise convolution: 2 x width x channels;
* the Mamba-2 SSD layer in its chunked form (chunk c = min(ssm_chunk, S)),
  per head: (c + 1)(N + P) for C.B and the masked product with x inside
  the chunk (each a causal triangle), and 4NP for reading and writing the
  state carried between chunks;
* causal attention: 2 (S + 1) head_dim heads for q.k and probs.v.

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

PASSES = {"maml": 12, "fomaml": 6}


def forward_flops_per_token(arch: dict, seq: int) -> float:
    d, V, L = arch["d_model"], arch["vocab_size"], arch["num_layers"]
    if arch["arch_type"] == "ssm":
        H = arch["ssm_expand"] * d // arch["ssm_head_dim"]
        P, N, G = arch["ssm_head_dim"], arch["ssm_state"], arch["ssm_groups"]
        c = min(arch["ssm_chunk"], seq)
        proj = 2 * d * H * P + 2 * d * G * N + d * H + H * P * d
        conv = arch["ssm_conv"] * (H * P + 2 * G * N)
        ssd = H * ((c + 1) * (N + P) + 4 * N * P)
        layer = 2 * proj + 2 * conv + ssd
    elif arch["arch_type"] == "dense":
        H, KV, hd = arch["num_heads"], arch["num_kv_heads"], arch["head_dim"]
        proj = d * H * hd * 2 + d * KV * hd * 2 + 3 * d * arch["d_ff"]
        layer = 2 * proj + 2 * (seq + 1) * hd * H
    else:
        raise ValueError(f"no FLOP count for arch_type {arch['arch_type']!r}")
    return float(L * layer + 2 * d * V)


def meta_step_flops(arch: dict, *, K: int, T: int, tb: int, seq: int) -> float:
    """FLOPs of one meta-step of K agents, T tasks each, tb rows per side."""
    try:
        passes = PASSES[arch["meta_mode"]]
    except KeyError:
        raise ValueError(f"no FLOP count for meta_mode "
                         f"{arch['meta_mode']!r}") from None
    return passes * K * T * tb * seq * forward_flops_per_token(arch, seq)
