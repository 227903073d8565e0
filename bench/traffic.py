"""The benchmark's one traffic generator: Dif-MAML language-model meta-batches.

A traffic file (``bench/traffic/<name>.json``) holds only parameters; this
module turns them, with ``--seed``, into the episodes the program's input
pipeline feeds to the meta step.  The generator is the benchmark's own copy
of the repository's per-domain Markov task source (``data/lm_tasks.py``):

* the domain universe has ``train_domains`` domains, split into K contiguous
  disjoint shards, one per agent (the paper's heterogeneous pi_k);
* a domain is an order-1 Markov chain over the vocabulary: each of
  ``buckets`` state buckets (token mod ``buckets``) may move to one of
  ``branching`` next tokens, drawn per domain from the seed;
* each step, agent k draws T domains from its shard and, per domain,
  ``2 * tb`` sequences of ``seq_len + 1`` tokens (support then query rows);
  tokens are inputs, the next tokens are labels.

Every seed gives the same sizes; only the token ids move with it.  Episode
``step`` is a pure function of ``(seed, step)``, so the reference can draw
the first steps again without reading anything the program holds.
"""
from __future__ import annotations

import numpy as np

_TABLE_SALT = 0x7AB1E
_STEP_SALT = 0x57E9


class MarkovTraffic:
    """Meta-batches of shape ``(K, T, tb, seq_len)`` for one cell."""

    def __init__(self, params: dict, *, vocab_size: int, K: int, T: int,
                 tb: int, seed: int):
        self.seq_len = int(params["seq_len"])
        self.branching = int(params["branching"])
        self.buckets = int(params["buckets"])
        self.train_domains = int(params["train_domains"])
        if self.train_domains < K:
            raise ValueError(f"{self.train_domains} domains cannot give "
                             f"K={K} agents disjoint shards")
        self.vocab_size = int(vocab_size)
        self.K, self.T, self.tb = K, T, tb
        self.seed = int(seed)
        self.shards = np.array_split(np.arange(self.train_domains), K)
        self.tables = np.stack([
            np.random.default_rng([_TABLE_SALT, self.seed, d]).integers(
                0, self.vocab_size, size=(self.buckets, self.branching))
            for d in range(self.train_domains)]).astype(np.int32)

    @property
    def tokens_per_step(self) -> int:
        """Tokens one meta-step consumes: support + query, all agents."""
        return self.K * self.T * 2 * self.tb * self.seq_len

    def sample(self, step: int) -> tuple[dict, dict]:
        """``(support, query)``, each ``{"tokens", "labels"}`` of int32 with
        leading axes ``(K, T, tb)``."""
        K, T, tb, S = self.K, self.T, self.tb, self.seq_len
        rows = T * 2 * tb
        doms, firsts, choices = [], [], []
        for k in range(K):
            rng = np.random.default_rng([_STEP_SALT, self.seed, step, k])
            doms.append(rng.choice(self.shards[k], size=T))
            firsts.append(rng.integers(0, self.vocab_size, size=rows))
            choices.append(rng.integers(0, self.branching, size=(rows, S)))
        row_dom = np.repeat(np.stack(doms).reshape(-1), 2 * tb)
        toks = np.empty((K * rows, S + 1), np.int32)
        toks[:, 0] = np.concatenate(firsts)
        choice = np.concatenate(choices)
        for t in range(S):
            toks[:, t + 1] = self.tables[row_dom, toks[:, t] % self.buckets,
                                         choice[:, t]]
        folded = toks.reshape(K, T, 2 * tb, S + 1)

        def pack(a):
            return {"tokens": np.ascontiguousarray(a[..., :-1]),
                    "labels": np.ascontiguousarray(a[..., 1:])}

        return pack(folded[:, :, :tb]), pack(folded[:, :, tb:])
