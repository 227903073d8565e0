"""Serving CLI: adaptation-as-a-service over a launch-model checkpoint.

Dif-MAML's product is a *launch model*: at serving time an agent adapts it
to each live task with a few gradient steps, then serves batched decode
requests from the adapted model.  The machinery lives in
``repro.serve.ServeEngine`` — batched (vmapped, bucket-compiled)
``inner_adapt`` over concurrent user episodes, an LRU adapted-state cache
keyed by task signature (recurring users skip re-adaptation via low-rank
delta reconstruction), and a dispatch-free two-scan decode.  This module
is the thin CLI: restore the checkpoint centroid (or a fresh init), drive
``--users`` concurrent requests for ``--rounds`` rounds (round 2+ re-draws
the same tasks — the recurring-user fast path), decode from the first
adapted model, and optionally write the engine's ``kind=serve`` record to
a JSONL run log.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \\
      --batch 4 --prompt-len 8 --gen 16 --adapt-steps 2 --seed 0 \\
      [--users 4 --rounds 2] [--ckpt-dir ckpts/seed0] [--run-log serve.jsonl]

``main(argv)`` runs in the caller's process and returns a summary of the
session, so a script that already holds the accelerator can drive it.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import restore_centroid
from repro.configs import get_config
from repro.data.lm_tasks import LMTaskSource
from repro.launch import steps as S
from repro.launch.compile_cache import enable_compile_cache
from repro.serve import ServeEngine


def make_support_source(cfg, seq_len: int, task_batch: int,
                        seed: int = 0) -> LMTaskSource:
    """Serve-time episode stream: one live task per request, drawn from a
    small domain universe whose tail is held out — ``split='unseen'``
    reproduces the launch scenario (adapt to a domain never trained on)."""
    return LMTaskSource(
        vocab_size=cfg.padded_vocab, seq_len=seq_len, K=1,
        tasks_per_agent=1, task_batch=task_batch,
        n_domains=8, holdout_domains=2, seed=seed)


def main(argv: list[str] | None = None) -> dict:
    """Run one serving session; ``argv`` defaults to ``sys.argv[1:]``.

    Returns ``{"rounds", "decode", "tokens", "cache"}``: the adapt metrics
    of each round (hits, misses, seconds), the decode phase metrics, the
    generated tokens and the cache counters."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--adapt-steps", type=int, default=2)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0,
                    help="drives launch-model init (no checkpoint), the "
                         "support episode draws, and sampling — serve-time "
                         "sampling is reproducible per seed, not fixed")
    ap.add_argument("--ckpt-dir", default=None,
                    help="training checkpoint dir (e.g. ckpts/seed0): the "
                         "launch model is the checkpoint's agent-centroid; "
                         "omit to serve from a fresh init")
    ap.add_argument("--split", default=None,
                    choices=["recurring", "unseen", "full"],
                    help="which eval split the live tasks are drawn from "
                         "(default: unseen — the launch scenario)")
    ap.add_argument("--users", type=int, default=4,
                    help="concurrent adaptation requests per round (one "
                         "vmapped inner_adapt dispatch, bucket-padded)")
    ap.add_argument("--rounds", type=int, default=2,
                    help="request rounds; rounds after the first re-draw "
                         "the same tasks, exercising the adapted-state "
                         "cache's recurring-user fast path")
    ap.add_argument("--cache-capacity", type=int, default=64)
    ap.add_argument("--rank", type=int, default=8,
                    help="low-rank delta factorization rank (per matrix "
                         "leaf, fidelity-gated — see serve/lowrank.py)")
    ap.add_argument("--run-log", default=None,
                    help="JSONL path for the engine's kind=serve record "
                         "(cache counters, adapt p50/p99, per-phase tok/s)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    dt = S.DTYPES[cfg.dtype] if not args.reduced else jnp.float32

    B, total = args.batch, args.prompt_len + args.gen
    engine = ServeEngine(
        cfg, prompt_len=args.prompt_len, gen=args.gen, batch=B,
        adapt_steps=args.adapt_steps, temperature=args.temperature,
        cache_capacity=args.cache_capacity, rank=args.rank, dtype=dt)

    if args.ckpt_dir:
        # the centroid in the engine's dtype (f32 for --reduced, whatever
        # dtype the trainer stored)
        like = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, dt),
                            engine.bundle.params_specs)
        params = restore_centroid(args.ckpt_dir, like)
        print(f"[serve] launch model = checkpoint centroid ({args.ckpt_dir})")
    else:
        params = engine.model.init(jax.random.key(args.seed), dt)
        print(f"[serve] launch model = fresh init (seed {args.seed})")
    engine.load_params(params)

    # -- adapt: --users concurrent episodes per round; same tasks each
    # round (same eval seed → same domain draw), so rounds 2+ are the
    # recurring-user path and resolve from the adapted-state cache
    source = make_support_source(cfg, total, B, seed=args.seed)
    ep, rounds = None, []
    for rnd in range(args.rounds):
        ep = source.eval_sample(args.users, seed=args.seed, split=args.split)
        requests = engine.requests_from_episode(source, ep)
        adapted, m = engine.adapt(requests)
        rounds.append(m)
        doms = np.asarray(ep.domains).tolist()
        print(f"[serve] round {rnd}: adapted {m['n']} users "
              f"(domains {doms}) in {m['seconds']:.3f}s — "
              f"{m['hits']} cache hits, {m['misses']} misses "
              f"(buckets {m['buckets']})")

    # -- decode from the first user's adapted model: prompts are fresh
    # sequences of the domain it just adapted to (the episode's query half)
    prompt = np.asarray(ep.query["tokens"][0])[:, : args.prompt_len]
    tokens, dm = engine.decode(adapted[0], prompt, seed=args.seed)
    print(f"[serve] prompt: {B} seqs × {args.prompt_len} tok in "
          f"{dm['prefill_s']:.3f}s ({dm['prompt_tok_s']:.1f} tok/s prefill)")
    print(f"[serve] decode: {B} seqs × {args.gen} tok in "
          f"{dm['decode_s']:.3f}s ({dm['decode_tok_s']:.1f} tok/s)")
    print("[serve] sample:", tokens[0].tolist())

    stats = engine.cache.stats()
    print(f"[serve] cache: {stats['hits']} hits / {stats['misses']} misses "
          f"/ {stats['evictions']} evictions, {stats['residents']} "
          f"residents, {stats['compression']:.2f}x delta compression")

    if args.run_log:
        from repro.launch.train import RunLog
        log = RunLog(args.run_log)
        log.write(**engine.log_record())
        log.close()
        print(f"[serve] run log -> {args.run_log}")
    return {"rounds": rounds, "decode": dm, "tokens": tokens, "cache": stats}


if __name__ == "__main__":
    main()
