"""Adapt-then-serve, end-to-end on the unified TaskSource surface.

The product of Dif-MAML is a launch model that specializes fast.  This
example reproduces the full production path on CPU:

  1. meta-train a reduced config for a few steps, checkpointing the
     K-agent ``TrainState`` (``launch/train.py``);
  2. restore the checkpoint's **centroid** launch model
     (``checkpoint.restore_centroid`` — mean over the agent axis);
  3. adapt it to an unseen-domain ``eval_sample`` episode through the
     shared engine (``maml.inner_adapt``, via ``launch/serve.py``);
  4. serve batched decode requests from the adapted weights.

  PYTHONPATH=src python examples/serve_adapted.py [--arch qwen2-1.5b]
"""
import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.launch.serve import main as serve_main
from repro.launch.train import main as train_main


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--train-steps", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    args, rest = ap.parse_known_args()

    ckpt_root = tempfile.mkdtemp(prefix="serve_adapted_")
    print(f"== meta-train {args.train_steps} steps -> checkpoint "
          f"({ckpt_root}) ==")
    train_main(["--arch", args.arch, "--reduced",
                "--steps", str(args.train_steps), "--seq", "16",
                "--global-batch", "16", "--agents", "4",
                "--seed", str(args.seed), "--ckpt-dir", ckpt_root,
                "--run-log", os.path.join(ckpt_root, "run.jsonl")])

    print("== adapt the checkpoint centroid to an unseen domain, "
          "then serve ==")
    serve_main(["--arch", args.arch, "--reduced",
                "--seed", str(args.seed),
                "--ckpt-dir", os.path.join(ckpt_root, f"seed{args.seed}"),
                "--batch", "4", "--prompt-len", "8", "--gen", "16",
                "--adapt-steps", "2"] + rest)
