"""Smoke run of decentralized meta-training and adaptation serving on a TPU.

Drives the system's own entry points in this one process, at the published
widths and full depth of ``mamba2-130m`` (24 layers, d_model 768, random
weights from seed 0), on the reference Dif-MAML job: K=4 agents on a ring,
``atc`` strategy, second-order ``maml``, seq 1024, global batch 8 (T=1 task
of tb=1 example per agent).

  python3 chip_smoke.py              one chip:
      1. ``launch.train.main``: 3 meta-steps, default (dense) combine, K=4
         agents stacked on the chip; loss finite, disagreement > 0 and
         falling; writes a checkpoint to a temporary directory.
      2. the same job for 2 steps with ``--fused-outer``: the compiled step
         holds the Pallas kernel (``tpu_custom_call``) and its per-step loss
         matches phase 1 within LOSS_RTOL.
      3. ``launch.serve.main`` from phase 1's checkpoint: 4 users adapt over
         2 rounds (round 2 is served from the adapted-state cache), then a
         short prompt and decode.
  python3 chip_smoke.py --chips 4    four chips, and only this phase: one
      agent per chip (``--mesh-agents 4``, ppermute combine on a bf16 wire)
      against the one-device stacked run of the same seed and data on the
      first chip; per-step loss within LOSS_RTOL, disagreement within
      DISAGREEMENT_RTOL.

Each phase prints its compile seconds, steady step or request seconds, the
chip's peak bytes in use so far, and its losses.  The last line of standard
output is one JSON object naming the device; it is printed only when every
phase passed.  Without a TPU, or outside the repository, the script exits
non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import time

# bf16 parameters: the fused kernel and the dense combine round the same
# f32 update differently, one bf16 ulp (2^-8 relative) per element at most;
# the loss, a mean over the batch, moves far less than that
LOSS_RTOL = 1e-2
# disagreement is a small difference of near-equal models after mixing, so
# the same per-element rounding is a larger share of it
DISAGREEMENT_RTOL = 5e-2

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
JOB = ["--arch", "mamba2-130m", "--seq", "1024", "--global-batch", "8",
       "--strategy", "atc", "--seed", "0"]


class PhaseFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise PhaseFailed(what)


class CompileClock:
    """Seconds the backend spent compiling, read from JAX's own events."""

    def __init__(self, monitoring):
        self.total = 0.0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event.endswith("backend_compile_duration"):
            self.total += duration

    def lap(self) -> float:
        total, self.total = self.total, 0.0
        return total


def peak_bytes(device) -> int | None:
    return (device.memory_stats() or {}).get("peak_bytes_in_use")


def report(phase: str, **fields) -> None:
    print(f"[chip_smoke] {phase}: " + json.dumps(fields), flush=True)


def train_phase(train, clock, device, tmp: str, name: str, extra: list[str]
                ) -> dict:
    t0 = time.perf_counter()
    out = train.main(JOB + extra + [
        "--run-log", os.path.join(tmp, f"{name}.jsonl")])
    steady = out["step_s"][1:]
    report(name, wall_s=time.perf_counter() - t0, compile_s=clock.lap(),
           steady_step_s=sum(steady) / len(steady), step_s=out["step_s"],
           peak_bytes_in_use=peak_bytes(device), K=out["K"],
           loss=out["loss"], disagreement=out["disagreement"],
           tpu_custom_calls=out["tpu_custom_calls"])
    check(out["K"] == 4, f"{name}: ran with K={out['K']}, not 4")
    check(all(math.isfinite(x) for x in out["loss"]),
          f"{name}: loss not finite: {out['loss']}")
    return out


def close(a: list[float], b: list[float], rtol: float) -> bool:
    return len(a) <= len(b) and all(
        abs(x - y) <= rtol * abs(y) for x, y in zip(a, b))


def one_chip(train, serve, clock, device, tmp: str) -> None:
    from repro.configs import get_config
    ckpt = os.path.join(tmp, "ckpt")
    ref = train_phase(train, clock, device, tmp, "train", [
        "--agents", "4", "--steps", "3", "--ckpt-dir", ckpt])
    dis = ref["disagreement"]
    check(all(d > 0 for d in dis) and all(
        b < a for a, b in zip(dis, dis[1:])),
        f"train: disagreement not positive and falling: {dis}")

    fused = train_phase(train, clock, device, tmp, "train_fused", [
        "--agents", "4", "--steps", "2", "--fused-outer"])
    check(fused["tpu_custom_calls"] > 0,
          "train_fused: no tpu_custom_call in the compiled step")
    check(close(fused["loss"], ref["loss"], LOSS_RTOL),
          f"train_fused: loss {fused['loss']} does not match {ref['loss']} "
          f"within rtol {LOSS_RTOL}")

    t0 = time.perf_counter()
    out = serve.main(["--arch", "mamba2-130m", "--seed", "0",
                      "--ckpt-dir", os.path.join(ckpt, "seed0"),
                      "--users", "4", "--rounds", "2", "--batch", "4",
                      "--prompt-len", "16", "--gen", "16"])
    r0, r1 = out["rounds"]
    tokens = out["tokens"]
    report("serve", wall_s=time.perf_counter() - t0, compile_s=clock.lap(),
           adapt_round_s=[r0["seconds"], r1["seconds"]],
           adapt_hits=[r0["hits"], r1["hits"]],
           prefill_s=out["decode"]["prefill_s"],
           decode_s=out["decode"]["decode_s"],
           peak_bytes_in_use=peak_bytes(device), cache=out["cache"])
    check(r0["misses"] == 4 and r1["hits"] == 4 and r1["misses"] == 0,
          f"serve: round 2 did not hit the cache ({r0}, {r1})")
    vocab = get_config("mamba2-130m").padded_vocab
    check(tokens.shape == (4, 32) and int(tokens.min()) >= 0
          and int(tokens.max()) < vocab, f"serve: bad tokens {tokens}")


def four_chips(train, clock, devices, tmp: str) -> None:
    mesh = train_phase(train, clock, devices[0], tmp, "train_mesh_agents", [
        "--mesh-agents", "4", "--combine", "mesh_sparse_dynamic",
        "--steps", "3"])
    stacked = train_phase(train, clock, devices[0], tmp, "train_stacked", [
        "--agents", "4", "--devices", "1", "--steps", "3"])
    check(close(mesh["loss"], stacked["loss"], LOSS_RTOL),
          f"loss {mesh['loss']} vs stacked {stacked['loss']} beyond rtol "
          f"{LOSS_RTOL}")
    check(close(mesh["disagreement"], stacked["disagreement"],
                DISAGREEMENT_RTOL),
          f"disagreement {mesh['disagreement']} vs stacked "
          f"{stacked['disagreement']} beyond rtol {DISAGREEMENT_RTOL}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the one-agent-per-chip phase")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              f"device(s)", file=sys.stderr)
        return 1
    sys.path.insert(0, SRC)
    try:
        from repro.launch import serve, train
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository is not here: {e}",
              file=sys.stderr)
        return 1
    print(f"[chip_smoke] compile cache: {enable_compile_cache()}")

    clock = CompileClock(jax.monitoring)
    # the K=4 checkpoint is about 6 GiB: outside any output directory
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        try:
            if args.chips == 4:
                four_chips(train, clock, devices, tmp)
            else:
                one_chip(train, serve, clock, devices[0], tmp)
        except PhaseFailed as e:
            print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
            return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
