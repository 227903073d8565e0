"""A cell of a model family the benchmark has not seen joins by new files
and ``BENCHMARK.json`` entries alone, and passes every benchmark test that
names it; a metric reader sees the step's counters and the cell's shapes
on a real run.  No chip is needed.
"""
from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import shutil
import subprocess
import sys
import xml.etree.ElementTree as ET

import numpy as np
import pytest

TESTS = pathlib.Path(__file__).resolve().parent
CHECKOUT = TESTS.parents[1]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src"), str(TESTS)]

import test_bench  # noqa: E402
from bench import harness  # noqa: E402

# A reader of a program counter: the step's ``disagreement`` (the agents'
# spread about their mean), averaged over the window's steps.
COUNTER_READER = '''"""Mean of the step's disagreement over the window."""
import numpy as np


def read(run):
    if run.counters is None or "disagreement" not in run.counters:
        return None
    return float(np.mean(run.counters["disagreement"]))
'''

# A reader of the cell's shapes: the forward FLOPs a step of the feed-forward
# products (three of d x width a layer; width the routed experts' for an
# expert layer), from ``run.arch`` and ``run.traffic``.
SHAPE_READER = '''"""Forward FLOPs a step of the feed-forward products."""


def read(run):
    if run.arch is None or run.traffic is None:
        return None
    a = run.arch
    width = (a["experts_per_token"] * a["moe_d_ff"] if a["num_experts"]
             else a["d_ff"])
    return float(6 * a["d_model"] * width * a["num_layers"]
                 * run.traffic["tokens_per_step"])
'''

READERS = {"new_disagreement": COUNTER_READER, "new_ffn_flops": SHAPE_READER}

# The digest of the toy configuration's weights, drawn as test_bench pins a
# configuration's (its small size, two agents, seed 2**40 + 15) with the toy
# family's own fan-in rule.
TOY_WEIGHTS_SHA256 = \
    "0e43adb1458e9fc9fcefdb4eea9c6e5abcffe58ac48e50088879d57127965419"


def _add_readers(root: pathlib.Path, b: dict, cell: str) -> None:
    for name, code in READERS.items():
        (root / "bench/metrics" / f"{name}.py").write_text(code)
        b["per_layer"].append({
            "name": name, "unit": "1", "better": "lower",
            "source": "program_counter", "layer": "meta step and model",
            "moves": "meta_tokens_per_s", "workloads": [cell]})


def _toy_checkout(root: pathlib.Path) -> None:
    """Add the toy MoE family's cell to the checkout at ``root`` by new
    files and entries alone."""
    cfg = test_bench.toy_config("toymoe")
    arch, reduced = cfg["arch"], cfg["reduced"]
    bench = root / "bench"
    (bench / "reference/toy_moe.py").write_text(test_bench.TOY_FAMILY)
    (bench / "configs/toymoe.json").write_text(json.dumps(cfg))
    (bench / "traffic/tiny.json").write_text(json.dumps(
        {"agents": 4, "layout": "stacked", "combine": "dense",
         "seq_len": 32, "global_batch": 8, "train_domains": 16,
         "branching": 8, "buckets": 16, "prefetch": 2}))
    (bench / "limits/toymoe.tiny.json").write_text(
        json.dumps(test_bench.TINY_LIMITS))
    d, f = arch["d_model"], arch["moe_d_ff"]
    per_token = float(arch["num_layers"] * 6 * d * f
                      * arch["experts_per_token"] + 2 * d * arch["vocab_size"])
    (bench / "tests/pins/cells/toymoe.tiny.json").write_text(
        json.dumps({"step_flops": 12 * 4 * 32 * per_token}))
    (bench / "tests/pins/configs/toymoe.json").write_text(
        json.dumps({"weights_sha256": TOY_WEIGHTS_SHA256}))
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "toymoe", "source": "test",
                         "file": "bench/configs/toymoe.json",
                         "reduced": reduced, "why": "test"})
    b["workloads"].append({"name": "toymoe.tiny", "config": "toymoe",
                           "traffic": "tiny", "chips": 1, "why": "test"})
    _add_readers(root, b, "toymoe.tiny")
    for m in b["per_layer"]:
        if m["name"] in ("step_mfu", "device_idle_share"):
            m["workloads"].append("toymoe.tiny")
    (root / "BENCHMARK.json").write_text(json.dumps(b))


def test_a_new_family_and_cell_pass_the_benchmark_tests(tmp_path):
    """The whole benchmark, tests and pins included, in a checkout of its
    own; the toy family, its configuration, cell, traffic, limits, pins and
    two readers are added as files, with their ``BENCHMARK.json`` entries.
    Every test case that names the new configuration or cell passes, and
    no file the benchmark had changes."""
    root = tmp_path / "checkout"
    shutil.copytree(CHECKOUT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(CHECKOUT / "BENCHMARK.json", root)
    (root / "src").symlink_to(CHECKOUT / "src")
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    _toy_checkout(root)
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PYTEST_") and k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH="src")
    report = tmp_path / "report.xml"
    done = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:randomly", "-k", "toymoe", f"--junitxml={report}",
         "bench/tests"], cwd=root, env=env, capture_output=True, text=True,
        timeout=900)
    assert done.returncode == 0, done.stdout[-4000:] + done.stderr[-2000:]
    cases = {c.get("name"): [x.tag for x in c]
             for c in ET.parse(report).iter("testcase")}
    assert all(outcome == [] for outcome in cases.values()), cases
    assert {"test_meta_step_flops_of_each_cell_are_pinned[toymoe.tiny]",
            "test_every_cell_loads_with_its_files[toymoe.tiny]",
            "test_configurations_match_the_repository[toymoe]",
            "test_weights_of_each_configuration_are_pinned[toymoe]",
            "test_each_cells_numbers_catch_the_faults_and_the_control"
            "[toymoe.tiny]"} <= set(cases), sorted(cases)
    assert all(p.read_bytes() == data for p, data in before.items())


def test_readers_see_the_counters_and_shapes_of_a_run(tmp_path, monkeypatch):
    """A run of the small qwen2 cell hands its readers every step's
    counters and the cell's shapes: the counter reader gives the window's
    mean ``disagreement``, the shape reader the count from the shapes."""
    import jax
    from repro.configs import get_config

    root = test_bench._tiny_root(tmp_path, "stacked")
    b = json.loads((root / "BENCHMARK.json").read_text())
    _add_readers(root, b, "tiny.tiny")
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    runs = []

    @dataclasses.dataclass
    class KeptRun(harness.Run):
        def __post_init__(self):
            runs.append(self)

    monkeypatch.setattr(harness, "Run", KeptRun)
    cell = harness.load_cell("tiny.tiny", root=root, trace=True)
    out = harness.run_cell(cell, jax.devices(), seed=7, seconds=0.2,
                           trace=False, t_start=0.0, peaks=None)
    assert out["correct"], out["checks"]
    (run,) = runs
    n = out["attempted"]
    assert set(run.counters) == {"loss", "per_agent_loss", "disagreement"}
    assert run.counters["disagreement"].shape == (n,)
    assert run.counters["per_agent_loss"].shape == (n, 4)
    assert np.all(run.counters["disagreement"] > 0)
    assert run.traffic == {"K": 4, "T": 1, "tb": 1, "seq_len": 32,
                           "layout": "stacked", "tokens_per_step": 256}
    small = get_config("qwen2-1.5b").reduced()
    assert run.arch == dataclasses.asdict(small)
    assert set(out["metrics"]) == set(READERS)
    assert out["metrics"]["new_disagreement"]["value"] == pytest.approx(
        float(np.mean(run.counters["disagreement"])), rel=1e-12)
    assert out["metrics"]["new_ffn_flops"]["value"] == \
        6 * small.d_model * small.d_ff * small.num_layers * 256
