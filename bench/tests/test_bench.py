"""Tests of the on-chip benchmark that need no chip.

They run on the CPU at small sizes: the trace reduction on a synthetic trace
and on a small trace recorded on a v5e chip, the peaks table, the model
FLOP count against XLA's, the data-driven layout, the configurations
against the repository's, and a whole run of a small cell with the chip
check skipped: sound, with each planted fault, and with the control.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

CHECKOUT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(CHECKOUT))
sys.path.insert(0, str(CHECKOUT / "src"))

from bench import faults, flops, harness, trace  # noqa: E402

DATA = pathlib.Path(__file__).resolve().parent / "data"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCHMARK = json.loads((CHECKOUT / "BENCHMARK.json").read_text())


# ---------------------------------------------------------------------------
# Trace reduction
# ---------------------------------------------------------------------------

def _trace(devices, spans, asyncs=None):
    return trace.Trace({k: trace.Ops.of(v) for k, v in devices.items()},
                       {k: trace.Ops.of(v) for k, v in (asyncs or {}).items()},
                       spans)


def test_summarize_busy_idle_collectives_and_gaps():
    ms = 1_000_000
    t = _trace(
        {"/device:TPU:0": [
            ("fusion.1", 0 * ms, 4 * ms),
            ("collective-permute-done.2", 3 * ms, 6 * ms),   # 2 ms exposed
            ("fusion.1", 8 * ms, 9 * ms)]},
        [("bench.window", 0, 10 * ms),
         ("bench.input_wait", 6 * ms, 8 * ms),
         ("bench.drain", 9 * ms, 10 * ms)])
    s = trace.summarize(t)
    assert s.window_s == pytest.approx(0.010)
    assert s.busy_s == pytest.approx(0.007)
    assert s.idle_share == pytest.approx(0.3)
    assert s.collective_s == pytest.approx(0.003)
    assert s.collective_exposed_s == pytest.approx(0.002)
    assert s.top_ops == [["fusion.1", pytest.approx(0.005)],
                         ["collective-permute-done.2", pytest.approx(0.003)]]
    assert s.idle_gaps == [["bench.input_wait", pytest.approx(0.002)],
                           ["bench.drain", pytest.approx(0.001)]]
    assert s.span_s["bench.input_wait"] == pytest.approx(0.002)


def test_summarize_counts_self_time_and_collectives_in_flight():
    """A loop's op encloses its body's ops: top ops count each op's own
    time.  An asynchronous permute in flight counts as collective time,
    exposed only where no other op runs (here after the loop ends)."""
    ms = 1_000_000
    t = _trace(
        {"/device:TPU:0": [
            ("while.7", 0, 8 * ms),
            ("fusion.1", 1 * ms, 3 * ms),
            ("fusion.2", 3 * ms, 4 * ms),
            ("fusion.1", 5 * ms, 7 * ms)]},
        [("bench.window", 0, 10 * ms)],
        asyncs={"/device:TPU:0": [("collective-permute-start.3", 6 * ms,
                                   9 * ms)]})
    s = trace.summarize(t)
    assert dict((n, v) for n, v in s.top_ops) == {
        "fusion.1": pytest.approx(0.004), "while.7": pytest.approx(0.003),
        "fusion.2": pytest.approx(0.001)}
    assert s.busy_s == pytest.approx(0.008)
    assert s.collective_s == pytest.approx(0.003)
    assert s.collective_exposed_s == pytest.approx(0.001)


def test_summarize_clips_to_the_window_and_averages_devices():
    ms = 1_000_000
    t = _trace(
        {"/device:TPU:0": [("a", -5 * ms, 5 * ms)],
         "/device:TPU:1": [("a", 0, 10 * ms), ("b", 12 * ms, 20 * ms)]},
        [("bench.window", 0, 10 * ms)])
    s = trace.summarize(t)
    assert s.busy_s == pytest.approx(0.0075)
    assert s.idle_share == pytest.approx(0.25)
    assert s.top_ops == [["a", pytest.approx(0.0075)]]


def test_summarize_refuses_a_trace_without_device_or_window():
    with pytest.raises(ValueError, match="device"):
        trace.summarize(_trace({}, [("bench.window", 0, 1)]))
    with pytest.raises(ValueError, match="bench.window"):
        trace.summarize(_trace({"/device:TPU:0": []}, []))


def test_recorded_v5e_trace():
    """A trace recorded on one v5e chip: a jitted matmul and a reduction
    dispatched four times under the harness's spans."""
    s = trace.summarize(trace.load(str(DATA / "small.xplane.pb")))
    assert s.n_devices == 1
    assert 0 < s.busy_s < s.window_s
    assert s.collective_s == 0.0
    assert s.span_n["bench.dispatch"] == 4
    assert s.span_n["bench.input_wait"] == 4
    assert s.top_ops and all(t > 0 for _, t in s.top_ops)
    assert {label for label, _ in s.idle_gaps} <= {
        "bench.window", "bench.input_wait", "bench.dispatch", "bench.drain"}


# ---------------------------------------------------------------------------
# Peaks and FLOPs
# ---------------------------------------------------------------------------

def test_peaks_table():
    v5e = harness.load_peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9
    assert v5e["ici_bits_per_s"] == 1600e9
    with pytest.raises(KeyError, match="peaks"):
        harness.load_peaks("cpu")


@pytest.mark.parametrize("base,family,kw", [
    ("mamba2-130m", "mamba2", dict(num_layers=1, d_model=256,
                                   vocab_size=1024, ssm_chunk=256)),
    ("qwen2-1.5b", "qwen2", dict(num_layers=1, d_model=256, num_heads=4,
                                 num_kv_heads=2, head_dim=64, d_ff=1024,
                                 vocab_size=1024, attn_q_chunk=None)),
])
def test_forward_flops_agree_with_xla(base, family, kw):
    """One forward pass at a small width, one layer and one chunk, so that
    XLA's count has no loop body counted once.  XLA computes the masked
    upper triangle of the causal products, which the count leaves out, so
    it is added back here; what stays apart is elementwise work the count
    leaves out (up to 3 %), and for the SSD the read of the zero state
    entering the only chunk, which XLA folds away (2NP per token and head,
    4 % here).  Without the triangle the count must stay under XLA's."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models.transformer import build_model

    cfg = dataclasses.replace(get_config(base), **kw)
    arch = dataclasses.asdict(cfg)
    model = build_model(cfg)
    R, S = 2, 256
    params = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    batch = {k: jax.ShapeDtypeStruct((R, S), jnp.int32)
             for k in ("tokens", "labels")}
    xla = dict(jax.jit(model.forward).lower(params, batch).compile()
               .cost_analysis())["flops"]
    ours = R * S * flops.forward_flops_per_token(family, arch, S)
    if cfg.arch_type == "ssm":
        H = cfg.ssm_d_inner // cfg.ssm_head_dim
        upper = R * S * H * (S - 1) * (cfg.ssm_state + cfg.ssm_head_dim)
    else:
        upper = R * 2 * cfg.head_dim * cfg.num_heads * S * (S - 1)
    assert ours < xla
    assert (ours + upper) / xla == pytest.approx(1.0, abs=0.05)


def test_meta_step_flops_count_twelve_forwards():
    arch = json.loads((CHECKOUT / "bench/configs/mamba2-130m.json")
                      .read_text())["arch"]
    per_token = flops.forward_flops_per_token("mamba2", arch, 1024)
    assert flops.meta_step_flops("mamba2", arch, K=4, T=1, tb=1,
                                 seq=1024) == 12 * 4 * 1024 * per_token
    with pytest.raises(ValueError):
        flops.meta_step_flops("mamba2", dict(arch, meta_mode="reptile"),
                              K=4, T=1, tb=1, seq=1024)
    with pytest.raises(ValueError, match="family"):
        flops.meta_step_flops("no_such_family", arch, K=4, T=1, tb=1,
                              seq=1024)


# Each entry's pins sit in a file of its own, which a new entry brings:
# pins/cells/<cell>.json and pins/configs/<config>.json.
PINS = pathlib.Path(__file__).resolve().parent / "pins"


def pinned(kind: str, name: str, key: str, got):
    """The value ``key`` pinned for the entry ``name`` in
    ``pins/<kind>/<name>.json``; a missing file fails the test with the
    path to add and ``got``, the value the test computed."""
    path = PINS / kind / f"{name}.json"
    if not path.is_file():
        pytest.fail(f"no pin for {name!r}: add {path.relative_to(CHECKOUT)} "
                    f"holding {json.dumps({key: got})}")
    return json.loads(path.read_text())[key]


@pytest.mark.parametrize("w", BENCHMARK["workloads"], ids=lambda w: w["name"])
def test_meta_step_flops_of_each_cell_are_pinned(w):
    cell = harness.load_cell(w["name"])
    tr = cell.traffic
    # global batch 8 is K=4 agents' support and query rows: T=1, tb=1
    assert tr["global_batch"] == 2 * tr["agents"]
    got = flops.meta_step_flops(
        cell.config["reference"], cell.config["arch"], K=tr["agents"], T=1,
        tb=1, seq=tr["seq_len"])
    # as the benchmark counted them before the counts moved into the family
    # modules: step_mfu reads the same
    assert got == pinned("cells", w["name"], "step_flops", got)


# ---------------------------------------------------------------------------
# Layout, names and configurations
# ---------------------------------------------------------------------------

def test_benchmark_names_and_units():
    b = BENCHMARK
    names = [c["name"] for c in b["configs"]] + \
        [w["name"] for w in b["workloads"]] + \
        [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in b["workloads"]] + \
            [w["config"] for w in b["workloads"]] + \
            [k for c in b["configs"] for k in c["reduced"]]:
        assert NAME.match(n), n
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    assert {m["name"] for m in b["end_to_end"]} >= {"setup_s"}
    layers = {m["layer"] for m in b["per_layer"]}
    assert all(1 <= len(x) <= 200 and "\n" not in x for x in layers)


@pytest.mark.parametrize("w", BENCHMARK["workloads"], ids=lambda w: w["name"])
def test_every_cell_loads_with_its_files(w):
    for trace_run in (False, True):
        cell = harness.load_cell(w["name"], trace=trace_run)
        assert cell.chips == w["chips"]
        assert cell.limits and set(cell.limits) <= {
            "loss_gap", "first_loss_gap", "grad_gap", "change_gap"}
        for m in cell.metrics:
            assert callable(harness.load_reader(m["name"]))


def test_a_cell_and_a_metric_are_added_with_files_only(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(CHECKOUT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    b = json.loads(json.dumps(BENCHMARK))
    (root / "bench/configs/extra.json").write_text(
        (CHECKOUT / "bench/configs/mamba2-130m.json").read_text())
    (root / "bench/traffic/extra.json").write_text(
        (CHECKOUT / "bench/traffic/ring4.s1024.json").read_text())
    (root / "bench/limits/extra.extra.json").write_text(
        json.dumps({"loss_gap": 1, "grad_gap": 1, "change_gap": 1}))
    (root / "bench/metrics/extra_metric.py").write_text(
        "def read(run):\n    return 2.0 * run.steps\n")
    b["workloads"].append({"name": "extra.extra", "config": "extra",
                           "traffic": "extra", "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "extra_metric", "unit": "n",
                           "better": "lower", "source": "host_clock",
                           "layer": "device", "moves": "meta_tokens_per_s",
                           "workloads": ["extra.extra"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    cell = harness.load_cell("extra.extra", root=root, trace=True)
    names = [m["name"] for m in cell.metrics]
    assert "extra_metric" in names and "step_mfu" not in names
    run = harness.Run(setup_s=1, window_s=1, steps=3, traced_steps=0,
                      tokens_per_step=1, step_flops=1, chips=1, peaks=None)
    assert harness.load_reader("extra_metric", root)(run) == 6.0
    assert cell.config["arch"]["name"] == "mamba2-130m"
    assert all(p.read_bytes() == data for p, data in before.items())


# The keys of ArchConfig's meta-learning block: the job, which a
# configuration file may set apart from the repository's configuration.
JOB_KEYS = {"placement", "meta_mode", "meta_tasks", "inner_lr",
            "inner_steps", "topology", "combine", "outer_optimizer",
            "outer_lr", "hvp_subsample", "inner_freeze", "remat",
            "remat_span"}


def config_faults(cfg: dict, reduced: list) -> list:
    """What keeps the configuration file ``cfg`` from being the
    repository's configuration ``cfg["base"]`` with the keys ``reduced``
    (of ``BENCHMARK.json``) cut and its ``job`` keys set: every other key
    is the repository's, whether the file states it or leaves it out (it
    then holds its ``ArchConfig`` default, which ``ArchConfig(**arch)``
    builds)."""
    from repro.configs import get_config
    from repro.configs.base import ArchConfig
    repo = dataclasses.asdict(get_config(cfg["base"]))
    default = {f.name: f.default for f in dataclasses.fields(ArchConfig)}
    arch, job = cfg["arch"], set(cfg.get("job", []))
    faults = [f"{k}: not an ArchConfig key" for k in set(arch) - set(repo)]
    faults += [f"{k}: job key outside the meta-learning block"
               for k in sorted(job - JOB_KEYS)]
    if cfg["reduced"] != reduced:
        faults.append(f"reduced {cfg['reduced']} is not {reduced}")
    for k in repo:
        if k not in arch:
            if default.get(k, dataclasses.MISSING) != repo[k]:
                faults.append(f"{k}: left out, but the repository's "
                              f"{repo[k]!r} is not the default")
        elif k in reduced:
            if arch[k] == repo[k] or cfg["published"][k] != repo[k]:
                faults.append(f"{k}: reduced, but not cut from {repo[k]!r}")
        elif arch[k] != repo[k] and k not in job:
            faults.append(f"{k}: {arch[k]!r}, the repository's {repo[k]!r}")
    return faults


@pytest.mark.parametrize("entry", BENCHMARK["configs"],
                         ids=lambda c: c["name"])
def test_configurations_match_the_repository(entry):
    cfg = json.loads((CHECKOUT / entry["file"]).read_text())
    assert config_faults(cfg, entry["reduced"]) == []


def test_the_configuration_check_holds_every_shape_key():
    """A job key may differ; a shape key outside ``reduced`` may not, nor
    may one left out whose repository value is not the default, nor a job
    key outside the meta-learning block."""
    cfg = json.loads((CHECKOUT / "bench/configs/mamba2-130m.json")
                     .read_text())
    arch = cfg["arch"]
    ok = dict(cfg, arch=dict(arch, meta_mode="fomaml", outer_lr=3e-4),
              job=["meta_mode", "outer_lr"])
    assert config_faults(ok, []) == []
    left_out = {k: v for k, v in arch.items() if k != "encoder_frames"}
    assert config_faults(dict(cfg, arch=left_out), []) == []
    for bad in (dict(cfg, arch=dict(arch, ssm_state=64)),
                dict(cfg, arch=dict(arch, ssm_state=64), job=["ssm_state"]),
                dict(cfg, arch=dict(arch, meta_mode="fomaml")),
                dict(cfg, arch={k: v for k, v in arch.items()
                                if k != "ssm_state"}),
                dict(cfg, arch=dict(arch, extra=1))):
        assert config_faults(bad, []), bad


TOY_FAMILY = '''"""A model family the benchmark has not seen: the program's MoE model at a
small size, whose expert weights are stacked (experts, d, f)."""


def loss(ops, params, tokens, labels, cfg):
    raise NotImplementedError("no reference is run here")


def forward_flops_per_token(arch, seq):
    d, f, k = arch["d_model"], arch["moe_d_ff"], arch["experts_per_token"]
    return float(arch["num_layers"] * 6 * d * f * k + 2 * d * arch["vocab_size"])


def fan_in(name, core_shape):
    if name in ("w1", "w2", "w3") and len(core_shape) == 3:
        return core_shape[1]
    return None
'''

TOY_SCRIPT = """
import json, pathlib, sys
root = pathlib.Path.cwd()
sys.path[:0] = [str(root), str(root / "src")]
import jax
import numpy as np
from bench import flops, harness
from bench.weights import seed_data
cell = harness.load_cell("toy.tiny")
prog = harness.build_program(cell, jax.devices()[:1])
b = prog.bundle
experts = prog.make_params(seed_data(2**33 + 5))["segments"][1][0]["ffn"]
print(json.dumps({
    "flops": flops.meta_step_flops(cell.config["reference"], prog.arch,
                                   K=b.K, T=b.T, tb=b.tb, seq=32),
    "std": {n: float(np.std(np.asarray(experts[n], np.float32)))
            for n in ("w1", "w2", "router")},
    "arch": prog.arch}))
"""


def toy_config(name: str) -> dict:
    """The toy family's configuration file: the repository's
    DeepSeek-V2-Lite at its small size (``ArchConfig.reduced``) with a
    ``maml``/Adam job set apart from it."""
    from repro.configs import get_config
    repo = get_config("deepseek-v2-lite-16b")
    small = dataclasses.asdict(repo.reduced())
    arch = dict(small, meta_mode="maml", outer_optimizer="adam")
    del arch["encoder_frames"]                 # left out: the default
    reduced = sorted(k for k, v in small.items()
                     if v != getattr(repo, k) and k not in JOB_KEYS)
    return {"name": name, "base": repo.name, "reference": "toy_moe",
            "reduced": reduced,
            "published": {k: getattr(repo, k) for k in reduced},
            "job": ["meta_mode", "outer_optimizer", "remat"], "arch": arch}


def test_a_model_family_is_added_with_files_only(tmp_path):
    """A family the benchmark has not seen joins by files alone: its
    module beside the references gives the FLOP count and a fan-in rule for
    its stacked expert weights, and its configuration sets a job apart from
    the repository's.  The cell loads, builds on one device and counts its
    FLOPs, and no file the benchmark had changes."""
    from repro.configs import get_config
    root = tmp_path / "checkout"
    shutil.copytree(CHECKOUT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (root / "src").symlink_to(CHECKOUT / "src")
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    cfg = toy_config("toy")
    small, reduced = cfg["arch"], cfg["reduced"]
    repo = get_config(cfg["base"])
    assert config_faults(cfg, reduced) == []
    (root / "bench/reference/toy_moe.py").write_text(TOY_FAMILY)
    (root / "bench/configs/toy.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/tiny.json").write_text(json.dumps(
        {"agents": 4, "layout": "stacked", "combine": "dense",
         "seq_len": 32, "global_batch": 8, "train_domains": 16,
         "branching": 8, "buckets": 16, "prefetch": 2}))
    (root / "bench/limits/toy.tiny.json").write_text(
        json.dumps({"grad_gap": 1}))
    b = json.loads(json.dumps(BENCHMARK))
    b["configs"].append({"name": "toy", "source": "test",
                         "file": "bench/configs/toy.json",
                         "reduced": reduced, "why": "test"})
    b["workloads"].append({"name": "toy.tiny", "config": "toy",
                           "traffic": "tiny", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    env = {**__import__("os").environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    done = subprocess.run([sys.executable, "-c", TOY_SCRIPT], cwd=root,
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    got = json.loads(done.stdout.strip().splitlines()[-1])
    d, f = small["d_model"], small["moe_d_ff"]
    per_token = 6 * d * f * 2 * small["num_layers"] + 2 * d * 512
    assert got["flops"] == 12 * 4 * 1 * 1 * 32 * per_token
    # experts (4, d, f) draw over d, not over the 4 experts; the router
    # (d, experts) keeps the default rule
    assert got["std"]["w1"] == pytest.approx(d ** -0.5, rel=0.05)
    assert got["std"]["w2"] == pytest.approx(f ** -0.5, rel=0.05)
    assert got["std"]["router"] == pytest.approx(d ** -0.5, rel=0.1)
    assert got["arch"]["meta_mode"] == "maml" != repo.meta_mode
    assert got["arch"]["encoder_frames"] == 0
    assert all(p.read_bytes() == data for p, data in before.items())


# ---------------------------------------------------------------------------
# Whole runs of a small cell, the chip check skipped
# ---------------------------------------------------------------------------

# Limits of the small cell, set from its readings on the CPU (seeds 7-9):
# the program reads at most 5.9e-4 on the losses, 2.5e-3 on grad_gap and
# 6.2e-5 on change_gap; the control reads 7.0e-3 or more on the losses and
# 1.9e-2 or more on grad_gap.
TINY_LIMITS = {"loss_gap": 2e-3, "first_loss_gap": 2e-3, "grad_gap": 8e-3,
               "change_gap": 0.01}

def _tiny_root(tmp: pathlib.Path, layout: str) -> pathlib.Path:
    """A checkout holding the benchmark and one small qwen2 cell."""
    from repro.configs import get_config
    root = tmp / f"tiny_{layout}"
    shutil.copytree(CHECKOUT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    arch = dataclasses.asdict(get_config("qwen2-1.5b").reduced())
    (root / "bench/configs/tiny.json").write_text(json.dumps(
        {"name": "tiny", "reference": "qwen2", "arch": arch}))
    (root / "bench/traffic/tiny.json").write_text(json.dumps(
        {"agents": 4, "layout": layout,
         "combine": "dense" if layout == "stacked" else "mesh_sparse_dynamic",
         "seq_len": 32, "global_batch": 8, "train_domains": 16,
         "branching": 8, "buckets": 16, "prefetch": 2}))
    (root / "bench/limits/tiny.tiny.json").write_text(json.dumps(TINY_LIMITS))
    b = json.loads(json.dumps(BENCHMARK))
    b["workloads"].append({"name": "tiny.tiny", "config": "tiny",
                           "traffic": "tiny",
                           "chips": 4 if layout == "mesh" else 1,
                           "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return root


def _run(root, seed=7, fault=None):
    import jax
    cell = harness.load_cell("tiny.tiny", root=root)
    if fault is None:
        return harness.run_cell(cell, jax.devices(), seed=seed, seconds=0.2,
                                trace=False, t_start=0.0, peaks=None)
    with faults.planted(fault):
        return harness.run_cell(cell, jax.devices(), seed=seed, seconds=0.2,
                                trace=False, t_start=0.0, peaks=None)


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return _tiny_root(tmp_path_factory.mktemp("bench"), "stacked")


def test_small_cell_is_correct_and_prints_its_checks(tiny):
    out = _run(tiny)
    assert set(out["checks"]) == set(TINY_LIMITS)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert set(out["metrics"]) == {"meta_tokens_per_s", "setup_s"}
    assert out["device"]["count"] == 1


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_planted_faults_read_incorrect(tiny, fault):
    out = _run(tiny, fault=fault)
    assert not out["correct"], out["checks"]


@pytest.fixture(scope="module")
def tiny_gaps(tiny):
    """The small cell's numbers: the program on seed 7, each planted fault
    on seed 7, and the control on seeds 7-9."""
    import jax
    cell = harness.load_cell("tiny.tiny", root=tiny)
    refs = {}   # the reference imports nothing of the program: one a seed

    def reference(prog, tr, seed):
        if seed not in refs:
            refs[seed] = harness.reference_readings(prog, tr, seed)
        return refs[seed]

    def program_gaps(seed=7):
        prog = harness.build_program(cell, jax.devices())
        tr = harness.traffic_for(prog, seed)
        with harness.pipeline(prog, tr) as pipe:
            _, got, _ = harness.first_steps(prog, pipe, seed)
        return harness.compare(got, reference(prog, tr, seed))

    gaps = {"program": [program_gaps()]}
    for fault in faults.FAULTS:
        with faults.planted(fault):
            gaps[fault] = [program_gaps()]
    prog = harness.build_program(cell, jax.devices())
    gaps["control"] = []
    for seed in (7, 8, 9):
        tr = harness.traffic_for(prog, seed)
        gaps["control"].append(harness.compare(
            harness.reference_readings(prog, tr, seed, quant=True),
            reference(prog, tr, seed)))
    return gaps


def test_control_reads_incorrect(tiny_gaps):
    """The reference one precision below, in the program's place, fails
    the small cell's limits on every seed."""
    gaps = tiny_gaps["control"]
    assert all(not harness.judge(g, TINY_LIMITS)[0] for g in gaps), gaps


@pytest.mark.parametrize("cell", [w["name"] for w in BENCHMARK["workloads"]])
def test_each_cells_numbers_catch_the_faults_and_the_control(tiny_gaps, cell):
    """Judged by the numbers that ``cell`` compares, at the small cell's
    limits: the sound program passes, and every planted fault and the
    control fail on every seed."""
    limits = {n: TINY_LIMITS[n] for n in harness.load_cell(cell).limits}
    assert harness.judge(tiny_gaps["program"][0], limits)[0], tiny_gaps
    for kind in (*faults.FAULTS, "control"):
        for g in tiny_gaps[kind]:
            assert not harness.judge(g, limits)[0], (kind, g, limits)


def test_control_products_are_float8_in_every_pass():
    """The control's products round both operands to float8 in the forward
    pass, in the backward pass, and in the derivative of the backward pass,
    also through a scan over layers."""
    import jax
    import jax.numpy as jnp
    from bench.reference import ops

    key = iter(jax.random.split(jax.random.key(0), 4))
    w = jax.random.normal(next(key), (3, 16, 16)) / 4
    x = jax.random.normal(next(key), (2, 8, 16))
    v = jax.random.normal(next(key), w.shape)

    def rounded(a, dims, fmt=ops.E4M3):
        return ops.fp8_round(a, dims, fmt)

    y = ops.fp8_einsum("rsd,de->rse", x, w[0])
    assert jnp.array_equal(y, jnp.einsum(
        "rsd,de->rse", rounded(x, (2,)), rounded(w[0], (0,)),
        precision=jax.lax.Precision.HIGHEST))
    ct = jax.random.normal(next(key), y.shape)
    _, back = jax.vjp(lambda x: ops.fp8_einsum("rsd,de->rse", x, w[0]), x)
    assert jnp.array_equal(back(ct)[0], jnp.einsum(
        "rse,de->rsd", rounded(ct, (2,), ops.E5M2), rounded(w[0], (1,)),
        precision=jax.lax.Precision.HIGHEST))

    def loss(quant, unrolled):
        o = ops.Ops(quant)
        block = lambda p, h: jnp.tanh(o.einsum("rsd,de->rse", h, p))

        def f(w):
            if unrolled:
                h = x
                for p in w:
                    h = block(p, h)
            else:
                h = ops.scan_layers(block, x, w)
            return jnp.sum(h * h)
        return f

    def hvp(f):
        _, back = jax.vjp(jax.grad(f), w)
        return back(v)[0]

    exact = hvp(loss(False, True))
    scanned, unrolled = hvp(loss(True, False)), hvp(loss(True, True))
    gap = lambda a: float(jnp.linalg.norm(a - exact) / jnp.linalg.norm(exact))
    # float8 moves the second-order pass by percents either way; the scan
    # and the unrolled loop differ only where a rounding flips
    assert 0.01 < gap(scanned) < 0.5 and 0.01 < gap(unrolled) < 0.5
    assert float(jnp.linalg.norm(scanned - unrolled)) < \
        float(jnp.linalg.norm(scanned - exact))


@pytest.mark.parametrize("entry", BENCHMARK["configs"],
                         ids=lambda c: c["name"])
def test_weights_of_each_configuration_are_pinned(entry):
    """The digest of the configuration's weights at the repository's small
    size (``ArchConfig.reduced``), two agents, seed 2**40 + 15, as the
    benchmark drew them before a family could give its own fan-in rule."""
    import hashlib

    import jax
    import jax.numpy as jnp
    from bench.weights import leaf_name, make_params, seed_data
    from repro.configs import get_config
    from repro.models.transformer import build_model

    cfg = json.loads((CHECKOUT / entry["file"]).read_text())
    model = build_model(get_config(cfg["base"]).reduced())
    shapes = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    abstract = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((2,) + s.shape, jnp.bfloat16), shapes)
    got = jax.jit(lambda k: make_params(abstract, k,
                                        family=cfg["reference"]))(
        seed_data(2**40 + 15))
    digest = hashlib.sha256()
    for path, x in jax.tree_util.tree_flatten_with_path(got)[0]:
        digest.update(leaf_name(path).encode())
        digest.update(np.asarray(x.astype(jnp.float32)).tobytes())
    found = digest.hexdigest()
    assert found == pinned("configs", entry["name"], "weights_sha256", found)


def test_mamba2_mixer_weights_follow_the_published_init():
    """Uniform within 1/sqrt(fan_in), the output projection also over
    sqrt(layers); other weights stay normal."""
    import jax
    import jax.numpy as jnp
    from bench.weights import make_params, seed_data

    K, L, d, H, P, cw = 4, 6, 64, 4, 16, 4
    spec = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    tree = {"segments": [[{"mamba": {
        "w_x": spec(K, L, d, H, P), "conv_x": spec(K, L, cw, H, P),
        "w_out": spec(K, L, H, P, d)}, "mlp": {"w1": spec(K, L, d, 256)}}]]}
    got = make_params(tree, seed_data(2**40 + 3))
    (layer,) = got["segments"][0]
    for x, bound in ((layer["mamba"]["w_x"], d ** -0.5),
                     (layer["mamba"]["conv_x"], cw ** -0.5),
                     (layer["mamba"]["w_out"], (H * P * L) ** -0.5)):
        x = np.asarray(x)
        assert np.abs(x).max() <= bound
        assert np.std(x) == pytest.approx(bound / np.sqrt(3), rel=0.02)
    w1 = np.asarray(layer["mlp"]["w1"])
    assert np.abs(w1).max() > d ** -0.5        # normal, not uniform
    assert np.std(w1) == pytest.approx(d ** -0.5, rel=0.02)


def test_mamba2_reference_blocks_agree_with_one_piece():
    """The reference's SSD, computed in blocks of output positions, is the
    quadratic form of the paper computed over the whole sequence at once."""
    import jax
    import jax.numpy as jnp
    from bench.reference import mamba2, ops

    R, L, H, P, G, N = 1, 2 * mamba2.Q_BLOCK, 2, 4, 1, 8
    key = iter(jax.random.split(jax.random.key(1), 5))
    x = jax.random.normal(next(key), (R, L, H, P))
    dt = jax.nn.softplus(jax.random.normal(next(key), (R, L, H)) - 3)
    A = -jnp.exp(jax.random.normal(next(key), (H,)))
    B = jax.random.normal(next(key), (R, L, G, N))
    C = jax.random.normal(next(key), (R, L, G, N))

    cs = jnp.cumsum(dt * A, axis=1)
    seg = cs[:, :, None, :] - cs[:, None, :, :]
    causal = jnp.tril(jnp.ones((L, L), bool))[None, :, :, None]
    scores = jnp.einsum("rthn,rshn->rtsh", jnp.repeat(C, H, 2),
                        jnp.repeat(B, H, 2), precision="highest") \
        * jnp.exp(jnp.where(causal, seg, -jnp.inf)) * dt[:, None]
    whole = jnp.einsum("rtsh,rshp->rthp", scores, x, precision="highest")
    blocked = mamba2._ssd(ops.Ops(), x, dt, A, B, C)
    assert float(jnp.max(jnp.abs(blocked - whole))) \
        <= 1e-5 * float(jnp.max(jnp.abs(whole)))


MESH_SCRIPT = """
import json, pathlib, sys
sys.path[:0] = [{checkout!r}, {src!r}, {tests!r}]
import test_bench as t
root = pathlib.Path({root!r})
print(json.dumps([t._run(root)["correct"],
                  t._run(root, fault="no_exchange")["correct"]]))
"""


def test_mesh_cell_on_four_virtual_devices(tmp_path):
    """The one-agent-per-chip path, with and without its exchange."""
    import os
    root = _tiny_root(tmp_path, "mesh")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    script = MESH_SCRIPT.format(checkout=str(CHECKOUT),
                                src=str(CHECKOUT / "src"),
                                tests=str(pathlib.Path(__file__).parent),
                                root=str(root))
    done = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    assert json.loads(done.stdout.strip().splitlines()[-1]) == [True, False]


# ---------------------------------------------------------------------------
# The command refuses to run without a TPU
# ---------------------------------------------------------------------------

def _command(cwd, *extra):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         BENCHMARK["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0", *extra], cwd=cwd, capture_output=True, text=True,
        timeout=300, env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})


def test_command_exits_without_a_tpu():
    done = _command(CHECKOUT)
    assert done.returncode != 0 and done.stdout.strip() == ""
    assert "no TPU" in done.stderr


def test_command_exits_in_a_bare_checkout(tmp_path):
    shutil.copytree(CHECKOUT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(CHECKOUT / "BENCHMARK.json", tmp_path)
    done = _command(tmp_path)
    assert done.returncode != 0 and done.stdout.strip() == ""


def test_command_exits_on_a_device_kind_without_peaks(monkeypatch, capsys):
    import jax
    from bench import run

    class Chip:
        platform, device_kind = "tpu", "TPU v0 unknown"

    monkeypatch.setattr(jax, "devices", lambda *a: [Chip()] * 4)
    assert run.main(["--workload", BENCHMARK["workloads"][0]["name"],
                     "--seed", "1", "--seconds", "1", "--trace", "0"]) != 0
    assert capsys.readouterr().out == ""


def test_traffic_is_a_function_of_seed_and_step():
    from bench.traffic import MarkovTraffic
    params = json.loads((CHECKOUT / "bench/traffic/ring4.s1024.json")
                        .read_text())
    params["seq_len"] = 16
    a = MarkovTraffic(params, vocab_size=1000, K=4, T=1, tb=1, seed=2**40 + 1)
    b = MarkovTraffic(params, vocab_size=1000, K=4, T=1, tb=1, seed=2**40 + 1)
    c = MarkovTraffic(params, vocab_size=1000, K=4, T=1, tb=1, seed=1)
    s0, q0 = a.sample(0)
    assert s0["tokens"].shape == (4, 1, 1, 16)
    assert np.array_equal(s0["tokens"], b.sample(0)[0]["tokens"])
    assert not np.array_equal(s0["tokens"], c.sample(0)[0]["tokens"])
    assert not np.array_equal(s0["tokens"], a.sample(1)[0]["tokens"])
    assert np.array_equal(s0["labels"][..., :-1], s0["tokens"][..., 1:])
    assert a.tokens_per_step == 4 * 2 * 16
    rows = np.concatenate([s0["tokens"].reshape(4, -1),
                           q0["tokens"].reshape(4, -1)])
    assert len({r.tobytes() for r in rows}) == 8


def test_limits_sit_between_the_program_and_the_control():
    from bench import calibrate
    rows = [{"kind": "program", "seed": s, "loss_gap": 1e-3 * s,
             "first_loss_gap": 1e-4, "grad_gap": 0.5, "change_gap": 1e-4}
            for s in (1, 2)]
    rows += [{"kind": "control", "seed": 1, "loss_gap": 3e-3,
              "first_loss_gap": 1e-2, "grad_gap": 0.6, "change_gap": 1e-4},
             {"kind": "fault:no_exchange", "seed": 1, "loss_gap": 2e-3,
              "first_loss_gap": 1e-4, "grad_gap": 0.5, "change_gap": 0.5}]
    got = calibrate.propose(rows)
    # loss_gap: the control reads under 3x the lower reading, the fault
    # under 10x; grad_gap: nothing reads 3x its lower reading
    assert set(got) == {"first_loss_gap", "change_gap"}
    assert got["first_loss_gap"]["upper"] == 1e-2
    assert got["first_loss_gap"]["limit"] == pytest.approx(2.2e-3)
    assert got["change_gap"]["upper"] == 0.5   # the fault, below 1 unchanged
    for v in got.values():
        assert v["lower"] < v["limit"] < v["upper"]
