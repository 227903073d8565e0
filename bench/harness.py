"""One benchmark run of a Dif-MAML meta-training cell.

The cell (a ``workloads`` entry of ``BENCHMARK.json``) names a
configuration and a traffic mix.  Nothing here names a cell, a
configuration, a model family or a metric, so a later change adds one with
new files and ``BENCHMARK.json`` entries alone, and edits no file the
benchmark has:

* a configuration, ``bench/configs/<config>.json``, holds ``arch``, the
  keyword arguments of the program's ``ArchConfig`` (a key left out takes
  its default there), ``base``, the repository's configuration it is cut
  from, ``reduced``, the keys of the model's shape changed from that, and
  ``job``, the keys of ``ArchConfig``'s meta-learning block that the cell
  sets apart from it; ``reference`` names its model family;
* a model family is one file, ``bench/reference/<family>.py``: its plain
  reference ``loss``, its ``forward_flops_per_token(arch, seq)`` by the
  conventions of ``bench/flops.py``, and, where its weights need one, a
  ``fan_in(name, core_shape)`` rule for ``bench/weights.py``;
* a traffic mix is ``bench/traffic/<traffic>.json``, parameters of the one
  generator, ``bench/traffic.py``;
* a cell's limits are ``bench/limits/<cell>.json``, from
  ``bench/calibrate.py``;
* the tests' pins: ``bench/tests/pins/cells/<cell>.json``
  (``{"step_flops": ...}``, the FLOPs a meta-step) and
  ``bench/tests/pins/configs/<config>.json`` (``{"weights_sha256": ...}``,
  the digest of its weights at the repository's small size); a test whose
  pin file is missing fails with the value to put there;
* a metric is read by ``bench/metrics/<metric>.py``, ``read(run) -> float |
  None``, which returns ``None`` where it finds nothing to read;
* ``BENCHMARK.json`` gains the ``configs``, ``workloads`` and metric
  entries, and the cell's name in the ``workloads`` list of each metric
  it already has that the cell reports (``step_mfu``,
  ``device_idle_share``, ...).

A reader gets a :class:`Run`: the window's times and step counts, the
model FLOPs a step and the chips' peaks, with ``--trace 1`` the trace's
summary and the device time by named scope, and in every run

* ``counters``: each entry of the metrics dict that the program's step
  returns, stacked over the window's steps (leading axis the step; the
  traced tail is the last ``traced_steps`` rows), so a counter the step
  adds reaches a reader with no edit here;
* ``arch``: ``ArchConfig``'s fields as built;
* ``traffic``: the resolved ``K``, ``T``, ``tb``, ``seq_len``, ``layout``
  and ``tokens_per_step``.

A run (:func:`run_cell`):

1. builds the program's meta step for the cell (``launch.steps.build_train``)
   on a mesh of the cell's chips, jitted with the state donated;
2. makes the state on the device from the seed in one jitted call: the
   benchmark's own weights (``weights.py``), the optimizer state at zero;
3. compiles the step, then drives it through its first ``CHECK_STEPS``
   steps with the program's input pipeline (``TrainBundle.make_pipeline``,
   prefetch thread running) fed by the benchmark's traffic; these steps warm every
   shape the window uses and give the readings that decide ``correct``;
4. measures a window of back-to-back steps, with no host sync but the last,
   keeping each step's metrics on the device;
   with ``--trace 1`` the profiler records the window's last second or so
   of steps, from a drained queue, as the host span ``bench.window``;
5. after the window, reads the peak memory, fetches the steps' metrics in
   one transfer, frees the program's state,
   with ``--trace 1`` splits the traced steps' device time by the
   program's named scopes (``bench/scopes.py``), and runs the plain
   reference over the same weights and batches.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import pathlib
import shutil
import statistics
import sys
import tempfile
import time

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
CHECKOUT = BENCH.parent
CHECK_STEPS = 3      # steps the reference follows
# With --trace 1 the profiler records the window's last steps: about this
# many seconds of them, and at least this many.
TRACE_S = 1.0
TRACE_MIN_STEPS = 3


# ---------------------------------------------------------------------------
# The files of a cell
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    metrics: list          # BENCHMARK.json metric entries this cell reports
    root: pathlib.Path = CHECKOUT


def _json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: pathlib.Path = CHECKOUT, trace: bool = False
              ) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files, and the
    metrics it reports: the end-to-end ones, or with ``trace`` the
    per-layer ones."""
    bench = _json(root / "BENCHMARK.json")
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if len(entries) != 1:
        raise KeyError(f"no workload {name!r} in {root / 'BENCHMARK.json'}")
    w = entries[0]
    here = root / "bench"
    metrics = [m for m in bench["per_layer" if trace else "end_to_end"]
               if name in m.get("workloads", [name])]
    return Cell(name=name, chips=int(w["chips"]),
                config=_json(here / "configs" / f"{w['config']}.json"),
                traffic=_json(here / "traffic" / f"{w['traffic']}.json"),
                limits=_json(here / "limits" / f"{name}.json"),
                metrics=metrics, root=root)


def load_reader(metric: str, root: pathlib.Path = CHECKOUT):
    """``read(run) -> float | None`` of ``bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}",
                                                  path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_peaks(kind: str) -> dict:
    """Published peaks of ``device_kind``; an unknown kind is an error."""
    table = _json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json "
                       f"({sorted(table)})")
    return table[kind]


# ---------------------------------------------------------------------------
# What a run gives the metric readers
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    setup_s: float
    window_s: float
    steps: int
    traced_steps: int                 # steps of the traced tail (--trace 1)
    tokens_per_step: int
    step_flops: float
    chips: int
    peaks: dict | None
    trace: object = None              # trace.Summary with --trace 1
    # With --trace 1: device ms a step by (step scope, model scope), mean
    # over the chips, and the producer thread's ms a step
    # (bench/scopes.py; None where no such span ran).
    scope_ms: dict | None = None
    produce_ms: float | None = None
    # The step's metrics dict, each entry stacked over the window's steps
    counters: dict | None = None
    arch: dict | None = None          # ArchConfig's fields as built
    # K, T, tb, seq_len, layout and tokens_per_step as resolved
    traffic: dict | None = None


class CompileClock:
    """Seconds the backend spent compiling, and how often, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax
        self.seconds, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event.endswith("backend_compile_duration"):
            self.seconds += duration
            self.count += 1


# ---------------------------------------------------------------------------
# The program under test
# ---------------------------------------------------------------------------

def _program():
    """The repository's modules; the benchmark needs its ``src``."""
    src = str(CHECKOUT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.configs.base import ArchConfig, InputShape
    from repro.data.episodes import Episode
    from repro.launch import steps
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_host_mesh
    return ArchConfig, InputShape, Episode, steps, enable_compile_cache, \
        make_host_mesh


class _Source:
    """The traffic as the program's ``TaskSource``."""

    def __init__(self, traffic, episode_cls):
        self.traffic, self._episode = traffic, episode_cls
        self.K, self.tasks_per_agent = traffic.K, traffic.T
        self.task_batch = traffic.tb

    def sample(self, step: int):
        support, query = self.traffic.sample(step)
        return self._episode(support, query, step=step)


@dataclasses.dataclass
class Program:
    """The compiled meta step with its bundle, traffic and state maker."""
    cell: Cell
    arch: dict                   # ArchConfig's fields, defaults filled in
    bundle: object
    mesh: object
    step: object                 # compiled (state, batch) -> (state, metrics)
    make_state: object           # jitted key data -> state
    make_params: object          # jitted key data -> stacked params (bf16)
    episode_cls: object
    compile_s: float


def build_program(cell: Cell, devices) -> Program:
    """Build and compile the cell's meta step on ``devices``."""
    import jax
    import jax.numpy as jnp

    from bench.weights import make_params

    ArchConfig, InputShape, Episode, steps, _, make_host_mesh = _program()
    cfg = ArchConfig(**cell.config["arch"])
    family = cell.config["reference"]
    tr = cell.traffic
    K = int(tr["agents"])
    devices = list(devices)[:cell.chips]
    if tr["layout"] == "mesh":
        mesh = make_host_mesh(model=1, agents=K, devices=devices)
    else:
        mesh = make_host_mesh(data=len(devices), devices=devices)
    shape = InputShape("bench", int(tr["seq_len"]), int(tr["global_batch"]),
                       "train")
    with mesh:
        bundle = steps.build_train(cfg, mesh, shape,
                                   combine_override=tr["combine"], agents=K)
    specs = bundle.state_specs

    def state_from(key_data):
        zeros = lambda t: jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), t)
        return specs._replace(step=jnp.zeros((), jnp.int32),
                              params=make_params(specs.params, key_data,
                                                 family=family),
                              opt_state=zeros(specs.opt_state))

    make_state = jax.jit(state_from, out_shardings=bundle.state_shardings)
    params0 = jax.jit(lambda k: make_params(specs.params, k,
                                            family=family),
                      out_shardings=bundle.state_shardings.params)
    jitted = jax.jit(bundle.step_fn, donate_argnums=(0,),
                     out_shardings=(bundle.state_shardings, None))
    state_in = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        specs, bundle.state_shardings)
    batch_in = {k: jax.ShapeDtypeStruct(
        (shape.global_batch, shape.seq_len), jnp.int32,
        sharding=bundle.batch_shardings[k]) for k in ("tokens", "labels")}
    t = time.perf_counter()
    with mesh:
        compiled = jitted.lower(state_in, batch_in).compile()
    return Program(cell, dataclasses.asdict(cfg), bundle, mesh, compiled,
                   make_state, params0, Episode, time.perf_counter() - t)


def traffic_for(prog: Program, seed: int):
    """The cell's traffic from ``seed``, at the bundle's (K, T, tb)."""
    from bench.traffic import MarkovTraffic
    b = prog.bundle
    return MarkovTraffic(prog.cell.traffic, vocab_size=b.cfg.vocab_size,
                         K=b.K, T=b.T, tb=b.tb, seed=seed)


def pipeline(prog: Program, traffic):
    """The program's prefetching input pipeline over ``traffic``."""
    return prog.bundle.make_pipeline(_Source(traffic, prog.episode_cls),
                                     depth=int(prog.cell.traffic["prefetch"]))


# ---------------------------------------------------------------------------
# Readings of the first steps, program side
# ---------------------------------------------------------------------------

def _norms(tree, scale: float = 1.0) -> dict:
    import jax
    import jax.numpy as jnp
    from bench.weights import leaf_name
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {leaf_name(p): jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) * scale for p, x in flat}


def first_steps(prog: Program, pipe, seed: int):
    """Run the cell's first ``CHECK_STEPS`` steps through the compiled step
    and the pipeline.  Returns ``(state, readings, step_s)``: the state after them,
    the program's readings (per-agent loss of each step, the first gradient
    per leaf from Adam's first moment after step 1, each leaf's change over
    the steps) and the seconds of the last step, waited for."""
    import jax
    from bench.reference import ADAM_B1
    from bench.weights import seed_data

    key = seed_data(seed)
    with prog.mesh:
        state = prog.make_state(key)
        grad_norms = jax.jit(lambda mu: _norms(mu, 1.0 / (1 - ADAM_B1)))
        change_norms = jax.jit(lambda p, p0: _norms(jax.tree.map(
            lambda a, b: a.astype("float32") - b.astype("float32"), p, p0)))
        losses, step_s = [], 0.0
        for i in range(CHECK_STEPS):
            batch = next(pipe)
            t = time.perf_counter()
            state, metrics = prog.step(state, batch)
            losses.append(metrics["per_agent_loss"])
            if i == 0:
                grads = grad_norms(state.opt_state.mu)
            jax.block_until_ready(state)
            step_s = time.perf_counter() - t
        change = change_norms(state.params, prog.make_params(key))
        readings = {"loss": [[float(x) for x in np.asarray(l)]
                             for l in losses],
                    "grad": {n: float(x) for n, x in grads.items()},
                    "change": {n: float(x) for n, x in change.items()}}
        del change
    return state, readings, step_s


def reference_readings(prog: Program, traffic, seed: int,
                       quant: bool = False) -> dict:
    """The plain reference over the same weights and the same batches."""
    import jax
    import jax.numpy as jnp
    from bench import reference
    from bench.weights import seed_data

    arch = prog.arch
    if arch["topology"] != "ring" or arch["meta_mode"] != "maml" \
            or arch["inner_steps"] != 1 or arch["outer_optimizer"] != "adam":
        raise ValueError("the reference runs ring/maml/1 inner step/adam")
    key = seed_data(seed)
    batches = [traffic.sample(s) for s in range(CHECK_STEPS)]
    dev = jax.devices()[0]
    with jax.default_device(dev):
        return reference.readings(
            prog.cell.config["reference"], arch,
            lambda: jax.device_put(prog.make_params(key), dev), batches,
            A=reference.ring_metropolis(traffic.K), inner_lr=arch["inner_lr"],
            outer_lr=arch["outer_lr"], quant=quant)


# ---------------------------------------------------------------------------
# The comparison that decides ``correct``
# ---------------------------------------------------------------------------

SMALL_GRAD = 1e-3   # leaves under this share of the median leaf's gradient


def compare(prog_r: dict, ref_r: dict) -> dict:
    """Numbers that can be compared, each a gap against the reference; a
    cell compares those its limits file names:

    * ``loss_gap``: the largest relative gap of an agent's loss at a step;
    * ``first_loss_gap``: the same over the first step alone, the query
      loss at the parameters adapted from the seed's weights;
    * ``grad_gap``: the worst leaf's gap between the norms of the first
      gradient, over the larger of the reference leaf's norm and the median
      leaf's;
    * ``change_gap``: the same for each leaf's change over the steps, with
      leaves whose reference gradient is under ``SMALL_GRAD`` of the median
      leaf's left out (they move by round-off alone).
    """
    def loss_gap(steps):
        return max(abs(p - r) / abs(r) for ps, rs in steps
                   for p, r in zip(ps, rs))

    def worst(prog, ref, leaves):
        med = statistics.median(ref[n] for n in leaves)
        return max(abs(prog[n] - ref[n]) / max(ref[n], med) for n in leaves)

    g_ref = ref_r["grad"]
    g_med = statistics.median(g_ref.values())
    moving = [n for n in g_ref if g_ref[n] >= SMALL_GRAD * g_med]
    steps = list(zip(prog_r["loss"], ref_r["loss"]))
    return {"loss_gap": loss_gap(steps),
            "first_loss_gap": loss_gap(steps[:1]),
            "grad_gap": worst(prog_r["grad"], g_ref, list(g_ref)),
            "change_gap": worst(prog_r["change"], ref_r["change"], moving)}


def judge(gaps: dict, limits: dict) -> tuple[bool, dict]:
    checks = {n: {"value": gaps.get(n, math.nan), "limit": limits[n]}
              for n in limits}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks


# ---------------------------------------------------------------------------
# A whole run
# ---------------------------------------------------------------------------

def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def run_cell(cell: Cell, devices, *, seed: int, seconds: float, trace: bool,
             t_start: float, peaks: dict | None) -> dict:
    """One run; returns the result line's object, ``checks`` last."""
    import jax

    from bench import flops, scopes
    from bench import trace as tracing

    enable_cache = _program()[4]
    enable_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    clock = CompileClock()
    t_build = time.perf_counter()
    prog = build_program(cell, devices)
    tr = traffic_for(prog, seed)
    step_metrics = []
    with pipeline(prog, tr) as pipe:
        t_first = time.perf_counter()
        state, prog_r, step_s = first_steps(prog, pipe, seed)
        first_steps_s = time.perf_counter() - t_first
        n = max(1, round(seconds / step_s))
        traced = min(n, max(TRACE_MIN_STEPS, math.ceil(TRACE_S / step_s))) \
            if trace else 0
        tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
        setup_s = time.perf_counter() - t_start
        compiles_before = clock.count
        with prog.mesh, contextlib.ExitStack() as tail:
            t0 = time.perf_counter()
            for i in range(n):
                if i == n - traced:
                    # the traced tail starts from a drained queue
                    jax.block_until_ready(state)
                    jax.profiler.start_trace(tdir)
                    tail.callback(jax.profiler.stop_trace)
                    tail.enter_context(_annotate("bench.window"))
                with _annotate("bench.input_wait"):
                    batch = next(pipe)
                with _annotate("bench.dispatch"):
                    state, metrics = prog.step(state, batch)
                step_metrics.append(metrics)
            with _annotate("bench.drain"):
                jax.block_until_ready(state)
            window_s = time.perf_counter() - t0
    window_compiles = clock.count - compiles_before
    used = list(devices)[:cell.chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    fetched = jax.device_get(step_metrics)
    counters = {k: np.stack([np.asarray(m[k]) for m in fetched])
                for k in fetched[0]}
    failed = int(np.sum(~np.isfinite(counters["loss"])))
    del state, step_metrics, fetched
    summary = scoped = None
    if trace:
        path = tracing.find_xplane(tdir)
        loaded, spans = tracing.load(path), scopes.program_spans(path)
        shutil.rmtree(tdir, ignore_errors=True)
        summary = tracing.summarize(loaded)
        t_scopes = time.perf_counter()
        scoped = scopes.per_step(loaded, spans, prog.step.as_text(), traced)
        scoped["notes"]["scopes_s"] = time.perf_counter() - t_scopes
        del loaded, spans
    run = Run(setup_s=setup_s, window_s=window_s, steps=n,
              traced_steps=traced,
              tokens_per_step=tr.tokens_per_step,
              step_flops=flops.meta_step_flops(
                  cell.config["reference"], prog.arch, K=tr.K, T=tr.T,
                  tb=tr.tb, seq=tr.seq_len),
              chips=cell.chips, peaks=peaks, trace=summary,
              scope_ms=scoped["scope_ms"] if scoped else None,
              produce_ms=scoped["produce_ms"] if scoped else None,
              counters=counters, arch=prog.arch,
              traffic={"K": tr.K, "T": tr.T, "tb": tr.tb,
                       "seq_len": tr.seq_len,
                       "layout": cell.traffic["layout"],
                       "tokens_per_step": tr.tokens_per_step})
    metrics_out = {}
    for m in cell.metrics:
        value = load_reader(m["name"], cell.root)(run)
        if value is not None:
            metrics_out[m["name"]] = {"value": value, "unit": m["unit"]}
    t_ref = time.perf_counter()
    ref_r = reference_readings(prog, tr, seed)
    reference_s = time.perf_counter() - t_ref
    gaps = compare(prog_r, ref_r)
    correct, checks = judge(gaps, cell.limits)
    correct = correct and failed == 0
    d0 = used[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(used), "memory_peak_bytes": int(peak)}
    out = {"correct": correct, "attempted": n, "failed": failed,
           "metrics": metrics_out, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = {"device_ops": summary.top_ops,
                            "idle_gaps": summary.idle_gaps}
    out["notes"] = {"start_s": t_build - t_start,
                    "build_s": t_first - t_build, "compile_s": prog.compile_s,
                    "first_steps_s": first_steps_s,
                    "window_compiles": window_compiles, "steps": n,
                    "traced_steps": traced,
                    "window_s": window_s, "first_step_estimate_s": step_s,
                    "reference_s": reference_s}
    if scoped is not None:
        out["notes"].update(scoped["notes"])
    out["checks"] = checks
    return out
