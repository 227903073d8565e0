"""The meta step names its phases and the model its blocks.

``jax.named_scope`` puts ``dif.step.*`` (inner adaptation, outer gradient,
HVP, outer update, combine, the disagreement metric) and ``dif.model.*``
(mixer, feed-forward, vocabulary head) on the ``op_name`` of every op
compiled under them, and nothing else: the optimized module is the same
program with or without them.  The pipeline's producer thread marks each
meta-batch it makes with the profiler host span ``dif.pipeline.produce``.
"""
import contextlib
import dataclasses
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.configs.base import InputShape
from repro.launch import steps as S
from repro.launch.mesh import make_host_mesh

STEP = ["dif.step.inner_adapt", "dif.step.outer_grad", "dif.step.hvp",
        "dif.step.outer_update", "dif.step.combine",
        "dif.step.disagreement"]
ARCHS = ["qwen2-1.5b", "mamba2-130m"]
TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def model_scopes(arch):
    ffn = ["dif.model.ffn"] if arch.startswith("qwen2") else []
    return ["dif.model.mixer", *ffn, "dif.model.head"]


def compiled_step(arch, mesh):
    """Optimized HLO text of the reduced ``arch``'s meta step: K=4 agents
    stacked, dense combine, maml, remat as the benchmark runs it."""
    cfg = dataclasses.replace(get_config(arch).reduced(), remat=True)
    shape = InputShape("scopes", 32, 8, "train")
    with mesh:
        b = S.build_train(cfg, mesh, shape, combine_override="dense",
                          agents=4)
        state = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            b.state_specs, b.state_shardings)
        batch = {k: jax.ShapeDtypeStruct((8, 32), jnp.int32,
                                         sharding=b.batch_shardings[k])
                 for k in ("tokens", "labels")}
        return jax.jit(b.step_fn, donate_argnums=(0,)).lower(
            state, batch).compile().as_text()


def op_names(hlo_text):
    return re.findall(r'op_name="([^"]*)"', hlo_text)


def without_debug_info(hlo_text):
    """The module with its op metadata and source tables taken out and
    its instructions numbered in order of appearance."""
    lines, skip = [], False
    for line in hlo_text.splitlines():
        if line in TABLES:
            skip = True
        elif skip and not line[:1].isdigit():
            skip = False
        if not skip:
            lines.append(re.sub(r",? metadata=\{[^}]*\}", "", line))
    ids: dict = {}
    return re.sub(r"%([A-Za-z_][\w\-]*?)(?:\.\d+)?\b(?![\w.\-])",
                  lambda m: f"%{m.group(1)}#{ids.setdefault(m.group(0), len(ids))}",
                  "\n".join(lines))


class _NoScope(contextlib.ContextDecorator):
    def __init__(self, name):
        pass

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


@pytest.fixture(scope="module", params=ARCHS)
def steps(request):
    """``(arch, HLO text, HLO text with every named scope a no-op)``.

    The persistent compilation cache, which another test of the process
    may have turned on, is kept out: its key ignores metadata, so it would
    hand the build without scopes the module compiled with them."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        mesh = make_host_mesh(devices=jax.devices()[:1])
        text = compiled_step(request.param, mesh)
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(jax, "named_scope", _NoScope)
            plain = compiled_step(request.param, mesh)
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    return request.param, text, plain


def test_step_and_model_scopes_reach_the_compiled_module(steps):
    arch, text, plain = steps
    names = "\n".join(op_names(text))
    for scope in STEP + model_scopes(arch):
        assert scope in names, scope
    assert ("dif.model.ffn" in names) == arch.startswith("qwen2")
    assert "dif." not in "\n".join(op_names(plain))


def test_scopes_change_only_metadata(steps):
    _, text, plain = steps
    assert text != plain
    assert without_debug_info(text) == without_debug_info(plain)


def test_the_pipeline_marks_each_produced_meta_batch(tmp_path):
    from jax.profiler import ProfileData

    from repro.data import LMTaskSource, MetaBatchPipeline

    sampled = []

    class Counted(LMTaskSource):
        def sample(self, step):
            sampled.append(step)
            return super().sample(step)

    src = Counted(vocab_size=64, seq_len=8, K=2, tasks_per_agent=1,
                  task_batch=2, n_domains=4, seed=0)
    with jax.profiler.trace(str(tmp_path)):
        with MetaBatchPipeline(src, depth=2) as pipe:
            for _ in range(5):
                next(pipe)
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    spans = [e for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events
             if e.name == "dif.pipeline.produce"]
    assert len(sampled) >= 5
    assert len(spans) == len(sampled)
    assert all(e.duration_ns > 0 for e in spans)
