"""Async sharded meta-batch pipeline.

Episode generation is host-side python/numpy (Markov chains, prototype
mixing) and used to run *between* jitted steps — the device sat idle while
the host sampled, and the host sat idle while the device stepped.
:class:`MetaBatchPipeline` moves sampling (and the ``device_put`` onto the
train step's ``NamedSharding``s, via ``prepare``) onto a background thread
so episode ``i+1`` is generated and transferred while the device runs step
``i``.  The jitted step releases the GIL inside XLA, so the overlap is real
even on a single host.

``depth=0`` is the synchronous fallback (no thread, sample-on-demand) used
by tests and debugging; any depth produces the identical batch sequence
because ``TaskSource.sample(step)`` is a pure function of ``step``.
"""
from __future__ import annotations

import queue
import threading
from typing import Any, Callable

import jax

from repro.data.episodes import Episode, TaskSource

__all__ = ["MetaBatchPipeline"]

_POLL_S = 0.05


class MetaBatchPipeline:
    """Iterator of device-ready meta-batches drawn from a :class:`TaskSource`.

    Args:
      source:     any TaskSource; ``source.sample(step)`` is called for
                  ``step = start_step, start_step+1, ...``.
      depth:      prefetch buffer depth; 0 = synchronous (no thread).
      prepare:    ``Episode -> batch`` transform run on the producer side
                  (flattening, ``jax.device_put`` with shardings, ...).
                  Default: the Episode itself.  With ``stack > 1`` it
                  receives a *list* of ``stack`` consecutive Episodes.
      start_step: first step index (e.g. a restored checkpoint's step).
      stack:      meta-batches per item: each ``next()`` yields ``stack``
                  consecutive steps' episodes (as one ``prepare``d item) —
                  the superstep driver's per-dispatch input.  The sample
                  sequence is identical to ``stack=1``; only the grouping
                  changes.
    """

    def __init__(self, source: TaskSource, *, depth: int = 2,
                 prepare: Callable[[Episode], Any] | None = None,
                 start_step: int = 0, stack: int = 1):
        if stack < 1:
            raise ValueError(f"stack must be >= 1, got {stack}")
        self.source = source
        self.depth = depth
        self.stack = stack
        self._prepare = prepare if prepare is not None else (lambda ep: ep)
        self._step = start_step
        self._exc: BaseException | None = None
        self._thread = None
        if depth > 0:
            self._queue: queue.Queue = queue.Queue(maxsize=depth)
            self._stop = threading.Event()
            self._thread = threading.Thread(
                target=self._worker, name="meta-batch-prefetch", daemon=True)
            self._thread.start()

    # --- producer ----------------------------------------------------------

    def _sample_item(self, step: int) -> Any:
        """One prepared item: a single episode, or ``stack`` consecutive
        episodes handed to ``prepare`` as a list; a profiler host span
        ``dif.pipeline.produce`` on the producing thread."""
        with jax.profiler.TraceAnnotation("dif.pipeline.produce"):
            if self.stack == 1:
                return self._prepare(self.source.sample(step))
            return self._prepare([self.source.sample(step + j)
                                  for j in range(self.stack)])

    def _worker(self) -> None:
        step = self._step
        try:
            while not self._stop.is_set():
                item = self._sample_item(step)
                step += self.stack
                while not self._stop.is_set():
                    try:
                        self._queue.put(item, timeout=_POLL_S)
                        break
                    except queue.Full:
                        continue
        except BaseException as e:  # surfaced to the consumer in __next__
            self._exc = e
            self._stop.set()

    # --- consumer ----------------------------------------------------------

    def __iter__(self) -> "MetaBatchPipeline":
        return self

    def __next__(self) -> Any:
        if self.depth <= 0:
            item = self._sample_item(self._step)
            self._step += self.stack
            return item
        while True:
            try:
                item = self._queue.get(timeout=_POLL_S)
            except queue.Empty:
                if self._exc is not None:
                    raise RuntimeError(
                        "MetaBatchPipeline prefetch worker failed"
                    ) from self._exc
                if self._thread is None or not self._thread.is_alive():
                    raise StopIteration   # stop() was called / worker gone
                continue
            self._step += self.stack
            return item

    @property
    def step(self) -> int:
        """Index of the next batch the consumer will receive."""
        return self._step

    # --- lifecycle ---------------------------------------------------------

    def stop(self) -> None:
        if self._thread is None:
            return
        self._stop.set()
        while True:  # drain so a blocked put() observes the stop event
            try:
                self._queue.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout=5.0)
        self._thread = None
        while True:  # a blocked put() may have landed one last item
            try:
                self._queue.get_nowait()
            except queue.Empty:
                break

    def __enter__(self) -> "MetaBatchPipeline":
        return self

    def __exit__(self, *exc_info) -> None:
        self.stop()
