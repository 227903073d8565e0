"""Reduction of a profiler trace to the benchmark's device numbers.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  Its device
planes (``/device:TPU:<n>``) carry the ops that ran on the line ``XLA Ops``,
where a loop's op encloses the ops of its body, and the asynchronous ops in
flight on ``Async XLA Ops``; the host plane carries the harness's own spans,
named ``bench.<what>`` (``jax.profiler.TraceAnnotation``), on the same
clock.  An op is named as the trace names it, up to the `` = `` that starts
its HLO text (``%fusion.12 = bf16[...] fusion(...)`` is ``fusion.12``).
The window is the host span ``bench.window``; everything is clipped to it.

* busy: the union of the op intervals of a device; the idle share is one
  minus busy over the window, averaged over the devices;
* collectives: ops named as XLA's collectives, on either line; their time
  is the union of their intervals, and the exposed part is what of it no
  other op of that device covers;
* top ops: each op's self time (its interval less the ops it encloses),
  summed by name and averaged over the devices;
* idle gaps: the longest stretches of the window in which a device runs
  nothing, each labelled by the innermost harness span the host was in at
  its middle.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
COLLECTIVE = re.compile(r"(collective-permute|all-reduce|all-gather|"
                        r"reduce-scatter|all-to-all|collective-broadcast)")
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Ops:
    """One device's ops: ``names[idx[i]]`` ran over ``[start[i], end[i])``
    (ns)."""
    names: list
    idx: np.ndarray
    start: np.ndarray
    end: np.ndarray

    @classmethod
    def of(cls, ops) -> Ops:
        """From ``[(name, start, end)]``."""
        names = sorted({n for n, _, _ in ops})
        pos = {n: i for i, n in enumerate(names)}
        return cls(names, np.asarray([pos[n] for n, _, _ in ops], np.int64),
                   np.asarray([s for _, s, _ in ops], np.int64),
                   np.asarray([e for _, _, e in ops], np.int64))

    @classmethod
    def empty(cls) -> Ops:
        return cls([], *(np.zeros(0, np.int64),) * 3)

    def collective(self) -> np.ndarray:
        is_coll = np.asarray([bool(COLLECTIVE.search(n)) for n in self.names],
                             bool)
        return is_coll[self.idx] if len(self.idx) else np.zeros(0, bool)


@dataclasses.dataclass
class Trace:
    """Per device plane: its ops and its in-flight asynchronous ops; the
    harness's host spans ``[(name, start, end)]``; all in ns on one clock."""
    devices: dict          # plane name -> Ops
    asyncs: dict           # plane name -> Ops
    spans: list


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                   # mean over devices
    idle_share: float               # 0..1
    collective_s: float             # mean over devices
    collective_exposed_s: float     # mean over devices
    top_ops: list                   # [[name, seconds]] mean over devices
    idle_gaps: list                 # [[label, seconds]] longest first
    span_s: dict                    # bench.* host span -> total seconds
    span_n: dict                    # bench.* host span -> count
    n_devices: int


def find_xplane(directory: str) -> str:
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {directory},"
                                f" found {len(found)}")
    return found[0]


def short_name(name: str) -> str:
    return name.split(" = ", 1)[0].lstrip("%")


def _line_ops(line) -> Ops:
    short: dict = {}
    idx, start, dur = [], [], []
    for e in line.events:
        n = e.name
        i = short.get(n)
        if i is None:
            i = short[n] = len(short)
        idx.append(i)
        start.append(e.start_ns)
        dur.append(e.duration_ns)
    # several HLO texts may share a short name; give each short name one id
    shorts = sorted({short_name(n) for n in short})
    pos = {s: k for k, s in enumerate(shorts)}
    remap = np.asarray([pos[short_name(n)] for n in short], np.int64)
    start = np.asarray(start, np.int64)
    return Ops(shorts, remap[np.asarray(idx, np.int64)] if idx
               else np.zeros(0, np.int64), start,
               start + np.asarray(dur, np.int64))


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, asyncs, spans = {}, {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            devices[plane.name] = (_line_ops(lines[OPS_LINE])
                                   if OPS_LINE in lines else Ops.empty())
            asyncs[plane.name] = (_line_ops(lines[ASYNC_LINE])
                                  if ASYNC_LINE in lines else Ops.empty())
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events if e.name.startswith("bench.")]
    return Trace(devices, asyncs, spans)


def union(start: np.ndarray, end: np.ndarray) -> np.ndarray:
    """Merge [start, end) intervals into disjoint sorted ones, (n, 2)."""
    if len(start) == 0:
        return np.zeros((0, 2), np.int64)
    order = np.argsort(start, kind="stable")
    s, e = start[order], np.maximum.accumulate(end[order])
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > e[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, len(s) - 1)
    return np.stack([s[first], e[last]], axis=1)


def length(iv: np.ndarray) -> int:
    return int(np.sum(iv[:, 1] - iv[:, 0])) if len(iv) else 0


def covered(a: np.ndarray, b: np.ndarray) -> int:
    """Length of disjoint sorted intervals ``a`` that ``b`` (disjoint,
    sorted) covers."""
    total = 0
    for s, e in a if len(b) else ():
        j0 = np.searchsorted(b[:, 1], s, side="right")
        j1 = np.searchsorted(b[:, 0], e, side="left")
        if j1 > j0:
            seg = b[j0:j1]
            total += int(np.sum(np.minimum(seg[:, 1], e)
                                - np.maximum(seg[:, 0], s)))
    return total


def self_times(start: np.ndarray, end: np.ndarray) -> np.ndarray:
    """Each interval's length less the intervals it encloses directly (the
    ops of a loop body inside the loop's op)."""
    order = np.lexsort((-end, start))
    s, e = start[order].tolist(), end[order].tolist()
    own = [b - a for a, b in zip(s, e)]
    stack: list = []
    for i in range(len(s)):
        while stack and e[stack[-1]] < e[i] or stack and s[i] >= e[stack[-1]]:
            stack.pop()
        if stack:
            own[stack[-1]] -= e[i] - s[i]
        stack.append(i)
    out = np.empty(len(s), np.int64)
    out[order] = own
    return out


def _clip(ops: Ops, lo: int, hi: int) -> Ops:
    s, e = np.maximum(ops.start, lo), np.minimum(ops.end, hi)
    keep = e > s
    return Ops(ops.names, ops.idx[keep], s[keep], e[keep])


def summarize(trace: Trace, top: int = 10) -> Summary:
    windows = [(s, e) for n, s, e in trace.spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace has no {WINDOW_SPAN} host span")
    if not trace.devices:
        raise ValueError("the trace has no TPU device plane")
    lo, hi = windows[0]
    window = hi - lo
    inner = sorted([(n, s, e) for n, s, e in trace.spans
                    if n != WINDOW_SPAN and e > lo and s < hi],
                   key=lambda x: x[1])
    busy, coll, exposed, gaps = [], [], [], []
    by_name: dict = {}
    for plane, all_ops in trace.devices.items():
        ops = _clip(all_ops, lo, hi)
        busy_iv = union(ops.start, ops.end)
        busy.append(length(busy_iv))
        is_coll = ops.collective()
        flight = _clip(trace.asyncs.get(plane, Ops.empty()), lo, hi)
        f_coll = flight.collective()
        c_iv = union(np.concatenate([ops.start[is_coll],
                                     flight.start[f_coll]]),
                     np.concatenate([ops.end[is_coll], flight.end[f_coll]]))
        coll.append(length(c_iv))
        other = union(ops.start[~is_coll], ops.end[~is_coll])
        exposed.append(length(c_iv) - covered(c_iv, other))
        own = np.bincount(ops.idx, weights=self_times(ops.start, ops.end),
                          minlength=len(ops.names))
        for k in np.flatnonzero(own):
            by_name[ops.names[k]] = by_name.get(ops.names[k], 0.0) + own[k]
        edges = np.concatenate([[lo], busy_iv.reshape(-1), [hi]])
        idle = edges.reshape(-1, 2)
        idle = idle[idle[:, 1] > idle[:, 0]]
        longest = idle[np.argsort(idle[:, 0] - idle[:, 1],
                                  kind="stable")[:top]]
        gaps += [(_label(inner, (s + e) / 2), int(e - s)) for s, e in longest]
    nd = len(trace.devices)
    span_s, span_n = {}, {}
    for n, s, e in inner:
        span_s[n] = span_s.get(n, 0.0) + (min(e, hi) - max(s, lo)) * 1e-9
        span_n[n] = span_n.get(n, 0) + 1
    top_ops = sorted(by_name.items(), key=lambda x: -x[1])[:top]
    gaps = sorted(gaps, key=lambda x: -x[1])[:top]
    return Summary(
        window_s=window * 1e-9,
        busy_s=float(np.mean(busy)) * 1e-9,
        idle_share=1.0 - float(np.mean(busy)) / window,
        collective_s=float(np.mean(coll)) * 1e-9,
        collective_exposed_s=float(np.mean(exposed)) * 1e-9,
        top_ops=[[n, float(t) * 1e-9 / nd] for n, t in top_ops],
        idle_gaps=[[n, t * 1e-9] for n, t in gaps],
        span_s=span_s, span_n=span_n, n_devices=nd)


def _label(spans, t) -> str:
    """Innermost (latest-starting) harness span covering time ``t``."""
    best = WINDOW_SPAN
    for n, s, e in spans:
        if s > t:
            break
        if e >= t:
            best = n
    return best
