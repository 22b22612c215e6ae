"""Profiler capture of part of a window, and its reduction to numbers.

``Tracer.start``/``stop`` bracket the traced part of a window with
``jax.profiler``; the trace goes to a temporary directory (under ``TMPDIR``)
that ``reduce`` reads and deletes. ``reduce_planes`` does the arithmetic on
plain event lists, so a test can feed it a small recorded trace:

* the traced window is the span of the benchmark's own host spans
  (``bench.*`` annotations) that fall in the trace;
* busy time is the union of the intervals in which an operation ran on a
  device (its ``XLA Ops`` line), clipped to the window and averaged over
  the devices; idle is the rest;
* program time is summed per jitted program (the ``XLA Modules`` line),
  with the trailing ``(id)`` the runtime appends taken off the name;
* each idle gap is labelled by the benchmark span and the innermost event
  of the host thread that runs the benchmark (the line that holds its
  spans, whatever the thread is called: ``python3`` when started as the
  command says) open at
  its middle; ``breakdown`` lists the operations that took the most device
  self time (a loop's body ops count, not the loop again) and the idle time
  per label.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile
import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["Tracer", "Event", "read_xplane", "reduce_planes", "merge_intervals"]

Event = Tuple[str, int, int]  # (name, start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
_ID_SUFFIX = re.compile(r"\(\d+\)$")
_LAYOUT = re.compile(r"\{[^{}]*\}")
TOP = 10


class Tracer:
    def __init__(self):
        self.dir: Optional[str] = None
        self.requests = 0
        self.stop_s = 0.0

    def start(self) -> None:
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self, requests: int) -> None:
        import jax

        t = time.perf_counter()
        jax.profiler.stop_trace()
        self.stop_s = time.perf_counter() - t
        self.requests = requests

    def reduce(self) -> Dict:
        try:
            files = glob.glob(os.path.join(self.dir, "plugins", "profile", "*", "*.xplane.pb"))
            if not files:
                raise FileNotFoundError(f"no xplane.pb under {self.dir}")
            t = time.perf_counter()
            planes = read_xplane(files[0])
            out = reduce_planes(planes)
            out["requests"] = self.requests
            out["trace_bytes"] = os.path.getsize(files[0])
            out["parse_s"] = time.perf_counter() - t
            out["stop_s"] = self.stop_s
            return out
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def read_xplane(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """{plane: {line: [(name, start_ns, end_ns)]}}: the device planes' op and
    program lines, and the host lines that hold a benchmark span."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: Dict[str, Dict[str, List[Event]]] = {}
    for plane in data.planes:
        host = plane.name.startswith("/host:")
        if not (host or DEVICE_PLANE.match(plane.name)):
            continue
        lines: Dict[str, List[Event]] = {}
        for i, line in enumerate(plane.lines):
            if host or line.name in (OPS_LINE, MODULES_LINE):
                evs = [(e.name, int(e.start_ns), int(e.end_ns)) for e in line.events]
                if not host:
                    lines[line.name] = evs
                elif any(name.startswith(SPAN_PREFIX) for name, _, _ in evs):
                    lines[f"{line.name}#{i}"] = evs
        out[plane.name] = lines
    return out


def merge_intervals(iv: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(iv: Sequence[Tuple[int, int]], lo: int, hi: int) -> List[Tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def op_label(name: str) -> str:
    """``%fusion.24 f32[717825,300]`` from an op's HLO text: the name and
    the start of its result shape, layouts dropped."""
    head, _, rest = name.partition(" = ")
    rest = _LAYOUT.sub("", rest)
    return f"{head} {rest[:48]}".strip() if rest else head


def self_times(evs: Sequence[Event], lo: int, hi: int) -> Dict[str, int]:
    """Self ns per op label inside [lo, hi]: an op's time less the ops nested
    in it (a while loop holds its body's ops), so nothing counts twice."""
    out: Dict[str, int] = {}
    stack: List[List] = []  # [label, end, child_ns]

    def close(item):
        label, _, dur, child = item
        out[label] = out.get(label, 0) + dur - child

    for name, s, e in sorted((ev for ev in evs if ev[2] > lo and ev[1] < hi),
                             key=lambda ev: (ev[1], -ev[2])):
        s, e = max(s, lo), min(e, hi)
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += e - s
        stack.append([op_label(name), e, e - s, 0])
    while stack:
        close(stack.pop())
    return out


def _label_gaps(gaps: List[Tuple[int, int]], spans: List[Event]) -> Dict[str, int]:
    """Idle ns per label: the benchmark span and the innermost host event of
    the Python thread (JAX's dispatch, a transfer) open at each gap's
    middle, by one sweep over span edges and gap middles."""
    edges = []
    for i, (_, s, e) in enumerate(spans):  # spans: benchmark and host events
        edges.append((s, 1, i))
        edges.append((e, 0, i))
    for j, (s, e) in enumerate(gaps):
        edges.append(((s + e) // 2, 2, j))
    edges.sort()
    open_spans: Dict[int, int] = {}
    idle: Dict[str, int] = {}
    for t, kind, i in edges:
        if kind == 1:
            open_spans[i] = spans[i][1]
        elif kind == 0:
            open_spans.pop(i, None)
        else:
            s, e = gaps[i]
            bench = [q for q in open_spans if spans[q][0].startswith(SPAN_PREFIX)]
            other = [q for q in open_spans if not spans[q][0].startswith(SPAN_PREFIX)]
            label = "none"
            if bench:
                k = max(bench, key=lambda q: (open_spans[q], q))
                label = spans[k][0][len(SPAN_PREFIX):]
            if other:
                k = max(other, key=lambda q: (open_spans[q], q))
                label = f"{label}>{spans[k][0]}"
            idle[label] = idle.get(label, 0) + (e - s)
    return idle


def reduce_planes(planes: Dict[str, Dict[str, List[Event]]]) -> Dict:
    host = [ev for name, lines in planes.items() if name.startswith("/host:")
            for evs in lines.values() for ev in evs]
    spans = [ev for ev in host if ev[0].startswith(SPAN_PREFIX)]
    devices = sorted(n for n in planes if DEVICE_PLANE.match(n))
    if not spans or not devices:
        raise ValueError(
            f"trace holds {len(spans)} benchmark spans and devices {devices}")
    lo = min(s for _, s, _ in spans)
    hi = max(e for _, _, e in spans)
    window_ns = hi - lo
    busy_ns, ops, modules = [], {}, {}
    busy_sets = []
    for d in devices:
        evs = planes[d].get(OPS_LINE, [])
        merged = merge_intervals(_clip([(s, e) for _, s, e in evs], lo, hi))
        busy_sets.append(merged)
        busy_ns.append(sum(e - s for s, e in merged))
        for label, ns in self_times(evs, lo, hi).items():
            ops[label] = ops.get(label, 0) + ns
        for name, s, e in planes[d].get(MODULES_LINE, []):
            if e > lo and s < hi:
                key = _ID_SUFFIX.sub("", name)
                modules[key] = modules.get(key, 0) + (min(e, hi) - max(s, lo))
    # idle gaps of the first device, labelled by the innermost open span
    gaps = []
    cur = lo
    for s, e in busy_sets[0]:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    idle = _label_gaps(gaps, [ev for ev in host if ev[2] > lo and ev[1] < hi])
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    top_idle = sorted(idle.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": window_ns / 1e9,
        "busy_s": float(np.mean(busy_ns)) / 1e9,
        "devices": len(devices),
        "modules": {k: v / 1e9 for k, v in modules.items()},
        "breakdown": {
            "device_ops": [[k, v / 1e9] for k, v in top_ops],
            "idle_gaps": [[k, v / 1e9] for k, v in top_idle],
        },
    }
