"""The program's spans in a profiler trace, and the device time they launched.

The program mirrors each of its spans into the profiler as a host event
named ``ample.<name>`` (``repro.observe.trace``), on the thread that ran it.
From a trace this computes, per traced request (``ample.request`` events):

* each span name's host time, and its self time: the span less the spans
  nested in it on the same thread;
* the device time of the programs each span launched: every device program
  event (the ``XLA Modules`` line of a device plane) is linked to the host
  launch that issued it, and so to the innermost program span open on the
  launching thread at that moment;
* the count of programs launched inside ``ample.request`` spans.

Linking. On a TPU the host marks each launch with a
``PJRT_LoadedExecutable_Execute linkage`` event inside the ``PjitFunction(f)``
call that issued it; the device's program event ``jit_f(<id>)`` carries a
run id, and the launch carries only a flow id that reaches the device event
through the runtime's worker threads. No id is on both, so launches and
device program events are paired in order, the k-th with the k-th: one
thread launches onto one in-order stream. The names check the pairing:
``renamed`` counts pairs whose launch sat in a call of another name (a
jitted function run inside another's dispatch, such as ``_broadcast_arrays``
converting its operands), and ``unpaired_launches`` and ``unpaired_device``
what one side has beyond the other.

``read_events`` reads what this needs from an ``xplane.pb``, apart from
``trace.read_xplane`` (whose output stays as it is); ``reduce_events`` does
the arithmetic on plain event lists, so a test can hand it a few events.

Nothing in the benchmark's runs calls this yet: ``trace.Tracer.reduce``
deletes the trace once ``reduce_planes`` has read it, so per-layer metrics
built on these numbers need one line there that adds ``reduce_file``'s
output to the reduced trace (PERF.md, Open questions).
"""
from __future__ import annotations

import re
from typing import Dict, List, Sequence, Tuple

from bench import trace

__all__ = ["read_events", "reduce_events", "reduce_file", "SPAN_PREFIX", "LAUNCH"]

SPAN_PREFIX = "ample."
LAUNCH = "PJRT_LoadedExecutable_Execute linkage"
_PJIT = re.compile(r"^PjitFunction\((.*)\)$")
_MODULE = re.compile(r"^jit_(.*?)(\(\d+\))?$")
_NON_WORD = re.compile(r"\W")


def _host_kept(name: str) -> bool:
    return name.startswith(SPAN_PREFIX) or name == LAUNCH or bool(_PJIT.match(name))


def read_events(path: str) -> Dict[str, Dict[str, List[trace.Event]]]:
    """``{"host": {line: events}, "device": {plane: events}}``: the host lines
    that hold a program span (their spans, ``PjitFunction`` calls and
    launches) and each device's program events."""
    from jax.profiler import ProfileData

    host: Dict[str, List[trace.Event]] = {}
    device: Dict[str, List[trace.Event]] = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                evs = [(e.name, int(e.start_ns), int(e.end_ns))
                       for e in line.events if _host_kept(e.name)]
                if any(n.startswith(SPAN_PREFIX) for n, _, _ in evs):
                    host[f"{plane.name}/{line.name}#{i}"] = evs
        elif trace.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == trace.MODULES_LINE:
                    device[plane.name] = [(e.name, int(e.start_ns), int(e.end_ns))
                                          for e in line.events]
    return {"host": host, "device": device}


def _program(name: str) -> str:
    """One key for a program on both sides: ``PjitFunction(f)`` and
    ``jit_f(<id>)`` both give ``f`` (non-word characters as ``_``)."""
    m = _PJIT.match(name) or _MODULE.match(name)
    return _NON_WORD.sub("_", m.group(1) if m else name)


def _sweep(evs: Sequence[trace.Event]):
    """Walk one thread's events in nesting order, yielding each program span
    as ``("span", [name, start, end, child_ns])`` (its child time is final
    once the walk ends) and each launch as ``("launch", (start, program,
    innermost open span, whether a request span is open))``."""
    stack: List[list] = []  # open program spans
    calls: List[Tuple[str, int]] = []  # open PjitFunction calls (program, end)
    for name, s, e in sorted(evs, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][2] <= s:
            stack.pop()
        while calls and calls[-1][1] <= s:
            calls.pop()
        if name.startswith(SPAN_PREFIX):
            rec = [name[len(SPAN_PREFIX):], s, e, 0]
            if stack:
                stack[-1][3] += e - s
            stack.append(rec)
            yield "span", rec
        elif name == LAUNCH:
            yield "launch", (s, calls[-1][0] if calls else "",
                             stack[-1][0] if stack else "none",
                             any(r[0] == "request" for r in stack))
        else:
            calls.append((_program(name), e))


def reduce_events(events: Dict[str, Dict[str, List[trace.Event]]]) -> Dict:
    """Per-request host, self and device milliseconds of each span name, and
    launches per request; ``requests`` is 0 where the trace holds no
    ``ample.request`` span (a program without spans)."""
    spans: List[list] = []
    launches: List[Tuple[int, str, str, bool]] = []
    for evs in events.get("host", {}).values():
        for kind, item in _sweep(evs):
            (spans if kind == "span" else launches).append(item)
    requests = sum(1 for r in spans if r[0] == "request")
    out = {"requests": requests, "host_ms": {}, "self_ms": {}, "device_ms": {},
           "program_ms": {}, "launches": None}
    if not requests:
        return out
    per = 1e6 * requests  # ns -> ms per request
    for name, s, e, child in spans:
        out["host_ms"][name] = out["host_ms"].get(name, 0.0) + (e - s) / per
        out["self_ms"][name] = out["self_ms"].get(name, 0.0) + (e - s - child) / per

    launches.sort()
    device_ns: Dict[str, int] = {}
    program_ns: Dict[str, int] = {}
    launched = paired = renamed = 0
    planes = events.get("device", {})
    # the first device's stream: every cell launches onto one chip
    devs = sorted(planes[min(planes)], key=lambda ev: ev[1]) if planes else []
    for (name, s, e), (_, program, span, in_request) in zip(devs, launches):
        paired += 1
        launched += in_request
        renamed += program != _program(name)
        device_ns[span] = device_ns.get(span, 0) + (e - s)
        key = f"{span}/{_program(name)}"
        program_ns[key] = program_ns.get(key, 0) + (e - s)
    out["device_ms"] = {k: v / per for k, v in device_ns.items()}
    out["program_ms"] = {k: v / per for k, v in program_ns.items()}
    out["launches"] = launched / requests
    out.update(paired=paired, renamed=renamed, unpaired_launches=len(launches) - paired,
               unpaired_device=len(devs) - paired)
    return out


def reduce_file(path: str) -> Dict:
    return reduce_events(read_events(path))

