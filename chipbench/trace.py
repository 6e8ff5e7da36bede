"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read: device busy time (the union of the intervals in
which an operation runs), device time by operation name, the part of the
window in which a collective runs with no other operation beside it, and
the idle gaps named by the host span open at the time.

Everything is clipped to the benchmark's own window span, which the
harness opens around the measured window. Device planes are the TPU
planes (``/device:TPU:<n>``); operations are the events of their
``XLA Ops`` line. Host spans are the harness's ``TraceAnnotation`` events,
read from every host thread.
"""
from __future__ import annotations

import glob
import os
import re

WINDOW_SPAN = "chipbench.window"
SPAN_PREFIXES = ("chipbench.", "fl.", "decoder.")
OPS_LINE = "XLA Ops"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
                         r"collective-permute|send|recv)", re.IGNORECASE)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def union(intervals) -> list[tuple[float, float]]:
    """Sorted, merged (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list[tuple[float, float]]:
    """Parts of the merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def read_events(path: str):
    """(device ops by device index, host spans), each event a
    (name, start_ns, end_ns) tuple."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: dict[int, list] = {}
    spans: list = []
    for plane in data.planes:
        m = _DEVICE.match(plane.name)
        if m:
            ops = devices.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                            for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                          for ev in line.events
                          if ev.name.startswith(SPAN_PREFIXES)]
    return devices, spans


def summarize(devices: dict, spans: list, gap_top: int = 10,
              op_top: int = 10) -> dict:
    """Window-clipped busy time, op time by name, collective-only time and
    named idle gaps, averaged over the devices that ran anything."""
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    lo, hi = windows[0]
    timeline = _timeline([(n, s, e) for n, s, e in spans
                          if n != WINDOW_SPAN], lo, hi)
    used = {d: ops for d, ops in devices.items() if ops}
    if not used:
        raise ValueError("no device operation in the trace")
    busy_total, coll_total = 0.0, 0.0
    op_time: dict[str, float] = {}
    gaps: dict[str, float] = {}
    for d, ops in used.items():
        ops = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
               if min(e, hi) > max(s, lo)]
        busy = union((s, e) for _, s, e in ops)
        busy_total += length(busy)
        for n, t in self_times(ops).items():
            op_time[n] = op_time.get(n, 0.0) + t
        is_coll = [bool(_COLLECTIVE.search(op_name(n))) for n, _, _ in ops]
        coll = union((s, e) for (_, s, e), c in zip(ops, is_coll) if c)
        other = union((s, e) for (_, s, e), c in zip(ops, is_coll) if not c)
        coll_total += length(subtract(coll, other))
        for name, s, e in _overlaps(timeline, subtract([(lo, hi)], busy)):
            gaps[name] = gaps.get(name, 0.0) + (e - s)
    n = len(used)
    ns = 1e-9
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": busy_total / n * ns,
        "collective_only_s": coll_total / n * ns,
        "devices": n,
        "op_s": {k: v / n * ns for k, v in op_time.items()},
        "device_ops": [[k, v / n * ns] for k, v in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:op_top]],
        "idle_gaps": [[k, v / n * ns] for k, v in
                      sorted(gaps.items(), key=lambda kv: -kv[1])[:gap_top]],
    }


_HLO = re.compile(r"^%([\w\-]+?)(?:\.\d+)*\s*=\s*\(?([a-z0-9]+\[[0-9,]*\])?")


def op_name(hlo: str) -> str:
    """A stable short name for an XLA op event: the instruction's name
    without its numeric suffix, and the shape it returns (the first, for
    a tuple): ``fusion f32[25000,16,10]``, ``grad_aggregate_raw f32[1,1024]``."""
    m = _HLO.match(hlo)
    if not m:
        return hlo[:80]
    return f"{m.group(1)} {m.group(2)}" if m.group(2) else m.group(1)


def self_times(ops) -> dict:
    """Time of each op by short name, less the ops that run inside it: a
    loop's event on the ``XLA Ops`` line spans the events of its body."""
    out: dict[str, float] = {}
    stack: list = []
    for n, s, e in sorted(ops, key=lambda t: (t[1], -t[2])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        name = op_name(n)
        out[name] = out.get(name, 0.0) + (e - s)
        if stack:
            parent = stack[-1][0]
            out[parent] -= min(e, stack[-1][1]) - s
        stack.append((name, e))
    return out


def _timeline(spans, lo: float, hi: float) -> list:
    """[lo, hi] cut into (name, start, end) pieces, each named by the
    innermost harness span open over it ("harness" where none is). The
    harness's spans come from one thread, so they nest."""
    events = []
    for n, s, e in spans:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            events += [(s, 1, -e, n), (e, 0, 0, n)]
    events.sort()
    stack, out, t = [], [], lo
    for when, is_start, _, name in events:
        if when > t:
            out.append((stack[-1] if stack else "harness", t, when))
            t = when
        if is_start:
            stack.append(name)
        elif name in stack:
            del stack[len(stack) - 1 - stack[::-1].index(name)]
    if hi > t:
        out.append((stack[-1] if stack else "harness", t, hi))
    return out


def _overlaps(timeline, intervals):
    """(name, start, end) pieces of the merged ``intervals`` under the
    timeline's names."""
    out, j = [], 0
    for s, e in intervals:
        while j < len(timeline) and timeline[j][2] <= s:
            j += 1
        k = j
        while k < len(timeline) and timeline[k][1] < e:
            name, ts, te = timeline[k]
            a, b = max(s, ts), min(e, te)
            if b > a:
                out.append((name, a, b))
            k += 1
    return out


def op_seconds(summary: dict, pattern: str) -> float:
    """Device seconds (per device) of the operations whose name matches."""
    rx = re.compile(pattern)
    return sum(v for k, v in summary["op_s"].items() if rx.search(k))
