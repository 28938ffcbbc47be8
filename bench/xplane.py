"""Reduction of a profiler trace (``.xplane.pb``) to device busy time, the
time of named operations, and the breakdown of where the window went.

* Device events: every event on the ``XLA Ops`` line of each ``/device:TPU:N``
  plane, named by its HLO instruction. Its label (opcode, instruction name,
  custom-call target) is what the per-layer metrics match their patterns
  against; events nest (a loop's event spans its body's), and the breakdown
  counts only the innermost.
* Host spans: the ``bench.*`` annotations the harness opens around its calls
  into the program, on the host planes; ``bench.window`` bounds the window.
* Busy time of a device: the length of the union of its event intervals
  inside the window. Idle time is the complement, split by the host span
  open during it (``none`` where the harness had none open).
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from pathlib import Path

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
WINDOW = "bench.window"
# an event on the ops line is named by its HLO instruction's text:
# "%sort.45 = (f32[8,65536]{...}, s32[...]) sort(f32[8,65536]{...} %x, ...)"
HLO = re.compile(r"^%?(?P<name>[^\s=]+) = .*?\s(?P<op>[a-z][\w-]*)\(")
TARGET = re.compile(r'custom_call_target="([^"]+)"')


def find(path):
    """The newest ``.xplane.pb`` under ``path`` (or ``path`` itself)."""
    p = Path(path)
    if p.is_file():
        return p
    files = sorted(p.rglob("*.xplane.pb"), key=lambda f: f.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {p}")
    return files[-1]


def describe(text):
    """(short name, label) of an ops-line event. The label, which patterns
    match, is the opcode, the instruction name and any custom-call target;
    the short name is the instruction's text up to its opcode."""
    text = text[:2000]
    m = HLO.match(text)
    if not m:
        return text[:120], text[:120]
    t = TARGET.search(text)
    label = f"{m['op']} {m['name']}" + (f" {t.group(1)}" if t else "")
    return text[:m.end() - 1][:120], label


def load(path):
    """Device events and host spans of one trace, times in nanoseconds:
    ``{"devices": {index: [(start, end, name, label)]}, "spans": [(start,
    end, name)]}``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(find(path)))
    devices, spans = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            evs = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    evs.append((e.start_ns, e.end_ns) + describe(e.name))
            devices[int(m.group(1))] = sorted(evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append((e.start_ns, e.end_ns, e.name))
    return {"devices": devices, "spans": sorted(spans)}


def union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def length(intervals):
    return sum(e - s for s, e in intervals)


def window(trace):
    w = [(s, e) for s, e, n in trace["spans"] if n == WINDOW]
    if not w:
        raise ValueError("the trace holds no bench.window span")
    return w[0]


def matched(trace, patterns, devices):
    """Per device: the union of the events whose label matches one of
    ``patterns`` (regular expressions), inside the window."""
    lo, hi = window(trace)
    rx = re.compile("|".join(f"(?:{p})" for p in patterns))
    return {d: clip(union((s, e) for s, e, _, lab in trace["devices"][d]
                          if rx.search(lab)), lo, hi)
            for d in devices}


def reduce(trace, n_devices, top=10):
    """Busy and window seconds (busy averaged over the devices used), the
    per-device busy intervals, and the breakdown: the device operations that
    took most time and the idle time by the host span open during it."""
    lo, hi = window(trace)
    devices = sorted(trace["devices"])[:n_devices]
    busy = {d: clip(union((s, e) for s, e, _, _ in trace["devices"][d]), lo, hi)
            for d in devices}
    ops = defaultdict(float)
    for d in devices:
        for s, e, name, _ in leaves(trace["devices"][d]):
            if e > lo and s < hi:
                ops[name] += (min(e, hi) - max(s, lo)) / 1e9 / len(devices)
    spans = [(s, e, n) for s, e, n in trace["spans"] if n != WINDOW]
    starts = [s for s, _, _ in spans]
    idle = defaultdict(float)
    for d in devices:
        edges = [lo] + [t for iv in busy[d] for t in iv] + [hi]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            for host, ns in _host_during(spans, starts, gs, ge):
                idle[host] += ns / 1e9 / len(devices)
    by_time = lambda kv: -kv[1]  # noqa: E731
    return {
        "busy_s": sum(length(b) for b in busy.values()) / 1e9 / len(devices),
        "window_s": (hi - lo) / 1e9,
        "devices": devices,
        "busy": busy,
        "trace": trace,
        "breakdown": {
            "device_ops": [[k, v] for k, v in sorted(ops.items(), key=by_time)[:top]],
            "idle_gaps": [[k, v] for k, v in sorted(idle.items(), key=by_time)[:top]],
        },
    }


def share(red, patterns):
    """Matched device time over busy time, summed over the devices, in %;
    None where nothing matches."""
    m = matched(red["trace"], patterns, red["devices"])
    t = sum(length(v) for v in m.values())
    if t == 0:
        return None
    return 100.0 * t / sum(length(b) for b in red["busy"].values())


def seconds(red, patterns):
    """Matched device seconds, summed over the devices."""
    m = matched(red["trace"], patterns, red["devices"])
    return sum(length(v) for v in m.values()) / 1e9


def leaves(events):
    """Events that hold no other event: a while loop's event spans the
    events of its body, which are the ones that did the work."""
    out = []
    for i, ev in enumerate(events):
        nxt = events[i + 1] if i + 1 < len(events) else None
        if not (nxt and nxt[0] < ev[1] and nxt[1] <= ev[1]):
            out.append(ev)
    return out


def _host_during(spans, starts, s, e):
    """[(host span, overlap)] of the gap [s, e), with ``none`` for the part
    no span covers. Host spans are sequential: look back from the last span
    that starts before ``e`` until one ends before ``s``."""
    out, covered = [], 0
    j = bisect.bisect_left(starts, e) - 1
    while j >= 0 and spans[j][1] > s:
        a, b, n = spans[j]
        ov = min(b, e) - max(a, s)
        if ov > 0:
            out.append((n, ov))
            covered += ov
        j -= 1
    if e - s > covered:
        out.append(("none", e - s - covered))
    return out
