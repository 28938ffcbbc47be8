"""The program's named scopes in a profiler trace.

The program labels its hot regions with ``jax.named_scope``: ``mv`` and
``rmv`` (operator applications), ``threshold`` (H_s), ``backtrack`` (the
step-size rounds), ``qmm_pad`` (the packed kernel's per-call copies), and on
the matrix-free path ``fft``, ``sample`` and ``dwt``. XLA keeps the scopes in
each instruction's ``op_name``, and the TPU profiler writes that path as the
``tf_op`` stat of the instruction's event metadata on the device plane, e.g.
``jit(_sharded_call)/while/body/mv/qmm_pad/jit(_pad)/pad:``.

``jax.profiler.ProfileData`` does not expose metadata stats, so this module
decodes them from the raw ``XSpace`` protobuf with the standard library: on
each ``/device:TPU:N`` plane, the stat metadata (field 5) and the event
metadata (field 4). The lines (field 3), which hold the events and most of
the bytes, are skipped by their length prefix; the events themselves are the
ones ``bench/xplane.py`` already loaded, joined to their paths by name.

A scope matches a whole ``/``-separated component of the path: ``mv``
matches ``.../mv/...`` but not ``jit(mv)``. Scopes nest (``backtrack/mv``
counts in both). A share is the union of the matching events' intervals
inside the window, over busy time, summed over the devices, as
``xplane.share`` computes it.
"""
from __future__ import annotations

import sys
import time

from bench import xplane

SCOPES = ("mv", "rmv", "threshold", "backtrack", "qmm_pad", "fft", "sample", "dwt")
TF_OP = "tf_op"


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf, lo=0, hi=None):
    """(field number, value) of the protobuf message in ``buf[lo:hi]``: an
    int for varints, a ``(start, end)`` slice for every other wire type."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            val, i = (i, i + n), i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            val, i = (i, i + n), i + n
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, val


def _text(buf, sl):
    return bytes(buf[sl[0]:sl[1]]).decode("utf-8", "replace")


def _map_value(buf, sl):
    """The value (field 2) of a map entry, as a slice."""
    for num, val in fields(buf, *sl):
        if num == 2:
            return val
    return (sl[0], sl[0])


def _plane(buf, lo, hi):
    """(name, {event name: tf_op}) of one plane; a name whose events carry
    two different paths maps to None."""
    name, stat_names, events = "", {}, []
    for num, val in fields(buf, lo, hi):
        if num == 2:
            name = _text(buf, val)
            if not xplane.DEVICE_PLANE.match(name):
                return name, {}
        elif num == 5:      # stat_metadata: id -> XStatMetadata {1 id, 2 name}
            sid, sname = 0, ""
            for n, v in fields(buf, *_map_value(buf, val)):
                if n == 1:
                    sid = v
                elif n == 2:
                    sname = _text(buf, v)
            stat_names[sid] = sname
        elif num == 4:      # event_metadata: id -> XEventMetadata {2 name, 5 stats}
            ename, stats = "", []
            for n, v in fields(buf, *_map_value(buf, val)):
                if n == 2:
                    ename = _text(buf, v)
                elif n == 5:
                    stats.append(v)
            events.append((ename, stats))
    paths = {}
    for ename, stats in events:
        path = None
        for sl in stats:    # XStat {1 metadata_id, 5 str_value, 7 ref_value}
            sid, value = None, None
            for n, v in fields(buf, *sl):
                if n == 1:
                    sid = v
                elif n == 5:
                    value = _text(buf, v)
                elif n == 7:
                    value = stat_names.get(v)
            if stat_names.get(sid) == TF_OP:
                path = value
        if path is None:
            continue
        paths[ename] = path if paths.get(ename, path) == path else None
    return name, paths


def decode(path):
    """``{device index: {event name: tf_op path}}`` from the TPU device
    planes of the ``XSpace`` at ``path`` (a file or a directory, as
    ``xplane.find`` takes). A name that carries two paths maps to None."""
    buf = memoryview(xplane.find(path).read_bytes())
    out = {}
    for num, val in fields(buf):
        if num == 1:        # XSpace.planes
            name, paths = _plane(buf, *val)
            m = xplane.DEVICE_PLANE.match(name)
            if m:
                out[int(m.group(1))] = paths
    return out


def components(tf_op):
    """The scopes and calls of a ``tf_op`` path, without its ``:type``."""
    op_name = tf_op.rpartition(":")[0] if ":" in tf_op else tf_op
    return frozenset(op_name.split("/"))


def trace_file(ctx):
    """The trace the harness reduced: ``ctx["trace_path"]`` where the
    harness passes it, else the ``trace_path`` of the caller that holds one
    (``harness.run_cell``); None where neither exists."""
    path = ctx.get("trace_path")
    frame = sys._getframe(1)
    while path is None and frame is not None:
        path = frame.f_locals.get("trace_path")
        frame = frame.f_back
    return path


def scoped(ctx):
    """Per device: ``[(start, end, components)]`` of the events in the
    reduced trace whose name maps to a single path. Decoded once per run and
    kept in ``ctx``; None where the trace cannot be found."""
    if "scoped_events" in ctx:
        return ctx["scoped_events"]
    path = trace_file(ctx)
    if path is None:
        ctx["notes"].append("scopes: the trace's path is not known to the readers")
        ctx["scoped_events"] = None
        return None
    t0 = time.perf_counter()
    paths = decode(path)
    red = ctx["trace"]
    out, clashes = {}, set()
    for d in red["devices"]:
        by_key = {}
        for name, p in paths.get(d, {}).items():
            key = xplane.describe(name)
            if p is None or by_key.get(key, p) != p:
                by_key[key] = None
                clashes.add(key[0])
            else:
                by_key[key] = p
        evs = []
        for s, e, short, label in red["trace"]["devices"][d]:
            p = by_key.get((short, label))
            if p is not None:
                evs.append((s, e, components(p)))
        out[d] = evs
    ctx["scoped_events"] = out
    ctx["notes"].append(
        f"scopes: decoded {sum(map(len, paths.values()))} event paths in "
        f"{time.perf_counter() - t0:.3f} s" + (
            f"; {len(clashes)} names with two paths count as unscoped, e.g. "
            f"{sorted(clashes)[0][:80]!r}" if clashes else ""))
    return out


def _seconds(red, events, keep):
    lo, hi = xplane.window(red["trace"])
    return sum(xplane.length(xplane.clip(xplane.union(
        (s, e) for s, e, comps in events[d] if keep(comps)), lo, hi))
        for d in red["devices"])


def share(ctx, scope, metric):
    """Device time of the events inside ``scope`` over busy time, in %; None
    where no event is. Notes the share of busy time that no scope claims."""
    events = scoped(ctx)
    if not events:
        return None
    red = ctx["trace"]
    busy = sum(xplane.length(b) for b in red["busy"].values())
    t = _seconds(red, events, lambda comps: scope in comps)
    if t == 0 or busy == 0:
        return None
    claimed = _seconds(red, events, lambda comps: not comps.isdisjoint(SCOPES))
    ctx["notes"].append(f"{metric}: scope {scope!r} {100.0 * t / busy:.3f} % of busy "
                        f"time; unscoped {100.0 * (1 - claimed / busy):.3f} %")
    return 100.0 * t / busy
