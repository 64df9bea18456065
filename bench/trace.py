"""Reduction of a JAX profiler trace to the benchmark's device numbers.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  Each device
is a plane named ``/device:TPU:<n>``; its line ``XLA Ops`` holds one event
per operation the device ran, with a start and a duration in nanoseconds.
The host's planes hold the harness's own ``TraceAnnotation`` spans on the
same clock: ``traced_window`` around the traced steps, and per step
``batch``, ``place``, ``dispatch`` and ``loss_read``.

Only the innermost operations count (:func:`leaves`): a control-flow
operation spans its body's.  Busy time is the union of a device's
operation intervals inside the traced window; the idle share is one minus busy over the window.  A collective's
exposed time is the part of the union of its intervals (collective
operations, their ``-start``/``-done`` halves included) during which no
other operation runs on that device.  Both are averaged over the devices.
"""
from __future__ import annotations

import glob
import os
import re

#: XLA's names of the operations that move data between devices
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|ppermute|\bsend\b|\brecv\b|send-done|recv-done")
WINDOW = "traced_window"
HOST_SPANS = ("batch", "place", "dispatch", "loss_read")
OPS_LINE = "XLA Ops"


def find(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` output dir."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def union(intervals):
    """Sorted, merged ``[(start, end)]``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def minus(a, b):
    """Merged intervals ``a`` less merged intervals ``b``."""
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


def leaves(ops):
    """The operations of one line that contain no other operation of it.

    A device's ``XLA Ops`` line also holds the control-flow operations
    (``while``, ``conditional``, ``call``) whose bodies' operations run
    inside their interval; only the innermost events are work.  Events of
    no duration are dropped."""
    ops = sorted(((n, s, e) for n, s, e in ops if e > s),
                 key=lambda o: (o[1], -o[2]))
    out = []
    for i, (n, s, e) in enumerate(ops):
        j = i + 1
        # skip the events that start inside this one and end after it
        while j < len(ops) and ops[j][1] < e and ops[j][2] > e:
            j += 1
        if j == len(ops) or ops[j][1] >= e:
            out.append((n, s, e))
    return out


def events(plane, line_name=None):
    for line in plane.lines:
        if line_name is not None and line.name != line_name:
            continue
        for ev in line.events:
            yield ev.name, ev.start_ns, ev.start_ns + ev.duration_ns


def read(path: str) -> dict:
    """``{"devices": {name: [(op, start, end)]}, "host": [(span, start,
    end)]}`` from an ``.xplane.pb``, times in ns."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            devices[plane.name] = list(events(plane, OPS_LINE))
        elif plane.name.startswith("/host:"):
            host += [e for e in events(plane)
                     if e[0] in HOST_SPANS or e[0] == WINDOW]
    return {"devices": devices, "host": host}


def reduce(raw: dict, top: int = 10) -> dict | None:
    """Busy time, idle share, exposed collective time, the operations that
    took most time and the longest idle gaps by host span.  None when the
    trace holds no device operation."""
    wins = [(s, e) for n, s, e in raw["host"] if n == WINDOW]
    devs = {k: v for k, v in raw["devices"].items() if v}
    if not devs:
        return None
    if wins:
        lo, hi = wins[0]
    else:
        lo = min(s for ops in devs.values() for _, s, _ in ops)
        hi = max(e for ops in devs.values() for _, _, e in ops)
    spans = sorted((s, e, n) for n, s, e in raw["host"] if n != WINDOW)
    busy = exposed = 0.0
    op_time: dict[str, float] = {}
    gaps = []
    for name, ops in devs.items():
        ops = [(n, max(s, lo), min(e, hi)) for n, s, e in leaves(ops)
               if e > lo and s < hi]
        all_u = union((s, e) for _, s, e in ops)
        coll_u = union((s, e) for n, s, e in ops if COLLECTIVE.search(n))
        comp_u = union((s, e) for n, s, e in ops
                       if not COLLECTIVE.search(n))
        busy += length(all_u)
        exposed += length(minus(coll_u, comp_u))
        for n, s, e in ops:
            op_time[short(n)] = op_time.get(short(n), 0.0) + (e - s)
        for s, e in minus([(lo, hi)], all_u):
            gaps.append((e - s, _label(spans, (s + e) / 2)))
    nd = len(devs)
    window = hi - lo
    ns = 1e-9
    return {
        "devices": nd,
        "window_s": window * ns,
        "busy_s": busy / nd * ns,
        "idle_share": 1.0 - busy / nd / window if window > 0 else None,
        "collective_exposed_s": exposed / nd * ns,
        "collective_ops": sum(1 for ops in devs.values()
                              for n, _, _ in ops if COLLECTIVE.search(n)),
        "device_ops": [[n, t / nd * ns] for n, t in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[lab, g * ns] for g, lab in sorted(
            gaps, key=lambda x: -x[0])[:top]],
    }


def short(op: str) -> str:
    """An operation's name without its HLO text: ``%fusion.12 = bf16[..]
    fusion(..), kind=kOutput, calls=..`` -> ``%fusion.12 fusion``."""
    name, eq, rest = op.partition(" = ")
    if not eq:
        return op
    m = re.search(r"\s([a-z][\w-]*)\(", rest)
    return f"{name} {m.group(1)}" if m else name


def _label(spans, t) -> str:
    """The host span open at time ``t`` (the innermost, latest-started)."""
    best = None
    for s, e, n in spans:
        if s > t:
            break
        if e >= t:
            best = n
    return best or "between_spans"
