"""Reduction of a profiler trace to what the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
two things: every operation on each TPU's "XLA Ops" line, and the
harness's own host spans (``TraceAnnotation`` names that start with
``bench.``).  On a v5e an operation's event name is its whole HLO
instruction (``%sort.76 = (s32[16,45]...) sort(...), ...``); ``load``
keeps its name (``sort.76``) and its kind: the opcode, or for a custom
call its target (``tpu_custom_call`` for a Pallas kernel, whose name the
trace does not carry).  Host and device events share one clock.

``reduce`` clips the device operations to the host span
``bench.window``, takes the union of their intervals as busy time, sums
each operation's self time (an operation inside a ``while`` or
``conditional`` is subtracted from it) by name and by kind, and names
each idle gap by the host span that covers most of it.  Tests check it
on a trace recorded on a v5e chip (``tests/bench/data``).
"""

from __future__ import annotations

import glob
import os
import re

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane file under {log_dir}, "
                           f"found {len(paths)}")
    return paths[0]


_OPCODE = re.compile(r" ([a-z][a-z0-9_-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_name_kind(hlo: str) -> tuple[str, str]:
    """("sort.76", "sort") from ``%sort.76 = (...) sort(...), ...``."""
    if " = " not in hlo:
        return hlo, hlo
    lhs, rhs = hlo.split(" = ", 1)
    m = _OPCODE.search(" " + rhs)
    kind = m.group(1) if m else "?"
    if kind == "custom-call":
        t = _TARGET.search(rhs)
        kind = t.group(1) if t else kind
    return lhs.lstrip("%"), kind


def load(xplane_path: str) -> dict:
    """{"devices": {plane: [[op, kind, start_ns, dur_ns], ...]},
    "host": [[span, start_ns, dur_ns], ...]}."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend([*op_name_kind(ev.name), ev.start_ns,
                                ev.duration_ns] for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([ev.name, ev.start_ns, ev.duration_ns]
                            for ev in line.events
                            if ev.name.startswith("bench."))
    return {"devices": {k: v for k, v in devices.items() if v},
            "host": host}


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _span_of(gap, spans) -> str:
    best, name = 0.0, "(no span)"
    for n, s, e in spans:
        cover = min(e, gap[1]) - max(s, gap[0])
        if cover > best:
            best, name = cover, n
    return name


def _self_times(ops):
    """(name, kind, self seconds) of clipped (start, end, name, kind)
    operations, an operation nested in another subtracted from it."""
    out, stack = [], []
    for s, e, n, k in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        row = [n, k, (e - s) * 1e-9]
        if stack and e <= stack[-1][1]:
            stack[-1][2][2] -= (e - s) * 1e-9
        stack.append((s, e, row))
        out.append(row)
    return out


def reduce(events: dict) -> dict:
    """Busy and idle time, device self time per operation name and per
    kind (mean over the devices), and the idle gaps of the first device,
    all inside the ``bench.window`` span.  Times in seconds."""
    windows = [(s, s + d) for n, s, d in events["host"] if n == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(windows)}")
    w0, w1 = windows[0]
    spans = [(n, s, s + d) for n, s, d in events["host"] if n != WINDOW]
    planes = sorted(events["devices"])
    if not planes:
        raise ValueError("no device operations in the trace")
    busy, op_s, kind_s, gaps = [], {}, {}, []
    for i, plane in enumerate(planes):
        clipped = [(max(s, w0), min(s + d, w1), n, k)
                   for n, k, s, d in events["devices"][plane]
                   if s < w1 and s + d > w0]
        for n, k, t in _self_times(clipped):
            op_s[n] = op_s.get(n, 0.0) + t / len(planes)
            kind_s[k] = kind_s.get(k, 0.0) + t / len(planes)
        merged = _merge((s, e) for s, e, _, _ in clipped)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        if i == 0:
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            for s, e in zip(edges[::2], edges[1::2]):
                if e > s:
                    gaps.append((_span_of((s, e), spans), (e - s) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return {"window_s": (w1 - w0) * 1e-9, "busy_s": sum(busy) / len(busy),
            "devices": len(planes), "op_s": op_s, "kind_s": kind_s,
            "gaps": gaps}


def breakdown(summary: dict, top: int = 10) -> dict:
    ops = sorted(summary["op_s"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in summary["gaps"][:top]]}
