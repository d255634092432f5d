"""Per-layer attribution of a traced window: the ``join.*`` scope of
every device operation, the serving engine's host spans, and the
live-row counter, for the readers of ``bench/metrics``.

How the trace yields an operation's scope (read by hand off a v5e
trace): an event on a TPU's "XLA Ops" line carries its HLO instruction
text with no ``op_name`` and no module.  The "XLA Modules" line of the
same plane names the program running at each moment (``jit_run(<id>)``),
and the ``/host:metadata`` plane keeps each such program's ``HloProto``
(stat "Hlo Proto") whose instructions keep ``metadata.op_name``, JAX's
name stack (``jit(run)/vmap(join.probe)/jit(searchsorted)/.../gather``).
``load`` maps each operation to (module, instruction) and so to its
``op_name``; its layer is the innermost ``join.*`` component, bare or
inside a transform (``vmap(join.probe)``), and ``None`` where there is
none.  A fusion without an ``op_name`` of its own takes the scoped one
nearest the root of its fused computation.
The protos are read with a small protobuf decoder below, since JAX's
``ProfileData`` shows no event metadata.

``load`` adds two keys to what ``tracing.load`` gives: ``scopes``, one
list per plane parallel to its operations, and ``spans``, the engine's
``engine.*`` spans with their ``query`` argument.  ``reduce`` adds
``scope_s`` (device self seconds per scope, mean over the devices) and
``gaps_by_span`` (idle seconds per host span, each stretch of a gap
going to the innermost ``bench.*`` or ``engine.*`` span covering it,
mean over the devices) to what ``tracing.reduce`` gives.  Both leave
every key of ``tracing`` as it is.
"""

from __future__ import annotations

import bisect
import re

import tracing

ENGINE = "engine."
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"

_SCOPE = re.compile(r"(?:^|[/(])join\.([a-z_]+)(?=[)/]|$)")


def scope_of(op_name: str | None) -> str | None:
    """The innermost ``join.*`` component of a name stack."""
    found = _SCOPE.findall(op_name or "")
    return found[-1] if found else None


# -- protobuf wire format (the fields used here only) -------------------------

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields are
    skipped."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        wire = tag & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire == 1:
            i += 8
            continue
        elif wire == 5:
            i += 4
            continue
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield tag >> 3, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _module_op_names(hlo_proto) -> dict:
    """{instruction name: op_name} of one serialized ``xla.HloProto``
    (HloProto.hlo_module = 1; HloModuleProto.computations = 3;
    HloComputationProto: instructions = 2, id = 5; HloInstructionProto:
    name = 1, metadata = 7, called_computation_ids = 38;
    OpMetadata.op_name = 2)."""
    comps, instrs = {}, []
    for f, mod in _fields(hlo_proto):
        if f != 1:
            continue
        for f2, comp in _fields(mod):
            if f2 != 3:
                continue
            cid, body = None, []
            for f3, v in _fields(comp):
                if f3 == 2:
                    name, op_name, called = None, None, []
                    for f4, w in _fields(v):
                        if f4 == 1:
                            name = _text(w)
                        elif f4 == 7:
                            op_name = next((_text(x) for f5, x in _fields(w)
                                            if f5 == 2), None)
                        elif f4 == 38:
                            called.extend([w] if isinstance(w, int) else
                                          _packed(w))
                    body.append((name, op_name, called))
                elif f3 == 5:
                    cid = v
            comps[cid] = body
            instrs.extend(body)

    def resolve(op_name, called, depth=0):
        # A fusion or call with no op_name of its own (XLA's rewrites
        # drop it, as when a scatter is expanded) takes the scoped one
        # nearest the root of the computation it calls.
        if op_name or depth > 8:
            return op_name
        for cid in called:
            for _, inner, inner_called in reversed(comps.get(cid, [])):
                found = resolve(inner, inner_called, depth + 1)
                if scope_of(found):
                    return found
        return None

    return {name: resolve(op_name, called)
            for name, op_name, called in instrs}


def _packed(buf):
    i, out = 0, []
    while i < len(buf):
        v, i = _varint(buf, i)
        out.append(v)
    return out


def module_op_names(xplane_path: str) -> dict:
    """{module event name: {instruction: op_name}} from the HLO protos of
    the trace's metadata plane (XSpace.planes = 1; XPlane: name = 2,
    event_metadata = 4, stat_metadata = 5; map entries key = 1,
    value = 2; XEventMetadata: name = 2, stats = 5; XStat:
    metadata_id = 1, bytes_value = 6; XStatMetadata: id = 1, name = 2)."""
    with open(xplane_path, "rb") as f:
        space = memoryview(f.read())
    for f1, plane in _fields(space):
        if f1 != 1:
            continue
        name = next((_text(v) for f, v in _fields(plane) if f == 2), "")
        if name != METADATA_PLANE:
            continue
        stat_names, metas = {}, []
        for f, v in _fields(plane):
            if f in (4, 5):
                value = next((x for k, x in _fields(v) if k == 2), None)
                if value is None:
                    continue
                if f == 5:
                    sid = sname = None
                    for k, x in _fields(value):
                        if k == 1:
                            sid = x
                        elif k == 2:
                            sname = _text(x)
                    stat_names[sid] = sname
                else:
                    metas.append(value)
        out = {}
        for meta in metas:
            ename, protos = None, []
            for k, x in _fields(meta):
                if k == 2:
                    ename = _text(x)
                elif k == 5:
                    stat = dict(_fields(x))
                    protos.append((stat.get(1), stat.get(6)))
            for sid, blob in protos:
                if stat_names.get(sid) == "Hlo Proto" and blob is not None:
                    out[ename] = _module_op_names(blob)
        return out
    return {}


# -- load and reduce ----------------------------------------------------------

def load(xplane_path: str) -> dict:
    """``tracing.load`` plus ``scopes`` ({plane: [scope or None, ...]},
    parallel to ``devices``) and ``spans`` ([[name, start_ns, dur_ns,
    query], ...] of the ``engine.*`` host spans)."""
    from jax.profiler import ProfileData
    events = tracing.load(xplane_path)
    op_names = module_op_names(xplane_path)
    data = ProfileData.from_file(xplane_path)
    scopes, spans = {}, []
    for plane in data.planes:
        if plane.name in events["devices"]:
            modules = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                              ev.name) for line in plane.lines
                             if line.name == MODULES_LINE
                             for ev in line.events)
            starts = [m[0] for m in modules]
            out = scopes.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != tracing.OPS_LINE:
                    continue
                for ev in line.events:
                    k = bisect.bisect_right(starts, ev.start_ns) - 1
                    names = (op_names.get(modules[k][2], {})
                             if k >= 0 and ev.start_ns < modules[k][1]
                             else {})
                    op = tracing.op_name_kind(ev.name)[0]
                    out.append(scope_of(names.get(op)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(ENGINE):
                        query = dict(ev.stats).get("query")
                        spans.append([ev.name, ev.start_ns, ev.duration_ns,
                                      None if query is None else int(query)])
    return {**events, "scopes": scopes, "spans": spans}


def _innermost(gap, spans) -> dict:
    """Seconds of one idle gap per innermost covering span: the gap is
    cut at every span edge inside it, and each piece goes to the
    shortest span that covers it (``(no span)`` where none does)."""
    s0, e0 = gap
    inside = [(n, s, e) for n, s, e in spans if s < e0 and e > s0]
    cuts = sorted({s0, e0} | {x for _, s, e in inside for x in (s, e)
                              if s0 < x < e0})
    out = {}
    for a, b in zip(cuts, cuts[1:]):
        cover = [(e - s, n) for n, s, e in inside if s <= a and e >= b]
        name = min(cover)[1] if cover else "(no span)"
        out[name] = out.get(name, 0.0) + (b - a) * 1e-9
    return out


def reduce(events: dict) -> dict:
    """``tracing.reduce`` plus ``scope_s`` ({scope: device self seconds,
    mean over the devices}, ``None`` for operations outside every
    scope) and ``gaps_by_span`` ({span: idle seconds, mean over the
    devices}), all inside the ``bench.window`` span."""
    summary = tracing.reduce(events)
    (w0, w1), = [(s, s + d) for n, s, d in events["host"]
                 if n == tracing.WINDOW]
    spans = [(n, s, s + d) for n, s, d in events["host"]
             if n != tracing.WINDOW]
    spans += [(n, s, s + d) for n, s, d, _ in events.get("spans", [])]
    planes = sorted(events["devices"])
    scope_s, gaps = {}, {}
    for plane in planes:
        ops = events["devices"][plane]
        scopes = events.get("scopes", {}).get(plane, [None] * len(ops))
        clipped = [(max(s, w0), min(s + d, w1), n, sc)
                   for (n, _, s, d), sc in zip(ops, scopes)
                   if s < w1 and s + d > w0]
        for _, sc, t in tracing._self_times(clipped):
            scope_s[sc] = scope_s.get(sc, 0.0) + t / len(planes)
        merged = tracing._merge((s, e) for s, e, _, _ in clipped)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                for name, t in _innermost((s, e), spans).items():
                    gaps[name] = gaps.get(name, 0.0) + t / len(planes)
    return {**summary, "scope_s": scope_s, "gaps_by_span": gaps}
