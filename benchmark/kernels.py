"""The program's device-side op scopes in a profiler trace, per chip.

A `jax.named_scope` around part of the traced program reaches the HLO
metadata of each op it holds; the TPU profiler writes that name stack as
the ``tf_op`` stat of the op's event metadata on the device plane, e.g.
``jit(chunk)/while/body/closed_call/igg.stokes.pt/pallas_call:``.
`jax.profiler.ProfileData` gives the events but not their metadata's
stats, so this module reads those from the ``*.xplane.pb`` file itself,
with a decoder of the few fields of the protobuf wire format it needs
(``XSpace.planes``; ``XPlane.name``, ``event_metadata``,
``stat_metadata``; ``XEventMetadata.name`` and ``stats``), and joins them
to the "XLA Ops" events by name. Control-flow containers are left out, as
in `trace.load`.

The readers `pt_kernel_roofline` and `pt_slab_pct` take the device time
of the ops under one scope: the union of their intervals in the traced
window, per chip. A trace whose ops carry no such scope (another model,
or a program without the scopes) gives nothing."""

from __future__ import annotations

from benchmark import boundary
from benchmark import trace as TR

STACK_STAT = "tf_op"


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
    """``(field number, value)`` of one message: an int for a varint, the
    bytes for a length-delimited field; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield num, v
        elif wire == 2:
            size, i = _varint(buf, i)
            yield num, buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")


def _map_values(entry):
    """The value of one map entry (key 1, value 2)."""
    return next((v for num, v in _fields(entry) if num == 2), b"")


def _stacks(plane: bytes) -> tuple:
    """``(plane name, {event metadata name: name stack})`` of one
    ``XPlane``."""
    name, metas, stat_names = "", [], {}
    for num, v in _fields(plane):
        if num == 2:
            name = bytes(v).decode()
        elif num == 4:
            metas.append(_map_values(v))
        elif num == 5:
            sid, sname = 0, ""
            for n2, v2 in _fields(_map_values(v)):
                if n2 == 1:
                    sid = v2
                elif n2 == 2:
                    sname = bytes(v2).decode()
            stat_names[sid] = sname
    if not name.startswith("/device:"):
        return name, {}
    stack_ids = {k for k, v in stat_names.items() if v == STACK_STAT}
    out = {}
    for md in metas:
        ev_name, stack = "", None
        for num, v in _fields(md):
            if num == 2:
                ev_name = bytes(v).decode()
            elif num == 5:
                sid, text = None, None
                for n2, v2 in _fields(v):
                    if n2 == 1:
                        sid = v2
                    elif n2 == 5:
                        text = bytes(v2).decode()
                    elif n2 == 7:
                        text = stat_names.get(v2)
                if sid in stack_ids and text is not None:
                    stack = text
        if stack is not None:
            out[ev_name] = stack
    return name, out


def load(path: str) -> dict:
    """``{device name: [(name stack, start, end)]}`` of the ops on each
    device plane's "XLA Ops" line whose metadata holds a name stack, with
    device names as `trace.load` gives them ("TPU:0")."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        space = memoryview(f.read())
    stacks = dict(_stacks(v) for num, v in _fields(space) if num == 1)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        by_name = stacks.get(plane.name)
        if not by_name:
            continue
        ops = []
        for line in plane.lines:
            if line.name != TR.OPS_LINE:
                continue
            for e in line.events:
                stack = by_name.get(e.name)
                if (stack is not None and e.duration_ns > 0
                        and TR.op_kind(e.name) not in TR.CONTAINERS):
                    ops.append((stack, e.start_ns, e.end_ns))
        out[plane.name.replace("/device:", "")] = ops
    return out


def scoped_ops(tr: TR.Trace) -> dict:
    """`load` of the profile ``tr`` was read from: ``tr.scoped_ops``
    where set, else the captured profile whose benchmark spans are
    ``tr``'s (kept as ``tr.scoped_ops`` for the next reader)."""
    if getattr(tr, "scoped_ops", None) is None:
        path = next((p for p in boundary._trace_files()
                     if boundary.load_spans(p)[1] == tr.spans), None)
        tr.scoped_ops = load(path) if path else {}
    return tr.scoped_ops


def under(stack: str, scope: str) -> bool:
    """Whether a name stack holds ``scope`` as one of its parts."""
    return scope in stack.rstrip(":").split("/")


def scope_ns(ctx, scope: str):
    """Per chip of ``ctx.devices``, the device time in ns of the ops under
    ``scope`` in the traced window (the union of their intervals); None
    where no op of any chip there carries the scope."""
    ops = scoped_ops(ctx.trace)
    out = []
    for d in ctx.devices:
        mine = [(s, e) for stack, s, e in ops.get(d.name, ())
                if under(stack, scope)]
        out.append(TR.total(TR.merge(TR.clip(mine, ctx.window))))
    return out if any(out) else None
