"""Profiling/tracing — the TPU-native upgrade of the reference's timing story.

The reference offers only `tic`/`toc` (`/root/reference/src/tools.jl:230-236`)
and keeps its streams/tasks persistent partly so external profilers can see
the overlap structure (`src/update_halo.jl:207` note). On TPU the profiler IS
the external tool: `jax.profiler` captures an XLA trace (HLO ops, fusion
boundaries, collective overlap, HBM traffic) viewable in XProf/TensorBoard or
Perfetto. This module wraps it with the framework's naming conventions AND
analyzes the capture in-process (`utils/xplane.py` decodes the profile
protobuf directly), so comm/compute overlap is a NUMBER the framework can
report, not a screenshot:

    with igg.trace("/tmp/igg_trace"):
        T = igg.sync(run_diffusion(T, Cp, p, nt))  # whole hot loop captured

    stats = igg.overlap_stats("/tmp/igg_trace")
    # {'TPU:0': {'busy_us': ..., 'comm_us': ..., 'hidden_comm_us': ...,
    #            'exposed_comm_us': ..., 'overlap_frac': ...}, ...}

    igg.op_breakdown("/tmp/igg_trace")   # top ops by device time

`overlap_stats` is the quantitative analog of inspecting the reference's
max-priority-stream overlap in Nsight: collectives (`collective-permute` =
the exchange's ppermutes, plus all-reduce/all-gather) are attributed from
the device planes' "XLA Ops"/"Async XLA Ops" lines; async collective spans
that run concurrently with compute intervals count as HIDDEN communication.
"""

from __future__ import annotations

import contextlib
import re

__all__ = ["trace", "annotate", "overlap_stats", "op_breakdown"]

# The PR-2 `health_counters`/`record_health_event`/`reset_health_counters`
# shims that lived here were RETIRED after two majors of deprecation
# notice (PRs 3-9): the resilient runtime records through
# `telemetry.hooks.record_health_event` and readers consume the
# ``igg_health_events_total{kind=...}`` family via
# ``igg.metrics_registry()`` / ``igg.prometheus_snapshot()``.


@contextlib.contextmanager
def trace(log_dir: str, *, create_perfetto_link: bool = False):
    """Capture a `jax.profiler` trace of the enclosed block into ``log_dir``.

    Pass the block's outputs through `igg.sync` before exiting so trailing
    device work lands inside the capture window. Analyze the capture with
    `overlap_stats`/`op_breakdown`, or open it in XProf/TensorBoard.
    """
    import jax

    with jax.profiler.trace(log_dir, create_perfetto_link=create_perfetto_link):
        yield


_annotation = None  # jax.profiler.TraceAnnotation, bound at first use


def annotate(name: str, **stats):
    """Named region in the profiler timeline (XLA `TraceAnnotation`): shows
    up around everything dispatched inside the block. Scalar ``stats``
    become the event's metadata (``event.stats`` in
    `jax.profiler.ProfileData`). With no profiler running, entering and
    leaving the region costs under a microsecond; jax is imported at the
    first call, not with this module."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    return _annotation(name, **stats)


# HLO ops that move data between devices. `collective-permute` is the
# exchange's wire op (one pair per axis — tests/test_hlo_audit.py); the rest
# guard against hidden collectives sneaking into a "local" program.
_COMM_RE = re.compile(
    r"collective-permute|all-reduce|all-gather|all-to-all|reduce-scatter"
    r"|ppermute|send|recv", re.IGNORECASE)

_OP_KIND_RE = re.compile(r"\s([a-z][a-z0-9._-]*)\(")


def _op_kind(name: str) -> str:
    """Short op kind from an HLO event name ('%fusion.3 = f32[…] fusion(…)'
    -> 'fusion'); module-level events ('jit_step(123…)') keep their title.

    Tuple-typed ops ('%f = (f32[…], f32[…]) fusion(…)') put spaces inside
    the type, so the kind is located as the last lowercase token before a
    '(' AFTER skipping a parenthesized tuple type when present."""
    rhs = name.split(" = ", 1)[-1]
    if rhs.startswith("("):  # tuple type: skip to its closing paren
        depth = 0
        for i, ch in enumerate(rhs):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    rhs = rhs[i + 1:]
                    break
    m = _OP_KIND_RE.search(" " + rhs)
    if m:
        return m.group(1)
    # short-form names (real captures emit e.g. 'copy.15', 'fusion.35'):
    # drop the instruction suffix so kinds aggregate
    short = name.split("(")[0].strip() or name
    return re.sub(r"\.\d+$", "", short)


_planes_cache: dict = {}


def _all_planes(log_dir: str):
    """All planes of the newest capture; memoized on the capture files'
    (path, mtime, size) so overlap_stats + op_breakdown on the same trace
    decode the (potentially large) protobuf once. Only the most recent
    trace is retained (size-1 cache): analyzing several large traces in
    one process must not accumulate all their decoded events."""
    import os

    from .xplane import find_xplane_files, parse_xspace

    files = find_xplane_files(log_dir)
    key = tuple((p, os.path.getmtime(p), os.path.getsize(p)) for p in files)
    hit = _planes_cache.get(log_dir)
    if hit is not None and hit[0] == key:
        return hit[1]
    planes = []
    for path in files:
        planes.extend(parse_xspace(path))
    _planes_cache.clear()
    _planes_cache[log_dir] = (key, planes)
    return planes


def _device_planes(log_dir: str):
    return [p for p in _all_planes(log_dir)
            if p.name.startswith("/device:")]


def _merge(intervals):
    """Union of [start, end) intervals; returns merged list and total."""
    if not intervals:
        return [], 0
    intervals = sorted(intervals)
    out = [list(intervals[0])]
    for s, e in intervals[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out, sum(e - s for s, e in out)


def _intersect_total(a, b):
    """Total overlap between two MERGED interval lists."""
    i = j = 0
    total = 0
    while i < len(a) and j < len(b):
        s = max(a[i][0], b[j][0])
        e = min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def overlap_stats(log_dir: str):
    """Comm/compute overlap numbers per device plane of the NEWEST capture
    under ``log_dir``.

    For each `/device:*` plane: compute intervals come from the non-comm
    events of every op line; comm intervals from events matching the
    collective patterns on any line — crucially including the "Async XLA
    Ops" line, where an async collective's event SPANS start→done, so the
    span's intersection with compute intervals measures communication the
    scheduler actually hid (the XLA analog of the reference overlapping
    its pack kernels and MPI traffic with user kernels on max-priority
    streams). Returns ``{device_name: {busy_us, compute_us, comm_us,
    hidden_comm_us, exposed_comm_us, overlap_frac}}``.

    Captures with no ``/device:`` planes (the XLA:CPU backend, incl. the
    virtual multi-device mesh) fall back to `_host_overlap_stats`, which
    reads the same quantities off the runtime thread-pool lines and
    returns one aggregate ``CPU:threadpool`` entry; an empty dict means
    the capture had neither device planes nor pool events."""
    out = {}
    for plane in _device_planes(log_dir):
        comm = []
        compute = []
        for line in plane.lines:
            if line.name in ("XLA Modules", "Steps", "Framework Ops",
                             "TC Overlay"):
                continue  # containers duplicating the op lines
            # Comm events are recognized on EVERY op line (async collective
            # spans live on "Async XLA Ops"); compute intervals come ONLY
            # from the synchronous "XLA Ops" line — a non-collective async
            # span (copy-start, host offload DMA) is not core compute, and
            # counting it would inflate hidden_comm when a collective
            # merely overlaps another DMA while the core sits idle.
            for ev in line.events:
                if ev.duration_ps <= 0:
                    continue
                iv = (ev.start_ps, ev.end_ps)
                # classify by the OP KIND, not the full HLO text — a fusion
                # consuming '%collective-permute-done.2' is compute, not comm
                if _COMM_RE.search(_op_kind(ev.name)):
                    comm.append(iv)
                elif line.name == "XLA Ops":
                    compute.append(iv)
        out[plane.name.replace("/device:", "")] = _stats_from(comm, compute)
    if not out:
        out = _host_overlap_stats(log_dir)
    return out


def _stats_from(comm, compute) -> dict:
    """The shared stats record of both the device-plane and host-fallback
    paths: merged totals, busy union, and comm∩compute = hidden."""
    comm_m, comm_total = _merge(comm)
    comp_m, comp_total = _merge(compute)
    busy = _merge(comm + compute)[1]
    hidden = _intersect_total(comm_m, comp_m)
    return {
        "busy_us": busy / 1e6,
        "compute_us": comp_total / 1e6,
        "comm_us": comm_total / 1e6,
        "hidden_comm_us": hidden / 1e6,
        "exposed_comm_us": (comm_total - hidden) / 1e6,
        "overlap_frac": hidden / comm_total if comm_total else None,
    }


# Runtime-infrastructure event names on the host thread lines that must
# count as COMMUNICATION: the XLA:CPU backend implements cross-(virtual-)
# device collectives by in-process rendezvous, so a device's exchange
# appears as a `ppermute` thunk span plus nested Rendezvous waits.
_HOST_COMM_RE = re.compile(
    r"^(Rendezvous|InvokeRendezvous|Wait for rendezvous)|^psum",
)


def _host_event_class(ev):
    """Classify one host thread-pool event: ``"comm"`` (collective op
    kinds + the CPU backend's rendezvous machinery), ``"thunk"`` (HLO
    thunk spans: lowercase-named, not C++ infrastructure, not the
    ``while`` container), or ``None`` (completion markers, zero-duration,
    infrastructure). The ONE predicate shared by `_host_overlap_stats`
    and `_host_op_agg` so the two fallbacks can never desynchronize."""
    if ev.duration_ps <= 0 or ev.name.startswith("end: "):
        # completion markers are neither comm nor compute — excluded
        # BEFORE the comm match, or 'end: ppermute.3' would count
        return None
    kind = _op_kind(ev.name)
    if _COMM_RE.search(kind) or _HOST_COMM_RE.search(ev.name):
        return "comm"
    if ev.name[:1].islower() and "::" not in ev.name and kind != "while":
        return "thunk"
    return None


def _host_overlap_stats(log_dir: str):
    """Comm/compute overlap from the HOST thread-pool lines — the fallback
    when the capture has no ``/device:`` planes (the XLA:CPU backend, incl.
    the virtual ``--xla_force_host_platform_device_count`` mesh, attributes
    op execution to runtime pool threads of ``/host:CPU``, not to device
    planes).

    Classification on the pool (``tf_*``) lines: comm = collective op
    kinds (`_COMM_RE`) plus the CPU backend's rendezvous machinery
    (`_HOST_COMM_RE` — ppermute spans block in an in-process rendezvous,
    the CPU analog of an exposed wire transfer); compute = HLO thunk spans,
    recognized as lowercase-named events (``wrapped_add``, ``fusion.3``,
    ``copy.15``…) that are not C++ infrastructure (``::``), not completion
    markers (``end: …``), and not the ``while`` control-flow container
    (its span covers the whole loop body, comm included).

    All pool threads aggregate into ONE ``CPU:threadpool`` entry: virtual
    devices share the pool, so per-thread attribution is meaningless.
    ``hidden_comm_us`` is comm time during which at least one thread was
    computing — communication the runtime actually covered with useful
    work; ``exposed_comm_us`` is comm time with the whole pool idle or
    blocked, the quantity that transfers to ICI-exposed time on hardware
    (round-4 verdict: separate core contention from exposed collectives).

    Caveat: the window must not contain a compile (warm every chunk size
    first) — compiler passes run on the same pool and a CamelCase pass
    name slipping through the lowercase filter is not compute."""
    comm = []
    compute = []
    for plane in _all_planes(log_dir):
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            if not line.name.startswith("tf_"):
                continue
            for ev in line.events:
                cls = _host_event_class(ev)
                if cls == "comm":
                    comm.append((ev.start_ps, ev.end_ps))
                elif cls == "thunk":
                    compute.append((ev.start_ps, ev.end_ps))
    if not comm and not compute:
        return {}
    return {"CPU:threadpool": _stats_from(comm, compute)}


def op_breakdown(log_dir: str, top: int = 12):
    """Aggregate device time by op kind over the NEWEST capture under
    ``log_dir``: ``[(kind, total_us, count), …]`` sorted by time. Fusions
    appear as 'fusion', the exchange's wire ops as 'collective-permute*',
    Pallas kernels as 'custom-call' (Mosaic kernels are custom calls).

    Captures with no ``/device:`` op events (the XLA:CPU backend, incl.
    the virtual multi-device mesh) fall back to the host thread-pool
    lines — the same fallback `overlap_stats` has — aggregating the HLO
    thunk spans (and the rendezvous comm machinery) by op kind; an empty
    list means the capture had neither."""
    agg: dict = {}
    for plane in _device_planes(log_dir):
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                kind = _op_kind(ev.name)
                t, c = agg.get(kind, (0, 0))
                agg[kind] = (t + ev.duration_ps, c + 1)
    if not agg:
        agg = _host_op_agg(log_dir)
    rows = sorted(((k, t / 1e6, c) for k, (t, c) in agg.items()),
                  key=lambda r: -r[1])
    return rows[:top]


def _host_op_agg(log_dir: str) -> dict:
    """`op_breakdown`'s host thread-pool fallback: per-kind (time, count)
    from the runtime pool (``tf_*``) lines of ``/host:CPU`` planes, using
    the SAME event classification as `_host_overlap_stats`
    (`_host_event_class`): HLO thunk spans plus the collective/rendezvous
    comm spans; completion markers and C++ infrastructure excluded."""
    agg: dict = {}
    for plane in _all_planes(log_dir):
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            if not line.name.startswith("tf_"):
                continue
            for ev in line.events:
                if _host_event_class(ev) is None:
                    continue
                kind = _op_kind(ev.name)
                t, c = agg.get(kind, (0, 0))
                agg[kind] = (t + ev.duration_ps, c + 1)
    return agg
