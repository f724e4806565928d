"""The supervised driver's chunk boundary, split by the program's own spans.

`ResilientRun` opens host profiler spans ``igg.prepare``, ``igg.dispatch``,
``igg.guard_fetch``, ``igg.commit`` (and ``igg.perf_watch`` inside it) at
every boundary, with stats ``chunk`` and ``step``. For boundary k of a
traced window:

- ``F`` is the ``igg.guard_fetch`` span that waited on chunk program run
  k, and ``e_k`` is the latest end of run k over the chips;
- ``D`` is the next ``igg.dispatch`` after ``F``; ``s_{k+1}`` is the
  earliest start, over the chips, of the run after run k;
- fetch = ``F.end - e_k`` (device done to guard vector on the host), host
  = ``D.start - F.end`` (commit, the caller, prepare), launch =
  ``s_{k+1} - D.start`` (dispatch call to device start).

The profiler places each device plane on the host clock only to within
about a millisecond per process, as much as the launch term itself. So
each chip's plane is first shifted onto the host clock by the TPU
runtime's own host events (`offset_ranges`), which makes fetch and launch
independent of that offset and each of them at least 0. Their sum is the
gap between chunk program runs. The readers ``boundary_fetch_ms``,
``boundary_host_ms`` and ``boundary_launch_ms`` take the means over the
window's boundaries; a trace without the spans (a program that lacks
them) gives nothing.

Run as a script, this runs one cell as `run.py` does and prints
informational lines before the result: ``boundary_phase_ms`` (the
program's ``igg_boundary_seconds_total`` counter per boundary of the
window after its first) with ``runner_cache_misses_in_window`` in every
run; traced, ``boundary_spans_ms`` (mean duration of each ``igg.*`` span)
and ``boundary_split_ms`` (the three means, whichever cells list the
metrics, beside the mean gap between chunk program runs and the half
width of each chip's offset range)::

    python3 benchmark/boundary.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
"""

import bisect
import glob
import math
import os
import sys
import tempfile

if __package__ in (None, ""):  # run as a script: the checkout's root
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmark import trace as TR  # noqa: E402

PREFIX = "igg."
DISPATCH, FETCH = PREFIX + "dispatch", PREFIX + "guard_fetch"
# the TPU runtime's host events around a program run: it enqueues the
# program before the device starts it, and completes the execution after
# the device ends it
ENQUEUE, DONE = "DoEnqueueProgram", "tpu::System::Execute=>Done"
PHASES_FAMILY = "igg_boundary_seconds_total"
MISSES_FAMILY = "igg_runner_cache_total"


def load_spans(path: str):
    """``(program, bench)`` host events of one ``*.xplane.pb``, each
    sorted by start: the program's ``igg.*`` spans and the runtime's
    enqueue and done events as ``(name, start, end, stats)``, and the
    benchmark's own spans as ``(name, start, end)``."""
    from jax.profiler import ProfileData

    program, bench = [], []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    program.append((e.name, e.start_ns, e.end_ns,
                                    dict(e.stats)))
                elif e.name in (ENQUEUE, DONE):
                    program.append((e.name, e.start_ns, e.end_ns, {}))
                elif e.name.startswith(TR.SPAN_PREFIX):
                    bench.append((e.name, e.start_ns, e.end_ns))
    return (sorted(program, key=lambda s: s[1]),
            sorted(bench, key=lambda s: s[1]))


def _trace_files():
    """The profiles of traced runs in this process's temporary directory
    (where the harness captures), newest first."""
    return sorted(glob.glob(os.path.join(
        tempfile.gettempdir(), "bench_trace_*", "plugins", "profile", "*",
        "*.xplane.pb")), key=os.path.getmtime, reverse=True)


def program_spans(tr: TR.Trace) -> list:
    """The program's host events (`load_spans`) of the trace ``tr`` was
    read from: ``tr.program_spans`` where the loader set it, else those of
    the captured profile whose benchmark spans are ``tr``'s (kept as
    ``tr.program_spans`` for the next reader)."""
    if getattr(tr, "program_spans", None) is None:
        tr.program_spans = next(
            (program for program, bench in map(load_spans, _trace_files())
             if bench == tr.spans), [])
    return tr.program_spans


def _starts(spans, name: str) -> list:
    return sorted(s[1] for s in spans if s[0] == name)


def _pairs(spans, runs, w: TR.Window) -> list:
    """``[(f0, f1, d0, ks)]``, one per boundary of ``w``: ``F``'s start
    and end, ``D``'s start and, per chip, the index of run k in its
    ``runs``. Run k is the run whose end lies nearest ``F``'s end (inside
    ``F``, or past its end by less than half its length), which holds
    while the planes sit off the host clock by less than that; a boundary
    where some chip has no such run, or no run after it, is left out."""
    dispatches = _starts(spans, DISPATCH)
    ends = [[e for _, e in r] for r in runs]
    out = []
    for name, f0, f1, _ in spans:
        if name != FETCH or f0 < w.start or f1 > w.end:
            continue
        i = bisect.bisect_left(dispatches, f1)
        if i == len(dispatches):
            continue
        ks = []
        for es in ends:
            j = bisect.bisect_left(es, f1)
            k = min((j - 1, j), key=lambda k: abs(es[k] - f1)
                    if 0 <= k < len(es) else math.inf)
            if not (0 <= k < len(es) - 1
                    and f0 <= es[k] <= f1 + (f1 - f0) / 2):
                break
            ks.append(k)
        else:
            out.append((f0, f1, dispatches[i], ks))
    return out


def offset_ranges(spans, runs, pairs) -> list:
    """Per chip, the range ``(lo, hi)`` in ns of the shift that places
    its plane on the host clock, as causality leaves it: at every
    boundary, run k+1 starts after the runtime's first program enqueue
    past ``D``'s start (``shift >= enqueue - s_{k+1}``), and run k ends
    before the last execution done inside ``F`` (``shift <= done -
    e_k``). Empty where a boundary lacks either event, or a chip's range
    is empty (the planes would not keep one offset over the window)."""
    enqueues, dones = _starts(spans, ENQUEUE), _starts(spans, DONE)
    lo, hi = [-math.inf] * len(runs), [math.inf] * len(runs)
    for f0, f1, d0, ks in pairs:
        i = bisect.bisect_left(enqueues, d0)
        j = bisect.bisect_right(dones, f1) - 1
        if i == len(enqueues) or j < 0 or dones[j] < f0:
            return []
        for c, (r, k) in enumerate(zip(runs, ks)):
            lo[c] = max(lo[c], enqueues[i] - r[k + 1][0])
            hi[c] = min(hi[c], dones[j] - r[k][1])
    if not pairs or any(a > b for a, b in zip(lo, hi)):
        return []
    return list(zip(lo, hi))


def boundaries(spans, devices, w: TR.Window) -> list:
    """``[(fetch, host, launch)]`` in ns, one per boundary of window ``w``
    that the spans and every device's chunk program runs cover, with each
    chip's plane shifted by the middle of its `offset_ranges` (each term
    is then at least 0, and off by at most the range's half width)."""
    runs = [TR.chunk_runs(d, w) for d in devices]
    if not runs or not all(runs):
        return []
    pairs = _pairs(spans, runs, w)
    ranges = offset_ranges(spans, runs, pairs)
    shift = [(lo + hi) / 2 for lo, hi in ranges]
    out = []
    for _, f1, d0, ks in pairs if ranges else ():
        e_k = max(r[k][1] + x for r, k, x in zip(runs, ks, shift))
        s_next = min(r[k + 1][0] + x for r, k, x in zip(runs, ks, shift))
        out.append((f1 - e_k, d0 - f1, s_next - d0))
    return out


def module_gaps(devices, w: TR.Window) -> list:
    """Gaps in ns between consecutive chunk program runs in ``w``, on the
    planes as recorded: the earliest next start over the chips less the
    latest end."""
    runs = [TR.chunk_runs(d, w) for d in devices]
    n = min((len(r) for r in runs), default=0)
    return [min(r[j + 1][0] for r in runs) - max(r[j][1] for r in runs)
            for j in range(n - 1)]


def mean_ms(ctx, term: int):
    """Mean over the window's boundaries of one term (0 fetch, 1 host,
    2 launch) of a reader's context, in ms; None where the trace holds no
    boundary."""
    b = boundaries(program_spans(ctx.trace), ctx.devices, ctx.window)
    if not b:
        return None
    return sum(x[term] for x in b) / len(b) / 1e6


def span_means_ms(spans, w: TR.Window) -> dict:
    """Mean duration in ms of each ``igg.*`` span name inside ``w``."""
    per: dict = {}
    for name, s, e, _ in spans:
        if name.startswith(PREFIX) and s >= w.start and e <= w.end:
            per.setdefault(name, []).append(e - s)
    return {k: sum(v) / len(v) / 1e6 for k, v in sorted(per.items())}


def _counter(family: str) -> dict:
    """The program's counter ``family`` by its one label's value (empty
    where the program lacks it)."""
    from implicitglobalgrid_tpu.telemetry import metrics_registry

    fam = metrics_registry().get(family)
    return {} if fam is None else {
        next(iter(labels.values())): v for labels, v in fam.samples()}


def main(argv=None) -> int:
    from benchmark import harness, run

    inner_window, inner_layers = harness.run_window, harness.layer_metrics

    def run_window(r, seconds, sample, traced):
        # counted from the end of the window's first boundary, whose
        # caller phase holds the profiler's start in a traced run
        first = []
        advance = r.advance

        def first_advance():
            try:
                return advance()
            finally:
                first.append((_counter(PHASES_FAMILY),
                              _counter(MISSES_FAMILY)))
                r.advance = advance

        r.advance = first_advance
        rec = inner_window(r, seconds, sample, traced)
        (phases0, misses0), = first
        phases, misses = _counter(PHASES_FAMILY), _counter(MISSES_FAMILY)
        n = rec["attempted"] - 1
        harness.info(boundary_phase_ms={
            k: (v - phases0.get(k, 0.0)) / n * 1e3
            for k, v in phases.items()} if n > 0 else {},
            runner_cache_misses_in_window=misses.get("miss", 0.0)
            - misses0.get("miss", 0.0))
        return rec

    def layer_metrics(cell, path, used_ids, *args):
        out = inner_layers(cell, path, used_ids, *args)
        tr = TR.load(path)
        tr.program_spans = spans = load_spans(path)[0]
        w = TR.Window.of(tr, harness.ADVANCE)
        devs = [d for d in tr.devices
                if d.name.rsplit(":", 1)[-1].isdigit()
                and int(d.name.rsplit(":", 1)[-1]) in used_ids]
        runs = [TR.chunk_runs(d, w) for d in devs]
        ranges = offset_ranges(spans, runs, _pairs(spans, runs, w))
        b = boundaries(spans, devs, w)
        gaps = module_gaps(devs, w)
        harness.info(boundary_spans_ms=span_means_ms(spans, w))
        harness.info(boundary_split_ms={
            k: sum(x[i] for x in b) / len(b) / 1e6
            for i, k in enumerate(("fetch", "host", "launch"))} if b else {},
            boundaries=len(b), module_gap_ms=sum(gaps) / len(gaps) / 1e6
            if gaps else None,
            offset_ms=[[lo / 1e6, hi / 1e6] for lo, hi in ranges])
        return out

    harness.run_window, harness.layer_metrics = run_window, layer_metrics
    return run.main(argv)


if __name__ == "__main__":
    import traceback

    try:
        sys.exit(main())
    except Exception:  # no result line: exit nonzero
        traceback.print_exc()
        sys.exit(1)
