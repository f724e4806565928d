"""The chunk-boundary split (`benchmark/boundary.py`) and its three readers:
known values on hand-built traces (one chip; four chips with skewed ends;
a plane off the host clock), nothing on a trace of a program without the
driver's spans or of a runtime without the enqueue and done events, and
on a small trace recorded on one v5e with them
(`data/v5e_diffusion_spans.xplane.pb`: the diffusion3d-256.supervised
cell at 128^3 local, chunks of 100 steps): every term at least 0, the sum
equal to `boundary_idle_ms`, and the split unmoved by a shift of the
device plane."""

import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import boundary, harness, spec  # noqa: E402
from benchmark import trace as TR  # noqa: E402

DATA = Path(__file__).parent / "data"
NO_SPANS = DATA / "v5e_diffusion_small.xplane.pb"
SPANS = DATA / "v5e_diffusion_spans.xplane.pb"
READERS = ("boundary_fetch_ms", "boundary_host_ms", "boundary_launch_ms")


def _readers():
    r = spec.load_cell("diffusion3d-256.supervised-2x2").readers
    return {name: r[name] for name in READERS}


def _ctx(tr):
    return harness.LayerContext(tr, TR.Window.of(tr, "bench.advance"),
                                tr.devices, 0, 0.0, None)


def _span(name, s, e, chunk):
    return (name, s, e, {"chunk": chunk, "step": 100 * chunk})


def _rt(name, t):
    return (name, t, t + 0.5, {})


def _enqueue(t):
    return _rt(boundary.ENQUEUE, t)


def _done(t):
    return _rt(boundary.DONE, t)


def _one_chip(shift=0):
    """Three runs, two boundaries; the plane ``shift`` ns off the host."""
    dev = TR.Device("TPU:0")
    dev.modules = [("jit_chunk", s + shift, e + shift) for s, e in
                   ((0, 100), (110, 200), (215, 300))]
    dev.modules.append(("jit_small", 101 + shift, 102 + shift))
    tr = TR.Trace([dev], [("bench.advance", -10, 104),
                          ("bench.advance", 104, 205),
                          ("bench.advance", 205, 320)])
    tr.program_spans = sorted([
        _span("igg.dispatch", 1, 2, 0), _span("igg.guard_fetch", 2, 103, 0),
        _done(101),
        _span("igg.dispatch", 106, 108, 1), _enqueue(107),
        _span("igg.guard_fetch", 108, 204, 1), _done(202),
        _span("igg.dispatch", 209, 211, 2), _enqueue(211),
        _span("igg.guard_fetch", 211, 302, 2)], key=lambda s: s[1])
    return tr


@pytest.mark.parametrize("shift", [0, -6, 7])
def test_one_chip_split_on_hand_built_spans(shift):
    """On the host clock causality leaves the plane a shift in
    [max(107-110, 211-215), min(101-100, 202-200)] = [-3, 1]: the middle,
    -1, places run ends at 99 and 199 and starts at 109 and 214. A plane
    ``shift`` ns off, where recorded as is run 1 would start before its
    dispatch (-6) or run 0 end after its fetch (+7), reads the same."""
    tr = _one_chip(shift)
    w = _ctx(tr).window
    runs = [TR.chunk_runs(tr.devices[0], w)]
    raw_fetch, raw_launch = 103 - runs[0][0][1], runs[0][1][0] - 106
    assert (min(raw_fetch, raw_launch) < 0) == (shift != 0)
    pairs = boundary._pairs(tr.program_spans, runs, w)
    assert boundary.offset_ranges(tr.program_spans, runs, pairs) == \
        [(-3 - shift, 1 - shift)]
    # the last fetch has no dispatch after it: two boundaries
    assert boundary.boundaries(tr.program_spans, tr.devices, w) == \
        [(4, 3, 3), (5, 5, 5)]
    got = {k: r.read(_ctx(tr)) for k, r in _readers().items()}
    assert got == pytest.approx({"boundary_fetch_ms": 4.5e-6,
                                 "boundary_host_ms": 4e-6,
                                 "boundary_launch_ms": 4e-6})
    assert sum(got.values()) == pytest.approx(
        sum(boundary.module_gaps(tr.devices, w)[:2]) / 2 / 1e6)
    assert boundary.span_means_ms(tr.program_spans, w) == \
        pytest.approx({"igg.dispatch": 5 / 3 / 1e6,
                       "igg.guard_fetch": 288 / 3 / 1e6})


def test_four_chips_with_skewed_ends():
    """Each chip gets its own shift (0, 0.5, 2, 0 from the enqueue at 108
    and the done at 104); e_k is then the latest end over the chips,
    s_{k+1} the earliest start."""
    devs = []
    for i, (e0, s1) in enumerate([(100, 112), (101, 110), (97, 111),
                                  (99, 113)]):
        d = TR.Device(f"TPU:{i}")
        d.modules = [("jit_chunk", i, e0), ("jit_chunk", s1, 200 + i)]
        devs.append(d)
    tr = TR.Trace(devs, [("bench.advance", 0, 106),
                         ("bench.advance", 106, 210)])
    tr.program_spans = [
        _span("igg.dispatch", 0, 1, 0), _span("igg.guard_fetch", 50, 105, 0),
        _done(104), _span("igg.dispatch", 107, 108, 1), _enqueue(108),
        _span("igg.guard_fetch", 108, 209, 1)]
    w = _ctx(tr).window
    runs = [TR.chunk_runs(d, w) for d in devs]
    assert boundary.offset_ranges(
        tr.program_spans, runs, boundary._pairs(tr.program_spans, runs, w)
    ) == [(-4, 4), (-2, 3), (-3, 7), (-5, 5)]
    got = {k: r.read(_ctx(tr)) for k, r in _readers().items()}
    assert got == pytest.approx({"boundary_fetch_ms": 3.5e-6,
                                 "boundary_host_ms": 2e-6,
                                 "boundary_launch_ms": 3.5e-6})
    assert boundary.module_gaps(devs, w) == [9]


@pytest.mark.parametrize("case", ["no enqueue", "no done", "empty range"])
def test_no_place_on_the_host_clock_reads_nothing(case):
    """A runtime that leaves out the enqueue or the done event, or runs
    that no one shift fits (here run 1 ends after the runtime saw it
    done at 190): no split, rather than one that moves with the
    offset."""
    tr = _one_chip()
    drop = {"no enqueue": boundary.ENQUEUE, "no done": boundary.DONE}
    tr.program_spans = [
        _done(190) if case == "empty range" and s == _done(202) else s
        for s in tr.program_spans if s[0] != drop.get(case)]
    w = _ctx(tr).window
    runs = [TR.chunk_runs(tr.devices[0], w)]
    pairs = boundary._pairs(tr.program_spans, runs, w)
    assert len(pairs) == 2
    assert boundary.offset_ranges(tr.program_spans, runs, pairs) == []
    assert all(r.read(_ctx(tr)) is None for r in _readers().values())


def test_a_trace_without_the_spans_reads_nothing(tmp_path, monkeypatch):
    """The recorded trace of a program without the driver's spans, found
    the way the harness leaves it: in the temporary directory."""
    prof = tmp_path / "bench_trace_x" / "plugins" / "profile" / "1"
    prof.mkdir(parents=True)
    shutil.copy(NO_SPANS, prof / "host.xplane.pb")
    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))
    tr = TR.load(str(prof / "host.xplane.pb"))
    found = boundary.program_spans(tr)
    assert found and not any(s[0].startswith("igg.") for s in found)
    ctx = harness.LayerContext(tr, TR.Window.of(tr, "bench.advance"),
                               tr.devices, 600, 12 * 256 ** 3, 819e9)
    assert all(r.read(ctx) is None for r in _readers().values())


def test_program_spans_finds_the_profile_by_its_benchmark_spans(
        tmp_path, monkeypatch):
    for i, src in enumerate((SPANS, NO_SPANS)):
        prof = tmp_path / f"bench_trace_{i}" / "plugins" / "profile" / "1"
        prof.mkdir(parents=True)
        shutil.copy(src, prof / "host.xplane.pb")
    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))
    tr = TR.load(str(SPANS))
    spans = boundary.program_spans(tr)
    assert spans and spans == boundary.load_spans(str(SPANS))[0]


def _recorded(shift=0):
    tr = TR.load(str(SPANS))
    tr.program_spans = boundary.load_spans(str(SPANS))[0]
    for d in tr.devices:
        d.modules = [(n, s + shift, e + shift) for n, s, e in d.modules]
        d.ops = [(n, s + shift, e + shift) for n, s, e in d.ops]
        d.comm = [(s + shift, e + shift) for s, e in d.comm]
    return tr


def test_recorded_chip_trace_with_spans():
    tr = _recorded()
    spans = tr.program_spans
    assert {s[0] for s in spans} == {
        "igg.prepare", "igg.dispatch", "igg.guard_fetch", "igg.commit",
        "igg.perf_watch", boundary.ENQUEUE, boundary.DONE}
    ctx = _ctx(tr)
    b = boundary.boundaries(spans, tr.devices, ctx.window)
    assert len(b) == len(boundary.module_gaps(tr.devices, ctx.window)) == 4
    assert all(term >= 0 for x in b for term in x)
    got = [r.read(ctx) for r in _readers().values()]
    idle = spec.load_cell("diffusion3d-256.supervised").readers[
        "boundary_idle_ms"].read(ctx)
    # one chip, nothing else on it in the gaps: the split sums to the idle
    assert sum(got) == pytest.approx(idle, rel=1e-6)


@pytest.mark.parametrize("shift_ms", [-1.5, 1.6])
def test_recorded_trace_with_its_plane_off_the_host_clock(shift_ms):
    """The recorded plane moved 1.5 ms early (every run then "starts"
    before its dispatch) or 1.6 ms late (every run "ends" after its guard
    fetch), the window widened by as much: the aligned split is the
    recorded one, every term >= 0."""
    shift = int(shift_ms * 1e6)
    tr, ref = _recorded(shift), _recorded()
    w0 = _ctx(ref).window
    w = TR.Window(w0.start + min(shift, 0), w0.end + max(shift, 0))
    runs = [TR.chunk_runs(tr.devices[0], w)]
    pairs = boundary._pairs(tr.program_spans, runs, w)
    assert len(pairs) == 4
    if shift < 0:  # run k+1 on the plane as shifted, against D's start
        assert all(runs[0][k + 1][0] < d0 for _, _, d0, (k,) in pairs)
    else:  # run k's end against F's end
        assert all(runs[0][k][1] > f1 for _, f1, _, (k,) in pairs)
    b = boundary.boundaries(tr.program_spans, tr.devices, w)
    assert b == pytest.approx(boundary.boundaries(
        ref.program_spans, ref.devices, w0))
    assert len(b) == 4 and all(term >= 0 for x in b for term in x)
