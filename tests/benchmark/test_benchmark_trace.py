"""The benchmark's trace reduction: interval arithmetic and the per-layer
readers on synthetic intervals, and on a small trace recorded on one v5e
(`data/v5e_diffusion_small.xplane.pb`: 6 chunks of 100 steps of the
diffusion3d-256.supervised cell, PR 22)."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import harness, spec  # noqa: E402
from benchmark import trace as TR  # noqa: E402

RECORDED = Path(__file__).parent / "data" / "v5e_diffusion_small.xplane.pb"


def test_interval_arithmetic():
    m = TR.merge([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert m == [(0, 3), (5, 8)] and TR.total(m) == 6
    assert TR.intersect(m, [(2, 6)]) == [(2, 3), (5, 6)]
    assert TR.complement(m, -1, 10) == [(-1, 0), (3, 5), (8, 10)]
    assert TR.complement([], 0, 4) == [(0, 4)]
    w = TR.Window(1, 6)
    assert TR.clip([(0, 2), (5, 9), (7, 8)], w) == [(1, 2), (5, 6)]


def test_op_kinds_and_names():
    hlo = ("%closed_call.8 = f32[256,256,256]{2,1,0:T(8,128)} custom-call("
           "f32[256,256,256]{2,1,0} %a), custom_call_target=\"tpu\"")
    assert TR.op_kind(hlo) == "custom-call"
    assert TR.short_name(hlo) == "%closed_call.8 custom-call"
    tup = "%f = (f32[2]{0}, f32[2]{0}) fusion(f32[2]{0} %x), kind=kLoop"
    assert TR.op_kind(tup) == "fusion"
    assert TR.is_comm("%collective-permute-start.1 = f32[4]{0} "
                      "collective-permute-start(f32[4]{0} %x)")
    assert not TR.is_comm("%fusion.2 = f32[4]{0} fusion(f32[4]{0} "
                          "%collective-permute-done.1)")


def _synthetic():
    """Two chunk programs [0, 100) and [110, 200) on one device with ops
    inside; a collective [20, 50) of which [30, 40) has no compute."""
    dev = TR.Device("TPU:0")
    dev.modules = [("jit_chunk(1)", 0, 100), ("jit_chunk(1)", 110, 200),
                   ("jit_other(2)", 100, 102)]
    dev.ops = [("%a fusion", 0, 30), ("%b fusion", 40, 100),
               ("%c fusion", 100, 102), ("%a fusion", 110, 200)]
    dev.comm = [(20, 50)]
    spans = [("bench.window", 0, 200), ("bench.advance", 0, 101),
             ("bench.advance", 101, 200)]
    return TR.Trace([dev], spans), dev


def _ctx(tr, steps=10, bytes_per_step=1e3, peak=1e9):
    return harness.LayerContext(tr, TR.Window.of(tr, "bench.advance"),
                                tr.devices, steps, bytes_per_step, peak)


def test_per_device_quantities_on_synthetic_intervals():
    tr, dev = _synthetic()
    w = TR.Window.of(tr, "bench.advance")
    assert (w.start, w.end) == (0, 200)
    assert TR.total(TR.compute(dev, w)) == 182
    assert TR.total(TR.busy(dev, w)) == 192
    assert TR.exposed_comm(dev, w) == 10
    assert TR.chunk_runs(dev, w) == [(0, 100), (110, 200)]
    # the boundary [100, 110) holds 2 ns of another program's op
    assert TR.boundary_idle(dev, w) == [8]
    # [30, 40) is covered by the collective: one idle gap
    assert TR.idle_gaps(dev, w, tr.spans) == [("bench.advance", 8e-9)]
    assert TR.top_ops([dev], w, 2) == [("%a fusion", 120e-9),
                                       ("%b fusion", 60e-9)]


def test_readers_on_synthetic_intervals():
    tr, _ = _synthetic()
    readers = spec.load_cell("diffusion3d-256.supervised-2x2").readers
    ctx = _ctx(tr)
    assert readers["device_idle_pct"].read(ctx) == pytest.approx(4.0)
    assert readers["exposed_comm_pct"].read(ctx) == pytest.approx(5.0)
    assert readers["boundary_idle_ms"].read(ctx) == pytest.approx(8e-6)
    assert readers["chunk_p95_ms"].read(ctx) is None  # one interval only
    # 10 steps x 1e3 B over 182 ns of compute at 1e9 B/s
    assert readers["step_roofline"].read(ctx) == pytest.approx(
        100 * 1e4 / 182e-9 / 1e9)


def test_readers_find_nothing_and_return_nothing():
    dev = TR.Device("TPU:0")
    tr = TR.Trace([dev], [("bench.advance", 0, 10)])
    readers = spec.load_cell("diffusion3d-256.supervised-2x2").readers
    ctx = _ctx(tr, steps=0)
    for name in ("step_roofline", "boundary_idle_ms", "chunk_p95_ms",
                 "exposed_comm_pct"):
        assert readers[name].read(ctx) is None, name


def test_recorded_chip_trace():
    tr = TR.load(str(RECORDED))
    assert [d.name for d in tr.devices] == ["TPU:0"]
    dev = tr.devices[0]
    w = TR.Window.of(tr, "bench.advance")
    assert len(TR.chunk_runs(dev, w)) == 6 and not dev.comm
    assert all(not n.startswith("%while") for n, _, _ in dev.ops)
    ctx = harness.LayerContext(tr, w, tr.devices, 600, 12 * 256 ** 3,
                               819e9)
    got = {name: r.read(ctx) for name, r in
           spec.load_cell("diffusion3d-256.supervised").readers.items()}
    # the fixture predates the driver's igg.* spans: the three boundary
    # split readers find nothing to read there and return None
    assert got == pytest.approx({
        "step_roofline": 96.77891036095679,
        "device_idle_pct": 7.006857028588243,
        "boundary_idle_ms": 1.8435112,
        "chunk_p95_ms": 27.5596204,
        "boundary_fetch_ms": None, "boundary_host_ms": None,
        "boundary_launch_ms": None}, rel=1e-9)
    top = TR.top_ops(tr.devices, w, 1)[0]
    assert top[0].endswith("custom-call") and top[1] > 0.03
