"""The PT Stokes cells of BENCHMARK.json on the CPU, and the readers of the
program's device-side scopes (`benchmark/kernels.py`).

Both cells load by name and run whole at local 10x9x8 (the look for a
chip skipped): sound runs are correct and the lower-precision control is
not. The readers find the fused PT pass and the send-slab computes on a
small trace recorded on four v5e chips
(`data/v5e_stokes_small.xplane.pb`: the stokes3d-256.supervised-2x2 cell
at 64^3 local, one traced chunk of 10 PT iterations, cut to the planes,
lines and stats the readers take, each op's name to its instruction name
and kind), and nothing on a trace of the diffusion program."""

import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import harness, kernels, spec  # noqa: E402
from benchmark import trace as TR  # noqa: E402
from benchmark.layout import Layout  # noqa: E402

CELLS = ("stokes3d-256.supervised-200", "stokes3d-256.supervised-2x2")
READERS = ("pt_kernel_roofline", "pt_slab_pct")
DATA = Path(__file__).parent / "data"
STOKES_TRACE = DATA / "v5e_stokes_small.xplane.pb"
DIFFUSION_TRACE = DATA / "v5e_diffusion_spans.xplane.pb"
# how the fixture was recorded
TRACE_LOCAL_N, TRACE_MESH, TRACE_STEPS = (64, 64, 64), (2, 2, 1), 10


@pytest.fixture(autouse=True)
def _short_runs(monkeypatch):
    monkeypatch.setattr(harness, "WARMUP_CHUNKS", 1)
    monkeypatch.setattr(harness, "SAMPLE_FROM_FIRST", 4)


@pytest.mark.parametrize("control", [False, True], ids=["sound", "control"])
@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_on_the_cpu(name, control):
    cell = spec.load_cell(name)
    assert set(cell.readers) >= ({"pt_kernel_roofline", "pt_slab_pct"}
                                 if cell.chips == 4
                                 else {"pt_kernel_roofline"})
    r = harness.run_cell(cell, 2 ** 32 + 4321, 0.3, False,
                         t_start=time.perf_counter(), require_tpu=False,
                         local_n=(10, 9, 8), control=control)
    c = r["compared"]["max_rel_err"]
    assert r["failed"] == 0 and r["correct"] is (not control)
    assert (c["value"] <= c["limit"]) is (not control)


def _readers():
    r = spec.load_cell(CELLS[1]).readers
    return {name: r[name] for name in READERS}


def _ctx(path, steps=0, bytes_per_step=0.0, peak=None):
    tr = TR.load(str(path))
    tr.scoped_ops = kernels.load(str(path))
    return harness.LayerContext(tr, TR.Window.of(tr, harness.ADVANCE),
                                tr.devices, steps, bytes_per_step, peak)


def test_readers_on_the_recorded_stokes_trace():
    cell = spec.load_cell(CELLS[1])
    layout = Layout(TRACE_LOCAL_N, TRACE_MESH, {
        k: f["stagger"] for k, f in cell.config["fields"].items()})
    ctx = _ctx(STOKES_TRACE, TRACE_STEPS,
               harness.algorithmic_bytes(cell.config, layout),
               spec.hbm_peak_bytes_per_s("TPU v5 lite"))
    assert len(ctx.devices) == 4
    pt = kernels.scope_ns(ctx, "igg.stokes.pt")
    slabs = kernels.scope_ns(ctx, "igg.stokes.slabs")
    assert all(t > 0 for t in pt) and all(t > 0 for t in slabs)
    # every chip ran the same program: the scoped times agree
    assert max(pt) < 1.1 * min(pt) and max(slabs) < 1.1 * min(slabs)
    for name, reader in _readers().items():
        v = reader.read(ctx)
        assert v is not None and 0 < v <= 100, (name, v)


def test_readers_read_nothing_without_the_scopes():
    ctx = _ctx(DIFFUSION_TRACE, 100, 1e6, 819e9)
    assert kernels.scope_ns(ctx, "igg.stokes.pt") is None
    for name, reader in _readers().items():
        assert reader.read(ctx) is None, name


def test_a_scope_is_a_whole_part_of_the_name_stack():
    stack = "jit(chunk)/while/body/closed_call/igg.stokes.pt/pallas_call:"
    assert kernels.under(stack, "igg.stokes.pt")
    assert kernels.under("jit(chunk)/igg.stokes.pt:", "igg.stokes.pt")
    assert not kernels.under(stack, "igg.stokes")
    assert not kernels.under(stack.replace(".pt", ".pt2"), "igg.stokes.pt")
