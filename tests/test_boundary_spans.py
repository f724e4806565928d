"""The supervised driver's chunk-boundary spans and phase counters: one
``igg.prepare`` / ``igg.dispatch`` / ``igg.guard_fetch`` / ``igg.commit``
per chunk (``igg.perf_watch`` nested in the commit) on the profiler's
clock, read back through `benchmark/boundary.py`; the
``igg_boundary_seconds_total{phase}`` counters partition the host time
between dispatches; flight records are as they were."""

import glob
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import implicitglobalgrid_tpu as igg
from implicitglobalgrid_tpu.runtime.driver import ResilientRun, RunSpec

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark import boundary  # noqa: E402

pytestmark = pytest.mark.telemetry

SPANS = ("igg.prepare", "igg.dispatch", "igg.guard_fetch", "igg.commit")


def _run(nt=40, nt_chunk=10, **spec):
    from implicitglobalgrid_tpu.models import (
        diffusion_step_local, init_diffusion3d,
    )

    igg.init_global_grid(8, 8, 8, dimx=2, dimy=2, dimz=2, periodx=1,
                         periody=1, periodz=1, quiet=True)
    T, Cp, p = init_diffusion3d(dtype=np.float32)

    def step(s):
        return {"T": diffusion_step_local(s["T"], s["Cp"], p, "xla"),
                "Cp": s["Cp"]}

    return ResilientRun(step, {"T": T, "Cp": Cp}, nt,
                        RunSpec(nt_chunk=nt_chunk, key=("spans",), **spec))


def _phases() -> dict:
    fam = igg.metrics_registry().get("igg_boundary_seconds_total")
    return {} if fam is None else {
        labels["phase"]: v for labels, v in fam.samples()}


def test_each_chunk_has_one_span_of_each_phase_in_order(tmp_path):
    run = _run()
    run.advance()  # compile outside the capture
    with igg.trace(str(tmp_path)):
        while run.advance():
            pass
    run.close()
    path, = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    spans, _ = boundary.load_spans(path)
    outer = [s for s in spans if s[0] in SPANS]
    assert [s[0] for s in outer] == list(SPANS) * 3
    for i in range(3):
        four = outer[4 * i:4 * i + 4]
        assert all(s[3] == {"chunk": i + 1, "step": 10 * (i + 1)}
                   for s in four)
        # each phase starts where the previous ended, or later
        assert all(a[2] <= b[1] for a, b in zip(four, four[1:]))
    watch = [s for s in spans if s[0] == "igg.perf_watch"]
    commits = [s for s in outer if s[0] == "igg.commit"]
    assert len(watch) == 3
    for w, c in zip(watch, commits):
        assert c[1] <= w[1] and w[2] <= c[2] and w[3] == c[3]


def test_boundary_phases_partition_host_time_between_dispatches():
    run = _run(nt=60)
    run.advance()
    before = _phases()
    pause = 0.005
    t0 = time.monotonic()
    while True:
        more = run.advance()
        if not more:
            break
        time.sleep(pause)  # the caller's own time
    wall = time.monotonic() - t0
    run.close()
    after = _phases()
    d = {k: after[k] - before.get(k, 0.0) for k in after}
    assert set(d) == {"caller", "prepare", "dispatch", "fetch", "commit"}
    assert all(v > 0 for v in d.values())
    assert d["caller"] >= 4 * pause
    # five boundaries and the gaps between them: the run's wall time less
    # the time before its first boundary began, within 5%
    assert sum(d.values()) == pytest.approx(wall, rel=0.05)


def test_interleaved_jobs_charge_each_stretch_once():
    """A scheduler interleaving two jobs on one thread: each boundary's
    ``caller`` runs from the previous boundary of either job, so the
    phases still sum to the thread's wall time (a per-run ``caller``
    would count the other job's boundaries again)."""
    from implicitglobalgrid_tpu.models import (
        diffusion_step_local, init_diffusion3d,
    )
    from implicitglobalgrid_tpu.service import JobSpec, MeshScheduler

    def setup():
        T, Cp, p = init_diffusion3d(dtype=np.float32)

        def step(s):
            return {"T": diffusion_step_local(s["T"], s["Cp"], p, "xla"),
                    "Cp": s["Cp"]}

        return step, {"T": T, "Cp": Cp}

    def job(name, n):
        return JobSpec(name=name, setup=setup, nt=80,
                       grid=dict(nx=n, ny=n, nz=n, dimx=2, dimy=2, dimz=2),
                       run=RunSpec(nt_chunk=10, key=("spans", name)))

    with MeshScheduler(policy="round_robin") as sched:
        sched.submit(job("a", 8))
        sched.submit(job("b", 10))
        sched.run(max_slices=2)  # admit and compile each job
        before = _phases()
        t0 = time.monotonic()
        sched.run(max_slices=8)  # four boundaries each, a b a b ...
        wall = time.monotonic() - t0
        after = _phases()
        assert all(not j.run.done for j in (sched.job("a"), sched.job("b")))
    d = {k: after[k] - before.get(k, 0.0) for k in after}
    assert set(d) == {"caller", "prepare", "dispatch", "fetch", "commit"}
    assert sum(d.values()) == pytest.approx(wall, rel=0.05)


def test_flight_records_keep_their_kinds_and_fields(tmp_path):
    """The spans add no flight record: kinds, order and fields of a run
    with a checkpoint cadence are those the driver wrote before them."""
    run = _run(nt=30, checkpoint_dir=str(tmp_path / "ck"))
    igg.start_flight_recorder(str(tmp_path / "f.jsonl"), run_id="r")
    try:
        while run.advance():
            pass
        run.close()
    finally:
        events = igg.read_flight_events(igg.stop_flight_recorder())
    common = {"kind", "pid", "proc", "run", "seq", "t"}
    fields = {
        "recorder_open": {"version", "wall"},
        "checkpoint_save": {"dur_s", "op", "path", "step"},
        "runner_cache": {"result"},
        "chunk": {"build_s", "chunk", "exec_s", "n", "ok", "reasons",
                  "step_begin", "step_end"},
        "run_end": {"chunks", "completed"},
        "recorder_close": set()}
    kinds = ["recorder_open", "checkpoint_save"] + [
        "runner_cache", "chunk", "checkpoint_save"] * 3 + [
        "run_end", "recorder_close"]
    assert [e["kind"] for e in events] == kinds
    for e in events:
        extra = {"build_s"} if (e["kind"] == "runner_cache"
                                and e["result"] == "miss") else set()
        assert set(e) == common | fields[e["kind"]] | extra, e["kind"]


def test_record_span_is_its_profiler_span(tmp_path):
    """`record_span` opens the profiler annotation of the same name around
    its block, scalar fields as stats; the flight record is unchanged."""
    igg.start_flight_recorder(str(tmp_path / "f.jsonl"))
    try:
        with igg.trace(str(tmp_path / "tr")):
            with igg.record_span("igg.test_span", chunk=3, label="x",
                                 names=["T"]):
                time.sleep(0.002)
            with igg.annotate("igg.bare", step=7):
                pass
    finally:
        events = igg.read_flight_events(igg.stop_flight_recorder())
    ev, = [e for e in events if e["kind"] == "igg.test_span"]
    assert ev["names"] == ["T"] and ev["chunk"] == 3 and ev["dur_s"] > 0
    path, = glob.glob(f"{tmp_path}/tr/plugins/profile/*/*.xplane.pb")
    spans, _ = boundary.load_spans(path)
    got = {s[0]: s for s in spans}
    assert got["igg.test_span"][3] == {"chunk": 3, "label": "x"}
    assert got["igg.test_span"][2] - got["igg.test_span"][1] >= 2e6
    assert got["igg.bare"][3] == {"step": 7}
