"""Benchmark: the mesh-observability pipeline's cost envelope (ISSUE 5).

Two gated figures:

- ``trace_pipeline_s_10k_events``: wall time to aggregate two synthetic
  per-process flight streams totalling ~10k events (clock alignment +
  run-id/seq validation), run the straggler analyzer, and export the
  Chrome/Perfetto trace JSON. All pure post-hoc host work — the gate
  (< 5 s) keeps the operator loop ("the run just died, what happened")
  interactive even for long flights.
- ``metrics_server_off_overhead_frac``: the step-loop cost the mesh layer
  adds to a supervised run when the live endpoint is NOT enabled — the
  per-chunk-boundary heartbeat gauge stamps are the ONLY addition
  (serving runs on its own thread and only when opted in via
  ``metrics_port``). Deterministic accounting like bench_telemetry.py:
  the microbenchmarked per-heartbeat cost times the boundaries a real
  run crosses, over the run's median wall time — target < 2% (measures
  orders of magnitude under; "zero" at the gate's resolution). The row
  also asserts no server thread exists when ``metrics_port`` is unset.

Usage: python bench_trace.py          (real chip)
       python bench_trace.py --cpu    (8-device virtual CPU mesh)
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import tempfile
import time

import bench_util


def _write_synth_stream(path, proc, n_chunks, *, events_between=3,
                        run_id="bench"):
    """One synthetic per-process flight JSONL: a barrier-consistent chunk
    schedule plus interleaved halo/snapshot events, dense enough that two
    processes total ~10k events at the default sizing."""
    t = 1000.0 + 0.001 * proc
    seq = 0
    with open(path, "w") as f:
        def ev(kind, **kw):
            nonlocal seq
            f.write(json.dumps({"t": t, "kind": kind, "run": run_id,
                                "pid": 10 + proc, "proc": proc,
                                "seq": seq, **kw}) + "\n")
            seq += 1

        ev("recorder_open", wall=2000.0 + 0.01 * proc, version=1)
        ev("run_begin", nt=n_chunks * 10, nt_chunk=10, names=["T"])
        for c in range(n_chunks):
            start = t + (0.002 if proc else 0.0)
            t += 0.01
            for i in range(events_between):
                ev("halo_exchange", fields=1, ppermutes=6,
                   wire_bytes=4096, local_copy_bytes=0)
            ev("snapshot_write", step=(c + 1) * 10, dur_s=0.001,
               nbytes=1 << 16, queue_depth=1, path="x")
            ev("chunk", chunk=c, step_begin=c * 10, step_end=(c + 1) * 10,
               n=10, ok=True, reasons=[], build_s=0.001,
               exec_s=t - start)
        ev("run_end", completed=n_chunks * 10, chunks=n_chunks)
        ev("recorder_close")
    return seq


def trace_pipeline_rows(n_events_target: int = 10_000):
    """Aggregate + analyze + export wall time on a synthetic two-process
    stream of ~``n_events_target`` events (host-only; no grid)."""
    import implicitglobalgrid_tpu as igg

    tmp = tempfile.mkdtemp(prefix="igg_bench_trace_")
    # each chunk contributes (events_between + 2) records per process,
    # plus a handful of run-level records
    per_chunk = 3 + 2
    n_chunks = max(1, n_events_target // (2 * per_chunk))
    total = 0
    for proc in range(2):
        total += _write_synth_stream(
            os.path.join(tmp, f"flight_p{proc}.jsonl"), proc, n_chunks)

    t0 = time.monotonic()
    agg = igg.aggregate_flight(tmp)
    t_agg = time.monotonic() - t0
    t0 = time.monotonic()
    rep = igg.straggler_report(agg)
    t_strag = time.monotonic() - t0
    out = os.path.join(tmp, "trace.json")
    t0 = time.monotonic()
    igg.export_chrome_trace(agg, out)
    t_export = time.monotonic() - t0
    assert rep["summary"]["chunks"] == n_chunks
    assert os.path.getsize(out) > 0

    return [{
        "metric": "trace_pipeline_s_10k_events",
        "value": t_agg + t_strag + t_export,
        "unit": "seconds to aggregate+analyze+export (target < 5)",
        "target": 5.0,
        "events": total,
        "aggregate_s": t_agg,
        "stragglers_s": t_strag,
        "export_s": t_export,
        "trace_bytes": os.path.getsize(out),
    }]


def heartbeat_overhead_rows(nx: int, nt_chunk: int, n_chunks: int = 3,
                            reps: int = 5):
    """Deterministic accounting of the server-off step-loop addition (the
    per-boundary heartbeat stamps) on the CURRENT grid — the
    bench_telemetry.py estimator, scoped to the mesh layer."""
    import numpy as np

    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.models import (
        diffusion_step_local, init_diffusion3d,
    )
    from implicitglobalgrid_tpu.telemetry import metrics_server
    from implicitglobalgrid_tpu.telemetry.hooks import note_heartbeat

    T, Cp, p = init_diffusion3d(dtype=np.float32)

    def step(s):
        return {"T": diffusion_step_local(s["T"], s["Cp"], p, "xla"),
                "Cp": s["Cp"]}

    state = {"T": T, "Cp": Cp}
    nt = nt_chunk * n_chunks
    key = ("bench_trace", nx, nt_chunk)

    def run():
        igg.run_resilient(step, state, nt, nt_chunk=nt_chunk, key=key)

    run()  # warm compile
    assert metrics_server() is None  # metrics_port unset -> no server
    times = []
    for _ in range(reps):
        igg.tic()
        run()
        times.append(igg.toc())
    assert metrics_server() is None

    n_probe = 20_000
    t0 = time.monotonic()
    for i in range(n_probe):
        note_heartbeat(i)
    per_call_s = (time.monotonic() - t0) / n_probe
    # boundaries per run: one per loop iteration + the final run_end stamp
    boundaries = n_chunks + 1
    t_med = statistics.median(times)
    return [{
        "metric": "metrics_server_off_overhead_frac",
        "value": per_call_s * boundaries / t_med,
        "unit": "fraction of run time, deterministic per-heartbeat "
                "accounting (target < 0.02)",
        "target": 0.02,
        "nt": nt,
        "nt_chunk": nt_chunk,
        "per_heartbeat_s": per_call_s,
        "boundaries_per_run": boundaries,
        "run_s_median": t_med,
        "note": "metrics_port unset: no server thread exists (asserted); "
                "the per-boundary heartbeat gauge stamps are the only "
                "step-loop addition of the mesh-observability layer",
    }]


def run_trace_overhead(dims, cpu: bool):
    """The canonical leg: host-side pipeline timing plus the server-off
    step-loop accounting on a grid over ``dims``. Shared by this script's
    __main__ and `bench_all.py` so the config stays in ONE place."""
    import implicitglobalgrid_tpu as igg

    rows = trace_pipeline_rows()
    nx, nt_chunk = (32, 60) if cpu else (256, 200)
    igg.init_global_grid(nx, nx, nx, dimx=dims[0], dimy=dims[1],
                         dimz=dims[2], periodx=1, periody=1, periodz=1,
                         quiet=True)
    try:
        rows += heartbeat_overhead_rows(nx, nt_chunk)
    finally:
        igg.finalize_global_grid()
    return rows


def main() -> None:
    cpu = "--cpu" in sys.argv
    if cpu:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        ).strip()
        import jax

        jax.config.update("jax_platforms", "cpu")
    import jax

    import implicitglobalgrid_tpu as igg

    nd = len(jax.devices())
    dims = tuple(int(d) for d in igg.dims_create(nd, (0, 0, 0)))
    for row in run_trace_overhead(dims, cpu):
        bench_util.emit(row)


if __name__ == "__main__":
    bench_util.run(main, "trace_pipeline_s_10k_events", "seconds")
