"""Benchmark: the performance oracle — overhead gate + model fidelity.

Two legs (both land in BENCH_ALL.json via bench_all.py):

- ``perf_overhead_frac`` (gated < 2%): what the live drift detector
  costs the step loop. The per-boundary work is one
  `PerfWatch.observe` call — a handful of float ops on a rolling window
  plus 2-4 gauge writes — so, like the telemetry leg, the gated figure
  is DETERMINISTIC accounting: the microbenchmarked per-observe cost
  times the boundaries a supervised run crosses, over the run's wall
  time (expect per-boundary arithmetic only, orders of magnitude under
  the gate).

- ``perf_model_ratio_*`` (recorded, acceptance: within 2x on the CPU
  mesh): measured vs modeled per-step time for the diffusion3D and
  acoustic3D bench configs — the model calibrated on THIS mesh
  (`telemetry.calibrate_machine`), the measurement the same two-point
  steady-state slope `bench_all.py` uses. Three INDEPENDENT calibrations
  back each row: the modeled time is their median prediction, the
  roofline verdict (``bound``) is the majority vote, and
  ``bound_stable`` says a majority existed — a single contention burst
  during one calibration cannot flip the recorded classification.

Usage: python bench_perf.py          (real chip)
       python bench_perf.py --cpu    (8-device virtual CPU mesh)
"""

from __future__ import annotations

import os
import sys

import bench_util


def perf_overhead_rows(nx: int, nt_chunk: int, n_chunks: int = 3):
    """Drift-detector overhead on the CURRENT grid (caller owns
    init/finalize): deterministic per-boundary accounting vs run time."""
    import statistics
    import time

    import numpy as np

    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.models import (
        diffusion_step_local, init_diffusion3d,
    )

    T, Cp, p = init_diffusion3d(dtype=np.float32)

    def step(s):
        return {"T": diffusion_step_local(s["T"], s["Cp"], p, "xla"),
                "Cp": s["Cp"]}

    state = {"T": T, "Cp": Cp}
    nt = nt_chunk * n_chunks
    key = ("bench_perf", nx, nt_chunk)

    def run():
        igg.run_resilient(step, state, nt, nt_chunk=nt_chunk, key=key)

    run()  # warm: compile once
    times = []
    for _ in range(5):
        igg.tic()
        run()
        times.append(igg.toc())
    t_run = statistics.median(times)

    # the per-boundary cost: one observe() on a warm window, gauges incl.
    watch = igg.PerfWatch(window=16, zmax=4.0, model_step_s=1e-3)
    n_probe = 5000
    t0 = time.monotonic()
    for i in range(n_probe):
        watch.observe(chunk=i, step_begin=0, step_end=nt_chunk,
                      n=nt_chunk, exec_s=0.01)
    per_observe_s = (time.monotonic() - t0) / n_probe
    frac = per_observe_s * n_chunks / t_run
    return [{
        "metric": "perf_overhead_frac",
        "value": frac,
        "unit": "fraction of run time, deterministic per-boundary "
                "accounting (target < 0.02)",
        "target": 0.02,
        "nt": nt, "nt_chunk": nt_chunk,
        "per_observe_s": per_observe_s,
        "run_s_median": t_run,
        "note": "one PerfWatch.observe (rolling median+MAD + igg_perf_* "
                "gauge writes) per chunk boundary — the drift detector's "
                "whole step-loop footprint",
    }]


def model_ratio_rows(dims, cpu: bool):
    """Measured/modeled per-step ratio rows for the diffusion3D and
    acoustic3D bench configs, on self-initialized grids over ``dims``.
    Calibrates THREE times so the rows witness classification stability
    (majority-vote verdict, median model time)."""
    import numpy as np

    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.models import (
        init_acoustic3d, init_diffusion3d, run_acoustic, run_diffusion,
    )

    rows = []
    profiles = []

    def measured_step_s(run_fn, nt):
        # min-of-3 over longer windows: the SAME least-contended estimate
        # the calibration's min-of-reps produces, so the ratio compares
        # like with like on a shared box
        c1 = max(2, nt // 5)
        return bench_util.two_point(lambda c: run_fn(c, c), c1, 3 * c1,
                                    reps=3)

    # --- diffusion3D f32 (the flagship config) -------------------------
    nx, nt = (48, 50) if cpu else (256, 1000)
    igg.init_global_grid(nx, nx, nx, dimx=dims[0], dimy=dims[1],
                         dimz=dims[2], periodx=1, periody=1, periodz=1,
                         quiet=True)
    try:
        for _ in range(3):  # independent calibrations (majority vote)
            profiles.append(igg.calibrate_machine())
        T, Cp, p = init_diffusion3d(dtype=np.float32)
        t_step = measured_step_s(
            lambda n, c: run_diffusion(T, Cp, p, n, nt_chunk=c), nt)
        preds = [igg.predict_step("diffusion3d", (T, Cp), profile=pr)
                 for pr in profiles]
        rows.append(_ratio_row("diffusion3D_f32", t_step, preds))
    finally:
        igg.finalize_global_grid()

    # --- acoustic3D with overlap ---------------------------------------
    nxa, nta = (32, 30) if cpu else (192, 600)
    igg.init_global_grid(nxa, nxa, nxa, dimx=dims[0], dimy=dims[1],
                         dimz=dims[2], periodx=1, periody=1, periodz=1,
                         quiet=True)
    try:
        state, p = init_acoustic3d(dtype=np.float32, overlap=True)
        t_step = measured_step_s(
            lambda n, c: run_acoustic(state, p, n, nt_chunk=c), nta)
        preds = [igg.predict_step("acoustic3d", state, profile=pr,
                                  overlap=True)
                 for pr in profiles]
        rows.append(_ratio_row("acoustic3D_overlap_f32", t_step, preds))
    finally:
        igg.finalize_global_grid()
    return rows


def _ratio_row(tag: str, measured_s: float, preds: list) -> dict:
    """One BENCH_ALL row from N independent calibrations' predictions:
    median model time (robust to one contended calibration), majority
    bound verdict, ``bound_stable`` = a majority existed."""
    import statistics
    from collections import Counter

    model_s = statistics.median(p["step_s"] for p in preds)
    ratio = measured_s / model_s if model_s else None
    bounds = [p["bound"] for p in preds]
    (bound, votes), = Counter(bounds).most_common(1)
    lead = next(p for p in preds if p["bound"] == bound)
    return {
        "metric": f"perf_model_ratio_{tag}",
        "value": ratio,
        "unit": "measured / modeled per-step time (acceptance: within "
                "2x, i.e. 0.5 <= ratio <= 2)",
        "measured_step_s": measured_s,
        "model_step_s": model_s,
        "bound": bound,
        "bound_detail": lead["bound_detail"],
        "bound_votes": bounds,
        "bound_stable": votes > len(bounds) // 2,
        "profile_source": lead["profile_source"],
        "within_2x": (ratio is not None and 0.5 <= ratio <= 2.0),
    }


def run_perf_overhead(dims, cpu: bool):
    """The canonical overhead leg: init its own grid over ``dims``,
    measure, finalize, return the rows (shared with `bench_all.py`)."""
    import implicitglobalgrid_tpu as igg

    nx, nt_chunk = (32, 60) if cpu else (256, 200)
    igg.init_global_grid(nx, nx, nx, dimx=dims[0], dimy=dims[1],
                         dimz=dims[2], periodx=1, periody=1, periodz=1,
                         quiet=True)
    try:
        return perf_overhead_rows(nx, nt_chunk)
    finally:
        igg.finalize_global_grid()


def run_model_ratio(dims, cpu: bool):
    """The canonical model-fidelity leg (shared with `bench_all.py`)."""
    return model_ratio_rows(dims, cpu)


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
    for row in run_perf_overhead(dims, cpu):
        bench_util.emit(row)
    for row in run_model_ratio(dims, cpu):
        bench_util.emit(row)


if __name__ == "__main__":
    bench_util.run(main, "perf_overhead_frac", "fraction")
