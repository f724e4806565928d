"""Measure the closed-loop auto-tuner (ISSUE 13): search wall time and the
tuned-vs-default guarantee.

`telemetry.tune_config` searches `predict_step` over per-axis
``comm_every`` x wire precision x coalescing (x overlap x ensemble) and
validates the top candidates with short measured calibration runs. Two
properties ride the perf gates:

- ``tuned_vs_default_speedup`` — measured default-config step time over
  the measured winner's (ABSOLUTE gate >= 1.0: the all-defaults baseline
  is always in the measured candidate set, so the tuner can surface a
  win but can never ship a regression);
- ``tune_search_s`` — the whole search's wall time (pricing every
  candidate + the measured validation runs), the cost a job pays once
  per (model, mesh) to stop hand-setting env vars.

Usage: python bench_tune.py --cpu   (8-device virtual mesh)
       python bench_tune.py         (real devices)
"""

from __future__ import annotations

import sys

import bench_util


def run_tune_rows(dims, cpu: bool):
    """The canonical leg (shared with bench_all.py — config in ONE
    place): a measured diffusion3D tune on a small latency-leaning grid
    over cadence {1, 2, z:2} candidates."""
    from implicitglobalgrid_tpu.telemetry import tune_config

    nx = 24 if cpu else 64
    grid = dict(nx=nx, ny=nx, nz=nx, dimx=dims[0], dimy=dims[1],
                dimz=dims[2], periodx=1, periody=1, periodz=1)
    cfg = tune_config("diffusion3d", grid, None, measure=True, top_k=2,
                      comm_every_options=("1", "2", "z:2"))
    return [
        {
            "metric": "tuned_vs_default_speedup",
            "value": cfg.speedup,
            "unit": "measured default step_s / tuned step_s (>= 1.0 by "
                    "construction: the default is always in the "
                    "measured set)",
            "winner": cfg.knobs(),
            "measured_step_s": cfg.measured_step_s,
            "baseline_step_s": cfg.baseline_step_s,
            "predicted_step_s": cfg.predicted_step_s,
            "candidates_priced": cfg.meta["priced"],
            "candidates_measured": cfg.meta["measured"],
        },
        {
            "metric": "tune_search_s",
            "value": cfg.meta["search_s"],
            "unit": "s wall (price every candidate + measured top-k "
                    "validation, min-of-3 windows)",
        },
    ]


def main() -> None:
    cpu = "--cpu" in sys.argv
    if cpu:
        import os

        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        ).strip()
        import jax

        jax.config.update("jax_platforms", "cpu")
    import jax

    import implicitglobalgrid_tpu as igg

    dims = tuple(int(d) for d in igg.dims_create(len(jax.devices()),
                                                 (0, 0, 0)))
    for row in run_tune_rows(dims, cpu):
        bench_util.emit(row)


if __name__ == "__main__":
    bench_util.run(main, "tuned_vs_default_speedup", "t1/t2")
