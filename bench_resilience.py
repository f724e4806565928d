"""Benchmark: health-guard overhead of the resilient runtime.

The driver (`runtime/driver.py`) fuses a per-chunk health probe into the
compiled chunk program (`runtime/health.py`): per field a non-finite count
and a norm accumulator, reduced with ONE tiny psum and fetched once per
chunk boundary. This leg measures what that supervision costs at the
driver's operating point — a full guarded chunk (probe + fetch included)
against the plain chunk of `make_state_runner` — as a fraction of step
time. Target: < 2% (`ISSUE` acceptance; the HLO-side guarantee of exactly
one extra small collective is tested in tests/test_hlo_audit.py).

Note the measurement is INCLUSIVE single-chunk timing, not the two-point
slope: the guard is a per-chunk fixed cost, which a slope over two window
sizes would cancel out by construction.

Prints one JSON row (plus per-config rows when run through bench_all).

Usage: python bench_resilience.py          (real chip)
       python bench_resilience.py --cpu    (8-device virtual CPU mesh)
"""

from __future__ import annotations

import sys

import bench_util


def guard_overhead_rows(nx: int, nt_chunk: int, reps: int = 12):
    """One row: guarded vs plain chunk time on the CURRENT grid (caller
    owns init/finalize). ``value`` is the fractional per-step overhead of
    supervision at chunk size ``nt_chunk`` — probe compute, the one
    psum, and the driver's per-chunk stats fetch all included."""
    import numpy as np

    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.models import (
        diffusion_step_local, init_diffusion3d,
    )
    from implicitglobalgrid_tpu.models.common import make_state_runner
    from implicitglobalgrid_tpu.runtime.health import make_guarded_runner

    T, Cp, p = init_diffusion3d(dtype=np.float32)

    def step(s):
        return (diffusion_step_local(s[0], s[1], p, "xla"), s[1])

    plain = make_state_runner(step, (3, 3), nt_chunk=nt_chunk,
                              key=("bench_resil", nx, nt_chunk))
    guarded = make_guarded_runner(step, (3, 3), nt_chunk=nt_chunk,
                                  key=("bench_resil", nx, nt_chunk))

    # The A/B isolates the guard's MARGINAL cost: both sides run inside a
    # tic/toc window whose closing barrier performs the identical drain,
    # so `guarded` pays exactly its extras — in-chunk probe, the one
    # psum, the driver's tiny stats fetch — on top of the same chunk.
    def run_plain():
        plain(T, Cp)  # drained by toc's barrier

    def run_guarded():
        np.asarray(guarded(T, Cp)[-1])  # the driver's per-chunk fetch

    def run_chunked_style():
        igg.sync(plain(T, Cp))  # what run_chunked does per chunk call

    # Interleaved reps (back-to-back blocks would fold machine drift into
    # the tiny difference); min is the estimator the rest of the suite
    # uses (`bench_util.two_point`), median emitted alongside since the
    # per-call jitter of the shared-CPU mesh (±15% observed) is an order
    # of magnitude above the guard cost being bounded.
    import statistics

    run_plain()
    run_guarded()
    run_chunked_style()  # warm: compile + first dispatch outside windows
    times = {"p": [], "g": [], "s": []}
    for _ in range(reps):
        for fn, slot in ((run_plain, "p"), (run_guarded, "g"),
                         (run_chunked_style, "s")):
            igg.tic()
            fn()
            times[slot].append(igg.toc())
    t_plain, t_guarded, t_sync = (min(times[s]) for s in "pgs")
    frac = (t_guarded - t_plain) / t_plain
    med = {s: statistics.median(times[s]) for s in "pgs"}
    return [{
        "metric": "resilience_guard_overhead_frac",
        "value": frac,
        "unit": "fraction of plain chunk time (target < 0.02)",
        "target": 0.02,
        "nt_chunk": nt_chunk,
        "plain_chunk_s": t_plain,
        "guarded_chunk_s": t_guarded,
        "median_overhead_frac": (med["g"] - med["p"]) / med["p"],
        # the driver's fetch REPLACES run_chunked's separate sync-drain
        # program; vs that baseline supervision is usually free or better
        "sync_drain_chunk_s": t_sync,
        "vs_run_chunked_frac": (t_guarded - t_sync) / t_sync,
    }]


def run_guard_overhead(dims, cpu: bool):
    """The canonical leg: init its own grid over ``dims``, measure,
    finalize, return the rows. Shared by this script's __main__ and
    `bench_all.py` so the config stays in ONE place."""
    import implicitglobalgrid_tpu as igg

    # the guard is a per-chunk FIXED cost: the chunk must be long enough
    # that single-call jitter (multi-% on the shared-CPU mesh) does not
    # swamp the sub-1% signal being bounded
    nx, nt_chunk = (32, 100) if cpu else (256, 200)
    igg.init_global_grid(nx, nx, nx, dimx=dims[0], dimy=dims[1],
                         dimz=dims[2], periodx=1, periody=1, periodz=1,
                         quiet=True)
    try:
        return guard_overhead_rows(nx, nt_chunk)
    finally:
        igg.finalize_global_grid()


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

    nd = len(jax.devices())
    dims = tuple(int(d) for d in igg.dims_create(nd, (0, 0, 0)))
    for row in run_guard_overhead(dims, cpu):
        bench_util.emit(row)


if __name__ == "__main__":
    bench_util.run(main, "resilience_guard_overhead_frac", "fraction")
