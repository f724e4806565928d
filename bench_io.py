"""Benchmark: async snapshot overhead + speedup vs gather-per-snapshot.

The io pipeline's perf claims (ISSUE 4 acceptance):

- ``io_snapshot_overhead_frac`` (gated < 0.02): what enabling async
  snapshots adds to a supervised run. The only step-loop-blocking work is
  `SnapshotWriter.submit` — the device->host copy of this process's shard
  blocks plus the enqueue; serialization/fsync/commit runs on the writer
  thread under the next chunk. Like bench_telemetry, the gated figure is
  DETERMINISTIC accounting: the microbenchmarked submit cost times the
  snapshots a run takes, over the run's median snapshot-off time — the
  end-to-end A/B (alternating interleaved pairs) corroborates on the
  noisy shared-CPU mesh rather than resolving the sub-1% signal.
- ``io_async_vs_gather_speedup``: the same output cadence done the
  legacy way — `gather_interior` to the root + a synced `np.save` at
  every snapshot step, serialized INTO the run — versus the async
  pipeline. The recorded value is the STEADY-STATE accounting
  (run + n*measured gather+write) / (run + n*measured submit): what each
  path costs the step loop per cadence once the terminal drain is
  amortized (a long run drains once; this 1-2 s bench run would charge
  it every rep, and fsync latency on the shared filesystem swings by
  >10x rep to rep — the measured on-run median is attached for
  corroboration). On a single-host CPU mesh the gather is a local
  device_get, so the figure understates the multi-host win, where the
  gather is an O(global) DCN collective on every process.

Usage: python bench_io.py          (real chip)
       python bench_io.py --cpu    (8-device virtual CPU mesh)
"""

from __future__ import annotations

import itertools
import os
import shutil
import sys
import tempfile

import bench_util


def snapshot_overhead_rows(nx: int, nt_chunk: int, n_chunks: int = 3,
                           reps: int = 8):
    """Rows on the CURRENT grid (caller owns init/finalize)."""
    import statistics
    import time

    import numpy as np

    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.io.snapshot import SnapshotWriter
    from implicitglobalgrid_tpu.models import (
        diffusion_step_local, init_diffusion3d,
    )

    T, Cp, p = init_diffusion3d(dtype=np.float32)

    def step(s):
        return {"T": diffusion_step_local(s["T"], s["Cp"], p, "xla"),
                "Cp": s["Cp"]}

    state = {"T": T, "Cp": Cp}
    nt = nt_chunk * n_chunks
    key = ("bench_io", nx, nt_chunk)
    tmp = tempfile.mkdtemp(prefix="igg_bench_io_")
    seq = itertools.count()

    def run_off():
        igg.run_resilient(step, state, nt, nt_chunk=nt_chunk, key=key)

    def run_on():
        # snapshot only T — the same single field the gather baseline
        # writes, so the two paths move comparable bytes
        d = os.path.join(tmp, f"snaps{next(seq)}")
        igg.run_resilient(step, state, nt, nt_chunk=nt_chunk, key=key,
                          snapshot_dir=d, snapshot_every=nt_chunk,
                          snapshot_fields=("T",))

    # warm: compile once (shared key), one committed snapshot set
    run_off()
    run_on()

    # --- end-to-end A/B (corroboration) --------------------------------
    times = {"off": [], "on": []}
    pair_fracs = []
    for r in range(reps):
        order = [(run_off, "off"), (run_on, "on")] if r % 2 == 0 \
            else [(run_on, "on"), (run_off, "off")]
        d = {}
        for fn, slot in order:
            igg.tic()
            fn()
            d[slot] = igg.toc()
            times[slot].append(d[slot])
        pair_fracs.append((d["on"] - d["off"]) / d["off"])
    pair_fracs.sort()
    iqr = (pair_fracs[(3 * len(pair_fracs)) // 4]
           - pair_fracs[len(pair_fracs) // 4])
    t_off_med = statistics.median(times["off"])
    t_on_med = statistics.median(times["on"])

    # --- deterministic accounting (the gated figure) -------------------
    # submit = D2H of this process's shard blocks + enqueue: the ONLY
    # work the step loop waits on; everything else overlaps on the
    # writer thread. Probe it directly with a deep queue (no disk wait),
    # drain outside the timed window.
    n_probe = 30
    w = SnapshotWriter(os.path.join(tmp, "probe"),
                       queue_depth=n_probe + 1, policy="block",
                       fields=("T",))
    t0 = time.monotonic()
    for i in range(n_probe):
        w.submit(state, i)
    per_submit_s = (time.monotonic() - t0) / n_probe
    w.close(timeout=120.0)
    accounted = per_submit_s * n_chunks / t_off_med

    # --- synchronous gather-per-snapshot baseline ----------------------
    # the legacy output path, serialized into the run: gather_interior to
    # the root + a synced np.save, once per snapshot step
    def gather_write(i):
        G = igg.gather_interior(state["T"])
        path = os.path.join(tmp, f"gather_{i}.npy")
        with open(path, "wb") as f:
            np.save(f, G)
            f.flush()
            os.fsync(f.fileno())

    gather_write(-1)  # warm the transfer path
    g_times = []
    for i in range(5):
        t0 = time.monotonic()
        gather_write(i)
        g_times.append(time.monotonic() - t0)
    t_gather = statistics.median(g_times)
    sync_run_s = t_off_med + n_chunks * t_gather
    async_run_s = t_off_med + n_chunks * per_submit_s
    speedup = sync_run_s / async_run_s

    shutil.rmtree(tmp, ignore_errors=True)
    return [{
        "metric": "io_snapshot_overhead_frac",
        "value": accounted,
        "unit": "fraction of run time, deterministic submit accounting "
                "(target < 0.02)",
        "target": 0.02,
        "nt": nt,
        "nt_chunk": nt_chunk,
        "snapshots_per_run": n_chunks,
        "per_submit_s": per_submit_s,
        "off_run_s_median": t_off_med,
        "on_run_s_median": t_on_med,
        "ab_median_frac": statistics.median(pair_fracs),
        "ab_noise_iqr": iqr,
        "note": "submit (D2H + enqueue) is the only step-loop-blocking "
                "cost of async snapshots; the A/B corroborates under "
                "shared-CPU jitter",
    }, {
        "metric": "io_async_vs_gather_speedup",
        "value": speedup,
        "unit": "x (sync gather_interior+save per snapshot / async "
                "SnapshotWriter submit, steady-state accounting)",
        "gather_write_s_median": t_gather,
        "per_submit_s": per_submit_s,
        "sync_run_s": sync_run_s,
        "async_run_s_accounted": async_run_s,
        "on_run_s_median_measured": t_on_med,
        "note": "steady-state: terminal-drain amortized (a long run "
                "drains once; this short bench would charge it every "
                "rep under >10x fsync jitter). Single-host CPU gather is "
                "a local device_get — multi-host runs pay an O(global) "
                "DCN collective instead, so the figure is a floor",
    }]


def run_io_overhead(dims, cpu: bool):
    """The canonical leg: init its own grid over ``dims``, measure,
    finalize, return the rows. Shared by this script's __main__ and
    `bench_all.py` so the config stays in ONE place."""
    import implicitglobalgrid_tpu as igg

    nx, nt_chunk = (32, 60) if cpu else (256, 200)
    igg.init_global_grid(nx, nx, nx, dimx=dims[0], dimy=dims[1],
                         dimz=dims[2], periodx=1, periody=1, periodz=1,
                         quiet=True)
    try:
        return snapshot_overhead_rows(nx, nt_chunk)
    finally:
        igg.finalize_global_grid()


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
    for row in run_io_overhead(dims, cpu):
        bench_util.emit(row)


if __name__ == "__main__":
    bench_util.run(main, "io_snapshot_overhead_frac", "fraction")
