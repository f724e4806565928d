"""Benchmark: multi-run scheduler overhead vs back-to-back solo runs.

The scheduler (`service.MeshScheduler`) multiplexes jobs through one
device pool in chunk-granular slices; this leg measures what that costs
at steady state, where it matters — compiles excluded by construction
(both sides warmed first), so the numbers isolate the scheduler's own
bookkeeping:

- ``service_overhead_frac``: warm per-slice scheduler bookkeeping (grid
  swap, recorder swap, policy pick, per-job gauges, journal write) as a
  fraction of the chunk work the slice carried. Target < 2% (ISSUE 8
  acceptance).
- ``service_warm_switch_s``: the absolute warm context-switch cost per
  slice, in seconds (recorded alongside the gate).

Measurement is DETERMINISTIC per-slice accounting, not a wall-clock A/B:
each journal ``slice`` event brackets exactly one chunk-boundary
`advance()`, whose own ``chunk`` event stamps its ``build_s + exec_s`` —
the difference is the scheduler's added machinery, and because both
stamps come from the SAME slice, the shared box's ±15% per-call jitter
cancels instead of swamping the sub-1% signal (the bench_telemetry/
bench_perf lesson for bounding deterministic costs; a wall-clock A/B of
two warm loops was tried first and its window-to-window drift exceeded
the entire gate several-fold in both directions). What the subtraction
leaves also includes the driver's own per-boundary bookkeeping (report
build, heartbeat, watch) that a solo run pays too — so the gated number
OVERSTATES the scheduler's true marginal cost; it passing the 2% gate
is conservative.

Cold costs are excluded and visible elsewhere by design: admission is
journaled as ``admit_s`` and each job's first dispatch is its flight
stream's ``cold`` chunk — attributed to the job that pays them, which is
the scheduling contract, not an overhead of it.

Usage: python bench_service.py          (real chip)
       python bench_service.py --cpu    (8-device virtual CPU mesh)
"""

from __future__ import annotations

import json
import sys
import time

import bench_util


def _diffusion_setup():
    import numpy as np

    from implicitglobalgrid_tpu.models import (
        diffusion_step_local, init_diffusion3d,
    )

    T, Cp, p = init_diffusion3d(dtype=np.float32)

    def step(s):
        return {"T": diffusion_step_local(s["T"], s["Cp"], p, "xla"),
                "Cp": s["Cp"]}

    return step, {"T": T, "Cp": Cp}


def run_service_overhead(dims, cpu: bool):
    """The canonical leg: run a two-job round_robin queue to completion
    with a flight directory, then account each warm slice's journal
    duration against the chunk work it carried. Shared by this script's
    __main__ and `bench_all.py` so the config stays in ONE place."""
    import os
    import statistics
    import tempfile

    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.runtime import RunSpec
    from implicitglobalgrid_tpu.service import JobSpec, MeshScheduler

    nx, chunk, chunks_per_job = (24, 20, 13) if cpu else (128, 50, 13)
    grid = dict(nx=nx, ny=nx, nz=nx, dimx=int(dims[0]), dimy=int(dims[1]),
                dimz=int(dims[2]), periodx=1, periody=1, periodz=1)
    nt = chunk * chunks_per_job

    d = tempfile.mkdtemp(prefix="bench_service_")
    with MeshScheduler(policy="round_robin", flight_dir=d) as sched:
        for name in ("a", "b"):
            sched.submit(JobSpec(
                name=name, setup=_diffusion_setup, nt=nt, grid=grid,
                run=RunSpec(nt_chunk=chunk, key=("bench_svc", name))))
        sched.run()
        states = sched.status()["states"]
    if states != {"done": 2}:
        raise RuntimeError(f"bench_service: jobs did not finish: {states}")

    # per-slice accounting: journal slice dur_s minus the matching chunk
    # event's build_s + exec_s (one chunk boundary per slice, in order);
    # the first slice per job carries admission + the XLA compile and is
    # excluded (cold — the attributed cost, not the overhead)
    from implicitglobalgrid_tpu.telemetry import read_flight_events

    slices: dict = {}
    for e in read_flight_events(os.path.join(d, "scheduler.jsonl")):
        if e.get("kind") == "slice":
            slices.setdefault(e["job"], []).append(float(e["dur_s"]))
    over, base = [], []
    for name, durs in sorted(slices.items()):
        chunks = [e for e in read_flight_events(
            os.path.join(d, f"job_{name}.jsonl"))
            if e.get("kind") == "chunk"]
        assert len(chunks) == len(durs), (len(chunks), len(durs))
        for dur, c in list(zip(durs, chunks))[1:]:
            work = float(c["build_s"]) + float(c["exec_s"])
            over.append(dur - work)
            base.append(float(c["exec_s"]))
    switch_s = statistics.median(over)
    frac = switch_s / statistics.median(base)
    return [{
        "metric": "service_overhead_frac",
        "value": frac,
        "unit": "fraction of warm chunk time (target < 0.02)",
        "target": 0.02,
        "nt_chunk": chunk,
        "warm_slices": len(over),
        "chunk_s_median": statistics.median(base),
        # worst case rides along: one bookkeeping outlier must be visible
        # even while the median gates
        "switch_s_max": max(over),
    }, {
        "metric": "service_warm_switch_s",
        "value": switch_s,
        "unit": "s per warm context switch (slice minus its chunk work)",
        "nt_chunk": chunk,
    }]


def run_serving_tier(dims, cpu: bool):
    """Serving-tier legs (ISSUE 17), shared with `bench_all.py`:

    - ``api_roundtrip_s``: median submit+status HTTP round trip against
      a live `serve.JobApiServer` (loopback, ephemeral port) — the
      front-door latency a tenant pays per job, queue-record write and
      journal-derived status read included.
    - ``query_read_s``: cold sub-box read of a committed snapshot over
      HTTP (`serve.SnapshotQueryServer`) — checksum verify + block
      decode + O(box) assembly + npy streaming.
    - ``query_cache_speedup``: cold / warm for the SAME box — the warm
      read answers from the block LRU (decoded once across clients), so
      this must never drop below 1.0 (absolute gate under
      IGG_BENCH_STRICT=1; cold medianed over fresh-cache servers so one
      slow first open cannot fake a speedup)."""
    import io
    import statistics
    import tempfile
    import urllib.request

    import numpy as np

    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.serve import (
        JobApiServer, SnapshotQueryServer,
    )

    rows = []

    # -- job API round trip (host-only: no scheduler attached) --------------
    d = tempfile.mkdtemp(prefix="bench_serve_api_")
    reps = 20
    with JobApiServer(d) as api:
        u = f"http://{api.host}:{api.port}"
        durs = []
        for i in range(reps):
            rec = json.dumps({"name": f"j{i:03d}", "model": "diffusion3d",
                              "nt": 8, "run": {"nt_chunk": 4}}).encode()
            t0 = time.perf_counter()
            req = urllib.request.Request(u + "/v1/jobs", data=rec,
                                         method="POST")
            with urllib.request.urlopen(req) as r:
                r.read()
            with urllib.request.urlopen(u + f"/v1/jobs/j{i:03d}") as r:
                r.read()
            durs.append(time.perf_counter() - t0)
    rows.append({
        "metric": "api_roundtrip_s",
        "value": statistics.median(durs),
        "unit": "s per submit+status HTTP round trip (loopback)",
        "requests": reps,
    })

    # -- read-side query: cold vs warm over one committed snapshot ----------
    nx = 32 if cpu else 128
    grid = dict(nx=nx, ny=nx, nz=nx, dimx=int(dims[0]), dimy=int(dims[1]),
                dimz=int(dims[2]))
    root = tempfile.mkdtemp(prefix="bench_serve_query_")
    igg.init_global_grid(quiet=True, **grid)
    T = igg.zeros_g() + 1.5
    igg.write_snapshot(root, step=1, state={"T": T})
    gx = int(igg.nx_g())
    igg.finalize_global_grid()
    box = f"1:{gx - 1},1:{gx - 1},0:{nx // 2}"  # spans every x/y block

    def read_once(q):
        u = f"http://{q.host}:{q.port}/v1/snapshots/1/T?box={box}"
        t0 = time.perf_counter()
        with urllib.request.urlopen(u) as r:
            body = r.read()
        dt = time.perf_counter() - t0
        return dt, np.load(io.BytesIO(body))

    cold = []
    for _ in range(5):  # fresh cache per server: honest cold medians
        with SnapshotQueryServer(root) as q:
            dt, arr = read_once(q)
            cold.append(dt)
    with SnapshotQueryServer(root) as q:
        read_once(q)  # fill the LRU
        warm = [read_once(q)[0] for _ in range(9)]
        assert q.cache.stats()["hits"] > 0
    cold_s = statistics.median(cold)
    warm_s = statistics.median(warm)
    rows.append({
        "metric": "query_read_s",
        "value": cold_s,
        "unit": "s per cold sub-box HTTP read (verify+decode+assemble)",
        "box": box,
        "box_bytes": int(arr.nbytes),
    })
    rows.append({
        "metric": "query_cache_speedup",
        "value": cold_s / warm_s,
        "unit": "x cold/warm for the same box (target >= 1.0)",
        "target": 1.0,
        "warm_s": warm_s,
    })
    return rows


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
    for row in run_service_overhead(dims, cpu):
        bench_util.emit(row)
    for row in run_serving_tier(dims, cpu):
        bench_util.emit(row)


if __name__ == "__main__":
    bench_util.run(main, "service_overhead_frac", "fraction")
