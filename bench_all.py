"""Benchmark suite: one JSON line per BASELINE.json config.

Runs every workload family of `/root/repo/BASELINE.json` on the available
devices (one real TPU chip, or the 8-device virtual CPU mesh with --cpu):

- diffusion3D 256^3/chip, f32 and f64 (configs 1, 3; f64 is the reference's
  anchor dtype — on v5e it runs through the f32 pipeline emulation)
- 2-D diffusion, f32 (config 2)
- 3-D acoustic wave with hide_communication overlap (config 4)
- 3-D pseudo-transient Stokes (config 5)

`bench.py` stays the single-headline-metric entry point (the driver runs
it); this suite is for the full per-config record. Weak-scaling efficiency
needs >1 chip — see bench_weak.py (virtual-mesh harness).

Usage: python bench_all.py [--cpu]
"""

from __future__ import annotations

import json
import sys

import bench_util


def _rate(cells, steps, t):
    return cells * steps / t


def main() -> None:
    cpu = "--cpu" in sys.argv
    if cpu:
        import os

        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
        ).strip()
        import jax

        jax.config.update("jax_platforms", "cpu")
    import jax

    if cpu:  # f64 anchor config needs x64; TPU has no native f64 pipeline
        jax.config.update("jax_enable_x64", True)
    import numpy as np

    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.models import (
        init_acoustic3d, init_diffusion2d, init_diffusion3d,
        run_acoustic, run_diffusion, run_stokes, init_stokes3d,
    )

    nd = len(jax.devices())
    dims3 = tuple(int(d) for d in igg.dims_create(nd, (0, 0, 0)))
    n_chips = int(np.prod(dims3))
    results = []

    def record(name, value, unit, baseline=None):
        row = {"metric": name, "value": value, "unit": unit}
        if baseline:
            row["vs_baseline"] = value / baseline
        results.append(bench_util.emit(row))

    def timed(run_fn, state, nt, chunk):
        """Two-point steady-state: returns equivalent seconds for ``nt``
        steps, i.e. nt * the per-step slope (`bench_util.two_point`)."""
        del chunk

        def one(c):
            run_fn(state, c, c)  # run_* drain internally (run_chunked)

        c1 = max(1, nt // 10)
        return nt * bench_util.two_point(one, c1, 3 * c1)

    # --- diffusion3D f32 / f64 (BASELINE configs 1, 3) ---------------------
    nx, nt = (48, 50) if cpu else (256, 1000)
    dtypes = [(np.float32, "f32")]
    if cpu:
        dtypes.append((np.float64, "f64"))
    else:
        row = {
            "metric": "diffusion3D_f64_cell_updates_per_s_per_chip",
            "value": None, "unit": "cell-updates/s/chip",
            "note": "no native f64 on this TPU generation; f64 semantics "
                    "verified on the x64 CPU mesh (tests, bench_all --cpu)",
        }
        results.append(bench_util.emit(row))
    for dtype, tag in dtypes:
        igg.init_global_grid(nx, nx, nx, dimx=dims3[0], dimy=dims3[1],
                             dimz=dims3[2], periodx=1, periody=1, periodz=1,
                             quiet=True)
        T, Cp, p = init_diffusion3d(dtype=dtype)
        t = timed(lambda s, n, c: run_diffusion(s[0], s[1], p, n, nt_chunk=c),
                  (T, Cp), nt, max(1, nt // 10))
        cells = float(igg.nx_g()) * float(igg.ny_g()) * float(igg.nz_g())
        record(f"diffusion3D_{tag}_cell_updates_per_s_per_chip",
               _rate(cells, nt, t) / n_chips, "cell-updates/s/chip",
               baseline=0.95e9)  # reference: 0.95e9/GPU f64 (BASELINE.md)
        igg.finalize_global_grid()

    # --- diffusion2D f32 (BASELINE config 2: 2-D on a 2x2 mesh) ------------
    dims2 = tuple(int(d) for d in igg.dims_create(nd, (0, 0, 1)))
    nx2, nt2 = (64, 50) if cpu else (4096, 1000)
    igg.init_global_grid(nx2, nx2, 1, dimx=dims2[0], dimy=dims2[1], dimz=1,
                         periodx=1, periody=1, quiet=True)
    T, Cp, p = init_diffusion2d(dtype=np.float32)
    t = timed(lambda s, n, c: run_diffusion(s[0], s[1], p, n, nt_chunk=c),
              (T, Cp), nt2, max(1, nt2 // 10))
    record("diffusion2D_f32_cell_updates_per_s_per_chip",
           _rate(float(igg.nx_g()) * float(igg.ny_g()), nt2, t) / n_chips,
           "cell-updates/s/chip")
    igg.finalize_global_grid()

    # --- acoustic 3-D with hide_communication (BASELINE config 4) ----------
    nxa, nta = (32, 30) if cpu else (192, 600)
    igg.init_global_grid(nxa, nxa, nxa, dimx=dims3[0], dimy=dims3[1],
                         dimz=dims3[2], periodx=1, periody=1, periodz=1,
                         quiet=True)
    state, p = init_acoustic3d(dtype=np.float32, overlap=True)
    t = timed(lambda s, n, c: run_acoustic(s, p, n, nt_chunk=c),
              state, nta, max(1, nta // 10))
    cells = float(igg.nx_g()) * float(igg.ny_g()) * float(igg.nz_g())
    record("acoustic3D_overlap_f32_cell_updates_per_s_per_chip",
           _rate(cells, nta, t) / n_chips, "cell-updates/s/chip")
    igg.finalize_global_grid()

    # --- halo coalescing A/B (2/4/8/16 fields) + pack attribution ----------
    # one packed ppermute pair per axis vs 2·N per-field permutes, plus the
    # pack/unpack-vs-permute attribution rows (`update_halo_pack_frac_*`)
    # the perfdb gate watches for pack-bound regressions. Config owned by
    # `bench_halo.run_coalescing_ab` (shared with the standalone bench).
    import bench_halo

    coalesce_rows = bench_halo.run_coalescing_ab(dims3, cpu)
    for row in coalesce_rows:
        results.append(bench_util.emit(row))
    # ISSUE 11 absolute gate: the 8-field coalesced exchange must beat the
    # per-field baseline (>= 1x) — the canonical-wire-schema fix for the
    # 0.75x regression. A direct gate like lint_ok: rc 1 under
    # IGG_BENCH_STRICT=1, independent of the trailing-median perfdb check
    # (which would tolerate a slow drift back below 1x).
    speed8 = next(r["value"] for r in coalesce_rows
                  if r["metric"] == "update_halo_coalesced_speedup_8fields")
    coalesce8_ok = speed8 >= 1.0
    results.append(bench_util.emit({
        "metric": "coalesce_8field_restored_ok",
        "value": 1.0 if coalesce8_ok else 0.0,
        "unit": "bool (1 = 8-field coalesced exchange >= per-field)",
        "speedup_8fields": speed8,
    }))

    # --- ensemble axis: per-member step vs solo at E=4/8/16 (ISSUE 12) -----
    # one vmapped chunk advances E scenario members behind the SAME
    # collectives; per-member speedup rows ride the perfdb gate and two
    # absolute gates travel with them: compiled permute+psum count at E=8
    # equals E=1 (`ensemble_permutes_flat_ok`) and per-member step within
    # 10% of solo (`ensemble_amortization_ok`). Config owned by
    # `bench_ensemble.run_ensemble_ab` (shared with the standalone bench).
    import bench_ensemble

    ensemble_rows = bench_ensemble.run_ensemble_ab(dims3, cpu)
    for row in ensemble_rows:
        results.append(bench_util.emit(row))
    ensemble_ok = all(
        r["value"] >= 1.0 for r in ensemble_rows
        if r["metric"] in ("ensemble_permutes_flat_ok",
                           "ensemble_amortization_ok"))

    # --- quantized halo wire A/B (ISSUE 10) --------------------------------
    # static f32/int8 wire-byte ratio at 4 coalesced fields (payload +
    # per-slab scales), the quantize/dequantize overhead gate on the live
    # mesh, and the modeled exposed-comm delta of the per-axis z:int8
    # policy on an ICI+DCN profile. Config owned by
    # `bench_quant.run_quant_ab` (shared with the standalone bench).
    import bench_quant

    for row in bench_quant.run_quant_ab(dims3, cpu):
        results.append(bench_util.emit(row))

    # --- resilience guard overhead (guarded vs plain chunk) ----------------
    # the supervised driver's per-chunk health probe + fetch as a fraction
    # of step time; target < 2% (ISSUE 2). Config owned by
    # `bench_resilience.run_guard_overhead` (shared with the standalone).
    import bench_resilience

    for row in bench_resilience.run_guard_overhead(dims3, cpu):
        results.append(bench_util.emit(row))

    # --- telemetry overhead (flight recorder + metrics on vs off) ----------
    # the observability layer's host-side cost per supervised run as a
    # fraction of run time; target < 2% (ISSUE 3). Config owned by
    # `bench_telemetry.run_telemetry_overhead` (shared with the standalone).
    import bench_telemetry

    tel_rows = bench_telemetry.run_telemetry_overhead(dims3, cpu)
    for row in tel_rows:
        results.append(bench_util.emit(row))

    # --- live observability plane (ISSUE 18) --------------------------------
    # the in-process alert cadence (tail drain + default rule pack per
    # chunk boundary — what MeshScheduler(alerts=True) adds per slice) as
    # a fraction of the telemetry leg's off-run time, gated < 2%; the
    # /v1/observe round trip and /v1/events append-to-line lag ride the
    # perfdb trajectory. Config owned by `bench_telemetry.live_plane_rows`.
    tel_ref = next(r for r in tel_rows
                   if r["metric"] == "telemetry_overhead_frac")
    live_rows = bench_telemetry.live_plane_rows(
        tel_ref["off_run_s_median"],
        n_boundaries=tel_ref["nt"] // tel_ref["nt_chunk"])
    for row in live_rows:
        results.append(bench_util.emit(row))
    live_ok = next(r["value"] for r in live_rows
                   if r["metric"] == "live_tail_overhead_frac") < 0.02

    # --- distributed tracing (ISSUE 20) -------------------------------------
    # the recorder's per-event trace stamp (two dict inserts) as a
    # fraction of the telemetry leg's off-run time, gated < 2%; the
    # 10k-event OTLP export rides the perfdb trajectory. Config owned by
    # `bench_telemetry.tracing_rows`.
    tracing = bench_telemetry.tracing_rows(tel_ref["off_run_s_median"],
                                           tel_ref["events_per_run"])
    for row in tracing:
        results.append(bench_util.emit(row))
    tracing_ok = next(r["value"] for r in tracing
                      if r["metric"] == "trace_ctx_overhead_frac") < 0.02

    # --- io: async snapshot overhead + vs-gather speedup -------------------
    # the snapshot pipeline's step-loop cost (submit = D2H + enqueue) as a
    # fraction of run time, target < 2%, plus the speedup over the legacy
    # gather-per-snapshot output path (ISSUE 4). Config owned by
    # `bench_io.run_io_overhead` (shared with the standalone bench).
    import bench_io

    for row in bench_io.run_io_overhead(dims3, cpu):
        results.append(bench_util.emit(row))

    # --- performance oracle: drift-detector overhead + model fidelity ------
    # the live PerfWatch's per-boundary cost (deterministic accounting,
    # target < 2%) and the calibrated model's measured/modeled per-step
    # ratio for the diffusion3D/acoustic3D configs with the roofline
    # bound verdict and its repeat-calibration stability (ISSUE 6).
    # Config owned by `bench_perf.run_perf_overhead`/`run_model_ratio`.
    import bench_perf

    for row in bench_perf.run_perf_overhead(dims3, cpu):
        results.append(bench_util.emit(row))
    for row in bench_perf.run_model_ratio(dims3, cpu):
        results.append(bench_util.emit(row))

    # --- closed-loop auto-tuner (ISSUE 13) ---------------------------------
    # search predict_step over per-axis comm_every x wire x coalesce,
    # validate the top candidates with measured runs: the tuned config
    # must never lose to the default (absolute gate >= 1.0 — the
    # baseline is in the measured set) and the search wall time rides
    # the perfdb trajectory. Config owned by `bench_tune.run_tune_rows`.
    import bench_tune

    tune_rows = bench_tune.run_tune_rows(dims3, cpu)
    for row in tune_rows:
        results.append(bench_util.emit(row))
    tuned_speedup = next(r["value"] for r in tune_rows
                         if r["metric"] == "tuned_vs_default_speedup")
    tuned_ok = tuned_speedup is not None and tuned_speedup >= 1.0

    # --- on-device elastic resharding (ISSUE 14) ---------------------------
    # resize downtime of the HBM-to-HBM collective re-block vs the
    # checkpoint (disk) path it replaces: the on-device path must never
    # lose (absolute gate `reshard_vs_disk_speedup >= 1.0` under
    # IGG_BENCH_STRICT; downtimes + one-time compile ride the perfdb
    # trajectory). Config owned by `bench_reshard.run_reshard_ab`.
    import bench_reshard

    reshard_rows = bench_reshard.run_reshard_ab(dims3, cpu)
    for row in reshard_rows:
        results.append(bench_util.emit(row))
    reshard_speedup = next(r["value"] for r in reshard_rows
                           if r["metric"] == "reshard_vs_disk_speedup")
    reshard_ok = reshard_speedup is None or reshard_speedup >= 1.0

    # --- multi-run scheduler: steady-state multiplexing overhead -----------
    # warm per-slice time of a two-job round_robin scheduler (every slice
    # a context switch) vs a bare warm ResilientRun loop; target < 2%,
    # warm switch cost recorded (ISSUE 8). Config owned by
    # `bench_service.run_service_overhead` (shared with the standalone).
    import bench_service

    for row in bench_service.run_service_overhead(dims3, cpu):
        results.append(bench_util.emit(row))

    # --- serving tier: job API round trip + read-side query cache ----------
    # the HTTP front doors (ISSUE 17): submit+status round trip against a
    # live JobApiServer, cold sub-box snapshot read over HTTP, and the
    # block-LRU cold/warm speedup — the warm read answers from decoded
    # blocks, so `query_cache_speedup >= 1.0` is an absolute gate (rc 1
    # under IGG_BENCH_STRICT=1); the latencies ride the perfdb trajectory.
    # Config owned by `bench_service.run_serving_tier`.
    serve_rows = bench_service.run_serving_tier(dims3, cpu)
    for row in serve_rows:
        results.append(bench_util.emit(row))
    query_speedup = next(r["value"] for r in serve_rows
                         if r["metric"] == "query_cache_speedup")
    serve_ok = query_speedup is None or query_speedup >= 1.0

    # --- closed-loop autoscaler (ISSUE 19) ---------------------------------
    # per-boundary policy cost (< 2% of the slice it rides) and the
    # reactivity gate: the drill's starved tenant must be GROWN and the
    # idle one SHRUNK through the journaled control path with no
    # operator input (`autoscale_reacts_ok`, rc 1 under
    # IGG_BENCH_STRICT=1). Config owned by
    # `bench_autoscale.run_autoscale_rows` (shared with the standalone).
    import bench_autoscale

    autoscale_rows = bench_autoscale.run_autoscale_rows(dims3, cpu)
    for row in autoscale_rows:
        results.append(bench_util.emit(row))
    autoscale_ok = all(
        (r["frac_of_slice"] < 0.02 if r["metric"] == "autoscale_decision_s"
         else r["value"] >= 1.0)
        for r in autoscale_rows)

    # --- static analysis: compile-time audit overhead ----------------------
    # run_resilient(audit=True)'s one-time trace+lower+parse+check cost as
    # a fraction of run time; target < 2% (ISSUE 7). Config owned by
    # `bench_audit.run_audit_overhead` (shared with the standalone bench).
    import bench_audit

    for row in bench_audit.run_audit_overhead(dims3, cpu):
        results.append(bench_util.emit(row))

    # --- pseudo-transient Stokes 3-D (BASELINE config 5) -------------------
    nxs, nts = (24, 20) if cpu else (128, 300)
    igg.init_global_grid(nxs, nxs, nxs, dimx=dims3[0], dimy=dims3[1],
                         dimz=dims3[2], quiet=True)
    state, p = init_stokes3d(dtype=np.float32)
    t = timed(lambda s, n, c: run_stokes(s, p, n, nt_chunk=c),
              state, nts, max(1, nts // 10))
    cells = float(igg.nx_g()) * float(igg.ny_g()) * float(igg.nz_g())
    record("stokes3D_pt_f32_cell_updates_per_s_per_chip",
           _rate(cells, nts, t) / n_chips, "cell-updates/s/chip")
    igg.finalize_global_grid()

    # --- repo lint gate: `ruff check .` travels with the perf gates --------
    # (ISSUE 7) the [tool.ruff] config in pyproject.toml is the contract;
    # value 1 = clean tree, 0 = findings (a direct gate: rc 1 under
    # IGG_BENCH_STRICT=1, same contract as the perfdb gate below).
    # Containers without ruff record the row as skipped instead of
    # vacuously passing.
    import os
    import subprocess

    lint = subprocess.run(
        [sys.executable, "-m", "ruff", "check", "."],
        capture_output=True, text=True,
        cwd=os.path.dirname(os.path.abspath(__file__)))
    ruff_missing = lint.returncode != 0 and "No module named" in lint.stderr
    results.append(bench_util.emit({
        "metric": "lint_ok",
        "value": None if ruff_missing else (1.0 if lint.returncode == 0
                                            else 0.0),
        "unit": "bool (1 = `python -m ruff check .` clean)",
        **({"note": "ruff unavailable in this environment; row skipped"}
           if ruff_missing else
           {} if lint.returncode == 0 else
           {"findings": lint.stdout.strip().splitlines()[-20:]}),
    }))

    # --- perf-history gate: the bench trajectory checks itself -------------
    # current run vs the trailing PERF_HISTORY.jsonl window (checked
    # BEFORE appending, so a run never gates against itself); the verdict
    # rides BENCH_ALL.json as its own row. Exit-0-with-recorded-failure is
    # the bench contract; IGG_BENCH_STRICT=1 turns a regression into rc=1.
    from implicitglobalgrid_tpu.telemetry import perfdb_add, perfdb_check

    hist = "PERF_HISTORY.jsonl"
    gate = perfdb_check(hist, results)
    perfdb_add(hist, results)
    results.append(bench_util.emit({
        "metric": "perfdb_gate_ok",
        "value": 1.0 if gate["ok"] else 0.0,
        "unit": "bool (1 = no metric regressed vs the trailing window)",
        "history_runs": gate["history_runs"],
        "checked": gate["checked"],
        "regressions": [r["metric"] for r in gate["regressions"]],
        "improvements": [r["metric"] for r in gate["improvements"]],
    }))

    with open("BENCH_ALL.json", "w") as f:
        json.dump(results, f, indent=1)
    lint_failed = not ruff_missing and lint.returncode != 0
    if (not gate["ok"] or lint_failed or not coalesce8_ok
            or not ensemble_ok or not tuned_ok or not reshard_ok
            or not serve_ok or not live_ok
            or not autoscale_ok or not tracing_ok) \
            and os.environ.get("IGG_BENCH_STRICT") == "1":
        sys.exit(1)


if __name__ == "__main__":
    bench_util.run(main, "bench_all", "suite")
