"""Benchmark: telemetry overhead of the supervised driver.

The observability layer (ISSUE 3) instruments the resilient driver's
per-chunk host path — flight-recorder JSONL events (run/chunk/cache
records, flushed per line), metrics-registry counter bumps, and the
runner-cache notes — all strictly host-side (the HLO-level guarantee that
the chunk PROGRAM is unchanged lives in tests/test_hlo_audit.py). This leg
bounds what that instrumentation costs at the driver's operating point,
against the <2% gate (ISSUE 3 acceptance), with two measurements:

- ``value`` (gated): the DETERMINISTIC accounting — the microbenchmarked
  cost of one flushed recorder event (including its registry bumps and
  the open/close amortized) times the events a supervised run actually
  emits, over the run's median telemetry-off time. This measures the
  exact marginal work telemetry adds, reproducibly.
- ``ab_median_frac`` (corroboration): an end-to-end telemetry-on vs
  telemetry-off `run_resilient` A/B — alternating-order interleaved
  pairs, median of the per-pair fractional differences. On the shared
  CPU mesh the per-run jitter (±30-100% observed, `ab_noise_iqr`) is
  orders of magnitude above the ~0.1% signal, so this corroborates that
  the cost is lost in the noise rather than resolving it; on quiet
  hardware the two figures converge.

Like the guard-overhead leg (bench_resilience.py) this is INCLUSIVE
per-chunk cost, not a two-point slope: the overhead is per-chunk fixed,
which a slope over two window sizes would cancel by construction.

Usage: python bench_telemetry.py          (real chip)
       python bench_telemetry.py --cpu    (8-device virtual CPU mesh)
"""

from __future__ import annotations

import itertools
import os
import sys
import tempfile

import bench_util


def telemetry_overhead_rows(nx: int, nt_chunk: int, n_chunks: int = 3,
                            reps: int = 10):
    """One row on the CURRENT grid (caller owns init/finalize): the
    telemetry overhead fraction of a supervised run (see module
    docstring for the two estimators)."""
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
    key = ("bench_telemetry", nx, nt_chunk)
    tmp = tempfile.mkdtemp(prefix="igg_bench_tel_")
    seq = itertools.count()

    def run_off():
        igg.run_resilient(step, state, nt, nt_chunk=nt_chunk, key=key)

    def run_on():
        igg.start_flight_recorder(
            os.path.join(tmp, f"run{next(seq)}.jsonl"))
        try:
            igg.run_resilient(step, state, nt, nt_chunk=nt_chunk, key=key)
        finally:
            igg.stop_flight_recorder()

    # warm: compile once (shared key), first JSONL file created
    run_off()
    run_on()

    # --- end-to-end A/B (corroboration) --------------------------------
    # alternating-order interleaved pairs cancel position bias; the
    # median of pair diffs is the only estimator that does not turn into
    # a coin flip at a sub-0.1% effect under multi-10% machine jitter
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

    # --- deterministic accounting (the gated figure) -------------------
    # one flushed event write (registry bumps included via the same
    # hooks), open/close amortized over the probe; scaled by the events a
    # real run emits over the run's median telemetry-off time
    n_events = len(igg.read_flight_events(
        os.path.join(tmp, "run0.jsonl")))
    probe = os.path.join(tmp, "probe.jsonl")
    n_probe = 2000
    t0 = time.monotonic()
    igg.start_flight_recorder(probe)
    for i in range(n_probe):
        igg.record_event("chunk", chunk=i, step_begin=0, step_end=nt_chunk,
                         n=nt_chunk, ok=True, reasons=[], build_s=1e-3,
                         exec_s=0.1)
    igg.stop_flight_recorder()
    per_event_s = (time.monotonic() - t0) / n_probe
    t_off_med = statistics.median(times["off"])
    accounted = per_event_s * n_events / t_off_med

    return [{
        "metric": "telemetry_overhead_frac",
        "value": accounted,
        "unit": "fraction of run time, deterministic per-event accounting "
                "(target < 0.02)",
        "target": 0.02,
        "nt": nt,
        "nt_chunk": nt_chunk,
        "events_per_run": n_events,
        "per_event_write_s": per_event_s,
        "off_run_s_median": t_off_med,
        "on_run_s_median": statistics.median(times["on"]),
        "ab_median_frac": statistics.median(pair_fracs),
        "ab_noise_iqr": iqr,
        "note": "ab_median_frac is the end-to-end A/B (median of "
                "alternating interleaved pairs); on the shared-CPU mesh "
                "its noise floor (ab_noise_iqr) sits far above the "
                "accounted cost, corroborating the gate rather than "
                "resolving it",
    }]


def live_plane_rows(t_ref_s: float, n_boundaries: int = 3):
    """The LIVE observability plane's cost (ISSUE 18), host-only:

    - ``live_tail_overhead_frac`` (gated < 2%): the DETERMINISTIC
      per-boundary accounting — one full in-process alert cadence
      (append the driver's ~4 boundary events, drain the tail, evaluate
      the default rule pack over a fresh snapshot) microbenchmarked,
      times the boundaries a reference run crosses, over that run's
      telemetry-off wall time (``t_ref_s``, from the telemetry leg).
      This is exactly what `MeshScheduler(alerts=True)` adds per slice.
    - ``observe_roundtrip_s``: one ``GET /v1/observe`` against a live
      `ObserveServer` (poll + derive + serialize), median.
    - ``events_stream_lag_s``: append-to-NDJSON-line latency through an
      open ``GET /v1/events`` stream (the tail cadence bound), median.

    The latter two ride the perfdb trajectory (no absolute gate — they
    are loopback-HTTP latencies, machine-dependent by nature)."""
    import json
    import statistics
    import time
    import urllib.request

    from implicitglobalgrid_tpu.serve import ObserveServer
    from implicitglobalgrid_tpu.telemetry.live import (
        AlertEngine, LiveAggregate,
    )

    tmp = tempfile.mkdtemp(prefix="igg_bench_live_")
    path = os.path.join(tmp, "flight_j.jsonl")
    state = {"t": 100.0, "seq": 0}

    def append(kind, **kw):
        state["t"] += 0.05
        rec = {"t": state["t"], "kind": kind, "run": "j", "pid": 1,
               "proc": 0, "seq": state["seq"], **kw}
        state["seq"] += 1
        with open(path, "a") as f:
            f.write(json.dumps(rec) + "\n")

    def boundary(i):
        # the supervised driver's per-boundary emissions
        append("chunk", chunk=i, step_begin=4 * i, step_end=4 * i + 4,
               n=4, ok=True, reasons=[], build_s=1e-3, exec_s=0.1)
        append("deadline_slack", step=4 * i + 4, slack_s=100.0)
        append("checkpoint", step=4 * i + 4, seconds=0.01)
        append("snapshot_submit", step=4 * i + 4, bytes=1 << 20)

    append("recorder_open", wall=5000.0)
    live = LiveAggregate(tmp)
    eng = AlertEngine()  # the default pack — the scheduler's cadence
    live.poll()

    # --- deterministic per-boundary accounting (the gated figure) ------
    n_probe = 300
    t0 = time.monotonic()
    for i in range(n_probe):
        boundary(i)
        live.poll()
        eng.evaluate(live.snapshot())
    per_boundary_s = (time.monotonic() - t0) / n_probe
    frac = per_boundary_s * n_boundaries / t_ref_s

    rows = [{
        "metric": "live_tail_overhead_frac",
        "value": frac,
        "unit": "fraction of run time, deterministic per-boundary "
                "accounting (target < 0.02)",
        "target": 0.02,
        "per_boundary_s": per_boundary_s,
        "events_per_boundary": 4,
        "boundaries_per_run": n_boundaries,
        "ref_run_s": t_ref_s,
        "note": "one in-process alert cadence (tail drain + default "
                "rule pack over a fresh snapshot) per chunk boundary — "
                "what MeshScheduler(alerts=True) adds per slice",
    }]

    # --- the HTTP surface ----------------------------------------------
    with ObserveServer(tmp) as obs:
        u = f"http://{obs.host}:{obs.port}"
        rts = []
        for _ in range(15):
            t0 = time.monotonic()
            with urllib.request.urlopen(u + "/v1/observe",
                                        timeout=10) as r:
                cursor = json.loads(r.read())["cursor"]
            rts.append(time.monotonic() - t0)
        lags = []
        stream = urllib.request.urlopen(
            u + f"/v1/events?since={cursor}&timeout_s=30&heartbeat_s=10",
            timeout=35)
        try:
            for i in range(5):
                t0 = time.monotonic()
                append("chunk", chunk=n_probe + i, n=4, ok=True,
                       reasons=[], build_s=1e-3, exec_s=0.1,
                       step_begin=0, step_end=4)
                while True:
                    e = json.loads(stream.readline())
                    if e.get("kind") != "heartbeat":
                        lags.append(time.monotonic() - t0)
                        break
        finally:
            stream.close()
    rows.append({
        "metric": "observe_roundtrip_s",
        "value": statistics.median(rts),
        "unit": "s (GET /v1/observe: poll + derive + serialize, median "
                "of 15 loopback round trips)",
        "reps": len(rts),
    })
    rows.append({
        "metric": "events_stream_lag_s",
        "value": statistics.median(lags),
        "unit": "s (flight append -> NDJSON line on an open /v1/events "
                "stream, median of 5; floor = the 50 ms tail cadence)",
        "reps": len(lags),
    })
    return rows


def tracing_rows(t_ref_s: float, n_events: int):
    """Distributed tracing's cost (ISSUE 20), host-only:

    - ``trace_ctx_overhead_frac`` (gated < 2%): the DETERMINISTIC
      accounting — the recorder's trace stamp is two dict inserts per
      event (`FlightRecorder.trace`), measured as the per-event delta
      between a traced and an untraced recorder over interleaved
      flushed-write probes, times the events a supervised run emits,
      over the telemetry leg's off-run time. The delta is clamped at
      zero: the stamp costs nanoseconds against a ~10 us flushed write,
      so the raw difference (recorded alongside) can go negative under
      machine jitter.
    - ``otlp_export_s``: `export_otlp` wall time on a 10k-event traced
      stream (journal-style minted span ids + flight-style synthesized
      ones) — the post-hoc export an operator runs per incident; perfdb
      trajectory, no absolute gate."""
    import json
    import statistics
    import time

    from implicitglobalgrid_tpu.telemetry import (
        FlightRecorder, TraceContext, export_otlp,
    )

    tmp = tempfile.mkdtemp(prefix="igg_bench_tracing_")
    tr = TraceContext.new().child()  # the job root, as the scheduler sets
    n_probe = 2000
    seq = itertools.count()

    def probe(trace):
        rec = FlightRecorder(os.path.join(tmp, f"p{next(seq)}.jsonl"),
                             run_id="probe")
        rec.trace = trace
        t0 = time.monotonic()
        for i in range(n_probe):
            rec.event("chunk", chunk=i, step_begin=0, step_end=4, n=4,
                      ok=True, reasons=[], build_s=1e-3, exec_s=0.1)
        dt = time.monotonic() - t0
        rec.close()
        return dt / n_probe

    offs, ons = [], []
    for r in range(5):  # alternating order cancels position bias
        for trace, acc in ([(None, offs), (tr, ons)] if r % 2 == 0
                           else [(tr, ons), (None, offs)]):
            acc.append(probe(trace))
    per_off = statistics.median(offs)
    per_on = statistics.median(ons)
    delta = per_on - per_off
    rows = [{
        "metric": "trace_ctx_overhead_frac",
        "value": max(0.0, delta) * n_events / t_ref_s,
        "unit": "fraction of run time, deterministic per-event "
                "accounting (target < 0.02)",
        "target": 0.02,
        "per_event_off_s": per_off,
        "per_event_traced_s": per_on,
        "per_event_delta_s": delta,
        "events_per_run": n_events,
        "ref_run_s": t_ref_s,
        "note": "the stamp is two dict inserts before a flushed JSONL "
                "write; span ids are synthesized at export, never on "
                "the hot path",
    }]

    # --- the post-hoc OTLP export on a 10k-event traced stream ---------
    path = os.path.join(tmp, "otlp_stream.jsonl")
    n_stream = 10_000
    with open(path, "w") as f:
        f.write(json.dumps({"kind": "recorder_open", "wall": 5000.0,
                            "t": 100.0, "run": "j", "pid": 1, "proc": 0,
                            "seq": 0}) + "\n")
        for i in range(n_stream):
            e = {"t": 100.0 + 0.01 * i,
                 "kind": "slice" if i % 2 == 0 else "chunk",
                 "run": "j", "pid": 1, "proc": 0, "seq": i + 1,
                 "trace_id": tr.trace_id, "parent_span_id": tr.span_id,
                 "chunk": i, "exec_s": 0.005, "ok": True}
            if i % 2 == 0:  # journal-style events mint their span id
                e["span_id"] = f"{i + 1:016x}"
            f.write(json.dumps(e) + "\n")
    out = os.path.join(tmp, "spans.json")
    t0 = time.monotonic()
    export_otlp(path, out)
    otlp_s = time.monotonic() - t0
    with open(out) as f:
        n_spans = sum(len(ss["spans"])
                      for rs in json.load(f)["resourceSpans"]
                      for ss in rs["scopeSpans"])
    rows.append({
        "metric": "otlp_export_s",
        "value": otlp_s,
        "unit": "s (export_otlp on a 10k-event traced stream: read + "
                "encode + write)",
        "events": n_stream + 1,
        "spans": n_spans,
    })
    return rows


def run_telemetry_overhead(dims, cpu: bool):
    """The canonical leg: init its own grid over ``dims``, measure,
    finalize, return the rows. Shared by this script's __main__ and
    `bench_all.py` so the config stays in ONE place."""
    import implicitglobalgrid_tpu as igg

    # per-chunk fixed cost: chunks long enough that call jitter does not
    # swamp the sub-1% signal (same sizing rationale as bench_resilience)
    nx, nt_chunk = (32, 60) if cpu else (256, 200)
    igg.init_global_grid(nx, nx, nx, dimx=dims[0], dimy=dims[1],
                         dimz=dims[2], periodx=1, periody=1, periodz=1,
                         quiet=True)
    try:
        return telemetry_overhead_rows(nx, nt_chunk)
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
    rows = run_telemetry_overhead(dims, cpu)
    for row in rows:
        bench_util.emit(row)
    t_ref = next(r["off_run_s_median"] for r in rows
                 if r["metric"] == "telemetry_overhead_frac")
    n_chunks = next(r["nt"] // r["nt_chunk"] for r in rows
                    if r["metric"] == "telemetry_overhead_frac")
    for row in live_plane_rows(t_ref, n_boundaries=n_chunks):
        bench_util.emit(row)
    n_events = next(r["events_per_run"] for r in rows
                    if r["metric"] == "telemetry_overhead_frac")
    for row in tracing_rows(t_ref, n_events):
        bench_util.emit(row)


if __name__ == "__main__":
    bench_util.run(main, "telemetry_overhead_frac", "fraction")
