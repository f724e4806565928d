"""One run of one cell: set-up, the measured window, the check.

Set-up builds the grid, makes the state from the seed on the device (each
shard on its own chip), builds the supervised run (`ResilientRun`, the
machine `run_resilient` and `MeshScheduler` drive) and advances it a few
chunks, which compiles or loads the cell's one chunk program. The window
then calls `advance()` until ``seconds`` have passed and ends at the
commit that crosses the limit. Afterwards the check replays a sample of
the window's committed chunks with the plain reference."""

from __future__ import annotations

import gc
import json
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass

import numpy as np

from benchmark import trace as TR
from benchmark.layout import Layout
from benchmark.spec import Cell, NoResult, hbm_peak_bytes_per_s

ADVANCE, WINDOW = TR.SPAN_PREFIX + "advance", TR.SPAN_PREFIX + "window"
# a window whose chunks keep failing still ends this long after its limit
_LATE_S = 60.0
# chunks advanced in set-up: the first loads (or compiles) the chunk program
WARMUP_CHUNKS = 3
# chunks checked besides the window's last: drawn from the seed among the
# window's first SAMPLE_FROM_FIRST
SAMPLES, SAMPLE_FROM_FIRST = 2, 16
# a traced window: a few seconds hold some hundred chunks; a longer one
# only costs time to write and read
TRACE_SECONDS = 4.0


class CompileLog:
    """Backend compile seconds and persistent-cache hits, from JAX's
    monitoring events (register once per process)."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0

    def register(self):
        import jax
        from jax._src import dispatch

        def on_duration(event, duration, **_):
            if event == dispatch.BACKEND_COMPILE_EVENT:
                self.seconds += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
        return self


def devices_for(chips: int, require_tpu: bool = True):
    """The first ``chips`` devices, or `NoResult` where JAX finds no TPU or
    fewer chips than the cell asks for."""
    import jax

    devs = jax.devices()
    if require_tpu and (not devs or devs[0].platform != "tpu"):
        raise NoResult(f"no TPU: JAX's devices are "
                       f"{devs[0].platform if devs else 'none'!r}")
    if len(devs) < chips:
        raise NoResult(f"the cell needs {chips} chips; JAX sees {len(devs)}")
    return list(devs[:chips])


def info(**kw):
    """An informational line on standard output (not a metric)."""
    print(json.dumps(kw), flush=True)


@dataclass
class LayerContext:
    """What a per-layer reader (`benchmark/layer_metrics/`) reads."""
    trace: TR.Trace
    window: TR.Window
    devices: list        # the `trace.Device`s of the chips the cell used
    steps: int           # steps committed in the traced window
    bytes_per_step: float  # algorithmic HBM bytes per step per chip
    hbm_peak: float | None  # bytes/s of the device kind


def algorithmic_bytes(cfg: dict, layout: Layout) -> float:
    """The least HBM traffic of one step on one chip: each updated field's
    local array read and written once, each read-only field read once."""
    itemsize = np.dtype(cfg["dtype"]).itemsize
    return float(sum((2 if f["role"] == "updated" else 1)
                     * np.prod(layout.local_shape(k)) * itemsize
                     for k, f in cfg["fields"].items()))


def _kernel_counts(step, arrays: dict, mesh) -> dict:
    """Pallas kernels and collective-permutes in one lowered step of the
    tier the run used (informational)."""
    import jax

    from implicitglobalgrid_tpu.ops.fields import field_partition_spec

    names = list(arrays)
    specs = tuple(field_partition_spec(3) for _ in names)

    def one(*xs):
        out = step(dict(zip(names, xs)))
        return tuple(out[k] for k in names)

    txt = jax.jit(jax.shard_map(one, mesh=mesh, in_specs=specs,
                                out_specs=specs, check_vma=False)).lower(
        *(arrays[k] for k in names)).as_text()
    return {"tpu_custom_call": txt.count("tpu_custom_call"),
            "collective_permute": txt.count("collective_permute")}


def run_window(run, seconds: float, sample: set, traced: bool):
    """Drive ``run.advance()`` for ``seconds``. Returns the window's record:
    commit times, steps, chunk counts, failures and the sampled
    ``(before, after, steps)`` state pairs (the last chunk's included)."""
    import contextlib

    import jax

    def span(name):
        return (jax.profiler.TraceAnnotation(name) if traced
                else contextlib.nullcontext())

    rec = {"attempted": 0, "failed": 0, "errors": [], "kept": [],
           "commits": []}
    step0 = run.step
    with span(WINDOW):
        t0 = time.perf_counter()
        i = 0
        while True:
            before, s0 = run.state, run.step
            try:
                with span(ADVANCE):
                    run.advance()
            except Exception as e:  # a guard trip or a crash of the chunk
                rec["failed"] += 1
                if len(rec["errors"]) < 3:
                    rec["errors"].append(f"{type(e).__name__}: {e}")
            t = time.perf_counter()
            rec["attempted"] += 1
            done = run.step > s0
            if done:
                rec["commits"].append(t)
                if i in sample:
                    rec["kept"].append((before, run.state, run.step - s0))
            i += 1
            if (done and t - t0 >= seconds) or t - t0 >= seconds + _LATE_S:
                break
    if done and i - 1 not in sample:
        rec["kept"].append((before, run.state, run.step - s0))
    rec["t0"], rec["steps"] = t0, run.step - step0
    rec["window_s"] = (rec["commits"][-1] if rec["commits"] else t) - t0
    return rec


def _chunk_times(rec) -> dict:
    """Host-clock intervals between commits, and the rate in five-second
    bins of the window (informational: where the time of a run went)."""
    import statistics

    t = [rec["t0"]] + rec["commits"]
    iv = [(b - a) * 1e3 for a, b in zip(t, t[1:])]
    if len(iv) < 2:
        return {}
    q = statistics.quantiles(iv, n=20, method="inclusive")
    bins = {}
    for a, b in zip(t, t[1:]):
        k = int((b - rec["t0"]) // 5)
        bins[k] = bins.get(k, 0) + 1
    return {"chunk_ms_median": statistics.median(iv), "chunk_ms_p5": q[0],
            "chunk_ms_p95": q[18], "chunk_ms_max": max(iv),
            "chunks_per_5s": [bins[k] for k in sorted(bins)]}


COMPARE_REGIONS = ("stacked", "owned")


def comparisons(cfg: dict, phys: dict) -> dict:
    """How `check` reads each field: ``{name: (region, of, per)}``, the
    error over ``region`` against ``max|reference of| / per``.

    From the field's entry in the configuration:

    - ``"compare"``: ``"stacked"`` (the default), the whole stacked array,
      halos included; or ``"owned"``, each global entry once from its
      owning shard, for state whose halos the program never exchanges.
    - ``"scale"``: by default the field's own ``max|reference|``; or
      ``{"field": f, "over": c}``, ``max|reference f| / phys[c]``, for a
      rate of change of ``f`` that an iteration drives towards zero, so
      that its error is read by the change it makes to ``f`` in one step
      of ``c``.

    Either key needs a ``"why"`` beside it. An unknown region, field or
    physics constant is a `NoResult`."""
    out = {}
    for k, f in cfg["fields"].items():
        region = f.get("compare", "stacked")
        if region not in COMPARE_REGIONS:
            raise NoResult(f"field {k!r}: unknown compare region {region!r}; "
                           f"have {list(COMPARE_REGIONS)}")
        scale = f.get("scale", {"field": k})
        if (not isinstance(scale, dict) or set(scale) - {"field", "over"}
                or scale.get("field") not in cfg["fields"]):
            raise NoResult(f"field {k!r}: scale {scale!r} is not "
                           "{\"field\": <a field>, \"over\": <a constant>}")
        of, over = scale["field"], scale.get("over")
        per = 1.0 if over is None else phys.get(over)
        if not isinstance(per, (int, float)) or not per > 0:
            raise NoResult(f"field {k!r}: scale over {over!r} is no "
                           f"positive physics constant ({sorted(phys)})")
        if (region != "stacked" or "scale" in f) and not f.get("why"):
            raise NoResult(f"field {k!r}: a \"compare\" or \"scale\" says "
                           "why, in a \"why\" beside it")
        out[k] = (region, of, per)
    return out


def check(cell: Cell, layout: Layout, phys: dict, kept, device,
          control: bool = False) -> dict:
    """Replay each kept chunk with the plain reference and compare, on
    ``device``.

    Reading: the largest, over the kept chunks and the fields, of
    ``max|program - reference|`` over the field's region, halos included
    or each global entry once from its owning shard, divided by
    ``max|reference|`` of the field or of the one its scale names
    (`comparisons`). With ``control``, the reference computed in the
    precision below the configuration's (bfloat16 for float32) takes the
    program's place, on the same inputs."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(cell.config["dtype"])
    plan = comparisons(cell.config, phys)
    out = {"max_rel_err": 0.0}

    def rel(a, b, scale):
        err = float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                    - b.astype(jnp.float32))))
        return (err if np.isfinite(err) else float("inf")) / scale

    fns = {}
    for before, after, n in kept:
        if n not in fns:
            fns[n] = (cell.model.reference(phys, n, dtype),
                      cell.model.reference(phys, n, jnp.bfloat16)
                      if control else None)
        ref, low = fns[n]
        g = {k: layout.to_global(k, jax.device_put(before[k], device), jnp)
             for k in plan}
        r = ref(g)
        lo = low(g) if control else None
        del g
        for k, (region, of, per) in plan.items():
            scale = float(jnp.max(jnp.abs(r[of]))) / per or 1.0
            if region == "owned":
                got = (lo[k] if control else layout.to_global(
                    k, jax.device_put(after[k], device), jnp))
                want = r[k]
            else:
                got = (layout.to_stacked(k, lo[k], jnp) if control
                       else jax.device_put(after[k], device))
                want = layout.to_stacked(k, r[k], jnp)
            out["max_rel_err"] = max(out["max_rel_err"],
                                     rel(got, want, scale))
    return out


def layer_metrics(cell: Cell, path: str, used_ids, steps: int,
                  bytes_per_step: float, hbm_peak):
    """Per-layer metrics, the ``device`` trace fields and the breakdown
    from one trace file."""
    tr = TR.load(path)
    w = TR.Window.of(tr, ADVANCE)
    devs = [d for d in tr.devices
            if d.name.rsplit(":", 1)[-1].isdigit()
            and int(d.name.rsplit(":", 1)[-1]) in used_ids]
    if w is None or not devs:
        raise RuntimeError(f"the trace holds no window ({w}) or no device "
                           f"plane of devices {sorted(used_ids)} "
                           f"(planes: {[d.name for d in tr.devices]})")
    ctx = LayerContext(tr, w, devs, steps, bytes_per_step, hbm_peak)
    metrics = {}
    for m in cell.per_layer:
        v = cell.readers[m["name"]].read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    busy = sum(TR.total(TR.busy(d, w)) for d in devs) / len(devs) / 1e9
    gaps = sorted((g for d in devs for g in TR.idle_gaps(d, w, tr.spans)),
                  key=lambda g: -g[1])[:10]
    breakdown = {"device_ops": [list(r) for r in TR.top_ops(devs, w)],
                 "idle_gaps": [list(g) for g in gaps]}
    return metrics, {"busy_s": busy, "window_s": w.length / 1e9}, breakdown


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool, *,
             t_start: float, require_tpu: bool = True, local_n=None,
             control: bool = False, keep_trace: str | None = None,
             compile_log: CompileLog | None = None) -> dict:
    """One run of ``cell``; returns the result record (`emit` prints it).
    ``local_n`` overrides the configuration's local size (tests on the
    CPU only); ``require_tpu=False`` skips the look for a chip."""
    import jax
    import jax.numpy as jnp

    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.models.common import resolve_pallas_impl
    from implicitglobalgrid_tpu.ops.fields import field_partition_spec
    from implicitglobalgrid_tpu.runtime.driver import ResilientRun, RunSpec

    cfg, traffic = cell.config, cell.traffic
    devices = devices_for(cell.chips, require_tpu)
    kind = devices[0].device_kind
    hbm_peak = hbm_peak_bytes_per_s(kind) if require_tpu else None
    n = tuple(local_n or cfg["local_n"])
    dims = tuple(traffic["mesh"])
    if int(np.prod(dims)) != cell.chips:
        raise NoResult(f"traffic mesh {dims} does not fit {cell.chips} "
                       "chips")
    if not cfg["periodic"]:
        raise NoResult("the benchmark's layout covers periodic grids only")
    io = {}
    igg.init_global_grid(*n, dimx=dims[0], dimy=dims[1], dimz=dims[2],
                         periodx=1, periody=1, periodz=1, devices=devices,
                         quiet=True)
    t_grid = time.perf_counter()
    try:
        gg = igg.global_grid()
        layout = Layout(n, dims, {k: f["stagger"]
                                  for k, f in cfg["fields"].items()})
        phys = cell.model.physics(cfg, layout)
        comparisons(cfg, phys)  # a bad entry ends the run before set-up
        sharding = jax.sharding.NamedSharding(gg.mesh,
                                              field_partition_spec(3))
        made = cell.model.make_state(cfg, layout, seed, sharding,
                                     jnp.dtype(cfg["dtype"]))
        state = {k: made[k] for k in cfg["fields"]}
        del made
        t_state = time.perf_counter()
        impl = resolve_pallas_impl(None)
        step = cell.model.program_step(phys, impl)
        nt_chunk = int(traffic["nt_chunk"])
        # checkpoint and snapshot cadences of the traffic mix write under
        # the run's TMPDIR; a cadence that is a multiple of the chunk
        # clips no chunk
        for what in ("checkpoint", "snapshot"):
            every = traffic.get(f"{what}_every")
            if every:
                io[f"{what}_dir"] = tempfile.mkdtemp(prefix=f"bench_{what}_")
                io[f"{what}_every"] = int(every)
        spec = RunSpec(nt_chunk=nt_chunk, key=("benchmark", cell.name),
                       check_vma=False if impl.startswith("pallas")
                       else None, **io)
        # nt far beyond any window, and a multiple of the chunk: the run
        # never clips a chunk, so one program serves every chunk
        run = ResilientRun(step, state, nt_chunk * 10 ** 7, spec)
        shapes = {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                          sharding=v.sharding)
                  for k, v in state.items()}
        del state
        warm = {"attempted": 0, "failed": 0}
        t_warm = time.perf_counter()
        for _ in range(WARMUP_CHUNKS):
            warm["attempted"] += 1
            try:
                run.advance()
            except Exception as e:
                warm["failed"] += 1
                print(f"warm-up chunk failed: {type(e).__name__}: {e}",
                      file=sys.stderr)
        jax.block_until_ready(run.state)
        t_end = time.perf_counter()
        setup_s = t_end - t_start
        phases = {"to_grid_s": t_grid - t_start,
                  "state_s": t_state - t_grid, "warmup_s": t_end - t_warm}
        compile_s = compile_log.seconds if compile_log else None
        hits = compile_log.cache_hits if compile_log else None

        rng = np.random.default_rng(int(seed) % (1 << 64))
        sample = set(int(i) for i in rng.choice(
            SAMPLE_FROM_FIRST, size=SAMPLES, replace=False))
        tdir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
        if traced:
            seconds = min(seconds, TRACE_SECONDS)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            rec = run_window(run, seconds, sample, traced)
        finally:
            if traced:
                jax.profiler.stop_trace()
        mem = [d.memory_stats() or {} for d in devices]
        peak = max(m.get("peak_bytes_in_use", 0) for m in mem)
        info(tier=impl, **_kernel_counts(step, shapes, gg.mesh))
        info(memory_peak_bytes_per_device={
            str(d.id): m.get("peak_bytes_in_use") for d, m in
            zip(devices, mem)})
        info(setup_s=setup_s, **phases, backend_compile_s=compile_s,
             compile_cache_hits=hits, warmup_chunks=warm["attempted"],
             window_chunks=rec["attempted"], window_steps=rec["steps"],
             window_s=rec["window_s"], sampled_chunks=sorted(sample))
        info(**_chunk_times(rec))
        for e in rec["errors"]:
            print(f"window chunk failed: {e}", file=sys.stderr)
        # the check runs once the program's state is freed; it keeps only
        # the sampled chunks' inputs and outputs
        kept = rec.pop("kept")
        run.close()
        del run
        gc.collect()
        t_check = time.perf_counter()
        readings = check(cell, layout, phys, kept, devices[0], control)
        info(check_s=time.perf_counter() - t_check, chunks_checked=len(kept))
        del kept
    finally:
        igg.finalize_global_grid()
        for k, v in io.items():
            if k.endswith("_dir"):
                shutil.rmtree(v, ignore_errors=True)

    chips = cell.chips
    cells = float(np.prod(layout.global_shape))
    result = {"attempted": warm["attempted"] + rec["attempted"],
              "failed": warm["failed"] + rec["failed"]}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    breakdown = None
    dev = {"platform": devices[0].platform, "kind": kind, "count": chips,
           "memory_peak_bytes": peak}
    if traced:
        import glob

        path = glob.glob(f"{tdir}/plugins/profile/*/*.xplane.pb")[0]
        if keep_trace:
            shutil.copy(path, keep_trace)
        metrics, dtrace, breakdown = layer_metrics(
            cell, path, {d.id for d in devices}, rec["steps"],
            algorithmic_bytes(cfg, layout), hbm_peak)
        dev.update(dtrace)
        shutil.rmtree(tdir, ignore_errors=True)
    else:
        values = {"cell_updates_per_s_per_chip":
                  cells * rec["steps"] / rec["window_s"] / chips / 1e9,
                  "setup_s": setup_s}
        metrics = {k: {"value": v, "unit": units[k]}
                   for k, v in values.items() if k in units}
    limit = float(cfg["limits"]["max_rel_err"])
    compared = {"max_rel_err": {"value": readings["max_rel_err"],
                                "limit": limit},
                "failed_chunks": {"value": result["failed"], "limit": 0}}
    result["correct"] = bool(readings["max_rel_err"] <= limit
                             and result["failed"] == 0)
    result["metrics"] = metrics
    result["device"] = dev
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    return result


def emit(result: dict) -> None:
    """The compared numbers as the last lines of standard error, and the
    result as the last line of standard output."""
    for name, c in result["compared"].items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    line = {k: result[k] for k in ("correct", "attempted", "failed",
                                   "metrics", "device", "breakdown",
                                   "compared") if k in result}
    print(json.dumps(line), flush=True)
