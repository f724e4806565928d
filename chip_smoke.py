"""Bring-up smoke run: the supervised main path on the TPU, checked.

    python chip_smoke.py             one chip: diffusion3D 256³, acoustic3D
                                     192³, Stokes PT 128³
    python chip_smoke.py --chips 4   four chips (2x2x1 periodic mesh):
                                     diffusion3D 256³/chip, acoustic3D
                                     192³/chip — the fused multi-shard
                                     exchange kernels, nothing else

Every phase goes through the public entry points: `init_global_grid` ->
`init_diffusion3d`/`init_acoustic3d`/`init_stokes3d` -> `run_resilient`
(health guard on, one checkpoint written) -> `gather_interior` ->
`finalize_global_grid`. It runs the kernel tier the library selects
(`resolve_pallas_impl(None)`, which must be ``pallas`` on a TPU grid) and
compares it with the XLA tier of the same model on the same chips, and
the diffusion phase also with the NumPy f64 reference below (independent
of the package). Informational lines (one JSON object per phase) come
first; the last line is ``{"ok": true, "device": {...}}``. Any failed
comparison, any exception, or a device that is not a TPU exits nonzero
and prints no such line.
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time

import numpy as np

# Library tier vs XLA tier, same chip, same f32 inputs. The tiers order
# their f32 roundings differently (a few ulp of max|field| per step); the
# schemes are stable, so that grows at most linearly: 300 steps x ~2.4e-7
# is ~7e-5 of max|field|.
TOL_TIER = 1e-4
# Package f32 vs NumPy f64 after NT_NUMPY steps from the same f32 initial
# state: ~10 f32 roundings (6e-8 each) per cell per step stay under ~1e-6
# of max|T| after 5 steps.
TOL_NUMPY = 1e-5
NT_NUMPY = 5

# (model, local n, periodic, steps, chunk) — the reference README's
# diffusion3D local size (`README.md:163-167` of the reference) and the
# benchmark widths of acoustic and Stokes (bench.py).
PHASES_1 = (("diffusion3d", 256, True, 300, 100),
            ("acoustic3d", 192, True, 200, 100),
            ("stokes3d", 128, False, 200, 100))
PHASES_4 = (("diffusion3d", 256, True, 300, 100),
            ("acoustic3d", 192, True, 200, 100))
DIMS = {1: (1, 1, 1), 4: (2, 2, 1)}

_compile = {"seconds": 0.0, "cache_hits": 0}


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check_tpu(devices, chips: int):
    """The devices to run on: the first ``chips`` TPU devices, or raise."""
    if not devices or devices[0].platform != "tpu":
        kind = devices[0].platform if devices else "none"
        raise SmokeFailure(f"no TPU: JAX's default devices are {kind!r}")
    if len(devices) < chips:
        raise SmokeFailure(f"--chips {chips} needs {chips} TPU devices; "
                           f"JAX sees {len(devices)}")
    return list(devices[:chips])


def numpy_diffusion(T, Cp, lam, dt, h, nt):
    """Plain f64 diffusion on the global periodic grid:
    ``T += dt * lam * laplacian(T) / Cp``, neighbours by `np.roll`."""
    T = np.asarray(T, np.float64)
    Cp = np.asarray(Cp, np.float64)
    for _ in range(nt):
        lap = sum((np.roll(T, -1, a) - 2.0 * T + np.roll(T, 1, a)) / h[a] ** 2
                  for a in range(T.ndim))
        T = T + dt * lam * lap / Cp
    return T


def rel_err(got: dict, ref: dict) -> float:
    """Max over fields of max|got - ref| / max|ref|."""
    out = 0.0
    for k, r in ref.items():
        r = np.asarray(r, np.float64)
        scale = float(np.max(np.abs(r))) or 1.0
        out = max(out, float(np.max(np.abs(
            np.asarray(got[k], np.float64) - r))) / scale)
    return out


def _on_duration(event, duration, **_):
    from jax._src import dispatch

    if event == dispatch.BACKEND_COMPILE_EVENT:
        _compile["seconds"] += duration


def _on_event(event, **_):
    if event == "/jax/compilation_cache/cache_hits":
        _compile["cache_hits"] += 1


def _setup(model):
    """``(state, fields_to_compare, step_for(impl), params)`` on the live
    grid, with the model's own initial conditions."""
    import implicitglobalgrid_tpu.models as M

    if model == "diffusion3d":
        T, Cp, p = M.init_diffusion3d(dtype=np.float32)

        def step_for(impl):
            def step(s):
                return {"T": M.diffusion_step_local(s["T"], s["Cp"], p, impl),
                        "Cp": s["Cp"]}
            return step
        return {"T": T, "Cp": Cp}, ("T",), step_for, p
    if model == "acoustic3d":
        state, p = M.init_acoustic3d(dtype=np.float32)
        names = ("P", "Vx", "Vy", "Vz")
        fn = M.acoustic_step_local
    else:
        state, p = M.init_stokes3d(dtype=np.float32)
        names = ("P", "Vx", "Vy", "Vz", "dVx", "dVy", "dVz", "rhog")
        fn = M.stokes_step_local

    def step_for(impl):
        def step(s):
            return dict(zip(names, fn(tuple(s[n] for n in names), p, impl)))
        return step
    return dict(zip(names, state)), names[:4], step_for, p


def kernel_counts(step, state) -> dict:
    """Pallas kernels and collective-permutes in ONE step's lowered
    program — what the tier actually put on the chip (a ``pallas`` impl
    whose shape gate fails falls through to XLA silently)."""
    import jax

    import implicitglobalgrid_tpu as igg

    names = list(state)
    arrays = [state[k] for k in names]
    specs = tuple(a.sharding.spec for a in arrays)

    def one(*xs):
        out = step(dict(zip(names, xs)))
        return tuple(out[k] for k in names)

    txt = jax.jit(jax.shard_map(
        one, mesh=igg.global_grid().mesh, in_specs=specs, out_specs=specs,
        check_vma=False)).lower(*arrays).as_text()
    return {"tpu_custom_call": txt.count("tpu_custom_call"),
            "collective_permute": txt.count("collective_permute")}


def _mem(devices, key="bytes_in_use") -> dict:
    return {d: d.memory_stats()[key] for d in devices}


def _placement(devices, before, state) -> list:
    """Every state array has one shard on each device, and each device's
    ``bytes_in_use`` grew by about the bytes of its shards."""
    import gc

    import jax

    want = {d: 0 for d in devices}
    for k, a in state.items():
        on = [s.device for s in a.addressable_shards]
        if sorted(d.id for d in on) != sorted(d.id for d in devices):
            raise SmokeFailure(f"{k}: shards on devices "
                               f"{[d.id for d in on]}, want one on each of "
                               f"{[d.id for d in devices]}")
        for s in a.addressable_shards:
            want[s.device] += s.data.nbytes
    # the initial conditions are built unsharded on one device and then
    # resharded: wait for that copy, and let its source buffers go
    jax.block_until_ready(state)
    gc.collect()
    rows = []
    now, peak = _mem(devices), _mem(devices, "peak_bytes_in_use")
    for d in devices:
        grew = now[d] - before[d]
        rows.append({"device": d.id, "grew": grew, "shard_bytes": want[d],
                     "peak_bytes_in_use": peak[d]})
        if not 0.5 * want[d] <= grew <= 2.0 * want[d]:
            raise SmokeFailure(f"device {d.id}: bytes_in_use grew {grew}, "
                               f"its shards hold {want[d]}")
    return rows


def _advance(step, impl, state, nt, chunk, key, checkpoint=False):
    """`run_resilient` under the health guard; every chunk must pass it.
    Pallas outputs carry no mesh-axis variance, so a step of the
    ``impl="pallas"`` tier runs with ``check_vma=False``."""
    import implicitglobalgrid_tpu as igg

    with tempfile.TemporaryDirectory() as ck:
        state, reports = igg.run_resilient(
            step, state, nt, nt_chunk=chunk, key=key,
            check_vma=False if impl.startswith("pallas") else None,
            checkpoint_dir=ck if checkpoint else None,
            checkpoint_every=nt if checkpoint else None)
    bad = [r for r in reports if not r.ok]
    if bad:
        raise SmokeFailure(f"{key}: health guard tripped: {bad[0]}")
    return state


def run_tier(model, n, periodic, nt, chunk, devices, impl=None,
             numpy_steps=0, placement=False):
    """One supervised run of ``model`` on a fresh grid over ``devices``.

    ``impl=None`` takes the tier the library selects. With
    ``numpy_steps`` the run first advances that many steps and gathers
    (the NumPy reference's window) before the remaining ``nt`` steps.
    Returns a dict of gathered fields and facts."""
    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.models.common import resolve_pallas_impl

    dx, dy, dz = DIMS[len(devices)]
    per = int(periodic)
    igg.init_global_grid(n, n, n, dimx=dx, dimy=dy, dimz=dz, periodx=per,
                         periody=per, periodz=per, devices=devices,
                         quiet=True)
    try:
        before = _mem(devices) if placement else None
        state, names, step_for, p = _setup(model)
        out = {"placement": _placement(devices, before, state)
               if placement else None}
        impl = resolve_pallas_impl(impl)
        step = step_for(impl)
        out["tier"] = impl
        out["kernels"] = kernel_counts(step, state)
        out["params"] = p
        key = ("chip_smoke", model, impl)
        if numpy_steps:
            out["initial"] = {k: igg.gather_interior(state[k])
                              for k in ("T", "Cp")}
            state = _advance(step, impl, state, numpy_steps, numpy_steps,
                             key)
            out["early"] = {k: igg.gather_interior(state[k]) for k in names}
        state = _advance(step, impl, state, nt, chunk, key, checkpoint=True)
        out["final"] = {k: igg.gather_interior(state[k]) for k in names}
        out["steps"] = numpy_steps + nt
        if model == "diffusion3d" and impl != "xla" and len(devices) == 1:
            out["block_until_ready"] = probe_block_until_ready(
                p, state["T"], state["Cp"], impl)
        return out
    finally:
        igg.finalize_global_grid()


def probe_block_until_ready(p, T, Cp, impl, chunk=100, calls=3):
    """Does `jax.block_until_ready` return only after the device finished?
    Enqueue ``calls`` chained chunks, wait with it, then fetch a value that
    data-depends on every shard (`igg.sync`). An early return would leave
    the device work to the fetch."""
    import jax

    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.models import make_run

    run = make_run(p, nt_chunk=chunk, impl=impl)
    igg.sync(run(T, Cp))  # compile outside the window
    t0 = time.perf_counter()
    s = (T, Cp)
    for _ in range(calls):
        s = run(*s)
    t1 = time.perf_counter()
    jax.block_until_ready(s)
    t2 = time.perf_counter()
    ready = all(a.is_ready() for a in s)
    igg.sync(s)
    t3 = time.perf_counter()
    return {"steps": chunk * calls, "enqueue_s": t1 - t0,
            "block_until_ready_s": t2 - t0, "is_ready_after": ready,
            "sync_after_s": t3 - t2}


def smoke_phase(model, n, periodic, nt, chunk, devices):
    """Library tier vs XLA tier (and NumPy f64 for diffusion) for one
    model. Returns the phase's informational record; raises on failure.
    On TPU devices the library's tier must be ``pallas``."""
    t0 = time.perf_counter()
    c0, h0 = _compile["seconds"], _compile["cache_hits"]
    npy = NT_NUMPY if model == "diffusion3d" else 0
    multi = len(devices) > 1
    lib = run_tier(model, n, periodic, nt, chunk, devices, numpy_steps=npy,
                   placement=multi)
    if devices[0].platform == "tpu" and (lib["tier"] != "pallas"
                           or not lib["kernels"]["tpu_custom_call"]):
        raise SmokeFailure(f"{model}: the library ran tier {lib['tier']!r} "
                           f"with kernels {lib['kernels']}; a TPU grid "
                           "must run the pallas tier")
    if multi and not lib["kernels"]["collective_permute"]:
        raise SmokeFailure(f"{model}: no collective-permute in the "
                           f"{len(devices)}-chip step")
    xla = run_tier(model, n, periodic, nt, chunk, devices, impl="xla",
                   numpy_steps=npy)
    rec = {"phase": model, "local_n": n, "chips": len(devices),
           "periodic": periodic, "tier": lib["tier"],
           "kernels": lib["kernels"], "steps": lib["steps"],
           "err_vs_xla": rel_err(lib["final"], xla["final"]),
           "tol_vs_xla": TOL_TIER}
    if npy:
        rec["err_vs_xla_early"] = rel_err(lib["early"], xla["early"])
        p = lib["params"]
        ref = numpy_diffusion(lib["initial"]["T"], lib["initial"]["Cp"],
                              p.lam, p.dt, (p.dx, p.dy, p.dz), npy)
        rec["err_vs_numpy"] = rel_err({"T": lib["early"]["T"]}, {"T": ref})
        rec["err_xla_vs_numpy"] = rel_err({"T": xla["early"]["T"]},
                                          {"T": ref})
        rec["numpy_steps"] = npy
        rec["tol_vs_numpy"] = TOL_NUMPY
    if lib["placement"] is not None:
        rec["placement"] = lib["placement"]
    if lib.get("block_until_ready") is not None:
        rec["block_until_ready"] = lib["block_until_ready"]
    rec["compile_s"] = _compile["seconds"] - c0
    rec["compile_cache_hits"] = _compile["cache_hits"] - h0
    rec["phase_s"] = time.perf_counter() - t0
    rec["peak_bytes_in_use"] = max(_mem(devices, "peak_bytes_in_use")
                                   .values())
    print(json.dumps(rec), flush=True)
    failed = [k for k in ("err_vs_xla", "err_vs_xla_early")
              if rec.get(k, 0.0) > TOL_TIER]
    failed += [k for k in ("err_vs_numpy", "err_xla_vs_numpy")
               if rec.get(k, 0.0) > TOL_NUMPY]
    if failed:
        raise SmokeFailure(f"{model}: {failed} over tolerance: "
                           + ", ".join(f"{k}={rec[k]:.3e}" for k in failed))
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    from implicitglobalgrid_tpu.utils.compile_cache import use_compile_cache

    all_devices = jax.devices()
    devices = check_tpu(all_devices, args.chips)
    cache = use_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    print(json.dumps({"compile_cache": cache,
                      "devices": [str(d) for d in devices]}), flush=True)
    for phase in (PHASES_4 if args.chips == 4 else PHASES_1):
        smoke_phase(*phase, devices=devices)
    print(json.dumps({"ok": True, "device": {
        "platform": all_devices[0].platform,
        "kind": all_devices[0].device_kind,
        "count": len(all_devices)}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception as e:  # any failure: nonzero, and no "ok" line
        import traceback

        traceback.print_exc()
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        sys.exit(1)
