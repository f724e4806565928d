"""3-D heat diffusion on the implicit global grid — port of the reference's
canonical example (`/root/reference/examples/diffusion3D_multicpu_novis.jl` /
`diffusion3D_multigpu_CuArrays_novis.jl`).

One code runs on any mesh: CPU (emulated multi-device), one TPU chip, or a
TPU pod — the device count/topology comes from `init_global_grid` exactly like
the reference's "3 lines to go distributed" UX (`reference README.md:29-33`).

Run:  python examples/diffusion3D_multixpu_novis.py [--cpu]
"""

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

if "--cpu" in sys.argv:
    import os

    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")

import jax
import jax.numpy as jnp

import implicitglobalgrid_tpu as igg
from implicitglobalgrid_tpu.models import init_diffusion3d, run_diffusion


def diffusion3D():
    # Physics & numerics (reference example :13-24)
    nx, ny, nz = (64, 64, 64) if "--cpu" in sys.argv else (256, 256, 256)
    nt = 100 if "--cpu" in sys.argv else 1000
    me, dims, nprocs, coords, mesh = igg.init_global_grid(nx, ny, nz)

    # ICs: two Gaussian anomalies each for Cp and T (reference :34-38)
    T, Cp, p = init_diffusion3d(lam=1.0, cp_min=1.0, lx=10.0, ly=10.0, lz=10.0,
                                dtype=jnp.float32)

    # Whole time loop as one compiled program per chunk (TPU-first hot loop;
    # replaces the reference's per-step broadcast dispatches :41-48).
    # One-chunk warmup (same chunk size ⇒ same cached program) so tic/toc
    # measures steady state, not XLA compilation. run_diffusion returns only
    # after the work drained (data-dependent sync inside run_chunked).
    chunk = max(1, nt // 10)
    run_diffusion(T, Cp, p, chunk, nt_chunk=chunk)
    if nt % chunk:  # remainder chunk is a second program — warm it too
        run_diffusion(T, Cp, p, nt % chunk, nt_chunk=chunk)
    igg.tic()
    T = run_diffusion(T, Cp, p, nt, nt_chunk=chunk)
    t = igg.toc(sync_on=T)

    cells = igg.nx_g() * igg.ny_g() * igg.nz_g()
    G = igg.gather_interior(T)   # collective in multi-host: every process calls it
    if me == 0:
        print(f"nt={nt} steps on {nprocs} device(s): {t:.3f}s "
              f"({cells * nt / t / 1e9:.2f} G cell-updates/s)")
        print(f"T interior mean: {float(G.mean()):.6f}")

    igg.finalize_global_grid()   # reference :50


if __name__ == "__main__":
    from implicitglobalgrid_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    diffusion3D()
