"""3-D pseudo-transient Stokes flow on the implicit global grid.

The BASELINE weak-scaling workload (config 5): iterate the damped PT
system for a buoyant sphere until the global residuals drop below ``tol``
— the convergence-monitored solver loop of the reference's multi-physics
application family (`reference README.md:6-8`). Demonstrates the
multi-array staggered state, the fused Pallas PT-iteration tier, and
`stokes_residuals` (pmax-reduced over the mesh — the collective the
reference's companion apps compute with MPI reductions).

Run:  python examples/stokes3D_multixpu.py [--cpu]
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

import numpy as np

import implicitglobalgrid_tpu as igg
from implicitglobalgrid_tpu.models import (
    init_stokes3d, run_stokes, stokes_residuals,
)


def stokes3D():
    cpu = "--cpu" in sys.argv
    nx = 24 if cpu else 96
    max_iters, check_every = (300, 100) if cpu else (6000, 500)
    tol = 5e-4
    me, dims, nprocs, coords, mesh = igg.init_global_grid(nx, nx, nx)

    state, p = init_stokes3d(dtype=np.float32)
    # warm the chunk + residual programs (functional: the warm run's
    # advanced state is discarded) so tic/toc measures the solve, not XLA
    # compilation — same pattern as the diffusion/acoustic examples
    stokes_residuals(run_stokes(state, p, check_every,
                                nt_chunk=check_every), p)
    igg.tic()
    it = 0
    err = float("inf")
    while it < max_iters:
        state = run_stokes(state, p, check_every, nt_chunk=check_every)
        it += check_every
        err_div, err_mom = stokes_residuals(state, p)
        err = max(err_div, err_mom)
        if me == 0:
            print(f"iters={it:6d}  max|divV|={err_div:.3e}  "
                  f"max|R|={err_mom:.3e}")
        if err < tol:
            break
    t = igg.toc(sync_on=state[0])

    P = igg.gather_interior(state[0])
    if me == 0:
        status = "converged" if err < tol else "max-iters"
        print(f"{status} after {it} PT iterations in {t:.2f}s "
              f"({igg.nx_g()}x{igg.ny_g()}x{igg.nz_g()} global, "
              f"{nprocs} device(s)); P range [{float(P.min()):+.3e}, "
              f"{float(P.max()):+.3e}]")

    igg.finalize_global_grid()


if __name__ == "__main__":
    from implicitglobalgrid_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    stokes3D()
