"""3-D staggered-grid acoustic wave on the implicit global grid.

The BASELINE weak-scaling workload (config 4): leapfrog pressure/velocity
updates on a staggered grid — the model family the reference's companion
ParallelStencil miniapps cover (`reference README.md:10` cites the same
multi-physics app family). Demonstrates staggered fields (Vx is
``(nx+1, ny, nz)``), the fused Pallas step+exchange tier, and the
`hide_communication` overlap option of the XLA tier.

Run:  python examples/acoustic3D_multixpu.py [--cpu] [--xla]
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
from implicitglobalgrid_tpu.models import init_acoustic3d, run_acoustic


def acoustic3D():
    cpu = "--cpu" in sys.argv
    nx = 32 if cpu else 192
    nt = 60 if cpu else 600
    impl = "xla" if "--xla" in sys.argv else None  # None -> kernel tier on TPU
    me, dims, nprocs, coords, mesh = igg.init_global_grid(
        nx, nx, nx, periodx=1, periody=1, periodz=1)

    # Gaussian pressure pulse at the domain center; velocities at rest.
    state, p = init_acoustic3d(dtype=np.float32, overlap=impl == "xla")

    chunk = max(1, nt // 10)
    run_acoustic(state, p, chunk, nt_chunk=chunk, impl=impl)  # warm
    igg.tic()
    state = run_acoustic(state, p, nt, nt_chunk=chunk, impl=impl)
    t = igg.toc(sync_on=state[0])

    P = igg.gather_interior(state[0])
    cells = igg.nx_g() * igg.ny_g() * igg.nz_g()
    if me == 0:
        print(f"nt={nt} steps on {nprocs} device(s): {t:.3f}s "
              f"({cells * nt / t / 1e9:.2f} G cell-updates/s)")
        print(f"P interior: mean {float(P.mean()):+.3e}  "
              f"max |P| {float(np.abs(P).max()):.3e}")

    igg.finalize_global_grid()


if __name__ == "__main__":
    from implicitglobalgrid_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    acoustic3D()
