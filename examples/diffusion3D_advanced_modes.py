"""The TPU-native modes the reference cannot express, in one script:

1. STOCHASTIC-ROUNDING bf16 storage (`sr=True`): half the HBM traffic of
   f32 with an unbiased store, so long runs track the f32 trajectory
   instead of stagnating (F64_ACCURACY.json: 9.8e-3 vs 0.85 max-rel).
2. COMMUNICATION-AVOIDING deep halos (`comm_every=2`): a 2-wide exchange
   every 2 steps — same wire bytes, half the collectives, bit-identical
   trajectory (tests/test_comm_avoid.py; COMM_AVOID.json).
3. MEASURED overlap: `igg.trace` + `igg.overlap_stats` turn the
   comm/compute schedule into numbers on any backend.

Run:  python examples/diffusion3D_advanced_modes.py [--cpu]
"""

import pathlib
import sys
import tempfile

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

if "--cpu" in sys.argv:
    import os

    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")

import jax
import jax.numpy as jnp
import numpy as np

import implicitglobalgrid_tpu as igg
from implicitglobalgrid_tpu.models import init_diffusion3d, run_diffusion


def main():
    cpu = "--cpu" in sys.argv
    nx = 32 if cpu else 192
    nt = 40 if cpu else 400

    # --- 1. stochastic-rounding bf16 vs plain bf16 vs f32 ----------------
    finals = {}
    for tag, dtype, sr in (("f32", jnp.float32, False),
                           ("bf16", jnp.bfloat16, False),
                           ("bf16_sr", jnp.bfloat16, True)):
        igg.init_global_grid(nx, nx, nx, quiet=True)
        T, Cp, p = init_diffusion3d(dtype=dtype, sr=sr)
        out = run_diffusion(T, Cp, p, nt, nt_chunk=nt,
                            impl="xla" if not sr else None)
        g = igg.gather_interior(out)
        finals[tag] = (np.asarray(g).astype(np.float64)
                       if g is not None else None)
        igg.finalize_global_grid()
    if finals["f32"] is not None:
        scale = np.abs(finals["f32"]).max()
        for tag in ("bf16", "bf16_sr"):
            err = np.abs(finals[tag] - finals["f32"]).max() / scale
            print(f"{tag:8s} vs f32 after {nt} steps: max_rel={err:.2e}")

    # --- 2. deep halos: half the collectives, identical numbers ----------
    # (grid with 2-wide halos; nt must be a multiple of comm_every)
    igg.init_global_grid(nx + 2, nx + 2, nx + 2,
                         overlaps=(4, 4, 4), halowidths=(2, 2, 2),
                         periodx=1, periody=1, periodz=1, quiet=True)
    T, Cp, p = init_diffusion3d(dtype=jnp.float32, comm_every=2)
    igg.tic()
    out = run_diffusion(T, Cp, p, nt, nt_chunk=nt)
    t = igg.toc(sync_on=out)
    print(f"comm_every=2: {nt} steps in {t:.3f}s "
          f"({nt // 2} exchanges instead of {nt})")
    igg.finalize_global_grid()

    # --- 3. measured overlap of the standard schedule --------------------
    igg.init_global_grid(nx, nx, nx, periodx=1, periody=1, periodz=1,
                         quiet=True)
    T, Cp, p = init_diffusion3d(dtype=jnp.float32, overlap=True)
    run_diffusion(T, Cp, p, 8, nt_chunk=8, impl="xla")     # warm
    with tempfile.TemporaryDirectory() as d:
        with igg.trace(d):
            igg.sync(run_diffusion(T, Cp, p, 8, nt_chunk=8, impl="xla"))
        stats = igg.overlap_stats(d)
    for dev, s in sorted(stats.items()):
        frac = s["overlap_frac"]
        print(f"overlap[{dev}]: hidden "
              f"{s['hidden_comm_us']:.0f}us / {s['comm_us']:.0f}us comm "
              f"({'n/a' if frac is None else f'{100 * frac:.0f}%'})")
    igg.finalize_global_grid()


if __name__ == "__main__":
    from implicitglobalgrid_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    main()
