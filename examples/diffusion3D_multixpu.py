"""3-D heat diffusion with in-situ visualization — port of the reference's
vis example (`/root/reference/examples/diffusion3D_multigpu_CuArrays.jl`,
pattern documented at `reference README.md:108-168`), rebuilt on the io
pipeline: instead of gathering the halo-stripped field to the root every
``nvis`` steps (the reference's O(global)-through-one-host pattern), the
supervised run writes ASYNC sharded snapshots (`snapshot_every=nvis` —
the step loop never waits on disk, no gather ever), and the frames are
assembled AFTER the run by the lazy reader: one O(plane) `read_global`
box per snapshot, pulling only the z-midplane. An in-situ `Stats`
reducer streams max/mean per chunk so the run is monitorable live
without touching the grid either.

Output: diffusion3D.gif if matplotlib is available, else diffusion3D_frames.npy.

Run:  python examples/diffusion3D_multixpu.py [--cpu]
"""

import pathlib
import shutil
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
from implicitglobalgrid_tpu.models import diffusion_step_local, init_diffusion3d
from implicitglobalgrid_tpu.models.common import resolve_pallas_impl


def diffusion3D():
    cpu = "--cpu" in sys.argv
    nx = 64 if cpu else 128
    nt, nvis = (100, 10) if cpu else (1000, 100)
    me, dims, nprocs, coords, mesh = igg.init_global_grid(nx, nx, nx)

    T, Cp, p = init_diffusion3d(dtype=np.float32)
    impl = resolve_pallas_impl(None)  # the grid's tier: pallas on a TPU

    def step(s):
        return {"T": diffusion_step_local(s["T"], s["Cp"], p, impl),
                "Cp": s["Cp"]}

    # Supervised run with async snapshots every nvis steps (O(shard) per
    # process, committed in the background) and an in-situ stats reducer
    # (rides the health guard's psum — zero extra collectives). The
    # snapshot root must be ONE directory shared by every process (the
    # multi-host commit protocol stages into a common dir on the shared
    # filesystem — same requirement as checkpoint_dir), so it lives at a
    # deterministic path in the working directory, not a per-process
    # tempdir.
    snaps = "diffusion3D_snapshots"
    if me == 0:  # a previous interrupted run's snapshots must not
        shutil.rmtree(snaps, ignore_errors=True)  # interleave into the gif
    state, reports = igg.run_resilient(
        step, {"T": T, "Cp": Cp}, nt, nt_chunk=nvis, key="diffusion3D_vis",
        snapshot_dir=snaps, snapshot_every=nvis, snapshot_fields=("T",),
        reducers=[igg.Stats("T", which=("max", "mean"))],
        on_reduce=lambda s, v: me == 0 and print(
            f"step {s:4d}  max={v['stats:T']['max']:.3f}  "
            f"mean={v['stats:T']['mean']:.4f}"))

    # Analysis side: assemble ONLY the z-midplane of each snapshot — an
    # O(plane) read per frame, never the global volume (host-only numpy;
    # this part would typically run on a separate analysis machine).
    frames = []
    if me == 0:
        zmid = igg.open_snapshot(
            igg.list_snapshots(snaps)[0][1]).global_shape("T")[2] // 2
        for step_n, path in igg.list_snapshots(snaps):
            snap = igg.open_snapshot(path)
            plane = snap.read_global("T", box=(None, None, (zmid, zmid + 1)))
            frames.append(plane[:, :, 0].copy())

    if me == 0:
        try:
            import matplotlib

            matplotlib.use("Agg")
            import matplotlib.animation as anim
            import matplotlib.pyplot as plt

            fig, ax = plt.subplots()
            im = ax.imshow(frames[0].T, origin="lower", cmap="inferno",
                           vmin=0, vmax=max(f.max() for f in frames))

            def update(f):
                im.set_data(f.T)
                return [im]

            a = anim.FuncAnimation(fig, update, frames=frames, blit=True)
            a.save("diffusion3D.gif", writer="pillow", fps=5)
            print("wrote diffusion3D.gif")
        except Exception as e:  # matplotlib/pillow unavailable
            np.save("diffusion3D_frames.npy", np.stack(frames))
            print(f"wrote diffusion3D_frames.npy ({e.__class__.__name__}: no gif)")

    if me == 0:  # all writers drained before run_resilient returned
        shutil.rmtree(snaps, ignore_errors=True)
    igg.finalize_global_grid()


if __name__ == "__main__":
    from implicitglobalgrid_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    diffusion3D()
