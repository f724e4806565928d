"""Measure on-device elastic resharding (ISSUE 14): resize downtime,
HBM-to-HBM vs the checkpoint (disk) path.

An elastic ``dims`` change used to round-trip through disk: live state ->
sharded checkpoint -> `restore_checkpoint_elastic` host reads. The
`reshard` subsystem re-blocks the state as a collective program (ppermute
slice rounds over the live device pool) with no disk in the loop. Two
properties ride the gates:

- ``reshard_vs_disk_speedup`` — the checkpoint path's wall time
  (sharded save + elastic restore, what EVERY disk resize pays) over the
  on-device path's steady-state wall time (the compiled transfer
  program re-dispatched; its one-time XLA compile is recorded
  separately, exactly like a chunk runner's cold compile). ABSOLUTE
  gate >= 1.0 under ``IGG_BENCH_STRICT`` — the autoscaling primitive
  must never lose to the disk it replaces.
- ``reshard_device_resize_s`` / ``reshard_disk_resize_s`` — the two
  downtimes themselves, plus ``reshard_compile_s`` (the one-time cost),
  all riding the perfdb trajectory.

Config owned by `run_reshard_ab` (shared with bench_all.py).

Usage: python bench_reshard.py --cpu   (8-device virtual mesh)
       python bench_reshard.py         (real devices)
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

import bench_util


def _grid(nx, dims, igg):
    igg.init_global_grid(nx, nx, nx, dimx=dims[0], dimy=dims[1],
                         dimz=dims[2], quiet=True)


def run_reshard_ab(dims, cpu: bool):
    """The canonical leg (config in ONE place, shared with bench_all):
    a 4-field f32 state bounced between two decompositions of the same
    implicit global grid — on-device (steady-state: both directions'
    programs warm, the autoscaling regime) vs checkpoint save + elastic
    restore per resize. Sized so the moved-byte volume dominates the
    grid re-init both paths pay (a tiny state would gate on noise)."""
    import numpy as np

    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.reshard import (
        build_reshard_plan, fields_of_state, live_topology, reshard_state,
    )
    from implicitglobalgrid_tpu.telemetry import predict_reshard
    from implicitglobalgrid_tpu.utils.checkpoint import (
        restore_checkpoint_elastic, save_checkpoint_sharded,
    )

    nx = 40 if cpu else 128
    src = tuple(int(d) for d in dims)
    if int(np.prod(src)) == 1:
        return [{
            "metric": "reshard_vs_disk_speedup", "value": None,
            "unit": "disk resize s / on-device resize s",
            "note": "single-device pool: nothing to re-block; row "
                    "skipped",
        }]
    # the destination: rotate the decomposition (same device count —
    # the re-balance move; grow/shrink correctness is tier-1-tested);
    # a cubic mesh rotates onto itself, so fold two axes instead
    dst = (src[2], src[0], src[1])
    if dst == src:
        dst = (src[0] * src[1], src[2], 1)
    reps = 3

    fields = ("T", "Cp", "Vx", "Vy")   # 4-field state: the byte volume
    _grid(nx, src, igg)                # must dominate the shared grid-
    rng = np.random.default_rng(14)    # re-init cost both paths pay
    stacked = tuple(src[d] * nx for d in range(3))
    state = {
        k: igg.device_put_g(rng.normal(size=stacked).astype(np.float32))
        for k in fields
    }
    plan = build_reshard_plan(live_topology(), dst, fields_of_state(state))
    predicted = predict_reshard(plan)

    # --- on-device path: first resize pays the XLA compile, then bounce
    # src <-> dst warm (the steady state an autoscaling service lives in)
    t0 = time.monotonic()
    state, _ = reshard_state(state, dst)
    compile_s = time.monotonic() - t0   # includes the one-time compile
    state, _ = reshard_state(state, src)  # warm the reverse program too
    times = []
    cur, other = src, dst
    for _ in range(2 * reps):
        t0 = time.monotonic()
        state, _ = reshard_state(state, other)
        times.append(time.monotonic() - t0)
        cur, other = other, cur
    device_s = min(times)
    igg.finalize_global_grid()

    # --- disk path: every resize pays save + elastic restore
    disk_times = []
    with tempfile.TemporaryDirectory() as tmp:
        _grid(nx, src, igg)
        state_d = {
            k: igg.device_put_g(
                rng.normal(size=stacked).astype(np.float32))
            for k in fields
        }
        cur, other = src, dst
        for i in range(2 * reps):
            ck = os.path.join(tmp, f"ck{i}")
            t0 = time.monotonic()
            save_checkpoint_sharded(ck, state_d)
            igg.finalize_global_grid()
            from implicitglobalgrid_tpu.utils.checkpoint import (
                elastic_local_size, saved_topology,
            )

            nloc = elastic_local_size(saved_topology(ck), other)
            igg.init_global_grid(nloc[0], nloc[1], nloc[2],
                                 dimx=other[0], dimy=other[1],
                                 dimz=other[2], quiet=True)
            state_d, _ = restore_checkpoint_elastic(ck)
            disk_times.append(time.monotonic() - t0)
            # each checkpoint is read exactly once: drop it so the leg
            # holds ONE checkpoint of temp disk, not 2*reps (at the
            # real-device config that difference is gigabytes)
            import shutil

            shutil.rmtree(ck, ignore_errors=True)
            cur, other = other, cur
        igg.finalize_global_grid()
    disk_s = min(disk_times)

    speedup = disk_s / device_s if device_s > 0 else None
    return [
        {
            "metric": "reshard_vs_disk_speedup",
            "value": speedup,
            "unit": "disk resize s / on-device resize s (>= 1.0: the "
                    "HBM path must never lose to the disk round-trip "
                    "it replaces)",
            "src_dims": list(src), "dst_dims": list(dst), "nx": nx,
            "rounds": plan.rounds, "wire_bytes": plan.wire_bytes,
        },
        {
            "metric": "reshard_device_resize_s",
            "value": device_s,
            "unit": "s wall, warm collective program (min of "
                    f"{2 * reps})",
            "predicted_s": predicted["seconds"],
        },
        {
            "metric": "reshard_disk_resize_s",
            "value": disk_s,
            "unit": "s wall, sharded save + elastic restore (min of "
                    f"{2 * reps})",
        },
        {
            "metric": "reshard_compile_s",
            "value": compile_s,
            "unit": "s wall of the FIRST resize (one-time XLA compile "
                    "of the transfer program, paid once per (plan, "
                    "devices))",
        },
    ]


def main() -> None:
    cpu = "--cpu" in sys.argv
    if cpu:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8").strip()
        import jax

        jax.config.update("jax_platforms", "cpu")
    import jax

    import implicitglobalgrid_tpu as igg

    dims = tuple(int(d) for d in igg.dims_create(len(jax.devices()),
                                                 (0, 0, 0)))
    rows = [bench_util.emit(r) for r in run_reshard_ab(dims, cpu)]
    with open("BENCH_RESHARD.json", "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    bench_util.run(main, "bench_reshard", "suite")
