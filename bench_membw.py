"""Memory-bandwidth microbenchmarks backing `docs/performance.md`'s
`update_halo` ceiling analysis (round-3 verdict: that analysis cited
in-session v5e numbers — ~294 GB/s Pallas copy bound, ~169 GB/s strided
edge-tile RMW — with no committed measurement behind them).

Rows (one JSON line each + a summary line):

- ``xla_triad_GBps``: fused XLA elementwise (2 reads + 1 write) — the
  practical HBM ceiling (same quantity as `bench.py`'s in-run
  calibration).
- ``pallas_copy_GBps``: a bare BlockSpec-pipelined Pallas read+write pass
  (1 read + 1 write) — the VMEM-mediated copy bound every delivery
  kernel is subject to.
- ``edge_rmw_GBps``: in-place RMW of the dim-2 (lane-edge) halo tiles via
  `pallas_halo.halo_write_inplace` — the strided-tile alternative the
  combined one-pass kernel beats (array-traffic convention: bytes moved
  = the touched lane tiles, 2 * 512-lane-tile columns).
- ``combined_unpack_GBps``: `halo_write_combined_pallas` delivering all
  six received slabs in ~2 full array passes (array-traffic convention:
  2 passes over the block).

Usage: python bench_membw.py          (real chip, 512^3 f32)
       python bench_membw.py --cpu    (small smoke run, virtual mesh)
"""

from __future__ import annotations

import sys

import bench_util


def main() -> None:
    cpu = "--cpu" in sys.argv
    if cpu:
        import os

        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8").strip()
        import jax

        jax.config.update("jax_platforms", "cpu")
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from implicitglobalgrid_tpu.ops import pallas_halo as ph

    n = 64 if cpu else 512
    interpret = cpu
    A = jnp.asarray(np.random.default_rng(0)
                    .standard_normal((n, n, n)).astype(np.float32))
    nbytes = A.size * 4
    rows = []

    def wall_timer(fn):
        t0 = time.perf_counter()
        fn()
        return time.perf_counter() - t0

    def rate(name, make_chunk, bytes_per_iter, note=None):
        c1 = 2 if cpu else 8
        s = bench_util.two_point(make_chunk, c1, 3 * c1, timer=wall_timer)
        row = bench_util.emit({
            "metric": name, "value": bytes_per_iter / s / 1e9,
            "unit": "GB/s", "note": note,
            "method": bench_util.two_point.last["method"],
        })
        rows.append(row)

    # --- XLA fused triad: 2 reads + 1 write (shared calibration) ---------
    rows.append(bench_util.emit({
        "metric": "xla_triad_GBps",
        "value": bench_util.measure_triad_gbps(A.size),
        "unit": "GB/s",
        "note": "fused elementwise, 2R+1W — practical HBM ceiling (same "
                "helper as bench.py's hbm_triad_GBps)",
        "method": bench_util.two_point.last["method"],
    }))

    # --- bare Pallas copy pass: 1 read + 1 write -------------------------
    from jax.experimental import pallas as pl

    def copy_kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    # 2-plane blocks: Pallas double-buffers in+out (4 buffers), so at
    # 512^3 f32 this keeps the VMEM working set at ~8 MiB (an (8,n,n)
    # block would need 32 MiB and fail Mosaic allocation)
    blk = (2, n, n)

    def copy_once(x):
        return pl.pallas_call(
            copy_kernel,
            grid=(n // blk[0],),
            in_specs=[pl.BlockSpec(blk, lambda i: (i, 0, 0))],
            out_specs=pl.BlockSpec(blk, lambda i: (i, 0, 0)),
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=interpret,
        )(x)

    @jax.jit
    def copy_loop(a, c):
        return jax.lax.fori_loop(0, c, lambda _, x: copy_once(x), a)

    rate("pallas_copy_GBps",
         lambda c: jax.block_until_ready(copy_loop(A, c)), 2 * nbytes,
         "BlockSpec-pipelined read+write pass — the VMEM copy bound")

    # --- dim-2 (lane-edge) strided tile RMW ------------------------------
    # The HYPOTHETICAL dim-2 delivery the framework deliberately does NOT
    # use (pallas_halo has no dim-2 kernel — this access pattern measured
    # slower than the combined one-pass unpack; docs/performance.md).
    # Measurement-only kernel: per x-plane, read+write the two edge
    # lane-tile columns in place, replacing the halo lane.
    from jax import lax

    lane_tile = min(128, n // 2)  # n//2 keeps the two edge tiles distinct
    n_lt = n // lane_tile

    def rmw_kernel(x_ref, o_ref):
        s = pl.program_id(1)
        row = x_ref[0]                          # (n, lane_tile)
        col = lax.broadcasted_iota(jnp.int32, row.shape, 1)
        halo_lane = jnp.where(s == 0, 0, lane_tile - 1)
        o_ref[0] = jnp.where(col == halo_lane, jnp.float32(0.123), row)

    def rmw_once(x):
        edge = pl.BlockSpec((1, n, lane_tile),
                            lambda i, s: (i, 0, s * (n_lt - 1)))
        return pl.pallas_call(
            rmw_kernel,
            grid=(n, 2),
            in_specs=[edge],
            out_specs=edge,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            input_output_aliases={0: 0},
            interpret=interpret,
        )(x)

    @jax.jit
    def rmw_loop(a, c):
        return jax.lax.fori_loop(0, c, lambda _, x: rmw_once(x), a)

    tile_bytes = 2 * (n * n * lane_tile * 4) * 2    # 2 sides, R+W
    rate("edge_rmw_GBps",
         lambda c: jax.block_until_ready(rmw_loop(A, c)), tile_bytes,
         f"strided in-place RMW of the 2 edge {lane_tile}-lane tile "
         "columns (the dim-2 delivery alternative the combined one-pass "
         "kernel replaces)")

    # --- combined one-pass unpack (all six slabs) ------------------------
    recvs = {
        0: (jnp.zeros((1, n, n), np.float32),) * 2,
        1: (jnp.zeros((n, 1, n), np.float32),) * 2,
        2: (jnp.zeros((n, n, 1), np.float32),) * 2,
    }

    @jax.jit
    def unpack_loop(a, c):
        def body(_, x):
            return ph.halo_write_combined_pallas(
                x, recvs, modes=(True, True, True), hws=(1, 1, 1),
                interpret=interpret)
        return jax.lax.fori_loop(0, c, body, a)

    rate("combined_unpack_GBps",
         lambda c: jax.block_until_ready(unpack_loop(A, c)), 2 * nbytes,
         "all six received slabs in one delivery pass; traffic = 2 "
         "array passes")

    bench_util.emit({
        "metric": "membw_suite", "value": float(len(rows)),
        "unit": "rows", "rows": [r["metric"] for r in rows],
        "block": [n, n, n],
    })


if __name__ == "__main__":
    bench_util.run(main, "membw_suite", "rows")
