"""Benchmark: `update_halo` effective GB/s per chip.

First metric of BASELINE.json ("update_halo! effective GB/s/chip"); the
reference claims "halo updates close to hardware limit" qualitatively
(`reference README.md:10,30`) with no number published.

Accounting (effective-bandwidth convention): per exchanged dimension, each
chip sends 2 slabs and receives 2 slabs of ``hw x plane`` cells, i.e.
``bytes/call = sum_dims 4 * hw * plane_cells * itemsize``. Periodic on all
dims so every chip exchanges on every side (single chip: the self-neighbor
local-copy path, the reference's 1-process test technique).

The timed region runs the exchanges INSIDE one compiled program
(`lax.fori_loop` of `local_update_halo` under `shard_map`) — how the
framework actually uses halo exchange in a hot loop — so per-dispatch host
latency is excluded, exactly like the reference measures `update_halo!`
inside its running time loop.

Prints ONE JSON line.

Usage: python bench_halo.py          (real chip, f32, 512^3 local)
       python bench_halo.py --cpu    (small smoke run on virtual CPU mesh)
"""

from __future__ import annotations

import sys

import bench_util


def _pack_roundtrip_step(gg):
    """A `local_update_halo`-shaped program with the ppermutes REPLACED BY
    IDENTITY: per dim, the same canonical-schema pack -> unpack -> deliver
    pipeline the coalesced exchange runs (`ops.wire`), minus the wire.
    Timing it attributes the coalesced exchange's cost between pack/unpack
    work and the collectives themselves — the attribution the perfdb gate
    watches so a future PACK-bound regression (the 0.75x 8-field episode
    this PR fixes) is caught as `update_halo_pack_frac_*` drift, not by
    eyeballing BENCH_ALL."""
    from jax import lax

    from implicitglobalgrid_tpu.ops.halo import (
        DEFAULT_DIMS_ORDER, _check_slab_fit, _dim_meta,
    )
    from implicitglobalgrid_tpu.ops.wire import slab_schema

    def step(arrays):
        arrays = list(arrays)
        for dim in DEFAULT_DIMS_ORDER:
            D, periodic, disp = _dim_meta(gg, dim)
            if D == 1:
                # mirror `_coalesce_groups`: self-neighbor axes are
                # per-field local swaps with NO pack on the live path —
                # packing them here would overstate pack_frac on meshes
                # with singleton axes
                continue
            sends_r, sends_l, metas = [], [], []
            for a in arrays:
                hw = int(gg.halowidths[dim])
                s = a.shape[dim]
                ol_d = int(gg.overlaps[dim] + (s - gg.nxyz[dim]))
                _check_slab_fit(s, dim, ol_d, hw)
                sends_r.append(lax.slice_in_dim(a, s - ol_d, s - ol_d + hw,
                                                axis=dim))
                sends_l.append(lax.slice_in_dim(a, ol_d - hw, ol_d,
                                                axis=dim))
                metas.append((hw, s))
            schema = slab_schema(dim, [x.shape for x in sends_r],
                                 arrays[0].dtype)
            recv_l = schema.unpack(schema.pack(sends_r))  # wire = identity
            recv_r = schema.unpack(schema.pack(sends_l))
            for k, a in enumerate(arrays):
                hw, s = metas[k]
                a = lax.dynamic_update_slice_in_dim(a, recv_l[k], 0,
                                                    axis=dim)
                arrays[k] = lax.dynamic_update_slice_in_dim(
                    a, recv_r[k], s - hw, axis=dim)
        return tuple(arrays)

    return step


def coalescing_ab_rows(nx: int, c1: int, field_counts=(2, 4, 8, 16),
                       dtype=None):
    """A/B + attribution rows for the coalesced multi-field exchange.

    For each field count N, times the N-field `local_update_halo` hot loop
    with collective coalescing ON (one ppermute pair per axis) and OFF
    (2·N permutes per axis) on the CURRENT grid, plus the PACK-ROUNDTRIP
    program (same schema pack/unpack/deliver, identity wire). Returns two
    rows per N: the A/B ``update_halo_coalesced_speedup_{N}fields``
    (value = per_field_s / coalesced_s, >1 means coalescing wins) and the
    attribution ``update_halo_pack_frac_{N}fields`` (value = pack-roundtrip
    share of the coalesced call — the perfdb gate flags it rising, i.e. a
    pack-bound regression, independent of scheduler noise in the A/B).
    Caller owns grid init/finalize."""
    import numpy as np

    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.models.common import make_state_runner

    dtype = dtype or np.float32
    gg = igg.global_grid()
    rows = []
    for n_fields in field_counts:
        fields = tuple(igg.ones_g((nx, nx, nx), dtype) * (i + 1)
                       for i in range(n_fields))
        secs = {}
        pack_step = _pack_roundtrip_step(gg)
        modes = (("coalesced", True), ("per_field", False),
                 ("pack_roundtrip", None))
        for mode, co in modes:
            if co is None:
                def step(s):
                    return pack_step(s)
            else:
                def step(s, co=co):
                    out = igg.local_update_halo(*s, coalesce=co)
                    return out if isinstance(out, tuple) else (out,)

            def chunk(c):
                run = make_state_runner(
                    step, (3,) * n_fields, nt_chunk=c,
                    key=("bench_halo_ab", mode, n_fields, nx, str(dtype)))
                igg.sync(run(*fields))

            # reps=4 (min-kept): the contended shared-core mesh injects
            # scheduler spikes into individual windows; the min over four
            # is the same contention-robust estimator `calibrate_machine`
            # uses, and the A/B ratio is only meaningful between two
            # uncontended draws
            secs[mode] = bench_util.two_point(chunk, c1, 3 * c1, reps=4)
        rows.append({
            "metric": f"update_halo_coalesced_speedup_{n_fields}fields",
            "value": secs["per_field"] / secs["coalesced"],
            "unit": "x (per_field_s / coalesced_s)",
            "coalesced_s_per_call": secs["coalesced"],
            "per_field_s_per_call": secs["per_field"],
        })
        rows.append({
            "metric": f"update_halo_pack_frac_{n_fields}fields",
            "value": secs["pack_roundtrip"] / secs["coalesced"],
            "unit": "frac (pack+unpack+deliver share of coalesced call)",
            "pack_roundtrip_s_per_call": secs["pack_roundtrip"],
            "permute_attributed_s_per_call": max(
                0.0, secs["coalesced"] - secs["pack_roundtrip"]),
        })
    return rows


def run_coalescing_ab(dims, cpu: bool):
    """The canonical A/B leg: init its own all-periodic grid over ``dims``,
    measure, finalize, return the rows. Shared by this script's __main__
    and `bench_all.py` so the config stays in ONE place."""
    import implicitglobalgrid_tpu as igg

    # c_ab=8 (was 4): the A/B slope at 32^3 is dispatch-overhead-bound and
    # the contended shared-core mesh swings short chunks by tens of
    # percent — longer two-point chunks cut the draw-to-draw scatter
    nx_ab, c_ab = (32, 8) if cpu else (256, 20)
    igg.init_global_grid(nx_ab, nx_ab, nx_ab, dimx=dims[0], dimy=dims[1],
                         dimz=dims[2], periodx=1, periody=1, periodz=1,
                         quiet=True)
    try:
        return coalescing_ab_rows(nx_ab, c_ab)
    finally:
        igg.finalize_global_grid()


def main() -> None:
    cpu = "--cpu" in sys.argv
    if cpu:
        import os

        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
        ).strip()
        import jax

        jax.config.update("jax_platforms", "cpu")
    import jax
    import numpy as np

    import implicitglobalgrid_tpu as igg

    if cpu:
        nx, c1 = 64, 5
        dims = (2, 2, 2)
    else:
        nx, c1 = 512, 60
        nd = len(jax.devices())
        dims = tuple(int(d) for d in igg.dims_create(nd, (0, 0, 0)))

    igg.init_global_grid(nx, nx, nx, dimx=dims[0], dimy=dims[1], dimz=dims[2],
                         periodx=1, periody=1, periodz=1, quiet=True)
    gg = igg.global_grid()
    hw = [int(h) for h in gg.halowidths]
    A = igg.ones_g((nx, nx, nx), np.float32)

    from implicitglobalgrid_tpu.models.common import make_state_runner

    def chunk(c):
        run = make_state_runner(lambda s: (igg.local_update_halo(s[0]),),
                                (3,), nt_chunk=c, key="bench_halo")
        igg.sync(run(A))

    s = bench_util.two_point(chunk, c1, 3 * c1)

    itemsize = 4
    planes = [nx * nx] * 3  # local plane cells per dim (cubic block)
    bytes_per_call = sum(4 * hw[d] * planes[d] * itemsize for d in range(3))
    gbps = bytes_per_call / s / 1e9
    # No published reference number exists (BASELINE.md: qualitative claim
    # only); vs_baseline is vs 1 GB/s/chip as a nominal floor.
    bench_util.emit({
        "metric": "update_halo_effective_GBps_per_chip",
        "value": gbps,
        "unit": "GB/s/chip",
        "vs_baseline": gbps / 1.0,
    })

    igg.finalize_global_grid()

    # Coalesced vs per-field A/B (2/4/8 fields) on its own grid — the
    # multi-field leg `bench_all.py` also records into BENCH_ALL.json.
    for row in run_coalescing_ab(dims, cpu):
        bench_util.emit(row)


if __name__ == "__main__":
    bench_util.run(main, "update_halo_effective_GBps_per_chip", "GB/s/chip")
