"""Machine calibration: short measured runs -> a `MachineProfile`.

The cost model (`telemetry.perfmodel`) is only as good as its
coefficients, and spec sheets lie about achieved rates — on the emulated
CPU mesh the 8 "devices" share one host's cores, on a real pod the
achieved HBM stream rate sits well under the headline number. So the
profile is MEASURED, with the same machinery the standalone benches use
(`bench_membw.py`'s fused triad, `bench_halo.py`'s exchange shape),
scaled down to milliseconds of timed windows (the wall clock is
compile-dominated):

- ``membw_GBps`` — a fused elementwise triad (2 reads + 1 write) over a
  SHARDED array spanning the live mesh, so every device streams
  concurrently and the per-device rate includes real contention;
- ``flops_G`` — a chain of 3-point shifted-add stencil updates over a
  small sharded array (many FLOPs per byte: the compute roofline, not
  the memory one — and slice-heavy like the real steps, so the rate is
  what stencil code achieves, not peak FMA);
- per-axis ``{"GBps", "latency_s"}`` — a forward+backward ppermute pair
  (exactly the halo exchange's wire shape) along each multi-shard mesh
  axis, timed at a small and a large payload: the two-point fit
  ``t(S) = latency + S / bw`` separates the per-collective launch cost
  from the streaming rate per link.

All measurements use the two-window slope idiom of `bench_util.two_point`
(both windows pay identical fixed costs; the slope is the pure per-call
time), re-implemented here because the package cannot depend on the
repo-root bench scripts. `calibrate_machine` needs an initialized grid
(the mesh IS the machine being profiled) and returns/persists a
`MachineProfile` with ``source="calibrated"``.

CLI: ``python -m implicitglobalgrid_tpu.tools calibrate --out profile.json``
(``--cpu`` profiles the 8-device virtual CPU mesh).
"""

from __future__ import annotations

import time

from ..utils.exceptions import InvalidArgumentError
from .perfmodel import MachineProfile, save_machine_profile

__all__ = ["calibrate_machine"]


def _two_point(run_chunk, c1: int, c2: int, reps: int = 3) -> float:
    """Steady-state seconds/iteration via two warmed one-call windows
    (the `bench_util.two_point` idiom; wall-clock timer, caller drains).
    Min-of-``reps`` per window: calibration runs on a live (possibly
    shared) host, and the minimum is the least-contended estimate — the
    timed windows are milliseconds next to the per-shape compiles, so
    extra reps are nearly free."""
    run_chunk(c1)
    run_chunk(c2)

    def timed(c):
        t0 = time.perf_counter()
        run_chunk(c)
        return time.perf_counter() - t0

    t1 = min(timed(c1) for _ in range(reps))
    t2 = min(timed(c2) for _ in range(reps))
    if t2 <= t1:  # timer jitter: fall back to the inclusive rate
        return t2 / c2
    return (t2 - t1) / (c2 - c1)


def _sharded_ones(gg, elems_per_device: int, dtype):
    """A stacked array spanning the live mesh with ~``elems_per_device``
    elements per shard (every device streams concurrently during the
    calibration loops)."""
    import jax.numpy as jnp

    from ..ops.alloc import device_put_g

    dims = [int(d) for d in gg.dims]
    # local block (m, m, m) with m^3 ~ elems_per_device, kept modest
    m = max(8, int(round(elems_per_device ** (1.0 / 3.0))))
    shape = tuple(d * m for d in dims)
    return device_put_g(jnp.ones(shape, dtype=dtype)), m ** 3


def _measure_membw_gbps(gg, elems_per_device: int, c1: int) -> float:
    """Per-device achieved triad bandwidth (2R + 1W) over the live mesh."""
    import jax
    import jax.numpy as jnp

    a, local_elems = _sharded_ones(gg, elems_per_device, jnp.float32)
    b, _ = _sharded_ones(gg, elems_per_device, jnp.float32)

    @jax.jit
    def chunk(a, b, c):
        # carry keeps b in place (a swapped carry pays a hidden copy)
        def body(_, ab):
            a, b = ab
            return (b * 1.0001 + a * 0.5, b)
        return jax.lax.fori_loop(0, c, body, (a, b))

    s = _two_point(lambda c: jax.block_until_ready(chunk(a, b, c)),
                   c1, 3 * c1)
    return 3 * 4 * local_elems / s / 1e9


def _measure_flops_g(gg, elems_per_device: int, c1: int,
                     fma_per_iter: int = 64) -> float:
    """Per-device achieved FMA rate (many FLOPs per byte: the compute
    roofline, not a second bandwidth measurement). Measured against the
    fused stencil steps this prices, XLA's elementwise fusion brings the
    real kernels within ~10-20% of this chain (verified in the
    decomposition behind the bench_perf model-ratio rows), so no
    separate stencil-efficiency fudge factor is carried."""
    import jax
    import jax.numpy as jnp

    a, local_elems = _sharded_ones(gg, elems_per_device // 8, jnp.float32)

    @jax.jit
    def chunk(a, c):
        def body(_, x):
            for _ in range(fma_per_iter):
                x = x * 1.000001 + 1e-9
            return x
        return jax.lax.fori_loop(0, c, body, a)

    s = _two_point(lambda c: jax.block_until_ready(chunk(a, c)), c1, 3 * c1)
    return 2 * fma_per_iter * local_elems / s / 1e9


def _measure_axis_link(gg, dim: int, small_bytes: int, large_bytes: int,
                       c1: int) -> dict:
    """One mesh axis's effective link coefficients from the REAL exchange
    (`local_update_halo(x, dims=(dim,))` inside a compiled loop — the
    exact pack + ppermute pair + select + unpack the steps pay, which a
    bare ppermute ring under-prices by several x): timed at two slab
    payload sizes -> ``t_exchange(S) = latency_s + S / GBps``. The field
    is THIN along the measured axis (slab bytes scale with the
    cross-section, array size stays small) so the large payload stays
    cheap to allocate."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..ops.fields import field_partition_spec
    from ..ops.halo import local_update_halo
    from jax import shard_map

    hw = max(1, int(gg.halowidths[dim]))

    def exchange_time(nbytes: int) -> float:
        # the measured axis keeps the grid's own local extent (a size
        # mismatch there would read as a staggered field and shift the
        # overlap, see `ol`); the cross-section dims are free and set the
        # one-direction slab payload = mm^2 * hw * 4 bytes
        mm = max(8, int(round((nbytes / (hw * 4)) ** 0.5)))
        local = [mm] * 3
        local[dim] = int(gg.nxyz[dim])
        stacked = tuple(l * int(d) for l, d in zip(local, gg.dims))
        x = jnp.ones(stacked, jnp.float32)
        spec = field_partition_spec(3)

        def body(x, c):
            def one(_, x):
                return local_update_halo(x, dims=(dim,))
            return jax.lax.fori_loop(0, c[0], one, x)

        # check_vma off: the traced while-loop trip count has no
        # replication rule under the variance checker
        fn = jax.jit(shard_map(body, mesh=gg.mesh, in_specs=(spec, P()),
                               out_specs=spec, check_vma=False))

        def run_chunk(c):
            jax.block_until_ready(fn(x, jnp.asarray([c], jnp.int32)))

        actual = mm * mm * hw * 4
        return _two_point(run_chunk, c1, 3 * c1), actual

    t_small, s_small = exchange_time(small_bytes)
    t_large, s_large = exchange_time(large_bytes)
    if t_large > t_small and s_large > s_small:
        bw = (s_large - s_small) / (t_large - t_small)
        lat = max(0.0, t_small - s_small / bw)
    else:  # jitter collapse: charge everything to bandwidth
        bw = s_large / t_large
        lat = 0.0
    return {"GBps": bw / 1e9, "latency_s": lat}


def calibrate_machine(path=None, *, elems_per_device: int = 1 << 18,
                      link_bytes=(1 << 13, 1 << 20), c1: int = 4,
                      ensemble: int | None = None,
                      profile_meta: dict | None = None) -> MachineProfile:
    """Measure this mesh's machine profile (milliseconds of measured
    windows; wall clock is dominated by the handful of per-shape XLA
    compiles the micro-kernels pay).

    Needs an initialized grid — the live `jax.sharding.Mesh` IS the
    machine being profiled (per-device rates include any device-sharing
    contention; per-axis links are measured along the actual mesh axes).
    ``elems_per_device`` sizes the bandwidth/FLOPs arrays;
    ``link_bytes=(small, large)`` are the two payloads of the per-axis
    two-point link fit; ``c1`` is the small window's iteration count.
    Axes with a single non-periodic shard carry no wire and are profiled
    as the mean of the measured axes when the model asks.

    ``ensemble=E`` calibrates the link fit in the E-member payload
    regime (ISSUE 12): the two fitted payload sizes scale by E — the
    batched exchange ships E x the slab bytes behind the same ppermute
    pair, so an ensemble-sized fit measures the bandwidth plateau those
    payloads actually ride instead of extrapolating from solo slabs. The
    member count is recorded in the profile's ``meta``.

    With ``path``, the profile is also persisted as JSON
    (`save_machine_profile` / `load_machine_profile`). Returns the
    `MachineProfile` (``source="calibrated"``)."""
    from ..parallel.topology import check_initialized, global_grid

    check_initialized()
    gg = global_grid()
    if len(link_bytes) != 2 or link_bytes[0] >= link_bytes[1]:
        raise InvalidArgumentError(
            f"calibrate_machine: link_bytes must be (small, large) with "
            f"small < large; got {tuple(link_bytes)}.")
    if ensemble is not None:
        E = int(ensemble)
        if E < 1:
            raise InvalidArgumentError(
                f"calibrate_machine: ensemble must be >= 1; got "
                f"{ensemble}.")
        link_bytes = (int(link_bytes[0]) * E, int(link_bytes[1]) * E)
        profile_meta = dict(profile_meta or {}, ensemble=E)

    t0 = time.time()
    membw = _measure_membw_gbps(gg, elems_per_device, c1)
    flops = _measure_flops_g(gg, elems_per_device, c1)
    axes = {}
    from ..parallel.topology import AXIS_NAMES

    for dim in range(3):
        D = int(gg.dims[dim])
        if D <= 1:
            continue  # no inter-shard link along this axis
        axes[AXIS_NAMES[dim]] = _measure_axis_link(
            gg, dim, int(link_bytes[0]), int(link_bytes[1]), c1)

    device = {"platform": gg.device_type,
              "dims": [int(d) for d in gg.dims],
              "n_shards": int(gg.nprocs)}
    try:
        import jax

        d0 = jax.devices()[0]
        device["device_kind"] = d0.device_kind
    except Exception:
        pass
    profile = MachineProfile(
        membw_GBps=membw, flops_G=flops, axes=axes, source="calibrated",
        device=device, calibrated_at=t0,
        meta={**(profile_meta or {}),
              "elems_per_device": int(elems_per_device),
              "link_bytes": [int(b) for b in link_bytes],
              "calibrate_s": time.time() - t0})
    if path is not None:
        save_machine_profile(profile, path)
    return profile
