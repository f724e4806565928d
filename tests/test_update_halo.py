"""Halo-exchange acceptance tests — port of the reference's strategy
(`/root/reference/test/test_update_halo.jl`):

- coordinate-encoding restoration: encode each cell's global coordinates into
  its value, zero the halos, `update_halo`, require exact restoration
  (`test_update_halo.jl:1004-1018`).
- periodic self-neighbor single-shard runs (the reference's "1 process +
  periodic" technique, `test_update_halo.jl:1-3`).
- a numpy ORACLE implementing the reference's exact per-dimension semantics
  (pack all send slabs from pre-exchange values, then deliver — matching
  `update_halo.jl:45-82`), checked against every configuration.
- staggered fields, halowidth>1, multi-field calls, 1-D/2-D grids, dtypes,
  and the `check_fields` error catalog (`update_halo.jl:410-472`).
"""

import numpy as np
import pytest

import implicitglobalgrid_tpu as igg
from implicitglobalgrid_tpu.utils.exceptions import (
    IncoherentArgumentError, InvalidArgumentError,
)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def encode(A):
    """Cell value = x_g + 1e3*y_g + 1e6*z_g (reference encodes z*1e2+y*1e1+x,
    `test_update_halo.jl:1004`)."""
    cs = igg.coords_g(1.0, 1.0, 1.0, A)
    enc = np.zeros(tuple(int(s) for s in A.shape))
    for d, c in enumerate(cs):
        enc = enc + np.asarray(c) * (10.0 ** (3 * d))
    return enc


def zero_halos(P, local_shape, hw_list, dims_sel):
    """Zero the halo slabs of every block along the selected dims."""
    P = P.copy()
    gg = igg.global_grid()
    for d in dims_sel:
        if d >= P.ndim:
            continue
        s = int(local_shape[d])
        hw = int(hw_list[d])
        for c in range(int(gg.dims[d])):
            sl = [slice(None)] * P.ndim
            sl[d] = slice(c * s, c * s + hw)
            P[tuple(sl)] = 0
            sl[d] = slice((c + 1) * s - hw, (c + 1) * s)
            P[tuple(sl)] = 0
    return P


def _blk(c, s, lo, hi):
    return slice(c * s + lo, c * s + hi)


def oracle_update(P, local_shape, hw_list, order):
    """Reference-exact halo exchange on the stacked numpy array: per dim,
    snapshot, then deliver both sides (pack-before-deliver semantics of
    `update_halo.jl:46-48` vs `:72-74`)."""
    gg = igg.global_grid()
    P = P.copy()
    for dim in order:
        if dim >= P.ndim:
            continue
        s = int(local_shape[dim])
        hw = int(hw_list[dim])
        ol_d = int(gg.overlaps[dim]) + (s - int(gg.nxyz[dim]))
        if ol_d < 2 * hw:
            continue
        D = int(gg.dims[dim])
        per = bool(gg.periods[dim])
        disp = int(gg.disp)
        if D == 1 and not per:
            continue
        snap = P.copy()
        for c in range(D):
            ln = (c - disp) % D if per else c - disp
            if ln >= 0:
                src = [slice(None)] * P.ndim
                dst = [slice(None)] * P.ndim
                src[dim] = _blk(ln, s, s - ol_d, s - ol_d + hw)   # right send slab
                dst[dim] = _blk(c, s, 0, hw)                      # left halo
                P[tuple(dst)] = snap[tuple(src)]
            rn = (c + disp) % D if per else (c + disp if c + disp < D else -1)
            if rn >= 0:
                src = [slice(None)] * P.ndim
                dst = [slice(None)] * P.ndim
                src[dim] = _blk(rn, s, ol_d - hw, ol_d)           # left send slab
                dst[dim] = _blk(c, s, s - hw, s)                  # right halo
                P[tuple(dst)] = snap[tuple(src)]
    return P


def run_config(nx, ny, nz, *, dims=(0, 0, 0), periods=(0, 0, 0),
               overlaps=(2, 2, 2), halowidths=None, stagger=(0, 0, 0),
               dtype=np.float64, order=None, ndim=3, disp=1, reorder=1):
    """Init, build encoded field, zero halos, exchange, compare to oracle.
    Returns (result, oracle, reference_encoding)."""
    igg.init_global_grid(
        nx, ny, nz, dimx=dims[0], dimy=dims[1], dimz=dims[2],
        periodx=periods[0], periody=periods[1], periodz=periods[2],
        overlaps=overlaps, halowidths=halowidths, quiet=True,
        disp=disp, reorder=reorder,
    )
    gg = igg.global_grid()
    base = [nx, ny, nz][:ndim]
    local_shape = tuple(int(b) + int(st) for b, st in zip(base, stagger))
    hw_list = tuple(int(h) for h in gg.halowidths)
    A = igg.zeros_g(local_shape, dtype)
    enc = encode(A).astype(dtype)
    order = order if order is not None else igg.DEFAULT_DIMS_ORDER
    Pz = zero_halos(enc, local_shape, hw_list, [d for d in order if d < ndim])
    res = igg.update_halo(igg.device_put_g(Pz), dims=order)
    exp = oracle_update(Pz, local_shape, hw_list, order)
    return np.asarray(res), exp, enc


# ---------------------------------------------------------------------------
# restoration tests (the reference's headline acceptance tests)
# ---------------------------------------------------------------------------

def test_restore_3d_periodic_all_dims_2x2x2():
    res, exp, enc = run_config(5, 5, 5, dims=(2, 2, 2), periods=(1, 1, 1))
    assert np.array_equal(res, exp)
    # fully periodic ⇒ every halo cell restored to its encoding
    assert np.array_equal(res, enc)


def test_restore_3d_nonperiodic_2x2x2():
    res, exp, enc = run_config(5, 5, 5, dims=(2, 2, 2))
    assert np.array_equal(res, exp)
    # interior-facing halos restored: check the x-interface plane
    assert np.array_equal(res[4:6, 1:9, 1:9], enc[4:6, 1:9, 1:9])
    # physical-boundary halos keep their (zeroed) values: PROC_NULL no-op
    assert np.all(res[0, :, :] == 0) and np.all(res[-1, :, :] == 0)


def test_restore_self_neighbor_single_shard_periodic():
    # "1 process + periodic": the full machinery through the local-copy path
    # (reference update_halo.jl:62-68; test_update_halo.jl:839-924)
    res, exp, enc = run_config(5, 5, 5, dims=(1, 1, 1), periods=(1, 1, 1))
    assert np.array_equal(res, exp)
    assert np.array_equal(res, enc)


def test_restore_mixed_periodicity_4x2x1():
    res, exp, _ = run_config(5, 5, 5, dims=(4, 2, 1), periods=(1, 0, 1))
    assert np.array_equal(res, exp)


def test_restore_asymmetric_local_sizes():
    res, exp, _ = run_config(6, 4, 7, dims=(2, 2, 2), periods=(0, 1, 0))
    assert np.array_equal(res, exp)


def test_restore_staggered_fields():
    # Vx-like field: local (nx+1, ny, nz) — overlap grows to ol+1 (shared.jl:107)
    for stagger in [(1, 0, 0), (0, 1, 0), (0, 0, 1), (1, 1, 1)]:
        res, exp, _ = run_config(5, 5, 5, dims=(2, 2, 2), periods=(0, 0, 0),
                                 stagger=stagger)
        assert np.array_equal(res, exp), f"stagger={stagger}"
        igg.finalize_global_grid()


def test_restore_negative_stagger():
    # smaller-than-nxyz field: ol-1 = 1 < 2*hw ⇒ NO halo update in that dim
    res, exp, _ = run_config(6, 6, 6, dims=(2, 2, 2), stagger=(-1, 0, 0))
    assert np.array_equal(res, exp)
    gg = igg.global_grid()
    assert igg.ol(0, (5, 6, 6)) == 1  # below 2*hw ⇒ x untouched


def test_restore_halowidth_2_overlap_4():
    res, exp, enc = run_config(9, 9, 9, dims=(2, 2, 2), periods=(1, 1, 1),
                               overlaps=(4, 4, 4))
    gg_hw = 2
    assert np.array_equal(res, exp)
    assert np.array_equal(res, enc)


def test_restore_asymmetric_overlaps_and_hw():
    res, exp, _ = run_config(9, 8, 7, dims=(2, 2, 2), overlaps=(4, 2, 3),
                             halowidths=(2, 1, 1), periods=(1, 0, 0))
    assert np.array_equal(res, exp)


def test_restore_2d_grid():
    res, exp, enc = run_config(6, 6, 1, dims=(4, 2, 0), periods=(1, 1, 0), ndim=2)
    assert np.array_equal(res, exp)
    assert np.array_equal(res, enc)


def test_restore_1d_grid():
    res, exp, enc = run_config(8, 1, 1, dims=(8, 0, 0), periods=(1, 0, 0), ndim=1)
    assert np.array_equal(res, exp)
    assert np.array_equal(res, enc)


def _bf16():
    import jax.numpy as jnp

    return jnp.bfloat16


@pytest.mark.parametrize("dtype", [np.float32, np.float64, np.int32,
                                   np.complex128, "bfloat16"])
def test_dtypes(dtype):
    if dtype == "bfloat16":  # TPU-native dtype (reference has no analog)
        dtype = _bf16()
    res, exp, _ = run_config(5, 5, 5, dims=(2, 2, 1), periods=(1, 1, 0), dtype=dtype)
    assert res.dtype == np.dtype(dtype)
    assert np.array_equal(res, exp)


def test_dims_order_subset():
    # dims=(0,): only the x exchange runs (reference's per-dim dims kwarg)
    res, exp, _ = run_config(5, 5, 5, dims=(2, 2, 2), periods=(1, 1, 1), order=(0,))
    assert np.array_equal(res, exp)


def test_multi_field_call():
    igg.init_global_grid(5, 5, 5, dimx=2, dimy=2, dimz=2, periodx=1, quiet=True)
    A = igg.zeros_g()
    enc = encode(A)
    Pz = zero_halos(enc, (5, 5, 5), (1, 1, 1), (0, 1, 2))
    Vx_enc = encode(igg.zeros_g((6, 5, 5)))
    Vz = zero_halos(Vx_enc, (6, 5, 5), (1, 1, 1), (0, 1, 2))
    a, b = igg.update_halo(igg.device_put_g(Pz), igg.device_put_g(Vz))
    assert np.array_equal(np.asarray(a), oracle_update(Pz, (5, 5, 5), (1, 1, 1),
                                                       igg.DEFAULT_DIMS_ORDER))
    assert np.array_equal(np.asarray(b), oracle_update(Vz, (6, 5, 5), (1, 1, 1),
                                                       igg.DEFAULT_DIMS_ORDER))


def test_per_field_halowidths():
    igg.init_global_grid(9, 9, 9, dimx=2, dimy=2, dimz=2,
                         overlaps=(4, 4, 4), quiet=True)
    A = igg.zeros_g()
    enc = encode(A)
    Pz = zero_halos(enc, (9, 9, 9), (2, 2, 2), (0, 1, 2))
    # pass hw=(1,1,1) instead of default (2,2,2) via Field / tuple form
    r1 = igg.update_halo(igg.Field(igg.device_put_g(Pz), (1, 1, 1)))
    r2 = igg.update_halo((igg.device_put_g(Pz), (1, 1, 1)))
    exp = oracle_update(Pz, (9, 9, 9), (1, 1, 1), igg.DEFAULT_DIMS_ORDER)
    assert np.array_equal(np.asarray(r1), exp)
    assert np.array_equal(np.asarray(r2), exp)


def test_pytree_fields():
    # dict-of-arrays = the CellArray analog (reference extract, shared.jl:133-137)
    igg.init_global_grid(5, 5, 5, dimx=2, dimy=2, dimz=2, periodz=1, quiet=True)
    enc = encode(igg.zeros_g())
    Pz = zero_halos(enc, (5, 5, 5), (1, 1, 1), (0, 1, 2))
    a, b = igg.update_halo({"u": igg.device_put_g(Pz), "v": igg.device_put_g(Pz + 1)})
    exp = oracle_update(Pz, (5, 5, 5), (1, 1, 1), igg.DEFAULT_DIMS_ORDER)
    assert np.array_equal(np.asarray(a), exp)


def test_local_update_halo_inside_shard_map():
    import jax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    igg.init_global_grid(5, 5, 5, dimx=2, dimy=2, dimz=2, periody=1, quiet=True)
    gg = igg.global_grid()
    enc = encode(igg.zeros_g())
    Pz = zero_halos(enc, (5, 5, 5), (1, 1, 1), (0, 1, 2))

    fn = jax.jit(shard_map(
        lambda a: igg.local_update_halo(a),
        mesh=gg.mesh, in_specs=P("gx", "gy", "gz"), out_specs=P("gx", "gy", "gz"),
    ))
    res = np.asarray(fn(igg.device_put_g(Pz)))
    ctrl = np.asarray(igg.update_halo(igg.device_put_g(Pz)))
    assert np.array_equal(res, ctrl)


def test_repeated_calls_reuse_cache():
    from implicitglobalgrid_tpu.ops import halo as halo_mod

    igg.init_global_grid(5, 5, 5, dimx=2, dimy=2, dimz=2, quiet=True)
    A = igg.zeros_g()
    igg.update_halo(A)
    n1 = len(halo_mod._exchange_cache)
    igg.update_halo(A + 1)
    assert len(halo_mod._exchange_cache) == n1  # same signature ⇒ cached program
    igg.update_halo(igg.zeros_g((6, 5, 5)))
    assert len(halo_mod._exchange_cache) == n1 + 1
    igg.finalize_global_grid()
    assert len(halo_mod._exchange_cache) == 0   # freed (finalize_global_grid.jl:17)


# ---------------------------------------------------------------------------
# error paths (check_fields catalog, update_halo.jl:410-472)
# ---------------------------------------------------------------------------

def test_error_no_halo_field():
    igg.init_global_grid(5, 5, 5, dimx=2, dimy=2, dimz=2, quiet=True)
    # hw=(2,2,2) with ol=2 < 2*hw everywhere ⇒ "has no halo; remove it"
    with pytest.raises(IncoherentArgumentError):
        igg.update_halo(igg.Field(igg.zeros_g(), (2, 2, 2)))


def test_error_duplicate_field():
    igg.init_global_grid(5, 5, 5, dimx=2, dimy=2, dimz=2, quiet=True)
    A = igg.zeros_g()
    with pytest.raises(IncoherentArgumentError):
        igg.update_halo(A, A)


def test_error_bad_halowidth():
    igg.init_global_grid(5, 5, 5, dimx=2, dimy=2, dimz=2, quiet=True)
    with pytest.raises(InvalidArgumentError):
        igg.update_halo(igg.Field(igg.zeros_g(), (0, 1, 1)))


def test_error_bad_ndim_and_bad_dims_arg():
    igg.init_global_grid(5, 5, 5, dimx=2, dimy=2, dimz=2, quiet=True)
    import jax.numpy as jnp

    with pytest.raises(InvalidArgumentError):
        igg.update_halo(jnp.zeros((2, 2, 2, 2)))
    with pytest.raises(InvalidArgumentError):
        igg.update_halo(igg.zeros_g(), dims=(3,))
    with pytest.raises(InvalidArgumentError):
        igg.update_halo()


def test_error_indivisible_stacked_shape():
    igg.init_global_grid(5, 5, 5, dimx=2, dimy=2, dimz=2, quiet=True)
    import jax.numpy as jnp

    with pytest.raises((IncoherentArgumentError, InvalidArgumentError)):
        igg.update_halo(jnp.zeros((11, 10, 10)))


# ---------------------------------------------------------------------------
# Pallas halo kernels (interpret mode) vs the XLA dynamic-update-slice path —
# the analog of the reference testing its GPU pack kernels against the CPU
# copies (`test_update_halo.jl:497-634`).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dims,periods,label", [
    ((1, 1, 1), (1, 1, 1), "self-neighbor all periodic (single-pass kernel)"),
    ((1, 1, 1), (1, 0, 1), "self-neighbor x,z only"),
    ((2, 2, 2), (1, 1, 1), "2x2x2 periodic (per-dim kernels)"),
    ((2, 2, 2), (0, 0, 0), "2x2x2 non-periodic (PROC_NULL edges)"),
    ((2, 1, 4), (1, 0, 1), "mixed multi/self/skip"),
])
def test_pallas_halo_kernels_match_dus(dims, periods, label):
    import implicitglobalgrid_tpu.ops.halo as halo_mod

    shape_local = (16, 16, 128)
    igg.init_global_grid(*shape_local, dimx=dims[0], dimy=dims[1],
                         dimz=dims[2], periodx=periods[0], periody=periods[1],
                         periodz=periods[2], quiet=True)
    rng = np.random.default_rng(0)
    stacked = tuple(int(d * n) for d, n in zip(dims, shape_local))
    A = igg.device_put_g(rng.standard_normal(stacked).astype(np.float32))
    try:
        halo_mod._FORCE_PALLAS_WRITE_INTERPRET = False
        r_dus = np.asarray(igg.gather(igg.update_halo(A)))
        halo_mod._FORCE_PALLAS_WRITE_INTERPRET = True
        r_pal = np.asarray(igg.gather(igg.update_halo(A)))
    finally:
        halo_mod._FORCE_PALLAS_WRITE_INTERPRET = False
    assert np.array_equal(r_dus, r_pal), label


@pytest.mark.parametrize("periods", [(1, 1, 1), (0, 0, 0), (1, 0, 1)])
def test_restore_disp2_4shard(periods):
    """disp=2 neighbor displacement (reference threads `disp` through
    `Cart_shift`, `init_global_grid.jl:104-106`): slabs travel two shards."""
    res, exp, enc = run_config(6, 5, 5, dims=(4, 1, 1), periods=periods,
                               disp=2)
    assert np.array_equal(res, exp)


def test_restore_disp2_periodic_wrap():
    """disp=2 on a 2-shard periodic axis wraps to self (coord+2 mod 2)."""
    res, exp, enc = run_config(6, 5, 5, dims=(2, 2, 1), periods=(1, 1, 0),
                               disp=2)
    assert np.array_equal(res, exp)


def test_reorder0_matches_reorder1():
    """reorder=0 (keep device order) must produce the same exchange result
    as the default reorder=1 (reference `Cart_create` reorder flag)."""
    res1, exp1, _ = run_config(5, 5, 5, dims=(2, 2, 2), periods=(1, 0, 1))
    igg.finalize_global_grid()
    res0, exp0, _ = run_config(5, 5, 5, dims=(2, 2, 2), periods=(1, 0, 1),
                               reorder=0)
    assert np.array_equal(res0, exp0)
    assert np.array_equal(res0, res1)


# Combined one-pass unpack path (dim 2 participating with ppermute dims):
# adversarial configs — staggering, disp, asymmetric halowidths, self/multi
# mixes — against the XLA path.
@pytest.mark.parametrize("dims,periods,kw,label", [
    ((2, 2, 2), (1, 1, 1), {}, "all-periodic all-multi"),
    ((2, 2, 2), (0, 0, 0), {}, "non-periodic PROC_NULL corners"),
    ((1, 2, 2), (1, 0, 1), {}, "x self-neighbor + y PROC_NULL + z multi"),
    ((2, 1, 2), (0, 1, 1), {}, "y self-neighbor mix"),
    ((4, 1, 2), (1, 1, 1), {"disp": 2}, "disp=2 combined"),
    ((2, 2, 2), (1, 1, 1),
     {"overlaps": (4, 2, 2), "halowidths": (2, 1, 1)},
     "halowidth 2 along x (whole-plane dim)"),
    ((2, 2, 2), (1, 1, 1),
     {"overlaps": (2, 4, 4), "halowidths": (1, 2, 2)},
     "halowidth 2 along y,z: combined unsupported, per-dim fallback"),
])
def test_pallas_combined_unpack_matches_dus(dims, periods, kw, label):
    import implicitglobalgrid_tpu.ops.halo as halo_mod

    shape_local = (16, 16, 128)
    igg.init_global_grid(*shape_local, dimx=dims[0], dimy=dims[1],
                         dimz=dims[2], periodx=periods[0], periody=periods[1],
                         periodz=periods[2], quiet=True, **kw)
    rng = np.random.default_rng(2)
    stacked = tuple(int(d * n) for d, n in zip(dims, shape_local))
    A = igg.device_put_g(rng.standard_normal(stacked).astype(np.float32))
    try:
        halo_mod._FORCE_PALLAS_WRITE_INTERPRET = False
        r_dus = np.asarray(igg.gather(igg.update_halo(A)))
        halo_mod._FORCE_PALLAS_WRITE_INTERPRET = True
        r_pal = np.asarray(igg.gather(igg.update_halo(A)))
    finally:
        halo_mod._FORCE_PALLAS_WRITE_INTERPRET = False
    assert np.array_equal(r_dus, r_pal), label


def test_pallas_combined_unpack_staggered_matches_dus():
    """Staggered field (+1 along x) through the combined path."""
    import implicitglobalgrid_tpu.ops.halo as halo_mod

    igg.init_global_grid(16, 16, 128, dimx=2, dimy=2, dimz=2,
                         periodx=1, periody=1, periodz=1, quiet=True)
    rng = np.random.default_rng(3)
    A = igg.device_put_g(rng.standard_normal((34, 32, 256)).astype(np.float32))
    try:
        halo_mod._FORCE_PALLAS_WRITE_INTERPRET = False
        r_dus = np.asarray(igg.gather(igg.update_halo(A)))
        halo_mod._FORCE_PALLAS_WRITE_INTERPRET = True
        r_pal = np.asarray(igg.gather(igg.update_halo(A)))
    finally:
        halo_mod._FORCE_PALLAS_WRITE_INTERPRET = False
    assert np.array_equal(r_dus, r_pal)


# ---------------------------------------------------------------------------
# Coalesced multi-field exchange (one packed ppermute pair per mesh axis and
# dtype group, `ops/halo.py` module docstring) — must be BIT-IDENTICAL to the
# per-field path on every configuration: packing is ravel/concat, the wire
# carries the same values.
# ---------------------------------------------------------------------------

def _exchange_both_ways(fields, **kw):
    """(coalesced, per_field) update_halo results as numpy arrays."""
    a = igg.update_halo(*fields, coalesce=True, **kw)
    b = igg.update_halo(*fields, coalesce=False, **kw)
    if len(fields) == 1:
        a, b = (a,), (b,)
    return ([np.asarray(x) for x in a], [np.asarray(x) for x in b])


@pytest.mark.parametrize("n,dims,periods,kw,label", [
    (6, (2, 2, 2), (1, 1, 1), {}, "all-periodic"),
    (6, (2, 2, 2), (0, 0, 0), {}, "non-periodic PROC_NULL edges"),
    (6, (1, 2, 2), (1, 0, 1), {}, "x self-neighbor + y PROC_NULL + z multi"),
    (6, (4, 2, 1), (1, 0, 1), {"disp": 2}, "disp=2"),
    (9, (2, 2, 2), (1, 0, 1),
     {"overlaps": (4, 4, 4), "halowidths": (2, 2, 2)}, "halowidth 2"),
])
def test_coalesced_matches_per_field(n, dims, periods, kw, label):
    igg.init_global_grid(n, n, n, dimx=dims[0], dimy=dims[1], dimz=dims[2],
                         periodx=periods[0], periody=periods[1],
                         periodz=periods[2], quiet=True, **kw)
    rng = np.random.default_rng(7)
    stacked = tuple(int(d) * n for d in igg.global_grid().dims)

    def mk(dtype):
        return igg.device_put_g(
            rng.standard_normal(stacked).astype(dtype))

    fields = [mk(np.float64) for _ in range(3)]
    co, pf = _exchange_both_ways(fields)
    for c, p in zip(co, pf):
        assert np.array_equal(c, p), label


def test_coalesced_mixed_dtypes_and_fallback():
    """3 f32 + 2 f64 + 1 int32: two packed groups plus a per-field
    fallback for the lone-dtype field — all bit-identical to the
    fully per-field path."""
    igg.init_global_grid(6, 6, 6, dimx=2, dimy=2, dimz=2,
                         periodx=1, quiet=True)
    rng = np.random.default_rng(8)
    fields = [igg.device_put_g(rng.standard_normal((12, 12, 12)).astype(dt))
              for dt in [np.float32] * 3 + [np.float64] * 2 + [np.int32]]
    co, pf = _exchange_both_ways(fields)
    for c, p in zip(co, pf):
        assert np.array_equal(c, p)


def test_coalesced_per_field_halowidths_and_stagger():
    """Fields disagreeing on halowidths and shape (staggered +1) still
    pack — the flat packer carries per-field slab sizes; results equal
    the per-field path exactly."""
    igg.init_global_grid(9, 9, 9, dimx=2, dimy=2, dimz=2,
                         overlaps=(4, 4, 4), periodx=1, periody=1, quiet=True)
    rng = np.random.default_rng(9)
    A = igg.device_put_g(rng.standard_normal((18, 18, 18)))
    B = igg.device_put_g(rng.standard_normal((18, 18, 18)))   # hw (1,1,1)
    Vx = igg.device_put_g(rng.standard_normal((20, 18, 18)))  # staggered +1
    fields = [A, igg.Field(B, (1, 1, 1)), Vx]
    co, pf = _exchange_both_ways(fields)
    for c, p in zip(co, pf):
        assert np.array_equal(c, p)
    # and against the oracle (coalesced path is reference-exact, not just
    # per-field-path-exact)
    exp = oracle_update(np.asarray(A), (9, 9, 9), (2, 2, 2),
                        igg.DEFAULT_DIMS_ORDER)
    assert np.array_equal(co[0], exp)


def test_coalesced_2d_and_participation_mix():
    """2-D grid with a field that participates only along one dim (no halo
    along the other): group membership is per-dim; fallback engages where
    packing is inapplicable."""
    igg.init_global_grid(6, 6, 1, dimx=4, dimy=2,
                         periodx=1, periody=1, quiet=True)
    rng = np.random.default_rng(10)
    A = igg.device_put_g(rng.standard_normal((24, 12)))
    B = igg.device_put_g(rng.standard_normal((24, 12)))
    co, pf = _exchange_both_ways([A, B])
    for c, p in zip(co, pf):
        assert np.array_equal(c, p)


def test_coalesced_pallas_multi_unpack_matches_dus():
    """The multi-field Pallas unpack kernel (interpret mode) delivers the
    same bits as the XLA dynamic-update-slice unpack on the coalesced
    path."""
    import implicitglobalgrid_tpu.ops.halo as halo_mod

    igg.init_global_grid(16, 16, 128, dimx=2, dimy=2, dimz=2,
                         periodx=1, periody=1, periodz=1, quiet=True)
    rng = np.random.default_rng(11)
    fs = [igg.device_put_g(
        rng.standard_normal((32, 32, 256)).astype(np.float32))
        for _ in range(3)]
    fs.append(igg.device_put_g(                      # staggered +1 along x
        rng.standard_normal((34, 32, 256)).astype(np.float32)))
    try:
        halo_mod._FORCE_PALLAS_WRITE_INTERPRET = False
        dus = [np.asarray(igg.gather(x)) for x in igg.update_halo(*fs)]
        halo_mod._FORCE_PALLAS_WRITE_INTERPRET = True
        pal = [np.asarray(igg.gather(x)) for x in igg.update_halo(*fs)]
    finally:
        halo_mod._FORCE_PALLAS_WRITE_INTERPRET = False
    for d, p in zip(dus, pal):
        assert np.array_equal(d, p)


# ---------------------------------------------------------------------------
# Wire-precision mode (`IGG_HALO_WIRE_DTYPE` / wire_dtype=) — opt-in only.
# ---------------------------------------------------------------------------

def test_wire_precision_defaults_off_and_is_bit_identical_when_off():
    igg.init_global_grid(6, 6, 6, dimx=2, dimy=2, dimz=2, periodx=1,
                         quiet=True)
    rng = np.random.default_rng(12)
    A = igg.device_put_g(rng.standard_normal((12, 12, 12)).astype(np.float32))
    B = igg.device_put_g(rng.standard_normal((12, 12, 12)).astype(np.float32))
    r_default = igg.update_halo(A, B)
    r_off = igg.update_halo(A, B, wire_dtype="off")
    for x, y in zip(r_default, r_off):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def test_wire_precision_bf16_rounds_interior_keeps_boundary_exact():
    """bf16 wire: interior-facing halos carry bf16-rounded values (within
    bf16 eps of the exact exchange); PROC_NULL boundary halos never cross
    the wire and stay exact; the coalesced and per-field wire paths round
    identically (bit-identical to each other)."""
    igg.init_global_grid(6, 6, 6, dimx=2, dimy=2, dimz=2, quiet=True)
    rng = np.random.default_rng(13)
    A = igg.device_put_g(rng.standard_normal((12, 12, 12)).astype(np.float32))
    B = igg.device_put_g(rng.standard_normal((12, 12, 12)).astype(np.float32))
    exact = [np.asarray(x) for x in igg.update_halo(A, B)]
    co = [np.asarray(x) for x in
          igg.update_halo(A, B, wire_dtype="bfloat16", coalesce=True)]
    pf = [np.asarray(x) for x in
          igg.update_halo(A, B, wire_dtype="bfloat16", coalesce=False)]
    for c, p in zip(co, pf):
        assert np.array_equal(c, p)  # packing never changes rounding
    for c, e in zip(co, exact):
        assert np.allclose(c, e, rtol=2 ** -7, atol=2 ** -7)  # bf16 eps
        assert not np.array_equal(c, e)  # the rounding actually happened
        # physical-boundary halo cells (PROC_NULL, non-periodic grid) never
        # cross the wire: exact. Restrict to cells of the x=0 plane that are
        # not ALSO y/z halo cells of their shard (those receive later y/z
        # exchange slabs, which do go through the wire).
        assert np.array_equal(c[0, 1:5, 1:5], e[0, 1:5, 1:5])
        assert np.array_equal(c[-1, 7:11, 7:11], e[-1, 7:11, 7:11])


def test_wire_precision_ignores_non_float_fields():
    """int32 payloads never convert (conversion would corrupt them)."""
    igg.init_global_grid(6, 6, 6, dimx=2, dimy=2, dimz=2, periodx=1,
                         quiet=True)
    rng = np.random.default_rng(14)
    A = igg.device_put_g(rng.integers(-1000, 1000, (12, 12, 12)).astype(np.int32))
    B = igg.device_put_g(rng.integers(-1000, 1000, (12, 12, 12)).astype(np.int32))
    r_wire = igg.update_halo(A, B, wire_dtype="bfloat16")
    r_exact = igg.update_halo(A, B)
    for x, y in zip(r_wire, r_exact):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def test_wire_precision_env_var():
    import os

    import implicitglobalgrid_tpu.ops.halo as halo_mod

    igg.init_global_grid(6, 6, 6, dimx=2, dimy=2, dimz=2, periodx=1,
                         quiet=True)
    rng = np.random.default_rng(15)
    A = igg.device_put_g(rng.standard_normal((12, 12, 12)).astype(np.float32))
    B = igg.device_put_g(rng.standard_normal((12, 12, 12)).astype(np.float32))
    explicit = [np.asarray(x)
                for x in igg.update_halo(A, B, wire_dtype="bfloat16")]
    os.environ["IGG_HALO_WIRE_DTYPE"] = "bfloat16"
    try:
        via_env = [np.asarray(x) for x in igg.update_halo(A, B)]
    finally:
        del os.environ["IGG_HALO_WIRE_DTYPE"]
    for x, y in zip(explicit, via_env):
        assert np.array_equal(x, y)


# ---------------------------------------------------------------------------
# Quantized wire (int8/int4 per-slab-scale payloads, per-axis policy)
# ---------------------------------------------------------------------------

@pytest.mark.quant
def test_quantized_wire_bounded_error_and_boundary_exact():
    """int8 wire: every received halo stays within scale/(2*127) of the
    exact exchange per slab (loose global bound below), the rounding
    actually happens, PROC_NULL boundary halos never cross the wire and
    stay exact, and the coalesced and per-field-buffer paths quantize
    identically (each slab carries its own scale in both layouts)."""
    igg.init_global_grid(6, 6, 6, dimx=2, dimy=2, dimz=2, quiet=True)
    rng = np.random.default_rng(31)
    A = igg.device_put_g(rng.standard_normal((12, 12, 12)).astype(np.float32))
    B = igg.device_put_g(rng.standard_normal((12, 12, 12)).astype(np.float32))
    exact = [np.asarray(x) for x in igg.update_halo(A, B)]
    co = [np.asarray(x) for x in
          igg.update_halo(A, B, wire_dtype="int8", coalesce=True)]
    pf = [np.asarray(x) for x in
          igg.update_halo(A, B, wire_dtype="int8", coalesce=False)]
    for c, p in zip(co, pf):
        assert np.array_equal(c, p)  # packing never changes quantization
    for c, e in zip(co, exact):
        # |err| <= max_slab_scale/(2*127); slab maxima of N(0,1) draws sit
        # well under 5, and errors compound across the 3 sequential dims
        assert np.abs(c - e).max() < 3 * 5 / 254
        assert not np.array_equal(c, e)  # the quantization happened
        # physical-boundary halos (PROC_NULL, non-periodic): exact (same
        # cell selection as the bf16 test above)
        assert np.array_equal(c[0, 1:5, 1:5], e[0, 1:5, 1:5])
        assert np.array_equal(c[-1, 7:11, 7:11], e[-1, 7:11, 7:11])


@pytest.mark.quant
def test_quantized_wire_per_axis_policy_quantizes_only_named_axis():
    """`wire_dtype="z:int8"`: payloads on the x/y axes stay EXACT while
    z-axis halos quantize — every differing cell lies in a z-halo plane
    of some local block (the x/y exchanges are bit-identical to the
    full-precision run away from the z seams their send slabs patch)."""
    igg.init_global_grid(6, 6, 6, dimx=2, dimy=1, dimz=2, periodx=1,
                         periodz=1, quiet=True)
    rng = np.random.default_rng(32)
    A = igg.device_put_g(rng.standard_normal((12, 6, 12)).astype(np.float32))
    exact = np.asarray(igg.update_halo(A))
    mixed = np.asarray(igg.update_halo(A, wire_dtype="z:int8"))
    diff = mixed != exact
    assert diff.any()  # z quantization happened
    # local z blocks are 6 wide: halo planes sit at stacked z indices
    # {0, 5, 6, 11} (hw=1 each side of each block)
    z_halo = np.zeros_like(diff)
    z_halo[:, :, [0, 5, 6, 11]] = True
    assert not (diff & ~z_halo).any()  # x/y wire untouched
    # fully-mixed policy: int4 on z, exact-cast f32 on x — still only
    # z-plane differences
    mixed4 = np.asarray(igg.update_halo(A, wire_dtype="z:int4,x:f32"))
    d4 = mixed4 != exact
    assert d4.any() and not (d4 & ~z_halo).any()


@pytest.mark.quant
def test_quantized_wire_ignores_non_float_and_defaults_off():
    """int32 payloads never quantize (corruption), and the quantized mode
    is opt-in: the default exchange stays bit-identical."""
    igg.init_global_grid(6, 6, 6, dimx=2, dimy=2, dimz=2, periodx=1,
                         quiet=True)
    rng = np.random.default_rng(33)
    A = igg.device_put_g(
        rng.integers(-1000, 1000, (12, 12, 12)).astype(np.int32))
    F = igg.device_put_g(rng.standard_normal((12, 12, 12)).astype(np.float32))
    rq = igg.update_halo(A, F, wire_dtype="int8")
    re_ = igg.update_halo(A, F)
    assert np.array_equal(np.asarray(rq[0]), np.asarray(re_[0]))  # int exact
    assert not np.array_equal(np.asarray(rq[1]), np.asarray(re_[1]))
    r_env_off = igg.update_halo(A, F, wire_dtype="off")
    for x, y in zip(re_, r_env_off):
        assert np.array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.quant
def test_quantized_policy_on_unpartitioned_axis_is_noop():
    """A policy naming only axes a field has no ppermute on (dimz=1 here:
    z is self-copy/no-neighbor) is a NO-OP: results bit-identical to the
    exact exchange, and the field keeps the fast combined/self kernel
    tiers (it is not evicted to per-dim exchanges for nothing)."""
    igg.init_global_grid(6, 6, 6, dimx=2, dimy=2, dimz=1, periodx=1,
                         periodz=1, quiet=True)
    rng = np.random.default_rng(35)
    A = igg.device_put_g(rng.standard_normal((12, 12, 6)).astype(np.float32))
    exact = np.asarray(igg.update_halo(A))
    noop = np.asarray(igg.update_halo(A, wire_dtype="z:int8"))
    assert np.array_equal(noop, exact)
    # plan agrees: no int8 anywhere, bytes identical to exact
    pe = igg.halo_comm_plan(A)
    pq = igg.halo_comm_plan(A, wire_dtype="z:int8")
    assert pq["wire_bytes"] == pe["wire_bytes"]
    assert all("int8" not in r["by_dtype"] for r in pq["axes"].values())


@pytest.mark.quant
def test_quantized_wire_pallas_unpack_matches_dus():
    """The dequantized slabs feed the SAME delivery tiers as exact ones:
    the multi-field Pallas unpack (interpret mode) delivers bit-identical
    results to the `dynamic_update_slice` path under int8 wire."""
    import implicitglobalgrid_tpu.ops.halo as halo_mod

    igg.init_global_grid(8, 8, 8, dimx=2, dimy=2, dimz=2, periodx=1,
                         periody=1, periodz=1, quiet=True)
    rng = np.random.default_rng(34)
    fs = [igg.device_put_g(
        rng.standard_normal((16, 16, 16)).astype(np.float32))
        for _ in range(2)]
    try:
        halo_mod._FORCE_PALLAS_WRITE_INTERPRET = False
        dus = [np.asarray(igg.gather(x))
               for x in igg.update_halo(*fs, wire_dtype="int8")]
        halo_mod._FORCE_PALLAS_WRITE_INTERPRET = True
        pal = [np.asarray(igg.gather(x))
               for x in igg.update_halo(*fs, wire_dtype="int8")]
    finally:
        halo_mod._FORCE_PALLAS_WRITE_INTERPRET = False
    for d, p in zip(dus, pal):
        assert np.array_equal(d, p)


@pytest.mark.quant
def test_quantized_wire_propagates_nonfinite():
    """A NaN in a send slab poisons the received halo slab to non-finite
    values (slab-granular propagation): quantization may coarsen a NaN
    but can never launder it into a plausible finite halo."""
    igg.init_global_grid(6, 6, 6, dimx=2, dimy=1, dimz=1, periodx=1,
                         quiet=True)
    a = np.ones((12, 6, 6), np.float32)
    a[4, 3, 3] = np.nan  # inside shard 0's right send slab (ol=2, hw=1)
    A = igg.device_put_g(a)
    out = np.asarray(igg.update_halo(A, wire_dtype="int8"))
    # the right-neighbor shard's left halo (stacked x index 6) received
    # the poisoned slab: wholly non-finite
    assert not np.isfinite(out[6, :, :]).any()
    # the exact path keeps the NaN point-local
    out_exact = np.asarray(igg.update_halo(A))
    assert np.isnan(out_exact[6, 3, 3]) and np.isfinite(out_exact[6, 0, 0])


def test_pallas_halo_multi_field_matches_dus():
    import implicitglobalgrid_tpu.ops.halo as halo_mod

    igg.init_global_grid(16, 16, 128, periodx=1, periody=1, periodz=1,
                         quiet=True)
    rng = np.random.default_rng(1)
    A = igg.device_put_g(rng.standard_normal((16, 16, 128)).astype(np.float32))
    B = igg.device_put_g(rng.standard_normal((16, 16, 128)).astype(np.float32))
    try:
        halo_mod._FORCE_PALLAS_WRITE_INTERPRET = False
        ra, rb = igg.update_halo(A, B)
        ra, rb = np.asarray(igg.gather(ra)), np.asarray(igg.gather(rb))
        halo_mod._FORCE_PALLAS_WRITE_INTERPRET = True
        pa, pb = igg.update_halo(A, B)
        pa, pb = np.asarray(igg.gather(pa)), np.asarray(igg.gather(pb))
    finally:
        halo_mod._FORCE_PALLAS_WRITE_INTERPRET = False
    assert np.array_equal(ra, pa)
    assert np.array_equal(rb, pb)
