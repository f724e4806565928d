"""Pallas TPU kernels for the diffusion stencil step.

The reference's GPU extension hand-writes pack kernels (`write_d2x!`,
`/root/reference/src/CUDAExt/update_halo.jl:210-227`) because CUDA broadcasts
leave >10x on the table (`reference README.md:167`). The TPU analog of that
native-kernel tier is Pallas: this module fuses one full diffusion time step
(flux computation + divergence + update) into a single pass over the local
block, pipelined plane-by-plane through VMEM — removing the intermediate
full-array materializations the XLA broadcast formulation pays for.

The arithmetic is the exact flux-form sequence of the reference example
(`examples/diffusion3D_multicpu_novis.jl:42-46`):

    qx = -λ dT/dx (faces);  dT/dt = -div q / cp;  T += dt dT/dt   (interior)

in the same accumulation order as the XLA flux-form step, so results agree to
the last ulp or two (exact bitwise equality across the two compilers is not
guaranteed — fma contraction differs).

Kernel shape requirements: 3-D local blocks, last dim a multiple of 128
(lane width) and second-to-last a multiple of 8 for peak efficiency; other
shapes work but pad internally in the Mosaic compiler. Use
``diffusion3d_step_pallas(..., interpret=True)`` on CPU (tests).
"""

from __future__ import annotations

from functools import partial

__all__ = ["diffusion3d_step_pallas", "diffusion3d_step_halo_pallas",
           "diffusion3d_step_halo_pallas_mp", "mp_supported",
           "pallas_supported", "fusable_halo_dims",
           "step_exchange_modes", "step_exchange_folds_z",
           "diffusion3d_step_exchange_pallas",
           "strip_rows_2d", "diffusion2d_step_exchange_pallas"]


def pallas_supported(T) -> bool:
    """Whether the Pallas step kernel supports this local block."""
    return T.ndim == 3 and T.shape[0] >= 3


def fusable_halo_dims(gg, ndim: int = 3):
    """Which dims' halo exchange can fuse into the step kernel output pass.

    A dim is fusable when it takes the reference's self-neighbor local path
    (periodic axis, single shard — `update_halo.jl:62-68`) with the default
    overlap/halowidth (ol=2, hw=1), i.e. the halo write is a pure in-plane
    copy. Fusion must respect the reference's strict dim sequencing
    (z, x, y — `update_halo.jl:45`): a dim may fuse only if every dim
    BEFORE it in the order either fuses too or exchanges nothing — otherwise
    its send slabs would miss the earlier dims' received corners. Returns
    (fuse_x, fuse_y, fuse_z) or None if nothing can fuse.
    """
    if ndim != 3:
        return None
    fuse = [False, False, False]
    for dim in (2, 0, 1):  # DEFAULT_DIMS_ORDER
        D = int(gg.dims[dim])
        periodic = bool(gg.periods[dim])
        if D == 1 and not periodic:
            continue  # no exchange on this dim — doesn't block later fusion
        if (D == 1 and periodic and int(gg.overlaps[dim]) == 2
                and int(gg.halowidths[dim]) == 1 and int(gg.disp) == 1):
            fuse[dim] = True
        else:
            break  # multi-shard (or nonstandard) exchange: later dims can't fuse
    if not any(fuse):
        return None
    return tuple(fuse)


def _plane_halo_kernel(Tm_ref, Tc_ref, Tp_ref, Cp_ref, out_ref, *,
                       lam, dt, dx, dy, dz, nx, fuse):
    """One output x-plane of the fused step + self-neighbor halo update.

    Inputs are (1, ny, nz) planes: source plane and its two x-neighbors of T
    plus Cp. The flux arithmetic is in the EXACT accumulation order of the
    reference example (`-d_xa(qx)/dx - d_ya(qy)/dy - d_za(qz)/dz`, then
    `/Cp`, then `T + dt*dTdt` — `diffusion3D_multicpu_novis.jl:42-47`) so
    results match the XLA flux-form step to the last ulp or two. Boundary
    planes/rows/lanes keep their input values (the reference updates the
    interior only), then come the halo writes of the reference's
    self-neighbor local path (`update_halo.jl:62-68`) folded into the same
    output pass, in the reference's exact dim order z, x, y
    (`update_halo.jl:29,45`):

    - z/y halos are in-plane copies (lane/row selects on the computed plane);
    - the x halo re-sources output plane 0 from updated plane nx-2 and plane
      nx-1 from updated plane 1 (``sigma`` in the BlockSpec index maps), so
      the halo planes are recomputed rather than staged — two extra
      plane-triple reads total, no extra array pass.

    Corner semantics match the reference because the z edits are applied to
    the computed plane BEFORE it is used as an x/y halo source, exactly like
    the sequential exchange.
    """
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    fuse_x, fuse_y, fuse_z = fuse
    i = pl.program_id(0)
    tc = Tc_ref[0]
    ny, nz = tc.shape
    upd = _stencil_plane(Tm_ref[0], tc, Tp_ref[0], Cp_ref[0],
                         lam=lam, dt=dt, dx=dx, dy=dy, dz=dz)

    row = lax.broadcasted_iota(jnp.int32, (ny, nz), 0)
    col = lax.broadcasted_iota(jnp.int32, (ny, nz), 1)
    sp = _sigma(i, nx) if fuse_x else i
    interior_yz = (row > 0) & (row < ny - 1) & (col > 0) & (col < nz - 1)
    u = jnp.where(interior_yz & (sp > 0) & (sp < nx - 1), upd, tc)
    if fuse_z:  # halo lanes <- own interior lanes (broadcast column selects)
        u = jnp.where(col == 0, u[:, nz - 2:nz - 1], u)
        u = jnp.where(col == nz - 1, u[:, 1:2], u)
    if fuse_y:  # after z (and x via sigma), like the sequential exchange
        u = jnp.where(row == 0, u[ny - 2:ny - 1, :], u)
        u = jnp.where(row == ny - 1, u[1:2, :], u)
    out_ref[0] = u


def _sigma(i, nx):
    """Source plane of output plane ``i`` under the fused x halo update."""
    import jax.numpy as jnp

    return jnp.where(i == 0, nx - 2, jnp.where(i == nx - 1, 1, i))


def diffusion3d_step_halo_pallas(T, Cp, *, lam, dt, dx, dy, dz, fuse,
                                 interpret=False):
    """Fused diffusion step + self-neighbor halo exchange on a LOCAL 3-D
    block. ``fuse`` = (fuse_x, fuse_y, fuse_z) from `fusable_halo_dims`;
    non-fused dims behave exactly like `diffusion3d_step_pallas` (exchange
    them afterwards with `local_update_halo`)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    nx, ny, nz = T.shape
    plane = (1, ny, nz)
    fuse_x = bool(fuse[0])
    dtp = _const_dtype(T.dtype)
    kernel = partial(
        _plane_halo_kernel,
        lam=dtp(lam), dt=dtp(dt), dx=dtp(dx), dy=dtp(dy), dz=dtp(dz),
        nx=nx, fuse=tuple(bool(f) for f in fuse),
    )

    def src(off):
        def index_map(i):
            s = _sigma(i, nx) if fuse_x else i
            return (jnp.clip(s + off, 0, nx - 1), 0, 0)
        return index_map

    try:
        out_shape = jax.ShapeDtypeStruct(T.shape, T.dtype, vma=jax.typeof(T).vma)
    except (AttributeError, TypeError):
        out_shape = jax.ShapeDtypeStruct(T.shape, T.dtype)

    return pl.pallas_call(
        kernel,
        grid=(nx,),
        in_specs=[
            pl.BlockSpec(plane, src(-1)),
            pl.BlockSpec(plane, src(0)),
            pl.BlockSpec(plane, src(+1)),
            pl.BlockSpec(plane, src(0)),
        ],
        out_specs=pl.BlockSpec(plane, lambda i: (i, 0, 0)),
        out_shape=out_shape,
        interpret=interpret,
    )(T, T, T, Cp)


def diffusion3d_step_pallas(T, Cp, *, lam, dt, dx, dy, dz, interpret=False):
    """One fused diffusion step on a LOCAL 3-D block (no halo exchange —
    compose with `local_update_halo`). The ``fuse=(False, False, False)``
    specialization of `diffusion3d_step_halo_pallas` — one shared kernel so
    the ulp-sensitive accumulation order cannot diverge between the paths."""
    return diffusion3d_step_halo_pallas(
        T, Cp, lam=lam, dt=dt, dx=dx, dy=dy, dz=dz,
        fuse=(False, False, False), interpret=interpret,
    )


# ---------------------------------------------------------------------------
# Fused step + MULTI-SHARD exchange: the flagship path on real pods.
#
# `fusable_halo_dims` only covers self-neighbor (single-shard periodic) dims;
# on a pod every axis is multi-shard and the round-1 design fell back to
# step-kernel + separate exchange (~4 array passes/step). This path keeps the
# whole step at ~2 passes where z does not cross chips:
#
#   1. compute the POST-update send slabs from thin input slabs (XLA — a few
#      planes or rows; valid because the update is a radius-1 stencil and
#      the send slabs sit >= 1 cell inside the block). A z slab is a lane
#      column: it pads to 128 lanes in memory, a large share of a pass;
#   2. run the `exchange_recv_slabs` pipeline on them (ppermutes / local
#      swaps, slab-level corner patching, PROC_NULL masking) — the permutes
#      depend ONLY on the thin slabs, so XLA's scheduler overlaps them with
#      the step kernel's plane sweep;
#   3. ONE Pallas pass computes the update for the whole block AND writes
#      the received slabs (z lanes -> x planes -> y rows precedence, same
#      corner argument as `halo_write_combined_pallas`).
#
# A self-neighbor z (periodic, one shard) therefore skips the pipeline: its
# halo is a pair of lane copies, applied to the kernel's computed planes and
# to each x/y send slab (`step_exchange_folds_z`).
# ---------------------------------------------------------------------------


def step_exchange_modes(gg, T):
    """Participation modes for the fused step+exchange, or None.

    Eligible when every EXCHANGING dim has the default overlap 2 and
    halowidth 1 and the block is unstaggered (``T.shape == nxyz`` — the
    flagship model's fields), with at least one exchanging dim. Self and
    multi-shard dims mix freely (self dims become local swaps in the slab
    pipeline). 2-D blocks are eligible too (the returned 3-tuple then has
    ``modes[2] = False``; grid dims beyond the array's rank never apply to
    it, mirroring `ops.halo._dim_exchanges`)."""
    if T.ndim not in (2, 3) or T.shape[0] < 3:
        return None
    if tuple(int(s) for s in T.shape) != tuple(
            int(n) for n in gg.nxyz[:T.ndim]):
        return None
    modes = [False, False, False]
    for dim in range(T.ndim):
        D = int(gg.dims[dim])
        periodic = bool(gg.periods[dim])
        disp = int(gg.disp)
        if D == 1 and not periodic:
            continue
        if D > 1 and not periodic and disp >= D:
            continue
        if int(gg.overlaps[dim]) != 2 or int(gg.halowidths[dim]) != 1:
            return None
        modes[dim] = True
    if not any(modes):
        return None
    return tuple(modes)


def step_exchange_folds_z(gg, modes) -> bool:
    """Whether the fused step+exchange folds the z halo in place of
    exchanging it: z exchanges (``modes[2]``) as a self-neighbor (one
    shard, periodic, displacement 1; overlap 2 and halowidth 1 are
    `step_exchange_modes`'s own gate)."""
    return (bool(modes[2]) and int(gg.dims[2]) == 1
            and bool(gg.periods[2]) and int(gg.disp) == 1)


def _fold_z_halo(u):
    """The self-neighbor z halo update as two lane selects over the last
    axis: lane 0 <- lane nz-2, then lane nz-1 <- lane 1 (the reference's
    local path, `update_halo.jl:62-68`, at overlap 2, halowidth 1)."""
    import jax.numpy as jnp
    from jax import lax

    nz = u.shape[-1]
    col = lax.broadcasted_iota(jnp.int32, u.shape, u.ndim - 1)
    u = jnp.where(col == 0, u[..., nz - 2:nz - 1], u)
    return jnp.where(col == nz - 1, u[..., 1:2], u)


def _xla_update_slab(T, Cp, dim, start, size, consts):
    """Updated-state values at ``[start, start+size)`` along ``dim`` (full
    extent elsewhere), computed from a thin input slab grown by the stencil
    radius (1). Works for 3-D and 2-D blocks (`_stencil_plane` /
    `_stencil_row` arithmetic respectively).

    Cells on the GLOBAL block boundary keep their input values. Slab-edge
    x-neighbors are edge-clones; this is sound because for every range this
    is called with (send slabs at depth >= 1, current-halo slabs at the
    boundary itself) the emitted cells either have their true neighbors
    in-slab or are boundary cells masked back to their input values."""
    import jax.numpy as jnp
    from jax import lax

    s = T.shape[dim]
    lo = max(start - 1, 0)
    hi = min(start + size + 1, s)
    Ts = lax.slice_in_dim(T, lo, hi, axis=dim)
    Cs = lax.slice_in_dim(Cp, lo, hi, axis=dim)
    tm = jnp.concatenate([Ts[:1], Ts[:-1]], axis=0)
    tp = jnp.concatenate([Ts[1:], Ts[-1:]], axis=0)
    stencil = _stencil_plane if T.ndim == 3 else _stencil_row
    upd = stencil(tm, Ts, tp, Cs, **consts)
    # global-interior mask (dim positions offset by lo; other dims span the
    # full block so slab positions are global)
    m = None
    for d in range(T.ndim):
        pos = lax.broadcasted_iota(jnp.int32, Ts.shape, d)
        if d == dim:
            pos = pos + lo
            n_d = s
        else:
            n_d = Ts.shape[d]
        md = (pos > 0) & (pos < n_d - 1)
        m = md if m is None else m & md
    out = jnp.where(m, upd, Ts)
    return lax.slice_in_dim(out, start - lo, start - lo + size, axis=dim)


def _plane_step_recv_kernel(*refs, nx, modes, self_z, lam, dt, dx, dy, dz):
    """One output plane of the fused step + exchange: compute the update,
    then deliver the received halo slabs (z lanes, then x whole planes, then
    y rows — the reference's write order restricted to this plane; received
    planes replace the computed one entirely, carrying their own corners).
    With ``self_z`` the z lanes are the plane's own (`_fold_z_halo`)."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    it = iter(refs)
    tm_ref, tc_ref, tp_ref, cp_ref = (next(it) for _ in range(4))
    rx_ref = next(it) if modes[0] else None
    ry_ref = next(it) if modes[1] else None
    rz_ref = next(it) if modes[2] else None
    o_ref = refs[-1]

    i = pl.program_id(0)
    tc = tc_ref[0]
    ny, nz = tc.shape
    upd = _stencil_plane(tm_ref[0], tc, tp_ref[0], cp_ref[0],
                         lam=lam, dt=dt, dx=dx, dy=dy, dz=dz)
    row = lax.broadcasted_iota(jnp.int32, (ny, nz), 0)
    col = lax.broadcasted_iota(jnp.int32, (ny, nz), 1)
    interior_yz = (row > 0) & (row < ny - 1) & (col > 0) & (col < nz - 1)
    u = jnp.where(interior_yz & (i > 0) & (i < nx - 1), upd, tc)
    if self_z:
        u = _fold_z_halo(u)
    if modes[2]:  # halowidth 1 throughout (step_exchange_modes)
        u = jnp.where(col == 0, rz_ref[0, :, 0:1], u)
        u = jnp.where(col == nz - 1, rz_ref[0, :, 1:2], u)
    if modes[0]:
        u = jnp.where(i == 0, rx_ref[0], jnp.where(i == nx - 1, rx_ref[1], u))
    if modes[1]:
        u = jnp.where(row == 0, ry_ref[0, 0:1, :], u)
        u = jnp.where(row == ny - 1, ry_ref[0, 1:2, :], u)
    o_ref[0] = u


def _mp_step_recv_kernel(*refs, nx, P, modes, self_z, lam, dt, dx, dy, dz,
                         handoff=False):
    """Multi-plane form of `_plane_step_recv_kernel`: P output planes per
    program from a double-buffered (P+2)-plane T window (`_window_pipeline`
    — the same HBM-traffic win as `_mp_kernel`), each delivered its
    received slabs in the z, x, y order (z folded in place with
    ``self_z``)."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    it = iter(refs)
    T_hbm = next(it)
    cp_ref = next(it)                              # (P, ny, nz)
    rx_ref = next(it) if modes[0] else None        # (2, ny, nz) const
    ry_ref = next(it) if modes[1] else None        # (P, 2, nz)
    rz_ref = next(it) if modes[2] else None        # (P, ny, 2)
    out_ref = refs[-3]
    scratch = refs[-2]
    sems = refs[-1]

    if handoff:   # static: VMEM overlap handoff, 1.0x T reads
        win, l0 = _window_pipeline_handoff(T_hbm, scratch, sems, nx=nx, B=P)
    else:
        win, l0 = _window_pipeline(T_hbm, scratch, sems, nx=nx, B=P)
    g0 = pl.program_id(0) * P

    ny, nz = out_ref.shape[1:]
    row = lax.broadcasted_iota(jnp.int32, (ny, nz), 0)
    col = lax.broadcasted_iota(jnp.int32, (ny, nz), 1)
    interior_yz = (row > 0) & (row < ny - 1) & (col > 0) & (col < nz - 1)

    for j in range(P):
        g = g0 + j
        l = l0 + j
        tc = win[pl.ds(l, 1)][0]
        tm = win[pl.ds(jnp.maximum(l - 1, 0), 1)][0]
        tp = win[pl.ds(jnp.minimum(l + 1, P + 1), 1)][0]
        upd = _stencil_plane(tm, tc, tp, cp_ref[j],
                             lam=lam, dt=dt, dx=dx, dy=dy, dz=dz)
        u = jnp.where(interior_yz & (g > 0) & (g < nx - 1), upd, tc)
        if self_z:
            u = _fold_z_halo(u)
        if modes[2]:  # halowidth 1 throughout (step_exchange_modes)
            u = jnp.where(col == 0, rz_ref[j, :, 0:1], u)
            u = jnp.where(col == nz - 1, rz_ref[j, :, 1:2], u)
        if modes[0]:
            u = jnp.where(g == 0, rx_ref[0],
                          jnp.where(g == nx - 1, rx_ref[1], u))
        if modes[1]:
            u = jnp.where(row == 0, ry_ref[j, 0:1, :], u)
            u = jnp.where(row == ny - 1, ry_ref[j, 1:2, :], u)
        out_ref[j] = u


def diffusion3d_step_exchange_pallas(T, Cp, gg, modes, *, lam, dt, dx, dy,
                                     dz, interpret=False):
    """Fused diffusion step + full halo exchange for arbitrary shardings
    (see module comment above): thin-slab send computation -> the shared
    `exchange_recv_slabs` pipeline -> one Pallas pass for update + delivery.
    Uses the multi-plane window kernel where the shape gate passes
    ((1+2/P)x T reads), else the plane-per-program form (3x). Matches
    `diffusion3d_step_pallas` followed by the exchange to ulp level:
    the slab computes share `_stencil_plane`'s accumulation order, but they
    run through XLA while the block runs through Mosaic, and fma contraction
    can differ in the last ulp between the compilers (module docstring)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .halo import exchange_recv_slabs

    nx, ny, nz = T.shape
    plane = (1, ny, nz)
    dtp = _const_dtype(T.dtype)
    consts = dict(lam=dtp(lam), dt=dtp(dt), dx=dtp(dx), dy=dtp(dy), dz=dtp(dz))

    from .precision import resolve_wire_dtype

    # A self-neighbor z stays out of the pipeline: every x/y slab (sends
    # and PROC_NULL current halos) takes the z lane copy first, which is
    # what patching it with the z recvs gives, since z comes first.
    self_z = step_exchange_folds_z(gg, modes)
    modes = (bool(modes[0]), bool(modes[1]), bool(modes[2]) and not self_z)

    def get_slab(dim, start, size):
        slab = _xla_update_slab(T, Cp, dim, start, size, consts)
        return _fold_z_halo(slab) if self_z else slab

    recvs = exchange_recv_slabs(gg, T.shape, (1, 1, 1), modes, get_slab,
                                wire=resolve_wire_dtype(None))

    P = mp_planes(T, interpret=interpret)
    mp = P is not None
    blk = (P, ny, nz) if mp else plane

    operands = []
    in_specs = []
    if mp:
        operands += [T, Cp]
        in_specs += [
            pl.BlockSpec(memory_space=pl.ANY),      # T: manual DMA window
            pl.BlockSpec(blk, lambda i: (i, 0, 0)),
        ]
    else:
        operands += [T, T, T, Cp]
        in_specs += [
            pl.BlockSpec(plane, lambda i: (jnp.maximum(i - 1, 0), 0, 0)),
            pl.BlockSpec(plane, lambda i: (i, 0, 0)),
            pl.BlockSpec(plane, lambda i: (jnp.minimum(i + 1, nx - 1), 0, 0)),
            pl.BlockSpec(plane, lambda i: (i, 0, 0)),
        ]
    if modes[0]:
        rx = jnp.concatenate(recvs[0], axis=0)          # (2, ny, nz)
        operands.append(rx)
        in_specs.append(pl.BlockSpec((2, ny, nz), lambda i: (0, 0, 0)))
    if modes[1]:
        ry = jnp.concatenate(recvs[1], axis=1)          # (nx, 2, nz)
        operands.append(ry)
        in_specs.append(pl.BlockSpec((blk[0], 2, nz), lambda i: (i, 0, 0)))
    if modes[2]:
        rz = jnp.concatenate(recvs[2], axis=2)          # (nx, ny, 2)
        operands.append(rz)
        in_specs.append(pl.BlockSpec((blk[0], ny, 2), lambda i: (i, 0, 0)))

    vma = None
    try:
        vma = jax.typeof(T).vma
        for op in operands[1:]:
            vma = vma | jax.typeof(op).vma
        out_shape = jax.ShapeDtypeStruct(T.shape, T.dtype, vma=vma)
    except (AttributeError, TypeError):
        out_shape = jax.ShapeDtypeStruct(T.shape, T.dtype)

    if mp:
        kernel = partial(_mp_step_recv_kernel, nx=nx, P=P,
                         handoff=mp_handoff(T, interpret=interpret),
                         modes=modes, self_z=self_z, **consts)
        return pl.pallas_call(
            kernel,
            grid=(nx // P,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(blk, lambda i: (i, 0, 0)),
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((2, P + 2, ny, nz), T.dtype),
                            pltpu.SemaphoreType.DMA((2,))],
            interpret=interpret,
            **_sequential_grid_params(interpret),
        )(*operands)

    kernel = partial(_plane_step_recv_kernel, nx=nx, modes=modes,
                     self_z=self_z, **consts)
    return pl.pallas_call(
        kernel,
        grid=(nx,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(plane, lambda i: (i, 0, 0)),
        out_shape=out_shape,
        interpret=interpret,
    )(*operands)


# ---------------------------------------------------------------------------
# Multi-plane variant: P output planes per program through a DMA'd window.
# ---------------------------------------------------------------------------

_MP_CANDIDATES = (32, 16, 8, 4)    # preferred plane counts, best first


_MP_VMEM_BUDGET = 13 * 1024 * 1024  # leave headroom under the ~16 MB VMEM


_MP_TEMP_PLANES = 6  # slack for Mosaic stencil temporaries (qy/qz/acc/masks)


def _compute_itemsize(dtype) -> int:
    """Bytes per element of the stencil's COMPUTE dtype: bf16 states are
    computed in f32 (`_stencil_plane`), so their temporaries cost 4 B."""
    return max(int(dtype.itemsize), 4) if dtype.itemsize < 4 \
        else int(dtype.itemsize)


def _const_dtype(dtype):
    """Scalar constructor for the kernel constants: f32 for bf16 states
    (quantizing dx/dt to bf16 would put ~0.4% systematic error into every
    flux term that the f32 compute path is meant to avoid), the state's own
    dtype otherwise."""
    import jax.numpy as jnp
    import numpy as np

    if dtype == jnp.bfloat16:
        return np.float32
    return dtype.type


def _sublane_tile(dtype) -> int:
    """Rows per native sublane tile: 8 for f32, 16 for bf16 (the (8,128)
    f32 / (16,128) bf16 TPU tilings). The single source of truth for every
    alignment gate and the strip kernel's halo-tile height."""
    import numpy as np

    return max(1, 32 // int(np.dtype(dtype).itemsize))


def window_dma_ok(shape, dtype) -> bool:
    """Whether the manual HBM->VMEM window DMA of `_window_pipeline` is
    known-good for blocks whose last two dims are ``shape[-2:]``: the copy
    requires NATIVE-TILE alignment — lane dim a multiple of 128 and sublane
    dim a multiple of the dtype's sublane tile (8 for f32, 16 for bf16).
    Mosaic rejects the dynamic-start HBM slice on partially-tiled shapes
    (verified on v5e: (…, 192)-lane windows fail to compile), so callers
    must fall back to the BlockSpec-pipelined kernels."""
    return (int(shape[-1]) % 128 == 0
            and int(shape[-2]) % _sublane_tile(dtype) == 0)


def mp_planes(T, interpret=False):
    """Plane count P for the multi-plane kernel, or None if unsupported.

    Picks the largest candidate P that divides the plane axis with >= 2
    programs and whose VMEM working set fits: double-buffered (P+2)-plane T
    windows (2*(P+2)) plus double-buffered Cp in and out blocks (2*P each)
    in STORAGE dtype, plus per-plane temporaries slack in COMPUTE dtype
    (bf16 computes in f32). Larger P amortizes the 2-plane window overlap
    (T read amplification 1+2/P); the plane-per-program kernel is the
    fallback for everything else — including lane/sublane-unaligned
    blocks, which the window DMA cannot copy (`window_dma_ok`; a
    Mosaic-compile-only constraint, so interpret mode skips it and keeps
    the multi-plane kernels under test at small shapes)."""
    if T.ndim != 3:
        return None
    if not interpret and not window_dma_ok(T.shape, T.dtype):
        return None
    cells = int(T.shape[1]) * int(T.shape[2])
    plane_store = cells * T.dtype.itemsize
    plane_compute = cells * _compute_itemsize(T.dtype)
    for P in _MP_CANDIDATES:
        if T.shape[0] % P or T.shape[0] < 2 * P:
            continue
        working_set = (6 * P + 4) * plane_store \
            + _MP_TEMP_PLANES * plane_compute
        if working_set <= _MP_VMEM_BUDGET:
            return P
    return None


def mp_supported(T, interpret=False) -> bool:
    """Whether the multi-plane kernel applies (see `mp_planes`)."""
    return mp_planes(T, interpret=interpret) is not None


def _stencil_plane(tm, tc, tp, cp, *, lam, dt, dx, dy, dz):
    """The flux-form update of one plane (or a 3-D slab — y/z derivatives
    run over the LAST two axes) — the single shared arithmetic (same
    accumulation order as the reference example and the plane-per-program
    kernel). bfloat16 inputs are computed in f32 and cast back (bf16
    storage, f32 arithmetic — the TPU-native mixed-precision recipe; the
    flux differences would otherwise lose most of their bits)."""
    import jax.numpy as jnp

    out_dt = tc.dtype
    if out_dt == jnp.bfloat16:
        tm, tc, tp, cp = (a.astype(jnp.float32) for a in (tm, tc, tp, cp))
    zeros = [(0, 0)] * (tc.ndim - 2)
    qxr = -lam * (tp - tc) / dx
    qxl = -lam * (tc - tm) / dx
    acc = -((qxr - qxl) / dx)
    qy = -lam * (tc[..., 1:, :] - tc[..., :-1, :]) / dy
    acc = acc - jnp.pad((qy[..., 1:, :] - qy[..., :-1, :]) / dy,
                        zeros + [(1, 1), (0, 0)])
    qz = -lam * (tc[..., :, 1:] - tc[..., :, :-1]) / dz
    acc = acc - jnp.pad((qz[..., :, 1:] - qz[..., :, :-1]) / dz,
                        zeros + [(0, 0), (1, 1)])
    return (tc + dt * (acc / cp)).astype(out_dt)


def _stencil_row(tm, tc, tp, cp, *, lam, dt, dx, dy):
    """2-D flux-form update of a row strip: the x-derivative comes from the
    ``tm``/``tc``/``tp`` row triple, the y-derivative runs over the LAST
    axis — same accumulation order as the XLA 2-D step
    (`models/diffusion.upd2`, mirroring the reference example's sequence).
    bfloat16 inputs compute in f32 like `_stencil_plane`."""
    import jax.numpy as jnp

    out_dt = tc.dtype
    if out_dt == jnp.bfloat16:
        tm, tc, tp, cp = (a.astype(jnp.float32) for a in (tm, tc, tp, cp))
    zeros = [(0, 0)] * (tc.ndim - 1)
    qxr = -lam * (tp - tc) / dx
    qxl = -lam * (tc - tm) / dx
    acc = -((qxr - qxl) / dx)
    qy = -lam * (tc[..., 1:] - tc[..., :-1]) / dy
    acc = acc - jnp.pad((qy[..., 1:] - qy[..., :-1]) / dy, zeros + [(1, 1)])
    return (tc + dt * (acc / cp)).astype(out_dt)


def _window_pipeline_general(ref, scratch, sems, *, size, start_fn):
    """Double-buffered HBM->VMEM window fetch across SEQUENTIAL grid
    programs: program i starts the DMA of window i+1 into the other buffer
    slot before waiting on its own, so the next window's reads ride under
    this window's compute. Window g covers ``[start_fn(g), +size)`` along
    axis 0 (uniform size). The grid MUST run in order — callers pass
    ``dimension_semantics=("arbitrary",)``. Returns this program's window
    ref."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i = pl.program_id(0)
    nprog = pl.num_programs(0)

    def window_dma(slot, g):
        return pltpu.make_async_copy(
            ref.at[pl.ds(start_fn(g), size)], scratch.at[slot],
            sems.at[slot])

    @pl.when(i == 0)
    def _():
        window_dma(0, 0).start()

    @pl.when(i + 1 < nprog)
    def _():
        window_dma((i + 1) % 2, i + 1).start()

    slot = i % 2
    window_dma(slot, i).wait()
    return scratch.at[slot]


def _window_pipeline(T_hbm, scratch, sems, *, nx, B):
    """The stencil kernels' standard window: ``[clip(g*B-1, 0, nx-(B+2)),
    +B+2)`` (one neighbor plane each side, clamped at the global edges).
    Returns ``(window_ref, l0)`` where ``l0`` is the window index of global
    position ``i*B``."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def wstart(g):
        return jnp.clip(g * B - 1, 0, nx - (B + 2))

    win = _window_pipeline_general(T_hbm, scratch, sems, size=B + 2,
                                   start_fn=wstart)
    i = pl.program_id(0)
    return win, i * B - wstart(i)


def _window_pipeline_handoff(ref, scratch, sems, *, nx, B):
    """`_window_pipeline` with a VMEM HANDOFF of the window overlap:
    program i copies the 2-3 overlap planes from the tail of ITS window
    into the head of the next window's slot and prefetches only the NEW
    planes from HBM — total T reads become exactly ``nx`` planes (1.0x)
    instead of the plain pipeline's (1+2/P)x re-read.

    Overlap bookkeeping (windows ``[clip(g*B-1, 0, nx-(B+2)), +B+2)``,
    ``nx % B == 0``, ``m = nx//B`` programs): the clamp at both global
    edges makes the overlap 3 planes into windows 1 and m-1 and 2 planes
    into every interior window; with m == 2 it would be 4 (callers use the
    plain pipeline there). Total fetched = (B+2) + 2(B-1) + (m-3)B = mB =
    nx exactly.

    The prefetch DMA (head-disjoint) still starts BEFORE this window's
    wait, so next-window HBM reads ride under this window's compute; the
    handoff copy runs after the wait (its source must be complete) as
    plane-aligned direct stores (an async VMEM->VMEM DMA form tripped an
    XLA CPU fusion codegen crash in interpret mode), and the sequential
    grid guarantees it lands before program i+1 reads it. Requires m >= 3
    and the same in-order execution as the plain pipeline."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i = pl.program_id(0)
    m = pl.num_programs(0)
    S = B + 2

    def wstart(g):
        return jnp.clip(g * B - 1, 0, nx - S)

    def full_dma(slot, g):
        return pltpu.make_async_copy(
            ref.at[pl.ds(wstart(g), S)], scratch.at[slot], sems.at[slot])

    def partial_dma(slot, g, o):  # fetch the S-o NEW planes (o static)
        return pltpu.make_async_copy(
            ref.at[pl.ds(wstart(g) + o, S - o)],
            scratch.at[slot, pl.ds(o, S - o)], sems.at[slot])

    cur, nxt = i % 2, (i + 1) % 2
    edge_next = (i + 1 == 1) | (i + 1 == m - 1)
    edge_cur = (i == 1) | (i == m - 1)

    @pl.when(i == 0)
    def _():
        full_dma(0, 0).start()

    # prefetch next window's NEW planes (disjoint from its handoff head)
    @pl.when((i + 1 < m) & edge_next)
    def _():
        partial_dma(nxt, i + 1, 3).start()

    @pl.when((i + 1 < m) & ~edge_next)
    def _():
        partial_dma(nxt, i + 1, 2).start()

    # wait on OUR window (descriptor must match the copy that filled it)
    @pl.when(i == 0)
    def _():
        full_dma(0, 0).wait()

    @pl.when((i > 0) & edge_cur)
    def _():
        partial_dma(cur, i, 3).wait()

    @pl.when((i > 0) & ~edge_cur)
    def _():
        partial_dma(cur, i, 2).wait()

    # hand the overlap planes to the next window in VMEM (direct stores:
    # plane-aligned, static sizes)
    @pl.when((i + 1 < m) & edge_next)
    def _():
        scratch[nxt, pl.ds(0, 3)] = scratch[cur, pl.ds(S - 3, 3)]

    @pl.when((i + 1 < m) & ~edge_next)
    def _():
        scratch[nxt, pl.ds(0, 2)] = scratch[cur, pl.ds(S - 2, 2)]

    return scratch.at[cur], i * B - wstart(i)


def handoff_ok(nx, P) -> bool:
    """The shared window-handoff gate for every kernel family: >= 3
    windows (the 2-window case has a 4-plane overlap and keeps the plain
    re-reading `_window_pipeline`)."""
    return P is not None and nx // P >= 3


def mp_handoff(T, interpret=False) -> bool:
    """Whether the multi-plane kernel uses the VMEM window handoff (1.0x T
    reads) for this shape: needs >= 3 windows, else the plain (1+2/P)x
    pipeline."""
    return handoff_ok(int(T.shape[0]), mp_planes(T, interpret=interpret))


def mp_bytes_per_cell(T, interpret=False):
    """Traffic model of the multi-plane kernel for this shape (bench.py's
    roofline accounting): T reads 1.0x with the window handoff else
    (1+2/P)x, + Cp read 1x + T write 1x, in storage itemsize."""
    P = mp_planes(T, interpret=interpret)
    t_reads = 1.0 if mp_handoff(T, interpret=interpret) \
        else (1.0 + 2.0 / P if P else 3.0)
    return (t_reads + 2.0) * T.dtype.itemsize


def _window_pipeline_aligned_handoff(ref, scratch, sems, *, size, B):
    """Handoff form of the ALIGNED window ``[g*B, g*B+size)`` (uniform
    overlap ``o = size - B``, no clamping — e.g. the acoustic Vx face
    window, size=P+1): program i hands the o overlap planes across in
    VMEM and prefetches only the B new planes. Total fetch = size +
    (m-1)*B = nx + o exactly. Works for any m >= 2 (the overlap is
    uniform, unlike the clamped `_window_pipeline_handoff`). Same
    sequential-grid contract as the other pipelines."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i = pl.program_id(0)
    m = pl.num_programs(0)
    o = size - B

    def full_dma(slot, g):
        return pltpu.make_async_copy(
            ref.at[pl.ds(g * B, size)], scratch.at[slot], sems.at[slot])

    def partial_dma(slot, g):
        return pltpu.make_async_copy(
            ref.at[pl.ds(g * B + o, B)],
            scratch.at[slot, pl.ds(o, B)], sems.at[slot])

    cur, nxt = i % 2, (i + 1) % 2

    @pl.when(i == 0)
    def _():
        full_dma(0, 0).start()

    @pl.when(i + 1 < m)
    def _():
        partial_dma(nxt, i + 1).start()

    @pl.when(i == 0)
    def _():
        full_dma(0, 0).wait()

    @pl.when(i > 0)
    def _():
        partial_dma(cur, i).wait()

    @pl.when(i + 1 < m)
    def _():
        scratch[nxt, pl.ds(0, o)] = scratch[cur, pl.ds(size - o, o)]

    return scratch.at[cur]


def _sequential_grid_params(interpret):
    """pallas_call kwargs forcing in-order grid execution (required by the
    cross-program DMA handoff of `_window_pipeline`)."""
    if interpret:
        return {}
    from jax.experimental.pallas import tpu as pltpu

    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("arbitrary",))}


def _mp_kernel(T_hbm, Cp_ref, out_ref, scratch, sems, *,
               lam, dt, dx, dy, dz, nx, P, fuse, handoff=False):
    """Compute P output planes from a (P+2)-plane VMEM window of T.

    The window is DMA'd once per program, so interior T planes are read
    ~(1+2/P)x instead of the 3x of the plane-per-program kernel's three
    BlockSpec streams — the stencil's dominant HBM term. The window DMA is
    DOUBLE-BUFFERED across grid programs (program i starts the fetch of
    window i+1 before computing window i, the standard overlap pattern), so
    the HBM reads of the next window ride under this window's VPU work just
    like the auto-pipelined Cp/out streams; the grid must therefore execute
    sequentially ("arbitrary" dimension semantics, set by the caller).
    z/y halo edits are in-plane selects like `_plane_halo_kernel`; x halo
    planes (if fused) are NOT handled here —
    `diffusion3d_step_halo_pallas_mp` patches them with the in-place dim-0
    halo write afterwards.
    """
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    fuse_x, fuse_y, fuse_z = fuse
    if handoff:   # static: VMEM overlap handoff, 1.0x T reads
        win, l0 = _window_pipeline_handoff(T_hbm, scratch, sems, nx=nx, B=P)
    else:
        win, l0 = _window_pipeline(T_hbm, scratch, sems, nx=nx, B=P)
    g0 = pl.program_id(0) * P                    # first output plane

    ny, nz = out_ref.shape[1:]
    row = lax.broadcasted_iota(jnp.int32, (ny, nz), 0)
    col = lax.broadcasted_iota(jnp.int32, (ny, nz), 1)
    interior_yz = (row > 0) & (row < ny - 1) & (col > 0) & (col < nz - 1)

    for j in range(P):
        g = g0 + j
        l = l0 + j
        tc = win[pl.ds(l, 1)][0]
        tm = win[pl.ds(jnp.maximum(l - 1, 0), 1)][0]      # clamps at g==0
        tp = win[pl.ds(jnp.minimum(l + 1, P + 1), 1)][0]  # ... at g==nx-1
        upd = _stencil_plane(tm, tc, tp, Cp_ref[j],
                             lam=lam, dt=dt, dx=dx, dy=dy, dz=dz)
        u = jnp.where(interior_yz & (g > 0) & (g < nx - 1), upd, tc)
        if fuse_z:
            u = jnp.where(col == 0, u[:, nz - 2:nz - 1], u)
            u = jnp.where(col == nz - 1, u[:, 1:2], u)
        if fuse_y:
            u = jnp.where(row == 0, u[ny - 2:ny - 1, :], u)
            u = jnp.where(row == ny - 1, u[1:2, :], u)
        out_ref[j] = u


def diffusion3d_step_halo_pallas_mp(T, Cp, *, lam, dt, dx, dy, dz, fuse,
                                    interpret=False):
    """Multi-plane fused step (+ self-neighbor halo): the faster form of
    `diffusion3d_step_halo_pallas` for blocks with `mp_supported` shapes.
    Identical semantics; x halo planes (when fused) are recomputed at slab
    level in XLA and written in place by the dim-0 halo kernel."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nx, ny, nz = T.shape
    P = mp_planes(T, interpret=interpret)
    blk = (P, ny, nz)
    dtp = _const_dtype(T.dtype)
    consts = dict(lam=dtp(lam), dt=dtp(dt), dx=dtp(dx), dy=dtp(dy), dz=dtp(dz))
    handoff = mp_handoff(T, interpret=interpret)
    kernel = partial(_mp_kernel, nx=nx, P=P, handoff=handoff,
                     fuse=tuple(bool(f) for f in fuse), **consts)

    try:
        out_shape = jax.ShapeDtypeStruct(T.shape, T.dtype, vma=jax.typeof(T).vma)
    except (AttributeError, TypeError):
        out_shape = jax.ShapeDtypeStruct(T.shape, T.dtype)

    kwargs = _sequential_grid_params(interpret)
    U = pl.pallas_call(
        kernel,
        grid=(nx // P,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),          # T: manual DMA window
            pl.BlockSpec(blk, lambda i: (i, 0, 0)),     # Cp
        ],
        out_specs=pl.BlockSpec(blk, lambda i: (i, 0, 0)),
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((2, P + 2, ny, nz), T.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
        interpret=interpret,
        **kwargs,
    )(T, Cp)

    if not fuse[0]:
        return U
    # Fused x halo: plane 0 <- updated plane nx-2 (with z edits), plane nx-1
    # <- updated plane 1, then their y halo rows — computed at slab level
    # (reference order z, x, y; same corner argument as _plane_halo_kernel)
    # and written IN PLACE by the dim-0 halo kernel (2-plane write).
    from .pallas_halo import halo_write_inplace

    def patch(src):  # src: global index of the source plane
        tm = lax.slice_in_dim(T, src - 1, src, axis=0)[0]
        tc = lax.slice_in_dim(T, src, src + 1, axis=0)[0]
        tp = lax.slice_in_dim(T, src + 1, src + 2, axis=0)[0]
        cp = lax.slice_in_dim(Cp, src, src + 1, axis=0)[0]
        upd = _stencil_plane(tm, tc, tp, cp, **consts)
        row = lax.broadcasted_iota(jnp.int32, (ny, nz), 0)
        col = lax.broadcasted_iota(jnp.int32, (ny, nz), 1)
        interior_yz = (row > 0) & (row < ny - 1) & (col > 0) & (col < nz - 1)
        u = jnp.where(interior_yz, upd, tc)     # src planes are x-interior
        if fuse[2]:
            u = jnp.where(col == 0, u[:, nz - 2:nz - 1], u)
            u = jnp.where(col == nz - 1, u[:, 1:2], u)
        if fuse[1]:
            u = jnp.where(row == 0, u[ny - 2:ny - 1, :], u)
            u = jnp.where(row == ny - 1, u[1:2, :], u)
        return u[None]

    return halo_write_inplace(U, patch(nx - 2), patch(1), dim=0, hw=1,
                              interpret=interpret)


# ---------------------------------------------------------------------------
# 2-D fused step + exchange (BASELINE config 2): row strips through a
# double-buffered VMEM window, same structure as the 3-D multi-plane path.
# ---------------------------------------------------------------------------

_STRIP2D_CANDIDATES = (256, 128, 64, 32, 16, 8)


def strip_rows_2d(T, interpret=False):
    """Rows per program R for the 2-D strip kernel, or None if unsupported.

    Working set: double-buffered R-row T bodies (+2 halo rows) plus
    double-buffered Cp in and out blocks (2R rows each) in STORAGE dtype,
    plus the shifted-window temporaries of the vectorized strip compute
    (~6R rows) in COMPUTE dtype (bf16 computes in f32). Compiled mode
    additionally requires native-tile-aligned shapes for the strip DMA
    (`window_dma_ok`); interpret mode (tests) has no such constraint."""
    if T.ndim != 2:
        return None
    row_store = int(T.shape[1]) * T.dtype.itemsize
    row_compute = int(T.shape[1]) * _compute_itemsize(T.dtype)
    if not interpret and not window_dma_ok(T.shape, T.dtype):
        return None
    sublane = _sublane_tile(T.dtype)
    for R in _STRIP2D_CANDIDATES:
        if T.shape[0] % R or T.shape[0] < 2 * R:
            continue
        if R % sublane:
            # Body slices must start on tile-row boundaries, and the halo
            # tiles' clamp arithmetic assumes R % H == 0 — in interpret mode
            # too (the kernel's row picks would silently be wrong otherwise).
            continue
        if (6 * R + 8) * row_store + 6 * R * row_compute <= _MP_VMEM_BUDGET:
            return R
    return None


def _strip2d_kernel(*refs, nx, R, H, modes, lam, dt, dx, dy):
    """Compute R output rows from a manually DMA'd VMEM strip of T, then
    deliver the received halo slabs: x whole rows first, then y lanes — the
    exchange order for 2-D blocks (dims 0 then 1 of the z, x, y default;
    the y slabs carry x's received corners via the slab pipeline's
    patching).

    The strip fetch is split into an ALIGNED R-row body plus two H-row halo
    tiles bracketing it (H = the dtype's sublane tile): 2-D arrays are
    tiled in BOTH dims, so every HBM slice must be tile-row aligned and
    sized (Mosaic rejects 1-row slices and dynamic-offset multi-row vector
    loads alike); the rows just above/below the strip are the last/first
    rows of those tiles. Tile fetches clamp at the global edges, where the
    garbage row only reaches globally-masked boundary rows. Body and below
    tiles are double-buffered across the sequential grid like
    `_window_pipeline`; every above tile after the first is the previous
    body's last H rows, handed across in VMEM. tm/tp are edge-patched
    shifts of the body."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    it = iter(refs)
    T_hbm = next(it)
    cp_ref = next(it)
    rx_ref = next(it) if modes[0] else None       # (2, ny)
    ry_ref = next(it) if modes[1] else None       # (R, 2) strip
    o_ref = refs[-5]                              # outs precede scratches
    body_scr, above_scr, below_scr, sems = refs[-4:]

    i = pl.program_id(0)
    nprog = pl.num_programs(0)

    def ds(start, size):
        # every start is a multiple of the H-row tile by construction
        # (R % H == 0, nx % H == 0 — `strip_rows_2d`); Mosaic needs the
        # explicit hint to slice the row-tiled 2-D memref at a traced index
        return pl.ds(pl.multiple_of(start, H), size)

    def body_dma(slot, g):
        return pltpu.make_async_copy(
            T_hbm.at[ds(g * R, R)], body_scr.at[slot], sems.at[slot, 0])

    def above_dma(slot, g):
        return pltpu.make_async_copy(
            T_hbm.at[ds(jnp.maximum(g * R - H, 0), H)],
            above_scr.at[slot], sems.at[slot, 1])

    def below_dma(slot, g):
        return pltpu.make_async_copy(
            T_hbm.at[ds(jnp.minimum(g * R + R, nx - H), H)],
            below_scr.at[slot], sems.at[slot, 2])

    @pl.when(i == 0)
    def _():
        body_dma(0, 0).start()
        below_dma(0, 0).start()
        above_dma(0, 0).start()

    @pl.when(i + 1 < nprog)
    def _():
        body_dma((i + 1) % 2, i + 1).start()
        below_dma((i + 1) % 2, i + 1).start()

    slot = i % 2
    body_dma(slot, i).wait()
    below_dma(slot, i).wait()
    # the above tile for g >= 1 is the tail of the PREVIOUS body — handed
    # across in VMEM by the previous program; only program 0 fetched its
    # (edge-clamped) above tile from HBM
    @pl.when(i == 0)
    def _():
        above_dma(0, 0).wait()

    @pl.when(i + 1 < nprog)
    def _():
        above_scr[(i + 1) % 2] = body_scr[slot][R - H:, :]

    g0 = i * R
    tc = body_scr[slot]                                        # (R, ny)
    row_above = above_scr[slot][H - 1:H]  # last row of the tile ending at g0
    row_below = below_scr[slot][0:1]   # first row of the tile after the body
    tm = jnp.concatenate([row_above, tc[:-1]], axis=0)
    tp = jnp.concatenate([tc[1:], row_below], axis=0)
    upd = _stencil_row(tm, tc, tp, cp_ref[...], lam=lam, dt=dt, dx=dx, dy=dy)

    ny = tc.shape[1]
    g = g0 + lax.broadcasted_iota(jnp.int32, (R, ny), 0)   # global row index
    col = lax.broadcasted_iota(jnp.int32, (R, ny), 1)
    interior = (g > 0) & (g < nx - 1) & (col > 0) & (col < ny - 1)
    u = jnp.where(interior, upd, tc)
    if modes[0]:  # x rows first (received rows replace them entirely)
        u = jnp.where(g == 0, rx_ref[0:1], u)
        u = jnp.where(g == nx - 1, rx_ref[1:2], u)
    if modes[1]:  # then y lanes (their slabs carry x's received corners)
        u = jnp.where(col == 0, ry_ref[:, 0:1], u)
        u = jnp.where(col == ny - 1, ry_ref[:, 1:2], u)
    o_ref[...] = u


def diffusion2d_step_exchange_pallas(T, Cp, gg, modes, *, lam, dt, dx, dy,
                                     interpret=False):
    """Fused 2-D diffusion step + halo exchange for arbitrary shardings —
    the 2-D analog of `diffusion3d_step_exchange_pallas`: thin-slab send
    computation in XLA -> the shared `exchange_recv_slabs` pipeline
    (ppermutes / local swaps / PROC_NULL masking) -> one strip-pipelined
    Pallas pass for update + delivery."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .halo import exchange_recv_slabs

    nx, ny = T.shape
    R = strip_rows_2d(T, interpret=interpret)
    dtp = _const_dtype(T.dtype)
    consts = dict(lam=dtp(lam), dt=dtp(dt), dx=dtp(dx), dy=dtp(dy))

    from .precision import resolve_wire_dtype

    recvs = exchange_recv_slabs(
        gg, T.shape, (1, 1), modes,
        lambda dim, start, size: _xla_update_slab(T, Cp, dim, start, size,
                                                  consts),
        wire=resolve_wire_dtype(None))

    blk = (R, ny)
    operands = [T, Cp]
    in_specs = [
        pl.BlockSpec(memory_space=pl.ANY),            # T: manual DMA window
        pl.BlockSpec(blk, lambda i: (i, 0)),          # Cp
    ]
    if modes[0]:
        rx = jnp.concatenate(recvs[0], axis=0)        # (2, ny)
        operands.append(rx)
        in_specs.append(pl.BlockSpec((2, ny), lambda i: (0, 0)))
    if modes[1]:
        ry = jnp.concatenate(recvs[1], axis=1)        # (nx, 2)
        operands.append(ry)
        in_specs.append(pl.BlockSpec((R, 2), lambda i: (i, 0)))

    try:
        vma = jax.typeof(T).vma
        for op in operands[1:]:
            vma = vma | jax.typeof(op).vma
        out_shape = jax.ShapeDtypeStruct(T.shape, T.dtype, vma=vma)
    except (AttributeError, TypeError):
        out_shape = jax.ShapeDtypeStruct(T.shape, T.dtype)

    H = _sublane_tile(T.dtype)
    # the above-tile handoff needs no gate: the overlap is uniform and
    # `strip_rows_2d` guarantees >= 2 strips
    kernel = partial(_strip2d_kernel, nx=nx, R=R, H=H,
                     modes=tuple(bool(m) for m in modes), **consts)
    kwargs = _sequential_grid_params(interpret)
    return pl.pallas_call(
        kernel,
        grid=(nx // R,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(blk, lambda i: (i, 0)),
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((2, R, ny), T.dtype),
                        pltpu.VMEM((2, H, ny), T.dtype),
                        pltpu.VMEM((2, H, ny), T.dtype),
                        pltpu.SemaphoreType.DMA((2, 3))],
        interpret=interpret,
        **kwargs,
    )(*operands)
