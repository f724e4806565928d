"""Fused Pallas pass for the pseudo-transient Stokes iteration — the kernel
tier for BASELINE config 5 (`models/stokes.py`).

One PT iteration reads the 8-field state and writes 7 arrays, with a
4-field halo exchange at the end. The XLA formulation materializes the
stress intermediates and pays ~2 extra passes for the exchange unpack; this
module runs the WHOLE iteration — divergence, pressure, stresses, damped
momentum, velocity updates, AND the (Vx, Vy, Vz, Pn) halo delivery — as one
plane-pipelined Pallas pass (the Stokes analog of
`pallas_wave.acoustic_step_exchange_pallas`).

Soundness of fusing the exchange: every update reads only the PRE-step
state (the sequential order is update-everything, then exchange), so the
send slabs are computed from local thin windows. The slab computes reuse
`models.stokes._stokes_terms` on MINI-STATES — all 8 fields sliced to a
3-cell (cell-target) or 2-cell (face-target) window around the slab — whose
central values are exactly the full-step values (the stencil radius fits
the window; `_inner`'s trims align the mini interior with the target).
Received slabs flow through the shared PACKED pipeline
(`exchange_recv_slabs_multi`: the 4 exchanged fields' slabs ride ONE
ppermute pair per mesh axis on the canonical wire schema — wire policy
included — plus local swaps / PROC_NULL masking / per-field corner
patching), and are delivered in the kernel's output pass in the
reference's z, x, y order. A self-neighbor z beside crossing x/y dims
stays out of the pipeline (`stokes_exchange_folds_z`): its halo is a pair
of lane copies, applied to the kernel's computed planes and to every x/y
slab the pipeline asks for. Vx's extra face plane (and dVx's, which is
not exchanged) is written post-kernel like the acoustic kernel's.

Requires the full-size face-aligned dV state of `init_stokes3d` and
halowidth-1 grids; `stokes_exchange_modes` gates eligibility.
"""

from __future__ import annotations

from functools import partial

from .pallas_common import slab1 as _slab

__all__ = ["SCOPES", "stokes_exchange_modes", "stokes_exchange_folds_z",
           "stokes_step_exchange_pallas"]

# Device-side name scopes of the traced step (`jax.named_scope`): they reach
# each op's HLO metadata, so a profiler trace's op events carry them in the
# ``tf_op`` stat of their event metadata. "pt": the fused PT pass (the
# Mosaic custom call); "slabs": the send-slab getters' computes; "vx_planes":
# the post-kernel writes of Vx's and dVx's extra x planes.
SCOPES = {"pt": "igg.stokes.pt", "slabs": "igg.stokes.slabs",
          "vx_planes": "igg.stokes.vx_planes"}

# Scoped VMEM of the fused pass where it takes received y/z slabs: beside
# the 11 state planes, 7 output planes and the full-plane intermediates
# they bring a 256^3 local block to 16.25 MiB inside the chunk loop, over
# Mosaic's 16 MiB default. Every TPU generation has at least 32 MiB of
# VMEM. Passes without them keep the default: a larger limit takes VMEM
# from XLA's own placements of the ops around the kernel.
_VMEM_LIMIT_BYTES = 32 * 1024 * 1024


def stokes_exchange_modes(gg, shapes):
    """Per-field participation modes for the fused PT iteration, or None.

    ``shapes`` = the 8 state shapes (P, Vx, Vy, Vz, dVx, dVy, dVz, rhog).
    Eligible when the shapes follow the model's staggering pattern (dV
    mirroring V) and every halowidth is 1. Returns ``{"P": modes, "Vx":
    ..., "Vy": ..., "Vz": ...}`` for the exchanged fields (all-False modes
    mean a pure fused update with no deliveries)."""
    from .halo import _dim_exchanges

    sp, sx, sy, sz, sdx, sdy, sdz, srh = (
        tuple(int(v) for v in s) for s in shapes)
    if len(sp) != 3 or sp[0] < 3:
        return None
    if sp != tuple(int(n) for n in gg.nxyz) or srh != sp:
        return None
    nx, ny, nz = sp
    if sx != (nx + 1, ny, nz) or sy != (nx, ny + 1, nz) \
            or sz != (nx, ny, nz + 1):
        return None
    if (sdx, sdy, sdz) != (sx, sy, sz):
        return None
    if any(int(h) != 1 for h in gg.halowidths):
        return None
    hws = (1, 1, 1)
    out = {}
    for name, s in (("P", sp), ("Vx", sx), ("Vy", sy), ("Vz", sz)):
        out[name] = tuple(_dim_exchanges(gg, s, hws, d) for d in range(3))
    # all-False modes are still eligible: the kernel then fuses the whole
    # PT iteration into one pass with no deliveries (single-chip
    # non-periodic — the BASELINE bench configuration)
    return out


def stokes_exchange_folds_z(gg, modes) -> bool:
    """Whether the fused PT pass folds the z halo in place of exchanging
    it: some field exchanges z as a self-neighbor (one shard, periodic,
    displacement 1) on a grid that is not all-self (the all-self path
    delivers every dim in the kernel already)."""
    from .pallas_common import all_self_exchange

    return (any(bool(m[2]) for m in modes.values())
            and int(gg.dims[2]) == 1 and bool(gg.periods[2])
            and int(gg.disp) == 1 and not all_self_exchange(gg, modes))


def _mini_state(state, dim, lo, hi):
    """All 8 fields sliced to the cell-window ``[lo, hi)`` along ``dim``
    (face-staggered fields get one extra layer)."""
    from jax import lax

    nc = state[0].shape[dim]
    out = []
    for a in state:
        hi_a = hi + 1 if a.shape[dim] == nc + 1 else hi
        out.append(lax.slice_in_dim(a, lo, hi_a, axis=dim))
    return tuple(out)


def _pn_get_slab(state, p):
    """get_slab for Pn: the pressure update on a width-1 cell window (the
    update is unmasked — every cell, incl. boundaries, gets it). Computed
    directly (same div+update arithmetic as `_stokes_terms`) because the
    1-cell window is too narrow for the stress terms' `_inner` trims."""
    from ..models.stokes import _d

    def get(dim, start, size):
        assert size == 1
        Pm, Vxm, Vym, Vzm = _mini_state(state, dim, start, start + 1)[:4]
        divV = (_d(Vxm, 0) / p.dx + _d(Vym, 1) / p.dy + _d(Vzm, 2) / p.dz)
        return Pm - p.dt_p * divV
    return get


def _v_get_slab(state, p, which):
    """get_slab for velocity ``which`` (0=x,1=y,2=z): the full PT update on
    a mini-state window; non-interior targets return raw slices (faces on
    the global boundary are never updated)."""
    from ..models.stokes import _stokes_terms

    V = state[1 + which]

    def get(dim, start, size):
        assert size == 1
        n = V.shape[dim]
        if start < 1 or start > n - 2:
            return _slab(V, dim, start)
        stag = which == dim
        lo, hi = (start - 1, start + 1) if stag else (start - 1, start + 2)
        mini = _mini_state(state, dim, lo, hi)
        terms = _stokes_terms(mini, p)
        R = terms[2:][which]                  # (Rx, Ry, Rz)[which]
        Vm = mini[1 + which]
        dVm = mini[4 + which]
        ix = (slice(1, -1),) * 3
        dnew = p.damp * dVm[ix] + R
        Vn = Vm.at[ix].add(p.dt_v * dnew)
        return _slab(Vn, dim, start - lo)
    return get


from .pallas_common import recv_kinds as _stokes_recv_kinds


def _stokes_kernel(*refs, nx, modes, mu, dt_v, dt_p, damp, dx, dy, dz,
                   self_ols=None, self_z=None):
    """One x-plane of the fused PT iteration. Arithmetic mirrors
    `models.stokes._stokes_terms` term-for-term (same accumulation order)
    restricted to this plane; then the interior-masked dV/V updates and the
    halo deliveries (z, x, y per field; Vx's x planes post-kernel). With
    ``self_z`` (field -> z overlap) the z halo is folded from the plane's
    own lanes (`fold_z_lanes`) and no z recv operand is taken. The
    ``[i-1]`` planes of P, Vx, Vy and Vz arrive by VMEM relay
    (`pallas_common.plane_relay`), not as HBM streams.

    Every intermediate stays at FULL plane size, positioned on a canonical
    grid and shifted with the edge-cloning operators of `pallas_common`
    (Mosaic cannot lower interior-slice-then-pad — see `shift_up`); edge
    garbage only ever reaches rows/lanes the interior masks cut away.
    Canonical grids: cell quantities on (ny, nz); x-y edge stresses
    ``txyE[e] = txy(edge e-1/2)`` on (ny, nz); x-z edges ``txzE[:, f]`` on
    (ny, nz); y-z edges ``tyzE[f, g]`` on (ny, nz) (valid from index 1 in
    each edge direction)."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    from .pallas_common import deliver_recvs as _deliver
    from .pallas_common import fold_z_lanes, plane_relay
    from .pallas_common import shift_down, shift_left, shift_right, shift_up

    it = iter(refs)
    p_c = next(it)[0]
    vxc, vxp = (next(it)[0] for _ in range(2))
    vyc, vyp = (next(it)[0] for _ in range(2))
    vzc, vzp = (next(it)[0] for _ in range(2))
    dvxc = next(it)[0]
    dvyc = next(it)[0]
    dvzc = next(it)[0]
    rhc = next(it)[0]

    from .pallas_common import take_recvs

    kinds = dict(_stokes_recv_kinds(self_ols is not None))
    rP = take_recvs(it, modes, "P", kinds["P"])
    rVx = take_recvs(it, modes, "Vx", kinds["Vx"])
    rVy = take_recvs(it, modes, "Vy", kinds["Vy"])
    rVz = take_recvs(it, modes, "Vz", kinds["Vz"])

    i = pl.program_id(0)
    oP, oVx, oVy, oVz, odVx, odVy, odVz = refs[-11:-4]
    relP, relVx, relVy, relVz = refs[-4:]
    p_m = plane_relay(relP, i, p_c)
    vxm = plane_relay(relVx, i, vxc)
    vym = plane_relay(relVy, i, vyc)
    vzm = plane_relay(relVz, i, vzc)
    ny, nz = p_c.shape

    def d_y(a):  # cell-centred face difference (full size: (ny+1,.) -> (ny,.))
        return a[1:, :] - a[:-1, :]

    def d_z(a):
        return a[:, 1:] - a[:, :-1]

    # --- _stokes_terms restricted to cells i (c) and i-1 (m) --------------
    divc = (vxp - vxc) / dx + d_y(vyc) / dy + d_z(vzc) / dz
    divm = (vxc - vxm) / dx + d_y(vym) / dy + d_z(vzm) / dz
    pnc = p_c - dt_p * divc
    pnm = p_m - dt_p * divm
    txxc = 2 * mu * ((vxp - vxc) / dx - divc / 3)
    txxm = 2 * mu * ((vxc - vxm) / dx - divm / 3)
    tyyc = 2 * mu * (d_y(vyc) / dy - divc / 3)
    tzzc = 2 * mu * (d_z(vzc) / dz - divc / 3)
    # edge stresses on canonical full-size grids: txyE[e] at y-edge e-1/2 of
    # the x-edge carried by face i; txyEp at face i+1 (valid rows e >= 1)
    txyE = mu * ((vxc - shift_down(vxc)) / dy + (vyc - vym)[:ny] / dx)
    txyEp = mu * ((vxp - shift_down(vxp)) / dy + (vyp - vyc)[:ny] / dx)
    txzE = mu * ((vxc - shift_right(vxc)) / dz + (vzc - vzm)[:, :nz] / dx)
    txzEp = mu * ((vxp - shift_right(vxp)) / dz + (vzp - vzc)[:, :nz] / dx)
    tyzE = mu * ((vyc - shift_right(vyc))[:ny] / dz
                 + (vzc - shift_down(vzc))[:, :nz] / dy)

    # residuals, full size (same accumulation order as `_stokes_terms`):
    # RxF on cells (valid 1..ny-2, 1..nz-2), RyF on y-faces 1..ny-1 (cell
    # cols 1..nz-2), RzF on z-faces 1..nz-1 (cell rows 1..ny-2)
    RxF = (((txxc - pnc) - (txxm - pnm)) / dx
           + (shift_up(txyE) - txyE) / dy
           + (shift_left(txzE) - txzE) / dz)
    Ty = tyyc - pnc
    RyF = ((Ty - shift_down(Ty)) / dy + (txyEp - txyE) / dx
           + (shift_left(tyzE) - tyzE) / dz)
    Tz = tzzc - pnc
    RzF = ((Tz - shift_right(Tz)) / dz + (txzEp - txzE) / dx
           + (shift_up(tyzE) - tyzE) / dy
           + 0.5 * (rhc + shift_right(rhc)))

    # --- interior-masked damped-momentum + velocity updates ---------------
    row = lax.broadcasted_iota(jnp.int32, (ny, nz), 0)
    col = lax.broadcasted_iota(jnp.int32, (ny, nz), 1)
    rowy = lax.broadcasted_iota(jnp.int32, (ny + 1, nz), 0)
    coly = lax.broadcasted_iota(jnp.int32, (ny + 1, nz), 1)
    rowz = lax.broadcasted_iota(jnp.int32, (ny, nz + 1), 0)
    colz = lax.broadcasted_iota(jnp.int32, (ny, nz + 1), 1)
    face_ok = (i >= 1) & (i <= nx - 1)
    cell_ok = (i >= 1) & (i <= nx - 2)

    mx = face_ok & (row > 0) & (row < ny - 1) & (col > 0) & (col < nz - 1)
    dnx = damp * dvxc + RxF
    u_dvx = jnp.where(mx, dnx, dvxc)
    u_vx = jnp.where(mx, vxc + dt_v * dnx, vxc)

    my = cell_ok & (rowy > 0) & (rowy < ny) & (coly > 0) & (coly < nz - 1)
    dny = damp * dvyc + jnp.concatenate([RyF, RyF[-1:]], axis=0)
    u_dvy = jnp.where(my, dny, dvyc)
    u_vy = jnp.where(my, vyc + dt_v * dny, vyc)

    mz = cell_ok & (rowz > 0) & (rowz < ny - 1) & (colz > 0) & (colz < nz)
    dnz = damp * dvzc + jnp.concatenate([RzF, RzF[:, -1:]], axis=1)
    u_dvz = jnp.where(mz, dnz, dvzc)
    u_vz = jnp.where(mz, vzc + dt_v * dnz, vzc)

    # --- halo deliveries (z, x, y per field) ------------------------------
    if self_ols is not None:
        from .pallas_common import self_deliver

        u_vx = self_deliver(u_vx, i, nx, modes["Vx"], None,
                            *self_ols["Vx"])
        u_vy = self_deliver(u_vy, i, nx, modes["Vy"], rVy["x"],
                            *self_ols["Vy"])
        u_vz = self_deliver(u_vz, i, nx, modes["Vz"], rVz["x"],
                            *self_ols["Vz"])
        pn = self_deliver(pnc, i, nx, modes["P"], rP["x"], *self_ols["P"])
    else:
        ol_z = self_z or {}
        u_vx = _deliver(fold_z_lanes(u_vx, ol_z.get("Vx")), i, nx,
                        modes["Vx"], None, rVx["y"], rVx["z"], ny - 1, nz - 1)
        u_vy = _deliver(fold_z_lanes(u_vy, ol_z.get("Vy")), i, nx,
                        modes["Vy"], rVy["x"], rVy["y"], rVy["z"], ny, nz - 1)
        u_vz = _deliver(fold_z_lanes(u_vz, ol_z.get("Vz")), i, nx,
                        modes["Vz"], rVz["x"], rVz["y"], rVz["z"], ny - 1, nz)
        pn = _deliver(fold_z_lanes(pnc, ol_z.get("P")), i, nx, modes["P"],
                      rP["x"], rP["y"], rP["z"], ny - 1, nz - 1)

    oP[0] = pn
    oVx[0] = u_vx
    oVy[0] = u_vy
    oVz[0] = u_vz
    odVx[0] = u_dvx
    odVy[0] = u_dvy
    odVz[0] = u_dvz


def _scope(part):
    """The named scope ``SCOPES[part]``, a context manager."""
    import jax

    return jax.named_scope(SCOPES[part])


def _scoped(get, ol_z=None):
    """A send-slab getter whose computes carry the ``slabs`` scope; with
    ``ol_z`` each slab takes the field's folded z halo (`fold_z_lanes`)."""
    from .pallas_common import fold_z_lanes

    def scoped_get(dim, start, size):
        with _scope("slabs"):
            return fold_z_lanes(get(dim, start, size), ol_z)
    return scoped_get


def stokes_step_exchange_pallas(state, gg, modes, p, *, interpret=False):
    """One fused PT iteration (all updates + the 4-field halo exchange) for
    arbitrary shardings. ``modes`` from `stokes_exchange_modes`."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    from .halo import exchange_recv_slabs_multi
    from .precision import resolve_wire_dtype

    P, Vx, Vy, Vz, dVx, dVy, dVz, rhog = state
    nx, ny, nz = P.shape
    dtp = P.dtype.type
    hws = (1, 1, 1)

    from .pallas_common import all_self_exchange, self_recvs_and_ols

    shapes = {"P": P.shape, "Vx": Vx.shape, "Vy": Vy.shape, "Vz": Vz.shape}
    fold = {}  # field -> z overlap, where the z halo is folded
    if stokes_exchange_folds_z(gg, modes):
        # a self-neighbor z stays out of the pipeline: every x/y slab
        # (sends and PROC_NULL current halos) takes the z lane copy first,
        # which is what patching it with the z recvs gives, since z comes
        # first; the kernel folds its computed planes the same way
        fold = {f: int(gg.overlaps[2]) + int(s[2]) - int(gg.nxyz[2])
                for f, s in shapes.items() if modes[f][2]}
        modes = {f: (m[0], m[1], False) for f, m in modes.items()}
    getters = {
        "Vx": _scoped(_v_get_slab(state, p, 0), fold.get("Vx")),
        "Vy": _scoped(_v_get_slab(state, p, 1), fold.get("Vy")),
        "Vz": _scoped(_v_get_slab(state, p, 2), fold.get("Vz")),
        "P": _scoped(_pn_get_slab(state, p), fold.get("P")),
    }
    all_self = all_self_exchange(gg, modes)
    self_ols = None
    if all_self:
        # single-shard periodic on every exchanging dim: y/z halos become
        # in-plane selects inside the kernel, x slabs are raw updated
        # source planes (see pallas_wave / pallas_common.self_deliver)
        recvs, self_ols = self_recvs_and_ols(gg, shapes, modes, getters)
    else:
        # the shared packed pipeline: ONE ppermute pair per mesh axis for
        # the 4 exchanged fields, on the canonical wire schema + policy
        recvs = exchange_recv_slabs_multi(gg, shapes, hws, modes, getters,
                                          wire=resolve_wire_dtype(None))

    def spec(shape, index_map):
        return pl.BlockSpec(shape, index_map)

    cP = (1, ny, nz)
    cY = (1, ny + 1, nz)
    cZ = (1, ny, nz + 1)
    # the [i-1] planes come from the in-kernel VMEM relay: 11 HBM input
    # streams, not 15
    operands = [P, Vx, Vx, Vy, Vy, Vz, Vz, dVx, dVy, dVz, rhog]
    in_specs = [
        spec(cP, lambda i: (i, 0, 0)),                        # P[i]
        spec(cP, lambda i: (i, 0, 0)),                        # Vx[i]
        spec(cP, lambda i: (i + 1, 0, 0)),                    # Vx[i+1]
        spec(cY, lambda i: (i, 0, 0)),                        # Vy[i]
        spec(cY, lambda i: (jnp.minimum(i + 1, nx - 1), 0, 0)),
        spec(cZ, lambda i: (i, 0, 0)),                        # Vz[i]
        spec(cZ, lambda i: (jnp.minimum(i + 1, nx - 1), 0, 0)),
        spec(cP, lambda i: (i, 0, 0)),                        # dVx[i]
        spec(cY, lambda i: (i, 0, 0)),                        # dVy[i]
        spec(cZ, lambda i: (i, 0, 0)),                        # dVz[i]
        spec(cP, lambda i: (i, 0, 0)),                        # rhog[i]
    ]

    from .pallas_common import add_recv_operands, out_shape_with_vma

    def add_recvs(field, kinds, shapes_specs):
        add_recv_operands(operands, in_specs, modes, recvs, field, kinds,
                          shapes_specs)

    c0 = lambda i: (0, 0, 0)
    ci = lambda i: (i, 0, 0)
    all_specs = {
        "P": [(0, (2, ny, nz), c0), (1, (1, 2, nz), ci),
              (2, (1, ny, 2), ci)],
        "Vx": [(1, (1, 2, nz), ci), (2, (1, ny, 2), ci)],
        "Vy": [(0, (2, ny + 1, nz), c0), (1, (1, 2, nz), ci),
               (2, (1, ny + 1, 2), ci)],
        "Vz": [(0, (2, ny, nz + 1), c0), (1, (1, 2, nz + 1), ci),
               (2, (1, ny, 2), ci)],
    }
    from .pallas_common import add_all_recvs

    add_all_recvs(operands, in_specs, modes, recvs, all_specs, all_self)

    def out_shape_of(a):
        return out_shape_with_vma(a, operands)

    kernel = partial(
        _stokes_kernel, nx=nx,
        modes={k: tuple(bool(b) for b in v) for k, v in modes.items()},
        mu=dtp(p.mu), dt_v=dtp(p.dt_v), dt_p=dtp(p.dt_p), damp=dtp(p.damp),
        dx=dtp(p.dx), dy=dtp(p.dy), dz=dtp(p.dz), self_ols=self_ols,
        self_z=fold)

    from jax.experimental.pallas import tpu as pltpu

    extra = {"scratch_shapes": [pltpu.VMEM((2, ny, nz), P.dtype),
                                pltpu.VMEM((2, ny, nz), Vx.dtype),
                                pltpu.VMEM((2, ny + 1, nz), Vy.dtype),
                                pltpu.VMEM((2, ny, nz + 1), Vz.dtype)]}
    recv_yz = not all_self and any(m[1] or m[2] for m in modes.values())
    if not interpret:
        extra["compiler_params"] = pltpu.CompilerParams(
            # the relay needs the grid in order
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES if recv_yz else None)

    with _scope("pt"):
        Pn, Vxn, Vyn, Vzn, dVxn, dVyn, dVzn = pl.pallas_call(
            kernel,
            grid=(nx,),
            in_specs=in_specs,
            out_specs=[
                spec(cP, lambda i: (i, 0, 0)),
                spec(cP, lambda i: (i, 0, 0)),
                spec(cY, lambda i: (i, 0, 0)),
                spec(cZ, lambda i: (i, 0, 0)),
                spec(cP, lambda i: (i, 0, 0)),
                spec(cY, lambda i: (i, 0, 0)),
                spec(cZ, lambda i: (i, 0, 0)),
            ],
            out_shape=[out_shape_of(a) for a in
                       (P, Vx, Vy, Vz, dVx, dVy, dVz)],
            interpret=interpret,
            **extra,
        )(*operands)

    # Vx plane nx (the kernel grid covers planes 0..nx-1): delivered like
    # the acoustic kernel's; dVx plane nx is never updated nor exchanged —
    # rewritten with its raw values.
    from .pallas_common import vx_extra_plane_slabs, vx_extra_planes_self
    from .pallas_halo import halo_write_inplace

    with _scope("vx_planes"):
        if all_self:
            plane0, planeN = vx_extra_planes_self(
                Vx, Vxn, recvs["Vx"], modes["Vx"], self_ols["Vx"], nx)
        else:
            plane0, planeN = vx_extra_plane_slabs(Vx, Vxn, recvs["Vx"],
                                                  modes["Vx"], nx,
                                                  fold.get("Vx"))
        Vxn = halo_write_inplace(Vxn, plane0, planeN, dim=0, hw=1,
                                 interpret=interpret)
        dVxn = halo_write_inplace(
            dVxn, lax.slice_in_dim(dVx, 0, 1, axis=0),
            lax.slice_in_dim(dVx, nx, nx + 1, axis=0), dim=0, hw=1,
            interpret=interpret)
    return (Pn, Vxn, Vyn, Vzn, dVxn, dVyn, dVzn, rhog)
