"""Fused Pallas pass for the acoustic leapfrog step — the kernel tier for
the staggered-grid wave model (BASELINE config 4).

The XLA formulation of one acoustic step (`models/acoustic.py`) costs ~5
array passes over 4 fields: the three velocity updates, their 3-field halo
exchange, the pressure update, and its exchange. This module fuses the WHOLE
step — both updates AND both exchanges — into one plane-pipelined Pallas
pass over all four fields (the staggered-field analog of
`pallas_stencil.diffusion3d_step_exchange_pallas`, and of the reference's
kernel tier serving every field type, `CUDAExt/update_halo.jl:143-146`).

Why one pass is semantically sound (halowidth-1 fields):

- The velocity update touches only INTERIOR faces and reads only P — no
  received values needed.
- Velocity SEND slabs sit >= 1 face inside the block, so they are computed
  from local P alone (`_xla_update_slab`-style thin-slab computes); the
  received slabs come from the shared PACKED pipeline
  (`exchange_recv_slabs_multi`: all four fields' slabs ride ONE ppermute
  pair per mesh axis on the canonical wire schema — the same wire, and
  the same `IGG_HALO_WIRE_DTYPE` policy, the XLA tier ships — plus local
  swaps / PROC_NULL masking / corner patching).
- The pressure update needs post-exchange V faces ONLY at cells that are
  themselves P halo cells: every surviving cell of every P send slab is
  interior in the cross dimensions (its cross-dim edge cells are either
  patched from earlier dims' recvs before sending or overwritten by later
  dims' recvs after delivery — the z, x, y order), and interior cells read
  only locally-updated faces. At PROC_NULL edges the kept faces are the
  un-updated boundary faces — exactly the local raw values. Hence the P
  send slabs are computed from LOCAL updated V values only, and the fused
  pass reproduces the sequential update->exchange->update->exchange result.

Delivery order inside the kernel is the reference's z, x, y per field; Vx's
x-extent (nx+1 planes) exceeds the grid (nx programs), so its two x halo
planes are written afterwards by the in-place dim-0 kernel with slabs whose
y rows are patched from the y recvs (preserving the x-before-y order).
"""

from __future__ import annotations

from functools import partial

from .pallas_common import deliver_recvs as _deliver
from .pallas_common import slab1 as _slab

__all__ = ["wave_exchange_modes", "acoustic_step_exchange_pallas"]


def wave_exchange_modes(gg, shapes):
    """Per-field participation modes for the fused acoustic step, or None.

    ``shapes`` = (P, Vx, Vy, Vz) local shapes. Eligible when the shapes
    follow the model's staggering pattern (faces on +1 axes) and every
    grid halowidth is 1 (the delivery selects hardwire width-1 halos).
    Returns a dict ``{"P": modes, "Vx": modes, ...}`` of 3-tuples
    (all-False modes mean a pure fused update with no deliveries)."""
    from .halo import _dim_exchanges

    sp, sx, sy, sz = (tuple(int(v) for v in s) for s in shapes)
    if len(sp) != 3 or sp[0] < 3:
        return None
    if sp != tuple(int(n) for n in gg.nxyz):
        return None
    nx, ny, nz = sp
    if sx != (nx + 1, ny, nz) or sy != (nx, ny + 1, nz) \
            or sz != (nx, ny, nz + 1):
        return None
    if any(int(h) != 1 for h in gg.halowidths):
        return None
    hws = (1, 1, 1)
    out = {}
    for name, s in (("P", sp), ("Vx", sx), ("Vy", sy), ("Vz", sz)):
        out[name] = tuple(_dim_exchanges(gg, s, hws, d) for d in range(3))
    # all-False modes are still eligible: the kernel then fuses both
    # updates into one pass with no deliveries (single-chip non-periodic)
    return out


def _upd_vx_plane(Vx, P, f, c):
    """Updated Vx face plane ``f`` (static index): interior faces get the
    leapfrog P-gradient update, boundary faces (0, nx) keep their values
    (reference `Vx.at[1:-1].add`, `models/acoustic.py`)."""
    from jax import lax

    nx1 = Vx.shape[0]
    v = lax.slice_in_dim(Vx, f, f + 1, axis=0)
    if f < 1 or f > nx1 - 2:
        return v
    pm = lax.slice_in_dim(P, f - 1, f, axis=0)
    pc = lax.slice_in_dim(P, f, f + 1, axis=0)
    return v + c * (pc - pm)


def _upd_v_inplane(V, P, axis, c):
    """All ``axis``-faces of V updated from P within a slab spanning the
    full ``axis`` extent: interior faces via the padded P difference (the
    pad zeroes the update at boundary faces, keeping them raw)."""
    import jax.numpy as jnp
    from jax import lax

    n = P.shape[axis]
    d = (lax.slice_in_dim(P, 1, n, axis=axis)
         - lax.slice_in_dim(P, 0, n - 1, axis=axis))
    pads = [(0, 0)] * P.ndim
    pads[axis] = (1, 1)
    return V + c * jnp.pad(d, pads)


def _make_v_get_slab(V, P, axis, c):
    """get_slab for a velocity field staggered along ``axis``: returns the
    POST-update values of the width-1 slab at ``start`` along ``dim``."""
    def get(dim, start, size):
        assert size == 1
        if dim == axis:
            if axis == 0:
                return _upd_vx_plane(V, P, start, c)
            Vs = _slab(V, dim, start)  # one face layer; needs P start-1,start
            if start < 1 or start > V.shape[dim] - 2:
                return Vs
            return Vs + c * (_slab(P, dim, start) - _slab(P, dim, start - 1))
        # slab across the staggered axis: update all its axis-faces locally
        return _upd_v_inplane(_slab(V, dim, start), _slab(P, dim, start),
                              axis, c)
    return get


def _make_p_get_slab(P, Vx, Vy, Vz, cx, cy, cz, dtK, dx, dy, dz):
    """get_slab for P: POST-update pressure on the width-1 slab, computed
    from LOCALLY updated faces only (see module docstring for why received
    faces are never needed on surviving cells)."""
    from jax import lax

    def div_term(Vn, axis, dd):
        n = Vn.shape[axis]
        return (lax.slice_in_dim(Vn, 1, n, axis=axis)
                - lax.slice_in_dim(Vn, 0, n - 1, axis=axis)) / dd

    def get(dim, start, size):
        assert size == 1
        Ps = _slab(P, dim, start)
        if dim == 0:
            vxa = _upd_vx_plane(Vx, P, start, cx)
            vxb = _upd_vx_plane(Vx, P, start + 1, cx)
            divx = (vxb - vxa) / dx
            vyn = _upd_v_inplane(_slab(Vy, 0, start), Ps, 1, cy)
            vzn = _upd_v_inplane(_slab(Vz, 0, start), Ps, 2, cz)
            return Ps - dtK * (divx + div_term(vyn, 1, dy)
                               + div_term(vzn, 2, dz))
        axis, c, dd, Vs = ((1, cy, dy, Vy) if dim == 1 else (2, cz, dz, Vz))

        def vface(g):  # updated face layer g of the staggered-axis field
            Vf = _slab(Vs, dim, g)
            if g < 1 or g > Vs.shape[dim] - 2:
                return Vf
            return Vf + c * (_slab(P, dim, g) - _slab(P, dim, g - 1))

        divs = (vface(start + 1) - vface(start)) / dd
        vxn = _upd_v_inplane(_slab(Vx, dim, start), Ps, 0, cx)
        oa, oc, od, oV = ((2, cz, dz, Vz) if dim == 1 else (1, cy, dy, Vy))
        von = _upd_v_inplane(_slab(oV, dim, start), Ps, oa, oc)
        return Ps - dtK * (div_term(vxn, 0, dx) + divs
                           + div_term(von, oa, od))
    return get


from .pallas_common import self_deliver as _self_deliver


def _wave_plane_body(g, nx, p_m, p_c, p_p, vx_c, vx_p, vy_c, vz_c,
                     rP, rVx, rVy, rVz, *, modes, cx, cy, cz, dtK,
                     dx, dy, dz, self_ols=None):
    """The fused-step arithmetic for ONE global x-plane ``g``: velocity
    updates, velocity halo delivery, pressure update from the delivered
    faces, pressure halo delivery. Shared by the plane-per-program and
    multi-plane-window kernels. Returns (p_new, vx, vy, vz).

    ``self_ols`` (all-self-neighbor grids): ``{field: (ol_y, ol_z)}`` —
    y/z halos become in-plane selects via `_self_deliver` (the r* dicts
    then carry only the "x" slabs)."""
    import jax.numpy as jnp

    ny, nz = p_c.shape

    # --- velocity updates (interior faces only; x-masks are dynamic in g)
    vx = jnp.where((g >= 1) & (g <= nx - 1), vx_c + cx * (p_c - p_m), vx_c)
    vxp = jnp.where(g + 1 <= nx - 1, vx_p + cx * (p_p - p_c), vx_p)
    dyv = p_c[1:, :] - p_c[:-1, :]
    vy = vy_c + cy * jnp.pad(dyv, ((1, 1), (0, 0)))
    dzv = p_c[:, 1:] - p_c[:, :-1]
    vz = vz_c + cz * jnp.pad(dzv, ((0, 0), (1, 1)))

    if self_ols is not None:
        vx = _self_deliver(vx, g, nx, modes["Vx"], None, *self_ols["Vx"])
        vy = _self_deliver(vy, g, nx, modes["Vy"], rVy["x"], *self_ols["Vy"])
        vz = _self_deliver(vz, g, nx, modes["Vz"], rVz["x"], *self_ols["Vz"])
        divx = (vxp - vx) / dx
        divy = (vy[1:, :] - vy[:-1, :]) / dy
        divz = (vz[:, 1:] - vz[:, :-1]) / dz
        p_new = p_c - dtK * (divx + divy + divz)
        p_new = _self_deliver(p_new, g, nx, modes["P"], rP["x"],
                              *self_ols["P"])
        return p_new, vx, vy, vz

    # --- velocity halo delivery (z, x, y; Vx's x planes are post-kernel)
    vx = _deliver(vx, g, nx, modes["Vx"], None, rVx["y"], rVx["z"],
                  ny - 1, nz - 1)
    vy = _deliver(vy, g, nx, modes["Vy"], rVy["x"], rVy["y"], rVy["z"],
                  ny, nz - 1)
    vz = _deliver(vz, g, nx, modes["Vz"], rVz["x"], rVz["y"], rVz["z"],
                  ny - 1, nz)

    # --- pressure update from the DELIVERED faces (vxp undelivered: its
    # values only reach P halo cells, where they match the sequential
    # semantics — see module docstring)
    divx = (vxp - vx) / dx
    divy = (vy[1:, :] - vy[:-1, :]) / dy
    divz = (vz[:, 1:] - vz[:, :-1]) / dz
    p_new = p_c - dtK * (divx + divy + divz)
    p_new = _deliver(p_new, g, nx, modes["P"], rP["x"], rP["y"], rP["z"],
                     ny - 1, nz - 1)
    return p_new, vx, vy, vz


from .pallas_common import recv_kinds as _wave_recv_kinds


def _wave_kernel(*refs, nx, modes, cx, cy, cz, dtK, dx, dy, dz,
                 self_ols=None):
    """Plane-per-program form of the fused step (`_wave_plane_body`).
    P[i-1] arrives by VMEM relay instead of a third HBM pressure
    stream."""
    from jax.experimental import pallas as pl

    from .pallas_common import plane_relay, take_recvs

    it = iter(refs)
    p_c, p_p = (next(it)[0] for _ in range(2))
    vx_c, vx_p = (next(it)[0] for _ in range(2))
    vy_c = next(it)[0]
    vz_c = next(it)[0]
    kinds = dict(_wave_recv_kinds(self_ols is not None))
    rP = take_recvs(it, modes, "P", kinds["P"])
    rVx = take_recvs(it, modes, "Vx", kinds["Vx"])
    rVy = take_recvs(it, modes, "Vy", kinds["Vy"])
    rVz = take_recvs(it, modes, "Vz", kinds["Vz"])

    i = pl.program_id(0)
    oP, oVx, oVy, oVz = refs[-5:-1]
    p_m = plane_relay(refs[-1], i, p_c)
    p_new, vx, vy, vz = _wave_plane_body(
        i, nx, p_m, p_c, p_p, vx_c, vx_p, vy_c, vz_c, rP, rVx, rVy, rVz,
        modes=modes, cx=cx, cy=cy, cz=cz, dtK=dtK, dx=dx, dy=dy, dz=dz,
        self_ols=self_ols)
    oP[0] = p_new
    oVx[0] = vx
    oVy[0] = vy
    oVz[0] = vz


# The wave kernel keeps more per-plane temporaries live than the diffusion
# stencil (three P planes, five velocity planes, div terms, p_new, recvs) —
# its own slack constant, sized above the stencil's 6.
_WAVE_TEMP_PLANES = 12


def wave_mp_planes(p_shape, dtype, interpret=False):
    """Plane count P for the multi-plane acoustic kernel, or None.

    VMEM model (in P-plane units of the pressure plane): double-buffered
    manual windows for P (2*(P+2)) and Vx (2*(P+1)), auto-pipelined Vy/Vz
    input blocks (2P each, slightly larger), and double-buffered outputs
    for all four fields (~8P) — ~(18P + 6) planes plus temporaries.
    Lane/sublane-unaligned planes cannot use the manual window DMA
    (`pallas_stencil.window_dma_ok` — a Mosaic-compile-only constraint:
    interpret mode skips it, keeping the kernel under test at small
    shapes) and take the plane-per-program form."""
    from .pallas_stencil import (
        _MP_VMEM_BUDGET, _compute_itemsize, window_dma_ok,
    )

    nx, ny, nz = (int(v) for v in p_shape)
    import numpy as np

    if not interpret and not window_dma_ok((ny, nz), dtype):
        return None
    plane_store = ny * nz * np.dtype(dtype).itemsize
    plane_compute = ny * nz * _compute_itemsize(np.dtype(dtype))
    for P in (8, 4):
        if nx % P or nx < 2 * P:
            continue
        if (18 * P + 6) * plane_store \
                + _WAVE_TEMP_PLANES * plane_compute <= _MP_VMEM_BUDGET:
            return P
    return None


def _wave_mp_kernel(*refs, nx, P, modes, cx, cy, cz, dtK, dx, dy, dz,
                    self_ols=None, handoff=False):
    """Multi-plane form: P output planes per program; the pressure planes
    come from a double-buffered (P+2)-window and the Vx faces from a
    (P+1)-window (faces g0..g0+P — exact, no clamping), cutting their HBM
    reads from 3x/2x to (1+2/P)x/(1+1/P)x — and to 1.0x pressure reads
    with the VMEM window handoff (`handoff`, >= 3 windows)."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from .pallas_stencil import (
        _window_pipeline, _window_pipeline_aligned_handoff,
        _window_pipeline_general, _window_pipeline_handoff,
    )

    it = iter(refs)
    P_hbm = next(it)
    Vx_hbm = next(it)
    vy_blk = next(it)                              # (P, ny+1, nz)
    vz_blk = next(it)                              # (P, ny, nz+1)
    # x recvs arrive as (2, rows, cols) constants; y/z recvs as
    # (P, 2, cols)/(P, rows, 2) per-plane blocks — load raw here (same
    # field/kind iteration order as `add_recv_operands`/`take_recvs`).
    from .pallas_common import AXIS_OF

    got = {}
    for field, kinds in _wave_recv_kinds(self_ols is not None):
        d = {}
        for k in kinds:
            if not modes[field][AXIS_OF[k]]:
                d[k] = None
                continue
            d[k] = next(it)[...]
        got[field] = d
    # outs (4) precede scratches (4: P window, Vx window, 2 sem arrays)
    oP, oVx, oVy, oVz = refs[-8:-4]
    p_scr, vx_scr, p_sems, vx_sems = refs[-4:]

    g0 = pl.program_id(0) * P
    if handoff:   # static: VMEM overlap handoff — 1.0x pressure reads and
        # (nx+1)-plane total Vx fetches (the aligned window's uniform
        # 1-plane overlap is handed across instead of re-read)
        p_win, l0 = _window_pipeline_handoff(P_hbm, p_scr, p_sems,
                                             nx=nx, B=P)
        vx_win = _window_pipeline_aligned_handoff(
            Vx_hbm, vx_scr, vx_sems, size=P + 1, B=P)
    else:
        p_win, l0 = _window_pipeline(P_hbm, p_scr, p_sems, nx=nx, B=P)
        vx_win = _window_pipeline_general(
            Vx_hbm, vx_scr, vx_sems, size=P + 1, start_fn=lambda g: g * P)

    def per_plane(field, k, j):
        r = got[field][k]
        if r is None:
            return None
        return r if k == "x" else r[j]

    kinds = dict(_wave_recv_kinds(self_ols is not None))
    for j in range(P):
        g = g0 + j
        l = l0 + j
        p_m = p_win[pl.ds(jnp.maximum(l - 1, 0), 1)][0]
        p_c = p_win[pl.ds(l, 1)][0]
        p_p = p_win[pl.ds(jnp.minimum(l + 1, P + 1), 1)][0]
        vx_c = vx_win[pl.ds(j, 1)][0]
        vx_p = vx_win[pl.ds(j + 1, 1)][0]
        rPj = {k: per_plane("P", k, j) for k in kinds["P"]}
        rVxj = {k: per_plane("Vx", k, j) for k in kinds["Vx"]}
        rVyj = {k: per_plane("Vy", k, j) for k in kinds["Vy"]}
        rVzj = {k: per_plane("Vz", k, j) for k in kinds["Vz"]}
        p_new, vx, vy, vz = _wave_plane_body(
            g, nx, p_m, p_c, p_p, vx_c, vx_p, vy_blk[j], vz_blk[j],
            rPj, rVxj, rVyj, rVzj,
            modes=modes, cx=cx, cy=cy, cz=cz, dtK=dtK, dx=dx, dy=dy, dz=dz,
            self_ols=self_ols)
        oP[j] = p_new
        oVx[j] = vx
        oVy[j] = vy
        oVz[j] = vz


def acoustic_step_exchange_pallas(state, gg, modes, *, rho, K, dt,
                                  dx, dy, dz, interpret=False):
    """One fused acoustic step (updates + full exchange of all four fields)
    for arbitrary shardings. ``modes`` from `wave_exchange_modes`."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    from .halo import exchange_recv_slabs_multi
    from .precision import resolve_wire_dtype

    P, Vx, Vy, Vz = state
    nx, ny, nz = P.shape
    dtp = P.dtype.type
    cx, cy, cz = (dtp(-dt / rho / d) for d in (dx, dy, dz))
    dtK = dtp(dt * K)
    dxp, dyp, dzp = (dtp(v) for v in (dx, dy, dz))
    hws = (1, 1, 1)

    # ALL-SELF fast path (single-shard periodic on every exchanging dim —
    # the reference's sendrecv_halo_local situation): y/z halos become
    # in-plane selects INSIDE the kernel and the x slabs are the raw
    # updated source planes (`_self_deliver` re-applies the z/y edits), so
    # the whole slab pipeline (per-dim mini-computes, corner patching,
    # local swaps — measured at ~2/3 of the step on v5e) collapses to at
    # most four 2-plane computes.
    from .pallas_common import all_self_exchange, self_recvs_and_ols

    getters = {
        "Vx": _make_v_get_slab(Vx, P, 0, cx),
        "Vy": _make_v_get_slab(Vy, P, 1, cy),
        "Vz": _make_v_get_slab(Vz, P, 2, cz),
        "P": _make_p_get_slab(P, Vx, Vy, Vz, cx, cy, cz, dtK, dxp, dyp, dzp),
    }
    shapes = {"P": P.shape, "Vx": Vx.shape, "Vy": Vy.shape, "Vz": Vz.shape}
    all_self = all_self_exchange(gg, modes)
    self_ols = None
    if all_self:
        recvs, self_ols = self_recvs_and_ols(gg, shapes, modes, getters)
    else:
        # the shared packed pipeline: ONE ppermute pair per mesh axis for
        # all four fields (the same canonical wire schema — and the same
        # wire POLICY — the XLA tier ships; `exchange_recv_slabs_multi`)
        recvs = exchange_recv_slabs_multi(gg, shapes, hws, modes, getters,
                                          wire=resolve_wire_dtype(None))

    def spec(shape, index_map):
        return pl.BlockSpec(shape, index_map)

    Pmp = wave_mp_planes(P.shape, P.dtype, interpret=interpret)
    mp = Pmp is not None
    B = Pmp if mp else 1

    if mp:
        operands = [P, Vx, Vy, Vz]
        in_specs = [
            pl.BlockSpec(memory_space=pl.ANY),      # P: manual DMA window
            pl.BlockSpec(memory_space=pl.ANY),      # Vx: manual DMA window
            spec((B, ny + 1, nz), lambda i: (i, 0, 0)),
            spec((B, ny, nz + 1), lambda i: (i, 0, 0)),
        ]
    else:
        # P[i-1] comes from the in-kernel VMEM relay, not an HBM stream
        operands = [P, P, Vx, Vx, Vy, Vz]
        in_specs = [
            spec((1, ny, nz), lambda i: (i, 0, 0)),
            spec((1, ny, nz), lambda i: (jnp.minimum(i + 1, nx - 1), 0, 0)),
            spec((1, ny, nz), lambda i: (i, 0, 0)),
            spec((1, ny, nz), lambda i: (i + 1, 0, 0)),
            spec((1, ny + 1, nz), lambda i: (i, 0, 0)),
            spec((1, ny, nz + 1), lambda i: (i, 0, 0)),
        ]

    from .pallas_common import add_recv_operands, out_shape_with_vma

    def add_recvs(field, kinds, shapes_specs):
        add_recv_operands(operands, in_specs, modes, recvs, field, kinds,
                          shapes_specs)

    c0 = lambda i: (0, 0, 0)
    ci = lambda i: (i, 0, 0)
    all_specs = {
        "P": [(0, (2, ny, nz), c0), (1, (B, 2, nz), ci),
              (2, (B, ny, 2), ci)],
        "Vx": [(1, (B, 2, nz), ci), (2, (B, ny, 2), ci)],
        "Vy": [(0, (2, ny + 1, nz), c0), (1, (B, 2, nz), ci),
               (2, (B, ny + 1, 2), ci)],
        "Vz": [(0, (2, ny, nz + 1), c0), (1, (B, 2, nz + 1), ci),
               (2, (B, ny, 2), ci)],
    }
    from .pallas_common import add_all_recvs

    add_all_recvs(operands, in_specs, modes, recvs, all_specs, all_self)

    def out_shape_of(a):
        return out_shape_with_vma(a, operands)

    kmod = {k: tuple(bool(b) for b in v) for k, v in modes.items()}
    out_specs = [
        pl.BlockSpec((B, ny, nz), lambda i: (i, 0, 0)),
        pl.BlockSpec((B, ny, nz), lambda i: (i, 0, 0)),
        pl.BlockSpec((B, ny + 1, nz), lambda i: (i, 0, 0)),
        pl.BlockSpec((B, ny, nz + 1), lambda i: (i, 0, 0)),
    ]
    out_shapes = [out_shape_of(P), out_shape_of(Vx), out_shape_of(Vy),
                  out_shape_of(Vz)]
    if mp:
        from jax.experimental.pallas import tpu as pltpu

        from .pallas_stencil import _sequential_grid_params, handoff_ok

        kernel = partial(_wave_mp_kernel, nx=nx, P=Pmp, modes=kmod,
                         cx=cx, cy=cy, cz=cz, dtK=dtK, dx=dxp, dy=dyp,
                         dz=dzp, self_ols=self_ols,
                         handoff=handoff_ok(nx, Pmp))
        Pn, Vxn, Vyn, Vzn = pl.pallas_call(
            kernel,
            grid=(nx // Pmp,),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shapes,
            scratch_shapes=[pltpu.VMEM((2, Pmp + 2, ny, nz), P.dtype),
                            pltpu.VMEM((2, Pmp + 1, ny, nz), Vx.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SemaphoreType.DMA((2,))],
            interpret=interpret,
            **_sequential_grid_params(interpret),
        )(*operands)
    else:
        from jax.experimental.pallas import tpu as pltpu

        from .pallas_stencil import _sequential_grid_params

        kernel = partial(
            _wave_kernel, nx=nx, modes=kmod,
            cx=cx, cy=cy, cz=cz, dtK=dtK, dx=dxp, dy=dyp, dz=dzp,
            self_ols=self_ols)
        Pn, Vxn, Vyn, Vzn = pl.pallas_call(
            kernel,
            grid=(nx,),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shapes,
            scratch_shapes=[pltpu.VMEM((2, ny, nz), P.dtype)],
            interpret=interpret,
            **_sequential_grid_params(interpret),
        )(*operands)

    # The kernel wrote Vx planes 0..nx-1 of the (nx+1)-plane output; plane
    # nx is ALWAYS written here (it would otherwise be uninitialized), and
    # plane 0 is rewritten with its final value (`vx_extra_plane_slabs`).
    from .pallas_common import vx_extra_plane_slabs
    from .pallas_halo import halo_write_inplace

    if all_self:
        from .pallas_common import vx_extra_planes_self

        plane0, planeN = vx_extra_planes_self(
            Vx, Vxn, recvs["Vx"], modes["Vx"], self_ols["Vx"], nx)
    else:
        plane0, planeN = vx_extra_plane_slabs(Vx, Vxn, recvs["Vx"],
                                              modes["Vx"], nx)
    Vxn = halo_write_inplace(Vxn, plane0, planeN, dim=0, hw=1,
                             interpret=interpret)
    return (Pn, Vxn, Vyn, Vzn)
