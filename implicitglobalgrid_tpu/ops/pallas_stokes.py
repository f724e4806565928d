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
send slabs are computed from local thin windows before the kernel runs:
one small Pallas pass per exchanged axis (`_pt_pass`) runs the kernel's
own plane arithmetic (`_pt_planes`) on the x planes the exchange asks for,
on two 8-row windows at the y ends of every plane, or (crossing z, or a y
extent the windows do not fit) on whole planes, and the getters the
pipeline calls slice its outputs.

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
# Mosaic custom call); "slabs": the send-slab passes (`_pt_pass`, one
# Mosaic custom call per exchanged axis) and the getters' slicing;
# "vx_planes": the post-kernel writes of Vx's and dVx's extra x planes.
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


from .pallas_common import recv_kinds as _stokes_recv_kinds


def _pt_planes(p_m, p_c, vxm, vxc, vxp, vym, vyc, vyp, vzm, vzc, vzp,
               dvxc, dvyc, dvzc, rhc, *, plane, row0, nx, ny, nz, mu, dt_v,
               dt_p, damp, dx, dy, dz):
    """The PT update of x planes ``plane`` from the pre-step state: the
    arithmetic of `models.stokes._stokes_terms` term-for-term (same
    accumulation order) restricted to those planes, then the
    interior-masked dV/V updates. Returns (Pn, Vx, Vy, Vz, dVx, dVy, dVz);
    Pn is unmasked (every cell gets the pressure update).

    Operands are planes, or stacks of planes on a leading axis, over the
    last two axes (y rows, z lanes): ``*m``/``*c``/``*p`` are the fields at
    x planes i-1, i and i+1 (for Vx: faces). ``plane`` is i, a scalar or an
    array broadcasting over the leading axis; ``row0`` the global y index
    of the first row of a window of rows (cell fields R rows, Vy R+1), or
    None for whole planes; ``nx``, ``ny``, ``nz`` the local cell extents
    the masks cut at.

    Every intermediate stays at full operand size, positioned on a
    canonical grid and shifted with the edge-cloning operators of
    `pallas_common` (Mosaic cannot lower interior-slice-then-pad — see
    `shift_up`); edge garbage only ever reaches rows/lanes the interior
    masks cut away, or a window's edge rows. Canonical grids: cell
    quantities on (rows, cols); x-y edge stresses ``txyE[e] = txy(edge
    e-1/2)`` on (rows, cols); x-z edges ``txzE[:, f]`` on (rows, cols); y-z
    edges ``tyzE[f, g]`` on (rows, cols) (valid from index 1 in each edge
    direction)."""
    import jax.numpy as jnp
    from jax import lax

    from .pallas_common import shift_down, shift_left, shift_right, shift_up

    rows, cols = p_c.shape[-2:]

    def d_y(a):  # cell-centred face difference (rows+1 -> rows)
        return a[..., 1:, :] - a[..., :-1, :]

    def d_z(a):
        return a[..., :, 1:] - a[..., :, :-1]

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
    txyE = mu * ((vxc - shift_down(vxc)) / dy
                 + (vyc - vym)[..., :rows, :] / dx)
    txyEp = mu * ((vxp - shift_down(vxp)) / dy
                  + (vyp - vyc)[..., :rows, :] / dx)
    txzE = mu * ((vxc - shift_right(vxc)) / dz + (vzc - vzm)[..., :cols] / dx)
    txzEp = mu * ((vxp - shift_right(vxp)) / dz
                  + (vzp - vzc)[..., :cols] / dx)
    tyzE = mu * ((vyc - shift_right(vyc))[..., :rows, :] / dz
                 + (vzc - shift_down(vzc))[..., :cols] / dy)

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
    def index(shape, axis):  # global y (axis 0) or z (axis 1) index
        ix = lax.broadcasted_iota(jnp.int32, shape[-2:], axis)
        return ix if axis or row0 is None else ix + row0

    row, col = index(p_c.shape, 0), index(p_c.shape, 1)
    rowy, coly = index(vyc.shape, 0), index(vyc.shape, 1)
    rowz, colz = index(vzc.shape, 0), index(vzc.shape, 1)
    face_ok = (plane >= 1) & (plane <= nx - 1)
    cell_ok = (plane >= 1) & (plane <= nx - 2)

    mx = face_ok & (row > 0) & (row < ny - 1) & (col > 0) & (col < nz - 1)
    dnx = damp * dvxc + RxF
    u_dvx = jnp.where(mx, dnx, dvxc)
    u_vx = jnp.where(mx, vxc + dt_v * dnx, vxc)

    my = cell_ok & (rowy > 0) & (rowy < ny) & (coly > 0) & (coly < nz - 1)
    dny = damp * dvyc + jnp.concatenate([RyF, RyF[..., -1:, :]], axis=-2)
    u_dvy = jnp.where(my, dny, dvyc)
    u_vy = jnp.where(my, vyc + dt_v * dny, vyc)

    mz = cell_ok & (rowz > 0) & (rowz < ny - 1) & (colz > 0) & (colz < nz)
    dnz = damp * dvzc + jnp.concatenate([RzF, RzF[..., -1:]], axis=-1)
    u_dvz = jnp.where(mz, dnz, dvzc)
    u_vz = jnp.where(mz, vzc + dt_v * dnz, vzc)
    return pnc, u_vx, u_vy, u_vz, u_dvx, u_dvy, u_dvz


def _stokes_kernel(*refs, nx, modes, self_ols=None, self_z=None, **consts):
    """One x-plane of the fused PT iteration: the update of `_pt_planes`,
    then the halo deliveries (z, x, y per field; Vx's x planes
    post-kernel). With ``self_z`` (field -> z overlap) the z halo is folded
    from the plane's own lanes (`fold_z_lanes`) and no z recv operand is
    taken. The ``[i-1]`` planes of P, Vx, Vy and Vz arrive by VMEM relay
    (`pallas_common.plane_relay`), not as HBM streams."""
    from jax.experimental import pallas as pl

    from .pallas_common import deliver_recvs as _deliver
    from .pallas_common import fold_z_lanes, plane_relay

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

    pnc, u_vx, u_vy, u_vz, u_dvx, u_dvy, u_dvz = _pt_planes(
        p_m, p_c, vxm, vxc, vxp, vym, vyc, vyp, vzm, vzc, vzp, dvxc, dvyc,
        dvzc, rhc, plane=i, row0=None, nx=nx, ny=ny, nz=nz, **consts)

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


# Rows of a y window of the send-slab pass: one f32 sublane tile.
_WINDOW_ROWS = 8
# Rows a block of the send-slab pass stacks over its planes: those of a
# 256-row plane, the size of the fused pass's values.
_BLOCK_ROWS = 256


def _pick(table, k):
    """``table[k]`` for a traced grid index ``k`` over a static tuple."""
    import jax.numpy as jnp

    out = table[-1]
    for j in range(len(table) - 2, -1, -1):
        out = jnp.where(k == j, table[j], out)
    return out


def _slab_kernel(*refs, nx, ny, nz, plane0, row0, vy_split, fold, consts):
    """One block of the send-slab pass (`_pt_pass`): the update of
    `_pt_planes` on a block of planes, written for P, Vx, Vy and Vz with
    their folded z halos. Each field's block comes with the plane before
    it and (Vx, Vy, Vz) the plane after it; dV and rhog come alone. With
    ``vy_split`` Vy's blocks hold a row window of faces and come with the
    next window of the same planes, whose first row is the face closing
    the window."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    from .pallas_common import fold_z_lanes

    it = iter(refs)

    def take(n):
        return [next(it)[...] for _ in range(n)]

    def before(c, b):  # planes i-1 of the block's planes i
        return b if c.shape[0] == 1 else jnp.concatenate([b, c[:-1]], axis=0)

    def after(c, a):  # planes i+1
        return a if c.shape[0] == 1 else jnp.concatenate([c[1:], a], axis=0)

    p_c, p_b = take(2)
    vx_c, vx_b, vx_a = take(3)
    vy = take(6 if vy_split else 3)
    if vy_split:
        vy = [jnp.concatenate([w, nxt[..., :1, :]], axis=-2)
              for w, nxt in zip(vy[:3], vy[3:])]
    vy_c, vy_b, vy_a = vy
    vz_c, vz_b, vz_a = take(3)
    dvx, dvy, dvz, rh = take(4)
    if vy_split:  # the closing face's dVy: a clone, cut from the output
        dvy = jnp.concatenate([dvy, dvy[..., -1:, :]], axis=-2)

    b = p_c.shape[0]
    i0 = plane0(pl.program_id(0))
    plane = i0 if b == 1 else i0 + lax.broadcasted_iota(jnp.int32,
                                                         (b, 1, 1), 0)
    pn, vx, vy, vz = _pt_planes(
        before(p_c, p_b), p_c, before(vx_c, vx_b), vx_c, after(vx_c, vx_a),
        before(vy_c, vy_b), vy_c, after(vy_c, vy_a), before(vz_c, vz_b),
        vz_c, after(vz_c, vz_a), dvx, dvy, dvz, rh, plane=plane,
        row0=row0(pl.program_id(1)), nx=nx, ny=ny, nz=nz, **consts)[:4]
    if vy_split:
        vy = vy[..., :p_c.shape[1], :]
    for o, u, f in zip(refs[-4:], (pn, vx, vy, vz), ("P", "Vx", "Vy", "Vz")):
        o[...] = fold_z_lanes(u, fold.get(f))


def _pt_pass(state, p, *, planes=None, row_blocks=None, fold=None,
             interpret=False):
    """The PT update of P, Vx, Vy and Vz on part of the local block, in
    one pallas_call: at the x planes ``planes`` (a sorted tuple; one whole
    plane a grid step), or at every x plane (blocks of planes) on the
    ``_WINDOW_ROWS``-row windows ``row_blocks`` (block indices along y) or
    on whole planes. Returns ``{field: array}``, one plane per entry of
    ``planes`` (else nx; Vx's face nx is not computed) and the windows'
    rows stacked (else every row). With ``fold`` (field -> z overlap)
    every output takes the field's folded z halo (`fold_z_lanes`)."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .pallas_common import out_shape_with_vma

    P, Vx, Vy, Vz, dVx, dVy, dVz, rhog = state
    nx, ny, nz = P.shape
    dtp = P.dtype.type
    rows = _WINDOW_ROWS if row_blocks else ny
    vy_rows = rows if row_blocks else ny + 1
    if planes:
        b, grid0 = 1, len(planes)
        plane0 = partial(_pick, tuple(planes))
        xblk = plane0
    else:
        b = max([d for d in range(1, nx + 1)
                 if nx % d == 0 and d * rows <= _BLOCK_ROWS] or [1])
        grid0 = nx // b
        plane0 = lambda g: g * b                      # noqa: E731
        xblk = lambda g: g                            # noqa: E731
    if row_blocks:
        rb = partial(_pick, tuple(row_blocks))
        row0 = lambda w: rb(w) * rows                 # noqa: E731
    else:
        rb = lambda w: 0                              # noqa: E731
        row0 = lambda w: None                         # noqa: E731

    def specs(a, r, c, before=False, after=None, dy=0):
        """``(a, BlockSpec)`` of the block of ``a`` (r rows, c lanes),
        then, with ``before``, of the plane before it and, with ``after``,
        of the plane after it (clamped to plane ``after``); ``dy`` shifts
        the row window by whole windows."""
        def at(x):
            return lambda g, w: (x(g), rb(w) + dy, 0)
        ops = [(a, pl.BlockSpec((b, r, c), at(xblk)))]
        if before:
            ops.append((a, pl.BlockSpec((1, r, c), at(
                lambda g: jnp.maximum(plane0(g) - 1, 0)))))
        if after is not None:
            ops.append((a, pl.BlockSpec((1, r, c), at(
                lambda g: jnp.minimum(plane0(g) + b, after)))))
        return ops

    wired = (specs(P, rows, nz, True) + specs(Vx, rows, nz, True, nx)
             + specs(Vy, vy_rows, nz, True, nx - 1)
             + (specs(Vy, rows, nz, True, nx - 1, dy=1) if row_blocks
                else [])
             + specs(Vz, rows, nz + 1, True, nx - 1) + specs(dVx, rows, nz)
             + specs(dVy, vy_rows, nz) + specs(dVz, rows, nz + 1)
             + specs(rhog, rows, nz))
    operands = [a for a, _ in wired]
    n_planes = len(planes) if planes else nx
    n_rows = rows * len(row_blocks) if row_blocks else ny
    outs = {"P": (n_rows, nz), "Vx": (n_rows, nz),
            "Vy": (n_rows if row_blocks else ny + 1, nz),
            "Vz": (n_rows, nz + 1)}
    kernel = partial(
        _slab_kernel, nx=nx, ny=ny, nz=nz, plane0=plane0, row0=row0,
        vy_split=bool(row_blocks), fold=dict(fold or {}),
        consts=dict(mu=dtp(p.mu), dt_v=dtp(p.dt_v), dt_p=dtp(p.dt_p),
                    damp=dtp(p.damp), dx=dtp(p.dx), dy=dtp(p.dy),
                    dz=dtp(p.dz)))
    extra = {}
    if not interpret:
        extra["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"))
    got = pl.pallas_call(
        kernel,
        grid=(grid0, len(row_blocks) if row_blocks else 1),
        in_specs=[s for _, s in wired],
        out_specs=[pl.BlockSpec((b, rows if row_blocks else n, c),
                                lambda g, w: (g, w, 0))
                   for n, c in outs.values()],
        out_shape=[out_shape_with_vma(P, operands, shape=(n_planes, n, c))
                   for n, c in outs.values()],
        interpret=interpret,
        **extra,
    )(*operands)
    return dict(zip(outs, got))


def _z_fold(gg, modes, shapes):
    """``(fold, modes)``: ``fold`` maps each field whose z halo is folded
    to its z overlap (`stokes_exchange_folds_z`; else empty), and
    ``modes`` drops z from the exchange where it is folded."""
    if not stokes_exchange_folds_z(gg, modes):
        return {}, modes
    # a self-neighbor z stays out of the pipeline: every x/y slab (sends
    # and PROC_NULL current halos) takes the z lane copy first, which is
    # what patching it with the z recvs gives, since z comes first; the
    # kernel folds its computed planes the same way
    fold = {f: int(gg.overlaps[2]) + int(s[2]) - int(gg.nxyz[2])
            for f, s in shapes.items() if modes[f][2]}
    return fold, {f: (m[0], m[1], False) for f, m in modes.items()}


def _send_starts(gg, modes, shapes, dim):
    """``{field: starts}``: where along ``dim`` the exchange asks a
    field's getter for a send slab (`exchange_recv_slabs_multi`,
    `self_recvs_and_ols`; halowidth 1): the two overlap planes, and on a
    non-periodic crossing dim the boundary planes (the PROC_NULL current
    halos)."""
    out = {}
    for f, s in shapes.items():
        if not modes[f][dim]:
            continue
        n = int(s[dim])
        ol = int(gg.overlaps[dim]) + n - int(gg.nxyz[dim])
        starts = {n - ol, ol - 1}
        if int(gg.dims[dim]) > 1 and not bool(gg.periods[dim]):
            starts |= {0, n - 1}
        out[f] = tuple(sorted(starts))
    return out


def _y_windows(starts, ny):
    """``{(field, row): window row}`` where every y start lies inside one
    of the two ``_WINDOW_ROWS``-row windows at the plane's y ends with a
    row of margin on each side (P needs none: its update reads no y
    neighbour cell), else None (the pass then takes whole planes)."""
    w = _WINDOW_ROWS
    if ny % w or ny < 2 * w:
        return None
    hi = ny - w
    pos = {}
    for f, ss in starts.items():
        for s in ss:
            if 1 <= s <= w - 2 or (f == "P" and s < w):
                pos[f, s] = s
            elif hi + 1 <= s <= hi + w - 2 or (f == "P" and s >= hi):
                pos[f, s] = w + s - hi
            else:
                return None
    return pos


def _send_slab_getters(state, gg, modes, p, fold, all_self, interpret):
    """The send-slab getters (``get(dim, start, size)``) of P, Vx, Vy and
    Vz for one PT iteration, in the exchange's pack order. Their slabs are
    slices of one `_pt_pass` per exchanged axis, computed here once: x at
    the planes asked; y on the two end row windows (whole planes where the
    rows asked do not fit them); z, where it crosses chips, on whole
    planes, shared with y's. Faces on a global boundary, never updated,
    are raw slices; every slab carries the field's folded z halo
    (``fold``)."""
    import jax.numpy as jnp

    from .pallas_common import fold_z_lanes

    fields = dict(zip(("P", "Vx", "Vy", "Vz"), state[:4]))
    shapes = {f: a.shape for f, a in fields.items()}
    nx, ny = (int(n) for n in state[0].shape[:2])

    def computed(dim):
        """The starts whose slabs the pass computes: all but the faces on
        a global boundary."""
        return {f: tuple(s for s in ss
                         if f == "P" or 1 <= s <= shapes[f][dim] - 2)
                for f, ss in _send_starts(gg, modes, shapes, dim).items()}

    def run(**kw):
        return _pt_pass(state, p, fold=fold, interpret=interpret, **kw)

    outs, pos = {}, {}
    xs = computed(0)
    if xs:
        planes = tuple(sorted({s for ss in xs.values() for s in ss}))
        outs[0] = run(planes=planes)
        pos[0] = {(f, s): planes.index(s) for f, ss in xs.items() for s in ss}
    whole = None
    for dim in (() if all_self else (1, 2)):
        ss = computed(dim)
        if not ss:
            continue
        pos[dim] = _y_windows(ss, ny) if dim == 1 else None
        if pos[dim] is not None:
            w = _WINDOW_ROWS
            outs[dim] = run(row_blocks=(0, ny // w - 1))
        else:
            whole = whole or run()
            outs[dim] = whole
            pos[dim] = {(f, s): s for f, st in ss.items() for s in st}

    def getter(f):
        a = fields[f]
        ol_z = fold.get(f)

        def get(dim, start, size):
            assert size == 1
            with _scope("slabs"):
                if not (f == "P" or 1 <= start <= a.shape[dim] - 2):
                    return fold_z_lanes(_slab(a, dim, start), ol_z)
                s = _slab(outs[dim][f], dim, pos[dim][f, start])
                if f == "Vx" and dim:  # face nx: never updated
                    s = jnp.concatenate([s, fold_z_lanes(_slab(
                        _slab(a, 0, nx), dim, start), ol_z)], axis=0)
                return s
        return get

    return {f: getter(f) for f in ("Vx", "Vy", "Vz", "P")}


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
    fold, modes = _z_fold(gg, modes, shapes)
    all_self = all_self_exchange(gg, modes)
    with _scope("slabs"):
        getters = _send_slab_getters(state, gg, modes, p, fold, all_self,
                                     interpret)
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
