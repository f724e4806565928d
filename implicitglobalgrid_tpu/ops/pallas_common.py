"""Shared machinery of the fused multi-field Pallas passes
(`pallas_wave.py`, `pallas_stokes.py`): recv-operand wiring, kernel-side
recv unpacking, vma-aware output shapes, and the post-kernel delivery of a
face-staggered field's extra x plane (the grid covers one plane fewer than
the array)."""

from __future__ import annotations

__all__ = ["slab1", "take_recvs", "add_recv_operands", "out_shape_with_vma",
           "vx_extra_plane_slabs", "deliver_recvs", "AXIS_OF",
           "shift_up", "shift_down", "shift_left", "shift_right",
           "self_deliver", "fold_z_lanes", "all_self_exchange",
           "self_recvs_and_ols",
           "vx_extra_planes_self", "recv_kinds", "add_all_recvs"]

AXIS_OF = {"x": 0, "y": 1, "z": 2}


# Full-size shift operators for kernel-side stencil arithmetic. Mosaic
# cannot lower `jnp.pad`/concat of values carrying DIFFERENT implicit
# sublane+lane offsets ("offset mismatch on non-concat dimension" — hit by
# interior-slice-then-pad formulations); these helpers keep every
# intermediate at full plane size with offset-0 layouts, cloning the edge
# row/lane (callers mask the garbage edge through their interior masks).

def plane_relay(rel_ref, i, cur):
    """The previous grid program's plane (``cur`` itself at i == 0,
    matching the edge-clamped ``[max(i-1, 0)]`` stream it replaces), while
    storing ``cur`` for the next program: one HBM input stream per field
    becomes a VMEM relay across the IN-ORDER grid ("arbitrary" dimension
    semantics required). ``rel_ref``: VMEM ``(2, *plane)`` scratch.
    Alignment-free — works for staggered (ny+1 / nz+1) planes where the
    manual window DMA cannot (`window_dma_ok`)."""
    import jax.numpy as jnp
    from jax import lax

    prev = rel_ref[(i + 1) % 2]
    # vector mask (scalar-predicate selects are Mosaic-fragile)
    row = lax.broadcasted_iota(jnp.int32, cur.shape, 0)
    out = jnp.where((row >= 0) & (i > 0), prev, cur)
    rel_ref[i % 2] = cur
    return out


def shift_up(a):
    """out[r] = a[r+1]; last row clones a[-1] (garbage — mask it)."""
    import jax.numpy as jnp

    return jnp.concatenate([a[1:], a[-1:]], axis=0)


def shift_down(a):
    """out[r] = a[r-1]; first row clones a[0] (garbage — mask it)."""
    import jax.numpy as jnp

    return jnp.concatenate([a[:1], a[:-1]], axis=0)


def shift_left(a):
    """out[:, c] = a[:, c+1]; last lane clones a[:, -1] (garbage)."""
    import jax.numpy as jnp

    return jnp.concatenate([a[:, 1:], a[:, -1:]], axis=1)


def shift_right(a):
    """out[:, c] = a[:, c-1]; first lane clones a[:, 0] (garbage)."""
    import jax.numpy as jnp

    return jnp.concatenate([a[:, :1], a[:, :-1]], axis=1)


def slab1(A, dim, start):
    """Width-1 slice along ``dim``."""
    from jax import lax

    return lax.slice_in_dim(A, start, start + 1, axis=dim)


def take_recvs(it, modes, field, kinds):
    """Kernel-side: pull this field's recv refs off the operand iterator.

    x recv blocks are (2, rows, cols) plane pairs — loaded whole; y/z recv
    blocks are (1, ...) per-plane streams — the leading axis is dropped.
    Non-participating kinds yield None (their operand was never passed)."""
    got = {}
    for k in kinds:
        if not modes[field][AXIS_OF[k]]:
            got[k] = None
            continue
        ref = next(it)
        got[k] = ref[...] if k == "x" else ref[0]
    return got


def add_recv_operands(operands, in_specs, modes, recvs, field, kinds,
                      shapes_specs):
    """Host-side: append a field's participating recv slabs (concatenated
    left+right) and their BlockSpecs, in the same order `take_recvs` reads
    them."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    for k, (cat, blk, imap) in zip(kinds, shapes_specs):
        if not modes[field][AXIS_OF[k]]:
            continue
        rl, rr = recvs[field][AXIS_OF[k]]
        operands.append(jnp.concatenate([rl, rr], axis=cat))
        in_specs.append(pl.BlockSpec(blk, imap))


def out_shape_with_vma(a, operands):
    """ShapeDtypeStruct for ``a`` carrying the joint mesh-axis variance of
    every operand (shard_map's vma tracking), when the jax version has it."""
    import jax

    try:
        vma = jax.typeof(a).vma
        for op in operands:
            vma = vma | jax.typeof(op).vma
        return jax.ShapeDtypeStruct(a.shape, a.dtype, vma=vma)
    except (AttributeError, TypeError):
        return jax.ShapeDtypeStruct(a.shape, a.dtype)


def vx_extra_plane_slabs(Vx, Vxn, recvs_vx, modes_vx, nx, ol_z=None):
    """Final values of an x-staggered field's planes 0 and nx.

    The fused kernels' grid has nx programs but the field has nx+1 planes:
    plane nx is delivered (or kept raw) here, and plane 0 is rewritten with
    its final value, via the in-place dim-0 halo write. The slab patching
    preserves the z, x, y exchange order: the x recv slabs already carry z
    corners (pipeline patching); the y recvs' corner rows go on top.
    ``ol_z``: the field's z overlap where its self-neighbour z halo is
    folded (`fold_z_lanes`) instead of received."""
    from jax import lax

    def lane_patch(plane, xpos):
        if ol_z is not None:
            return fold_z_lanes(plane, ol_z)
        if not modes_vx[2]:
            return plane
        zl, zr = recvs_vx[2]
        zls = lax.slice_in_dim(zl, xpos, xpos + 1, axis=0)
        zrs = lax.slice_in_dim(zr, xpos, xpos + 1, axis=0)
        plane = lax.dynamic_update_slice_in_dim(plane, zls, 0, axis=2)
        return lax.dynamic_update_slice_in_dim(
            plane, zrs, plane.shape[2] - 1, axis=2)

    def row_patch(plane, xpos):
        if not modes_vx[1]:
            return plane
        yl, yr = recvs_vx[1]
        yls = lax.slice_in_dim(yl, xpos, xpos + 1, axis=0)
        yrs = lax.slice_in_dim(yr, xpos, xpos + 1, axis=0)
        plane = lax.dynamic_update_slice_in_dim(plane, yls, 0, axis=1)
        return lax.dynamic_update_slice_in_dim(
            plane, yrs, plane.shape[1] - 1, axis=1)

    if modes_vx[0]:
        rl, rr = recvs_vx[0]         # z corners already patched in-pipeline
        return row_patch(rl, 0), row_patch(rr, nx)
    # no x exchange: plane nx keeps its raw values with the z then y recvs
    # applied; plane 0 is already final in the kernel output.
    planeN = row_patch(lane_patch(
        lax.slice_in_dim(Vx, nx, nx + 1, axis=0), nx), nx)
    plane0 = lax.slice_in_dim(Vxn, 0, 1, axis=0)
    return plane0, planeN


def self_deliver(u, g, nx_planes, fmodes, rx, ol_y, ol_z):
    """ALL-SELF-NEIGHBOR delivery of one computed plane (halowidth 1).

    The single-shard-periodic analog of `deliver_recvs`, with NO received
    slabs for y/z: their halo rows/lanes are in-plane copies of the
    plane's own interior (the reference's `sendrecv_halo_local`,
    `update_halo.jl:363-380`), and the x halo planes are replaced by
    ``rx`` — the RAW updated source planes — BEFORE the selects, so the
    z-then-y edits land on them exactly as the sequential z, x, y order
    produces (an x slab extracted post-z == the raw slab with the z
    select re-applied, because z's sources are the slab's own lanes).

    ``ol_y``/``ol_z`` are the field's overlaps along y/z (source index
    ``ol-1`` fills the right halo, ``extent-ol`` the left), or None when
    that dim doesn't exchange for this field."""
    import jax.numpy as jnp
    from jax import lax

    rows, cols = u.shape
    if fmodes[0] and rx is not None:
        u = jnp.where(g == 0, rx[0], jnp.where(g == nx_planes - 1, rx[1], u))
    if fmodes[2] and ol_z is not None:
        u = fold_z_lanes(u, ol_z)
    if fmodes[1] and ol_y is not None:
        row = lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
        u = jnp.where(row == 0, u[rows - ol_y:rows - ol_y + 1, :], u)
        u = jnp.where(row == rows - 1, u[ol_y - 1:ol_y, :], u)
    return u


def fold_z_lanes(u, ol_z):
    """A self-neighbor z halo (halowidth 1) as two lane selects over the
    last axis of a plane or slab: lane 0 <- lane ``cols-ol_z``, then lane
    ``cols-1`` <- lane ``ol_z-1`` (`sendrecv_halo_local`); ``ol_z`` is the
    field's z overlap, None for a field whose z does not exchange."""
    import jax.numpy as jnp
    from jax import lax

    if ol_z is None:
        return u
    cols = u.shape[-1]
    col = lax.broadcasted_iota(jnp.int32, u.shape, u.ndim - 1)
    u = jnp.where(col == 0, u[..., cols - ol_z:cols - ol_z + 1], u)
    return jnp.where(col == cols - 1, u[..., ol_z - 1:ol_z], u)


def all_self_exchange(gg, modes) -> bool:
    """Whether every exchanging dim of a multi-field kernel takes the
    self-neighbor path (single shard, periodic) — the gate for the
    in-kernel `self_deliver` fast path."""
    exch = [d for d in range(3) if any(m[d] for m in modes.values())]
    return bool(exch) and all(
        int(gg.dims[d]) == 1 and bool(gg.periods[d]) for d in exch)


def self_recvs_and_ols(gg, shapes, modes, getters):
    """Host-side wiring of the all-self fast path: per field, the raw
    updated x source slabs (recv_l <- own right send slab and vice versa
    — `sendrecv_halo_local` routing) and the (ol_y, ol_z) select overlaps
    for `self_deliver`. Returns (recvs, self_ols)."""
    recvs = {}
    self_ols = {}
    for f, shape in shapes.items():
        ol = [int(gg.overlaps[d]) + (int(shape[d]) - int(gg.nxyz[d]))
              for d in range(3)]
        self_ols[f] = (ol[1] if modes[f][1] else None,
                       ol[2] if modes[f][2] else None)
        if modes[f][0]:
            s0 = int(shape[0])
            recvs[f] = {0: (getters[f](0, s0 - ol[0], 1),
                            getters[f](0, ol[0] - 1, 1))}
        else:
            recvs[f] = {}
    return recvs, self_ols


def recv_kinds(all_self: bool):
    """(field, kinds) recv-operand order — the kernel<->host protocol of
    every 4-field fused pass (`pallas_wave`, `pallas_stokes`); both the
    kernel-side `take_recvs` unpacking and the host-side
    `add_recv_operands` wiring iterate THIS tuple. All-self grids pass
    only the x slabs (y/z become in-plane selects, `self_deliver`)."""
    if all_self:
        return (("P", ("x",)), ("Vx", ()), ("Vy", ("x",)), ("Vz", ("x",)))
    return (("P", ("x", "y", "z")), ("Vx", ("y", "z")),
            ("Vy", ("x", "y", "z")), ("Vz", ("x", "y", "z")))


def add_all_recvs(operands, in_specs, modes, recvs, all_specs, all_self):
    """Host-side recv wiring for the 4-field fused passes: append every
    participating field/kind's slabs in `recv_kinds` order, with the
    BlockSpec rows of ``all_specs[field]`` matched by concat axis."""
    for field, kinds in recv_kinds(all_self):
        rows = [ss for k in kinds for ss in all_specs[field]
                if ss[0] == AXIS_OF[k]]
        add_recv_operands(operands, in_specs, modes, recvs, field, kinds,
                          rows)


def vx_extra_planes_self(Vx, Vxn, recvs_vx, modes_vx, ols_vx, nx):
    """Final values of an x-staggered field's planes 0 and nx on an
    ALL-SELF grid: both x halo planes come from the raw updated source
    slabs (plane 0 <- updated plane nx-ol, plane nx <- updated plane
    ol-1) with the z-then-y in-plane selects applied — the same
    order/argument as `self_deliver`. When x doesn't exchange, plane 0 is
    already final in the kernel output and plane nx keeps its raw values
    + selects."""
    from jax import lax

    ol_y, ol_z = ols_vx

    def selects(plane):
        return self_deliver(plane[0], 0, 1,
                            (False, modes_vx[1], modes_vx[2]), None,
                            ol_y, ol_z)[None]

    if modes_vx[0]:
        plane0 = selects(recvs_vx[0][0])
        planeN = selects(recvs_vx[0][1])
    else:
        plane0 = lax.slice_in_dim(Vxn, 0, 1, axis=0)
        planeN = selects(lax.slice_in_dim(Vx, nx, nx + 1, axis=0))
    return plane0, planeN


def deliver_recvs(u, i, nx_planes, modes, rx, ry, rz, row_hi, col_hi):
    """Apply a field's received halo slabs to its computed plane ``u``, in
    the reference order z, x, y. ``rx`` is None for fields whose x planes
    are written post-kernel (Vx). ``row_hi``/``col_hi`` are the last
    row/lane indices of the plane (staggered extents differ)."""
    import jax.numpy as jnp
    from jax import lax

    rows, cols = u.shape
    row = lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
    col = lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    if modes[2]:
        u = jnp.where(col == 0, rz[:, 0:1], u)
        u = jnp.where(col == col_hi, rz[:, 1:2], u)
    if modes[0] and rx is not None:
        u = jnp.where(i == 0, rx[0], jnp.where(i == nx_planes - 1, rx[1], u))
    if modes[1]:
        u = jnp.where(row == 0, ry[0:1, :], u)
        u = jnp.where(row == row_hi, ry[1:2, :], u)
    return u
