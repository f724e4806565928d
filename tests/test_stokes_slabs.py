"""The send slabs of the fused PT Stokes pass (`ops/pallas_stokes`, one
Pallas pass per exchanged axis, in interpret mode) against a plain XLA
reference: the PT update of `models.stokes._stokes_terms` on a mini-state,
all 8 fields sliced to a thin window around the slab, whose central values
are the full-step values. Every field, axis and side the exchange asks
for, on the all-self grid (x slabs only), a periodic 2x2x1 grid with z
folded (x slabs and y row windows), and non-periodic grids (raw boundary
faces, whole-plane y and z)."""

import numpy as np
import pytest

import implicitglobalgrid_tpu as igg


def _mini_state(state, dim, lo, hi):
    """All 8 fields sliced to the cell window ``[lo, hi)`` along ``dim``
    (face-staggered fields get one extra layer)."""
    from jax import lax

    nc = state[0].shape[dim]
    return tuple(lax.slice_in_dim(a, lo, hi + (a.shape[dim] == nc + 1),
                                  axis=dim) for a in state)


def _reference_slab(state, p, field, dim, start):
    """The updated slab of ``field`` at ``start`` along ``dim``: Pn's
    unmasked pressure update on a 1-cell window; a velocity's full PT
    update on a 2-cell (its own staggering) or 3-cell window, with faces on
    the global boundary left raw."""
    from jax import lax

    from implicitglobalgrid_tpu.models.stokes import _d, _stokes_terms

    if field == "P":
        P, Vx, Vy, Vz = _mini_state(state, dim, start, start + 1)[:4]
        div = _d(Vx, 0) / p.dx + _d(Vy, 1) / p.dy + _d(Vz, 2) / p.dz
        return P - p.dt_p * div
    which = ("Vx", "Vy", "Vz").index(field)
    V = state[1 + which]
    if start < 1 or start > V.shape[dim] - 2:
        return lax.slice_in_dim(V, start, start + 1, axis=dim)
    lo, hi = (start - 1, start + 1) if which == dim else (start - 1,
                                                            start + 2)
    mini = _mini_state(state, dim, lo, hi)
    R = _stokes_terms(mini, p)[2 + which]
    ix = (slice(1, -1),) * 3
    Vn = mini[1 + which].at[ix].add(
        p.dt_v * (p.damp * mini[4 + which][ix] + R))
    return lax.slice_in_dim(Vn, start - lo, start - lo + 1, axis=dim)


@pytest.mark.parametrize("dims,periods,local", [
    ((1, 1, 1), (1, 1, 1), (10, 9, 8)),
    ((2, 2, 1), (1, 1, 1), (8, 16, 16)),
    ((2, 2, 2), (0, 0, 0), (8, 24, 16)),
    ((1, 2, 4), (1, 0, 1), (6, 9, 8)),
], ids=["1x1x1-all-self", "2x2x1-periodic-z-folded",
        "2x2x2-walls-y-windows", "1x2x4-whole-plane-y-z"])
def test_send_slabs_match_the_xla_mini_state_update(dims, periods, local):
    import jax
    from jax.sharding import PartitionSpec

    from implicitglobalgrid_tpu.models import init_stokes3d
    from implicitglobalgrid_tpu.ops import pallas_stokes as ps
    from implicitglobalgrid_tpu.ops.fields import field_partition_spec
    from implicitglobalgrid_tpu.ops.pallas_common import (
        all_self_exchange, fold_z_lanes,
    )
    from implicitglobalgrid_tpu.parallel.topology import AXIS_NAMES

    igg.init_global_grid(*local, dimx=dims[0], dimy=dims[1], dimz=dims[2],
                         periodx=periods[0], periody=periods[1],
                         periodz=periods[2], quiet=True)
    gg = igg.global_grid()
    state, p = init_stokes3d(dtype=np.float32)
    rng = np.random.default_rng(11)
    state = tuple(igg.device_put_g(
        rng.uniform(-1, 1, a.shape).astype(np.float32)) for a in state)
    locs = tuple(tuple(int(s) // int(gg.dims[d]) for d, s in
                       enumerate(a.shape)) for a in state)
    names = ("P", "Vx", "Vy", "Vz")
    shapes = dict(zip(names, locs))
    fold, modes = ps._z_fold(gg, ps.stokes_exchange_modes(gg, locs), shapes)
    all_self = all_self_exchange(gg, modes)
    assert bool(fold) == (dims == (2, 2, 1))
    asked = []
    for dim in ((0,) if all_self else (0, 1, 2)):
        for f in names:
            if not modes[f][dim]:
                continue
            n = shapes[f][dim]
            ol = int(gg.overlaps[dim]) + n - int(gg.nxyz[dim])
            sides = [ol - 1, n - ol]     # the left and right send slabs
            if gg.dims[dim] > 1 and not gg.periods[dim]:
                sides += [0, n - 1]      # the PROC_NULL current halos
            asked += [(f, dim, s) for s in sides]
    assert {d for _, d, _ in asked} == ({0} if all_self else
                                        {0, 1} if fold else {0, 1, 2})

    def errors(*s):
        get = ps._send_slab_getters(s, gg, modes, p, fold, all_self,
                                    interpret=True)
        out = []
        for f, dim, start in asked:
            got = get[f](dim, start, 1)
            ref = fold_z_lanes(_reference_slab(s, p, f, dim, start),
                               fold.get(f))
            assert got.shape == ref.shape, (f, dim, start)
            out.append(abs(got - ref).max() / abs(ref).max())
        return jax.numpy.stack(out)[None, None, None]

    specs = tuple(field_partition_spec(3) for _ in state)
    err = jax.jit(jax.shard_map(
        errors, mesh=gg.mesh, in_specs=specs,
        out_specs=PartitionSpec(*AXIS_NAMES, None), check_vma=False))(*state)
    err = np.asarray(err).reshape(-1, len(asked)).max(axis=0)
    bad = {a: e for a, e in zip(asked, err) if not e <= 2e-6}
    assert not bad, bad
