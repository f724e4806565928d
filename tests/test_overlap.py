"""Tests of `hide_communication` — the overlapped step must be semantically
identical to plain update-then-exchange (the reference's `@hide_communication`
contract: same results, communication hidden; `reference README.md:10`)."""

import jax
from jax import shard_map
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import implicitglobalgrid_tpu as igg
from implicitglobalgrid_tpu.models import init_diffusion3d
from implicitglobalgrid_tpu.ops.overlap import hide_communication
from implicitglobalgrid_tpu.ops.stencil import (
    d_xa, d_xi, d_ya, d_yi, d_za, d_zi, inn,
)


def assert_overlap_equal(a, b, steps=1, ulp_tol=False):
    """hide_communication vs plain update-then-exchange: bit-identical,
    unless ``ulp_tol`` admits drift of a few ulp per step (for a model
    whose long expression chain XLA:CPU rounds differently at different
    array positions — see the Stokes test)."""
    if not ulp_tol:
        np.testing.assert_array_equal(a, b)
        return
    eps = float(np.finfo(a.dtype).eps)
    tol = 64 * eps * steps
    np.testing.assert_allclose(a, b, rtol=tol,
                               atol=tol * max(1.0, float(np.abs(a).max())))


def _update(p):
    def f(T, Cp):
        qx = -p.lam * d_xi(T) / p.dx
        qy = -p.lam * d_yi(T) / p.dy
        qz = -p.lam * d_zi(T) / p.dz
        dT = (-d_xa(qx) / p.dx - d_ya(qy) / p.dy - d_za(qz) / p.dz) / inn(Cp)
        return T.at[1:-1, 1:-1, 1:-1].add(p.dt * dT)
    return f


def _compare(periods, dims, nx=12):
    igg.init_global_grid(nx, nx, nx, dimx=dims[0], dimy=dims[1], dimz=dims[2],
                         periodx=periods[0], periody=periods[1],
                         periodz=periods[2], quiet=True)
    gg = igg.global_grid()
    T, Cp, p = init_diffusion3d(dtype=np.float64)
    up = _update(p)
    spec = P("gx", "gy", "gz")

    plain = jax.jit(shard_map(
        lambda t, c: igg.local_update_halo(up(t, c)),
        mesh=gg.mesh, in_specs=(spec, spec), out_specs=spec))
    overlapped = jax.jit(shard_map(
        lambda t, c: hide_communication(up, t, c, radius=1),
        mesh=gg.mesh, in_specs=(spec, spec), out_specs=spec))

    a = np.asarray(plain(T, Cp))
    b = np.asarray(overlapped(T, Cp))
    igg.finalize_global_grid()
    return a, b


@pytest.mark.parametrize("periods,dims", [
    ((0, 0, 0), (2, 2, 2)),
    ((1, 1, 1), (2, 2, 2)),
    ((1, 0, 1), (4, 2, 1)),
    ((1, 1, 1), (1, 1, 1)),   # self-neighbor path
])
def test_overlapped_equals_plain(periods, dims):
    a, b = _compare(periods, dims)
    assert_overlap_equal(a, b)


def test_overlapped_multiple_steps():
    igg.init_global_grid(12, 12, 12, dimx=2, dimy=2, dimz=2,
                         periodx=1, quiet=True)
    gg = igg.global_grid()
    T, Cp, p = init_diffusion3d(dtype=np.float64)
    up = _update(p)
    spec = P("gx", "gy", "gz")
    from jax import lax

    f = jax.jit(shard_map(
        lambda t, c: lax.fori_loop(
            0, 5, lambda i, tc: hide_communication(up, tc, c), t),
        mesh=gg.mesh, in_specs=(spec, spec), out_specs=spec))
    g = jax.jit(shard_map(
        lambda t, c: lax.fori_loop(
            0, 5, lambda i, tc: igg.local_update_halo(up(tc, c)), t),
        mesh=gg.mesh, in_specs=(spec, spec), out_specs=spec))
    assert_overlap_equal(np.asarray(f(T, Cp)), np.asarray(g(T, Cp)), steps=5)


def test_thin_block_fallback():
    # block too thin to split -> falls back to the plain path, same result
    igg.init_global_grid(5, 5, 5, dimx=2, dimy=2, dimz=2, quiet=True)
    gg = igg.global_grid()
    T, Cp, p = init_diffusion3d(dtype=np.float64)
    up = _update(p)
    spec = P("gx", "gy", "gz")
    a = np.asarray(jax.jit(shard_map(
        lambda t, c: hide_communication(up, t, c),
        mesh=gg.mesh, in_specs=(spec, spec), out_specs=spec))(T, Cp))
    b = np.asarray(jax.jit(shard_map(
        lambda t, c: igg.local_update_halo(up(t, c)),
        mesh=gg.mesh, in_specs=(spec, spec), out_specs=spec))(T, Cp))
    assert np.array_equal(a, b)


@pytest.mark.parametrize("wire", ["z:int8", "z:int8,x:f32"])
def test_overlapped_equals_plain_quantized_wire(wire):
    """ISSUE 11 small fix: the overlapped path and the plain fallback must
    agree under QUANTIZED per-axis wire policies too (previously only the
    exact wire was asserted). Equality holds because the send slabs are
    extracted from the shell, whose values equal the plain update's — so
    the per-slab max-abs quantization scales cannot diverge between the
    paths; a shell that drifted by even one ulp would flip quantization
    bins and fail this test loudly."""
    igg.init_global_grid(12, 12, 12, dimx=2, dimy=2, dimz=2,
                         periodx=1, periodz=1, quiet=True)
    gg = igg.global_grid()
    T, Cp, p = init_diffusion3d(dtype=np.float64)
    up = _update(p)
    spec = P("gx", "gy", "gz")

    plain = jax.jit(shard_map(
        lambda t, c: igg.local_update_halo(up(t, c), wire_dtype=wire),
        mesh=gg.mesh, in_specs=(spec, spec), out_specs=spec))
    overlapped = jax.jit(shard_map(
        lambda t, c: hide_communication(up, t, c, radius=1,
                                        wire_dtype=wire),
        mesh=gg.mesh, in_specs=(spec, spec), out_specs=spec))
    a = np.asarray(plain(T, Cp))
    b = np.asarray(overlapped(T, Cp))
    igg.finalize_global_grid()
    assert_overlap_equal(a, b)


def test_multi_field_overlap_staggered_equals_plain():
    """The MULTI-FIELD interior-first shape (`hide_communication` on a
    tuple of staggered outputs — the acoustic V round's form): one
    coalesced exchange round of all outputs, same values as plain
    update-then-exchange."""
    from implicitglobalgrid_tpu.models import init_acoustic3d

    igg.init_global_grid(12, 12, 12, dimx=2, dimy=2, dimz=2,
                         periodx=1, quiet=True)
    gg = igg.global_grid()
    (Pf, Vx, Vy, Vz), p = init_acoustic3d(dtype=np.float64)
    from jax import lax

    def dP(A, d):
        n = A.shape[d]
        return (lax.slice_in_dim(A, 1, n, axis=d)
                - lax.slice_in_dim(A, 0, n - 1, axis=d))

    def v_upd(vx, vy, vz, Pc):
        vx = vx.at[1:-1, :, :].add(-p.dt / p.rho * dP(Pc, 0) / p.dx)
        vy = vy.at[:, 1:-1, :].add(-p.dt / p.rho * dP(Pc, 1) / p.dy)
        vz = vz.at[:, :, 1:-1].add(-p.dt / p.rho * dP(Pc, 2) / p.dz)
        return vx, vy, vz

    spec = P("gx", "gy", "gz")
    specs = (spec, spec, spec, spec)

    plain = jax.jit(shard_map(
        lambda vx, vy, vz, Pc: igg.local_update_halo(*v_upd(vx, vy, vz, Pc)),
        mesh=gg.mesh, in_specs=specs, out_specs=specs[:3]))
    overlapped = jax.jit(shard_map(
        lambda vx, vy, vz, Pc: hide_communication(
            v_upd, (vx, vy, vz), Pc, radius=1),
        mesh=gg.mesh, in_specs=specs, out_specs=specs[:3]))
    a = plain(Vx, Vy, Vz, Pf)
    b = overlapped(Vx, Vy, Vz, Pf)
    igg.finalize_global_grid()
    for x, y in zip(a, b):
        assert_overlap_equal(np.asarray(x), np.asarray(y))


def test_stokes_overlap_matches_plain():
    """StokesParams(overlap=True) routes the XLA PT iteration through the
    interior-first shape (7 shell updates, one coalesced 4-field round,
    interior under the collectives); results must match the plain path.
    Ulp tolerance: XLA:CPU's vector-loop epilogues round this model's long
    expression chain differently at different array positions, so the
    shell/interior split moves a few cells by 1 ulp (40 of 14,976 on
    jax 0.9.0 — the caveat in `StokesParams`' docstring); every other
    overlap test here stays bit-exact."""
    import dataclasses

    from implicitglobalgrid_tpu.models import init_stokes3d, run_stokes

    igg.init_global_grid(12, 12, 12, dimx=2, dimy=2, dimz=2, quiet=True)
    state, p = init_stokes3d(dtype=np.float32)
    a = run_stokes(state, p, 6, nt_chunk=3, impl="xla")
    po = dataclasses.replace(p, overlap=True)
    b = run_stokes(state, po, 6, nt_chunk=3, impl="xla")
    igg.finalize_global_grid()
    for x, y in zip(a, b):
        assert_overlap_equal(np.asarray(x), np.asarray(y), steps=6,
                             ulp_tol=True)


def test_diffusion_overlap_matches_plain():
    """DiffusionParams(overlap=True) routes the XLA step through
    hide_communication; results must equal the plain path bit-for-bit."""
    import dataclasses

    from implicitglobalgrid_tpu.models import init_diffusion3d, run_diffusion

    igg.init_global_grid(8, 8, 8, dimx=2, dimy=2, dimz=2,
                         periodx=1, periodz=1, quiet=True)
    T, Cp, p = init_diffusion3d(dtype=np.float32)
    po = dataclasses.replace(p, overlap=True)
    a = np.asarray(igg.gather(run_diffusion(T, Cp, p, 6, nt_chunk=3,
                                            impl="xla")))
    b = np.asarray(igg.gather(run_diffusion(T, Cp, po, 6, nt_chunk=3,
                                            impl="xla")))
    assert_overlap_equal(a, b, steps=6)


def test_diffusion2d_overlap_matches_plain():
    import dataclasses

    from implicitglobalgrid_tpu.models import init_diffusion2d, run_diffusion

    igg.init_global_grid(8, 8, 1, dimx=2, dimy=2, periodx=1, quiet=True)
    T, Cp, p = init_diffusion2d(dtype=np.float32)
    po = dataclasses.replace(p, overlap=True)
    a = np.asarray(igg.gather(run_diffusion(T, Cp, p, 6, nt_chunk=3,
                                            impl="xla")))
    b = np.asarray(igg.gather(run_diffusion(T, Cp, po, 6, nt_chunk=3,
                                            impl="xla")))
    assert_overlap_equal(a, b, steps=6)
