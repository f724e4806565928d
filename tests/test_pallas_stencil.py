"""Pallas stencil kernel tests (interpret mode on the CPU mesh) — the analog
of the reference testing its hand-written GPU pack kernels on every backend
(`test_update_halo.jl:497-634`): the fused Pallas step must reproduce the XLA
flux-form step to ulp accuracy, standalone and composed with the halo
exchange inside a whole-loop run."""

import numpy as np
import pytest

import implicitglobalgrid_tpu as igg
from implicitglobalgrid_tpu.models import (
    init_diffusion3d, make_run, make_step, run_diffusion,
)


def test_pallas_step_matches_xla():
    igg.init_global_grid(8, 8, 8, dimx=2, dimy=2, dimz=2, periodx=1, quiet=True)
    T, Cp, p = init_diffusion3d(dtype=np.float32)
    a = np.asarray(make_step(p, impl="xla")(T, Cp))
    b = np.asarray(make_step(p, impl="pallas_interpret")(T, Cp))
    assert np.allclose(a, b, rtol=2e-6, atol=2e-5)


def test_pallas_whole_loop_matches_xla():
    igg.init_global_grid(8, 8, 8, dimx=2, dimy=2, dimz=2,
                         periodx=1, periody=1, periodz=1, quiet=True)
    T, Cp, p = init_diffusion3d(dtype=np.float32)
    a = np.asarray(run_diffusion(T, Cp, p, 3, nt_chunk=3, impl="xla"))
    b = np.asarray(run_diffusion(T, Cp, p, 3, nt_chunk=3, impl="pallas_interpret"))
    assert np.allclose(a, b, rtol=1e-5, atol=1e-4)
    assert not np.allclose(a, np.asarray(T))  # it did something


def test_pallas_bf16():
    """TPU-native dtype through both step implementations."""
    import jax.numpy as jnp

    igg.init_global_grid(8, 8, 8, dimx=2, dimy=2, dimz=2, periodx=1, quiet=True)
    T, Cp, p = init_diffusion3d(dtype=jnp.bfloat16)
    a = np.asarray(make_step(p, impl="xla")(T, Cp)).astype(np.float32)
    b = np.asarray(make_step(p, impl="pallas_interpret")(T, Cp)).astype(np.float32)
    assert np.allclose(a, b, rtol=2e-2, atol=0.5)


def test_pallas_f64():
    igg.init_global_grid(8, 8, 8, dimx=2, dimy=2, dimz=2, quiet=True)
    T, Cp, p = init_diffusion3d(dtype=np.float64)
    a = np.asarray(make_step(p, impl="xla")(T, Cp))
    b = np.asarray(make_step(p, impl="pallas_interpret")(T, Cp))
    assert np.allclose(a, b, rtol=1e-13, atol=1e-12)


@pytest.mark.parametrize("dims,periods,expected_fuse", [
    ((1, 1, 1), (1, 1, 1), (True, True, True)),    # all self-neighbor
    ((2, 1, 1), (1, 1, 1), (False, False, True)),  # z fuses; x multi-shard blocks y
    ((1, 1, 2), (1, 1, 1), None),                  # z multi-shard blocks everything
    ((1, 1, 1), (0, 0, 0), None),                  # nothing exchanges
    ((1, 2, 1), (1, 0, 1), (True, False, True)),   # z,x fuse; y (multi-shard) breaks
    ((1, 1, 1), (1, 1, 0), (True, True, False)),   # z exchanges nothing -> x,y still fuse
])
def test_fusable_halo_dims(dims, periods, expected_fuse):
    """Fusion must cover only a prefix of the z, x, y exchange order
    (reference `update_halo.jl:45` sequencing — corners propagate dim by
    dim)."""
    from implicitglobalgrid_tpu.ops.pallas_stencil import fusable_halo_dims

    igg.init_global_grid(8, 8, 8, dimx=dims[0], dimy=dims[1], dimz=dims[2],
                         periodx=periods[0], periody=periods[1],
                         periodz=periods[2], quiet=True)
    assert fusable_halo_dims(igg.global_grid()) == expected_fuse


@pytest.mark.parametrize("nx", [16, 12])  # 16: multi-plane kernel; 12: plane-per-program
@pytest.mark.parametrize("dims,periods", [
    ((1, 1, 1), (1, 1, 1)),  # all dims fused in-kernel
    ((2, 1, 1), (1, 1, 1)),  # mixed: fused z + ppermute x + local y
    ((1, 1, 1), (0, 0, 0)),  # no exchange at all
])
def test_pallas_fused_halo_matches_xla(dims, periods, nx):
    """The fused step+halo kernels (both the multi-plane and the
    plane-per-program form) must reproduce the XLA step followed by the
    sequential exchange — including corner propagation through the dims."""
    igg.init_global_grid(nx, 16, 16, dimx=dims[0], dimy=dims[1], dimz=dims[2],
                         periodx=periods[0], periody=periods[1],
                         periodz=periods[2], quiet=True)
    T, Cp, p = init_diffusion3d(dtype=np.float32)
    a = np.asarray(igg.gather(make_run(p, 10, impl="xla")(T, Cp)[0]))
    b = np.asarray(igg.gather(make_run(p, 10, impl="pallas_interpret")(T, Cp)[0]))
    assert np.allclose(a, b, rtol=1e-5, atol=1e-4)


def test_mp_window_handoff_selection_and_equivalence():
    """The VMEM window handoff (1.0x T reads) engages only with >= 3
    windows, and the traffic model follows the shape — while the kernel
    output matches the XLA reference over a multi-step run (nx=12, P=4 ->
    3 windows)."""
    import jax

    from implicitglobalgrid_tpu.ops.pallas_stencil import (
        mp_bytes_per_cell, mp_handoff, mp_planes,
    )

    s12 = jax.ShapeDtypeStruct((12, 16, 16), np.float32)
    s8 = jax.ShapeDtypeStruct((8, 16, 16), np.float32)
    assert mp_planes(s12, interpret=True) == 4
    assert mp_planes(s8, interpret=True) == 4
    assert mp_handoff(s12, interpret=True)          # 3 windows
    assert not mp_handoff(s8, interpret=True)       # 2 windows: plain
    assert mp_bytes_per_cell(s12, interpret=True) == 3.0 * 4
    assert mp_bytes_per_cell(s8, interpret=True) == (3.0 + 2.0 / 4) * 4

    igg.init_global_grid(12, 16, 16, dimx=1, dimy=1, dimz=1,
                         periodx=1, periody=1, periodz=1, quiet=True)
    T, Cp, p = init_diffusion3d(dtype=np.float32)
    a = np.asarray(igg.gather(make_run(p, 10, impl="xla")(T, Cp)[0]))
    b = np.asarray(igg.gather(
        make_run(p, 10, impl="pallas_interpret")(T, Cp)[0]))
    assert np.allclose(a, b, rtol=1e-5, atol=1e-4)


def test_mp_handoff_multishard_matches_xla():
    """The handoff window inside the multi-shard fused step+exchange
    kernel (`_mp_step_recv_kernel`, local nx=12 -> 3 windows): 10-step
    whole-loop equality with the XLA step + sequential exchange."""
    import jax

    from implicitglobalgrid_tpu.ops.pallas_stencil import (
        mp_handoff, step_exchange_modes,
    )

    igg.init_global_grid(12, 12, 16, dimx=2, dimy=2, dimz=2,
                         periodx=1, periody=1, periodz=1, quiet=True)
    gg = igg.global_grid()
    sds = jax.ShapeDtypeStruct((12, 12, 16), np.float32)
    assert mp_handoff(sds, interpret=True)
    assert step_exchange_modes(gg, sds) == (True, True, True)
    T, Cp, p = init_diffusion3d(dtype=np.float32)
    a = np.asarray(igg.gather(make_run(p, 10, impl="xla")(T, Cp)[0]))
    b = np.asarray(igg.gather(
        make_run(p, 10, impl="pallas_interpret")(T, Cp)[0]))
    assert np.allclose(a, b, rtol=1e-5, atol=1e-4)


def test_impl_resolution_from_env_flag():
    from implicitglobalgrid_tpu.models.diffusion import _resolve_impl

    igg.init_global_grid(8, 8, 8, dimx=2, dimy=2, dimz=2, quiet=True)
    # on the CPU test mesh, default stays xla even if the flag is set
    assert _resolve_impl(None) == "xla"
    assert _resolve_impl("pallas") == "pallas"
    gg = igg.global_grid()
    gg.use_pallas[:] = True
    assert _resolve_impl(None) == "xla"  # device_type is cpu here


# local nx 6 fails the multi-plane gate: the plane-per-program kernel runs
_PLANE_KERNEL_CASE = "2x2x1 self z, plane-per-program kernel (local nx 6)"


@pytest.mark.parametrize("dims,periods,label", [
    ((2, 2, 2), (1, 1, 1), "all multi-shard periodic"),
    ((2, 2, 2), (0, 0, 0), "all multi-shard PROC_NULL edges"),
    ((2, 1, 1), (1, 0, 0), "multi x only: partial modes (True,False,False)"),
    ((1, 2, 4), (1, 0, 1), "self x + PROC_NULL y + 4-shard z"),
    ((2, 2, 1), (1, 1, 1), "2x2x1 periodic: self z folded"),
    ((2, 2, 1), (0, 0, 1), "2x2x1 PROC_NULL x/y: self z folded"),
    ((2, 1, 1), (1, 1, 1), "2x1x1 periodic: self z folded, self y swapped"),
    ((2, 2, 1), (1, 1, 1), _PLANE_KERNEL_CASE),
])
def test_step_exchange_fused_matches_xla(dims, periods, label):
    """The fused step+exchange path (thin-slab sends -> ppermute -> one
    delivery pass) must reproduce the XLA step followed by the sequential
    exchange over a 10-step whole loop — corners propagate through mixed
    self/multi-shard dims, and through a self z folded in the kernel and
    the send slabs instead of exchanged."""
    from implicitglobalgrid_tpu.ops.pallas_stencil import (
        fusable_halo_dims, mp_planes, step_exchange_folds_z,
        step_exchange_modes,
    )

    nx = 6 if label == _PLANE_KERNEL_CASE else 8
    igg.init_global_grid(nx, 8, 16, dimx=dims[0], dimy=dims[1], dimz=dims[2],
                         periodx=periods[0], periody=periods[1],
                         periodz=periods[2], quiet=True)
    gg = igg.global_grid()
    T, Cp, p = init_diffusion3d(dtype=np.float32)
    # the config must actually take the new path
    from implicitglobalgrid_tpu.ops.fields import local_shape_of
    import jax

    loc = local_shape_of(tuple(int(s) for s in T.shape))
    sds = jax.ShapeDtypeStruct(loc, T.dtype)
    modes = step_exchange_modes(gg, sds)
    assert modes is not None, label
    fuse = fusable_halo_dims(gg)
    assert fuse is None or not fuse[0], label  # not the one-pass self kernel
    self_z = dims[2] == 1 and periods[2] == 1
    assert step_exchange_folds_z(gg, modes) == self_z, label
    assert (mp_planes(sds, interpret=True) is None) == (nx == 6), label
    a = np.asarray(igg.gather(make_run(p, 10, impl="xla")(T, Cp)[0]))
    b = np.asarray(igg.gather(make_run(p, 10, impl="pallas_interpret")(T, Cp)[0]))
    assert np.allclose(a, b, rtol=1e-5, atol=1e-4), label


def test_partial_fuse_with_nonstandard_dim_matches_xla():
    """A self-neighbor prefix (z) fuses in-kernel while a nonstandard dim
    (x with halowidth 2 — ineligible for the fused exchange) is exchanged
    afterwards over only the remaining dims — results must match the XLA
    step + sequential exchange."""
    igg.init_global_grid(12, 12, 16, dimx=2, dimy=1, dimz=1,
                         periodx=1, periodz=1,
                         overlaps=(4, 2, 2), halowidths=(2, 1, 1), quiet=True)
    from implicitglobalgrid_tpu.ops.pallas_stencil import (
        fusable_halo_dims, step_exchange_modes,
    )
    import jax

    gg = igg.global_grid()
    assert fusable_halo_dims(gg) == (False, False, True)
    assert step_exchange_modes(
        gg, jax.ShapeDtypeStruct((12, 12, 16), np.float32)) is None
    T, Cp, p = init_diffusion3d(dtype=np.float32)
    a = np.asarray(igg.gather(make_run(p, 5, impl="xla")(T, Cp)[0]))
    b = np.asarray(igg.gather(
        make_run(p, 5, impl="pallas_interpret")(T, Cp)[0]))
    assert np.allclose(a, b, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("dims,periods,label", [
    ((1, 1), (1, 1), "2-D all self-neighbor"),
    ((2, 2), (1, 1), "2-D all multi-shard periodic"),
    ((2, 2), (0, 0), "2-D PROC_NULL edges"),
    ((2, 1), (1, 0), "2-D multi x only"),
])
def test_step_exchange_2d_matches_xla(dims, periods, label):
    """The 2-D fused step+exchange strip kernel (BASELINE config 2) must
    reproduce the XLA 2-D step followed by the sequential exchange."""
    from implicitglobalgrid_tpu.models import init_diffusion2d
    from implicitglobalgrid_tpu.ops.pallas_stencil import (
        step_exchange_modes, strip_rows_2d,
    )
    import jax

    igg.init_global_grid(16, 16, 1, dimx=dims[0], dimy=dims[1], dimz=1,
                         periodx=periods[0], periody=periods[1], quiet=True)
    gg = igg.global_grid()
    T, Cp, p = init_diffusion2d(dtype=np.float32)
    from implicitglobalgrid_tpu.ops.fields import local_shape_of

    loc = local_shape_of(tuple(int(s) for s in T.shape))
    sds = jax.ShapeDtypeStruct(loc, T.dtype)
    assert step_exchange_modes(gg, sds) is not None, label
    # compiled mode requires tile-aligned shapes; interpret (this test) not
    assert strip_rows_2d(sds, interpret=True) is not None, label
    assert strip_rows_2d(sds) is None, label
    a = np.asarray(igg.gather(make_run(p, 10, ndim=2, impl="xla")(T, Cp)[0]))
    b = np.asarray(igg.gather(
        make_run(p, 10, ndim=2, impl="pallas_interpret")(T, Cp)[0]))
    assert np.allclose(a, b, rtol=1e-5, atol=1e-4), label


def test_step_exchange_modes_gates():
    from implicitglobalgrid_tpu.ops.pallas_stencil import step_exchange_modes
    import jax

    # nonstandard halowidth: ineligible
    igg.init_global_grid(12, 12, 12, dimx=2, dimy=2, dimz=2,
                         overlaps=(4, 4, 4), halowidths=(2, 2, 2), quiet=True)
    gg = igg.global_grid()
    s = jax.ShapeDtypeStruct((12, 12, 12), np.float32)
    assert step_exchange_modes(gg, s) is None
    igg.finalize_global_grid()
    # staggered block: ineligible
    igg.init_global_grid(8, 8, 8, dimx=2, dimy=1, dimz=1, periodx=1,
                         quiet=True)
    gg = igg.global_grid()
    assert step_exchange_modes(
        gg, jax.ShapeDtypeStruct((9, 8, 8), np.float32)) is None
    # unstaggered, only x multi-shard (y/z single-shard non-periodic)
    assert step_exchange_modes(
        gg, jax.ShapeDtypeStruct((8, 8, 8), np.float32)) == (True, False, False)


def test_mp_planes_vmem_selection():
    """Plane-count selection respects the VMEM budget: f32 256-cube picks a
    smaller P than bf16 (half the plane bytes), tiny blocks fall back."""
    import jax

    from implicitglobalgrid_tpu.ops.pallas_stencil import (
        _MP_VMEM_BUDGET, _MP_TEMP_PLANES, mp_planes, strip_rows_2d,
    )

    import jax.numpy as jnp

    P32 = mp_planes(jax.ShapeDtypeStruct((256, 256, 256), np.float32))
    P16 = mp_planes(jax.ShapeDtypeStruct((256, 256, 256), jnp.bfloat16))
    assert P32 is not None and P16 is not None and P16 >= P32
    ws = (6 * P32 + 4 + _MP_TEMP_PLANES) * 256 * 256 * 4
    assert ws <= _MP_VMEM_BUDGET  # the chosen P actually fits the budget
    # bf16 temporaries cost f32 (compute dtype): the model accounts for it
    from implicitglobalgrid_tpu.ops.pallas_stencil import _compute_itemsize
    assert _compute_itemsize(np.dtype(jnp.bfloat16)) == 4
    # indivisible plane axis -> None
    assert mp_planes(jax.ShapeDtypeStruct((7, 256, 256), np.float32)) is None
    # lane-unaligned blocks cannot use the window DMA (Mosaic rejects the
    # dynamic-start HBM slice on partially-tiled shapes; verified on v5e)
    assert mp_planes(jax.ShapeDtypeStruct((192, 192, 192), np.float32)) is None
    from implicitglobalgrid_tpu.ops.pallas_wave import wave_mp_planes
    assert wave_mp_planes((192, 192, 192), np.float32) is None
    assert wave_mp_planes((128, 128, 128), np.float32) is not None
    # 2-D strip selection fits the budget too
    R = strip_rows_2d(jax.ShapeDtypeStruct((4096, 4096), np.float32))
    assert R is not None and (12 * R + 8) * 4096 * 4 <= _MP_VMEM_BUDGET
    # bf16 strips: f32 temporaries halve R vs the naive bf16-only estimate
    Rb = strip_rows_2d(jax.ShapeDtypeStruct((8192, 8192), jnp.bfloat16))
    assert Rb is not None
    assert (6 * Rb + 8) * 8192 * 2 + 6 * Rb * 8192 * 4 <= _MP_VMEM_BUDGET


def test_pallas_bf16_f32_accumulation_beats_plain_bf16():
    """The kernels compute bf16 states in f32 (storage stays bf16): over a
    multi-step run they must track the f32 solution at least as well as
    the plain bf16 XLA arithmetic."""
    import jax.numpy as jnp

    igg.init_global_grid(16, 16, 16, dimx=1, dimy=1, dimz=1,
                         periodx=1, periody=1, periodz=1, quiet=True)
    T32, Cp32, p = init_diffusion3d(dtype=np.float32)
    ref = np.asarray(run_diffusion(T32, Cp32, p, 20, nt_chunk=10,
                                   impl="xla")).astype(np.float64)
    T16, Cp16, p16 = init_diffusion3d(dtype=jnp.bfloat16)
    a = np.asarray(run_diffusion(T16, Cp16, p16, 20, nt_chunk=10,
                                 impl="xla")).astype(np.float64)
    b = np.asarray(run_diffusion(T16, Cp16, p16, 20, nt_chunk=10,
                                 impl="pallas_interpret")).astype(np.float64)
    err_xla = np.abs(a - ref).max()
    err_pal = np.abs(b - ref).max()
    assert err_pal <= err_xla * 1.05, (err_pal, err_xla)
