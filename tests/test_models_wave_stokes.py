"""Acoustic-wave and PT-Stokes model tests: distributed == single-device on
the implicit global grid, plus physics sanity (wave propagates, PT iteration
converges, buoyancy drives flow)."""

import numpy as np
import pytest

import implicitglobalgrid_tpu as igg
from implicitglobalgrid_tpu.models import (
    init_acoustic3d, init_stokes3d, run_acoustic, run_stokes,
    stokes_residuals,
)


def _acoustic(nx, dims, nt, overlap=False, periods=(0, 0, 0)):
    igg.init_global_grid(nx, nx, nx, dimx=dims[0], dimy=dims[1], dimz=dims[2],
                         periodx=periods[0], periody=periods[1],
                         periodz=periods[2], quiet=True)
    state, p = init_acoustic3d(dtype=np.float64, overlap=overlap)
    state = run_acoustic(state, p, nt, nt_chunk=5)
    out = [igg.gather_interior(a) for a in state]
    igg.finalize_global_grid()
    return out


def test_acoustic_distributed_matches_single():
    multi = _acoustic(6, (2, 2, 2), nt=12)
    single = _acoustic(10, (1, 1, 1), nt=12)
    for m, s in zip(multi, single):
        assert m.shape == s.shape
        assert np.allclose(m, s, rtol=0, atol=1e-12)


def test_acoustic_overlap_matches_plain():
    a = _acoustic(8, (2, 2, 2), nt=10, overlap=False)
    b = _acoustic(8, (2, 2, 2), nt=10, overlap=True)
    for x, y in zip(a, b):
        assert np.array_equal(x, y)


def test_acoustic_f32_stays_f32_under_x64():
    """Params must be weak python floats: a np.float64 scalar would promote
    f32 state to f64 under jax_enable_x64 (regression: hide_communication
    dtype mismatch)."""
    igg.init_global_grid(8, 8, 8, dimx=2, dimy=2, dimz=2, quiet=True)
    state, p = init_acoustic3d(dtype=np.float32, overlap=True)
    out = run_acoustic(state, p, 4, nt_chunk=2)
    assert all(a.dtype == np.float32 for a in out)


def test_acoustic_wave_propagates():
    P0 = _acoustic(8, (2, 2, 2), nt=0)[0]
    P1 = _acoustic(8, (2, 2, 2), nt=20)[0]
    # pulse leaves the center, energy radiates outward
    c = P0.shape[0] // 2
    assert P1[c, c, c] < P0[c, c, c]
    assert np.abs(P1).sum() > 0


def _stokes(nx, dims, nt):
    igg.init_global_grid(nx, nx, nx, dimx=dims[0], dimy=dims[1], dimz=dims[2],
                         quiet=True)
    state, p = init_stokes3d(dtype=np.float64)
    state = run_stokes(state, p, nt, nt_chunk=10)
    res = stokes_residuals(state, p)
    out = [igg.gather_interior(state[i]) for i in range(4)]  # P, Vx, Vy, Vz
    igg.finalize_global_grid()
    return out, res


def test_stokes_distributed_matches_single():
    multi, _ = _stokes(6, (2, 2, 2), nt=10)
    single, _ = _stokes(10, (1, 1, 1), nt=10)
    for m, s in zip(multi, single):
        assert m.shape == s.shape
        assert np.allclose(m, s, rtol=0, atol=1e-12)


def test_stokes_converges_and_buoyancy_drives_flow():
    igg.init_global_grid(12, 12, 12, dimx=2, dimy=2, dimz=2, quiet=True)
    state, p = init_stokes3d(dtype=np.float64)
    r0 = stokes_residuals(state, p)
    state = run_stokes(state, p, 60, nt_chunk=30)
    r1 = stokes_residuals(state, p)
    # momentum residual drops as the PT iteration relaxes
    assert r1[1] < r0[1]
    # the buoyant sphere drives upward flow at the domain center
    Vz = igg.gather_interior(state[3])
    c = Vz.shape[0] // 2
    assert Vz[c, c, c] > 0


@pytest.mark.parametrize("dims,periods,label", [
    ((1, 1, 1), (1, 1, 1), "all self-neighbor"),
    ((2, 2, 2), (1, 1, 1), "all multi-shard periodic"),
    ((2, 2, 2), (0, 0, 0), "all multi-shard PROC_NULL edges"),
    ((1, 2, 4), (1, 0, 1), "self x + PROC_NULL y + 4-shard z"),
    ((1, 1, 1), (0, 0, 0), "no exchange at all"),
])
def test_acoustic_pallas_fused_matches_xla(dims, periods, label):
    """The fused acoustic Pallas pass (updates + 4-field exchange in ONE
    kernel, `ops/pallas_wave.py`) must reproduce the XLA step + sequential
    per-field exchanges over a multi-step run — staggered send slabs,
    PROC_NULL masking, and cross-field corner semantics included."""
    from implicitglobalgrid_tpu.ops.pallas_wave import wave_exchange_modes

    igg.init_global_grid(8, 8, 16, dimx=dims[0], dimy=dims[1], dimz=dims[2],
                         periodx=periods[0], periody=periods[1],
                         periodz=periods[2], quiet=True)
    gg = igg.global_grid()
    state, p = init_acoustic3d(dtype=np.float32)
    shapes = tuple(
        tuple(int(s) // int(gg.dims[d]) for d, s in enumerate(a.shape))
        for a in state)
    modes = wave_exchange_modes(gg, shapes)
    assert modes is not None, label
    if periods == (0, 0, 0) and dims == (1, 1, 1):
        # nothing exchanges: all-False modes -> pure fused update
        assert not any(any(m) for m in modes.values()), label
    a = run_acoustic(state, p, 6, nt_chunk=3, impl="xla")
    b = run_acoustic(state, p, 6, nt_chunk=3, impl="pallas_interpret")
    for fa, fb, name in zip(a, b, ("P", "Vx", "Vy", "Vz")):
        ga, gb = np.asarray(igg.gather(fa)), np.asarray(igg.gather(fb))
        assert np.allclose(ga, gb, rtol=1e-5, atol=1e-5), (label, name)


def test_acoustic_plane_form_relay_matches_xla():
    """The plane-per-program wave kernel (local nx=10: indivisible by any
    mp plane count, so the mp gate rejects) with the P[i-1] VMEM relay
    must match the XLA formulation."""
    from implicitglobalgrid_tpu.ops.pallas_wave import wave_mp_planes

    igg.init_global_grid(10, 8, 16, dimx=1, dimy=1, dimz=1,
                         periodx=1, periody=1, periodz=1, quiet=True)
    assert wave_mp_planes((10, 8, 16), np.float32, interpret=True) is None
    state, p = init_acoustic3d(dtype=np.float32)
    a = run_acoustic(state, p, 6, nt_chunk=3, impl="xla")
    b = run_acoustic(state, p, 6, nt_chunk=3, impl="pallas_interpret")
    for fa, fb, name in zip(a, b, ("P", "Vx", "Vy", "Vz")):
        ga, gb = np.asarray(igg.gather(fa)), np.asarray(igg.gather(fb))
        assert np.allclose(ga, gb, rtol=1e-5, atol=1e-5), name


def test_acoustic_pallas_window_handoff_matches_xla():
    """The acoustic pressure window with the VMEM overlap handoff
    (local nx=12, P=4 -> 3 windows): fused pass equality vs the XLA
    formulation."""
    from implicitglobalgrid_tpu.ops.pallas_wave import wave_mp_planes

    igg.init_global_grid(12, 8, 16, dimx=1, dimy=1, dimz=1,
                         periodx=1, periody=1, periodz=1, quiet=True)
    assert wave_mp_planes((12, 8, 16), np.float32, interpret=True) == 4
    state, p = init_acoustic3d(dtype=np.float32)
    a = run_acoustic(state, p, 6, nt_chunk=3, impl="xla")
    b = run_acoustic(state, p, 6, nt_chunk=3, impl="pallas_interpret")
    for fa, fb, name in zip(a, b, ("P", "Vx", "Vy", "Vz")):
        ga, gb = np.asarray(igg.gather(fa)), np.asarray(igg.gather(fb))
        assert np.allclose(ga, gb, rtol=1e-5, atol=1e-5), name


@pytest.mark.parametrize("dims,periods,label", [
    ((1, 1, 1), (1, 1, 1), "all self-neighbor"),
    ((2, 2, 2), (0, 0, 0), "all multi-shard PROC_NULL edges"),
    ((2, 2, 2), (1, 1, 1), "all multi-shard periodic"),
    ((1, 2, 4), (1, 0, 1), "self x + PROC_NULL y + 4-shard z"),
    ((2, 2, 1), (1, 1, 1), "2x2 periodic + self z folded"),
    ((2, 2, 1), (0, 0, 1), "2x2 PROC_NULL x/y + self z folded"),
    ((2, 1, 1), (1, 1, 1), "2-shard x + self y swapped + self z folded"),
    ((1, 2, 1), (0, 1, 1), "no x exchange + 2-shard y + self z folded"),
])
def test_stokes_pallas_fused_matches_xla(dims, periods, label):
    """The fused Stokes Pallas pass (all PT updates + 4-field exchange in
    ONE kernel, `ops/pallas_stokes.py`) must reproduce the XLA step +
    sequential exchanges over a multi-iteration run. A self-neighbor z
    beside a crossing dim is folded into the kernel and the x/y slabs (and
    Vx's extra plane where x does not exchange). Every field starts random,
    so every halo and boundary plane carries data from the first step."""
    from implicitglobalgrid_tpu.ops.pallas_stokes import (
        stokes_exchange_folds_z, stokes_exchange_modes,
    )

    igg.init_global_grid(8, 8, 16, dimx=dims[0], dimy=dims[1], dimz=dims[2],
                         periodx=periods[0], periody=periods[1],
                         periodz=periods[2], quiet=True)
    gg = igg.global_grid()
    state, p = init_stokes3d(dtype=np.float32)
    rng = np.random.default_rng(7)
    state = tuple(igg.device_put_g(
        rng.uniform(-1, 1, a.shape).astype(np.float32)) for a in state)
    shapes = tuple(
        tuple(int(s) // int(gg.dims[d]) for d, s in enumerate(a.shape))
        for a in state)
    modes = stokes_exchange_modes(gg, shapes)
    assert modes is not None, label
    folds = dims[2] == 1 and periods[2] == 1 and dims[:2] != (1, 1)
    assert stokes_exchange_folds_z(gg, modes) == folds, label
    a = run_stokes(state, p, 4, nt_chunk=2, impl="xla")
    b = run_stokes(state, p, 4, nt_chunk=2, impl="pallas_interpret")
    names = ("P", "Vx", "Vy", "Vz", "dVx", "dVy", "dVz", "rhog")
    for fa, fb, name in zip(a, b, names):
        ga, gb = np.asarray(igg.gather(fa)), np.asarray(igg.gather(fb))
        scale = max(1e-30, np.abs(ga).max())
        assert np.allclose(ga, gb, rtol=1e-4, atol=1e-5 * scale), (
            label, name, np.abs(ga - gb).max())
