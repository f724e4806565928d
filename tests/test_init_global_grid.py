"""Tests of grid initialization — port of the reference's
`test/test_init_global_grid.jl` ideas: return values, implicit-global-size
formula, argument defaults, and the full error-path catalog
(`test_init_global_grid.jl:96-116`)."""

import numpy as np
import pytest

import implicitglobalgrid_tpu as igg
from implicitglobalgrid_tpu.utils.exceptions import (
    AlreadyInitializedError, IncoherentArgumentError, InvalidArgumentError,
    NotInitializedError,
)


def test_basic_init_returns():
    me, dims, nprocs, coords, mesh = igg.init_global_grid(4, 4, 4, quiet=True)
    assert me == 0
    assert nprocs == 8 and int(np.prod(dims)) == 8
    assert mesh.shape == {"gx": int(dims[0]), "gy": int(dims[1]), "gz": int(dims[2])}
    assert igg.grid_is_initialized()


def test_implicit_global_size_formula():
    # nxyz_g = dims*(nxyz-overlaps) + overlaps*(periods==0)  (init_global_grid.jl:107)
    igg.init_global_grid(5, 5, 5, dimx=2, dimy=2, dimz=2, quiet=True)
    assert (igg.nx_g(), igg.ny_g(), igg.nz_g()) == (8, 8, 8)
    igg.finalize_global_grid()

    igg.init_global_grid(5, 5, 5, dimx=2, dimy=2, dimz=2, periodx=1, quiet=True)
    assert igg.nx_g() == 2 * (5 - 2)  # periodic: no +overlap term
    assert igg.ny_g() == 8
    igg.finalize_global_grid()

    igg.init_global_grid(8, 8, 8, dimx=2, dimy=2, dimz=2,
                         overlaps=(4, 4, 4), quiet=True)
    assert igg.nx_g() == 2 * (8 - 4) + 4


def test_degenerate_dims_pinned():
    # nxyz==1 pins the corresponding dims entry to 1 (init_global_grid.jl:91)
    me, dims, nprocs, *_ = igg.init_global_grid(16, 16, 1, quiet=True)
    assert dims[2] == 1
    assert nprocs == 8 and dims[0] * dims[1] == 8


def test_fixed_dims_use_device_subset():
    me, dims, nprocs, *_ = igg.init_global_grid(4, 4, 4, dimx=2, dimy=1, dimz=1, quiet=True)
    assert nprocs == 2 and list(dims) == [2, 1, 1]


def test_partially_fixed_dims_use_device_subset():
    # 8-device pool, dimx=3: 8 is not a multiple of 3 — fall back to the
    # largest usable subset (6 devices, free dims filled over 6/3=2)
    # instead of a divisibility error (round-3 verdict item 9).
    me, dims, nprocs, *_ = igg.init_global_grid(
        4, 4, 4, dimx=3, quiet=True)
    assert nprocs == 6 and dims[0] == 3 and int(np.prod(dims)) == 6
    igg.finalize_global_grid()

    # prime fixed dim larger than any divisor: subset of exactly `fixed`
    me, dims, nprocs, *_ = igg.init_global_grid(8, 8, 8, dimx=5, quiet=True)
    assert nprocs == 5 and list(dims) == [5, 1, 1]
    igg.finalize_global_grid()

    # fixed dims exceeding the pool: actionable error
    with pytest.raises(InvalidArgumentError, match="device pool"):
        igg.init_global_grid(32, 32, 32, dimx=16, quiet=True)


def test_default_halowidths():
    igg.init_global_grid(8, 8, 8, overlaps=(4, 4, 2), quiet=True)
    gg = igg.global_grid()
    assert list(gg.halowidths) == [2, 2, 1]  # max(1, overlaps//2)


def test_quiet_banner(capsys):
    igg.init_global_grid(4, 4, 4, quiet=True)
    assert capsys.readouterr().out == ""
    igg.finalize_global_grid()
    igg.init_global_grid(5, 5, 5, dimx=2, dimy=2, dimz=2)
    out = capsys.readouterr().out
    assert "Global grid: 8x8x8" in out and "nprocs: 8" in out and "2x2x2" in out


def test_error_paths():
    # catalog from test_init_global_grid.jl:96-116
    with pytest.raises(InvalidArgumentError):
        igg.init_global_grid(0, 4, 4, quiet=True)      # nxyz < 1
    with pytest.raises(InvalidArgumentError):
        igg.init_global_grid(1, 4, 4, quiet=True)      # nx can never be 1
    with pytest.raises(InvalidArgumentError):
        igg.init_global_grid(4, 1, 4, quiet=True)      # ny==1 while nz>1
    with pytest.raises(InvalidArgumentError):
        igg.init_global_grid(4, 4, 4, dimx=-1, quiet=True)
    with pytest.raises(InvalidArgumentError):
        igg.init_global_grid(4, 4, 4, periodx=2, quiet=True)
    with pytest.raises(InvalidArgumentError):
        igg.init_global_grid(4, 4, 4, halowidths=(0, 1, 1), quiet=True)
    with pytest.raises(IncoherentArgumentError):
        igg.init_global_grid(4, 4, 1, dimz=2, quiet=True)       # nz==1 but dimz=2
    with pytest.raises(IncoherentArgumentError):
        igg.init_global_grid(2, 4, 4, periodx=1, quiet=True)    # nx < 2*ol-1 with periodic
    with pytest.raises(IncoherentArgumentError):
        igg.init_global_grid(8, 8, 8, halowidths=(2, 1, 1), quiet=True)  # hw > ol//2
    with pytest.raises(InvalidArgumentError):
        igg.init_global_grid(4, 4, 4, device_type="rocm", quiet=True)
    with pytest.raises(InvalidArgumentError, match="device pool"):
        igg.init_global_grid(4, 4, 4, dimx=5, dimy=2, quiet=True)  # fixed 10 > 8 devices
    with pytest.raises(InvalidArgumentError):
        igg.init_global_grid(4, 4, 4, dimx=5, dimy=2, dimz=1, quiet=True)  # 10 > 8 devices
    assert not igg.grid_is_initialized()


def test_double_init_and_not_initialized():
    igg.init_global_grid(4, 4, 4, quiet=True)
    with pytest.raises(AlreadyInitializedError):
        igg.init_global_grid(4, 4, 4, quiet=True)
    igg.finalize_global_grid()
    with pytest.raises(NotInitializedError):
        igg.nx_g()
    with pytest.raises(NotInitializedError):
        igg.finalize_global_grid()


def test_rejected_env_vars(monkeypatch):
    # reference rejects legacy env vars (init_global_grid.jl:57); the TPU
    # build rejects the GPU-aware-MPI family (N/A on ICI).
    monkeypatch.setenv("IGG_CUDAAWARE_MPI", "1")
    with pytest.raises(InvalidArgumentError):
        igg.init_global_grid(4, 4, 4, quiet=True)


@pytest.mark.parametrize("var", [
    "IGG_HALO_WIRE_STAGE", "IGG_TPU_DCN_GRANULES",
    "IGG_MP_HANDOFF", "IGG_PLANE_RELAY",
])
def test_removed_env_vars_rejected(monkeypatch, var):
    # variables of removed features fail loudly instead of being ignored:
    # a job script that still sets one would otherwise run something else
    monkeypatch.setenv(var, "1")
    with pytest.raises(InvalidArgumentError, match=var):
        igg.init_global_grid(4, 4, 4, quiet=True)


def test_select_device_shim():
    igg.init_global_grid(4, 4, 4, quiet=True)
    assert isinstance(igg.select_device(), int)
