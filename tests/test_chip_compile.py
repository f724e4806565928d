"""Compile-only checks of the main-path programs for a described TPU v5e.

Nothing runs: each test builds a grid over devices of a described v5e:2x2
topology and compiles a runner at the benchmark width through the TPU's
own compiler, which checks what the Pallas interpreter skips (Mosaic tile
alignment, VMEM limits, partitioning). The topology is described inside
the fixture only: only one process may load libtpu, and under xdist every
worker imports this file (the `on-chip-measurement` guide, section 2).
"""

import numpy as np
import pytest


@pytest.fixture(scope="module")
def topo():
    import os

    import jax

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        from jax.experimental import topologies

        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it. The
    # suite runs with x64 on (conftest); the chip path is f32, and the
    # kernels' index arithmetic does not trace under x64.
    was = (jax.config.jax_enable_compilation_cache,
           jax.config.jax_enable_x64)
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was[0])
    jax.config.update("jax_enable_x64", was[1])


@pytest.fixture
def grid_on(topo, monkeypatch):
    """``grid_on(n, dims, ndim=3, periodic=True)`` inits the grid on the
    first ``prod(dims)`` described devices. `init_global_grid` executes a
    barrier probe, which a described device cannot run: stub it."""
    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.utils import timing

    monkeypatch.setattr(timing, "_device_barrier", lambda: None)

    def init(n, dims, ndim=3, periodic=True):
        nz = n if ndim == 3 else 1
        per = int(periodic)
        igg.init_global_grid(n, n, nz, dimx=dims[0], dimy=dims[1],
                             dimz=dims[2], periodx=per, periody=per,
                             periodz=per if ndim == 3 else 0,
                             devices=topo.devices[:int(np.prod(dims))],
                             select_device=False, quiet=True)
        return igg.global_grid()

    return init


def _sds(gg, shapes, dtype):
    """Stacked-array shapes with the grid's field sharding."""
    import jax
    from jax.sharding import NamedSharding

    from implicitglobalgrid_tpu.ops.fields import field_partition_spec

    out = []
    for loc in shapes:
        glob = tuple(int(n) * int(d) for n, d in zip(loc, gg.dims))
        out.append(jax.ShapeDtypeStruct(glob, dtype, sharding=NamedSharding(
            gg.mesh, field_partition_spec(len(loc)))))
    return out


def _compiled_text(runner, args):
    return runner.lower(*args).compile().as_text()


def _check(txt, multi):
    assert "tpu_custom_call" in txt
    if multi:
        assert "collective-permute" in txt


def _assert_z_folded(txt, n):
    """On a 2x2x1 mesh z is a self-neighbor axis: the fused step+exchange
    folds its halo into the kernel and the x/y send slabs, so the program
    holds no lane-sparse z slab (minor dim 1-3, padded to 128 lanes), the
    kernel takes no (n, n, 2) z operand, and T is never copied whole into
    a z-major layout."""
    import re

    assert not re.search(rf"f32\[{n},{n},[123]\]", txt)
    for line in txt.splitlines():
        if "tpu_custom_call" in line:
            assert f"f32[{n},{n},2]" not in line
        assert not re.search(
            rf"= f32\[{n},{n},{n}\]\{{1,0,2\b[^}}]*\}} copy\(", line)


def _assert_stokes_z_folded(txt, n):
    """The PT Stokes analog of `_assert_z_folded`: on a 2x2x1 mesh the
    fused pass folds the self-neighbor z halo of P, Vx, Vy and Vz into the
    kernel and the x/y send slabs, so no array of the program is a
    lane-sparse z window over the x-y extent (the mini-state slab computes
    and the concatenated z recvs, minor dim 1-4), and the PT kernel takes
    no (.., .., 2) z operand."""
    import re

    xy = rf"{n}|{n + 1}"
    assert not re.search(rf"f32\[(?:{xy}),(?:{xy}),[1-4]\]", txt)
    for line in txt.splitlines():
        if "tpu_custom_call" in line and "igg.stokes.pt/" in line:
            assert not re.search(r"f32\[\d+,\d+,2\]", line)


@pytest.mark.parametrize("n,dims,dtype", [
    (256, (1, 1, 1), np.float32),
    (256, (1, 1, 1), "bfloat16"),
    (256, (2, 2, 1), np.float32),
], ids=["1chip-f32", "1chip-bf16", "2x2x1-f32"])
def test_diffusion3d_runner_compiles(grid_on, n, dims, dtype):
    import jax.numpy as jnp

    from implicitglobalgrid_tpu.models import DiffusionParams, make_run

    gg = grid_on(n, dims)
    d = 10.0 / (int(gg.nxyz_g[0]) - 1)
    p = DiffusionParams(lam=1.0, dt=d * d / 8.1, dx=d, dy=d, dz=d)
    run = make_run(p, nt_chunk=2)
    txt = _compiled_text(run, _sds(gg, [(n, n, n)] * 2, jnp.dtype(dtype)))
    _check(txt, multi=gg.nprocs > 1)
    if dims == (2, 2, 1):
        _assert_z_folded(txt, n)


def test_diffusion2d_strip_kernel_compiles(grid_on):
    from implicitglobalgrid_tpu.models import DiffusionParams, make_run

    n = 4096
    gg = grid_on(n, (1, 1, 1), ndim=2)
    d = 10.0 / (int(gg.nxyz_g[0]) - 1)
    p = DiffusionParams(lam=1.0, dt=d * d / 4.1, dx=d, dy=d)
    run = make_run(p, nt_chunk=2, ndim=2)
    _check(_compiled_text(run, _sds(gg, [(n, n)] * 2, np.float32)),
           multi=False)


@pytest.mark.parametrize("dims", [(1, 1, 1), (2, 2, 1)],
                         ids=["1chip", "2x2x1"])
def test_acoustic3d_runner_compiles(grid_on, dims):
    from implicitglobalgrid_tpu.models import AcousticParams, make_acoustic_run

    n = 192
    gg = grid_on(n, dims)
    d = 10.0 / (int(gg.nxyz_g[0]) - 1)
    p = AcousticParams(rho=1.0, K=1.0, dt=d / np.sqrt(3.1), dx=d, dy=d, dz=d)
    run = make_acoustic_run(p, nt_chunk=2)
    shapes = [(n, n, n), (n + 1, n, n), (n, n + 1, n), (n, n, n + 1)]
    _check(_compiled_text(run, _sds(gg, shapes, np.float32)),
           multi=gg.nprocs > 1)


@pytest.mark.parametrize("n,dims,periodic,nt_chunk", [
    (128, (1, 1, 1), False, 2),
    (256, (1, 1, 1), True, 200),
    (256, (2, 2, 1), True, 100),
], ids=["128-1chip-walls", "256-1chip-periodic", "256-2x2x1-periodic"])
def test_stokes3d_runner_compiles(grid_on, n, dims, periodic, nt_chunk):
    """At the benchmark cells' local size and chunk lengths: the fused
    pass's scoped VMEM depends on the chunk loop around it."""
    import re
    from collections import Counter

    from implicitglobalgrid_tpu.models import StokesParams, make_stokes_run

    gg = grid_on(n, dims, periodic=periodic)
    n_g = max(int(v) for v in gg.nxyz_g)
    d = 10.0 / (n_g - 1)
    p = StokesParams(mu=1.0, dt_v=d * d / 6.1 / 2.0, dt_p=6.1 / n_g,
                     damp=1.0 - 6.0 / n_g, dx=d, dy=d, dz=d)
    run = make_stokes_run(p, nt_chunk=nt_chunk)
    c, fx, fy, fz = (n, n, n), (n + 1, n, n), (n, n + 1, n), (n, n, n + 1)
    shapes = [c, fx, fy, fz, fx, fy, fz, c]
    txt = _compiled_text(run, _sds(gg, shapes, np.float32))
    _check(txt, multi=gg.nprocs > 1)
    # the device-side scope reaches the compiled kernel's metadata
    assert any("custom-call(" in line and "igg.stokes.pt/pallas_call" in line
               for line in txt.splitlines())
    if gg.nprocs > 1:
        # the 4 exchanged fields ride one permute pair (left and right)
        # per crossing axis per step; 3 custom calls a step (the PT pass
        # and the two extra-plane writes)
        steps = txt.count("tpu_custom_call") // 3
        pairs = re.findall(r"collective-permute-start\(.*"
                           r"source_target_pairs=(\{\{[0-9,{}]*\}\})", txt)
        crossing = sum(int(v) > 1 for v in dims)
        assert steps and sorted(Counter(pairs).values()) == [2 * steps] * crossing
    if dims == (2, 2, 1):
        _assert_stokes_z_folded(txt, n)
