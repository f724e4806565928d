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
    lane-sparse z window over the x-y extent (z send slabs and the
    concatenated z recvs, minor dim 1-4), and the PT kernel takes no
    (.., .., 2) z operand."""
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
        # per crossing axis per step; 5 custom calls a step: the PT pass,
        # the two extra-plane writes and one send-slab pass per crossing
        # axis, each under its scope
        crossing = sum(int(v) > 1 for v in dims)
        steps, rest = divmod(txt.count("tpu_custom_call"), 3 + crossing)
        assert steps and not rest
        pairs = re.findall(r"collective-permute-start\(.*"
                           r"source_target_pairs=(\{\{[0-9,{}]*\}\})", txt)
        assert sorted(Counter(pairs).values()) == [2 * steps] * crossing
        calls = [line for line in txt.splitlines()
                 if "tpu_custom_call" in line]
        scopes = Counter(re.search(r"igg\.stokes\.(\w+)/pallas_call",
                                   line).group(1) for line in calls)
        assert scopes == {"pt": steps, "vx_planes": 2 * steps,
                          "slabs": crossing * steps}
    if dims == (2, 2, 1):
        _assert_stokes_z_folded(txt, n)


# Lowers the 2x2x1 PT Stokes chunk program of the benchmark cell (256^3
# per chip, chunks of 100) for a described v5e:2x2 and prints what the
# persistent compile cache keys and gates on; exits 3 where no topology
# can be described.
_LOWER_STOKES_2X2 = r"""
import hashlib, json, os, re, sys
import numpy as np
import jax
jax.config.update("jax_enable_compilation_cache", False)
try:
    from jax.experimental import topologies
    desc = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
except Exception:
    sys.exit(3)
from jax.sharding import NamedSharding
import implicitglobalgrid_tpu as igg
from implicitglobalgrid_tpu.models import StokesParams, make_stokes_run
from implicitglobalgrid_tpu.ops.fields import field_partition_spec
from implicitglobalgrid_tpu.utils import timing
timing._device_barrier = lambda: None
n = 256
igg.init_global_grid(n, n, n, dimx=2, dimy=2, dimz=1, periodx=1,
                     periody=1, periodz=1, devices=desc.devices[:4],
                     select_device=False, quiet=True)
gg = igg.global_grid()
d = 10.0 / (int(gg.nxyz_g[0]) - 1)
p = StokesParams(mu=1.0, dt_v=d * d / 6.1 / 2.0, dt_p=6.1 / 511,
                 damp=1.0 - 6.0 / 511, dx=d, dy=d, dz=d)
sh = NamedSharding(gg.mesh, field_partition_spec(3))
c, fx, fy, fz = (n, n, n), (n + 1, n, n), (n, n + 1, n), (n, n, n + 1)
args = [jax.ShapeDtypeStruct((s[0] * 2, s[1] * 2, s[2]), np.float32,
                             sharding=sh)
        for s in (c, fx, fy, fz, fx, fy, fz, c)]
low = make_stokes_run(p, nt_chunk=100).lower(*args)
text = low.as_text()
print(json.dumps({
    "sha256": hashlib.sha256(text.encode()).hexdigest(),
    "host_callbacks": len(low._lowering.compile_args["host_callbacks"]),
    "callback_ops": len(re.findall(
        r"callback|stablehlo\.(?:send|recv)\b", text)),
    "custom_calls": text.count("tpu_custom_call"),
}))
"""


def test_stokes_2x2_chunk_program_is_cacheable():
    """JAX's persistent cache serves a program to a later process only if
    that process lowers it to the same text, and writes none that has host
    callbacks: the 2x2x1 Stokes chunk program lowers alike under two hash
    seeds (no kernel parameter built from set or dict order or an object's
    identity) and holds no host callback."""
    import json
    import os
    import subprocess
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parent.parent
    got = []
    for seed in ("1", "2"):
        env = dict(os.environ, PYTHONHASHSEED=seed, JAX_PLATFORMS="cpu",
                   TPU_LOG_DIR="disabled",
                   PYTHONPATH=str(root))
        env.pop("XLA_FLAGS", None)
        r = subprocess.run([sys.executable, "-c", _LOWER_STOKES_2X2],
                           cwd=root, env=env, capture_output=True,
                           text=True, timeout=600)
        if r.returncode == 3:
            pytest.skip("no v5e:2x2 topology can be described in a child "
                        "process here")
        assert r.returncode == 0, r.stderr[-4000:]
        got.append(json.loads(r.stdout.strip().splitlines()[-1]))
    assert got[0] == got[1]
    assert got[0]["host_callbacks"] == 0 and got[0]["callback_ops"] == 0
    # one step: the PT pass, the two extra-plane writes, the x and y
    # send-slab passes
    assert got[0]["custom_calls"] == 5
