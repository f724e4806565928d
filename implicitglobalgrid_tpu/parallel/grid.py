"""Grid lifecycle: `init_global_grid`, `finalize_global_grid`, `select_device`.

TPU-native re-design of the reference's lifecycle layer
(`/root/reference/src/init_global_grid.jl`, `src/finalize_global_grid.jl`,
`src/select_device.jl`). The MPI pieces map as:

- `MPI.Init` / world size        → JAX runtime (+ `jax.distributed.initialize`
                                   in multi-host deployments)
- `MPI.Dims_create!`             → `topology.dims_create`
- `MPI.Cart_create(...,reorder)` → `mesh.build_mesh` (reorder = ICI-aware
                                   device layout via mesh_utils)
- `MPI.Cart_coords/Cart_shift`   → `lax.axis_index` inside shard_map /
                                   `topology.neighbors_table`
- node-local GPU binding (`select_device.jl:15-39`) → no-op: PJRT binds
  devices; kept as an API shim.

Every argument-coherence check of the reference (`init_global_grid.jl:82-91`)
is reproduced with the same message in spirit.
"""

from __future__ import annotations

import numpy as np

from ..utils.config import read_env_config
from ..utils.exceptions import (
    AlreadyInitializedError, IncoherentArgumentError, InvalidArgumentError,
)
from . import topology as top
from .mesh import build_mesh, controller_coords_of, resolve_devices
from .topology import GlobalGrid, NDIMS, dims_create, set_global_grid

__all__ = ["init_global_grid", "finalize_global_grid", "select_device"]

DEVICE_TYPE_NONE = "none"
DEVICE_TYPE_AUTO = "auto"
SUPPORTED_DEVICE_TYPES = ("tpu", "cpu", "gpu")  # analog of shared.jl:33-37


def init_global_grid(
    nx: int, ny: int = 1, nz: int = 1, *,
    dimx: int = 0, dimy: int = 0, dimz: int = 0,
    periodx: int = 0, periody: int = 0, periodz: int = 0,
    overlaps=(2, 2, 2),
    halowidths=None,
    disp: int = 1,
    reorder: int = 1,
    devices=None,
    init_dist: bool | None = None,
    device_type: str = DEVICE_TYPE_AUTO,
    select_device: bool = True,
    quiet: bool = False,
):
    """Initialize the Cartesian device grid, implicitly defining the global grid.

    API parity with the reference (`init_global_grid.jl:41`): ``nx, ny, nz``
    are the size of each LOCAL block; ``dimx/y/z`` fix shards per dimension
    (0 = choose automatically, the `MPI_Dims_create` analog);
    ``periodx/y/z`` make dimensions periodic; ``overlaps``/``halowidths`` as in
    the reference; ``disp`` is the neighbor displacement (`Cart_shift` analog);
    ``reorder`` lets the mesh builder pick an ICI-contiguous device layout.

    TPU-specific replacements:

    - ``devices``: explicit JAX device list (default: all devices of the
      selected backend) — the analog of the ``comm`` kwarg.
    - ``init_dist``: initialize `jax.distributed` (multi-host). Default
      ``None`` auto-initializes only when a cluster environment is detected —
      the analog of ``init_MPI``.
    - ``device_type``: "tpu", "cpu", "gpu", "none" (CPU-only) or "auto"
      (reference `init_global_grid.jl:76-81`).

    Returns ``(me, dims, nprocs, coords, mesh)`` — mesh takes the place of the
    reference's ``comm_cart`` (`init_global_grid.jl:116`).
    """
    if top.grid_is_initialized():
        raise AlreadyInitializedError("The global grid has already been initialized.")

    cfg = read_env_config()

    nxyz = np.array([nx, ny, nz], dtype=np.int64)
    dims = np.array([dimx, dimy, dimz], dtype=np.int64)
    periods = np.array([periodx, periody, periodz], dtype=np.int64)
    overlaps = np.array(list(overlaps), dtype=np.int64)
    if overlaps.shape != (NDIMS,):
        raise InvalidArgumentError("overlaps must have 3 entries.")
    if halowidths is None:
        halowidths = np.maximum(1, overlaps // 2)  # reference default, init_global_grid.jl:41
    halowidths = np.array(list(halowidths), dtype=np.int64)
    if halowidths.shape != (NDIMS,):
        raise InvalidArgumentError("halowidths must have 3 entries.")

    # Argument-coherence checks (reference init_global_grid.jl:76-91).
    if device_type not in (DEVICE_TYPE_NONE, DEVICE_TYPE_AUTO) + SUPPORTED_DEVICE_TYPES:
        raise InvalidArgumentError(
            f"Argument `device_type`: invalid value obtained ({device_type}). Valid values "
            f"are: {', '.join(SUPPORTED_DEVICE_TYPES + (DEVICE_TYPE_NONE, DEVICE_TYPE_AUTO))}"
        )
    if np.any(nxyz < 1):
        raise InvalidArgumentError("Invalid arguments: nx, ny, and nz cannot be less than 1.")
    if np.any(dims < 0):
        raise InvalidArgumentError("Invalid arguments: dimx, dimy, and dimz cannot be negative.")
    if np.any(~np.isin(periods, (0, 1))):
        raise InvalidArgumentError(
            "Invalid arguments: periodx, periody, and periodz must be either 0 or 1."
        )
    if np.any(halowidths < 1):
        raise InvalidArgumentError("Invalid arguments: halowidths cannot be less than 1.")
    if nx == 1:
        raise InvalidArgumentError("Invalid arguments: nx can never be 1.")
    if ny == 1 and nz > 1:
        raise InvalidArgumentError("Invalid arguments: ny cannot be 1 if nz is greater than 1.")
    if np.any((nxyz == 1) & (dims > 1)):
        raise IncoherentArgumentError(
            "Incoherent arguments: if nx, ny, or nz is 1, then the corresponding dimx, dimy "
            "or dimz must not be set (or set 0 or 1)."
        )
    if np.any((nxyz < 2 * overlaps - 1) & (periods > 0)):
        raise IncoherentArgumentError(
            "Incoherent arguments: if nx, ny, or nz is smaller than 2*overlaps[d]-1, then the "
            "corresponding periodx, periody or periodz must not be set (or set 0)."
        )
    if np.any((overlaps > 0) & (halowidths > overlaps // 2)):
        raise IncoherentArgumentError(
            "Incoherent arguments: if overlap is greater than 0, then halowidth cannot be "
            "greater than overlap//2, in each dimension."
        )
    dims[(nxyz == 1) & (dims == 0)] = 1  # reference init_global_grid.jl:91

    # Runtime init (analog of MPI.Init, init_global_grid.jl:92-97).
    import jax

    if init_dist is None:
        import os

        # Auto-detect a cluster environment WITHOUT touching any jax API that
        # would initialize the XLA backend (jax.distributed.initialize must
        # run before backend init).
        init_dist = bool(
            os.environ.get("JAX_COORDINATOR_ADDRESS")
            or os.environ.get("COORDINATOR_ADDRESS")
        ) and not jax.distributed.is_initialized()
    if init_dist:
        if jax.distributed.is_initialized():
            raise AlreadyInitializedError(
                "jax.distributed is already initialized. Pass init_dist=False."
            )
        try:
            jax.distributed.initialize()
        except RuntimeError as e:
            raise AlreadyInitializedError(
                f"jax.distributed failed to initialize: {e}. If the runtime was "
                "already set up, pass init_dist=False."
            ) from e

    if devices is None:
        devices, resolved_type = resolve_devices(device_type, cfg.platform)
    else:
        devices = list(devices)
        resolved_type = devices[0].platform if devices else "none"

    # nprocs: with fully-fixed dims, the grid takes prod(dims) devices (a
    # subset is allowed — unlike MPI, the device pool is not the job size);
    # otherwise all devices are used and free dims are filled like
    # MPI_Dims_create (reference init_global_grid.jl:98-99).
    if np.all(dims > 0):
        nprocs = int(np.prod(dims))
    else:
        nprocs = len(devices)
        # Free dims of size-1 grid dimensions were pinned to 1 above. When
        # the pool size is not a multiple of the fixed dims (e.g. 6 devices
        # with dimx=4), fall back to the largest usable device subset —
        # mirroring the fully-fixed case, where a subset is already allowed
        # (unlike MPI, the device pool is not the job size).
        fixed = int(np.prod(dims[dims > 0])) if np.any(dims > 0) else 1
        if fixed > nprocs:
            raise InvalidArgumentError(
                f"The fixed dims require {fixed} shard(s) but only {nprocs} "
                "device(s) are available; reduce dimx/dimy/dimz or pass a "
                "larger device pool via devices=."
            )
        if nprocs % fixed != 0:
            import warnings

            new = (nprocs // fixed) * fixed
            warnings.warn(
                f"Device pool of {nprocs} is not a multiple of the fixed "
                f"dims product ({fixed}); using {new} device(s) — "
                f"{nprocs - new} idle. Adjust dimx/dimy/dimz or pass "
                "devices= to use the full pool.")
            nprocs = new
    dims = dims_create(nprocs, dims)
    if int(np.prod(dims)) > len(devices):
        raise InvalidArgumentError(
            f"Grid of {int(np.prod(dims))} shards exceeds the {len(devices)} available device(s)."
        )

    mesh = build_mesh(tuple(int(d) for d in dims), devices, reorder,
                      cfg.dcn_axes)

    me = jax.process_index()
    # This controller's Cartesian coords — its first addressable device's
    # mesh position (reference per-rank `Cart_coords`,
    # `init_global_grid.jl:101-106`). Zeros in single-controller runs, where
    # per-shard coords come from `lax.axis_index` inside shard_map.
    coords = controller_coords_of(mesh.devices, me)

    # THE implicit-global-grid formula (reference init_global_grid.jl:107).
    nxyz_g = dims * (nxyz - overlaps) + overlaps * (periods == 0)

    gg = GlobalGrid(
        nxyz_g=nxyz_g, nxyz=nxyz, dims=dims, overlaps=overlaps,
        halowidths=halowidths, nprocs=nprocs, me=me, coords=coords,
        periods=periods, disp=int(disp), reorder=int(reorder), mesh=mesh,
        device_type=resolved_type,
        # Pallas kernel tier: on by default on TPU (measured ~3x over the
        # broadcast form — bench.py), explicit IGG_USE_PALLAS[=0] overrides.
        use_pallas=np.array(
            [(resolved_type == "tpu") if v is None else v for v in cfg.use_pallas],
            dtype=bool),
        dcn_axes=cfg.dcn_axes, quiet=bool(quiet),
    )
    set_global_grid(gg)

    if not quiet and me == 0:
        print(
            f"Global grid: {int(nxyz_g[0])}x{int(nxyz_g[1])}x{int(nxyz_g[2])} "
            f"(nprocs: {nprocs}, dims: {int(dims[0])}x{int(dims[1])}x{int(dims[2])}; "
            f"device support: {resolved_type})"
        )

    if select_device and resolved_type not in ("none",):
        _select_device()

    from ..utils.timing import init_timing_functions

    init_timing_functions()
    return me, dims.copy(), nprocs, coords.copy(), mesh


def finalize_global_grid(*, finalize_dist: bool = False) -> None:
    """Finalize the global grid (reference `finalize_global_grid.jl:15-26`):
    free the compiled halo-exchange programs (the buffer-pool analog), reset
    the singleton, optionally shut down `jax.distributed`."""
    import gc

    top.check_initialized()
    from ..ops.halo import free_update_halo_caches
    from ..utils import timing

    free_update_halo_caches()
    # barrier probes: same retention rule as the exchange caches — a
    # scheduler-held tenant's probe survives another tenant's finalize
    for k in [k for k in timing._probe_cache
              if k not in top._retained_epochs]:
        del timing._probe_cache[k]
    timing._t0 = None  # a chronometer from a dead grid epoch is meaningless
    if finalize_dist:
        import jax

        try:
            jax.distributed.shutdown()
        except Exception:
            pass
    set_global_grid(None)
    gc.collect()


def node_local_rank():
    """(node-local rank, processes on this host, devices on this host) — the
    analog of the reference's `MPI.Comm_split_type(COMM_TYPE_SHARED)` +
    `Comm_rank` node grouping (`select_device.jl:26-32`).

    COLLECTIVE in multi-process runs (like the reference's MPI call):
    every process must call it, or the callers deadlock in the allgather.
    Processes are grouped by hostname (gathered with a tiny
    `process_allgather` — the shared-memory-communicator analog); the rank
    is this process's index among co-hosted processes in `process_index`
    order. Single-process runs return ``(0, 1, local device count)``
    without any collective."""
    import jax

    n_local = len(jax.local_devices())
    if jax.process_count() == 1:
        return 0, 1, n_local
    import hashlib
    import socket

    import numpy as np
    from jax.experimental import multihost_utils

    # int32-safe hash: without jax_enable_x64 the allgather truncates int64
    h = int.from_bytes(
        hashlib.sha1(socket.gethostname().encode()).digest()[:4], "big",
        signed=True)
    row = np.array([h, n_local], dtype=np.int32)
    allrows = np.asarray(multihost_utils.process_allgather(row))
    mine = jax.process_index()
    same = [i for i in range(allrows.shape[0]) if allrows[i, 0] == h]
    me_l = same.index(mine)
    dev_on_node = int(sum(allrows[i, 1] for i in same))
    return me_l, len(same), dev_on_node


def _select_device():
    """Device binding (reference `_select_device`, `select_device.jl:15-39`).

    The reference computes the node-local rank and binds that GPU, erroring
    when a node hosts more ranks than devices. With PJRT each process's
    devices are already bound at runtime init, so binding is a no-op — but
    the node-grouping semantics and the oversubscription guard are kept:
    more co-hosted controllers than devices on the host is a config error
    (unrepresentable in healthy PJRT deployments, where every process owns
    at least one device — the check guards degenerate runtimes)."""
    import jax

    me_l, n_procs_node, dev_on_node = node_local_rank()
    if n_procs_node > dev_on_node:
        raise IncoherentArgumentError(
            f"This host runs {n_procs_node} processes but only "
            f"{dev_on_node} device(s): it is not possible to run more "
            "processes per node than there are devices on it (reference "
            "select_device.jl:28)."
        )
    return jax.local_devices()[0].id


def select_device() -> int:
    """Return the device id bound to this process after the node-local
    oversubscription check (reference `select_device`, `select_device.jl:15`).

    COLLECTIVE in multi-process runs — every process must call it together,
    exactly like the reference's `MPI.Comm_split_type` inside
    `_select_device` (`select_device.jl:26`). `init_global_grid` calls it
    symmetrically on every process."""
    top.check_initialized()
    return _select_device()
