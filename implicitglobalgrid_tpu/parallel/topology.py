"""Cartesian process/device topology and the GlobalGrid singleton.

TPU-native re-design of the reference's shared-state + topology layer
(`/root/reference/src/shared.jl:29-127`, `/root/reference/src/init_global_grid.jl:98-107`).

Where the reference derives the topology from MPI (`MPI.Dims_create!`,
`MPI.Cart_create`, `MPI.Cart_shift` — `init_global_grid.jl:99-106`), here the
topology IS a `jax.sharding.Mesh` over the pod's devices: each mesh coordinate
owns one local block of every field, and the *global* grid is never allocated —
it exists only implicitly through

    nxyz_g = dims * (nxyz - overlaps) + overlaps * (periods == 0)

(the implicit-global-grid formula, reference `init_global_grid.jl:107`).

There is no per-rank state: the single controller holds one `GlobalGrid` whose
mesh spans all shards; per-shard coordinates come from `jax.lax.axis_index`
inside `shard_map` (the analog of `MPI.Cart_coords`).
"""

from __future__ import annotations

import copy
from dataclasses import dataclass
from typing import Any

import numpy as np

from ..utils.exceptions import (
    IncoherentArgumentError,
    InvalidArgumentError,
    ModuleInternalError,
    NotInitializedError,
)

__all__ = [
    "NDIMS", "NNEIGHBORS_PER_DIM", "PROC_NULL", "AXIS_NAMES",
    "GlobalGrid", "global_grid", "set_global_grid", "grid_is_initialized",
    "check_initialized", "get_global_grid", "grid_epoch",
    "swap_global_grid", "retain_epoch", "release_epoch", "live_epochs",
    "dims_create", "cart_rank", "cart_coords", "cart_shift", "neighbors_table",
    "ol", "axis_perm_pairs",
]

# Everything is padded to 3-D internally, like the reference (NDIMS_MPI=3,
# `shared.jl:29`): fixed-size coords/neighbors and simple code.
NDIMS = 3
NNEIGHBORS_PER_DIM = 2          # left + right (reference `shared.jl:30`)
PROC_NULL = -1                  # analog of MPI.PROC_NULL (reference `init_global_grid.jl:103`)
AXIS_NAMES = ("gx", "gy", "gz")  # mesh axis names for the three grid dimensions


@dataclass
class GlobalGrid:
    """Singleton grid state (analog of reference `GlobalGrid`, `shared.jl:58-78`).

    Vectors are numpy arrays and the dataclass is mutable on purpose: the
    reference keeps its struct's vectors mutable "useful for writing tests"
    (`shared.jl:57` comment) — tests here simulate topologies the same way.
    """
    nxyz_g: np.ndarray          # implicit global grid size (3,)
    nxyz: np.ndarray            # local block size (3,)
    dims: np.ndarray            # shards per dimension (3,)
    overlaps: np.ndarray        # (3,)
    halowidths: np.ndarray      # (3,)
    nprocs: int                 # number of shards = prod(dims)
    me: int                     # controller process index (jax.process_index())
    coords: np.ndarray          # controller coords; per-shard coords via axis_index
    periods: np.ndarray         # (3,) of 0/1
    disp: int
    reorder: int
    mesh: Any                   # jax.sharding.Mesh with axes AXIS_NAMES (or None)
    device_type: str            # "tpu" | "cpu" | "gpu" | "none"
    use_pallas: np.ndarray      # (3,) bool — pallas pack kernels per dim
    dcn_axes: tuple             # mesh axes that ride DCN (multi-slice)
    quiet: bool
    epoch: int = 0              # bumped at every init; invalidates jit caches

    def __iter__(self):  # convenience: me, dims, nprocs, coords, mesh unpacking
        return iter((self.me, self.dims, self.nprocs, self.coords, self.mesh))


_NULL = None  # sentinel; module-level singleton mirrors reference `shared.jl:83-94`
_global_grid: GlobalGrid | None = _NULL
_epoch_counter: int = 0


def global_grid() -> GlobalGrid:
    check_initialized()
    return _global_grid


def set_global_grid(gg: GlobalGrid | None) -> None:
    global _global_grid, _epoch_counter
    if gg is not None:
        _epoch_counter += 1
        gg.epoch = _epoch_counter
    _global_grid = gg


def grid_is_initialized() -> bool:
    return _global_grid is not None and _global_grid.nprocs > 0


def check_initialized() -> None:
    if not grid_is_initialized():
        raise NotInitializedError(
            "No function of the module can be called before init_global_grid() "
            "or after finalize_global_grid()."
        )


def get_global_grid() -> GlobalGrid:
    """Return a deep copy of the global grid (reference `shared.jl:93`)."""
    check_initialized()
    return copy.deepcopy(_global_grid)


def grid_epoch() -> int:
    check_initialized()
    return _global_grid.epoch


# ---------------------------------------------------------------------------
# Grid multiplexing (the multi-run scheduler's context-switch primitives)
# ---------------------------------------------------------------------------
# A normal init assigns a FRESH epoch (set_global_grid bumps the counter),
# which is what invalidates every epoch-keyed jit cache after a re-init.
# The scheduler (`service.MeshScheduler`) instead keeps SEVERAL live grids
# over one device pool and switches between them per slice; each keeps the
# epoch it was born with, so each job's compiled runners/exchanges stay
# warm across context switches. The caches learn which epochs are live via
# `retain_epoch`/`live_epochs` and evict only the dead ones.

_retained_epochs: set = set()


def swap_global_grid(gg: GlobalGrid | None) -> GlobalGrid | None:
    """Make ``gg`` the current grid WITHOUT assigning a new epoch, and
    return the previously-current grid (or None). This is the scheduler's
    context switch: the swapped-in grid keeps its original epoch, so the
    epoch-keyed compiled-program caches (chunk runners, halo exchanges,
    drain probes) keep serving it. Ordinary code wants `init_global_grid`
    / `finalize_global_grid`; only hold multiple grids over the SAME
    device pool."""
    global _global_grid
    old = _global_grid
    _global_grid = gg
    return old


def retain_epoch(epoch: int) -> None:
    """Mark ``epoch`` as belonging to a live (scheduler-held) grid: the
    epoch-keyed caches will not evict its entries while retained."""
    _retained_epochs.add(int(epoch))


def release_epoch(epoch: int) -> None:
    """Drop the retention of ``epoch`` (no-op if not retained). The epoch's
    cache entries become evictable at the next cache miss; callers that
    want the memory back NOW sweep the caches themselves (the scheduler
    does, on job completion)."""
    _retained_epochs.discard(int(epoch))


def live_epochs() -> frozenset:
    """Epochs whose compiled-program cache entries must survive: the
    current grid's (if any) plus every retained one."""
    live = set(_retained_epochs)
    if _global_grid is not None:
        live.add(_global_grid.epoch)
    return frozenset(live)


# ---------------------------------------------------------------------------
# Topology math (analog of MPI_Dims_create / Cart_create / Cart_shift)
# ---------------------------------------------------------------------------

def dims_create(nprocs: int, dims) -> np.ndarray:
    """Fill the zero entries of ``dims`` with a balanced factorization of
    ``nprocs`` (behavioral analog of `MPI_Dims_create`, used at reference
    `init_global_grid.jl:99`).

    Fixed (nonzero) entries are kept; the remaining factor of ``nprocs`` is
    split across free entries as evenly as possible, larger factors first
    (matching the MPI spec's "as close to each other as possible,
    non-increasing order" requirement).
    """
    dims = np.asarray(dims, dtype=np.int64).copy()
    if dims.shape != (NDIMS,):
        raise InvalidArgumentError(f"dims must have {NDIMS} entries, got {dims.shape}.")
    if np.any(dims < 0):
        raise InvalidArgumentError("Invalid arguments: dimx, dimy, and dimz cannot be negative.")
    fixed = int(np.prod(dims[dims > 0])) if np.any(dims > 0) else 1
    if nprocs % fixed != 0:
        raise IncoherentArgumentError(
            f"nprocs ({nprocs}) is not divisible by the product of the fixed dims ({fixed})."
        )
    rem = nprocs // fixed
    free = [i for i in range(NDIMS) if dims[i] == 0]
    if not free:
        if rem != 1:
            raise IncoherentArgumentError(
                f"prod(dims) ({fixed}) does not equal nprocs ({nprocs})."
            )
        return dims
    # Balanced split of `rem` into len(free) factors, non-increasing.
    # The search branches only on DIVISORS (computed once in O(sqrt rem)),
    # so even nprocs ~ 2^20 costs microseconds, not a dense integer sweep.
    best = None
    k = len(free)
    divs = []
    f = 1
    while f * f <= rem:
        if rem % f == 0:
            divs.append(f)
            if f != rem // f:
                divs.append(rem // f)
        f += 1
    divs.sort(reverse=True)

    def search(remaining, max_factor, acc):
        nonlocal best
        if len(acc) == k - 1:
            if remaining <= max_factor:
                cand = tuple(acc + [remaining])
                score = (max(cand) - min(cand), max(cand))
                if best is None or score < best[0]:
                    best = (score, cand)
            return
        for f in divs:
            if f <= max_factor and remaining % f == 0:
                search(remaining // f, f, acc + [f])

    search(rem, rem, [])
    if best is None:  # pragma: no cover - rem>=1 always factorizable
        raise ModuleInternalError("dims_create failed to factorize.")
    for i, f in zip(free, best[1]):
        dims[i] = f
    return dims


def cart_rank(coords, dims) -> int:
    """Row-major Cartesian rank (MPI cart order; reference relies on it for
    `gather!` displacements, `gather.jl:40-47`)."""
    c, d = np.asarray(coords), np.asarray(dims)
    return int((c[0] * d[1] + c[1]) * d[2] + c[2])


def cart_coords(rank: int, dims) -> np.ndarray:
    d = np.asarray(dims)
    cz = rank % d[2]
    cy = (rank // d[2]) % d[1]
    cx = rank // (d[1] * d[2])
    return np.array([cx, cy, cz], dtype=np.int64)


def cart_shift(coords, dim: int, disp: int, dims, periods):
    """Left/right neighbor ranks of ``coords`` along ``dim`` (analog of
    `MPI.Cart_shift`, reference `init_global_grid.jl:104-106`). Returns
    ``(left, right)`` with PROC_NULL where no neighbor exists."""
    coords = np.asarray(coords)
    dims = np.asarray(dims)
    out = []
    for sgn in (-1, +1):
        c = coords.copy()
        t = c[dim] + sgn * disp
        if periods[dim]:
            c[dim] = t % dims[dim]
            out.append(cart_rank(c, dims))
        elif 0 <= t < dims[dim]:
            c[dim] = t
            out.append(cart_rank(c, dims))
        else:
            out.append(PROC_NULL)
    return tuple(out)


def neighbors_table(coords, dims=None, periods=None, disp=None) -> np.ndarray:
    """2×3 neighbor table for a shard at ``coords`` (analog of the reference's
    per-rank `neighbors` array, `init_global_grid.jl:103-106`). Row 0 = left
    neighbors (n=1 in the reference's 1-based convention), row 1 = right."""
    if dims is None:
        gg = global_grid()
        dims, periods, disp = gg.dims, gg.periods, gg.disp
    tbl = np.full((NNEIGHBORS_PER_DIM, NDIMS), PROC_NULL, dtype=np.int64)
    for d in range(NDIMS):
        tbl[0, d], tbl[1, d] = cart_shift(coords, d, disp, dims, periods)
    return tbl


# ---------------------------------------------------------------------------
# Field/overlap sugar (analog of reference `shared.jl:104-127`)
# ---------------------------------------------------------------------------

def axis_perm_pairs(D: int, periodic, disp: int):
    """The (forward, backward) single-axis ppermute pairs of an exchanging
    axis — wrap-around when periodic, truncated chains (PROC_NULL edges)
    when not. THE one pair generator: `ops.halo` ships these pairs live
    and `analysis.contracts` proves them — a single source so the wire
    pattern can never diverge between layers."""
    D, disp = int(D), int(disp)
    if periodic:
        return ([(i, (i + disp) % D) for i in range(D)],
                [(i, (i - disp) % D) for i in range(D)])
    if disp >= D:
        return [], []
    return ([(i, i + disp) for i in range(D - disp)],
            [(i, i - disp) for i in range(disp, D)])


def ol(dim: int, local_shape=None) -> int:
    """Overlap of a field along ``dim`` (0-based). For a field whose local
    block shape differs from ``nxyz`` (staggered grids), the overlap grows by
    the size difference — reference `shared.jl:107`:
    ``ol(dim, A) = overlaps[dim] + (size(A, dim) - nxyz[dim])``."""
    gg = global_grid()
    if local_shape is None:
        return int(gg.overlaps[dim])
    size_d = local_shape[dim] if dim < len(local_shape) else 1
    return int(gg.overlaps[dim] + (size_d - gg.nxyz[dim]))
