"""Environment/config handling.

TPU-native analog of the reference's env-var config block
(`/root/reference/src/init_global_grid.jl:57-75`): the reference reads
`IGG_CUDAAWARE_MPI`, `IGG_ROCMAWARE_MPI`, `IGG_USE_POLYESTER` (each with
`_DIMX/_DIMY/_DIMZ` per-dimension refinements) and rejects the legacy
`IGG_LOOPVECTORIZATION`. On TPU the GPU-aware-MPI distinction does not exist —
ICI collectives always move HBM-to-HBM — so those variables are *rejected* with
an explanatory error (mirroring the reference's legacy-var rejection at
`init_global_grid.jl:57`). The TPU-meaningful knobs are:

- ``IGG_TPU_PLATFORM``: force the JAX backend platform ("tpu", "cpu", "gpu").
- ``IGG_USE_PALLAS`` (+ ``_DIMX/_DIMY/_DIMZ``): select the hand-written
  Pallas TPU kernels where they exist (analog of the reference's
  copy-kernel toggle `IGG_USE_POLYESTER`, `init_global_grid.jl:60,71-75`).
  Unlike the reference's opt-in default, the Pallas tier is ON by default on
  TPU grids (it is ~3x faster than the broadcast form there — see bench.py);
  set ``IGG_USE_PALLAS=0`` to force the pure-XLA path. Selects the fused
  Pallas stencil step in the models (`models.diffusion._resolve_impl`); the
  per-dim refinements are recorded on the grid for the future per-dimension
  halo pack path.
- ``IGG_TPU_DCN_AXES``: comma-separated mesh axes ("x","y","z") that cross
  slice boundaries (DCN) in a multi-slice deployment.

The variables of removed features (the staged halo wire and the kernel
A/B toggles) are rejected the same way, each with what now happens, so a
job script that still sets one fails loudly instead of being silently
ignored (`_REJECTED_ENV_VARS`).
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field

from .exceptions import InvalidArgumentError

__all__ = ["EnvConfig", "read_env_config"]

_REJECTED_ENV_VARS = {
    "IGG_CUDAAWARE_MPI": "GPU-aware MPI does not apply on TPU: ICI collectives always move data HBM-to-HBM.",
    "IGG_ROCMAWARE_MPI": "GPU-aware MPI does not apply on TPU: ICI collectives always move data HBM-to-HBM.",
    "IGG_LOOPVECTORIZATION": "Environment variable IGG_LOOPVECTORIZATION is not supported. Use IGG_USE_PALLAS instead.",
    "IGG_USE_POLYESTER": "Environment variable IGG_USE_POLYESTER does not apply on TPU. Use IGG_USE_PALLAS instead.",
    "IGG_HALO_WIRE_STAGE": "the topology-staged halo wire was removed; every axis exchanges on the flat ppermute wire.",
    "IGG_TPU_DCN_GRANULES": "DCN granule declarations were removed with the staged halo wire; every axis exchanges on the flat ppermute wire.",
    "IGG_MP_HANDOFF": "the window-handoff toggle was removed; the multi-plane kernels hand off between windows whenever the shape allows (3 or more windows).",
    "IGG_PLANE_RELAY": "the plane-relay toggle was removed; the plane-per-program passes always relay the previous plane through VMEM.",
}

_DIM_SUFFIXES = ("_DIMX", "_DIMY", "_DIMZ")


def _env_flag(name: str) -> bool | None:
    if name not in os.environ:
        return None
    try:
        return int(os.environ[name]) > 0
    except ValueError as e:
        raise InvalidArgumentError(
            f"Environment variable {name}: expected an integer, got {os.environ[name]!r}."
        ) from e


@dataclass
class EnvConfig:
    platform: str | None = None            # IGG_TPU_PLATFORM
    use_pallas: list = field(default_factory=lambda: [None, None, None])
    # tri-state per dim: None = unset (resolved at init: True on TPU grids,
    # False elsewhere), True/False = explicit env setting
    dcn_axes: tuple = ()                   # IGG_TPU_DCN_AXES


def read_env_config() -> EnvConfig:
    """Read and validate env configuration (called from ``init_global_grid``,
    mirroring reference `init_global_grid.jl:57-75`)."""
    for var, msg in _REJECTED_ENV_VARS.items():
        if var in os.environ:
            raise InvalidArgumentError(f"Environment variable {var} is not supported: {msg}")
        for sfx in _DIM_SUFFIXES:
            if var + sfx in os.environ:
                raise InvalidArgumentError(f"Environment variable {var + sfx} is not supported: {msg}")

    cfg = EnvConfig()
    cfg.platform = os.environ.get("IGG_TPU_PLATFORM") or None

    # Per-dimension refinement semantics mirror the reference: the global flag
    # sets all three; per-dim vars refine only when the global flag was not set
    # to a blanket True (reference `init_global_grid.jl:71-75` refines only
    # `if all(use_polyester)` after a global default of false — we mirror the
    # observable behavior: global var sets all dims, per-dim vars override).
    g = _env_flag("IGG_USE_PALLAS")
    if g is not None:
        cfg.use_pallas = [g, g, g]
    for d, sfx in enumerate(_DIM_SUFFIXES):
        v = _env_flag("IGG_USE_PALLAS" + sfx)
        if v is not None:
            cfg.use_pallas[d] = v

    axes = os.environ.get("IGG_TPU_DCN_AXES", "")
    if axes:
        names = tuple(a.strip() for a in axes.split(",") if a.strip())
        bad = [a for a in names if a not in ("x", "y", "z")]
        if bad:
            raise InvalidArgumentError(
                f"Environment variable IGG_TPU_DCN_AXES: invalid axis name(s) {bad}; valid names are x, y, z."
            )
        if len(set(names)) != len(names):
            raise InvalidArgumentError(
                f"Environment variable IGG_TPU_DCN_AXES: duplicate axis name(s) in {names}."
            )
        cfg.dcn_axes = names
    return cfg
