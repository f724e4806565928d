"""Barrier-synchronized timing — analog of reference `tic`/`toc`
(`/root/reference/src/tools.jl:230-236`): `MPI.Barrier(comm())` + wall clock.

On TPU the barrier is: flush every device's execution queue by running a tiny
jitted psum over the full grid mesh and blocking on the result (devices
execute their queues in order, so the probe completing means all previously
enqueued work completed), plus a cross-process sync in multi-host deployments
(`multihost_utils.sync_global_devices`). The probe is compiled once at init
(analog of the reference pre-compiling tic/toc, `init_global_grid.jl:119-123`).
"""

from __future__ import annotations

import time

from ..parallel.topology import (
    AXIS_NAMES, check_initialized, global_grid, grid_is_initialized,
)

__all__ = ["tic", "toc", "barrier", "sync", "init_timing_functions"]

_t0 = None
_probe_cache: dict = {}
_drain_cache: dict = {}


def _drain_fn(gg, sig):
    """Compiled drain for a leaf signature: local first element of every
    leaf (inside shard_map, so each SHARD contributes), psum over every
    mesh axis, ONE replicated scalar out. Fetching that scalar proves every
    device executed past all the leaves' producers — one D2H round trip
    total instead of one per shard per array."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    specs = tuple(spec for (_, _, spec) in sig)

    def drain(*leaves):
        s = jnp.zeros((), jnp.float32)
        for x in leaves:
            v = x[(0,) * x.ndim] if x.ndim else x
            if jnp.issubdtype(v.dtype, jnp.complexfloating):
                v = v.real
            s = s + v.astype(jnp.float32)
        for ax in AXIS_NAMES:
            s = lax.psum(s, ax)
        return s

    from jax import shard_map

    return jax.jit(shard_map(drain, mesh=gg.mesh, in_specs=specs,
                             out_specs=P()))


def _sync_strong(tree):
    """Drain ``tree`` with the single-fetch compiled program when every
    array leaf is NamedSharding'ed on the grid mesh; returns (tree, True)
    on success, (tree, False) when some leaf needs the per-shard path."""
    import jax
    import numpy as np

    if not grid_is_initialized():
        return tree, False
    gg = global_grid()
    if gg.mesh is None:
        return tree, False
    leaves = [l for l in jax.tree_util.tree_leaves(tree)
              if isinstance(l, jax.Array)]
    if not leaves:
        # nothing to drain, but NOT a barrier either — callers relying on
        # the barrier semantics (tic/toc/barrier) must still run the probe
        return tree, False
    sig = []
    for l in leaves:
        sh = l.sharding
        if not (isinstance(sh, jax.sharding.NamedSharding)
                and sh.mesh == gg.mesh):
            return tree, False
        sig.append((tuple(l.shape), str(l.dtype), sh.spec))
    key = (gg.epoch, tuple(sig))
    fn = _drain_cache.get(key)
    if fn is None:
        if _drain_cache:
            # dead-epoch eviction only: scheduler-retained grids
            # (`topology.retain_epoch`) keep their drains warm across
            # context switches
            from ..parallel.topology import live_epochs

            live = live_epochs()
            for k in [k for k in _drain_cache if k[0] not in live]:
                del _drain_cache[k]
        fn = _drain_fn(gg, sig)
        _drain_cache[key] = fn
    np.asarray(fn(*leaves))  # concrete fetch = the ordering guarantee
    return tree, True


def sync(tree):
    """Force completion of every computation producing ``tree``'s arrays and
    return ``tree``.

    Resolves a CONCRETE value that data-depends on every shard of every
    leaf, so it is also a barrier: every device of the mesh — in every
    process — has finished the leaves' producers when it returns (the
    reference's ``MPI.Barrier`` in `tic`/`toc`). For local completion
    alone, ``jax.block_until_ready`` is as good on a TPU host: on one v5e
    it returned only after a 300-step chain had finished, and a drain
    right after it took ~1 ms (PR 21, chip_smoke.py's probe).

    Fast path (grid-mesh arrays): ONE compiled psum-drain program and ONE
    scalar D2H for the whole tree (cached per tree signature). Fallback
    (foreign shardings, no grid): one element per device shard —
    ``shard.data`` is locally addressable even for multi-host arrays.
    """
    tree, done = _sync_strong(tree)
    if not done:
        _sync_slow(tree)
    return tree


def _sync_slow(tree) -> None:
    """Per-shard scalar-fetch fallback drain (see `sync`)."""
    import jax
    import numpy as np

    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array):
            for shard in leaf.addressable_shards:
                d = shard.data
                np.asarray(d[(0,) * d.ndim] if d.ndim else d)


def _device_barrier() -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    gg = global_grid()
    mesh = gg.mesh
    if mesh is None:
        return
    key = gg.epoch
    fn = _probe_cache.get(key)
    if fn is None:
        from ..parallel.topology import live_epochs

        live = live_epochs()
        for k in [k for k in _probe_cache if k not in live]:
            del _probe_cache[k]

        def probe(x):
            s = x
            for ax in AXIS_NAMES:
                s = jax.lax.psum(s, ax)
            return s

        from jax import shard_map

        fn = jax.jit(shard_map(probe, mesh=mesh, in_specs=P(), out_specs=P()))
        _probe_cache[key] = fn
    # concrete fetch of the psum: a barrier over every device (see `sync`)
    import numpy as np

    np.asarray(fn(jnp.zeros(())))
    if jax.process_count() > 1:  # DCN barrier for multi-host
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices("igg_tpu_barrier")


def _sync_then_barrier(sync_on) -> None:
    """Shared tic/toc/barrier path. When ``sync_on`` drains through the
    strong single-fetch program, that drain already psums over every mesh
    axis and resolves concretely — strictly stronger than the probe — so
    the separate device barrier (an extra D2H round trip inside timed
    windows) is skipped; multi-host still adds the DCN sync."""
    import jax

    strong = False
    if sync_on is not None:
        _, strong = _sync_strong(sync_on)
        if not strong:
            _sync_slow(sync_on)
    if strong:
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices("igg_tpu_barrier")
        return
    _device_barrier()


def barrier(sync_on=None) -> None:
    """Block until all devices (and processes) reach this point. Pass the
    arrays whose pending computations must drain as ``sync_on`` for a
    data-dependent guarantee (see `sync`)."""
    check_initialized()
    _sync_then_barrier(sync_on)


def tic(sync_on=None) -> None:
    """Start the chronometer once all devices have reached this point
    (reference `tools.jl:234`)."""
    global _t0
    check_initialized()
    _sync_then_barrier(sync_on)
    _t0 = time.time()


def toc(sync_on=None) -> float:
    """Elapsed seconds since `tic` once all devices have reached this point
    (reference `tools.jl:235`). Pass the arrays produced by the timed region
    as ``sync_on`` to guarantee their computations are included (data-
    dependent drain; framework runners like ``run_chunked`` already sync).

    Raises `InvalidArgumentError` when no `tic` started the chronometer
    (instead of the bare ``NoneType`` TypeError the subtraction would
    throw)."""
    check_initialized()
    if _t0 is None:
        from .exceptions import InvalidArgumentError

        raise InvalidArgumentError(
            "toc() called with no running chronometer: call tic() first "
            "(init_global_grid pre-compiles the pair, but "
            "finalize_global_grid resets it).")
    _sync_then_barrier(sync_on)
    return time.time() - _t0


def init_timing_functions() -> None:
    """Pre-compile the barrier probe so first user timing is cheap
    (reference `init_global_grid.jl:119-123`)."""
    tic()
    toc()
