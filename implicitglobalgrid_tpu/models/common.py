"""Shared model machinery: whole-loop-jitted runners over pytree state.

Every model follows the same TPU-first shape: a pure LOCAL step function over
per-shard blocks (the reference's per-rank hot loop, e.g.
`/root/reference/examples/diffusion3D_multicpu_novis.jl:41-48`), compiled as
ONE XLA program per chunk of time steps (`lax.fori_loop` with the halo
ppermutes inline) instead of per-step dispatches.
"""

from __future__ import annotations

from ..ops.fields import field_partition_spec
from ..parallel.topology import check_initialized, global_grid

__all__ = ["make_state_runner", "run_chunked", "default_check_vma",
           "resolve_pallas_impl", "fresh_mask", "validate_deep_halo",
           "resolve_comm_every", "interior_first_step",
           "ensemble_partition_spec", "ensemble_state",
           "resolve_ensemble_impl"]

_runner_cache: dict = {}


def resolve_pallas_impl(impl, eligible: bool = True):
    """Shared default-impl rule for every model family: an explicit ``impl``
    wins; otherwise the Pallas kernel tier is the default on TPU grids with
    all IGG_USE_PALLAS flags on (the reference's per-dim copy-kernel toggle,
    `init_global_grid.jl:60,71-75`) when the model has a kernel for this
    configuration (``eligible``), else the XLA path."""
    if impl is not None:
        return impl
    from ..parallel.topology import global_grid

    gg = global_grid()
    if eligible and bool(gg.use_pallas.all()) and gg.device_type == "tpu":
        return "pallas"
    return "xla"


def ensemble_partition_spec(ndim: int):
    """PartitionSpec of an ENSEMBLE-stacked field: a new leading member
    axis (replicated — every shard holds all E members of its block)
    ahead of the usual mesh-axis sharding of the ``ndim`` physical axes.
    The member axis is deliberately mesh-axis-FREE: members never talk to
    each other, so sharding them would only fragment the one batched
    payload per ppermute the ensemble exists to ship."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.topology import AXIS_NAMES

    return P(None, *AXIS_NAMES[:ndim])


def ensemble_state(state, members: int, *, perturb: float = 0.0):
    """Stack ``members`` copies of stacked global field(s) along a NEW
    leading member axis, placed with the ensemble sharding
    (`ensemble_partition_spec`) — the state an ensemble runner
    (`make_state_runner(ensemble=members)`) advances.

    ``state`` may be one array, a tuple/list, or a dict of stacked
    arrays (the `run_resilient` state form); the container shape is
    preserved. ``perturb`` scales member ``m`` by ``1 + perturb * m`` — a
    deterministic parameter ramp that makes members distinct scenarios
    (member 0 is always the unperturbed base, so it stays bit-comparable
    to the solo run)."""
    import jax
    import jax.numpy as jnp

    from ..utils.exceptions import InvalidArgumentError

    check_initialized()
    gg = global_grid()
    E = int(members)
    if E < 1:
        raise InvalidArgumentError(
            f"ensemble_state: members must be >= 1; got {members}.")

    def one(A):
        A = jnp.asarray(A)
        stacked = jnp.broadcast_to(A[None], (E,) + tuple(A.shape))
        if perturb:
            fac = (1.0 + float(perturb)
                   * jnp.arange(E, dtype=jnp.float32)).astype(A.dtype)
            stacked = stacked * fac.reshape((E,) + (1,) * A.ndim)
        sh = jax.sharding.NamedSharding(gg.mesh,
                                        ensemble_partition_spec(A.ndim))
        return jax.device_put(stacked, sh)

    if isinstance(state, dict):
        return {k: one(v) for k, v in state.items()}
    if isinstance(state, (tuple, list)):
        return type(state)(one(v) for v in state)
    return one(state)


def resolve_ensemble_impl(impl, model: str = "step") -> str:
    """The ensemble tier's impl rule: the member axis is a ``vmap`` over
    the step program, validated on the XLA formulation (the fused Pallas
    kernels' batching under vmap is unproven hardware territory) — an
    explicit Pallas request raises instead of silently running a
    different tier; ``None``/"xla" resolve to "xla"."""
    from ..utils.exceptions import InvalidArgumentError

    if impl is not None and not str(impl).startswith("xla"):
        raise InvalidArgumentError(
            f"impl={impl!r} is incompatible with ensemble batching: the "
            f"ensemble axis currently runs the {model} step's XLA tier "
            "(vmap over the fused Pallas kernels is not validated). Pass "
            "impl=None/'xla' or drop ensemble=.")
    return "xla"


def default_check_vma(step_uses_pallas: bool = False) -> bool:
    """shard_map ``check_vma`` value for a step program: variance checking
    stays ON unless Pallas kernels actually appear — either in the step
    itself (``step_uses_pallas``) or via `local_update_halo`'s kernel tier
    on the current grid (`ops.halo.halo_may_use_pallas`)."""
    from ..ops.halo import halo_may_use_pallas

    return not (step_uses_pallas or halo_may_use_pallas())


def fresh_mask(shape, retreat, base_lo, base_hi):
    """Update-region mask for communication-avoiding deep-halo sub-steps
    (True = this cell's stencil dependencies are fresh).

    Per dim ``d``: ``[base_lo[d] + retreat_d·L, n_d - base_hi[d] -
    retreat_d·R)`` where L/R flag a neighbor on that side of THIS shard
    (`lax.axis_index` per mesh axis — one SPMD program serves edge and
    interior shards; periodic sides always have a neighbor, incl. self).
    ``base_lo/hi`` encode the scheme's exchange-fresh update region
    (diffusion interior: 1/1; a face-staggered dim: 1/1; a full-array
    update: 0/0); ``retreat`` is how many sub-steps of staleness the
    field's dependencies have accumulated — a scalar, or a PER-DIM
    sequence under a per-axis cadence (`CommCadence`: each axis's
    staleness advances at its own rate between its own exchanges). The
    skipped cells keep stale values and are overwritten by that axis's
    next k-wide exchange — which is why deep-halo trajectories stay
    bit-identical (tests/test_comm_avoid.py).
    """
    import numpy as np

    import jax.numpy as jnp
    from jax import lax

    from ..parallel.topology import AXIS_NAMES, global_grid

    gg = global_grid()
    per_dim = np.iterable(retreat)
    m = None
    for d in range(len(shape)):
        idx = lax.axis_index(AXIS_NAMES[d])
        per = bool(int(gg.periods[d]))
        has_l = jnp.logical_or(idx > 0, per)
        has_r = jnp.logical_or(idx < int(gg.dims[d]) - 1, per)
        i = jnp.arange(shape[d])
        r_d = retreat[d] if per_dim else retreat
        lo = base_lo[d] + jnp.where(has_l, r_d, 0)
        hi = shape[d] - base_hi[d] - jnp.where(has_r, r_d, 0)
        md = (i >= lo) & (i < hi)
        md = md.reshape([-1 if dd == d else 1
                         for dd in range(len(shape))])
        m = md if m is None else m & md
    return m


def resolve_comm_every(comm_every=None):
    """The models' entry to the per-axis cadence resolver
    (`ops.wire.resolve_comm_every`): int / ``"z:4,x:1"`` / dict /
    `CommCadence` / ``None`` (= consult ``IGG_COMM_EVERY``, default 1)
    -> `CommCadence`."""
    from ..ops.wire import resolve_comm_every as _resolve

    return _resolve(comm_every)


def validate_deep_halo(gg, ndim: int, k, depth_per_step: int = 1) -> None:
    """Shared `comm_every` coherence checks. ``k`` is the cadence — an
    int or a resolved `CommCadence` (per-axis). ``depth_per_step`` is the
    scheme's per-sub-step dependency radius — 1 for radius-1 stencils
    (diffusion, the acoustic leapfrog), 2 for the Stokes PT iteration
    (V needs stresses which need V: the band retreats 2 cells per
    iteration). Every exchanging dim ``d`` needs halo depth >=
    depth_per_step·k_d AND local size >= overlap + depth_per_step·k_d
    (the send slabs must stay inside the LAST sub-step's freshly-updated
    region, or an interior shard silently ships one-sub-step-stale
    values)."""
    from ..utils.exceptions import IncoherentArgumentError

    cad = resolve_comm_every(k)
    for d in range(ndim):
        k_d = cad.for_dim(d)
        need = depth_per_step * k_d
        exchanging = int(gg.dims[d]) > 1 or int(gg.periods[d])
        if not exchanging:
            continue
        if int(gg.halowidths[d]) < need:
            raise IncoherentArgumentError(
                f"comm_every={cad} needs halowidths[{d}] >= {need} on "
                f"every exchanging dim (got {int(gg.halowidths[d])}): "
                f"init the grid with overlaps[{d}] >= {2 * need} and "
                f"halowidths[{d}] = {need}.")
        n_d, ol_d = int(gg.nxyz[d]), int(gg.overlaps[d])
        if n_d < ol_d + need:
            raise IncoherentArgumentError(
                f"comm_every={cad} needs local size >= overlap + {need} "
                f"on dim {d} (got n={n_d}, overlap={ol_d}): the send "
                "slabs would leave the freshly-updated region.")


def interior_first_step(update_fn, outs, aux=(), *, radius: int = 1,
                        n_exchange: int | None = None, coalesce=None,
                        wire_dtype=None):
    """The INTERIOR-FIRST default shape of a step program (the chunk body
    every model's ``overlap=True`` path routes through): boundary-shell
    update -> ONE coalesced exchange round that depends only on the shell
    -> interior update scheduled UNDER the collectives -> stitch. A thin,
    named entry over `ops.overlap.hide_communication`'s multi-field form,
    so model step functions declare the shape instead of re-deriving the
    slab bookkeeping: ``outs`` is the tuple of updated fields (the first
    ``n_exchange`` of them exchanged — the Stokes iteration updates 7
    fields but wires 4), ``aux`` the read-only inputs, ``radius`` the
    update's stencil radius. Semantically identical to
    ``local_update_halo(*update_fn(*outs, *aux))``; the structural
    independence of interior and permutes is HLO-audited
    (tests/test_hlo_audit.py, `ProgramIR.closure`)."""
    from ..ops.overlap import hide_communication

    return hide_communication(update_fn, tuple(outs), *aux, radius=radius,
                              n_exchange=n_exchange, coalesce=coalesce,
                              wire_dtype=wire_dtype)


def make_state_runner(step_local, state_ndims, *, nt_chunk: int, key=None,
                      check_vma: bool | None = None, unroll: int | None = None,
                      post_chunk=None, ensemble: int | None = None):
    """Compile ``state -> state`` advancing ``nt_chunk`` steps.

    ``step_local(state) -> state`` operates on a tuple of LOCAL blocks;
    ``state_ndims`` gives each block's ndim (its sharding spec). ``key``
    (hashable) identifies the step function for caching — required because
    closures are rebuilt per call; pass e.g. (model_name, params, nt_chunk).
    ``check_vma=None`` resolves via `default_check_vma` (off only when the
    halo layer emits Pallas kernels; pass False yourself if the step uses
    Pallas directly).

    ``post_chunk(state) -> aux`` is the in-chunk guard hook (the resilient
    runtime's health probe, `runtime/health.py`): it runs INSIDE the same
    shard_map program once after the time loop, and its (replicated,
    ``P()``-spec'ed) result is appended to the runner's outputs — the
    compiled chunk becomes ``state -> (*state, aux)``. Because it lives in
    the chunk body, whatever it computes rides the one compiled program:
    no extra dispatch, and any reduction it performs (e.g. ONE psum of a
    tiny stats vector) is the only collective added per chunk boundary.
    The hook's module-qualified name joins the cache key (so the guarded
    and unguarded runners, or two different module-level hooks, never
    collide), but — exactly like ``step_local`` itself — the closure's
    CONTENT does not: two distinct hooks sharing a qualname (closures from
    one factory) need distinct ``key``s.

    ``unroll`` (default 4 on TPU, 1 elsewhere) unrolls the time loop body:
    XLA's while-loop buffer assignment pins each carry to ONE buffer, so a
    1-step body pays a full state copy per step to get the step kernel's
    output back into the carry buffer (~30% of the flagship step, measured
    via `overlap_stats`/`op_breakdown` on a v5e trace); an unrolled body
    ping-pongs intermediate buffers and pays that copy once per ``unroll``
    steps (`lax.fori_loop` handles non-divisible trip counts).

    ``ensemble=E`` is the ENSEMBLE axis (ISSUE 12): the compiled chunk
    advances E scenario members per step by ``vmap``-ing ``step_local``
    over a NEW leading member axis of every state array (state arrays are
    ``(E, *physical)``, sharded `ensemble_partition_spec` — build them
    with `ensemble_state`). ``state_ndims`` stays the PHYSICAL per-field
    rank. jax's collective batching rules keep the chunk's collective
    COUNT flat in E: each halo ppermute pair carries all members' (and
    all fields') slabs in one E x payload, and the ``post_chunk`` hook is
    vmapped too, so the health guard's single psum becomes one
    ``f32[E, 2N+R]`` reduction — per-member verdicts behind one
    collective (HLO-audited in tests/test_ensemble.py). XLA tier only —
    route model steps through `resolve_ensemble_impl`."""
    import time

    import jax
    from jax import lax

    from ..telemetry import note_runner_cache

    check_initialized()
    gg = global_grid()
    if ensemble is not None:
        from ..utils.exceptions import InvalidArgumentError

        ensemble = int(ensemble)
        if ensemble < 1:
            raise InvalidArgumentError(
                f"make_state_runner: ensemble must be >= 1; got "
                f"{ensemble}.")
    if check_vma is None:
        check_vma = default_check_vma()
    if unroll is None:
        unroll = 4 if gg.device_type == "tpu" else 1
    unroll = max(1, min(int(unroll), int(nt_chunk)))
    t_build0 = time.monotonic()
    if key is not None:
        # the halo exchange knobs (IGG_HALO_COALESCE / IGG_HALO_WIRE_DTYPE)
        # are resolved at TRACE time inside `local_update_halo` calls in
        # the step body; keying on them keeps a flip within one grid epoch
        # honest (no stale cached runner).
        from ..ops.halo import resolve_halo_coalesce
        from ..ops.precision import resolve_wire_dtype

        hook_id = None if post_chunk is None else (
            getattr(post_chunk, "__module__", None),
            getattr(post_chunk, "__qualname__", repr(post_chunk)))
        full_key = (gg.epoch, key, tuple(state_ndims), int(nt_chunk),
                    bool(check_vma), int(unroll),
                    resolve_halo_coalesce(None),
                    str(resolve_wire_dtype(None)), hook_id, ensemble)
        fn = _runner_cache.get(full_key)
        if fn is not None:
            # telemetry: compiled-chunk reuse vs recompile is THE
            # execute/compile split the flight recorder attributes chunks to
            note_runner_cache("hit")
            return fn
        if _runner_cache:
            # evict DEAD epochs only: after a plain re-init that is
            # everything but the current epoch (the historical behavior),
            # but the multi-run scheduler keeps several grids live at once
            # (`topology.retain_epoch`) and their warm runners must survive
            # its context switches
            from ..parallel.topology import live_epochs

            live = live_epochs()
            for k in [k for k in _runner_cache if k[0] not in live]:
                del _runner_cache[k]
    if ensemble is None:
        specs = tuple(field_partition_spec(nd) for nd in state_ndims)
        run_step = step_local
        run_hook = post_chunk
    else:
        # the member axis: ONE vmap over the whole step (and the guard
        # hook) — jax's collective batching rules are what keep the
        # compiled collective count flat in E (each ppermute/psum absorbs
        # the batch dim into its payload instead of replaying per member).
        # The exchange is trace-scoped to the pure-XLA tier: every XLA op
        # batches by rule, while the Pallas halo kernels' vmap batching is
        # unvalidated (`ops.halo.force_xla_exchange`).
        from ..ops.halo import force_xla_exchange

        specs = tuple(ensemble_partition_spec(nd) for nd in state_ndims)
        vstep = jax.vmap(lambda *blocks: tuple(step_local(blocks)))

        def run_step(s):
            with force_xla_exchange():
                return vstep(*s)

        if post_chunk is None:
            run_hook = None
        else:
            vhook = jax.vmap(lambda *blocks: post_chunk(blocks))

            def run_hook(s):
                return vhook(*s)
    out_specs = specs

    if run_hook is None:
        def chunk(*state):
            return lax.fori_loop(0, nt_chunk,
                                 lambda i, s: tuple(run_step(s)),
                                 tuple(state), unroll=unroll)
    else:
        from jax.sharding import PartitionSpec as P

        out_specs = specs + (P(),)

        def chunk(*state):
            out = lax.fori_loop(0, nt_chunk,
                                lambda i, s: tuple(run_step(s)),
                                tuple(state), unroll=unroll)
            return out + (run_hook(out),)

    from jax import shard_map

    fn = jax.jit(shard_map(
        chunk, mesh=gg.mesh, in_specs=specs, out_specs=out_specs,
        check_vma=check_vma,
    ))
    if key is not None:
        _runner_cache[full_key] = fn
    # build_s is host-side program construction; the XLA compile itself is
    # paid inside the FIRST dispatch of this runner (a chunk following a
    # `miss` is a cold chunk — `telemetry.run_report` joins the two)
    note_runner_cache("miss" if key is not None else "uncached",
                      build_s=time.monotonic() - t_build0)
    return fn


def run_chunked(runner_factory, state, nt: int, nt_chunk: int):
    """Advance ``nt`` steps using ``runner_factory(chunk_size)``; compiles at
    most two chunk sizes. Returns only after the work finished on every
    device (`utils.timing.sync`)."""
    from ..utils.timing import sync

    full, rem = divmod(nt, nt_chunk)
    if full:
        run = runner_factory(nt_chunk)
        for _ in range(full):
            state = run(*state)
    if rem:
        state = runner_factory(rem)(*state)
    return sync(state)
