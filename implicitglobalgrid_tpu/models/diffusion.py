"""3-D (and 2-D) heat diffusion — the framework's flagship workload.

TPU-native re-design of the reference's canonical example
(`/root/reference/examples/diffusion3D_multicpu_novis.jl:11-51`,
`diffusion3D_multigpu_CuArrays_novis.jl:12-54`): Fourier-law fluxes +
energy-conservation update + halo exchange every step.

The TPU-first difference: instead of one dispatched broadcast per operation
per step (the reference's hot loop, which its own README notes leaves >10x
headroom, `README.md:167`), the ENTIRE time loop runs as one compiled XLA
program — `lax.fori_loop` over the fused stencil update with the per-axis
`ppermute` halo exchange inline (`run` below). XLA fuses flux computation,
divergence, and update into a handful of kernels per step and overlaps the
halo collectives with interior compute via its latency-hiding scheduler.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from ..ops.alloc import device_put_g, zeros_g
from ..ops.fields import field_partition_spec
from ..ops.halo import local_update_halo
from ..ops.stencil import d_xa, d_xi, d_ya, d_yi, d_za, d_zi, inn
from ..parallel.topology import check_initialized, global_grid
from ..tools import coords_g, nx_g, ny_g, nz_g

__all__ = ["DiffusionParams", "init_diffusion3d", "init_diffusion2d",
           "diffusion_step_local", "make_step", "make_run", "make_run_sr",
           "make_run_deep", "deep_step", "run_diffusion"]


@dataclass(frozen=True)
class DiffusionParams:
    """Physics/numerics constants (static: baked into the compiled program).

    ``overlap`` routes the XLA step through `hide_communication` (shell
    update first, halo ppermutes overlap the interior compute — the
    `@hide_communication` analog). It pays an extra interior-stitch pass, so
    it wins only where collective latency is a significant fraction of the
    step (small local blocks in strong scaling, DCN-crossing axes); at the
    256^3 anchor size on ICI the default data-flow scheduling is faster.
    The Pallas fused step+exchange path structures communication itself and
    ignores this flag.

    ``sr`` enables STOCHASTIC-ROUNDING bf16 storage (`ops/precision.py`):
    the state stays bf16 in HBM (the bandwidth tier) but each step computes
    in f32 and rounds the store stochastically, which removes the
    increment-absorption bias that stagnates plain-bf16 long runs
    (bench_f64_accuracy.py). Runner-level feature (`make_run_sr`/
    `run_diffusion` thread the per-step PRNG); currently XLA-tier only —
    the Pallas kernels would need an in-kernel PRNG, pending hardware
    validation — and, like the Pallas tier, it ignores ``overlap``. No
    effect unless the state dtype is bfloat16.

    ``comm_every`` enables COMMUNICATION-AVOIDING deep-halo stepping: with
    halowidths >= k the exchange runs once per k steps (k-wide slabs), and
    between exchanges each sub-step updates a region that retreats one
    cell per sub-step from every side that has a neighbor — the cells it
    skips are halo-band cells the NEXT exchange overwrites anyway, so the
    interior trajectory is bit-identical to comm_every=1 (asserted by
    tests/test_comm_avoid.py). Same wire bytes per step; 1/k the
    collective count and latency — the lever for latency-bound regimes
    (small blocks in strong scaling, DCN-crossing axes; see
    `exposed_comm_ms_per_step` in WEAK_SCALING.json).

    The cadence is PER MESH AXIS (`ops.wire.resolve_comm_every` — the
    `wire_dtype` spelling family): an int ``k``, a spec like ``"z:4"`` /
    ``"z:4,x:1"`` (axes x/y/z or gx/gy/gz; unnamed axes exchange every
    step), a ``{axis: k}`` dict, or ``None`` to consult
    ``IGG_COMM_EVERY``. A slow DCN-mapped axis can then amortize its
    collective latency over ``k`` steps while ICI axes keep per-step
    exchanges and 1-wide halos — the configuration where a UNIFORM
    cadence loses on slab-width compute (the Stokes COMM_AVOID.json row)
    turns into a win. Each axis ``d`` needs ``halowidths[d] = k_d`` /
    ``overlaps[d] >= 2*k_d``; the compiled super-step advances
    ``lcm(k_d)`` physical steps. XLA tier; ignores ``overlap``."""
    lam: float      # thermal conductivity
    dt: float
    dx: float
    dy: float = 1.0
    dz: float = 1.0
    overlap: bool = False
    sr: bool = False
    sr_seed: int = 0
    comm_every: int | str = 1


def _gaussian(x, amp, cx, w=1.0):
    import jax.numpy as jnp

    return amp * jnp.exp(-(((x - cx) / w) ** 2))


def _upd3(Tb, Cpb, p: DiffusionParams):
    """The 3-D flux/divergence/update stencil — ONE definition shared by
    the plain-XLA, overlap, and stochastic-rounding paths (the accuracy
    bench compares their trajectories; the arithmetic must not fork)."""
    qx = -p.lam * d_xi(Tb) / p.dx
    qy = -p.lam * d_yi(Tb) / p.dy
    qz = -p.lam * d_zi(Tb) / p.dz
    dTdt = (-d_xa(qx) / p.dx - d_ya(qy) / p.dy
            - d_za(qz) / p.dz) / inn(Cpb)
    return Tb.at[1:-1, 1:-1, 1:-1].add(p.dt * dTdt)


def _upd2(Tb, Cpb, p: DiffusionParams):
    """2-D variant of `_upd3`."""
    qx = -p.lam * d_xi(Tb) / p.dx
    qy = -p.lam * d_yi(Tb) / p.dy
    dTdt = (-d_xa(qx) / p.dx - d_ya(qy) / p.dy) / inn(Cpb)
    return Tb.at[1:-1, 1:-1].add(p.dt * dTdt)


def _fresh_mask(shape, retreat):
    """Diffusion's deep-halo sub-step mask: the interior update retreats
    ``retreat`` cells per neighbor side (a scalar, or per-dim under a
    per-axis cadence) — ``[1 + r_d·L, n-1 - r_d·R)`` per dim (see
    `common.fresh_mask` for the shared machinery and the soundness
    argument)."""
    from .common import fresh_mask

    return fresh_mask(shape, retreat, (1,) * len(shape), (1,) * len(shape))


def init_diffusion3d(*, lam=1.0, cp_min=1.0, lx=10.0, ly=10.0, lz=10.0,
                     dtype=None, overlap=False, sr=False, sr_seed=0,
                     comm_every=None):
    """Build (T, Cp, params) with the reference example's initial conditions
    (two Gaussian anomalies each,
    `diffusion3D_multigpu_CuArrays_novis.jl:34-38`) as stacked sharded arrays.

    The grid must be initialized; local size is the grid's ``(nx, ny, nz)``.
    """
    import jax.numpy as jnp

    check_initialized()
    dx = lx / (nx_g() - 1)
    dy = ly / (ny_g() - 1)
    dz = lz / (nz_g() - 1)
    dt = min(dx * dx, dy * dy, dz * dz) * cp_min / lam / 8.1  # example :41

    Tz = zeros_g(dtype=dtype)
    x, y, z = coords_g(dx, dy, dz, Tz)
    x, y, z = (jnp.asarray(np.asarray(v), dtype=Tz.dtype) for v in (x, y, z))
    Cp = cp_min \
        + 5 * jnp.exp(-((x - lx / 1.5) ** 2) - ((y - ly / 2) ** 2) - ((z - lz / 1.5) ** 2)) \
        + 5 * jnp.exp(-((x - lx / 3.0) ** 2) - ((y - ly / 2) ** 2) - ((z - lz / 1.5) ** 2))
    T = 100 * jnp.exp(-(((x - lx / 2) / 2) ** 2) - (((y - ly / 2) / 2) ** 2) - (((z - lz / 3.0) / 2) ** 2)) \
        + 50 * jnp.exp(-(((x - lx / 2) / 2) ** 2) - (((y - ly / 2) / 2) ** 2) - (((z - lz / 1.5) / 2) ** 2))
    T = device_put_g(jnp.broadcast_to(T, Tz.shape).astype(Tz.dtype))
    Cp = device_put_g(jnp.broadcast_to(Cp, Tz.shape).astype(Tz.dtype))
    from .common import resolve_comm_every

    # comm_every=None consults IGG_COMM_EVERY (the wire-policy env
    # convention); stored canonically so the params value is hashable and
    # spelling-independent ("gz:4" and "z:4" build one cached runner)
    return T, Cp, DiffusionParams(lam=lam, dt=dt, dx=dx, dy=dy, dz=dz,
                                  overlap=overlap, sr=sr, sr_seed=sr_seed,
                                  comm_every=str(resolve_comm_every(
                                      comm_every)))


def init_diffusion2d(*, lam=1.0, cp_min=1.0, lx=10.0, ly=10.0, dtype=None):
    """2-D variant (BASELINE config: 2-D diffusion on a 2x2 mesh)."""
    import jax.numpy as jnp

    check_initialized()
    gg = global_grid()
    dx = lx / (nx_g() - 1)
    dy = ly / (ny_g() - 1)
    dt = min(dx * dx, dy * dy) * cp_min / lam / 4.1
    Tz = zeros_g(tuple(int(n) for n in gg.nxyz[:2]), dtype=dtype)
    x, y = coords_g(dx, dy, 1.0, Tz)[:2]
    x, y = (jnp.asarray(np.asarray(v), dtype=Tz.dtype) for v in (x, y))
    Cp = cp_min + 5 * jnp.exp(-((x - lx / 1.5) ** 2) - ((y - ly / 2) ** 2))
    T = 100 * jnp.exp(-(((x - lx / 2) / 2) ** 2) - (((y - ly / 2) / 2) ** 2))
    T = device_put_g(jnp.broadcast_to(T, Tz.shape).astype(Tz.dtype))
    Cp = device_put_g(jnp.broadcast_to(Cp, Tz.shape).astype(Tz.dtype))
    return T, Cp, DiffusionParams(lam=lam, dt=dt, dx=dx, dy=dy)


def diffusion_step_local(T, Cp, p: DiffusionParams, impl: str = "xla",
                         sr_key=None):
    """One time step on a LOCAL block (use inside shard_map) — the reference
    hot loop (`diffusion3D_multicpu_novis.jl:41-47`):

        q = -λ ∇T;   δT/δt = -∇·q / cₚ;   T += dt δT/δt;   update_halo(T)

    ``impl``: "xla" (broadcast flux form, fused by XLA) or "pallas" (fused
    single-pass Pallas TPU kernel, same arithmetic to the last ulp;
    "pallas_interpret" for CPU testing). Pallas covers 3-D and 2-D
    blocks; other ndims fall back to the XLA path.

    ``sr_key`` (with ``p.sr`` and a bfloat16 state) selects the
    stochastic-rounding storage path: f32 flux arithmetic, bf16 store with
    an unbiased round (`ops/precision.py`) — removes the plain-bf16
    stagnation bias. XLA formulation (the kernel tier has no in-kernel
    PRNG yet).
    """
    import jax.numpy as jnp

    if p.sr and T.dtype == jnp.bfloat16 and T.ndim in (2, 3):
        if sr_key is None:
            # make_step/make_run have no PRNG to thread — silently running
            # plain round-to-nearest here would reintroduce the exact
            # stagnation sr=True exists to prevent
            from ..utils.exceptions import InvalidArgumentError

            raise InvalidArgumentError(
                "DiffusionParams(sr=True) with a bfloat16 state needs the "
                "stochastic-rounding runner: use run_diffusion or "
                "make_run_sr (make_step/make_run cannot thread the "
                "per-step PRNG key).")
        from ..ops.precision import shard_unique_fold, stochastic_round_bf16

        key = shard_unique_fold(sr_key)
        upd = _upd3 if T.ndim == 3 else _upd2
        Tf = upd(T.astype(jnp.float32), Cp.astype(jnp.float32), p)
        return local_update_halo(stochastic_round_bf16(Tf, key))
    if impl.startswith("pallas") and T.ndim == 3:
        from ..ops.halo import _dim_exchanges
        from ..ops.pallas_stencil import (
            diffusion3d_step_exchange_pallas, diffusion3d_step_halo_pallas,
            diffusion3d_step_halo_pallas_mp, diffusion3d_step_pallas,
            fusable_halo_dims, mp_supported, step_exchange_modes,
        )

        gg = global_grid()
        interpret = impl == "pallas_interpret"
        kw = dict(lam=p.lam, dt=p.dt, dx=p.dx, dy=p.dy, dz=p.dz,
                  interpret=interpret)
        hws = tuple(int(h) for h in gg.halowidths)
        fuse = fusable_halo_dims(gg)
        covers_all = fuse is not None and not any(
            _dim_exchanges(gg, T.shape, hws, d) for d in range(3)
            if not fuse[d])
        if covers_all:
            # Every exchanging dim is self-neighbor: halo updates fold into
            # the step's output pass for free (in-plane selects / sigma
            # plane resourcing — no slab materialization at all). The
            # multi-plane kernel cuts T read traffic ~2.4x where its shape
            # gates pass.
            if mp_supported(T, interpret=interpret):
                return diffusion3d_step_halo_pallas_mp(T, Cp, fuse=fuse, **kw)
            return diffusion3d_step_halo_pallas(T, Cp, fuse=fuse, **kw)
        ex_modes = step_exchange_modes(gg, T)
        if ex_modes is not None:
            # Multi-shard (or mixed) exchange fused with the step: send
            # slabs computed from thin input slabs, ppermuted while the
            # plane sweep runs, delivered in the same output pass — the
            # pod-scale path (~2 array passes/step regardless of sharding).
            return diffusion3d_step_exchange_pallas(T, Cp, gg, ex_modes, **kw)
        if fuse is not None:
            # Partial fusion (a self-neighbor prefix of the z, x, y order
            # fuses in-kernel; a later dim is nonstandard): exchange only
            # the REMAINING dims afterwards — the suffix of the order, so
            # the reference's sequential-corner semantics hold.
            if mp_supported(T, interpret=interpret):
                T = diffusion3d_step_halo_pallas_mp(T, Cp, fuse=fuse, **kw)
            else:
                T = diffusion3d_step_halo_pallas(T, Cp, fuse=fuse, **kw)
            from ..ops.halo import DEFAULT_DIMS_ORDER

            rem = tuple(d for d in DEFAULT_DIMS_ORDER if not fuse[d])
            return local_update_halo(T, dims=rem)
        if mp_supported(T, interpret=interpret):
            T = diffusion3d_step_halo_pallas_mp(
                T, Cp, fuse=(False, False, False), **kw)
        else:
            T = diffusion3d_step_pallas(T, Cp, **kw)
    elif impl.startswith("pallas") and T.ndim == 2:
        from ..ops.pallas_stencil import (
            diffusion2d_step_exchange_pallas, step_exchange_modes,
            strip_rows_2d,
        )

        gg = global_grid()
        interpret = impl == "pallas_interpret"
        ex_modes = step_exchange_modes(gg, T)
        if ex_modes is not None and strip_rows_2d(
                T, interpret=interpret) is not None:
            # 2-D fused step + exchange (BASELINE config 2): row strips
            # through a double-buffered VMEM window; send slabs from thin
            # XLA slab computes, delivered in the same output pass.
            return diffusion2d_step_exchange_pallas(
                T, Cp, gg, ex_modes, lam=p.lam, dt=p.dt, dx=p.dx, dy=p.dy,
                interpret=interpret)
        return diffusion_step_local(T, Cp, p, impl="xla")
    elif T.ndim == 3:
        def upd(Tb, Cpb):
            return _upd3(Tb, Cpb, p)

        if p.overlap:
            from ..ops.overlap import hide_communication

            return hide_communication(upd, T, Cp, radius=1)
        T = upd(T, Cp)
    else:
        def upd2(Tb, Cpb):
            return _upd2(Tb, Cpb, p)

        if p.overlap:
            from ..ops.overlap import hide_communication

            return hide_communication(upd2, T, Cp, radius=1)
        T = upd2(T, Cp)
    return local_update_halo(T)


def _resolve_impl(impl, ndim=3):
    """Default impl: the grid's IGG_USE_PALLAS flag (the analog of the
    reference's per-dimension copy-kernel toggle IGG_USE_POLYESTER,
    `init_global_grid.jl:60,71-75`) selects the Pallas kernels on TPU grids
    (on by default there). The 3-D and 2-D steps have Pallas kernels —
    other ndims resolve to the XLA path so check_vma stays on for them. The
    fused step kernel covers all dims at once, so ANY explicit per-dim
    opt-out (e.g. IGG_USE_PALLAS_DIMX=0) falls back to the XLA path."""
    from .common import resolve_pallas_impl

    return resolve_pallas_impl(impl, eligible=ndim in (2, 3))


def _reject_comm_every(p: DiffusionParams, what: str):
    """make_step/make_run advance one exchange per step — silently running
    them with a deep cadence would measure nothing; route to
    `make_run_deep`/`run_diffusion` instead (same precedent as sr)."""
    from .common import resolve_comm_every

    if resolve_comm_every(p.comm_every).deep:
        from ..utils.exceptions import InvalidArgumentError

        raise InvalidArgumentError(
            f"DiffusionParams(comm_every={p.comm_every!r}) needs the "
            f"deep-halo runner: use run_diffusion or make_run_deep "
            f"({what} exchanges every step and cannot honor the cadence).")


def make_step(p: DiffusionParams, ndim: int = 3, impl: str | None = None):
    """Controller-level jitted single step on stacked arrays:
    ``T = step(T, Cp)``."""
    import jax

    _reject_comm_every(p, "make_step")
    check_initialized()
    gg = global_grid()
    spec = field_partition_spec(ndim)
    impl = _resolve_impl(impl, ndim)

    def local(T, Cp):
        return diffusion_step_local(T, Cp, p, impl)

    from jax import shard_map
    from .common import default_check_vma

    return jax.jit(shard_map(
        local, mesh=gg.mesh, in_specs=(spec, spec), out_specs=spec,
        check_vma=default_check_vma(impl.startswith("pallas")),
    ))


def make_run(p: DiffusionParams, nt_chunk: int, ndim: int = 3,
             impl: str | None = None, ensemble: int | None = None):
    """Whole-loop runner: ONE compiled program advancing ``nt_chunk`` steps
    (`lax.fori_loop` with the halo ppermutes inline) — the TPU-first
    replacement for the reference's per-step dispatch loop. Built on the
    shared epoch-cached runner machinery (`models/common.py`); the state is
    ``(T, Cp)`` with ``Cp`` carried through unchanged.

    ``ensemble=E`` advances E scenario members per step through the SAME
    collectives (the vmapped chunk of `make_state_runner(ensemble=)`):
    state arrays lead with the member axis (`common.ensemble_state`),
    per-member ``Cp``/initial-condition variants included. XLA tier."""
    from .common import make_state_runner, resolve_ensemble_impl

    _reject_comm_every(p, "make_run")
    if ensemble is not None:
        impl = resolve_ensemble_impl(impl, "diffusion")
    else:
        impl = _resolve_impl(impl, ndim)

    def step(state):
        T, Cp = state
        return diffusion_step_local(T, Cp, p, impl), Cp

    return make_state_runner(
        step, (ndim, ndim), nt_chunk=nt_chunk,
        key=("diffusion", p, impl),
        check_vma=False if impl.startswith("pallas") else None,
        ensemble=ensemble,
    )


def make_run_sr(p: DiffusionParams, nt_chunk: int, ndim: int = 3):
    """Stochastic-rounding runner: state is ``(T, Cp, n)`` with ``n`` a
    replicated scalar GLOBAL step counter — the per-step PRNG key is
    ``fold_in(PRNGKey(p.sr_seed), n)``, so randomness never repeats across
    chunk calls (a chunk-local loop index would reuse the same stream
    every chunk, correlating the round directions of successive chunks).
    """
    import jax
    import jax.numpy as jnp

    from .common import make_state_runner

    def step(state):
        T, Cp, n = state
        # 'rbg' keys draw from lax.rng_bit_generator — the TPU's hardware
        # RNG path, much cheaper per bit than threefry's ALU lattice on a
        # bandwidth-bound step (and supported on cpu/gpu backends too)
        key = jax.random.fold_in(jax.random.key(p.sr_seed, impl="rbg"), n)
        T = diffusion_step_local(T, Cp, p, impl="xla", sr_key=key)
        return T, Cp, n + jnp.int32(1)

    return make_state_runner(step, (ndim, ndim, 0), nt_chunk=nt_chunk,
                             key=("diffusion_sr", p))


def deep_step(p: DiffusionParams, ndim: int = 3):
    """The communication-avoiding SUPER-STEP as a local step function:
    ``lcm(k_d)`` masked sub-steps (`_fresh_mask`, per-dim retreats) with
    each mesh axis's k-wide exchange issued only at the sub-steps its
    cadence makes it due (`CommCadence.due_dims` — a ``k_d = 1`` axis
    exchanges every sub-step, a deep axis once per ``k_d``). Validates
    the grid's halo geometry against the cadence; returns ``(step,
    cycle)`` where ``step((T, Cp)) -> (T, Cp)`` advances ``cycle``
    physical steps. The building block of `make_run_deep` and the
    scheduler's tuned builtin jobs (`service.job.builtin_setup`)."""
    import jax.numpy as jnp

    from .common import resolve_comm_every, validate_deep_halo

    check_initialized()
    gg = global_grid()
    cad = resolve_comm_every(p.comm_every)
    validate_deep_halo(gg, ndim, cad)
    K = cad.cycle

    upd = _upd3 if ndim == 3 else _upd2

    def step(state):
        T, Cp = state
        for j in range(K):
            Tn = upd(T, Cp, p)
            r = cad.retreats(j, ndim)
            if any(r):
                T = jnp.where(_fresh_mask(T.shape, r), Tn, T)
            else:
                T = Tn  # all axes fresh: full-interior update
            due = cad.due_dims(j, ndim)
            if due:
                T = local_update_halo(T, dims=due)
        return T, Cp

    return step, K


def make_run_deep(p: DiffusionParams, nt_chunk_super: int, ndim: int = 3,
                  ensemble: int | None = None):
    """Communication-avoiding runner: ONE super-step = the per-axis
    cadence's full cycle of masked sub-steps (`deep_step`), with each
    axis's k-wide exchange once per ``k_d`` sub-steps.
    ``nt_chunk_super`` counts super-steps (physical steps / lcm(k_d)).
    ``ensemble=E`` batches E scenario members through the SAME deep-halo
    collectives (the vmapped chunk of `make_state_runner(ensemble=)` —
    XLA tier, like the cadence itself)."""
    from .common import make_state_runner, resolve_comm_every

    step, _ = deep_step(p, ndim)
    cad = resolve_comm_every(p.comm_every)
    return make_state_runner(step, (ndim, ndim), nt_chunk=nt_chunk_super,
                             key=("diffusion_deep", p, str(cad), ensemble),
                             ensemble=ensemble)


def run_diffusion(T, Cp, p: DiffusionParams, nt: int, *, nt_chunk: int = 100,
                  impl: str | None = None, ensemble: int | None = None):
    """Advance ``nt`` steps, compiling at most two chunk sizes. With
    ``p.sr`` and a bfloat16 state, routes through the stochastic-rounding
    runner (the step counter is threaded internally).

    ``ensemble=E`` advances an E-member batch (``T``/``Cp`` lead with the
    member axis — `common.ensemble_state`): one mesh, one set of
    collectives, E trajectories per step. Composes with ``comm_every``
    deep-halo cadences on the XLA tier (the vmapped deep super-step —
    each batched ppermute now amortizes BOTH ways: E members per payload,
    1/k_d launches per axis); ``sr=True`` stays a solo-run feature."""
    import jax.numpy as jnp

    from ..utils.exceptions import InvalidArgumentError
    from .common import resolve_comm_every, run_chunked

    cad = resolve_comm_every(p.comm_every)
    if ensemble is not None:
        E = int(ensemble)
        if p.sr:
            raise InvalidArgumentError(
                "ensemble batching does not support sr=True "
                "(stochastic-rounding storage is a solo-run feature).")
        if T.ndim < 2 or int(T.shape[0]) != E:
            raise InvalidArgumentError(
                f"ensemble={E} expects T to lead with the member axis "
                f"(shape (E, ...)); got {tuple(T.shape)} — build the "
                "state with models.common.ensemble_state.")
        ndim = T.ndim - 1
        if cad.deep:
            if impl is not None and not impl.startswith("xla"):
                raise InvalidArgumentError(
                    f"impl={impl!r} is incompatible with comm_every="
                    f"{cad}: deep-halo stepping (batched or solo) runs "
                    "only the XLA tier.")
            K = cad.cycle
            if nt % K:
                raise InvalidArgumentError(
                    f"nt={nt} must be a multiple of the cadence cycle "
                    f"{K} (comm_every={cad} defines the trajectory).")
            T, Cp = run_chunked(
                lambda c: make_run_deep(p, c, ndim, ensemble=E),
                (T, Cp), nt // K, max(1, nt_chunk // K))
            return T
        T, Cp = run_chunked(
            lambda c: make_run(p, c, ndim, impl, ensemble=E),
            (T, Cp), nt, nt_chunk)
        return T
    ndim = T.ndim
    if cad.deep:
        from ..utils.exceptions import InvalidArgumentError

        if p.sr and T.dtype == jnp.bfloat16:  # sr is a no-op otherwise
            raise InvalidArgumentError(
                "a deep comm_every cadence with sr=True is not supported "
                "yet (the deep-halo runner has no PRNG threading).")
        if impl is not None and not impl.startswith("xla"):
            raise InvalidArgumentError(
                f"impl={impl!r} is incompatible with comm_every={cad}: "
                "deep-halo stepping currently runs only the XLA tier.")
        K = cad.cycle
        if nt % K:
            raise InvalidArgumentError(
                f"nt={nt} must be a multiple of the cadence cycle {K} "
                f"(comm_every={cad} defines the trajectory).")
        T, Cp = run_chunked(lambda c: make_run_deep(p, c, ndim),
                            (T, Cp), nt // K, max(1, nt_chunk // K))
        return T
    if p.sr and T.dtype == jnp.bfloat16:
        if impl is not None and not impl.startswith("xla"):
            from ..utils.exceptions import InvalidArgumentError

            raise InvalidArgumentError(
                f"impl={impl!r} is incompatible with DiffusionParams(sr="
                "True) on a bfloat16 state: stochastic-rounding storage "
                "currently runs only the XLA tier (the Pallas kernels "
                "have no in-kernel PRNG yet). Pass impl=None/'xla' or "
                "disable sr.")
        T, Cp, _ = run_chunked(lambda c: make_run_sr(p, c, ndim),
                               (T, Cp, jnp.int32(0)), nt, nt_chunk)
        return T
    T, Cp = run_chunked(lambda c: make_run(p, c, ndim, impl), (T, Cp),
                        nt, nt_chunk)
    return T
