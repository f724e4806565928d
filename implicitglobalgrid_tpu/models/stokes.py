"""3-D pseudo-transient (PT) Stokes solver on a staggered grid.

The BASELINE config "3-D pseudo-transient Stokes solver, weak-scale to
v5p-256" (`/root/repo/BASELINE.json`): isoviscous, incompressible Stokes flow
driven by a buoyant spherical inclusion, solved by damped pseudo-transient
iteration — the hydro-mechanical miniapp family the reference's weak-scaling
figure is built on (`reference README.md:6-8`). Built entirely on the
framework's staggered-field machinery (per-field overlaps `shared.jl:107`):

    cell centers: P, τxx, τyy, τzz, ρg      faces: Vx, Vy, Vz
    edges: τxy, τxz, τyz

    divV = ∇·V
    P   ← P − dτ_P divV
    τii ← 2μ (∂iVi − divV/3)
    τij ← μ (∂jVi + ∂iVj)
    R_i = −∂iP + ∂jτij (+ buoyancy)
    dV  ← damp·dV + R          (damped PT momentum)
    V   ← V + dτ_V dV
    halo-exchange V (and P)

One PT iteration is one `step_local` inside the whole-loop-jitted runner;
convergence is monitored by `residuals` (max |divV|, max |R|) — psum-reduced
across the mesh.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from ..ops.alloc import device_put_g, zeros_g
from ..ops.halo import local_update_halo
from ..parallel.topology import AXIS_NAMES, check_initialized, global_grid
from ..tools import coords_g, nx_g, ny_g, nz_g
from .common import make_state_runner, run_chunked

__all__ = ["StokesParams", "init_stokes3d", "stokes_step_local",
           "make_stokes_run", "make_stokes_run_deep", "deep_step",
           "run_stokes", "stokes_residuals"]


@dataclass(frozen=True)
class StokesParams:
    """``comm_every`` enables communication-avoiding deep halos for the
    PT iteration (see `DiffusionParams.comm_every` for the scheme). The
    PT dependency radius is 2 per iteration (V consumes stresses, which
    consume V), so k iterations need ``halowidths = 2k`` /
    ``overlaps >= 4k`` grids, and the super-step exchange carries SEVEN
    fields (P, V×3, dV×3 — dV is damped state that the base scheme keeps
    consistent by recomputing it at every face every iteration, so the
    deep scheme must exchange it). One 7-field round per k iterations
    replaces k 4-field rounds. The cadence is PER MESH AXIS
    (``"z:2,x:1"`` / ``IGG_COMM_EVERY`` — see
    `DiffusionParams.comm_every`), each axis needing ``halowidths[d] =
    2*k_d`` / ``overlaps[d] >= 4*k_d``: this is the configuration that
    rescues the recorded COMM_AVOID.json LOSING row — a z-only cadence
    amortizes the slow axis's latency without paying the doubled slab
    compute on the fast axes. XLA tier. Trajectory: agrees with the
    per-iteration-exchange scheme to ~1 ulp per super-step pair on
    XLA:CPU (tests/test_comm_avoid.py asserts <=1e-12 rel with five
    decades of headroom; P stays BIT-exact over one super-step pair).
    The residual is a backend-codegen artifact, not a scheme
    error: the masked scheme substitutes a locally computed cell for the
    exchanged copy of the same physical cell, which is exact only when
    codegen rounds identically at different array positions — the CPU
    backend's vector-loop epilogues break that by 1 ulp for this model's
    long expression chain (diagnosed round 5: the k=1 degenerate deep
    runner IS bit-exact vs the base scheme, P — short chain — stays
    bit-exact at every k, and ~25 cells/super-step-pair at
    lane-boundary positions carry the ulp). Immaterial for a PT solver
    converging to a tolerance; expected bit-exact on TPU's uniform
    vector lanes (no epilogues), pending hardware validation.

    ``overlap`` routes the XLA iteration through the INTERIOR-FIRST step
    shape (`models/common.interior_first_step`): the 7 updated fields'
    boundary shells compute first, the single coalesced 4-field
    (Vx, Vy, Vz, Pn) ppermute round depends only on them, and the
    interior update schedules under the collectives. Semantically
    identical to the plain iteration (same caveat about CPU vector-loop
    epilogue ulps as comm_every; asserted under the overlap-equivalence
    tolerance in tests/test_overlap.py). XLA tier; the fused Pallas pass
    structures its own communication and ignores it."""
    mu: float       # shear viscosity
    dt_v: float     # pseudo time step, momentum
    dt_p: float     # pseudo time step, pressure
    damp: float     # PT damping factor
    dx: float
    dy: float
    dz: float
    comm_every: int | str = 1
    overlap: bool = False


def init_stokes3d(*, mu=1.0, lx=10.0, ly=10.0, lz=10.0, rhog_mag=1.0,
                  r_incl=1.0, dtype=None, comm_every=None, overlap=False):
    """State (P, Vx, Vy, Vz, dVx, dVy, dVz, rhog): zero initial flow, a
    buoyant sphere of radius ``r_incl`` at the domain center."""
    check_initialized()
    gg = global_grid()
    nx, ny, nz = (int(n) for n in gg.nxyz)
    dx, dy, dz = lx / (nx_g() - 1), ly / (ny_g() - 1), lz / (nz_g() - 1)
    # standard PT scalings (damped wave equation analogy)
    min_d = min(dx, dy, dz)
    n_max = max(nx_g(), ny_g(), nz_g())
    dt_v = min_d ** 2 / mu / 6.1 / 2.0
    dt_p = 6.1 * mu / n_max
    damp = 1.0 - 6.0 / n_max

    P = zeros_g((nx, ny, nz), dtype=dtype)
    x, y, z = coords_g(dx, dy, dz, P)
    r2 = ((np.asarray(x) - lx / 2) ** 2 + (np.asarray(y) - ly / 2) ** 2
          + (np.asarray(z) - lz / 2) ** 2)
    rhog = device_put_g(
        np.broadcast_to((r2 < r_incl ** 2) * rhog_mag, P.shape).astype(P.dtype))
    Vx = zeros_g((nx + 1, ny, nz), dtype=dtype)
    Vy = zeros_g((nx, ny + 1, nz), dtype=dtype)
    Vz = zeros_g((nx, ny, nz + 1), dtype=dtype)
    # damped-momentum fields mirror the velocity shapes (only interior faces
    # are ever nonzero) — face-aligned full-size arrays keep the Pallas
    # kernel tier's plane mapping uniform across the state
    dVx = zeros_g((nx + 1, ny, nz), dtype=dtype)
    dVy = zeros_g((nx, ny + 1, nz), dtype=dtype)
    dVz = zeros_g((nx, ny, nz + 1), dtype=dtype)
    state = (P, Vx, Vy, Vz, dVx, dVy, dVz, rhog)
    from .common import resolve_comm_every

    return state, StokesParams(mu=mu, dt_v=dt_v, dt_p=dt_p, damp=damp,
                               dx=dx, dy=dy, dz=dz,
                               comm_every=str(resolve_comm_every(
                                   comm_every)),
                               overlap=overlap)


def _d(A, d):
    from jax import lax

    n = A.shape[d]
    return lax.slice_in_dim(A, 1, n, axis=d) - lax.slice_in_dim(A, 0, n - 1, axis=d)


def _inner(A, dims_sel):
    from jax import lax

    for d in dims_sel:
        A = lax.slice_in_dim(A, 1, A.shape[d] - 1, axis=d)
    return A


def _stokes_terms(state, p: StokesParams):
    """Residuals R_i at interior faces (shared by step and monitor)."""
    P, Vx, Vy, Vz, dVx, dVy, dVz, rhog = state
    divV = _d(Vx, 0) / p.dx + _d(Vy, 1) / p.dy + _d(Vz, 2) / p.dz  # centers
    Pn = P - p.dt_p * divV
    txx = 2 * p.mu * (_d(Vx, 0) / p.dx - divV / 3)
    tyy = 2 * p.mu * (_d(Vy, 1) / p.dy - divV / 3)
    tzz = 2 * p.mu * (_d(Vz, 2) / p.dz - divV / 3)
    # edge shear stresses on interior edges
    txy = p.mu * (_inner(_d(Vx, 1), (0,)) / p.dy + _inner(_d(Vy, 0), (1,)) / p.dx)
    txz = p.mu * (_inner(_d(Vx, 2), (0,)) / p.dz + _inner(_d(Vz, 0), (2,)) / p.dx)
    tyz = p.mu * (_inner(_d(Vy, 2), (1,)) / p.dz + _inner(_d(Vz, 1), (2,)) / p.dy)

    Rx = (_inner(_d(txx - Pn, 0), (1, 2)) / p.dx
          + _d(_inner(txy, (2,)), 1) / p.dy
          + _d(_inner(txz, (1,)), 2) / p.dz)
    Ry = (_inner(_d(tyy - Pn, 1), (0, 2)) / p.dy
          + _d(_inner(txy, (2,)), 0) / p.dx
          + _d(_inner(tyz, (0,)), 2) / p.dz)
    rg_face = 0.5 * (_d(rhog, 2) + 2 * rhog[:, :, :-1])  # avg to z-faces
    Rz = (_inner(_d(tzz - Pn, 2), (0, 1)) / p.dz
          + _d(_inner(txz, (1,)), 0) / p.dx
          + _d(_inner(tyz, (0,)), 1) / p.dy
          + _inner(rg_face, (0, 1)))
    return Pn, divV, Rx, Ry, Rz


def stokes_step_local(state, p: StokesParams, impl: str = "xla"):
    """One damped PT iteration on LOCAL blocks (inside shard_map).

    ``impl``: "xla", or "pallas" — ONE fused Pallas pass computing the
    pressure/stress/momentum updates AND delivering the halo exchange of
    (Vx, Vy, Vz, Pn) (`ops/pallas_stokes.py`; "pallas_interpret" on CPU)."""
    P, Vx, Vy, Vz, dVx, dVy, dVz, rhog = state
    if impl.startswith("pallas"):
        from ..ops.pallas_stokes import (
            stokes_exchange_modes, stokes_step_exchange_pallas,
        )

        gg = global_grid()
        modes = stokes_exchange_modes(gg, tuple(a.shape for a in state))
        if modes is not None:
            return stokes_step_exchange_pallas(
                state, gg, modes, p, interpret=impl == "pallas_interpret")
        # ineligible config: fall through to the XLA formulation
    ix = (slice(1, -1),) * 3

    def pt_update(vx, vy, vz, Pc, dvx, dvy, dvz, rh):
        """One PT update on (a slab of) the state — everything but the
        exchange, returning the 7 updated fields in exchange-first order
        (Vx, Vy, Vz, Pn first: the wired round of the interior-first
        shape)."""
        Pn, divV, Rx, Ry, Rz = _stokes_terms(
            (Pc, vx, vy, vz, dvx, dvy, dvz, rh), p)
        dvx_i = p.damp * dvx[ix] + Rx
        dvy_i = p.damp * dvy[ix] + Ry
        dvz_i = p.damp * dvz[ix] + Rz
        return (vx.at[ix].add(p.dt_v * dvx_i),
                vy.at[ix].add(p.dt_v * dvy_i),
                vz.at[ix].add(p.dt_v * dvz_i),
                Pn,
                dvx.at[ix].set(dvx_i),
                dvy.at[ix].set(dvy_i),
                dvz.at[ix].set(dvz_i))

    if p.overlap:
        # interior-first: shells of all 7 updated fields, ONE coalesced
        # (Vx, Vy, Vz, Pn) round depending only on them, interior under
        # the collectives (models/common.interior_first_step)
        from .common import interior_first_step

        Vx, Vy, Vz, Pn, dVx, dVy, dVz = interior_first_step(
            pt_update, (Vx, Vy, Vz, P, dVx, dVy, dVz), (rhog,),
            radius=1, n_exchange=4)
        return (Pn, Vx, Vy, Vz, dVx, dVy, dVz, rhog)
    Vx, Vy, Vz, Pn, dVx, dVy, dVz = pt_update(Vx, Vy, Vz, P,
                                              dVx, dVy, dVz, rhog)
    Vx, Vy, Vz, Pn = local_update_halo(Vx, Vy, Vz, Pn)
    return (Pn, Vx, Vy, Vz, dVx, dVy, dVz, rhog)


def deep_step(p: StokesParams):
    """The deep-halo PT SUPER-STEP as a local step function: ``lcm(k_d)``
    masked iterations with the 7-field 2k-wide exchange (P, V×3, dV×3)
    fired per axis at its own cadence. Returns ``(step, cycle)``.

    Iteration masks, per dim ``d`` with staleness ``r_d = j mod k_d``
    (`common.fresh_mask`; the PT dependency radius is 2 per iteration,
    derived from the pre-update V the terms consume):
    - P: retreat ``2·r_d`` with base 0 (the base update touches every
      cell; its V dependencies are ``2(r_d-1)+2`` deep at staleness
      r_d >= 1);
    - V and dV: retreat ``2·r_d+1`` where ``r_d >= 1`` (0 on a
      just-exchanged axis) with base 1 per dim (base region ``at[1:-1]``;
      they consume THIS iteration's Pn — retreat 2·r_d — plus edge
      stresses one cell deeper).
    The masked bands (<= 2·k_d wide between an axis's exchanges) are
    exactly what that axis's 2k-wide exchange overwrites; dV joins the
    exchange because the base scheme keeps its band consistent by
    recomputing every face every iteration, which the deep scheme's
    masks skip."""
    import jax.numpy as jnp

    from .common import (
        fresh_mask, resolve_comm_every, validate_deep_halo,
    )

    check_initialized()
    gg = global_grid()
    cad = resolve_comm_every(p.comm_every)
    validate_deep_halo(gg, 3, cad, depth_per_step=2)
    K = cad.cycle

    ix = (slice(1, -1),) * 3

    def step(state):
        P, Vx, Vy, Vz, dVx, dVy, dVz, rhog = state
        for j in range(K):
            r = cad.retreats(j)
            Pn, divV, Rx, Ry, Rz = _stokes_terms(
                (P, Vx, Vy, Vz, dVx, dVy, dVz, rhog), p)
            if any(r):
                Pn = jnp.where(fresh_mask(P.shape,
                                          tuple(2 * x for x in r),
                                          (0, 0, 0), (0, 0, 0)), Pn, P)
            upd = []
            for V, dV, R in ((Vx, dVx, Rx), (Vy, dVy, Ry), (Vz, dVz, Rz)):
                dV_i = p.damp * dV[ix] + R
                dVn = dV.at[ix].set(dV_i)
                Vn = V.at[ix].add(p.dt_v * dV_i)
                if any(r):
                    m = fresh_mask(V.shape,
                                   tuple(2 * x + 1 if x else 0 for x in r),
                                   (1, 1, 1), (1, 1, 1))
                    Vn = jnp.where(m, Vn, V)
                    dVn = jnp.where(m, dVn, dV)
                upd.append((Vn, dVn))
            (Vx, dVx), (Vy, dVy), (Vz, dVz) = upd
            P = Pn
            due = cad.due_dims(j)
            if due:
                P, Vx, Vy, Vz, dVx, dVy, dVz = local_update_halo(
                    P, Vx, Vy, Vz, dVx, dVy, dVz, dims=due)
        return (P, Vx, Vy, Vz, dVx, dVy, dVz, rhog)

    return step, K


def make_stokes_run_deep(p: StokesParams, nt_chunk_super: int,
                         ensemble: int | None = None):
    """Deep-halo PT runner: ONE super-step = the cadence cycle of masked
    iterations (`deep_step`) with per-axis 7-field 2k-wide exchanges.
    ``ensemble=E`` batches E member realizations through the same deep
    collectives (XLA tier)."""
    from .common import make_state_runner, resolve_comm_every

    step, _ = deep_step(p)
    cad = resolve_comm_every(p.comm_every)
    return make_state_runner(step, (3,) * 8, nt_chunk=nt_chunk_super,
                             key=("stokes3d_deep", p, str(cad), ensemble),
                             ensemble=ensemble)


def _resolve_impl(impl):
    from .common import resolve_pallas_impl

    return resolve_pallas_impl(impl)


def make_stokes_run(p: StokesParams, nt_chunk: int, impl: str | None = None,
                    ensemble: int | None = None):
    from .common import resolve_comm_every

    if resolve_comm_every(p.comm_every).deep:
        from ..utils.exceptions import InvalidArgumentError

        raise InvalidArgumentError(
            f"StokesParams(comm_every={p.comm_every!r}) needs the "
            "deep-halo runner: use run_stokes or make_stokes_run_deep "
            "(make_stokes_run exchanges every iteration).")
    if ensemble is not None:
        from .common import resolve_ensemble_impl

        impl = resolve_ensemble_impl(impl, "stokes")
    else:
        impl = _resolve_impl(impl)
    return make_state_runner(
        lambda s: stokes_step_local(s, p, impl), (3,) * 8,
        nt_chunk=nt_chunk, key=("stokes3d", p, impl),
        check_vma=False if impl.startswith("pallas") else None,
        ensemble=ensemble,
    )


def run_stokes(state, p: StokesParams, nt: int, *, nt_chunk: int = 100,
               impl: str | None = None, ensemble: int | None = None):
    """Run ``nt`` PT iterations (one compiled program per chunk). With
    ``p.comm_every > 1``, routes through the deep-halo runner.
    ``ensemble=E`` batches E member realizations through one chunk
    (member-stacked state, `common.ensemble_state`; plain XLA tier)."""
    from ..utils.exceptions import InvalidArgumentError
    from .common import resolve_comm_every

    cad = resolve_comm_every(p.comm_every)
    if cad.deep:
        if impl is not None and not impl.startswith("xla"):
            raise InvalidArgumentError(
                f"impl={impl!r} is incompatible with comm_every={cad}: "
                "deep-halo stepping currently runs only the XLA tier.")
        K = cad.cycle
        if nt % K:
            raise InvalidArgumentError(
                f"nt={nt} must be a multiple of the cadence cycle {K} "
                f"(comm_every={cad} defines the trajectory).")
        E = None if ensemble is None else int(ensemble)
        return run_chunked(
            lambda c: make_stokes_run_deep(p, c, ensemble=E), state,
            nt // K, max(1, nt_chunk // K))
    if ensemble is not None:
        return run_chunked(
            lambda c: make_stokes_run(p, c, impl, ensemble=int(ensemble)),
            state, nt, nt_chunk)
    impl = _resolve_impl(impl)
    return run_chunked(lambda c: make_stokes_run(p, c, impl), state, nt,
                       nt_chunk)


_residual_cache: dict = {}


def stokes_residuals(state, p: StokesParams):
    """Global (max |divV|, max |R|) — pmax-reduced over the mesh (the
    convergence monitor of the PT loop). Compiled once per (grid, params)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as Pspec

    check_initialized()
    gg = global_grid()
    key = (gg.epoch, p)
    cached = _residual_cache.get(key)
    if cached is not None:
        a, b = cached(*state)
        return float(a), float(b)
    if _residual_cache and next(iter(_residual_cache))[0] != gg.epoch:
        _residual_cache.clear()
    spec = Pspec(*AXIS_NAMES)

    def local(*s):
        _, divV, Rx, Ry, Rz = _stokes_terms(tuple(s), p)
        err_div = jnp.max(jnp.abs(divV))
        err_mom = jnp.maximum(jnp.maximum(jnp.max(jnp.abs(Rx)),
                                          jnp.max(jnp.abs(Ry))),
                              jnp.max(jnp.abs(Rz)))
        for ax in AXIS_NAMES:
            err_div = lax.pmax(err_div, ax)
            err_mom = lax.pmax(err_mom, ax)
        return err_div, err_mom

    from jax import shard_map

    fn = jax.jit(shard_map(
        local, mesh=gg.mesh, in_specs=(spec,) * 8,
        out_specs=(Pspec(), Pspec())))
    _residual_cache[key] = fn
    a, b = fn(*state)
    return float(a), float(b)
