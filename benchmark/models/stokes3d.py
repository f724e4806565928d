"""3-D pseudo-transient (PT) Stokes iteration on a staggered grid: the
program's step, the state from the seed, and the plain reference.

State: ``P`` and ``rhog`` at cell centres, ``Vx/Vy/Vz`` and the damped
momentum ``dVx/dVy/dVz`` on faces. Equations, as the program's model
docstring states them (one damped PT iteration)::

    divV = div(V)
    P   <- P - dt_p divV
    tii <- 2 mu (d_i Vi - divV / 3)
    tij <- mu (d_j Vi + d_i Vj)
    R_i = -d_i P + d_j tij (+ buoyancy)
    dV  <- damp dV + R
    V   <- V + dt_v dV

on the periodic global grid. Face ``f`` lies between cells ``f - 1`` and
``f`` (`benchmark.layout`), so cell ``c`` lies between faces ``c`` and
``c + 1``. The reference is plain `jax.numpy` with `jnp.roll` neighbours,
independent of the program. Where the docstring leaves a choice open, the
reference takes the one below:

- the shear stress ``tij`` lives on the edge where the ``i``-face and the
  ``j``-face meet, stored at the index of those two faces and of the cell
  along the third axis;
- ``R`` reads the updated pressure ``P - dt_p divV``, since the docstring
  updates ``P`` first;
- buoyancy adds ``+ rhog`` to ``Rz``, averaged over the two cells beside
  each z-face; ``rhog`` is read-only, with zero global mean
  (`make_state`);
- every face is updated: on a periodic global grid every face is an
  interior face. The program updates ``dV`` on each shard's interior faces
  and never exchanges it, so ``dV``'s halo entries hold stale values that
  no later step reads. A configuration compares ``dV`` on its owned
  entries only (``"compare": "owned"``), and against ``max|V| / dt_v``
  (``"scale"``), as the PT iteration drives ``dV`` towards rounding
  noise (`harness.comparisons`)."""

from __future__ import annotations

NAMES = ("P", "Vx", "Vy", "Vz", "dVx", "dVy", "dVz", "rhog")


def physics(cfg: dict, layout) -> dict:
    """``h = extent / (N - 1)``, ``dt_v = min(h)^2 / mu / 6.1 / 2``,
    ``dt_p = 6.1 mu / max(N)``, ``damp = 1 - 6 / max(N)``, as
    `init_stokes3d` sets them."""
    h = [L / (N - 1) for L, N in zip(cfg["extent"], layout.global_shape)]
    mu, n_max = float(cfg["mu"]), max(layout.global_shape)
    return {"mu": mu, "dt_v": min(h) ** 2 / mu / 6.1 / 2.0,
            "dt_p": 6.1 * mu / n_max, "damp": 1.0 - 6.0 / n_max, "h": h}


def make_state(cfg: dict, layout, seed: int, sharding, dtype) -> dict:
    """All 8 fields uniform in [-amp/2, amp/2), hashed from the seed and
    the global index of each cell or face; then ``rhog`` less its global
    mean. A periodic box holds no net body force: the mean buoyancy would
    accelerate all of the fluid along z without bound, and the growing
    ``Vz`` would swamp the other fields' rounding. Less a constant, the
    halos still agree with their partners."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.layout import owned, seeded_state

    amp = cfg["amp"]
    state = seeded_state(layout, {k: (-amp / 2, amp) for k in NAMES}, seed,
                         dtype, sharding)
    wx, wy, wz = (np.isin(np.arange(d * m), owned(m, m, d)).astype(np.float32)
                  for m, d in zip(layout.local_shape("rhog"), layout.dims))
    cells = float(np.prod(layout.global_shape))

    def less_mean(r):  # the mean over owned cells, each global cell once
        r = r.astype(jnp.float32)
        w = (jnp.asarray(wx)[:, None, None] * jnp.asarray(wy)[None, :, None]
             * jnp.asarray(wz)[None, None, :])
        return (r - jnp.sum(r * w) / cells).astype(dtype)

    state["rhog"] = jax.jit(less_mean, out_shardings=sharding)(state["rhog"])
    return state


def program_step(phys: dict, impl: str):
    """The program's own local step (what `service.job` builds for a
    stokes3d job), as a dict -> dict function."""
    from implicitglobalgrid_tpu.models import StokesParams, stokes_step_local

    hx, hy, hz = phys["h"]
    p = StokesParams(mu=phys["mu"], dt_v=phys["dt_v"], dt_p=phys["dt_p"],
                     damp=phys["damp"], dx=hx, dy=hy, dz=hz)

    def step(s):
        return dict(zip(NAMES, stokes_step_local(
            tuple(s[k] for k in NAMES), p, impl)))

    return step


def reference(phys: dict, nt: int, dtype):
    """A jitted function: global fields -> the fields after ``nt`` plain PT
    iterations on the periodic grid, computed in ``dtype`` (the state is
    cast to it first)."""
    import jax
    import jax.numpy as jnp

    mu, dt_v, dt_p, damp, h = (phys[k] for k in
                               ("mu", "dt_v", "dt_p", "damp", "h"))

    def at_cells(F, a):  # faces -> the cells between them
        return (jnp.roll(F, -1, a) - F) / h[a]

    def at_faces(C, a):  # cells (or edges) -> the faces between them
        return (C - jnp.roll(C, 1, a)) / h[a]

    def body(_, s):
        P, V, dV, rhog = s[0], s[1:4], s[4:7], s[7]
        divV = sum(at_cells(V[a], a) for a in range(3))
        Pn = P - dt_p * divV
        tau = {(a, a): 2 * mu * (at_cells(V[a], a) - divV / 3)
               for a in range(3)}
        for a, b in ((0, 1), (0, 2), (1, 2)):
            tau[a, b] = tau[b, a] = mu * (at_faces(V[a], b)
                                          + at_faces(V[b], a))
        R = [at_faces(tau[a, a] - Pn, a)
             + sum(at_cells(tau[a, b], b) for b in range(3) if b != a)
             for a in range(3)]
        R[2] = R[2] + 0.5 * (rhog + jnp.roll(rhog, 1, 2))
        dV = tuple(damp * dV[a] + R[a] for a in range(3))
        V = tuple(V[a] + dt_v * dV[a] for a in range(3))
        return (Pn,) + V + dV + (rhog,)

    def run(fields):
        out = jax.lax.fori_loop(0, nt, body,
                                tuple(fields[k].astype(dtype) for k in NAMES))
        return dict(zip(NAMES, out))

    return jax.jit(run)
