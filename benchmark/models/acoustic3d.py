"""3-D staggered acoustic leapfrog (P at cell centres, Vx/Vy/Vz on faces):
the program's step, the state from the seed, and the plain reference.

Equations (ParallelStencil's acoustic3D miniapp family):
``V -= dt / rho * grad(P)`` on faces, then ``P -= dt * K * div(V)`` at
centres, on the periodic global grid. Face ``f`` lies between cells
``f - 1`` and ``f`` (`benchmark.layout`). The reference is plain
`jax.numpy` with `jnp.roll` neighbours, independent of the program."""

from __future__ import annotations

import math


def physics(cfg: dict, layout) -> dict:
    """``dx = lx / (nx_g - 1)``, ``dt = min(dx) / c / sqrt(3.1)`` with
    ``c = sqrt(K / rho)``, as `init_acoustic3d` sets them."""
    h = [L / (N - 1) for L, N in zip(cfg["extent"], layout.global_shape)]
    c = math.sqrt(cfg["K"] / cfg["rho"])
    dt = min(h) / c / math.sqrt(3.1)
    return {"rho": float(cfg["rho"]), "K": float(cfg["K"]), "dt": float(dt),
            "h": h}


def make_state(cfg: dict, layout, seed: int, sharding, dtype) -> dict:
    """Every field uniform in [-amp/2, amp/2), hashed from the seed and the
    global index of its cell or face."""
    from benchmark.layout import seeded_state

    amp = cfg["amp"]
    return seeded_state(layout, {k: (-amp / 2, amp)
                                 for k in ("P", "Vx", "Vy", "Vz")},
                        seed, dtype, sharding)


def program_step(phys: dict, impl: str):
    """The program's own local step (what `service.job` builds for an
    acoustic3d job), as a dict -> dict function."""
    from implicitglobalgrid_tpu.models import (
        AcousticParams, acoustic_step_local,
    )

    hx, hy, hz = phys["h"]
    p = AcousticParams(rho=phys["rho"], K=phys["K"], dt=phys["dt"], dx=hx,
                       dy=hy, dz=hz)
    names = ("P", "Vx", "Vy", "Vz")

    def step(s):
        return dict(zip(names, acoustic_step_local(
            tuple(s[k] for k in names), p, impl)))

    return step


def reference(phys: dict, nt: int, dtype):
    """A jitted function: global fields -> the fields after ``nt`` plain
    leapfrog steps on the periodic grid, computed in ``dtype`` (the state
    is cast to it first)."""
    import jax
    import jax.numpy as jnp

    rho, K, dt, h = phys["rho"], phys["K"], phys["dt"], phys["h"]

    names = ("P", "Vx", "Vy", "Vz")

    def run(fields):
        def body(_, s):
            P, V = s[0], s[1:]
            V = tuple(V[a] - dt / rho * (P - jnp.roll(P, 1, a)) / h[a]
                      for a in range(3))
            div = sum((jnp.roll(V[a], -1, a) - V[a]) / h[a]
                      for a in range(3))
            return (P - dt * K * div,) + V
        out = jax.lax.fori_loop(0, nt, body,
                                tuple(fields[k].astype(dtype) for k in names))
        return dict(zip(names, out))

    return jax.jit(run)
