"""3-D heat diffusion: the program's step, the state from the seed, and the
plain reference.

Equations (reference example `diffusion3D_multigpu_CuArrays_novis.jl`):
``T += dt * lam * laplacian(T) / Cp`` on the periodic global grid, ``Cp``
read-only. The reference below is straightforward `jax.numpy` on the
global interior (neighbours by `jnp.roll`), independent of the program."""

from __future__ import annotations


def physics(cfg: dict, layout) -> dict:
    """Grid spacing and time step, as the reference example sets them
    (``dx = lx / (nx_g - 1)``, ``dt = min(dx²) * cp_min / lam / 8.1``)."""
    h = [L / (N - 1) for L, N in zip(cfg["extent"], layout.global_shape)]
    dt = min(v * v for v in h) * cfg["cp_min"] / cfg["lam"] / 8.1
    return {"lam": float(cfg["lam"]), "dt": float(dt), "h": h}


def make_state(cfg: dict, layout, seed: int, sharding, dtype) -> dict:
    """``T`` uniform in [0, T_amp), ``Cp`` uniform in [cp_min, cp_min +
    cp_span), each hashed from the seed and the global cell index."""
    from benchmark.layout import seeded_state

    return seeded_state(layout, {"T": (0.0, cfg["T_amp"]),
                                 "Cp": (cfg["cp_min"], cfg["cp_span"])},
                        seed, dtype, sharding)


def program_step(phys: dict, impl: str):
    """The program's own local step (what `service.job` builds for a
    diffusion3d job), as a dict -> dict function."""
    from implicitglobalgrid_tpu.models import (
        DiffusionParams, diffusion_step_local,
    )

    hx, hy, hz = phys["h"]
    p = DiffusionParams(lam=phys["lam"], dt=phys["dt"], dx=hx, dy=hy, dz=hz)

    def step(s):
        return {"T": diffusion_step_local(s["T"], s["Cp"], p, impl),
                "Cp": s["Cp"]}

    return step


def reference(phys: dict, nt: int, dtype):
    """A jitted function: global fields -> the fields after ``nt`` plain
    steps on the periodic grid, computed in ``dtype`` (the state is cast
    to it first)."""
    import jax
    import jax.numpy as jnp

    lam, dt, h = phys["lam"], phys["dt"], phys["h"]

    def run(fields):
        T, Cp = fields["T"].astype(dtype), fields["Cp"].astype(dtype)

        def body(_, T):
            lap = sum((jnp.roll(T, -1, a) - 2 * T + jnp.roll(T, 1, a))
                      / (h[a] * h[a]) for a in range(3))
            return T + dt * lam * lap / Cp
        return {"T": jax.lax.fori_loop(0, nt, body, T), "Cp": Cp}

    return jax.jit(run)
