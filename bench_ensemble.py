"""Benchmark: ensemble axis — per-member step time vs solo (ISSUE 12).

The ensemble batches E scenario members through ONE compiled chunk (vmap
over the member axis, `models.common.make_state_runner(ensemble=E)`), and
jax's collective batching keeps the chunk's ppermute/psum COUNT flat in E
while every payload scales E x. The economics: per-member step time =
(E·compute + comm) / E = compute + comm/E — the exchange cost amortizes
over the batch, so per-member time approaches (from above or below,
depending on cache pressure) the solo step and the latency-bound share
vanishes as 1/E. This bench measures exactly that claim on the live mesh:

- ``ensemble_per_member_speedup_E{4,8,16}``: solo step time / per-member
  step time at E (>= 1 means a member inside the batch is no slower than
  a solo run — the amortization paid for the batching). Gated by the
  perfdb trailing-median check (higher-better by name).
- ``ensemble_permutes_flat_ok``: ABSOLUTE gate — the compiled guarded
  chunk at E=8 carries exactly the E=1 permute count and the same single
  guard psum (collective count independent of ensemble size, proven on
  the compiled program, not the plan).
- ``ensemble_amortization_ok``: ABSOLUTE gate — every measured per-member
  step sits within 10% of the solo step (speedup >= 0.9), the ISSUE-12
  acceptance bar.

Usage: python bench_ensemble.py          (real chip)
       python bench_ensemble.py --cpu    (8-device virtual CPU mesh)
"""

from __future__ import annotations

import sys

import bench_util

MEMBERS = (4, 8, 16)


def ensemble_rows(nx: int, c1: int, members=MEMBERS, dtype=None):
    """Measure per-member-vs-solo rows + the permute-flat gate on the
    CURRENT grid (caller owns init/finalize). Diffusion f32: the flagship
    workload, one exchanged field — the leanest program whose exchange
    the ensemble can amortize."""
    import numpy as np

    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.analysis import parse_program
    from implicitglobalgrid_tpu.models import (
        diffusion_step_local, ensemble_state, init_diffusion3d, make_run,
    )
    from implicitglobalgrid_tpu.runtime.health import make_guarded_runner

    dtype = dtype or np.float32
    T, Cp, p = init_diffusion3d(dtype=dtype)
    rows = []

    def timed(E):
        if E is None:
            args = (T, Cp)
        else:
            args = (ensemble_state(T, E, perturb=0.01),
                    ensemble_state(Cp, E))

        def chunk(c):
            run = make_run(p, c, 3, "xla", ensemble=E)
            igg.sync(run(*args))

        # reps=4 min-kept: same contention-robust estimator as the
        # coalescing A/B (bench_halo) — the shared-core mesh spikes
        # individual windows
        return bench_util.two_point(chunk, c1, 3 * c1, reps=4)

    t_solo = timed(None)
    rows.append({
        "metric": "ensemble_solo_step_s",
        "value": t_solo,
        "unit": "s/step (solo reference for the speedup rows)",
    })
    speedups = {}
    for E in members:
        t_e = timed(E)
        per_member = t_e / E
        speedups[E] = t_solo / per_member
        rows.append({
            "metric": f"ensemble_per_member_speedup_E{E}",
            "value": speedups[E],
            "unit": "x (solo_step_s / per_member_step_s; >=1 = batched "
                    "member no slower than solo)",
            "per_member_step_s": per_member,
            "ensemble_step_s": t_e,
            "solo_step_s": t_solo,
        })

    # absolute gate: compiled collective count flat in E — parse the
    # GUARDED chunk (the program the service actually dispatches: halo
    # permutes + the one stats psum) at E=1 and E=8
    def step(s):
        return (diffusion_step_local(s[0], s[1], p, "xla"), s[1])

    counts = {}
    for E in (1, 8):
        run = make_guarded_runner(step, (3, 3), nt_chunk=1,
                                  key=("bench_ens_gate", nx), ensemble=E)
        args = (ensemble_state(T, E), ensemble_state(Cp, E))
        ir = parse_program(run, *args)
        counts[E] = (len(ir.permutes), len(ir.all_reduces))
    flat_ok = counts[1] == counts[8] and counts[1][1] == 1
    rows.append({
        "metric": "ensemble_permutes_flat_ok",
        "value": 1.0 if flat_ok else 0.0,
        "unit": "bool (1 = compiled permute+psum count at E=8 equals E=1)",
        "permutes_E1": counts[1][0], "permutes_E8": counts[8][0],
        "psums_E1": counts[1][1], "psums_E8": counts[8][1],
    })
    amort_ok = all(s >= 0.9 for s in speedups.values())
    rows.append({
        "metric": "ensemble_amortization_ok",
        "value": 1.0 if amort_ok else 0.0,
        "unit": "bool (1 = per-member step within 10% of solo at every E)",
        "speedups": {str(k): v for k, v in speedups.items()},
    })
    return rows


def run_ensemble_ab(dims, cpu: bool):
    """The canonical ensemble leg: init its own all-periodic grid over
    ``dims``, measure, finalize, return the rows. Shared by this script's
    __main__ and `bench_all.py` so the config stays in ONE place.

    Block 16^3 on the CPU mesh: small enough that E=16 x 8 shards stays
    cache-resident, large enough that the exchange is a visible share —
    the regime the amortization claim is about."""
    import implicitglobalgrid_tpu as igg

    nx_e, c_e = (16, 8) if cpu else (128, 20)
    igg.init_global_grid(nx_e, nx_e, nx_e, dimx=dims[0], dimy=dims[1],
                         dimz=dims[2], periodx=1, periody=1, periodz=1,
                         quiet=True)
    try:
        return ensemble_rows(nx_e, c_e)
    finally:
        igg.finalize_global_grid()


def main() -> None:
    cpu = "--cpu" in sys.argv
    if cpu:
        import os

        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        ).strip()
        import jax

        jax.config.update("jax_platforms", "cpu")
    import jax

    import implicitglobalgrid_tpu as igg

    nd = len(jax.devices())
    dims = tuple(int(d) for d in igg.dims_create(nd, (0, 0, 0)))
    for row in run_ensemble_ab(dims, cpu):
        bench_util.emit(row)


if __name__ == "__main__":
    bench_util.run(main, "ensemble_per_member_speedup_E8", "x (solo_step_s / per_member_step_s)")
