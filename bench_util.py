"""Shared benchmark harness: supervision, device tagging, measurement.

Every bench entry point ends in :func:`run`. The parent process never
touches JAX: it re-executes the script as ONE child (a chip belongs to one
process, so only the child may claim it), forwards the child's JSON rows
on success, and otherwise prints one error row and exits 1. The child
turns on the persistent compile cache and, unless ``--cpu`` asks for the
virtual CPU mesh (rehearsal), refuses to measure anywhere but on a TPU: a
run that finds no TPU fails instead of emitting CPU rows.

Every row emitted through :func:`emit` carries ``platform`` /
``device_kind`` / ``n_devices`` fields, so a CPU-mesh number can never pass
for a device number.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

_CHILD_ENV = "IGG_BENCH_CHILD"


def device_fields() -> dict:
    """platform/device_kind/n_devices of the active jax backend."""
    import jax

    d = jax.devices()
    return {
        "platform": d[0].platform,
        "device_kind": d[0].device_kind,
        "n_devices": len(d),
    }


def emit(row: dict) -> dict:
    """Tag *row* with device fields and print it as one JSON line."""
    row = {**row, **device_fields()}
    print(json.dumps(row))
    return row


def child_env() -> dict:
    """Environment for spawning the measurement child of THIS process: the
    marker carries our pid plus a random token, so neither a leaked ``1``
    nor a stale marker from another run can route a fresh invocation down
    the unsupervised child path."""
    import secrets

    return {**os.environ,
            _CHILD_ENV: f"{os.getpid()}:{secrets.token_hex(8)}"}


def is_child() -> bool:
    """True only when the marker has the ``<ppid>:<token>`` shape stamped
    by :func:`child_env` and the pid half names OUR direct parent — a
    leaked ``IGG_BENCH_CHILD=1`` from the invoking environment cannot
    bypass supervision."""
    val = os.environ.get(_CHILD_ENV, "")
    pid, sep, token = val.partition(":")
    return bool(sep) and len(token) >= 8 and pid == str(os.getppid())


def require_tpu() -> None:
    """Raise unless JAX's default devices are TPUs (``--cpu`` runs are
    rehearsals and skip this)."""
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise RuntimeError(
            f"no TPU: JAX's default platform is {platform!r}. Pass --cpu "
            "to rehearse on the virtual CPU mesh.")


def supervise(metric: str, unit: str) -> int:
    """Run this script again as one child process; forward its JSON rows
    and return 0 when it succeeds, else print one error row and return
    1."""
    proc = subprocess.run([sys.executable, *sys.argv], env=child_env(),
                          capture_output=True, text=True)
    rows = [ln.strip() for ln in proc.stdout.splitlines()
            if ln.strip().startswith("{")]
    if proc.returncode == 0 and rows:
        print("\n".join(rows), flush=True)
        return 0
    # a failed child may have printed partial rows: drop them, so no
    # number from a failed run reaches the capture
    tail = (proc.stderr or proc.stdout or "")[-2000:]
    sys.stderr.write(tail + "\n")
    print(json.dumps({"metric": metric, "value": None, "unit": unit,
                      "error": tail[-1000:] or f"rc={proc.returncode}"}))
    return 1


def run(main, metric: str, unit: str) -> None:
    """A bench script's ``__main__``: supervise, or (in the child) measure."""
    if not is_child():
        sys.exit(supervise(metric, unit))
    from implicitglobalgrid_tpu.utils.compile_cache import use_compile_cache

    if "--cpu" not in sys.argv:
        require_tpu()
    use_compile_cache()
    main()


def measure_triad_gbps(n: int, c1: int = 4) -> float:
    """Fused-XLA triad bandwidth (2 reads + 1 write over ``n`` f32
    elements): the practical HBM ceiling used for roofline percentages.
    Shared by `bench.py` (in-run calibration) and `bench_membw.py` — the
    loop carry keeps ``b`` in place, because a swapped carry pins
    while-loop buffers and pays a hidden full-array copy per step (see
    docs/performance.md trace notes). Grid-independent (wall-clock timer;
    the chunk drains its own outputs)."""
    import time

    import jax
    import jax.numpy as jnp

    a = jnp.arange(n, dtype=jnp.float32)
    b = jnp.ones((n,), jnp.float32)

    @jax.jit
    def triad_chunk(a, b, c):
        def body(_, ab):
            a, b = ab
            return (b * 1.0001 + a * 0.5, b)
        return jax.lax.fori_loop(0, c, body, (a, b))

    def chunk(c):
        jax.block_until_ready(triad_chunk(a, b, c))

    def timer(fn):
        t0 = time.perf_counter()
        fn()
        return time.perf_counter() - t0

    s = two_point(chunk, c1, 3 * c1, timer=timer)
    return 3 * 4 * n / s / 1e9


def two_point(run_chunk, c1: int, c2: int, reps: int = 2,
              timer=None) -> float:
    """Steady-state seconds/step via two warmed one-call chunk windows.

    ``run_chunk(c)`` must execute ONE chunk call of ``c`` steps and drain
    its outputs (`igg.sync`). Both windows pay identical fixed costs (one
    dispatch + one drain round trip), so the slope
    ``(t(c2)-t(c1))/(c2-c1)`` is the pure per-step device time — the same
    amortized steady-state quantity the reference's 100k-step wall-clock
    anchor reports (`reference README.md:163-167`). Each window is
    measured ``reps`` times, keeping the minimum.

    ``timer(fn) -> seconds`` defaults to the barrier-synchronized
    ``igg.tic()``/``igg.toc()`` pair; tests inject a fake clock.

    After each call, ``two_point.last`` records ``{"method", "t1", "t2"}``;
    ``method`` is ``"two-point"`` for a true slope or
    ``"inclusive-fallback"`` when timer jitter produced ``t2 <= t1`` and
    the bigger window's inclusive rate was returned instead (that rate
    re-includes the fixed per-call cost — emitted rows should carry the
    distinction)."""
    if timer is None:
        import implicitglobalgrid_tpu as igg

        def timer(fn):
            igg.tic()
            fn()
            return igg.toc()

    run_chunk(c1)
    run_chunk(c2)  # warm both programs + both drain signatures

    t1 = min(timer(lambda: run_chunk(c1)) for _ in range(reps))
    t2 = min(timer(lambda: run_chunk(c2)) for _ in range(reps))
    if t2 <= t1:  # timer jitter on tiny windows: never emit a <=0 slope;
        two_point.last = {"method": "inclusive-fallback", "t1": t1, "t2": t2}
        return t2 / c2  # fall back to the bigger window's inclusive rate
    two_point.last = {"method": "two-point", "t1": t1, "t2": t2}
    return (t2 - t1) / (c2 - c1)


two_point.last = None
