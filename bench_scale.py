"""Scale-up dryrun: does the compiled exchange behave toward pod scale?

The north star is v5p-256 (`BASELINE.json`); real hardware here is one chip.
This harness builds 8/27/64-device VIRTUAL CPU meshes (one subprocess per
config — the device count must be fixed before backend init), plus a
4-process x 16-device hybrid-DCN mesh (`IGG_TPU_DCN_AXES=z`, the multi-slice
layout), and records for each:

- mesh construction + `init_global_grid` wall time,
- lower+compile wall time of the flagship whole-step program (stencil +
  inline halo ppermutes),
- the optimized HLO's collective-permute count (SPMD: must stay EXACTLY one
  pair per exchanging axis — 6 — independent of device count; a count that
  grows with N means the program stopped being scale-free),
- optimized HLO size and one-step execution wall time (virtual mesh, so an
  emulation number, not a perf claim).

Output: one JSON line per config + a summary line; `SCALE_DRYRUN.json`
committed at the repo root is this script's captured output
(`python bench_scale.py > SCALE_DRYRUN.json`).

The per-shard program is O(1) in device count by construction (shard_map
SPMD) — what CAN grow is compile time (XLA re-verifies the mesh) and mesh
bookkeeping; that growth curve is what this artifact pins.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import textwrap

# One measurement template; the single- and multi-process variants differ
# only in their preamble (device count / distributed init) and row extras,
# injected via format fields — so the measured quantities can never drift
# between the two.
_MEASURE_TEMPLATE = """
import json, os, sys, time

{preamble}
import numpy as np

sys.path.insert(0, "/root/repo")
import implicitglobalgrid_tpu as igg
from implicitglobalgrid_tpu.models import init_diffusion3d, make_run

dims = [int(d) for d in igg.dims_create(n, (0, 0, 0))]
t0 = time.perf_counter()
igg.init_global_grid(8, 8, 8, dimx=dims[0], dimy=dims[1], dimz=dims[2],
                     periodx=1, periody=1, periodz=1, quiet=True,
                     **init_kw)
t_init = time.perf_counter() - t0

T, Cp, p = init_diffusion3d(dtype=np.float32)
run = make_run(p, nt_chunk=1, impl="xla")
t0 = time.perf_counter()
compiled = run.lower(T, Cp).compile()
t_compile = time.perf_counter() - t0
hlo = compiled.as_text()
permutes = hlo.count("collective-permute-start") or \\
    hlo.count("collective-permute(")

out = jax.block_until_ready(run(T, Cp))
t0 = time.perf_counter()
out = jax.block_until_ready(run(*out))
t_exec = time.perf_counter() - t0

row = {{
    "n_devices": n, "dims": dims, "t_init_s": round(t_init, 3),
    "t_compile_s": round(t_compile, 3),
    "collective_permutes": permutes,
    "hlo_bytes": len(hlo), "t_exec_s": round(t_exec, 4),
}}
row.update(extras)
if emit:
    print(prefix + json.dumps(row), flush=True)
"""

_CHILD = _MEASURE_TEMPLATE.format(preamble=textwrap.dedent("""
    n = int(sys.argv[1])
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n}")
    import jax

    jax.config.update("jax_platforms", "cpu")
    init_kw = {}
    extras = {"processes": 1}
    emit, prefix = True, ""
"""))

_CHILD_MP = _MEASURE_TEMPLATE.format(preamble=textwrap.dedent("""
    pid, nproc, port, ndev = (int(sys.argv[1]), int(sys.argv[2]),
                              sys.argv[3], int(sys.argv[4]))
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={ndev}")
    os.environ["IGG_TPU_DCN_AXES"] = "z"
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(coordinator_address=f"127.0.0.1:{port}",
                               num_processes=nproc, process_id=pid)
    n = nproc * ndev
    init_kw = {"init_dist": False, "reorder": 0}
    extras = {"processes": nproc, "dcn_axes": "z"}
    emit, prefix = (pid == 0), "SCALE_MP "
"""))


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _clean_env():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = ""
    return env


def run_single(n: int, tmp: str, timeout: int = 900):
    path = os.path.join(tmp, f"scale_child_{n}.py")
    with open(path, "w") as f:
        f.write(_CHILD)
    proc = subprocess.run([sys.executable, path, str(n)],
                          capture_output=True, text=True, timeout=timeout,
                          env=_clean_env(), cwd="/root/repo")
    for ln in proc.stdout.splitlines():
        if ln.strip().startswith("{"):
            return json.loads(ln)
    return {"n_devices": n, "error":
            (proc.stderr or proc.stdout or "no output")[-800:]}


def run_multiprocess(nproc: int, ndev: int, tmp: str, timeout: int = 900):
    path = os.path.join(tmp, "scale_child_mp.py")
    with open(path, "w") as f:
        f.write(_CHILD_MP)
    port = _free_port()
    procs = [subprocess.Popen(
        [sys.executable, path, str(pid), str(nproc), str(port), str(ndev)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=_clean_env(), cwd="/root/repo") for pid in range(nproc)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    row = None
    for out in outs:
        for ln in out.splitlines():
            if ln.startswith("SCALE_MP "):
                row = json.loads(ln[len("SCALE_MP "):])
    if row is None:
        row = {"n_devices": nproc * ndev, "processes": nproc, "error":
               "\\n---\\n".join(o[-400:] for o in outs)}
    return row


def main() -> None:
    import tempfile

    single_ns = [int(x) for x in
                 os.environ.get("IGG_SCALE_NS", "8,27,64").split(",")]
    rows = []

    def guarded(fn, n, *args):
        # a hung config must become an error ROW, not a traceback that
        # loses the summary and the remaining configs
        try:
            return fn(*args)
        except Exception as e:
            return {"n_devices": n, "error": f"{type(e).__name__}: {e}"}

    # The 4-process x 16-device distributed-CPU config exercises a real
    # jax.distributed coordinator — valuable evidence, but a coordinator
    # flake or slow shared runner must not redden a build that only asked
    # for the trimmed single-process sweep.  IGG_SCALE_MP=1/0 forces it
    # on/off; otherwise it runs only for the full (untrimmed) sweep
    # (ADVICE r4: CI trims with IGG_SCALE_NS and must not gate on it).
    mp_env = os.environ.get("IGG_SCALE_MP", "").strip().lower()
    if mp_env in ("1", "true", "yes"):
        run_mp = True
    elif mp_env in ("0", "false", "no"):
        run_mp = False
    elif mp_env:
        sys.stderr.write(f"[bench_scale] ignoring IGG_SCALE_MP={mp_env!r} "
                         "(expected 1/0)\n")
        run_mp = "IGG_SCALE_NS" not in os.environ
    else:
        run_mp = "IGG_SCALE_NS" not in os.environ

    with tempfile.TemporaryDirectory() as tmp:
        for n in single_ns:
            rows.append(guarded(run_single, n, n, tmp))
            print(json.dumps(rows[-1]), flush=True)
        if run_mp:
            rows.append(guarded(run_multiprocess, 64, 4, 16, tmp))
            print(json.dumps(rows[-1]), flush=True)

    ok_rows = [r for r in rows if "error" not in r]
    permutes = sorted({r["collective_permutes"] for r in ok_rows})
    summary = {
        "metric": "scale_dryrun_compile_growth",
        "value": (max(r["t_compile_s"] for r in ok_rows) /
                  min(r["t_compile_s"] for r in ok_rows)) if ok_rows else None,
        "unit": "max/min compile time over configs",
        "permute_counts": permutes,
        "scale_free_program": permutes == [6],
        "configs_ok": len(ok_rows), "configs_total": len(rows),
        "note": "SPMD per-shard program: permute count must stay 6 (one "
                "pair per axis) at every device count; compile time growth "
                "bounds the v5p-256 extrapolation",
    }
    if not run_mp:
        # record the skip so a trimmed sweep cannot read as full evidence
        summary["mp_skipped"] = ("4-process DCN config not run "
                                 "(trimmed sweep; set IGG_SCALE_MP=1 to "
                                 "include it)")
    print(json.dumps(summary), flush=True)
    # CI gate (same contract as the other benches' IGG_BENCH_STRICT): red
    # build when a config failed or the program stopped being scale-free.
    if os.environ.get("IGG_BENCH_STRICT") == "1" and not (
            len(ok_rows) == len(rows) and summary["scale_free_program"]):
        sys.exit(1)


if __name__ == "__main__":
    from implicitglobalgrid_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    main()
