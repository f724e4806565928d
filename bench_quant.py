"""Benchmark: the quantized halo wire's A/B leg (ISSUE 10).

WEAK_SCALING.json shows exposed comm per step as the scaling ceiling and
PR 1's `wire_dtype` casts stop at 2x. This leg records what the int8/int4
per-slab-scale wire buys and costs:

- ``quant_wire_bytes_ratio`` — static, from `halo_comm_plan`: f32 bytes /
  int8 bytes at 4 coalesced fields (payload + appended scales; the
  contract tests pin >= 3.5x, the EQuARX-region number is ~3.76x).
- ``quant_step_speedup`` — measured exact-wire / int8-wire seconds per
  exchange-loop call on the live mesh. On the emulated CPU mesh there is
  no real wire to save, so this is an OVERHEAD gate in disguise: the
  quantize/dequantize arithmetic must not blow up the step
  (``quant_overhead_gate_ok`` = speedup >= 1/2.5); on ICI/DCN hardware
  the wire-byte reduction is the win the ratio row prices.
- ``quant_exposed_comm_model_delta_frac`` — the perf oracle's exposed-comm
  delta for diffusion3D on a 2-axis mesh under the per-axis policy
  ``z:int8,x:f32`` vs exact wire (`predict_step` on a deterministic
  ICI+DCN hierarchical profile: 45 GB/s x-links, 2 GB/s z-links), the
  HiCCL-style slow-axis-only configuration COMM_AVOID.json motivates.

Prints one JSON line per row. Usage: python bench_quant.py [--cpu]
"""

from __future__ import annotations

import sys

import bench_util


def quant_ab_rows(nx: int, c1: int, n_fields: int = 4):
    """A/B rows for the quantized wire on the CURRENT grid (caller owns
    init/finalize): static byte ratio + measured exact/int8 loop times."""
    import numpy as np

    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.models.common import make_state_runner

    fields = tuple(igg.ones_g((nx, nx, nx), np.float32) * (i + 1)
                   for i in range(n_fields))
    plan_f32 = igg.halo_comm_plan(*fields)
    plan_int8 = igg.halo_comm_plan(*fields, wire_dtype="int8")
    ratio = plan_f32["wire_bytes"] / plan_int8["wire_bytes"]

    secs = {}
    for mode, wire in (("exact", None), ("int8", "int8")):
        def step(s, wire=wire):
            out = igg.local_update_halo(*s, wire_dtype=wire or "off")
            return out if isinstance(out, tuple) else (out,)

        def chunk(c):
            run = make_state_runner(
                step, (3,) * n_fields, nt_chunk=c,
                key=("bench_quant_ab", mode, n_fields, nx))
            igg.sync(run(*fields))

        secs[mode] = bench_util.two_point(chunk, c1, 3 * c1)
    speedup = secs["exact"] / secs["int8"]
    return [
        {
            "metric": "quant_wire_bytes_ratio",
            "value": ratio,
            "unit": f"x (f32 bytes / int8 payload+scale bytes, "
                    f"{n_fields} coalesced fields)",
            "f32_wire_bytes": plan_f32["wire_bytes"],
            "int8_wire_bytes": plan_int8["wire_bytes"],
            "int4_wire_bytes": igg.halo_comm_plan(
                *fields, wire_dtype="int4")["wire_bytes"],
        },
        {
            "metric": "quant_step_speedup",
            "value": speedup,
            "unit": "x (exact_s / int8_s per exchange-loop call)",
            "exact_s_per_call": secs["exact"],
            "int8_s_per_call": secs["int8"],
            "note": "the emulated CPU mesh has no wire to save: this is "
                    "the quantize/dequantize overhead gate; the byte "
                    "ratio row prices the on-wire win",
        },
        {
            "metric": "quant_overhead_gate_ok",
            "value": 1.0 if speedup >= 1.0 / 2.5 else 0.0,
            "unit": "bool (1 = int8 wire costs < 2.5x the exact exchange "
                    "even with zero wire savings)",
        },
    ]


def exposed_comm_model_row(dims2):
    """The per-axis-policy exposed-comm delta, MODELED (`predict_step` —
    deterministic): diffusion3D on a 2-axis mesh with the z axis
    quantized (``z:int8,x:f32``) vs exact wire, priced on a HIERARCHICAL
    profile (x = ICI-class 45 GB/s, z = DCN-class 2 GB/s / 50 us — the
    COMM_AVOID.json regime where slow-axis tricks pay): the
    configuration the per-axis policy exists for."""
    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.telemetry.perfmodel import MachineProfile

    import jax
    import numpy as np

    profile = MachineProfile(
        membw_GBps=800.0, flops_G=45000.0,
        axes={"gx": {"GBps": 45.0, "latency_s": 5e-6},
              "gy": {"GBps": 45.0, "latency_s": 5e-6},
              "gz": {"GBps": 2.0, "latency_s": 5e-5}},
        source="default", device={"platform": "model:ici+dcn"})
    # production-scale blocks (256^3/shard): the z slab is ~100s of KB,
    # deep in the DCN link's bandwidth-bound regime — priced statically
    # via ShapeDtypeStruct, nothing is allocated
    nx = 256
    igg.init_global_grid(nx, nx, nx, dimx=dims2[0], dimy=dims2[1],
                         dimz=dims2[2], periodx=1, periodz=1, quiet=True)
    try:
        stacked = tuple(nx * d for d in dims2)
        T = jax.ShapeDtypeStruct(stacked, np.float32)
        Cp = jax.ShapeDtypeStruct(stacked, np.float32)
        exact = igg.predict_step("diffusion3d", (T, Cp), profile=profile)
        if "gz" not in exact["comm"]:  # z unpartitioned (e.g. 1 device)
            return {
                "metric": "quant_exposed_comm_model_delta_frac",
                "value": None,
                "note": f"mesh {dims2} has no partitioned z axis to "
                        "quantize; row skipped",
            }
        quant = igg.predict_step("diffusion3d", (T, Cp), profile=profile,
                                 wire_dtype="z:int8,x:f32")
        delta = exact["exposed_comm_s"] - quant["exposed_comm_s"]
        frac = (delta / exact["exposed_comm_s"]
                if exact["exposed_comm_s"] else 0.0)
        return {
            "metric": "quant_exposed_comm_model_delta_frac",
            "value": frac,
            "unit": "fraction of exposed comm removed by z:int8 on the "
                    "2-axis mesh (modeled, ICI+DCN hierarchical profile)",
            "exact_exposed_comm_s": exact["exposed_comm_s"],
            "quant_exposed_comm_s": quant["exposed_comm_s"],
            "z_wire_bytes_exact": exact["comm"]["gz"]["per_link_bytes"],
            "z_wire_bytes_quant": quant["comm"]["gz"]["per_link_bytes"],
        }
    finally:
        igg.finalize_global_grid()


def run_quant_ab(dims, cpu: bool):
    """The canonical leg: all-periodic grid over ``dims`` for the A/B,
    then a 2-axis mesh for the modeled per-axis-policy delta. Shared by
    this script's __main__ and `bench_all.py` (config in ONE place)."""
    import implicitglobalgrid_tpu as igg

    nx_ab, c_ab = (32, 4) if cpu else (256, 20)
    igg.init_global_grid(nx_ab, nx_ab, nx_ab, dimx=dims[0], dimy=dims[1],
                         dimz=dims[2], periodx=1, periody=1, periodz=1,
                         quiet=True)
    try:
        rows = quant_ab_rows(nx_ab, c_ab)
    finally:
        igg.finalize_global_grid()
    nd = dims[0] * dims[1] * dims[2]
    # always give the policy leg a partitioned z when possible; on one
    # device `exposed_comm_model_row` records the row as skipped
    dims2 = (2, 1, nd // 2) if nd >= 4 else (1, 1, nd)
    rows.append(exposed_comm_model_row(dims2))
    return rows


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
    for row in run_quant_ab(dims, cpu):
        bench_util.emit(row)


if __name__ == "__main__":
    bench_util.run(main, "quant_wire_bytes_ratio", "x")
