"""Accuracy record for the f64 anchor substitution (BASELINE config 3).

The reference's anchor workload is Float64 (its example default,
`/root/reference/examples/diffusion3D_multicpu_novis.jl:26`); this TPU
generation has no native f64 pipeline, so the framework's anchor rows run
f32 (and bf16-with-f32-compute). This script makes that substitution a
MEASURED decision instead of a note: it advances the anchor diffusion
physics in f64 (the ground truth), f32, and bf16 side by side on the
x64-enabled CPU mesh and reports the drift after ``nt`` steps:

    max_rel = max|T_x - T_f64| / max|T_f64|
    l2_rel  = ||T_x - T_f64||_2 / ||T_f64||_2

One JSON line, driver-parseable. The measured numbers are recorded in
`docs/performance.md` ("f64 anchor accuracy"); re-run with
``python bench_f64_accuracy.py [nx] [nt]`` (defaults 48, 400).
"""

from __future__ import annotations

import json
import os
import sys


def main() -> None:
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp
    import numpy as np

    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.models import init_diffusion3d, run_diffusion

    nx = int(sys.argv[1]) if len(sys.argv) > 1 else 48
    nt = int(sys.argv[2]) if len(sys.argv) > 2 else 400
    # the reference legs (f64 ground truth, f32, bf16) must run with an
    # EXACT wire even if the invoking shell exports IGG_HALO_WIRE_DTYPE
    # — an ambient policy would silently corrupt every drift row; the
    # wire legs set it per leg below
    os.environ.pop("IGG_HALO_WIRE_DTYPE", None)
    nd = len(jax.devices())
    dims = tuple(int(d) for d in igg.dims_create(nd, (0, 0, 0)))

    finals = {}
    # bf16 runs twice: through the XLA path (native bf16 flux arithmetic)
    # and through the kernel tier in interpret mode (bf16 storage, f32
    # compute — `pallas_stencil._stencil_plane`'s mixed-precision recipe).
    # "f64_bf16ic" integrates the bf16-QUANTIZED initial condition in f64:
    # bf16 legs compared against it isolate ARITHMETIC error from the
    # (irreducible) IC quantization error. The wire legs (5th tuple slot)
    # run f32 state with the quantized halo wire (ISSUE 10): drift vs f64
    # is the accuracy cost of shipping halos as per-slab-scaled int8/int4
    # — the error model docs/performance.md tabulates.
    legs = ((np.float64, "f64", None, False, None),
            (np.float32, "f32", None, False, None),
            (np.float64, "f64_bf16ic", None, True, None),
            (jnp.bfloat16, "bf16_xla", "xla", False, None),
            (jnp.bfloat16, "bf16_kernel", "pallas_interpret", False, None),
            # stochastic-rounding bf16 storage (ops/precision.py): f32
            # compute, unbiased bf16 store — the leg that decides whether
            # bf16 is a correctness-preserving mode or only a bandwidth
            # study (round-4 verdict)
            (jnp.bfloat16, "bf16_sr", "sr", False, None),
            (np.float32, "int8_wire", None, False, "int8"),
            (np.float32, "int4_wire", None, False, "int4"))
    for dtype, tag, impl, bf16_ic, wire in legs:
        igg.init_global_grid(nx, nx, nx, dimx=dims[0], dimy=dims[1],
                             dimz=dims[2], periodx=1, periody=1, periodz=1,
                             quiet=True)
        # identical physics: ICs are built in the target dtype by the model,
        # but dt/dx come from f64 host scalars either way
        if bf16_ic:
            Tb, Cpb, _ = init_diffusion3d(dtype=jnp.bfloat16)
            _, _, p = init_diffusion3d(dtype=dtype)
            T = igg.device_put_g(np.asarray(Tb).astype(dtype))
            Cp = igg.device_put_g(np.asarray(Cpb).astype(dtype))
        else:
            T, Cp, p = init_diffusion3d(dtype=dtype, sr=(impl == "sr"))
        if wire is not None:
            os.environ["IGG_HALO_WIRE_DTYPE"] = wire
        try:
            out = run_diffusion(T, Cp, p, nt, nt_chunk=max(1, nt // 4),
                                impl=None if impl == "sr" else impl)
        finally:
            if wire is not None:
                os.environ.pop("IGG_HALO_WIRE_DTYPE", None)
        finals[tag] = np.asarray(igg.gather_interior(out), dtype=np.float64)
        igg.finalize_global_grid()

    scale = float(np.max(np.abs(finals["f64"])))
    l2 = float(np.linalg.norm(finals["f64"]))
    drift = {}
    for tag, ref_tag in (("f32", "f64"), ("f64_bf16ic", "f64"),
                         ("bf16_xla", "f64_bf16ic"),
                         ("bf16_kernel", "f64_bf16ic"),
                         ("bf16_sr", "f64_bf16ic"),
                         ("int8_wire", "f64"), ("int4_wire", "f64")):
        d = finals[tag] - finals[ref_tag]
        drift[tag] = {
            "vs": ref_tag,
            "max_rel": float(np.max(np.abs(d)) / scale),
            "l2_rel": float(np.linalg.norm(d) / l2),
        }

    print(json.dumps({
        "metric": "diffusion3D_f64_substitution_drift",
        "value": drift["f32"]["max_rel"],
        "unit": f"max|T_f32-T_f64|/max|T_f64| after nt={nt}, global grid "
                f"{'x'.join(str(s) for s in finals['f64'].shape)}",
        "drift": drift,
        "nx": nx, "nt": nt,
        "note": "anchor physics advanced in f64/f32/bf16 side by side on "
                "the x64 CPU mesh; f32 drift (vs f64) is the accuracy cost "
                "of the TPU anchor substitution (BASELINE config 3). "
                "f64_bf16ic (vs f64) is the irreducible bf16 IC "
                "quantization; bf16_xla / bf16_kernel compare against it, "
                "isolating ARITHMETIC drift: native bf16 flux arithmetic "
                "vs the kernel tier's bf16-storage/f32-compute recipe vs "
                "stochastic-rounding storage (bf16_sr, ops/precision.py). "
                "int8_wire / int4_wire (vs f64) run f32 state with the "
                "quantized halo wire (ISSUE 10, per-slab-scaled payloads): "
                "the drift bound the quant-marked accuracy tier asserts",
    }))


if __name__ == "__main__":
    from implicitglobalgrid_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    main()
