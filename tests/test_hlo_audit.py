"""HLO-level audit of the compiled halo exchange — on the `analysis`
subsystem.

Guards the framework's core performance claim — "the reference's
pack/send/recv/unpack machinery collapses into one `collective-permute` pair
per exchanging axis" (`ops/halo.py` module docstring) — against XLA
regressions, the way the reference wire-tests its `isend_halo`/`irecv_halo!`
requests (`/root/reference/test/test_update_halo.jl:925-970`).

Since ISSUE 7 these tests are CONTRACT DECLARATIONS, not regex scans: each
compiles a program, parses it into `analysis.ProgramIR`, and checks it
against a `CollectiveContract` derived from the same static wire plan the
telemetry layer prices (`exchange_contract` = `halo_comm_plan` + topology
routes) — so every assertion is dtype-generic (the old f32-only shape regex
silently skipped bf16/f16/f64 payloads), route-aware (each permute's
``source_target_pairs`` must match a mesh axis of the plan), and
byte-exact (all-links wire bytes, not just op counts). Parser unit tests
against checked-in golden dumps live in tests/test_analysis.py.
"""

import numpy as np
import pytest
from jax import shard_map

import implicitglobalgrid_tpu as igg
from implicitglobalgrid_tpu.analysis import (
    CollectiveContract, check_contract, exchange_contract, guard_contract,
    parse_program,
)

pytestmark = pytest.mark.audit


def _exchange_args(dims, shape, n_fields=1, dtypes=None):
    import jax.numpy as jnp

    dtypes = dtypes or [np.float32] * n_fields
    return [jnp.zeros(tuple(d * s for d, s in zip(dims, shape)), dt)
            for dt in dtypes]


def _compiled_exchange(args, dims_order=None, coalesce=None, wire=None,
                       optimized=True):
    """`ProgramIR` of the compiled multi-field exchange (the program the
    old `_compiled_hlo` regex-scanned)."""
    import jax

    from implicitglobalgrid_tpu.ops import halo as halo_mod
    from implicitglobalgrid_tpu.ops.fields import field_partition_spec
    from implicitglobalgrid_tpu.ops.precision import resolve_wire_dtype

    gg = igg.global_grid()
    n_fields = len(args)
    specs = (field_partition_spec(args[0].ndim),) * n_fields
    wire_r = resolve_wire_dtype(wire)

    def exchange(*arrays):
        return tuple(halo_mod._exchange_arrays(
            gg, list(arrays),
            [gg.halowidths] * n_fields,
            halo_mod._normalize_dims_order(dims_order),
            coalesce=coalesce, wire=wire_r,
        ))

    fn = jax.jit(shard_map(
        exchange, mesh=gg.mesh, in_specs=specs, out_specs=specs))
    return parse_program(fn, *args, optimized=optimized)


def _assert_honors(ir, contract):
    findings = check_contract(ir, contract)
    assert not findings, [f.to_json() for f in findings]


def test_one_permute_pair_per_exchanging_axis():
    """2x2x2 periodic: three exchanging axes -> exactly 6 permutes (one
    left+right pair per axis), each on a legal route of its axis, each
    slab-sized, with the plan's exact all-links wire bytes — none more."""
    igg.init_global_grid(8, 8, 8, dimx=2, dimy=2, dimz=2,
                         periodx=1, periody=1, periodz=1, quiet=True)
    args = _exchange_args((2, 2, 2), (8, 8, 8))
    ir = _compiled_exchange(args)
    contract = exchange_contract(*args)
    assert sorted(contract.axes) == ["gx", "gy", "gz"]
    assert all(v["permutes"] == 2 for v in contract.axes.values())
    _assert_honors(ir, contract)
    assert len(ir.permutes) == 6


def test_self_neighbor_axes_emit_no_collectives():
    """Periodic single-shard axes take the local-copy path: no collectives
    at all (reference self-neighbor branch, `update_halo.jl:62-68`)."""
    igg.init_global_grid(8, 8, 8, periodx=1, periody=1, periodz=1,
                         dimx=1, dimy=1, dimz=1, quiet=True)
    args = _exchange_args((1, 1, 1), (8, 8, 8))
    ir = _compiled_exchange(args)
    contract = exchange_contract(*args)
    assert contract.axes == {}  # the plan prices zero wire traffic
    _assert_honors(ir, contract)
    assert not ir.permutes and not ir.all_reduces and not ir.all_gathers


def test_non_exchanging_axis_emits_no_permute():
    """dims=(2,1,4), periody=0: y has no neighbors -> only x and z axes
    exchange -> 4 permutes, and every permute rides an x- or z-route."""
    igg.init_global_grid(8, 8, 8, dimx=2, dimy=1, dimz=4,
                         periodx=1, periody=0, periodz=1, quiet=True)
    args = _exchange_args((2, 1, 4), (8, 8, 8))
    ir = _compiled_exchange(args)
    contract = exchange_contract(*args)
    assert sorted(contract.axes) == ["gx", "gz"]
    _assert_honors(ir, contract)
    assert len(ir.permutes) == 4


def test_multi_field_shares_no_extra_collectives():
    """Two same-dtype fields exchanged in one program COALESCE: the axis
    costs one packed permute pair regardless of field count (2, not
    2 fields x 2 directions), with no hidden reduction/gather
    collectives. ``coalesce=False`` restores the per-field 2N scaling."""
    igg.init_global_grid(8, 8, 8, dimx=8, dimy=1, dimz=1,
                         periodx=1, quiet=True)
    args = _exchange_args((8, 1, 1), (8, 8, 8), n_fields=2)
    _assert_honors(_compiled_exchange(args), exchange_contract(*args))
    assert exchange_contract(*args).axes["gx"]["permutes"] == 2
    pf = exchange_contract(*args, coalesce=False)
    assert pf.axes["gx"]["permutes"] == 4
    _assert_honors(_compiled_exchange(args, coalesce=False), pf)


@pytest.mark.parametrize("n_fields", [2, 4, 8])
def test_coalesced_permute_count_independent_of_field_count(n_fields):
    """THE tentpole claim: on the coalesced path the compiled exchange
    contains exactly 2 ppermutes per exchanged mesh axis for ANY number of
    same-dtype fields (2x2x2 periodic: 3 axes -> 6), where the per-field
    path pays 2 x N x axes."""
    igg.init_global_grid(8, 8, 8, dimx=2, dimy=2, dimz=2,
                         periodx=1, periody=1, periodz=1, quiet=True)
    args = _exchange_args((2, 2, 2), (8, 8, 8), n_fields=n_fields)
    contract = exchange_contract(*args)
    assert all(v["permutes"] == 2 for v in contract.axes.values())
    _assert_honors(_compiled_exchange(args), contract)
    pf = exchange_contract(*args, coalesce=False)
    assert all(v["permutes"] == 2 * n_fields for v in pf.axes.values())
    _assert_honors(_compiled_exchange(args, coalesce=False), pf)


def test_coalesced_mixed_dtypes_one_pair_per_group():
    """dtype groups pack separately (the wire payload of one ppermute has
    one dtype): 3 f32 + 2 f64 fields on one exchanging axis -> 2 groups x
    2 directions = 4 permutes, not 2 x 5 — and the f64 payloads are
    route/slab/byte-audited exactly like the f32 ones (the old f32-only
    regex was blind to them)."""
    igg.init_global_grid(8, 8, 8, dimx=8, dimy=1, dimz=1,
                         periodx=1, quiet=True)
    args = _exchange_args((8, 1, 1), (8, 8, 8), n_fields=5,
                          dtypes=[np.float32] * 3 + [np.float64] * 2)
    contract = exchange_contract(*args)
    assert contract.axes["gx"]["permutes"] == 4
    assert sorted(contract.axes["gx"]["dtypes"]) == ["f32", "f64"]
    ir = _compiled_exchange(args)
    _assert_honors(ir, contract)
    payloads = {str(ir.payload_of(p)) for p in ir.permutes}
    assert any(s.startswith("f64") for s in payloads), payloads


def test_wire_precision_converts_payload():
    """Wire-precision mode: f32 fields cross the link as bf16 — every
    collective_permute in the LOWERED module (pre-backend-optimization:
    the XLA:CPU float-normalization pass rewrites bf16 payloads back to
    f32 around a convert fusion, TPU keeps them native) carries a
    bf16 SLAB-SIZED payload on a legal route with the plan's (halved)
    wire bytes; OFF by default."""
    igg.init_global_grid(8, 8, 8, dimx=2, dimy=2, dimz=2,
                         periodx=1, periody=1, periodz=1, quiet=True)
    args = _exchange_args((2, 2, 2), (8, 8, 8), n_fields=2)
    contract = exchange_contract(*args, wire_dtype="bfloat16")
    assert all(v["dtypes"] == ("bf16",) for v in contract.axes.values())
    ir = _compiled_exchange(args, wire="bfloat16", optimized=False)
    _assert_honors(ir, contract)
    assert len(ir.permutes) == 6
    assert all(ir.payload_of(p).dtype == "bf16" for p in ir.permutes)
    assert ir.count("convert") > 0
    # the wire-downcast lint agrees the narrowing reached the wire
    from implicitglobalgrid_tpu.analysis import default_lint_config, run_lints
    cfg = default_lint_config(state_dtypes=("f32",), wire_dtype="bfloat16")
    assert run_lints(ir, config=cfg, rules=("wire-downcast-missing",)) == []
    # the optimized program still has one permute pair per axis, and the
    # bf16 rounding survives backend normalization (converts feed the wire)
    ir_opt = _compiled_exchange(args, wire="bfloat16")
    assert len(ir_opt.permutes) == 6
    assert ir_opt.count("convert") > 0
    # default: no reduced-precision wire anywhere in the lowered program,
    # and the lint CATCHES a requested-but-absent downcast
    ir_off = _compiled_exchange(args, optimized=False)
    assert not any(op.has_shape("bf16") for op in ir_off.ops)
    missing = run_lints(ir_off, config=cfg,
                        rules=("wire-downcast-missing",))
    assert [f.rule for f in missing] == ["wire-downcast-missing"]
    assert missing[0].severity == "error"


@pytest.mark.quant
def test_quantized_wire_contract_one_pair_s8_bytes_exact():
    """THE quantized-wire claim (ISSUE 10): with ``wire_dtype="int8"``
    the 4-field coalesced exchange still compiles to ONE ppermute pair
    per exchanging axis (collective count unchanged), every payload is
    the packed s8 buffer (slabs + bitcast per-slab f32 scales), the
    plan's wire bytes match the compiled program TO THE BYTE — and sit
    >= 3.5x below the f32 plan at 4 fields. int8 payloads survive
    backend optimization (no float-normalization), so this is the DEEP
    post-SPMD audit, not the lowered-module fallback bf16 needs."""
    igg.init_global_grid(8, 8, 8, dimx=2, dimy=1, dimz=4,
                         periodx=1, periodz=1, quiet=True)
    args = _exchange_args((2, 1, 4), (8, 8, 8), n_fields=4)
    contract = exchange_contract(*args, wire_dtype="int8")
    assert sorted(contract.axes) == ["gx", "gz"]
    assert all(v["permutes"] == 2 and v["dtypes"] == ("s8",)
               for v in contract.axes.values())
    ir = _compiled_exchange(args, wire="int8")  # optimized HLO
    _assert_honors(ir, contract)
    assert len(ir.permutes) == 4
    assert all(ir.payload_of(p).dtype == "s8" for p in ir.permutes)
    # byte accounting: >= 3.5x below f32 at 4 fields (the EQuARX-style
    # 3.75x target region; scales cost 4B per slab against 4x on cells)
    exact = exchange_contract(*args)
    for axis in ("gx", "gz"):
        ratio = (exact.axes[axis]["wire_bytes"]
                 / contract.axes[axis]["wire_bytes"])
        assert ratio >= 3.5, (axis, ratio)
    # int4: same pair count, halved payload again (>= 7x total)
    c4 = exchange_contract(*args, wire_dtype="int4")
    _assert_honors(_compiled_exchange(args, wire="int4"), c4)
    for axis in ("gx", "gz"):
        assert (exact.axes[axis]["wire_bytes"]
                / c4.axes[axis]["wire_bytes"]) >= 7.0


@pytest.mark.quant
def test_quantized_wire_per_axis_policy_contract():
    """Per-axis policy proven at the HLO level: one compiled 2-axis
    program under ``wire_dtype="z:int8,x:f32"`` carries EXACT f32
    payloads on the x axis and packed s8 payloads on the z axis, honors
    the plan's per-axis bytes, and the per-axis-aware wire-downcast lint
    agrees (full-width x payloads are legal under the mixed policy — the
    pre-policy global check would have flagged them)."""
    from implicitglobalgrid_tpu.analysis import (
        default_lint_config, measure_axes, run_lints,
    )
    from implicitglobalgrid_tpu.analysis.contracts import axis_routes

    igg.init_global_grid(8, 8, 8, dimx=2, dimy=1, dimz=4,
                         periodx=1, periodz=1, quiet=True)
    args = _exchange_args((2, 1, 4), (8, 8, 8), n_fields=2)
    contract = exchange_contract(*args, wire_dtype="z:int8,x:f32")
    assert contract.axes["gx"]["dtypes"] == ("f32",)
    assert contract.axes["gz"]["dtypes"] == ("s8",)
    ir = _compiled_exchange(args, wire="z:int8,x:f32")
    _assert_honors(ir, contract)
    by_axis = measure_axes(ir, axis_routes())
    assert by_axis["gx"]["dtypes"] == ("f32",)
    assert by_axis["gz"]["dtypes"] == ("s8",)
    # exact x bytes == the full-precision plan's; quantized z bytes <<
    exact = exchange_contract(*args)
    assert (contract.axes["gx"]["wire_bytes"]
            == exact.axes["gx"]["wire_bytes"])
    assert (contract.axes["gz"]["wire_bytes"] * 3.5
            <= exact.axes["gz"]["wire_bytes"])
    # lint: mixed program clean under the mixed policy; an all-exact
    # program still flags (z narrowing missing); and the quantized
    # program is clean under a UNIFORM int8 policy too (s8 payloads are
    # never stale — integer widths are legal under any wider policy)
    cfg = default_lint_config(state_dtypes=("f32",),
                              wire_dtype="z:int8,x:f32")
    assert run_lints(ir, config=cfg,
                     rules=("wire-downcast-missing",)) == []
    ir_off = _compiled_exchange(args)
    stale = run_lints(ir_off, config=cfg,
                      rules=("wire-downcast-missing",))
    assert [f.rule for f in stale] == ["wire-downcast-missing"]


def test_no_full_array_copies_around_permutes():
    """The permutes must ride on SLAB-sized operands — a full-array-shaped
    payload feeding a collective-permute means XLA failed to fuse the slab
    slicing (the whole point of the design). `exchange_contract` bounds
    every permute payload strictly below the local block."""
    igg.init_global_grid(16, 16, 16, dimx=2, dimy=2, dimz=2,
                         periodx=1, periody=1, periodz=1, quiet=True)
    args = _exchange_args((2, 2, 2), (16, 16, 16))
    contract = exchange_contract(*args)
    assert contract.max_payload_cells == 16 ** 3
    ir = _compiled_exchange(args)
    _assert_honors(ir, contract)
    assert len(ir.permutes) == 6  # the slab audit actually saw permutes


def test_slab_audit_is_dtype_generic():
    """REGRESSION (ISSUE 7 satellite): the old `_assert_slab_sized_permutes`
    only recognized ``f32[...]`` shapes, so bf16 wire payloads and f64
    fields were invisible to the slab check. The contract bound is
    dtype-blind: a block-sized bf16 or f64 permute payload must FAIL."""
    block = "bf16[8,8,8]", "f64[8,8,8]", "f32[8,8,8]"
    for shape in block:
        text = f"""HloModule synthetic_{shape.split('[')[0]}

ENTRY %main (p0: {shape}) -> {shape} {{
  %p0 = {shape} parameter(0)
  ROOT %cp = {shape} collective-permute(%p0), source_target_pairs={{{{0,1}},{{1,0}}}}
}}
"""
        ir = parse_program(text)
        findings = check_contract(
            ir, CollectiveContract(max_payload_cells=8 ** 3))
        assert [f.rule for f in findings] == ["permute-payload"], shape
        # ... while a genuinely slab-sized payload of the same dtype passes
        slab = shape.replace("[8,8,8]", "[1,8,8]")
        ok_text = text.replace(shape, slab)
        assert check_contract(parse_program(ok_text),
                              CollectiveContract(max_payload_cells=8 ** 3)) \
            == []


def test_live_bf16_and_f64_payloads_are_slab_audited():
    """The live counterpart: a bf16-wire exchange (lowered module) and an
    f64-field exchange (optimized module) both carry non-f32 payloads, and
    the contract's slab bound demonstrably COVERS them — tighten the bound
    below the actual slab size and the same programs fail."""
    igg.init_global_grid(8, 8, 8, dimx=8, dimy=1, dimz=1,
                         periodx=1, quiet=True)
    cases = [
        (_exchange_args((8, 1, 1), (8, 8, 8), dtypes=[np.float64]),
         dict(optimized=True), "f64"),
        (_exchange_args((8, 1, 1), (8, 8, 8)),
         dict(wire="bfloat16", optimized=False), "bf16"),
    ]
    for args, build, dtype in cases:
        ir = _compiled_exchange(args, **build)
        assert {ir.payload_of(p).dtype for p in ir.permutes} == {dtype}
        _assert_honors(ir, exchange_contract(
            *args, wire_dtype="bfloat16" if dtype == "bf16" else None))
        too_tight = CollectiveContract(max_payload_cells=1)
        bad = check_contract(ir, too_tight)
        assert {f.rule for f in bad} == {"permute-payload"}, dtype
        assert len(bad) == len(ir.permutes)


def _compiled_step_ir(impl, ndim=3):
    """`ProgramIR` of the optimized model step program (the fused Pallas
    step+exchange in interpret mode on the CPU mesh, or the XLA step)."""
    from implicitglobalgrid_tpu.models import (
        init_diffusion2d, init_diffusion3d, make_step,
    )

    if ndim == 3:
        T, Cp, p = init_diffusion3d(dtype=np.float32)
    else:
        T, Cp, p = init_diffusion2d(dtype=np.float32)
    fn = make_step(p, ndim=ndim, impl=impl)
    return parse_program(fn, T, Cp)


def _fused_contract(local_shape, n_permutes):
    """Structural pin of a fused program's permute count: slab bound,
    forbidden reductions/gathers, and route legality from the subsystem
    (the full byte-exact plan contracts are checked by the audit_model
    tests below — since the fused tier rides the canonical wire schema,
    those are REAL `model_contract`s, not a per-field carve-out)."""
    from implicitglobalgrid_tpu.analysis import axis_routes

    return CollectiveContract(
        routes=axis_routes(), allreduces=0,
        max_payload_cells=int(np.prod(local_shape)),
        meta={"n_permutes": n_permutes})


def _assert_fused(ir, local_shape, n_permutes):
    _assert_honors(ir, _fused_contract(local_shape, n_permutes))
    assert len(ir.permutes) == n_permutes
    assert not ir.all_reduces and not ir.all_gathers and not ir.all_to_alls


def test_fused_step_exchange_one_permute_pair_per_axis():
    """The FUSED Pallas step+exchange (`diffusion3d_step_exchange_pallas`)
    must keep the exchange's wire shape: one slab-sized permute pair per
    exchanging axis (6 on a 2x2x2 periodic mesh) riding legal axis routes,
    no full-array collective operands, no hidden reductions — the perf
    claim of `pallas_stencil.py`'s module comment, audited at the HLO
    level like the reference's wire-level request assertions
    (`test_update_halo.jl:925-970`)."""
    import jax

    from implicitglobalgrid_tpu.ops.pallas_stencil import step_exchange_modes

    igg.init_global_grid(8, 8, 16, dimx=2, dimy=2, dimz=2,
                         periodx=1, periody=1, periodz=1, quiet=True)
    gg = igg.global_grid()
    assert step_exchange_modes(
        gg, jax.ShapeDtypeStruct((8, 8, 16), np.float32)) \
        == (True, True, True)
    _assert_fused(_compiled_step_ir("pallas_interpret"), (8, 8, 16), 6)


def test_fused_step_exchange_mixed_mesh_permutes():
    """Mixed self/multi-shard mesh (self x + PROC_NULL y + periodic z):
    only the two ppermute axes emit collectives -> 4 permutes, slab-sized."""
    igg.init_global_grid(8, 8, 16, dimx=1, dimy=2, dimz=4,
                         periodx=1, periody=0, periodz=1, quiet=True)
    _assert_fused(_compiled_step_ir("pallas_interpret"), (8, 8, 16), 4)


def test_fused_step_exchange_self_z_permutes():
    """2x2x1 periodic (what `dims_create(4)` gives): z is a self-neighbor
    axis folded into the kernel and the send slabs, so only x and y emit
    collectives — 4 permutes, one pair per axis, each one (1, ny, nz) or
    (nx, 1, nz) slab, none on z — and no op makes or takes a z slab over
    the x-y extent."""
    from implicitglobalgrid_tpu.analysis import axis_routes, measure_axes

    igg.init_global_grid(8, 8, 16, dimx=2, dimy=2, dimz=1,
                         periodx=1, periody=1, periodz=1, quiet=True)
    ir = _compiled_step_ir("pallas_interpret")
    _assert_fused(ir, (8, 8, 16), 4)
    axes = measure_axes(ir, axis_routes())
    assert {a: r["permutes"] for a, r in axes.items()} == {"gx": 2, "gy": 2}
    assert {ir.payload_of(op).cells for op in ir.permutes} == {8 * 16}
    z_slabs = [o.line for o in ir.find(dtype="f32")
               if any(s.dims[:2] == (8, 8) and s.dims[2:] < (4,)
                      for s in o.shapes + o.operand_shapes
                      if len(s.dims) == 3)]
    assert not z_slabs, z_slabs[:3]


def test_fused_stokes_self_z_permutes():
    """The fused PT Stokes pass on 2x2x1 periodic: z is a self-neighbor
    axis folded into the kernel and the x/y send slabs
    (`stokes_exchange_folds_z`), so the 4 exchanged fields ride one packed
    permute pair per crossing axis — 4 permutes on gx/gy, byte-exact to the
    plan — and no op makes or takes a z slab over the x-y extent (the
    mini-state windows and the z recvs of P, Vx, Vy and Vz)."""
    from implicitglobalgrid_tpu.analysis import (
        axis_routes, measure_axes, model_contract,
    )
    from implicitglobalgrid_tpu.models import init_stokes3d, make_stokes_run

    igg.init_global_grid(8, 8, 16, dimx=2, dimy=2, dimz=1,
                         periodx=1, periody=1, periodz=1, quiet=True)
    state, p = init_stokes3d(dtype=np.float32)
    ir = parse_program(make_stokes_run(p, 1, impl="pallas_interpret"),
                       *state)
    _assert_fused(ir, (8, 8, 16), 4)
    _assert_honors(ir, model_contract("stokes3d", state, impl="pallas"))
    axes = measure_axes(ir, axis_routes())
    assert {a: r["permutes"] for a, r in axes.items()} == {"gx": 2, "gy": 2}
    xy = {(8, 8), (9, 8), (8, 9)}
    z_slabs = [o.line for o in ir.find(dtype="f32")
               if any(s.dims[:2] in xy and s.dims[2:] < (5,)
                      for s in o.shapes + o.operand_shapes
                      if len(s.dims) == 3)]
    assert not z_slabs, z_slabs[:3]


def test_fused_step_all_self_emits_no_collectives():
    """All-self mesh: the fused step (multi-plane kernel + in-kernel halo
    fusion) must emit NO collectives at all."""
    igg.init_global_grid(16, 16, 16, dimx=1, dimy=1, dimz=1,
                         periodx=1, periody=1, periodz=1, quiet=True)
    _assert_fused(_compiled_step_ir("pallas_interpret"), (16, 16, 16), 0)


def test_fused_step_2d_permutes():
    """2-D fused strip kernel on a 2x2 periodic mesh: 4 slab-sized
    permutes (one pair per axis)."""
    igg.init_global_grid(16, 16, 1, dimx=2, dimy=2, dimz=1,
                         periodx=1, periody=1, quiet=True)
    _assert_fused(_compiled_step_ir("pallas_interpret", ndim=2),
                  (16, 16), 4)


def test_fused_acoustic_permutes():
    """Fused acoustic pass on a 2x2x2 periodic mesh: all 4 fields ride the
    canonical PACKED wire (one ppermute pair per mesh axis for the whole
    round — `exchange_recv_slabs_multi`) = 6 slab-sized permutes, down
    from the pre-schema per-field 24, byte-exact to the fused-round
    contract."""
    from implicitglobalgrid_tpu.analysis import model_contract
    from implicitglobalgrid_tpu.models import (
        init_acoustic3d, make_acoustic_run,
    )

    igg.init_global_grid(8, 8, 16, dimx=2, dimy=2, dimz=2,
                         periodx=1, periody=1, periodz=1, quiet=True)
    state, p = init_acoustic3d(dtype=np.float32)
    fn = make_acoustic_run(p, 1, impl="pallas_interpret")
    ir = parse_program(fn, *state)
    _assert_fused(ir, (8, 8, 16), 6)
    contract = model_contract("acoustic3d", state, impl="pallas")
    assert all(v["permutes"] == 2 for v in contract.axes.values())
    _assert_honors(ir, contract)


def test_fused_stokes_permutes():
    """Fused Stokes pass on a 2x2x2 periodic mesh: the 4 EXCHANGED fields
    (Pn, Vx, Vy, Vz) pack into one ppermute pair per mesh axis = 6
    slab-sized permutes (pre-schema: 24 per-field) — the dV fields must
    not add wire traffic, and the payload is byte-exact to the plan."""
    from implicitglobalgrid_tpu.analysis import model_contract
    from implicitglobalgrid_tpu.models import init_stokes3d, make_stokes_run

    igg.init_global_grid(8, 8, 16, dimx=2, dimy=2, dimz=2,
                         periodx=1, periody=1, periodz=1, quiet=True)
    state, p = init_stokes3d(dtype=np.float32)
    fn = make_stokes_run(p, 1, impl="pallas_interpret")
    ir = parse_program(fn, *state)
    _assert_fused(ir, (8, 8, 16), 6)
    _assert_honors(ir, model_contract("stokes3d", state, impl="pallas"))


@pytest.mark.slow
def test_fused_acoustic_all_self_no_collectives():
    """The all-self fast path (single shard, periodic everywhere) must
    emit NO collectives: deliveries are in-plane selects / raw source
    slabs inside the kernel (`pallas_common.self_deliver`).

    `slow`: the all-self-mesh claim keeps
    `test_fused_step_all_self_emits_no_collectives` (diffusion) as its
    fast tier-1 representative; these per-family variants ride the slow
    tier (tier-1 wall-time budget, see ROADMAP)."""
    from implicitglobalgrid_tpu.models import (
        init_acoustic3d, make_acoustic_run,
    )

    igg.init_global_grid(8, 8, 16, dimx=1, dimy=1, dimz=1,
                         periodx=1, periody=1, periodz=1, quiet=True)
    state, p = init_acoustic3d(dtype=np.float32)
    fn = make_acoustic_run(p, 1, impl="pallas_interpret")
    _assert_fused(parse_program(fn, *state), (8, 8, 16), 0)


@pytest.mark.slow
def test_fused_stokes_all_self_no_collectives():
    from implicitglobalgrid_tpu.models import init_stokes3d, make_stokes_run

    igg.init_global_grid(8, 8, 16, dimx=1, dimy=1, dimz=1,
                         periodx=1, periody=1, periodz=1, quiet=True)
    state, p = init_stokes3d(dtype=np.float32)
    fn = make_stokes_run(p, 1, impl="pallas_interpret")
    _assert_fused(parse_program(fn, *state), (8, 8, 16), 0)


def test_overlap_interior_independent_of_permutes():
    """THE structural overlap claim (`ops/overlap.py`): in the lowered
    `hide_communication` step, the interior-update compute must have NO
    SSA path to or from any collective-permute — that independence is
    what lets the latency-hiding scheduler run the interior under the
    collectives on TPU (a single-chip trace can never verify this; the
    round-3 verdict asked for exactly this regression test). Also asserts
    the `optimization_barrier` guarding the stitch is present — without
    it, XLA fuses the (independent) interior INTO the permute-dependent
    stitch fusion and serializes it after the collectives (observed on
    the CPU backend, whose pipeline also strips the barrier before
    fusion, which is why this asserts on the lowered module rather than
    backend-optimized HLO). Runs on `ProgramIR.closure`, the def-use
    graph the parser builds for either dialect."""
    import jax
    from jax.sharding import PartitionSpec as P

    from implicitglobalgrid_tpu.models import init_diffusion3d
    from implicitglobalgrid_tpu.ops.overlap import hide_communication
    from implicitglobalgrid_tpu.ops.stencil import (
        d_xa, d_xi, d_ya, d_yi, d_za, d_zi, inn,
    )

    igg.init_global_grid(16, 16, 16, dimx=2, dimy=2, dimz=2,
                         periodx=1, periody=1, periodz=1, quiet=True)
    gg = igg.global_grid()
    T, Cp, p = init_diffusion3d(dtype=np.float32)

    def up(T, Cp):
        qx = -p.lam * d_xi(T) / p.dx
        qy = -p.lam * d_yi(T) / p.dy
        qz = -p.lam * d_zi(T) / p.dz
        dT = (-d_xa(qx) / p.dx - d_ya(qy) / p.dy - d_za(qz) / p.dz) / inn(Cp)
        return T.at[1:-1, 1:-1, 1:-1].add(p.dt * dT)

    spec = P("gx", "gy", "gz")
    fn = jax.jit(shard_map(
        lambda t, c: hide_communication(up, t, c, radius=1),
        mesh=gg.mesh, in_specs=(spec, spec), out_specs=spec))
    ir = parse_program(fn, T, Cp, optimized=False)

    permutes = ir.permutes
    assert len(permutes) == 6  # one pair per exchanging axis
    barriers = ir.find("optimization-barrier")
    assert barriers, (
        "no optimization_barrier around the stitch — TPU fusion is free "
        "to merge the interior compute into the permute-dependent stitch")
    tainted = ir.closure(permutes, "up") | ir.closure(permutes, "down") \
        | set(permutes)

    # interior-update compute: arithmetic over the interior-sized block
    # (16^3 local, ol=2 each side -> 12^3), independent of every permute
    def interior_sized(op):
        return any(s.dtype == "f32" and s.dims == (12, 12, 12)
                   for s in op.shapes)

    interior_ops = {"add", "multiply", "subtract", "divide", "select",
                    "dynamic-update-slice"}
    independent_interior = [
        op for op in ir.ops
        if op.op in interior_ops and interior_sized(op)
        and op not in tainted]
    assert independent_interior, (
        "no interior-sized compute is independent of the collective-"
        "permutes — the interior was serialized with the exchange "
        "(overlap structurally impossible)")
    # and the barrier consumes the independent interior result (any op
    # kind — the final crop is a `slice`): an interior-sized operand with
    # no path to/from the permutes
    barrier_feeds = [
        prod for b in barriers for name in b.operands
        if (prod := ir.resolve(b.computation, name)) is not None]
    assert any(interior_sized(prod) and prod not in tainted
               for prod in barrier_feeds), (
        "optimization_barrier does not guard the interior result")


def _assert_interior_first(ir, min_cells, n_permutes):
    """Structural interior-first claim on a LOWERED step program: the
    expected permute count, an optimization_barrier guarding the stitch,
    and interior-scale f32 compute with NO SSA path to or from any
    collective-permute (`ProgramIR.closure`)."""
    permutes = ir.permutes
    assert len(permutes) == n_permutes
    assert ir.find("optimization-barrier"), (
        "no optimization_barrier around the stitch — fusion is free to "
        "serialize the interior after the collectives")
    tainted = ir.closure(permutes, "up") | ir.closure(permutes, "down") \
        | set(permutes)
    interior_ops = {"add", "multiply", "subtract", "divide", "select",
                    "dynamic-update-slice"}

    def big(op):
        return any(s.dtype == "f32" and s.dims
                   and int(np.prod(s.dims)) >= min_cells
                   for s in op.shapes)

    independent = [op for op in ir.ops
                   if op.op in interior_ops and big(op)
                   and op not in tainted]
    assert independent, (
        "no interior-scale compute is independent of the collective-"
        "permutes — the interior-first shape degraded to a serialized "
        "exchange")


def test_overlap_interior_first_acoustic_multi_field():
    """The MULTI-FIELD interior-first round (the acoustic V round: three
    STAGGERED outputs, ONE coalesced ppermute pair per axis) keeps its
    collectives structurally independent of its interior update — the
    live `ProgramIR.closure` check of the ISSUE-11 acceptance. Audited on
    the round in isolation: in the full two-round step, round 2's shell
    legitimately consumes round 1's exchanged halos, so the per-round
    independence is the invariant (diffusion's single-field form is
    audited above; the stokes 7-field single-round form rides the slow
    tier; the golden host-only counterpart is
    tests/data/hlo/overlap_interior_first.stablehlo.txt)."""
    from jax import lax

    from implicitglobalgrid_tpu.models import init_acoustic3d
    from implicitglobalgrid_tpu.models.common import interior_first_step

    igg.init_global_grid(12, 12, 12, dimx=2, dimy=2, dimz=2,
                         periodx=1, periody=1, periodz=1, quiet=True)
    gg = igg.global_grid()
    (Pf, Vx, Vy, Vz), p = init_acoustic3d(dtype=np.float32, overlap=True)

    def dP(A, d):
        n = A.shape[d]
        return (lax.slice_in_dim(A, 1, n, axis=d)
                - lax.slice_in_dim(A, 0, n - 1, axis=d))

    def v_upd(vx, vy, vz, Pc):
        vx = vx.at[1:-1, :, :].add(-p.dt / p.rho * dP(Pc, 0) / p.dx)
        vy = vy.at[:, 1:-1, :].add(-p.dt / p.rho * dP(Pc, 1) / p.dy)
        vz = vz.at[:, :, 1:-1].add(-p.dt / p.rho * dP(Pc, 2) / p.dz)
        return vx, vy, vz

    from jax.sharding import PartitionSpec as P
    import jax

    spec = P("gx", "gy", "gz")
    fn = jax.jit(shard_map(
        lambda vx, vy, vz, Pc: interior_first_step(
            v_upd, (vx, vy, vz), (Pc,), radius=1),
        mesh=gg.mesh, in_specs=(spec,) * 4, out_specs=(spec,) * 3))
    ir = parse_program(fn, Vx, Vy, Vz, Pf, optimized=False)
    # one coalesced 3-field pair per exchanging axis
    _assert_interior_first(ir, min_cells=(12 - 4) ** 3, n_permutes=6)


@pytest.mark.slow
def test_overlap_interior_first_stokes_multi_field():
    """The 7-output / 4-exchanged stokes interior-first iteration: one
    coalesced (Vx, Vy, Vz, Pn) ppermute round per axis, interior PT
    update independent of every permute."""
    from implicitglobalgrid_tpu.models import (
        init_stokes3d, stokes_step_local,
    )

    igg.init_global_grid(12, 12, 12, dimx=2, dimy=2, dimz=2,
                         periodx=1, periody=1, periodz=1, quiet=True)
    gg = igg.global_grid()
    state, p = init_stokes3d(dtype=np.float32, overlap=True)
    from jax.sharding import PartitionSpec as P
    import jax

    spec = P("gx", "gy", "gz")
    fn = jax.jit(shard_map(
        lambda *s: stokes_step_local(s, p, impl="xla"),
        mesh=gg.mesh, in_specs=(spec,) * 8, out_specs=(spec,) * 8))
    ir = parse_program(fn, *state, optimized=False)
    _assert_interior_first(ir, min_cells=(12 - 4) ** 3, n_permutes=6)


def test_guarded_runner_adds_exactly_one_small_allreduce():
    """THE resilient-runtime wire claim: the health guard fused into a
    chunk (`runtime/health.make_guarded_runner`) costs exactly ONE extra
    collective — a tiny all-reduce of the (2*nfields,) stats vector —
    regardless of field count or chunk length, and does not perturb the
    exchange's permute count (`guard_contract`, the same contract
    `run_resilient(audit=True)` checks at compile time)."""
    from implicitglobalgrid_tpu.models import (
        diffusion_step_local, init_diffusion3d,
    )
    from implicitglobalgrid_tpu.models.common import make_state_runner
    from implicitglobalgrid_tpu.runtime.health import make_guarded_runner

    igg.init_global_grid(8, 8, 8, dimx=2, dimy=2, dimz=2,
                         periodx=1, periody=1, periodz=1, quiet=True)
    T, Cp, p = init_diffusion3d(dtype=np.float32)

    def step(s):
        return (diffusion_step_local(s[0], s[1], p, "xla"), s[1])

    for nt_chunk in (1, 4):
        plain = make_state_runner(step, (3, 3), nt_chunk=nt_chunk,
                                  key="hlo_plain")
        guarded = make_guarded_runner(step, (3, 3), nt_chunk=nt_chunk,
                                      key="hlo_guard")
        ir_p = parse_program(plain, T, Cp)
        ir_g = parse_program(guarded, T, Cp)
        # the plain chunk: zero reductions, zero gathers
        _assert_honors(ir_p, CollectiveContract(allreduces=0))
        # the guarded chunk: exactly one f32[4] psum, gathers forbidden,
        # payload checked on EVERY all-reduce present
        _assert_honors(ir_g, guard_contract(2))
        assert len(ir_g.all_reduces) == 1
        assert (len(ir_g.permutes) == len(ir_p.permutes))


def test_run_resilient_audit_leaves_chunk_program_untouched(tmp_path):
    """THE ISSUE-7 wire claim: `run_resilient(audit=True)` audits the
    chunk program at COMPILE time only — trace+lower, no second backend
    compile — so the XLA executable the run dispatches is built exactly
    as without the audit: identical collective counts, identical fetch
    surface (same parameter count, no infeed/outfeed), and the run's
    results are bit-identical. The audit's verdict streams to the flight
    recorder (one ``audit`` event -> `run_report`'s ``"audit"`` section)
    and the ``igg_audit_findings_total`` family."""
    from implicitglobalgrid_tpu.models import (
        diffusion_step_local, init_diffusion3d,
    )
    from implicitglobalgrid_tpu.runtime.health import make_guarded_runner
    from implicitglobalgrid_tpu.telemetry import (
        read_flight_events, run_report, start_flight_recorder,
        stop_flight_recorder,
    )

    igg.init_global_grid(8, 8, 8, dimx=2, dimy=2, dimz=2,
                         periodx=1, periody=1, periodz=1, quiet=True)
    T, Cp, p = init_diffusion3d(dtype=np.float32)

    def step(s):
        return {"T": diffusion_step_local(s["T"], s["Cp"], p, "xla"),
                "Cp": s["Cp"]}

    # reference program, no audit anywhere near it
    def tup_step(s):
        return (diffusion_step_local(s[0], s[1], p, "xla"), s[1])

    ref = make_guarded_runner(tup_step, (3, 3), nt_chunk=2, key="aud_ref")
    ir_ref = parse_program(ref, T, Cp)

    jsonl = tmp_path / "fr.jsonl"
    start_flight_recorder(str(jsonl))
    try:
        st_a, _ = igg.run_resilient(step, {"T": T, "Cp": Cp}, 4,
                                    nt_chunk=2, audit=True)
    finally:
        stop_flight_recorder()
    st_p, _ = igg.run_resilient(step, {"T": T, "Cp": Cp}, 4, nt_chunk=2)
    assert np.array_equal(np.asarray(st_a["T"]), np.asarray(st_p["T"]))

    # the audited run's chunk program == the reference guarded program
    run = make_guarded_runner(tup_step, (3, 3), nt_chunk=2, key="aud_run")
    ir_run = parse_program(run, T, Cp)
    assert len(ir_run.permutes) == len(ir_ref.permutes)
    assert len(ir_run.all_reduces) == len(ir_ref.all_reduces) == 1
    assert not ir_run.all_gathers and not ir_run.all_to_alls
    assert len(ir_run.parameters()) == len(ir_ref.parameters())
    assert ir_run.count("infeed") == ir_run.count("outfeed") == 0

    # verdict reached the flight recorder and the report's audit section
    evs = read_flight_events(str(jsonl))
    audits = [e for e in evs if e.get("kind") == "audit"]
    assert len(audits) == 1 and audits[0]["ok"] \
        and audits[0]["dialect"] == "stablehlo"
    section = run_report(str(jsonl), include_metrics=False)["audit"]
    assert section["programs"] == 1 and section["ok"] is True
    assert section["errors"] == 0 and section["findings"] == []


def test_telemetry_leaves_chunk_program_untouched(tmp_path):
    """THE observability wire claim (ISSUES 3, 5, and 6): telemetry is
    host-side only — building the guarded chunk runner with an ACTIVE
    flight recorder, live metrics registry, RUNNING metrics server, fresh
    driver heartbeats, AND the performance oracle live (a predict_step
    model attached, a PerfWatch drift detector observing boundaries and
    stamping the igg_perf_* gauges) yields a program with identical
    collective counts and an identical fetch surface (same output arity,
    same parameter count) as with everything off. Zero extra collectives,
    zero extra D2H fetches per chunk (cross-process aggregation and the
    cost model are pure host arithmetic — the heartbeat/server/watch are
    the only RUN-time additions)."""
    from implicitglobalgrid_tpu.models import (
        diffusion_step_local, init_diffusion3d,
    )
    from implicitglobalgrid_tpu.runtime.health import make_guarded_runner
    from implicitglobalgrid_tpu.telemetry import (
        PerfWatch, note_heartbeat, predict_step, start_flight_recorder,
        start_metrics_server, stop_flight_recorder, stop_metrics_server,
    )

    igg.init_global_grid(8, 8, 8, dimx=2, dimy=2, dimz=2,
                         periodx=1, periody=1, periodz=1, quiet=True)
    T, Cp, p = init_diffusion3d(dtype=np.float32)

    def step(s):
        return (diffusion_step_local(s[0], s[1], p, "xla"), s[1])

    off = make_guarded_runner(step, (3, 3), nt_chunk=4, key="hlo_tel_off")
    ir_off = parse_program(off, T, Cp)
    start_flight_recorder(str(tmp_path / "fr.jsonl"))
    start_metrics_server(0)
    try:
        note_heartbeat(0)
        pred = predict_step("diffusion3d", (T, Cp))  # host arithmetic only
        watch = PerfWatch(window=8, model_step_s=pred["step_s"])
        for i in range(6):  # live drift detector + igg_perf_* gauges
            watch.observe(chunk=i, step_begin=4 * i, step_end=4 * i + 4,
                          n=4, exec_s=0.01)
        on = make_guarded_runner(step, (3, 3), nt_chunk=4, key="hlo_tel_on")
        ir_on = parse_program(on, T, Cp)
        out_on = on(T, Cp)
        watch.observe(chunk=6, step_begin=24, step_end=28, n=4,
                      exec_s=0.01)
        note_heartbeat(4)
    finally:
        stop_metrics_server()
        stop_flight_recorder()
    out_off = off(T, Cp)

    assert len(ir_on.permutes) == len(ir_off.permutes)
    assert len(ir_on.all_reduces) == len(ir_off.all_reduces) == 1
    assert not ir_on.all_gathers and not ir_on.all_to_alls
    # identical fetch surface: same program inputs and outputs — the
    # driver's one tiny stats fetch stays the ONLY per-chunk D2H
    assert len(ir_on.parameters()) == len(ir_off.parameters())
    for op in ("infeed", "outfeed"):
        assert ir_on.count(op) == ir_off.count(op) == 0
    assert len(out_on) == len(out_off) == 3  # T, Cp, stats vector


def test_tracing_leaves_chunk_program_untouched(tmp_path):
    """THE ISSUE-20 wire claim: distributed tracing is host-side dict
    stamping only — building and running the guarded chunk runner while
    the active flight recorder carries a `TraceContext` (every record
    stamped with the trace id and the job-root parent span) yields a
    program with identical collective counts and an identical fetch
    surface as untraced, and bit-identical outputs. The trace rides the
    JSONL records, never the compiled program."""
    from implicitglobalgrid_tpu.models import (
        diffusion_step_local, init_diffusion3d,
    )
    from implicitglobalgrid_tpu.runtime.health import make_guarded_runner
    from implicitglobalgrid_tpu.telemetry import (
        TraceContext, flight_recorder, read_flight_events,
        start_flight_recorder, stop_flight_recorder,
    )

    igg.init_global_grid(8, 8, 8, dimx=2, dimy=2, dimz=2,
                         periodx=1, periody=1, periodz=1, quiet=True)
    T, Cp, p = init_diffusion3d(dtype=np.float32)

    def step(s):
        return (diffusion_step_local(s[0], s[1], p, "xla"), s[1])

    off = make_guarded_runner(step, (3, 3), nt_chunk=4, key="hlo_tr_off")
    ir_off = parse_program(off, T, Cp)
    out_off = off(T, Cp)

    tr = TraceContext.new().child()  # the job root, as the scheduler sets
    start_flight_recorder(str(tmp_path / "fr.jsonl"))
    try:
        flight_recorder().trace = tr
        igg.record_event("run_begin", nt=4)
        on = make_guarded_runner(step, (3, 3), nt_chunk=4,
                                 key="hlo_tr_on")
        ir_on = parse_program(on, T, Cp)
        out_on = on(T, Cp)
        igg.record_event("chunk", chunk=0, step_begin=0, step_end=4,
                         ok=True, exec_s=0.01)
    finally:
        path = stop_flight_recorder()

    assert len(ir_on.permutes) == len(ir_off.permutes)
    assert len(ir_on.all_reduces) == len(ir_off.all_reduces) == 1
    assert not ir_on.all_gathers and not ir_on.all_to_alls
    assert len(ir_on.parameters()) == len(ir_off.parameters())
    for op in ("infeed", "outfeed"):
        assert ir_on.count(op) == ir_off.count(op) == 0
    for a, b in zip(out_on, out_off):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # ... and the trace really was live across the build + run
    evs = read_flight_events(path)
    stamped = [e for e in evs if e.get("kind") in ("run_begin", "chunk")]
    assert len(stamped) == 2
    assert all(e["trace_id"] == tr.trace_id
               and e["parent_span_id"] == tr.span_id for e in stamped)


def test_live_plane_leaves_chunk_program_untouched(tmp_path):
    """THE ISSUE-18 wire claim: the live observability plane is pure
    host-side tailing — building the guarded chunk runner while a
    flight recorder streams, a `LiveAggregate` incrementally tails the
    same file between chunks, an `AlertEngine` (default rule pack)
    evaluates every snapshot, and an `ObserveServer` answers
    ``/v1/observe`` + ``/v1/events`` over HTTP mid-run yields a program
    with identical collective counts and an identical fetch surface as
    with the plane off. Zero extra collectives, zero extra D2H fetches
    per chunk — the tail reads bytes from disk, never the device."""
    import json as _json
    import urllib.request

    from implicitglobalgrid_tpu.models import (
        diffusion_step_local, init_diffusion3d,
    )
    from implicitglobalgrid_tpu.runtime.health import make_guarded_runner
    from implicitglobalgrid_tpu.serve import ObserveServer
    from implicitglobalgrid_tpu.telemetry import (
        record_event, start_flight_recorder, stop_flight_recorder,
    )
    from implicitglobalgrid_tpu.telemetry.live import (
        AlertEngine, LiveAggregate,
    )

    igg.init_global_grid(8, 8, 8, dimx=2, dimy=2, dimz=2,
                         periodx=1, periody=1, periodz=1, quiet=True)
    T, Cp, p = init_diffusion3d(dtype=np.float32)

    def step(s):
        return (diffusion_step_local(s[0], s[1], p, "xla"), s[1])

    off = make_guarded_runner(step, (3, 3), nt_chunk=4,
                              key="hlo_live_off")
    ir_off = parse_program(off, T, Cp)

    jsonl = tmp_path / "flight_live.jsonl"
    start_flight_recorder(str(jsonl))
    live = LiveAggregate(str(jsonl))
    engine = AlertEngine()  # the default pack, observer-side
    try:
        with ObserveServer(str(tmp_path)) as obs:
            u = f"http://{obs.host}:{obs.port}"
            for i in range(3):  # the plane tails BETWEEN chunks
                record_event("chunk", chunk=i, step_begin=4 * i,
                             step_end=4 * i + 4, n=4, ok=True,
                             exec_s=0.01)
                live.poll()
                engine.evaluate(live.snapshot())
                with urllib.request.urlopen(u + "/v1/observe",
                                            timeout=10) as r:
                    _json.loads(r.read())
            on = make_guarded_runner(step, (3, 3), nt_chunk=4,
                                     key="hlo_live_on")
            ir_on = parse_program(on, T, Cp)
            out_on = on(T, Cp)
            with urllib.request.urlopen(
                    u + "/v1/events?since=-1&timeout_s=0.1",
                    timeout=10) as r:
                lines = [_json.loads(x) for x in r.read().splitlines()]
            assert any(e["kind"] == "chunk" for e in lines)
    finally:
        stop_flight_recorder()
    out_off = off(T, Cp)

    assert len(ir_on.permutes) == len(ir_off.permutes)
    assert len(ir_on.all_reduces) == len(ir_off.all_reduces) == 1
    assert not ir_on.all_gathers and not ir_on.all_to_alls
    # identical fetch surface: same program inputs and outputs
    assert len(ir_on.parameters()) == len(ir_off.parameters())
    for op in ("infeed", "outfeed"):
        assert ir_on.count(op) == ir_off.count(op) == 0
    assert len(out_on) == len(out_off) == 3  # T, Cp, stats vector


def test_reducers_share_the_guard_psum():
    """THE io wire claim (ISSUE 4): an enabled in-situ reducer set adds
    ZERO extra collectives to the chunk program — probe, axis slice and
    global min/max/mean/RMS segments concatenate into the health guard's
    single tiny all-reduce (one psum total, f32[2N + R] — exactly
    `guard_contract(N, R)`, the contract `run_resilient(audit=True)`
    checks), and the exchange's permute count is untouched."""
    from implicitglobalgrid_tpu.io.reducers import (
        AxisSlice, Probe, Stats, build_reducer_plan,
        make_reduced_post_chunk,
    )
    from implicitglobalgrid_tpu.models import (
        diffusion_step_local, init_diffusion3d,
    )
    from implicitglobalgrid_tpu.models.common import make_state_runner
    from implicitglobalgrid_tpu.runtime.health import make_guarded_runner

    igg.init_global_grid(8, 8, 8, dimx=2, dimy=2, dimz=2,
                         periodx=1, periody=1, periodz=1, quiet=True)
    T, Cp, p = init_diffusion3d(dtype=np.float32)

    def step(s):
        return (diffusion_step_local(s[0], s[1], p, "xla"), s[1])

    names = ("T", "Cp")
    reducers = [Probe("T", (0, 0, 0)), AxisSlice("T", 0, (0, 1, 1)),
                Stats("T")]
    plan = build_reducer_plan(reducers, names,
                              {"T": T, "Cp": Cp})
    guarded = make_guarded_runner(step, (3, 3), nt_chunk=2,
                                  key="hlo_io_plain")
    reduced = make_state_runner(
        step, (3, 3), nt_chunk=2, key=("hlo_io_red", plan.signature),
        post_chunk=make_reduced_post_chunk(names, plan))
    ir_g = parse_program(guarded, T, Cp)
    ir_r = parse_program(reduced, T, Cp)
    # the combined stats vector: 2 fields * 2 health entries + probe(1) +
    # slice(12: the implicit global x-size, 2*(8-2) periodic) + stats(2 +
    # 2*8 min/max slots) = 4 + 1 + 12 + 18 = 35 floats
    assert plan.length == 1 + 12 + 2 + 2 * 8
    _assert_honors(ir_g, guard_contract(len(names)))
    _assert_honors(ir_r, guard_contract(len(names), plan.length))
    assert len(ir_r.all_reduces) == len(ir_g.all_reduces) == 1
    assert len(ir_r.permutes) == len(ir_g.permutes)


def test_snapshot_writer_leaves_chunk_program_untouched(tmp_path):
    """Enabling snapshots adds ZERO collectives: with an ACTIVE
    SnapshotWriter (submitting, queue draining) the guarded chunk
    program compiles to identical collective counts and an identical
    fetch surface as with snapshots off — the writer only ever sees the
    host copies `submit` makes at chunk boundaries."""
    from implicitglobalgrid_tpu.io import SnapshotWriter
    from implicitglobalgrid_tpu.models import (
        diffusion_step_local, init_diffusion3d,
    )
    from implicitglobalgrid_tpu.runtime.health import make_guarded_runner

    igg.init_global_grid(8, 8, 8, dimx=2, dimy=2, dimz=2,
                         periodx=1, periody=1, periodz=1, quiet=True)
    T, Cp, p = init_diffusion3d(dtype=np.float32)

    def step(s):
        return (diffusion_step_local(s[0], s[1], p, "xla"), s[1])

    off = make_guarded_runner(step, (3, 3), nt_chunk=2, key="hlo_snap_off")
    ir_off = parse_program(off, T, Cp)
    with SnapshotWriter(tmp_path / "s") as w:
        w.submit({"T": T, "Cp": Cp}, 0)
        on = make_guarded_runner(step, (3, 3), nt_chunk=2,
                                 key="hlo_snap_on")
        ir_on = parse_program(on, T, Cp)
        w.flush(timeout=30.0)
    assert len(ir_on.permutes) == len(ir_off.permutes)
    assert len(ir_on.all_reduces) == len(ir_off.all_reduces) == 1
    assert len(ir_on.parameters()) == len(ir_off.parameters())
    for op in ("infeed", "outfeed"):
        assert ir_on.count(op) == ir_off.count(op) == 0


def test_permute_count_with_halowidth_2():
    """halowidth>1 exchanges still cost one pair per axis (slab width is
    static, not a per-row loop) — byte-audited: the hw=2 slabs carry
    exactly the plan's doubled wire bytes."""
    igg.init_global_grid(12, 12, 12, dimx=2, dimy=2, dimz=2,
                         periodx=1, periody=1, periodz=1,
                         overlaps=(4, 4, 4), halowidths=(2, 2, 2),
                         quiet=True)
    args = _exchange_args((2, 2, 2), (12, 12, 12))
    contract = exchange_contract(*args)
    assert all(v["permutes"] == 2 for v in contract.axes.values())
    _assert_honors(_compiled_exchange(args), contract)


@pytest.mark.parametrize("model,impl", [
    ("diffusion3d", "xla"), ("acoustic3d", "xla"), ("stokes3d", "xla"),
    # the fused tier's fast tier-1 representative: same byte-exact
    # contract + crosscheck, via the canonical wire schema (the per-model
    # fused matrix rides the audit tests above / the slow tier)
    ("diffusion3d", "pallas_interpret"),
])
def test_audit_model_crosschecks_perfmodel(model, impl):
    """ISSUE-7 acceptance (extended to EVERY kernel tier): for each model
    family, the perf oracle's priced ppermute PAIRS and all-links wire
    bytes (`predict_step` over the tier's `StepWorkload.groups_for`
    rounds) EQUAL what the compiler actually emitted, per mesh axis, on
    the CPU mesh — static-model drift is a caught `perfmodel-drift`
    finding, not a silent mispricing. The same call also proves the
    plan-derived contract: slab-sized payloads on legal routes, exact
    per-axis counts, no gathers."""
    igg.init_global_grid(8, 8, 8, dimx=2, dimy=2, dimz=2,
                         periodx=1, periody=1, periodz=1, quiet=True)
    rep = igg.audit_model(model, impl=impl)
    assert rep.ok, [f.to_json() for f in rep.findings]
    cc = rep.crosscheck
    assert cc is not None and cc["ok"]
    assert sorted(cc["axes"]) == ["gx", "gy", "gz"]
    for rec in cc["axes"].values():
        assert rec["modeled_pairs"] == rec["parsed_pairs"] > 0
        assert rec["modeled_wire_bytes"] == rec["parsed_wire_bytes"] > 0


@pytest.mark.slow
def test_audit_model_wire_dtype_self_contained(monkeypatch):
    """`audit_model(wire_dtype=...)` must apply the wire format to BOTH
    sides: the compile (scoped ``IGG_HALO_WIRE_DTYPE`` — the kwarg alone
    must produce a passing audit with nothing exported, and must never
    leak the reduced-precision mode into the process) and the
    expectation (contract payload dtypes, wire bytes, crosscheck
    pricing). On XLA:CPU — which normalizes bf16 payloads back to f32 in
    optimized HLO — the LOWERED module is audited instead, recorded in
    ``meta``, so the documented CLI exit-1 gate cannot false-fail."""
    import os

    monkeypatch.delenv("IGG_HALO_WIRE_DTYPE", raising=False)
    igg.init_global_grid(8, 8, 8, dimx=2, dimy=2, dimz=2,
                         periodx=1, periody=1, periodz=1, quiet=True)
    rep = igg.audit_model("diffusion3d", wire_dtype="bfloat16")
    assert rep.ok, [f.to_json() for f in rep.findings]
    assert rep.crosscheck is not None and rep.crosscheck["ok"]
    assert rep.dialect == "stablehlo"
    assert "lowered_for_wire_audit" in rep.meta
    assert "IGG_HALO_WIRE_DTYPE" not in os.environ


@pytest.mark.slow
def test_audit_model_fused_fallback_contract_follows_xla_rounds():
    """REGRESSION (review finding): on a grid the fused kernel's
    eligibility gate rejects (halowidth != 1 — the deep-halo
    configuration), a Pallas request falls back to the XLA formulation;
    the contract must follow the FALLBACK's rounds (acoustic: V round +
    P round = 2 pairs/axis), not the requested fused grouping (1
    pair/axis) — else `tools audit` exit-1-fails a healthy program, the
    false-failure class the retired exemption existed to prevent."""
    igg.init_global_grid(12, 12, 12, dimx=2, dimy=2, dimz=2,
                         periodx=1, periody=1, periodz=1,
                         overlaps=(4, 4, 4), halowidths=(2, 2, 2),
                         quiet=True)
    rep = igg.audit_model("acoustic3d", impl="pallas_interpret")
    assert rep.ok, [f.to_json() for f in rep.findings]
    assert rep.crosscheck is not None and rep.crosscheck["ok"]
    assert "rounds_impl" in rep.meta  # the fallback was recorded
    # XLA rounds: V round + P round -> 4 permutes per exchanging axis
    assert all(v["permutes"] == 4 for v in rep.contract.axes.values())


@pytest.mark.slow
def test_audit_model_fused_tier_has_real_contract():
    """REGRESSION (reversal of the PR-7 carve-out): `audit_model` on a
    fused Pallas impl used to SKIP the contract and crosscheck
    (`meta["contract_skipped"]`) because the fused kernels exchanged
    per-field in-kernel. The canonical wire schema retired that — the
    fused tier ships the same packed one-pair-per-axis wire the plan
    prices, so a Pallas audit must now carry a REAL byte-exact contract
    AND a passing perfmodel crosscheck, and the `tools audit` exit-1
    gate covers fused programs. (Fast representative:
    test_audit_model_crosschecks_perfmodel's pallas leg.)"""
    igg.init_global_grid(8, 8, 16, dimx=2, dimy=2, dimz=2,
                         periodx=1, periody=1, periodz=1, quiet=True)
    rep = igg.audit_model("acoustic3d", impl="pallas_interpret")
    assert rep.ok, [f.to_json() for f in rep.findings]
    assert rep.contract is not None
    assert rep.crosscheck is not None and rep.crosscheck["ok"]
    assert "contract_skipped" not in rep.meta
    # the fused pass packs all 4 fields into ONE round: 2 permutes/axis
    assert rep.collectives["permutes"] == 6
    assert all(v["permutes"] == 2 for v in rep.contract.axes.values())
