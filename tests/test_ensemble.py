"""Ensemble-axis tests (ISSUE 12): batch E scenario members through one
mesh with collective counts flat in E.

THE claim under test: `make_state_runner(ensemble=E)` vmaps the member
axis over the chunk program, and jax's collective batching turns each
per-member collective into ONE op with an E x payload — so the compiled
exchange keeps exactly its solo permute count (byte-exact E-scaled
payloads, proven against the plan-derived contract), the health guard's
psum stays a single all-reduce of ``f32[E·(2N+R)]``, and each member's
trajectory is bit-identical to its solo run. Tier-1 keeps ONE fast
representative per behavior; E x policy sweeps ride the slow tier
(ROADMAP tier-1 wall-time budget). The per-member fault-isolation
representative lives in tests/test_resilience.py.
"""

import os

import numpy as np
import pytest

import implicitglobalgrid_tpu as igg
from implicitglobalgrid_tpu.utils.exceptions import InvalidArgumentError

pytestmark = pytest.mark.ensemble

_DATA = os.path.join(os.path.dirname(__file__), "data", "hlo")


def _diffusion(dtype=np.float32):
    from implicitglobalgrid_tpu.models import init_diffusion3d

    return init_diffusion3d(dtype=dtype)


# ---------------------------------------------------------------------------
# state construction + validation (no compiles)
# ---------------------------------------------------------------------------

def test_ensemble_state_layout_and_validation():
    """`ensemble_state` stacks a new leading member axis (replicated over
    the mesh — P(None, gx, gy, gz)), applies the deterministic perturb
    ramp with member 0 unperturbed, and every entry layer rejects
    ill-formed ensemble requests loudly."""
    from jax.sharding import PartitionSpec as P

    from implicitglobalgrid_tpu.models import ensemble_state, run_diffusion
    from implicitglobalgrid_tpu.models.common import (
        ensemble_partition_spec, make_state_runner, resolve_ensemble_impl,
    )

    igg.init_global_grid(6, 6, 6, dimx=2, dimy=2, dimz=1, quiet=True)
    T, Cp, p = _diffusion()
    E = 3
    ET = ensemble_state(T, E, perturb=0.5)
    assert tuple(ET.shape) == (E,) + tuple(T.shape)
    assert ET.sharding.spec == P(None, "gx", "gy", "gz")
    assert ensemble_partition_spec(2) == P(None, "gx", "gy")
    h = np.asarray(ET)
    base = np.asarray(T)
    assert np.array_equal(h[0], base)                    # member 0 = base
    assert np.allclose(h[2], base * 2.0, rtol=1e-6)      # 1 + 0.5*2
    # dict/tuple containers preserved
    d = ensemble_state({"T": T, "Cp": Cp}, E)
    assert set(d) == {"T", "Cp"} and d["T"].shape[0] == E
    # rejections: E < 1, Pallas impl, non-stacked state, bad leading dim
    with pytest.raises(InvalidArgumentError, match=">= 1"):
        ensemble_state(T, 0)
    with pytest.raises(InvalidArgumentError, match=">= 1"):
        make_state_runner(lambda s: s, (3,), nt_chunk=1, ensemble=0)
    with pytest.raises(InvalidArgumentError, match="XLA tier"):
        resolve_ensemble_impl("pallas")
    with pytest.raises(InvalidArgumentError, match="member axis"):
        run_diffusion(T, Cp, p, 2, ensemble=4)
    with pytest.raises(InvalidArgumentError, match="ensemble_state"):
        igg.run_resilient(lambda s: s, {"T": T}, 2, ensemble=4)
    # ProcessLoss under ensemble is ACCEPTED since ISSUE 14: the elastic
    # redistribution passes the member axis through untouched (the
    # end-to-end restart rides tests/test_reshard.py) — validation-level,
    # the machine constructs cleanly with the fault queued
    from implicitglobalgrid_tpu.runtime.driver import ResilientRun

    run = ResilientRun(lambda s: s, {"T": ET}, 4, igg.RunSpec(
        ensemble=E, faults=(igg.ProcessLoss(step=2, new_dims=(1, 2, 2)),)))
    run.close()


# ---------------------------------------------------------------------------
# THE tentpole: compiled collective count flat in E, byte-exact payloads
# ---------------------------------------------------------------------------

def test_ensemble_collectives_flat_in_E_byte_exact():
    """`audit_model(ensemble=8)` compiles the 8-member batched diffusion
    chunk and proves, on the OPTIMIZED program: identical per-axis
    permute counts to solo, payloads byte-exactly 8 x the solo plan
    (contract check), and the perf oracle's ensemble pricing equal to
    what the compiler emitted (crosscheck) — collective count flat in E,
    machine-verified end to end."""
    igg.init_global_grid(6, 6, 6, dimx=2, dimy=2, dimz=1, quiet=True)
    rep1 = igg.audit_model("diffusion3d")
    rep8 = igg.audit_model("diffusion3d", ensemble=8)
    assert rep8.ok, [f.to_json() for f in rep8.findings]
    assert rep8.meta["ensemble"] == 8
    # flat in E: exactly the solo collective inventory
    assert rep8.collectives["permutes"] == rep1.collectives["permutes"]
    assert rep8.collectives["all_reduces"] == 0
    assert rep8.collectives["all_gathers"] == 0
    # byte-exact: 8x the solo wire, per axis and in total
    assert rep8.collectives["wire_bytes"] \
        == 8 * rep1.collectives["wire_bytes"] > 0
    for axis, exp in rep8.contract.axes.items():
        assert exp["permutes"] == rep1.contract.axes[axis]["permutes"]
        assert exp["wire_bytes"] \
            == 8 * rep1.contract.axes[axis]["wire_bytes"]
    cc = rep8.crosscheck
    assert cc is not None and cc["ok"] and cc["ensemble"] == 8
    for rec in cc["axes"].values():
        assert rec["modeled_pairs"] == rec["parsed_pairs"] > 0
        assert rec["modeled_wire_bytes"] == rec["parsed_wire_bytes"] > 0


def test_ensemble_guarded_chunk_single_batched_psum():
    """The guarded ensemble chunk still carries exactly ONE all-reduce —
    the batched ``f32[E·2N]`` stats — and its permute count equals the
    solo guarded chunk's (`guard_contract(..., ensemble=E)`, the same
    contract `run_resilient(audit=True)` checks for batched runs)."""
    from implicitglobalgrid_tpu.analysis import guard_contract, parse_program
    from implicitglobalgrid_tpu.models import (
        diffusion_step_local, ensemble_state,
    )
    from implicitglobalgrid_tpu.runtime.health import make_guarded_runner

    igg.init_global_grid(6, 6, 6, dimx=2, dimy=2, dimz=1, quiet=True)
    T, Cp, p = _diffusion()

    def step(s):
        return (diffusion_step_local(s[0], s[1], p, "xla"), s[1])

    E = 3
    solo = make_guarded_runner(step, (3, 3), nt_chunk=2, key="ens_g1")
    ens = make_guarded_runner(step, (3, 3), nt_chunk=2, key="ens_g3",
                              ensemble=E)
    ir_solo = parse_program(solo, T, Cp)
    ir_ens = parse_program(ens, ensemble_state(T, E), ensemble_state(Cp, E))
    assert len(ir_ens.permutes) == len(ir_solo.permutes)
    assert len(ir_ens.all_reduces) == 1
    pay = ir_ens.payload_of(ir_ens.all_reduces[0])
    assert pay.dtype == "f32" and pay.cells == E * 4
    findings = igg.check_contract(ir_ens, guard_contract(2, ensemble=E))
    assert findings == [], [f.to_json() for f in findings]


def test_ensemble_member_trajectories_bit_identical_to_solo():
    """Member 0 of a perturbed 4-member batch (perturb ramp leaves member
    0 at the base state) ends BIT-IDENTICAL to the solo run of the same
    steps, and perturbed members genuinely diverge — the vmapped chunk
    changes the economics, never the numerics."""
    from implicitglobalgrid_tpu.models import ensemble_state, run_diffusion

    igg.init_global_grid(6, 6, 6, dimx=2, dimy=2, dimz=1, quiet=True)
    T, Cp, p = _diffusion(np.float64)
    E = 4
    ET = ensemble_state(T, E, perturb=0.01)
    ECp = ensemble_state(Cp, E)
    out = run_diffusion(ET, ECp, p, 6, nt_chunk=3, ensemble=E)
    ref = run_diffusion(T, Cp, p, 6, nt_chunk=3)
    h = np.asarray(out)
    assert tuple(out.shape) == (E,) + tuple(T.shape)
    assert np.array_equal(h[0], np.asarray(ref))
    assert not np.array_equal(h[1], h[0])


def test_ensemble_2d_checkpoint_roundtrip(tmp_path):
    """REGRESSION (review finding): restore used a rank heuristic that
    cannot tell a 2-D ensemble ``(E, x, y)`` from a solo 3-D field, so it
    sharded the member axis over ``gx`` and every wanted block key missed
    the saved set. The save now records each array's leading replicated
    (member) axes and restore rebuilds the TRUE sharding — round-trip
    bit-exact through both the plain and the elastic (same-dims
    delegation) paths; elastic onto DIFFERENT dims re-blocks the batched
    state too (ISSUE 14: the member axis passes through untouched)."""
    from jax.sharding import PartitionSpec as P

    from implicitglobalgrid_tpu.models import ensemble_state

    igg.init_global_grid(6, 6, 1, dimx=4, dimy=2, dimz=1, quiet=True)
    T = igg.ones_g((6, 6), np.float32)
    E = 3
    ET = ensemble_state(T, E, perturb=0.5)
    d = str(tmp_path / "ck2d")
    igg.save_checkpoint_sharded(d, {"T": ET}, step=5)
    st, step = igg.restore_checkpoint_sharded(d)
    assert step == 5
    assert np.array_equal(np.asarray(st["T"]), np.asarray(ET))
    assert st["T"].sharding.spec == P(None, "gx", "gy")
    st2, _ = igg.restore_checkpoint_elastic(d)  # same-dims delegation
    assert np.array_equal(np.asarray(st2["T"]), np.asarray(ET))
    # a DIFFERENT decomposition of the same implicit global grid
    # (18, 10): the batched state re-blocks with the member axis passed
    # through — T is a per-member constant ramp, so every restored
    # member must be exactly its constant
    igg.finalize_global_grid()
    igg.init_global_grid(10, 4, 1, dimx=2, dimy=4, dimz=1, quiet=True)
    st3, _ = igg.restore_checkpoint_elastic(d)
    got = np.asarray(st3["T"])
    assert got.shape == (E, 20, 16)
    assert st3["T"].sharding.spec == P(None, "gx", "gy")
    for m in range(E):
        assert np.array_equal(
            got[m], np.full((20, 16), np.float32(1 + 0.5 * m)))


# ---------------------------------------------------------------------------
# quantized wire: per-member scale slabs (ISSUE 12 x ISSUE 9)
# ---------------------------------------------------------------------------

@pytest.mark.quant
def test_ensemble_quantized_wire_per_member_scales_roundtrip():
    """The quantized ensemble wire keeps PER-(member, slab) scales in the
    same scales-in-band layout: each member of the vmapped int8 exchange
    receives halos BIT-IDENTICAL to its own solo int8 exchange (the
    member's slabs quantize against the member's own max-abs scales —
    batching cannot launder one member's range into another's), and the
    plan prices E x the quantized payload including E x the scale tails
    behind the SAME pair count."""
    import jax
    import jax.numpy as jnp

    from implicitglobalgrid_tpu.models.common import (
        ensemble_partition_spec, ensemble_state,
    )
    from implicitglobalgrid_tpu.ops import halo as halo_mod
    from implicitglobalgrid_tpu.ops.precision import resolve_wire_dtype
    from jax import shard_map

    igg.init_global_grid(4, 8, 8, dimx=8, dimy=1, dimz=1, periodx=1,
                         quiet=True)
    gg = igg.global_grid()
    E = 3
    rng = np.random.default_rng(7)
    A = igg.device_put_g(rng.normal(size=(32, 8, 8)).astype(np.float32))
    B = igg.device_put_g(rng.normal(size=(32, 8, 8)).astype(np.float32))
    wire = resolve_wire_dtype("int8")

    def exchange(*arrays):
        return tuple(halo_mod._exchange_arrays(
            gg, list(arrays), [gg.halowidths] * 2,
            halo_mod._normalize_dims_order(None), coalesce=True,
            wire=wire))

    espec = (ensemble_partition_spec(3),) * 2
    fn = jax.jit(shard_map(jax.vmap(exchange), mesh=gg.mesh,
                           in_specs=espec, out_specs=espec))
    # distinct member magnitudes: the per-member scales MUST differ
    EA = ensemble_state(A, E, perturb=10.0)
    EB = ensemble_state(B, E, perturb=10.0)
    out_a, out_b = fn(EA, EB)
    for m in range(E):
        solo_a, solo_b = igg.update_halo(
            jnp.asarray(EA[m]), jnp.asarray(EB[m]), wire_dtype="int8")
        assert np.array_equal(np.asarray(out_a)[m], np.asarray(solo_a)), m
        assert np.array_equal(np.asarray(out_b)[m], np.asarray(solo_b)), m
    # static pricing: same pairs, E x quantized bytes (scale tails incl.)
    solo_plan = igg.halo_comm_plan(A, B, wire_dtype="int8")
    ens_plan = igg.halo_comm_plan(A, B, wire_dtype="int8", ensemble=E)
    assert ens_plan["ppermutes"] == solo_plan["ppermutes"]
    assert ens_plan["wire_bytes"] == E * solo_plan["wire_bytes"]


# ---------------------------------------------------------------------------
# golden fixture (capture of the compiled ensemble exchange)
# ---------------------------------------------------------------------------

def test_ensemble_golden_fixture_honors_live_contract():
    """The checked-in optimized HLO of the E=4 two-field coalesced
    exchange (8-shard periodic ring) honors the LIVE plan-derived
    ensemble contract byte-exactly: one permute pair whose payloads are
    the member-batched ``f32[4,2,8,8]`` slabs — 4 x the solo bytes behind
    the solo pair count. Parser-level assertions on the same fixture live
    in tests/test_analysis.py (host-only, no grid)."""
    import jax

    from implicitglobalgrid_tpu.analysis import (
        check_contract, exchange_contract, parse_text,
    )

    with open(os.path.join(_DATA, "exchange_ensemble_coalesced.hlo.txt"),
              encoding="utf-8") as f:
        ir = parse_text(f.read())
    igg.init_global_grid(4, 8, 8, dimx=8, dimy=1, dimz=1, periodx=1,
                         quiet=True)
    args = [jax.ShapeDtypeStruct((32, 8, 8), np.float32),
            jax.ShapeDtypeStruct((32, 8, 8), np.float32)]
    contract = exchange_contract(*args, ensemble=4)
    assert contract.meta["ensemble"] == 4
    findings = check_contract(ir, contract)
    assert findings == [], [f.to_json() for f in findings]
    assert len(ir.permutes) == 2
    assert {ir.payload_of(p).dims for p in ir.permutes} == {(4, 2, 8, 8)}
    solo = exchange_contract(*args)
    assert contract.axes["gx"]["permutes"] == solo.axes["gx"]["permutes"]
    assert contract.axes["gx"]["wire_bytes"] \
        == 4 * solo.axes["gx"]["wire_bytes"]


# ---------------------------------------------------------------------------
# the service serves batched jobs (PR 8 rung d)
# ---------------------------------------------------------------------------

@pytest.mark.service
def test_scheduler_serves_batched_job(tmp_path):
    """An ensemble `JobSpec` (builtin_setup(ensemble=2) + RunSpec
    (ensemble=2)) runs to DONE under the scheduler: the result leads with
    the member axis, per-chunk reports carry member indices, and the
    job's scoped registry exposes per-member gauges
    (igg_member_rms{job=...,member=...})."""
    from implicitglobalgrid_tpu.service import JobSpec, MeshScheduler
    from implicitglobalgrid_tpu.service.job import builtin_setup

    E = 2
    spec = JobSpec(
        name="batched", setup=builtin_setup("diffusion3d", ensemble=E,
                                            perturb=0.1),
        nt=4, grid=dict(nx=6, ny=6, nz=6, dimx=2, dimy=2, dimz=1),
        run=igg.RunSpec(nt_chunk=2, key="ens_job", ensemble=E))
    with MeshScheduler(flight_dir=str(tmp_path)) as sched:
        sched.submit(spec)
        sched.run()
        job = sched.job("batched")
        assert job.state == "done", job.error
        assert tuple(job.result["T"].shape)[0] == E
        assert {r.member for r in job.reports} == {0, 1}
        fam = igg.metrics_registry().get("igg_job_member_rms")
        assert fam is not None
        labels = {(l.get("job"), l.get("member"), l.get("field"))
                  for l, _ in fam.samples()}
        assert ("batched", "0", "T") in labels
        assert ("batched", "1", "T") in labels


# ---------------------------------------------------------------------------
# slow tier: E x policy sweeps, CLI, other model families
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_ensemble_flat_for_acoustic_and_stokes():
    """E-sweep across the other model families: the multi-field acoustic
    leapfrog (two exchange rounds) and the 8-field Stokes PT iteration
    keep their solo per-axis permute counts at E=4 with byte-exact
    4 x payloads (the fast diffusion representative runs in tier-1)."""
    igg.init_global_grid(8, 8, 16, dimx=2, dimy=2, dimz=2,
                         periodx=1, periody=1, periodz=1, quiet=True)
    for model in ("acoustic3d", "stokes3d"):
        rep1 = igg.audit_model(model)
        rep4 = igg.audit_model(model, ensemble=4)
        assert rep4.ok, (model, [f.to_json() for f in rep4.findings])
        assert rep4.collectives["permutes"] == rep1.collectives["permutes"]
        assert rep4.collectives["wire_bytes"] \
            == 4 * rep1.collectives["wire_bytes"]
        assert rep4.crosscheck["ok"]


@pytest.mark.slow
def test_tools_audit_ensemble_cli():
    """`tools audit diffusion3d --ensemble 8 --cpu` exits 0 with a
    passing byte-exact contract + crosscheck (the operator-facing gate
    of the flat-in-E claim)."""
    import subprocess
    import sys

    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "implicitglobalgrid_tpu.tools", "audit",
         "diffusion3d", "--ensemble", "8", "--cpu", "--nx", "8", "--json"],
        capture_output=True, text=True, timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    import json

    rep = json.loads(proc.stdout)
    assert rep["ok"] and rep["programs"][0]["meta"]["ensemble"] == 8


@pytest.mark.slow
def test_ensemble_predict_step_amortization_fields():
    """`predict_step(ensemble=E)` exposes the priced amortization the
    auto-tuner searches over: per_member_* fields, the solo anchor, and
    a ratio that IMPROVES with E in a latency-visible regime."""
    igg.init_global_grid(6, 6, 6, dimx=2, dimy=2, dimz=1, quiet=True)
    T, Cp, _ = _diffusion()
    ratios = []
    for E in (2, 8, 16):
        pred = igg.predict_step("diffusion3d", (T, Cp), ensemble=E)
        assert pred["ensemble"] == E
        assert pred["per_member_step_s"] == pytest.approx(
            pred["step_s"] / E)
        assert pred["solo_step_s"] > 0
        ratios.append(pred["ensemble_amortization"])
    assert ratios[0] > ratios[1] > ratios[2]  # amortization grows with E
    solo = igg.predict_step("diffusion3d", (T, Cp))
    assert "per_member_step_s" not in solo and solo["ensemble"] == 1
