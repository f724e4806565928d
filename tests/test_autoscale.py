"""Closed-loop autoscaler tests (ISSUE 19): the mesh resizes itself.

The acceptance bar is the DRILL: a queue-pressured high-priority job is
grown and an idle one shrunk with NO operator input, every resize
preceded by a journaled ``autoscale_decision`` whose priced break-even
is satisfied, the post-resize re-tune recorded, all tenants BIT-
IDENTICAL to their solo (no-autoscale) reference runs, and the decision
chain reconstructable from the journal alone (`explain_autoscale` /
``tools autoscale explain``). The thrash test proves hysteresis: a
bounced signal files NOTHING.

Budget note (ROADMAP tier-1): one end-to-end drill is the fast
representative; everything else here is host-only dict arithmetic.
"""

import json
import os
import re

import numpy as np
import pytest

import implicitglobalgrid_tpu as igg
from implicitglobalgrid_tpu.service import (
    Autoscaler, AutoscalePolicy, FairSharePolicy, Job, JobSpec,
    MeshScheduler, ScaleBounds, builtin_setup, explain_autoscale,
    service_report,
)
from implicitglobalgrid_tpu.utils.exceptions import InvalidArgumentError

pytestmark = pytest.mark.service

# hot: compute-dominated single-device grid with room to grow (glob span
# 64 per axis re-blocks evenly at dims 1/2/4); idle: small grid spread
# over 4 devices it does not need
GRID_HOT = dict(nx=66, ny=66, nz=66, dimx=1, dimy=1, dimz=1,
                overlaps=(2, 2, 2))
GRID_IDLE = dict(nx=18, ny=18, nz=18, dimx=2, dimy=2, dimz=1,
                 overlaps=(2, 2, 2))


def _signals(slack, *, pending=0, name="hot", devices=1, priority=2):
    """A `MeshScheduler._live_signals`-shaped synthetic snapshot."""
    return {"jobs": {name: {"state": "running",
                            "deadline_slack_s": slack,
                            "priority": priority, "devices": devices}},
            "queue": {"pending": pending, "queued": 0}}


class _StubSched:
    """The minimal scheduler surface the policy engine touches —
    journal sink, job table, queue backend."""

    def __init__(self):
        self.jobs = {}
        self.queue = None
        self.events = []

    def _log(self, kind, **fields):
        self.events.append(dict(kind=kind, **fields))


# ---------------------------------------------------------------------------
# Public API / validation (host-only)
# ---------------------------------------------------------------------------

def test_public_api_exports():
    from implicitglobalgrid_tpu import service

    for sym in ("Autoscaler", "AutoscalePolicy", "ScaleBounds",
                "explain_autoscale"):
        assert hasattr(service, sym), sym
        assert sym in service.__all__, sym


def test_policy_and_bounds_validation():
    with pytest.raises(InvalidArgumentError, match="min_devices"):
        ScaleBounds(min_devices=0)
    with pytest.raises(InvalidArgumentError, match="max_devices"):
        ScaleBounds(min_devices=4, max_devices=2)
    with pytest.raises(InvalidArgumentError, match="via"):
        AutoscalePolicy(via="sideways")
    with pytest.raises(InvalidArgumentError, match="hysteresis"):
        AutoscalePolicy(hysteresis_slices=0)
    with pytest.raises(InvalidArgumentError, match="cooldown"):
        AutoscalePolicy(cooldown_slices=-1)
    with pytest.raises(InvalidArgumentError, match="ScaleBounds"):
        AutoscalePolicy(bounds={"j": (1, 2)})
    with pytest.raises(InvalidArgumentError, match="AutoscalePolicy"):
        Autoscaler(42)
    # kwargs-dict form (the MeshScheduler(autoscale={...}) path) and the
    # JSON policy echo both round-trip
    a = Autoscaler({"grow_slack_s": 5.0,
                    "bounds": {"hot": ScaleBounds(2, 6)}})
    echo = a.policy.describe()
    assert json.loads(json.dumps(echo))["bounds"]["hot"] == {
        "min_devices": 2, "max_devices": 6}
    assert a.policy.bounds_for("other") == ScaleBounds()


def test_scheduler_rejects_bogus_autoscale_arg(tmp_path):
    with pytest.raises(InvalidArgumentError, match="autoscale"):
        MeshScheduler(flight_dir=str(tmp_path), autoscale=123)


def test_fair_share_slack_boost_reprioritizes():
    """Satellite: `fair` spends mesh time where deadline pressure is —
    BEFORE the alert engine's hard cancel — via a slack-weighted stride
    boost, smoothly and reversibly (`granted` accounting untouched)."""
    import types

    pol = FairSharePolicy(low_slack_s=10.0, slack_boost=4.0,
                          slack_horizon_s=20.0)
    jobs = []
    for i, slack in enumerate([None, 25.0, -15.0]):
        spec = JobSpec(name=f"j{i}", setup=lambda: None, nt=10)
        j = Job(spec, i)
        j.run = types.SimpleNamespace(deadline_slack_s=slack)
        jobs.append(j)
    # equal shares: only the starved job (slack -15 < 10) boosts; its
    # deficit 25s saturates the 20s horizon -> full 1 + 4.0 stride
    for j in jobs:
        pol.granted(j, 8.0)
    assert pol._boost(jobs[0]) == 1.0      # no deadline: plain fair share
    assert pol._boost(jobs[1]) == 1.0      # comfortable slack
    assert pol._boost(jobs[2]) == 5.0      # saturated boost
    assert pol.pick(jobs) is jobs[2]
    # recovery is reversible: slack back above the bar, boost gone
    jobs[2].run.deadline_slack_s = 11.0
    assert pol._boost(jobs[2]) == 1.0
    assert pol.pick(jobs) is jobs[0]
    with pytest.raises(InvalidArgumentError, match="slack_boost"):
        FairSharePolicy(slack_boost=-1)
    with pytest.raises(InvalidArgumentError, match="slack_horizon_s"):
        FairSharePolicy(slack_horizon_s=0)


# ---------------------------------------------------------------------------
# Hysteresis / cooldown (synthetic signals, host-only)
# ---------------------------------------------------------------------------

def test_bounced_signal_never_files_thrash_proof():
    """An oscillating starvation signal (slack dips below the bar on
    alternate boundaries) NEVER matures past hysteresis: zero moves
    filed, every rejection is ``hysteresis`` — the mesh cannot thrash."""
    a = Autoscaler(AutoscalePolicy(grow_slack_s=0.0, hysteresis_slices=3))
    reasons = []
    for i in range(12):
        slack = -1.0 if i % 2 == 0 else 1.0
        for d in a.evaluate(_signals(slack)):
            reasons.append((d["verdict"], d["reason"]))
    assert reasons and set(reasons) == {("rejected", "hysteresis")}
    assert a.moves_filed == 0
    assert a.evaluations == 12
    assert a.decision_s_total > 0 and a.last_decision_s >= 0


def test_constant_pressure_matures_and_journal_dedups():
    """A PERSISTENT signal matures exactly at ``hysteresis_slices``
    consecutive votes; repeated identical rejections collapse to one
    journal record while the counters count every verdict."""
    from implicitglobalgrid_tpu.telemetry import hooks

    reg = igg.metrics_registry()
    reg.reset(hooks.AUTOSCALE_DECISIONS)
    reg.reset(hooks.AUTOSCALE_REJECTED)
    sched = _StubSched()
    a = Autoscaler(AutoscalePolicy(grow_slack_s=0.0, hysteresis_slices=2),
                   scheduler=sched)
    verdicts = []
    for _ in range(5):
        for d in a.evaluate(_signals(-1.0)):
            verdicts.append(d["reason"])
    # boundary 1 rejects on hysteresis; 2..5 mature but find no live job
    # in the (empty) stub table — the plan stage WAS reached
    assert verdicts == ["hysteresis"] + ["no_live_job"] * 4
    journaled = [e for e in sched.events
                 if e["kind"] == "autoscale_decision"]
    assert [e["reason"] for e in journaled] == ["hysteresis",
                                                "no_live_job"]
    fam = reg.get(hooks.AUTOSCALE_DECISIONS)
    assert fam.value(action="grow", verdict="rejected") == 5.0
    rej = reg.get(hooks.AUTOSCALE_REJECTED)
    assert rej.value(reason="hysteresis") == 1.0
    assert rej.value(reason="no_live_job") == 4.0


def test_vote_reset_on_non_consecutive_boundary():
    """The hysteresis contract is CONSECUTIVE boundaries: a healthy
    boundary between two starved ones resets the streak."""
    a = Autoscaler(AutoscalePolicy(grow_slack_s=0.0, hysteresis_slices=2))
    assert a.evaluate(_signals(-1.0))[0]["streak"] == 1
    assert a.evaluate(_signals(5.0)) == []          # vote did not repeat
    assert a.evaluate(_signals(-1.0))[0]["streak"] == 1  # back to one


# ---------------------------------------------------------------------------
# The drill: end-to-end closed loop (tier-1 fast representative)
# ---------------------------------------------------------------------------

def _drill_job(name, grid, *, priority=1, deadline_s=None):
    return JobSpec(name=name, setup=builtin_setup("diffusion3d"),
                   model="diffusion3d", nt=60, grid=grid,
                   run=igg.RunSpec(nt_chunk=5, key=("autoscale", name)),
                   priority=priority, deadline_s=deadline_s)


def _interior(sched, name):
    from implicitglobalgrid_tpu.parallel import topology as top

    job = sched.job(name)
    prev = top.swap_global_grid(job.gg)
    try:
        return igg.gather_interior(job.result["T"])
    finally:
        top.swap_global_grid(prev)


def _solo_interior(tmp_path, name, grid, **spec_kw):
    """The job's gathered interior from a NO-autoscale scheduler run —
    the bit-identity reference."""
    d = str(tmp_path / f"solo_{name}")
    with MeshScheduler(policy="fair", flight_dir=d) as sched:
        sched.submit(_drill_job(name, grid, **spec_kw))
        sched.run()
        assert sched.job(name).state == "done"
        return _interior(sched, name)


def test_autoscale_drill_grow_shrink_explainable_bit_identical(tmp_path):
    """THE ISSUE-19 acceptance drill. Two tenants on one 8-device pool:
    ``hot`` (high priority, deadline, one device, compute-dominated) and
    ``idle`` (no deadline, 4 devices it does not need). With
    ``grow_slack_s`` above any live slack, every boundary votes grow-hot
    / shrink-idle; the policy must grow hot to its 4-device cap and
    shrink idle to one device with no operator input — every resize
    preceded by a journaled, PRICED decision, re-tuned after applying,
    both results bit-identical to their solo no-autoscale runs, and the
    whole story reconstructable from the journal alone."""
    from implicitglobalgrid_tpu.telemetry import hooks

    reg = igg.metrics_registry()
    for fam in (hooks.AUTOSCALE_DECISIONS, hooks.AUTOSCALE_RESIZES,
                hooks.AUTOSCALE_REJECTED, hooks.JOB_TARGET_DEVICES):
        reg.reset(fam)
    ref_hot = _solo_interior(tmp_path, "hot", GRID_HOT, priority=2,
                             deadline_s=120.0)
    ref_idle = _solo_interior(tmp_path, "idle", GRID_IDLE)

    d = str(tmp_path / "svc")
    pol = AutoscalePolicy(grow_slack_s=1e9,  # any live slack = starved
                          shrink_queue_pending=1, hysteresis_slices=2,
                          cooldown_slices=2,
                          bounds={"hot": ScaleBounds(1, 4),
                                  "idle": ScaleBounds(1, 8)})
    with MeshScheduler(policy="fair", flight_dir=d,
                       autoscale=pol) as sched:
        sched.submit(_drill_job("hot", GRID_HOT, priority=2,
                                deadline_s=120.0))
        sched.submit(_drill_job("idle", GRID_IDLE))
        sched.run()
        hot, idle = sched.job("hot"), sched.job("idle")
        assert (hot.state, hot.error) == ("done", None)
        assert (idle.state, idle.error) == ("done", None)
        # the loop converged with no operator input
        assert tuple(int(x) for x in hot.gg.dims) == (4, 1, 1)
        assert tuple(int(x) for x in idle.gg.dims) == (1, 1, 1)
        # bit-identity: the resizes were exact re-blockings and the
        # re-tuned knobs are bit-exact transport knobs
        np.testing.assert_array_equal(_interior(sched, "hot"), ref_hot)
        np.testing.assert_array_equal(_interior(sched, "idle"), ref_idle)
        # per-job target gauge tracks the final allocation (scoped
        # series retire when the scheduler closes — read them live)
        tgt = reg.get(hooks.JOB_TARGET_DEVICES)
        assert tgt.value(job="hot") == 4.0
        assert tgt.value(job="idle") == 1.0

    # -- explainability: the journal alone reconstructs the WHY --------
    rec = explain_autoscale(d)
    assert rec["policy"]["grow_slack_s"] == 1e9
    assert rec["filed"] >= 4 and rec["decisions"] > rec["filed"]
    assert rec["rejected_by_reason"].get("hysteresis", 0) >= 1
    applied = [m for m in rec["moves"] if m["applied"]]
    assert {(m["job"], m["action"]) for m in applied} >= {
        ("hot", "grow"), ("idle", "shrink")}
    full_chain = ["autoscale_decision", "control", "resize_requested",
                  "job_resized", "job_retuned"]
    for m in applied:
        # actuation went through the public control path and re-tuned
        assert m["chain"] == full_chain, m
        be = m["pricing"]["break_even"]
        if m["action"] == "grow":
            # a grow files only when priced break-even lands inside the
            # job's remaining horizon
            assert be["within_horizon"] is True
            assert be["break_even_steps"] <= be["nt_remaining"]
        assert m["pricing"]["new_dims"] == m["new_dims"]
        assert m["signals"]["queue"] is not None
    # every applied resize traces back to a filed decision: no private
    # path into the mesh
    events = [json.loads(line) for line in
              open(os.path.join(d, "scheduler.jsonl"))]
    resized = [e for e in events if e.get("kind") == "job_resized"]
    assert len(resized) == len(applied)
    # every applied resize re-tuned (plus possibly extra perf-drift
    # re-tunes — the stale-config path now re-tunes instead of clearing)
    retuned = [e for e in events if e.get("kind") == "job_retuned"]
    assert len([e for e in retuned if e["reason"] == "resize"]) \
        == len(applied)
    assert all("predicted_step_s" in e for e in retuned)

    # -- the report folds the same story -------------------------------
    rep = service_report(d, include_jobs=False)
    assert rep["autoscale"]["filed"] == rec["filed"]
    assert rep["jobs"]["hot"]["resizes"] >= 1
    assert rep["jobs"]["idle"]["resizes"] >= 1

    # -- counters track the journal ------------------------------------
    fam = reg.get(hooks.AUTOSCALE_DECISIONS)
    # the counters count EVERY verdict; the journal collapses repeated
    # identical rejections — so the family can only run ahead of it
    assert sum(v for _, v in fam.samples()) >= rec["decisions"]
    assert fam.value(action="grow", verdict="filed") >= 1
    assert fam.value(action="shrink", verdict="filed") >= 1
    assert reg.get(hooks.AUTOSCALE_RESIZES).value() == rec["filed"]


def _program_only(hlo: str) -> str:
    """Compiled HLO text without its source locations: the stack-frame
    tables after the module header and every op's ``metadata={...}`` name
    the tracing call's source line, which differs between two calls by
    construction."""
    lines = hlo.splitlines()
    body = next(i for i, ln in enumerate(lines)
                if ln.startswith(("%", "ENTRY")))
    return re.sub(r",? metadata=\{[^}]*\}", "",
                  "\n".join(lines[:1] + lines[body:]))


def test_autoscale_drill_hlo_untouched(tmp_path):
    """HLO audit: the chunk program a geometry compiles to is identical
    before and after the autoscaler has priced, filed, and re-tuned
    moves in the same process — the policy engine lives entirely outside
    the compiled artifact."""
    import jax
    from jax.sharding import PartitionSpec as P

    from implicitglobalgrid_tpu.models import (
        diffusion_step_local, init_diffusion3d,
    )
    from implicitglobalgrid_tpu.parallel.topology import AXIS_NAMES
    from jax import shard_map

    def _hlo():
        igg.init_global_grid(quiet=True, nx=18, ny=18, nz=18,
                             dimx=2, dimy=2, dimz=1, overlaps=(2, 2, 2))
        try:
            from implicitglobalgrid_tpu.parallel.topology import (
                global_grid,
            )

            gg = global_grid()
            T, Cp, p = init_diffusion3d(dtype=np.float32)
            spec = P(*AXIS_NAMES)

            def run(T, Cp):
                return diffusion_step_local(T, Cp, p, "xla")

            fn = jax.jit(shard_map(run, mesh=gg.mesh,
                                   in_specs=(spec, spec),
                                   out_specs=spec))
            return _program_only(fn.lower(T, Cp).compile().as_text())
        finally:
            igg.finalize_global_grid()

    before = _hlo()
    d = str(tmp_path / "svc")
    pol = AutoscalePolicy(grow_slack_s=1e9, shrink_queue_pending=0,
                          hysteresis_slices=1, cooldown_slices=0,
                          bounds={"idle": ScaleBounds(1, 8)})
    with MeshScheduler(policy="fair", flight_dir=d,
                       autoscale=pol) as sched:
        sched.submit(_drill_job("idle", GRID_IDLE))
        sched.run()
    assert explain_autoscale(d)["decisions"] > 0  # the policy DID run
    assert _hlo() == before
