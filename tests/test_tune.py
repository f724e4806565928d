"""Closed-loop auto-tuner tests (ISSUE 13): the search picks the knob
the machine profile says pays, the winning config round-trips through
JSON and the per-job application surface (`RunSpec(tuned=...)` →
`MeshScheduler` admission), and the measured-validation path never
returns a config slower than the default (the baseline is always in the
measured set)."""

import dataclasses
import json
import os

import numpy as np
import pytest

import implicitglobalgrid_tpu as igg
from implicitglobalgrid_tpu.telemetry.tune import (
    TunedConfig, resolve_tuned, tuned_config_path,
)
from implicitglobalgrid_tpu.utils.exceptions import InvalidArgumentError

pytestmark = pytest.mark.tune

_GRID = dict(nx=16, ny=16, nz=16, dimx=2, dimy=2, dimz=2,
             periodx=1, periody=1, periodz=1)


def _hier_profile(z_lat=5e-4):
    """ICI-fast x/y, DCN-slow z — the hierarchical mesh the per-axis
    cadence exists for."""
    return igg.MachineProfile(
        membw_GBps=800.0, flops_G=45000.0,
        axes={"gx": {"GBps": 45.0, "latency_s": 5e-6},
              "gy": {"GBps": 45.0, "latency_s": 5e-6},
              "gz": {"GBps": 2.0, "latency_s": z_lat}})


# ---------------------------------------------------------------------------
# the search
# ---------------------------------------------------------------------------

def test_search_picks_slow_axis_cadence():
    """On the ICI+DCN profile the model-only search must rank the z-only
    cadence ABOVE both the exchange-every-step default and the uniform
    deep cadence (which pays slab-width compute on the fast axes too) —
    the COMM_AVOID.json losing row turned into a win."""
    cfg = igg.tune_config("stokes3d", dict(_GRID), _hier_profile(),
                          measure=False,
                          comm_every_options=("1", "2", "z:2"))
    assert cfg.model == "stokes3d"
    assert cfg.comm_every == "z:2"
    ranked = [r["comm_every"] for r in cfg.meta["ranking"]]
    assert ranked.index("z:2") < ranked.index("2")
    assert ranked.index("z:2") < ranked.index("1")
    assert cfg.predicted_step_s and cfg.predicted_step_s > 0
    assert cfg.meta["priced"] >= 3


def test_search_keeps_default_on_flat_fast_mesh():
    """With negligible latency everywhere, deep halos only cost slab
    compute — the tuner must return the default cadence, not a
    regression."""
    prof = igg.MachineProfile(
        membw_GBps=800.0, flops_G=45000.0,
        axes={a: {"GBps": 100.0, "latency_s": 1e-9}
              for a in ("gx", "gy", "gz")})
    cfg = igg.tune_config("diffusion3d", dict(_GRID), prof,
                          measure=False,
                          comm_every_options=("1", "2", "z:2"))
    assert cfg.comm_every == "1"


def test_search_sweeps_ensemble_and_wire():
    """E rides the search like any other knob (scored PER MEMBER — the
    amortization makes E>1 win on a latency-priced profile), and the
    per-axis wire policy is searchable alongside the cadence."""
    cfg = igg.tune_config(
        "diffusion3d", dict(_GRID), _hier_profile(),
        measure=False, comm_every_options=("1",),
        wire_dtype_options=(None, "z:int8,x:f32"),
        ensemble_options=(None, 8))
    assert cfg.ensemble == 8
    assert cfg.wire_dtype == "z:int8,x:f32"


def test_infeasible_candidates_skipped_loudly():
    """A cadence the geometry cannot carry is a recorded skip, not a
    crash; an all-infeasible space raises."""
    small = dict(_GRID, nx=4, ny=4, nz=4)
    cfg = igg.tune_config("stokes3d", small, _hier_profile(),
                          measure=False,
                          comm_every_options=("1", "z:8"))
    assert cfg.comm_every == "1"
    assert any(s["comm_every"] == "z:8" for s in cfg.meta["skipped"])
    with pytest.raises(InvalidArgumentError, match="infeasible"):
        igg.tune_config("stokes3d", dict(small, nx=2, ny=2, nz=2),
                        _hier_profile(), measure=False,
                        comm_every_options=("z:8",))


def test_tune_preserves_callers_grid():
    """`tune_config` owns its candidate grids but must hand back the
    caller's live grid untouched (epoch retained across the swaps)."""
    igg.init_global_grid(8, 8, 8, dimx=2, dimy=2, dimz=2, quiet=True)
    try:
        epoch = igg.global_grid().epoch
        igg.tune_config("diffusion3d", dict(_GRID), _hier_profile(),
                        measure=False, comm_every_options=("1",))
        assert igg.grid_is_initialized()
        assert igg.global_grid().epoch == epoch
    finally:
        igg.finalize_global_grid()


# ---------------------------------------------------------------------------
# persistence + application
# ---------------------------------------------------------------------------

def test_tuned_config_json_roundtrip(tmp_path):
    cfg = TunedConfig(model="diffusion3d", comm_every="z:2",
                      wire_dtype="z:int8", coalesce=True, overlap=False,
                      ensemble=4, predicted_step_s=1e-3, speedup=1.2)
    path = tuned_config_path(tmp_path / "profile.json", "diffusion3d")
    assert path.endswith("tuned_diffusion3d.json")
    igg.save_tuned_config(cfg, path)
    back = igg.load_tuned_config(path)
    assert back.knobs() == cfg.knobs()
    assert back.env() == {"IGG_COMM_EVERY": "z:2",
                          "IGG_HALO_WIRE_DTYPE": "z:int8",
                          "IGG_HALO_COALESCE": "1"}
    # every accepted RunSpec.tuned form resolves
    assert resolve_tuned(None) is None
    assert resolve_tuned(cfg) is cfg
    assert resolve_tuned(cfg.to_json()).knobs() == cfg.knobs()
    assert resolve_tuned(path).knobs() == cfg.knobs()
    with pytest.raises(InvalidArgumentError):
        resolve_tuned(42)
    with pytest.raises(InvalidArgumentError):
        igg.load_tuned_config(tmp_path / "missing.json")


def test_stored_staged_wire_config_refused(tmp_path):
    """A stored record that selects the removed staged halo wire is
    refused, not run flat: that would not be the tuned configuration."""
    rec = TunedConfig(model="diffusion3d", comm_every="z:2").to_json()
    rec["wire_stage"] = "z:staged"
    path = tmp_path / "tuned_diffusion3d.json"
    path.write_text(json.dumps(rec))
    for form in (rec, path, str(path)):
        with pytest.raises(InvalidArgumentError, match="staged halo wire"):
            resolve_tuned(form)
    # a flat record (null wire_stage, as older tuners wrote it) still loads
    rec["wire_stage"] = None
    assert resolve_tuned(rec).comm_every == "z:2"


def test_tune_runspec_scheduler_roundtrip(tmp_path):
    """ISSUE 13 acceptance: tune_config → persisted TunedConfig →
    `RunSpec(tuned=path)` → `MeshScheduler` load-and-apply on admission.
    The tuned job runs the deep super-step on the tuned geometry, the
    scheduler journals ``job_tuned``, the driver records the ``tuned``
    flight event, and the result is bit-identical to the solo deep
    run."""
    from implicitglobalgrid_tpu.models import init_diffusion3d, \
        run_diffusion
    from implicitglobalgrid_tpu.service import JobSpec, MeshScheduler, \
        builtin_setup

    path = os.path.join(tmp_path, "tuned_diffusion3d.json")
    cfg = igg.tune_config("diffusion3d",
                          dict(_GRID, nx=12, ny=12, nz=12),
                          _hier_profile(), measure=False,
                          comm_every_options=("1", "z:2"), path=path)
    assert cfg.comm_every == "z:2" and os.path.exists(path)
    grid_kw = dict(cfg.grid["winner"])

    # the reference trajectory: solo deep run of the same knobs
    igg.init_global_grid(**grid_kw)
    try:
        T, Cp, p = init_diffusion3d(dtype=np.float32, comm_every="z:2")
        ref = np.asarray(run_diffusion(T, Cp, p, 4, nt_chunk=2))
    finally:
        igg.finalize_global_grid()

    flight = os.path.join(tmp_path, "flight")
    with MeshScheduler(flight_dir=flight) as sched:
        sched.submit(JobSpec(
            name="tuned", setup=builtin_setup("diffusion3d", tuned=path),
            nt=2,  # super-steps: 2 cycles x cycle 2 = 4 physical steps
            grid=grid_kw,
            run=igg.RunSpec(nt_chunk=1, key=("tuned-rt",), tuned=path)))
        sched.run()
        job = sched.job("tuned")
        assert job.state == "done", job.error
        assert np.array_equal(np.asarray(job.result["T"]), ref)
    journal = [json.loads(line) for line in
               open(os.path.join(flight, "scheduler.jsonl"))]
    tuned_ev = [e for e in journal if e.get("kind") == "job_tuned"]
    assert tuned_ev and tuned_ev[0]["comm_every"] == "z:2"
    flight_ev = [json.loads(line) for line in
                 open(os.path.join(flight, "job_tuned.jsonl"))]
    assert any(e.get("kind") == "tuned" for e in flight_ev)


def test_builtin_setup_rejects_model_mismatch(tmp_path):
    from implicitglobalgrid_tpu.service import builtin_setup

    cfg = TunedConfig(model="stokes3d", comm_every="z:2")
    with pytest.raises(InvalidArgumentError, match="refusing"):
        builtin_setup("diffusion3d", tuned=cfg)


def test_tuned_ensemble_fills_runspec(tmp_path):
    """A tuned ensemble becomes the job's batch size when the RunSpec
    left it unset — the scheduler's `ResilientRun` then vmaps the chunk
    and the per-member guard surface engages."""
    from implicitglobalgrid_tpu.service import JobSpec, MeshScheduler, \
        builtin_setup

    cfg = TunedConfig(model="diffusion3d", comm_every="1", ensemble=2)
    with MeshScheduler() as sched:
        sched.submit(JobSpec(
            name="batched",
            setup=builtin_setup("diffusion3d", tuned=cfg),
            nt=2, grid=dict(nx=8, ny=8, nz=8, dimx=2, dimy=2, dimz=2),
            run=igg.RunSpec(nt_chunk=2, key=("tuned-ens",), tuned=cfg)))
        sched.run()
        job = sched.job("batched")
        assert job.state == "done", job.error
        assert job.run.ensemble == 2
        assert int(job.result["T"].shape[0]) == 2


@pytest.mark.slow
def test_measured_tune_never_regresses(tmp_path):
    """The measured path: baseline (all defaults) is always in the
    measured set, so the returned speedup is >= 1.0 by construction and
    the winner's measured step time is the set's minimum."""
    cfg = igg.tune_config(
        "diffusion3d", dict(_GRID, nx=12, ny=12, nz=12), None,
        measure=True, top_k=2, comm_every_options=("1", "2", "z:2"),
        path=os.path.join(tmp_path, "tuned.json"))
    assert cfg.measured_step_s is not None
    assert cfg.baseline_step_s is not None
    assert cfg.speedup >= 1.0
    assert cfg.meta["measured"] >= 2


@pytest.mark.slow
def test_tune_cli_smoke(tmp_path):
    """`tools tune` produce + show round-trip in a subprocess (the
    operator surface)."""
    import subprocess
    import sys

    out = os.path.join(tmp_path, "tuned_diffusion3d.json")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-m", "implicitglobalgrid_tpu.tools", "tune",
         "diffusion3d", "--cpu", "--nx", "12", "--no-measure",
         "--comm-every-options", "1;z:2", "--out", out],
        capture_output=True, text=True, env=env, timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.loads(r.stdout)
    assert rec["model"] == "diffusion3d"
    assert os.path.exists(out)
    r2 = subprocess.run(
        [sys.executable, "-m", "implicitglobalgrid_tpu.tools", "tune",
         "show", out],
        capture_output=True, text=True, env=env, timeout=120,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert r2.returncode == 0 and json.loads(r2.stdout)["model"] \
        == "diffusion3d"
