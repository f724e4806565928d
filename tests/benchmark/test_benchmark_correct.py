"""The check that decides `correct`, driven through a whole run on the CPU at
a size a test holds (the look for a chip skipped): sound runs pass and the
lower-precision control fails; with the timed path broken underneath, each
fault the cells can have makes `correct` false.

Besides the cells of BENCHMARK.json, the acoustic adapter
(`benchmark/models/acoustic3d.py`, staggered fields) and the PT Stokes
adapter (`benchmark/models/stokes3d.py`, 8 fields on three staggerings, on
one shard and on a 2x2x1 mesh) are driven through the same run, under
configurations of this test's own: neither has a cell yet (PERF.md, Open
questions). Stokes' damped momentum ``dV`` is never exchanged, so its
configuration compares it on owned entries only (``"compare": "owned"``);
the tests below pin what that region keeps and what it gives up."""

import copy
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import harness, spec  # noqa: E402

ACOUSTIC, STOKES, STOKES_2X2 = "acoustic3d", "stokes3d", "stokes3d-2x2"
CELLS = ("diffusion3d-256.supervised", "diffusion3d-256.supervised-2x2",
         ACOUSTIC, STOKES, STOKES_2X2)
LOCAL_N = (10, 9, 8)
ACOUSTIC_CONFIG = {
    "name": ACOUSTIC, "model": ACOUSTIC, "local_n": list(LOCAL_N),
    "periodic": True, "dtype": "float32", "nt": None,
    "extent": [10.0, 10.0, 10.0], "rho": 1.0, "K": 1.0, "amp": 1.0,
    "fields": {"P": {"role": "updated", "stagger": [0, 0, 0]},
               "Vx": {"role": "updated", "stagger": [1, 0, 0]},
               "Vy": {"role": "updated", "stagger": [0, 1, 0]},
               "Vz": {"role": "updated", "stagger": [0, 0, 1]}},
    "limits": {"max_rel_err": 1e-2}}
_DV_WHY = ("damped momentum, a rate of change of V that the PT iteration "
           "drives towards zero: updated on each shard's interior faces and "
           "never exchanged, its halo entries dead; its error is read by "
           "the change dt_v * dV makes to V")


def _dv(stagger, v):
    return {"role": "updated", "stagger": stagger, "compare": "owned",
            "scale": {"field": v, "over": "dt_v"}, "why": _DV_WHY}


STOKES_CONFIG = {
    "name": STOKES, "model": STOKES, "local_n": list(LOCAL_N),
    "periodic": True, "dtype": "float32", "nt": None,
    "extent": [10.0, 10.0, 10.0], "mu": 1.0, "amp": 1.0,
    "fields": {"P": {"role": "updated", "stagger": [0, 0, 0]},
               "Vx": {"role": "updated", "stagger": [1, 0, 0]},
               "Vy": {"role": "updated", "stagger": [0, 1, 0]},
               "Vz": {"role": "updated", "stagger": [0, 0, 1]},
               "dVx": _dv([1, 0, 0], "Vx"), "dVy": _dv([0, 1, 0], "Vy"),
               "dVz": _dv([0, 0, 1], "Vz"),
               "rhog": {"role": "read", "stagger": [0, 0, 0]}},
    "limits": {"max_rel_err": 1e-4}}
# test-only cells: the BENCHMARK.json cell whose traffic and chips each
# takes, and its configuration
OWN_CELLS = {ACOUSTIC: ("diffusion3d-256.supervised", ACOUSTIC_CONFIG),
             STOKES: ("diffusion3d-256.supervised", STOKES_CONFIG),
             STOKES_2X2: ("diffusion3d-256.supervised-2x2", STOKES_CONFIG)}


@pytest.fixture(autouse=True)
def _short_runs(monkeypatch):
    monkeypatch.setattr(harness, "WARMUP_CHUNKS", 1)
    monkeypatch.setattr(harness, "SAMPLE_FROM_FIRST", 4)


def _cell(name, **traffic):
    if name in OWN_CELLS:
        base, config = OWN_CELLS[name]
        cell = spec.load_cell(base)
        cell.name, cell.config = name, copy.deepcopy(config)
        cell.model = spec.load_module(
            ROOT / f"benchmark/models/{config['model']}.py",
            f"bench_test_{config['model']}")
    else:
        cell = spec.load_cell(name)
    cell.traffic = {**cell.traffic, "nt_chunk": 10, **traffic}
    return cell


def _run(cell, control=False, seed=2 ** 32 + 12345):
    return harness.run_cell(cell, seed, 0.3, False,
                            t_start=time.perf_counter(), require_tpu=False,
                            local_n=LOCAL_N, control=control)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    r = _run(_cell(name))
    c = r["compared"]
    assert r["correct"] is True and r["failed"] == 0
    assert c["max_rel_err"]["value"] <= c["max_rel_err"]["limit"]


@pytest.mark.parametrize("name", CELLS)
def test_the_control_is_not_correct(name):
    # bfloat16 reference in the program's place, same inputs
    r = _run(_cell(name), control=True)
    c = r["compared"]
    assert r["correct"] is False and r["failed"] == 0
    assert c["max_rel_err"]["value"] > c["max_rel_err"]["limit"]


def _updated(cell):
    return [k for k, f in cell.config["fields"].items()
            if f["role"] == "updated"]


def _break(cell, fault):
    """Wrap the program's step with ``fault``."""
    import jax.numpy as jnp

    make = cell.model.program_step
    names = _updated(cell)

    def broken(phys, impl):
        step = make(phys, impl)

        def run(s):
            out = step(s)
            if fault == "unchanged":
                return dict(s)
            out = dict(out)
            for k in names:
                new, old = out[k], s[k]
                if fault == "half_domain":
                    i = jnp.arange(new.shape[0])[:, None, None]
                    out[k] = jnp.where(i < new.shape[0] // 2, new, old)
                elif fault == "altered":
                    out[k] = new.at[2, 2, 2].add(
                        0.01 * jnp.max(jnp.abs(new)))
                elif fault == "nan":
                    out[k] = new.at[2, 2, 2].set(jnp.nan)
            return out
        return run

    cell.model.program_step = broken


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", ["unchanged", "half_domain", "altered"])
def test_a_broken_step_is_not_correct(name, fault):
    cell = _cell(name)
    _break(cell, fault)
    r = _run(cell)
    assert r["correct"] is False
    assert r["compared"]["max_rel_err"]["value"] \
        > r["compared"]["max_rel_err"]["limit"]


@pytest.mark.parametrize("name", CELLS)
def test_a_dropped_exchange_is_not_correct(name, monkeypatch):
    import implicitglobalgrid_tpu.models.acoustic as A
    import implicitglobalgrid_tpu.models.diffusion as D
    import implicitglobalgrid_tpu.models.stokes as S

    def no_exchange(*fields, **_):
        return fields[0] if len(fields) == 1 else fields

    monkeypatch.setattr(D, "local_update_halo", no_exchange)
    monkeypatch.setattr(A, "local_update_halo", no_exchange)
    monkeypatch.setattr(S, "local_update_halo", no_exchange)
    r = _run(_cell(name))
    assert r["correct"] is False


def test_a_guard_trip_counts_as_a_failed_chunk():
    cell = _cell("diffusion3d-256.supervised")
    _break(cell, "nan")
    r = _run(cell)
    assert r["failed"] >= 1 and r["correct"] is False
    assert r["compared"]["failed_chunks"]["value"] == r["failed"]


def test_checkpoint_and_snapshot_cadences_from_the_traffic_file():
    r = _run(_cell("diffusion3d-256.supervised", checkpoint_every=20,
                   snapshot_every=20))
    assert r["correct"] is True and r["failed"] == 0


STOKES_CELLS = (STOKES, STOKES_2X2)
DV = ("dVx", "dVy", "dVz")


@pytest.mark.parametrize("name", STOKES_CELLS)
def test_stokes_compared_stacked_everywhere_is_not_correct(name):
    """Without ``"compare": "owned"``, dV's halo entries (stale values that
    no step reads) make a sound run read far above the limit."""
    cell = _cell(name)
    for f in cell.config["fields"].values():
        f.pop("compare", None)
    r = _run(cell)
    c = r["compared"]["max_rel_err"]
    assert r["correct"] is False and r["failed"] == 0
    assert c["value"] > 100 * c["limit"]


@pytest.mark.parametrize("name", STOKES_CELLS)
def test_stokes_dv_on_its_own_scale_is_not_correct(name):
    """Chunks of 1000 iterations: the PT iteration has converged by the
    window, and dV holds rounding noise. Without ``"scale"``, read against
    max|dV|, the noise's own size, a sound run reads far above the
    limit."""
    cell = _cell(name, nt_chunk=1000)
    for k in DV:
        del cell.config["fields"][k]["scale"]
    r = _run(cell)
    c = r["compared"]["max_rel_err"]
    assert r["correct"] is False and r["failed"] == 0
    assert c["value"] > 100 * c["limit"]
    # with it, the same run is correct
    assert _run(_cell(name, nt_chunk=1000))["correct"] is True


def _shift_dv(cell, at):
    """Wrap the program's step: 0.01 added at local index ``at`` of each
    shard's dV blocks, every step."""
    make = cell.model.program_step

    def broken(phys, impl):
        step = make(phys, impl)

        def run(s):
            out = dict(step(s))
            for k in DV:
                out[k] = out[k].at[at].add(0.01)
            return out
        return run

    cell.model.program_step = broken


@pytest.mark.parametrize("name", STOKES_CELLS)
@pytest.mark.parametrize("at, caught", [((2, 2, 2), True), ((0, 0, 0), False)],
                         ids=["owned_entry", "halo_entry"])
def test_owned_region_judges_each_global_entry_once(name, at, caught):
    """A fault in an owned dV entry is caught; one in a dV halo entry that
    no step reads (local index 0 on every axis) is what ``"owned"`` gives
    up, and the run stays correct."""
    cell = _cell(name)
    _shift_dv(cell, at)
    r = _run(cell)
    assert r["failed"] == 0 and r["correct"] is (not caught)


@pytest.mark.parametrize("change", [
    {"compare": "interior"}, {"why": ""},
    {"scale": {"field": "V", "over": "dt_v"}},
    {"scale": {"field": "Vx", "over": "dt"}},
    {"scale": {"field": "Vx", "over": "dt_v", "times": 2}}],
    ids=["unknown_region", "without_why", "scale_of_no_field",
         "scale_over_no_constant", "scale_with_another_key"])
def test_a_bad_comparison_is_no_result(change):
    cell = _cell(STOKES)
    cell.config["fields"]["dVx"].update(change)
    with pytest.raises(spec.NoResult):
        _run(cell)


@pytest.mark.parametrize("region", ["owned", "stacked"])
@pytest.mark.parametrize("entry", ["owned", "halo"])
def test_check_reads_a_dv_fault_by_its_region(region, entry):
    """`check` alone, on a 2x2x1 layout: the program's answer is the
    reference's, but for half of max|dVx| added at one stacked entry of
    dVx, owned (local index 2 on every axis) or halo (local index 0). The
    ``"owned"`` region reads the first and not the second; ``"stacked"``
    reads both, against max|Vx| / dt_v."""
    import jax
    import jax.numpy as jnp

    from benchmark.layout import Layout

    cell = _cell(STOKES_2X2)
    cfg = cell.config
    cfg["fields"]["dVx"]["compare"] = region
    layout = Layout(LOCAL_N, (2, 2, 1), {k: f["stagger"] for k, f in
                                         cfg["fields"].items()})
    phys = cell.model.physics(cfg, layout)
    dev = jax.devices()[0]
    before = cell.model.make_state(cfg, layout, 7,
                                   jax.sharding.SingleDeviceSharding(dev),
                                   jnp.float32)
    ref = cell.model.reference(phys, 3, jnp.float32)(
        {k: layout.to_global(k, v, jnp) for k, v in before.items()})
    after = {k: layout.to_stacked(k, v, jnp) for k, v in ref.items()}
    shift = 0.5 * float(jnp.max(jnp.abs(ref["dVx"])))
    at = (2, 2, 2) if entry == "owned" else (0, 0, 0)
    after["dVx"] = after["dVx"].at[at].add(shift)
    got = harness.check(cell, layout, phys, [(before, after, 3)], dev)
    if region == "owned" and entry == "halo":
        assert got["max_rel_err"] < 1e-6
    else:
        assert got["max_rel_err"] == pytest.approx(
            shift * phys["dt_v"] / float(jnp.max(jnp.abs(ref["Vx"]))),
            rel=1e-3)
