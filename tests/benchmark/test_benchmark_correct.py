"""The check that decides `correct`, driven through a whole run on the CPU at
a size a test holds (the look for a chip skipped): sound runs pass and the
lower-precision control fails; with the timed path broken underneath, each
fault the cells can have makes `correct` false.

Besides the cells of BENCHMARK.json, the acoustic adapter
(`benchmark/models/acoustic3d.py`, staggered fields) is driven through the
same run, under a configuration of this test's own: it has no cell until a
source for its size is found (PERF.md, Open questions)."""

import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import harness, spec  # noqa: E402

ACOUSTIC = "acoustic3d"
CELLS = ("diffusion3d-256.supervised", "diffusion3d-256.supervised-2x2",
         ACOUSTIC)
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


@pytest.fixture(autouse=True)
def _short_runs(monkeypatch):
    monkeypatch.setattr(harness, "WARMUP_CHUNKS", 1)
    monkeypatch.setattr(harness, "SAMPLE_FROM_FIRST", 4)


def _cell(name, **traffic):
    if name == ACOUSTIC:
        cell = spec.load_cell("diffusion3d-256.supervised")
        cell.name, cell.config = ACOUSTIC, ACOUSTIC_CONFIG
        cell.model = spec.load_module(
            ROOT / "benchmark/models/acoustic3d.py", "bench_test_acoustic")
    else:
        cell = spec.load_cell(name)
    cell.traffic = dict(cell.traffic, nt_chunk=10, **traffic)
    return cell


def _run(cell, control=False):
    return harness.run_cell(cell, 2 ** 32 + 12345, 0.3, False,
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

    def no_exchange(*fields, **_):
        return fields[0] if len(fields) == 1 else fields

    monkeypatch.setattr(D, "local_update_halo", no_exchange)
    monkeypatch.setattr(A, "local_update_halo", no_exchange)
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
