"""The benchmark's harness on the CPU: argument parsing, finding each piece
by name, the contract's limits on BENCHMARK.json, the last line's shape,
and refusal without a TPU or with an unknown device kind."""

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import harness, spec  # noqa: E402
from benchmark.run import parse  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_parse_takes_the_driver_arguments():
    a = parse(["--workload", "x", "--seed", str(2 ** 33 + 5), "--seconds",
               "10", "--trace", "1"])
    assert (a.workload, a.seed, a.seconds, a.trace, a.control) == (
        "x", 2 ** 33 + 5, 10.0, 1, 0)
    with pytest.raises(SystemExit):
        parse(["--workload", "x", "--seed", "1", "--seconds", "1",
               "--trace", "2"])


def test_benchmark_json_keeps_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    assert 1 <= BENCH["run_seconds"] <= 51
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [x["name"] for x in
             BENCH["configs"] + BENCH["workloads"] + metrics]
    assert len(names) == len(set(names))
    assert all(NAME_RE.match(n) for n in names)
    for m in metrics:
        assert UNIT_RE.match(m["unit"]) and m["better"] in ("lower",
                                                            "higher")
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and len(m["layer"]) <= 200
        assert set(m.get("workloads", cells)) <= cells
        assert (ROOT / "benchmark/layer_metrics" / f"{m['name']}.py").is_file()
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file() and c["file"].startswith(
            "benchmark/")
        assert all(NAME_RE.match(k) for k in c["reduced"])
    for x in BENCH["configs"] + BENCH["workloads"]:
        assert 1 <= len(x["why"]) <= 200 and "\n" not in x["why"]
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_each_cell_finds_its_pieces_by_name(name):
    cell = spec.load_cell(name)
    w = {x["name"]: x for x in BENCH["workloads"]}[name]
    assert cell.chips == w["chips"]
    assert cell.config["name"] == w["config"]
    assert int(__import__("numpy").prod(cell.traffic["mesh"])) == cell.chips
    for fn in ("physics", "make_state", "program_step", "reference"):
        assert callable(getattr(cell.model, fn))
    assert set(cell.readers) == {m["name"] for m in cell.per_layer}
    assert all(callable(r.read) for r in cell.readers.values())


def test_unknown_cell_and_unknown_device_kind_give_no_result():
    with pytest.raises(spec.NoResult):
        spec.load_cell("no-such-cell")
    with pytest.raises(spec.NoResult):
        spec.hbm_peak_bytes_per_s("TPU v99")
    assert spec.hbm_peak_bytes_per_s("TPU v5 lite") == 819e9


def test_no_tpu_or_too_few_chips_gives_no_result():
    with pytest.raises(spec.NoResult, match="no TPU"):
        harness.devices_for(1)
    with pytest.raises(spec.NoResult, match="needs 64 chips"):
        harness.devices_for(64, require_tpu=False)


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "diffusion3d-256.supervised", "--seed", "1", "--seconds", "1",
         "--trace", "0", *extra], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def _result_line(stdout):
    lines = stdout.strip().splitlines()
    try:
        return "correct" in json.loads(lines[-1])
    except (IndexError, ValueError, TypeError):
        return False


def test_run_without_a_tpu_exits_nonzero_with_no_result():
    p = _run(ROOT)
    assert p.returncode != 0 and not _result_line(p.stdout)
    assert "no TPU" in p.stderr


def test_run_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0 and not _result_line(p.stdout)


def test_last_lines_hold_the_result_and_the_compared_numbers(capsys,
                                                            monkeypatch):
    import time

    monkeypatch.setattr(harness, "WARMUP_CHUNKS", 1)

    cell = spec.load_cell("diffusion3d-256.supervised")
    cell.traffic = dict(cell.traffic, nt_chunk=10)
    r = harness.run_cell(cell, 7, 0.2, False, t_start=time.perf_counter(),
                         require_tpu=False, local_n=(8, 8, 8))
    harness.emit(r)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 2
    assert set(line["metrics"]) == {"cell_updates_per_s_per_chip",
                                    "setup_s"}
    assert line["metrics"]["setup_s"]["unit"] == "s"
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    tail = err.strip().splitlines()[-len(line["compared"]):]
    for (name, c), text in zip(line["compared"].items(), tail):
        assert text == f"{name} {c['value']!r} limit {c['limit']!r}"
