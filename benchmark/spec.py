"""Find a cell's pieces by the names in BENCHMARK.json.

- configuration: the ``file`` its entry names (``benchmark/configs/``);
- traffic mix: ``benchmark/traffic/<traffic>.json``;
- model adapter: ``benchmark/models/<model>.py``, ``model`` named in the
  configuration file (state from the seed, the program's step, the plain
  reference);
- per-layer reader: ``benchmark/layer_metrics/<metric>.py``;
- peak table: ``benchmark/peaks.json``.

A later PR adds a cell, configuration or metric by adding files and
entries; nothing here names one."""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


class NoResult(RuntimeError):
    """The run cannot give a result line (unknown cell, no chip, a device
    the peak table lacks): exit nonzero and print none."""


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import one file of the benchmark by its path."""
    if not path.is_file():
        raise NoResult(f"missing benchmark file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    model: object            # the adapter module
    end_to_end: list         # metric entries this cell reports
    per_layer: list
    readers: dict            # per-layer metric name -> reader module


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise NoResult(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(work)}")
    w = work[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(root / cfg_entry["file"])
    traffic = load_json(HERE / "traffic" / f"{w['traffic']}.json")
    model = load_module(HERE / "models" / f"{config['model']}.py",
                        f"benchmark_model_{config['model']}")
    per_layer = [m for m in bench["per_layer"] if _applies(m, name)]
    readers = {m["name"]: load_module(
        HERE / "layer_metrics" / f"{m['name']}.py",
        f"benchmark_layer_{m['name']}") for m in per_layer}
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, model=model,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=per_layer, readers=readers)


def hbm_peak_bytes_per_s(device_kind: str) -> float:
    """Published HBM bandwidth of ``device_kind``; a device the table lacks
    is an error, not a default."""
    table = load_json(HERE / "peaks.json")["hbm_GBps"]
    if device_kind not in table:
        raise NoResult(f"no HBM peak for device kind {device_kind!r} in "
                       "benchmark/peaks.json")
    return float(table[device_kind]) * 1e9


def use_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent compile cache at a fixed path in the checkout
    (``JAX_COMPILATION_CACHE_DIR`` wins where it is set), every program
    cached, so only a checkout's first run of a cell compiles."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
