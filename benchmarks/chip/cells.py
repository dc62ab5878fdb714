"""Finds everything a run needs by name, so that a new configuration,
traffic mix, limit or per-layer metric is added by adding files only.

* ``BENCHMARK.json`` at the checkout root: the cells, the metrics and
  which cells each metric is read in;
* ``configs/<config>.json``: the model, its deployment and, under
  ``arch``, the name of its architecture module (the path is the config
  entry's ``file`` in ``BENCHMARK.json``);
* ``archs/<arch>.py``: what depends on the block (``ARCH_API``): the
  weights' shapes, the float32 reference and its float8 control, and
  the work counts;
* ``traffic/<mix>.json``: the traffic mix (see ``traffic.py``);
* ``limits/<cell>.json``: the limit of each number the correctness
  check compares, with the readings it was set from;
* ``metrics/<metric>.py``: one reader per per-layer metric;
* ``peaks.json``: the chips' published peaks by ``device_kind``.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path
from types import ModuleType

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

# what an architecture module gives: path -> (shape, std) of every weight;
# the float32 reference's logits and their float8 control; weights
# multiplied per token; attention FLOPs of one query over a context; KV
# bytes of one position
ARCH_API = ("shapes", "logits", "matmul_params", "attention_flops",
            "kv_bytes_per_position")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # configs/<config>.json
    arch: ModuleType        # archs/<config["arch"]>.py
    mix: dict               # traffic/<mix>.json, its base merged in
    limits: dict            # limits/<cell>.json
    end_to_end: list        # BENCHMARK.json metrics this cell reports
    per_layer: list


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_mix(name: str, directory: Path = HERE / "traffic") -> dict:
    mix = load_json(directory / f"{name}.json")
    if "base" in mix:
        mix = {**load_mix(mix["base"], directory),
               **{k: v for k, v in mix.items() if k != "base"}}
    return mix


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def find_cell(name: str, root: Path = ROOT, here: Path = HERE) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files:
    configuration files by their path from ``root``, architecture
    modules, traffic mixes and limits from ``here``.  A configuration
    that names no ``arch`` is an error."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    file = root / configs[w["config"]]["file"]
    config = load_json(file)
    if "arch" not in config:
        raise ValueError(f"{file} names no \"arch\": the architecture "
                         f"module archs/<arch>.py that builds its block")
    return Cell(
        name=name, chips=int(w["chips"]), config=config,
        arch=load_arch(config["arch"], here / "archs"),
        mix=load_mix(w["traffic"], here / "traffic"),
        limits=load_json(here / "limits" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)])


@functools.lru_cache(maxsize=None)
def load_arch(name: str, directory: Path = HERE / "archs") -> ModuleType:
    """``archs/<name>.py``, checked to give every name of ``ARCH_API``.
    Loaded once per file, so that its jitted reference compiles once."""
    path = directory / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.chip.archs.{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    missing = [k for k in ARCH_API if not callable(getattr(module, k, None))]
    if missing:
        raise ValueError(f"{path} gives no {', '.join(missing)}")
    return module


def load_reader(metric: str, directory: Path = HERE / "metrics"):
    """``metrics/<metric>.py``'s ``read(run) -> float | None``."""
    path = directory / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.chip.metrics.{metric.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def peaks(device_kind: str) -> dict:
    """Published peaks of ``device_kind``; a chip not in the table is an
    error, never a default."""
    table = load_json(HERE / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (have {sorted(table)})")
    return table[device_kind]
