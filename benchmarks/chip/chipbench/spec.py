"""BENCHMARK.json and the files it names, found by name.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric lives in a file of its own, so a later change adds a
cell by adding files and an entry, and edits nothing here:

    configs/<config>.json     the configuration as it is run (file named
                              by the ``configs`` entry)
    traffic/<traffic>.json    the traffic mix, read by ``feed.py``
    limits/<workload>.json    the limits of the correctness comparison
    metrics/<metric>.py       the reader of one per-layer metric
    archs/<model_type>.py     one architecture: its sizes, parameter
                              tree, plain reference and FLOPs, found by
                              the configuration's ``model_type``
                              (``chipbench/model_spec.py`` says what
                              such a file holds)
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
import sys
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    bench_dir: str = BENCH_DIR


def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _read_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def reports(metric: Dict[str, Any], workload: str) -> bool:
    """Whether ``workload`` reports ``metric`` (all cells without a
    ``workloads`` key)."""
    return "workloads" not in metric or workload in metric["workloads"]


def resolve(workload: str, bench: Optional[Dict[str, Any]] = None,
            root: str = ROOT) -> Cell:
    """The cell named ``workload`` with its configuration, traffic and
    limits read from their files. Raises KeyError for an unknown cell."""
    bench = bench or load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read_json(os.path.join(root, configs[w["config"]]["file"]))
    bench_dir = os.path.join(root, bench["paths"][0])
    traffic = _read_json(os.path.join(bench_dir, "traffic",
                                      w["traffic"] + ".json"))
    limits = _read_json(os.path.join(bench_dir, "limits", workload + ".json"))
    return Cell(
        name=workload, config_name=w["config"], chips=int(w["chips"]), config=config, traffic=traffic, limits=limits,
        end_to_end=[m for m in bench["end_to_end"] if reports(m, workload)],
        per_layer=[m for m in bench["per_layer"] if reports(m, workload)],
        bench_dir=bench_dir)


def metric_reader(name: str, bench_dir: str = BENCH_DIR) -> Callable:
    """``read(ctx)`` of ``metrics/<name>.py``: the number, or None when
    the run holds nothing for it to read."""
    path = os.path.join(bench_dir, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + re.sub(r"\W", "_", name), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


_ARCHS: Dict[str, Any] = {}


def arch(model_type: str, bench_dir: str = BENCH_DIR):
    """The module ``archs/<model_type>.py``: everything the benchmark
    knows of one architecture. Loaded once per process, from the
    directory of the first call that asks for it; later calls get that
    module whatever directory they name."""
    if model_type not in _ARCHS:
        path = os.path.join(bench_dir, "archs", f"{model_type}.py")
        if not (isinstance(model_type, str) and NAME_RE.match(model_type)
                and os.path.isfile(path)):
            raise ValueError(f"model_type {model_type!r}: no architecture "
                             f"file archs/{model_type}.py in {bench_dir}")
        name = "chipbench_arch_" + re.sub(r"\W", "_", model_type)
        spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(spec)
        sys.modules[name] = module        # dataclasses look the module up
        spec.loader.exec_module(module)
        _ARCHS[model_type] = module
    return _ARCHS[model_type]
