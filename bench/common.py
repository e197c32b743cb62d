"""Where the benchmark's files live, and loading them by name.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own, found by the name that BENCHMARK.json
gives it:

* ``bench/configs/<config>.json``  sizes, engine settings, source, cuts;
* ``bench/traffic/<mix>.json``     parameters of the one traffic generator;
* ``bench/kinds/<kind>.py``        a kind of traffic the generator and the
  client know (``open_loop``, ``backlog``);
* ``bench/metrics/<metric>.py``    a reader with ``read(ctx)``;
* ``bench/correct/reference_<name>.py``  the architecture a configuration
  names under ``reference``: the one place that knows it (below).

An architecture module provides

* ``FIELDS``: each key its configurations' ``model`` group may hold ->
  the system's ``ModelConfig`` field, or a function of the ``ModelConfig``
  where the system derives the value (``run.system_config`` stops a run
  on a key the table does not map);
* ``dims_of(model)``: the sizes the weights, the reference and the metric
  readers compute with, ``"V"`` (the vocabulary) among them;
* ``layout(dims)``: the weight tree as ``(shape, init)`` leaves
  (``bench/weights.py``);
* ``logits(params, tokens, dims, compute=None)``: the plain reference.

So a configuration of a new architecture arrives as new files only.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Any, Dict

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_benchmark(path: Path = ROOT / "BENCHMARK.json") -> Dict[str, Any]:
    return load_json(path)


def find_cell(benchmark: Dict[str, Any], workload: str) -> Dict[str, Any]:
    for cell in benchmark["workloads"]:
        if cell["name"] == workload:
            return cell
    names = [c["name"] for c in benchmark["workloads"]]
    raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has {names}")


def load_config(name: str) -> Dict[str, Any]:
    return load_json(BENCH / "configs" / f"{name}.json")


def load_traffic(name: str) -> Dict[str, Any]:
    return load_json(BENCH / "traffic" / f"{name}.json")


def _load_module(path: Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def traffic_kind(name: str) -> ModuleType:
    """``bench/kinds/<name>.py``: ``count``, ``gaps``, ``feed``, ``lead_in``."""
    return _load_module(BENCH / "kinds" / f"{name}.py", f"bench_kind_{name}")


def metric_reader(name: str) -> ModuleType:
    """``bench/metrics/<name>.py``; its ``read(ctx)`` returns a number, or
    None where the run gave it nothing to read."""
    return _load_module(BENCH / "metrics" / f"{name}.py",
                        "bench_metric_" + name.replace(".", "_"))


def reference_module(name: str) -> ModuleType:
    return _load_module(BENCH / "correct" / f"reference_{name}.py",
                        f"bench_reference_{name}")


def cell_metrics(benchmark: Dict[str, Any], workload: str,
                 kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    return [m for m in benchmark[kind]
            if "workloads" not in m or workload in m["workloads"]]
