"""Names in ``BENCHMARK.json`` resolved to the files that define them.

The harness holds no list or registry of its own: a cell, a configuration, a
traffic mix and its generator, a kind of run, a per-layer metric and a
reference are each found by name, as a file under ``benchmark/``. Adding one
is adding files and entries (README.md), never editing a file that is there.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os
from typing import Any, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(path: str) -> Dict[str, Any]:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_manifest() -> Dict[str, Any]:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def _entry(entries: List[Dict[str, Any]], name: str, what: str):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"BENCHMARK.json has no {what} named {name!r}")


@functools.lru_cache(maxsize=None)
def load_module(path: str):
    """Import one file by path (metric names may hold a '.', which a module
    name cannot). Once per path: a reference's jitted functions are traced
    and compiled once, not once per sample."""
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    name = "benchmark_file_" + os.path.relpath(path, HERE).replace(
        os.sep, "_").replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_paths(manifest: Dict[str, Any], workload: str) -> Dict[str, str]:
    cell = _entry(manifest["workloads"], workload, "workload")
    config = _entry(manifest["configs"], cell["config"], "config")
    return {
        "config": os.path.join(ROOT, config["file"]),
        "workload": os.path.join(HERE, "workloads", workload + ".json"),
        "traffic": os.path.join(HERE, "traffic", cell["traffic"] + ".json"),
    }


def load_cell(manifest: Dict[str, Any], workload: str) -> Dict[str, Any]:
    """Everything that defines one cell, read from its own files."""
    cell = dict(_entry(manifest["workloads"], workload, "workload"))
    paths = cell_paths(manifest, workload)
    cell["config_file"] = load_json(paths["config"])
    cell["traffic_file"] = load_json(paths["traffic"])
    cell.update(load_json(paths["workload"]))
    return cell


def kind_path(kind: str) -> str:
    return os.path.join(HERE, "kinds", kind + ".py")


def generator_path(generator: str) -> str:
    return os.path.join(HERE, "generators", generator + ".py")


def reference_path(family: str) -> str:
    return os.path.join(HERE, "reference", family + ".py")


def layer_metric_path(metric: str) -> str:
    return os.path.join(HERE, "layer_metrics", metric + ".py")


def metrics_of(manifest: Dict[str, Any], group: str,
               workload: str) -> List[Dict[str, Any]]:
    """The metrics of ``group`` (``end_to_end`` | ``per_layer``) that the
    cell reports: those without a ``workloads`` key (every cell that reports
    what they move) and those that list it."""
    e2e_here = {m["name"] for m in manifest["end_to_end"]
                if "workloads" not in m or workload in m["workloads"]}
    out = []
    for m in manifest[group]:
        if "workloads" in m:
            if workload in m["workloads"]:
                out.append(m)
        elif group == "end_to_end" or m["moves"] in e2e_here:
            out.append(m)
    return out
