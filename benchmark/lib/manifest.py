"""Finding a cell's files by the names in ``BENCHMARK.json``. Nothing here
lists a cell, a configuration or a metric: a later PR adds files and
entries, and edits nothing."""
from __future__ import annotations

import importlib
import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _load_json(path: str) -> dict:
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


def load(manifest_path: str, workload: str) -> dict:
    """The manifest, and of ``workload`` its entry, its data file
    (``<a path>/workloads/<name>.json``) and its configuration."""
    manifest = _load_json(manifest_path)
    entries = {w["name"]: w for w in manifest["workloads"]}
    if workload not in entries:
        raise SystemExit(f"{manifest_path} has no workload {workload!r}; "
                         f"it has {sorted(entries)}")
    entry = entries[workload]
    for d in manifest["paths"]:
        path = os.path.join(d, "workloads", workload + ".json")
        if os.path.exists(os.path.join(ROOT, path)):
            cell = _load_json(path)
            break
    else:
        raise SystemExit(f"no workloads/{workload}.json under "
                         f"{manifest['paths']}")
    for key in ("config", "chips"):
        if cell.get(key, entry[key]) != entry[key]:
            raise SystemExit(f"{workload}: {key} is {entry[key]!r} in the "
                             f"manifest and {cell[key]!r} in its file")
    configs = {c["name"]: c for c in manifest["configs"]}
    config = _load_json(configs[entry["config"]]["file"])
    return {"manifest": manifest, "entry": entry, "cell": cell,
            "config": config}


def metrics_of(manifest: dict, workload: str, group: str) -> list:
    """The metrics of ``group`` that ``workload`` reports."""
    return [m for m in manifest[group]
            if "workloads" not in m or workload in m["workloads"]]


def driver(kind: str):
    return importlib.import_module(f"benchmark.drivers.{kind}")


def family(name: str):
    return importlib.import_module(f"benchmark.families.{name}")


def metric_reader(name: str):
    """``benchmark/metrics/<name>.py``'s ``read``. Names hold dots, so the
    file is loaded by its path."""
    path = os.path.join(ROOT, "benchmark", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
