"""Find a cell and everything it names, by name, under `bench/`."""
from __future__ import annotations

import importlib.util
import json
import os
from types import ModuleType
from typing import Dict, List, NamedTuple

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


class Cell(NamedTuple):
    name: str
    workload: Dict        # the BENCHMARK.json `workloads` entry
    config: Dict          # bench/configs/<config>.json
    traffic: Dict         # bench/traffic/<traffic>.json
    end_to_end: List[Dict]   # the metrics this cell reports with --trace 0
    per_layer: List[Dict]    # ... and with --trace 1


def load_json(path: str) -> Dict:
    with open(path) as fh:
        return json.load(fh)


def _applies(metric: Dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell `name` of `root`/BENCHMARK.json with its configuration,
    its traffic mix and the metrics that apply to it."""
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                         f"have {sorted(by_name)}")
    wl = by_name[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[wl["config"]]
    config = load_json(os.path.join(root, cfg_entry["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     wl["traffic"] + ".json"))
    return Cell(name, wl, config, traffic,
                [m for m in spec["end_to_end"] if _applies(m, name)],
                [m for m in spec["per_layer"] if _applies(m, name)])


def _load_module(path: str, label: str) -> ModuleType:
    mod_spec = importlib.util.spec_from_file_location(label, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench: str = BENCH) -> ModuleType:
    """bench/metrics/<name>.py: a module with `read(ctx) -> float|None`."""
    return _load_module(os.path.join(bench, "metrics", name + ".py"),
                        f"bench_metric_{name.replace('-', '_')}")


def reference_rule(problem: str) -> ModuleType:
    """bench/reference/<problem>.py: the plain decision rule."""
    return _load_module(os.path.join(BENCH, "reference", problem + ".py"),
                        f"bench_reference_{problem}")


def peaks(device_kind: str) -> Dict:
    """The chip's published peaks; an unknown kind is an error."""
    table = load_json(os.path.join(BENCH, "peaks.json"))
    if device_kind not in table["kinds"]:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"bench/peaks.json (have {sorted(table['kinds'])})")
    return table["kinds"][device_kind]
