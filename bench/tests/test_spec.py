"""The harness finds cells, configurations, mixes, references and
metrics by name: a new one is new files and entries, no edit."""
import json
import os
import re

import pytest

from harness import spec
from harness.context import Context

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _bench():
    return spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))


def test_every_cell_loads_with_its_files():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    for w in b["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert spec.reference_rule(cell.config["problem"]["name"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in names
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"])
        assert callable(spec.metric_reader(m["name"]).read)


def test_names_and_units_follow_the_contract():
    b = _bench()
    for c in b["configs"]:
        assert NAME.match(c["name"])
        assert c["file"].startswith("bench/")
        cfg = spec.load_json(os.path.join(spec.ROOT, c["file"]))
        assert set(c["reduced"]) == set(cfg["reduced"])
    for m in b["end_to_end"]:
        assert 0 < m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = {m["layer"] for m in b["per_layer"]}
    cells = {w["name"] for w in b["workloads"]}
    for m in b["per_layer"]:
        assert m["moves"] in {e["name"] for e in b["end_to_end"]}
        assert set(m["workloads"]) <= cells
    assert layers


def test_unknown_device_kind_is_an_error():
    with pytest.raises(SystemExit):
        spec.peaks("TPU v9 imaginary")
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_new_cell_config_mix_and_metric_from_new_files(tmp_path):
    """A later PR adds a configuration, a mix, a per-layer metric and a
    cell as files plus BENCHMARK.json entries; the harness picks all
    of them up without a change to any file that is there."""
    root = tmp_path
    for d in ("bench/configs", "bench/traffic", "bench/metrics"):
        (root / d).mkdir(parents=True)
    b = _bench()
    b["configs"].append({"name": "tiny-majority", "source": "test",
                         "file": "bench/configs/tiny-majority.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "tiny-majority.bursty",
                           "config": "tiny-majority", "traffic": "bursty",
                           "chips": 1, "why": "test"})
    b["end_to_end"].append({"name": "cycles_per_s.bursty",
                            "unit": "cycles/s", "better": "higher",
                            "bound": 0.05, "source": "host_clock",
                            "workloads": ["tiny-majority.bursty"]})
    b["per_layer"].append({"name": "pumps_per_window", "unit": "1",
                           "better": "lower", "source": "host_clock",
                           "layer": "serve", "moves": "cycles_per_s.bursty",
                           "workloads": ["tiny-majority.bursty"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    (root / "bench/configs/tiny-majority.json").write_text(json.dumps(
        {"name": "tiny-majority", "n": 64, "problem": {"name": "majority"}}))
    (root / "bench/traffic/bursty.json").write_text(json.dumps(
        {"kind": "open_loop", "rate_per_s": 3}))
    (root / "bench/metrics/pumps_per_window.py").write_text(
        "def read(ctx):\n    return 42.0\n")

    cell = spec.load_cell("tiny-majority.bursty", root=str(root))
    assert cell.config["n"] == 64
    assert cell.traffic["rate_per_s"] == 3
    assert [m["name"] for m in cell.per_layer] == ["pumps_per_window"]
    assert {m["name"] for m in cell.end_to_end} == {"setup_s",
                                                    "cycles_per_s.bursty"}
    reader = spec.metric_reader("pumps_per_window",
                                bench=str(root / "bench"))
    assert reader.read(Context(None, {}, 0.0)) == 42.0
