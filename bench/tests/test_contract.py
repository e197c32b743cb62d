"""BENCHMARK.json holds to the shape the benchmark is checked against,
and every name in it finds its file."""

import json
import re

from bench import common

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
B = common.load_benchmark()


def test_top_level_keys_and_sizes():
    assert set(B) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert len(json.dumps(B)) < 64 * 1024
    assert B["command"] == ["python3", "bench/run.py"] and B["paths"] == ["bench"]
    assert 1 <= B["run_seconds"] <= 51
    # a full check of 24 cells fits its time: 2 + 14 runs a cell, each
    # run_seconds + 60 s, 2 x 90 s of compile a cell, 1200 s spare
    runs = 2 + 14 * 24
    assert runs * (B["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_entry_keys():
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        assert all(NAME.match(k) for k in c["reduced"])
        cfg = common.load_config(c["name"])
        assert (common.ROOT / c["file"]).exists() and cfg["reduced"] == c["reduced"]
        assert cfg["source"] == c["source"]
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert w["config"] in {c["name"] for c in B["configs"]}
        common.load_traffic(w["traffic"])
    for m in B["end_to_end"] + B["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in B["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in B["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert hasattr(common.metric_reader(m["name"]), "read")


def test_every_cell_reports_what_its_metrics_move():
    e2e_names = {m["name"] for m in B["end_to_end"]}
    assert "setup_s" in e2e_names
    for w in B["workloads"]:
        e2e = {m["name"] for m in common.cell_metrics(B, w["name"], "end_to_end")}
        layer = common.cell_metrics(B, w["name"], "per_layer")
        assert "setup_s" in e2e and len(e2e) >= 2 and layer
        for m in layer:
            assert m["moves"] in e2e, (w["name"], m["name"])


def test_kernel_shares_are_named_and_in_percent():
    for m in B["per_layer"]:
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    assert any(m["name"].endswith("_roofline") for m in B["per_layer"])
    assert any("mfu" in m["name"] for m in B["per_layer"])
