"""BENCHMARK.json against the contract's naming rules, and every cell,
configuration, mix and metric found by name from its own file."""
import json

import pytest

from harness import device, spec

BENCH = spec.load_benchmark()


def test_names_and_units_are_plain():
    assert spec.check_names(BENCH) == []


@pytest.mark.parametrize("bad", ["has space", "a,b", "x/y", "", "é"])
def test_check_names_refuses(bad):
    b = json.loads(json.dumps(BENCH))
    b["end_to_end"][0]["name"] = bad
    assert spec.check_names(b)


def test_unit_rules():
    assert spec.UNIT_RE.match("launches/scan")
    assert spec.UNIT_RE.match("%")
    assert not spec.UNIT_RE.match("tokens per second")
    assert not spec.UNIT_RE.match("x" * 17)


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_config_found_by_name(entry):
    cfg = spec.config(BENCH, entry["name"])
    assert entry["file"].startswith("bench/configs/")
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
    for key in ("source", "guarantees", "assumed", "control", "tables"):
        assert key in cfg


@pytest.mark.parametrize("wl", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_found_by_name(wl):
    assert spec.workload(BENCH, wl["name"]) is wl
    mix = spec.traffic(wl["traffic"])
    cfg = spec.config(BENCH, wl["config"])
    tables = {t["name"] for t in cfg["tables"]}
    assert set(mix["tables"]) <= tables
    assert {st["table"] for st in mix["streams"]} <= set(mix["tables"])
    spec.load_module("deploys", cfg["deploy"]).build
    spec.load_module("controls", cfg["control"]).view
    for st in mix["streams"]:                 # the code the data names
        loop = spec.load_module("traffic", st["loop"])
        assert callable(loop.Source) and callable(loop.place)
        for entry in st["mix"] + st["warm"]:
            spec.load_module("traffic", entry["keys"]).draw
            spec.load_module("ops", entry["op"]).answer
    assert wl["chips"] == 1
    e2e = spec.metrics_for(BENCH, wl["name"], traced=False)
    layer = spec.metrics_for(BENCH, wl["name"], traced=True)
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    assert layer
    e2e_names = {m["name"] for m in e2e}
    for m in layer:                # each moves a metric the cell reports
        assert m["moves"] in e2e_names


@pytest.mark.parametrize("m", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_reader_found_by_name(m):
    mod = spec.load_module("metrics", m["name"])
    assert callable(mod.read)


def test_unknown_names_refused():
    with pytest.raises(spec.SpecError):
        spec.workload(BENCH, "no-such-cell")
    with pytest.raises(spec.SpecError):
        spec.traffic("no-such-mix")
    with pytest.raises(spec.SpecError):
        spec.load_module("metrics", "no_such_metric")
    with pytest.raises(spec.SpecError):
        spec.load_module("metrics", "../harness/spec")


def test_peaks_refuse_an_unknown_device_kind():
    with pytest.raises(KeyError, match="not in peaks.json"):
        device.load_peaks("TPU v99")


def test_peaks_of_v5e():
    p = device.load_peaks("TPU v5 lite")
    assert p["int8_ops_per_s"] == 393e12
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
