"""Every cell, configuration, traffic mix and metric resolves by its name."""
import json
import re

import pytest

from bench import run, traffic, work

SPEC = run.load_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", [c["name"] for c in SPEC["workloads"]])
def test_cell_loads(cell):
    entry, config, mix = run.load_cell(cell, SPEC)
    assert entry["config"] == config["name"]
    assert entry["chips"] in (1, 4)
    assert config.get("shards", 1) == entry["chips"]
    assert config["scoring"] in ("f32", "sq8")
    assert set(config["limits"]) >= {"bad_ids", "recall_loss", "order_gap"}
    assert config["limits"]["bad_ids"] == 0
    if config["exact"]:
        assert "rank_gap" in config["limits"]
    reported = run.cell_metrics(SPEC, cell, per_layer=False)
    assert "setup_s" in {m["name"] for m in reported} and len(reported) >= 2
    assert run.cell_metrics(SPEC, cell, per_layer=True)


@pytest.mark.parametrize("path", sorted((run.BENCH / "traffic").glob("*.json")), ids=lambda p: p.stem)
def test_traffic_file_loads(path):
    assert NAME.match(path.stem)
    traffic.validate(json.loads(path.read_text()))


@pytest.mark.parametrize("path", sorted((run.BENCH / "configs").glob("*.json")), ids=lambda p: p.stem)
def test_config_file_loads(path):
    config = json.loads(path.read_text())
    assert config["name"] == path.stem
    entry = {c["name"]: c for c in SPEC["configs"]}[config["name"]]
    assert entry["file"] == f"bench/configs/{path.name}"
    assert entry["reduced"] == config["reduced"] and entry["source"] == config["source"]


@pytest.mark.parametrize("metric", SPEC["end_to_end"] + SPEC["per_layer"], ids=lambda m: m["name"])
def test_metric_reader_found(metric):
    assert callable(run.reader(metric["name"]))
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    cells = {c["name"] for c in SPEC["workloads"]}
    assert set(metric.get("workloads", [])) <= cells


def test_every_cell_reports_enough():
    for cell in SPEC["workloads"]:
        e2e = run.cell_metrics(SPEC, cell["name"], per_layer=False)
        layer = run.cell_metrics(SPEC, cell["name"], per_layer=True)
        assert len(e2e) >= 2 and layer
        assert {m["moves"] for m in layer} <= {m["name"] for m in e2e}


def test_peaks_name_their_source():
    peak = work.peaks("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12 and peak["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in peak["source"]
    with pytest.raises(KeyError):
        work.peaks("cpu")
