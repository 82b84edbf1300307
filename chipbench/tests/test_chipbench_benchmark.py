"""``BENCHMARK.json`` and the files it names agree."""
import importlib
import json
import os

import pytest

import _paths

BENCH = json.load(open(os.path.join(_paths.ROOT, "BENCHMARK.json")))


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_states_its_cut(entry):
    assert entry["file"].startswith("chipbench/configs/")
    cfg = json.load(open(os.path.join(_paths.ROOT, entry["file"])))
    assert cfg["name"] == entry["name"] and cfg["source"] == entry["source"]
    # BENCHMARK.json lists every key changed from the source: the cuts of
    # scale (the file's ``reduced``) and the other changes (``changed``)
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"] + cfg["changed"])
    for key in ("published", "assumed", "departures", "deployment"):
        assert cfg[key], key


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader(metric):
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    assert metric["moves"] in e2e
    assert set(metric.get("workloads", cells)) <= cells
    reader = importlib.import_module(f"layer_metrics.{metric['name']}")
    assert callable(reader.read)


def test_every_cell_has_its_files():
    for w in BENCH["workloads"]:
        for sub, name in (("cells", w["name"]), ("mixes", w["traffic"])):
            assert os.path.exists(os.path.join(_paths.BENCH, sub,
                                               f"{name}.json")), (sub, name)
