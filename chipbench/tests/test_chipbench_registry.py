"""BENCHMARK.json and the files it names, found by name."""

import json
import re

import pytest

from chipbench.tests import tiny
from chipbench import registry

BENCH = registry.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_benchmark_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert BENCH["paths"] == ["chipbench"]
    names = [x["name"] for g in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[g]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_files(conf):
    data = registry.config(conf["name"])
    assert conf["file"] == f"chipbench/configs/{conf['name']}.json"
    assert data["name"] == conf["name"] and data["reduced"] == conf["reduced"]
    assert data["source"] == conf["source"]
    # every width is the program's own
    entry = registry.module("entries", data["entry"])
    entry.program_config(data)


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cells_find_their_files(cell):
    conf = registry.config(cell["config"])
    mix = registry.traffic(cell["traffic"])
    registry.module("traffic", mix["kind"])
    registry.module("entries", conf["entry"])
    lim = registry.limits(cell["name"])
    entry = registry.module("entries", conf["entry"])
    assert set(entry.NUMBERS) <= set(lim)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    e2e = registry.metrics_for(BENCH, cell["name"], trace=False)
    assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
    per = registry.metrics_for(BENCH, cell["name"], trace=True)
    reported = {m["name"] for m in e2e}
    assert per and all(m["moves"] in reported for m in per)


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_every_metric_has_a_reader(metric):
    assert callable(registry.module("metrics", metric["name"]).read)


def test_unknown_names_are_refused():
    with pytest.raises(KeyError):
        registry.cell(BENCH, "no_such.cell")
    with pytest.raises(KeyError):
        registry.config("no_such_config")
    with pytest.raises(KeyError):
        registry.module("metrics", "no_such_metric")
