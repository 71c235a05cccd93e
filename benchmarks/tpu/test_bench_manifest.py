"""``BENCHMARK.json`` and the files it names are whole and consistent."""
import json
import os
import re

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = {w["name"]: w for w in BENCH["workloads"]}
E2E = {m["name"]: m for m in BENCH["end_to_end"]}


def test_names_and_units():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [x["name"] for x in BENCH["configs"] + BENCH["workloads"] + metrics]
    assert all(NAME.match(n) for n in names)
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert all(UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
               for m in metrics)
    assert "setup_s" in E2E and E2E["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in E2E.values())


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_files_are_found_by_name(cell):
    w = CELLS[cell]
    assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    entry = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    assert config["name"] == entry["name"]
    assert sorted(config["reduced"]) == sorted(entry["reduced"])
    with open(os.path.join(HERE, "traffic", w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert os.path.exists(os.path.join(HERE, "drivers",
                                       traffic["driver"] + ".py"))
    reported = [m for m in E2E.values() if cell in m.get("workloads", [cell])]
    assert "setup_s" in {m["name"] for m in reported} and len(reported) >= 2
    layer = [m for m in BENCH["per_layer"] if cell in m["workloads"]]
    assert layer and all(m["moves"] in {r["name"] for r in reported}
                         for m in layer)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_each_limit_lies_between_its_readings(cell):
    """Every limit of ``correct`` is set from two readings kept beside it:
    the largest of sound runs (lower) and the smallest of the control or a
    fault (upper).  It lies above the one and below the other; an exact
    comparison has the limit 0."""
    with open(os.path.join(HERE, "traffic",
                           CELLS[cell]["traffic"] + ".json")) as f:
        traffic = json.load(f)
    for name, limit in traffic["limits"].items():
        r = traffic["readings"][name]
        assert r["lower"] < limit or r["lower"] == limit == 0, (name, r)
        assert r["upper"] is None or limit < r["upper"], (name, r)
        assert r["from"]


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_each_layer_metric_has_a_reader(metric):
    assert os.path.exists(os.path.join(HERE, "metrics", metric + ".py"))
