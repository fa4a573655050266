"""``BENCHMARK.json`` names only files that exist, and each name follows
the benchmark's rules, so a new cell or metric is found by name."""
import json
import os
import re

import run_cell

ROOT = os.path.dirname(run_cell.BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_name_resolves():
    b = _bench()
    configs = {c["name"]: c for c in b["configs"]}
    for c in b["configs"]:
        assert NAME.match(c["name"]) and os.path.isfile(
            os.path.join(ROOT, c["file"]))
        assert all(NAME.match(k) for k in c["reduced"])
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs
        traffic = os.path.join(run_cell.BENCH, "traffic",
                               w["traffic"] + ".json")
        with open(traffic) as f:
            driver = json.load(f)["driver"]
        assert os.path.isfile(os.path.join(run_cell.BENCH, "drivers",
                                           driver + ".py"))
        spec = run_cell.cell_spec(w["name"])
        names = {m["name"] for m in spec["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2
        assert spec["per_layer"], w["name"]
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in b["per_layer"]:
        assert os.path.isfile(run_cell.reader_path(m["name"])), m["name"]
        assert m["moves"] in {e["name"] for e in b["end_to_end"]}


def test_split_metric_shares_its_base_reader():
    base = run_cell.reader_path("hdep.write_s")
    assert run_cell.reader_path("hdep.write_s.lod") == base
    assert base.endswith(os.path.join("metrics", "hdep.write_s.py"))
    assert not os.path.isfile(run_cell.reader_path("hdep.no_such.lod"))
