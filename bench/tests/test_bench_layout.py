"""Every BENCHMARK.json entry resolves to its files, and the file keeps
to the shapes the benchmark's contract fixes."""
import json
import re
from pathlib import Path

import pytest

from bench import archs
from bench import check as CK
from bench import traffic as T

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert BENCH["command"][1] == "bench/run.py"
    assert (ROOT / BENCH["command"][1]).is_file()
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_resolves(conf):
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(conf["name"])
    assert conf["file"].startswith("bench/configs/")
    data = json.loads((ROOT / conf["file"]).read_text())
    assert data["name"] == conf["name"] and data["source"] == conf["source"]
    assert sorted(conf["reduced"]) == sorted(data["reduced"])
    arch = archs.of(data)                 # bench/archs/<bench_arch>.py
    assert (ROOT / "bench" / "archs" / f"{data['bench_arch']}.py").is_file()
    s = arch.spec_of(data)                # every size the harness reads
    assert s["V"] == data["vocab_size"] and s["dtype"] == data["torch_dtype"]
    assert all(0 < v for v in CK.limits(data).values())


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_workload_resolves(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    assert cell["config"] in {c["name"] for c in BENCH["configs"]}
    mix = T.load_mix(cell["traffic"], cell["name"])
    assert mix["loop"] in ("open", "closed")
    e2e = [m for m in BENCH["end_to_end"]
           if "workloads" not in m or cell["name"] in m["workloads"]]
    names = {m["name"] for m in e2e}
    assert "setup_s" in names and len(names) >= 2
    assert any("workloads" not in m or cell["name"] in m["workloads"]
               for m in BENCH["per_layer"])


def test_metrics():
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= cells
    for m in BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in e2e
        for w in m["workloads"]:
            assert w in cells
            assert w in e2e[m["moves"]].get("workloads", cells)
        assert 1 <= len(m["layer"]) <= 200


def test_traffic_files_are_data():
    for cell in BENCH["workloads"]:
        assert (ROOT / "bench" / "traffic" / f"{cell['traffic']}.json").is_file()
