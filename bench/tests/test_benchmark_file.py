"""BENCHMARK.json keeps the shape its readers rely on, and every name in
it finds its files."""
import json
import re

import pytest

from bench import harness

B = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_top_level_keys():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["paths"] == ["bench"]
    assert B["command"] == ["python3", "bench/run.py"]
    assert 1 <= B["run_seconds"] <= 51


@pytest.mark.parametrize("entry", B["configs"], ids=lambda e: e["name"])
def test_config_entry(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"])
    assert entry["file"].startswith("bench/configs/")
    conf = json.loads((harness.ROOT / entry["file"]).read_text())
    assert conf["name"] == entry["name"]
    assert (harness.ROOT / entry["file"]).with_suffix(".py").is_file()


@pytest.mark.parametrize("entry", B["workloads"], ids=lambda e: e["name"])
def test_workload_entry(entry):
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(entry["name"]) and entry["chips"] in (1, 4)
    assert len(entry["why"]) <= 200
    assert entry["config"] in {c["name"] for c in B["configs"]}
    assert (harness.BENCH_DIR / "traffic" / f"{entry['traffic']}.json"
            ).is_file()
    reported = harness.cell_metrics(B, entry["name"], "end_to_end")
    names = {m["name"] for m in reported}
    assert "setup_s" in names and len(names) >= 2
    assert harness.cell_metrics(B, entry["name"], "per_layer")


@pytest.mark.parametrize("m", B["end_to_end"] + B["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entry(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    cells = {w["name"] for w in B["workloads"]}
    assert set(m.get("workloads", ())) <= cells
    if m in B["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    else:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        # every listed cell reports the end-to-end metric this one moves
        for cell in m["workloads"]:
            moved = {e["name"] for e in harness.cell_metrics(B, cell,
                                                             "end_to_end")}
            assert m["moves"] in moved
        base = m["name"].split(".")[0]
        assert (harness.BENCH_DIR / "metrics" / f"{base}.py").is_file()
