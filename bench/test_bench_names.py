"""Every name in BENCHMARK.json resolves to its file, and names, units and
the other fields keep to the characters and limits the harness allows."""
import json
import re
from pathlib import Path

import pytest

from bench import run as bench_run

ROOT = Path(__file__).resolve().parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
ENTRY_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source", "workloads"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves",
                  "workloads"},
}
CELLS = [w["name"] for w in BENCH["workloads"]]


def _text_ok(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_and_paths():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and (ROOT / p).is_dir()
    for word in BENCH["command"]:
        assert _text_ok(word) and not word.startswith("/") and ".." not in word
    assert (ROOT / BENCH["command"][1]).is_file()
    assert len(json.dumps(BENCH)) <= 64 * 1024


@pytest.mark.parametrize("group", sorted(ENTRY_KEYS))
def test_entries_names_and_units(group):
    names = [e["name"] for e in BENCH[group]]
    assert len(names) == len(set(names))
    for e in BENCH[group]:
        assert set(e) <= ENTRY_KEYS[group] and NAME.match(e["name"])
        if "unit" in e:
            assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
        for key in ("why", "layer", "source"):
            assert key not in e or _text_ok(e[key])
        for cell in e.get("workloads", []):
            assert cell in CELLS


def test_metric_sources_bounds_and_moves():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert _text_ok(m["layer"]) and m["moves"] in e2e
        moved = e2e[m["moves"]].get("workloads", CELLS)
        assert set(m["workloads"]) <= set(moved)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    spec = bench_run.load_spec(cell)
    w = spec["cell"]
    assert w["chips"] in (1, 4) and _text_ok(w["why"])
    assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    driver = bench_run.BENCH / "drivers" / f"{spec['traffic']['driver']}.py"
    assert driver.is_file()
    assert hasattr(bench_run.load_module(driver), "run")
    assert "limits" in spec["traffic"]
    reported = [m["name"] for m in spec["end_to_end"]]
    assert "setup_s" in reported and len(reported) >= 2
    assert spec["per_layer"]
    for m in spec["per_layer"]:
        mod = bench_run.load_module(bench_run.BENCH / "metrics"
                                    / f"{m['name']}.py")
        assert callable(mod.read)


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_files(conf):
    assert conf["file"].startswith("bench/") and _text_ok(conf["source"])
    data = json.loads((ROOT / conf["file"]).read_text())
    assert data["name"] == conf["name"]
    assert data["reduced"] == conf["reduced"] and len(conf["reduced"]) <= 16
    for key in conf["reduced"]:
        assert NAME.match(key) and key in data["published"]
        assert not re.search(r"(_dim|_rank|size|hidden|intermediate|width)$",
                             key)
    assert any(c["config"] == conf["name"] for c in BENCH["workloads"])
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))


def test_four_chip_cells_at_most_half():
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(CELLS) // 2)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
