"""BENCHMARK.json resolves by name to files, and a cell is added by files
and entries alone."""
import json
import re
import shutil

from bench import loadgen
from bench.run import Cell
from bench.tests.tiny import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def spec():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_every_cell_config_mix_and_metric_has_its_file():
    s = spec()
    for c in s["configs"]:
        assert (ROOT / c["file"]).is_file(), c["file"]
        assert c["file"].startswith("bench/")
    for w in s["workloads"]:
        assert (ROOT / "bench/traffic" / f"{w['traffic']}.json").is_file()
        cell = Cell.load(ROOT, w["name"])
        assert cell.per_layer and cell.end_to_end
        for m in cell.per_layer:
            assert callable(cell.reader(m["name"]))
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    for m in s["per_layer"]:
        assert (ROOT / "bench/metrics" / f"{m['name']}.py").is_file()


def test_names_units_and_bounds_keep_to_the_format():
    s = spec()
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in s[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in s["end_to_end"] + s["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in s["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m
    layers = {m["layer"] for m in s["per_layer"]}
    e2e = {m["name"] for m in s["end_to_end"]}
    assert all(m["moves"] in e2e for m in s["per_layer"])
    assert layers
    cells = {w["name"] for w in s["workloads"]}
    for m in s["end_to_end"] + s["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells
    assert 1 <= s["run_seconds"] <= 51


def test_a_new_cell_needs_new_files_and_entries_only(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    s = spec()
    # a new mix, a new metric reader and a new cell: files and entries
    (root / "bench/traffic/short-c8.json").write_text(json.dumps({
        "clients": 8, "deck": 16,
        "prompt": {"mean": 64, "cv": 0.5, "min": 8, "max": 256},
        "output": {"mean": 32, "cv": 0.5, "min": 4, "max": 128}}))
    (root / "bench/metrics/host_syncs_per_step.py").write_text(
        "def read(r):\n"
        "    return r.counters['host_syncs'] / r.counters['super_iterations']\n")
    s["workloads"].append({"name": "qwen3-4b.short-c8", "config": "qwen3-4b",
                           "traffic": "short-c8", "chips": 1, "why": "test"})
    s["per_layer"].append({"name": "host_syncs_per_step", "unit": "syncs",
                           "better": "lower", "source": "program_counter",
                           "layer": "engine (serving/async_engine)",
                           "moves": "tbt_mean_ms",
                           "workloads": ["qwen3-4b.short-c8"]})
    (root / "BENCHMARK.json").write_text(json.dumps(s))
    cell = Cell.load(root, "qwen3-4b.short-c8")
    assert cell.mix.clients == 8 and len(cell.mix.pairs()) == 16
    assert "host_syncs_per_step" in [m["name"] for m in cell.per_layer]
    assert "ttft_mean_s" not in [m["name"] for m in cell.end_to_end]
    read = cell.reader("host_syncs_per_step")
    from bench.readings import Readings
    r = Readings(window_s=1.0, counters={"host_syncs": 3,
                                         "super_iterations": 4}, work={})
    assert read(r) == 0.75
    # the existing cells still resolve as before
    assert Cell.load(root, "qwen3-8b.chat2k-c32").mix == loadgen.Mix.load(
        ROOT / "bench/traffic/chat2k-c32.json")
