"""BENCHMARK.json resolves by name to files, and a cell, or an
architecture, is added by files and entries alone."""
import dataclasses
import json
import re
import shutil
from pathlib import Path
from types import SimpleNamespace

import jax.numpy as jnp
import pytest

from bench import loadgen
from bench.model import load_arch, load_config
from bench.run import Cell
from bench.tests.tiny import ROOT, small_sample, tiny_config, tiny_mix

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


# written out, not imported from bench.arch, so that a narrowed interface
# fails here
INTERFACE = ("param_shapes", "fan_in", "arch_config", "logits_at",
             "matmul_flops", "attn_flops", "attn_bytes", "head_flops",
             "step_weight_bytes")


def checkout(tmp_path) -> Path:
    """A scratch checkout: ``bench/`` and BENCHMARK.json."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root)
    return root


def test_every_config_names_an_architecture_with_the_interface():
    s = spec()
    for c in s["configs"]:
        cfg = load_config(ROOT / c["file"])
        path = ROOT / "bench/arch" / f"{cfg['model_type']}.py"
        assert path.is_file(), (c["file"], cfg["model_type"])
        arch = load_arch(ROOT, cfg, c["file"])
        for f in INTERFACE:
            assert callable(getattr(arch, f, None)), (path, f)
    for w in s["workloads"]:
        cell = Cell.load(ROOT, w["name"])
        assert Path(cell.arch.__file__) == ROOT / "bench/arch" / (
            cell.config["model_type"] + ".py")


@pytest.mark.parametrize("model_type", [None, "nonesuch", "../run"])
def test_a_config_without_a_known_model_type_is_refused_by_name(
        tmp_path, model_type):
    root = checkout(tmp_path)
    file = "bench/configs/qwen3-4b.json"
    cfg = load_config(root / file)
    if model_type is None:
        del cfg["model_type"]
    else:
        cfg["model_type"] = model_type
    (root / file).write_text(json.dumps(cfg))
    with pytest.raises(ValueError, match=re.escape(file)):
        Cell.load(root, "qwen3-4b.decode2k-c32")
    # the other configuration still resolves
    assert Cell.load(root, "qwen3-8b.chat2k-c32").arch.__name__ \
        == "bench.arch.qwen3"


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


def with_unit_qk_norms(w):
    """Weights without q/k-norm scales, given scales of 1: the nearest
    weights that Qwen3's reference can read."""
    def layer(p):
        dh = p["attn"]["w_q"].shape[-1]
        one = jnp.ones((dh,), p["attn"]["w_q"].dtype)
        return dict(p, attn=dict(p["attn"], q_norm=one, k_norm=one))
    return dict(w, layers=[layer(p) for p in w["layers"]])


def test_a_new_architecture_needs_new_files_and_entries_only(
        tmp_path, monkeypatch):
    from bench import run
    root = checkout(tmp_path)
    # an architecture, its configuration and a mix: new files
    shutil.copy(ROOT / "bench/tests/toy_gqa.py", root / "bench/arch")
    cfg = dict(tiny_config(), model_type="toy_gqa")
    (root / "bench/configs/toy-gqa-tiny.json").write_text(json.dumps(cfg))
    mix = dataclasses.asdict(tiny_mix())
    del mix["name"]
    (root / "bench/traffic/tiny-c3.json").write_text(json.dumps(mix))
    # and entries
    s = spec()
    s["configs"].append({"name": "toy-gqa-tiny", "source": "test",
                         "file": "bench/configs/toy-gqa-tiny.json",
                         "reduced": [], "why": "test"})
    s["workloads"].append({"name": "toy-gqa.tiny-c3",
                           "config": "toy-gqa-tiny", "traffic": "tiny-c3",
                           "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(s))

    cell = Cell.load(root, "toy-gqa.tiny-c3")
    assert Path(cell.arch.__file__) == root / "bench/arch/toy_gqa.py"
    assert "q_norm" not in cell.arch.param_shapes(cfg)["layers"][0]["attn"]
    assert not cell.arch.arch_config(cfg, "toy").qk_norm
    small_sample(monkeypatch)
    seed = 2**34 + 9
    out = run.run(cell, seed, 1.0, False, require_chip=False)
    assert out["correct"], out["checked"]

    # the same run judged by Qwen3's reference is not correct: the cell's
    # own module decided it
    qwen3 = load_arch(ROOT, tiny_config(), "qwen3")
    judge = {f: getattr(cell.arch, f) for f in INTERFACE}
    judge["logits_at"] = lambda w, *a, **kw: qwen3.logits_at(
        with_unit_qk_norms(w), *a, **kw)
    out = run.run(dataclasses.replace(cell, arch=SimpleNamespace(**judge)),
                  seed, 1.0, False, require_chip=False)
    assert not out["correct"], out["checked"]
    gap = out["checked"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
