"""A tiny Qwen3 cell for CPU tests: the published family at toy widths."""
from __future__ import annotations

import copy
from functools import cache
from pathlib import Path

from bench import loadgen
from bench.model import load_arch, load_config

ROOT = Path(__file__).resolve().parents[2]


def tiny_config(layers: int = 2) -> dict:
    cfg = copy.deepcopy(load_config(ROOT / "bench/configs/qwen3-8b-18l.json"))
    cfg.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, num_hidden_layers=layers,
               vocab_size=512)
    cfg["engine"].update(max_slots=3, token_budget=48, kv_pool_tokens=1024)
    # over 64 compared tokens on the CPU, seeds 1-6: the program's widest
    # gap reads at most 0.020, the fp8 control's at least 0.118
    # (bench/limits.py); the broken paths of test_bench_faults read above 1
    cfg["check"]["max_logit_gap"] = 0.05
    return cfg


@cache
def tiny_arch():
    """The architecture module ``tiny_config`` names (``qwen3``), loaded
    once, so the reference's programs compile once per test process."""
    return load_arch(ROOT, tiny_config(), "tiny")


def small_sample(mp):
    """Compare the few tokens a tiny run serves (``mp``: a MonkeyPatch)."""
    from bench import run
    mp.setattr(run, "SAMPLE_TOKENS", 64)
    mp.setattr(run, "SAMPLE_REQUESTS", 12)


def tiny_mix() -> loadgen.Mix:
    return loadgen.Mix(
        name="tiny", clients=3, deck=8,
        prompt=loadgen.LengthSpec(mean=30, cv=0.8, min=8, max=64),
        output=loadgen.LengthSpec(mean=6, cv=0.5, min=2, max=12))


def tiny_cell():
    from bench.run import Cell
    e2e = [{"name": n, "unit": u} for n, u in (
        ("ttft_mean_s", "s"), ("tbt_mean_ms", "ms"), ("tbt_p99_ms", "ms"),
        ("output_tokens_per_s", "tokens/s"), ("setup_s", "s"))]
    pl = [{"name": n, "unit": "%"} for n in (
        "compiles_in_window", "duet_share", "prefix_hit_rate", "step_mfu",
        "device_idle_share")]
    return Cell(name="tiny", chips=1, config=tiny_config(),
                arch=tiny_arch(), config_name="tiny", mix=tiny_mix(),
                end_to_end=e2e, per_layer=pl,
                metrics_dir=ROOT / "bench" / "metrics")
