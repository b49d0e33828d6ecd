"""A whole run at a tiny size on the CPU, the chip check skipped: sound, it
reads ``correct``; with the timed path broken underneath, it does not."""
import pytest

import repro.serving.async_engine as ae
from bench import run as bench_run
from bench.tests.tiny import small_sample, tiny_cell

SEED = 2**33 + 41


def broken(fault):
    """``make_superiter_fn`` whose programs break the served path."""
    make = ae.make_superiter_fn

    def wrapped(*a, **kw):
        prog = make(*a, **kw)

        def run(*args):
            out = list(prog(*args))
            if fault == "token":       # a served token altered where made
                out[0] = (out[0] + 1) % 512
            elif fault == "state":     # the KV pools returned unchanged
                out[4] = args[1]
            return tuple(out)
        return run
    return wrapped


@pytest.fixture(scope="module")
def sound():
    with pytest.MonkeyPatch.context() as mp:
        small_sample(mp)
        return bench_run.run(tiny_cell(), SEED, 1.0, False,
                             require_chip=False)


def test_sound_run_is_correct_and_reports_its_metrics(sound):
    assert sound["correct"], sound["checked"]
    assert sound["failed"] == 0 and sound["attempted"] > 0
    assert {"tbt_mean_ms", "tbt_p99_ms", "output_tokens_per_s",
            "setup_s"} <= set(sound["metrics"])
    assert list(sound)[-1] == "checked"
    assert sound["device"]["platform"] == "cpu"


@pytest.mark.parametrize("fault", ["token", "state"])
def test_broken_path_is_not_correct(monkeypatch, fault):
    monkeypatch.setattr(ae, "make_superiter_fn", broken(fault))
    small_sample(monkeypatch)
    out = bench_run.run(tiny_cell(), SEED, 1.0, False, require_chip=False)
    assert not out["correct"], out["checked"]
    gap = out["checked"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
