"""The correctness limit's readings at a tiny size on the CPU: the program
passes the run's own verdict and the fp8 control fails it."""
from bench import limits
from bench.tests.tiny import small_sample, tiny_cell


def test_program_passes_and_control_fails_the_runs_verdict(monkeypatch):
    small_sample(monkeypatch)
    monkeypatch.setattr(limits, "SAMPLE_TOKENS", 64)
    monkeypatch.setattr(limits, "SAMPLE_REQUESTS", 12)
    rows = limits.readings(tiny_cell(), [2**33 + 7, 19], 1.0,
                           require_chip=False)
    assert len(rows) == 2
    for r in rows:
        assert r["tokens"] >= 64
        assert r["program_correct"], r
        assert not r["control_correct"], r
        assert r["control"] > r["program"]
