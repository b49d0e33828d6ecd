"""CLI launcher smoke tests (serve.py / train.py run end-to-end on CPU)."""
import jax
import pytest

from repro.launch.serve import main as serve_main
from repro.launch.train import main as train_main


def test_serve_cli(capsys):
    serve_main(["--arch", "qwen3-4b", "--reduced", "--num-requests", "4",
                "--qps", "20", "--max-len", "256", "--token-budget", "64"])
    captured = capsys.readouterr()
    assert '"num_finished": 4' in captured.out
    # clamping is no longer silent: the truncation is reported on stderr
    assert "warning:" in captured.err and "clamping" in captured.err
    # sharded runs are diagnosable from the summary alone; the default is
    # the degenerate 1-device mesh with zero collectives
    assert '"mesh"' in captured.out
    assert '"collectives_per_iteration": 0' in captured.out
    assert '"tp": 1' in captured.out


def test_serve_cli_stream(capsys):
    """--stream serves through AsyncDuetEngine: JSONL token/finish events
    followed by a summary that carries the dispatch/sync counters."""
    import json
    serve_main(["--arch", "qwen3-4b", "--reduced", "--num-requests", "3",
                "--qps", "20", "--max-len", "128", "--token-budget", "32",
                "--stream"])
    out = capsys.readouterr().out
    events = [json.loads(line) for line in out.splitlines()
              if line.startswith('{"event"')]
    assert sum(1 for e in events if e["event"] == "finish") == 3
    assert any(e["event"] == "token" for e in events)
    assert '"num_finished": 3' in out
    assert '"dispatch_stats"' in out and '"host_syncs"' in out


def test_serve_cli_slab_mode(capsys):
    """--no-paged routes through the slab oracle engine."""
    serve_main(["--arch", "qwen3-4b", "--reduced", "--num-requests", "3",
                "--qps", "20", "--max-len", "128", "--token-budget", "32",
                "--no-paged"])
    assert '"num_finished": 3' in capsys.readouterr().out


def test_train_cli(capsys):
    train_main(["--arch", "xlstm-350m", "--reduced", "--steps", "6",
                "--batch", "2", "--seq", "32", "--model", "1"])
    out = capsys.readouterr().out
    assert "step    0" in out and "loss" in out


def test_mesh_helpers():
    from repro.launch.mesh import data_axes, make_test_mesh, \
        split_duet_submeshes
    mesh = make_test_mesh(1, 1)
    assert mesh.shape == {"data": 1, "model": 1}
    assert data_axes(mesh) == ("data",)
    # duet sub-mesh splitting needs >1 model column: a clear ValueError,
    # not a bare assert (callers branch on it to fall back to kernel-grid
    # partitioning)
    with pytest.raises(ValueError, match="decode_chips"):
        split_duet_submeshes(mesh, 1)


def test_make_test_mesh_validates_device_count():
    """Oversubscribed shapes name the fix (forced host devices) instead of
    dying inside jax.make_mesh's reshape. Multi-device split geometry is
    covered in tests/test_sharded_serving.py (subprocess, 8 devices)."""
    import jax
    too_many = jax.device_count() + 1
    with pytest.raises(ValueError,
                       match="xla_force_host_platform_device_count"):
        from repro.launch.mesh import make_test_mesh
        make_test_mesh(too_many, 1)
    with pytest.raises(ValueError, match="positive"):
        from repro.launch.mesh import make_test_mesh
        make_test_mesh(0, 1)


def test_compile_cache_dir(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code; without
    it an accelerator caches in the checkout's one fixed directory, and the
    CPU backend caches nothing."""
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert compile_cache.enable_compile_cache() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir == before

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache.enable_compile_cache() is None
    monkeypatch.setattr(compile_cache.jax, "default_backend", lambda: "tpu")
    try:
        got = compile_cache.enable_compile_cache()
        assert got == str(compile_cache.CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == got
        assert compile_cache.CACHE_DIR.parent.joinpath("chip_smoke.py") \
            .exists()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
