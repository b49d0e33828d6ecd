"""The serving kernels compile for a TPU v5e at qwen3-4b widths.

Each kernel of the main serving path is compiled ahead of time, in bf16,
for one chip of a described ``v5e:2x2`` topology (no chip needed: the TPU
compiler is installed with libtpu). Mosaic refuses what interpret mode
accepts — unaligned blocks, unsupported vector reshapes — so these compiles
catch a kernel the chip would reject before any chip time is spent. The
topology is described inside a fixture, never at import, and the tests
compile in this process (the TPU library is held by one process at a time).
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops

# qwen3-4b attention: 32 query heads over 8 KV heads of width 128
H, G, DH = 32, 8, 128
PAGE, PAGES, SLOTS, TABLE = 16, 1025, 8, 128


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # described-chip executables can be cached but never read back here
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _paged_args(sh):
    return (_spec(sh, (SLOTS, H, DH)), _spec(sh, (PAGES, PAGE, G, DH)),
            _spec(sh, (PAGES, PAGE, G, DH)),
            _spec(sh, (SLOTS, TABLE), jnp.int32),
            _spec(sh, (SLOTS,), jnp.int32))


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_paged_decode_compiles(one_chip):
    _assert_kernel(lambda *a: ops.paged_decode(*a, interpret=False),
                   *_paged_args(one_chip))


@pytest.mark.parametrize("num_splits", [2, 8])
def test_paged_decode_splitkv_compiles(one_chip, num_splits):
    _assert_kernel(lambda *a: ops.paged_decode_splitkv(
        *a, num_splits=num_splits, interpret=False), *_paged_args(one_chip))


@pytest.mark.parametrize("block_q", [1, 8])
def test_duet_attention_paged_compiles(one_chip, block_q):
    # the engine's fused step: 8 decode rows + a 256-token chunk, one row
    # per tile (block_q=1) as Model.duet_step_paged dispatches it
    rows = SLOTS + 256
    sh = one_chip
    _assert_kernel(
        lambda *a: ops.duet_attention_paged(*a, block_q=block_q,
                                            interpret=False),
        _spec(sh, (rows, H, DH)), _spec(sh, (rows, 1), jnp.int32),
        _spec(sh, (rows // block_q,), jnp.int32),
        _spec(sh, (PAGES, PAGE, G, DH)), _spec(sh, (PAGES, PAGE, G, DH)),
        _spec(sh, (rows, TABLE), jnp.int32))


def test_flash_prefill_compiles(one_chip):
    sh = one_chip
    _assert_kernel(lambda *a: ops.flash_prefill(*a, interpret=False),
                   _spec(sh, (1, 512, H, DH)), _spec(sh, (1, 512, G, DH)),
                   _spec(sh, (1, 512, G, DH)))


def test_paged_decode_superiteration_names_its_kernel(one_chip, monkeypatch):
    """The engine's depth-1 paged decode super-iteration, at qwen3-4b
    widths with 2 layers and 32 slots over 128-page tables: each layer's
    attention custom-call carries the kernel's own name, which a device
    trace reports as the op family, and no instruction is named after the
    scan body that holds it (``closed_call``)."""
    import dataclasses
    import re

    from repro.configs import get_config
    from repro.core.lookahead import make_superiter_fn
    from repro.models import Model

    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    cfg = dataclasses.replace(get_config("qwen3-4b"), num_layers=2,
                              block_pattern=())
    model = Model(cfg, attn_kernel=True)
    B, sh = 32, one_chip

    def specs(tree, dtype=None):
        return jax.tree.map(
            lambda x: _spec(sh, x.shape, dtype or x.dtype), tree)

    params = specs(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                   jnp.bfloat16)
    pool = _spec(sh, (PAGES, PAGE, G, DH))
    state = specs(jax.eval_shape(
        lambda: model.init_state_cache(B, jnp.bfloat16)))
    i32 = jnp.int32
    args = (params, [(pool, pool)] * cfg.num_layers, state,
            _spec(sh, (B, 1), i32), _spec(sh, (B,), i32),
            _spec(sh, (B, TABLE), i32), _spec(sh, (2,), jnp.uint32),
            _spec(sh, (B,), jnp.bool_), _spec(sh, (1, 1), i32),
            _spec(sh, (1, 1), i32), _spec(sh, (), i32), _spec(sh, (), i32),
            _spec(sh, (), i32))
    prog = make_superiter_fn(model, 1, paged=True, donate=False)
    text = prog.lower(*args).compile().as_text()
    calls = re.findall(r"%([\w.-]+) = \S+ custom-call\(.*"
                       r"custom_call_target=\"tpu_custom_call\"", text)
    assert len(calls) == cfg.num_layers
    assert all(re.fullmatch(r"paged_decode(_splitkv)?\.\d+", c)
               for c in calls), calls
    assert not re.search(r"%closed_call[.\d]* = ", text)
