"""Qwen3 through its architecture module (``bench/arch/qwen3.py``) gives
what the harness gave before the architecture had a module of its own, bit
for bit: the weight tree with its names, shapes and flatten order, the
weights a seed makes, the reference's logits and the work counts. The
literals were computed by the harness as it was before the move."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import model as bm
from bench import work
from bench.tests.tiny import ROOT, tiny_arch, tiny_config

LAYER0 = ("['layers'][0]['attn']['k_norm']", "['layers'][0]['attn']['q_norm']",
          "['layers'][0]['attn']['w_k']", "['layers'][0]['attn']['w_o']",
          "['layers'][0]['attn']['w_q']", "['layers'][0]['attn']['w_v']",
          "['layers'][0]['attn_norm']", "['layers'][0]['mlp']['w_down']",
          "['layers'][0]['mlp']['w_gate']", "['layers'][0]['mlp']['w_up']",
          "['layers'][0]['mlp_norm']")

PINNED = {
    "qwen3-8b-18l": {
        "leaves": 201,
        "sha256": "7999fa22602a6f0f73d755a0dc80c4ff"
                  "1d6c44abd38b1594a0508dda63ae408f",
        "first": [("['embedding']", (152064, 4096)),
                  ("['final_norm']", (4096,))]
        + list(zip(LAYER0, [(128,), (128,), (4096, 8, 128),
                            (32, 128, 4096), (4096, 32, 128), (4096, 8, 128),
                            (4096,), (12288, 4096), (4096, 12288),
                            (4096, 12288), (4096,)])),
        "last": ("['w_out']", (152064, 4096)),
        "decode_flops": 8485339136.0,
        "prefill_flops": 4997570035712.0,
        "attn_bytes": 73728000.0,
        "step_weight_bytes": 8190427136.0,
    },
    "qwen3-4b": {
        "leaves": 398,
        "sha256": "7ccfb1ea84b09e59ed64f1627c9d2d62"
                  "58b4b6ae8bb1eb5fa86ffd0a78a81652",
        "first": [("['embedding']", (152064, 2560)),
                  ("['final_norm']", (2560,))]
        + list(zip(LAYER0, [(128,), (128,), (2560, 8, 128),
                            (32, 128, 2560), (2560, 32, 128), (2560, 8, 128),
                            (2560,), (9728, 2560), (2560, 9728),
                            (2560, 9728), (2560,)])),
        "last": ("['layers'][35]['mlp_norm']", (2560,)),
        "decode_flops": 8634368000.0,
        "prefill_flops": 5355996446720.0,
        "attn_bytes": 147456000.0,
        "step_weight_bytes": 8044544000.0,
    },
}

TINY_WEIGHTS_SHA256 = ("bd3706a98bf6493c4f11abece6f088d0"
                       "66000ebb7738cba95f00e3fc9ee58ab5")
# reference logits of the tiny weights (seed 7, float32) at three
# (position, token) cells of a 90-token sequence, in float32 and fp8
TINY_LOGITS = {
    "float32": (-0.434487909078598, -0.7071936726570129, -2.1584489345550537),
    "fp8": (-0.5511907339096069, -0.6999027729034424, -2.1780807971954346),
}
CELLS = ((0, 0), (45, 100), (89, 511))


def config(name):
    file = f"bench/configs/{name}.json"
    cfg = bm.load_config(ROOT / file)
    return cfg, bm.load_arch(ROOT, cfg, file)


def leaves(arch, cfg):
    paths, _ = jax.tree_util.tree_flatten_with_path(
        arch.param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    return [(jax.tree_util.keystr(p), tuple(s)) for p, s in paths]


@pytest.mark.parametrize("name", sorted(PINNED))
def test_weight_tree_is_as_before(name):
    cfg, arch = config(name)
    want = PINNED[name]
    got = leaves(arch, cfg)
    assert len(got) == want["leaves"]
    assert got[:len(want["first"])] == want["first"]
    assert got[-1] == want["last"]
    assert hashlib.sha256(repr(got).encode()).hexdigest() == want["sha256"]


@pytest.mark.parametrize("name", sorted(PINNED))
def test_work_counts_are_as_before(name):
    cfg, arch = config(name)
    want = PINNED[name]
    assert work.decode_flops(arch, cfg, 1000) == want["decode_flops"]
    assert work.prefill_flops(arch, cfg, 700, start=300) \
        == want["prefill_flops"]
    assert arch.attn_bytes(cfg, 1000) == want["attn_bytes"]
    assert arch.step_weight_bytes(cfg) == want["step_weight_bytes"]


@pytest.fixture(scope="module")
def tiny_weights():
    return bm.make_weights(tiny_arch(), tiny_config(), 7, dtype=jnp.float32)


def test_tiny_weights_are_as_before(tiny_weights):
    h = hashlib.sha256()
    for a in jax.tree.leaves(tiny_weights):
        h.update(np.asarray(a).tobytes())
    assert h.hexdigest() == TINY_WEIGHTS_SHA256


@pytest.mark.parametrize("precision", sorted(TINY_LOGITS))
def test_tiny_reference_logits_are_as_before(tiny_weights, precision):
    cfg = tiny_config()
    toks = np.random.default_rng(3).integers(0, cfg["vocab_size"], 90)
    lg = np.zeros((90, cfg["vocab_size"]), np.float32)
    for sl, part in tiny_arch().logits_at(tiny_weights, cfg,
                                          toks.astype(np.int32),
                                          np.arange(90), precision=precision):
        lg[sl] = np.asarray(part)
    got = [float(lg[i, j]) for i, j in CELLS]
    # float32 on the CPU: the same program gives the same bits; the room
    # is for a host whose matmul splits its sums another way
    assert got == pytest.approx(TINY_LOGITS[precision], rel=1e-6, abs=1e-6)
