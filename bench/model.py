"""Model configuration files and the weights the benchmark makes.

A configuration file (``bench/configs/<name>.json``) holds the published
Hugging Face keys of a dense Qwen3-style decoder as it is run, the keys
changed from the source (``reduced``), the sizes assumed, and the engine
settings of the deployment it stands for.

The weights are made here, on the device, in one jitted call from the run
seed and in bfloat16, the type they are served in. They are laid out as
the program's parameter tree expects them, so the same arrays feed the
program and the reference (``bench/reference.py``), which never sees
anything the program made.
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from typing import Dict

import numpy as np

VOCAB_PAD = 256      # the program pads its embedding rows to this multiple


def load_config(path) -> Dict:
    return json.loads(Path(path).read_text())


def padded_vocab(cfg: Dict) -> int:
    v = cfg["vocab_size"]
    return -(-v // VOCAB_PAD) * VOCAB_PAD


def param_shapes(cfg: Dict) -> Dict:
    """Parameter tree of shapes: {name: shape}, with ``layers`` a list."""
    D, H, G = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    dh, F = cfg["head_dim"], cfg["intermediate_size"]
    layer = {
        "attn_norm": (D,),
        "attn": {"w_q": (D, H, dh), "w_k": (D, G, dh), "w_v": (D, G, dh),
                 "w_o": (H, dh, D), "q_norm": (dh,), "k_norm": (dh,)},
        "mlp_norm": (D,),
        "mlp": {"w_gate": (D, F), "w_up": (D, F), "w_down": (F, D)},
    }
    tree = {"embedding": (padded_vocab(cfg), D), "final_norm": (D,),
            "layers": [layer] * cfg["num_hidden_layers"]}
    if not cfg["tie_word_embeddings"]:
        tree["w_out"] = (padded_vocab(cfg), D)
    return tree


def weight_seed(seed: int) -> int:
    """31-bit key for ``jax.random`` from a seed of any size."""
    return int(np.random.SeedSequence([int(seed), 0]).generate_state(1)[0]
               & 0x7FFFFFFF)


def make_weights(cfg: Dict, seed: int, dtype=None):
    """Random weights from ``seed``: one jitted call, made on the default
    device in ``dtype`` (bfloat16 unless given). Matrices are normal with
    variance 1/fan_in; norm scales are 1 + 0.1 N(0, 1)."""
    import jax
    import jax.numpy as jnp
    dtype = jnp.bfloat16 if dtype is None else dtype
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))

    def fan_in(name, shape):
        if name == "w_o":                       # (H, dh, D)
            return shape[0] * shape[1]
        if name in ("embedding", "w_out"):      # (V, D)
            return shape[1]
        return shape[0]

    def make(key):
        keys = jax.random.split(key, len(paths))
        out = []
        for (path, shape), k in zip(paths, keys):
            if len(shape) == 1:
                out.append((1.0 + 0.1 * jax.random.normal(k, shape,
                                                          jnp.float32)
                            ).astype(dtype))
            else:
                scale = 1.0 / math.sqrt(fan_in(path[-1].key, shape))
                out.append(jax.random.normal(k, shape, dtype)
                           * jnp.asarray(scale, dtype))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(make)(jax.random.PRNGKey(weight_seed(seed)))


def arch_config(cfg: Dict, name: str):
    """The program's ``ArchConfig`` for a configuration file."""
    from repro.configs.base import ArchConfig
    return ArchConfig(
        name=name, family="dense", source=cfg["source"],
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        qk_norm=True, rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        activation=cfg["hidden_act"])

