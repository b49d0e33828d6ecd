"""Model configuration files and the weights the benchmark makes.

A configuration file (``bench/configs/<name>.json``) holds the published
Hugging Face keys of the model as it is run, the keys changed from the
source (``reduced``), the sizes assumed, and the engine settings of the
deployment it stands for. Its ``model_type``, the published key, names the
architecture's module (``bench/arch/<model_type>.py``, ``load_arch``):
the weight tree, the program's ``ArchConfig``, the reference and the work
counts are that module's.

The weights are made here, on the device, in one jitted call from the run
seed and in bfloat16, the type they are served in. They are laid out as
the program's parameter tree expects them, so the same arrays feed the
program and the reference (``bench/reference.py``), which never sees
anything the program made.
"""
from __future__ import annotations

import importlib.util
import json
import math
import re
from pathlib import Path
from typing import Dict

import numpy as np

VOCAB_PAD = 256      # the program pads its embedding rows to this multiple
MODEL_TYPE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_-]*$")


def load_config(path) -> Dict:
    return json.loads(Path(path).read_text())


def load_arch(root: Path, cfg: Dict, file: str):
    """The module of the architecture that the configuration ``cfg``
    (read from ``file``) names by ``model_type``:
    ``<root>/bench/arch/<model_type>.py``, loaded by its path. Raises
    ``ValueError`` naming ``file`` where the key is absent, names no
    module, or the module lacks a function of the interface."""
    from bench.arch import INTERFACE
    mt = cfg.get("model_type")
    if not isinstance(mt, str) or not MODEL_TYPE.match(mt):
        raise ValueError(f"{file}: \"model_type\" must name the "
                         f"architecture's module, a name; got {mt!r}")
    path = Path(root) / "bench" / "arch" / f"{mt}.py"
    if not path.is_file():
        raise ValueError(f"{file}: model_type {mt!r} has no module "
                         f"bench/arch/{mt}.py")
    spec = importlib.util.spec_from_file_location(f"bench.arch.{mt}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [f for f in INTERFACE if not callable(getattr(mod, f, None))]
    if missing:
        raise ValueError(f"{file}: bench/arch/{mt}.py lacks {missing}")
    return mod


def padded_vocab(cfg: Dict) -> int:
    v = cfg["vocab_size"]
    return -(-v // VOCAB_PAD) * VOCAB_PAD


def weight_seed(seed: int) -> int:
    """31-bit key for ``jax.random`` from a seed of any size."""
    return int(np.random.SeedSequence([int(seed), 0]).generate_state(1)[0]
               & 0x7FFFFFFF)


def make_weights(arch, cfg: Dict, seed: int, dtype=None):
    """Random weights of ``arch``'s tree from ``seed``: one jitted call,
    made on the default device in ``dtype`` (bfloat16 unless given).
    Matrices are normal with variance 1/``arch.fan_in``; norm scales are
    1 + 0.1 N(0, 1)."""
    import jax
    import jax.numpy as jnp
    dtype = jnp.bfloat16 if dtype is None else dtype
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        arch.param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))

    def make(key):
        keys = jax.random.split(key, len(paths))
        out = []
        for (path, shape), k in zip(paths, keys):
            if len(shape) == 1:
                out.append((1.0 + 0.1 * jax.random.normal(k, shape,
                                                          jnp.float32)
                            ).astype(dtype))
            else:
                scale = 1.0 / math.sqrt(arch.fan_in(path[-1].key, shape))
                out.append(jax.random.normal(k, shape, dtype)
                           * jnp.asarray(scale, dtype))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(make)(jax.random.PRNGKey(weight_seed(seed)))
