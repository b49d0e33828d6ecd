"""A dense grouped-query decoder without per-head q/k norm
(``model_type`` "toy_gqa"): Qwen3's layer less ``q_norm`` and ``k_norm``, a
variant the program runs (``ArchConfig.qk_norm`` False, no norm weights in
the tree). Tests copy it to ``bench/arch/toy_gqa.py`` of a scratch checkout
to show that a new architecture arrives as new files only. It shares with
``qwen3`` what is the same: the init scales, the rotary embedding, the
output head and the work counts (the norm scales are no matrix products).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.arch import qwen3
from bench.arch.qwen3 import (attn_bytes, attn_flops, fan_in,  # noqa: F401
                              head_flops, matmul_flops, step_weight_bytes)
from bench.reference import (Q_BLOCK, ROW_BLOCK, act_dtype, bucket, rms,
                             weight_as)


def param_shapes(cfg: Dict) -> Dict:
    tree = qwen3.param_shapes(cfg)
    layer = dict(tree["layers"][0])
    layer["attn"] = {k: v for k, v in layer["attn"].items()
                     if k not in ("q_norm", "k_norm")}
    return dict(tree, layers=[layer] * cfg["num_hidden_layers"])


def arch_config(cfg: Dict, name: str):
    return dataclasses.replace(qwen3.arch_config(cfg, name), qk_norm=False)


@partial(jax.jit, static_argnames=("eps", "theta", "precision"))
def _layer(x, p, *, eps, theta, precision):
    """One decoder layer over x (T, D)."""
    dt = act_dtype(precision)
    a = p["attn"]
    wq, wk, wv, wo = (weight_as(a[n], precision)
                      for n in ("w_q", "w_k", "w_v", "w_o"))
    H, dh, G = wq.shape[1], wq.shape[2], wk.shape[1]
    T = x.shape[0]
    h = rms(x, p["attn_norm"], eps)
    q = jnp.einsum("td,dhe->the", h, wq, preferred_element_type=jnp.float32)
    k = jnp.einsum("td,dge->tge", h, wk, preferred_element_type=jnp.float32)
    v = jnp.einsum("td,dge->tge", h, wv, preferred_element_type=jnp.float32)
    q = qwen3._rope(q.astype(dt), theta)
    k = qwen3._rope(k.astype(dt), theta)
    v = v.astype(dt)
    qb = q.reshape(T // Q_BLOCK, Q_BLOCK, G, H // G, dh)
    kpos = jnp.arange(T)

    def block(args):
        i, qi = args
        s = jnp.einsum("qgre,tge->grqt", qi, k,
                       preferred_element_type=jnp.float32) / math.sqrt(dh)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, None, None, :] <= qpos[None, None, :, None],
                      s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1).astype(dt)
        return jnp.einsum("grqt,tge->qgre", pr, v,
                          preferred_element_type=jnp.float32).astype(dt)

    o = jax.lax.map(block, (jnp.arange(T // Q_BLOCK), qb)).reshape(T, H, dh)
    x = x + jnp.einsum("the,hed->td", o, wo,
                       preferred_element_type=jnp.float32).astype(dt)
    m = p["mlp"]
    h = rms(x, p["mlp_norm"], eps)
    g = jnp.einsum("td,df->tf", h, weight_as(m["w_gate"], precision),
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("td,df->tf", h, weight_as(m["w_up"], precision),
                   preferred_element_type=jnp.float32)
    f = (jax.nn.silu(g) * u).astype(dt)
    return x + jnp.einsum("tf,fd->td", f, weight_as(m["w_down"], precision),
                          preferred_element_type=jnp.float32).astype(dt)


def logits_at(weights, cfg: Dict, tokens: np.ndarray,
              positions: np.ndarray, precision: str = "float32"):
    """Yields (row slice, logits) blocks at ``positions`` of ``tokens``."""
    eps = float(cfg["rms_norm_eps"])
    with jax.default_matmul_precision("highest"):
        ids = np.zeros(bucket(len(tokens)), np.int32)
        ids[:len(tokens)] = tokens
        x = jnp.take(weights["embedding"], jnp.asarray(ids), axis=0)
        x = x.astype(act_dtype(precision))
        for p in weights["layers"]:
            x = _layer(x, p, eps=eps, theta=float(cfg["rope_theta"]),
                       precision=precision)
        for s in range(0, len(positions), ROW_BLOCK):
            part = positions[s:s + ROW_BLOCK]
            idx = np.zeros(ROW_BLOCK, np.int32)
            idx[:len(part)] = part
            rows = jnp.take(x, jnp.asarray(idx), axis=0)
            lg = qwen3._head(rows, weights["final_norm"],
                             qwen3._out_weight(weights),
                             vocab=int(cfg["vocab_size"]), eps=eps,
                             precision=precision)
            yield slice(s, s + len(part)), lg[:len(part)]
