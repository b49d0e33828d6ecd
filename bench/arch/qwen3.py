"""Qwen3, a dense decoder (``model_type`` "qwen3"): grouped-query attention
with per-head RMSNorm on queries and keys, a SwiGLU MLP, an output head
tied to the embedding where the configuration says so.

Reference: it follows the published Qwen3 description: RMSNorm before
attention and MLP, per-head RMSNorm on queries and keys, rotary embedding
in the rotate-half convention with the configured theta, grouped-query
causal attention with scale 1/sqrt(head_dim), SwiGLU MLP, a final RMSNorm
and an output head. Matrix products run at ``highest`` precision, so
float32 is float32 on the TPU too. Layers run one at a time and attention
in blocks of queries, so the reference fits beside the weights on one
chip.

Work, per token and layer: the projection and MLP matrix products (2
FLOPs per weight), and attention over ``c`` cached positions (QK^T and PV,
4 * H * dh * c FLOPs; the K and V of those positions read once, 2 * G * dh
* c elements). The output head costs 2 * D * V per token whose logits are
needed. Weights are read once per device step, whatever the batch.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.model import padded_vocab
from bench.reference import (Q_BLOCK, ROW_BLOCK, VOCAB_BLOCKS, act_dtype,
                             bucket, rms, weight_as)
from bench.work import BYTES


# ------------------------------------------------------------------ weights
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


def fan_in(name: str, shape) -> int:
    if name == "w_o":                       # (H, dh, D)
        return shape[0] * shape[1]
    if name in ("embedding", "w_out"):      # (V, D)
        return shape[1]
    return shape[0]


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


# ---------------------------------------------------------------- reference
def _rope(x, theta):
    """x (T, N, dh): rotate-half rotary embedding at positions 0..T-1."""
    T, _, dh = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :dh // 2], x32[..., dh // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


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
    q = _rope(rms(q.astype(dt), a["q_norm"], eps), theta)
    k = _rope(rms(k.astype(dt), a["k_norm"], eps), theta)
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

    o = jax.lax.map(block, (jnp.arange(T // Q_BLOCK), qb))
    o = o.reshape(T, H, dh)
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


@partial(jax.jit, static_argnames=("vocab", "eps", "precision"))
def _head(rows, final_norm, w_out, *, vocab, eps, precision):
    """Logits (R, vocab) of hidden rows (R, D), the output head taken in
    VOCAB_BLOCKS slices so no float32 copy of the whole head exists."""
    h = rms(rows, final_norm, eps)
    V, D = w_out.shape
    blocks = w_out.reshape(VOCAB_BLOCKS, V // VOCAB_BLOCKS, D)
    lg = jax.lax.map(
        lambda w: jnp.einsum("rd,vd->rv", h, weight_as(w, precision),
                             preferred_element_type=jnp.float32), blocks)
    return jnp.moveaxis(lg, 0, 1).reshape(rows.shape[0], V)[:, :vocab]


def _hidden(weights, cfg: Dict, tokens: np.ndarray, precision: str):
    """Final hidden states (T_padded, D) of a token sequence."""
    T = len(tokens)
    Tp = bucket(T)
    ids = np.zeros(Tp, np.int32)
    ids[:T] = tokens
    emb = weights["embedding"]
    x = jnp.take(emb, jnp.asarray(ids), axis=0).astype(act_dtype(precision))
    for p in weights["layers"]:
        x = _layer(x, p, eps=float(cfg["rms_norm_eps"]),
                   theta=float(cfg["rope_theta"]), precision=precision)
    return x


def _out_weight(weights):
    return weights["w_out"] if "w_out" in weights else weights["embedding"]


def logits_at(weights, cfg: Dict, tokens: np.ndarray,
              positions: np.ndarray, precision: str = "float32"):
    """Yields (row slice, logits) blocks at ``positions`` of ``tokens``."""
    with jax.default_matmul_precision("highest"):
        x = _hidden(weights, cfg, tokens, precision)
        n = len(positions)
        for s in range(0, n, ROW_BLOCK):
            idx = np.zeros(ROW_BLOCK, np.int32)
            part = positions[s:s + ROW_BLOCK]
            idx[:len(part)] = part
            rows = jnp.take(x, jnp.asarray(idx), axis=0)
            lg = _head(rows, weights["final_norm"], _out_weight(weights),
                       vocab=int(cfg["vocab_size"]),
                       eps=float(cfg["rms_norm_eps"]), precision=precision)
            yield slice(s, s + len(part)), lg[:len(part)]


# --------------------------------------------------------------------- work
def _dims(cfg: Dict):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["num_hidden_layers"],
            cfg["vocab_size"])


def matmul_flops(cfg: Dict) -> float:
    """Matrix-product FLOPs of one token through every layer: the
    projections and the MLP."""
    D, H, G, dh, F, L, _ = _dims(cfg)
    return L * (2.0 * (D * H * dh * 2 + D * G * dh * 2 + 3 * D * F))


def head_flops(cfg: Dict) -> float:
    D, *_, V = _dims(cfg)
    return 2.0 * D * V


def attn_flops(cfg: Dict, ctx: float) -> float:
    """Attention FLOPs of one query over ``ctx`` positions, all layers."""
    _, H, _, dh, _, L, _ = _dims(cfg)
    return 4.0 * H * dh * ctx * L


def attn_bytes(cfg: Dict, ctx: float) -> float:
    """K and V bytes one query reads over ``ctx`` positions, all layers."""
    _, _, G, dh, _, L, _ = _dims(cfg)
    return 2.0 * G * dh * ctx * L * BYTES


def step_weight_bytes(cfg: Dict) -> float:
    """Weight bytes one device step reads: every layer plus the head."""
    D, *_, V = _dims(cfg)
    return (matmul_flops(cfg) / 2.0 + D * V) * BYTES
