"""Plain float32 reference of a dense Qwen3 decoder, and the comparison
that decides ``correct``.

Independent of the program: it imports only JAX and NumPy and reads the
weights the benchmark made (``bench/model.py``) by their names. It follows
the published Qwen3 description: RMSNorm before attention and MLP, per-head
RMSNorm on queries and keys, rotary embedding in the rotate-half
convention with the configured theta, grouped-query causal attention with
scale 1/sqrt(head_dim), SwiGLU MLP, a final RMSNorm and an output head
(tied to the embedding where the configuration says so). Matrix products
run at ``highest`` precision, so float32 is float32 on the TPU too.

The sequence is prompt plus served tokens, padded at the end to a bucket
(padding after the last real position changes nothing before it under the
causal mask). Layers run one at a time and attention in blocks of queries,
so the reference fits beside the weights on one chip.

``precision="fp8"`` is the control: the same computation with every weight
matrix rounded to float8 e4m3 (one scale per tensor, per quarter of the
output head) and activations in bfloat16, the step below the
configuration's bfloat16.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

BUCKET = 2048        # sequence lengths are padded up to a multiple of this
Q_BLOCK = 512        # query rows per attention block
ROW_BLOCK = 256      # logit rows per output-head block
F8_MAX = 448.0       # largest finite float8 e4m3 value
VOCAB_BLOCKS = 4     # output-head slices (the padded vocabulary divides)


def _bucket(n: int) -> int:
    """Padded length, so that few lengths compile: a power of two from
    Q_BLOCK up to BUCKET, then the next multiple of BUCKET."""
    if n > BUCKET:
        return -(-n // BUCKET) * BUCKET
    return max(Q_BLOCK, 1 << (n - 1).bit_length())


def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


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


def _weight(w, precision):
    """A weight matrix as the computation sees it."""
    if precision == "fp8":
        w32 = w.astype(jnp.float32)
        s = jnp.maximum(jnp.max(jnp.abs(w32)), 1e-30) / F8_MAX
        q = (w32 / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
        return q.astype(jnp.bfloat16)
    return w.astype(jnp.float32)


def _act_dtype(precision):
    return jnp.bfloat16 if precision == "fp8" else jnp.float32


@partial(jax.jit, static_argnames=("eps", "theta", "precision"))
def _layer(x, p, *, eps, theta, precision):
    """One decoder layer over x (T, D)."""
    dt = _act_dtype(precision)
    a = p["attn"]
    wq, wk, wv, wo = (_weight(a[n], precision)
                      for n in ("w_q", "w_k", "w_v", "w_o"))
    H, dh, G = wq.shape[1], wq.shape[2], wk.shape[1]
    T = x.shape[0]
    h = _rms(x, p["attn_norm"], eps)
    q = jnp.einsum("td,dhe->the", h, wq, preferred_element_type=jnp.float32)
    k = jnp.einsum("td,dge->tge", h, wk, preferred_element_type=jnp.float32)
    v = jnp.einsum("td,dge->tge", h, wv, preferred_element_type=jnp.float32)
    q = _rope(_rms(q.astype(dt), a["q_norm"], eps), theta)
    k = _rope(_rms(k.astype(dt), a["k_norm"], eps), theta)
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
    h = _rms(x, p["mlp_norm"], eps)
    g = jnp.einsum("td,df->tf", h, _weight(m["w_gate"], precision),
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("td,df->tf", h, _weight(m["w_up"], precision),
                   preferred_element_type=jnp.float32)
    f = (jax.nn.silu(g) * u).astype(dt)
    return x + jnp.einsum("tf,fd->td", f, _weight(m["w_down"], precision),
                          preferred_element_type=jnp.float32).astype(dt)


@partial(jax.jit, static_argnames=("vocab", "eps", "precision"))
def _head(rows, final_norm, w_out, *, vocab, eps, precision):
    """Logits (R, vocab) of hidden rows (R, D), the output head taken in
    VOCAB_BLOCKS slices so no float32 copy of the whole head exists."""
    h = _rms(rows, final_norm, eps)
    V, D = w_out.shape
    blocks = w_out.reshape(VOCAB_BLOCKS, V // VOCAB_BLOCKS, D)
    lg = jax.lax.map(
        lambda w: jnp.einsum("rd,vd->rv", h, _weight(w, precision),
                             preferred_element_type=jnp.float32), blocks)
    return jnp.moveaxis(lg, 0, 1).reshape(rows.shape[0], V)[:, :vocab]


def _hidden(weights, cfg: Dict, tokens: np.ndarray, precision: str):
    """Final hidden states (T_padded, D) of a token sequence."""
    T = len(tokens)
    Tp = _bucket(T)
    ids = np.zeros(Tp, np.int32)
    ids[:T] = tokens
    emb = weights["embedding"]
    x = jnp.take(emb, jnp.asarray(ids), axis=0).astype(_act_dtype(precision))
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


def served_positions(prompt_len: int, n_served: int) -> np.ndarray:
    """Positions whose logits predict served tokens 0..n_served-1."""
    return prompt_len - 1 + np.arange(n_served)


def gaps(weights, cfg: Dict, prompt: np.ndarray, served: Sequence[int],
         others: Optional[Dict[str, np.ndarray]] = None) -> Dict[str, np.ndarray]:
    """Float32 reference gaps: for each served position, the reference's
    best logit minus its logit of the served token (>= 0; 0 where the
    served token is the reference's argmax). ``others`` maps a name to
    alternative tokens at the same positions, gapped the same way."""
    served = np.asarray(served, np.int32)
    seq = np.concatenate([np.asarray(prompt, np.int32), served[:-1]])
    pos = served_positions(len(prompt), len(served))
    cands = {"served": served, **(others or {})}
    out = {k: np.zeros(len(served), np.float32) for k in cands}
    for sl, lg in logits_at(weights, cfg, seq, pos):
        best = jnp.max(lg, axis=-1)
        for k, toks in cands.items():
            t = jnp.asarray(toks[sl])
            got = jnp.take_along_axis(lg, t[:, None], axis=-1)[:, 0]
            out[k][sl] = np.asarray(best - got)
    return out


def control_tokens(weights, cfg: Dict, prompt: np.ndarray,
                   served: Sequence[int]) -> np.ndarray:
    """The token the fp8 control puts first at each served position, on
    the same prompt and served tokens."""
    served = np.asarray(served, np.int32)
    seq = np.concatenate([np.asarray(prompt, np.int32), served[:-1]])
    pos = served_positions(len(prompt), len(served))
    out = np.zeros(len(served), np.int32)
    for sl, lg in logits_at(weights, cfg, seq, pos, precision="fp8"):
        out[sl] = np.asarray(jnp.argmax(lg, axis=-1))
    return out
