"""Flash-attention prefill kernel (Pallas TPU).

Causal GQA attention for the prefill phase with online softmax, tiled for
VMEM: the grid walks (batch, kv-head group, query block, kv block); per
(q-block) the running max/denominator/accumulator live in VMEM scratch and
the output block is written once at the final kv step. Query positions carry
an offset so chunked prefill (queries are the tail of the key range) reuses
the same kernel.

Block shapes default to MXU-aligned (128, 128) tiles over (seq, head_dim).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import (DENOM_EPS, NEG_INF, default_sm_scale,
                               gqa_split_heads)


def _kernel(q_off_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            block_q: int, block_k: int, rep: int, sm_scale: float,
            kv_len: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]                  # (block_q, Hr, Dh) — one kv-head group
    k = k_ref[0, 0]                  # (block_k, Dh)
    v = v_ref[0, 0]
    bq, Hr, Dh = q.shape

    # scores: (block_q, Hr, block_k)
    s = jax.lax.dot_general(
        q.reshape(bq * Hr, Dh), k,
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).reshape(bq, Hr, -1)
    s = s * sm_scale

    q_pos = q_off_ref[0] + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (bq, Hr, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (bq, Hr, block_k), 2)
    mask = (k_pos <= q_pos) & (k_pos < kv_len)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]              # (block_q, Hr)
    l_prev = l_ref[...]
    m_cur = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new[..., None])
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=-1)

    pv = jax.lax.dot_general(
        p.reshape(bq * Hr, -1).astype(v.dtype), v,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).reshape(bq, Hr, Dh)
    acc_ref[...] = acc_ref[...] * alpha[..., None] + pv
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], DENOM_EPS)[..., None]
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_prefill(q, k, v, *, q_offset=0, kv_len=None, block_q: int = 128,
                  block_k: int = 128, interpret: bool = False):
    """q (B,Sq,H,Dh); k,v (B,Sk,G,Dh); returns (B,Sq,H,Dh).

    Sq/Sk must divide by the block sizes (callers pad); H % G == 0.
    ``q_offset`` (scalar int32) shifts query positions for chunked prefill;
    ``kv_len`` masks out padded keys beyond the true length.
    """
    B, Sq, H, Dh = q.shape
    _, Sk, G, _ = k.shape
    assert H % G == 0 and Sq % block_q == 0 and Sk % block_k == 0
    rep = H // G
    # layout: group queries by kv head -> (B, G, Sq, rep, Dh)
    qg = gqa_split_heads(q, G).transpose(0, 2, 1, 3, 4)
    kg = k.transpose(0, 2, 1, 3)     # (B, G, Sk, Dh)
    vg = v.transpose(0, 2, 1, 3)

    grid = (B, G, Sq // block_q, Sk // block_k)
    kernel = functools.partial(_kernel, block_q=block_q, block_k=block_k,
                               rep=rep, sm_scale=default_sm_scale(Dh),
                               kv_len=kv_len if kv_len is not None else Sk)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, block_q, rep, Dh),
                             lambda b, g, i, j, off: (b, g, i, 0, 0)),
                pl.BlockSpec((1, 1, block_k, Dh),
                             lambda b, g, i, j, off: (b, g, j, 0)),
                pl.BlockSpec((1, 1, block_k, Dh),
                             lambda b, g, i, j, off: (b, g, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, block_q, rep, Dh),
                                   lambda b, g, i, j, off: (b, g, i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((block_q, rep), jnp.float32),
                pltpu.VMEM((block_q, rep), jnp.float32),
                pltpu.VMEM((block_q, rep, Dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, G, Sq // block_q * block_q, rep,
                                        Dh), q.dtype),
        interpret=interpret,
        name="flash_prefill",
    )(jnp.asarray([q_offset], jnp.int32), qg, kg, vg)
    return out.transpose(0, 2, 1, 3, 4).reshape(B, Sq, H, Dh)
