"""Paged decode-attention kernels (Pallas TPU).

One new query token per request attends over its paged KV cache
(PagedAttention layout: pages (N, page_size, G, Dh) + per-request block
tables). The grid walks (request, page-block); block tables arrive as scalar
prefetch so the BlockSpec index maps gather the right page for each step —
the TPU version of the GPU kernel's pointer-chasing, with HBM→VMEM page
copies driven by the prefetched indices.

Memory-bound by design (the decode phase of the paper's Fig. 3c): per grid
step the kernel moves one KV page through VMEM and does rank-1 compute.

Two variants:

* :func:`paged_decode` — sequential page walk, one running (m, l, acc) per
  request.
* :func:`paged_decode_splitkv` — flash-decoding style: each request's page
  chain is partitioned across a second grid axis into ``num_splits``
  contiguous spans; every split keeps its own (m, l, acc) partial in
  scratch and a log-sum-exp reduction epilogue combines them at the
  request's final grid step. Long-context decode is latency-bound on the
  single serial page walk; splitting restores page-level parallelism on
  hardware that overlaps the per-split DMA streams.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import (DENOM_EPS, MASKED_M_THRESHOLD, NEG_INF,
                               default_sm_scale, gqa_split_heads)


def _kernel(tables_ref, lengths_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, page_size: int, rep: int,
            sm_scale: float):
    b = pl.program_id(0)
    pi = pl.program_id(1)

    @pl.when(pi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                       # (H, Dh)
    k = k_ref[0]                       # (page_size, G, Dh)
    v = v_ref[0]
    H, Dh = q.shape
    G = k.shape[1]

    qg = gqa_split_heads(q, G)
    # scores (G, rep, page_size)
    s = jax.lax.dot_general(qg, k, (((2,), (2,)), ((0,), (1,))),
                            preferred_element_type=jnp.float32)
    s = s * sm_scale

    tok = pi * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (G, rep, page_size), 2)
    valid = tok < lengths_ref[b]
    s = jnp.where(valid, s, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1)
    m_ref[...] = m_new
    # pv: (G, rep, Dh)
    pv = jax.lax.dot_general(p.astype(v.dtype), v,
                             (((2,), (0,)), ((0,), (1,))),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha[..., None] + pv

    @pl.when(pi == pl.num_programs(1) - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], DENOM_EPS)[..., None]
        o_ref[0] = (acc_ref[...] / denom).reshape(H, Dh).astype(o_ref.dtype)


def paged_decode(q, k_pages, v_pages, tables, lengths, *,
                 interpret: bool = False):
    """q (B,H,Dh); k/v_pages (N,ps,G,Dh); tables (B,P) int32 page ids;
    lengths (B,) int32 true context lengths. Returns (B,H,Dh).

    Unused table slots must point at a valid (e.g. null) page — they are
    masked by ``lengths``.
    """
    B, H, Dh = q.shape
    N, ps, G, _ = k_pages.shape
    P = tables.shape[1]
    assert H % G == 0
    rep = H // G
    kernel = functools.partial(_kernel, page_size=ps, rep=rep,
                               sm_scale=default_sm_scale(Dh))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, P),
            in_specs=[
                pl.BlockSpec((1, H, Dh), lambda b, p, tbl, ln: (b, 0, 0)),
                pl.BlockSpec((1, ps, G, Dh),
                             lambda b, p, tbl, ln: (tbl[b, p], 0, 0, 0)),
                pl.BlockSpec((1, ps, G, Dh),
                             lambda b, p, tbl, ln: (tbl[b, p], 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, H, Dh), lambda b, p, tbl, ln: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, rep), jnp.float32),
                pltpu.VMEM((G, rep), jnp.float32),
                pltpu.VMEM((G, rep, Dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, Dh), q.dtype),
        interpret=interpret,
        name="paged_decode",
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32), q, k_pages,
      v_pages)
    return out


def _splitkv_kernel(tables_ref, lengths_ref, q_ref, k_ref, v_ref, o_ref,
                    m_ref, l_ref, acc_ref, *, page_size: int, rep: int,
                    pages_per_split: int, sm_scale: float):
    b = pl.program_id(0)
    si = pl.program_id(1)
    pi = pl.program_id(2)

    @pl.when(pi == 0)
    def _init():
        m_ref[si] = jnp.full(m_ref.shape[1:], NEG_INF, m_ref.dtype)
        l_ref[si] = jnp.zeros(l_ref.shape[1:], l_ref.dtype)
        acc_ref[si] = jnp.zeros(acc_ref.shape[1:], acc_ref.dtype)

    q = q_ref[0]                       # (H, Dh)
    k = k_ref[0]                       # (page_size, G, Dh)
    v = v_ref[0]
    H, Dh = q.shape
    G = k.shape[1]

    qg = gqa_split_heads(q, G)
    s = jax.lax.dot_general(qg, k, (((2,), (2,)), ((0,), (1,))),
                            preferred_element_type=jnp.float32)
    s = s * sm_scale

    tok = (si * pages_per_split + pi) * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (G, rep, page_size), 2)
    valid = tok < lengths_ref[b]
    s = jnp.where(valid, s, NEG_INF)

    m_prev, l_prev = m_ref[si], l_ref[si]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    # a split whose pages lie entirely past `lengths` has every score at
    # NEG_INF; exp(NEG_INF - NEG_INF) == 1 would silently inflate l, so
    # masked scores get zero probability. The full-shape `valid` mask does
    # this without broadcasting a (G, rep) boolean, which Mosaic cannot
    # reshape.
    p = jnp.where(valid, jnp.exp(s - m_new[..., None]), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[si] = alpha * l_prev + jnp.sum(p, axis=-1)
    m_ref[si] = m_new
    pv = jax.lax.dot_general(p.astype(v.dtype), v,
                             (((2,), (0,)), ((0,), (1,))),
                             preferred_element_type=jnp.float32)
    acc_ref[si] = acc_ref[si] * alpha[..., None] + pv

    @pl.when((si == pl.num_programs(1) - 1) & (pi == pl.num_programs(2) - 1))
    def _combine():
        # log-sum-exp reduction over the per-split partials
        ms = m_ref[...]                            # (S, G, rep)
        m_star = jnp.max(ms, axis=0)
        w = jnp.exp(ms - m_star[None])
        w = jnp.where(ms > MASKED_M_THRESHOLD, w, 0.0)   # dead splits
        l_star = jnp.sum(w * l_ref[...], axis=0)
        acc = jnp.sum(acc_ref[...] * w[..., None], axis=0)
        denom = jnp.maximum(l_star, DENOM_EPS)[..., None]
        o_ref[0] = (acc / denom).reshape(H, Dh).astype(o_ref.dtype)


def paged_decode_splitkv(q, k_pages, v_pages, tables, lengths, *,
                         num_splits: int, interpret: bool = False):
    """Flash-decoding variant of :func:`paged_decode`.

    Same contract; the page walk is partitioned over a second grid axis
    into ``num_splits`` contiguous spans of the block table (padded to a
    multiple with the null page — padding tokens sit past ``lengths`` and
    mask out). Per-split (m, l, acc) partials live in scratch rows indexed
    by the split id and are LSE-combined at the request's last grid step.
    """
    B, H, Dh = q.shape
    N, ps, G, _ = k_pages.shape
    P = tables.shape[1]
    assert H % G == 0
    rep = H // G
    S = max(1, min(num_splits, P))
    pps = -(-P // S)                   # pages per split
    pad = S * pps - P
    tbl = jnp.pad(tables.astype(jnp.int32), ((0, 0), (0, pad)))
    kernel = functools.partial(_splitkv_kernel, page_size=ps, rep=rep,
                               pages_per_split=pps,
                               sm_scale=default_sm_scale(Dh))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, S, pps),
            in_specs=[
                pl.BlockSpec((1, H, Dh),
                             lambda b, s, p, tbl, ln: (b, 0, 0)),
                pl.BlockSpec((1, ps, G, Dh),
                             lambda b, s, p, tbl, ln:
                             (tbl[b, s * pps + p], 0, 0, 0)),
                pl.BlockSpec((1, ps, G, Dh),
                             lambda b, s, p, tbl, ln:
                             (tbl[b, s * pps + p], 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, H, Dh),
                                   lambda b, s, p, tbl, ln: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((S, G, rep), jnp.float32),
                pltpu.VMEM((S, G, rep), jnp.float32),
                pltpu.VMEM((S, G, rep, Dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, Dh), q.dtype),
        interpret=interpret,
        name="paged_decode_splitkv",
    )(tbl, lengths.astype(jnp.int32), q, k_pages, v_pages)
    return out
