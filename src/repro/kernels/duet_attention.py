"""Fused duet attention — DuetServe's SM-partitioned concurrent
prefill+decode execution, adapted to the TPU grid (DESIGN.md §2).

On GPU the paper binds the prefill and decode streams to disjoint SM sets via
libsmctrl. A TPU TensorCore executes one kernel's grid sequentially, so the
within-chip analogue of spatial multiplexing is *grid interleaving*: a single
``pallas_call`` processes both phases' attention tiles, and the tile ORDER
(built by ``ops.build_duet_schedule`` from the Algorithm-1 ratio) interleaves
decode tiles among prefill tiles so decode tokens complete early in the
launch instead of queueing behind the whole prefill — bounding TBT exactly
the way the SM partition does, without a second kernel launch.

Work items are *rows*: a decode row is one request's single query token; a
prefill row is one query position of the chunk being prefilled. Rows are
grouped into per-slot tiles of ``block_q`` rows; scalar-prefetched tile
descriptors drive the BlockSpec index maps.

Two KV layouts:

* :func:`duet_attention` — the engine's legacy slab cache (Ns, S, G, Dh);
  tile descriptors resolve tile -> slab slot.
* :func:`duet_attention_paged` — the page pool the engines actually
  allocate from (N, ps, G, Dh): the descriptors resolve
  (tile -> slot -> block-table row -> page id) in the index map, so the
  Algorithm-1 interleave executes over real allocated pages with no slab
  copy. The kv grid axis walks the block table one page per step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import (DENOM_EPS, NEG_INF, default_sm_scale,
                               gqa_split_heads)


def _attend_tile(tile_live, q, k, v, k_pos, pos_ref, m_ref, l_ref, acc_ref,
                 *, rep: int, sm_scale: float):
    """One (query-tile, kv-block) step of the shared online-softmax body.

    ``k_pos`` carries each kv position's absolute index (iota pre-offset by
    the caller for its layout); masking is causal per row plus the tile/row
    liveness flags.
    """
    bq, H, Dh = q.shape
    G = k.shape[1]

    qg = gqa_split_heads(q, G)            # (bq, G, rep, Dh)
    # scores (G, bq, rep, block_k): contract Dh, batch over G
    s = jax.lax.dot_general(
        qg.transpose(1, 0, 2, 3).reshape(G, bq * rep, Dh), k.transpose(1, 0, 2),
        (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32).reshape(G, bq, rep, -1)
    s = s * sm_scale

    pos = pos_ref[0][:, 0]                # (bq,)
    row_pos = pos[None, :, None, None]
    valid = (k_pos <= row_pos) & (row_pos >= 0) & tile_live
    s = jnp.where(valid, s, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1)
    m_ref[...] = m_new
    pv = jax.lax.dot_general(
        p.reshape(G, bq * rep, -1).astype(v.dtype), v.transpose(1, 0, 2),
        (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32).reshape(G, bq, rep, Dh)
    acc_ref[...] = acc_ref[...] * alpha[..., None] + pv


def _tile_rows(row_pos, num_tiles: int, block_q: int):
    """(T*block_q, 1) row positions -> (T, block_q, 1), so one tile's
    block spans the array's last two dims: Mosaic takes a (block_q, 1)
    block of the flat layout only when block_q is a multiple of 8."""
    return row_pos.astype(jnp.int32).reshape(num_tiles, block_q, 1)


def _write_tile(o_ref, m_ref, l_ref, acc_ref):
    denom = jnp.maximum(l_ref[...], DENOM_EPS)[..., None]
    out = (acc_ref[...] / denom)                  # (G, bq, rep, Dh)
    G, bq, rep, Dh = out.shape
    o_ref[...] = out.transpose(1, 0, 2, 3).reshape(bq, G * rep, Dh).astype(
        o_ref.dtype)


def _kernel(tile_slot_ref, q_ref, pos_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, block_q: int, block_k: int, rep: int,
            sm_scale: float):
    t = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...]                        # (block_q, H, Dh)
    k = k_ref[0]                          # (block_k, G, Dh)
    v = v_ref[0]
    G = k.shape[1]
    k_pos = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (G, block_q, rep, block_k), 3)
    _attend_tile(tile_slot_ref[t] >= 0, q, k, v, k_pos, pos_ref,
                 m_ref, l_ref, acc_ref, rep=rep, sm_scale=sm_scale)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        _write_tile(o_ref, m_ref, l_ref, acc_ref)


def duet_attention(q, row_pos, tile_slot, k_slab, v_slab, *,
                   block_q: int = 8, block_k: int = 128,
                   interpret: bool = False):
    """Fused mixed-phase attention over the slab cache.

    Args:
      q:         (T*block_q, H, Dh) query rows, tile-grouped. Tile t's rows
                 all target slab slot ``tile_slot[t]`` (host groups + pads).
      row_pos:   (T*block_q, 1) int32 absolute position per row (-1 = pad row).
      tile_slot: (T,) int32 slab slot per tile (-1 = pad tile). The ORDER of
                 tiles is the duet schedule (decode tiles interleaved).
      k_slab/v_slab: (Ns, S, G, Dh) engine slab KV (chunk K/V pre-written).
    Returns (T*block_q, H, Dh).
    """
    R, H, Dh = q.shape
    Ns, S, G, _ = k_slab.shape
    T = tile_slot.shape[0]
    assert R == T * block_q and H % G == 0 and S % block_k == 0
    rep = H // G
    kernel = functools.partial(_kernel, block_q=block_q, block_k=block_k,
                               rep=rep, sm_scale=default_sm_scale(Dh))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(T, S // block_k),
            in_specs=[
                pl.BlockSpec((block_q, H, Dh), lambda t, j, ts: (t, 0, 0)),
                pl.BlockSpec((1, block_q, 1), lambda t, j, ts: (t, 0, 0)),
                pl.BlockSpec((1, block_k, G, Dh),
                             lambda t, j, ts: (jnp.maximum(ts[t], 0), j, 0, 0)),
                pl.BlockSpec((1, block_k, G, Dh),
                             lambda t, j, ts: (jnp.maximum(ts[t], 0), j, 0, 0)),
            ],
            out_specs=pl.BlockSpec((block_q, H, Dh),
                                   lambda t, j, ts: (t, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, block_q, rep), jnp.float32),
                pltpu.VMEM((G, block_q, rep), jnp.float32),
                pltpu.VMEM((G, block_q, rep, Dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((R, H, Dh), q.dtype),
        interpret=interpret,
        name="duet_attention",
    )(tile_slot.astype(jnp.int32), q, _tile_rows(row_pos, T, block_q),
      k_slab, v_slab)
    return out


def _paged_kernel(tile_slot_ref, tables_ref, q_ref, pos_ref, k_ref, v_ref,
                  o_ref, m_ref, l_ref, acc_ref, *, block_q: int,
                  page_size: int, rep: int, sm_scale: float):
    t = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...]                        # (block_q, H, Dh)
    k = k_ref[0]                          # (page_size, G, Dh)
    v = v_ref[0]
    G = k.shape[1]
    # flat index into a table-ordered, densely-filled page chain == absolute
    # position (same invariant as models.attention._paged_gather)
    k_pos = j * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (G, block_q, rep, page_size), 3)
    _attend_tile(tile_slot_ref[t] >= 0, q, k, v, k_pos, pos_ref,
                 m_ref, l_ref, acc_ref, rep=rep, sm_scale=sm_scale)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        _write_tile(o_ref, m_ref, l_ref, acc_ref)


def duet_attention_paged(q, row_pos, tile_slot, k_pages, v_pages, tables, *,
                         block_q: int = 8, interpret: bool = False):
    """Fused mixed-phase attention over the paged pool.

    Args:
      q:         (T*block_q, H, Dh) query rows, tile-grouped as in
                 :func:`duet_attention`.
      row_pos:   (T*block_q, 1) int32 absolute position per row (-1 = pad).
      tile_slot: (T,) int32 — index into ``tables`` rows per tile (-1 = pad
                 tile; pads read the null chain tables[0] and mask out).
      k_pages/v_pages: (N, ps, G, Dh) device page pools.
      tables:    (B, P) int32 block tables; row ``tile_slot[t]`` is tile
                 t's page chain. Unused entries must hold a valid (null)
                 page id.
    Returns (T*block_q, H, Dh). The kv grid axis walks the P table columns;
    the index map resolves (tile -> table row -> page id) from the two
    scalar-prefetched descriptors, so each grid step DMAs one real
    allocated page into VMEM — no slab copy, no gather materialization.
    """
    R, H, Dh = q.shape
    N, ps, G, _ = k_pages.shape
    B, P = tables.shape
    T = tile_slot.shape[0]
    assert R == T * block_q and H % G == 0
    rep = H // G
    kernel = functools.partial(_paged_kernel, block_q=block_q, page_size=ps,
                               rep=rep, sm_scale=default_sm_scale(Dh))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(T, P),
            in_specs=[
                pl.BlockSpec((block_q, H, Dh),
                             lambda t, j, ts, tbl: (t, 0, 0)),
                pl.BlockSpec((1, block_q, 1),
                             lambda t, j, ts, tbl: (t, 0, 0)),
                pl.BlockSpec((1, ps, G, Dh),
                             lambda t, j, ts, tbl:
                             (tbl[jnp.maximum(ts[t], 0), j], 0, 0, 0)),
                pl.BlockSpec((1, ps, G, Dh),
                             lambda t, j, ts, tbl:
                             (tbl[jnp.maximum(ts[t], 0), j], 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((block_q, H, Dh),
                                   lambda t, j, ts, tbl: (t, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, block_q, rep), jnp.float32),
                pltpu.VMEM((G, block_q, rep), jnp.float32),
                pltpu.VMEM((G, block_q, rep, Dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((R, H, Dh), q.dtype),
        interpret=interpret,
        name="duet_attention_paged",
    )(tile_slot.astype(jnp.int32), tables.astype(jnp.int32), q,
      _tile_rows(row_pos, T, block_q), k_pages, v_pages)
    return out
