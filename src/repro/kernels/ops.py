"""jit'd public wrappers for the Pallas kernels + the duet schedule builder.

``interpret`` defaults to True on the CPU backend only, so the kernels
validate there (the kernel-validation mode); on a TPU backend they compile to
Mosaic.

This module is also the single source of truth for the kernel-wide
conventions every kernel used to re-derive independently: the masking
constant (:data:`NEG_INF`), the softmax scale (:func:`default_sm_scale`)
and the GQA head-grouping layout (:func:`gqa_split_heads` /
:func:`gqa_repeat_kv`). The helpers live ABOVE the kernel imports below so
the kernel modules can import them during this module's own (partial)
initialization without a cycle.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Shared kernel conventions (imported by kernels/{flash_prefill,paged_decode,
# duet_attention,ref}.py — keep above the kernel imports).
# ---------------------------------------------------------------------------
NEG_INF = -1e30
# an online-softmax denominator is clamped to this before any division
DENOM_EPS = 1e-20
# masked-split guard: a running max still at NEG_INF means "saw no valid
# token yet" — compare against half the sentinel so float error can't flip it
MASKED_M_THRESHOLD = NEG_INF * 0.5


def default_sm_scale(head_dim: int) -> float:
    """The shared 1/sqrt(Dh) softmax scale."""
    return 1.0 / float(head_dim) ** 0.5


def gqa_split_heads(x: jax.Array, num_groups: int) -> jax.Array:
    """(..., H, Dh) -> (..., G, rep, Dh). Query head h serves kv group
    h // rep — the layout every kernel and reference assumes."""
    *lead, H, Dh = x.shape
    assert H % num_groups == 0, (H, num_groups)
    return x.reshape(*lead, num_groups, H // num_groups, Dh)


def gqa_merge_heads(x: jax.Array) -> jax.Array:
    """Inverse of :func:`gqa_split_heads`: (..., G, rep, Dh) -> (..., H, Dh)."""
    *lead, G, rep, Dh = x.shape
    return x.reshape(*lead, G * rep, Dh)


def gqa_repeat_kv(kv: jax.Array, rep: int) -> jax.Array:
    """Broadcast kv heads to query heads on the head axis (-2):
    (..., G, Dh) -> (..., G*rep, Dh), matching :func:`gqa_split_heads`."""
    return jnp.repeat(kv, rep, axis=-2)


def num_splits_for(num_pages: int, page_size: int,
                   split_threshold: Optional[int]) -> int:
    """Static split count for one paged-decode launch.

    The decision is made on the table's token *capacity* (a static shape),
    not the traced lengths, so the jitted program stays shape-stable: the
    engine's table-width bucketing already tracks context growth. Returns 1
    (no split) below the threshold; above it, enough splits to bring each
    split under the threshold, capped at 8 and at one page per split.
    """
    if not split_threshold or split_threshold <= 0:
        return 1
    capacity = num_pages * page_size
    if capacity <= split_threshold:
        return 1
    return max(2, min(num_pages, -(-capacity // split_threshold), 8))


from repro.kernels import duet_attention as _duet  # noqa: E402
from repro.kernels import flash_prefill as _flash  # noqa: E402
from repro.kernels import paged_decode as _paged  # noqa: E402


def default_interpret() -> bool:
    """Pallas interpret mode: on where the backend is the CPU (tests),
    off everywhere else — on the chip the kernels always compile to Mosaic."""
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("q_offset", "kv_len", "block_q",
                                             "block_k", "interpret"))
def flash_prefill(q, k, v, *, q_offset: int = 0, kv_len=None,
                  block_q: int = 128, block_k: int = 128, interpret=None):
    interpret = default_interpret() if interpret is None else interpret
    return _flash.flash_prefill(q, k, v, q_offset=q_offset, kv_len=kv_len,
                                block_q=block_q, block_k=block_k,
                                interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode(q, k_pages, v_pages, tables, lengths, *, interpret=None):
    interpret = default_interpret() if interpret is None else interpret
    return _paged.paged_decode(q, k_pages, v_pages, tables, lengths,
                               interpret=interpret)


@functools.partial(jax.jit, static_argnames=("num_splits", "interpret"))
def paged_decode_splitkv(q, k_pages, v_pages, tables, lengths, *,
                         num_splits: int, interpret=None):
    interpret = default_interpret() if interpret is None else interpret
    return _paged.paged_decode_splitkv(q, k_pages, v_pages, tables, lengths,
                                       num_splits=num_splits,
                                       interpret=interpret)


def paged_decode_sharded(q, k_pages, v_pages, tables, lengths, *, mesh,
                         num_splits: int = 1, interpret: bool = False):
    """TP>1 kernel path: shard_map over the KV-head (``model``) mesh axis.

    Per-shard grids see their local head shard of q (B, H/tp, Dh) and of the
    page pools (N, ps, G/tp, Dh); block tables and lengths stay host-global
    (replicated) — page ids index the page axis, which is NOT partitioned.
    Softmax is per-head and heads are fully partitioned, so no cross-shard
    reduction is needed and ``check_vma=False`` is sound.
    """
    from jax.sharding import PartitionSpec as P

    def local(qs, kp, vp, tbl, ln):
        if num_splits > 1:
            return _paged.paged_decode_splitkv(
                qs, kp, vp, tbl, ln, num_splits=num_splits,
                interpret=interpret)
        return _paged.paged_decode(qs, kp, vp, tbl, ln, interpret=interpret)

    head_spec = P(None, "model", None)
    pool_spec = P(None, None, "model", None)
    return jax.shard_map(local, mesh=mesh,
                         in_specs=(head_spec, pool_spec, pool_spec, P(), P()),
                         out_specs=head_spec, check_vma=False)(
        q, k_pages, v_pages, tables, lengths)


def paged_decode_auto(q, k_pages, v_pages, tables, lengths, *, mesh=None,
                      split_threshold: Optional[int] = 0, interpret=None):
    """Kernel-path dispatcher used by the model's decode step.

    Statics (``mesh``, ``split_threshold``, ``interpret``) come from Model
    attributes, so calls from inside the engine's jitted programs stay
    shape-stable. Routes to the shard_map wrapper when a TP mesh is given
    and to the split-KV kernel when the table capacity crosses the
    (roofline-priced) threshold.
    """
    interpret = default_interpret() if interpret is None else interpret
    splits = num_splits_for(tables.shape[1], k_pages.shape[1],
                            split_threshold)
    if mesh is not None and mesh.shape.get("model", 1) > 1:
        return paged_decode_sharded(q, k_pages, v_pages, tables, lengths,
                                    mesh=mesh, num_splits=splits,
                                    interpret=interpret)
    if splits > 1:
        return _paged.paged_decode_splitkv(q, k_pages, v_pages, tables,
                                           lengths, num_splits=splits,
                                           interpret=interpret)
    return _paged.paged_decode(q, k_pages, v_pages, tables, lengths,
                               interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k",
                                             "interpret"))
def duet_attention(q, row_pos, tile_slot, k_slab, v_slab, *,
                   block_q: int = 8, block_k: int = 128, interpret=None):
    interpret = default_interpret() if interpret is None else interpret
    return _duet.duet_attention(q, row_pos, tile_slot, k_slab, v_slab,
                                block_q=block_q, block_k=block_k,
                                interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_q", "interpret"))
def duet_attention_paged(q, row_pos, tile_slot, k_pages, v_pages, tables, *,
                         block_q: int = 8, interpret=None):
    interpret = default_interpret() if interpret is None else interpret
    return _duet.duet_attention_paged(q, row_pos, tile_slot, k_pages,
                                      v_pages, tables, block_q=block_q,
                                      interpret=interpret)


# ---------------------------------------------------------------------------
# Duet schedule builder (host side)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class DuetSchedule:
    """Tile layout for one fused duet launch.

    ``order`` maps kernel tile index -> (kind, original index) for unpacking;
    decode tiles are interleaved among prefill tiles at the Algorithm-1 ratio
    so they retire early in the grid (the TBT guarantee)."""
    tile_slot: np.ndarray            # (T,) int32
    row_slot: np.ndarray             # (T*bq,) int32 (-1 pad)
    row_pos: np.ndarray              # (T*bq,) int32 (-1 pad)
    row_src: np.ndarray              # (T*bq,) int32 index into the packed
    # source row list (-1 pad), used to scatter kernel output back
    num_decode_tiles: int
    num_prefill_tiles: int


def build_duet_schedule(decode_rows: Sequence[Tuple[int, int]],
                        prefill_rows: Sequence[Tuple[int, int]],
                        *, block_q: int = 8,
                        decode_share: float = 0.25) -> DuetSchedule:
    """Group rows into per-slot tiles and interleave the two phases.

    Args:
      decode_rows: [(slot, pos)] one per active decode request.
      prefill_rows: [(slot, pos)] one per query position of the prefill chunk.
      decode_share: S_d / (S_d + S_p) from the partition optimizer — sets the
        interleave ratio (a decode tile is placed after every
        ``(1-share)/share`` prefill tiles).
    Rows are indexed in the order given: row_src refers to
    list(decode_rows) + list(prefill_rows).
    """
    def tiles_for(rows, base):
        by_slot: dict = {}
        for i, (slot, pos) in enumerate(rows):
            by_slot.setdefault(slot, []).append((base + i, pos))
        tiles = []
        for slot, items in sorted(by_slot.items()):
            for off in range(0, len(items), block_q):
                chunk = items[off:off + block_q]
                tiles.append((slot, chunk))
        return tiles

    d_tiles = tiles_for(decode_rows, 0)
    p_tiles = tiles_for(prefill_rows, len(decode_rows))

    # interleave: after every `stride` prefill tiles, insert one decode tile
    order: List[Tuple[int, list]] = []
    if not p_tiles:
        order = d_tiles
    elif not d_tiles:
        order = p_tiles
    else:
        stride = max(1, round((1.0 - decode_share) / max(decode_share, 1e-6)))
        di, pi = 0, 0
        while di < len(d_tiles) or pi < len(p_tiles):
            if di < len(d_tiles):
                order.append(d_tiles[di])
                di += 1
            take = min(stride, len(p_tiles) - pi)
            order.extend(p_tiles[pi:pi + take])
            pi += take

    T = max(1, len(order))
    tile_slot = np.full((T,), -1, np.int32)
    row_slot = np.full((T * block_q,), -1, np.int32)
    row_pos = np.full((T * block_q,), -1, np.int32)
    row_src = np.full((T * block_q,), -1, np.int32)
    for t, (slot, items) in enumerate(order):
        tile_slot[t] = slot
        for r, (src, pos) in enumerate(items):
            row_slot[t * block_q + r] = slot
            row_pos[t * block_q + r] = pos
            row_src[t * block_q + r] = src
    return DuetSchedule(tile_slot=tile_slot, row_slot=row_slot,
                        row_pos=row_pos, row_src=row_src,
                        num_decode_tiles=len(d_tiles),
                        num_prefill_tiles=len(p_tiles))


def pack_duet_queries(schedule: DuetSchedule, src_q: jax.Array) -> jax.Array:
    """Scatter packed source query rows (Nsrc, H, Dh) into tile layout."""
    idx = jnp.asarray(np.maximum(schedule.row_src, 0))
    q = src_q[idx]
    return jnp.where((schedule.row_src >= 0)[:, None, None], q, 0.0)


def unpack_duet_output(schedule: DuetSchedule, out: jax.Array,
                       num_src: int) -> jax.Array:
    """Gather kernel output rows back to packed source order (Nsrc, H, Dh)."""
    res = jnp.zeros((num_src,) + out.shape[1:], out.dtype)
    valid = schedule.row_src >= 0
    return res.at[jnp.asarray(schedule.row_src[valid])].set(
        out[jnp.asarray(np.where(valid)[0])])
