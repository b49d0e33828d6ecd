"""Interruption-free look-ahead decode execution — paper §4.3, TPU-native.

On GPU the paper records decode into CUDA Graphs and replays k of them
back-to-back without host synchronisation, with KV slots and metadata for all
k steps preallocated. The JAX analogue is *stronger*: the k-step loop is
compiled *inside* one jitted program via ``lax.scan`` — a single dispatch
covers k decode iterations, zero host round-trips between steps (DESIGN.md
§2). The planner half (slot preallocation) lives in the serving engine's KV
manager; this module provides the fused multi-step decode program plus
greedy/temperature sampling inside the loop.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.transformer import Model


def _freeze_inactive(new_cache, old_cache, active_mask: jax.Array):
    """Keep inactive slots' per-slot cache/state rows untouched. Every leaf
    is batch-leading (paged pools are not routed through here — their
    inactive writes land in the reserved null page instead)."""

    def merge(new, old):
        m = active_mask.reshape((-1,) + (1,) * (new.ndim - 1))
        return jnp.where(m, new, old)

    return jax.tree.map(merge, new_cache, old_cache)


@jax.named_scope("sample")
def _sample(logits: jax.Array, key: jax.Array, temperature: float):
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    g = -jnp.log(-jnp.log(jax.random.uniform(key, logits.shape) + 1e-9) + 1e-9)
    return jnp.argmax(logits / temperature + g, axis=-1).astype(jnp.int32)


def lookahead_decode(model: Model, params, cache, first_token: jax.Array,
                     start_pos: jax.Array, k: int, *,
                     key: Optional[jax.Array] = None,
                     temperature: float = 0.0,
                     sliding: bool = False,
                     active_mask: Optional[jax.Array] = None):
    """Run ``k`` decode steps without host synchronisation.

    Args:
      first_token: (B, 1) int32 — token to feed at the first step.
      start_pos: (B,) int32 — cache position of the first step per request
        (continuous batching: requests sit at different depths).
      active_mask: (B,) bool — inactive slots keep their state frozen
        (position not advanced) so retired slots don't corrupt the cache.

    Returns: (tokens (B, k), cache, new_pos (B,)).
    """
    B = first_token.shape[0]
    if key is None:
        key = jax.random.PRNGKey(0)
    if active_mask is None:
        active_mask = jnp.ones((B,), bool)

    def step(carry, step_key):
        tok, pos, cache = carry
        logits, new_cache = model.decode_step(params, cache, tok, pos,
                                              sliding=sliding)
        nxt = _sample(logits, step_key, temperature)[:, None]
        nxt = jnp.where(active_mask[:, None], nxt, tok)
        new_pos = jnp.where(active_mask, pos + 1, pos)
        # freeze ALL per-slot cache rows of inactive slots. The KV slab write
        # would be rewritten identically next step (pos unchanged), but a
        # stale pos can point into a row now owned by a mid-prefill request,
        # and recurrent (mamba/xLSTM) state integrates every step — both
        # must be masked back to their previous values.
        new_cache = _freeze_inactive(new_cache, cache, active_mask)
        return (nxt, new_pos, new_cache), nxt[:, 0]

    keys = jax.random.split(key, k)
    (last, pos, cache), toks = jax.lax.scan(
        step, (first_token, start_pos, cache), keys)
    return toks.T, cache, pos


def lookahead_decode_paged(model: Model, params, pools, state,
                           first_token: jax.Array, start_pos: jax.Array,
                           tables: jax.Array, k: int, *,
                           key: Optional[jax.Array] = None,
                           temperature: float = 0.0,
                           active_mask: Optional[jax.Array] = None):
    """Paged-KV variant of :func:`lookahead_decode`: k fused decode steps
    against per-layer page pools with fixed block tables. The engine's
    look-ahead reservation guarantees every (page, slot) address touched by
    the k steps is already allocated, so ``tables`` stays constant across
    the scan — the host never syncs mid-program (§4.3).

    Returns (tokens (B, k), pools, state, new_pos (B,)).
    """
    B = first_token.shape[0]
    if key is None:
        key = jax.random.PRNGKey(0)
    if active_mask is None:
        active_mask = jnp.ones((B,), bool)

    def step(carry, step_key):
        tok, pos, pools, state = carry
        old_state = state
        logits, pools, state = model.decode_step_paged(
            params, pools, state, tok, pos, tables)
        nxt = _sample(logits, step_key, temperature)[:, None]
        nxt = jnp.where(active_mask[:, None], nxt, tok)
        new_pos = jnp.where(active_mask, pos + 1, pos)
        # attention KV of inactive slots is safe by construction (all-zero
        # table rows write into the reserved null page), but recurrent state
        # integrates every step and must be frozen explicitly.
        state = _freeze_inactive(state, old_state, active_mask)
        return (nxt, new_pos, pools, state), nxt[:, 0]

    keys = jax.random.split(key, k)
    (last, pos, pools, state), toks = jax.lax.scan(
        step, (first_token, start_pos, pools, state), keys)
    return toks.T, pools, state, pos


def make_lookahead_fn(model: Model, k: int, *, temperature: float = 0.0,
                      sliding: bool = False, ctx=None):
    """jit-compiled k-step decode program (one per k — the engine caches
    these exactly like the paper caches one CUDA Graph per batch shape).

    ``ctx`` (:class:`repro.core.device.DeviceContext`): compile with
    explicit in/out shardings — params per the TP rules, the slab cache
    and all host-global metadata replicated. ``None`` keeps the
    placement-agnostic single-device program."""
    fn = functools.partial(lookahead_decode, model, k=k,
                           temperature=temperature, sliding=sliding)

    def run(params, cache, first_token, start_pos, key, active_mask):
        return fn(params, cache, first_token, start_pos, key=key,
                  active_mask=active_mask)

    if ctx is None:
        return jax.jit(run)
    rep = ctx.replicated
    return jax.jit(
        run,
        in_shardings=(ctx.param_shardings(), rep, rep, rep, rep, rep),
        out_shardings=(rep, rep, rep))


def make_paged_lookahead_fn(model: Model, k: int, *,
                            temperature: float = 0.0, ctx=None):
    """jit-compiled k-step paged decode program (one per k).

    With ``ctx``, the program pins the mesh layout end to end: params over
    the TP rules, page pools sharded on their KV-head axis (pages stay
    host-global), recurrent state / tokens / tables / positions
    replicated — decode state lives on the mesh across successive
    dispatches with no resharding between programs."""
    fn = functools.partial(lookahead_decode_paged, model, k=k,
                           temperature=temperature)

    def run(params, pools, state, first_token, start_pos, tables, key,
            active_mask):
        return fn(params, pools, state, first_token, start_pos, tables,
                  key=key, active_mask=active_mask)

    if ctx is None:
        return jax.jit(run)
    rep = ctx.replicated
    pool_sh = ctx.pool_shardings()
    return jax.jit(
        run,
        in_shardings=(ctx.param_shardings(), pool_sh, rep, rep, rep, rep,
                      rep, rep),
        out_shardings=(rep, pool_sh, rep, rep))


# ---------------------------------------------------------------------------
# Fused duet super-iteration (async engine): k look-ahead decode steps plus
# one prefill chunk compiled into a SINGLE device program. All sampling —
# including the first token of a finishing prefill — happens in-program, so
# the host never reads a device value to build the next dispatch; decode
# input tokens and positions stay resident on device (`last_tok`/`pos`
# threaded through successive programs with buffer donation off-CPU).
# ---------------------------------------------------------------------------
def _tree_slice(tree, idx):
    """Slice batch row `idx` (traced scalar ok) out of every leaf."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_slice_in_dim(a, idx, 1, axis=0), tree)


def _tree_write(tree, sub, idx):
    """Write a 1-row subtree back at batch row `idx` (traced scalar ok)."""
    return jax.tree.map(
        lambda full, part: jax.lax.dynamic_update_slice_in_dim(
            full, part.astype(full.dtype), idx, axis=0), tree, sub)


def make_superiter_fn(model: Model, kb: int, *, paged: bool, chunk: int = 0,
                      finish: bool = False, sample: bool = False,
                      temperature: float = 0.0, donate: bool = True,
                      duet_kernel: bool = False, ctx=None):
    """Build one fused duet super-iteration program.

    Static bucket parameters (each combination compiles once — the engine's
    dispatch cache keys on them plus the argument shape buckets):

      kb      — look-ahead decode depth (0 = prefill-only dispatch)
      chunk   — prefill chunk length (0 = decode-only dispatch)
      finish  — this chunk completes the prompt: set the slot's position and
                decode input token in-program
      sample  — the finishing token is argmax-sampled from the chunk logits
                (False on preemption resume: the host already knows the next
                token and passes it as ``override_tok``)

    Signatures (B = engine slot count, W/Wp = block-table width buckets,
    C = chunk):

      paged: run(params, pools, state, last_tok (B,1), pos (B,),
                 tables (B,W), key, active (B,),
                 pre_toks (1,C), pre_tbl (1,Wp), pre_start, pre_slot,
                 override_tok)
               -> (toks (B,kb), sampled, last_tok, pos, pools, state, key)
      slab:  run(params, cache, last_tok, pos, key, active,
                 pre_toks, pre_start, pre_slot, override_tok)
               -> (toks (B,kb), sampled, last_tok, pos, cache, key)

    ``sampled`` is the finishing prefill's next-token (or -1): the host
    fetches it together with ``toks`` in the single per-iteration sync.

    The jitted function is named by kind, so a profile's ``XLA Modules``
    line tells the programs apart: ``superiter_decode`` (chunk 0),
    ``superiter_prefill`` (kb 0), ``superiter_mixed`` (the decode steps,
    then the chunk) and ``superiter_duet`` (the fused duet grid). Inside,
    named scopes mark ``decode_step``, ``prefill_chunk``, ``duet_grid`` and
    ``sample``.

    ``ctx`` (:class:`repro.core.device.DeviceContext`): compile the fused
    program with explicit in/out shardings, so the whole super-iteration —
    k decode steps, the prefill chunk, in-program sampling, and the
    device-resident ``last_tok``/``pos`` carry — executes on the mesh with
    params TP-sharded and page pools sharded over the KV-head axis. The
    async engine's single batched ``device_get`` per super-iteration is
    unchanged: every fetched output is replicated, so the read is local.
    """
    if kb == 0 and chunk == 0:
        raise ValueError("empty super-iteration")
    if duet_kernel and (not paged or kb == 0 or chunk == 0):
        raise ValueError("duet_kernel needs paged mode with both phases "
                         "(kb > 0 and chunk > 0)")

    @jax.named_scope("decode_step")
    def _decode(params, kvstate, last_tok, pos, tables, dkey, active):
        if paged:
            pools, state = kvstate
            toks, pools, state, pos = lookahead_decode_paged(
                model, params, pools, state, last_tok, pos, tables, kb,
                key=dkey, temperature=temperature, active_mask=active)
            kvstate = (pools, state)
        else:
            (cache,) = kvstate
            toks, cache, pos = lookahead_decode(
                model, params, cache, last_tok, pos, kb, key=dkey,
                temperature=temperature, active_mask=active)
            kvstate = (cache,)
        # feed the last generated token back as the next decode input; the
        # engine guarantees kb <= remaining output for every batch member,
        # so the final scan step is always a live token for active slots
        last_tok = jnp.where(active[:, None], toks[:, -1:], last_tok)
        return toks, last_tok, pos, kvstate

    @jax.named_scope("prefill_chunk")
    def _prefill(params, kvstate, last_tok, pos, pre_toks, pre_tbl,
                 pre_start, pre_slot, override_tok):
        if paged:
            pools, state = kvstate
            sub = _tree_slice(state, pre_slot)
            logits, pools, sub = model.prefill_paged(
                params, pre_toks, pools, sub, pre_tbl, start_pos=pre_start)
            kvstate = (pools, _tree_write(state, sub, pre_slot))
        else:
            (cache,) = kvstate
            sub = _tree_slice(cache, pre_slot)
            logits, sub = model.prefill(params, pre_toks, cache=sub,
                                        start_pos=pre_start)
            kvstate = (_tree_write(cache, sub, pre_slot),)
        sampled = jnp.int32(-1)
        if finish:
            with jax.named_scope("sample"):
                tok = (jnp.argmax(logits[0]).astype(jnp.int32) if sample
                       else override_tok)
            last_tok = jax.lax.dynamic_update_slice(
                last_tok, tok[None, None], (pre_slot, 0))
            pos = jax.lax.dynamic_update_slice(
                pos, (pre_start + chunk)[None].astype(pos.dtype),
                (pre_slot,))
            if sample:
                sampled = tok
        return sampled, last_tok, pos, kvstate

    shardings = {}
    if duet_kernel:
        # Algorithm-1 fused grid: decode step 1 and the whole prefill chunk
        # execute as ONE duet_attention_paged launch per layer (decode rows
        # + chunk rows, interleaved by the `order` tile permutation); the
        # remaining kb-1 look-ahead steps run as the usual fused scan.
        # Same signature as the paged program plus the trailing `order`
        # (B+chunk,) input, so the async engine's one-device_get contract
        # and donation layout are unchanged.
        def fused(params, pools, state, last_tok, pos, tables, key, active,
                  pre_toks, pre_tbl, pre_start, pre_slot, override_tok,
                  order):
            B = last_tok.shape[0]
            key, dkey = jax.random.split(key)
            k_first, k_rest = jax.random.split(dkey)
            row_tok = jnp.concatenate([last_tok[:, 0], pre_toks[0]])[:, None]
            row_pos = jnp.concatenate(
                [pos, pre_start + jnp.arange(chunk, dtype=pos.dtype)])
            W, Wp = tables.shape[1], pre_tbl.shape[1]
            Wm = max(W, Wp)
            row_tbl = jnp.concatenate([
                jnp.pad(tables, ((0, 0), (0, Wm - W))),
                jnp.repeat(jnp.pad(pre_tbl, ((0, 0), (0, Wm - Wp))),
                           chunk, axis=0)])
            with jax.named_scope("duet_grid"):
                logits, pools, state = model.duet_step_paged(
                    params, pools, state, row_tok, row_pos, row_tbl, order)
            # decode step 1 retires inside the duet grid
            nxt = _sample(logits[:B], k_first, temperature)[:, None]
            nxt = jnp.where(active[:, None], nxt, last_tok)
            pos = jnp.where(active, pos + 1, pos)
            toks = nxt
            if kb > 1:
                with jax.named_scope("decode_step"):
                    rest, pools, state, pos = lookahead_decode_paged(
                        model, params, pools, state, nxt, pos, tables,
                        kb - 1, key=k_rest, temperature=temperature,
                        active_mask=active)
                toks = jnp.concatenate([nxt, rest], axis=1)
            last_tok = jnp.where(active[:, None], toks[:, -1:], last_tok)
            # the chunk's last row carries the prefill logits
            sampled = jnp.int32(-1)
            if finish:
                with jax.named_scope("sample"):
                    tok = (jnp.argmax(logits[B + chunk - 1])
                           .astype(jnp.int32) if sample else override_tok)
                last_tok = jax.lax.dynamic_update_slice(
                    last_tok, tok[None, None], (pre_slot, 0))
                pos = jax.lax.dynamic_update_slice(
                    pos, (pre_start + chunk)[None].astype(pos.dtype),
                    (pre_slot,))
                if sample:
                    sampled = tok
            return toks, sampled, last_tok, pos, pools, state, key

        donate_argnums = (1, 2, 3, 4) if donate else ()
        if ctx is not None:
            rep = ctx.replicated
            pool_sh = ctx.pool_shardings()
            shardings = dict(
                in_shardings=(ctx.param_shardings(), pool_sh) + (rep,) * 12,
                out_shardings=(rep, rep, rep, rep, pool_sh, rep, rep))
    elif paged:
        def fused(params, pools, state, last_tok, pos, tables, key, active,
                  pre_toks, pre_tbl, pre_start, pre_slot, override_tok):
            key, dkey = jax.random.split(key)
            kvstate = (pools, state)
            toks = jnp.zeros((last_tok.shape[0], 0), jnp.int32)
            sampled = jnp.int32(-1)
            if kb > 0:
                toks, last_tok, pos, kvstate = _decode(
                    params, kvstate, last_tok, pos, tables, dkey, active)
            if chunk > 0:
                sampled, last_tok, pos, kvstate = _prefill(
                    params, kvstate, last_tok, pos, pre_toks, pre_tbl,
                    pre_start, pre_slot, override_tok)
            pools, state = kvstate
            return toks, sampled, last_tok, pos, pools, state, key

        donate_argnums = (1, 2, 3, 4) if donate else ()
        if ctx is not None:
            rep = ctx.replicated
            pool_sh = ctx.pool_shardings()
            shardings = dict(
                in_shardings=(ctx.param_shardings(), pool_sh) + (rep,) * 11,
                out_shardings=(rep, rep, rep, rep, pool_sh, rep, rep))
    else:
        def fused(params, cache, last_tok, pos, key, active,
                  pre_toks, pre_start, pre_slot, override_tok):
            key, dkey = jax.random.split(key)
            kvstate = (cache,)
            toks = jnp.zeros((last_tok.shape[0], 0), jnp.int32)
            sampled = jnp.int32(-1)
            if kb > 0:
                toks, last_tok, pos, kvstate = _decode(
                    params, kvstate, last_tok, pos, None, dkey, active)
            if chunk > 0:
                sampled, last_tok, pos, kvstate = _prefill(
                    params, kvstate, last_tok, pos, pre_toks, None,
                    pre_start, pre_slot, override_tok)
            (cache,) = kvstate
            return toks, sampled, last_tok, pos, cache, key

        donate_argnums = (1, 2, 3) if donate else ()
        if ctx is not None:
            rep = ctx.replicated
            shardings = dict(
                in_shardings=(ctx.param_shardings(),) + (rep,) * 9,
                out_shardings=(rep,) * 6)
    kind = ("duet" if duet_kernel else "decode" if chunk == 0
            else "prefill" if kb == 0 else "mixed")
    fused.__name__ = fused.__qualname__ = f"superiter_{kind}"
    return jax.jit(fused, donate_argnums=donate_argnums, **shardings)
