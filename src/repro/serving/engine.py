"""Real-JAX DuetServe engine: continuous batching with chunked prefill,
adaptive duet multiplexing, paged-KV execution, and interruption-free
look-ahead decode (fused k-step jitted programs, §4.3).

Execution vs time accounting: the engine *computes real tokens* with the JAX
model (greedy/temperature sampling) on whatever devices the session's mesh
provides — host CPU devices in tests/CI, TPU chips on the serving target.
The engine clock deliberately advances by the attention-aware roofline
prediction rather than wall time — the same oracle the paper's scheduler
uses and validates (Fig. 8; reproduced against real JAX wall-time in
benchmarks/fig8). So its metrics (TTFT/TBT/throughput) are predictions for
the device the replica runs on (``roofline.hardware_spec``; the CPU prices
the v5e virtual target) on every platform, the chip included, and they are
reproducible across hosts while every generated token is real. Measured
time comes only from the host clock around the run (``serve.py``'s
``wall_*`` fields).

KV memory (DESIGN.md §3): by default attention KV lives in per-layer device
page pools (PagedAttention layout) addressed through per-request block
tables; admission is page-granular against the live
:class:`PagedKVCacheManager`, look-ahead decode preallocates pages for all k
fused steps, and under pool pressure the engine first shrinks k, then
preempts a victim (free its pages, requeue for recompute-from-prompt).
``EngineConfig(paged=False)`` keeps the fixed-slot slab cache as the
equivalence oracle — there ``max_slots x max_len`` is a hard per-request and
aggregate ceiling, while the paged path serves any request whose footprint
fits the pool.

Duet mode on a single chip uses the fused duet-attention kernel's grid
partitioning (kernel-level analogue of SM masking — DESIGN.md §2); across
chips the launcher splits the mesh instead (launch/serve.py).

Mesh-aware execution (DESIGN.md §7): a :class:`DeviceContext` threads the
mesh + shardings through params, page pools and every jitted program;
single-device serving is the degenerate 1-device mesh, and TP>1 runs are
token-identical to it (tests/test_sharded_serving.py).
"""
from __future__ import annotations

import copy
import math
import warnings
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import GQA_KINDS, ArchConfig
from repro.core.device import DeviceContext
from repro.core.lookahead import make_lookahead_fn, make_paged_lookahead_fn
from repro.core.multiplexer import AdaptiveMultiplexer
from repro.core.roofline import HardwareSpec, RooflineModel, hardware_spec
from repro.models.transformer import Model
from repro.serving.kvcache import (DEFAULT_PAGE_SIZE, HostPoolConfig,
                                   PagedKVCacheManager, PagePoolConfig,
                                   copy_pool_pages, init_page_pools)
from repro.serving.request import (Phase, Request, ServingMetrics,
                                   synth_prompt_tokens)
from repro.serving.scheduler import DuetPolicy, IterationPlan, QueueState

K_BUCKETS = (1, 2, 4, 8, 16, 32)


def _k_bucket(k: int) -> int:
    for b in reversed(K_BUCKETS):
        if k >= b:
            return b
    return 1


@dataclass
class EngineConfig:
    max_slots: int = 8           # concurrent requests resident on the chip
    max_len: int = 2048          # slab KV length per slot (slab mode only)
    token_budget: int = 512
    tbt_slo: float = 0.1
    units: int = 1               # chips in this replica
    tp: int = 1
    page_size: int = DEFAULT_PAGE_SIZE
    temperature: float = 0.0
    sched_overhead: float = 0.0005
    dispatch_overhead: float = 0.004
    # paged-KV execution (default). ``kv_pool_tokens`` sizes the device page
    # pools; None matches the slab budget (max_slots * max_len) so the two
    # modes are capacity-equivalent out of the box.
    paged: bool = True
    kv_pool_tokens: Optional[int] = None
    # copy-on-write prefix caching over the paged pool (ignored in slab
    # mode). Requests sharing a prompt prefix map the cached pages
    # read-only and prefill only the uncached suffix.
    prefix_cache: bool = True
    # host-DRAM demotion tier (DESIGN.md §9): cold cached pages demote to a
    # numpy page store of ``host_kv_tokens`` capacity instead of being
    # evicted, and promote back on a prefix hit. ``kv_quant`` picks the
    # host storage format: "none" = fp32 (byte-exact round trips), "int8" =
    # symmetric per-tensor quantization with stored scales. 0 disables the
    # tier (eviction-only baseline). Requires paged + prefix_cache.
    host_kv_tokens: int = 0
    kv_quant: str = "none"
    # Pallas kernel path (Model.attn_kernel engines). The capability probe
    # resolves the executed path into ``DuetEngine.kernel_path`` (one of
    # KERNEL_PATHS); a kernel request the geometry cannot serve is a
    # ValueError. ``split_kv_threshold``: table capacity in tokens above
    # which decode uses the flash-decoding split-KV kernel — None prices
    # the threshold from the roofline, 0 disables splitting.
    split_kv_threshold: Optional[int] = None

    KERNEL_PATHS = ("jnp", "pallas", "pallas_sharded")


def params_dtype(params) -> jnp.dtype:
    """Storage dtype of a parameter tree (its embedding table's): the
    engine keeps its page pools and state cache in the same dtype."""
    emb = params.get("embedding", params.get("codebook_embeddings"))
    return emb.dtype


class DuetEngine:
    def __init__(self, model: Model, params, engine_cfg: EngineConfig,
                 hw: Optional[HardwareSpec] = None, seed: int = 0,
                 ctx: Optional[DeviceContext] = None):
        self.model = model
        self.cfg: ArchConfig = model.cfg
        self.ec = engine_cfg
        self.key = jax.random.PRNGKey(seed)
        self.paged = engine_cfg.paged

        # device context: mesh + shardings. Single-device serving is the
        # degenerate 1-device mesh, so there is exactly one execution path
        # and TP>1 cannot drift from the tested single-chip behavior.
        self.ctx = ctx if ctx is not None else DeviceContext.single(self.cfg)
        # the roofline prices the device this replica runs on
        self.hw = hw if hw is not None else hardware_spec(
            self.ctx.mesh.devices.flat[0].device_kind)
        if engine_cfg.tp not in (1, self.ctx.tp) and self.ctx.tp != 1:
            raise ValueError(
                f"EngineConfig.tp={engine_cfg.tp} contradicts the device "
                f"context's model axis ({self.ctx.tp}); pass one geometry")
        # tp for planning: the executed mesh wins; EngineConfig.tp remains
        # the modeling-only knob for single-device what-if runs
        self._tp = self.ctx.tp if self.ctx.tp > 1 else engine_cfg.tp
        # capability probe: resolve the attention path this engine will
        # actually execute and pin it on a per-engine Model copy (other
        # engines may share the Model). ``kernel_path`` is the explicit
        # report — surfaced by serve.py in summaries and the JSONL mesh
        # event; a kernel request the geometry cannot serve is an error.
        model = copy.copy(model)
        self.kernel_path = "jnp"
        if model.attn_kernel:
            if self.ctx.tp == 1:
                self.kernel_path = "pallas"
            elif self.paged and self.ctx.rules().get("kv_heads") == "model":
                # per-shard grids read their local page-pool shard; block
                # tables stay host-global (replicated)
                self.kernel_path = "pallas_sharded"
                model.kernel_mesh = self.ctx.mesh
            else:
                reason = (
                    "non-paged serving has no sharded slab kernel"
                    if not self.paged else
                    f"kv heads ({self.cfg.num_kv_heads}) do not shard over "
                    f"the model axis ({self.ctx.tp})")
                raise ValueError(
                    f"attn_kernel unusable under this geometry ({reason})")
            if self.paged:
                thr = engine_cfg.split_kv_threshold
                if thr is None:  # roofline-priced default; 0 disables
                    thr = RooflineModel(
                        self.cfg, self.hw, tp=self._tp,
                        page_size=engine_cfg.page_size).split_kv_threshold()
                model.split_kv_threshold = int(thr)
        assert self.kernel_path in EngineConfig.KERNEL_PATHS
        self.model = model
        self.params = self.ctx.place_params(params)

        # prefix caching skips the matched prefix's prefill entirely, which
        # is only sound when every layer's sequence state lives in the paged
        # KV pool. Recurrent blocks (mamba2/slstm/mlstm) keep per-slot state
        # that must process every prompt token, so for hybrid/recurrent
        # patterns a prefix hit would silently produce wrong tokens.
        self.prefix_cache = self.paged and engine_cfg.prefix_cache
        if self.prefix_cache and not self.cfg.attention_only:
            warnings.warn(
                f"prefix_cache disabled for {self.cfg.name}: block pattern "
                "contains recurrent layers whose per-slot state must "
                "process every prompt token; serving a cached prefix would "
                "corrupt it")
            self.prefix_cache = False

        ps = engine_cfg.page_size
        # pools and state cache store KV in the parameters' dtype (bf16
        # serving keeps bf16 KV; float32 oracles stay float32 end to end)
        dtype = params_dtype(self.params)
        if self.paged:
            pool_tokens = engine_cfg.kv_pool_tokens \
                or engine_cfg.max_slots * engine_cfg.max_len
            num_pages = -(-pool_tokens // ps) + 1   # +1: reserved null page
            host_pool = None
            if engine_cfg.host_kv_tokens > 0 and self.prefix_cache:
                host_pool = HostPoolConfig(
                    num_pages=-(-engine_cfg.host_kv_tokens // ps),
                    quant=engine_cfg.kv_quant)
            self.kv_mgr = PagedKVCacheManager(
                PagePoolConfig(num_pages=num_pages, page_size=ps),
                prefix_cache=self.prefix_cache, host_pool=host_pool)
            # block-table width: one request may span the whole pool
            self.max_pages = num_pages - 1
            self.pools = init_page_pools(self.cfg, self.kv_mgr.pool, dtype,
                                         shardings=self.ctx.pool_shardings())
            self.cache = self.ctx.place_replicated(
                model.init_state_cache(engine_cfg.max_slots, dtype))
        else:
            pool_pages = engine_cfg.max_slots * (
                -(-engine_cfg.max_len // ps)) + 1
            self.kv_mgr = PagedKVCacheManager(
                PagePoolConfig(num_pages=pool_pages, page_size=ps))
            self.max_pages = -(-engine_cfg.max_len // ps)
            self.pools = None
            self.cache = self.ctx.place_replicated(
                model.init_cache(engine_cfg.max_slots, engine_cfg.max_len,
                                 dtype))
        # the multiplexer and the partition optimizer plan with the SAME
        # geometry the sharded programs execute: the mesh sets the
        # communication term's TP degree, and a TP replica spans tp chips
        self.mux = AdaptiveMultiplexer(
            self.cfg, hw=self.hw,
            total_units=max(engine_cfg.units, self._tp),
            tbt_slo=engine_cfg.tbt_slo, tp=self._tp,
            page_size=ps if self.paged else 1,
            mesh=self.ctx.mesh if self.ctx.tp > 1 else None)
        self.policy = DuetPolicy(self.mux,
                                 token_budget=engine_cfg.token_budget,
                                 max_batch=engine_cfg.max_slots,
                                 kv_mgr=self.kv_mgr,
                                 reserve_on_admit=False)
        self.state = QueueState()
        self.now = 0.0
        self.free_slots = list(range(engine_cfg.max_slots))
        self.slot_pos = np.zeros(engine_cfg.max_slots, np.int32)
        self.slot_last_token = np.zeros(engine_cfg.max_slots, np.int32)
        self.finished: List[Request] = []
        # submission queue + epoch bookkeeping: ``submit`` accumulates, the
        # serving loop consumes, and ``run`` reports metrics over the
        # requests ingested since the previous ``run`` (so a reused or
        # router-driven engine never double-counts)
        self._pending: List[Request] = []
        self._all: List[Request] = []
        self._epoch = 0
        self._epoch_now = 0.0
        self._decode_fns: Dict[int, callable] = {}
        # prefill programs carry explicit in/out shardings: params per the
        # TP rules, pools sharded on the KV-head axis, everything host-
        # global (tokens, tables, start offsets, logits) replicated
        rep = self.ctx.replicated
        psh = self.ctx.param_shardings()
        pool_sh = self.ctx.pool_shardings()
        self._prefill_fn = jax.jit(
            lambda p, toks, cache, start: model.prefill(
                p, toks, cache=cache, start_pos=start),
            in_shardings=(psh, rep, rep, rep),
            out_shardings=(rep, rep))
        self._prefill_paged_fn = jax.jit(
            lambda p, toks, pools, state, tbl, start: model.prefill_paged(
                p, toks, pools, state, tbl, start_pos=start),
            in_shardings=(psh, rep, pool_sh, rep, rep, rep),
            out_shardings=(rep, pool_sh, rep))

    # ------------------------------------------------------------- plumbing
    def _decode_fn(self, k: int):
        if k not in self._decode_fns:
            if self.paged:
                self._decode_fns[k] = make_paged_lookahead_fn(
                    self.model, k, temperature=self.ec.temperature,
                    ctx=self.ctx)
            else:
                self._decode_fns[k] = make_lookahead_fn(
                    self.model, k, temperature=self.ec.temperature,
                    ctx=self.ctx)
        return self._decode_fns[k]

    def _table_width(self, rids: List[int]) -> int:
        """Per-dispatch block-table width: the smallest power-of-two bucket
        covering the widest table in the batch. Keeps the jnp gather path
        O(context) instead of O(pool) while bounding jit recompiles;
        ``max_pages`` stays the admission bound only."""
        n = max((len(self.kv_mgr.page_table(rid)) for rid in rids),
                default=1)
        return 1 << (max(1, n) - 1).bit_length()

    def _slice_cache(self, slot: int):
        return jax.tree.map(lambda a: a[slot:slot + 1], self.cache,
                            is_leaf=lambda x: isinstance(x, jnp.ndarray))

    def _write_cache(self, slot: int, sub):
        self.cache = jax.tree.map(
            lambda full, part: full.at[slot].set(part[0]), self.cache, sub)

    # ------------------------------------------------------------ lifecycle
    def _materialize_prompt(self, r: Request):
        """Deterministic rid-derived prompt tokens for trace requests that
        carry lengths only (shared with the async engine)."""
        if r.prompt_tokens is None:
            r.prompt_tokens = synth_prompt_tokens(
                r.rid, self.cfg.vocab_size, r.prompt_len)

    def submit(self, requests: Union[Request, Sequence[Request]],
               at: Optional[float] = None):
        """Enqueue requests for serving.

        Calls accumulate: a cluster router (or any incremental driver)
        submits requests one at a time as it routes them, interleaved with
        :meth:`service_until` calls.

        Args:
            requests: one :class:`Request` or a sequence of them. Trace
                requests carrying lengths only get deterministic
                rid-derived prompt tokens materialised here.
            at: optional arrival-time override applied to every submitted
                request (pass ``engine.now`` for "now").
        """
        if isinstance(requests, Request):
            requests = [requests]
        reqs = list(requests)
        for r in reqs:
            self._materialize_prompt(r)
            if at is not None:
                r.arrival = at
        self._pending.extend(reqs)
        self._pending.sort(key=lambda r: r.arrival)
        self._all.extend(reqs)

    # --------------------------------------------------- admission / eviction
    def _admit_waiting(self) -> List[Request]:
        """Slot admission, FCFS. A request whose footprint can never fit is
        rejected with a recorded outcome — never silently dropped. Newly
        slotted requests take a prefix-cache lock so scheduling, admission
        and the roofline all see the reduced (uncached-suffix) prefill.
        Returns the rejected requests (the async engine emits events)."""
        rejected = []
        for r in list(self.state.waiting):
            if not self._admissible(r):
                self.state.waiting.remove(r)
                self._reject(r, "kv_footprint_exceeds_capacity")
                rejected.append(r)
            elif r.slot is None and self.free_slots:
                r.slot = self.free_slots.pop()
                self._try_prefix_lock(r)
        return rejected

    def _try_prefix_lock(self, r: Request):
        """Start ``r`` at its longest cached prefix: matched pages map
        read-only into its block table and ``prefilled`` jumps to the
        matched length, so only the uncached suffix is scheduled. Also
        covers preemption-recompute — a victim whose prompt pages are still
        cached resumes from them instead of replaying the full prefill."""
        if not self.prefix_cache:
            return
        if r.prefilled or self.kv_mgr.page_table(r.rid):
            return
        matched = self.kv_mgr.lock_prefix(r.rid, r.prefill_token_ids())
        if matched:
            r.prefilled = matched
            r.cached_prompt += matched

    def _admissible(self, r: Request) -> bool:
        """Can this request's full KV footprint ever fit the engine?"""
        if self.paged:
            need = -(-(r.prompt_len + r.output_len) // self.ec.page_size)
            return need <= self.max_pages
        return r.prompt_len + r.output_len <= self.ec.max_len

    def _reject(self, r: Request, why: str):
        if r.slot is not None:
            self.free_slots.append(r.slot)
            r.slot = None
        self.kv_mgr.free(r.rid)
        r.phase = Phase.REJECTED
        r.finish_reason = f"rejected:{why}"
        self.finished.append(r)

    def _preempt(self, r: Request):
        """Victim eviction: free the request's pages and requeue it at the
        head of the waiting queue for recompute-from-prompt (the prefill will
        replay prompt + already-sampled outputs; greedy decode regenerates
        the identical suffix)."""
        self.kv_mgr.free(r.rid)
        if r.generated:
            r.resume_len = r.prompt_len + r.generated - 1
        r.prefilled = 0
        r.preemptions += 1
        r.phase = Phase.WAITING
        if r in self.state.running:
            self.state.running.remove(r)
        if r in self.state.prefilling:
            self.state.prefilling.remove(r)
        if r.slot is not None:
            self.free_slots.append(r.slot)
            r.slot = None
        self.state.waiting.insert(0, r)

    def drain_requests(self):
        """Evict every live request for re-dispatch elsewhere (elastic
        scale-down): running/prefilling requests go through the
        recompute-from-prompt preemption path (greedy decode regenerates
        the identical suffix on the new replica), queued and pending ones
        are withdrawn as-is. Drained requests leave this engine's
        accounting entirely — the router re-submits them, so they must
        count exactly once in the merged metrics.

        Returns:
            ``(requests, events)`` — the drained requests sorted by
            ``(arrival, rid)``, plus any serving events flushed on the way
            (always ``[]`` for the synchronous engine; the async override
            retires its in-flight super-iteration first).
        """
        for r in list(self.state.running) + list(self.state.prefilling):
            self._preempt(r)
        drained = []
        for r in list(self.state.waiting):
            # waiting slot-holders may hold a prefix lock from admission
            if r.slot is not None:
                self.free_slots.append(r.slot)
                r.slot = None
            self.kv_mgr.free(r.rid)
            r.prefilled = 0
            drained.append(r)
        self.state.waiting.clear()
        drained.extend(self._pending)
        self._pending.clear()
        gone = {id(r) for r in drained}
        self._all = [r for r in self._all if id(r) not in gone]
        drained.sort(key=lambda r: (r.arrival, r.rid))
        return drained, []

    def _ensure_pages(self, r: Request, new_tokens: int) -> bool:
        """Make room for a prefill chunk (including a potential CoW copy of
        a shared first page). Only other in-flight prefills are evicted
        (latest arrival first — LIFO keeps FCFS fairness); decode requests
        are never sacrificed for prefill progress. If that is not enough the
        chunk is deferred: decode completions free pages."""
        def fits() -> bool:
            need = self.kv_mgr.pages_needed(r.rid, new_tokens) \
                + self.kv_mgr.cow_pages_needed(r.rid, r.prefilled)
            return need <= self.kv_mgr.free_pages

        if fits():
            return True
        pre = sorted((x for x in self.state.prefilling
                      if x is not r and self.kv_mgr.page_table(x.rid)),
                     key=lambda x: x.arrival, reverse=True)
        for victim in pre:
            self._preempt(victim)
            if fits():
                return True
        return False

    # ----------------------------------------------------- tier migrations
    def _service_tiers(self):
        """Move queued page migrations (DESIGN.md §9): capture demoted
        pages' pool content for the host store, then scatter promoted host
        blocks into their fresh pages. Demotions are captured *first* — a
        promotion may target the very page id whose old content is still
        queued for capture. Must run before any device op that may rewrite
        pool pages (the demoted ids are already back on the free list);
        both engines call it from every dispatch and CoW site."""
        if not self.paged or self.pools is None:
            return
        for page, key in self.kv_mgr.drain_demotions():
            self._capture_demotion(key, [
                None if p is None else (p[0][page], p[1][page])
                for p in self.pools])
        promos = self.kv_mgr.drain_promotions()
        if promos:
            idx = jnp.asarray([page for page, _, _ in promos])
            pools = []
            for li, p in enumerate(self.pools):
                if p is None:
                    pools.append(None)
                    continue
                k, v = p
                kv_new = [jnp.asarray(
                    np.stack([pl[li][j] for _, _, pl in promos]),
                    dtype=k.dtype) for j in (0, 1)]
                pools.append((k.at[idx].set(kv_new[0]),
                              v.at[idx].set(kv_new[1])))
            self.pools = pools

    def _capture_demotion(self, key: bytes, slices: List):
        """Read one demoted page's per-layer device slices to host and
        complete the migration. Synchronous engine: immediate blocking
        reads. The async engine overrides this to batch the reads into its
        single per-super-iteration ``device_get``."""
        self.kv_mgr.complete_demotion(key, [
            None if s is None else (np.asarray(s[0]), np.asarray(s[1]))
            for s in slices])

    def _cow_copy(self, copies):
        """Apply CoW page copies, servicing the migration queues first —
        the CoW destination may be the very page a pending demotion still
        needs to capture, so the capture must be enqueued before the copy
        overwrites it."""
        if copies:
            self._service_tiers()
            self.pools = copy_pool_pages(self.pools, copies)

    # ------------------------------------------------------------ execution
    def _exec_prefill_chunk(self, r: Request, chunk: int) -> str:
        """Run one prefill chunk. Returns "continue" (more prompt left),
        "first" (prompt done, first token sampled), "resumed" (prompt done,
        resuming after preemption — the next token was sampled before the
        preemption), or "deferred" (no pages and nothing to preempt)."""
        if not self._ensure_pages(r, chunk):
            return "deferred"
        if self.paged:
            # the chunk's first write may land in a shared/cached page
            # (fully page-aligned prefix hit): privatise it first
            self._cow_copy(self.kv_mgr.ensure_writable(r.rid, r.prefilled))
        self.kv_mgr.allocate(r.rid, chunk)
        toks = jnp.asarray(
            r.prefill_token_ids()[r.prefilled:r.prefilled + chunk])[None, :]
        sub = self._slice_cache(r.slot)
        if self.paged:
            # flush tier migrations before the program touches the pools:
            # promoted prefix pages must hold their content and demoted
            # pages must be captured before the chunk may rewrite them
            self._service_tiers()
            tbl = jnp.asarray(
                self.kv_mgr.padded_tables([r.rid],
                                          self._table_width([r.rid])))
            logits, self.pools, sub = self._prefill_paged_fn(
                self.params, toks, self.pools, sub, tbl,
                jnp.int32(r.prefilled))
        else:
            logits, sub = self._prefill_fn(self.params, toks, sub,
                                           jnp.int32(r.prefilled))
        self._write_cache(r.slot, sub)
        r.prefilled += chunk
        r.prefill_executed += chunk
        if r.remaining_prompt > 0:
            return "continue"
        if self.prefix_cache:
            self.kv_mgr.insert_prefix(r.rid, r.prefill_token_ids())
        self.slot_pos[r.slot] = r.prefill_total
        if r.resume_len:
            self.slot_last_token[r.slot] = r.output_tokens[-1]
            return "resumed"
        tok = int(jnp.argmax(logits[0]))
        self.slot_last_token[r.slot] = tok
        r.output_tokens.append(tok)
        return "first"

    def _reserve_for(self, reqs: List[Request], kb: int) -> int:
        """Shrink kb down the bucket ladder until the look-ahead reservation
        covers every request; 0 when even k=1 does not fit. The reservation
        also budgets the CoW copies the decode append may trigger
        (``headroom``), so :meth:`_privatize_decode_pages` can always take
        a page instead of crashing on an exhausted pool."""
        cow = sum(self.kv_mgr.cow_pages_needed(r.rid,
                                               self.kv_mgr.length(r.rid))
                  for r in reqs)
        while kb >= 1:
            if self.kv_mgr.reserve_lookahead([r.rid for r in reqs], kb,
                                             headroom=cow):
                return kb
            kb = _k_bucket(kb - 1) if kb > 1 else 0
        return 0

    def _plan_decode_batch(self, decode_reqs: List[Request],
                           k: int) -> Tuple[int, List[Request]]:
        """Host-side half of §4.3 decode planning: reserve look-ahead pages
        for k steps, shrinking k down the bucket ladder and preempting
        victims under pool pressure. Returns the bucketed depth and the
        surviving batch — pure bookkeeping, no device work, so the async
        engine can plan iteration i+1 while iteration i runs on device."""
        reqs = list(decode_reqs)
        kb = 0
        while reqs:
            # §4.3: preallocate KV pages for all k look-ahead steps up front;
            # under pool pressure shrink k, then evict a victim. The depth
            # is re-bucketed after capping at the shortest remaining output
            # so only K_BUCKETS values reach the dispatch caches — a raw
            # remainder (e.g. 3) would compile a fresh program per tail
            want = min(_k_bucket(k),
                       min(r.output_len - r.generated for r in reqs))
            want = _k_bucket(max(1, want))
            kb = self._reserve_for(reqs, want)
            if kb:
                break
            # decode-first priority: evict page-holding prefills before
            # sacrificing a decode request
            pre = [x for x in self.state.prefilling
                   if self.kv_mgr.page_table(x.rid)]
            if pre:
                self._preempt(max(pre, key=lambda r: r.arrival))
                continue
            victim = max(reqs, key=lambda r: r.arrival)
            reqs.remove(victim)
            self._preempt(victim)
        return kb, reqs

    def _privatize_decode_pages(self, reqs: List[Request]):
        """CoW guard for the decode append: only the page holding the next
        write position can be shared (look-ahead pages are fresh). With
        page-granular prefix matching the suffix page is private by
        construction, so this is normally a no-op — it exists so any future
        sub-page sharing (e.g. fork) cannot corrupt cached pages. The pages
        it may take were budgeted as reservation headroom in
        :meth:`_reserve_for`, so ``_take_page`` cannot fail here."""
        if not self.paged:
            return
        for r in reqs:
            self._cow_copy(
                self.kv_mgr.ensure_writable(r.rid,
                                            self.kv_mgr.length(r.rid)))

    def _decode_args(self, dec_reqs: List[Request], kb: int):
        """Decode-dispatch inputs (active mask, block tables, width bucket)
        for the current batch. Must be called while every batch member
        still owns its pages — the async engine retires completing
        requests before its dispatch runs."""
        B = self.ec.max_slots
        active = np.zeros(B, bool)
        for r in dec_reqs:
            active[r.slot] = True
        if self.paged and kb > 0 and dec_reqs:
            width = self._table_width([r.rid for r in dec_reqs])
            tbl = np.zeros((B, width), np.int32)
            rows = self.kv_mgr.padded_tables([r.rid for r in dec_reqs],
                                             width)
            for r, row in zip(dec_reqs, rows):
                tbl[r.slot] = row
        else:
            width = 1
            tbl = np.zeros((B, 1), np.int32)
        return active, tbl, width

    def _exec_decode(self, decode_reqs: List[Request],
                     k: int) -> Tuple[int, List[Request]]:
        kb, reqs = self._plan_decode_batch(decode_reqs, k)
        if not reqs:
            return 0, []
        self._privatize_decode_pages(reqs)
        self._service_tiers()
        active, tbl, _ = self._decode_args(reqs, kb)
        first = jnp.asarray(self.slot_last_token)[:, None]
        pos = jnp.asarray(self.slot_pos)
        self.key, sub = jax.random.split(self.key)
        fn = self._decode_fn(kb)
        if self.paged:
            toks, self.pools, self.cache, new_pos = fn(
                self.params, self.pools, self.cache, first, pos,
                jnp.asarray(tbl), sub, jnp.asarray(active))
        else:
            toks, self.cache, new_pos = fn(self.params, self.cache, first,
                                           pos, sub, jnp.asarray(active))
        toks = np.array(toks)
        self.slot_pos = np.array(new_pos)
        for r in reqs:
            seq = toks[r.slot, :kb]
            take = min(kb, r.output_len - r.generated)
            r.output_tokens.extend(int(t) for t in seq[:take])
            self.slot_last_token[r.slot] = int(seq[take - 1])
            self.kv_mgr.commit_tokens(r.rid, take)
        return kb, reqs

    # ------------------------------------------------------------- run loop
    def run(self) -> ServingMetrics:
        """Serve every submitted request to a terminal state.

        Returns:
            :class:`ServingMetrics` over the requests ingested since the
            previous ``run`` (epoch-scoped, so a reused engine's
            throughput numbers are not diluted by earlier epochs).
        """
        self.service_until(math.inf)
        reqs = self._all[self._epoch:]
        self._epoch = len(self._all)
        duration, self._epoch_now = self.now - self._epoch_now, self.now
        return ServingMetrics(requests=reqs, duration=duration)

    def service_until(self, t: float) -> List:
        """Advance the engine's virtual clock up to time ``t``.

        Runs serving-loop iterations while the engine has live work and
        ``now < t`` (an in-flight iteration may overshoot ``t`` — it was
        already committed when ``t`` passed). This is the cluster router's
        driver hook: replicas are stepped in lockstep to each arrival so
        dispatch decisions observe real replica state at route time.

        Args:
            t: virtual-time horizon (``math.inf`` = serve to completion).

        Returns:
            Serving events produced while advancing — always ``[]`` for
            the synchronous engine; the async engine returns its
            token/finish events.
        """
        out: List = []
        while self.now < t:
            evs, progressed = self._tick()
            out.extend(evs)
            if not progressed:
                break
        return out

    def _tick(self) -> Tuple[List, bool]:
        """One serving-loop pass: admit arrivals, plan, execute one
        iteration (or jump the clock to the next arrival, or reject
        starved requests). Returns ``(events, progressed)`` —
        ``progressed=False`` means nothing can advance without new
        submissions."""
        self.state.admit_arrivals(self._pending, self.now)
        self._admit_waiting()
        # slot-less requests stay queued in `waiting`; _plan() exposes
        # only slot-holders to the policy, the rest wait FCFS.
        plan = self._plan()
        if not plan.is_idle:
            self._execute(plan)
            return [], True
        if self._pending:
            self.now = max(self.now, self._pending[0].arrival)
            return [], True
        if self.state.waiting:
            # nothing runs, nothing is pending, and the policy still
            # refuses every waiting request: no completion can ever
            # free pages, so these can never start.
            for r in list(self.state.waiting):
                self.state.waiting.remove(r)
                self._reject(r, "kv_admission_starved")
            return [], True
        return [], False

    def outstanding_tokens(self) -> int:
        """Total tokens of work this replica still owes: the remaining
        prefill + decode tokens of every resident request
        (``QueueState.outstanding_loads``) plus submitted-but-unarrived
        requests. The cluster router's least-outstanding-tokens and
        prefix-affinity tie-break signal."""
        n = sum(ld.q for ld in self.state.outstanding_loads())
        n += sum(r.remaining_prompt + max(0, r.output_len - r.generated)
                 for r in self._pending)
        return n

    def _plan(self) -> IterationPlan:
        # only slot-admitted requests are schedulable
        sched_state = QueueState(
            waiting=[r for r in self.state.waiting if r.slot is not None],
            running=self.state.running,
            prefilling=self.state.prefilling)
        plan = self.policy.schedule(sched_state)
        # sync admission back
        for r, _ in plan.prefill:
            if r in self.state.waiting:
                self.state.waiting.remove(r)
                if r not in self.state.prefilling:
                    self.state.prefilling.append(r)
        self.state.prefilling = sched_state.prefilling
        return plan

    def _iteration_timing(self, plan: IterationPlan):
        """(k, t_decode, t_prefill) for this iteration from the roofline
        decision. Shared by the sync and async engines — their virtual
        clocks must advance identically for the oracle equivalence to
        extend to TTFT/TBT metrics."""
        if plan.mode == "duet" and plan.decision.partition is not None:
            part = plan.decision.partition
            return part.k, part.t_decode, part.t_prefill
        pre_loads, dec_loads = plan.loads()
        t_iter = self.mux.predict_mixed(pre_loads + dec_loads) \
            + self.ec.sched_overhead \
            + (self.ec.dispatch_overhead if plan.prefill else 0.0)
        return 1, t_iter, t_iter

    def _iteration_span(self, plan: IterationPlan, kb: int, t_d: float,
                        t_p: float) -> float:
        """Wall-clock span of this iteration on the virtual TPU clock."""
        if plan.mode == "duet" and plan.decision.partition is not None:
            return max(kb * t_d, t_p) + self.ec.sched_overhead \
                + self.ec.dispatch_overhead
        return t_d

    def _execute(self, plan: IterationPlan):
        k, t_d, t_p = self._iteration_timing(plan)
        kb, ran = (self._exec_decode(plan.decode, k)
                   if plan.decode else (0, []))
        # metrics: decode tokens at t_d spacing (decode dispatched first).
        # Recorded before the prefill chunks run so a preemption triggered by
        # a prefill allocation sees consistent generated/output counts.
        for j in range(1, kb + 1):
            ts = self.now + j * t_d
            for r in list(ran):
                if r.generated < len(r.output_tokens):
                    r.record_token(ts)
                    if r.done:
                        self.state.running.remove(r)
                        self._retire(r)
        for r, chunk in plan.prefill:
            if r.phase != Phase.PREFILL:
                continue   # preempted earlier in this iteration
            status = self._exec_prefill_chunk(r, chunk)
            if status in ("first", "resumed"):
                self.state.prefilling.remove(r)
                r.phase = Phase.DECODE
                if status == "first":
                    r.record_token(self.now + t_p)
                if r.done:
                    self._retire(r)
                else:
                    self.state.running.append(r)
        self.now += self._iteration_span(plan, kb, t_d, t_p)

    def _retire(self, r: Request):
        self.kv_mgr.free(r.rid)
        if r.slot is not None:
            self.free_slots.append(r.slot)
            r.slot = None
        self.finished.append(r)
