"""Interruption-free asynchronous serving engine (paper §4.3) with
streaming admission.

:class:`AsyncDuetEngine` removes the two host round-trips the synchronous
:class:`~repro.serving.engine.DuetEngine` pays every iteration:

* **Fused super-iteration dispatch** — the k look-ahead decode steps *and*
  the iteration's prefill chunk compile into a single device program
  (:func:`repro.core.lookahead.make_superiter_fn`). All sampling happens
  in-program: the decode input tokens and slot positions live on device
  (``d_last_tok`` / ``d_pos``) and thread from one program to the next with
  buffer donation off-CPU, so the host never reads a device value to build
  the next dispatch. Programs are cached per shape bucket — (k bucket,
  block-table width bucket, chunk length, finish/sample flags) — so a
  second iteration in the same bucket compiles nothing
  (``dstats.cache_hits``).

* **Double-buffered host scheduling** — while iteration *i* executes on
  device, the host plans iteration *i+1* from last-known loads: admission,
  page reservation and the duet/aggregated mux decision are pure
  bookkeeping (greedy decode makes completion deterministic from counts,
  so planning never needs token *values*). Iteration *i*'s tokens are
  fetched in one batched ``jax.device_get`` when *i+1* has already been
  dispatched — at most one blocking host sync per super-iteration
  (``dstats.host_syncs``), and token values are only ever needed to emit
  stream events and to replay a preemption victim's sampled outputs.

* **Streaming front-end** — :meth:`submit` accepts requests mid-run (from
  event callbacks, another thread, or an asyncio task) and the engine
  yields :class:`TokenEvent` / :class:`FinishEvent` through
  :meth:`events` (generator), :meth:`run` (callback), or :meth:`astream`
  (async iterator).

* **Host spans and stage stamps** — each phase of a super-iteration is one
  flat ``jax.profiler.TraceAnnotation`` on the profiler's clock, so a
  trace puts the host's phases beside the device's programs:
  ``engine.plan`` (ingest, admission, planning and page bookkeeping up to
  the first dispatch), ``engine.tiers`` (tier migrations), one
  ``engine.dispatch`` per program (argument preparation and the jitted
  call; ``engine.compile`` nested inside on a dispatch-cache miss),
  ``engine.fetch`` (the one blocking ``device_get``) and ``engine.emit``
  (building events, completing demotions). Every span carries ``si``, the
  super-iteration index; a dispatch adds ``kb``, ``chunk``, ``duet`` and
  ``predicted_ms``, the roofline's span of its super-iteration. The spans
  cost about a microsecond each when no profiler session is active.
  Requests carry host-clock stage times in ``Request.stamps``, and
  ``dstats.predicted_s`` sums the roofline's spans.

The synchronous engine remains the token-equivalence oracle: greedy decode
makes the async engine's output streams token-identical on the same trace
(``tests/test_async_engine.py``), on both the paged and the slab path.
"""
from __future__ import annotations

import asyncio
import contextlib
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Iterator, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import GQA_KINDS
from repro.core.device import DeviceContext
from repro.core.lookahead import make_superiter_fn
from repro.core.roofline import HardwareSpec
from repro.kernels import build_duet_schedule
from repro.models.transformer import Model
from repro.serving.engine import DuetEngine, EngineConfig
from repro.serving.request import Phase, Request, ServingMetrics
from repro.serving.scheduler import IterationPlan


# --------------------------------------------------------------------- events
@dataclass(frozen=True)
class TokenEvent:
    """One generated token, streamed as soon as its iteration retires.
    ``t`` is the emission time the roofline predicts, on the engine's
    virtual clock, not a measured time (``Request.stamps`` holds the host
    clock's)."""
    rid: int
    token: int
    index: int          # position in the request's output stream
    t: float            # predicted emission time (virtual clock)


@dataclass(frozen=True)
class FinishEvent:
    """Terminal outcome of a request (completed or rejected). ``t`` is a
    predicted time on the virtual clock, as in :class:`TokenEvent`."""
    rid: int
    reason: str         # "completed" | "rejected:<why>"
    t: float            # predicted time (virtual clock)
    n_tokens: int
    output_tokens: List[int] = field(default_factory=list)


Event = Union[TokenEvent, FinishEvent]


@dataclass
class DispatchStats:
    """Dispatch-cache and host-sync accounting for the async engine."""
    super_iterations: int = 0
    dispatches: int = 0          # device programs launched
    host_syncs: int = 0          # blocking device->host fetches
    cache_hits: int = 0          # dispatches served by a cached program
    cache_misses: int = 0        # dispatches that compiled a new bucket
    predicted_s: float = 0.0     # roofline's forecast of the iterations run

    @property
    def syncs_per_super_iteration(self) -> float:
        return self.host_syncs / max(1, self.super_iterations)


# ------------------------------------------------------------------ in-flight
@dataclass
class _DecItem:
    req: Request
    slot: int
    times: List[float] = field(default_factory=list)


@dataclass
class _FirstItem:
    req: Request
    fetch_idx: int
    ts: float


@dataclass
class _Inflight:
    """Device handles + host metadata of one dispatched super-iteration.
    The handles are program *outputs* captured at dispatch, so later
    programs can run before this record is drained."""
    si: int = 0                  # super-iteration index (span argument)
    fetch: List[jax.Array] = field(default_factory=list)
    toks_idx: int = -1
    dec_items: List[_DecItem] = field(default_factory=list)
    first_items: List[_FirstItem] = field(default_factory=list)
    # tier demotions riding this iteration's batched device_get: (digest,
    # per-layer (k_idx, v_idx) positions into `fetch`, None for recurrent
    # layers). The slices were enqueued before any pool-rewriting op, so
    # they read the pre-overwrite page content.
    demotions: List[tuple] = field(default_factory=list)


class AsyncDuetEngine(DuetEngine):
    """Asynchronous, interruption-free DuetServe engine.

    Inherits all host-side planning from :class:`DuetEngine` (admission,
    page-granular reservation, look-ahead shrink / victim preemption, duet
    mux decision) and replaces the execution layer with fused
    super-iteration programs and a double-buffered dispatch loop.
    """

    def __init__(self, model: Model, params, engine_cfg: EngineConfig,
                 hw: Optional[HardwareSpec] = None, seed: int = 0,
                 ctx: Optional[DeviceContext] = None):
        super().__init__(model, params, engine_cfg, hw=hw, seed=seed,
                         ctx=ctx)
        B = engine_cfg.max_slots
        # device-resident decode inputs: next token + cache position per
        # slot — replicated on the mesh, so they thread between sharded
        # super-iteration programs without resharding and the per-iteration
        # batched device_get stays a local read
        self.d_last_tok = self.ctx.place_replicated(
            jnp.zeros((B, 1), jnp.int32))
        self.d_pos = self.ctx.place_replicated(jnp.zeros((B,), jnp.int32))
        self.d_key = self.ctx.place_replicated(self.key)
        # donation rebinds cache/pool buffers in place; the CPU backend does
        # not implement it and would warn on every dispatch
        self._donate = jax.default_backend() != "cpu"
        # paged duet kernel: when the engine resolved the single-device
        # Pallas path and every block is GQA attention, the decode batch and
        # the prefill chunk fuse into ONE duet_attention_paged grid per
        # layer (paper Algorithm 1 mapped to the TPU grid). The tile
        # permutation depends only on (max_slots, chunk), so it is cached
        # per chunk bucket and rides the dispatch as a device input.
        self._duet_kernel = (
            self.kernel_path == "pallas" and self.paged
            and all(k in GQA_KINDS for k in self.cfg.block_pattern))
        self._duet_orders: dict = {}
        self._duet_safe = True
        self._programs: dict = {}
        self.dstats = DispatchStats()
        # _pending/_all/_epoch bookkeeping lives in the base engine; the
        # async front-end adds only the thread-safe inbox feeding it
        self._inbox: deque = deque()
        self._lock = threading.Lock()
        self._inflight: Optional[_Inflight] = None
        # demotion slices captured during the current super-iteration's
        # planning/dispatch, waiting to be attached to its _Inflight so
        # they ride the one batched device_get (no extra host syncs)
        self._tier_captures: List[tuple] = []

    # ------------------------------------------------------------- streaming
    def submit(self, requests: Union[Request, Sequence[Request]],
               at: Optional[float] = None):
        """Enqueue requests; callable any time, including mid-run (from an
        event callback, another thread, or an asyncio task). ``at``
        overrides the arrival time (pass ``engine.now`` for "now")."""
        if isinstance(requests, Request):
            requests = [requests]
        reqs = list(requests)
        host_t = time.perf_counter()
        for r in reqs:
            self._materialize_prompt(r)
            if at is not None:
                r.arrival = at
            r.stamps.setdefault("submit", host_t)
        with self._lock:
            self._inbox.extend(reqs)

    def _ingest(self):
        with self._lock:
            new = list(self._inbox)
            self._inbox.clear()
        if not new:
            return
        self._all.extend(new)
        self._pending.extend(new)
        self._pending.sort(key=lambda r: r.arrival)

    def _finish_event(self, r: Request, t: Optional[float] = None,
                      host_t: Optional[float] = None) -> FinishEvent:
        r.stamps["finish"] = time.perf_counter() if host_t is None \
            else host_t
        return FinishEvent(r.rid, r.finish_reason or "completed",
                           self.now if t is None else t,
                           len(r.output_tokens), list(r.output_tokens))

    # ------------------------------------------------------------- run loops
    def run(self, on_event: Optional[Callable[[Event], None]] = None
            ) -> ServingMetrics:
        """Serve until every submitted request reaches a terminal state.
        Returns metrics over the requests ingested since the last run."""
        for ev in self.events():
            if on_event is not None:
                on_event(ev)
        reqs = self._all[self._epoch:]
        self._epoch = len(self._all)
        # duration covers this run's span only, so throughput numbers of a
        # reused engine are not diluted by earlier epochs
        duration, self._epoch_now = self.now - self._epoch_now, self.now
        return ServingMetrics(requests=reqs, duration=duration)

    async def astream(self):
        """Async iterator over serving events. The blocking engine loop
        (dispatch, bucket compiles, the per-iteration ``device_get``) runs
        on a worker thread and events are pumped through an asyncio queue,
        so concurrent tasks on the loop — e.g. network handlers calling
        ``submit()`` — keep running. Note: abandoning the iterator early
        does not stop the engine; it serves the queues to completion."""
        loop = asyncio.get_running_loop()
        queue: asyncio.Queue = asyncio.Queue()
        done = object()

        def pump():
            try:
                for ev in self.events():
                    loop.call_soon_threadsafe(queue.put_nowait, ev)
            finally:
                loop.call_soon_threadsafe(queue.put_nowait, done)

        worker = loop.run_in_executor(None, pump)
        try:
            while True:
                ev = await queue.get()
                if ev is done:
                    break
                yield ev
        finally:
            await worker   # surfaces engine exceptions

    def events(self) -> Iterator[Event]:
        """Generator core: open-loop arrival replay plus streaming
        admission. Terminates when queues, pending arrivals and the inbox
        are all empty (mirrors the synchronous run loop).

        Yields:
            :class:`TokenEvent` / :class:`FinishEvent` in virtual-time
            order as super-iterations retire.
        """
        while True:
            evs, progressed = self._tick()
            yield from evs
            if not progressed:
                break
        yield from self._drain()   # safety net; the idle tick drained

    def _tick(self):
        """One pass of the async serving loop (shared ``(events,
        progressed)`` contract with the base engine, so ``service_until``
        can drive either engine class). An idle pass's ``engine.plan``
        span carries the index of the super-iteration that follows it."""
        evs: List[Event] = []
        si = self.dstats.super_iterations + 1
        with TraceAnnotation("engine.plan", si=si):
            self._ingest()
            self.state.admit_arrivals(self._pending, self.now)
            for r in self._admit_waiting():
                evs.append(self._finish_event(r))
            plan = self._plan()
            staged = None if plan.is_idle else self._stage(plan, si, evs)
        if staged is not None:
            evs.extend(self._launch(si, *staged))
            return evs, True
        # idle: flush the pipeline, then wait for arrivals or stop
        evs.extend(self._drain())
        self._ingest()
        if self._pending:
            self.now = max(self.now, self._pending[0].arrival)
            return evs, True
        if self.state.waiting:
            # nothing runs and the policy still refuses every waiting
            # request: no completion can ever free pages
            for r in list(self.state.waiting):
                self.state.waiting.remove(r)
                self._reject(r, "kv_admission_starved")
                evs.append(self._finish_event(r))
            return evs, True
        return evs, False

    def outstanding_tokens(self) -> int:
        """Outstanding-work signal for the cluster router; extends the
        base count with requests still sitting in the thread-safe inbox."""
        with self._lock:
            inbox = list(self._inbox)
        return super().outstanding_tokens() + sum(
            r.remaining_prompt + max(0, r.output_len - r.generated)
            for r in inbox)

    def drain_requests(self):
        """Elastic scale-down drain. The in-flight super-iteration is
        retired *first* — its device tokens belong to requests about to be
        preempted, and preempting under an open ``_Inflight`` would append
        a stale fetch onto a recomputing request — then the inbox is
        folded into ``_pending`` so withdrawn work includes requests not
        yet ingested. The flushed token/finish events are returned for the
        caller to stream (they happened; a drain must not swallow them)."""
        evs = list(self._drain())
        self._ingest()
        drained, more = super().drain_requests()
        return drained, evs + more

    # ---------------------------------------------------------------- tiers
    def _capture_demotion(self, key: bytes, slices: List):
        """Defer the host read: hold the page's device slices (enqueued
        eagerly, before any op that rewrites the page, so they see the
        pre-overwrite content) until :meth:`_attach_tier_captures` folds
        them into the iteration's single batched ``device_get``."""
        self._tier_captures.append((key, slices))

    def _attach_tier_captures(self, inf: _Inflight):
        """Append pending demotion slices to ``inf.fetch``; their values
        arrive with the iteration's one blocking sync and complete the
        migrations in :meth:`_drain_record`."""
        for key, slices in self._tier_captures:
            layout = []
            for s in slices:
                if s is None:
                    layout.append(None)
                else:
                    layout.append((len(inf.fetch), len(inf.fetch) + 1))
                    inf.fetch.extend(s)
            inf.demotions.append((key, layout))
        self._tier_captures = []

    # -------------------------------------------------------- super-iteration
    def _stage(self, plan: IterationPlan, si: int, evs: List[Event]):
        """Host half of super-iteration ``si``: the page and token
        bookkeeping of ``plan``, up to the dispatch inputs. Bookkeeping is
        completion-deterministic (greedy decode), so the whole plan is
        built from host state while the previous iteration is still
        executing on device. Events of a catch-up drain go to ``evs``
        (its fetch and emit spans then nest in ``engine.plan``).
        Returns ``(kb, ran, dec_items, dec_args, pre_items, t_p, span)``
        for :meth:`_launch`."""
        self.dstats.super_iterations += 1
        # a preemption-resume chunk that replays already-sampled outputs
        # (its token slice reaches past the prompt, or it finishes and
        # feeds output_tokens[-1] back as the decode input) is the only
        # plan input that needs device token values — catch up only then,
        # so earlier chunks of a long resume prefill keep the overlap
        if any(r.resume_len and (r.prefilled + c > r.prompt_len
                                 or c >= r.remaining_prompt)
               for r, c in plan.prefill):
            evs.extend(self._drain())

        k, t_d, t_p = self._iteration_timing(plan)

        kb, ran = (self._plan_decode_batch(plan.decode, k)
                   if plan.decode else (0, []))
        span = self._iteration_span(plan, kb, t_d, t_p)
        self._privatize_decode_pages(ran)
        # duet fusion safety: a decode request finishing inside this
        # iteration returns its pages to the pool below, and the prefill
        # chunk may reallocate them. The sequential program orders decode
        # reads before the chunk's writes; the fused duet grid does not —
        # so those iterations dispatch the sequential program instead.
        self._duet_safe = not any(
            r.output_len - r.generated <= kb for r in ran)
        dec_items = [_DecItem(r, r.slot) for r in ran]
        for r in ran:
            self.kv_mgr.commit_tokens(r.rid, kb)
        # snapshot the decode dispatch inputs NOW: a request completing in
        # this iteration is retired below (its pages return to the pool, as
        # in the synchronous engine), so its block-table row must be
        # captured while it still owns its pages
        dec_args = self._decode_args(ran, kb)
        # decode token accounting at t_d spacing (before prefill, matching
        # the synchronous engine): values arrive at drain time
        for j in range(1, kb + 1):
            ts = self.now + j * t_d
            for it in dec_items:
                if not it.req.done:
                    it.req.record_token(ts)
                    it.times.append(ts)
                    if it.req.done:
                        self.state.running.remove(it.req)
                        self._retire(it.req)

        pre_items = []
        for r, chunk in plan.prefill:
            if r.phase != Phase.PREFILL:
                continue   # preempted earlier in this iteration
            if not self._ensure_pages(r, chunk):
                continue   # deferred: decode completions free pages
            if self.paged:
                # privatise a shared first page (CoW) before the chunk's
                # program writes into it — device copy, no host sync (any
                # pending demotion capture is enqueued first, same rule)
                self._cow_copy(
                    self.kv_mgr.ensure_writable(r.rid, r.prefilled))
            self.kv_mgr.allocate(r.rid, chunk)
            start = r.prefilled
            toks_np = r.prefill_token_ids()[start:start + chunk]
            # a short slice means a resume replay ran ahead of the drain
            # gate — fail loudly rather than dispatch a truncated chunk
            assert len(toks_np) == chunk, \
                "prefill chunk dispatched with stale host token values"
            r.prefilled += chunk
            r.prefill_executed += chunk
            if r.remaining_prompt > 0:
                status = "continue"
            elif r.resume_len:
                status = "resumed"
            else:
                status = "first"
            if status != "continue" and self.prefix_cache:
                self.kv_mgr.insert_prefix(r.rid, r.prefill_token_ids())
            # snapshot the chunk's block table before any retire below can
            # free the pages (an output_len==1 request finishes here)
            if self.paged:
                pwidth = self._table_width([r.rid])
                ptbl = self.kv_mgr.padded_tables([r.rid], pwidth)
            else:
                pwidth, ptbl = 1, np.zeros((1, 1), np.int32)
            pre_items.append((r, chunk, start, toks_np, status, ptbl,
                              pwidth))
            if status in ("first", "resumed"):
                self.state.prefilling.remove(r)
                r.phase = Phase.DECODE
                if status == "first":
                    r.record_token(self.now + t_p)
                if r.done:
                    self._retire(r)
                else:
                    self.state.running.append(r)
        return kb, ran, dec_items, dec_args, pre_items, t_p, span

    def _launch(self, si: int, kb: int, ran, dec_items, dec_args,
                pre_items, t_p: float, span: float) -> List[Event]:
        """Device half of super-iteration ``si``: dispatch its programs,
        drain the previous super-iteration, and advance the virtual clock
        by the roofline's ``span``."""
        # device dispatch: decode fuses with the first prefill chunk into
        # one program; extra chunks ride prefill-only programs (more
        # dispatches, still zero extra host syncs)
        inf = _Inflight(si=si, dec_items=dec_items)
        if ran or pre_items:
            self._dispatch(inf, kb if ran else 0, dec_args,
                           pre_items[0] if pre_items else None, t_p, span)
            for item in pre_items[1:]:
                self._dispatch(inf, 0, None, item, t_p, span)
        # demotion slices captured while planning/dispatching this
        # iteration ride its batched device_get — zero extra host syncs
        self._attach_tier_captures(inf)
        prev, self._inflight = self._inflight, (inf if inf.fetch else None)
        evs = self._drain_record(prev) if prev is not None else []
        self.dstats.predicted_s += span
        self.now += span
        return evs

    # ---------------------------------------------------------------- device
    def _program(self, key, kb, chunk, finish, sample, duet=False):
        prog = self._programs.get(key)
        if prog is None:
            self.dstats.cache_misses += 1
            prog = make_superiter_fn(
                self.model, kb, paged=self.paged, chunk=chunk,
                finish=finish, sample=sample,
                temperature=self.ec.temperature, donate=self._donate,
                duet_kernel=duet, ctx=self.ctx)
            self._programs[key] = prog
        else:
            self.dstats.cache_hits += 1
        return prog

    def _duet_order(self, chunk: int) -> np.ndarray:
        """Tile permutation for the fused duet grid: decode rows 0..B-1
        interleaved among chunk rows B..B+chunk-1 at the Algorithm-1 ratio
        (block_q=1: one row per tile, so ``row_src`` IS the permutation).
        Scheduling-only — the kernel's online softmax is order-invariant."""
        order = self._duet_orders.get(chunk)
        if order is None:
            B = self.ec.max_slots
            sched = build_duet_schedule(
                [(b, 0) for b in range(B)],
                [(B, i) for i in range(chunk)], block_q=1)
            order = sched.row_src.astype(np.int32)
            self._duet_orders[chunk] = order
        return order

    def _dispatch(self, inf: _Inflight, kb: int, dec_args, pre_item,
                  t_p: float, span: float):
        """Launch one fused program; capture its output handles in `inf`.
        Everything here is host->device only — no blocking reads."""
        # flush tier migrations first: promoted pages must hold their
        # content before this program can read them, and pending demotion
        # slices must be enqueued before the program rewrites their pages
        with TraceAnnotation("engine.tiers", si=inf.si):
            self._service_tiers()
        chunk = pre_item[1] if pre_item is not None else 0
        duet = (self._duet_kernel and self._duet_safe
                and kb > 0 and chunk > 0)
        with TraceAnnotation("engine.dispatch", si=inf.si, kb=kb,
                             chunk=chunk, duet=duet, predicted_ms=1e3 * span):
            self._call_program(inf, kb, dec_args, pre_item, t_p, duet)

    def _call_program(self, inf: _Inflight, kb: int, dec_args, pre_item,
                      t_p: float, duet: bool):
        B = self.ec.max_slots
        if dec_args is None:
            dec_args = (np.zeros(B, bool), np.zeros((B, 1), np.int32), 1)
        active, tbl, width = dec_args

        if pre_item is not None:
            r, chunk, start, toks_np, status, ptbl, pwidth = pre_item
            finish = status in ("first", "resumed")
            sample = status == "first"
            pre_toks = jnp.asarray(toks_np)[None, :]
            pre_tbl = jnp.asarray(ptbl)
            pre_start = jnp.int32(start)
            pre_slot = jnp.int32(r.slot)
            if finish and not sample:
                # resume finish: the pre-preemption next token becomes the
                # decode input — the _step drain gate must have caught us up
                assert len(r.output_tokens) == r.generated, \
                    "resume dispatched with stale host token values"
                override = jnp.int32(r.output_tokens[-1])
            else:
                override = jnp.int32(0)
        else:
            chunk, finish, sample, pwidth = 0, False, False, 1
            pre_toks = jnp.zeros((1, 1), jnp.int32)
            pre_tbl = jnp.zeros((1, 1), jnp.int32)
            pre_start = jnp.int32(0)
            pre_slot = jnp.int32(0)
            override = jnp.int32(0)

        key = (self.paged, kb, width if kb else 0, chunk,
               pwidth if chunk else 0, finish, sample, duet)
        built = key not in self._programs
        prog = self._program(key, kb, chunk, finish, sample, duet)
        self.dstats.dispatches += 1
        if pre_item is not None:
            pre_item[0].stamps.setdefault("first_dispatch",
                                          time.perf_counter())
        compiling = (TraceAnnotation("engine.compile", si=inf.si) if built
                     else contextlib.nullcontext())
        if self.paged:
            pargs = (self.params, self.pools, self.cache, self.d_last_tok,
                     self.d_pos, jnp.asarray(tbl), self.d_key,
                     jnp.asarray(active), pre_toks, pre_tbl, pre_start,
                     pre_slot, override)
            if duet:
                pargs += (jnp.asarray(self._duet_order(chunk)),)
            with compiling:
                (toks, sampled, self.d_last_tok, self.d_pos, self.pools,
                 self.cache, self.d_key) = prog(*pargs)
        else:
            with compiling:
                (toks, sampled, self.d_last_tok, self.d_pos, self.cache,
                 self.d_key) = prog(
                    self.params, self.cache, self.d_last_tok, self.d_pos,
                    self.d_key, jnp.asarray(active), pre_toks, pre_start,
                    pre_slot, override)
        if kb > 0:
            inf.toks_idx = len(inf.fetch)
            inf.fetch.append(toks)
        if pre_item is not None and sample:
            inf.first_items.append(
                _FirstItem(pre_item[0], len(inf.fetch), self.now + t_p))
            inf.fetch.append(sampled)

    # ----------------------------------------------------------------- drain
    def _drain(self) -> List[Event]:
        inf, self._inflight = self._inflight, None
        return self._drain_record(inf) if inf is not None else []

    def _drain_record(self, inf: _Inflight) -> List[Event]:
        """Retire one dispatched super-iteration: fetch every device value it
        produced in a single blocking sync, append token values to their
        requests, and build its stream events. One host-clock reading,
        taken when the values arrive, stamps all of its requests."""
        if not inf.fetch:
            return []
        with TraceAnnotation("engine.fetch", si=inf.si):
            vals = jax.device_get(inf.fetch)
        host_t = time.perf_counter()
        self.dstats.host_syncs += 1
        evs: List[Event] = []
        with TraceAnnotation("engine.emit", si=inf.si):
            if inf.toks_idx >= 0:
                toks = np.asarray(vals[inf.toks_idx])
                for it in inf.dec_items:
                    seq = toks[it.slot, :len(it.times)]
                    base = len(it.req.output_tokens)
                    it.req.output_tokens.extend(int(t) for t in seq)
                    evs.extend(
                        TokenEvent(it.req.rid, int(tok), base + j, ts)
                        for j, (tok, ts) in enumerate(zip(seq, it.times)))
                    evs.extend(self._maybe_finish(it.req, host_t))
            for fi in inf.first_items:
                tok = int(vals[fi.fetch_idx])
                fi.req.stamps.setdefault("first_fetch", host_t)
                evs.append(TokenEvent(fi.req.rid, tok,
                                      len(fi.req.output_tokens), fi.ts))
                fi.req.output_tokens.append(tok)
                evs.extend(self._maybe_finish(fi.req, host_t))
            for key, layout in inf.demotions:
                self.kv_mgr.complete_demotion(key, [
                    None if pair is None else (np.asarray(vals[pair[0]]),
                                               np.asarray(vals[pair[1]]))
                    for pair in layout])
        return evs

    def _maybe_finish(self, r: Request, host_t: float) -> List[Event]:
        if r.phase == Phase.FINISHED and \
                len(r.output_tokens) >= r.output_len:
            return [self._finish_event(r, t=r.finish_time, host_t=host_t)]
        return []
