"""Serving launcher: run the DuetServe engine on a trace (CLI).

On a TPU this process drives one replica (or ``--dp`` replicas, one per
chip); the duet decision is taken per iteration (core.multiplexer) and
realised either at kernel-grid granularity (single chip —
kernels.duet_attention) or by splitting the model axis into sub-meshes
(``mesh.split_duet_submeshes``). On the CPU the same path runs reduced
configs for tests. Parameters are initialised from ``--seed`` in bf16, and
the engine keeps its KV pools in the same dtype.

Timing: the summary's TTFT/TBT/throughput figures come from the engine's
virtual clock, which advances by the roofline's prediction for the device
(serving/engine.py) — they are predictions on every platform, the chip
included. ``wall_setup_s`` / ``wall_serve_s`` are the host clock: parameter
init plus engine construction, then serving (jit compiles included) up to
the last device output. With ``--stream`` the summary adds
``measured_{mean,p50,p99}_ttft_s`` and ``measured_{mean,p50,p99}_req_tbt_s``
on the host clock, from the stage stamps the async engine puts on each
request (compiles included, as in ``wall_serve_s``): TTFT, and statistics
over requests of each request's mean token gap, which is not a TBT tail.

Two execution modes:

* default — synchronous :class:`DuetEngine` (the token-equivalence oracle)
* ``--stream`` — asynchronous :class:`AsyncDuetEngine` with open-loop
  arrival replay: requests are fed through the streaming ``submit`` inbox
  as the virtual clock reaches their trace arrival, and per-token events
  are printed as JSON lines while generation is still in flight.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --reduced \
      --trace azure-conv --qps 4 --num-requests 32
  PYTHONPATH=src python -m repro.launch.serve --reduced --stream \
      --num-requests 8 --no-paged
  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python -m repro.launch.serve --reduced --tp 2 --num-requests 8
  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python -m repro.launch.serve --reduced --dp 2 \
      --router-policy prefix --shared-prefix-len 32 --num-requests 8

``--dp N`` serves N real engine replicas behind the cluster router
(serving/router.py): requests are dispatched per --router-policy, each
replica keeps its own KV pool/prefix cache/duet multiplexer, and the
summary reports per-replica plus cluster-aggregate metrics.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, list_configs, reduced
from repro.core.device import DeviceContext
from repro.launch.compile_cache import enable_compile_cache
from repro.models.transformer import Model
from repro.serving.async_engine import (AsyncDuetEngine, FinishEvent,
                                        TokenEvent)
from repro.serving.engine import DuetEngine, EngineConfig
from repro.serving.kvcache import DEFAULT_PAGE_SIZE, KV_QUANT_MODES
from repro.serving.loadgen import (ARRIVAL_PROCESSES, SERVICE_MIXES,
                                   ArrivalSpec, LoadGenerator, LoadSpec,
                                   ServiceSpec, qps_for_rho, request_cost)
from repro.serving.request import synth_prompt_tokens
from repro.serving.router import (ROUTER_POLICIES, ElasticConfig, Router,
                                  RouterEvent, ScaleEvent)
from repro.serving.traces import TRACES, synth_trace


def _warn(msg: str):
    print(f"warning: {msg}", file=sys.stderr)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        description="Run the DuetServe engine on a synthesised trace.")
    ap.add_argument("--arch", choices=list_configs(), default="qwen3-4b")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--trace", choices=list(TRACES), default="azure-conv")
    ap.add_argument("--qps", type=float, default=4.0)
    ap.add_argument("--num-requests", type=int, default=16)
    ap.add_argument("--token-budget", type=int, default=256)
    ap.add_argument("--tbt-slo", type=float, default=0.1)
    ap.add_argument("--max-len", type=int, default=1024)
    ap.add_argument("--max-slots", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    # engine mode (previously hardcoded)
    ap.add_argument("--paged", dest="paged", action="store_true",
                    default=True,
                    help="paged-KV execution (default)")
    ap.add_argument("--no-paged", dest="paged", action="store_false",
                    help="slab-KV oracle mode")
    ap.add_argument("--page-size", type=int, default=DEFAULT_PAGE_SIZE)
    ap.add_argument("--kv-pool-tokens", type=int, default=None,
                    help="device page-pool size in tokens "
                         "(default: max_slots * max_len)")
    ap.add_argument("--attn-kernel", action="store_true",
                    help="route decode attention through the Pallas kernels; "
                         "the engine probes the geometry and reports the "
                         "resolved path as kernel_path (pallas, or "
                         "pallas_sharded under --tp > 1); a geometry the "
                         "kernels cannot serve is an error")
    ap.add_argument("--split-kv-threshold", type=int, default=None,
                    help="block-table capacity (tokens) above which the "
                         "kernel path decodes with the flash-decoding "
                         "split-KV kernel (default: priced from the "
                         "roofline; 0 disables splitting)")
    ap.add_argument("--temperature", type=float, default=0.0)
    # mesh-aware serving: shard params + KV page pools over a device mesh.
    # tp=1, dp=1 (default) is the degenerate 1-device mesh — same code
    # path, token-identical output.
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree (mesh 'model' axis): "
                         "params shard per the arch TP rules, paged KV "
                         "pools shard their head axis; needs tp*dp "
                         "visible devices (XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N on CPU)")
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel replica count: dp>1 serves N real "
                         "engine replicas behind the cluster router, each "
                         "on its own TP submesh with its own params "
                         "placement, paged KV pool and prefix cache; "
                         "needs tp*dp visible devices (XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N on CPU)")
    # elastic data-parallelism: the active replica set breathes between
    # --min-dp and --max-dp against measured outstanding tokens; a drained
    # replica requeues its work via the preempt->recompute path
    ap.add_argument("--elastic", action="store_true",
                    help="scale the active replica set against load: the "
                         "mesh holds --max-dp replicas but dispatch starts "
                         "at --min-dp; scale decisions print as JSONL "
                         "'scale' events under --stream")
    ap.add_argument("--min-dp", type=int, default=1,
                    help="initial/minimum active replicas (--elastic)")
    ap.add_argument("--max-dp", type=int, default=None,
                    help="maximum replicas = mesh data extent (--elastic; "
                         "default: --dp, or 2 when --dp is 1)")
    ap.add_argument("--scale-up-tokens", type=int, default=512,
                    help="scale up when mean outstanding tokens per active "
                         "replica exceed this (--elastic)")
    ap.add_argument("--scale-down-tokens", type=int, default=64,
                    help="scale down when the cluster total fits under "
                         "this per replica with one fewer (--elastic)")
    ap.add_argument("--scale-cooldown", type=float, default=0.5,
                    help="minimum virtual seconds between scale actions")
    ap.add_argument("--scale-interval", type=float, default=0.25,
                    help="drain-phase control-tick grid in virtual seconds")
    # stochastic load generation (serving/loadgen.py): any non-default
    # selection switches trace synthesis from synth_trace to the seeded
    # open-loop generator
    ap.add_argument("--arrival", choices=list(ARRIVAL_PROCESSES),
                    default="poisson",
                    help="arrival process: poisson, or mmpp (2-state "
                         "Markov-modulated bursts at the same mean rate)")
    ap.add_argument("--service-mix", choices=list(SERVICE_MIXES),
                    default="lognormal",
                    help="request-length mix: the trace's lognormal, or a "
                         "two-point mixture with a heavy tail at the same "
                         "mean")
    ap.add_argument("--rho", type=float, default=None,
                    help="target utilisation: sets qps to rho * k / E[S] "
                         "using the roofline per-request cost estimate "
                         "(overrides --qps)")
    ap.add_argument("--router-policy", choices=list(ROUTER_POLICIES),
                    default="round-robin",
                    help="dispatch policy for --dp > 1: round-robin "
                         "(ClusterSim parity oracle), least-loaded "
                         "(fewest outstanding tokens), or prefix "
                         "(longest cached prompt prefix, tie-break on "
                         "load)")
    # copy-on-write prefix caching (paged mode only; default: follow
    # --paged, so --no-paged alone never warns about a flag nobody passed)
    ap.add_argument("--prefix-cache", dest="prefix_cache",
                    action="store_true", default=None,
                    help="share prompt-prefix KV pages across requests "
                         "(default in paged mode)")
    ap.add_argument("--no-prefix-cache", dest="prefix_cache",
                    action="store_false",
                    help="disable prefix sharing (cold-cache baseline)")
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="prepend a common system prompt of this many "
                         "tokens to every trace request (exercises the "
                         "prefix cache)")
    ap.add_argument("--shared-prefix-every", type=int, default=1,
                    metavar="N",
                    help="apply the shared prefix to every Nth request "
                         "only (default 1 = all). With N>1 the unshared "
                         "requests pressure the pool between prefix "
                         "reuses, forcing demote->promote round trips — "
                         "the tier-smoke workload")
    # tiered KV cache (DESIGN.md §9): host-DRAM demotion tier
    ap.add_argument("--host-kv-tokens", type=int, default=0,
                    help="host-DRAM demotion tier capacity in tokens: "
                         "cold cached pages demote there instead of being "
                         "evicted and promote back on a prefix hit "
                         "(0 = eviction-only baseline; requires the "
                         "prefix cache)")
    ap.add_argument("--kv-quant", choices=list(KV_QUANT_MODES),
                    default="none",
                    help="storage format of host-tier pages: none = fp32 "
                         "(byte-exact round trips), int8 = symmetric "
                         "per-tensor quantization with stored scales")
    # length handling (previously a silent clamp)
    ap.add_argument("--clamp", dest="clamp", action="store_true",
                    default=True,
                    help="clamp trace lengths into the engine capacity "
                         "(default; a warning reports every truncation)")
    ap.add_argument("--no-clamp", dest="clamp", action="store_false",
                    help="submit trace lengths unmodified; oversized "
                         "requests get explicit REJECTED outcomes")
    # async streaming front-end
    ap.add_argument("--stream", action="store_true",
                    help="serve with AsyncDuetEngine and print per-token "
                         "events as JSON lines")
    return ap


def _apply_shared_prefix(reqs, prefix_len: int, vocab_size: int, seed: int,
                         every: int = 1):
    """Prepend one common system prompt to every `every`-th request (the
    per-request body comes from the same rid-seeded derivation the engine
    uses, so --shared-prefix-len 0 and the default path produce identical
    bodies).  Runs *before* length clamping: the prefix counts against
    the caps.  With every > 1 the unshared requests act as pool
    polluters between prefix reuses, which is what drives the cached
    prefix through a host-tier demote->promote round trip."""
    if prefix_len <= 0:
        return reqs
    if every < 1:
        raise SystemExit("--shared-prefix-every must be >= 1")
    common = np.random.default_rng(10_000 + seed).integers(
        0, vocab_size, prefix_len).astype(np.int32)
    for r in reqs:
        if r.rid % every:
            continue
        body = synth_prompt_tokens(r.rid, vocab_size, r.prompt_len)
        r.prompt_tokens = np.concatenate([common, body])
        r.prompt_len += prefix_len
    return reqs


def _clamp_lengths(reqs, max_len: int, clamp: bool):
    """Fit trace lengths to the engine, loudly. Returns the request list."""
    p_cap, o_cap = max_len // 2, max_len // 4
    over = [r for r in reqs
            if r.prompt_len > p_cap or r.output_len > o_cap]
    if not over:
        return reqs
    if clamp:
        _warn(f"{len(over)}/{len(reqs)} trace requests exceed --max-len "
              f"{max_len} (prompt cap {p_cap}, output cap {o_cap}); "
              "clamping lengths — pass --no-clamp to reject them instead")
        for r in over:
            r.prompt_len = min(r.prompt_len, p_cap)
            r.output_len = min(r.output_len, o_cap)
            if r.prompt_tokens is not None:
                r.prompt_tokens = r.prompt_tokens[:r.prompt_len]
    else:
        _warn(f"{len(over)}/{len(reqs)} trace requests exceed --max-len "
              f"{max_len}; submitting unmodified — the engine will record "
              "REJECTED outcomes for footprints beyond its KV capacity")
    return reqs


def serve(argv=None):
    """Parse ``argv``, serve the synthesised trace, stream events to stdout.

    Returns:
        ``(summary, requests, server)``: the summary dict :func:`main`
        prints; the served :class:`~repro.serving.request.Request` objects
        (their ``output_tokens`` hold the generated tokens); and the engine
        that served them (the :class:`Router` under ``--dp``/``--elastic``),
        which still holds its parameters and KV pools on the devices.
    """
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    t_start = time.perf_counter()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    model = Model(cfg, attn_kernel=args.attn_kernel)

    # elastic replica set: the mesh holds max-dp replicas; dispatch starts
    # at min-dp and breathes against measured outstanding tokens
    elastic_cfg = None
    dp = args.dp
    if args.elastic:
        dp = args.max_dp or max(args.dp, 2)
        if args.dp > 1 and dp != args.dp:
            raise SystemExit("--max-dp contradicts --dp; pass one of them")
        elastic_cfg = ElasticConfig(
            min_replicas=args.min_dp, max_replicas=dp,
            scale_up_tokens=args.scale_up_tokens,
            scale_down_tokens=args.scale_down_tokens,
            cooldown_s=args.scale_cooldown,
            check_interval=args.scale_interval)
    elif args.max_dp is not None or args.min_dp != 1:
        _warn("--min-dp/--max-dp only apply with --elastic; ignored")

    # mesh-aware serving: a real (dp, tp) mesh when requested, otherwise
    # the engine's default degenerate 1-device mesh
    ctx = None
    if args.tp > 1 or dp > 1:
        ctx = DeviceContext.for_shape(cfg, tp=args.tp, dp=dp)
    # parameters materialise on the first replica's first device; each
    # engine then places its own copy (other replicas copy device to
    # device; TP shards), so no chip ends up holding two copies
    home = (jax.devices()[0] if ctx is None
            else ctx.split_replicas()[0].mesh.devices.flat[0])
    with jax.default_device(home):
        params = model.init(jax.random.PRNGKey(args.seed), jnp.bfloat16)

    # trace synthesis: the seeded open-loop generator when any stochastic
    # load knob is non-default, the legacy synth_trace otherwise
    qps = args.qps
    use_loadgen = (args.arrival != "poisson"
                   or args.service_mix != "lognormal"
                   or args.rho is not None)
    if use_loadgen:
        trace = TRACES[args.trace]
        service = ServiceSpec(trace=trace, mix=args.service_mix)
        if args.rho is not None:
            cost = request_cost(cfg, service, units=max(1, args.tp),
                                tp=args.tp,
                                token_budget=args.token_budget)
            qps = qps_for_rho(args.rho, cost, replicas=dp)
            _warn(f"rho={args.rho}: roofline E[S]={cost:.4f}s -> "
                  f"qps={qps:.3f} over {dp} replica(s)")
        spec = LoadSpec(arrival=ArrivalSpec(process=args.arrival, qps=qps),
                        service=service, seed=args.seed)
        reqs = LoadGenerator(spec).generate(args.num_requests)
    else:
        reqs = synth_trace(args.trace, args.num_requests, qps,
                           seed=args.seed)
    reqs = _apply_shared_prefix(reqs, args.shared_prefix_len,
                                cfg.vocab_size, args.seed,
                                every=args.shared_prefix_every)
    reqs = _clamp_lengths(reqs, args.max_len, args.clamp)

    if args.prefix_cache and not args.paged:
        # only reachable when --prefix-cache was passed explicitly
        _warn("--prefix-cache requires paged KV; running without it")
    prefix_cache = args.paged if args.prefix_cache is None \
        else args.prefix_cache
    if args.host_kv_tokens > 0 and not (args.paged and prefix_cache):
        _warn("--host-kv-tokens requires paged KV with the prefix cache; "
              "running without the host tier")

    ec = EngineConfig(
        max_slots=args.max_slots, max_len=args.max_len,
        token_budget=args.token_budget, tbt_slo=args.tbt_slo,
        paged=args.paged, page_size=args.page_size,
        kv_pool_tokens=args.kv_pool_tokens,
        prefix_cache=prefix_cache,
        host_kv_tokens=args.host_kv_tokens,
        kv_quant=args.kv_quant,
        temperature=args.temperature,
        split_kv_threshold=args.split_kv_threshold,
        tp=args.tp, units=max(1, args.tp))
    if args.split_kv_threshold is not None and not args.attn_kernel:
        _warn("--split-kv-threshold only applies with --attn-kernel; "
              "ignored on the jnp attention path")

    def print_event(ev):
        if isinstance(ev, TokenEvent):
            print(json.dumps({"event": "token", "rid": ev.rid,
                              "index": ev.index, "token": ev.token,
                              "t": round(ev.t, 6)}))
        elif isinstance(ev, FinishEvent):
            print(json.dumps({"event": "finish", "rid": ev.rid,
                              "reason": ev.reason,
                              "n_tokens": ev.n_tokens,
                              "t": round(ev.t, 6)}))
        elif isinstance(ev, RouterEvent):
            print(json.dumps({"event": "router", "rid": ev.rid,
                              "replica": ev.replica, "policy": ev.policy,
                              "matched_tokens": ev.matched_tokens,
                              "outstanding": list(ev.outstanding),
                              "t": round(ev.t, 6)}))
        elif isinstance(ev, ScaleEvent):
            print(json.dumps({"event": "scale", "action": ev.action,
                              "replica": ev.replica,
                              "active": list(ev.active),
                              "outstanding": list(ev.outstanding),
                              "requeued": ev.requeued,
                              "t": round(ev.t, 6)}))

    if dp > 1:
        # cluster path: N real replicas behind the dispatch policy; the
        # router drives sync or async replicas on the shared virtual clock
        router = Router(model, params, ec, ctx=ctx,
                        policy=args.router_policy,
                        engine_cls=AsyncDuetEngine if args.stream
                        else DuetEngine,
                        seed=args.seed, elastic=elastic_cfg)
        router.submit(reqs)
        t_setup = _wall(t_start, [e.params for e in router.engines])
        router.run(on_event=print_event if args.stream else None)
        t_serve = _wall(t_start + t_setup, [e.pools for e in router.engines])
        if args.stream:
            print(json.dumps({
                "event": "mesh", **router.ctx.describe(),
                "kernel_path": router.engines[0].kernel_path,
                "collectives_per_iteration":
                    router.ctx.collectives_per_iteration()}))
            if args.paged:
                pc = router.prefix_stats()
                pc.pop("per_replica", None)
                print(json.dumps({"event": "prefix_cache", **pc}))
        out = router.summary()
        if args.stream:
            out["dispatch_stats"] = [dataclasses.asdict(e.dstats)
                                     for e in router.engines]
        out["mesh"] = router.ctx.describe()
        out["kernel_path"] = router.engines[0].kernel_path
        out["collectives_per_iteration"] = \
            router.ctx.collectives_per_iteration()
        if args.paged:
            # per-replica stats already live under out["per_replica"];
            # keep the top-level block cluster-aggregate only
            pc = router.prefix_stats()
            pc.pop("per_replica", None)
            out["prefix_cache"] = pc
        out["wall_setup_s"], out["wall_serve_s"] = t_setup, t_serve
        return out, reqs, router

    if args.stream:
        engine = AsyncDuetEngine(model, params, ec, seed=args.seed,
                                 ctx=ctx)
        t_setup = _wall(t_start, engine.params)
        engine.submit(reqs)   # open-loop: arrivals replay on the inbox
        for ev in engine.events():
            print_event(ev)
        # stream consumers can diagnose a sharded run from the log alone:
        # the executed mesh geometry + predicted collective count ride the
        # JSONL stream next to the prefix_cache outcome
        print(json.dumps({
            "event": "mesh", **engine.ctx.describe(),
            "kernel_path": engine.kernel_path,
            "collectives_per_iteration":
                engine.ctx.collectives_per_iteration()}))
        if args.paged:
            # stream consumers get the cache outcome as a JSONL event too
            print(json.dumps({"event": "prefix_cache",
                              **engine.kv_mgr.prefix_stats()}))
        metrics = engine.run()   # drained: collects metrics only
        out = metrics.summary()
        out["dispatch_stats"] = dataclasses.asdict(engine.dstats)
    else:
        engine = DuetEngine(model, params, ec, seed=args.seed, ctx=ctx)
        t_setup = _wall(t_start, engine.params)
        engine.submit(reqs)
        metrics = engine.run()
        out = metrics.summary()
    out["slo_attainment"] = metrics.slo_attainment(args.tbt_slo)
    out["duet_fraction"] = engine.mux.stats.duet_fraction
    out["iterations"] = engine.mux.stats.iterations
    out["mesh"] = engine.ctx.describe()
    out["kernel_path"] = engine.kernel_path
    out["collectives_per_iteration"] = \
        engine.ctx.collectives_per_iteration()
    if args.paged:
        out["prefix_cache"] = engine.kv_mgr.prefix_stats()
    out["wall_setup_s"] = t_setup
    out["wall_serve_s"] = _wall(t_start + t_setup,
                                (engine.pools, engine.cache))
    return out, reqs, engine


def _wall(since: float, outputs) -> float:
    """Host-clock seconds from ``since`` until ``outputs`` are computed."""
    jax.block_until_ready(outputs)
    return time.perf_counter() - since


def main(argv=None) -> dict:
    """CLI entry point: serve, print the JSON summary, and return it."""
    out, _, _ = serve(argv)
    print(json.dumps(out, indent=2))
    return out


if __name__ == "__main__":
    main()
